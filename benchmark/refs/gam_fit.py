"""A binomial GAM of cubic regression splines, one smooth a column, held to
four things and two controls, in float64 numpy over ALL rows of the host
columns (blocks of ``BLOCK`` rows), with no code of the system.

A smooth of column x with K knots is the natural cubic spline f through its
values v [K] at the knots, x clipped to the knots' range: on [k_j, k_j+1]
with h its width, f(x) = a_m v_j + a_p v_j+1 + c_m m_j + c_p m_j+1, with
a_m = (k_j+1 - x)/h, a_p = (x - k_j)/h, c_m = (a_m^3 - a_m) h^2 / 6,
c_p = (a_p^3 - a_p) h^2 / 6 and m = F v its second derivatives at the knots
(0 at both ends, the rest from the continuity of f').  So the cardinal
basis row of x is a_m e_j + a_p e_j+1 + c_m F_j + c_p F_j+1, and its
products with a row vector r over the rows are ``bincount``s over j.  The
curvature penalty v' S v is the integral of f''^2.  The fit the
configuration asks for minimises the mean binomial deviance over 2 plus
sum_s lambda_s v_s' S_s v_s / 2 under the centring c_s' v_s = 0 (c_s the
mean cardinal row: each f_s sums to 0 over the rows), with lambda_s = the
smooth's ``scale`` over the largest eigenvalue of S_s on the centred
subspace.  The model's smooth s is f_s with v_s = G_s beta_s, G_s = T_s /
col_scale_s of its ``gam_meta`` (a cardinal spline's coefficients are its
values at the knots), beta_s its GLM's coefficients.

(i)   The knots: the model's against the quantiles of the column at
      ``linspace(0, 1, K)`` in float64 (``knots_max_abs``).
(ii)  Each smooth's fitted function at its knots against the reference's
      centred space: |c_s' v_s| (``centre_max_abs``), with c_s from the
      reference's own knots over all rows.
(iii) The penalized score equations at the model's functions: the
      intercept's sum(y - mu)/n, and for each smooth the part of
      B_s'(y - mu)/n - lambda_s S_s v_s off its centring direction
      (``score_max_abs``), B_s the reference's cardinal basis.
(iv)  ``predict`` on the timed frame, every row, against the float64
      sigmoid of the same functions (``p1_max_abs``).
(v)   Two controls in every run, each through the same verdict, each of
      which has to FAIL: ``bf16``, the coefficients each moved by the
      relative error of one bfloat16 rounding (``BF16_REL``, sign from the
      seed), through (ii), (iii) and (iv); ``tail``, the last hundredth of
      the rows scored at the mean response, through (iv).
"""

import numpy as np

from benchmark import refs

BF16_REL = 4e-3             # 2^-8: the rounding of a bfloat16 mantissa
BLOCK = 1 << 20
LIMITS = ("knots_max_abs", "centre_max_abs", "score_max_abs", "p1_max_abs")


def second_derivatives(knots):
    """F [K, K]: a natural spline's second derivatives at the knots from its
    values there."""
    K, h = len(knots), np.diff(knots)
    A, R = np.zeros((K, K)), np.zeros((K, K))
    A[0, 0] = A[-1, -1] = 1.0
    for i in range(1, K - 1):
        A[i, i - 1:i + 2] = h[i - 1] / 6.0, (h[i - 1] + h[i]) / 3.0, h[i] / 6.0
        R[i, i - 1:i + 2] = 1.0 / h[i - 1], -1.0 / h[i - 1] - 1.0 / h[i], 1.0 / h[i]
    return np.linalg.solve(A, R)


def penalty(knots, F):
    """S [K, K]: the integral of f''^2, f'' linear between the knots."""
    Q = np.zeros((len(knots), len(knots)))
    for j, h in enumerate(np.diff(knots)):
        Q[j:j + 2, j:j + 2] += np.array([[h / 3.0, h / 6.0], [h / 6.0, h / 3.0]])
    return F.T @ Q @ F


class Smooth:
    """One column's spline pieces; ``weights(x)`` gives a block's (j, a_m,
    a_p, c_m, c_p)."""

    def __init__(self, x, k, scale):
        self.knots = np.unique(np.quantile(x, np.linspace(0, 1, k)))
        self.K = len(self.knots)
        self.F = second_derivatives(self.knots)
        self.S = penalty(self.knots, self.F)
        self.scale = scale

    def weights(self, x):
        x = np.clip(x.astype(np.float64), self.knots[0], self.knots[-1])
        j = np.minimum(np.searchsorted(self.knots, x, side="right") - 1, self.K - 2)
        lo, hi = self.knots[j], self.knots[j + 1]
        h = hi - lo
        am, ap = (hi - x) / h, (x - lo) / h
        return j, am, ap, (am ** 3 - am) * h * h / 6.0, (ap ** 3 - ap) * h * h / 6.0

    def value(self, pieces, v):
        j, am, ap, cm, cp = pieces
        m = self.F @ v
        return am * v[j] + ap * v[j + 1] + cm * m[j] + cp * m[j + 1]

    def rmatvec(self, pieces, r):
        """B' r: the cardinal basis' columns against ``r``."""
        j, am, ap, cm, cp = pieces
        K = self.K
        out = np.bincount(j, r * am, K) + np.bincount(j + 1, r * ap, K)
        return out + self.F.T @ (np.bincount(j, r * cm, K) + np.bincount(j + 1, r * cp, K))

    def centre(self, c):
        """The centring row ``c``, its complement Z and lambda."""
        self.c = c
        _, _, vt = np.linalg.svd(c[None, :])
        self.Z = vt[1:].T
        self.lam = self.scale / np.linalg.eigvalsh(self.Z.T @ self.S @ self.Z).max()


def blocks(n):
    for lo in range(0, n, BLOCK):
        yield slice(lo, min(lo + BLOCK, n))


def functions(model, coef, names):
    """Each smooth's values at its knots (G beta) and the intercept."""
    v = {}
    for m in model.output["gam_meta"]:
        G = np.asarray(m["T"], np.float64) / np.asarray(m["col_scale"], np.float64)[None, :]
        v[m["cols"][0]] = G @ np.array([coef[f"{m['name']}_gam{j}"]
                                        for j in range(G.shape[1])])
    return [v[f] for f in names], float(coef["Intercept"])


def verdict(read, tol):
    """The limits of ``tol`` that ``read`` is over, or has no reading for."""
    return [k for k in LIMITS if not read.get(k, float("inf")) <= tol[k]]


def check(state, model, tol):
    params = state["cfg"]["params"]
    names = [c if isinstance(c, str) else c[0] for c in params["gam_columns"]]
    if sorted(names) != sorted(state["features"]) or state["categorical"]:
        return False, {"layout": "the reference reads one cr smooth of every numeric feature"}
    cols, n = state["cols"], state["rows"]
    k = params["num_knots"] if isinstance(params["num_knots"], list) \
        else [params["num_knots"]] * len(names)
    scale = params["scale"] if isinstance(params["scale"], list) \
        else [params["scale"]] * len(names)
    y = cols[state["response"]].astype(np.float64)
    smooths = [Smooth(cols[f], ki, si) for f, ki, si in zip(names, k, scale)]
    got_knots = {m["cols"][0]: np.asarray(m["knots"], np.float64)
                 for m in model.output["gam_meta"]}
    read = {"knots_max_abs": max(
        float(np.abs(got_knots[f] - s.knots).max()) if len(got_knots[f]) == s.K else np.inf
        for f, s in zip(names, smooths))}
    coef = model.coef
    sign = np.random.default_rng([state["seed"], 2]).choice((-1.0, 1.0), len(coef))
    moved = {key: val * (1.0 + BF16_REL * sg) for (key, val), sg in zip(coef.items(), sign)}
    fits = [functions(model, c, names) for c in (coef, moved)]
    # ONE pass over the rows: the centring rows, each fit's probabilities
    # and its B'(y - mu)/n
    sums = [np.zeros(s.K) for s in smooths]
    mus = [np.empty(n) for _ in fits]
    scores = [[np.zeros(s.K) for s in smooths] for _ in fits]
    for b in blocks(n):
        pieces = [s.weights(cols[f][b]) for s, f in zip(smooths, names)]
        ones = np.full(b.stop - b.start, 1.0 / n)
        for s, p, acc in zip(smooths, pieces, sums):
            acc += s.rmatvec(p, ones)
        for (v, b0), mu, score in zip(fits, mus, scores):
            eta = np.full(b.stop - b.start, b0)
            for s, p, vs in zip(smooths, pieces, v):
                eta += s.value(p, vs)
            mu[b] = refs.sigmoid(eta)
            r = (y[b] - mu[b]) / n
            for s, p, acc in zip(smooths, pieces, score):
                acc += s.rmatvec(p, r)
    for s, c in zip(smooths, sums):
        s.centre(c)

    label = state["domains"][state["response"]][1]
    frame_p1 = np.asarray(model.predict(state["frame"]).vec(label).to_numpy(), np.float64)

    def compare(fit, mu, score):
        v, _ = fit
        worst = [abs(float(np.sum(y - mu))) / n]
        worst += [float(np.abs(s.Z.T @ (u - s.lam * s.S @ vs)).max())
                  for s, u, vs in zip(smooths, score, v)]
        return {"centre_max_abs": max(abs(float(s.c @ vs)) for s, vs in zip(smooths, v)),
                "score_max_abs": max(worst),
                "p1_max_abs": float(np.abs(frame_p1 - mu).max())}

    read.update(compare(fits[0], mus[0], scores[0]))
    failed = verdict(read, tol)
    detail = dict(read, failed=failed, smooths=len(smooths),
                  knots=[s.K for s in smooths][:1], intercept=fits[0][1])
    control = compare(fits[1], mus[1], scores[1])
    detail["bf16"] = control
    detail["bf16_fails"] = verdict(dict(read, **control), tol)
    tail = max(n // 100, 1)
    lost = mus[0].copy()
    lost[-tail:] = y.mean()
    wrong = {"p1_max_abs": float(np.abs(frame_p1 - lost).max())}
    detail["tail"] = wrong
    detail["tail_fails"] = verdict(dict(read, **wrong), tol)
    return not failed, detail
