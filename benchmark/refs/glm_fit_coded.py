"""A binomial GLM fit with no penalty on a frame with categorical columns,
held to four things and two controls, all in float64 numpy from the host columns, with no
code of the system.  The design is the one-hot one: a numeric standardised
by its own mean and deviation, a categorical as one column per level but the
first (the model's further column for missing values is lit by no row here),
the intercept last.

(i)   The score equations at the coefficients the timed fit returned, over
      ALL rows: ``X'(y - mu) / n`` with ``mu = sigmoid(X beta)``, per numeric
      ``resid @ z / n``, per categorical ``bincount(codes, resid) / n`` over
      the design's levels, and the intercept's ``resid.sum() / n``.  At the
      maximum-likelihood coefficients all are 0; the largest absolute entry
      has to stay under ``score_max_abs``.
(ii)  The model's final Gram ``X'WX`` (``W = mu (1 - mu)``) against weighted
      cross-tabulations by ``bincount``, entry by entry over all 628 x 628,
      as ``gram_max_abs`` of ``Gram / n``.
(iii) ``predict`` on the frame the fits were timed on, every row of it,
      against ``mu``: ``p1_max_abs``.  It is the scoring program of the timed
      fit's own metrics pass, over the same row blocks, the last one
      included.
(iv)  What that pass left on the timed fit itself: the log loss of its
      ``training_metrics`` against the mean of ``-log P(y)`` under ``mu``
      (``logloss_abs``).  A coarse tie: a mean over the rows says little of
      any one of them, which is (iii)'s to say.
(v)   Two controls in every run, each through the same verdict, and each
      has to FAIL, by one limit and not by each.  ``bf16``: the returned
      coefficients, each moved by the relative error of one bfloat16
      product (``BF16_REL``, sign from the seed), through (i), (iii) and
      (iv), and the reference's own Gram with its weights rounded to
      bfloat16 through (ii); ``bf16_fails`` lists which limits it broke.  At
      the maximum the log loss is flat in the coefficients, so (iv) cannot
      tell this control from the fit and has its own.  ``tail``: the last
      hundredth of the rows left at 0, as in an output that a scoring walk
      never wrote to its end, through (iii) and (iv); ``tail_fails`` lists
      which it broke.
"""

import numpy as np

from benchmark import refs

BF16_REL = 4e-3             # 2^-8: the rounding of a bfloat16 mantissa
LIMITS = ("score_max_abs", "gram_max_abs", "p1_max_abs", "logloss_abs")


class Design:
    """The host columns as the one-hot design's parts, and its layout."""

    def __init__(self, state):
        cols = state["cols"]
        self.features, self.categorical = state["features"], state["categorical"]
        self.domains = state["domains"]
        self.y = cols[state["response"]].astype(np.float64)
        self.n = len(self.y)
        self.stats = {f: (float(np.mean(cols[f], dtype=np.float64)),
                          float(np.std(cols[f], dtype=np.float64, ddof=1)))
                      for f in self.features if f not in self.categorical}
        self.z = {f: (cols[f].astype(np.float64) - m) / s
                  for f, (m, s) in self.stats.items()}
        self.codes = {f: cols[f].astype(np.int64)
                      for f in self.features if f in self.categorical}

    def names(self):
        out = []
        for f in self.features:
            if f in self.categorical:
                out += [f"{f}.{label}" for label in self.domains[f][1:]]
                out.append(f"{f}.missing(NA)")
            else:
                out.append(f)
        return out + ["Intercept"]

    def eta(self, coef):
        """X beta from the coefficients on the ORIGINAL scale (``model.coef``)."""
        eta = np.full(self.n, float(coef["Intercept"]))
        for f in self.features:
            if f in self.categorical:
                table = np.array([0.0] + [float(coef[f"{f}.{label}"])
                                          for label in self.domains[f][1:]])
                eta += table[self.codes[f]]
            else:
                m, s = self.stats[f]
                eta += float(coef[f]) * (self.z[f] * s + m)
        return eta

    def xtv(self, v):
        """X'v over the design's columns, in ``names()``' order."""
        out = []
        for f in self.features:
            if f in self.categorical:
                levels = len(self.domains[f])
                out += list(np.bincount(self.codes[f], v, minlength=levels)[1:levels])
                out.append(0.0)                     # no row lights the NA column
            else:
                out.append(float(v @ self.z[f]))
        return np.array(out + [float(v.sum())])

    def gram(self, w):
        """X' diag(w) X by cross-tabulation, in ``names()``' order."""
        blocks, at = [], 0          # (feature, its first column)
        for f in self.features:
            blocks.append((f, at))
            at += len(self.domains[f]) if f in self.categorical else 1
        G = np.zeros((at + 1, at + 1))
        for f, a in blocks:
            # this feature's columns against every column: X'(w o column)
            if f in self.categorical:
                levels = len(self.domains[f])
                for g, b in blocks:
                    if g == f:
                        counts = np.bincount(self.codes[f], w, minlength=levels)[1:levels]
                        G[a:a + levels - 1, a:a + levels - 1] = np.diag(counts)
                    elif g in self.categorical and b > a:
                        other = len(self.domains[g])
                        table = np.bincount(self.codes[f] * other + self.codes[g], w,
                                            minlength=levels * other)
                        table = table.reshape(levels, other)[1:levels, 1:other]
                        G[a:a + levels - 1, b:b + other - 1] = table
                        G[b:b + other - 1, a:a + levels - 1] = table.T
                G[a:a + levels - 1, -1] = G[-1, a:a + levels - 1] = \
                    np.bincount(self.codes[f], w, minlength=levels)[1:levels]
            else:
                wz = w * self.z[f]
                column = self.xtv(wz)
                G[a, :] = column
                G[:, a] = column
        G[-1, -1] = w.sum()
        return G


def bfloat16(x):
    """``x`` rounded to the nearest bfloat16 (8 bits of mantissa), as float64."""
    bits = np.asarray(x, np.float32).view(np.uint32)
    return ((bits + 0x8000) & 0xFFFF0000).view(np.float32).astype(np.float64)


def scores_read(y, mu, frame_p1, metrics):
    """Readings (iii) and (iv): the frame's P(1) and the timed fit's
    ``metrics`` against the scores ``mu`` of the same rows."""
    p = np.clip(mu, 1e-15, 1.0 - 1e-15)
    logloss = -np.mean(np.where(y == 1.0, np.log(p), np.log1p(-p)))
    return {"p1_max_abs": float(np.abs(frame_p1 - mu).max()),
            "logloss_abs": abs(float(metrics.logloss) - float(logloss))}


def verdict(read, tol):
    """The limits of ``tol`` that ``read`` is over, or has no reading for."""
    return [k for k in LIMITS if not read.get(k, float("inf")) <= tol[k]]


def check(state, model, tol):
    coef = model.coef
    design = Design(state)
    if list(coef) != design.names():
        return False, {"layout": "the model's coefficients are not the reference's columns"}
    if not np.all(np.isfinite(list(coef.values()))):
        return False, {"coef": "not finite"}

    # (iii) predict on the timed frame, through the public API
    label = state["domains"][state["response"]][1]
    frame_p1 = np.asarray(model.predict(state["frame"]).vec(label).to_numpy(), np.float64)
    metrics = model.training_metrics

    def compare(coef):
        mu = refs.sigmoid(design.eta(coef))
        score = design.xtv(design.y - mu) / design.n
        return dict(scores_read(design.y, mu, frame_p1, metrics),
                    score_max_abs=float(np.abs(score).max())), mu

    read, mu = compare(coef)
    # (ii) the final Gram, X'WX at the returned coefficients
    want = design.gram(mu * (1.0 - mu)) / design.n
    got = np.asarray(model.output["gram"], np.float64) / design.n
    read["gram_max_abs"] = float(np.abs(got - want).max()) if got.shape == want.shape \
        else float("inf")
    failed = verdict(read, tol)
    detail = dict(read, failed=failed)

    # (v) the controls: coefficients a bfloat16 rounding away must fail ...
    sign = np.random.default_rng([state["seed"], 2]).choice((-1.0, 1.0), len(coef))
    control, _ = compare({k: v * (1.0 + BF16_REL * s) for (k, v), s in zip(coef.items(), sign)})
    control["gram_max_abs"] = float(np.abs(
        design.gram(bfloat16(mu * (1.0 - mu))) / design.n - want).max())
    detail["bf16"] = control
    detail["bf16_fails"] = verdict(control, tol)
    # ... and so must scores whose last rows were never written
    lost = mu.copy()
    lost[-max(design.n // 100, 1):] = 0.0
    tail = scores_read(design.y, lost, frame_p1, metrics)
    detail["tail"] = tail
    detail["tail_fails"] = verdict(dict(read, **tail), tol)
    return not failed, detail
