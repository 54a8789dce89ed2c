"""GLM: generalized linear models with elastic-net regularization.

Reference: ``hex/glm/GLM.java:1573`` (GLMDriver; IRLSM:2143, L-BFGS:2757,
COD:2840), ``hex/glm/GLMTask.java`` (gradient/Hessian MRTasks),
``hex/gram/Gram.java:1017`` (distributed X'X accumulation, reduce = matrix
add, Cholesky on the driver), families/links in ``hex/glm/GLMModel.java:978``.

TPU-native redesign: the per-iteration hot loop — Gram accumulation — is one
jit-compiled pass: ``X^T diag(w) X`` over the row-sharded design matrix runs
on the MXU and GSPMD inserts the ``psum`` that replaces GramTask's MRTask
reduce.  The small P x P solve (Cholesky for L2, coordinate descent on the
Gram for L1 — exactly the reference's IRLSM+COD strategy) happens on host.
Multinomial runs block-wise per-class Newton steps on softmax probabilities
(the COD-multinomial analog, GLM.java:1643).
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..frame.frame import Frame
from ..runtime import dkv
from ..runtime import observability as obs
from ..runtime.job import Job
from .base import Model, ModelBuilder, Parameters
from .datainfo import DataInfo
from ..metrics.core import make_metrics


# ------------------------------------------------------------------- families
class _Family:
    name = "gaussian"

    def linkinv(self, eta):
        return eta

    def variance(self, mu):
        return jnp.ones_like(mu)

    def dlinkinv(self, eta, mu):
        """d mu / d eta."""
        return jnp.ones_like(eta)

    def deviance(self, y, mu, w):
        return jnp.sum(w * (y - mu) ** 2)

    def init_eta(self, y, w):
        mean = jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-12)
        return jnp.full_like(y, mean)


class _Gaussian(_Family):
    pass


class _Binomial(_Family):
    name = "binomial"

    def linkinv(self, eta):
        return jax.nn.sigmoid(eta)

    def variance(self, mu):
        return mu * (1 - mu)

    def dlinkinv(self, eta, mu):
        return mu * (1 - mu)

    def deviance(self, y, mu, w):
        mu = jnp.clip(mu, 1e-15, 1 - 1e-15)
        return -2 * jnp.sum(w * (y * jnp.log(mu) + (1 - y) * jnp.log1p(-mu)))

    def init_eta(self, y, w):
        p = jnp.clip(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-12),
                     1e-6, 1 - 1e-6)
        return jnp.full_like(y, jnp.log(p / (1 - p)))


class _Quasibinomial(_Binomial):
    name = "quasibinomial"


class _Poisson(_Family):
    name = "poisson"

    def linkinv(self, eta):
        return jnp.exp(jnp.clip(eta, -30, 30))

    def variance(self, mu):
        return mu

    def dlinkinv(self, eta, mu):
        return mu

    def deviance(self, y, mu, w):
        mu = jnp.maximum(mu, 1e-15)
        t = jnp.where(y > 0, y * jnp.log(y / mu), 0.0)
        return 2 * jnp.sum(w * (t - (y - mu)))

    def init_eta(self, y, w):
        m = jnp.maximum(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-12), 1e-6)
        return jnp.full_like(y, jnp.log(m))


class _Gamma(_Family):
    name = "gamma"

    def linkinv(self, eta):
        return jnp.exp(jnp.clip(eta, -30, 30))

    def variance(self, mu):
        return mu * mu

    def dlinkinv(self, eta, mu):
        return mu

    def deviance(self, y, mu, w):
        mu = jnp.maximum(mu, 1e-15)
        ys = jnp.maximum(y, 1e-15)
        return 2 * jnp.sum(w * (-jnp.log(ys / mu) + (ys - mu) / mu))

    def init_eta(self, y, w):
        m = jnp.maximum(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-12), 1e-6)
        return jnp.full_like(y, jnp.log(m))


class _Tweedie(_Family):
    name = "tweedie"

    def __init__(self, p: float):
        self.p = float(p)

    def linkinv(self, eta):
        return jnp.exp(jnp.clip(eta, -30, 30))

    def variance(self, mu):
        return jnp.power(jnp.maximum(mu, 1e-15), self.p)

    def dlinkinv(self, eta, mu):
        return mu

    def deviance(self, y, mu, w):
        p = self.p
        mu = jnp.maximum(mu, 1e-15)
        if p == 1.0:
            return _Poisson().deviance(y, mu, w)
        if p == 2.0:
            return _Gamma().deviance(y, mu, w)
        ys = jnp.maximum(y, 0.0)
        a = jnp.where(ys > 0,
                      jnp.power(jnp.maximum(ys, 1e-15), 2 - p) / ((1 - p) * (2 - p)),
                      0.0)
        b = ys * jnp.power(mu, 1 - p) / (1 - p)
        c = jnp.power(mu, 2 - p) / (2 - p)
        return 2 * jnp.sum(w * (a - b + c))

    def init_eta(self, y, w):
        m = jnp.maximum(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-12), 1e-6)
        return jnp.full_like(y, jnp.log(m))


class _NegativeBinomial(_Family):
    name = "negativebinomial"

    def __init__(self, theta: float):
        self.theta = float(theta)          # inverse dispersion

    def linkinv(self, eta):
        return jnp.exp(jnp.clip(eta, -30, 30))

    def variance(self, mu):
        return mu + self.theta * mu * mu

    def dlinkinv(self, eta, mu):
        return mu

    def deviance(self, y, mu, w):
        mu = jnp.maximum(mu, 1e-15)
        th = self.theta
        ys = jnp.maximum(y, 0.0)
        t1 = jnp.where(ys > 0, ys * jnp.log(ys / mu), 0.0)
        t2 = (ys + 1.0 / th) * jnp.log((1 + th * mu) / (1 + th * ys))
        return 2 * jnp.sum(w * (t1 + t2))

    def init_eta(self, y, w):
        m = jnp.maximum(jnp.sum(w * y) / jnp.maximum(jnp.sum(w), 1e-12), 1e-6)
        return jnp.full_like(y, jnp.log(m))


def _make_family(name: str, params) -> _Family:
    if name == "tweedie":
        return _Tweedie(params.tweedie_variance_power)
    if name == "negativebinomial":
        return _NegativeBinomial(params.theta)
    return {"gaussian": _Gaussian, "binomial": _Binomial,
            "quasibinomial": _Quasibinomial, "poisson": _Poisson,
            "gamma": _Gamma}[name]()


# ------------------------------------------------------------------- kernels
def _ledger(name, jitted, orig=None):
    """Register a compiled GLM seam with the compile ledger (runtime/xprof)."""
    from ..runtime import xprof
    return xprof.register_program(name, jitted, orig=orig)


def _gram_kernel_impl(X, w):
    """Weighted Gram X'WX — the GramTask analog (gram/Gram.java:1017)."""
    Xw = X * w[:, None]
    return Xw.T @ X


_gram_kernel = _ledger("glm_gram", jax.jit(_gram_kernel_impl),
                       orig=_gram_kernel_impl)


def _make_irls_step(family: _Family):
    def step(X, y, w, beta, offset):
        eta = X @ beta + offset
        mu = family.linkinv(eta)
        g = jnp.maximum(family.dlinkinv(eta, mu), 1e-10)
        var = jnp.maximum(family.variance(mu), 1e-10)
        z = (eta - offset) + (y - mu) / g
        wi = w * g * g / var
        Xw = X * wi[:, None]
        gram = Xw.T @ X
        xtwz = Xw.T @ z
        dev = family.deviance(y, mu, w)
        return gram, xtwz, dev
    return _ledger("glm_irls", jax.jit(step), orig=step)


def _make_path_runner(family: _Family, l1_mode: bool, max_iter: int,
                      max_inner: int = 100):
    """The WHOLE regularization path as one device program.

    The host loop pays a device->host round trip per IRLS iteration,
    which makes a long lambda path fetch-bound.  Here lambdas run under
    ``lax.scan`` with warm-started betas, IRLS under ``lax.while_loop``
    (beta_epsilon early exit), and the penalized solve on device: one
    linear solve for pure L2, cyclic coordinate descent (the reference's
    COD, GLM.java:2840) under a while_loop for any L1.  One fetch at the
    end returns per-lambda betas/deviances/iteration counts + the final
    Gram (p-values).
    """

    def irls_gram(X, y, w, beta, offset):
        eta = X @ beta + offset
        mu = family.linkinv(eta)
        g = jnp.maximum(family.dlinkinv(eta, mu), 1e-10)
        var = jnp.maximum(family.variance(mu), 1e-10)
        z = (eta - offset) + (y - mu) / g
        wi = w * g * g / var
        Xw = X * wi[:, None]
        return Xw.T @ X, Xw.T @ z, family.deviance(y, mu, w)

    def run(X, y, w, offset, lambdas, alpha, penalize, beta0, n,
            beta_eps):
        P = beta0.shape[0]

        def solve(G, c, lam, warm):
            l2 = lam * (1 - alpha) * penalize
            if not l1_mode:
                A = G + jnp.diag(l2 + 1e-10)
                return jnp.linalg.solve(A, c)
            l1 = lam * alpha * penalize
            d = jnp.diag(G)

            def sweep(state):
                beta, _, it = state

                def upd(j, bd):
                    b, delta = bd
                    r = c[j] - (G[j] @ b - d[j] * b[j])
                    bj = jnp.where(
                        penalize[j] > 0,
                        jnp.sign(r) * jnp.maximum(jnp.abs(r) - l1[j], 0.0)
                        / (d[j] + l2[j] + 1e-12),
                        r / (d[j] + 1e-12))
                    delta = jnp.maximum(delta, jnp.abs(bj - b[j]))
                    return b.at[j].set(bj), delta

                beta2, delta = jax.lax.fori_loop(
                    0, P, upd, (beta, jnp.float32(0.0)))
                return beta2, delta, it + 1

            def cond(state):
                _, delta, it = state
                return (it < max_inner) & (delta > 1e-8)

            beta, _, _ = jax.lax.while_loop(
                cond, sweep, (warm, jnp.float32(jnp.inf), 0))
            return beta

        def per_lambda(beta, lam):
            def body(state):
                beta, _, it, _ = state
                gram, xtwz, dev = irls_gram(X, y, w, beta, offset)
                nb = solve(gram / n, xtwz / n, lam, beta)
                delta = jnp.max(jnp.abs(nb - beta))
                return nb, delta, it + 1, dev

            def cond(state):
                _, delta, it, _ = state
                return (it < max_iter) & (delta >= beta_eps)

            beta, _, iters, dev = jax.lax.while_loop(
                cond, body, (beta, jnp.float32(jnp.inf), 0,
                             jnp.float32(0.0)))
            return beta, (beta, dev, iters)

        beta_fin, (betas, devs, iters) = jax.lax.scan(
            per_lambda, beta0, lambdas)
        gram_fin, _, dev_fin = irls_gram(X, y, w, beta_fin, offset)
        return betas, devs, iters, gram_fin, dev_fin

    return _ledger("glm_path", jax.jit(run), orig=run)


def _make_softmax_stats(nclasses: int):
    def stats(X, y, w, beta, offset):
        """Per-class diagonal-block Newton quantities for multinomial."""
        eta = X @ beta + offset[:, None]
        probs = jax.nn.softmax(eta, axis=1)
        yi = jnp.clip(y.astype(jnp.int32), 0, nclasses - 1)
        Y = jax.nn.one_hot(yi, nclasses)
        p_true = jnp.clip(probs[jnp.arange(probs.shape[0]), yi], 1e-15, 1.0)
        ll = -jnp.sum(w * jnp.log(p_true))
        grams, xtwz = [], []
        for k in range(nclasses):
            mu = probs[:, k]
            wk = jnp.maximum(w * mu * (1 - mu), 1e-10 * w)
            zk = eta[:, k] - offset + (Y[:, k] - mu) / jnp.maximum(
                mu * (1 - mu), 1e-10)
            Xw = X * wk[:, None]
            grams.append(Xw.T @ X)
            xtwz.append(Xw.T @ zk)
        return jnp.stack(grams), jnp.stack(xtwz).T, ll, probs
    return _ledger("glm_softmax", jax.jit(stats), orig=stats)


# -------------------------------------------------------------------- solver
def _solve_penalized(gram: np.ndarray, xtwz: np.ndarray, n: float,
                     lam: float, alpha: float, beta0: np.ndarray,
                     penalize: np.ndarray, max_inner: int = 100,
                     tol: float = 1e-8,
                     nonneg: Optional[np.ndarray] = None) -> np.ndarray:
    """Solve 0.5 b'Gb - c'b + lam*(alpha*|b|_1 + (1-alpha)/2 |b|_2^2).

    G = gram/n, c = xtwz/n.  Pure L2 -> one Cholesky solve; any L1 or
    sign constraint -> cyclic coordinate descent on the Gram (the
    reference's COD, GLM.java:2840).  ``penalize`` masks out the
    intercept; ``nonneg`` marks coefficients clamped to >= 0 (the GLM
    ``non_negative`` option — per-coordinate projection, which for CD is
    the exact constrained minimizer).
    """
    G = gram / n
    c = xtwz / n
    # ``penalize`` is a per-coefficient penalty FACTOR (glmnet-style):
    # 0 = unpenalized (intercept, spline null space), 1 = standard, other
    # values scale both the L1 and L2 shares (GAM penalty eigenvalues)
    l2 = lam * (1 - alpha) * penalize
    l1 = lam * alpha * penalize
    constrained = nonneg is not None and bool(np.any(nonneg))
    if np.all(l1 == 0.0) and not constrained:
        A = G + np.diag(l2 + 1e-10)
        try:
            return np.linalg.solve(A, c)
        except np.linalg.LinAlgError:
            return np.linalg.lstsq(A, c, rcond=None)[0]
    beta = beta0.copy()
    if constrained:
        beta[nonneg] = np.maximum(beta[nonneg], 0.0)
    d = np.diag(G).copy()
    Gb = G @ beta
    for _ in range(max_inner):
        delta = 0.0
        for j in range(len(beta)):
            r = c[j] - (Gb[j] - d[j] * beta[j])
            if penalize[j] > 0:
                bj = np.sign(r) * max(abs(r) - l1[j], 0.0) \
                    / (d[j] + l2[j] + 1e-12)
            else:
                bj = r / (d[j] + 1e-12)
            if constrained and nonneg[j]:
                bj = max(bj, 0.0)
            diff = bj - beta[j]
            if diff != 0.0:
                Gb += G[:, j] * diff
                delta = max(delta, abs(diff))
                beta[j] = bj
        if delta < tol:
            break
    return beta


# ---------------------------------------------------------------- parameters
@dataclasses.dataclass
class GLMParameters(Parameters):
    family: str = "auto"                  # auto|gaussian|binomial|quasibinomial|
    # poisson|gamma|tweedie|negativebinomial|multinomial
    alpha: float = 0.5
    lambda_: Union[float, Sequence[float], None] = None   # None -> 0 / search
    lambda_search: bool = False
    nlambdas: int = 30
    lambda_min_ratio: float = 1e-4
    solver: str = "irlsm"
    # sign constraint (GLMParameters._non_negative): True = every
    # non-intercept coefficient >= 0; a list of column names constrains
    # only those columns (monotone GAM splines ride this)
    non_negative: Union[bool, Sequence[str]] = False
    # per-column penalty factors {column: factor}; cat columns apply the
    # factor to every one-hot slot (glmnet penalty.factor / GAM penalties)
    penalty_factors: Optional[dict] = None
    tweedie_variance_power: float = 1.5
    theta: float = 1.0                    # negative binomial
    beta_epsilon: float = 1e-5
    compute_p_values: bool = False
    intercept: bool = True
    max_iterations: int = 50


class GLMModel(Model):
    algo = "glm"

    def _predict_raw(self, X: jax.Array) -> jax.Array:
        beta = jnp.asarray(self.output["beta_std"])
        family = self.output["family"]
        if family == "multinomial":
            probs = jax.nn.softmax(X @ beta, axis=1)
            return probs
        if family == "ordinal":
            thetas = jnp.asarray(self.output["ordinal_thresholds"])
            eta = X @ beta                    # intercept col has beta 0
            cdf = jax.nn.sigmoid(thetas[None, :] - eta[:, None])
            cdf = jnp.concatenate(
                [jnp.zeros((cdf.shape[0], 1)), cdf,
                 jnp.ones((cdf.shape[0], 1))], axis=1)
            return jnp.clip(jnp.diff(cdf, axis=1), 0.0, 1.0)
        eta = X @ beta
        fam = _make_family(family, self.params)
        mu = fam.linkinv(eta)
        if self.datainfo.is_classifier:
            return jnp.stack([1 - mu, mu], axis=1)
        return mu

    @property
    def coef(self) -> dict:
        return dict(zip(self.output["coef_names"], self.output["beta"]))

    @property
    def coef_norm(self) -> dict:
        return dict(zip(self.output["coef_names"], self.output["beta_std_flat"]))


class GLM(ModelBuilder):
    """GLM builder — h2o.glm / H2OGeneralizedLinearEstimator analog."""

    algo = "glm"
    model_class = GLMModel

    def __init__(self, params: Optional[GLMParameters] = None, **kw):
        super().__init__(params or GLMParameters(**kw))

    def _resolve_family(self, di: DataInfo) -> str:
        fam = self.params.family
        if fam in ("auto", None):
            if di.is_classifier:
                fam = "binomial" if di.nclasses == 2 else "multinomial"
            else:
                fam = "gaussian"
        if fam in ("binomial", "quasibinomial") and not di.is_classifier:
            raise ValueError(f"family={fam} needs a categorical response")
        if fam == "multinomial" and di.nclasses < 3:
            fam = "binomial"
        if fam == "ordinal" and (not di.is_classifier or di.nclasses < 3):
            raise ValueError("family=ordinal needs a categorical response "
                             "with 3+ ordered levels")
        return fam

    def _fit(self, job: Job, frame: Frame, di: DataInfo,
             valid: Optional[Frame]) -> GLMModel:
        p: GLMParameters = self.params
        fam_name = self._resolve_family(di)
        with obs.span("glm.matrix"):
            X = di.make_matrix(frame)
            y = di.response(frame)
            w = di.weights(frame)
            y = jnp.nan_to_num(y)
            offset = di.offsets(frame)
            offset = offset if offset is not None else jnp.zeros_like(y)
            n = float(jnp.sum(w))
        P = di.nfeatures
        penalize = np.ones(P)
        if di.add_intercept:
            penalize[-1] = 0.0
        if p.penalty_factors:
            for spec in di.specs:
                f = p.penalty_factors.get(spec.name)
                if f is not None:
                    penalize[spec.offset: spec.offset + spec.width] = f
        nonneg = np.zeros(P, dtype=bool)
        if p.non_negative is True:
            nonneg[:] = True
            if di.add_intercept:
                nonneg[-1] = False
        elif p.non_negative:
            want = set(p.non_negative)
            matched = set()
            for spec in di.specs:
                if spec.name in want:
                    nonneg[spec.offset: spec.offset + spec.width] = True
                    matched.add(spec.name)
            if want - matched:
                raise ValueError(
                    f"non_negative names not in the design: "
                    f"{sorted(want - matched)}")
        if nonneg.any() and (fam_name in ("multinomial", "ordinal")
                             or p.solver.lower() in ("l_bfgs", "lbfgs")):
            raise ValueError("non_negative requires the IRLSM/COD solver "
                             "on a non-multinomial family")
        self._nonneg = nonneg if nonneg.any() else None

        if fam_name == "ordinal":
            lam0 = 0.0 if p.lambda_ is None else float(np.max(p.lambda_))
            return self._fit_ordinal(job, frame, di, X, y, w, offset, n,
                                     lam0, valid)
        lambdas = self._lambda_path(p, X, y, w, di, fam_name)
        if fam_name == "multinomial":
            model = self._fit_multinomial(job, frame, di, X, y, w, offset, n,
                                          penalize, lambdas, valid)
        else:
            model = self._fit_single(job, frame, di, X, y, w, offset, n,
                                     penalize, lambdas, fam_name, valid)
        return model

    # -------------------------------------------------------- lambda path
    def _lambda_path(self, p: GLMParameters, X, y, w, di, fam_name) -> List[float]:
        if p.lambda_ is not None and not p.lambda_search:
            return list(np.atleast_1d(np.asarray(p.lambda_, dtype=np.float64)))
        if not p.lambda_search:
            return [0.0]
        # lambda_max: smallest lambda zeroing all coefs = max |X'(y-ybar)|/(n*alpha)
        fam = _make_family(fam_name, p)
        eta0 = fam.init_eta(y, w)
        mu0 = fam.linkinv(eta0)
        grad = np.asarray(jnp.abs((X * w[:, None]).T @ (y - mu0)))
        if di.add_intercept:
            grad = grad[:-1]
        n = max(float(jnp.sum(w)), 1.0)
        lmax = float(grad.max()) / max(p.alpha, 1e-3) / n
        lmin = lmax * p.lambda_min_ratio
        return list(np.geomspace(lmax, lmin, p.nlambdas))

    # ------------------------------------------------------------- l-bfgs
    def _fit_lbfgs(self, job, frame, di, X, y, w, offset, n, penalize,
                   lam, fam_name, valid) -> "GLMModel":
        """L-BFGS solver — GLM.java:2757's solver=L_BFGS analog.

        Minimizes deviance/(2n) + lam*(1-alpha)/2 |b|_2^2 with optax's
        L-BFGS inside one jit-compiled scan (the whole optimization is a
        single device program).  Like the reference without ADMM, L1 is
        not supported on this solver — use IRLSM/COD for alpha > 0.
        """
        import optax
        from ..runtime.observability import log
        p: GLMParameters = self.params
        if p.alpha > 0 and (np.asarray(lam) > 0).any():
            # reference behavior: L_BFGS defaults alpha to 0 (no L1 without
            # ADMM); drop the L1 component rather than failing
            log.warning("solver='lbfgs' ignores the L1 component "
                        "(alpha=%s); keeping the L2 share", p.alpha)
        fam = _make_family(fam_name, p)
        pen = jnp.asarray(penalize, jnp.float32)
        lamf = float(lam)

        def obj(beta):
            eta = X @ beta + offset
            mu = fam.linkinv(eta)
            dev = fam.deviance(y, mu, w)
            return dev / (2 * n) + 0.5 * lamf * jnp.sum(pen * beta ** 2)

        opt = optax.lbfgs()
        vg = optax.value_and_grad_from_state(obj)

        iters = int(min(p.max_iterations, 100))

        @jax.jit
        def run(beta0):
            state = opt.init(beta0)

            def step_fn(carry, _):
                params, st = carry
                value, grad = vg(params, state=st)
                updates, st = opt.update(grad, st, params, value=value,
                                         grad=grad, value_fn=obj)
                params = optax.apply_updates(params, updates)
                return (params, st), value
            (beta, _), values = jax.lax.scan(step_fn, (beta0, state),
                                             None, length=iters)
            return beta, values

        P = di.nfeatures
        beta0 = jnp.zeros(P, jnp.float32)
        if di.add_intercept:
            beta0 = beta0.at[-1].set(fam.init_eta(y, w)[0])
        beta_j, values = run(beta0)
        beta = np.asarray(beta_j, np.float64)
        hist = [{"lambda": lamf, "iteration": i,
                 "deviance": float(v) * 2 * n, "delta": float("nan")}
                for i, v in enumerate(np.asarray(values))]
        # gram at the solution (p-values / std errors in _finalize)
        step = _make_irls_step(fam)
        gram, _, dev = step(X, y, w, jnp.asarray(beta, jnp.float32), offset)
        model = GLMModel(job.dest_key or dkv.make_key(self.algo), p, di)
        self._finalize(model, di, beta, fam_name, X, y, w, offset, n,
                       float(dev), hist, lamf, frame, valid,
                       gram_last=np.asarray(gram, np.float64))
        return model

    # ----------------------------------------------------------- ordinal
    def _fit_ordinal(self, job, frame, di, X, y, w, offset, n, lam,
                     valid) -> "GLMModel":
        """Proportional-odds (cumulative logit) — GLM.java family=ordinal.

        P(y <= j) = sigmoid(theta_j - X beta) with ordered thresholds,
        fit jointly by L-BFGS on the NLL inside one jit scan; thresholds
        are parameterized as theta_0 + cumulative softplus gaps so the
        ordering constraint holds by construction.
        """
        import optax
        p: GLMParameters = self.params
        K = di.nclasses
        P = di.nfeatures
        # drop the intercept column (absorbed into the thresholds)
        has_icpt = di.add_intercept
        Xf = X[:, :-1] if has_icpt else X
        Pf = Xf.shape[1]
        yi = jnp.clip(y.astype(jnp.int32), 0, K - 1)
        lamf = float(lam)

        def unpack(params):
            beta = params[:Pf]
            t0 = params[Pf]
            gaps = jax.nn.softplus(params[Pf + 1:])
            thetas = t0 + jnp.concatenate(
                [jnp.zeros(1), jnp.cumsum(gaps)])
            return beta, thetas

        def nll_fn(params):
            beta, thetas = unpack(params)
            eta = Xf @ beta + offset
            # cdf_j = P(y <= j), j = 0..K-2; boundaries 0 and 1 appended
            cdf = jax.nn.sigmoid(thetas[None, :] - eta[:, None])
            cdf = jnp.concatenate(
                [jnp.zeros((cdf.shape[0], 1)), cdf,
                 jnp.ones((cdf.shape[0], 1))], axis=1)
            probs = jnp.clip(jnp.diff(cdf, axis=1), 1e-12, 1.0)
            pick = jnp.take_along_axis(probs, yi[:, None], 1)[:, 0]
            return -jnp.sum(w * jnp.log(pick)) / n

        def obj(params):
            beta, _ = unpack(params)
            return nll_fn(params) + 0.5 * lamf * jnp.sum(beta ** 2)

        opt = optax.lbfgs()
        vg = optax.value_and_grad_from_state(obj)
        iters = int(min(p.max_iterations * 4, 200))

        @jax.jit
        def run(p0):
            state = opt.init(p0)

            def step(carry, _):
                prm, st = carry
                value, grad = vg(prm, state=st)
                upd, st = opt.update(grad, st, prm, value=value, grad=grad,
                                     value_fn=obj)
                return (optax.apply_updates(prm, upd), st), value
            (prm, _), values = jax.lax.scan(step, (p0, state), None,
                                            length=iters)
            return prm, values

        p0 = jnp.concatenate([jnp.zeros(Pf),
                              jnp.asarray([-1.0]),
                              jnp.full(K - 2, 0.5)]).astype(jnp.float32)
        prm, values = run(p0)
        beta, thetas = unpack(prm)
        final_nll = float(nll_fn(prm))     # penalty-free, at the FINAL point

        model = GLMModel(job.dest_key or dkv.make_key(self.algo), p, di)
        beta_full = np.zeros(P)
        beta_full[:Pf] = np.asarray(beta, np.float64)
        # destandardize for reporting (what _finalize does elsewhere)
        beta_orig = beta_full.copy()
        if di.standardize:
            ci = 0
            for spec in di.specs:
                if spec.type != "cat" and spec.width == 1 \
                        and ci < Pf and spec.sigma:
                    beta_orig[ci] = beta_full[ci] / spec.sigma
                ci += spec.width
        model.output.update({
            "family": "ordinal",
            "beta_std": beta_full,
            "ordinal_thresholds": np.asarray(thetas, np.float64),
            "coef_names": di.coef_names,
            "beta_std_flat": beta_full.tolist(),
            "beta": beta_orig.tolist(),
            "iterations": iters,
            "residual_deviance": final_nll * 2 * n,
        })
        model.scoring_history = [
            {"iteration": i, "deviance": float(v) * 2 * n}
            for i, v in enumerate(np.asarray(values[-5:]))]
        from ..metrics.core import make_metrics
        raw = model._predict_raw(X)
        model.training_metrics = make_metrics(di, raw, y, w)
        if valid is not None:
            model.validation_metrics = model.model_performance(valid)
        return model

    # ------------------------------------------------------- single-class
    def _fit_single(self, job, frame, di, X, y, w, offset, n, penalize,
                    lambdas, fam_name, valid) -> GLMModel:
        p: GLMParameters = self.params
        if p.solver.lower() in ("l_bfgs", "lbfgs"):
            return self._fit_lbfgs(job, frame, di, X, y, w, offset, n,
                                   penalize, lambdas[-1], fam_name, valid)
        fam = _make_family(fam_name, p)
        step = _make_irls_step(fam)
        P = di.nfeatures
        beta = np.zeros(P, dtype=np.float64)
        if di.add_intercept:
            with obs.span("glm.path", part="init"):
                eta0 = fam.init_eta(y, w)
                beta[-1] = float(eta0[0])      # a fetch: waits for the device
        if getattr(self, "_nonneg", None) is None:
            # every fit (single lambda included) runs as one fused device
            # program — the host loop below pays a device->host round trip
            # per IRLS iteration.  The host loop remains only for
            # non_negative (per-coordinate projection).
            # l1_mode only when L1 is actually active: the CD sweep costs
            # a while_loop per IRLS step that a plain solve doesn't.
            from ..runtime import failure
            failure.maybe_inject("glm_lambda")
            with obs.span("glm.path", lambdas=len(lambdas)):
                runner = _make_path_runner(
                    fam, l1_mode=p.alpha > 0 and float(np.max(lambdas)) > 0,
                    max_iter=p.max_iterations)
                out = runner(
                    X, y, w, offset, jnp.asarray(lambdas, jnp.float32),
                    jnp.float32(p.alpha), jnp.asarray(penalize, jnp.float32),
                    jnp.asarray(beta, jnp.float32), jnp.float32(n),
                    jnp.float32(p.beta_epsilon))
            with obs.span("glm.wait"):
                # the wait for the device and the fetch of its few KB in
                # one call, as device_get queues the copies behind the
                # program
                fetched = jax.device_get(out)
                obs.inc("transfer_bytes_total",
                        sum(a.nbytes for a in fetched), dir="d2h")
            betas, devs, iters, gram_fin, dev_fin = fetched
            hist = [{"lambda": float(lam), "iteration": int(iters[li]),
                     "deviance": float(devs[li]), "delta": float("nan")}
                    for li, lam in enumerate(lambdas)]
            for li, lam in enumerate(lambdas):
                job.update((li + 1) / len(lambdas),
                           f"lambda={lam:.3g} dev={float(devs[li]):.4g}")
            model = GLMModel(job.dest_key or dkv.make_key(self.algo), p, di)
            self._finalize(model, di, np.asarray(betas[-1], np.float64),
                           fam_name, X, y, w, offset, n, float(devs[-1]),
                           hist, lambdas[-1], frame, valid,
                           gram_last=np.asarray(gram_fin, np.float64))
            return model
        best = None
        hist = []
        dev = np.inf
        from ..runtime import failure, scheduler, snapshot
        for li, lam in enumerate(lambdas):
            # the host lambda loop journals its position: the in-progress
            # state (warm-start beta) is not a loadable model, so this is
            # a cursor-only progress record (bounded-rework accounting +
            # the /3/Recovery status view), throttled like full snapshots
            failure.maybe_inject("glm_lambda")
            # per-lambda device-lease yield: co-resident jobs interleave
            # here (the tree drivers yield at chunk boundaries)
            scheduler.DEVICE_LEASE.yield_turn()
            snapshot.progress(job, {"lambda_index": li,
                                    "lambda": float(lam)})
            for it in range(p.max_iterations):
                # one batched fetch per iteration (each separate fetch
                # would wait for the device again)
                gram, xtwz, dev_new = jax.device_get(step(
                    X, y, w, jnp.asarray(beta, dtype=jnp.float32), offset))
                gram = np.asarray(gram, np.float64)
                xtwz = np.asarray(xtwz, np.float64)
                new_beta = _solve_penalized(gram, xtwz, n, lam, p.alpha,
                                            beta, penalize,
                                            nonneg=getattr(self, "_nonneg",
                                                           None))
                delta = float(np.max(np.abs(new_beta - beta)))
                beta = new_beta
                dev_new = float(dev_new)
                hist.append({"lambda": lam, "iteration": it,
                             "deviance": dev_new, "delta": delta})
                job.update((li + it / p.max_iterations) / len(lambdas),
                           f"lambda={lam:.3g} iter={it} dev={dev_new:.4g}")
                if delta < p.beta_epsilon:
                    break
            dev = hist[-1]["deviance"]
            best = beta.copy()

        model = GLMModel(job.dest_key or dkv.make_key(self.algo), p, di)
        self._finalize(model, di, best, fam_name, X, y, w, offset, n,
                       dev, hist, lambdas[-1], frame, valid,
                       gram_last=gram)
        return model

    # -------------------------------------------------------- multinomial
    def _fit_multinomial(self, job, frame, di, X, y, w, offset, n, penalize,
                         lambdas, valid) -> GLMModel:
        p: GLMParameters = self.params
        K = di.nclasses
        P = di.nfeatures
        stats = _make_softmax_stats(K)
        beta = np.zeros((P, K), dtype=np.float64)
        hist = []
        lam = lambdas[-1]
        ll_prev = np.inf
        from ..runtime import failure, scheduler, snapshot
        for it in range(p.max_iterations):
            failure.maybe_inject("glm_lambda")
            scheduler.DEVICE_LEASE.yield_turn()
            snapshot.progress(job, {"iteration": it})
            # batched fetch of the SMALL outputs only — [:3] keeps the
            # [N, K] probs (4th return) on device
            grams, xtwz, ll = jax.device_get(stats(
                X, y, w, jnp.asarray(beta, jnp.float32), offset)[:3])
            grams = np.asarray(grams, np.float64)
            xtwz = np.asarray(xtwz, np.float64)
            delta = 0.0
            for k in range(K):
                bk = _solve_penalized(grams[k], xtwz[:, k], n, lam, p.alpha,
                                      beta[:, k], penalize)
                delta = max(delta, float(np.max(np.abs(bk - beta[:, k]))))
                beta[:, k] = bk
            ll = float(ll)
            hist.append({"lambda": lam, "iteration": it, "logloss": ll / n,
                         "delta": delta})
            job.update(it / p.max_iterations, f"iter={it} ll={ll:.4g}")
            if delta < p.beta_epsilon or abs(ll_prev - ll) < 1e-8 * n:
                break
            ll_prev = ll
        model = GLMModel(job.dest_key or dkv.make_key(self.algo), p, di)
        self._finalize(model, di, beta, "multinomial", X, y, w, offset, n,
                       2 * ll, hist, lam, frame, valid)
        return model

    # ------------------------------------------------------------ finalize
    @obs.span("glm.finalize")       # a span is a decorator too: one per call
    def _finalize(self, model, di, beta_std, fam_name, X, y, w, offset, n,
                  deviance, hist, lam, frame, valid, gram_last=None):
        p: GLMParameters = self.params
        # de-standardize coefficients back to the original data scale
        means = np.zeros(di.nfeatures)
        sigmas = np.ones(di.nfeatures)
        i = 0
        for s in di.specs:
            if s.type == "cat":
                i += s.width
            else:
                if di.standardize:
                    means[i], sigmas[i] = s.mean, s.sigma
                i += 1
        b = np.asarray(beta_std, np.float64)
        multi = b.ndim == 2
        bo = b / sigmas[:, None] if multi else b / sigmas
        if di.add_intercept:
            bo[-1] = b[-1] - (means[:-1] / sigmas[:-1]) @ b[:-1]

        model.output.update({
            "family": fam_name, "beta_std": np.asarray(beta_std, np.float32),
            "beta_std_flat": b.ravel().tolist(), "beta": bo,
            "coef_names": di.coef_names, "lambda": lam, "alpha": p.alpha,
            "iterations": len(hist), "residual_deviance": float(deviance),
            "rank": int(np.count_nonzero(np.atleast_2d(b))) ,
        })
        # null deviance
        fam = _make_family(fam_name if fam_name != "multinomial" else "binomial", p)
        if fam_name != "multinomial":
            mu0 = fam.linkinv(fam.init_eta(y, w))
            model.output["null_deviance"] = float(fam.deviance(y, mu0, w))
        model.scoring_history = hist
        # p-values for unpenalized fits (GLM.java compute_p_values path)
        if p.compute_p_values and lam == 0.0 and not multi and gram_last is not None:
            try:
                inv = np.linalg.inv(gram_last)
                disp = (deviance / max(n - len(b), 1.0)
                        if fam_name in ("gaussian", "gamma", "tweedie") else 1.0)
                se = np.sqrt(np.maximum(np.diag(inv) * disp, 0.0))
                zval = np.where(se > 0, b / np.maximum(se, 1e-30), np.nan)
                from scipy.stats import norm  # pragma: no cover
                pval = 2 * (1 - norm.cdf(np.abs(zval)))
            except Exception:
                se = zval = pval = None
            if se is not None:
                model.output.update({"std_errs": se, "z_values": zval,
                                     "p_values": pval})
        # training + validation metrics
        raw = model._predict_raw(X)
        model.training_metrics = make_metrics(di, raw, y, w)
        if valid is not None:
            model.validation_metrics = model.model_performance(valid)
