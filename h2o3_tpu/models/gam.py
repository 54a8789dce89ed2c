"""GAM: spline smooths with curvature penalties over the GLM.

Reference: ``hex/gam/GAM.java:53`` (4.7k LoC) — each ``gam_column`` expands
into a spline basis with a penalty matrix, identifiability-centered, then
the penalized GLM runs over [basis, other features].  Basis families:

- ``bs="cr"`` — cubic regression splines at quantile knots with the
  integrated-squared-second-derivative penalty
  (GamSplines/CubicRegressionSplines).
- ``bs="tp"`` — thin-plate regression splines, including MULTI-predictor
  smooths (``gam_columns`` entries may be lists of columns;
  GamSplines/ThinPlateRegressionUtils.java + ThinPlateDistanceWithKnots):
  radial basis at data knots, polynomial null space projected out, the
  bending-energy penalty from the radial block.
- ``bs="is"`` — monotone I-splines (GamSplines/ISplines): integrated
  B-spline basis whose coefficients are constrained non-negative through
  the GLM's ``non_negative`` option, yielding monotone-increasing smooths
  (``splines_non_negative``, NBSplinesTypeII analog).

TPU-native redesign: each penalty is diagonalized once per smooth
(Demmler-Reinsch: rotate by the centered penalty's eigenvectors) so it
becomes per-column ridge FACTORS on the shared GLM solver — no bespoke
penalized solver, and each null space (linear/polynomial trend) stays
unpenalized exactly as in mgcv/H2O.

A fit whose smooths are all ``cr`` over numeric columns never builds a
basis on the host: a cr smooth is a run of the GLM's design in code form
(``ColumnSpec.spline``: the raw column and its tables), and its rows are
built a block at a time inside the device programs that use them
(``datainfo.spline_rows``).  Its knots come from one quantile program over
the stacked gam columns (``binning._make_sketch_fn``, span ``gam.knots``),
the centring and scaling moments from one blocked pass (``gam.basis``, with
the K x K algebra on the host), the penalized IRLSM from ``GLM.fit_coded``
on the blocked runner (``gam.glm``), and scoring from ``jit_glm_score``.
``tp`` and ``is`` smooths, a smooth of a column that is not numeric, and a
family or solver that takes the dense design keep the host path: their
bases are built in numpy and uploaded as columns.  ``reference_gam.py`` is
the cr fit in float64 numpy.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..frame.frame import Frame
from ..frame.vec import Vec, T_NUM
from ..runtime import dkv
from ..runtime import observability as obs
from ..runtime.cluster import ROW_AXIS
from ..runtime.job import Job
from .base import Model, ModelBuilder
from .datainfo import (DataInfo, over_row_shards, spline_run,
                       sum_over_row_shards, sum_row_blocks)
from .glm import GLM, GLMParameters, _fit_block_rows


@dataclasses.dataclass
class GAMParameters(GLMParameters):
    # entries are column names, or LISTS of names for multi-predictor
    # thin-plate smooths (the reference's nested gam_columns)
    gam_columns: Sequence = ()
    # knots and smoothing strength: one number for every smooth, or one
    # entry per gam_columns entry (the reference's per-column arrays)
    num_knots: object = 8
    scale: object = 1.0
    # basis per smooth: "cr" | "tp" | "is" — a single string applies to
    # every smooth (the reference's bs array of 0=cr/1=tp/2=is codes)
    bs: object = "cr"
    # monotone (I-spline) smooths: constrain coefficients >= 0
    splines_non_negative: bool = True


def _crs_construct(knots: np.ndarray):
    """CRS machinery for one knot vector: returns (F_full, S).

    ``F_full`` [K, K] maps knot values -> second derivatives at the knots
    (natural boundary: zero curvature at the ends); ``S`` [K, K] is the
    integrated squared second derivative penalty  D' B^{-1} D  (the exact
    curvature penalty the reference's penalty_matrix encodes).
    """
    K = len(knots)
    h = np.diff(knots).astype(np.float64)
    D = np.zeros((K - 2, K))
    B = np.zeros((K - 2, K - 2))
    for i in range(K - 2):
        D[i, i] = 1.0 / h[i]
        D[i, i + 1] = -1.0 / h[i] - 1.0 / h[i + 1]
        D[i, i + 2] = 1.0 / h[i + 1]
        B[i, i] = (h[i] + h[i + 1]) / 3.0
        if i < K - 3:
            B[i, i + 1] = h[i + 1] / 6.0
            B[i + 1, i] = h[i + 1] / 6.0
    F = np.linalg.solve(B, D)                      # [K-2, K]
    F_full = np.vstack([np.zeros(K), F, np.zeros(K)])
    S = D.T @ F                                    # [K, K], PSD
    return F_full, S


def _crs_eval(x: np.ndarray, knots: np.ndarray,
              F_full: np.ndarray) -> np.ndarray:
    """Cardinal CRS basis values [n, K]: row r gives the weights such that
    f(x_r) = weights . f(knots) for the natural interpolating spline."""
    K = len(knots)
    h = np.diff(knots)
    xc = np.clip(x, knots[0], knots[-1])
    j = np.clip(np.searchsorted(knots, xc, side="right") - 1, 0, K - 2)
    kj, kj1 = knots[j], knots[j + 1]
    hj = h[j]
    am = (kj1 - xc) / hj
    ap = (xc - kj) / hj
    cm = ((kj1 - xc) ** 3 / hj - hj * (kj1 - xc)) / 6.0
    cp = ((xc - kj) ** 3 / hj - hj * (xc - kj)) / 6.0
    n = len(x)
    X = np.zeros((n, K))
    rows = np.arange(n)
    np.add.at(X, (rows, j), am)
    np.add.at(X, (rows, j + 1), ap)
    X += cm[:, None] * F_full[j] + cp[:, None] * F_full[j + 1]
    return X


def _tp_eta(r: np.ndarray, d: int) -> np.ndarray:
    """Thin-plate radial basis function for d input dimensions (m=2)."""
    if d == 1:
        return r ** 3 / 12.0
    if d == 2:
        with np.errstate(divide="ignore", invalid="ignore"):
            out = (r * r) * np.log(np.maximum(r, 1e-300)) / (8 * np.pi)
        return np.where(r > 0, out, 0.0)
    return -r / 8.0                         # d == 3 (odd-d general form)


def _tp_construct(Xk: np.ndarray):
    """Thin-plate machinery for one knot matrix [k, d]: returns (Z, S).

    ``Z`` [k, k-d-1] projects radial coefficients onto the null space of
    the polynomial constraint T'delta = 0 (T = [1, x1..xd] at the knots);
    ``S = Z' E Z`` is the bending-energy penalty with E the knot-knot
    radial matrix — the standard TPRS construction
    (ThinPlateRegressionUtils.java computes the same pieces distributedly).
    """
    k, d = Xk.shape
    r = np.linalg.norm(Xk[:, None, :] - Xk[None, :, :], axis=2)
    E = _tp_eta(r, d)
    T = np.concatenate([np.ones((k, 1)), Xk], axis=1)        # [k, d+1]
    q, _ = np.linalg.qr(T, mode="complete")
    Z = q[:, d + 1:]                                         # [k, k-d-1]
    S = Z.T @ E @ Z
    return Z, (S + S.T) / 2


def _tp_eval(X: np.ndarray, Xk: np.ndarray, Z: np.ndarray) -> np.ndarray:
    """Projected radial design block [n, k-d-1] for rows X [n, d]."""
    d = Xk.shape[1]
    r = np.linalg.norm(X[:, None, :] - Xk[None, :, :], axis=2)
    return _tp_eta(r, d) @ Z


def _is_basis(x: np.ndarray, knots: np.ndarray) -> np.ndarray:
    """I-spline (monotone) basis [n, K]: cumulative integrals of cubic
    M-splines — each column rises 0 -> 1, so non-negative coefficients
    give a monotone-increasing smooth (GamSplines/ISplines analog)."""
    from scipy.interpolate import BSpline
    order = 4                                # cubic
    t = np.concatenate([[knots[0]] * order, knots[1:-1],
                        [knots[-1]] * order])
    nb = len(t) - order
    xc = np.clip(x, knots[0], knots[-1])
    B = np.empty((len(x), nb))
    for j in range(nb):
        coef = np.zeros(nb)
        coef[j] = 1.0
        B[:, j] = BSpline(t, coef, order - 1)(xc)
    # I_j(x) = sum of B-spline columns m >= j+1 (integrated M-splines);
    # drop the first cumulative column (constant 1 = intercept clash)
    I = np.cumsum(B[:, ::-1], axis=1)[:, ::-1]
    return I[:, 1:]


def _centring(c: np.ndarray, S: np.ndarray):
    """Sum-to-zero centering + Demmler-Reinsch diagonalization, from the
    basis' column means ``c`` [K] and its penalty ``S`` [K, K].

    Returns (T, factors): the [K, K-1] transform applied to the basis and
    the per-output-column penalty factors (eigenvalues of the centered
    penalty; ~0 = unpenalized null space — the linear trend).
    """
    K = len(c)
    # Z: orthogonal complement of the column-mean constraint (mgcv's
    # sum-to-zero identifiability absorbing the intercept)
    q, _ = np.linalg.qr(np.concatenate([c[:, None],
                                        np.eye(K)[:, : K - 1]], axis=1))
    Z = q[:, 1:K]                                   # [K, K-1]
    Sc = Z.T @ S @ Z
    d, U = np.linalg.eigh((Sc + Sc.T) / 2)
    d = np.maximum(d, 0.0)
    T = Z @ U                                       # [K, K-1]
    return T, d


# ------------------------------------------------ cr smooths on the device
@functools.lru_cache(maxsize=None)
def _make_moments(layout: tuple, block: int):
    """Compiled ``gam_moments(w, num, tables)``: per spline run of
    ``layout`` the sums over the rows of weight ``w`` of its cardinal basis
    rows [S K] and of their products [S K, S K] (whose diagonal blocks are
    each smooth's), over the row blocks of the code-form design.  ``tables``
    are per run (knots, I, F_full): the identity and the knots'
    second-derivative map, so that ``spline_rows`` builds the cardinal
    basis."""
    def sums_of(tables, wb, nb):
        out, i_num, i_spl = [], 0, 0
        for kind, width in layout:
            if kind == "num":
                i_num += width
            elif kind == "spline":
                rows, used = spline_run(nb, i_num, tables, i_spl)
                wr = rows * wb[:, None]
                out.append((jnp.sum(wr, axis=0),
                            jnp.dot(wr.T, rows,
                                    precision=jax.lax.Precision.HIGHEST)))
                i_num, i_spl = i_num + used, i_spl + 1
        return out

    def shard(w, num, tables):
        return sum_over_row_shards(sum_row_blocks(
            functools.partial(sums_of, tables), block, w, num))

    def gam_moments(w, num, tables):
        return over_row_shards(
            shard, in_specs=(P(ROW_AXIS), P(ROW_AXIS, None), P()),
            out_specs=P())(w, num, tables)

    return jax.jit(gam_moments)


def _device_knots(frame: Frame, smooths: List[dict]) -> List[np.ndarray]:
    """Each smooth's knots: ``np.quantile`` of its column's non-missing
    values at ``linspace(0, 1, num_knots)``, duplicates dropped, by ONE
    exact quantile program a knot count over the stacked columns (the tree
    binning's sketch: min, max and the interior quantiles) and one fetch of
    its small result."""
    from .tree.binning import _make_sketch_fn
    out: List[Optional[np.ndarray]] = [None] * len(smooths)
    by_k: Dict[int, List[int]] = {}
    for i, s in enumerate(smooths):
        by_k.setdefault(max(s["num_knots"], 4), []).append(i)
    fetched = []
    for k, idx in by_k.items():
        X = jnp.stack([frame.vec(smooths[i]["cols"][0]).values()
                       .astype(jnp.float32) for i in idx], axis=0)
        sketch = _make_sketch_fn(frame.nrows, int(X.shape[1]), len(idx), k - 2)
        fetched.append((idx, sketch(X, jnp.ones((X.shape[1],), jnp.float32))))
    for idx, got in jax.device_get(fetched):     # ONE fetch of every result
        edges, lo, hi, m = (np.asarray(a, np.float64) for a in got)
        for row, i in enumerate(idx):
            col = smooths[i]["cols"][0]
            if m[row] == 0:
                raise ValueError(f"gam column {col!r} has no values")
            knots = np.unique(np.concatenate([lo[row:row + 1], edges[row],
                                              hi[row:row + 1]]))
            GAM._check_knots(knots, col)
            out[i] = knots
    return out


class GAMModel(Model):
    algo = "gam"

    def _block(self, m: dict, frame: Frame) -> np.ndarray:
        """Design block [n, width] for one smooth on any frame."""
        if m["kind"] == "cr":
            x = np.nan_to_num(frame.vec(m["cols"][0]).to_numpy(),
                              nan=m["mean"])
            B = _crs_eval(x, m["knots"], m["F_full"]) @ m["T"]
            return B / m["col_scale"][None, :]
        if m["kind"] == "tp":
            X = np.stack([np.nan_to_num(frame.vec(c).to_numpy(), nan=mu)
                          for c, mu in zip(m["cols"], m["means"])], axis=1)
            Xs = (X - np.asarray(m["means"])) / np.asarray(m["sigmas"])
            B = _tp_eval(Xs, m["knots"], m["Z"]) @ m["T"]
            B = B / m["col_scale"][None, :]
            return np.concatenate([B, Xs], axis=1)   # + linear null space
        x = np.nan_to_num(frame.vec(m["cols"][0]).to_numpy(),
                          nan=m["mean"])              # "is"
        return _is_basis(x, m["knots"])

    def _expand(self, frame: Frame) -> Frame:
        """``frame`` with every smooth's basis as uploaded columns (the host
        path); the frame itself where the GLM reads the smooths in code
        form."""
        if self.output.get("coded"):
            return frame
        meta = self.output["gam_meta"]
        smooth_cols = {c for m in meta for c in m["cols"]}
        names, vecs = [], []
        for n, v in zip(frame.names, frame.vecs):
            if n not in smooth_cols:
                names.append(n)
                vecs.append(v)
        for m in meta:
            B = self._block(m, frame)
            for j in range(B.shape[1]):
                names.append(f"{m['name']}_gam{j}")
                vecs.append(Vec.from_numpy(B[:, j], T_NUM))
        return Frame(names, vecs)

    def _score_matrix(self, frame: Frame):
        """The GLM's design of ``frame``: in code form where the smooths
        are spline runs (cross-validation scores every fold's model on
        it); the host path's expansion has no such matrix."""
        if not self.output.get("coded"):
            raise NotImplementedError("gam scores via its GLM")
        return dkv.get(self.output["glm_key"])._score_matrix(frame)

    def _predict_raw(self, X):
        if not self.output.get("coded"):
            raise NotImplementedError("gam scores via its GLM")
        return dkv.get(self.output["glm_key"])._predict_raw(X)

    def predict(self, frame: Frame) -> Frame:
        glm = dkv.get(self.output["glm_key"])
        return glm.predict(self._expand(frame))

    def model_performance(self, frame: Optional[Frame] = None):
        if frame is None:
            return self.training_metrics
        glm = dkv.get(self.output["glm_key"])
        return glm.model_performance(self._expand(frame))

    @property
    def coef(self) -> dict:
        return dkv.get(self.output["glm_key"]).coef


class GAM(ModelBuilder):
    """GAM builder — H2OGeneralizedAdditiveEstimator analog."""

    algo = "gam"
    model_class = GAMModel

    def __init__(self, params: Optional[GAMParameters] = None, **kw):
        super().__init__(params or GAMParameters(**kw))

    def _smooth_specs(self) -> List[dict]:
        """Normalize gam_columns / bs / num_knots / scale into per-smooth
        descriptors."""
        p: GAMParameters = self.params
        entries = [e if isinstance(e, (list, tuple)) else [e]
                   for e in p.gam_columns]

        def each(value, what):
            if not isinstance(value, (list, tuple)):
                return [value] * len(entries)
            if len(value) != len(entries):
                raise ValueError(f"{what} must be one value or one per "
                                 "gam_columns entry")
            return list(value)

        code = {0: "cr", 1: "tp", 2: "is", "cr": "cr", "tp": "tp",
                "is": "is", "ms": "is"}
        out = []
        for cols, k, knots, scale in zip(entries, each(p.bs, "bs"),
                                         each(p.num_knots, "num_knots"),
                                         each(p.scale, "scale")):
            kind = code.get(k)
            if kind is None:
                raise ValueError(f"unknown basis {k!r} (cr | tp | is)")
            if kind != "tp" and len(cols) > 1:
                raise ValueError("multi-column smooths need bs='tp'")
            if kind == "tp" and len(cols) > 3:
                raise ValueError(
                    "thin-plate smooths support up to 3 columns (the m=2 "
                    "radial basis needs 2m > d)")
            out.append({"cols": list(cols), "kind": kind,
                        "name": "_".join(cols), "num_knots": int(knots),
                        "scale": float(scale)})
        return out

    def _validate(self, frame: Frame) -> None:
        super()._validate(frame)
        p: GAMParameters = self.params
        if not p.gam_columns:
            raise ValueError("gam requires gam_columns")
        for s in self._smooth_specs():
            for c in s["cols"]:
                if c not in frame.names:
                    raise ValueError(f"gam column {c!r} not in frame")

    @staticmethod
    def _check_knots(knots: np.ndarray, col: str) -> None:
        if len(knots) < 4:
            raise ValueError(
                f"gam column {col!r} has too few distinct values "
                f"({len(knots)}) for a spline")

    @staticmethod
    def _quantile_knots(x: np.ndarray, k: int, col: str) -> np.ndarray:
        knots = np.unique(np.quantile(x, np.linspace(0, 1, max(k, 4))))
        GAM._check_knots(knots, col)
        return knots

    def _coded(self, smooths: List[dict], frame: Frame,
               di: DataInfo) -> bool:
        """Whether the fit reads its smooths in code form: every smooth a
        cr one of a numeric predictor, and a family and solver that the
        blocked IRLSM runs."""
        p: GAMParameters = self.params
        names = {s.name for s in di.specs}
        return (all(s["kind"] == "cr" and s["cols"][0] in names
                    and frame.vec(s["cols"][0]).type == T_NUM
                    for s in smooths)
                and p.solver.lower() not in ("l_bfgs", "lbfgs")
                and GLM(family=p.family)._resolve_family(di)
                not in ("multinomial", "ordinal"))

    def _fit(self, job: Job, frame: Frame, di: DataInfo,
             valid: Optional[Frame]) -> GAMModel:
        smooths = self._smooth_specs()
        model = GAMModel(job.dest_key or dkv.make_key(self.algo),
                         self.params, di)
        if self._coded(smooths, frame, di):
            self._fit_coded(job, frame, di, valid, smooths, model)
        else:
            self._fit_host(job, frame, valid, smooths, model)
        return model

    def _fit_coded(self, job: Job, frame: Frame, di: DataInfo,
                   valid: Optional[Frame], smooths: List[dict],
                   model: GAMModel) -> None:
        """The fit with every smooth a spline run of the code-form design:
        no basis is built on the host or uploaded."""
        p: GAMParameters = self.params
        with obs.span("gam.knots"):
            knots = _device_knots(frame, smooths)
        with obs.span("gam.basis"):
            cols = [s["cols"][0] for s in smooths]
            crs = [_crs_construct(k) for k in knots]
            # the layout with each smooth's run (tables of its width only),
            # whose code form is the final one's: the imputed raw columns
            skeleton = di.with_splines({
                c: (k, np.zeros((len(k), len(k) - 1)), None)
                for c, k in zip(cols, knots)})
            X = skeleton.make_coded(frame)
            # its runs again with the cardinal basis' tables, which the
            # moments program reads
            cardinal = skeleton.with_splines({
                c: (k, np.eye(len(k)), F) for c, k, (F, _) in
                zip(cols, knots, crs)})
            layout = skeleton.coded_layout()
            moments = _make_moments(layout, _fit_block_rows(
                layout, X.num.shape[0]))
            w = frame.valid_mask().astype(jnp.float32)
            sums = jax.device_get(moments(w, X.num,
                                          cardinal.spline_tables()))
            at = {s.name: (g, r)
                  for g, run in enumerate(cardinal.spline_groups())
                  for r, s in enumerate(run)}
            by_name = {t.name: t for t in di.specs}
            meta, tables, factors = [], {}, {}
            for s, c, k, (F, S) in zip(smooths, cols, knots, crs):
                g, r = at[c]
                K = len(k)
                c_sum, m2_sum = (np.asarray(a, np.float64) for a in sums[g])
                c_sum = c_sum[r * K:(r + 1) * K]
                m2_sum = m2_sum[r * K:(r + 1) * K, r * K:(r + 1) * K]
                mean = c_sum / frame.nrows
                T, d = _centring(mean, S)
                cov = m2_sum / frame.nrows - np.outer(mean, mean)
                col_scale = np.maximum(
                    np.sqrt(np.maximum(np.diag(T.T @ cov @ T), 0.0)), 1e-12)
                G = T / col_scale[None, :]
                tables[c] = (k, G, F @ G)
                meta.append({**s, "knots": k, "F_full": F, "T": T,
                             "mean": float(by_name[c].mean),
                             "col_scale": col_scale})
                # the factors of _fit_host's cr branch
                d_max = max(float(d.max()), 1e-30)
                factors[c] = s["scale"] * (d / d_max) \
                    / np.maximum(col_scale ** 2, 1e-30)
            sdi = di.with_splines(tables)
            obs.inc("gam_basis_columns_total",
                    sum(len(k) - 1 for k in knots), bs="cr")
        model.output.update(gam_meta=meta, coded=True)
        base_lam = 0.0 if p.lambda_ is None else float(np.max(p.lambda_))
        penalize = np.zeros(sdi.nfeatures)
        for spec in sdi.specs:
            penalize[spec.offset: spec.offset + spec.width] = \
                factors.get(spec.name, base_lam)
        job.update(0.3, "fitting penalized GLM over the spline bases")
        with obs.span("gam.glm"):
            y = jnp.nan_to_num(di.response(frame))
            wr = di.weights(frame)
            offset = di.offsets(frame)
            glm = GLM(response_column=p.response_column, family=p.family,
                      alpha=0.0, lambda_=1.0,
                      weights_column=p.weights_column,
                      seed=p.effective_seed(),
                      max_iterations=p.max_iterations).fit_coded(
                Job("gam glm"), frame, sdi, X, y, wr,
                offset if offset is not None else jnp.zeros_like(y),
                penalize, (), None)
        model.output["glm_key"] = glm.key
        model.output["family"] = glm.output.get("family")
        model.training_metrics = glm.training_metrics
        if valid is not None:
            model.validation_metrics = model.model_performance(valid)

    def _fit_host(self, job: Job, frame: Frame, valid: Optional[Frame],
                  smooths: List[dict], model: GAMModel) -> None:
        """The fit with every smooth's basis built in numpy and uploaded as
        columns beside the frame's others."""
        p: GAMParameters = self.params
        meta: List[dict] = []
        factors: Dict[str, float] = {}
        nonneg: List[str] = []
        for s in smooths:
            name, cols = s["name"], s["cols"]
            if s["kind"] == "cr":
                x = frame.vec(cols[0]).to_numpy()
                x = x[~np.isnan(x)]
                knots = self._quantile_knots(x, s["num_knots"], cols[0])
                F_full, S = _crs_construct(knots)
                Xb = _crs_eval(np.nan_to_num(frame.vec(cols[0]).to_numpy(),
                                             nan=float(x.mean())),
                               knots, F_full)
                T, d = _centring(Xb.mean(axis=0), S)
                col_scale = np.maximum((Xb @ T).std(axis=0), 1e-12)
                meta.append({**s, "knots": knots, "F_full": F_full, "T": T,
                             "mean": float(x.mean()),
                             "col_scale": col_scale})
                obs.inc("gam_basis_columns_total", len(d), bs="cr")
                # penalty factor for the scaled column: the design column
                # is Bt/s, so its coefficient is s*beta and a factor f
                # penalizes f*s^2*beta^2 — realizing scale*d_j*beta^2
                # needs f = scale*d/s^2.  d is normalized by its largest
                # eigenvalue (the reference scales penalty matrices
                # likewise) so scale=1 smooths mildly regardless of knot
                # spacing / data units.
                d_max = max(float(d.max()), 1e-30)
                for j, dj in enumerate(d):
                    factors[f"{name}_gam{j}"] = float(
                        s["scale"] * (dj / d_max)
                        / max(col_scale[j] ** 2, 1e-30))
            elif s["kind"] == "tp":
                Xcols, means, sigmas = [], [], []
                for c in cols:
                    xc = frame.vec(c).to_numpy()
                    mu = float(np.nanmean(xc))
                    sd = float(np.nanstd(xc)) or 1.0
                    Xcols.append(np.nan_to_num(xc, nan=mu))
                    means.append(mu)
                    sigmas.append(sd)
                X = (np.stack(Xcols, axis=1) - np.asarray(means)) \
                    / np.asarray(sigmas)
                dcols = X.shape[1]
                k = max(s["num_knots"], dcols + 3)
                # deterministic space-filling knots: evenly strided rows
                # of the lexicographic sort (kmeans-free knot placement)
                order = np.lexsort(X.T[::-1])
                idx = order[np.linspace(0, len(order) - 1, k).astype(int)]
                knots = np.unique(X[idx], axis=0)
                Z, S = _tp_construct(knots)
                B = _tp_eval(X, knots, Z)
                T, d = _centring(B.mean(axis=0), S)
                col_scale = np.maximum((B @ T).std(axis=0), 1e-12)
                meta.append({**s, "knots": knots, "Z": Z, "T": T,
                             "means": means, "sigmas": sigmas,
                             "col_scale": col_scale})
                obs.inc("gam_basis_columns_total", len(d) + dcols, bs="tp")
                # TP factors are normalized on the SCALED columns (the
                # radial basis has tiny raw magnitudes, so the CRS-style
                # d/col_scale^2 blows up): f_raw = d_j/col_scale_j^2,
                # rescaled so the stiffest direction gets exactly
                # ``scale`` — scale=1 then smooths mildly, matching the
                # CRS knob's feel.
                f_raw = np.maximum(np.asarray(d, float), 0.0) \
                    / np.maximum(col_scale ** 2, 1e-30)
                f_max = max(float(f_raw.max()), 1e-30)
                nrad = len(col_scale)
                for j in range(nrad):
                    factors[f"{name}_gam{j}"] = float(
                        s["scale"] * f_raw[j] / f_max)
                for j in range(dcols):            # linear null space
                    factors[f"{name}_gam{nrad + j}"] = 0.0
            else:                                 # "is" — monotone
                x = frame.vec(cols[0]).to_numpy()
                x = x[~np.isnan(x)]
                knots = self._quantile_knots(x, s["num_knots"], cols[0])
                meta.append({**s, "knots": knots, "mean": float(x.mean())})
                width = _is_basis(np.asarray([knots[0]]), knots).shape[1]
                obs.inc("gam_basis_columns_total", width, bs="is")
                for j in range(width):
                    cname = f"{name}_gam{j}"
                    factors[cname] = float(s["scale"])
                    if p.splines_non_negative:
                        nonneg.append(cname)
        model.output["gam_meta"] = meta

        # non-gam predictors keep the user's lambda as their factor
        base_lam = 0.0 if p.lambda_ is None else float(np.max(p.lambda_))
        expanded = model._expand(frame)
        for n in expanded.names:
            if n not in factors and n != p.response_column:
                factors[n] = base_lam
        job.update(0.3, "fitting penalized GLM over the spline bases")
        glm = GLM(response_column=p.response_column, family=p.family,
                  alpha=0.0, lambda_=1.0, penalty_factors=factors,
                  weights_column=p.weights_column,
                  non_negative=nonneg or False,
                  seed=p.effective_seed(),
                  max_iterations=p.max_iterations).train(
            expanded, model._expand(valid) if valid is not None else None)
        model.output["glm_key"] = glm.key
        model.output["family"] = glm.output.get("family")
        model.training_metrics = glm.training_metrics
        model.validation_metrics = glm.validation_metrics
