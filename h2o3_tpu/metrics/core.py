"""Model metrics: binomial / multinomial / regression, computed on device.

Reference: the ``hex/ModelMetrics*`` hierarchy (30+ classes) + ``hex/AUC2.java``
(exact AUC via a 400-bin treatment of the score distribution), GainsLift,
ConfusionMatrix — accumulated per-row by MetricBuilders inside the BigScore
MRTask and tree-reduced.

TPU-native redesign: each metric family is ONE fused XLA pass over the
row-sharded (predictions, response, weights) arrays — weighted histograms over
a fixed threshold grid replace AUC2's per-row treatment insertion, and the
reduce tree is GSPMD's automatic ``psum``.  Host-side dataclasses hold the
resulting scalars, mirroring the reference's metrics schema names.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

NBINS = 400  # AUC2's default number of threshold bins (hex/AUC2.java)


def _merge_custom(self, base: dict) -> dict:
    """Merge a custom-metric UDF result (plain data attr; picklable)."""
    cm = getattr(self, "custom_metric", None)
    if cm:
        return {**base, cm["name"]: cm["value"]}
    return base


# =========================================================== binomial kernels
@functools.partial(jax.jit, static_argnums=(3,))
def _binomial_hist_kernel(p1, y, w, nbins: int):
    """Weighted histograms of P(class1) for positives and negatives.

    Bin i covers scores in [i/nbins, (i+1)/nbins); returns (pos[nbins],
    neg[nbins], logloss_sum, se_sum, wsum, wpos).

    The histogram is a blocked one-hot matmul (HIGHEST precision keeps f32
    weights exact), not a scatter-add: TPU serializes scatters — measured
    0.58 s per 10M-row metrics call, ~40x the MXU formulation.
    """
    p1c = jnp.clip(p1, 1e-15, 1 - 1e-15)
    idx = jnp.clip((p1 * nbins).astype(jnp.int32), 0, nbins - 1)
    pos_w = w * (y == 1)
    neg_w = w * (y == 0)
    n = p1.shape[0]
    blk = max(min(n, 1 << 20), 1)          # n == 0: zero-block scan
    nblk = -(-n // blk)
    pad = nblk * blk - n
    idxp = jnp.pad(idx, (0, pad)).reshape(nblk, blk)
    S = jnp.pad(jnp.stack([pos_w, neg_w], axis=1),
                [(0, pad), (0, 0)]).reshape(nblk, blk, 2)
    biota = jax.lax.broadcasted_iota(jnp.int32, (nbins, 1), 0)

    def body(acc, args):
        ib, sb = args
        oh = (biota == ib[None, :]).astype(jnp.float32)      # [nbins, blk]
        return acc + jnp.dot(oh, sb,
                             precision=jax.lax.Precision.HIGHEST), None

    hist, _ = jax.lax.scan(body, jnp.zeros((nbins, 2), jnp.float32),
                           (idxp, S))
    pos, neg = hist[:, 0], hist[:, 1]
    ll = -jnp.sum(w * (y * jnp.log(p1c) + (1 - y) * jnp.log1p(-p1c)))
    se = jnp.sum(w * (y - p1) ** 2)
    # ONE packed result -> one device->host fetch (each fetch waits for
    # the device)
    return jnp.concatenate([pos, neg,
                            jnp.stack([ll, se, jnp.sum(w),
                                       jnp.sum(pos_w)])])


@dataclasses.dataclass
class ConfusionMatrix:
    """2x2 (or KxK) confusion matrix at a threshold, rows=actual."""
    table: np.ndarray
    domain: List[str]

    def __repr__(self):
        return f"ConfusionMatrix({self.domain}):\n{self.table}"


@dataclasses.dataclass
class ModelMetricsBinomial:
    nobs: float
    auc: float
    pr_auc: float
    gini: float
    logloss: float
    mse: float
    rmse: float
    mean_per_class_error: float
    max_f1: float
    max_f1_threshold: float
    accuracy: float
    domain: List[str]
    cm: ConfusionMatrix
    # ROC curve arrays (descending thresholds), for gains/lift & plots
    thresholds: np.ndarray
    tps: np.ndarray
    fps: np.ndarray

    @property
    def r2(self) -> float:
        return float("nan")

    def confusion_matrix(self) -> ConfusionMatrix:
        return self.cm

    def gains_lift(self, groups: int = 16) -> dict:
        """Quantile gains/lift table — hex/GainsLift.java analog."""
        from .gainslift import gains_lift_table
        return gains_lift_table(self.thresholds, self.tps, self.fps,
                                groups=groups)

    @property
    def ks(self) -> float:
        """Kolmogorov-Smirnov statistic (max TPR - FPR over thresholds)."""
        npos = float(self.tps[-1])
        nneg = float(self.fps[-1])
        if npos <= 0 or nneg <= 0:
            return float("nan")
        return float(np.max(self.tps / npos - self.fps / nneg))

    def describe(self) -> dict:
        return _merge_custom(self, {
            "auc": self.auc, "pr_auc": self.pr_auc, "logloss": self.logloss,
            "rmse": self.rmse, "gini": self.gini,
            "mean_per_class_error": self.mean_per_class_error,
            "max_f1": self.max_f1, "threshold": self.max_f1_threshold,
            "ks": self.ks})


def binomial_metrics(p1, y, w, domain: Optional[List[str]] = None
                     ) -> ModelMetricsBinomial:
    """AUC2-equivalent metrics from P(class1), labels {0,1}, weights."""
    packed = np.asarray(_binomial_hist_kernel(
        jnp.asarray(p1), jnp.asarray(y), jnp.asarray(w), NBINS), np.float64)
    pos, neg = packed[:NBINS], packed[NBINS: 2 * NBINS]
    ll, se, wsum, wpos = packed[2 * NBINS:]
    n = float(wsum)
    npos = float(wpos)
    nneg = n - npos
    # descending-threshold cumulatives: predict-1 iff score >= threshold
    tps = np.cumsum(pos[::-1])          # true positives at each threshold
    fps = np.cumsum(neg[::-1])          # false positives
    thresholds = (np.arange(NBINS)[::-1]) / NBINS
    tpr = tps / max(npos, 1e-12)
    fpr = fps / max(nneg, 1e-12)
    # trapezoid AUC over the ROC polyline (prepend origin)
    auc = float(np.trapezoid(np.concatenate([[0.0], tpr]),
                         np.concatenate([[0.0], fpr])))
    prec = tps / np.maximum(tps + fps, 1e-12)
    rec = tpr
    pr_auc = float(np.trapezoid(np.concatenate([[prec[0]], prec]),
                            np.concatenate([[0.0], rec])))
    f1 = 2 * prec * rec / np.maximum(prec + rec, 1e-12)
    best = int(np.argmax(f1))
    thr = float(thresholds[best])
    tp, fp = tps[best], fps[best]
    fn, tn = npos - tp, nneg - fp
    cm = ConfusionMatrix(np.array([[tn, fp], [fn, tp]]),
                         list(domain or ["0", "1"]))
    per_class_err = 0.5 * (fp / max(nneg, 1e-12) + fn / max(npos, 1e-12))
    return ModelMetricsBinomial(
        nobs=n, auc=auc, pr_auc=pr_auc, gini=2 * auc - 1,
        logloss=float(ll) / max(n, 1e-12), mse=float(se) / max(n, 1e-12),
        rmse=float(np.sqrt(float(se) / max(n, 1e-12))),
        mean_per_class_error=float(per_class_err),
        max_f1=float(f1[best]), max_f1_threshold=thr,
        accuracy=float((tp + tn) / max(n, 1e-12)),
        domain=list(domain or ["0", "1"]), cm=cm,
        thresholds=thresholds, tps=tps, fps=fps)


# ======================================================== multinomial kernels
@functools.partial(jax.jit, static_argnums=(3,))
def _multinomial_kernel(probs, y, w, nclasses: int):
    yi = jnp.clip(y.astype(jnp.int32), 0, nclasses - 1)
    p_true = jnp.clip(probs[jnp.arange(probs.shape[0]), yi], 1e-15, 1.0)
    ll = -jnp.sum(w * jnp.log(p_true))
    pred = jnp.argmax(probs, axis=1)
    # weighted KxK confusion matrix (actual, predicted)
    flat = yi * nclasses + pred
    cm = jnp.zeros(nclasses * nclasses, jnp.float32).at[flat].add(w)
    se = jnp.sum(w * jnp.sum((probs - jax.nn.one_hot(yi, nclasses)) ** 2, axis=1))
    # hit ratios: rank of true class
    order = jnp.argsort(-probs, axis=1)
    match = (order == yi[:, None])
    ranks = jnp.argmax(match, axis=1)
    topk = jnp.zeros(nclasses, jnp.float32).at[ranks].add(w)
    # packed: one fetch (see _binomial_hist_kernel)
    return jnp.concatenate([jnp.stack([ll, se, jnp.sum(w)]), cm, topk])


@dataclasses.dataclass
class ModelMetricsMultinomial:
    nobs: float
    logloss: float
    mse: float
    rmse: float
    mean_per_class_error: float
    accuracy: float
    domain: List[str]
    cm: ConfusionMatrix
    hit_ratios: np.ndarray

    def confusion_matrix(self) -> ConfusionMatrix:
        return self.cm

    def describe(self) -> dict:
        return _merge_custom(self, {
            "logloss": self.logloss, "rmse": self.rmse,
            "mean_per_class_error": self.mean_per_class_error,
            "accuracy": self.accuracy})


def multinomial_metrics(probs, y, w, domain: List[str]
                        ) -> ModelMetricsMultinomial:
    k = len(domain)
    packed = np.asarray(_multinomial_kernel(
        jnp.asarray(probs), jnp.asarray(y), jnp.asarray(w), k), np.float64)
    ll, se, wsum = packed[:3]
    cm = packed[3: 3 + k * k].reshape(k, k)
    topk = packed[3 + k * k:]
    n = float(wsum)
    row = cm.sum(axis=1)
    diag = np.diag(cm)
    per_class = np.where(row > 0, 1 - diag / np.maximum(row, 1e-12), 0.0)
    hit = np.cumsum(topk) / max(n, 1e-12)
    return ModelMetricsMultinomial(
        nobs=n, logloss=float(ll) / max(n, 1e-12),
        mse=float(se) / max(n, 1e-12),
        rmse=float(np.sqrt(float(se) / max(n, 1e-12))),
        mean_per_class_error=float(per_class[row > 0].mean()) if (row > 0).any() else 0.0,
        accuracy=float(diag.sum() / max(n, 1e-12)),
        domain=list(domain), cm=ConfusionMatrix(cm, list(domain)),
        hit_ratios=hit)


# ========================================================== regression kernel
@jax.jit
def _regression_kernel(pred, y, w):
    err = y - pred
    se = jnp.sum(w * err * err)
    ae = jnp.sum(w * jnp.abs(err))
    wsum = jnp.sum(w)
    ybar = jnp.sum(w * y) / jnp.maximum(wsum, 1e-12)
    sst = jnp.sum(w * (y - ybar) ** 2)
    # rmsle guarded against negatives
    ok = (pred > -1) & (y > -1)
    sle = jnp.sum(jnp.where(ok & (w > 0),
                            w * (jnp.log1p(jnp.clip(pred, -1 + 1e-12, None))
                                 - jnp.log1p(jnp.clip(y, -1 + 1e-12, None))) ** 2,
                            0.0))
    # packed: one fetch (see _binomial_hist_kernel)
    return jnp.stack([se, ae, wsum, sst, sle])


@dataclasses.dataclass
class ModelMetricsRegression:
    nobs: float
    mse: float
    rmse: float
    mae: float
    rmsle: float
    r2: float
    mean_residual_deviance: float

    def describe(self) -> dict:
        return _merge_custom(self, {
            "rmse": self.rmse, "mae": self.mae, "r2": self.r2,
            "mean_residual_deviance": self.mean_residual_deviance})


def regression_metrics(pred, y, w, deviance_sum: Optional[float] = None
                       ) -> ModelMetricsRegression:
    se, ae, wsum, sst, sle = np.asarray(_regression_kernel(
        jnp.asarray(pred), jnp.asarray(y), jnp.asarray(w)), np.float64)
    n = max(float(wsum), 1e-12)
    mse = float(se) / n
    return ModelMetricsRegression(
        nobs=float(wsum), mse=mse, rmse=float(np.sqrt(mse)),
        mae=float(ae) / n, rmsle=float(np.sqrt(max(float(sle), 0.0) / n)),
        r2=float(1.0 - float(se) / max(float(sst), 1e-12)),
        mean_residual_deviance=(deviance_sum / n if deviance_sum is not None
                                else mse))


# ============================================================ unified factory
def make_metrics(di, raw, y, w, distribution=None, deviance_sum=None,
                 custom_metric_func=None):
    """Dispatch on the DataInfo's response type — the BigScore metric step.

    ``custom_metric_func``: optional UDF ``(predictions, y, w) -> (name,
    value)`` — the water/udf/CMetricFunc analog; the result is attached to
    the metrics object and surfaces in ``describe()``.
    """
    if di.is_classifier:
        dom = [str(d) for d in di.response_domain]
        if len(dom) == 2:
            p1 = raw[:, 1] if raw.ndim == 2 else raw
            m = binomial_metrics(p1, y, w, domain=dom)
        else:
            m = multinomial_metrics(raw, y, w, domain=dom)
    else:
        pred = raw[:, 0] if raw.ndim == 2 else raw
        m = regression_metrics(pred, jnp.nan_to_num(y), w,
                               deviance_sum=deviance_sum)
    if custom_metric_func is not None:
        name, value = custom_metric_func(np.asarray(raw), np.asarray(y),
                                         np.asarray(w))
        # plain data attribute (picklable); describe() merges it
        m.custom_metric = {"name": str(name), "value": float(value)}
    return m
