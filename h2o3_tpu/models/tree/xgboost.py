"""XGBoost-parameter-compatible booster on the tpu_hist kernel family.

Reference: ``h2o-extensions/xgboost`` — ``hex/tree/xgboost/XGBoost.java``
(driver loop :371-398,486-524) delegates to native libxgboost
(``gpu_hist``/``hist`` tree builders + Rabit ring allreduce,
XGBoostModel.java:260-298 maps h2o params to xgboost params).

TPU-native redesign: same estimator surface and exact split math
(L1-soft-thresholded gain, gamma pruning, min_child_weight hessian
constraint, sparsity-aware NA direction — hist.py:best_splits) on the
tpu_hist MXU histogram kernels; ICI psum replaces Rabit.  ``booster='dart'``
runs libxgboost's DART dropout/renormalization inside the shared GBM driver.
The h2o alias surface (eta/subsample/colsample_bytree/...) is accepted
verbatim so estimator code ports 1:1.  Like gpu_hist, levels below the root
histogram only each parent's smaller child and derive the sibling by
subtraction (``hist_mode="subtract"``, the default; "full" is the exactness
oracle — shared.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from ...frame.frame import Frame
from .gbm import GBM, GBMModel, GBMParameters
from .shared import SharedTree, SharedTreeParameters

# h2o-py H2OXGBoostEstimator alias -> canonical field
_ALIASES = {
    "eta": "learn_rate",
    "subsample": "sample_rate",
    "colsample_bytree": "col_sample_rate_per_tree",
    "colsample_bylevel": "col_sample_rate",
    "max_bins": "nbins",
    "min_split_loss": "gamma",
    "n_estimators": "ntrees",
    "max_leaves": None,                 # accepted, depthwise growth only
    "tree_method": None,
    "grow_policy": None,
    "backend": None,
    "gpu_id": None,
}

# xgboost objective -> our distribution
_OBJECTIVES = {
    "reg:squarederror": "gaussian",
    "reg:linear": "gaussian",
    "binary:logistic": "bernoulli",
    "multi:softprob": "multinomial",
    "multi:softmax": "multinomial",
    "count:poisson": "poisson",
    "reg:gamma": "gamma",
    "reg:tweedie": "tweedie",
}


@dataclasses.dataclass
class XGBoostParameters(SharedTreeParameters):
    # xgboost defaults (XGBoostModel.java createParams defaults)
    ntrees: int = 50
    max_depth: int = 6
    learn_rate: float = 0.3
    min_rows: float = 1.0
    reg_lambda: float = 1.0
    reg_alpha: float = 0.0
    gamma: float = 0.0
    min_child_weight: float = 1.0
    nbins: int = 256
    sample_rate: float = 1.0
    col_sample_rate: float = 1.0
    col_sample_rate_per_tree: float = 1.0
    booster: str = "gbtree"              # gbtree | dart
    scale_pos_weight: float = 1.0
    # DART params (libxgboost dart booster)
    rate_drop: float = 0.0
    skip_drop: float = 0.0
    one_drop: bool = False
    normalize_type: str = "tree"         # tree | forest
    sample_type: str = "uniform"


class XGBoostModel(GBMModel):
    algo = "xgboost"


class XGBoost(GBM):
    """XGBoost-compatible builder — H2OXGBoostEstimator analog on tpu_hist."""

    algo = "xgboost"
    model_class = XGBoostModel

    def __init__(self, params: Optional[XGBoostParameters] = None, **kw):
        if params is None:
            canon = {}
            for k, v in kw.items():
                if k == "objective":
                    canon["distribution"] = _OBJECTIVES.get(v, v)
                    continue
                if k in _ALIASES:
                    tgt = _ALIASES[k]
                    if tgt is not None:
                        canon[tgt] = v
                    continue
                canon[k] = v
            params = XGBoostParameters(**canon)
        if params.booster not in ("gbtree", "dart"):
            raise ValueError(
                f"booster={params.booster!r} not supported (gbtree, dart); "
                "gblinear maps to GLM in this framework")
        SharedTree.__init__(self, params)

    def train(self, frame, valid=None, warm_start=None):
        p: XGBoostParameters = self.params
        # scale_pos_weight needs materialized response codes — a
        # StreamingFrame defers to the per-segment trains on its
        # visible prefixes (each a real Frame re-entering here)
        scaled = self._apply_scale_pos_weight(frame) \
            if p.scale_pos_weight != 1.0 and isinstance(frame, Frame) \
            else None
        if scaled is None:
            return super().train(frame, valid, warm_start=warm_start)
        frame2, params2 = scaled
        self.params = params2
        try:
            return super().train(frame2, valid, warm_start=warm_start)
        finally:
            self.params = p          # builder stays reusable

    def _apply_scale_pos_weight(self, frame):
        """Fold scale_pos_weight into a row-weight column (binary only)."""
        import numpy as np
        from ...frame.frame import Frame
        from ...frame.vec import Vec, T_NUM, T_CAT
        p: XGBoostParameters = self.params
        rv = frame.vec(p.response_column)
        if rv.type != T_CAT or len(rv.domain or []) != 2:
            return None
        codes = rv.to_numpy()
        w = np.where(codes == 1, p.scale_pos_weight, 1.0)
        if p.weights_column:
            w = w * frame.vec(p.weights_column).to_numpy()
        names = list(frame.names) + ["_xgb_w_"]
        vecs = list(frame.vecs) + [Vec.from_numpy(w, T_NUM)]
        return (Frame(names, vecs),
                dataclasses.replace(p, weights_column="_xgb_w_"))
