"""AUTO-GENERATED by h2o3_tpu.bindings.gen — do not edit.

Remote estimator classes (h2o-py H2O*Estimator analogs) over the REST
client.  Each class mirrors the server's /3/ModelBuilders parameter
schema: explicit keyword signature, types and server defaults in the
docstring.  Regenerate with: python -m h2o3_tpu.bindings.gen
"""

from typing import Optional


class _RemoteEstimator:
    """Base: holds params, trains via an H2OConnection."""

    algo = ""

    def __init__(self, **params):
        bad = set(params) - set(self._param_names)
        if bad:
            raise TypeError(
                f"unknown parameters for {type(self).__name__}: "
                f"{sorted(bad)}")
        self._params = params
        self.model = None

    def _init_from(self, args):
        """Collect explicitly passed (non-None) keyword args; None means
        'use the server default'."""
        self._params = {k: v for k, v in args.items()
                        if k != "self" and v is not None}
        self.model = None

    def get_params(self):
        return dict(self._params)

    def train(self, training_frame, validation_frame=None, connection=None):
        conn = connection or (training_frame.conn
                              if hasattr(training_frame, "conn") else None)
        if conn is None:
            raise ValueError("pass connection= or a RemoteFrame")
        self.model = conn.train(self.algo, training_frame,
                                validation_frame, **self._params)
        return self.model

    def predict(self, frame):
        if self.model is None:
            raise ValueError("train first")
        return self.model.predict(frame)


class H2OGLMEstimator(_RemoteEstimator):
    """glm estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    family : str, default 'auto'
    alpha : float, default 0.5
    lambda_ : Union[float, Sequence[float], None], default None
    lambda_search : bool, default False
    nlambdas : int, default 30
    lambda_min_ratio : float, default 0.0001
    solver : str, default 'irlsm'
    non_negative : Union[bool, Sequence[str]], default False
    penalty_factors : Optional[dict], default None
    tweedie_variance_power : float, default 1.5
    theta : float, default 1.0
    beta_epsilon : float, default 1e-05
    compute_p_values : bool, default False
    intercept : bool, default True
    """

    algo = 'glm'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'family', 'alpha', 'lambda_', 'lambda_search', 'nlambdas', 'lambda_min_ratio', 'solver', 'non_negative', 'penalty_factors', 'tweedie_variance_power', 'theta', 'beta_epsilon', 'compute_p_values', 'intercept')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'family': 'auto', 'alpha': 0.5, 'lambda_': None, 'lambda_search': False, 'nlambdas': 30, 'lambda_min_ratio': 0.0001, 'solver': 'irlsm', 'non_negative': False, 'penalty_factors': None, 'tweedie_variance_power': 1.5, 'theta': 1.0, 'beta_epsilon': 1e-05, 'compute_p_values': False, 'intercept': True}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, family=None, alpha=None, lambda_=None, lambda_search=None, nlambdas=None, lambda_min_ratio=None, solver=None, non_negative=None, penalty_factors=None, tweedie_variance_power=None, theta=None, beta_epsilon=None, compute_p_values=None, intercept=None):
        self._init_from(locals())

class H2OGBMEstimator(_RemoteEstimator):
    """gbm estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default False
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    ntrees : int, default 50
    max_depth : int, default 5
    min_rows : float, default 10.0
    nbins : int, default 64
    histogram_type : str, default 'QuantilesGlobal'
    monotone_constraints : Optional[dict], default None
    learn_rate : float, default 0.1
    sample_rate : float, default 1.0
    col_sample_rate : float, default 1.0
    col_sample_rate_per_tree : float, default 1.0
    min_split_improvement : float, default 1e-05
    reg_lambda : float, default 0.0
    reg_alpha : float, default 0.0
    gamma : float, default 0.0
    min_child_weight : float, default 0.0
    distribution : str, default 'auto'
    tweedie_power : float, default 1.5
    quantile_alpha : float, default 0.5
    huber_alpha : float, default 0.9
    score_tree_interval : int, default 5
    hist_precision : str, default 'bf16'
    hist_mode : str, default 'auto'
    split_mode : str, default 'auto'
    hist_layout : str, default 'auto'
    sparse_depth_threshold : int, default 8
    tree_program : str, default 'auto'
    calibrate_model : bool, default False
    calibration_frame : Optional[object], default None
    calibration_method : str, default 'platt'
    reproducible : bool, default False
    efb : str, default 'auto'
    custom_distribution_func : Optional[object], default None
    """

    algo = 'gbm'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'ntrees', 'max_depth', 'min_rows', 'nbins', 'histogram_type', 'monotone_constraints', 'learn_rate', 'sample_rate', 'col_sample_rate', 'col_sample_rate_per_tree', 'min_split_improvement', 'reg_lambda', 'reg_alpha', 'gamma', 'min_child_weight', 'distribution', 'tweedie_power', 'quantile_alpha', 'huber_alpha', 'score_tree_interval', 'hist_precision', 'hist_mode', 'split_mode', 'hist_layout', 'sparse_depth_threshold', 'tree_program', 'calibrate_model', 'calibration_frame', 'calibration_method', 'reproducible', 'efb', 'custom_distribution_func')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': False, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'ntrees': 50, 'max_depth': 5, 'min_rows': 10.0, 'nbins': 64, 'histogram_type': 'QuantilesGlobal', 'monotone_constraints': None, 'learn_rate': 0.1, 'sample_rate': 1.0, 'col_sample_rate': 1.0, 'col_sample_rate_per_tree': 1.0, 'min_split_improvement': 1e-05, 'reg_lambda': 0.0, 'reg_alpha': 0.0, 'gamma': 0.0, 'min_child_weight': 0.0, 'distribution': 'auto', 'tweedie_power': 1.5, 'quantile_alpha': 0.5, 'huber_alpha': 0.9, 'score_tree_interval': 5, 'hist_precision': 'bf16', 'hist_mode': 'auto', 'split_mode': 'auto', 'hist_layout': 'auto', 'sparse_depth_threshold': 8, 'tree_program': 'auto', 'calibrate_model': False, 'calibration_frame': None, 'calibration_method': 'platt', 'reproducible': False, 'efb': 'auto', 'custom_distribution_func': None}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, ntrees=None, max_depth=None, min_rows=None, nbins=None, histogram_type=None, monotone_constraints=None, learn_rate=None, sample_rate=None, col_sample_rate=None, col_sample_rate_per_tree=None, min_split_improvement=None, reg_lambda=None, reg_alpha=None, gamma=None, min_child_weight=None, distribution=None, tweedie_power=None, quantile_alpha=None, huber_alpha=None, score_tree_interval=None, hist_precision=None, hist_mode=None, split_mode=None, hist_layout=None, sparse_depth_threshold=None, tree_program=None, calibrate_model=None, calibration_frame=None, calibration_method=None, reproducible=None, efb=None, custom_distribution_func=None):
        self._init_from(locals())

class H2ORandomForestEstimator(_RemoteEstimator):
    """drf estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default False
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    ntrees : int, default 50
    max_depth : int, default 20
    min_rows : float, default 1.0
    nbins : int, default 64
    histogram_type : str, default 'QuantilesGlobal'
    monotone_constraints : Optional[dict], default None
    learn_rate : float, default 1.0
    sample_rate : float, default 0.632
    col_sample_rate : float, default 1.0
    col_sample_rate_per_tree : float, default 1.0
    min_split_improvement : float, default 1e-05
    reg_lambda : float, default 0.0
    reg_alpha : float, default 0.0
    gamma : float, default 0.0
    min_child_weight : float, default 0.0
    distribution : str, default 'auto'
    tweedie_power : float, default 1.5
    quantile_alpha : float, default 0.5
    huber_alpha : float, default 0.9
    score_tree_interval : int, default 5
    hist_precision : str, default 'bf16'
    hist_mode : str, default 'auto'
    split_mode : str, default 'auto'
    hist_layout : str, default 'auto'
    sparse_depth_threshold : int, default 8
    tree_program : str, default 'auto'
    calibrate_model : bool, default False
    calibration_frame : Optional[object], default None
    calibration_method : str, default 'platt'
    reproducible : bool, default False
    efb : str, default 'auto'
    mtries : int, default -1
    """

    algo = 'drf'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'ntrees', 'max_depth', 'min_rows', 'nbins', 'histogram_type', 'monotone_constraints', 'learn_rate', 'sample_rate', 'col_sample_rate', 'col_sample_rate_per_tree', 'min_split_improvement', 'reg_lambda', 'reg_alpha', 'gamma', 'min_child_weight', 'distribution', 'tweedie_power', 'quantile_alpha', 'huber_alpha', 'score_tree_interval', 'hist_precision', 'hist_mode', 'split_mode', 'hist_layout', 'sparse_depth_threshold', 'tree_program', 'calibrate_model', 'calibration_frame', 'calibration_method', 'reproducible', 'efb', 'mtries')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': False, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'ntrees': 50, 'max_depth': 20, 'min_rows': 1.0, 'nbins': 64, 'histogram_type': 'QuantilesGlobal', 'monotone_constraints': None, 'learn_rate': 1.0, 'sample_rate': 0.632, 'col_sample_rate': 1.0, 'col_sample_rate_per_tree': 1.0, 'min_split_improvement': 1e-05, 'reg_lambda': 0.0, 'reg_alpha': 0.0, 'gamma': 0.0, 'min_child_weight': 0.0, 'distribution': 'auto', 'tweedie_power': 1.5, 'quantile_alpha': 0.5, 'huber_alpha': 0.9, 'score_tree_interval': 5, 'hist_precision': 'bf16', 'hist_mode': 'auto', 'split_mode': 'auto', 'hist_layout': 'auto', 'sparse_depth_threshold': 8, 'tree_program': 'auto', 'calibrate_model': False, 'calibration_frame': None, 'calibration_method': 'platt', 'reproducible': False, 'efb': 'auto', 'mtries': -1}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, ntrees=None, max_depth=None, min_rows=None, nbins=None, histogram_type=None, monotone_constraints=None, learn_rate=None, sample_rate=None, col_sample_rate=None, col_sample_rate_per_tree=None, min_split_improvement=None, reg_lambda=None, reg_alpha=None, gamma=None, min_child_weight=None, distribution=None, tweedie_power=None, quantile_alpha=None, huber_alpha=None, score_tree_interval=None, hist_precision=None, hist_mode=None, split_mode=None, hist_layout=None, sparse_depth_threshold=None, tree_program=None, calibrate_model=None, calibration_frame=None, calibration_method=None, reproducible=None, efb=None, mtries=None):
        self._init_from(locals())

class H2OXGBoostEstimator(_RemoteEstimator):
    """xgboost estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default False
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    ntrees : int, default 50
    max_depth : int, default 6
    min_rows : float, default 1.0
    nbins : int, default 256
    histogram_type : str, default 'QuantilesGlobal'
    monotone_constraints : Optional[dict], default None
    learn_rate : float, default 0.3
    sample_rate : float, default 1.0
    col_sample_rate : float, default 1.0
    col_sample_rate_per_tree : float, default 1.0
    min_split_improvement : float, default 1e-05
    reg_lambda : float, default 1.0
    reg_alpha : float, default 0.0
    gamma : float, default 0.0
    min_child_weight : float, default 1.0
    distribution : str, default 'auto'
    tweedie_power : float, default 1.5
    quantile_alpha : float, default 0.5
    huber_alpha : float, default 0.9
    score_tree_interval : int, default 5
    hist_precision : str, default 'bf16'
    hist_mode : str, default 'auto'
    split_mode : str, default 'auto'
    hist_layout : str, default 'auto'
    sparse_depth_threshold : int, default 8
    tree_program : str, default 'auto'
    calibrate_model : bool, default False
    calibration_frame : Optional[object], default None
    calibration_method : str, default 'platt'
    reproducible : bool, default False
    efb : str, default 'auto'
    booster : str, default 'gbtree'
    scale_pos_weight : float, default 1.0
    rate_drop : float, default 0.0
    skip_drop : float, default 0.0
    one_drop : bool, default False
    normalize_type : str, default 'tree'
    sample_type : str, default 'uniform'
    """

    algo = 'xgboost'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'ntrees', 'max_depth', 'min_rows', 'nbins', 'histogram_type', 'monotone_constraints', 'learn_rate', 'sample_rate', 'col_sample_rate', 'col_sample_rate_per_tree', 'min_split_improvement', 'reg_lambda', 'reg_alpha', 'gamma', 'min_child_weight', 'distribution', 'tweedie_power', 'quantile_alpha', 'huber_alpha', 'score_tree_interval', 'hist_precision', 'hist_mode', 'split_mode', 'hist_layout', 'sparse_depth_threshold', 'tree_program', 'calibrate_model', 'calibration_frame', 'calibration_method', 'reproducible', 'efb', 'booster', 'scale_pos_weight', 'rate_drop', 'skip_drop', 'one_drop', 'normalize_type', 'sample_type')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': False, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'ntrees': 50, 'max_depth': 6, 'min_rows': 1.0, 'nbins': 256, 'histogram_type': 'QuantilesGlobal', 'monotone_constraints': None, 'learn_rate': 0.3, 'sample_rate': 1.0, 'col_sample_rate': 1.0, 'col_sample_rate_per_tree': 1.0, 'min_split_improvement': 1e-05, 'reg_lambda': 1.0, 'reg_alpha': 0.0, 'gamma': 0.0, 'min_child_weight': 1.0, 'distribution': 'auto', 'tweedie_power': 1.5, 'quantile_alpha': 0.5, 'huber_alpha': 0.9, 'score_tree_interval': 5, 'hist_precision': 'bf16', 'hist_mode': 'auto', 'split_mode': 'auto', 'hist_layout': 'auto', 'sparse_depth_threshold': 8, 'tree_program': 'auto', 'calibrate_model': False, 'calibration_frame': None, 'calibration_method': 'platt', 'reproducible': False, 'efb': 'auto', 'booster': 'gbtree', 'scale_pos_weight': 1.0, 'rate_drop': 0.0, 'skip_drop': 0.0, 'one_drop': False, 'normalize_type': 'tree', 'sample_type': 'uniform'}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, ntrees=None, max_depth=None, min_rows=None, nbins=None, histogram_type=None, monotone_constraints=None, learn_rate=None, sample_rate=None, col_sample_rate=None, col_sample_rate_per_tree=None, min_split_improvement=None, reg_lambda=None, reg_alpha=None, gamma=None, min_child_weight=None, distribution=None, tweedie_power=None, quantile_alpha=None, huber_alpha=None, score_tree_interval=None, hist_precision=None, hist_mode=None, split_mode=None, hist_layout=None, sparse_depth_threshold=None, tree_program=None, calibrate_model=None, calibration_frame=None, calibration_method=None, reproducible=None, efb=None, booster=None, scale_pos_weight=None, rate_drop=None, skip_drop=None, one_drop=None, normalize_type=None, sample_type=None):
        self._init_from(locals())

class H2ODeepLearningEstimator(_RemoteEstimator):
    """deeplearning estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 1000000000
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 5
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.0
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    hidden : Sequence[int], default [200, 200]
    activation : str, default 'rectifier'
    epochs : float, default 10.0
    mini_batch_size : int, default 128
    adaptive_rate : bool, default True
    rho : float, default 0.99
    epsilon : float, default 1e-08
    rate : float, default 0.005
    momentum_start : float, default 0.0
    momentum_stable : float, default 0.0
    input_dropout_ratio : float, default 0.0
    hidden_dropout_ratios : Optional[Sequence[float]], default None
    l1 : float, default 0.0
    l2 : float, default 0.0
    custom_loss_func : Optional[object], default None
    loss : str, default 'automatic'
    distribution : str, default 'auto'
    train_samples_per_iteration : int, default -2
    score_interval : float, default 5.0
    initial_weight_distribution : str, default 'uniform_adaptive'
    initial_weight_scale : float, default 1.0
    autoencoder : bool, default False
    precision : str, default 'bf16'
    shuffle_training_data : bool, default True
    """

    algo = 'deeplearning'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'hidden', 'activation', 'epochs', 'mini_batch_size', 'adaptive_rate', 'rho', 'epsilon', 'rate', 'momentum_start', 'momentum_stable', 'input_dropout_ratio', 'hidden_dropout_ratios', 'l1', 'l2', 'custom_loss_func', 'loss', 'distribution', 'train_samples_per_iteration', 'score_interval', 'initial_weight_distribution', 'initial_weight_scale', 'autoencoder', 'precision', 'shuffle_training_data')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 1000000000, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 5, 'stopping_metric': 'auto', 'stopping_tolerance': 0.0, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'hidden': [200, 200], 'activation': 'rectifier', 'epochs': 10.0, 'mini_batch_size': 128, 'adaptive_rate': True, 'rho': 0.99, 'epsilon': 1e-08, 'rate': 0.005, 'momentum_start': 0.0, 'momentum_stable': 0.0, 'input_dropout_ratio': 0.0, 'hidden_dropout_ratios': None, 'l1': 0.0, 'l2': 0.0, 'custom_loss_func': None, 'loss': 'automatic', 'distribution': 'auto', 'train_samples_per_iteration': -2, 'score_interval': 5.0, 'initial_weight_distribution': 'uniform_adaptive', 'initial_weight_scale': 1.0, 'autoencoder': False, 'precision': 'bf16', 'shuffle_training_data': True}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, hidden=None, activation=None, epochs=None, mini_batch_size=None, adaptive_rate=None, rho=None, epsilon=None, rate=None, momentum_start=None, momentum_stable=None, input_dropout_ratio=None, hidden_dropout_ratios=None, l1=None, l2=None, custom_loss_func=None, loss=None, distribution=None, train_samples_per_iteration=None, score_interval=None, initial_weight_distribution=None, initial_weight_scale=None, autoencoder=None, precision=None, shuffle_training_data=None):
        self._init_from(locals())

class H2OKMeansEstimator(_RemoteEstimator):
    """kmeans estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 10
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    k : int, default 1
    estimate_k : bool, default False
    init : str, default 'furthest'
    user_points : Optional[np.ndarray], default None
    """

    algo = 'kmeans'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'k', 'estimate_k', 'init', 'user_points')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 10, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'k': 1, 'estimate_k': False, 'init': 'furthest', 'user_points': None}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, k=None, estimate_k=None, init=None, user_points=None):
        self._init_from(locals())

class H2OPCAEstimator(_RemoteEstimator):
    """pca estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 1000
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    k : int, default 1
    transform : str, default 'none'
    pca_method : str, default 'gram_s_v_d'
    use_all_factor_levels : bool, default False
    compute_metrics : bool, default True
    """

    algo = 'pca'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'k', 'transform', 'pca_method', 'use_all_factor_levels', 'compute_metrics')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 1000, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'k': 1, 'transform': 'none', 'pca_method': 'gram_s_v_d', 'use_all_factor_levels': False, 'compute_metrics': True}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, k=None, transform=None, pca_method=None, use_all_factor_levels=None, compute_metrics=None):
        self._init_from(locals())

class H2OSVDEstimator(_RemoteEstimator):
    """svd estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 1000
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    k : int, default 1
    transform : str, default 'none'
    pca_method : str, default 'gram_s_v_d'
    use_all_factor_levels : bool, default False
    compute_metrics : bool, default True
    nv : int, default 1
    svd_method : str, default 'gram_s_v_d'
    keep_u : bool, default True
    """

    algo = 'svd'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'k', 'transform', 'pca_method', 'use_all_factor_levels', 'compute_metrics', 'nv', 'svd_method', 'keep_u')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 1000, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'k': 1, 'transform': 'none', 'pca_method': 'gram_s_v_d', 'use_all_factor_levels': False, 'compute_metrics': True, 'nv': 1, 'svd_method': 'gram_s_v_d', 'keep_u': True}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, k=None, transform=None, pca_method=None, use_all_factor_levels=None, compute_metrics=None, nv=None, svd_method=None, keep_u=None):
        self._init_from(locals())

class H2ONaiveBayesEstimator(_RemoteEstimator):
    """naivebayes estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default False
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    laplace : float, default 0.0
    min_sdev : float, default 0.001
    eps_sdev : float, default 0.0
    min_prob : float, default 0.001
    eps_prob : float, default 0.0
    compute_metrics : bool, default True
    """

    algo = 'naivebayes'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'laplace', 'min_sdev', 'eps_sdev', 'min_prob', 'eps_prob', 'compute_metrics')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': False, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'laplace': 0.0, 'min_sdev': 0.001, 'eps_sdev': 0.0, 'min_prob': 0.001, 'eps_prob': 0.0, 'compute_metrics': True}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, laplace=None, min_sdev=None, eps_sdev=None, min_prob=None, eps_prob=None, compute_metrics=None):
        self._init_from(locals())

class H2OIsolationForestEstimator(_RemoteEstimator):
    """isolationforest estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default False
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    ntrees : int, default 50
    max_depth : int, default 8
    min_rows : float, default 10.0
    nbins : int, default 64
    histogram_type : str, default 'QuantilesGlobal'
    monotone_constraints : Optional[dict], default None
    learn_rate : float, default 0.1
    sample_rate : float, default 1.0
    col_sample_rate : float, default 1.0
    col_sample_rate_per_tree : float, default 1.0
    min_split_improvement : float, default 1e-05
    reg_lambda : float, default 0.0
    reg_alpha : float, default 0.0
    gamma : float, default 0.0
    min_child_weight : float, default 0.0
    distribution : str, default 'auto'
    tweedie_power : float, default 1.5
    quantile_alpha : float, default 0.5
    huber_alpha : float, default 0.9
    score_tree_interval : int, default 5
    hist_precision : str, default 'bf16'
    hist_mode : str, default 'auto'
    split_mode : str, default 'auto'
    hist_layout : str, default 'auto'
    sparse_depth_threshold : int, default 8
    tree_program : str, default 'auto'
    calibrate_model : bool, default False
    calibration_frame : Optional[object], default None
    calibration_method : str, default 'platt'
    reproducible : bool, default False
    efb : str, default 'auto'
    sample_size : int, default 256
    contamination : float, default -1.0
    """

    algo = 'isolationforest'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'ntrees', 'max_depth', 'min_rows', 'nbins', 'histogram_type', 'monotone_constraints', 'learn_rate', 'sample_rate', 'col_sample_rate', 'col_sample_rate_per_tree', 'min_split_improvement', 'reg_lambda', 'reg_alpha', 'gamma', 'min_child_weight', 'distribution', 'tweedie_power', 'quantile_alpha', 'huber_alpha', 'score_tree_interval', 'hist_precision', 'hist_mode', 'split_mode', 'hist_layout', 'sparse_depth_threshold', 'tree_program', 'calibrate_model', 'calibration_frame', 'calibration_method', 'reproducible', 'efb', 'sample_size', 'contamination')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': False, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'ntrees': 50, 'max_depth': 8, 'min_rows': 10.0, 'nbins': 64, 'histogram_type': 'QuantilesGlobal', 'monotone_constraints': None, 'learn_rate': 0.1, 'sample_rate': 1.0, 'col_sample_rate': 1.0, 'col_sample_rate_per_tree': 1.0, 'min_split_improvement': 1e-05, 'reg_lambda': 0.0, 'reg_alpha': 0.0, 'gamma': 0.0, 'min_child_weight': 0.0, 'distribution': 'auto', 'tweedie_power': 1.5, 'quantile_alpha': 0.5, 'huber_alpha': 0.9, 'score_tree_interval': 5, 'hist_precision': 'bf16', 'hist_mode': 'auto', 'split_mode': 'auto', 'hist_layout': 'auto', 'sparse_depth_threshold': 8, 'tree_program': 'auto', 'calibrate_model': False, 'calibration_frame': None, 'calibration_method': 'platt', 'reproducible': False, 'efb': 'auto', 'sample_size': 256, 'contamination': -1.0}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, ntrees=None, max_depth=None, min_rows=None, nbins=None, histogram_type=None, monotone_constraints=None, learn_rate=None, sample_rate=None, col_sample_rate=None, col_sample_rate_per_tree=None, min_split_improvement=None, reg_lambda=None, reg_alpha=None, gamma=None, min_child_weight=None, distribution=None, tweedie_power=None, quantile_alpha=None, huber_alpha=None, score_tree_interval=None, hist_precision=None, hist_mode=None, split_mode=None, hist_layout=None, sparse_depth_threshold=None, tree_program=None, calibrate_model=None, calibration_frame=None, calibration_method=None, reproducible=None, efb=None, sample_size=None, contamination=None):
        self._init_from(locals())

class H2OExtendedIsolationForestEstimator(_RemoteEstimator):
    """extendedisolationforest estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default False
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    ntrees : int, default 50
    max_depth : int, default 8
    min_rows : float, default 10.0
    nbins : int, default 64
    histogram_type : str, default 'QuantilesGlobal'
    monotone_constraints : Optional[dict], default None
    learn_rate : float, default 0.1
    sample_rate : float, default 1.0
    col_sample_rate : float, default 1.0
    col_sample_rate_per_tree : float, default 1.0
    min_split_improvement : float, default 1e-05
    reg_lambda : float, default 0.0
    reg_alpha : float, default 0.0
    gamma : float, default 0.0
    min_child_weight : float, default 0.0
    distribution : str, default 'auto'
    tweedie_power : float, default 1.5
    quantile_alpha : float, default 0.5
    huber_alpha : float, default 0.9
    score_tree_interval : int, default 5
    hist_precision : str, default 'bf16'
    hist_mode : str, default 'auto'
    split_mode : str, default 'auto'
    hist_layout : str, default 'auto'
    sparse_depth_threshold : int, default 8
    tree_program : str, default 'auto'
    calibrate_model : bool, default False
    calibration_frame : Optional[object], default None
    calibration_method : str, default 'platt'
    reproducible : bool, default False
    efb : str, default 'auto'
    sample_size : int, default 256
    contamination : float, default -1.0
    extension_level : int, default 0
    """

    algo = 'extendedisolationforest'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'ntrees', 'max_depth', 'min_rows', 'nbins', 'histogram_type', 'monotone_constraints', 'learn_rate', 'sample_rate', 'col_sample_rate', 'col_sample_rate_per_tree', 'min_split_improvement', 'reg_lambda', 'reg_alpha', 'gamma', 'min_child_weight', 'distribution', 'tweedie_power', 'quantile_alpha', 'huber_alpha', 'score_tree_interval', 'hist_precision', 'hist_mode', 'split_mode', 'hist_layout', 'sparse_depth_threshold', 'tree_program', 'calibrate_model', 'calibration_frame', 'calibration_method', 'reproducible', 'efb', 'sample_size', 'contamination', 'extension_level')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': False, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'ntrees': 50, 'max_depth': 8, 'min_rows': 10.0, 'nbins': 64, 'histogram_type': 'QuantilesGlobal', 'monotone_constraints': None, 'learn_rate': 0.1, 'sample_rate': 1.0, 'col_sample_rate': 1.0, 'col_sample_rate_per_tree': 1.0, 'min_split_improvement': 1e-05, 'reg_lambda': 0.0, 'reg_alpha': 0.0, 'gamma': 0.0, 'min_child_weight': 0.0, 'distribution': 'auto', 'tweedie_power': 1.5, 'quantile_alpha': 0.5, 'huber_alpha': 0.9, 'score_tree_interval': 5, 'hist_precision': 'bf16', 'hist_mode': 'auto', 'split_mode': 'auto', 'hist_layout': 'auto', 'sparse_depth_threshold': 8, 'tree_program': 'auto', 'calibrate_model': False, 'calibration_frame': None, 'calibration_method': 'platt', 'reproducible': False, 'efb': 'auto', 'sample_size': 256, 'contamination': -1.0, 'extension_level': 0}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, ntrees=None, max_depth=None, min_rows=None, nbins=None, histogram_type=None, monotone_constraints=None, learn_rate=None, sample_rate=None, col_sample_rate=None, col_sample_rate_per_tree=None, min_split_improvement=None, reg_lambda=None, reg_alpha=None, gamma=None, min_child_weight=None, distribution=None, tweedie_power=None, quantile_alpha=None, huber_alpha=None, score_tree_interval=None, hist_precision=None, hist_mode=None, split_mode=None, hist_layout=None, sparse_depth_threshold=None, tree_program=None, calibrate_model=None, calibration_frame=None, calibration_method=None, reproducible=None, efb=None, sample_size=None, contamination=None, extension_level=None):
        self._init_from(locals())

class H2OIsotonicRegressionEstimator(_RemoteEstimator):
    """isotonicregression estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    out_of_bounds : str, default 'na'
    """

    algo = 'isotonicregression'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'out_of_bounds')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'out_of_bounds': 'na'}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, out_of_bounds=None):
        self._init_from(locals())

class H2OQuantileEstimator(_RemoteEstimator):
    """quantile estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    probs : Sequence[float], default [0.001, 0.01, 0.1, 0.25, 0.333, 0.5, 0.667, 0.75, 0.9, 0.99, 0.999]
    combine_method : str, default 'interpolate'
    """

    algo = 'quantile'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'probs', 'combine_method')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'probs': [0.001, 0.01, 0.1, 0.25, 0.333, 0.5, 0.667, 0.75, 0.9, 0.99, 0.999], 'combine_method': 'interpolate'}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, probs=None, combine_method=None):
        self._init_from(locals())

class H2OStackedEnsembleEstimator(_RemoteEstimator):
    """stackedensemble estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    base_models : Sequence[Union[str, Model]], default []
    metalearner_algorithm : str, default 'auto'
    metalearner_params : Optional[dict], default None
    metalearner_nfolds : int, default 0
    blending_frame : Optional[Frame], default None
    """

    algo = 'stackedensemble'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'base_models', 'metalearner_algorithm', 'metalearner_params', 'metalearner_nfolds', 'blending_frame')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'base_models': [], 'metalearner_algorithm': 'auto', 'metalearner_params': None, 'metalearner_nfolds': 0, 'blending_frame': None}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, base_models=None, metalearner_algorithm=None, metalearner_params=None, metalearner_nfolds=None, blending_frame=None):
        self._init_from(locals())

class H2OAdaBoostEstimator(_RemoteEstimator):
    """adaboost estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default False
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    ntrees : int, default 50
    max_depth : int, default 3
    min_rows : float, default 5.0
    nbins : int, default 64
    histogram_type : str, default 'QuantilesGlobal'
    monotone_constraints : Optional[dict], default None
    learn_rate : float, default 0.5
    sample_rate : float, default 1.0
    col_sample_rate : float, default 1.0
    col_sample_rate_per_tree : float, default 1.0
    min_split_improvement : float, default 1e-05
    reg_lambda : float, default 0.0
    reg_alpha : float, default 0.0
    gamma : float, default 0.0
    min_child_weight : float, default 0.0
    distribution : str, default 'auto'
    tweedie_power : float, default 1.5
    quantile_alpha : float, default 0.5
    huber_alpha : float, default 0.9
    score_tree_interval : int, default 5
    hist_precision : str, default 'bf16'
    hist_mode : str, default 'auto'
    split_mode : str, default 'auto'
    hist_layout : str, default 'auto'
    sparse_depth_threshold : int, default 8
    tree_program : str, default 'auto'
    calibrate_model : bool, default False
    calibration_frame : Optional[object], default None
    calibration_method : str, default 'platt'
    reproducible : bool, default False
    efb : str, default 'auto'
    nlearners : int, default 50
    """

    algo = 'adaboost'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'ntrees', 'max_depth', 'min_rows', 'nbins', 'histogram_type', 'monotone_constraints', 'learn_rate', 'sample_rate', 'col_sample_rate', 'col_sample_rate_per_tree', 'min_split_improvement', 'reg_lambda', 'reg_alpha', 'gamma', 'min_child_weight', 'distribution', 'tweedie_power', 'quantile_alpha', 'huber_alpha', 'score_tree_interval', 'hist_precision', 'hist_mode', 'split_mode', 'hist_layout', 'sparse_depth_threshold', 'tree_program', 'calibrate_model', 'calibration_frame', 'calibration_method', 'reproducible', 'efb', 'nlearners')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': False, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'ntrees': 50, 'max_depth': 3, 'min_rows': 5.0, 'nbins': 64, 'histogram_type': 'QuantilesGlobal', 'monotone_constraints': None, 'learn_rate': 0.5, 'sample_rate': 1.0, 'col_sample_rate': 1.0, 'col_sample_rate_per_tree': 1.0, 'min_split_improvement': 1e-05, 'reg_lambda': 0.0, 'reg_alpha': 0.0, 'gamma': 0.0, 'min_child_weight': 0.0, 'distribution': 'auto', 'tweedie_power': 1.5, 'quantile_alpha': 0.5, 'huber_alpha': 0.9, 'score_tree_interval': 5, 'hist_precision': 'bf16', 'hist_mode': 'auto', 'split_mode': 'auto', 'hist_layout': 'auto', 'sparse_depth_threshold': 8, 'tree_program': 'auto', 'calibrate_model': False, 'calibration_frame': None, 'calibration_method': 'platt', 'reproducible': False, 'efb': 'auto', 'nlearners': 50}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, ntrees=None, max_depth=None, min_rows=None, nbins=None, histogram_type=None, monotone_constraints=None, learn_rate=None, sample_rate=None, col_sample_rate=None, col_sample_rate_per_tree=None, min_split_improvement=None, reg_lambda=None, reg_alpha=None, gamma=None, min_child_weight=None, distribution=None, tweedie_power=None, quantile_alpha=None, huber_alpha=None, score_tree_interval=None, hist_precision=None, hist_mode=None, split_mode=None, hist_layout=None, sparse_depth_threshold=None, tree_program=None, calibrate_model=None, calibration_frame=None, calibration_method=None, reproducible=None, efb=None, nlearners=None):
        self._init_from(locals())

class H2OTargetEncoderEstimator(_RemoteEstimator):
    """targetencoder estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    columns : Optional[List[str]], default None
    data_leakage_handling : str, default 'none'
    blending : bool, default True
    inflection_point : float, default 10.0
    smoothing : float, default 20.0
    noise : float, default 0.0
    """

    algo = 'targetencoder'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'columns', 'data_leakage_handling', 'blending', 'inflection_point', 'smoothing', 'noise')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'columns': None, 'data_leakage_handling': 'none', 'blending': True, 'inflection_point': 10.0, 'smoothing': 20.0, 'noise': 0.0}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, columns=None, data_leakage_handling=None, blending=None, inflection_point=None, smoothing=None, noise=None):
        self._init_from(locals())

class H2OGLRMEstimator(_RemoteEstimator):
    """glrm estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 100
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    k : int, default 1
    gamma_x : float, default 0.0
    gamma_y : float, default 0.0
    transform : str, default 'none'
    init : str, default 'svd'
    recover_svd : bool, default False
    loss : str, default 'quadratic'
    multi_loss : str, default 'categorical'
    loss_by_col : Optional[dict], default None
    regularization_x : str, default 'none'
    regularization_y : str, default 'none'
    """

    algo = 'glrm'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'k', 'gamma_x', 'gamma_y', 'transform', 'init', 'recover_svd', 'loss', 'multi_loss', 'loss_by_col', 'regularization_x', 'regularization_y')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 100, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'k': 1, 'gamma_x': 0.0, 'gamma_y': 0.0, 'transform': 'none', 'init': 'svd', 'recover_svd': False, 'loss': 'quadratic', 'multi_loss': 'categorical', 'loss_by_col': None, 'regularization_x': 'none', 'regularization_y': 'none'}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, k=None, gamma_x=None, gamma_y=None, transform=None, init=None, recover_svd=None, loss=None, multi_loss=None, loss_by_col=None, regularization_x=None, regularization_y=None):
        self._init_from(locals())

class H2OCoxPHEstimator(_RemoteEstimator):
    """coxph estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 20
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    start_column : Optional[str], default None
    stop_column : str, default ''
    event_column : str, default ''
    stratify_by : Optional[str], default None
    ties : str, default 'efron'
    interactions : Optional[Sequence[str]], default None
    interaction_pairs : Optional[Sequence], default None
    """

    algo = 'coxph'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'start_column', 'stop_column', 'event_column', 'stratify_by', 'ties', 'interactions', 'interaction_pairs')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 20, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'start_column': None, 'stop_column': '', 'event_column': '', 'stratify_by': None, 'ties': 'efron', 'interactions': None, 'interaction_pairs': None}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, start_column=None, stop_column=None, event_column=None, stratify_by=None, ties=None, interactions=None, interaction_pairs=None):
        self._init_from(locals())

class H2OWord2VecEstimator(_RemoteEstimator):
    """word2vec estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    vec_size : int, default 100
    window_size : int, default 5
    min_word_freq : int, default 5
    epochs : int, default 5
    learn_rate : float, default 0.025
    negative_samples : int, default 5
    sent_sample_rate : float, default 0.001
    batch_size : int, default 8192
    """

    algo = 'word2vec'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'vec_size', 'window_size', 'min_word_freq', 'epochs', 'learn_rate', 'negative_samples', 'sent_sample_rate', 'batch_size')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'vec_size': 100, 'window_size': 5, 'min_word_freq': 5, 'epochs': 5, 'learn_rate': 0.025, 'negative_samples': 5, 'sent_sample_rate': 0.001, 'batch_size': 8192}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, vec_size=None, window_size=None, min_word_freq=None, epochs=None, learn_rate=None, negative_samples=None, sent_sample_rate=None, batch_size=None):
        self._init_from(locals())

class H2ORuleFitEstimator(_RemoteEstimator):
    """rulefit estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    algorithm : str, default 'gbm'
    min_rule_length : int, default 1
    max_rule_length : int, default 3
    max_num_rules : int, default -1
    model_type : str, default 'rules_and_linear'
    rule_generation_ntrees : int, default 30
    remove_duplicates : bool, default True
    lambda_ : Optional[float], default None
    """

    algo = 'rulefit'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'algorithm', 'min_rule_length', 'max_rule_length', 'max_num_rules', 'model_type', 'rule_generation_ntrees', 'remove_duplicates', 'lambda_')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'algorithm': 'gbm', 'min_rule_length': 1, 'max_rule_length': 3, 'max_num_rules': -1, 'model_type': 'rules_and_linear', 'rule_generation_ntrees': 30, 'remove_duplicates': True, 'lambda_': None}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, algorithm=None, min_rule_length=None, max_rule_length=None, max_num_rules=None, model_type=None, rule_generation_ntrees=None, remove_duplicates=None, lambda_=None):
        self._init_from(locals())

class H2OAggregatorEstimator(_RemoteEstimator):
    """aggregator estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    target_num_exemplars : int, default 100
    rel_tol_num_exemplars : float, default 0.5
    """

    algo = 'aggregator'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'target_num_exemplars', 'rel_tol_num_exemplars')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'target_num_exemplars': 100, 'rel_tol_num_exemplars': 0.5}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, target_num_exemplars=None, rel_tol_num_exemplars=None):
        self._init_from(locals())

class H2OGAMEstimator(_RemoteEstimator):
    """gam estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    family : str, default 'auto'
    alpha : float, default 0.5
    lambda_ : Union[float, Sequence[float], None], default None
    lambda_search : bool, default False
    nlambdas : int, default 30
    lambda_min_ratio : float, default 0.0001
    solver : str, default 'irlsm'
    non_negative : Union[bool, Sequence[str]], default False
    penalty_factors : Optional[dict], default None
    tweedie_variance_power : float, default 1.5
    theta : float, default 1.0
    beta_epsilon : float, default 1e-05
    compute_p_values : bool, default False
    intercept : bool, default True
    gam_columns : Sequence, default []
    num_knots : object, default 8
    scale : object, default 1.0
    bs : object, default 'cr'
    splines_non_negative : bool, default True
    """

    algo = 'gam'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'family', 'alpha', 'lambda_', 'lambda_search', 'nlambdas', 'lambda_min_ratio', 'solver', 'non_negative', 'penalty_factors', 'tweedie_variance_power', 'theta', 'beta_epsilon', 'compute_p_values', 'intercept', 'gam_columns', 'num_knots', 'scale', 'bs', 'splines_non_negative')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'family': 'auto', 'alpha': 0.5, 'lambda_': None, 'lambda_search': False, 'nlambdas': 30, 'lambda_min_ratio': 0.0001, 'solver': 'irlsm', 'non_negative': False, 'penalty_factors': None, 'tweedie_variance_power': 1.5, 'theta': 1.0, 'beta_epsilon': 1e-05, 'compute_p_values': False, 'intercept': True, 'gam_columns': [], 'num_knots': 8, 'scale': 1.0, 'bs': 'cr', 'splines_non_negative': True}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, family=None, alpha=None, lambda_=None, lambda_search=None, nlambdas=None, lambda_min_ratio=None, solver=None, non_negative=None, penalty_factors=None, tweedie_variance_power=None, theta=None, beta_epsilon=None, compute_p_values=None, intercept=None, gam_columns=None, num_knots=None, scale=None, bs=None, splines_non_negative=None):
        self._init_from(locals())

class H2OUpliftRandomForestEstimator(_RemoteEstimator):
    """upliftdrf estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default False
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    ntrees : int, default 50
    max_depth : int, default 10
    min_rows : float, default 10.0
    nbins : int, default 64
    histogram_type : str, default 'QuantilesGlobal'
    monotone_constraints : Optional[dict], default None
    learn_rate : float, default 0.1
    sample_rate : float, default 0.632
    col_sample_rate : float, default 1.0
    col_sample_rate_per_tree : float, default 1.0
    min_split_improvement : float, default 1e-05
    reg_lambda : float, default 0.0
    reg_alpha : float, default 0.0
    gamma : float, default 0.0
    min_child_weight : float, default 0.0
    distribution : str, default 'auto'
    tweedie_power : float, default 1.5
    quantile_alpha : float, default 0.5
    huber_alpha : float, default 0.9
    score_tree_interval : int, default 5
    hist_precision : str, default 'bf16'
    hist_mode : str, default 'auto'
    split_mode : str, default 'auto'
    hist_layout : str, default 'auto'
    sparse_depth_threshold : int, default 8
    tree_program : str, default 'auto'
    calibrate_model : bool, default False
    calibration_frame : Optional[object], default None
    calibration_method : str, default 'platt'
    reproducible : bool, default False
    efb : str, default 'auto'
    treatment_column : str, default ''
    uplift_metric : str, default 'KL'
    mtries : int, default -2
    """

    algo = 'upliftdrf'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'ntrees', 'max_depth', 'min_rows', 'nbins', 'histogram_type', 'monotone_constraints', 'learn_rate', 'sample_rate', 'col_sample_rate', 'col_sample_rate_per_tree', 'min_split_improvement', 'reg_lambda', 'reg_alpha', 'gamma', 'min_child_weight', 'distribution', 'tweedie_power', 'quantile_alpha', 'huber_alpha', 'score_tree_interval', 'hist_precision', 'hist_mode', 'split_mode', 'hist_layout', 'sparse_depth_threshold', 'tree_program', 'calibrate_model', 'calibration_frame', 'calibration_method', 'reproducible', 'efb', 'treatment_column', 'uplift_metric', 'mtries')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': False, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'ntrees': 50, 'max_depth': 10, 'min_rows': 10.0, 'nbins': 64, 'histogram_type': 'QuantilesGlobal', 'monotone_constraints': None, 'learn_rate': 0.1, 'sample_rate': 0.632, 'col_sample_rate': 1.0, 'col_sample_rate_per_tree': 1.0, 'min_split_improvement': 1e-05, 'reg_lambda': 0.0, 'reg_alpha': 0.0, 'gamma': 0.0, 'min_child_weight': 0.0, 'distribution': 'auto', 'tweedie_power': 1.5, 'quantile_alpha': 0.5, 'huber_alpha': 0.9, 'score_tree_interval': 5, 'hist_precision': 'bf16', 'hist_mode': 'auto', 'split_mode': 'auto', 'hist_layout': 'auto', 'sparse_depth_threshold': 8, 'tree_program': 'auto', 'calibrate_model': False, 'calibration_frame': None, 'calibration_method': 'platt', 'reproducible': False, 'efb': 'auto', 'treatment_column': '', 'uplift_metric': 'KL', 'mtries': -2}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, ntrees=None, max_depth=None, min_rows=None, nbins=None, histogram_type=None, monotone_constraints=None, learn_rate=None, sample_rate=None, col_sample_rate=None, col_sample_rate_per_tree=None, min_split_improvement=None, reg_lambda=None, reg_alpha=None, gamma=None, min_child_weight=None, distribution=None, tweedie_power=None, quantile_alpha=None, huber_alpha=None, score_tree_interval=None, hist_precision=None, hist_mode=None, split_mode=None, hist_layout=None, sparse_depth_threshold=None, tree_program=None, calibrate_model=None, calibration_frame=None, calibration_method=None, reproducible=None, efb=None, treatment_column=None, uplift_metric=None, mtries=None):
        self._init_from(locals())

class H2ODecisionTreeEstimator(_RemoteEstimator):
    """dt estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default False
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    ntrees : int, default 1
    max_depth : int, default 20
    min_rows : float, default 10.0
    nbins : int, default 64
    histogram_type : str, default 'QuantilesGlobal'
    monotone_constraints : Optional[dict], default None
    learn_rate : float, default 1.0
    sample_rate : float, default 1.0
    col_sample_rate : float, default 1.0
    col_sample_rate_per_tree : float, default 1.0
    min_split_improvement : float, default 1e-05
    reg_lambda : float, default 0.0
    reg_alpha : float, default 0.0
    gamma : float, default 0.0
    min_child_weight : float, default 0.0
    distribution : str, default 'auto'
    tweedie_power : float, default 1.5
    quantile_alpha : float, default 0.5
    huber_alpha : float, default 0.9
    score_tree_interval : int, default 5
    hist_precision : str, default 'bf16'
    hist_mode : str, default 'auto'
    split_mode : str, default 'auto'
    hist_layout : str, default 'auto'
    sparse_depth_threshold : int, default 8
    tree_program : str, default 'auto'
    calibrate_model : bool, default False
    calibration_frame : Optional[object], default None
    calibration_method : str, default 'platt'
    reproducible : bool, default False
    efb : str, default 'auto'
    mtries : int, default -2
    """

    algo = 'dt'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'ntrees', 'max_depth', 'min_rows', 'nbins', 'histogram_type', 'monotone_constraints', 'learn_rate', 'sample_rate', 'col_sample_rate', 'col_sample_rate_per_tree', 'min_split_improvement', 'reg_lambda', 'reg_alpha', 'gamma', 'min_child_weight', 'distribution', 'tweedie_power', 'quantile_alpha', 'huber_alpha', 'score_tree_interval', 'hist_precision', 'hist_mode', 'split_mode', 'hist_layout', 'sparse_depth_threshold', 'tree_program', 'calibrate_model', 'calibration_frame', 'calibration_method', 'reproducible', 'efb', 'mtries')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': False, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'ntrees': 1, 'max_depth': 20, 'min_rows': 10.0, 'nbins': 64, 'histogram_type': 'QuantilesGlobal', 'monotone_constraints': None, 'learn_rate': 1.0, 'sample_rate': 1.0, 'col_sample_rate': 1.0, 'col_sample_rate_per_tree': 1.0, 'min_split_improvement': 1e-05, 'reg_lambda': 0.0, 'reg_alpha': 0.0, 'gamma': 0.0, 'min_child_weight': 0.0, 'distribution': 'auto', 'tweedie_power': 1.5, 'quantile_alpha': 0.5, 'huber_alpha': 0.9, 'score_tree_interval': 5, 'hist_precision': 'bf16', 'hist_mode': 'auto', 'split_mode': 'auto', 'hist_layout': 'auto', 'sparse_depth_threshold': 8, 'tree_program': 'auto', 'calibrate_model': False, 'calibration_frame': None, 'calibration_method': 'platt', 'reproducible': False, 'efb': 'auto', 'mtries': -2}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, ntrees=None, max_depth=None, min_rows=None, nbins=None, histogram_type=None, monotone_constraints=None, learn_rate=None, sample_rate=None, col_sample_rate=None, col_sample_rate_per_tree=None, min_split_improvement=None, reg_lambda=None, reg_alpha=None, gamma=None, min_child_weight=None, distribution=None, tweedie_power=None, quantile_alpha=None, huber_alpha=None, score_tree_interval=None, hist_precision=None, hist_mode=None, split_mode=None, hist_layout=None, sparse_depth_threshold=None, tree_program=None, calibrate_model=None, calibration_frame=None, calibration_method=None, reproducible=None, efb=None, mtries=None):
        self._init_from(locals())

class H2OSupportVectorMachineEstimator(_RemoteEstimator):
    """psvm estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 200
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    hyper_param : float, default 1.0
    kernel_type : str, default 'gaussian'
    gamma : float, default -1.0
    rank_ratio : float, default -1.0
    positive_weight : float, default 1.0
    negative_weight : float, default 1.0
    sv_threshold : float, default 0.0001
    """

    algo = 'psvm'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'hyper_param', 'kernel_type', 'gamma', 'rank_ratio', 'positive_weight', 'negative_weight', 'sv_threshold')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 200, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'hyper_param': 1.0, 'kernel_type': 'gaussian', 'gamma': -1.0, 'rank_ratio': -1.0, 'positive_weight': 1.0, 'negative_weight': 1.0, 'sv_threshold': 0.0001}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, hyper_param=None, kernel_type=None, gamma=None, rank_ratio=None, positive_weight=None, negative_weight=None, sv_threshold=None):
        self._init_from(locals())

class H2OANOVAGLMEstimator(_RemoteEstimator):
    """anovaglm estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    family : str, default 'auto'
    alpha : float, default 0.0
    lambda_ : float, default 0.0
    """

    algo = 'anovaglm'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'family', 'alpha', 'lambda_')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'family': 'auto', 'alpha': 0.0, 'lambda_': 0.0}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, family=None, alpha=None, lambda_=None):
        self._init_from(locals())

class H2OModelSelectionEstimator(_RemoteEstimator):
    """modelselection estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    mode : str, default 'maxr'
    max_predictor_number : int, default 0
    min_predictor_number : int, default 1
    family : str, default 'auto'
    alpha : float, default 0.0
    lambda_ : float, default 0.0
    intercept : bool, default True
    build_glm_model : bool, default False
    """

    algo = 'modelselection'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'mode', 'max_predictor_number', 'min_predictor_number', 'family', 'alpha', 'lambda_', 'intercept', 'build_glm_model')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'mode': 'maxr', 'max_predictor_number': 0, 'min_predictor_number': 1, 'family': 'auto', 'alpha': 0.0, 'lambda_': 0.0, 'intercept': True, 'build_glm_model': False}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, mode=None, max_predictor_number=None, min_predictor_number=None, family=None, alpha=None, lambda_=None, intercept=None, build_glm_model=None):
        self._init_from(locals())

class H2OInfogramEstimator(_RemoteEstimator):
    """infogram estimator (remote, trains over REST).

    Parameters (None = server default)
    ---------------------------------
    response_column : Optional[str], default None
    ignored_columns : Sequence[str], default []
    weights_column : Optional[str], default None
    offset_column : Optional[str], default None
    seed : int, default -1
    max_iterations : int, default 50
    standardize : bool, default True
    missing_values_handling : str, default 'mean_imputation'
    stopping_rounds : int, default 0
    stopping_metric : str, default 'auto'
    stopping_tolerance : float, default 0.001
    checkpoint : Optional[str], default None
    export_checkpoints_dir : Optional[str], default None
    snapshot_interval : float, default -1.0
    balance_classes : bool, default False
    class_sampling_factors : Optional[Sequence[float]], default None
    nfolds : int, default 0
    fold_column : Optional[str], default None
    fold_assignment : str, default 'auto'
    keep_cross_validation_predictions : bool, default False
    custom_metric_func : Optional[Any], default None
    parallelism : int, default 0
    priority : Optional[int], default None
    device_budget : Optional[float], default None
    retry_budget : int, default 0
    stream : bool, default False
    warm_start : Optional[Any], default None
    algorithm : str, default 'gbm'
    infogram_algorithm_params : Optional[dict], default None
    protected_columns : Optional[Sequence[str]], default None
    total_information_threshold : float, default -1.0
    net_information_threshold : float, default -1.0
    relevance_index_threshold : float, default -1.0
    safety_index_threshold : float, default -1.0
    top_n_features : int, default 50
    data_fraction : float, default 1.0
    """

    algo = 'infogram'
    _param_names = ('response_column', 'ignored_columns', 'weights_column', 'offset_column', 'seed', 'max_iterations', 'standardize', 'missing_values_handling', 'stopping_rounds', 'stopping_metric', 'stopping_tolerance', 'checkpoint', 'export_checkpoints_dir', 'snapshot_interval', 'balance_classes', 'class_sampling_factors', 'nfolds', 'fold_column', 'fold_assignment', 'keep_cross_validation_predictions', 'custom_metric_func', 'parallelism', 'priority', 'device_budget', 'retry_budget', 'stream', 'warm_start', 'algorithm', 'infogram_algorithm_params', 'protected_columns', 'total_information_threshold', 'net_information_threshold', 'relevance_index_threshold', 'safety_index_threshold', 'top_n_features', 'data_fraction')
    _param_defaults = {'response_column': None, 'ignored_columns': [], 'weights_column': None, 'offset_column': None, 'seed': -1, 'max_iterations': 50, 'standardize': True, 'missing_values_handling': 'mean_imputation', 'stopping_rounds': 0, 'stopping_metric': 'auto', 'stopping_tolerance': 0.001, 'checkpoint': None, 'export_checkpoints_dir': None, 'snapshot_interval': -1.0, 'balance_classes': False, 'class_sampling_factors': None, 'nfolds': 0, 'fold_column': None, 'fold_assignment': 'auto', 'keep_cross_validation_predictions': False, 'custom_metric_func': None, 'parallelism': 0, 'priority': None, 'device_budget': None, 'retry_budget': 0, 'stream': False, 'warm_start': None, 'algorithm': 'gbm', 'infogram_algorithm_params': None, 'protected_columns': None, 'total_information_threshold': -1.0, 'net_information_threshold': -1.0, 'relevance_index_threshold': -1.0, 'safety_index_threshold': -1.0, 'top_n_features': 50, 'data_fraction': 1.0}

    def __init__(self, *, response_column=None, ignored_columns=None, weights_column=None, offset_column=None, seed=None, max_iterations=None, standardize=None, missing_values_handling=None, stopping_rounds=None, stopping_metric=None, stopping_tolerance=None, checkpoint=None, export_checkpoints_dir=None, snapshot_interval=None, balance_classes=None, class_sampling_factors=None, nfolds=None, fold_column=None, fold_assignment=None, keep_cross_validation_predictions=None, custom_metric_func=None, parallelism=None, priority=None, device_budget=None, retry_budget=None, stream=None, warm_start=None, algorithm=None, infogram_algorithm_params=None, protected_columns=None, total_information_threshold=None, net_information_threshold=None, relevance_index_threshold=None, safety_index_threshold=None, top_n_features=None, data_fraction=None):
        self._init_from(locals())

class H2OGridSearch:
    """Grid search over REST (h2o.grid / H2OGridSearch analog).

    Wraps an H2O*Estimator: its params become the base
    model config, ``hyper_params`` maps parameter name
    -> list of values to sweep.

    Grid-level parameters (None = server default)
    ---------------------------------------------
    search_criteria : object, default None
    parallelism : int, default 0
    grid_batch : str, default 'auto'
    """

    _grid_param_names = ('search_criteria', 'parallelism', 'grid_batch')
    _grid_param_defaults = {'search_criteria': None, 'parallelism': 0, 'grid_batch': 'auto'}

    def __init__(self, model, hyper_params, **params):
        self.model = model
        self.hyper_params = dict(hyper_params)
        self._grid_params = {k: v for k, v in params.items()
                             if k in self._grid_param_names}
        bad = set(params) - set(self._grid_params) - set(
            model._param_names)
        if bad:
            raise TypeError(
                f"unknown parameters for H2OGridSearch: {sorted(bad)}")
        self._base_params = {k: v for k, v in params.items()
                             if k not in self._grid_param_names}
        self.grid = None

    def train(self, training_frame, validation_frame=None,
              connection=None, sort_metric=None):
        conn = connection or (training_frame.conn
                              if hasattr(training_frame, "conn") else None)
        if conn is None:
            raise ValueError("pass connection= or a RemoteFrame")
        base = dict(self.model.get_params(), **self._base_params)
        gp = {k: v for k, v in self._grid_params.items()
              if v is not None}
        sc = gp.pop("search_criteria", None)
        self.grid = conn.grid(self.model.algo, self.hyper_params,
                              training_frame, validation_frame,
                              search_criteria=sc,
                              sort_metric=sort_metric, **gp, **base)
        return self.grid

    @property
    def failed_entries(self):
        return (self.grid.failed_entries
                if self.grid is not None else [])

__all__ = [
    'H2OGLMEstimator',
    'H2OGBMEstimator',
    'H2ORandomForestEstimator',
    'H2OXGBoostEstimator',
    'H2ODeepLearningEstimator',
    'H2OKMeansEstimator',
    'H2OPCAEstimator',
    'H2OSVDEstimator',
    'H2ONaiveBayesEstimator',
    'H2OIsolationForestEstimator',
    'H2OExtendedIsolationForestEstimator',
    'H2OIsotonicRegressionEstimator',
    'H2OQuantileEstimator',
    'H2OStackedEnsembleEstimator',
    'H2OAdaBoostEstimator',
    'H2OTargetEncoderEstimator',
    'H2OGLRMEstimator',
    'H2OCoxPHEstimator',
    'H2OWord2VecEstimator',
    'H2ORuleFitEstimator',
    'H2OAggregatorEstimator',
    'H2OGAMEstimator',
    'H2OUpliftRandomForestEstimator',
    'H2ODecisionTreeEstimator',
    'H2OSupportVectorMachineEstimator',
    'H2OANOVAGLMEstimator',
    'H2OModelSelectionEstimator',
    'H2OInfogramEstimator',
    'H2OGridSearch',
]
