"""Model / ModelBuilder: the training + scoring contract every algo follows.

Reference: ``hex/ModelBuilder.java:25`` (param validation, train/valid
adaptation, CV orchestration, Driver running computeImpl) and
``hex/Model.java`` (Parameters/Output, ``score()`` -> BigScore MRTask ->
per-row ``score0``, hex/Model.java:1901-1994).

TPU-native redesign: a ModelBuilder validates parameters, fits a DataInfo,
runs the algorithm's jit-compiled training program under a Job, and returns a
Model holding small host-side learned state (coefficients, trees, weights).
Scoring is a single batched SPMD program over the row-sharded design matrix —
the BigScore-per-row-score0 pattern collapses into one matmul-shaped pass.
Save/load is plain pickle of the host state (the portable MOJO-analog lives
in ``h2o3_tpu/export``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import pickle
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..frame.frame import Frame
from ..frame.vec import Vec, T_CAT, T_NUM
from ..runtime import dkv
from ..runtime import observability as obs
from ..runtime.cluster import cluster
from ..runtime.job import Job, JobCancelled
from .datainfo import DataInfo, MEAN_IMPUTATION


@dataclasses.dataclass
class Parameters:
    """Common training parameters — analog of hex.Model.Parameters."""

    response_column: Optional[str] = None
    ignored_columns: Sequence[str] = ()
    weights_column: Optional[str] = None
    offset_column: Optional[str] = None
    seed: int = -1
    max_iterations: int = 50
    standardize: bool = True
    missing_values_handling: str = MEAN_IMPUTATION
    # early stopping (hex/ScoreKeeper.java:319)
    stopping_rounds: int = 0
    stopping_metric: str = "auto"
    stopping_tolerance: float = 1e-3
    # checkpointing (hex/Model.java:521,543)
    checkpoint: Optional[str] = None
    export_checkpoints_dir: Optional[str] = None
    # in-training progress snapshots (runtime/snapshot.py): min seconds
    # between snapshot writes for THIS job; -1 defers to the cluster-wide
    # H2O3_TPU_SNAPSHOT_INTERVAL (default 30), 0 snapshots at every
    # opportunity.  Only effective when H2O3_TPU_RECOVERY_DIR is active.
    snapshot_interval: float = -1.0
    # class balancing (hex/Model.Parameters _balance_classes): applied
    # as per-class weights (deterministic equivalent of the reference's
    # oversampling) folded into the weights column for training+metrics
    balance_classes: bool = False
    class_sampling_factors: Optional[Sequence[float]] = None
    # cross-validation
    nfolds: int = 0
    fold_column: Optional[str] = None
    fold_assignment: str = "auto"          # auto|random|modulo|stratified
    keep_cross_validation_predictions: bool = False
    # custom metric UDF: (predictions, y, w) -> (name, value)
    # (water/udf/CMetricFunc analog)
    custom_metric_func: Optional[Any] = None
    # concurrent fold/member model building (hex/CVModelBuilder.java:16
    # "parallelization" + hex/ParallelModelBuilder.java): 0 = auto
    # (bounded pool), 1 = sequential, n>1 = exactly n builder threads
    parallelism: int = 0
    # cluster-scheduler placement (runtime/scheduler.py): dispatch
    # priority (None = PRIORITY_BUILD; lower runs first), device budget
    # as a mesh fraction in (0, 1] or an explicit chip count >= 1
    # (None = the scheduler's default share), and how many times a job
    # interrupted by a dead host may be requeued from its progress
    # snapshot before it is failed
    priority: Optional[int] = None
    device_budget: Optional[float] = None
    retry_budget: int = 0
    # streaming ingest (ingest/stream.py): train on already-landed rows
    # behind the StreamingFrame watermark, re-binning at chunk fences as
    # more data lands; per-segment row coverage is recorded into
    # model.output["stream_coverage"].  Only tree builders support it.
    stream: bool = False
    # warm start: continue boosting from a prior model — a Model, a DKV
    # key, or a saved-model path.  Public face of the checkpoint
    # machinery; bit-identical to passing checkpoint=<key>.
    warm_start: Optional[Any] = None

    def effective_seed(self) -> int:
        return np.random.default_rng().integers(2**31) if self.seed in (-1, None) \
            else int(self.seed)


@functools.partial(jax.jit, static_argnames=("classifier", "padded", "sharding"))
def prediction_columns(raw, nrows, thr, classifier, padded, sharding):
    """``predict``'s result columns from ``_predict_raw``'s scores, as one
    device program: ``[n, K]`` class probabilities give the ``int32`` label
    (``raw[:, 1] >= thr`` for K = 2, the first maximum past that) and one
    ``float32`` column per class, ``[n]`` regression scores the one column.
    Every column has ``padded`` rows under ``sharding`` (``raw`` is cut or
    padded to that); rows from ``nrows`` on hold what ``Vec.from_numpy``
    pads with, -1 in the label and NaN in the numeric columns.  ``nrows``
    and ``thr`` are traced, so neither a frame's length within one padding
    nor a model's own threshold compiles anything new."""
    raw = raw.astype(jnp.float32)
    if raw.shape[0] >= padded:
        raw = raw[:padded]
    else:
        raw = jnp.pad(raw, [(0, padded - raw.shape[0])] + [(0, 0)] * (raw.ndim - 1))
    real = jnp.arange(padded) < nrows

    def column(values, padding):
        return jax.lax.with_sharding_constraint(
            jnp.where(real, values, padding), sharding)

    if not classifier:
        return (column(raw, jnp.nan),)
    if raw.shape[1] == 2:
        labels = raw[:, 1] >= thr
    else:
        labels = jnp.argmax(raw, axis=1)
    return (column(labels.astype(jnp.int32), -1),) + tuple(
        column(raw[:, k], jnp.nan) for k in range(raw.shape[1]))


class Model:
    """A trained model: params + output + host-side learned state."""

    algo = "model"

    def __init__(self, key: str, params: Parameters, datainfo: DataInfo):
        self.key = key
        self.params = params
        self.datainfo = datainfo
        self.output: Dict[str, Any] = {}
        self.training_metrics = None
        self.validation_metrics = None
        self.cross_validation_metrics = None
        self.cv_predictions: Optional[np.ndarray] = None
        self.scoring_history: List[dict] = []
        dkv.put(key, self)

    # ---------------------------------------------------------------- scoring
    def _predict_raw(self, X: jax.Array) -> jax.Array:
        """[padded, nclasses] class probabilities or [padded] regression preds.

        The score0 analog — subclasses implement this as a pure jittable
        function of the design matrix.
        """
        raise NotImplementedError

    def _score_matrix(self, frame: Frame) -> jax.Array:
        """The matrix ``_predict_raw`` expects.  Default: the standardized
        one-hot design; tree models override with the raw-value design."""
        return self.datainfo.make_matrix(frame)

    def predict(self, frame: Frame) -> Frame:
        """Score a frame — returns a Frame shaped like the reference's preds.

        Classification: ``predict`` (label) + one probability column per
        class.  Regression: single ``predict`` column.
        """
        with obs.trace("predict", algo=self.algo, rows=frame.nrows):
            with obs.span("predict.matrix"):
                X = self._score_matrix(frame)
            with obs.span("predict.dispatch"):
                raw = self._predict_raw(X)
            with obs.span("predict.wait"):
                raw = jax.block_until_ready(raw)
            with obs.span("predict.frame"):
                return self._prediction_frame(raw, frame.nrows)

    def _prediction_frame(self, raw: jax.Array, nrows: int) -> Frame:
        """The result columns, built on the device from the raw scores."""
        di = self.datainfo
        cl = cluster()
        cols = prediction_columns(
            raw, np.int32(nrows), np.float32(self.default_threshold()),
            classifier=di.is_classifier, padded=cl.pad_rows(nrows),
            sharding=cl.row_sharding)
        if not di.is_classifier:
            return Frame(["predict"], [Vec(cols[0], T_NUM, nrows)])
        dom = [str(d) for d in di.response_domain]
        vecs = [Vec(cols[0], T_CAT, nrows, domain=dom)]
        vecs += [Vec(c, T_NUM, nrows) for c in cols[1:]]
        return Frame(["predict"] + dom, vecs)

    def default_threshold(self) -> float:
        m = self.training_metrics
        thr = getattr(m, "max_f1_threshold", None) if m is not None else None
        return float(thr) if thr is not None else 0.5

    def model_performance(self, frame: Optional[Frame] = None):
        """Compute metrics on a frame (None -> training metrics)."""
        if frame is None:
            return self.training_metrics
        from ..metrics.core import make_metrics
        di = self.datainfo
        raw = self._predict_raw(self._score_matrix(frame))
        y = di.response(frame)
        w = di.weights(frame)
        return make_metrics(di, raw, y, w, distribution=getattr(
            self.params, "distribution", None),
            custom_metric_func=self.params.custom_metric_func)

    # ------------------------------------------------------------ persistence
    # Model artifacts are pickles; load() may face bytes from outside this
    # process (POST /3/Models.upload.bin), so deserialization is allow-
    # listed: this package's CLASSES (never functions — blocks e.g.
    # h2o3_tpu.persist.delete as a gadget), numpy array reconstruction,
    # and stdlib containers.  save() already converts device arrays to
    # numpy, so legitimate artifacts never need anything else.  Known
    # limitation: a model whose params hold a user callable (custom
    # metric fn) will not reload — security of the upload route wins.
    _UNPICKLE_CLASS_MODULES = ("h2o3_tpu", "numpy", "collections",
                               "builtins")
    _UNPICKLE_CALLABLES = {
        "numpy._core.multiarray._reconstruct",
        "numpy.core.multiarray._reconstruct",
        "numpy._core.multiarray.scalar",
        "numpy.core.multiarray.scalar",
        "numpy._core.numeric._frombuffer",
        "numpy.core.numeric._frombuffer",
    }

    def save(self, path: str) -> str:
        """Save the model to any persist URI (local, gcs://, s3://, …)."""
        from .. import persist
        state = self.__dict__.copy()
        if isinstance(state.get("output"), dict):
            # "stacked" duplicates output["trees"] as raw device arrays;
            # it is rebuilt lazily on first scoring after load
            state["output"] = {k: v for k, v in state["output"].items()
                               if k != "stacked"}
        state = jax.tree.map(
            lambda v: np.asarray(v) if isinstance(v, jax.Array) else v, state)
        with persist.open_write(path) as f:
            pickle.dump((type(self), state), f)
        return path

    def download_mojo(self, path: str) -> str:
        """Export the portable scoring artifact (MOJO analog)."""
        from ..export.mojo import export_mojo
        return export_mojo(self, path)

    @staticmethod
    def load(path: str) -> "Model":
        from .. import persist
        with persist.open_read(path) as f:
            cls, state = _RestrictedUnpickler(f).load()
        m = object.__new__(cls)
        m.__dict__.update(state)
        dkv.put(m.key, m)
        return m

    def summary(self) -> dict:
        return {"key": self.key, "algo": self.algo, **{
            k: v for k, v in self.output.items()
            if isinstance(v, (int, float, str, bool, list))}}

    def __repr__(self):
        return f"<{type(self).__name__} {self.key}>"


class _RestrictedUnpickler(pickle.Unpickler):
    """Allowlisted unpickling for model artifacts (see Model.save note)."""

    def find_class(self, module, name):
        full = f"{module}.{name}"
        if full in Model._UNPICKLE_CALLABLES:
            return super().find_class(module, name)
        root = module.split(".", 1)[0]
        if root in Model._UNPICKLE_CLASS_MODULES:
            obj = super().find_class(module, name)
            # classes only: reconstructing instances is fine, but plain
            # functions (persist.delete, builtins.exec, np.f2py helpers…)
            # are exactly what pickle gadgets invoke
            if isinstance(obj, type):
                return obj
        raise pickle.UnpicklingError(
            f"model artifact references disallowed global {full}")


class ModelBuilder:
    """Base builder — analog of hex.ModelBuilder.trainModel()."""

    algo = "model"
    model_class = Model
    supervised = True

    def __init__(self, params: Parameters):
        self.params = params
        self.job: Optional[Job] = None

    # -- hooks ---------------------------------------------------------------
    def _validate(self, frame: Frame) -> None:
        p = self.params
        if self.supervised:
            if not p.response_column:
                raise ValueError(f"{self.algo}: response_column is required")
            if p.response_column not in frame.names:
                raise ValueError(
                    f"response_column {p.response_column!r} not in frame")

    def _make_datainfo(self, frame: Frame) -> DataInfo:
        p = self.params
        return DataInfo.fit(
            frame,
            response_column=p.response_column if self.supervised else None,
            ignored_columns=p.ignored_columns,
            weights_column=p.weights_column,
            offset_column=p.offset_column,
            standardize=p.standardize,
            missing_values_handling=p.missing_values_handling,
            force_classification=getattr(self, "_force_classification", False))

    def _fit(self, job: Job, frame: Frame, di: DataInfo,
             valid: Optional[Frame]) -> Model:
        raise NotImplementedError

    # -- driver --------------------------------------------------------------
    def _apply_balance(self, frame: Frame):
        """balance_classes as per-class weights: returns (frame,
        params_override or None).  The override is installed only for
        the duration of the run (xgboost's _xgb_w_ pattern) and the
        fitted model's DataInfo keeps the USER's weights column so
        scoring new frames honors their weights, not the synthetic
        training column."""
        p = self.params
        if not getattr(p, "balance_classes", False) or not self.supervised:
            return frame, None
        rvec = frame.vec(p.response_column)
        if rvec.type != T_CAT:
            return frame, None              # regression: nothing to balance
        k = rvec.cardinality
        if k <= 0:
            raise ValueError(
                "balance_classes needs a categorical response with a "
                "domain (got a cat column without one)")
        codes = np.asarray(rvec.data)[: frame.nrows]
        counts = np.bincount(codes[codes >= 0], minlength=k).astype(float)
        counts[counts == 0] = 1.0
        if p.class_sampling_factors is not None:
            factors = np.asarray(p.class_sampling_factors, float)
        else:
            factors = counts.sum() / (k * counts)
        if len(factors) != k:
            raise ValueError(
                f"class_sampling_factors needs {k} entries, got "
                f"{len(factors)}")
        w = np.where(codes >= 0, factors[np.clip(codes, 0, k - 1)], 0.0)
        if p.weights_column:
            w = w * frame.vec(p.weights_column).to_numpy()
        out = frame.with_vec("_balance_weights_",
                             Vec.from_numpy(w.astype(np.float64), T_NUM))
        return out, dataclasses.replace(
            p, weights_column="_balance_weights_")

    def _balance_valid(self, valid, orig):
        """Mirror the synthetic weights name onto the validation frame
        with the USER's weights (or ones): validation metrics are never
        class-balanced, matching the reference."""
        if valid is None or "_balance_weights_" in valid.names:
            return valid
        uv = valid.vec(orig.weights_column).to_numpy() \
            if orig.weights_column else np.ones(valid.nrows)
        return valid.with_vec(
            "_balance_weights_",
            Vec.from_numpy(np.asarray(uv, np.float64), T_NUM))

    #: set True by builders whose _fit honors params.checkpoint (the tree
    #: family) — gates warm_start= and StreamingFrame training, which are
    #: both built on checkpoint continuation
    _supports_checkpoint = False

    def train(self, frame: Frame, valid: Optional[Frame] = None,
              warm_start: Optional[Any] = None) -> Model:
        """Blocking train — the trainModel/Driver.computeImpl path.

        ``warm_start`` (also available as a parameter) continues boosting
        from a prior model — a Model, a DKV key, or a saved-model path —
        and is bit-identical to checkpoint continuation.  A
        ``StreamingFrame`` trains in stream mode: boosting starts on the
        rows already landed behind the watermark and re-bins at chunk
        fences as more data arrives.
        """
        ws = warm_start if warm_start is not None else self.params.warm_start
        if ws is not None:
            if not self._supports_checkpoint:
                raise ValueError(
                    f"{self.algo} does not support warm_start (no "
                    "checkpoint continuation)")
            orig = self.params
            try:
                self.params = dataclasses.replace(
                    orig, warm_start=None,
                    checkpoint=self._resolve_warm_start(ws))
                return self.train(frame, valid)
            finally:
                self.params = orig
        if not isinstance(frame, Frame) and hasattr(frame, "watermark"):
            return self._train_stream(frame, valid)
        # spans are opened here, in this frame: a helper between train()
        # and _fit is one more Python frame under everything a fit traces,
        # which read +12 ms on a 170 ms GLM fit on the v5e's host (PR 28)
        with obs.trace("train", algo=self.algo, rows=frame.nrows):
            with obs.span("train.validate"):
                self._validate(frame)
                frame, bal = self._apply_balance(frame)
            orig = self.params
            if bal is not None:
                self.params = bal
                valid = self._balance_valid(valid, orig)
            try:
                with obs.span("train.datainfo"):
                    di = self._make_datainfo(frame)
                self.job = Job(f"{self.algo} train",
                               dest_key=dkv.make_key(self.algo))
                if getattr(self, "_stream_ctx", None) is not None:
                    self.job.stream = self._stream_ctx.progress()
                return self.job.run(self._make_driver(
                    frame, di, valid,
                    orig_params=orig if bal is not None else None))
            finally:
                self.params = orig

    def _resolve_warm_start(self, ws) -> str:
        """Normalize a warm_start (Model | DKV key | saved path) to the
        DKV key checkpoint continuation expects."""
        if isinstance(ws, Model):
            if dkv.get(ws.key) is None:
                dkv.put(ws.key, ws)
            return ws.key
        if isinstance(ws, str):
            if dkv.get(ws) is not None:
                return ws
            import os
            if os.path.exists(ws):
                return Model.load(ws).key
            raise ValueError(
                f"warm_start {ws!r} is neither a DKV model key nor a "
                "saved model file")
        raise ValueError(f"warm_start must be a Model, key, or path, "
                         f"got {type(ws).__name__}")

    def _train_stream(self, sf, valid: Optional[Frame] = None) -> Model:
        """Train while a StreamingFrame lands: boost on the visible
        prefix, cut at a chunk fence when enough new rows arrive (or the
        landed-fraction tree budget is spent), re-bin the grown prefix
        with the prior's edges, and continue as a checkpoint segment.
        Bit-identity with batch training holds for the degenerate
        single-segment case; multi-segment runs record their per-segment
        row coverage in ``model.output["stream_coverage"]``.
        """
        import math

        from ..runtime.config import config
        from ..runtime.observability import inc

        if not self._supports_checkpoint:
            raise ValueError(
                f"{self.algo} cannot train on a StreamingFrame (no "
                "checkpoint continuation to re-bin against)")
        cfg = config()
        sf.start()
        sf.wait_rows(max(cfg.stream_min_rows, 1))
        p0 = self.params
        ntrees = getattr(p0, "ntrees", None)
        if ntrees is None:
            raise ValueError(f"{self.algo} has no ntrees — stream mode "
                             "is for the tree family")
        model, prior_key, prior_nt = None, p0.checkpoint, 0
        if prior_key is not None:
            prior = dkv.get(prior_key) if isinstance(prior_key, str) \
                else prior_key
            prior_nt = prior.output["ntrees_trained"]
        coverage: List[dict] = []
        self._stream_ctx = sf
        last_rows = 0
        try:
            while True:
                wm = sf.watermark
                total = sf.total_rows
                full = sf.complete and (total is None or wm >= total)
                r = cfg.stream_round_rows
                rows_vis = wm if (full or r <= 0) \
                    else ((wm // r) * r or wm)
                if not full and rows_vis <= last_rows:
                    # quantization floored us back onto the last segment:
                    # wait for more rows before cutting a new one
                    sf.wait_growth(max(last_rows, 1),
                                   cfg.stream_grow_min_frac)
                    continue
                if full:
                    # the landing thread's finalize assembles the
                    # registered frame anyway — wait for it instead of
                    # assembling a duplicate
                    vis = sf.frame()
                else:
                    vis = sf.visible_frame(
                        limit=rows_vis if rows_vis < wm else None)
                rows0 = vis.nrows
                grow = max(1, int(rows0 * cfg.stream_grow_min_frac))
                seg_prior_nt = prior_nt
                cut = {"hit": False}

                def fence(t_rel: int, _rows0=rows0, _grow=grow,
                          _pnt=seg_prior_nt, _cut=cut) -> bool:
                    if self.job is not None:
                        self.job.stream = sf.progress()
                    wm_now = sf.watermark
                    if sf.complete:
                        # grab the tail as soon as the stream runs out
                        # (or keep going: this segment IS the full data)
                        _cut["hit"] = wm_now > _rows0
                        return _cut["hit"]
                    tot = sf.total_rows
                    if not tot:
                        # size unknown: fall back to growth-based cuts
                        _cut["hit"] = wm_now >= _rows0 + _grow
                        return _cut["hit"]
                    # pace trees to the landed fraction; the budget rises
                    # as rows land mid-segment, so a fast stream defers
                    # the cut and a stalled one forces it (the outer
                    # loop then blocks in wait_growth — that's the pause)
                    budget = max(_pnt + 1, math.ceil(
                        ntrees * min(1.0, wm_now / tot)))
                    _cut["hit"] = _pnt + t_rel >= budget
                    return _cut["hit"]

                self._stream_fence = fence
                self.params = dataclasses.replace(
                    p0, checkpoint=prior_key, stream=False)
                try:
                    model = self.train(vis, valid)
                finally:
                    self._stream_fence = None
                    self.params = p0
                prior_key = model.key
                prior_nt = model.output["ntrees_trained"]
                coverage.append({"trees": int(prior_nt),
                                 "rows": int(rows0)})
                if len(coverage) > 1:
                    inc("stream_rebin_total", algo=self.algo)
                sf.consume(rows0)
                last_rows = rows0
                if prior_nt >= ntrees:
                    break
                if full and not cut["hit"]:
                    break                # early stop on the full data
                sf.wait_growth(rows0, cfg.stream_grow_min_frac)
        finally:
            self._stream_ctx = None
            self.params = p0
        model.output["stream_coverage"] = coverage
        model.output["stream_segments"] = len(coverage)
        if self.job is not None:
            self.job.stream = sf.progress()
        return model

    def _make_driver(self, frame: Frame, di: DataInfo,
                     valid: Optional[Frame], orig_params=None):
        """The full training driver (CV, post-fit hooks, checkpoint export)
        shared by the blocking and async entry points.  ``orig_params``
        is set when balance_classes installed a temporary params
        override: the driver restores it when done and journals/scores
        with the user's own parameters."""
        def _driver(job: Job) -> Model:
            from ..runtime import recovery
            # reuse a submit-time (or previous-life) journal entry: a
            # requeued job keeps its snapshot pointer for the next resume
            journal = job.journal_uri
            if not journal:
                with obs.span("train.journal", op="start"):
                    journal = recovery.journal_start(
                        self, frame, job, params=orig_params)
            job.journal_uri = journal      # gates in-training snapshots
            try:
                # the device lease serializes compiled-program launches
                # across co-resident jobs (XLA in-process collectives
                # deadlock on concurrent launches); chunk_fence yields
                # it at every chunk boundary so jobs still interleave
                from ..runtime import scheduler as _sched
                with contextlib.ExitStack() as lease:
                    # the span is the wait for the lease, not the body
                    with obs.span("train.device_slot"):
                        lease.enter_context(_sched.device_slot())
                    model = self._driver_body(job, frame, di, valid, journal)
            except BaseException as e:
                # cancelled / deterministically failing jobs must not be
                # resurrected as if the process had died — but a failure
                # caused by a dead/dying member stays 'running' in the
                # journal so recovery.resume() resurrects it after restart
                from ..runtime import failure
                if isinstance(e, JobCancelled) or not (
                        isinstance(e, failure.NodeFailedError)
                        or failure.cluster_degraded()):
                    recovery.journal_fail(journal, repr(e))
                raise
            finally:
                if orig_params is not None:
                    self.params = orig_params
            if orig_params is not None:
                # scoring frames carry the USER's weights column (if
                # any), never the synthetic training-only balance column
                model.datainfo = dataclasses.replace(
                    model.datainfo,
                    weights_column=orig_params.weights_column)
            return model
        return _driver

    def _driver_body(self, job: "Job", frame: Frame, di: DataInfo,
                     valid: Optional[Frame], journal) -> Model:
            from ..runtime import recovery
            t0 = time.time()
            with obs.span("train.fit"):
                if self.params.nfolds and self.params.nfolds > 1:
                    model = self._train_cv(job, frame, di, valid)
                else:
                    model = self._fit(job, frame, di, valid)
            with obs.span("train.post_fit"):
                model.output.setdefault("run_time_s", time.time() - t0)
                model.output.setdefault("training_frame_rows", frame.nrows)
                self._post_fit(model, frame, valid)
                if self.params.export_checkpoints_dir:
                    import os
                    os.makedirs(self.params.export_checkpoints_dir,
                                exist_ok=True)
                    model.save(os.path.join(
                        self.params.export_checkpoints_dir,
                        model.key + ".bin"))
            with obs.span("train.journal", op="done"):
                recovery.journal_done(journal)
            return model

    def _post_fit(self, model: Model, frame: Frame,
                  valid: Optional[Frame]) -> None:
        """Hook after _fit (calibration, etc.); default no-op."""

    def train_async(self, frame: Frame, valid: Optional[Frame] = None,
                    priority: Optional[int] = None,
                    user: Optional[str] = None) -> Job:
        """Queue training on the cluster scheduler; returns the Job.

        The h2o.train(..., async) analog over the fair-share scheduler
        (runtime/scheduler.py): poll ``job.status`` / ``/3/Jobs`` or
        ``job.join()`` for the model.  Placement comes from the params —
        ``priority`` (arg overrides), ``device_budget``,
        ``retry_budget`` — and the journal entry is written at SUBMIT
        time, so even a queued-but-unstarted job survives a coordinator
        restart via ``scheduler.readmit()``.
        """
        from ..runtime import recovery
        from ..runtime.job import scheduler, JobScheduler
        self._validate(frame)
        frame, bal = self._apply_balance(frame)
        orig_async = self.params
        if bal is not None:
            # stays installed while the queued driver runs; the driver's
            # finally restores it (concurrent reuse of one builder with
            # balance_classes is not supported)
            self.params = bal
            valid = self._balance_valid(valid, orig_async)
        p = self.params
        di = self._make_datainfo(frame)
        self.job = Job(f"{self.algo} train",
                       dest_key=dkv.make_key(self.algo))
        self.job.journal_uri = recovery.journal_start(
            self, frame, self.job,
            params=orig_async if bal is not None else None)
        if priority is None:
            priority = JobScheduler.PRIORITY_BUILD \
                if p.priority is None else p.priority
        try:
            return scheduler().submit(
                self.job,
                self._make_driver(frame, di, valid,
                                  orig_params=orig_async
                                  if bal is not None else None),
                priority=priority,
                device_budget=p.device_budget,
                retry_budget=p.retry_budget or 0,
                user=user)
        except BaseException as e:
            # admission rejected: the submit-time journal entry must not
            # be resurrected as if the process had died
            recovery.journal_fail(self.job.journal_uri, repr(e))
            raise

    # -- cross-validation (hex/CVModelBuilder.java:10) -----------------------
    def _train_cv(self, job: Job, frame: Frame, di: DataInfo,
                  valid: Optional[Frame]) -> Model:
        from .cv import cross_validate
        return cross_validate(self, job, frame, di, valid)
