"""DeepLearning: multi-layer perceptron / autoencoder, data-parallel on TPU.

Reference: ``hex/deeplearning/`` — DeepLearning.java (driver main loop),
DeepLearningTask.java:17 (Hogwild! lock-free per-node SGD on a local weight
copy), DeepLearningTask2.java:44-61 (cluster model averaging),
Neurons.java:184/189 (per-row fprop/bprop with gemv row kernels :638),
Dropout.java, DeepLearningModelInfo.java (flat weight arrays, elastic
averaging :751-758).

TPU-native redesign (SURVEY.md §2.10): Hogwild + periodic averaging is an
artifact of JVM threads — synchronous data-parallel SGD is strictly better on
TPU, so each step is ONE jit-compiled program: a minibatch read from the
row-sharded design, batched fprop/bprop as MXU matmuls (the per-row
gemv loops become [batch, features] @ [features, hidden]), gradients psum'd
over the mesh by GSPMD, optimizer update via optax (ADADELTA to match the
reference's adaptive-rate default, DeepLearningModelInfo rho/epsilon).
``train_samples_per_iteration`` keeps its reference semantics: samples
processed between scoring/early-stopping checks.

The design is held in code form (``datainfo.CodedDesign``: numerics beside
categorical codes), as the reference's ``Neurons.Input`` holds it; the dense
one-hot row exists only for the minibatch or the scoring block in hand
(``expand_coded``), never for the frame.  The first layer is then the plain
product of that expansion with ``W1``: on the MXU a [batch, expanded] one-hot
product costs less than a gather of three rows a sample and the scatter-add
of its backward pass (PERF.md §6, PR 30), and input dropout, every
activation and the autoencoder's target read the expanded minibatch as they
read the dense matrix before.  ``reference_dl.py`` is the same mathematics
in plain ``jax.numpy`` on the frame's dense expansion.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..frame.frame import Frame
from ..runtime import dkv
from ..runtime.cluster import ROW_AXIS, cluster
from ..runtime.job import Job
from ..runtime import observability as obs
from .base import Model, ModelBuilder, Parameters
from .datainfo import (CodedDesign, DataInfo, block_rows, expand_coded,
                       map_row_blocks)
from ..metrics.core import make_metrics
from .scorekeeper import stop_early


@dataclasses.dataclass
class DeepLearningParameters(Parameters):
    hidden: Sequence[int] = (200, 200)
    activation: str = "rectifier"       # tanh|rectifier|maxout (+_with_dropout)
    epochs: float = 10.0
    mini_batch_size: int = 128           # TPU-efficient default (ref default 1)
    adaptive_rate: bool = True           # ADADELTA (rho/epsilon), ref default
    rho: float = 0.99
    epsilon: float = 1e-8
    rate: float = 0.005                  # when adaptive_rate=False
    momentum_start: float = 0.0
    momentum_stable: float = 0.0
    input_dropout_ratio: float = 0.0
    hidden_dropout_ratios: Optional[Sequence[float]] = None
    l1: float = 0.0
    l2: float = 0.0
    # custom per-row loss UDF (CDistributionFunc analog): callable
    # (pred, y) -> per-row loss, jittable; pred is logits [B, K] for
    # classifiers / autoencoders, the scalar prediction [B] otherwise.
    # NOTE: with standardize=True (the default) regression targets reach
    # the loss STANDARDIZED ((y-mean)/sigma) — scale-sensitive losses
    # (e.g. huber with a delta in raw units) should set standardize=False
    custom_loss_func: Optional[object] = None
    loss: str = "automatic"              # automatic|cross_entropy|quadratic|
    # absolute|huber
    distribution: str = "auto"
    train_samples_per_iteration: int = -2   # -2 auto, -1 all, 0 one epoch
    score_interval: float = 5.0
    initial_weight_distribution: str = "uniform_adaptive"
    initial_weight_scale: float = 1.0
    autoencoder: bool = False
    standardize: bool = True
    stopping_rounds: int = 5
    stopping_metric: str = "auto"
    stopping_tolerance: float = 0.0
    max_iterations: int = 10 ** 9        # unused; epochs governs
    # bf16 MXU compute with f32 master weights/optimizer state (mixed
    # precision — the TPU-native default); "f32" forces full precision
    # (reproducible-mode analog for scale-sensitive losses)
    precision: str = "bf16"
    # rows are permuted once on device before training so the random-offset
    # block sampler (see _build_train_steps) draws unbiased minibatches
    # even from sorted frames; reference flag of the same name
    shuffle_training_data: bool = True


def _forward_pass(activation: str, params, X, deterministic=True, rng=None,
                  dropout_in: float = 0.0, dropout_hidden=(),
                  compute_dtype=None):
    """THE DL forward pass on dense rows (a minibatch or a scoring block,
    expanded by ``expand_coded``) — shared by the scoring program
    (``_make_score``) and the compiled training program (one implementation, so activation /
    dropout semantics cannot drift between training and scoring).

    ``compute_dtype=bf16`` runs the matmuls on the MXU in bf16 with f32
    accumulation (mixed precision); weights and biases stay f32 so the
    optimizer state and the autodiff transpose remain full precision.
    """
    act = _activation_fn(activation)
    maxout = act is None

    def mm(h, W):
        if compute_dtype is None:
            return h @ W
        return jnp.dot(h.astype(compute_dtype), W.astype(compute_dtype),
                       preferred_element_type=jnp.float32)

    h = X
    if not deterministic and dropout_in > 0:
        rng, k = jax.random.split(rng)
        h = h * jax.random.bernoulli(k, 1 - dropout_in, h.shape) \
            / (1 - dropout_in)
    for i, (W, b) in enumerate(params[:-1]):
        z = mm(h, W) + b
        z = z.reshape(z.shape[0], -1, 2).max(axis=2) if maxout else act(z)
        dr = dropout_hidden[i] if i < len(dropout_hidden) else 0.0
        if not deterministic and dr > 0:
            rng, k = jax.random.split(rng)
            z = z * jax.random.bernoulli(k, 1 - dr, z.shape) / (1 - dr)
        h = z
    W, b = params[-1]
    return mm(h, W) + b


class _StepConfig(NamedTuple):
    """Everything a training step closes over, hashable: the compiled
    programs are cached on it, and a trained model rebuilds the same step
    from its parameters and datainfo (``DeepLearningModel.train_interval``)."""
    layout: tuple                # DataInfo.coded_layout()
    activation: str
    dropout_in: float
    dropout_h: tuple
    loss_kind: str
    is_cls: bool
    autoenc: bool
    out_dim: int
    l1: float
    l2: float
    opt_cfg: tuple
    compute_dtype: object        # jnp.bfloat16 or None (float32)


def _step_config(p: "DeepLearningParameters", di: DataInfo) -> _StepConfig:
    layout = di.coded_layout()
    is_cls = di.is_classifier and not p.autoencoder
    if p.autoencoder:
        out_dim = sum(width for _, width in layout)
    else:
        out_dim = di.nclasses if is_cls else 1
    if p.adaptive_rate:
        opt_cfg = ("adadelta", p.rho, p.epsilon)
    elif p.momentum_stable > 0 or p.momentum_start > 0:
        opt_cfg = ("sgd_momentum", p.rate,
                   p.momentum_stable or p.momentum_start)
    else:
        opt_cfg = ("sgd", p.rate)
    loss_kind = p.loss
    if loss_kind == "automatic":
        loss_kind = "cross_entropy" if is_cls else "quadratic"
    dropout_h = tuple(p.hidden_dropout_ratios or ())
    if p.activation.endswith("_with_dropout") and not dropout_h:
        dropout_h = tuple(0.5 for _ in p.hidden)
    return _StepConfig(layout, p.activation, p.input_dropout_ratio, dropout_h,
                       loss_kind, is_cls, p.autoencoder, out_dim, p.l1, p.l2,
                       opt_cfg,
                       jnp.bfloat16 if p.precision == "bf16" else None)


def _build_train_steps(cfg: _StepConfig, batch: int, steps_per_iter: int,
                       n: int, custom_loss=None):
    """Build the compiled training-interval program (see _make_train_steps
    for the caching story; ``custom_loss`` bypasses the cache)."""
    def loss_fn(params, nb, cb, yb, wb, key):
        # the dense expansion exists for these rows only
        xb = expand_coded(cfg.layout, nb, cb)
        logits = _forward_pass(cfg.activation, params, xb,
                               deterministic=False, rng=key,
                               dropout_in=cfg.dropout_in,
                               dropout_hidden=cfg.dropout_h,
                               compute_dtype=cfg.compute_dtype)
        if custom_loss is not None:
            pred = logits if (cfg.is_cls or cfg.autoenc) else logits[:, 0]
            per = custom_loss(pred, xb if cfg.autoenc else yb)
        elif cfg.autoenc:
            per = jnp.mean((logits - xb) ** 2, axis=1)
        elif cfg.is_cls:
            yi = jnp.clip(yb.astype(jnp.int32), 0, cfg.out_dim - 1)
            per = optax.softmax_cross_entropy_with_integer_labels(logits, yi)
        elif cfg.loss_kind == "absolute":
            per = jnp.abs(logits[:, 0] - yb)
        elif cfg.loss_kind == "huber":
            per = optax.huber_loss(logits[:, 0], yb, delta=1.0)
        else:
            per = (logits[:, 0] - yb) ** 2
        loss = jnp.sum(per * wb) / jnp.maximum(jnp.sum(wb), 1e-12)
        if cfg.l2 > 0 or cfg.l1 > 0:
            for W, _ in params:
                loss = loss + cfg.l2 * jnp.sum(W * W) \
                    + cfg.l1 * jnp.sum(jnp.abs(W))
        return loss

    kind, *hp = cfg.opt_cfg
    if kind == "adadelta":
        tx = optax.adadelta(learning_rate=1.0, rho=hp[0], eps=hp[1])
    elif kind == "sgd_momentum":
        tx = optax.sgd(hp[0], momentum=hp[1])
    else:
        tx = optax.sgd(hp[0])

    def sgd_step(table, codes, carry, draw):
        params, opt_state = carry
        off, key = draw
        # random-offset contiguous block instead of a per-row gather: a
        # [batch]-row gather from a big table is far slower on TPU than a
        # contiguous read; dynamic_slice streams at HBM rate.  The rows
        # were permuted once up front (shuffle_training_data) and the
        # arrays carry a wraparound copy of the first `batch` rows
        # (_sample_copy_fn), so offsets draw uniformly over [0, n) and
        # every row has identical inclusion probability (a [0, n-batch]
        # range would under-sample both array ends by up to batch x).
        tb = jax.lax.dynamic_slice_in_dim(table, off, batch, axis=0)
        cb = jax.lax.dynamic_slice_in_dim(codes, off, batch, axis=0)
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tb[:, :-2], cb, tb[:, -2], tb[:, -1], key)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state), loss

    # the name the device trace knows the program by: jit_dl_train_steps
    def dl_train_steps(params, opt_state, rng0, it, table, codes):
        # offsets and dropout keys derive in-jit from (rng0, iteration), so
        # the driver loop dispatches nothing but the step program
        (params, opt_state), losses = jax.lax.scan(
            functools.partial(sgd_step, table, codes),
            (params, opt_state), _interval_draws(rng0, it, steps_per_iter, n))
        return params, opt_state, jnp.mean(losses)

    return jax.jit(dl_train_steps), tx


def _interval_draws(rng0, it, steps: int, n: int):
    """(block offsets [steps] in [0, n), dropout keys [steps]) of iteration
    ``it``.  All of an interval's offsets are one draw up front: a draw
    inside the step was 8 of its 21 us on the v5e (PERF.md section 6, PR 30)."""
    k_off, k_drop = jax.random.split(jax.random.fold_in(rng0, it))
    return (jax.random.randint(k_off, (steps,), 0, max(n, 1)),
            jax.random.split(k_drop, steps))


@functools.lru_cache(maxsize=None)
def _sample_copy_fn(n: int, batch: int, shuffle: bool):
    """The ONE frame-sized copy a fit makes, as the sampler reads it: a
    float table [n + batch, P_num + 2] (the numerics, then the label, then
    the weight) and the codes [n + batch, P_cat], the design's rows permuted
    if asked and followed by a wraparound copy of the first ``batch`` of
    them, so that the block sampler's dynamic_slice at any offset in [0, n)
    stays in bounds.  Label and weight ride in the numerics' table because
    a gather costs per index, not per value: on the v5e 0.50 s for a
    40M-row vector, 0.28 s for the [40M, 5] table (PERF.md section 6, PR 30).
    Compiled once per geometry."""
    @jax.jit
    def dl_sample_copy(num, codes, y, w, key):
        order = jax.random.permutation(key, n) if shuffle else jnp.arange(n)
        idx = jnp.concatenate([order, order[:batch]])
        table = jnp.concatenate([num, y[:, None], w[:, None]], axis=1)
        return jnp.take(table, idx, axis=0), jnp.take(codes, idx, axis=0)
    return dl_sample_copy


@functools.lru_cache(maxsize=None)
def _make_train_steps(cfg: _StepConfig, batch: int, steps_per_iter: int,
                      n: int):
    """Compiled training-interval program, CACHED ACROSS train() calls.

    The per-call ``@jax.jit def train_steps`` pattern recompiled (and paid
    the remote backend's multi-second first-execution penalty) on every
    train() — a warm-up model compiled a program the timed model then
    could not reuse (measured on chip: a timed MNIST-shaped run spent most
    of its wall clock there, reporting 2.7k samples/s).  Everything the
    program closes over is reconstructed from hashable config; the data
    (the sampler's table and codes) are traced arguments, so any same-shaped training
    run reuses the executable.  Returns (train_steps, tx).
    """
    return _build_train_steps(cfg, batch, steps_per_iter, n)


def _train_program(p: "DeepLearningParameters", cfg: _StepConfig, batch: int,
                   steps_per_iter: int, n: int):
    """(train_steps, tx) of a fit: cached across train() calls (same
    architecture, config and shapes reuse one executable: no recompile, no
    first-execution penalty) unless a custom python loss, which is not
    hashable, rides along: then the same builder, uncached."""
    if p.custom_loss_func is None:
        return _make_train_steps(cfg, batch, steps_per_iter, n)
    return _build_train_steps(cfg, batch, steps_per_iter, n,
                              custom_loss=p.custom_loss_func)


# ------------------------------------------------------------- scoring
def _score_block_rows(widths: Sequence[int], rows: int) -> int:
    """Rows one scoring block holds, from the layer widths and the device's
    memory (``datainfo.block_rows``): a block keeps its expanded rows and
    every layer's activations (float32, counted twice over for the casts and
    the compiler's temporaries) inside a sixteenth of the device."""
    return block_rows(2 * 4 * sum(widths), rows)


@functools.lru_cache(maxsize=None)
def _make_score(layout: tuple, activation: str, emit: str, block: int):
    """Compiled scoring program, cached on what it closes over: every
    row-shard walks its own rows in blocks of ``block`` (the last block
    is laid back over the one before it, so every block is whole), expands
    one block, runs THE forward pass on it and keeps ``emit`` of the result:
    ``"softmax"`` [rows, K], ``"first"`` (the regression output) [rows],
    ``"logits"`` [rows, out] or ``"anomaly"`` (mean squared reconstruction
    error) [rows]."""
    def rows_of(params, nb, cb):
        X = expand_coded(layout, nb, cb)
        logits = _forward_pass(activation, params, X)
        if emit == "softmax":
            return jax.nn.softmax(logits, axis=1)
        if emit == "first":
            return logits[:, 0]
        if emit == "anomaly":
            return jnp.mean((logits - X) ** 2, axis=1)
        return logits

    def shard(params, num, codes):
        return map_row_blocks(functools.partial(rows_of, params), block,
                              num, codes)

    # the name the device trace knows the program by: jit_dl_score
    def dl_score(params, num, codes):
        out_spec = P(ROW_AXIS) if emit in ("first", "anomaly") \
            else P(ROW_AXIS, None)
        return shard_map(shard, mesh=cluster().mesh,
                         in_specs=(P(), P(ROW_AXIS, None), P(ROW_AXIS, None)),
                         out_specs=out_spec)(params, num, codes)

    return jax.jit(dl_score)


def _activation_fn(name: str):
    base = name.replace("_with_dropout", "")
    if base == "tanh":
        return jnp.tanh
    if base == "rectifier":
        return jax.nn.relu
    if base == "maxout":
        return None                      # handled specially (pairwise max)
    raise ValueError(f"unknown activation {name!r}")


class DeepLearningModel(Model):
    algo = "deeplearning"

    def _device_params(self):
        return [(jnp.asarray(W), jnp.asarray(b))
                for W, b in self.output["weights"]]

    def _score_matrix(self, frame: Frame) -> CodedDesign:
        """The design ``_predict_raw`` expects: in code form."""
        return self.datainfo.make_coded(frame)

    def _score(self, X: CodedDesign, emit: str) -> jax.Array:
        """``emit`` of the forward pass over every row of ``X``, in row
        blocks sized from the layer widths and the device's memory."""
        di, weights = self.datainfo, self.output["weights"]
        widths = [weights[0][0].shape[0]] + [W.shape[1] for W, _ in weights]
        rows = X.num.shape[0] // cluster().n_row_shards
        score = _make_score(di.coded_layout(), self.params.activation, emit,
                            _score_block_rows(widths, rows))
        return score(self._device_params(), X.num, X.codes)

    def _predict_raw(self, X: CodedDesign) -> jax.Array:
        if self.params.autoencoder:
            return self._score(X, "logits")
        if self.datainfo.is_classifier:
            return self._score(X, "softmax")
        mu = self._score(X, "first")
        if self.datainfo.standardize:
            mu = mu * self.datainfo.response_sigma + self.datainfo.response_mean
        return mu

    def predict(self, frame: Frame) -> Frame:
        if not self.params.autoencoder:
            return super().predict(frame)
        # autoencoder predict = per-design-column reconstruction, named and
        # un-scaled like the reference (DeepLearningModel.scoreAutoEncoder
        # reverses standardization and names columns reconstr_<coef>)
        from ..frame.vec import Vec, T_NUM, T_CAT
        di = self.datainfo
        R = np.asarray(self._predict_raw(
            self._score_matrix(frame)))[: frame.nrows].astype(np.float64)
        if di.standardize:
            for s in di.specs:
                if s.type != T_CAT:
                    R[:, s.offset] = R[:, s.offset] * s.sigma + s.mean
        cnames = di.coef_names
        names, vecs = [], []
        for j in range(R.shape[1]):
            cn = cnames[j] if j < len(cnames) else str(j)
            names.append(f"reconstr_{cn}")
            vecs.append(Vec.from_numpy(R[:, j], T_NUM))
        return Frame(names, vecs)

    def anomaly(self, frame: Frame) -> Frame:
        """Autoencoder per-row reconstruction MSE (DL anomaly detection)."""
        from ..frame.vec import Vec, T_NUM
        err = np.asarray(self._score(self._score_matrix(frame),
                                     "anomaly"))[: frame.nrows]
        return Frame(["Reconstruction.MSE"], [Vec.from_numpy(err, T_NUM)])

    def train_interval(self, frame: Frame, steps: int, seed: int = 0) -> dict:
        """One launch of the fit's own programs on ``frame`` (a small one),
        from this model's weights and a fresh optimizer state, with all it
        read, so that a check can replay the launch against a reference.
        ``jit_dl_sample_copy`` makes the sampler's copy as ``_fit`` has it
        made, and ``jit_dl_train_steps``, from the builder and at the
        minibatch size ``_fit`` uses, runs ``steps`` minibatches of it.
        Returns ``offsets`` [steps] (a minibatch is the ``mini_batch_size``
        rows of the copy from its offset on), the copy as the step expands it
        (``rows`` [n + batch, expanded width], in ``coef_names``' order, with
        ``labels`` and ``row_weights``), the launch's mean ``loss``, the
        ``weights`` after it and, under ADADELTA, its ``accumulators``
        (``e_g``, ``e_d``: E[g^2] and E[D^2], shaped like the layers)."""
        p, di, n = self.params, self.datainfo, frame.nrows
        cfg = _step_config(p, di)
        X = di.make_coded(frame)
        y, w = _targets(p, di, frame, X)
        batch = min(p.mini_batch_size, n)
        rng, ks = jax.random.split(jax.random.PRNGKey(seed))
        table, codes = _sample_copy_fn(n, batch, bool(p.shuffle_training_data))(
            *X, y, w, ks)
        train_steps, tx = _train_program(p, cfg, batch, steps, n)
        params = self._device_params()
        params, opt_state, loss = train_steps(params, tx.init(params), rng, 0,
                                              table, codes)

        def to_host(layers):
            return [(np.asarray(W), np.asarray(b)) for W, b in layers]

        out = {"offsets": np.asarray(_interval_draws(rng, 0, steps, n)[0]),
               "rows": np.asarray(expand_coded(cfg.layout, table[:, :-2], codes)),
               "labels": np.asarray(table[:, -2]),
               "row_weights": np.asarray(table[:, -1]),
               "loss": float(loss), "weights": to_host(params)}
        for part in opt_state:
            if isinstance(part, optax.ScaleByAdaDeltaState):
                out["accumulators"] = {"e_g": to_host(part.e_g),
                                       "e_d": to_host(part.e_x)}
        return out


def _targets(p: "DeepLearningParameters", di: DataInfo, frame: Frame,
             X: CodedDesign):
    """(y, w) as the training step reads them: class codes, the response
    (standardised if the design is) or, for an autoencoder, nothing."""
    if p.autoencoder:
        y = jnp.zeros(X.num.shape[0], jnp.float32)
    elif di.is_classifier:
        y = di.response(frame)
    else:
        y = di.response(frame)
        if di.standardize:
            y = (y - di.response_mean) / di.response_sigma
        y = jnp.nan_to_num(y)
    return y, di.weights(frame)


class DeepLearning(ModelBuilder):
    algo = "deeplearning"
    model_class = DeepLearningModel

    def __init__(self, params: Optional[DeepLearningParameters] = None, **kw):
        super().__init__(params or DeepLearningParameters(**kw))
        self.supervised = not self.params.autoencoder

    def _init_params(self, rng, sizes: List[int], maxout: bool):
        p = self.params
        params = []
        keys = jax.random.split(rng, len(sizes) - 1)
        for i, (fan_in, fan_out) in enumerate(zip(sizes[:-1], sizes[1:])):
            units = fan_out * (2 if maxout and i < len(sizes) - 2 else 1)
            if p.initial_weight_distribution == "uniform_adaptive":
                # reference's UniformAdaptive: +-sqrt(6/(fan_in+fan_out))
                scale = math.sqrt(6.0 / (fan_in + units))
                W = jax.random.uniform(keys[i], (fan_in, units), jnp.float32,
                                       -scale, scale)
            elif p.initial_weight_distribution == "normal":
                W = p.initial_weight_scale * jax.random.normal(
                    keys[i], (fan_in, units), jnp.float32)
            else:
                W = jax.random.uniform(keys[i], (fan_in, units), jnp.float32,
                                       -p.initial_weight_scale,
                                       p.initial_weight_scale)
            params.append((W, jnp.zeros(units, jnp.float32)))
        return params

    def _fit(self, job: Job, frame: Frame, di: DataInfo,
             valid: Optional[Frame]) -> DeepLearningModel:
        p: DeepLearningParameters = self.params
        cfg = _step_config(p, di)
        with obs.span("dl.matrix"):
            X0 = di.make_coded(frame)
            y, w = _targets(p, di, frame, X0)
            jax.block_until_ready((X0, y, w))
        n = frame.nrows

        maxout = p.activation.startswith("maxout")
        sizes = [sum(width for _, width in cfg.layout), *p.hidden,
                 cfg.out_dim]
        seed = p.effective_seed()
        rng = jax.random.PRNGKey(seed)
        rng, k0 = jax.random.split(rng)
        model = DeepLearningModel(job.dest_key or dkv.make_key(self.algo),
                                  p, di)
        params = self._init_params(k0, sizes, maxout)
        if p.checkpoint:
            prior = dkv.get(p.checkpoint)
            if prior is None:
                raise ValueError(f"checkpoint {p.checkpoint!r} not found")
            params = [(jnp.asarray(W), jnp.asarray(b))
                      for W, b in prior.output["weights"]]

        batch = min(p.mini_batch_size, n)
        rng, ks = jax.random.split(rng)
        with obs.span("dl.shuffle", rows=n):
            # the design stays as it is (the frame memoizes it, and the
            # training metrics score it in frame order); the sampler reads
            # this one copy, dropped when the last iteration has run
            table, codes = jax.block_until_ready(_sample_copy_fn(
                n, batch, bool(p.shuffle_training_data))(*X0, y, w, ks))

        # iteration sizing: train_samples_per_iteration semantics
        tspi = p.train_samples_per_iteration
        if tspi in (-1, 0):
            samples_per_iter = n
        elif tspi == -2:
            samples_per_iter = max(n // 10, batch * 16)   # auto-tune analog
        else:
            samples_per_iter = max(int(tspi), batch)
        total_samples = int(p.epochs * n)
        steps_per_iter = max(samples_per_iter // batch, 1)
        n_iters = max(total_samples // (steps_per_iter * batch), 1)

        train_steps, tx = _train_program(p, cfg, batch, steps_per_iter, n)

        opt_state = tx.init(params)
        # Commit params/opt_state to the replicated sharding explicitly:
        # the jit executable cache keys on input sharding+committedness, and
        # fresh eager arrays ("unspecified") vs committed arrays from a
        # previous run's outputs would compile TWO executables for the same
        # program (measured: a 5.7 s recompile inside a timed DL run, while
        # the warm-up had compiled the other variant).
        rep = NamedSharding(cluster().mesh, P())
        params = jax.device_put(params, rep)
        opt_state = jax.device_put(opt_state, rep)

        # A per-iteration host fetch of the mean loss makes the host wait
        # for the device every iteration.  Dispatch stays per-iteration
        # (async — XLA pipelines the queued steps; cancellation and fault
        # injection keep their per-iteration semantics), but the loss is
        # only FETCHED per iteration when early stopping needs it on host;
        # otherwise the whole history is one fetch at the end.
        history = []
        device_losses = []
        seen = 0
        import time as _time
        t0 = _time.time()
        from ..runtime import failure, scheduler
        stopped_at = n_iters
        with obs.span("dl.train", iterations=n_iters, steps=steps_per_iter,
                      batch=batch):
            for it in range(n_iters):
                failure.maybe_inject("dl_iter")
                # per-iteration device-lease yield (tree drivers yield at
                # chunk boundaries): co-resident jobs interleave here
                scheduler.DEVICE_LEASE.yield_turn()
                params, opt_state, mean_loss = train_steps(
                    params, opt_state, rng, it, table, codes)
                seen += steps_per_iter * batch
                obs.inc("dl_train_launches_total")
                obs.inc("dl_optimizer_steps_total", steps_per_iter)
                obs.inc("dl_samples_trained_total", steps_per_iter * batch)
                # progress snapshot: weights-so-far + remaining-epochs cursor;
                # resume() restores weights via the checkpoint path and trains
                # only the remaining epochs (throttled/async/best-effort)
                from ..runtime import snapshot as _snapshot
                _snapshot.maybe_snapshot(
                    job, model,
                    {"epochs_done": seen / n, "iteration": it,
                     "resume_params": {
                         "epochs": max(p.epochs - seen / n, 1e-3)}},
                    lambda ps=params: {
                        "weights": [(np.asarray(W), np.asarray(b))
                                    for W, b in ps],
                        "epochs_trained": seen / n,
                        "samples_trained": seen})
                if p.stopping_rounds:
                    entry = {"iteration": it, "epochs": seen / n,
                             "samples": seen, "training_loss": float(mean_loss),
                             "samples_per_sec": seen / max(_time.time() - t0,
                                                           1e-9)}
                    history.append(entry)
                    job.update((it + 1) / n_iters,
                               f"epoch {seen / n:.2f} "
                               f"loss {float(mean_loss):.5f}")
                    if stop_early(
                            [h["training_loss"] for h in history],
                            p.stopping_rounds, p.stopping_tolerance,
                            maximize=False):
                        stopped_at = it + 1
                        break
                else:
                    device_losses.append(mean_loss)       # device scalar only
                    job.update((it + 1) / n_iters, f"epoch {seen / n:.2f}")
            if not p.stopping_rounds and device_losses:
                # batched device_get: one prefetch pass, no per-n_iters
                # jnp.stack program compile
                iter_losses = np.asarray(jax.device_get(device_losses))
                dt = max(_time.time() - t0, 1e-9)
                seen = 0
                for it in range(stopped_at):
                    seen += steps_per_iter * batch
                    history.append({
                        "iteration": it, "epochs": seen / n, "samples": seen,
                        "training_loss": float(iter_losses[it]),
                        "samples_per_sec": seen / (dt * (it + 1) / stopped_at)})
        del table, codes        # the sampler's copy: scoring needs the room

        model.output["weights"] = [(np.asarray(W), np.asarray(b))
                                   for W, b in params]
        model.output["epochs_trained"] = seen / n
        model.output["samples_trained"] = seen
        model.scoring_history = history
        if not p.autoencoder:
            with obs.span("dl.score", rows=n):
                raw = model._predict_raw(X0)
                yy = di.response(frame)
                if not cfg.is_cls:
                    yy = jnp.nan_to_num(yy)
                model.training_metrics = make_metrics(di, raw, yy,
                                                      di.weights(frame))
                if valid is not None:
                    model.validation_metrics = model.model_performance(valid)
        return model
