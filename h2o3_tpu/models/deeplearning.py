"""DeepLearning: multi-layer perceptron / autoencoder, data-parallel on TPU.

Reference: ``hex/deeplearning/`` — DeepLearning.java (driver main loop),
DeepLearningTask.java:17 (Hogwild! lock-free per-node SGD on a local weight
copy), DeepLearningTask2.java:44-61 (cluster model averaging),
Neurons.java:184/189 (per-row fprop/bprop with gemv row kernels :638),
Dropout.java, DeepLearningModelInfo.java (flat weight arrays, elastic
averaging :751-758).

TPU-native redesign (SURVEY.md §2.10): Hogwild + periodic averaging is an
artifact of JVM threads — synchronous data-parallel SGD is strictly better on
TPU, so each step is ONE jit-compiled program: minibatch gather from the
row-sharded design matrix, batched fprop/bprop as MXU matmuls (the per-row
gemv loops become [batch, features] @ [features, hidden]), gradients psum'd
over the mesh by GSPMD, optimizer update via optax (ADADELTA to match the
reference's adaptive-rate default, DeepLearningModelInfo rho/epsilon).
``train_samples_per_iteration`` keeps its reference semantics: samples
processed between scoring/early-stopping checks.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..frame.frame import Frame
from ..runtime import dkv
from ..runtime.job import Job
from .base import Model, ModelBuilder, Parameters
from .datainfo import DataInfo
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
    """THE DL forward pass — shared by predict-time ``Model._forward`` and
    the compiled training program (one implementation, so activation /
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


def _build_train_steps(activation: str, dropout_in: float, dropout_h: tuple,
                       loss_kind: str, is_cls: bool, autoenc: bool,
                       out_dim: int, l1: float, l2: float, opt_cfg: tuple,
                       batch: int, steps_per_iter: int, n: int,
                       custom_loss=None, compute_dtype=None):
    """Build the compiled training-interval program (see _make_train_steps
    for the caching story; ``custom_loss`` bypasses the cache)."""

    def forward(params, X, rng):
        return _forward_pass(activation, params, X, deterministic=False,
                             rng=rng, dropout_in=dropout_in,
                             dropout_hidden=dropout_h,
                             compute_dtype=compute_dtype)

    def loss_fn(params, xb, yb, wb, key):
        logits = forward(params, xb, key)
        if custom_loss is not None:
            pred = logits if (is_cls or autoenc) else logits[:, 0]
            per = custom_loss(pred, xb if autoenc else yb)
        elif autoenc:
            per = jnp.mean((logits - xb) ** 2, axis=1)
        elif is_cls:
            yi = jnp.clip(yb.astype(jnp.int32), 0, out_dim - 1)
            per = optax.softmax_cross_entropy_with_integer_labels(logits, yi)
        elif loss_kind == "absolute":
            per = jnp.abs(logits[:, 0] - yb)
        elif loss_kind == "huber":
            per = optax.huber_loss(logits[:, 0], yb, delta=1.0)
        else:
            per = (logits[:, 0] - yb) ** 2
        loss = jnp.sum(per * wb) / jnp.maximum(jnp.sum(wb), 1e-12)
        if l2 > 0 or l1 > 0:
            for W, _ in params:
                loss = loss + l2 * jnp.sum(W * W) + l1 * jnp.sum(jnp.abs(W))
        return loss

    kind, *hp = opt_cfg
    if kind == "adadelta":
        tx = optax.adadelta(learning_rate=1.0, rho=hp[0], eps=hp[1])
    elif kind == "sgd_momentum":
        tx = optax.sgd(hp[0], momentum=hp[1])
    else:
        tx = optax.sgd(hp[0])

    def sgd_step(X, y, w, carry, key):
        params, opt_state = carry
        k1, k2 = jax.random.split(key)
        # random-offset contiguous block instead of a per-row gather: a
        # [batch]-row gather from a big table is far slower on TPU than a
        # contiguous read; dynamic_slice streams at HBM rate.  The rows
        # were permuted once up front (shuffle_training_data) and the
        # arrays carry a wraparound copy of the first `batch` rows
        # (_extend_for_blocks), so offsets draw uniformly over [0, n) and
        # every row has identical inclusion probability (a [0, n-batch]
        # range would under-sample both array ends by up to batch x).
        off = jax.random.randint(k1, (), 0, max(n, 1))
        xb = jax.lax.dynamic_slice_in_dim(X, off, batch, axis=0)
        yb = jax.lax.dynamic_slice_in_dim(y, off, batch, axis=0)
        wb = jax.lax.dynamic_slice_in_dim(w, off, batch, axis=0)
        loss, grads = jax.value_and_grad(loss_fn)(params, xb, yb, wb, k2)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state), loss

    @jax.jit
    def train_steps(params, opt_state, rng0, it, X, y, w):
        # keys derive in-jit from (rng0, iteration), so the driver loop
        # dispatches nothing but the step program
        keys = jax.random.split(jax.random.fold_in(rng0, it), steps_per_iter)
        (params, opt_state), losses = jax.lax.scan(
            functools.partial(sgd_step, X, y, w), (params, opt_state), keys)
        return params, opt_state, jnp.mean(losses)

    return train_steps, tx


@functools.lru_cache(maxsize=None)
def _shuffle_fn(n: int, padded: int):
    """One compiled row-permutation program per (n, padded) geometry."""
    @jax.jit
    def sh(X, y, w, key):
        perm = jax.random.permutation(key, n)
        idx = jnp.concatenate([perm, jnp.arange(n, padded)])
        return (jnp.take(X, idx, axis=0), jnp.take(y, idx),
                jnp.take(w, idx))
    return sh


@functools.lru_cache(maxsize=None)
def _extend_fn(n: int, batch: int):
    """Append a wraparound copy of the first `batch` rows so the block
    sampler's dynamic_slice at any offset in [0, n) stays in bounds."""
    @jax.jit
    def ext(X, y, w):
        return (jnp.concatenate([X[:n], X[:batch]], axis=0),
                jnp.concatenate([y[:n], y[:batch]]),
                jnp.concatenate([w[:n], w[:batch]]))
    return ext


@functools.lru_cache(maxsize=None)
def _make_train_steps(activation: str, dropout_in: float, dropout_h: tuple,
                      loss_kind: str, is_cls: bool, autoenc: bool,
                      out_dim: int, l1: float, l2: float, opt_cfg: tuple,
                      batch: int, steps_per_iter: int, n: int,
                      compute_dtype=None):
    """Compiled training-interval program, CACHED ACROSS train() calls.

    The per-call ``@jax.jit def train_steps`` pattern recompiled (and paid
    the remote backend's multi-second first-execution penalty) on every
    train() — bench.py's warmup model compiled a program the timed model
    then could not reuse (measured on chip: the timed MNIST run spent most
    of its wall clock there, reporting 2.7k samples/s).  Everything the
    program closes over is reconstructed from hashable config; the data
    (X, y, w) are traced arguments, so any same-shaped training run reuses
    the executable.  Returns (train_steps, tx).
    """
    return _build_train_steps(activation, dropout_in, dropout_h, loss_kind,
                              is_cls, autoenc, out_dim, l1, l2, opt_cfg,
                              batch, steps_per_iter, n,
                              compute_dtype=compute_dtype)


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

    def _forward(self, params, X, deterministic=True, rng=None,
                 dropout_in=0.0, dropout_hidden=()):
        return _forward_pass(self.params.activation, params, X,
                             deterministic=deterministic, rng=rng,
                             dropout_in=dropout_in,
                             dropout_hidden=tuple(dropout_hidden))

    def _predict_raw(self, X: jax.Array) -> jax.Array:
        params = [(jnp.asarray(W), jnp.asarray(b))
                  for W, b in self.output["weights"]]
        logits = self._forward(params, X)
        if self.params.autoencoder:
            return logits
        if self.datainfo.is_classifier:
            return jax.nn.softmax(logits, axis=1)
        mu = logits[:, 0]
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
            di.make_matrix(frame)))[: frame.nrows].astype(np.float64)
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
        di = self.datainfo
        X = di.make_matrix(frame)
        R = self._predict_raw(X)
        err = np.asarray(jnp.mean((R - X) ** 2, axis=1))[: frame.nrows]
        return Frame(["Reconstruction.MSE"], [Vec.from_numpy(err, T_NUM)])


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
        X = di.make_matrix(frame)
        n = frame.nrows
        is_cls = di.is_classifier and not p.autoencoder
        if p.autoencoder:
            y = jnp.zeros(X.shape[0], jnp.float32)
            out_dim = X.shape[1]
        elif is_cls:
            y = di.response(frame)
            out_dim = di.nclasses
        else:
            y = di.response(frame)
            if di.standardize:
                y = (y - di.response_mean) / di.response_sigma
            y = jnp.nan_to_num(y)
            out_dim = 1
        w = di.weights(frame)

        maxout = p.activation.startswith("maxout")
        sizes = [X.shape[1], *p.hidden, out_dim]
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

        batch = min(p.mini_batch_size, n)
        X0 = X                      # unshuffled view for final scoring
        if p.shuffle_training_data:
            rng, ks = jax.random.split(rng)
            X, y, w = _shuffle_fn(n, X.shape[0])(X, y, w, ks)
        X, y, w = _extend_fn(n, batch)(X, y, w)
        cd = jnp.bfloat16 if p.precision == "bf16" else None

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

        if p.custom_loss_func is None:
            # cached across train() calls: same architecture/config/shapes
            # reuse one executable (no recompile, no first-exec penalty)
            train_steps, tx = _make_train_steps(
                p.activation, p.input_dropout_ratio, dropout_h, loss_kind,
                is_cls, p.autoencoder, out_dim, p.l1, p.l2, opt_cfg,
                batch, steps_per_iter, n, compute_dtype=cd)
        else:
            # custom python loss: not hashable — same builder, uncached
            train_steps, tx = _build_train_steps(
                p.activation, p.input_dropout_ratio, dropout_h, loss_kind,
                is_cls, p.autoencoder, out_dim, p.l1, p.l2, opt_cfg,
                batch, steps_per_iter, n, custom_loss=p.custom_loss_func,
                compute_dtype=cd)

        opt_state = tx.init(params)
        # Commit params/opt_state to the replicated sharding explicitly:
        # the jit executable cache keys on input sharding+committedness, and
        # fresh eager arrays ("unspecified") vs committed arrays from a
        # previous run's outputs would compile TWO executables for the same
        # program (measured: a 5.7 s recompile inside bench.py's timed DL
        # run, while the warmup had compiled the other variant).
        from jax.sharding import NamedSharding, PartitionSpec
        from ..runtime.cluster import cluster
        rep = NamedSharding(cluster().mesh, PartitionSpec())
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
        for it in range(n_iters):
            failure.maybe_inject("dl_iter")
            # per-iteration device-lease yield (tree drivers yield at
            # chunk boundaries): co-resident jobs interleave here
            scheduler.DEVICE_LEASE.yield_turn()
            params, opt_state, mean_loss = train_steps(params, opt_state,
                                                       rng, it, X, y, w)
            seen += steps_per_iter * batch
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

        model.output["weights"] = [(np.asarray(W), np.asarray(b))
                                   for W, b in params]
        model.output["epochs_trained"] = seen / n
        model.output["samples_trained"] = seen
        model.scoring_history = history
        if not p.autoencoder:
            raw = model._predict_raw(X0)
            yy = di.response(frame) if is_cls else jnp.nan_to_num(di.response(frame))
            model.training_metrics = make_metrics(di, raw, yy, di.weights(frame))
            if valid is not None:
                model.validation_metrics = model.model_performance(valid)
        return model
