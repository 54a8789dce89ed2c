"""Fused packed-ensemble traversal — the online scoring program.

One jitted program scores a ``[B, F]`` request batch against the whole
bitpacked ensemble (serving/pack.py layout): a ``depth``-step
``fori_loop`` advances every (row, tree) node pointer through the int32
word plane — no per-tree dispatch, no host loop — then class-reduces
and applies the link, all inside one executable.  The program registers
in the PR 10 compile ledger (``xprof.register_program("serve_score")``)
so serving executables are AOT-compiled once per batch signature, warm
at first request, and their flops/bytes are already Prometheus series.

Implementations mirror the hist.py convention:

* ``impl="xla"`` — gather-based traversal; what ``"auto"`` resolves to
  on every backend (``autotune.resolve_serve_impl``).
* ``impl="pallas"`` — batch-tiled Mosaic kernel, node planes in VMEM.
  It runs on a ``tpu`` backend only and raises anywhere else; today
  Mosaic refuses its 1-D gathers, so on the chip it fails at compile
  time with the compiler's own error.
* ``impl="pallas_interpret"`` — the same kernel in interpret mode, the
  parity twin the tests run.

``PackedScorer.score(..., score_mode=...)`` mirrors the
``hist_mode``/``split_mode`` knob convention: ``"packed"`` runs the
device program, ``"ref"`` the numpy ``ScoringModel`` walk, ``"check"``
runs both and raises on divergence.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..runtime import xprof
from ..runtime.config import config
from . import pack as packmod

_SCORE_MODES = ("packed", "ref", "check")


# ------------------------------------------------------------ traversal

def _step(nodes_i32, nodes_f32, X, node):
    """One depth step: advance every [B, R] node pointer (leaves self-loop)."""
    w = jnp.take(nodes_i32, node)
    leaf = (w >> packmod.LEAF_BIT) & 1
    feat = w & packmod.FEAT_MASK
    nal = (w >> packmod.NA_LEFT_BIT) & 1
    delta = (w >> packmod.DELTA_SHIFT) & packmod.DELTA_MASK
    thr = jnp.take(nodes_f32, node)
    x = jnp.take_along_axis(X, feat, axis=1)
    right = jnp.where(jnp.isnan(x), nal == 0, x >= thr).astype(jnp.int32)
    return node + jnp.where(leaf == 1, 0, delta + right)


def _traverse_xla(nodes_i32, nodes_f32, roots, X, depth: int):
    """[B, F] batch -> [B, R] leaf values, R = K*T trees."""
    B = X.shape[0]
    node = jnp.broadcast_to(roots[None, :], (B, roots.shape[0]))
    node = lax.fori_loop(
        0, depth, lambda _, n: _step(nodes_i32, nodes_f32, X, n), node)
    return jnp.take(nodes_f32, node)


def _make_pallas_traverse(depth: int, R: int, F: int, tile_b: int,
                          interpret: bool = False):
    """Batch-tiled kernel: node planes + roots resident in VMEM, one
    program instance per ``tile_b`` rows of the request batch."""
    from jax.experimental import pallas as pl

    def kernel(i32_ref, f32_ref, roots_ref, x_ref, out_ref):
        nodes_i32 = i32_ref[:]
        nodes_f32 = f32_ref[:]
        X = x_ref[:]
        node = jnp.broadcast_to(roots_ref[:][None, :], (tile_b, R))
        node = lax.fori_loop(
            0, depth, lambda _, n: _step(nodes_i32, nodes_f32, X, n), node)
        out_ref[:] = jnp.take(nodes_f32, node)

    def call(nodes_i32, nodes_f32, roots, X):
        B = X.shape[0]
        grid = (B // tile_b,)
        # XLA:TPU names the custom call after the enclosing scope, so the
        # scope is what the profiler's XLA Ops line shows (%serve_traverse.N)
        with jax.named_scope("serve_traverse"):
            return pl.pallas_call(
                kernel,
                name="serve_traverse",
                grid=grid,
                in_specs=[
                    pl.BlockSpec(nodes_i32.shape, lambda i: (0,)),
                    pl.BlockSpec(nodes_f32.shape, lambda i: (0,)),
                    pl.BlockSpec(roots.shape, lambda i: (0,)),
                    pl.BlockSpec((tile_b, F), lambda i: (i, 0)),
                ],
                out_specs=pl.BlockSpec((tile_b, R), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((B, R), jnp.float32),
                interpret=interpret,
            )(nodes_i32, nodes_f32, roots, X)

    return call


def _traverse_impl(impl: str, depth: int, R: int, F: int, B: int):
    """Resolve the traversal implementation for one batch signature."""
    if impl in ("", "auto"):
        from ..runtime import autotune
        impl = autotune.resolve_serve_impl(depth=depth, R=R, F=F, B=B)
    if impl == "xla":
        return functools.partial(_traverse_xla, depth=depth)
    if impl in ("pallas", "pallas_interpret"):
        interpret = impl == "pallas_interpret"
        if not interpret and jax.default_backend() != "tpu":
            raise ValueError(
                "serve impl='pallas' needs a tpu backend, found "
                f"{jax.default_backend()!r}; use 'xla', or "
                "'pallas_interpret' for the interpret-mode twin")
        tile_b = B if B <= 128 else 128
        while B % tile_b:
            tile_b //= 2
        return _make_pallas_traverse(depth, R, F, max(tile_b, 1),
                                     interpret=interpret)
    raise ValueError(f"unknown serve impl {impl!r} "
                     "(xla | pallas | pallas_interpret | auto)")


# ---------------------------------------------------------- the program

def _postprocess(sums, init, family: str, n_class: int, avg: bool,
                 ntrees: int, binomial: bool, link: str, c_norm: float,
                 xp=jnp):
    """[B, K] per-class leaf sums -> probability/score matrix.

    Mirrors ``ScoringModel._score_tree`` / ``_score_isolation`` exactly;
    ``xp`` swaps numpy in for the ref/check paths so both sides share
    one formula.
    """
    if family == "isolation":
        mean_len = sums[:, 0] / max(ntrees, 1)
        return xp.exp2(-mean_len / max(c_norm, 1e-9))[:, None]
    if n_class > 1:
        scores = sums + init[None, :]
        if avg:
            p = xp.clip(scores / max(ntrees, 1), 0, 1)
            return p / xp.maximum(p.sum(axis=1, keepdims=True), 1e-12)
        e = xp.exp(scores - scores.max(axis=1, keepdims=True))
        return e / e.sum(axis=1, keepdims=True)
    s = sums[:, 0] + init[0]
    if avg:
        s = s / max(ntrees, 1)
    if binomial:
        p1 = xp.clip(s if avg else 1 / (1 + xp.exp(-s)), 0.0, 1.0)
        return xp.stack([1 - p1, p1], axis=1)
    return (xp.exp(s) if link == "log" else s)[:, None]


class PackedScorer:
    """Device-resident packed ensemble + one AOT-compiled scoring program.

    Built from a numpy ``ScoringModel`` (mojo ``_extract`` output) — the
    scoring model stays attached as featurizer and as the "ref"/"check"
    oracle.  ``score(X)`` maps a raw f32 design batch to the probability
    matrix ``ScoringModel._score`` would produce; ``predict_rows`` adds
    row featurization and label decode for the REST realtime route.
    """

    def __init__(self, scoring_model, impl: Optional[str] = None):
        meta = scoring_model.meta
        if meta.get("family") not in ("tree", "isolation"):
            raise ValueError("packed serving supports tree/isolation "
                             f"ensembles, not {meta.get('family')!r}")
        self.ref = scoring_model
        self.meta = meta
        spec = meta["datainfo"]
        self.nfeatures = len(spec["specs"])
        self.packed = packmod.pack_ensemble(meta, scoring_model.arrays,
                                            self.nfeatures)
        self.impl = (impl if impl is not None
                     else config().serve_impl) or "auto"
        self.family = meta["family"]
        self.n_class = self.packed.n_class
        self.ntrees = self.packed.ntrees
        self.depth = self.packed.depth
        self.avg = bool(meta.get("tree_average", False))
        self.binomial = bool(spec.get("response_domain")) \
            and self.n_class == 1 and self.family == "tree"
        self.link = meta.get("link", "identity")
        self.c_norm = float(meta.get("c_norm", 1.0))
        init = meta.get("init_score", 0.0)
        self._init = np.atleast_1d(np.asarray(init, np.float32))
        # device residency: planes uploaded once, reused every launch
        self._d_i32 = jax.device_put(self.packed.nodes_i32)
        self._d_f32 = jax.device_put(self.packed.nodes_f32)
        self._d_roots = jax.device_put(self.packed.roots)
        self._d_init = jax.device_put(self._init)
        self._programs = {}

    # ------------------------------------------------------------ device
    def _program(self, B: int):
        """One ledger-registered executable per (batch, impl) signature."""
        key = (B, self.impl)
        prog = self._programs.get(key)
        if prog is None:
            R = int(self.packed.roots.shape[0])
            traverse = _traverse_impl(self.impl, self.depth, R,
                                      self.nfeatures, B)
            K, T = self.n_class, self.ntrees

            def score(nodes_i32, nodes_f32, roots, init, X):
                leaves = traverse(nodes_i32, nodes_f32, roots, X)
                sums = leaves.reshape(X.shape[0], K, T).sum(axis=2)
                return _postprocess(sums, init, self.family, K, self.avg,
                                    T, self.binomial, self.link,
                                    self.c_norm)

            prog = xprof.register_program("serve_score", jax.jit(score),
                                          orig=score)
            self._programs[key] = prog
        return prog

    # ----------------------------------------------------------- scoring
    def _packed_scores(self, X: np.ndarray) -> np.ndarray:
        prog = self._program(X.shape[0])
        out = prog(self._d_i32, self._d_f32, self._d_roots, self._d_init,
                   jnp.asarray(X, jnp.float32))
        return np.asarray(out)

    def _ref_scores(self, X: np.ndarray) -> np.ndarray:
        leaves = packmod.traverse(self.packed.nodes_i32,
                                  self.packed.nodes_f32,
                                  self.packed.roots, X, self.depth)
        sums = leaves.reshape(X.shape[0], self.n_class, self.ntrees) \
            .sum(axis=2)
        return _postprocess(sums, self._init, self.family, self.n_class,
                            self.avg, self.ntrees, self.binomial,
                            self.link, self.c_norm, xp=np)

    def score(self, X: np.ndarray,
              score_mode: Optional[str] = None) -> np.ndarray:
        """Raw f32 design batch ``[B, F]`` -> probability/score matrix."""
        mode = (score_mode if score_mode is not None
                else config().serve_score_mode) or "packed"
        if mode not in _SCORE_MODES:
            raise ValueError(f"score_mode {mode!r} not in {_SCORE_MODES}")
        X = np.ascontiguousarray(X, dtype=np.float32)
        if mode == "ref":
            return self._ref_scores(X)
        out = self._packed_scores(X)
        if mode == "check":
            ref = self._ref_scores(X)
            if not np.allclose(out, ref, rtol=1e-4, atol=1e-5,
                               equal_nan=True):
                diff = float(np.nanmax(np.abs(out - ref)))
                raise AssertionError(
                    f"score_mode='check' diverged: packed vs ref "
                    f"max|diff|={diff:.3e}")
        return out

    # --------------------------------------------------------- row plane
    def featurize(self, rows) -> np.ndarray:
        """List of row dicts -> raw f32 design matrix (cat codes, NaN)."""
        cols = {}
        for s in self.meta["datainfo"]["specs"]:
            name = s["name"]
            vals = [r.get(name) for r in rows]
            cols[name] = np.asarray(
                ["" if v is None else v for v in vals]
                if any(isinstance(v, str) for v in vals)
                else [np.nan if v is None else v for v in vals])
        return self.ref._design_raw(cols, len(rows))

    def decode(self, probs: np.ndarray) -> dict:
        """Probability matrix -> the ScoringModel.predict output shape."""
        domain = self.meta["datainfo"].get("response_domain")
        if domain and self.family == "tree":
            labels = np.asarray(domain, dtype=object)[
                np.argmax(probs, axis=1)]
            if probs.shape[1] == 2:
                thr = self.meta.get("default_threshold", 0.5)
                labels = np.asarray(domain, dtype=object)[
                    (probs[:, 1] >= thr).astype(int)]
            return {"predict": labels, "probabilities": probs}
        return {"predict": probs[:, 0]}

    def predict_rows(self, rows,
                     score_mode: Optional[str] = None) -> dict:
        return self.decode(self.score(self.featurize(rows),
                                      score_mode=score_mode))
