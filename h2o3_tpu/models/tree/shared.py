"""Shared tree infrastructure: level-wise growth driver + ensemble scoring.

Reference: ``hex/tree/SharedTree.java:29`` (Driver:231, scoreAndBuildTrees:483,
buildLayer:561), ``hex/tree/DTree.java`` (in-progress tree),
``hex/tree/CompressedTree`` (packed scoring form), ``hex/tree/Score.java``.

TPU-native redesign: a tree level is three fused device programs (histogram ->
split-search -> partition, see hist.py); a finished tree is a set of per-level
arrays (feature, threshold, NA-direction, valid) + leaf values — the
CompressedTree analog, directly gather-traversable on device.  Ensemble
prediction stacks trees per level and lax.scan's over them: depth gathers per
tree, all batched over rows on the VPU.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ...frame.frame import Frame
from ...frame.vec import T_CAT
from ...runtime import dkv
from ...runtime.job import Job
from ..base import Model, ModelBuilder, Parameters
from ..datainfo import DataInfo, ColumnSpec
from ..scorekeeper import stop_early, metric_direction
from ..distributions import make_distribution
from .binning import BinnedFrame, fit_bins, encode_bins
from .hist import (_ledger, make_hist_fn, make_varbin_hist_fn,
                   make_subtract_level_fn, make_batched_level_fn,
                   make_scan_level_fn, make_batched_scan_level_fn,
                   make_sparse_level_fn, make_batched_sparse_level_fn,
                   sparse_slot_budget, sparse_slot_maps, hist_kernel_kind,
                   offset_codes, best_splits,
                   fused_best_splits, fused_best_splits_batched,
                   partition, partition_right,
                   table_lookup, traverse_block, walk_block_rows)


@contextlib.contextmanager
def level_phase(phase: str, level: int):
    """Host-side span around one per-level phase (hist/split/partition).

    The level loop runs at TRACE time inside ``jax.jit``, so inside a
    jitted build ``span_seconds{span="tree_phase"}`` is trace-time cost,
    once per compile (the device-side timeline stays ``jax.profiler``'s
    job), and ``jax.named_scope(phase)`` puts the phase into the op
    metadata of everything traced here, for whoever opens the trace in
    xprof.  Around an EAGER phase call the span times real execution."""
    from ...runtime import observability as obs
    with obs.span("tree_phase", phase=phase, level=level), \
            jax.named_scope(phase):
        yield


@dataclasses.dataclass
class SharedTreeParameters(Parameters):
    ntrees: int = 50
    max_depth: int = 5
    min_rows: float = 10.0
    nbins: int = 64                  # quantile-sketch bins (ref nbins=20)
    histogram_type: str = "QuantilesGlobal"   # UniformAdaptive | Random
    # {column: 1|-1} — numeric features, binomial/regression only
    # (hex/tree/gbm monotone_constraints; enforced via split rejection +
    # propagated value-bound clamping, the XGBoost mechanism)
    monotone_constraints: Optional[dict] = None
    learn_rate: float = 0.1
    sample_rate: float = 1.0
    col_sample_rate: float = 1.0         # per split (mtries analog)
    col_sample_rate_per_tree: float = 1.0
    min_split_improvement: float = 1e-5
    reg_lambda: float = 0.0
    reg_alpha: float = 0.0               # L1 on leaf values (XGBoost alpha)
    gamma: float = 0.0                   # min loss reduction (XGBoost gamma)
    min_child_weight: float = 0.0        # min child hessian sum (XGBoost)
    distribution: str = "auto"
    tweedie_power: float = 1.5
    quantile_alpha: float = 0.5
    huber_alpha: float = 0.9
    score_tree_interval: int = 5
    stopping_rounds: int = 0
    standardize: bool = False            # trees never standardize
    hist_precision: str = "bf16"         # f32 for exact reproducibility
    # histogram build strategy per level (DHistogram/gpu_hist sibling trick):
    #   "subtract" (default) — compact each parent's SMALLER child into a
    #     dense row prefix, histogram only those <= N/2 rows, reconstruct
    #     the larger sibling as parent - small (hist.make_subtract_level_fn);
    #   "full"     — histogram every child from all N rows (the oracle);
    #   "auto"     (default) — the cost-model autotuner picks per
    #     (shape, depth, K, mesh) signature (runtime/autotune.py); with
    #     H2O3_TPU_AUTOTUNE=off this is exactly "subtract".
    hist_mode: str = "auto"
    # split-search strategy per level (mirrors hist_mode):
    #   "fused"    (default) — single-pass winner-record kernel between the
    #     histogram and the tiny feature-argmax epilogue (hist.py
    #     fused_best_splits; off-TPU the bit-identical XLA twin), and
    #     multinomial/DRF-multiclass/uplift rounds grow their K trees as
    #     ONE batched level program (one kernel launch per level);
    #   "separate" — the multi-pass best_splits oracle + sequential
    #     K-iteration class loops (the pre-batching pipeline, kept whole);
    #   "auto"     (default) — autotuner-decided, as with hist_mode
    #     ("fused" with the tuner off).
    # Monotone constraints and EFB bundling stay on the separate path
    # (drivers downgrade automatically).
    split_mode: str = "auto"
    # per-level histogram LAYOUT (mirrors hist_mode/split_mode):
    #   "auto"   (default) — dense [2^d, F, B] slot grids above
    #     sparse_depth_threshold, node-sparse [A, F, B] slots keyed by the
    #     compacted row prefix below it (hist.make_sparse_level_fn):
    #     histogram bytes scale with ALIVE leaves instead of 2^d, so the
    #     64 MB histogram budget no longer caps tree depth;
    #   "dense"  — the dense grid at every level (the oracle);
    #   "sparse" — force the sparse layout below the threshold even when
    #     "auto" would (identically) pick it; fails fast when it cannot
    #     engage (hist_mode="full" has no carry to subtract from).
    # Monotone constraints and EFB bundling stay dense (drivers downgrade
    # automatically, as with split_mode).
    hist_layout: str = "auto"
    # first sparse level under hist_layout auto/sparse (expert knob): level
    # d >= threshold histograms in slot space.  Clamped per frame to the
    # dense memory cap so the dense levels above it always fit the budget.
    sparse_depth_threshold: int = 8
    # whole-tree program STRUCTURE (mirrors hist_mode/split_mode):
    #   "level" — the level loop is unrolled at TRACE time inside one jit:
    #     the compiled program holds one hist + one split kernel per level
    #     (2*depth compiled launches per tree) — the pre-scan pipeline,
    #     kept whole as the oracle;
    #   "scan"  — the level loop becomes a lax.scan over levels inside the
    #     same jitted program: fixed-width padded levels with alive-slot
    #     masking, the early-exit fence a scan-carried on-device
    #     predicate, O(1) compiled kernel programs per tree regardless of
    #     depth (and a far smaller program to compile for deep trees);
    #   "auto"  (default) — autotuner-decided, as with hist_mode ("level"
    #     with the tuner off — bit-identical to the pre-scan pipeline).
    # Monotone constraints, EFB bundling, node-sparse deep levels and
    # depth-1 trees stay on the level path ("auto" downgrades
    # automatically; uplift always grows level-wise).  Both programs run
    # the variable-bin kernel where the frame packs and the width fits
    # (hist_site_kernel): "scan" forfeits no kernel.
    tree_program: str = "auto"
    # probability calibration (hex/tree CalibrationHelper)
    calibrate_model: bool = False
    calibration_frame: Optional[object] = None
    calibration_method: str = "platt"    # platt | isotonic
    # bit-reproducible runs (the reference's `reproducible` flag): forces
    # f32 histogram accumulation so sums don't depend on bf16 rounding;
    # psum ordering is already deterministic for a FIXED mesh shape —
    # results vary across different device counts, as in the reference
    # when node counts change
    reproducible: bool = False
    # exclusive feature bundling for wide/sparse frames (efb.py):
    # "auto" engages only when the packed-kernel cost drops enough to win
    efb: str = "auto"                    # auto | off

    @property
    def effective_hist_precision(self) -> str:
        return "f32" if self.reproducible else self.hist_precision


@dataclasses.dataclass
class Tree:
    """One grown tree — the CompressedTree analog (host-side)."""
    feat: List[np.ndarray]       # per level [2^d] int32
    thr: List[np.ndarray]        # per level [2^d] float32
    na_left: List[np.ndarray]    # per level [2^d] bool
    valid: List[np.ndarray]      # per level [2^d] bool
    values: np.ndarray           # [2^depth] float32
    cover: Optional[np.ndarray] = None   # [2^depth] weighted leaf counts


def stack_trees(trees: List[Tree]):
    """[T, ...] per-level stacks for compiled whole-ensemble traversal."""
    depth = len(trees[0].feat)
    levels = []
    # jnp.stack keeps device-resident per-level arrays on device — no
    # host round-trip per tree (matters for per-tree valid scoring)
    for d in range(depth):
        levels.append((
            jnp.stack([jnp.asarray(t.feat[d]) for t in trees]),
            jnp.stack([jnp.asarray(t.thr[d]) for t in trees]),
            jnp.stack([jnp.asarray(t.na_left[d]) for t in trees]),
            jnp.stack([jnp.asarray(t.valid[d]) for t in trees])))
    values = jnp.stack([jnp.asarray(t.values) for t in trees])
    return levels, values


@dataclasses.dataclass
class StackedTrees:
    """Device-resident whole-ensemble form: per-level [T, 2^d] stacks.

    This is the canonical trained-tree storage — trees never round-trip
    through host during training (the driver loop appends whole chunks of
    scanned trees), and traversal consumes it directly.  ``to_tree_list``
    materializes per-tree host ``Tree`` objects only when something needs
    them (MOJO export, SHAP, tests).
    """

    levels: List[tuple]          # per depth: (feat, thr, na_left, valid)
    values: jax.Array            # [T, 2^depth]
    covers: Optional[jax.Array] = None   # [T, 2^depth] leaf covers

    @property
    def ntrees(self) -> int:
        return int(self.values.shape[0])

    @property
    def depth(self) -> int:
        return len(self.levels)

    @staticmethod
    def from_trees(trees: List[Tree]) -> "StackedTrees":
        levels, values = stack_trees(trees)
        covers = None
        if all(t.cover is not None for t in trees):
            covers = jnp.stack([jnp.asarray(t.cover) for t in trees])
        return StackedTrees(levels, values, covers)

    @staticmethod
    def concat(chunks: Sequence["StackedTrees"]) -> "StackedTrees":
        """Host-side concatenation.  Tree metadata is kilobytes; a device
        ``jnp.concatenate`` here compiled one program per (level, array,
        chunk-count) geometry — measured 9.3 s of XLA compiles inside the
        bench's timed 50-tree train (chunk counts the warmup never saw).
        The fetch is ONE ``jax.device_get`` over every chunk array: it
        prefetches all transfers async, so the whole pull costs ~one round
        trip instead of one per array."""
        if len(chunks) == 1:
            return chunks[0]
        if any(c.depth != chunks[0].depth for c in chunks):
            raise ValueError(
                "StackedTrees.concat: chunks disagree on depth "
                f"({[c.depth for c in chunks]}); continuation stacks must "
                "share one effective depth (validate_checkpoint_depth)")
        host = jax.device_get([
            [[c.levels[d][i] for i in range(4)]
             for d in range(c.depth)] +
            [c.values, c.covers if c.covers is not None else np.zeros(0)]
            for c in chunks])
        depth = chunks[0].depth
        levels = []
        for d in range(depth):
            levels.append(tuple(
                np.concatenate([h[d][i] for h in host], axis=0)
                for i in range(4)))
        values = np.concatenate([h[depth] for h in host], axis=0)
        covers = None
        if all(c.covers is not None for c in chunks):
            covers = np.concatenate([h[depth + 1] for h in host], axis=0)
        return StackedTrees(levels, values, covers)

    def to_tree_list(self) -> List[Tree]:
        """Host materialization — one batched fetch, then slices."""
        host_levels, values, covers = jax.device_get(
            [[tuple(a for a in lv) for lv in self.levels], self.values,
             self.covers if self.covers is not None else np.zeros(0)])
        if self.covers is None:
            covers = None
        out = []
        for t in range(values.shape[0]):
            out.append(Tree(
                feat=[lv[0][t] for lv in host_levels],
                thr=[lv[1][t] for lv in host_levels],
                na_left=[lv[2][t] for lv in host_levels],
                valid=[lv[3][t] for lv in host_levels],
                values=values[t],
                cover=covers[t] if covers is not None else None))
        return out


class TreeListMulti:
    """Lazy per-round list of per-class ``Tree`` lists (multinomial form).

    ``output["trees"][t][k]`` — materialized from the K per-class
    ``StackedTrees`` only on first index, mirroring ``TreeList``.
    """

    def __init__(self, stacks: List[StackedTrees]):
        self._stacks = stacks
        self._cache: Optional[List[list]] = None

    def _mat(self) -> List[list]:
        if self._cache is None:
            per_class = [s.to_tree_list() for s in self._stacks]
            self._cache = [list(t) for t in zip(*per_class)]
        return self._cache

    def __len__(self):
        return self._stacks[0].ntrees

    def __getitem__(self, i):
        return self._mat()[i]

    def __iter__(self):
        return iter(self._mat())

    def __getstate__(self):
        return {"trees": self._mat()}

    def __setstate__(self, state):
        self._cache = state["trees"]
        self._stacks = [
            StackedTrees.from_trees([t[k] for t in self._cache])
            for k in range(len(self._cache[0]))]


class TreeList:
    """Lazy list-of-``Tree`` view over a ``StackedTrees``.

    Keeps ``model.output["trees"]`` available to export/inspection code
    without pulling the ensemble to host unless someone actually indexes it.
    """

    def __init__(self, stacked: StackedTrees):
        self._stacked = stacked
        self._cache: Optional[List[Tree]] = None

    def _mat(self) -> List[Tree]:
        if self._cache is None:
            self._cache = self._stacked.to_tree_list()
        return self._cache

    def __len__(self):
        return self._stacked.ntrees

    def __getitem__(self, i):
        return self._mat()[i]

    def __iter__(self):
        return iter(self._mat())

    def __getstate__(self):
        return {"trees": self._mat()}

    def __setstate__(self, state):
        self._cache = state["trees"]
        self._stacked = StackedTrees.from_trees(self._cache)


# The deepest ensemble the blocked walk takes: the deepest of which two
# trees' node tables fit one launch's SMEM.  No crossover in time comes
# first: on the v5e the blocked walk is 87x ahead of the per-level walk at
# depth 6, 15x at 10 and 6.7x at 14 (PERF.md section 6, PR 31).
TRAVERSE_BLOCK_DEPTH = 14


def _on_tpu() -> bool:
    from ...runtime.cluster import cluster
    return cluster().mesh.devices.flat[0].platform == "tpu"


def traverse_path(depth: int, F: int) -> str:
    """Which walk ``traverse`` takes for an ensemble of this depth over F
    features: "block" (hist.traverse_block, a kernel for the TPU) or
    "level"."""
    if _on_tpu() and depth <= TRAVERSE_BLOCK_DEPTH and walk_block_rows(F):
        return "block"
    return "level"


def traverse(levels, values, X):
    """Sum of leaf values over stacked trees for raw feature matrix X [N, F].

    A row goes right at a node where its feature is at least the threshold
    (NaN: where NA does not go left) and the node splits at all; leaves are
    summed in float32, in tree order.  On the TPU shallow ensembles are
    walked block by block with the rows resident on the core
    (hist.traverse_block); deeper ones, frames too wide for a block and
    every other backend, level by level over whole columns.  Both give the
    same bits."""
    if traverse_path(len(levels), X.shape[1]) == "block":
        return traverse_block(levels, values, X)
    return _traverse_levels(levels, values, X)


def _traverse_levels(levels, values, X):
    """The walk for deep ensembles, and for every ensemble off the TPU: scan
    over trees; per level one ``table_lookup`` of the node parameters by
    every row's node index, the row's feature by selects over X's columns,
    compare, descend.  Every level is a pass over all rows, whatever its
    width."""
    N, Fdim = X.shape
    big = jnp.finfo(jnp.float32).max

    def one_tree(carry, tree_slices):
        acc = carry
        node = jnp.zeros(N, jnp.int32)
        for (feat, thr, na_left, valid) in tree_slices[0]:
            L = feat.shape[0]
            # the look-up is a product with a one-hot, in which an infinite
            # threshold would turn the whole level's into NaN
            tbl = jnp.stack([feat.astype(jnp.float32),
                             jnp.clip(thr, -big, big),
                             na_left.astype(jnp.float32),
                             valid.astype(jnp.float32)], axis=0)
            t = table_lookup(tbl, node, L)
            f = t[0].astype(jnp.int32)
            x = jnp.zeros(N, X.dtype)
            for fi in range(Fdim):
                x = jnp.where(f == fi, X[:, fi], x)
            right = jnp.where(jnp.isnan(x), t[2] <= 0.5, x >= t[1])
            right = right & (t[3] > 0.5)
            node = 2 * node + right.astype(jnp.int32)
        V = tree_slices[1].shape[0]
        acc = acc + table_lookup(tree_slices[1][None, :], node, V)[0]
        return acc, None

    # lax.scan needs uniform pytrees; reorganize levels per tree via index map
    T = values.shape[0]

    def body(acc, i):
        slices = tuple((lv[0][i], lv[1][i], lv[2][i], lv[3][i])
                       for lv in levels)
        return one_tree(acc, (slices, values[i]))

    acc = jnp.zeros(N, jnp.float32)
    acc, _ = jax.lax.scan(lambda c, i: body(c, i), acc, jnp.arange(T))
    return acc


traverse_jit = jax.jit(traverse)


def dense_mem_cap(nbins: int, F: int) -> int:
    """Deepest level whose dense [2^d, F, B] histogram fits the 64 MB
    device budget — the memory wall the node-sparse layout removes."""
    B = nbins + 1
    mem_cap = 1
    while (mem_cap < 24
           and F * B * 3 * 2 ** mem_cap * 4 <= 64 * 1024 * 1024):
        mem_cap += 1
    return mem_cap


def effective_max_depth(max_depth: int, nbins: int, F: int,
                        n_padded: int, hist_layout: str = "dense",
                        sparse_depth_threshold: int = 8) -> int:
    """Depth cap, shared by EVERY consumer of the build factories (the
    scan drivers and checkpoint validation must agree with the tree
    builder on the level count).

    Dense levels are FULL-WIDTH [2^d] arrays (that is what makes every
    per-level op a dense matmul), so histogram memory doubles per level;
    the reference's node-sparse trees have no such coupling and default to
    depth 20 (DRF).  Cap where (a) a balanced tree would run out of rows
    (2^d > n admits only chain-shaped deeper trees, which terminal-leaf
    masking reproduces as no-op levels), and (b) — dense layout only —
    the per-level histogram would exceed a 64 MB device budget.  With the
    node-sparse layout engaged (``hist_layout`` "sparse"/"auto", passed
    here ALREADY RESOLVED for downgrades — see sparse_layout_active) the
    memory bound applies only to the dense levels above the threshold:
    the builder clamps the threshold itself to dense_mem_cap and the
    sparse levels' slot axis is budget-sized (hist.sparse_slot_budget),
    so depth becomes row/compute-bound.  Growth virtually always stops
    earlier via min_rows/purity (valid masking); configs asking for more
    depth get the capped tree — a documented design bound."""
    row_cap = max(1, int(np.ceil(np.log2(max(n_padded, 2)))) + 1)
    if hist_layout in ("sparse", "auto"):
        return max(1, min(max_depth, row_cap))
    return max(1, min(max_depth, row_cap, dense_mem_cap(nbins, F)))


def record_effective_depth(model, params, F: int, n_padded: int,
                           hist_layout: str = "dense") -> int:
    """Record requested vs effective depth in model.output and WARN when the
    dense-level bound caps the user's max_depth — the divergence from the
    reference's node-sparse trees (which honor depth 20+) must be visible,
    not silent (ADVICE round-4 medium finding).  ``hist_layout`` is the
    driver-RESOLVED layout (resolve_hist_layout), so a sparse-capable run
    records — and gets — the uncapped depth."""
    import warnings
    eff = effective_max_depth(
        params.max_depth, params.nbins, F, n_padded, hist_layout,
        getattr(params, "sparse_depth_threshold", 8))
    model.output["requested_max_depth"] = params.max_depth
    model.output["effective_max_depth"] = eff
    model.output["hist_layout"] = hist_layout
    if eff < params.max_depth:
        hint = ("rows bound the tree" if hist_layout != "dense" else
                "full-width [2^d] levels double histogram memory per "
                "level; hist_layout='auto' lifts the memory bound")
        warnings.warn(
            f"max_depth={params.max_depth} is capped to {eff} on this frame "
            f"({hint}; {F} features x {params.nbins} bins "
            f"x {n_padded} rows). Trees train at depth {eff}; lower "
            f"max_depth to silence this.", stacklevel=3)
    return eff


def validate_checkpoint_depth(prior, k, params, F: int, n_padded: int,
                              hist_layout: str = "dense"):
    """Continuation chunks must stack at ONE depth: the depth cap depends
    on the frame size AND the resolved histogram layout, so a continuation
    on a differently-sized frame (or with the other layout) could disagree
    with the checkpoint's level count — fail clearly instead of
    mis-stacking."""
    eff = effective_max_depth(
        params.max_depth, params.nbins, F, n_padded, hist_layout,
        getattr(params, "sparse_depth_threshold", 8))
    pd = prior_stacked(prior, k).depth
    if pd != eff:
        raise ValueError(
            f"checkpoint tree depth {pd} != effective depth {eff} on this "
            f"frame (depth cap under hist_layout={hist_layout!r}); continue "
            f"on a similarly sized frame with the same layout or lower "
            f"max_depth to {pd}")


def _per_k(x, extra_dims: int):
    """Broadcast a per-member ``[K]`` parameter against ``extra_dims``
    trailing axes; scalars pass through untouched so the scalar
    (non-grid) trace stays byte-identical."""
    return x.reshape(x.shape + (1,) * extra_dims) \
        if getattr(x, "ndim", 0) else x


def _level_geometry(max_depth: int, nbins: int, F: int, n_padded: int,
                    hist_mode: str, hist_layout: str,
                    sparse_depth_threshold: int):
    """The slot geometry of a build's levels: ``(effective max_depth,
    sparse_from, A_lv, Ap_lv, kern_L)``.  ``kern_L[d]`` is the slot count
    level ``d``'s histogram kernel runs at, never narrower than the level
    above it: the subtract path histograms at the PARENT slot count
    (2^(d-1)), the full oracle at the child count, a node-sparse level in
    its previous level's slot space.  The scan program's two sites are the
    first and the last of them."""
    max_depth = effective_max_depth(max_depth, nbins, F, n_padded,
                                    hist_layout, sparse_depth_threshold)
    # first node-sparse level: the threshold clamps to the dense memory
    # cap so every dense level above it fits the budget, and to >= 1 so
    # the root level (whose carry seeds the chain) is always dense
    t0 = max(1, min(sparse_depth_threshold, dense_mem_cap(nbins, F)))
    sparse_from = t0 if (hist_layout == "sparse" and max_depth > t0) \
        else max_depth
    A_cap = sparse_slot_budget(F, nbins + 1)
    # slot capacity per sparse level, and the PREVIOUS level's slot space
    # (the carry/compaction geometry) — at the boundary that is the dense
    # parent id space, so the first sparse level consumes the dense
    # subtract carry unchanged
    A_lv = {d: min(2 ** d, A_cap) for d in range(sparse_from, max_depth)}
    Ap_lv = {d: (2 ** (d - 1) if d == sparse_from else A_lv[d - 1])
             for d in range(sparse_from, max_depth)}
    kern_L = [Ap_lv[d] if d >= sparse_from
              else (2 ** d if hist_mode == "full" else 2 ** max(d - 1, 0))
              for d in range(max_depth)]
    return max_depth, sparse_from, A_lv, Ap_lv, kern_L


def hist_site_kernel(L: int, F: int, nbins: int, bin_counts=None) -> str:
    """``"varbin"`` | ``"uniform"`` | ``"einsum"``: the kernel that
    histograms a site of ``L`` slots of this frame on the live mesh.  A
    level of the level-unrolled build, the scan build's width and the
    driver's counter all ask here, with what a builder can observe in its
    input: the frame's ``bin_counts``, ``nbins``, ``F`` and the slot count
    (the bounds themselves are hist.hist_kernel_kind's)."""
    return hist_kernel_kind(
        L, F, nbins + 1, varbin=varbin_kernel_engages(bin_counts, nbins, F),
        on_tpu=_on_tpu())


def count_hist_kernel(tree_program: str, max_depth: int, nbins: int, F: int,
                      n_padded: int, *, bin_counts=None,
                      hist_mode: str = "subtract",
                      hist_layout: str = "dense",
                      sparse_depth_threshold: int = 8) -> None:
    """The tree drivers' once-a-fit record of the kernel their build's
    WIDEST histogram level runs, and under which program:
    ``tree_hist_kernel_total{program, kernel}``.  The scan program is one
    width, so its widest level's kernel is the whole build's."""
    from ...runtime import observability as obs
    *_, kern_L = _level_geometry(max_depth, nbins, F, n_padded, hist_mode,
                                 hist_layout, sparse_depth_threshold)
    obs.inc("tree_hist_kernel_total", program=tree_program,
            kernel=hist_site_kernel(kern_L[-1], F, nbins, bin_counts))


@functools.lru_cache(maxsize=None)
def make_build_tree_fn(max_depth: int, nbins: int, F: int, n_padded: int,
                       hist_precision: str = "bf16", bin_counts=None,
                       mono=None, plan=None, hist_mode: str = "subtract",
                       nk: int = 1, split_mode: str = "separate",
                       hist_layout: str = "dense",
                       sparse_depth_threshold: int = 8,
                       tree_program: str = "level"):
    """One compiled program that grows a whole tree on device.

    The level loop (SharedTree.buildLayer) is unrolled inside a single jit:
    histogram -> split-search -> threshold lookup -> partition per level,
    then final-leaf Newton values — zero host syncs per tree.  Returns
    (per-level (feat, thr, na_left, valid) tuples, leaf values, final leaf
    assignment), all device-resident.

    ``hist_mode`` picks the per-level histogram strategy:
    ``"subtract"`` (default) compacts each parent's smaller child into a
    dense row prefix, histograms only those <= N/2 rows and reconstructs
    the larger sibling by f32 subtraction from a per-shard parent carry
    (hist.make_subtract_level_fn — the DHistogram/gpu_hist sibling trick
    with the row stream actually halved, not just masked); ``"full"``
    histograms every child from all N rows and is kept as the exactness
    oracle (tests/test_hist_subtract.py grows trees both ways).

    ``split_mode="fused"`` swaps best_splits for the single-pass
    winner-record path (hist.fused_best_splits — on TPU a Pallas kernel
    that never materializes the [3, L, F, B] gain intermediates, off-TPU
    a bit-identical XLA twin).  ``nk > 1`` grows K trees at once: g/h,
    rng_key and tree_mask gain a leading [K] axis, every level issues ONE
    batched hist launch + ONE records launch for all K trees
    (hist.make_batched_level_fn), and levels/vals/cover/leaf come back
    with leading [K].  The batched build reproduces the sequential
    per-tree key chains exactly (vmapped threefry draws are bitwise the
    per-key calls), so a K-loop of single-tree builds is its oracle.

    ``hist_layout="sparse"`` switches levels at/below
    ``sparse_depth_threshold`` (clamped per frame to the dense memory cap)
    to the node-sparse slot layout: histograms, split search and routing
    run in an [A] slot space sized by ALIVE leaves (hist.sparse_slot_budget
    caps A so the 64 MB histogram budget holds at EVERY depth), rows carry
    a slot id updated through A+1-entry tables, and each level's records
    are expanded back to the dense [2^d] contract so traversal, exporters
    and checkpoints are layout-blind.  Requires hist_mode="subtract" (the
    slot carry IS the subtraction carry); dense candidate records on dead
    chains are not reproduced (sparse never histograms dead rows), so
    parity with "dense" is: valid/leaf routing exact, feat/thr/na_left
    exact WHERE VALID, leaf values to f32 tolerance
    (tests/test_sparse_levels.py).

    ``tree_program="scan"`` replaces the trace-time level unroll with a
    ``lax.scan`` over levels inside the same jit (one fixed-width level
    program compiled ONCE instead of one program pair per level):
    level 0 runs outside the scan on the existing depth-0 machinery and
    seeds the carries, levels 1..max_depth-1 run at the padded width
    2^(max_depth-1) with alive-slot masking, and the early-exit fence is
    a scan-carried on-device ``dead`` predicate (hist.make_scan_level_fn
    skips the histogram kernel and the builder skips partition on dead
    levels — both skips are bitwise the live computation).  Composes
    with hist_mode subtract/full, split_mode separate/fused, the
    batched K-tree build and the variable-bin kernel (``bin_counts``
    reach the scan build, which asks the level path's rule at its own
    width: _make_scan_build); NOT with mono/EFB/sparse layout (raises).
    """
    B = nbins + 1
    if hist_layout not in ("dense", "sparse"):
        raise ValueError(
            f"hist_layout={hist_layout!r}: use 'dense' or 'sparse' here "
            "('auto' is a driver mode — see resolve_hist_layout)")
    if hist_layout == "sparse":
        if hist_mode != "subtract":
            raise ValueError(
                "hist_layout='sparse' requires hist_mode='subtract': the "
                "slot-space level carry is the subtraction carry "
                "(hist_mode='full' has no carry to subtract from)")
        if mono is not None or plan is not None:
            raise ValueError(
                "hist_layout='sparse' does not compose with monotone "
                "constraints or EFB bundling; the drivers downgrade to "
                "'dense' automatically under hist_layout='auto'")
    if split_mode not in ("separate", "fused"):
        raise ValueError(
            f"split_mode={split_mode!r}: use 'separate' or 'fused' here "
            "('auto' is a driver mode — see resolve_split_mode)")
    if split_mode == "fused" and (mono is not None or plan is not None):
        raise ValueError(
            "split_mode='fused' does not compose with monotone "
            "constraints or EFB bundling; the drivers downgrade to "
            "'separate' automatically")
    if nk > 1 and split_mode != "fused":
        raise ValueError("the batched K-tree build (nk > 1) requires "
                         "split_mode='fused'")
    if plan is not None and mono is not None:
        raise ValueError("feature bundling (EFB) does not compose with "
                         "monotone constraints; the drivers disable it "
                         "automatically")
    if hist_mode not in ("subtract", "full"):
        raise ValueError(
            f"hist_mode={hist_mode!r}: use 'subtract' or 'full' here "
            "('auto' is a driver mode — see resolve_hist_mode)")
    if tree_program not in ("level", "scan"):
        raise ValueError(
            f"tree_program={tree_program!r}: use 'level' or 'scan' here "
            "('auto' is a driver mode — see resolve_tree_program)")
    if tree_program == "scan" and (mono is not None or plan is not None):
        raise ValueError(
            "tree_program='scan' does not compose with monotone "
            "constraints or EFB bundling; tree_program='auto' downgrades "
            "to 'level' automatically")
    max_depth, sparse_from, A_lv, Ap_lv, kern_L = _level_geometry(
        max_depth, nbins, F, n_padded, hist_mode, hist_layout,
        sparse_depth_threshold)
    if tree_program == "scan":
        if sparse_from < max_depth:
            raise ValueError(
                "tree_program='scan' requires the dense layout at every "
                "level (the scan body is ONE fixed-width program; node-"
                "sparse slot maps reshape per level); use "
                "hist_layout='dense' or tree_program='auto'")
        if max_depth < 2:
            raise ValueError(
                "tree_program='scan' needs effective max_depth >= 2 (a "
                "depth-1 tree is the root level only — nothing to scan); "
                "tree_program='auto' downgrades to 'level' automatically")
        return _make_scan_build(max_depth, nbins, F, n_padded,
                                hist_precision, hist_mode, nk, split_mode,
                                bin_counts)
    # per-feature packed bins (DHistogram-style): only the TPU Pallas path
    # has the ragged kernel; dense einsum covers CPU tests.  The packed
    # result has the exact same [3, L, F, B] contract, so split search is
    # byte-identical — this is a pure kernel-cost optimization.
    # H2O3_TPU_HIST_IMPL=varbin forces the varbin path off-TPU (interpret
    # Pallas) so the multichip dryrun exercises the varbin kernel's code.
    # Per-LEVEL kernel choice (hist_site_kernel, the scan build's rule
    # too): deeper levels take the uniform path, which falls back to
    # einsum past its own bound — the gate is per level so a deep tree
    # keeps the fast kernel on its shallow levels.
    varbin_level = [
        hist_site_kernel(L, F, nbins, bin_counts) == "varbin"
        for L in kern_L]
    force = "" if _on_tpu() else "pallas_interpret"

    # ---- node-sparse deep levels (hist_layout="sparse", d >= sparse_from)
    # Per-tree helpers shared by build()/buildK() (buildK vmaps them).
    # All slot bookkeeping is O(A) or O(2^d) index math — the only per-row
    # work is the A+1-entry table routing (partition_right) and the one
    # boundary slot lookup.
    sparse_fns = {}
    for d in range(sparse_from, max_depth):
        _kw = dict(bin_counts=(tuple(bin_counts) if varbin_level[d]
                               else None),
                   force_impl=force if varbin_level[d] else "",
                   precision=hist_precision)
        sparse_fns[d] = (
            make_batched_sparse_level_fn(Ap_lv[d], A_lv[d], nk, F, B,
                                         n_padded, **_kw)
            if nk > 1 else
            make_sparse_level_fn(Ap_lv[d], A_lv[d], F, B, n_padded, **_kw))

    def _slot_maps(d, prev_valid, slot_of_leaf, leaf_of_slot):
        """Slot assignment + dense<->slot index maps for sparse level d.
        ``prev_valid`` is the previous level's valid flags in its OWN
        space: dense [2^(d-1)] at the boundary, [Ap] slots after it."""
        A = A_lv[d]
        sidx = jnp.arange(A, dtype=jnp.int32)
        child_base, ps_of_slot, real = sparse_slot_maps(prev_valid, A)
        l2 = jnp.arange(2 ** d, dtype=jnp.int32)
        if d == sparse_from:
            sol = jnp.minimum(child_base[l2 >> 1] + (l2 & 1), A)
            los = 2 * ps_of_slot + (sidx & 1)
        else:
            sol = jnp.minimum(child_base[slot_of_leaf[l2 >> 1]]
                              + (l2 & 1), A)
            los = 2 * leaf_of_slot[ps_of_slot] + (sidx & 1)
        return child_base, ps_of_slot, real, sol, los

    def _sleaf_of_leaf(slot_of_leaf, leaf, L):
        # boundary only: dense leaf id -> slot id, one [1, 2^t] MXU lookup
        return table_lookup(slot_of_leaf[None].astype(jnp.float32),
                            leaf, L)[0].astype(jnp.int32)

    def _slot_collapse(valid_s, children_s):
        # the dense dead-slot stat collapse, in slot space: non-split
        # slots keep full totals on the left so their rows' leaf values
        # cover everything draining through them
        gl, hl, cl2 = children_s[:, 0], children_s[:, 1], children_s[:, 2]
        gr, hr, cr2 = children_s[:, 3], children_s[:, 4], children_s[:, 5]
        return jnp.stack(
            [jnp.where(valid_s, gl, gl + gr),
             jnp.where(valid_s, hl, hl + hr),
             jnp.where(valid_s, cl2, cl2 + cr2),
             jnp.where(valid_s, gr, 0.0),
             jnp.where(valid_s, hr, 0.0),
             jnp.where(valid_s, cr2, 0.0)], axis=1)

    def _expand_sparse(d, feat_s, bin_s, na_s, valid_s, children_s,
                       slot_of_leaf, prev_children):
        """Slot records -> the dense [2^d] level contract.  Unslotted
        nodes (dead chains / slot-budget overflow) are terminal: invalid
        records, child stats inherited from their side of the parent's
        record so every row draining through them keeps a leaf value
        (the dense collapse semantics, to f32 tolerance)."""
        A = A_lv[d]
        l2 = jnp.arange(2 ** d, dtype=jnp.int32)
        mapped = slot_of_leaf < A
        slc = jnp.minimum(slot_of_leaf, A - 1)
        feat_d = jnp.where(mapped, feat_s[slc], 0)
        bin_d = jnp.where(mapped, bin_s[slc], 0)
        na_d = jnp.where(mapped, na_s[slc], False)
        valid_d = mapped & valid_s[slc]
        pc = prev_children[l2 >> 1]
        tot = jnp.where((l2 & 1)[:, None] == 0, pc[:, 0:3], pc[:, 3:6])
        inherit = jnp.concatenate([tot, jnp.zeros_like(tot)], axis=1)
        children_d = jnp.where(mapped[:, None], children_s[slc], inherit)
        return feat_d, bin_d, na_d, valid_d, children_d

    def _pad_slot_tables(feat_s, bin_s, na_s, valid_s):
        # sentinel row (slot A): valid=False, so dead/overflowed rows
        # keep flowing left — dense terminality through slot tables
        def z(a):
            return jnp.concatenate([a, jnp.zeros((1,), a.dtype)])
        return z(feat_s), z(bin_s), z(na_s), z(valid_s)

    if nk > 1:
        lev_fns = [
            make_batched_level_fn(
                d, nk, F, B, n_padded,
                bin_counts=tuple(bin_counts) if varbin_level[d] else None,
                force_impl=force if varbin_level[d] else "",
                precision=hist_precision,
                subtract=(hist_mode == "subtract"))
            for d in range(sparse_from)]

        def buildK(codes, g, h, w, edges_mat, rng_keys, reg_lambda,
                   min_rows, min_split_improvement, learn_rate,
                   col_sample_rate, tree_mask, reg_alpha, gamma,
                   min_child_weight):
            # the K-tree analog of build() below: one level loop, every
            # array carrying a leading [K].  w may be [N] (row sample
            # shared across class trees — reference semantics) or [K, N]
            # (uplift arms); either broadcasts to g's shape.  The scalar
            # params also accept per-member [K] arrays (batched grid
            # sweeps) — anything that doesn't change trace shape batches.
            N = codes.shape[1]
            csr2 = _per_k(col_sample_rate, 2)
            wK = jnp.broadcast_to(w, g.shape)
            leaf = jnp.zeros((nk, N), jnp.int32)
            levels = []
            alive = jnp.ones((nk, 1), bool)
            # per-tree key chains: vmapped threefry emits bitwise the
            # per-key split/uniform results, so each tree's column draws
            # match the sequential oracle exactly
            keysK = jax.vmap(
                lambda kk: jax.random.split(kk, max_depth))(rng_keys)
            H_carry = None
            hcodes = offset_codes(codes, bin_counts, nbins) \
                if any(varbin_level) else codes
            for d in range(max_depth):
                L = 2 ** d
                per_split = jax.vmap(
                    lambda kd: jax.random.uniform(kd, (L, F)))(
                        keysK[:, d]) < csr2
                per_split = per_split.at[:, :, 0].set(
                    (per_split.any(axis=2) & per_split[:, :, 0])
                    | ~per_split.any(axis=2))
                mask = per_split & tree_mask[:, None, :]
                lcodes = hcodes if varbin_level[d] else codes
                if d >= sparse_from:
                    A = A_lv[d]
                    if d == sparse_from:
                        (child_base, ps_of_slot, real, slot_of_leaf,
                         leaf_of_slot) = jax.vmap(
                            lambda v: _slot_maps(d, v, None, None))(valid)
                        sleaf = jax.vmap(_sleaf_of_leaf,
                                         in_axes=(0, 0, None))(
                            slot_of_leaf, leaf, L)
                    else:
                        (child_base, ps_of_slot, real, slot_of_leaf,
                         leaf_of_slot) = jax.vmap(
                            functools.partial(_slot_maps, d))(
                            valid_s, slot_of_leaf, leaf_of_slot)
                        sleaf = jnp.minimum(
                            jnp.take_along_axis(child_base, sleaf, axis=1)
                            + right, A)
                    H, H_carry = sparse_fns[d](lcodes, sleaf, g, h, wK,
                                               H_carry, ps_of_slot)
                    # the col mask is DRAWN dense (same keys as the dense
                    # layout, bit-identical RNG), then gathered to slots
                    mask_s = jax.vmap(lambda m, i: m[i])(mask,
                                                         leaf_of_slot)
                    feat_s, bin_s, na_s, gain, valid_s, children_s = \
                        fused_best_splits_batched(
                            H, nbins, reg_lambda, min_rows,
                            min_split_improvement, mask_s, reg_alpha,
                            gamma, min_child_weight)
                    # phantom slots past the live range gathered parent
                    # slot 0's histogram — no rows, records discarded
                    valid_s = valid_s & real
                    children_s = jax.vmap(_slot_collapse)(valid_s,
                                                          children_s)
                    feat, bin_, na_left, valid, children = jax.vmap(
                        functools.partial(_expand_sparse, d))(
                        feat_s, bin_s, na_s, valid_s, children_s,
                        slot_of_leaf, children)
                    thr = edges_mat[feat, jnp.clip(bin_, 0, nbins - 1)]
                    fp, bp, nap, vp = jax.vmap(_pad_slot_tables)(
                        feat_s, bin_s, na_s, valid_s)
                    right = jax.vmap(
                        partition_right,
                        in_axes=(None, 0, 0, 0, 0, 0, None))(
                        codes, sleaf, fp, bp, nap, vp, jnp.int32(nbins))
                    leaf = 2 * leaf + right
                    levels.append((feat, thr, na_left, valid))
                    continue
                if hist_mode == "subtract":
                    if d == 0:
                        H, H_carry = lev_fns[0](lcodes, leaf, g, h, wK)
                    else:
                        H, H_carry = lev_fns[d](lcodes, leaf, g, h, wK,
                                                H_carry)
                else:
                    H = lev_fns[d](lcodes, leaf, g, h, wK)
                feat, bin_, na_left, gain, valid, children = \
                    fused_best_splits_batched(
                        H, nbins, reg_lambda, min_rows,
                        min_split_improvement, mask, reg_alpha, gamma,
                        min_child_weight)
                if d > 0:
                    valid = valid & alive
                    gl, hl, cl2 = (children[..., 0], children[..., 1],
                                   children[..., 2])
                    gr, hr, cr2 = (children[..., 3], children[..., 4],
                                   children[..., 5])
                    children = jnp.stack(
                        [jnp.where(valid, gl, gl + gr),
                         jnp.where(valid, hl, hl + hr),
                         jnp.where(valid, cl2, cl2 + cr2),
                         jnp.where(valid, gr, 0.0),
                         jnp.where(valid, hr, 0.0),
                         jnp.where(valid, cr2, 0.0)], axis=-1)
                alive = jnp.stack([valid, valid], axis=2).reshape(nk, -1)
                thr = edges_mat[feat, jnp.clip(bin_, 0, nbins - 1)]
                leaf = jax.vmap(partition,
                                in_axes=(None, 0, 0, 0, 0, 0, None))(
                    codes, leaf, feat, bin_, na_left, valid,
                    jnp.int32(nbins))
                levels.append((feat, thr, na_left, valid))
            gl, hl, cl = (children[..., 0], children[..., 1],
                          children[..., 2])
            gr, hr, cr = (children[..., 3], children[..., 4],
                          children[..., 5])

            from .hist import newton_value

            def newton(gc, hc, cc):
                return jnp.where(cc > 0,
                                 newton_value(gc, hc, _per_k(reg_lambda, 1),
                                              _per_k(reg_alpha, 1)),
                                 0.0)
            vals = jnp.stack([newton(gl, hl, cl), newton(gr, hr, cr)],
                             axis=2).reshape(nk, -1)
            vals = (vals * _per_k(learn_rate, 1)).astype(jnp.float32)
            cover = jnp.stack([cl, cr], axis=2).reshape(nk, -1) \
                .astype(jnp.float32)
            return levels, vals, cover, leaf

        return _ledger("tree_build_batched", jax.jit(buildK), orig=buildK)
    if hist_mode == "subtract":
        level_fns = [
            make_subtract_level_fn(
                d, F, B, n_padded,
                bin_counts=tuple(bin_counts) if varbin_level[d] else None,
                force_impl=force if varbin_level[d] else "",
                precision=hist_precision)
            for d in range(sparse_from)]
    else:
        hist_fns = [
            make_varbin_hist_fn(kern_L[d], F, tuple(bin_counts), B,
                                n_padded, precision=hist_precision,
                                force_impl=force)
            if varbin_level[d]
            else make_hist_fn(kern_L[d], F, B, n_padded,
                              precision=hist_precision)
            for d in range(max_depth)]

    def build(codes, g, h, w, edges_mat, rng_key, reg_lambda, min_rows,
              min_split_improvement, learn_rate, col_sample_rate, tree_mask,
              reg_alpha, gamma, min_child_weight):
        N = codes.shape[1]
        leaf = jnp.zeros(N, jnp.int32)
        levels = []
        # terminality invariant: once a node fails to split, every
        # descendant slot is dead too.  Without this mask a dead node's
        # rows (which keep flowing left through the dense [2^d] levels)
        # could be re-split at a deeper level when a fresh per-level
        # column draw (DRF mtries) samples a feature the failed level
        # missed — the node-sparse exporters (POJO/MOJO/SHAP/tree API)
        # all assume the first invalid node is a leaf, so such "revived"
        # splits made exported scorers diverge from device traversal.
        alive = jnp.ones((1,), bool)
        keys = jax.random.split(rng_key, max_depth)
        if mono is not None:
            mono_arr = jnp.asarray(mono, jnp.float32)        # [F] in {-1,0,1}
            lo = jnp.full((1,), -jnp.inf)                    # per-node value
            hi = jnp.full((1,), jnp.inf)                     # bounds
        H_carry = None            # subtract path: per-shard local hist stack
        hcodes = offset_codes(codes, bin_counts, nbins) \
            if any(varbin_level) else codes
        for d in range(max_depth):
            L = 2 ** d
            per_split = jax.random.uniform(keys[d], (L, F)) < col_sample_rate
            # always keep at least one feature per leaf
            per_split = per_split.at[:, 0].set(
                (per_split.any(axis=1) & per_split[:, 0])
                | ~per_split.any(axis=1))
            mask = per_split & tree_mask[None, :]
            if d >= sparse_from:
                A = A_lv[d]
                if d == sparse_from:
                    # boundary: slots assigned from the last DENSE level's
                    # valid flags; the dense subtract carry is consumed
                    # unchanged (its slot space is the dense parent space)
                    (child_base, ps_of_slot, real, slot_of_leaf,
                     leaf_of_slot) = _slot_maps(d, valid, None, None)
                    sleaf = _sleaf_of_leaf(slot_of_leaf, leaf, L)
                else:
                    (child_base, ps_of_slot, real, slot_of_leaf,
                     leaf_of_slot) = _slot_maps(d, valid_s, slot_of_leaf,
                                                leaf_of_slot)
                    sleaf = jnp.minimum(jnp.take(child_base, sleaf)
                                        + right, A)
                lcodes = hcodes if varbin_level[d] else codes
                with level_phase("hist", d):
                    H, H_carry = sparse_fns[d](lcodes, sleaf, g, h, w,
                                               H_carry, ps_of_slot)
                # col mask DRAWN dense (bit-identical RNG to the dense
                # layout), gathered to slots
                mask_s = mask[leaf_of_slot]
                with level_phase("split", d):
                    if split_mode == "fused":
                        feat_s, bin_s, na_s, gain, valid_s, children_s = \
                            fused_best_splits(
                                H, nbins, reg_lambda, min_rows,
                                min_split_improvement, mask_s, reg_alpha,
                                gamma, min_child_weight)
                    else:
                        feat_s, bin_s, na_s, gain, valid_s, children_s = \
                            best_splits(
                                H, nbins, reg_lambda, min_rows,
                                min_split_improvement, mask_s, reg_alpha,
                                gamma, min_child_weight)
                # phantom slots past the live range gathered parent slot
                # 0's histogram — no rows, records discarded here
                valid_s = valid_s & real
                children_s = _slot_collapse(valid_s, children_s)
                feat, bin_, na_left, valid, children = _expand_sparse(
                    d, feat_s, bin_s, na_s, valid_s, children_s,
                    slot_of_leaf, children)
                thr = edges_mat[feat, jnp.clip(bin_, 0, nbins - 1)]
                fp, bp, nap, vp = _pad_slot_tables(feat_s, bin_s, na_s,
                                                   valid_s)
                with level_phase("partition", d):
                    right = partition_right(codes, sleaf, fp, bp, nap, vp,
                                            jnp.int32(nbins))
                # same went-right bit updates BOTH ids: dense leaf (final
                # values/traversal) and slot (next level's routing)
                leaf = 2 * leaf + right
                levels.append((feat, thr, na_left, valid))
                continue
            lcodes = hcodes if varbin_level[d] else codes
            with level_phase("hist", d):
                if hist_mode == "subtract":
                    # smaller-sibling compaction + parent subtraction:
                    # the kernel streams only the <= N/2 rows of each
                    # parent's smaller child; the larger sibling is
                    # reconstructed from the per-shard parent carry
                    # (hist.py)
                    if d == 0:
                        H, H_carry = level_fns[0](lcodes, leaf, g, h, w)
                    else:
                        H, H_carry = level_fns[d](lcodes, leaf, g, h, w,
                                                  H_carry)
                else:
                    # "full" oracle: every child histogrammed from
                    # all rows
                    H = hist_fns[d](lcodes, leaf, g, h, w)
            with level_phase("split", d):
                if plan is not None:
                    from .efb import best_splits_mixed
                    (feat, bin_, na_left, gain, valid, children, wfeat,
                     lo_w, hi_w, inv_w) = best_splits_mixed(
                        H, nbins, plan, reg_lambda, min_rows,
                        min_split_improvement, mask, reg_alpha, gamma,
                        min_child_weight)
                elif split_mode == "fused":
                    # single-pass winner records between hist and the
                    # tiny feature argmax — no [3, L, F, B] gain
                    # intermediates
                    feat, bin_, na_left, gain, valid, children = \
                        fused_best_splits(
                            H, nbins, reg_lambda, min_rows,
                            min_split_improvement, mask, reg_alpha,
                            gamma, min_child_weight)
                else:
                    feat, bin_, na_left, gain, valid, children = \
                        best_splits(
                            H, nbins, reg_lambda, min_rows,
                            min_split_improvement, mask, reg_alpha,
                            gamma, min_child_weight,
                            mono=mono_arr if mono is not None else None)
            if d > 0:
                valid = valid & alive
                # collapse the child stats of dead slots back to "all rows
                # left" (full totals = left + right of whatever candidate
                # split best_splits picked), so final-level leaf values
                # cover every row that drains through a dead chain
                gl, hl, cl2 = children[:, 0], children[:, 1], children[:, 2]
                gr, hr, cr2 = children[:, 3], children[:, 4], children[:, 5]
                children = jnp.stack(
                    [jnp.where(valid, gl, gl + gr),
                     jnp.where(valid, hl, hl + hr),
                     jnp.where(valid, cl2, cl2 + cr2),
                     jnp.where(valid, gr, 0.0),
                     jnp.where(valid, hr, 0.0),
                     jnp.where(valid, cr2, 0.0)], axis=1)
            alive = jnp.stack([valid, valid], axis=1).reshape(-1)
            if mono is not None:
                # propagate value bounds to the children (the clamp at the
                # leaves is what guarantees global monotonicity, exactly
                # XGBoost's interaction of bounds + mid-point split)
                from .hist import newton_value
                vL = jnp.clip(newton_value(children[:, 0], children[:, 1],
                                           reg_lambda, reg_alpha), lo, hi)
                vR = jnp.clip(newton_value(children[:, 3], children[:, 4],
                                           reg_lambda, reg_alpha), lo, hi)
                mid = 0.5 * (vL + vR)
                c = mono_arr[feat] * valid.astype(jnp.float32)
                hi_l = jnp.where(c > 0, jnp.minimum(hi, mid), hi)
                lo_l = jnp.where(c < 0, jnp.maximum(lo, mid), lo)
                hi_r = jnp.where(c < 0, jnp.minimum(hi, mid), hi)
                lo_r = jnp.where(c > 0, jnp.maximum(lo, mid), lo)
                lo = jnp.stack([lo_l, lo_r], axis=1).reshape(-1)
                hi = jnp.stack([hi_l, hi_r], axis=1).reshape(-1)
            thr = edges_mat[feat, jnp.clip(bin_, 0, nbins - 1)]
            with level_phase("partition", d):
                if plan is not None:
                    from .hist import partition_ranged
                    leaf = partition_ranged(codes, leaf, wfeat, lo_w, hi_w,
                                            inv_w, na_left, valid,
                                            jnp.int32(nbins))
                else:
                    leaf = partition(codes, leaf, feat, bin_, na_left,
                                     valid, jnp.int32(nbins))
            levels.append((feat, thr, na_left, valid))
        # Newton leaf values from the last level's child sums — no extra
        # data pass (fitBestConstants from the histograms themselves)
        gl, hl, cl = children[:, 0], children[:, 1], children[:, 2]
        gr, hr, cr = children[:, 3], children[:, 4], children[:, 5]

        from .hist import newton_value

        def newton(gc, hc, cc):
            return jnp.where(cc > 0,
                             newton_value(gc, hc, reg_lambda, reg_alpha),
                             0.0)
        vals = jnp.stack([newton(gl, hl, cl), newton(gr, hr, cr)],
                         axis=1).reshape(-1)
        if mono is not None:
            # lo/hi were interleaved (left, right) per parent at the last
            # level — the same layout vals was just reshaped into
            vals = jnp.clip(vals, lo, hi)
        vals = (vals * learn_rate).astype(jnp.float32)
        # leaf covers (weighted row counts) from the same child sums — the
        # per-node weights TreeSHAP needs (PredictTreeSHAPTask reads them
        # from the compressed tree the same way)
        cover = jnp.stack([cl, cr], axis=1).reshape(-1).astype(jnp.float32)
        return levels, vals, cover, leaf

    return _ledger("tree_build", jax.jit(build), orig=build)


def _make_scan_build(max_depth: int, nbins: int, F: int, n_padded: int,
                     hist_precision: str, hist_mode: str, nk: int,
                     split_mode: str, bin_counts=None):
    """The ``tree_program="scan"`` build: one lax.scan over levels.

    Level 0 runs OUTSIDE the scan on the existing depth-0 machinery (the
    root histogram has no parent carry and no sibling to compact) and
    seeds the carries; levels 1..max_depth-1 are iterations of ONE
    fixed-width program at W = 2^(max_depth-1), the deepest level's
    child count.  Shallower levels leave slots >= 2^d empty; empty
    slots are bitwise inert end to end — they histogram exact zeros
    (no rows route there), the split search marks them invalid (then
    ``valid &= alive`` kills any padded-slot artifact), and the dead
    collapse writes the zero totals back — so each level's records and
    routing match the level-path build bit for bit on the live prefix.

    Per-level column-sample masks are drawn OUTSIDE the scan at their
    TRUE [2^d, F] shapes (threefry output depends on the draw shape, and
    bit-parity with the level path requires identical draws), padded to
    [W, F] with False and fed as scan xs.  The early-exit fence becomes
    the scan-carried ``dead = ~any(alive)`` predicate: a dead iteration
    skips the histogram kernel (hist.make_scan_level_fn's internal cond
    — the skip branch is provably the live branch's output when no rows
    moved) and the partition pass (all-invalid records route every row
    left, i.e. ``leaf -> 2*leaf`` exactly); the level path has no early
    exit, so the skips elide only provably-identical work and parity
    holds level by level.

    Bitwise caveat (documented in operations.md): the einsum histogram's
    row-block size depends on the slot width, so at padded width W vs
    the level path's true 2^d the row accumulation can associate
    differently once N is large enough to split blocks — structure stays
    exact, leaf values agree to f32 tolerance (tests/test_tree_scan.py's
    contract).

    The histogram kernel is the one the level path's rule
    (hist_site_kernel) gives the scan's width: the kernel runs at W slots
    under ``hist_mode="full"`` and at W // 2 under ``"subtract"``, as the
    level path's deepest level does.  Where that is the variable-bin
    kernel (a frame whose ``bin_counts`` pack, a width inside the kernel's
    bounds: 32 slots at depth 6 cost it what one slot does, its MXU
    product pads ``3 * slots`` to 128 lanes), the root and the scan body
    both run it on ``offset_codes``' packed ids, built once a tree outside
    the scan; ``partition`` keeps reading ``codes``.  The scan is ONE
    program at one width, so the root follows the body (the rule's bounds
    only tighten with the width): a frame that does not pack, or a width
    past the bounds, leaves the whole build the uniform-kernel program it
    was.
    """
    B = nbins + 1
    W = 2 ** (max_depth - 1)
    Wp = W // 2
    varbin = hist_site_kernel(W if hist_mode == "full" else Wp, F, nbins,
                              bin_counts) == "varbin"
    bc = tuple(bin_counts) if varbin else None
    force = "pallas_interpret" if varbin and not _on_tpu() else ""
    kw = dict(bin_counts=bc, force_impl=force, precision=hist_precision)
    if nk > 1:
        lev0 = make_batched_level_fn(0, nk, F, B, n_padded,
                                     subtract=(hist_mode == "subtract"),
                                     **kw)
        if hist_mode == "subtract":
            scan_lev = make_batched_scan_level_fn(W, nk, F, B, n_padded,
                                                  **kw)
        else:
            scan_lev = make_batched_level_fn(max_depth - 1, nk, F, B,
                                             n_padded, subtract=False,
                                             **kw)
    elif hist_mode == "subtract":
        lev0 = make_subtract_level_fn(0, F, B, n_padded, **kw)
        scan_lev = make_scan_level_fn(W, F, B, n_padded, **kw)
    else:
        lev0, scan_lev = (
            make_varbin_hist_fn(L, F, bc, B, n_padded, force_impl=force,
                                precision=hist_precision) if varbin
            else make_hist_fn(L, F, B, n_padded, precision=hist_precision)
            for L in (1, W))

    def _collapse(valid, ch):
        # the level path's dead-slot stat collapse (axis=-1 indexing
        # covers both the [W, 6] and the batched [K, W, 6] shapes)
        gl, hl, cl2 = ch[..., 0], ch[..., 1], ch[..., 2]
        gr, hr, cr2 = ch[..., 3], ch[..., 4], ch[..., 5]
        return jnp.stack(
            [jnp.where(valid, gl, gl + gr),
             jnp.where(valid, hl, hl + hr),
             jnp.where(valid, cl2, cl2 + cr2),
             jnp.where(valid, gr, 0.0),
             jnp.where(valid, hr, 0.0),
             jnp.where(valid, cr2, 0.0)], axis=-1)

    def build(codes, g, h, w, edges_mat, rng_key, reg_lambda, min_rows,
              min_split_improvement, learn_rate, col_sample_rate, tree_mask,
              reg_alpha, gamma, min_child_weight):
        N = codes.shape[1]
        leaf = jnp.zeros(N, jnp.int32)
        keys = jax.random.split(rng_key, max_depth)

        def draw_mask(d):
            L = 2 ** d
            ps = jax.random.uniform(keys[d], (L, F)) < col_sample_rate
            ps = ps.at[:, 0].set((ps.any(axis=1) & ps[:, 0])
                                 | ~ps.any(axis=1))
            return ps & tree_mask[None, :]

        def _split(H, mask):
            if split_mode == "fused":
                return fused_best_splits(H, nbins, reg_lambda, min_rows,
                                         min_split_improvement, mask,
                                         reg_alpha, gamma, min_child_weight)
            return best_splits(H, nbins, reg_lambda, min_rows,
                               min_split_improvement, mask, reg_alpha,
                               gamma, min_child_weight)

        # the histogram sites' code plane: the packed ids where the
        # variable-bin kernel reads them, built once a tree
        hcodes = offset_codes(codes, bc, nbins) if varbin else codes
        # ---- level 0 outside the scan (root: no carry, no sibling)
        if hist_mode == "subtract":
            H, Hc = lev0(hcodes, leaf, g, h, w)
            H_carry = jnp.pad(Hc, ((0, 0), (0, 0), (0, Wp - 1), (0, 0),
                                   (0, 0)))
        else:
            H = lev0(hcodes, leaf, g, h, w)
        feat, bin_, na_left, gain, valid, children = _split(H, draw_mask(0))
        thr = edges_mat[feat, jnp.clip(bin_, 0, nbins - 1)]
        leaf = partition(codes, leaf, feat, bin_, na_left, valid,
                         jnp.int32(nbins))
        lv0 = (feat, thr, na_left, valid)
        alive = jnp.pad(jnp.stack([valid, valid], axis=1).reshape(-1),
                        (0, W - 2))
        children = jnp.pad(children, ((0, W - 1), (0, 0)))
        masks = jnp.stack([
            jnp.pad(draw_mask(d), ((0, W - 2 ** d), (0, 0)))
            for d in range(1, max_depth)])

        def body(carry, mask):
            if hist_mode == "subtract":
                leaf, alive, children, H_carry = carry
            else:
                leaf, alive, children = carry
            dead = ~jnp.any(alive)
            if hist_mode == "subtract":
                H, H_carry = scan_lev(hcodes, leaf, g, h, w, H_carry, dead)
            else:
                H = scan_lev(hcodes, leaf, g, h, w)
            feat, bin_, na_left, gain, valid, ch = _split(H, mask)
            valid = valid & alive
            children = _collapse(valid, ch)
            # the next iteration reads only its first 2^(d+1) <= W slots:
            # the interleave of the first Wp parents covers them all
            alive = jnp.stack([valid[:Wp], valid[:Wp]], axis=1).reshape(-1)
            thr = edges_mat[feat, jnp.clip(bin_, 0, nbins - 1)]
            leaf = jax.lax.cond(
                dead,
                lambda c, l, f, b, na, v: 2 * l,
                lambda c, l, f, b, na, v: partition(c, l, f, b, na, v,
                                                    jnp.int32(nbins)),
                codes, leaf, feat, bin_, na_left, valid)
            out = (leaf, alive, children, H_carry) \
                if hist_mode == "subtract" else (leaf, alive, children)
            return out, (feat, thr, na_left, valid)

        carry0 = (leaf, alive, children, H_carry) \
            if hist_mode == "subtract" else (leaf, alive, children)
        carry, ys = jax.lax.scan(body, carry0, masks)
        leaf, children = carry[0], carry[2]
        # per-level records back to their true widths — static slicing
        # inside the jit, so the level contract is shape-identical to the
        # level path's
        levels = [lv0] + [
            tuple(y[i][: 2 ** (i + 1)] for y in ys)
            for i in range(max_depth - 1)]
        gl, hl, cl = children[:, 0], children[:, 1], children[:, 2]
        gr, hr, cr = children[:, 3], children[:, 4], children[:, 5]

        from .hist import newton_value

        def newton(gc, hc, cc):
            return jnp.where(cc > 0,
                             newton_value(gc, hc, reg_lambda, reg_alpha),
                             0.0)
        vals = jnp.stack([newton(gl, hl, cl), newton(gr, hr, cr)],
                         axis=1).reshape(-1)
        vals = (vals * learn_rate).astype(jnp.float32)
        cover = jnp.stack([cl, cr], axis=1).reshape(-1).astype(jnp.float32)
        return levels, vals, cover, leaf

    def buildK(codes, g, h, w, edges_mat, rng_keys, reg_lambda,
               min_rows, min_split_improvement, learn_rate,
               col_sample_rate, tree_mask, reg_alpha, gamma,
               min_child_weight):
        N = codes.shape[1]
        wK = jnp.broadcast_to(w, g.shape)
        leaf = jnp.zeros((nk, N), jnp.int32)
        keysK = jax.vmap(
            lambda kk: jax.random.split(kk, max_depth))(rng_keys)

        def draw_maskK(d):
            L = 2 ** d
            ps = jax.vmap(
                lambda kd: jax.random.uniform(kd, (L, F)))(
                    keysK[:, d]) < _per_k(col_sample_rate, 2)
            ps = ps.at[:, :, 0].set(
                (ps.any(axis=2) & ps[:, :, 0]) | ~ps.any(axis=2))
            return ps & tree_mask[:, None, :]

        hcodes = offset_codes(codes, bc, nbins) if varbin else codes
        if hist_mode == "subtract":
            H, Hc = lev0(hcodes, leaf, g, h, wK)
            H_carry = jnp.pad(Hc, ((0, 0), (0, 0), (0, 0), (0, Wp - 1),
                                   (0, 0), (0, 0)))
        else:
            H = lev0(hcodes, leaf, g, h, wK)
        feat, bin_, na_left, gain, valid, children = \
            fused_best_splits_batched(
                H, nbins, reg_lambda, min_rows, min_split_improvement,
                draw_maskK(0), reg_alpha, gamma, min_child_weight)
        thr = edges_mat[feat, jnp.clip(bin_, 0, nbins - 1)]
        leaf = jax.vmap(partition, in_axes=(None, 0, 0, 0, 0, 0, None))(
            codes, leaf, feat, bin_, na_left, valid, jnp.int32(nbins))
        lv0 = (feat, thr, na_left, valid)
        alive = jnp.pad(jnp.stack([valid, valid], axis=2).reshape(nk, -1),
                        ((0, 0), (0, W - 2)))
        children = jnp.pad(children, ((0, 0), (0, W - 1), (0, 0)))
        masks = jnp.stack([
            jnp.pad(draw_maskK(d), ((0, 0), (0, W - 2 ** d), (0, 0)))
            for d in range(1, max_depth)])

        def body(carry, mask):
            if hist_mode == "subtract":
                leaf, alive, children, H_carry = carry
            else:
                leaf, alive, children = carry
            # all K trees dead (an individually finished tree inside a
            # live iteration already produces the parent passthrough
            # bitwise on its own — every slot is invalid, so collapse
            # and routing are the identity for it)
            dead = ~jnp.any(alive)
            if hist_mode == "subtract":
                H, H_carry = scan_lev(hcodes, leaf, g, h, wK, H_carry,
                                      dead)
            else:
                H = scan_lev(hcodes, leaf, g, h, wK)
            feat, bin_, na_left, gain, valid, ch = \
                fused_best_splits_batched(
                    H, nbins, reg_lambda, min_rows,
                    min_split_improvement, mask, reg_alpha, gamma,
                    min_child_weight)
            valid = valid & alive
            children = _collapse(valid, ch)
            alive = jnp.stack([valid[:, :Wp], valid[:, :Wp]],
                              axis=2).reshape(nk, -1)
            thr = edges_mat[feat, jnp.clip(bin_, 0, nbins - 1)]
            leaf = jax.lax.cond(
                dead,
                lambda c, l, f, b, na, v: 2 * l,
                lambda c, l, f, b, na, v: jax.vmap(
                    partition, in_axes=(None, 0, 0, 0, 0, 0, None))(
                    c, l, f, b, na, v, jnp.int32(nbins)),
                codes, leaf, feat, bin_, na_left, valid)
            out = (leaf, alive, children, H_carry) \
                if hist_mode == "subtract" else (leaf, alive, children)
            return out, (feat, thr, na_left, valid)

        carry0 = (leaf, alive, children, H_carry) \
            if hist_mode == "subtract" else (leaf, alive, children)
        carry, ys = jax.lax.scan(body, carry0, masks)
        leaf, children = carry[0], carry[2]
        levels = [lv0] + [
            tuple(y[i][:, : 2 ** (i + 1)] for y in ys)
            for i in range(max_depth - 1)]
        gl, hl, cl = children[..., 0], children[..., 1], children[..., 2]
        gr, hr, cr = children[..., 3], children[..., 4], children[..., 5]

        from .hist import newton_value

        def newton(gc, hc, cc):
            return jnp.where(cc > 0,
                             newton_value(gc, hc, _per_k(reg_lambda, 1),
                                          _per_k(reg_alpha, 1)),
                             0.0)
        vals = jnp.stack([newton(gl, hl, cl), newton(gr, hr, cr)],
                         axis=2).reshape(nk, -1)
        vals = (vals * _per_k(learn_rate, 1)).astype(jnp.float32)
        cover = jnp.stack([cl, cr], axis=2).reshape(nk, -1) \
            .astype(jnp.float32)
        return levels, vals, cover, leaf

    if nk > 1:
        return _ledger("tree_build_scan_batched", jax.jit(buildK),
                       orig=buildK)
    return _ledger("tree_build_scan", jax.jit(build), orig=build)


def resolve_mono(params, di) -> Optional[tuple]:
    """monotone_constraints dict -> per-feature tuple in di.specs order."""
    mc = getattr(params, "monotone_constraints", None)
    if not mc:
        return None
    names = [s.name for s in di.specs]
    vec = [0.0] * len(names)
    for col, direction in mc.items():
        if col not in names:
            raise ValueError(f"monotone_constraints: unknown column "
                             f"{col!r}")
        spec = di.specs[names.index(col)]
        if getattr(spec, "type", None) == T_CAT:
            raise ValueError(f"monotone_constraints: {col!r} is "
                             "categorical; numeric features only")
        if direction not in (1, -1, 0):
            raise ValueError(f"monotone_constraints[{col!r}] must be "
                             f"1, -1 or 0, got {direction!r}")
        vec[names.index(col)] = float(direction)
    if not any(vec):
        return None                      # all zeros: unconstrained
    return tuple(vec)


def maybe_bundle(binned, params, mono, nrows: int):
    """Driver gate for EFB: plan bundles when the mode allows and the packed
    cost model says bundling wins; None keeps the un-bundled pipeline.
    Returns (plan, working_codes, F_w, working_bin_counts)."""
    from .efb import plan_bundles, apply_bundles
    mode = str(getattr(params, "efb", "auto")).lower()
    plan = None
    if mode not in ("off", "false", "0") and mono is None:
        plan = plan_bundles(binned.codes, binned.bin_counts, binned.nbins,
                            nrows)
    if plan is None:
        return None, binned.codes, binned.nfeatures, binned.bin_counts
    return (plan, apply_bundles(binned.codes, plan), plan.n_working,
            plan.bin_counts)


def resolve_hist_mode(params) -> str:
    """Validate + normalize the ``hist_mode`` knob (drivers call this
    once).  ``"auto"`` resolves to the fixed default here — drivers that route
    through ``autotune.resolve_tree_knobs`` get the tuned choice
    instead; this fallback is what the tuner's "off" mode serves."""
    mode = str(getattr(params, "hist_mode", "auto")).lower()
    if mode == "auto":
        return "subtract"
    if mode not in ("subtract", "full"):
        raise ValueError(
            f"hist_mode={mode!r}: use auto | subtract | full")
    return mode


def resolve_split_mode(params, *, mono=None, plan=None) -> str:
    """Validate + normalize the ``split_mode`` knob (mirrors
    resolve_hist_mode; drivers call this once).  Monotone constraints and
    EFB bundling have no fused implementation, so those builds downgrade
    to ``"separate"`` here — silently, matching the drivers' existing
    auto-gating of those features.  ``"auto"`` resolves to the fixed
    default here (see resolve_hist_mode)."""
    mode = str(getattr(params, "split_mode", "auto")).lower()
    if mode == "auto":
        mode = "fused"
    if mode not in ("fused", "separate"):
        raise ValueError(
            f"split_mode={mode!r}: use auto | fused | separate")
    if mono is not None or plan is not None:
        return "separate"
    return mode


def sparse_layout_active(hist_layout: str, hist_mode: str = "subtract", *,
                         mono=None, plan=None) -> bool:
    """Whether the node-sparse deep-level layout ENGAGES for a build with
    these features — the single predicate every consumer (the build
    factories, the scan factories' own depth computation,
    record_effective_depth / validate_checkpoint_depth, and the drivers'
    deep_level fault hook) shares, so level counts agree everywhere.
    Depth-threshold gating is the builder's job."""
    return (hist_layout in ("sparse", "auto")
            and hist_mode == "subtract"
            and mono is None and plan is None)


def resolve_hist_layout(params, *, hist_mode=None, mono=None,
                        plan=None) -> str:
    """Validate + normalize the ``hist_layout`` knob (mirrors
    resolve_split_mode; drivers call this once).  Returns the
    BUILDER value — "dense" or "sparse" ("sparse" means "below the
    clamped sparse_depth_threshold"; the builder applies the threshold,
    so "auto" and "sparse" build identically).  "auto" downgrades
    silently to "dense" for monotone constraints, EFB bundling or
    hist_mode="full" (no carry to subtract from); an EXPLICIT "sparse"
    with any of those raises — failing fast beats silently training a
    different layout than asked."""
    layout = str(getattr(params, "hist_layout", "auto")).lower()
    if layout not in ("dense", "sparse", "auto"):
        raise ValueError(
            f"hist_layout={layout!r}: use auto | dense | sparse")
    if int(getattr(params, "sparse_depth_threshold", 8)) < 1:
        raise ValueError("sparse_depth_threshold must be >= 1 (the root "
                         "level seeds the carry and is always dense)")
    if layout == "dense":
        return "dense"
    hm = hist_mode if hist_mode is not None else resolve_hist_mode(params)
    if not sparse_layout_active(layout, hm, mono=mono, plan=plan):
        if layout == "sparse":
            raise ValueError(
                "hist_layout='sparse' does not compose with "
                "hist_mode='full', monotone constraints or EFB bundling; "
                "use hist_layout='auto' to downgrade automatically")
        return "dense"
    return "sparse"


def varbin_kernel_engages(bin_counts, nbins: int, F: int) -> bool:
    """Whether the variable-bin packed kernel may carry this frame's
    histogram levels: on the TPU (or forced off it, interpret Pallas, by
    H2O3_TPU_HIST_IMPL=varbin) and where packing pays, every feature's
    8-padded segment with its NA and spare slots under the uniform
    ``F * (nbins + 1)`` one-hot rows.  The frame's half of the kernel
    rule; hist_site_kernel adds the site's width for both tree programs."""
    if bin_counts is None:
        return False
    if not (_on_tpu() or os.environ.get("H2O3_TPU_HIST_IMPL", "") == "varbin"):
        return False
    return sum(min(b, nbins) + 9 for b in bin_counts) < F * (nbins + 1)


def resolve_tree_program(params, *, hist_layout: str = "dense", mono=None,
                         plan=None, F: Optional[int] = None,
                         n_padded: Optional[int] = None) -> str:
    """Validate + normalize the ``tree_program`` knob (mirrors
    resolve_hist_layout; drivers call this once).  Returns the BUILDER
    value — "level" or "scan".

    ``"auto"`` resolves to the fixed default ("level") here — drivers
    that route through ``autotune.resolve_tree_knobs`` get the tuned
    choice instead, so with ``H2O3_TPU_AUTOTUNE=off`` the pipeline stays
    bit-identical to the pre-scan per-level path.  The scan composes
    with the dense layout and the plain (non-mono / non-EFB) split
    search at effective depth >= 2; an EXPLICIT "scan" raises for
    missing features (mono / EFB / engaged sparse levels / depth < 2).
    It forfeits no kernel: the scan runs the variable-bin kernel wherever
    the level path's rule gives it to the scan's width
    (hist_site_kernel)."""
    prog = str(getattr(params, "tree_program", "auto")).lower()
    if prog not in ("level", "scan", "auto"):
        raise ValueError(
            f"tree_program={prog!r}: use auto | level | scan")
    if prog in ("level", "auto"):
        return "level"
    md = int(getattr(params, "max_depth", 5))
    nb = int(getattr(params, "nbins", 64))
    thr = int(getattr(params, "sparse_depth_threshold", 8))
    if F is not None and n_padded is not None:
        md = effective_max_depth(md, nb, F, n_padded, hist_layout, thr)
    t0 = max(1, min(thr, dense_mem_cap(nb, F)) if F is not None else thr)
    if mono is not None or plan is not None:
        raise ValueError(
            "tree_program='scan' does not compose with monotone "
            "constraints or EFB bundling; use tree_program='auto' to "
            "downgrade automatically")
    if hist_layout == "sparse" and md > t0:
        raise ValueError(
            "tree_program='scan' requires the dense layout at every "
            "level (the scan body is ONE fixed-width program; node-"
            "sparse slot maps reshape per level); use "
            "hist_layout='dense' or tree_program='auto'")
    if md < 2:
        raise ValueError(
            "tree_program='scan' needs effective max_depth >= 2 (a "
            "depth-1 tree is the root level only — nothing to scan); "
            "use tree_program='auto' to downgrade automatically")
    return "scan"


@functools.lru_cache(maxsize=None)
def make_tree_scan_fn(mode: str, tweedie_power: float, quantile_alpha: float,
                      huber_alpha: float, max_depth: int, nbins: int, F: int,
                      n_padded: int, hist_precision: str, sample_rate: float,
                      col_sample_rate_per_tree: float,
                      bin_counts=None, mono=None, custom_fn=None, plan=None,
                      hist_mode: str = "subtract",
                      split_mode: str = "fused",
                      hist_layout: str = "dense",
                      sparse_depth_threshold: int = 8,
                      tree_program: str = "level"):
    """Scan a CHUNK of boosting/bagging rounds in ONE device dispatch.

    The per-tree driver loop (gradients -> row/column sample -> grow ->
    F update) becomes the body of a ``lax.scan`` over per-tree PRNG keys, so
    a whole scoring interval of trees costs one dispatch instead of
    one-plus per tree.  ``mode`` is a distribution name for boosting
    or ``"drf"`` for the forest mean-fit (grad=-y, hess=1).  Returns
    (F_final, levels, values) with levels/values carrying a leading [T] dim —
    exactly the ``StackedTrees`` layout.
    """
    from ..distributions import make_distribution
    dist = None
    if mode != "drf":
        dist = make_distribution(
            mode, nclasses=2 if mode == "bernoulli" else 1,
            tweedie_power=tweedie_power, quantile_alpha=quantile_alpha,
            huber_alpha=huber_alpha, custom_distribution_func=custom_fn)
    if mono is not None or plan is not None:
        split_mode = "separate"          # no fused path for these builds
        hist_layout = "dense"            # nor a sparse one (resolve_*)
        tree_program = "level"           # nor a scan-fused one
    bt_fn = make_build_tree_fn(max_depth, nbins, F, n_padded, hist_precision,
                               bin_counts=bin_counts, mono=mono,
                               plan=plan, hist_mode=hist_mode,
                               split_mode=split_mode,
                               hist_layout=hist_layout,
                               sparse_depth_threshold=sparse_depth_threshold,
                               tree_program=tree_program)

    def scan_fn(codes, y, w, F0, edges_mat, rng0, chunk_no, nchunk,
                reg_lambda, min_rows, min_split_improvement, learn_rate,
                col_sample_rate, reg_alpha, gamma, min_child_weight, salt=0):
        # Per-chunk keys derive IN-JIT from (rng0, chunk_no), so the driver
        # loop dispatches nothing but the chunk program.
        # ``nchunk`` (trees per chunk) is static — it sets the scan length.
        # ``salt`` decorrelates column/build randomness between callers that
        # share the chunk stream (DRF class trees share the bootstrap via ks
        # but must draw independent per-split feature subsets).
        keys = jax.random.split(jax.random.fold_in(rng0, chunk_no), nchunk)
        def body(Fc, key_t):
            ks, km, kb = jax.random.split(key_t, 3)
            km = jax.random.fold_in(km, salt)
            kb = jax.random.fold_in(kb, salt)
            if mode == "drf":
                g0, h0 = -y, jnp.ones_like(y)
            else:
                g0, h0 = dist.grad_hess(y, Fc)
            wv = w
            if sample_rate < 1.0:
                wv = w * jax.random.bernoulli(ks, sample_rate, w.shape)
            tm = jnp.ones((F,), bool)
            if col_sample_rate_per_tree < 1.0:
                m = jax.random.uniform(km, (F,)) < col_sample_rate_per_tree
                tm = m.at[0].set(m[0] | ~m.any())
            levels, vals, cover, leaf = bt_fn(
                codes, g0 * wv, h0 * wv, wv, edges_mat, kb, reg_lambda,
                min_rows, min_split_improvement, learn_rate, col_sample_rate,
                tm, reg_alpha, gamma, min_child_weight)
            from .hist import table_lookup
            dF = table_lookup(vals[None, :], leaf, vals.shape[0])[0]
            return Fc + dF, (tuple(levels), vals, cover)

        Ff, (lv, vals, covers) = jax.lax.scan(body, F0, keys)
        return Ff, list(lv), vals, covers

    return _ledger("tree_scan",
                   jax.jit(scan_fn, donate_argnums=(3,), static_argnums=(7,)),
                   static_argnums=(7,), orig=scan_fn)


@functools.lru_cache(maxsize=None)
def make_multinomial_scan_fn(K: int, max_depth: int, nbins: int, F: int,
                             n_padded: int, hist_precision: str,
                             sample_rate: float,
                             col_sample_rate_per_tree: float,
                             bin_counts=None, plan=None,
                             hist_mode: str = "subtract",
                             split_mode: str = "fused",
                             mode: str = "multinomial",
                             hist_layout: str = "dense",
                             sparse_depth_threshold: int = 8,
                             tree_program: str = "level"):
    """Scan a chunk of K-tree rounds in ONE dispatch.

    Each round grows K one-vs-rest trees — on softmax gradients for
    ``mode="multinomial"`` (GBM.java buildNextKTrees' K-tree loop) or on
    the constant forest fit (grad=-y, hess=1) for ``mode="drf"`` — all
    inside the scan body.  Rows are sampled once per round and shared
    across the K class trees (reference semantics).

    ``split_mode="fused"`` (default) grows the K trees as ONE batched
    build (make_build_tree_fn nk=K): one hist launch + one split-records
    launch per level regardless of K, and the traced scan body holds one
    level program instead of K copies.  ``"separate"`` keeps the
    K-iteration Python loop of single-tree builds — the oracle the
    batched path reproduces key-for-key (same fold_in structure;
    tests/test_fused_splits.py).

    Returns (F_final [N, K], levels with leading [T, K, ...] dims, values
    [T, K, 2^depth], covers [T, K, 2^depth]) — identical layout on both
    paths.
    """
    if mode not in ("multinomial", "drf"):
        raise ValueError(f"mode={mode!r}: use 'multinomial' or 'drf'")
    if plan is not None:
        split_mode = "separate"          # no fused path for these builds
        hist_layout = "dense"            # nor a sparse one (resolve_*)
        tree_program = "level"           # nor a scan-fused one
    # the builder clamps internally; the level-stacking loop below must
    # iterate the SAME effective count — layout-aware, like the builder
    max_depth = effective_max_depth(max_depth, nbins, F, n_padded,
                                    hist_layout, sparse_depth_threshold)
    batched = split_mode == "fused" and K > 1
    bt_fn = make_build_tree_fn(max_depth, nbins, F, n_padded,
                               hist_precision,
                               bin_counts=bin_counts, plan=plan,
                               hist_mode=hist_mode,
                               nk=K if batched else 1,
                               split_mode=split_mode,
                               hist_layout=hist_layout,
                               sparse_depth_threshold=sparse_depth_threshold,
                               tree_program=tree_program)

    def scan_fn(codes, Y1, w, F0, edges_mat, rng0, chunk_no, nchunk,
                reg_lambda, min_rows, min_split_improvement, learn_rate,
                col_sample_rate, reg_alpha, gamma, min_child_weight):
        from .hist import table_lookup
        # in-jit key derivation — see make_tree_scan_fn
        keys = jax.random.split(jax.random.fold_in(rng0, chunk_no), nchunk)

        def body(Fc, key_t):
            ks, km, kb = jax.random.split(key_t, 3)
            if mode == "drf":
                # forest mean-fit: constant pseudo-gradients, no feedback
                g = -Y1
                h = jnp.ones_like(Y1)
            else:
                Pr = jax.nn.softmax(Fc, axis=1)
                g = Pr - Y1
                h = jnp.maximum(Pr * (1 - Pr), 1e-10)
            wv = w
            if sample_rate < 1.0:
                wv = w * jax.random.bernoulli(ks, sample_rate, w.shape)
            # per-class key/mask derivation is IDENTICAL on both paths
            # (fold_in(kb, k) / fold_in(km, k)) so batched and separate
            # rounds draw the same columns and per-split subsets
            tms, kks = [], []
            for k in range(K):
                kks.append(jax.random.fold_in(kb, k))
                tm = jnp.ones((F,), bool)
                if col_sample_rate_per_tree < 1.0:
                    m = jax.random.uniform(
                        jax.random.fold_in(km, k),
                        (F,)) < col_sample_rate_per_tree
                    tm = m.at[0].set(m[0] | ~m.any())
                tms.append(tm)
            if batched:
                levels, vals, covers, leafK = bt_fn(
                    codes, (g * wv[:, None]).T, (h * wv[:, None]).T, wv,
                    edges_mat, jnp.stack(kks), reg_lambda, min_rows,
                    min_split_improvement, learn_rate, col_sample_rate,
                    jnp.stack(tms), reg_alpha, gamma, min_child_weight)
                dF = jax.vmap(
                    lambda v, l: table_lookup(v[None, :], l,
                                              v.shape[0])[0])(vals, leafK)
                return Fc + dF.T, (tuple(tuple(lvl) for lvl in levels),
                                   vals, covers)
            per_levels, per_vals, per_covers, dFs = [], [], [], []
            for k in range(K):
                levels, vals, cover, leaf = bt_fn(
                    codes, g[:, k] * wv, h[:, k] * wv, wv, edges_mat,
                    kks[k], reg_lambda, min_rows, min_split_improvement,
                    learn_rate, col_sample_rate, tms[k], reg_alpha, gamma,
                    min_child_weight)
                per_levels.append(levels)
                per_vals.append(vals)
                per_covers.append(cover)
                dFs.append(table_lookup(vals[None, :], leaf,
                                        vals.shape[0])[0])
            Fc = Fc + jnp.stack(dFs, axis=1)
            # stack class-k trees: per depth, each field gains a [K] dim
            lv = tuple(
                tuple(jnp.stack([per_levels[k][d][i] for k in range(K)])
                      for i in range(4))
                for d in range(max_depth))
            vals = jnp.stack(per_vals)
            covers = jnp.stack(per_covers)
            return Fc, (lv, vals, covers)

        Ff, (lv, vals, covers) = jax.lax.scan(body, F0, keys)
        return Ff, list(lv), vals, covers

    return _ledger("tree_scan_multinomial",
                   jax.jit(scan_fn, donate_argnums=(3,), static_argnums=(7,)),
                   static_argnums=(7,), orig=scan_fn)


@functools.lru_cache(maxsize=None)
def make_grid_scan_fn(G: int, mode: str, tweedie_power: float,
                      quantile_alpha: float, huber_alpha: float,
                      max_depth: int, nbins: int, F: int, n_padded: int,
                      hist_precision: str, custom_fn=None,
                      hist_mode: str = "subtract",
                      tree_program: str = "level"):
    """Scan a chunk of G-member GRID rounds in ONE dispatch.

    The hyperparameter analog of ``make_multinomial_scan_fn``: the K
    class-tree axis generalizes to G grid members of the SAME shape
    (max_depth/nbins/ntrees/layout), each carrying its OWN scalar
    hyperparameters as ``[G]`` operands — eta, row/column sample rates,
    lambda/alpha/gamma, ``min_rows``/``min_child_weight``/
    ``min_split_improvement``.  Anything that doesn't change trace shape
    batches; the shared ``[F, N]`` codes stay unbatched.

    Per-member RNG reproduces ``make_tree_scan_fn``'s sequential chains
    bitwise: each member supplies its own root key (``rng0G [G, 2]``),
    the chunk/tree/draw derivation (``fold_in(chunk_no)`` -> split ->
    ks/km/kb with the salt-0 fold) is vmapped per member, and vmapped
    threefry emits the per-key bits exactly — so a G-loop of sequential
    ``make_tree_scan_fn`` builds is this program's bitwise oracle.
    Row/column sampling draws ALWAYS happen here (the sequential path
    skips them statically at rate 1.0); a rate-1.0 member's mask is
    all-True and ``x * 1.0`` is an IEEE identity, so parity holds.

    ``alive [G]`` is the successive-halving retirement mask, a TRACED
    operand: retiring a member zeroes its row weights (all histograms
    empty -> every split invalid -> zero leaf values -> its F column
    freezes) without recompilation.

    Unlike the single/multinomial factories the per-member params are
    call operands, not factory constants — one compiled program serves
    the whole cohort across rungs.  Fused splits + dense layout only
    (grid cohorts gate mono/EFB/sparse to the wave path).
    """
    from ..distributions import make_distribution
    if G < 2:
        raise ValueError("make_grid_scan_fn needs G >= 2 (a single "
                         "member is the sequential path)")
    dist = None
    if mode != "drf":
        dist = make_distribution(
            mode, nclasses=2 if mode == "bernoulli" else 1,
            tweedie_power=tweedie_power, quantile_alpha=quantile_alpha,
            huber_alpha=huber_alpha, custom_distribution_func=custom_fn)
    bt_fn = make_build_tree_fn(max_depth, nbins, F, n_padded,
                               hist_precision, hist_mode=hist_mode,
                               nk=G, split_mode="fused",
                               hist_layout="dense",
                               tree_program=tree_program)

    def scan_fn(codes, y, w, F0, edges_mat, rng0G, chunk_no, nchunk,
                reg_lambda, min_rows, min_split_improvement, learn_rate,
                col_sample_rate, sample_rate, col_sample_rate_per_tree,
                alive, reg_alpha, gamma, min_child_weight):
        from .hist import table_lookup
        N = codes.shape[1]
        # per-member chunk keys, vmapped: [G, T, 2] -> scan xs [T, G, 2]
        keysG = jax.vmap(
            lambda r: jax.random.split(jax.random.fold_in(r, chunk_no),
                                       nchunk))(rng0G)
        keys = jnp.swapaxes(keysG, 0, 1)
        srG = jnp.broadcast_to(jnp.asarray(sample_rate, jnp.float32), (G,))
        csptG = jnp.broadcast_to(
            jnp.asarray(col_sample_rate_per_tree, jnp.float32), (G,))

        def body(Fc, keys_g):
            kk = jax.vmap(lambda k: jax.random.split(k, 3))(keys_g)
            ks, km, kb = kk[:, 0], kk[:, 1], kk[:, 2]
            # the sequential scan applies the salt fold unconditionally
            # (GBM salt=0, and fold_in(k, 0) != k) — replicate it
            km = jax.vmap(lambda k: jax.random.fold_in(k, 0))(km)
            kb = jax.vmap(lambda k: jax.random.fold_in(k, 0))(kb)
            if mode == "drf":
                g0 = jnp.broadcast_to(-y, Fc.shape)
                h0 = jnp.ones_like(Fc)
            else:
                g0, h0 = jax.vmap(dist.grad_hess, in_axes=(None, 0))(y, Fc)
            rs = jax.vmap(
                lambda k2, r: jax.random.bernoulli(k2, r, (N,)))(ks, srG)
            wv = (w[None, :] * rs) * alive[:, None]
            m = jax.vmap(
                lambda k2: jax.random.uniform(k2, (F,)))(km) \
                < csptG[:, None]
            tm = m.at[:, 0].set(m[:, 0] | ~m.any(axis=1))
            levels, vals, cover, leafG = bt_fn(
                codes, g0 * wv, h0 * wv, wv, edges_mat, kb, reg_lambda,
                min_rows, min_split_improvement, learn_rate,
                col_sample_rate, tm, reg_alpha, gamma, min_child_weight)
            dF = jax.vmap(
                lambda v, l: table_lookup(v[None, :], l,
                                          v.shape[0])[0])(vals, leafG)
            return Fc + dF, (tuple(tuple(lvl) for lvl in levels),
                             vals, cover)

        Ff, (lv, vals, covers) = jax.lax.scan(body, F0, keys)
        return Ff, list(lv), vals, covers

    return _ledger("tree_scan_grid",
                   jax.jit(scan_fn, donate_argnums=(3,), static_argnums=(7,)),
                   static_argnums=(7,), orig=scan_fn)


# jitted-program caches keyed on distribution parameters (pure functions of
# their key — custom UDF distributions bypass these)
_PREDS_JIT_CACHE: dict = {}
_PREP_JIT_CACHE: dict = {}


def tree_snapshot_state(chunks, init_host, edges) -> dict:
    """Model-so-far output override for a progress snapshot of a fused
    single-class tree build (runtime/snapshot.py): concatenates the
    trained chunks host-side (tree metadata — kilobytes) into exactly the
    fields ``resolve_checkpoint`` needs to continue the run."""
    st = StackedTrees.concat(list(chunks))
    return {"trees": TreeList(st), "ntrees_trained": st.ntrees,
            "init_score": init_host, "edges": edges}


def tree_snapshot_state_multi(chunks_k, init_host, edges) -> dict:
    """Multinomial variant of ``tree_snapshot_state`` (K per-class
    chunk lists -> TreeListMulti)."""
    stacks = [StackedTrees.concat(list(ch)) for ch in chunks_k]
    return {"trees": TreeListMulti(stacks),
            "ntrees_trained": stacks[0].ntrees,
            "init_score": init_host, "edges": edges}


def chunk_schedule(ntrees: int, score_tree_interval: int,
                   chunk_cap: int = 10, fence=None):
    """Yield (chunk_len, trees_done, score_now) for the scan driver loop.

    Chunks have a fixed length (``chunk_cap``) so every chunk reuses one
    compiled scan program; chunk boundaries land exactly on scoring
    intervals so early-stopping semantics match the per-tree loop.

    ``fence(trees_done) -> bool`` is the streaming-ingest rendezvous: it
    runs after the consumer has processed each yielded chunk, and a True
    return ends the schedule early so the driver can finalize on the
    trees built so far (the stream driver then re-bins the grown frame
    and continues via a checkpoint segment).
    """
    from ...runtime import failure, scheduler
    from .. import parallel
    interval = max(1, min(score_tree_interval, ntrees))
    cap = min(chunk_cap, interval)
    t = 0
    while t < ntrees:
        failure.maybe_inject("tree_chunk")
        # cooperative max_runtime_secs cancel: a deadline set by
        # map_builds (grid waves) or the cohort trainer fires HERE, at
        # the chunk fence, so an in-flight member stops between chunks
        # instead of overshooting the budget by a whole build
        parallel.check_deadline()
        # chunk boundaries are the fence for elastic mesh rebuilds: a
        # host join armed by the membership observer applies here, and
        # the next compile re-traces against the rebuilt mesh
        scheduler.chunk_fence()
        c = min(cap, ntrees - t, interval - (t % interval))
        t += c
        yield c, t, (t % interval == 0 or t >= ntrees)
        if fence is not None and t < ntrees and fence(t):
            return


def build_tree(codes, g, h, w, edges, nbins: int, max_depth: int,
               reg_lambda: float, min_rows: float, min_split_improvement: float,
               learn_rate: float, rng_key, col_sample_rate: float = 1.0,
               tree_col_mask: Optional[np.ndarray] = None,
               reg_alpha: float = 0.0, gamma: float = 0.0,
               min_child_weight: float = 0.0, hist_precision: str = "bf16",
               mono=None, hist_mode: str = "subtract",
               split_mode: str = "fused", hist_layout: str = "dense",
               sparse_depth_threshold: int = 8,
               tree_program: str = "level"):
    """Grow one tree — convenience wrapper around make_build_tree_fn.

    ``edges`` may be the per-feature edge list (converted to the dense
    lookup table here) or an already-built [F, nbins] matrix.
    Returns (Tree, final_leaf_assignment[N]); Tree fields stay on device
    until something materializes them.
    """
    from .binning import edges_matrix
    F, N = codes.shape
    if isinstance(edges, (list, tuple)):
        edges = edges_matrix(edges, nbins)
    edges_mat = jnp.asarray(edges, jnp.float32)
    tm = jnp.asarray(tree_col_mask, bool) if tree_col_mask is not None \
        else jnp.ones(F, bool)
    if mono is not None:
        split_mode = "separate"          # no fused path for these builds
        hist_layout = "dense"            # nor a sparse one (resolve_*)
        tree_program = "level"           # nor a scan-fused one
    fn = make_build_tree_fn(max_depth, nbins, F, N, hist_precision,
                            mono=mono, hist_mode=hist_mode,
                            split_mode=split_mode, hist_layout=hist_layout,
                            sparse_depth_threshold=sparse_depth_threshold,
                            tree_program=tree_program)
    from ...runtime import observability as obs
    with obs.span("tree_build", depth=max_depth, rows=int(N)):
        levels, vals, cover, leaf = fn(codes, g, h, w, edges_mat, rng_key,
                                       reg_lambda, min_rows,
                                       min_split_improvement, learn_rate,
                                       col_sample_rate, tm, reg_alpha,
                                       gamma, min_child_weight)
    tree = Tree([lv[0] for lv in levels], [lv[1] for lv in levels],
                [lv[2] for lv in levels], [lv[3] for lv in levels], vals,
                cover=cover)
    return tree, leaf


class SharedTreeModel(Model):
    """Tree-ensemble model: scores via compiled stacked-tree traversal."""

    def _calibration_curve(self, p1: np.ndarray) -> np.ndarray:
        cal = self.output.get("calibration")
        if cal is None:
            raise ValueError("model was not calibrated "
                             "(calibrate_model=True + calibration_frame)")
        if cal["method"] == "platt":
            return 1.0 / (1.0 + np.exp(-(cal["a"] * p1 + cal["b"])))
        return np.interp(p1, cal["x"], cal["y"])

    def calibrated_probabilities(self, frame: Frame) -> np.ndarray:
        """P(class 1) after calibration — CalibrationHelper.predict."""
        raw = np.asarray(self._predict_raw(
            self._score_matrix(frame)))[: frame.nrows]
        return self._calibration_curve(raw[:, 1] if raw.ndim == 2 else raw)

    def predict(self, frame: Frame) -> Frame:
        out = super().predict(frame)
        if self.output.get("calibration") is not None:
            from ...frame.vec import Vec
            # reuse the class-1 probability column already computed —
            # no second traversal of the ensemble
            dom = self.datainfo.response_domain
            p1 = self._calibration_curve(out.vec(str(dom[1])).to_numpy())
            out = out.with_vec("cal_p0", Vec.from_numpy(1.0 - p1))
            out = out.with_vec("cal_p1", Vec.from_numpy(p1))
        return out

    def varimp(self, frame: Optional[Frame] = None,
               method: str = "cover") -> dict:
        """Variable importances — hex/tree VarImp analog.

        ``method="cover"``: per-feature sum of training covers at the
        nodes that split on it (cover-weighted split frequency; computed
        from the recorded leaf covers, no data pass).  ``method="shap"``:
        mean |TreeSHAP contribution| over ``frame`` (needs a frame;
        binomial/regression only).  Returns {feature: relative importance}
        scaled so the max is 1.
        """
        names = [s.name for s in self.datainfo.specs]
        if method == "shap":
            if frame is None:
                raise ValueError("varimp(method='shap') needs a frame")
            contrib = self.predict_contributions(frame).to_numpy()[:, :-1]
            imp = np.abs(contrib).mean(axis=0)
        else:
            from ...export.treeshap import shap_trees_from_model
            imp = np.zeros(len(names))
            trees = list(self.output["trees"])
            if trees and isinstance(trees[0], list):
                trees = [tc for kt in trees for tc in kt]  # multinomial
            for t in shap_trees_from_model(trees):
                for d in range(t.depth):
                    valid = t.valid[d]
                    cover = t.cover[d]
                    feats = t.feat[d]
                    for i in np.flatnonzero(valid):
                        imp[int(feats[i])] += cover[i]
        mx = imp.max()
        rel = imp / mx if mx > 0 else imp
        order = np.argsort(-rel)
        return {names[i]: float(rel[i]) for i in order}

    def predict_contributions(self, frame: Frame) -> Frame:
        """Per-feature TreeSHAP contributions + BiasTerm (margin space).

        Reference: EasyPredictModelWrapper.predictContributions /
        PredictTreeSHAPTask — binomial and regression models only, exact
        Shapley values per Lundberg's TreeSHAP using the per-node covers
        recorded at training.  ``sum(contributions) + BiasTerm`` equals
        the raw margin (GBM/XGBoost) or the averaged leaf sum (DRF).
        """
        from ...export import treeshap
        K = self.output.get("nclass_trees", 1)
        if K > 1:
            raise ValueError("predict_contributions supports binomial and "
                             "regression models only (reference parity)")
        trees = list(self.output["trees"])
        st = treeshap.shap_trees_from_model(trees)
        X = np.asarray(self._design(frame))[: frame.nrows].astype(np.float64)
        if self.algo == "drf":
            scale, init = 1.0 / max(len(trees), 1), 0.0
        else:
            scale, init = 1.0, float(np.asarray(self.output["init_score"]))
        contribs = treeshap.ensemble_contributions(st, X, init, scale)
        names = [s.name for s in self.datainfo.specs] + ["BiasTerm"]
        from ...frame.vec import Vec
        vecs = [Vec.from_numpy(contribs[:, j]) for j in range(len(names))]
        return Frame(names, vecs)

    def _score_matrix(self, frame: Frame) -> jax.Array:
        return self._design(frame)

    def _design(self, frame: Frame) -> jax.Array:
        """Raw-value matrix [padded, F]: numerics as-is, cats as codes."""
        return jnp.stack(self._design_columns(frame), axis=1)

    def _design_columns(self, frame: Frame) -> List[jax.Array]:
        """``_design``'s columns, [padded] each, unstacked."""
        di = self.datainfo
        cols = []
        for s in di.specs:
            vec = frame.vec(s.name)
            if s.type == T_CAT:
                codes = di._aligned_codes(vec, s)
                cols.append(jnp.where(codes < 0, jnp.nan,
                                      codes.astype(jnp.float32)))
            else:
                cols.append(vec.values())
        return cols

    def _raw_scores(self, X: jax.Array):
        from ...runtime import observability as obs
        init = self.output["init_score"]
        K = self.output.get("nclass_trees", 1)
        stacked = self.output.get("stacked")

        def walk(st):
            obs.inc("traverse_dispatch_total",
                    path=traverse_path(st.depth, X.shape[1]))
            return traverse_jit(st.levels, st.values, X)
        if K == 1:
            if stacked is None:
                stacked = StackedTrees.from_trees(self.output["trees"])
                self.output["stacked"] = stacked
            return init + walk(stacked)
        if stacked is None:
            trees = self.output["trees"]
            stacked = [StackedTrees.from_trees([t[k] for t in trees])
                       for k in range(K)]
            self.output["stacked"] = stacked
        return jnp.stack([init[k] + walk(stacked[k]) for k in range(K)],
                         axis=1)


def resolve_checkpoint(params, di, algo: str):
    """Load + validate a checkpoint model for continued training.

    Reference: ``hex/Model.java:521`` (checkpoint support for DL/DRF/GBM/
    XGBoost) and GBM.java's non-modifiable-parameter check: the continued
    run must keep the tree geometry (max_depth, nbins, distribution) and
    ask for MORE trees; the prior model's bin edges are reused so codes
    stay consistent across the two runs.
    """
    ckpt = params.checkpoint
    if ckpt is None:
        return None
    prior = ckpt if not isinstance(ckpt, str) else dkv.get(ckpt)
    if prior is None:
        raise ValueError(f"checkpoint {ckpt!r} not found in DKV")
    if prior.algo != algo:
        raise ValueError(f"checkpoint algo {prior.algo!r} != {algo!r}")
    for attr in ("max_depth", "nbins", "distribution", "response_column",
                 "histogram_type"):
        a, b = getattr(prior.params, attr, None), getattr(params, attr, None)
        if a != b:
            raise ValueError(
                f"checkpoint parameter mismatch: {attr} was {a!r}, now {b!r}"
                " (non-modifiable for checkpoint continuation)")
    prior_nt = prior.output["ntrees_trained"]
    if params.ntrees <= prior_nt:
        raise ValueError(
            f"ntrees={params.ntrees} must exceed the checkpoint's "
            f"{prior_nt} trees")
    prior_cols = [s.name for s in prior.datainfo.specs]
    cols = [s.name for s in di.specs]
    if prior_cols != cols:
        raise ValueError("checkpoint feature columns differ from frame")
    return prior


def checkpoint_binned(frame: Frame, di: DataInfo, prior, nbins: int):
    """Re-encode a frame with the checkpoint model's stored bin edges."""
    from .binning import BinnedFrame, encode_bins
    names = [s.name for s in di.specs]
    is_cat = [s.type == T_CAT for s in di.specs]
    edges = prior.output["edges"]
    codes = encode_bins(frame, names, edges, is_cat, nbins)
    domains = [frame.vec(n).domain if c else None
               for n, c in zip(names, is_cat)]
    return BinnedFrame(codes=codes, edges=edges, names=names,
                       is_cat=is_cat, cat_domains=domains, nbins=nbins)


def prior_stacked(prior, k: Optional[int] = None) -> "StackedTrees":
    """The checkpoint's ensemble as StackedTrees (class k for multinomial)."""
    st = prior.output.get("stacked")
    if st is not None:
        if k is not None and isinstance(st, list):
            return st[k]
        if k is None and not isinstance(st, list):
            return st
    trees = prior.output["trees"]
    if k is not None:
        return StackedTrees.from_trees([t[k] for t in trees])
    return StackedTrees.from_trees(list(trees))


class SharedTree(ModelBuilder):
    """Common driver: binning, main loop, scoring, early stopping."""

    # the tree family honors params.checkpoint, which also unlocks
    # train(warm_start=...) and StreamingFrame stream training
    _supports_checkpoint = True

    #: builders whose fused driver can grow G same-shape grid members as
    #: one batched program (models/tree/grid_batch.py); opted in per
    #: subclass — the batched trainer mirrors GBM's fused chunk loop
    _grid_batchable = False

    def __init__(self, params: SharedTreeParameters):
        # a knob value no resolver accepts is refused at construction,
        # before any frame or device is touched; a fit runs the resolvers
        # again, with its monotone / EFB context (autotune.resolve_tree_knobs)
        resolve_hist_mode(params)
        resolve_split_mode(params)
        resolve_hist_layout(params)
        resolve_tree_program(params)
        super().__init__(params)

    def _validate(self, frame) -> None:
        super()._validate(frame)
        if getattr(self.params, "monotone_constraints", None) and \
                self.algo not in ("gbm", "xgboost"):
            raise ValueError(
                "monotone_constraints is only enforced for GBM/XGBoost; "
                f"{self.algo} would silently ignore it")
        p = self.params
        if getattr(p, "calibrate_model", False):
            # fail BEFORE training, not after (CalibrationHelper checks)
            if getattr(p, "calibration_frame", None) is None:
                raise ValueError(
                    "calibrate_model=True needs calibration_frame")
            if getattr(p, "calibration_method", "platt") not in (
                    "platt", "isotonic"):
                raise ValueError("calibration_method: platt | isotonic")
            rc = p.response_column
            dom = frame.vec(rc).domain if rc in frame.names else None
            if dom is not None and len(dom) != 2:
                raise ValueError("calibration supports binomial models only")

    def _post_fit(self, model, frame, valid) -> None:
        """Probability calibration on a held-out frame —
        hex/tree/CalibrationHelper (Platt scaling / isotonic)."""
        p = self.params
        if not getattr(p, "calibrate_model", False):
            return
        cal_fr = p.calibration_frame
        di = model.datainfo
        if not di.is_classifier or di.nclasses != 2:
            raise ValueError("calibration supports binomial models only")
        raw = np.asarray(model._predict_raw(
            model._score_matrix(cal_fr)))[: cal_fr.nrows]
        p1 = np.clip(raw[:, 1] if raw.ndim == 2 else raw, 1e-12, 1 - 1e-12)
        y = np.asarray(di.response(cal_fr))[: cal_fr.nrows]
        ok = np.isfinite(y)
        p1, y = p1[ok], y[ok]
        if p.calibration_method == "isotonic":
            from ..isotonic import _pav
            order = np.argsort(p1)
            ys = _pav(y[order].astype(np.float64),
                      np.ones(len(y), np.float64))
            model.output["calibration"] = {
                "method": "isotonic", "x": p1[order], "y": ys}
        else:
            # Platt: logistic regression of y on the raw score (1-D IRLS)
            a, b = 1.0, 0.0
            for _ in range(25):
                eta = a * p1 + b
                mu = 1.0 / (1.0 + np.exp(-eta))
                wq = np.maximum(mu * (1 - mu), 1e-9)
                z = eta + (y - mu) / wq
                X2 = np.stack([p1, np.ones_like(p1)], axis=1)
                A = (X2 * wq[:, None]).T @ X2
                rhs = (X2 * wq[:, None]).T @ z
                sol = np.linalg.solve(A + 1e-9 * np.eye(2), rhs)
                if abs(sol[0] - a) + abs(sol[1] - b) < 1e-9:
                    a, b = float(sol[0]), float(sol[1])
                    break
                a, b = float(sol[0]), float(sol[1])
            model.output["calibration"] = {"method": "platt",
                                           "a": a, "b": b}

    def _make_datainfo(self, frame: Frame) -> DataInfo:
        p = self.params
        return DataInfo.fit(
            frame, response_column=p.response_column if self.supervised else None,
            ignored_columns=p.ignored_columns, weights_column=p.weights_column,
            offset_column=p.offset_column, standardize=False,
            missing_values_handling="mean_imputation",
            force_classification=getattr(self, "_force_classification", False))

    def _score_and_log(self, model, it, F_train, y, w, di, dist, history,
                       valid_state):
        from ...metrics.core import make_metrics
        raw = self._scores_to_preds(F_train, dist, di)
        m = make_metrics(di, raw, y, w)
        entry = {"iteration": it, **m.describe()}
        mv = None
        if valid_state is not None:
            F_v, y_v, w_v = valid_state
            mv = make_metrics(di, self._scores_to_preds(F_v, dist, di),
                              y_v, w_v)
            entry.update({f"valid_{k}": v for k, v in mv.describe().items()})
        history.append(entry)
        # stash for _finalize_fused: when the last interval lands on the
        # final tree count, finalize reuses these instead of recomputing a
        # full-frame metrics pass (and a whole-ensemble valid traverse)
        model._interval_metrics = (it, m, mv)
        return m

    def _prep_targets(self, y, w, dist):
        """(y NaN-cleaned, init score) in ONE jitted program — the eager
        chain (isnan/where + the distribution's init reductions) costs a
        dispatch and a 10M-row temporary per op."""
        if dist.name == "custom":
            y0 = jnp.where(jnp.isnan(y), 0.0, y)
            return y0, dist.init_score(y0, w)
        key = (dist.name, getattr(dist, "p", None),
               getattr(dist, "alpha", None), getattr(dist, "delta", None))
        fn = _PREP_JIT_CACHE.get(key)
        if fn is None:
            def _prep(yv, wv, _d=dist):
                y0 = jnp.where(jnp.isnan(yv), 0.0, yv)
                return y0, _d.init_score(y0, wv)
            fn = jax.jit(_prep)
            _PREP_JIT_CACHE[key] = fn
        return fn(y, w)

    def _interval_score(self, model, t_done, F, y, w, di, dist, history,
                        vstate, metric_name, maximize) -> bool:
        """Score at an interval boundary; True = early-stop now (the
        shared tail of every fused chunk loop)."""
        p = self.params
        self._score_and_log(model, t_done, F, y, w, di, dist, history,
                            vstate)
        if not p.stopping_rounds:
            return False
        key = (f"valid_{metric_name}" if vstate is not None
               else metric_name)
        series = [hh.get(key) for hh in history if hh.get(key) is not None]
        return bool(series and stop_early(series, p.stopping_rounds,
                                          p.stopping_tolerance, maximize))

    def _scores_to_preds(self, F, dist, di):
        # jitted + cached: eagerly, the clip/stack chain pays a dispatch
        # and a full-length temporary per op
        kind = ("multi" if di.is_classifier and di.nclasses > 2
                else "binomial" if di.is_classifier else "regression")
        if dist.name == "custom":
            # user UDF linkinv: not keyable — keep the eager path
            if kind == "multi":
                return jax.nn.softmax(F, axis=1)
            if kind == "binomial":
                p1 = jnp.clip(dist.linkinv(F), 0.0, 1.0)
                return jnp.stack([1 - p1, p1], axis=1)
            return dist.linkinv(F)
        key = (kind, dist.name, getattr(dist, "p", None),
               getattr(dist, "alpha", None), getattr(dist, "delta", None))
        fn = _PREDS_JIT_CACHE.get(key)
        if fn is None:
            if kind == "multi":
                fn = jax.jit(lambda Fv: jax.nn.softmax(Fv, axis=1))
            elif kind == "binomial":
                def _binp(Fv, _d=dist):
                    p1 = jnp.clip(_d.linkinv(Fv), 0.0, 1.0)
                    return jnp.stack([1 - p1, p1], axis=1)
                fn = jax.jit(_binp)
            else:
                fn = jax.jit(lambda Fv, _d=dist: _d.linkinv(Fv))
            _PREDS_JIT_CACHE[key] = fn
        return fn(F)
