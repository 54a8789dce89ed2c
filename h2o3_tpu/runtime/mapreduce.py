"""The MRTask analog: sharded map + mesh-wide reduce as XLA programs.

Reference: ``water/MRTask.java`` (989 LoC) — user code is serialized, fanned
out over the cluster in a binary tree of RPCs (remote_compute,
MRTask.java:739-760), runs ``map(Chunk)`` on home-node chunks via ForkJoin
divide-and-conquer (compute2, :764-830), and ``reduce()``s partials up the
tree.  Code shipping requires the whole Iced/Weaver serialization machinery
(water/Weaver.java:14).

TPU-native redesign: there is no code shipping — a traced, jit-compiled SPMD
program IS the shipped code, and the reduce tree IS a hardware collective.
``map_reduce`` wraps a per-shard function in ``shard_map`` over the mesh's
row axes and combines partials with ``psum``, which replaces both MRTask's
RPC fan-out and its binary-tree reduce.

The reduce is HIERARCHICAL on the ``("hosts", "chips")`` mesh
(runtime/cluster.py): partials first psum around each host's ICI ring
(``"chips"``), then one small cross-host psum rides DCN (``"hosts"``).
That mirrors the reference's two-level reduce (node-local ForkJoin fold,
then the RPC tree) and keeps the large pre-reduce tensors off the slow
links.  The one-collective flat schedule stays available as the oracle
behind ``reduce_mode``:

  * ``"hier"``  — staged ICI-then-DCN psum (default; H2O3_TPU_REDUCE_MODE)
  * ``"flat"``  — single psum over the flattened product axis
  * ``"check"`` — run both whole programs and raise ``ReduceParityError``
                  on divergence

For most algorithms you don't even need ``map_reduce``: operating on
row-sharded arrays inside ``jax.jit`` lets GSPMD insert the collectives
automatically — use it when the per-shard view must be explicit
(histograms, per-partition state).
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .cluster import CHIP_AXIS, HOST_AXIS, ROW_AXES, ROW_AXIS, cluster
from jax import shard_map

REDUCE_MODES = ("flat", "hier", "check")

_forced_mode: str | None = None


class ReduceParityError(AssertionError):
    """flat and hier reductions disagreed (``reduce_mode="check"``)."""


def resolve_reduce_mode(mode: str | None = None) -> str:
    """Effective reduce mode: explicit arg > force_reduce_mode > config.

    ``"auto"`` (the config default) defers to the autotuner, which picks
    hier/flat per mesh geometry — and resolves to the historical fixed
    default (``hier``) when the tuner is off, so pinned runs stay
    bit-identical."""
    if not mode:
        mode = _forced_mode
    if not mode:
        from .config import config
        mode = config().reduce_mode
    if mode == "auto":
        from . import autotune
        mode = autotune.resolve_reduce_mode_auto()
    if mode not in REDUCE_MODES:
        raise ValueError(
            f"reduce_mode={mode!r} not in {REDUCE_MODES} + ('auto',)")
    return mode


@contextlib.contextmanager
def force_reduce_mode(mode: str):
    """Scoped override of the configured reduce mode (tests, benchmarks)."""
    if mode not in REDUCE_MODES and mode != "auto":
        raise ValueError(f"reduce_mode={mode!r} not in {REDUCE_MODES}")
    global _forced_mode
    prev = _forced_mode
    _forced_mode = mode
    try:
        yield
    finally:
        _forced_mode = prev


def psum_shards(x, mode: str = ""):
    """Sum ``x`` across every row shard, from inside a shard_map'd body.

    ``"flat"`` is one collective over the flattened product axis (the
    oracle).  ``"hier"`` stages it: psum around the host-local ``"chips"``
    ring first (ICI), then one ``"hosts"`` psum of the per-host partials
    (DCN) — same result, but the cross-host stage moves an already-reduced
    tensor.  ``"check"`` compiles the hier schedule here; the flat-vs-hier
    comparison runs one level up (``checked_pair``/``map_reduce``), where
    both whole programs can execute and be compared on the host.
    """
    mode = resolve_reduce_mode(mode or None)
    if mode == "flat":
        return jax.lax.psum(x, ROW_AXES)
    return jax.lax.psum(jax.lax.psum(x, CHIP_AXIS), HOST_AXIS)


def assert_reduce_parity(flat, hier, what: str = "map_reduce") -> None:
    """Compare flat/hier pytrees: bitwise first, tiny tolerance second.

    Integer-valued float stats (counts, quantized gradients) reduce
    bitwise-identically under both schedules; genuinely fractional floats
    may differ by reassociation ulps, which get recorded (not raised).
    Anything beyond tolerance raises ``ReduceParityError``.
    """
    from . import observability as obs
    flat_l, treedef_f = jax.tree.flatten(flat)
    hier_l, treedef_h = jax.tree.flatten(hier)
    if treedef_f != treedef_h:
        raise ReduceParityError(
            f"{what}: flat/hier output structures differ: "
            f"{treedef_f} vs {treedef_h}")
    for i, (a, b) in enumerate(zip(flat_l, hier_l)):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape == b.shape and a.tobytes() == b.tobytes():
            continue
        if a.shape == b.shape and np.allclose(a, b, rtol=1e-5, atol=1e-6,
                                              equal_nan=True):
            obs.record("reduce_parity_ulp", what=what, leaf=i)
            continue
        diff = np.max(np.abs(a.astype(np.float64) - b.astype(np.float64))) \
            if a.shape == b.shape else float("inf")
        raise ReduceParityError(
            f"{what}: flat/hier reduction divergence at leaf {i} "
            f"(shape {a.shape} vs {b.shape}, maxdiff {diff:.3e})")


def checked_pair(flat_fn: Callable, hier_fn: Callable,
                 what: str = "reduce") -> Callable:
    """Run both mode-variants of a program, compare, return the hier result.

    The ``reduce_mode="check"`` dispatcher: ``flat_fn``/``hier_fn`` are the
    same compiled program built with the two schedules (e.g. two entries of
    a builder's LRU cache keyed on ``reduce_mode``).
    """
    @functools.wraps(hier_fn)
    def run(*args, **kw):
        flat = flat_fn(*args, **kw)
        hier = hier_fn(*args, **kw)
        assert_reduce_parity(flat, hier, what=what)
        return hier
    return run


def map_partitions(fn: Callable, *arrays, out_spec=P(ROW_AXIS)):
    """Apply ``fn`` independently to each row-shard (the `map` half).

    ``fn`` sees the local shard of every input array and must return arrays
    whose row dim is the local shard size.  Equivalent of MRTask.map(Chunk)
    without a reduce.
    """
    mesh = cluster().mesh
    specs = tuple(P(ROW_AXIS, *([None] * (a.ndim - 1))) for a in arrays)
    f = shard_map(fn, mesh=mesh, in_specs=specs, out_specs=out_spec)
    return jax.jit(f)(*arrays)


# per-map_fn xprof wrappers, weakly keyed: a stable map_fn (module-level
# task) reuses its AOT-compiled program across calls instead of paying
# jax a fresh trace+compile per invocation; throwaway lambdas vanish
# with their entry.  Keyed further by (mode, ndims) since the shard_map
# specs depend on the operand ranks.
_MR_PROGRAMS: "weakref.WeakKeyDictionary[Callable, dict]" = None  # type: ignore


def _mr_program(map_fn: Callable, arrays, mode: str):
    global _MR_PROGRAMS
    if _MR_PROGRAMS is None:
        import weakref
        _MR_PROGRAMS = weakref.WeakKeyDictionary()
    from . import xprof
    mesh = cluster().mesh
    key = (mode, tuple(a.ndim for a in arrays), id(mesh))
    try:
        per_fn = _MR_PROGRAMS.setdefault(map_fn, {})
    except TypeError:                    # unweakrefable callable
        per_fn = {}
    prog = per_fn.get(key)
    if prog is None:
        def shard_fn(*local):
            partial = map_fn(*local)
            return jax.tree.map(lambda x: psum_shards(x, mode), partial)

        specs = tuple(P(ROW_AXIS, *([None] * (a.ndim - 1)))
                      for a in arrays)
        f = shard_map(shard_fn, mesh=mesh, in_specs=specs, out_specs=P())
        prog = xprof.register_program("map_reduce", jax.jit(f))
        per_fn[key] = prog
    return prog


def _map_reduce_once(map_fn: Callable, arrays, mode: str):
    from . import observability as obs
    prog = _mr_program(map_fn, arrays, mode)
    t0 = time.perf_counter()
    out = jax.block_until_ready(prog(*arrays))
    obs.observe("collective_seconds", time.perf_counter() - t0,
                axis="chips+hosts" if mode == "hier" else "rows",
                op="map_reduce")
    return out


def map_reduce(map_fn: Callable, *arrays, reduce_mode: str | None = None):
    """Full MRTask: per-shard map, then ``psum`` of the partials over rows.

    ``map_fn(*local_shards) -> pytree of partial reductions``; the result is
    the mesh-wide sum, replicated everywhere (MRTask.doAll + reduce()).
    Non-additive reductions (min/max) should be expressed by mapping into an
    additive/idempotent form first, exactly as reference MRTasks fold their
    state into arrays that reduce elementwise (e.g. DHistogram._vals adds).

    ``reduce_mode`` picks the collective schedule (module docstring); the
    default follows ``H2O3_TPU_REDUCE_MODE``/``force_reduce_mode``.
    """
    mode = resolve_reduce_mode(reduce_mode)
    if mode == "check":
        flat = _map_reduce_once(map_fn, arrays, "flat")
        hier = _map_reduce_once(map_fn, arrays, "hier")
        assert_reduce_parity(flat, hier, what="map_reduce")
        return hier
    return _map_reduce_once(map_fn, arrays, mode)


def psum_rows(x):
    """Replicated sum over the rows axis of a sharded array inside jit."""
    return jnp.sum(x, axis=0)
