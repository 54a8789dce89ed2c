"""xprof: the device/compiler observability plane.

PR 8's telemetry plane (runtime/observability.py) stops at the dispatch
boundary: spans and histograms time HOST work, and nothing records when
XLA recompiles a program, what a compiled program costs in FLOPs/bytes,
or how much of a bench section's wall clock was compilation.  This
module is the layer below that boundary, riding the same metric
registry:

* **Compile ledger** — every cached-program seam (the hist/level
  builders, the tree scan programs, ``map_reduce``, GLM's path runner,
  the fused split search) wraps its ``jax.jit`` product in
  ``register_program(name, jitted)``.  The wrapper compiles
  ahead-of-time (``lower().compile()``) on each new argument signature,
  timing the compile into ``compile_seconds{program}``, bumping
  ``recompiles_total{program,reason}`` and publishing the compiled
  program's ``cost_analysis()`` / ``memory_analysis()`` as
  ``program_flops{program}``, ``program_bytes_accessed{program}`` and
  ``program_temp_bytes{program}`` gauges.  Called under an active trace
  the wrapper is transparent (the program inlines into the outer trace
  exactly as before).  An error from the compiler or the device is
  raised where it happens and never retried through the plain jit; only
  misuse of the wrapper itself (an object with no ``.lower``, statics
  that do not mirror the jit's) downgrades it to the plain function,
  with an ``xprof_fallback`` event.

  Recompile reasons: ``first`` (program name never compiled in this
  process), ``cluster_reinit`` (first compile after
  ``cluster._invalidate_compiled_caches()`` flushed the compiled
  caches), ``shape_change`` (every other recompile — a new argument
  signature, or a seam that rebuilds its program per call, like
  ``map_reduce`` over a fresh lambda).

* **jax.monitoring backstop** — a duration listener on
  ``/jax/core/compile/*`` records every backend compile jax performs,
  including seams the ledger does not wrap, into
  ``jax_compile_seconds{event}``.

* **Device-phase timing** — ``tree_phase_seconds`` measures host
  dispatch only (the level loop runs at trace time).  With
  ``H2O3_TPU_DEVICE_TIMING=sampled|full``, ``maybe_device_sync``
  block-until-ready-syncs eagerly-dispatched work (every Nth call under
  ``sampled``; every call under ``full``) and records the true
  dispatch→ready wall time into ``tree_phase_device_seconds{phase}``.
  ``bench_pieces.py xprof`` pins the ``sampled`` overhead < 2%.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, Optional, Tuple

from . import observability as obs

_lock = threading.Lock()

# name -> ledger entry (survives builder-LRU clears and metric resets,
# so recompile REASONS stay correct across cluster re-inits)
_LEDGER: "collections.OrderedDict[str, dict]" = collections.OrderedDict()

# global invalidation epoch: cluster._invalidate_compiled_caches() bumps
# it; wrappers compare their snapshot per call and drop stale compiled
# executables (which closed over the dead mesh) without any per-wrapper
# bookkeeping on the invalidation side.
_EPOCH = 0

# cap of AOT-compiled signatures retained per program (oldest evicted);
# jax's own jit cache backs anything beyond it
_MAX_SIGS_PER_PROGRAM = 32


# ------------------------------------------------------------- signatures

def _sig_of(x) -> tuple:
    """Signature atom: arrays by (shape, dtype, sharding), scalars by
    type (jit traces python scalars to one weak-typed aval per type),
    containers structurally.  Statics are keyed by VALUE by the caller."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        sharding = getattr(x, "sharding", None)
        return ("a", tuple(shape), str(dtype),
                str(sharding) if sharding is not None else "")
    if isinstance(x, (bool, int, float, complex)) or x is None:
        return ("s", type(x).__name__)
    if isinstance(x, (tuple, list)):
        return ("t", tuple(_sig_of(v) for v in x))
    return ("o", type(x).__name__, repr(x)[:120])


def _static_key(x) -> tuple:
    try:
        hash(x)
        return ("v", x)
    except TypeError:
        return ("v", repr(x)[:200])


# ---------------------------------------------------------------- ledger

def _note_compile(name: str, seconds: float, compiled) -> str:
    """Record one compile into the ledger + registry; returns the reason."""
    global _EPOCH
    with _lock:
        ent = _LEDGER.get(name)
        if ent is None:
            reason = "first"
            ent = _LEDGER.setdefault(name, {
                "compiles": 0, "compile_s": 0.0, "last_compile_s": 0.0,
                "reasons": collections.Counter(), "epoch": _EPOCH,
                "flops": None, "bytes_accessed": None, "temp_bytes": None,
            })
        elif ent["epoch"] != _EPOCH:
            reason = "cluster_reinit"
        else:
            reason = "shape_change"
        ent["epoch"] = _EPOCH
        ent["compiles"] += 1
        ent["compile_s"] += seconds
        ent["last_compile_s"] = seconds
        ent["reasons"][reason] += 1
    obs.observe("compile_seconds", seconds, program=name)
    obs.inc("recompiles_total", program=name, reason=reason)
    _publish_costs(name, compiled)
    return reason


def _publish_costs(name: str, compiled) -> None:
    """cost_analysis()/memory_analysis() -> per-program gauges + ledger."""
    flops = bytes_accessed = temp = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if isinstance(ca, dict):
            flops = ca.get("flops")
            bytes_accessed = ca.get("bytes accessed")
    except Exception:                    # noqa: BLE001 — backend-optional
        pass
    try:
        ma = compiled.memory_analysis()
        temp = getattr(ma, "temp_size_in_bytes", None)
    except Exception:                    # noqa: BLE001
        pass
    if flops is not None:
        obs.set_gauge("program_flops", float(flops), program=name)
    if bytes_accessed is not None:
        obs.set_gauge("program_bytes_accessed", float(bytes_accessed),
                      program=name)
    if temp is not None:
        obs.set_gauge("program_temp_bytes", float(temp), program=name)
    with _lock:
        ent = _LEDGER.get(name)
        if ent is not None:
            if flops is not None:
                ent["flops"] = float(flops)
            if bytes_accessed is not None:
                ent["bytes_accessed"] = float(bytes_accessed)
            if temp is not None:
                ent["temp_bytes"] = float(temp)


def invalidate(reason: str = "cluster_reinit") -> None:
    """Mark every registered program stale (cluster re-init flushes the
    compiled caches): the NEXT compile of each program is attributed to
    ``reason`` and wrappers drop their stale executables lazily."""
    global _EPOCH
    with _lock:
        _EPOCH += 1
    obs.record("xprof_invalidate", reason=reason)


def ledger_snapshot() -> dict:
    """Plain-data view of the compile ledger (bench compile-vs-steady
    split, the tier-1 compile-stats artifact, /metrics cross-checks)."""
    with _lock:
        programs = {
            name: {
                "compiles": ent["compiles"],
                "compile_s": round(ent["compile_s"], 6),
                "last_compile_s": round(ent["last_compile_s"], 6),
                "reasons": dict(ent["reasons"]),
                "flops": ent["flops"],
                "bytes_accessed": ent["bytes_accessed"],
                "temp_bytes": ent["temp_bytes"],
            }
            for name, ent in _LEDGER.items()
        }
        epoch = _EPOCH
    return {
        "programs": programs,
        "epoch": epoch,
        "total_compiles": sum(p["compiles"] for p in programs.values()),
        "total_compile_s": round(
            sum(p["compile_s"] for p in programs.values()), 6),
    }


def reset_ledger() -> None:
    """Tests only: forget every program (reasons restart at 'first')."""
    with _lock:
        _LEDGER.clear()


# ------------------------------------------------------------- registrar

def _tracing(args, kwargs) -> bool:
    """Whether the call is being traced into an outer program: some
    argument is a tracer."""
    import jax
    return any(isinstance(x, jax.core.Tracer)
               for x in jax.tree_util.tree_leaves((args, kwargs)))


class _Program:
    """AOT-compiling wrapper around one jitted program (see module doc).

    Calls with a previously-seen signature dispatch the stored compiled
    executable directly (no retrace); a new signature pays one timed
    ``lower().compile()``.  Under an active jax trace, or once the wrapper
    is found misused, calls go straight to the wrapped function."""

    def __init__(self, name: str, jitted, static_argnums: Tuple[int, ...],
                 static_argnames: Tuple[str, ...], orig=None):
        self.name = name
        self.jitted = jitted
        self.orig = orig if orig is not None else jitted
        self.static_argnums = tuple(static_argnums)
        self.static_argnames = tuple(static_argnames)
        self.fallback = False
        self.calls = 0
        self.compiled: "collections.OrderedDict[tuple, Any]" = \
            collections.OrderedDict()
        self.epoch = _EPOCH
        self.__name__ = name
        self.__qualname__ = name

    def _sig(self, args, kwargs) -> tuple:
        parts = []
        for i, a in enumerate(args):
            parts.append(_static_key(a) if i in self.static_argnums
                         else _sig_of(a))
        for k in sorted(kwargs):
            parts.append((k, _static_key(kwargs[k])
                          if k in self.static_argnames
                          else _sig_of(kwargs[k])))
        return tuple(parts)

    def _strip_static(self, args, kwargs):
        dyn_args = tuple(a for i, a in enumerate(args)
                         if i not in self.static_argnums)
        dyn_kwargs = {k: v for k, v in kwargs.items()
                      if k not in self.static_argnames}
        return dyn_args, dyn_kwargs

    def _compile(self, args, kwargs):
        # an error from the compiler (XLA, Mosaic lowering, out of
        # memory) is raised here, once: retrying through the plain jit
        # would pay the same compile again only to fail the same way
        t0 = time.perf_counter()
        compiled = self.jitted.lower(*args, **kwargs).compile()
        _note_compile(self.name, time.perf_counter() - t0, compiled)
        return compiled

    def _misused(self, stage: str, error: str) -> None:
        """The wrapper itself was set up wrongly (not a jit product, or
        statics that do not mirror the jit's own): observe nothing, call
        the wrapped function as if it had never been registered."""
        self.fallback = True
        self.compiled.clear()
        obs.record("xprof_fallback", program=self.name, stage=stage,
                   error=error)

    def __call__(self, *args, **kwargs):
        if self.fallback or not obs.enabled():
            return self.jitted(*args, **kwargs)
        if _tracing(args, kwargs):
            # inline the ORIGINAL function into the outer program, without
            # a nested-jit hop, exactly as before registration
            return self.orig(*args, **kwargs)
        if not hasattr(self.jitted, "lower"):
            self._misused("compile", "AttributeError")
            return self.jitted(*args, **kwargs)
        if self.epoch != _EPOCH:
            # cluster re-init flushed the mesh these executables bound
            self.compiled.clear()
            self.epoch = _EPOCH
        sig = self._sig(args, kwargs)
        compiled = self.compiled.get(sig)
        if compiled is None:
            compiled = self._compile(args, kwargs)
            self.compiled[sig] = compiled
            while len(self.compiled) > _MAX_SIGS_PER_PROGRAM:
                self.compiled.popitem(last=False)
        dyn_args, dyn_kwargs = self._strip_static(args, kwargs)
        self.calls += 1
        t0 = time.perf_counter()
        try:
            out = compiled(*dyn_args, **dyn_kwargs)
        except TypeError as e:
            # the executable rejected the stripped argument list before
            # anything reached the device; device errors are not caught
            self._misused("call", type(e).__name__)
            return self.jitted(*args, **kwargs)
        maybe_device_sync(self.name, self.calls, t0, out)
        return out

    # the builders' LRU values are sometimes introspected (and passed to
    # jax.export, which duck-checks the stages.Wrapped protocol: lower +
    # trace); delegate the common jit surface so the wrapper stays a
    # drop-in
    def lower(self, *args, **kwargs):
        return self.jitted.lower(*args, **kwargs)

    def trace(self, *args, **kwargs):
        return self.jitted.trace(*args, **kwargs)

    def __repr__(self):
        return (f"<xprof.program {self.name!r} sigs={len(self.compiled)} "
                f"fallback={self.fallback}>")


def register_program(name: str, jitted, static_argnums: Tuple[int, ...] = (),
                     static_argnames: Tuple[str, ...] = (), orig=None):
    """Wrap a ``jax.jit`` product in the compile ledger (module doc).

    ``static_argnums``/``static_argnames`` MUST mirror the jit's own
    statics: statics key the signature by value and are stripped before
    invoking the compiled executable.  ``orig`` (optional) is the plain
    traceable function used when the wrapper is entered under an active
    trace — defaults to ``jitted`` (nested jit calls inline too)."""
    return _Program(name, jitted, static_argnums, static_argnames, orig)


# --------------------------------------------------- monitoring backstop

_listener_installed = False


def install_monitoring_listener() -> None:
    """Record every jax backend compile into ``jax_compile_seconds{event}``
    via ``jax.monitoring`` — the backstop for seams the ledger does not
    wrap.  Idempotent."""
    global _listener_installed
    from jax import monitoring
    with _lock:
        if _listener_installed:
            return
        _listener_installed = True

    def _on_duration(event: str, duration: float, **kw) -> None:
        if event.startswith("/jax/core/compile"):
            obs.observe("jax_compile_seconds", duration,
                        event=event.rsplit("/", 1)[-1])

    monitoring.register_event_duration_secs_listener(_on_duration)


# ----------------------------------------------------- device-phase time

def device_timing_mode() -> str:
    """Effective ``H2O3_TPU_DEVICE_TIMING``: ``off`` | ``sampled`` |
    ``full`` (unknown values read as ``off``)."""
    from .config import config
    mode = config().device_timing
    return mode if mode in ("sampled", "full") else "off"


def maybe_device_sync(phase: str, seq: int, started: float, out) -> bool:
    """Block until ``out`` is device-ready and record the dispatch→ready
    wall time into ``tree_phase_device_seconds{phase}``.

    ``started`` is the caller's ``time.perf_counter()`` taken BEFORE the
    dispatch, so the observation covers real device execution, not just
    the wait.  Under ``sampled`` only every Nth ``seq``
    (``H2O3_TPU_DEVICE_TIMING_SAMPLE``, default 4) syncs — the bounded-
    overhead mode training keeps on; ``full`` syncs every call.
    Returns whether a sync happened."""
    if not obs.enabled():
        return False
    mode = device_timing_mode()
    if mode == "off":
        return False
    if mode == "sampled":
        from .config import config
        every = max(int(config().device_timing_sample), 1)
        if seq % every:
            return False
    try:
        import jax
        jax.block_until_ready(out)
    except Exception:                    # noqa: BLE001 — tracers, tokens
        return False
    dt = time.perf_counter() - started
    obs.observe("tree_phase_device_seconds", dt, phase=phase)
    try:
        # feed the autotuner's measured-refinement loop: the sample
        # attributes to whatever config the calling thread's active
        # decision scope is running (no scope -> no-op)
        from . import autotune
        autotune.on_device_sample(phase, dt)
    except Exception:                    # noqa: BLE001 — observer only
        pass
    return True


def count_kernel_launches(fn, *args, **kwargs) -> int:
    """Static kernel-dispatch sites in ``fn``'s traced program.

    Traces ``fn`` on the given args (abstract evaluation only — nothing
    executes) and counts the jaxpr eqns that dispatch a compiled kernel
    program: ``shard_map`` (every hist/split/partition kernel seam goes
    through one) and ``pallas_call`` (a hand-written kernel outside a
    seam).  Sub-jaxprs of higher-order primitives (scan/cond/pjit/...)
    are descended and each body is counted ONCE — so a level-unrolled
    tree build reports one site per level while the scan-fused build
    reports a depth-independent handful.  That static count is the
    dispatch-overhead proxy the treescan bench pins: XLA launches the
    unrolled program's kernels one by one, while a ``lax.scan`` body is
    a single compiled loop on device.
    """
    import jax

    jaxpr = jax.make_jaxpr(fn, **kwargs)(*args)

    def _subjaxprs(v):
        out = []
        for x in (v if isinstance(v, (list, tuple)) else [v]):
            if hasattr(x, "jaxpr") and hasattr(x, "consts"):
                out.append(x.jaxpr)          # ClosedJaxpr
            elif hasattr(x, "eqns"):
                out.append(x)                # raw Jaxpr
        return out

    def _count(jx) -> int:
        n = 0
        for eqn in jx.eqns:
            if eqn.primitive.name in ("shard_map", "pallas_call"):
                n += 1
            for val in eqn.params.values():
                for sub in _subjaxprs(val):
                    n += _count(sub)
        return n

    return _count(jaxpr.jaxpr)
