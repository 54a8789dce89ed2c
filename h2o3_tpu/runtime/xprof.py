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

* **jax.monitoring listener** — a duration listener on
  ``/jax/core/compile/*`` records every jaxpr trace, lowering and backend
  compile jax performs, including seams the ledger does not wrap, into
  ``jax_compile_seconds{event, fun}``: ``fun`` is the traced function's
  name, so the series says what a fit re-traces.  A backend compile
  encloses jax's persistent cache, whose own events arrive on the same
  thread inside that interval; an event listener pairs them with it, and
  the ``backend_compile_duration`` series alone carries a third label,
  ``cache``: ``hit`` (retrieved), ``stored`` (compiled here and written),
  ``unstored`` (asked, nothing retrieved and nothing written: under
  jax's floors of compile time and entry size, so every process compiles
  it again) or ``off`` (the cache was not asked).  A hit's
  ``compile_time_saved_sec`` goes to ``jax_cache_saved_seconds{fun}``:
  what a cold process would pay for that function, read from a warm one.
  A compile that is not a hit also leaves a ``compile`` event on the
  ring, stamped with the span open on its thread.  ``ledger_snapshot()``
  lists all of it by function under ``jax``.

* **Idle time by span** — ``idle_by_span(xplane_path)`` reads a profiler
  trace: the gaps between the programs on the first TPU's ``XLA Modules``
  line, attributed to the innermost ``h2o3.*`` host span
  (``observability.span``) open over them.

* **Device-phase timing** — ``span_seconds{span="tree_phase"}`` is
  trace-time cost only (the level loop runs at trace time).  With
  ``H2O3_TPU_DEVICE_TIMING=sampled|full``, ``maybe_device_sync``
  block-until-ready-syncs eagerly-dispatched work (every Nth call under
  ``sampled``; every call under ``full``) and records the true
  dispatch→ready wall time into ``tree_phase_device_seconds{phase}``.
"""

from __future__ import annotations

import collections
import re
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


def ledger_programs() -> dict:
    """Plain-data view of the registered seams: name -> compiles, seconds,
    reasons and the executable's costs (what the autotuner reads)."""
    with _lock:
        return {
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


def ledger_snapshot() -> dict:
    """Plain-data view of the compile ledger (the tier-1 compile-stats
    artifact, /metrics cross-checks, ``GET /3/Profiler/compiles``):
    ``programs``, the registered seams, and ``jax``, every function the
    monitoring listener heard of.  It serialises the registry: not for a
    path that is timed."""
    programs = ledger_programs()
    return {
        "programs": programs,
        "epoch": _EPOCH,
        "total_compiles": sum(p["compiles"] for p in programs.values()),
        "total_compile_s": round(
            sum(p["compile_s"] for p in programs.values()), 6),
        "jax": _jax_table(),
    }


def _jax_table() -> list:
    """What the monitoring listener heard, one row a ``fun`` label: its
    seconds in all, ``[events, seconds]`` by ``event`` and, of the backend
    compiles, by ``cache``, and ``saved_s``, the compile seconds the
    persistent cache spared it; the row with the most seconds first.  Read
    from the registry, so it holds what ``/metrics`` holds."""
    rows: Dict[str, dict] = {}
    for s in obs.metrics_wire():
        labels = s["l"]
        if s["n"] not in ("jax_compile_seconds", "jax_cache_saved_seconds") \
                or "fun" not in labels:     # a series someone else registered
            continue
        row = rows.setdefault(labels["fun"], {
            "fun": labels["fun"], "seconds": 0.0, "by_event": {},
            "by_cache": {}, "saved_s": 0.0})
        if s["n"] == "jax_cache_saved_seconds":
            row["saved_s"] += s["s"]
            continue
        row["seconds"] += s["s"]
        for group, key in (("by_event", labels["event"]),
                           ("by_cache", labels.get("cache"))):
            if key is not None:
                cell = row[group].setdefault(key, [0, 0.0])
                cell[0] += s["n_obs"]
                cell[1] += s["s"]
    return sorted(rows.values(), key=lambda r: -r["seconds"])


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

# distinct values of jax_compile_seconds' ``fun`` label: every function
# jax traces is one, so the label is cut and capped
_FUN_CHARS = 64
_MAX_FUNS = 256
_funs: set = set()


def _fun_label(fun_name) -> str:
    fun = str(fun_name or "unknown")[:_FUN_CHARS]
    with _lock:
        if fun in _funs:
            return fun
        if len(_funs) < _MAX_FUNS:
            _funs.add(fun)
            return fun
    return "other"


# jax's persistent-cache events, which arrive on the compiling thread inside
# the enclosing backend_compile_duration -> the ``cache`` label they decide
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "unstored",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "stored",
}
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
_BACKEND_COMPILE = "backend_compile_duration"

# what the cache said of the backend compile in flight on this thread:
# ``cache`` and ``saved_s``, set by the events above, read and cleared when
# the compile's own duration arrives
_pending = threading.local()


def _on_event(event: str, **kw) -> None:
    cache = _CACHE_EVENTS.get(event)
    if cache is not None and obs.enabled():
        _pending.cache = cache


def _on_duration(event: str, duration: float, **kw) -> None:
    if not obs.enabled():
        return
    if event == _SAVED_EVENT:
        # less than 0 where the retrieval took longer than the compile
        _pending.saved_s = max(duration, 0.0)
        return
    if not event.startswith("/jax/core/compile"):
        return
    name = event.rsplit("/", 1)[-1]
    fun = _fun_label(kw.get("fun_name"))
    if name != _BACKEND_COMPILE:
        obs.observe("jax_compile_seconds", duration, event=name, fun=fun)
        return
    said = vars(_pending)
    cache, saved_s = said.pop("cache", "off"), said.pop("saved_s", 0.0)
    obs.observe("jax_compile_seconds", duration, event=name, fun=fun,
                cache=cache)
    if cache != "off":
        # a function that asked the cache has the series, empty until a
        # hit: a cold process reads 0 saved seconds, not nothing
        saved = obs.histogram("jax_cache_saved_seconds", fun=fun)
        if cache == "hit":
            saved.observe(saved_s)
    if cache != "hit":
        obs.record("compile", fun=fun, cache=cache, duration_s=duration,
                   **obs.open_span())


def install_monitoring_listener() -> None:
    """Record every jaxpr trace, lowering and backend compile jax performs
    into ``jax_compile_seconds{event, fun}`` via ``jax.monitoring`` — the
    complete per-program source (the ledger sees wrapped seams only) —
    and what the persistent cache did with each backend compile (module
    doc).  ``fun`` is the ``fun_name`` jax passes to its duration
    listeners, cut to 64 characters; past 256 distinct values it reads
    ``other``.  Idempotent."""
    global _listener_installed
    from jax import monitoring
    with _lock:
        if _listener_installed:
            return
        _listener_installed = True
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


# ------------------------------------------------------- idle time by span

SPAN_PREFIX = "h2o3."           # observability's profiler annotations
_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")


def attribute_idle(modules, spans, top: int = 10) -> dict:
    """Device idle seconds by the host span that was open.

    ``modules`` are the executed programs of one device and ``spans`` the
    host spans, both as ``(name, start_ns, end_ns)`` on one clock.  The
    window runs from the first start to the last end of either; a gap is
    an interval of it in which no program ran.  A gap is cut wherever a
    span opens or closes, and each piece goes to the innermost span (the
    latest to open) open over it, or to ``unattributed_s``: the one long
    gap at the end of a ``predict`` is the fetch, then the labels, then
    the upload, and its middle alone would name one of them.  ``top``
    lists the longest gaps whole, as ``(the span open at its middle,
    offset into the window in s, idle s)``."""
    events = list(modules) + list(spans)
    if not events:
        return {"by_span": {}, "unattributed_s": 0.0, "top": []}
    lo = min(s for _, s, _ in events)
    hi = max(e for _, _, e in events)
    gaps, cursor = [], lo
    for _, s, e in sorted(modules, key=lambda ev: ev[1]):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))

    def innermost(t, candidates):
        open_at = [sp for sp in candidates if sp[1] <= t < sp[2]]
        return max(open_at, key=lambda sp: (sp[1], -sp[2]))[0] \
            if open_at else ""

    # one sweep: gaps come in time order, so the spans that can overlap a
    # gap are those already opened and not yet closed before it (as many
    # as spans nest, however long the trace)
    pending = sorted(spans, key=lambda sp: sp[1], reverse=True)
    over: list = []
    by_span: Dict[str, float] = {}
    longest = []
    for s, e in gaps:
        while pending and pending[-1][1] < e:
            over.append(pending.pop())
        over = [sp for sp in over if sp[2] > s]
        cuts = sorted({s, e, *(t for sp in over for t in sp[1:] if s < t < e)})
        for a, b in zip(cuts, cuts[1:]):
            name = innermost((a + b) / 2, over)
            by_span[name] = by_span.get(name, 0.0) + (b - a) / 1e9
        longest.append((innermost((s + e) / 2, over), (s - lo) / 1e9,
                        (e - s) / 1e9))
    unattributed = by_span.pop("", 0.0)
    longest.sort(key=lambda g: -g[2])
    return {"by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
            "unattributed_s": unattributed, "top": longest[:top]}


def idle_by_span(xplane_path: str) -> dict:
    """``attribute_idle`` over a profiler trace (``.xplane.pb``): the
    ``XLA Modules`` line of the first ``/device:TPU:n`` plane against the
    ``h2o3.*`` events of the host planes, names without the prefix.  A
    trace with no TPU plane (a CPU run) has nothing to attribute."""
    import jax
    data = jax.profiler.ProfileData.from_file(xplane_path)
    modules, spans = [], []
    tpu = min((p.name for p in data.planes if _TPU_PLANE.match(p.name)),
              key=lambda name: int(name.rsplit(":", 1)[1]), default=None)
    for plane in data.planes:
        if plane.name == tpu:
            modules = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for line in plane.lines if line.name == "XLA Modules"
                       for e in line.events]
        elif plane.name.startswith("/host:"):
            spans += [(e.name[len(SPAN_PREFIX):], e.start_ns,
                       e.start_ns + e.duration_ns)
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    if not modules:
        return attribute_idle([], [])
    return attribute_idle(modules, spans)


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
