"""Observability: cluster telemetry plane — metrics, traces, logs, events.

Reference: ``water/TimeLine.java:22`` (per-node ring buffer of runtime
events, surfaced by ``water/api/TimelineHandler.java:12``), ``water/util/
Log.java`` (logging facade with per-node files), the MRProfile timings,
and ``WaterMeterCpuTicksHandler`` (per-node metering).

TPU redesign, four planes in one module:

* **events** — a process-local ring of (ts, kind, fields) dicts covering
  the control plane; ``span()`` wraps a timed unit of work and records
  failures (``ok``/``error``) instead of swallowing them.
* **metrics** — a registry of monotonic counters, gauges, and fixed-
  bucket latency histograms keyed by ``(name, labels)``.  Histogram
  buckets are log-spaced and IDENTICAL in every process, so per-node
  snapshots merge by plain summation.  ``metrics_wire()`` serializes the
  registry onto the heartbeat stamp; the coordinator's ``/metrics``
  route merges every node's snapshot into one Prometheus exposition.
* **traces** — hierarchical spans with ``trace_id``/``span_id``/parent
  that ride the DKV RPC envelope (``current_trace()`` on the client,
  ``trace_context()`` on the handler), stitching coordinator phases,
  worker work, and DKV calls into one tree (``trace_forest()``).
* **device** — delegates to ``jax.profiler`` traces, which capture the
  XLA/TPU timeline far better than any hand-rolled counter could; the
  host-side spans here time dispatch, never device execution.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import logging
import os
import sys
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from .config import config

_LOG_RING = collections.deque(maxlen=2000)
_EVENTS = collections.deque(maxlen=2000)
_lock = threading.Lock()

# master switch (H2O3_TPU_METRICS / config().metrics_enabled): the
# instrumentation fast-path — span()/observe()/inc()/set_gauge() return
# immediately when off.  Read
# here, so that a process started with H2O3_TPU_METRICS=0 is off from its
# first span and not only after a config.reload()
_enabled = bool(config().metrics_enabled)


def set_enabled(on: bool) -> bool:
    """Flip the telemetry master switch; returns the previous state."""
    global _enabled
    prev, _enabled = _enabled, bool(on)
    return prev


def enabled() -> bool:
    return _enabled


def node_name() -> str:
    """This process's telemetry identity — same formula as heartbeat's."""
    import socket
    return f"{socket.gethostname()}:{os.getpid()}"


# ------------------------------------------------------------------ logging

class _RingHandler(logging.Handler):
    def emit(self, record):
        with _lock:
            _LOG_RING.append(self.format(record))


_LOG_FORMAT = logging.Formatter(
    "%(asctime)s %(levelname)s %(name)s: %(message)s")
_file_handler: Optional[logging.FileHandler] = None

log = logging.getLogger("h2o3_tpu")
if not log.handlers:
    _h = _RingHandler()
    _h.setFormatter(_LOG_FORMAT)
    log.addHandler(_h)
    if os.environ.get("H2O3_TPU_LOG_STDERR"):
        log.addHandler(logging.StreamHandler())
    log.setLevel(config().log_level)


def open_log_file(path: Optional[str] = None) -> Optional[str]:
    """Attach the per-node log-file handler (water/util/Log.java analog).

    ``path`` defaults to ``H2O3_TPU_LOG_FILE``; ``%h``/``%p`` expand to
    hostname/pid so every member of a multi-process cloud gets its own
    file from one shared env value.  Re-opening replaces the previous
    handler; returns the resolved path (None when unconfigured)."""
    global _file_handler
    if path is None:
        path = config().log_file
    if not path:
        return None
    import socket
    path = path.replace("%h", socket.gethostname()) \
               .replace("%p", str(os.getpid()))
    close_log_file()
    h = logging.FileHandler(path)
    h.setFormatter(_LOG_FORMAT)
    log.addHandler(h)
    _file_handler = h
    return path


def close_log_file() -> None:
    """Detach + close the log-file handler (dkv.detach / shutdown)."""
    global _file_handler
    if _file_handler is not None:
        log.removeHandler(_file_handler)
        try:
            _file_handler.close()
        except Exception:                # noqa: BLE001
            pass
        _file_handler = None


if os.environ.get("H2O3_TPU_LOG_FILE"):
    open_log_file()


def apply_config(cfg) -> None:
    """Re-apply config-driven telemetry state (config.reload)."""
    global _enabled
    log.setLevel(cfg.log_level)
    _enabled = bool(cfg.metrics_enabled)
    if cfg.log_file:
        open_log_file(cfg.log_file)
    else:
        close_log_file()


# ------------------------------------------------------------------- events

def record(kind: str, **fields) -> None:
    """Append a timeline event (water.TimeLine.record analog)."""
    with _lock:
        _EVENTS.append({"ts": time.time(), "kind": kind, **fields})


def timeline_events(limit: int = 500) -> List[Dict]:
    with _lock:
        return list(_EVENTS)[-int(limit):]


def recent_logs(limit: int = 500) -> List[str]:
    with _lock:
        return list(_LOG_RING)[-int(limit):]


def events_wire(limit: int = 200) -> List[Dict]:
    """Bounded event tail for the heartbeat stamp — per-node /3/Timeline
    sections and cross-process trace stitching read these back."""
    return timeline_events(limit)


# ------------------------------------------------------------------ metrics
#
# Registry keyed by (name, sorted (label, value) tuple).  All three types
# are cluster-mergeable: counters and histogram buckets by summation,
# gauges by last-writer (each node's gauge is a distinct labeled series).

# log-spaced latency buckets (seconds), ~100 us .. 500 s.  FIXED: every
# process shares the same edges, so shipped histograms merge by summing
# the bucket counts — never change these without a wire-format bump.
LATENCY_BUCKETS: Tuple[float, ...] = tuple(
    round(f * 10.0 ** e, 10)
    for e in range(-4, 3) for f in (1.0, 2.5, 5.0))

_LabelKey = Tuple[Tuple[str, str], ...]
_REGISTRY: "collections.OrderedDict[Tuple[str, _LabelKey], Any]" = \
    collections.OrderedDict()


class Counter:
    """Monotonic counter."""
    kind = "counter"

    __slots__ = ("name", "labels", "value")

    def __init__(self, name: str, labels: _LabelKey):
        self.name, self.labels, self.value = name, labels, 0.0

    def inc(self, delta: float = 1.0) -> None:
        with _lock:
            self.value += delta

    def wire(self) -> dict:
        return {"n": self.name, "l": dict(self.labels), "t": "c",
                "v": self.value}


class Gauge:
    """Last-value (or high-watermark) gauge."""
    kind = "gauge"

    __slots__ = ("name", "labels", "value")

    def __init__(self, name: str, labels: _LabelKey):
        self.name, self.labels, self.value = name, labels, 0.0

    def set(self, value: float) -> None:
        with _lock:
            self.value = float(value)

    def set_max(self, value: float) -> None:
        """Watermark semantics: keep the max ever seen."""
        with _lock:
            self.value = max(self.value, float(value))

    def wire(self) -> dict:
        return {"n": self.name, "l": dict(self.labels), "t": "g",
                "v": self.value}


class Histogram:
    """Fixed-bucket latency histogram, mergeable by summation.

    ``counts[i]`` counts observations <= ``buckets[i]``; the final slot
    is the +Inf overflow.  Cumulative conversion happens only at render
    time (Prometheus ``le`` buckets are cumulative)."""
    kind = "histogram"

    __slots__ = ("name", "labels", "buckets", "counts", "sum", "count")

    def __init__(self, name: str, labels: _LabelKey,
                 buckets: Tuple[float, ...] = LATENCY_BUCKETS):
        self.name, self.labels = name, labels
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        import bisect
        i = bisect.bisect_left(self.buckets, value)
        with _lock:
            self.counts[i] += 1
            self.sum += value
            self.count += 1

    def wire(self) -> dict:
        return {"n": self.name, "l": dict(self.labels), "t": "h",
                "b": list(self.buckets), "c": list(self.counts),
                "s": self.sum, "n_obs": self.count}


def _series(cls, name: str, labels: Dict[str, Any], **kw):
    key = (name, tuple(sorted((k, str(v)) for k, v in labels.items())))
    with _lock:
        m = _REGISTRY.get(key)
    if m is None:
        m = cls(name, key[1], **kw)
        with _lock:
            m = _REGISTRY.setdefault(key, m)
    return m


def counter(name: str, **labels) -> Counter:
    return _series(Counter, name, labels)


def gauge(name: str, **labels) -> Gauge:
    return _series(Gauge, name, labels)


def histogram(name: str, buckets: Tuple[float, ...] = LATENCY_BUCKETS,
              **labels) -> Histogram:
    return _series(Histogram, name, labels, buckets=buckets)


def inc(name: str, delta: float = 1.0, **labels) -> None:
    if _enabled:
        counter(name, **labels).inc(delta)


def set_gauge(name: str, value: float, **labels) -> None:
    if _enabled:
        gauge(name, **labels).set(value)


def observe(name: str, value: float, **labels) -> None:
    """Record one latency/size observation into a labeled histogram."""
    if _enabled:
        histogram(name, **labels).observe(value)


def metrics_wire() -> List[dict]:
    """Serialize the registry for the heartbeat stamp (plain data only)."""
    with _lock:
        series = list(_REGISTRY.values())
    return [m.wire() for m in series]


def reset_metrics() -> None:
    """Drop every registered series (tests)."""
    with _lock:
        _REGISTRY.clear()


def merge_wire(per_node: Dict[str, List[dict]]) -> List[dict]:
    """Merge per-node wire snapshots into one cluster view: every series
    gains a ``node`` label; identical fixed buckets mean a PromQL
    ``sum by (le)`` (or ``merge_histograms`` here) is exact."""
    out: List[dict] = []
    for node, series in sorted(per_node.items()):
        for s in series or []:
            s2 = dict(s)
            s2["l"] = {**s.get("l", {}), "node": node}
            out.append(s2)
    return out


def merge_histograms(series: Iterable[dict]) -> Optional[dict]:
    """Sum same-bucket histogram wire records (the mergeability contract
    the fixed log-spaced edges exist for)."""
    acc: Optional[dict] = None
    for s in series:
        if s.get("t") != "h":
            continue
        if acc is None:
            acc = {"n": s["n"], "l": {}, "t": "h", "b": list(s["b"]),
                   "c": list(s["c"]), "s": s["s"], "n_obs": s["n_obs"]}
            continue
        if list(s["b"]) != acc["b"]:
            raise ValueError(f"histogram {s['n']!r}: bucket edges differ")
        acc["c"] = [a + b for a, b in zip(acc["c"], s["c"])]
        acc["s"] += s["s"]
        acc["n_obs"] += s["n_obs"]
    return acc


# ------------------------------------------------------------ prometheus

def _prom_name(name: str) -> str:
    import re
    return re.sub(r"[^a-zA-Z0-9_:]", "_", name)


def _prom_labels(labels: Dict[str, str], extra: Optional[dict] = None) -> str:
    merged = {**labels, **(extra or {})}
    if not merged:
        return ""
    inner = ",".join(
        '%s="%s"' % (_prom_name(k),
                     str(v).replace("\\", r"\\").replace('"', r'\"'))
        for k, v in sorted(merged.items()))
    return "{%s}" % inner


def _render_series(lines: List[str], s: dict) -> None:
    name = _prom_name(s["n"])
    labels = s.get("l", {})
    if s["t"] == "h":
        cum = 0
        edges = list(s["b"]) + [float("inf")]
        for edge, c in zip(edges, s["c"]):
            cum += c
            le = "+Inf" if edge == float("inf") else repr(float(edge))
            lines.append(f"{name}_bucket{_prom_labels(labels, {'le': le})}"
                         f" {cum}")
        lines.append(f"{name}_sum{_prom_labels(labels)} {s['s']}")
        lines.append(f"{name}_count{_prom_labels(labels)} {s['n_obs']}")
    else:
        lines.append(f"{name}{_prom_labels(labels)} {s['v']}")


def render_prometheus(cluster: bool = True) -> str:
    """Prometheus text exposition (the GET /metrics body).

    Local series are labeled with this process's node name; with
    ``cluster=True`` every heartbeat stamp's shipped snapshot is merged
    in too (other nodes' series appear under their own ``node`` label),
    so one coordinator scrape covers the whole cloud.  The flat
    ``count()`` counters are exported as ``h2o3_events_total{kind=...}``.
    """
    me = node_name()
    per_node: Dict[str, List[dict]] = {me: metrics_wire()}
    with _lock:
        flat = dict(_COUNTERS)
    for k, v in sorted(flat.items()):
        per_node[me].append({"n": "h2o3_events_total",
                             "l": {"kind": k}, "t": "c", "v": v})
    if cluster:
        try:
            for node, stamp in cluster_stamps().items():
                if node != me and isinstance(stamp, dict):
                    per_node[node] = stamp.get("metrics") or []
        except Exception:                 # noqa: BLE001 — local-only view
            pass
    merged = merge_wire(per_node)
    by_name: "collections.OrderedDict[str, list]" = collections.OrderedDict()
    for s in merged:
        by_name.setdefault(s["n"], []).append(s)
    prom_type = {"c": "counter", "g": "gauge", "h": "histogram"}
    lines: List[str] = []
    for name, series in by_name.items():
        lines.append(f"# TYPE {_prom_name(name)} "
                     f"{prom_type.get(series[0]['t'], 'untyped')}")
        for s in series:
            _render_series(lines, s)
    return "\n".join(lines) + "\n"


def cluster_stamps() -> Dict[str, dict]:
    """node -> heartbeat stamp (with shipped metrics/events), via DKV."""
    from . import dkv, heartbeat
    out: Dict[str, dict] = {}
    for key in dkv.keys(heartbeat.PREFIX):
        stamp = dkv.get(key)
        if isinstance(stamp, dict):
            out[key[len(heartbeat.PREFIX):]] = stamp
    return out


# ------------------------------------------------------------ flat counters

_COUNTERS: collections.Counter = collections.Counter()


def count(name: str, delta: int = 1) -> None:
    """Bump a flat monotonic named counter.

    For high-rate stats (DKV WAL records/bytes, dedup hits) that would
    churn the timeline ring if each were an event; surfaced alongside
    the ring on /3/Timeline and as ``h2o3_events_total`` on /metrics."""
    with _lock:
        _COUNTERS[name] += delta


def counters() -> Dict[str, int]:
    with _lock:
        return dict(_COUNTERS)


# ------------------------------------------------------------------- traces

_trace_ctx: "contextvars.ContextVar[Optional[Dict[str, str]]]" = \
    contextvars.ContextVar("h2o3_tpu_trace", default=None)
_ID_NONCE = f"{os.getpid():x}{os.urandom(3).hex()}"
_id_seq = 0


def _new_id() -> str:
    global _id_seq
    with _lock:
        _id_seq += 1
        return f"{_ID_NONCE}.{_id_seq:x}"


def current_trace() -> Optional[Dict[str, str]]:
    """The active trace context, as injected into RPC envelopes:
    ``{"trace_id": ..., "span_id": ...}`` or None outside any trace."""
    ctx = _trace_ctx.get()
    if not ctx:
        return None
    return {"trace_id": ctx["trace_id"], "span_id": ctx["span_id"]}


def open_span() -> Dict[str, str]:
    """The fields that put an event which is no span under the span open
    on this context: ``trace_id``, ``parent_span`` (that span's id) and
    ``span`` (its kind; a context adopted from the wire has none).  Empty
    outside any trace."""
    ctx = _trace_ctx.get()
    if not ctx:
        return {}
    out = {"trace_id": ctx["trace_id"], "parent_span": ctx["span_id"]}
    if "kind" in ctx:
        out["span"] = ctx["kind"]
    return out


@contextlib.contextmanager
def trace_context(wire: Optional[Dict[str, str]]):
    """Adopt a remote trace context (the RPC handler side): spans opened
    inside become children of the caller's span, sharing its trace_id."""
    if not wire or not wire.get("trace_id"):
        yield
        return
    token = _trace_ctx.set({"trace_id": str(wire["trace_id"]),
                            "span_id": str(wire.get("span_id", ""))})
    try:
        yield
    finally:
        _trace_ctx.reset(token)


_annotation_cls = None


def _trace_annotation(name: str):
    """An unentered ``jax.profiler.TraceAnnotation(name)``, or None in a
    process that has not imported jax (no profiler session can be live
    there, and a span must not be what imports it).  The annotation is a
    TraceMe: while no session is live, entering it costs an atomic load."""
    global _annotation_cls
    if _annotation_cls is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation
        _annotation_cls = TraceAnnotation
    return _annotation_cls(name)


@contextlib.contextmanager
def _timed_event(kind: str, root: bool, fields: dict):
    """The one span primitive.  While it is open, and only if telemetry is
    enabled, the span feeds three sinks: the event ring (ids, ``ok`` /
    ``error``, fields), a profiler annotation ``h2o3.<kind>`` (the host
    span on the device trace's clock) and the registry's
    ``span_seconds{span}`` / ``span_self_seconds{span}`` histograms.

    Self time is the duration minus that of the spans opened directly
    beneath it on the same context: each traced span carries a
    ``child_ns`` accumulator in its context dict and adds its own
    duration to its parent's when it closes.  Under one root the self
    seconds of all spans therefore sum to the root's duration, so any set
    of span names adds up without counting an interval twice.  The dict
    carries the span's ``kind`` too, for ``open_span()``."""
    if not _enabled:
        yield
        return
    parent = _trace_ctx.get()
    ids: Dict[str, str] = {}
    ctx = token = None
    if root or parent is not None:
        trace_id = parent["trace_id"] if parent else _new_id()
        span_id = _new_id()
        ids = {"trace_id": trace_id, "span_id": span_id}
        if parent and parent.get("span_id"):
            ids["parent_span"] = parent["span_id"]
        ctx = {"trace_id": trace_id, "span_id": span_id, "child_ns": 0,
               "kind": kind}
        token = _trace_ctx.set(ctx)
    annotation = _trace_annotation("h2o3." + kind)
    if annotation is not None:
        annotation.__enter__()
    error = None
    t0 = time.perf_counter_ns()
    try:
        yield
    except BaseException as e:
        error = type(e).__name__
        raise
    finally:
        ns = time.perf_counter_ns() - t0
        if annotation is not None:
            annotation.__exit__(None, None, None)
        if token is not None:
            _trace_ctx.reset(token)
        # a context adopted from the wire (trace_context) has no accumulator
        if parent is not None and "child_ns" in parent:
            parent["child_ns"] += ns
        self_ns = max(ns - ctx["child_ns"], 0) if ctx is not None else ns
        observe("span_seconds", ns / 1e9, span=kind)
        observe("span_self_seconds", self_ns / 1e9, span=kind)
        ev = dict(fields)
        ev.update(ids)
        ev["ok"] = error is None
        if error is not None:
            ev["error"] = error
        record(kind, duration_s=ns / 1e9, **ev)


def span(kind: str, **fields):
    """Timed event — the MRProfile analog for coordinator-side phases.

    Failures record too (``ok=False`` + ``error=<ExcType>``), so chaos-
    injected faults are visible on the timeline instead of vanishing.
    Inside an active trace the event carries trace/span/parent ids and
    becomes a node of that trace's tree; outside one it is a plain
    timed event (no id allocation on untraced hot paths)."""
    return _timed_event(kind, False, fields)


def trace(kind: str, **fields):
    """Root span: like ``span`` but always allocates ids, starting a new
    trace when none is active (jobs open one per training run)."""
    return _timed_event(kind, True, fields)


def trace_forest(events: Iterable[dict]) -> List[dict]:
    """Stitch span events (local + shipped) into trees by trace_id.

    Returns one dict per trace: ``{"trace_id", "spans": [roots]}`` where
    each span node carries its event fields plus ``children``.  Spans
    whose parent is missing from the window (ring rollover, un-shipped
    remote parent) surface as roots rather than being dropped."""
    by_trace: Dict[str, List[dict]] = {}
    for e in events:
        if e.get("trace_id") and e.get("span_id"):
            by_trace.setdefault(e["trace_id"], []).append(dict(e))
    forest = []
    for trace_id, spans in by_trace.items():
        nodes = {s["span_id"]: s for s in spans}
        for s in spans:
            s["children"] = []
        roots = []
        for s in sorted(spans, key=lambda s: s.get("ts", 0.0)):
            parent = nodes.get(s.get("parent_span"))
            if parent is not None and parent is not s:
                parent["children"].append(s)
            else:
                roots.append(s)
        forest.append({"trace_id": trace_id, "spans": roots})
    forest.sort(key=lambda t: (t["spans"][0].get("ts", 0.0)
                               if t["spans"] else 0.0))
    return forest


# ----------------------------------------------------------- device traces

_profiler_active = False
_profiler_logdir: Optional[str] = None
_profiler_summary: Optional[dict] = None


def profiler_active() -> bool:
    """Whether a device trace started HERE is currently capturing."""
    with _lock:
        return _profiler_active


def profiler_summary() -> Optional[dict]:
    """``xprof.idle_by_span`` of the last trace ``stop_device_trace``
    wrote (None before the first stop, or where it could not be read)."""
    with _lock:
        return _profiler_summary


def start_device_trace(logdir: str) -> bool:
    """Begin a jax.profiler trace (TensorBoard-viewable device timeline).

    The Python tracer is off: the ``h2o3.*`` annotations of ``span()`` say
    what the host is doing, and Python function events slow the very host
    path they would explain.

    Idempotent: a second start while a capture is live (including one
    jax.profiler reports out-of-band) records a ``profiler_noop`` event
    and returns False instead of propagating ``RuntimeError`` — the REST
    profiler route must never 500 a double-click.  Returns whether a new
    capture actually started; ``profiler_active`` gauges 1 while one is
    live (shipped in node snapshots like every other gauge)."""
    global _profiler_active, _profiler_logdir
    import jax
    with _lock:
        active = _profiler_active
    if active:
        record("profiler_noop", op="start", reason="already_active")
        return False
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(logdir, profiler_options=options)
    except RuntimeError as e:
        record("profiler_noop", op="start", reason="jax_runtime",
               error=str(e)[:200])
        return False
    with _lock:
        _profiler_active = True
        _profiler_logdir = logdir
    set_gauge("profiler_active", 1.0)
    record("profiler_start", logdir=logdir)
    return True


def _newest_xplane(logdir: Optional[str]) -> Optional[str]:
    import glob
    found = glob.glob(os.path.join(logdir or "", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def stop_device_trace() -> bool:
    """Stop the live device trace; a stop with no capture running records
    ``profiler_noop`` and returns False (idempotent, like start).

    The ``profiler_stop`` event (and ``profiler_summary()``, which the
    REST route returns) carries the device's idle seconds by program span
    (``xprof.idle_by_span``): the operator's first reading of the trace."""
    global _profiler_active, _profiler_summary
    import jax
    with _lock:
        active = _profiler_active
    if not active:
        record("profiler_noop", op="stop", reason="not_active")
        return False
    try:
        jax.profiler.stop_trace()
    except RuntimeError as e:
        record("profiler_noop", op="stop", reason="jax_runtime",
               error=str(e)[:200])
        return False
    finally:
        with _lock:
            _profiler_active = False
        set_gauge("profiler_active", 0.0)
    summary = None
    try:
        from . import xprof
        path = _newest_xplane(_profiler_logdir)
        if path is not None:
            summary = dict(xprof.idle_by_span(path), xplane=path)
    except Exception as e:               # noqa: BLE001 — the stop succeeded
        log.warning("profiler: trace not summarised: %r", e)
    with _lock:
        _profiler_summary = summary
    record("profiler_stop", idle_by_span=summary)
    return True


# ------------------------------------------------------------- diagnostics

def jstack() -> List[Dict]:
    """All-thread stack dump — water/api/JStackHandler (water.util.JStack)
    rendered for a Python runtime: one traceback per live thread."""
    import sys
    import threading
    import traceback
    frames = sys._current_frames()
    by_id = {t.ident: t for t in threading.enumerate()}
    out = []
    for tid, frame in frames.items():
        t = by_id.get(tid)
        out.append({
            "thread_id": tid,
            "name": t.name if t else f"thread-{tid}",
            "daemon": bool(t.daemon) if t else None,
            "traces": traceback.format_stack(frame),
        })
    return out


def network_test(sizes=(1_024, 1_048_576, 16_777_216)) -> List[Dict]:
    """Collective-bandwidth micro-bench — water/api/NetworkTestHandler.

    The reference times point-to-point UDP/TCP between cloud members; the
    mesh analog is an all-reduce (psum) at a few payload sizes, which is
    exactly the traffic training generates.  Each size is timed per mesh
    stage — the host-local ``"chips"`` ring (ICI), the cross-host
    ``"hosts"`` axis (DCN), and the flat product axis — so the report
    separates intra-host from inter-host bandwidth; every timing also
    lands in the ``collective_seconds{axis,op}`` histogram.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from .cluster import CHIP_AXIS, HOST_AXIS, ROW_AXES, ROW_AXIS, cluster
    from jax import shard_map

    cl = cluster()
    rows = cl.n_row_shards
    stages = [("rows", ROW_AXES)]
    if cl.mesh.shape[CHIP_AXIS] > 1:
        stages.append(("chips", CHIP_AXIS))
    if cl.mesh.shape[HOST_AXIS] > 1:
        stages.append(("hosts", HOST_AXIS))
    results = []
    for size in sizes:
        n = max(size // 4, rows)
        n = (n // rows) * rows
        x = jnp.ones((n,), jnp.float32)
        for axis_label, axis in stages:
            def allred(v, _axis=axis):
                return jax.lax.psum(v, _axis)

            # out spec stays row-sharded: a single-stage psum still varies
            # over the other row axis, so no replication can be claimed
            f = jax.jit(shard_map(allred, mesh=cl.mesh,
                                  in_specs=P(ROW_AXIS),
                                  out_specs=P(ROW_AXIS),
                                  check_vma=False))
            np_out = f(x)
            _ = float(np_out[0])              # warmup + compile sync
            t0 = time.perf_counter()
            reps = 5
            for _ in range(reps):
                out = f(x)
            _ = float(out[0])                 # fetch = sync point
            dt = (time.perf_counter() - t0) / reps
            observe("collective_seconds", dt, axis=axis_label, op="psum")
            results.append({
                "bytes": int(n * 4),
                "collective": "psum",
                "axis": axis_label,
                "seconds": dt,
                "gbytes_per_sec": (n * 4 / max(dt, 1e-12)) / 1e9,
            })
    return results
