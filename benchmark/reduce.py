"""From a profiler trace and counter snapshots to numbers.

The trace is the ``.xplane.pb`` the JAX profiler writes. On a TPU its plane
``/device:TPU:<n>`` has a line ``XLA Modules`` with one event per executed
program (``jit_<fn>(<hash>)``; these never overlap on one device) and a line
``XLA Ops`` with the ops, where a ``%while`` or ``%conditional`` encloses its
children (so ops sum to more than the window; only leaves are counted). The
host planes carry the benchmark's own ``jax.profiler.TraceAnnotation`` spans
on the same clock. Every function here takes plain tuples ``(name, start_ns,
end_ns)`` so that it can be tested without a trace.
"""

from __future__ import annotations

import glob
import os
import re
from statistics import fmean
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, end_ns
Window = Tuple[float, float]              # start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES = "XLA Modules"
OPS = "XLA Ops"
ANNOTATION_PREFIX = "bench"
WINDOW_ANNOTATION = "bench.window"
BETWEEN = "between-calls"


class Trace:
    """The device lines and the benchmark's annotations of one xplane file."""

    def __init__(self, devices: Dict[str, Dict[str, List[Event]]],
                 annotations: List[Event]):
        self.devices = devices            # plane -> line -> events
        self.annotations = annotations    # host events named bench*

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        import jax
        data = jax.profiler.ProfileData.from_file(path)
        devices, annotations = {}, []
        for plane in data.planes:
            if DEVICE_PLANE.match(plane.name):
                devices[plane.name] = {
                    line.name: sorted(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
                    for line in plane.lines if line.name in (MODULES, OPS)}
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    annotations += [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                        if e.name.startswith(ANNOTATION_PREFIX)]
        return cls(devices, sorted(annotations, key=lambda e: e[1]))

    @classmethod
    def from_dir(cls, log_dir: str) -> "Trace":
        """The one trace ``jax.profiler.start_trace(log_dir)`` wrote."""
        found = glob.glob(os.path.join(
            log_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if len(found) != 1:
            raise FileNotFoundError(
                f"expected one .xplane.pb under {log_dir}, found {found}")
        return cls.from_file(found[0])

    def line(self, name: str) -> List[List[Event]]:
        """That line's events, one list per device."""
        return [lines.get(name, []) for _, lines in sorted(self.devices.items())]

    def window(self, annotation: str = WINDOW_ANNOTATION) -> Window:
        """The span of the annotation of that name; where the trace has none,
        first start to last end of the executed programs."""
        spans = [(s, e) for n, s, e in self.annotations if n == annotation]
        if not spans:
            spans = [(s, e) for evs in self.line(MODULES) for _, s, e in evs]
        if not spans:
            raise ValueError("the trace holds no annotation and no program")
        return min(s for s, _ in spans), max(e for _, e in spans)


def clip(events: Sequence[Event], window: Window) -> List[Event]:
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union_ns(events: Sequence[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, None
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if end is None or s > end:
            total, end = total + (e - s), e
        elif e > end:
            total, end = total + (e - end), e
    return total


def module_seconds(trace: Trace, pattern: str, window: Window) -> Tuple[float, int]:
    """Device seconds (mean over devices) and events (on the first device)
    of the programs whose name matches, inside the window."""
    rx = re.compile(pattern)
    per_device = [[ev for ev in clip(evs, window) if rx.search(ev[0])]
                  for evs in trace.line(MODULES)]
    if not per_device:
        return 0.0, 0
    seconds = fmean(sum(e - s for _, s, e in evs) for evs in per_device) / 1e9
    return seconds, len(per_device[0])


def busy_seconds(trace: Trace, window: Window) -> float:
    """Seconds in which a program ran on the device, mean over devices."""
    per_device = [union_ns(clip(evs, window)) for evs in trace.line(MODULES)]
    return fmean(per_device) / 1e9 if per_device else 0.0


def leaf_seconds(events: Sequence[Event]) -> Dict[str, float]:
    """Summed seconds by name of the events that enclose no other event."""
    out: Dict[str, float] = {}
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    for i, (name, s, e) in enumerate(evs):
        encloses = i + 1 < len(evs) and evs[i + 1][1] < e and evs[i + 1][2] <= e \
            and (evs[i + 1][1], evs[i + 1][2]) != (s, e)
        if not encloses:
            out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out


def idle_gaps(trace: Trace, window: Window) -> List[Tuple[str, float]]:
    """Every gap of the first device inside the window, longest first, named
    by the benchmark's annotation open at its middle and its offset into
    the window."""
    lo, hi = window
    devices = trace.line(MODULES)
    if not devices:
        return []
    gaps, cursor = [], lo
    for _, s, e in sorted(clip(devices[0], window), key=lambda ev: ev[1]):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    units = [a for a in trace.annotations if a[0] != WINDOW_ANNOTATION]
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        label = next((n for n, a, b in units if a <= mid < b), BETWEEN)
        out.append((f"{label}@{(s - lo) / 1e9:.3f}s", (e - s) / 1e9))
    return sorted(out, key=lambda g: -g[1])


def breakdown(trace: Trace, window: Window, top_ops: int = 10,
              top_gaps: int = 5, width: int = 80) -> dict:
    """The contract's optional ``breakdown``: the leaf ops that took most
    device time and the longest idle gaps."""
    ops: Dict[str, float] = {}
    for evs in trace.line(OPS)[:1]:
        for name, sec in leaf_seconds(clip(evs, window)).items():
            ops[name[:width]] = ops.get(name[:width], 0.0) + sec
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:top_ops]
    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[n, s] for n, s in idle_gaps(trace, window)[:top_gaps]]}


# ------------------------------------------------------------- counters

def series_totals(wire: Sequence[dict], name: str,
                  labels: Optional[dict] = None) -> Tuple[float, float]:
    """(observations, summed value) of the program's series of that name
    whose labels match, from ``observability.metrics_wire()``; a plain
    counter gives its value as both. A label's wanted value may be a list of
    alternatives."""
    count, total = 0, 0.0
    for s in wire:
        if s.get("n") != name:
            continue
        have = s.get("l", {})
        if any(have.get(k) not in (v if isinstance(v, list) else [v])
               for k, v in (labels or {}).items()):
            continue
        if s.get("t") == "h":
            count, total = count + s["n_obs"], total + s["s"]
        else:
            count, total = count + s.get("v", 0.0), total + s.get("v", 0.0)
    return count, total
