"""A program that one unit of work launches several times, read per launch.

Why not ``module_share`` / ``roofline``, which sum the matching programs'
device seconds: a DeepLearning fit runs 312,500 optimizer steps of some forty
small ops each, and the profiler's device buffer holds about a third of
those op events. The device lines of the trace then END part way into the
window (PERF.md section 7), the sum covers part of the unit, and a share of a
roofline computed from it reads three times too high. A launch the trace
holds whole is read rightly, so this takes the MEDIAN device seconds of the
matching launches on the first device (one launch cut short by the buffer's
end does not move it). How many launches the window made, and how many steps
of work they did, is read from the program's own counters (``launches`` and
``steps`` name them), never assumed: a program without them reports nothing.

``value`` picks the number: ``share`` (launches in the window x that median,
as % of the window: the program's device seconds, extrapolated over the
launches the trace lost) or ``roofline`` (the least time for one launch's
bytes and operations over that median, in %: ``costs/<cost>.py`` gives bytes,
operations and steps of a unit, so bytes and operations of a step, and the
counters the steps of a launch; ``peaks.json`` the rates).
"""

import importlib
import re
import statistics
import sys

from benchmark import reduce as R


def gained(ctx, series):
    return (R.series_totals(ctx["counters_after"], series)[1]
            - R.series_totals(ctx["counters_before"], series)[1])


def reduce(spec, ctx):
    rx = re.compile(spec["match"])
    lines = ctx["trace"].line(R.MODULES)
    seconds = [(e - s) / 1e9 for n, s, e in R.clip(lines[0], ctx["window"])
               if rx.search(n)] if lines else []
    launches, steps = gained(ctx, spec["launches"]), gained(ctx, spec["steps"])
    if not seconds or not launches or not steps:
        return None
    launch = statistics.median(seconds)
    if spec["value"] == "share":
        return 100.0 * launches * launch / ctx["window_s"]
    if spec["value"] != "roofline":
        raise ValueError(f"unknown value {spec['value']!r}")
    cost = importlib.import_module(f"benchmark.costs.{spec['cost']}").cost(ctx["state"])
    peaks = ctx["peaks"]
    per_launch = steps / launches / cost["steps"]
    by_bytes = cost["bytes"] * per_launch / peaks["hbm_bytes_per_s"]
    by_ops = cost["ops"] * per_launch / peaks["flops_bf16_per_s"]
    bound = "bytes" if by_bytes >= by_ops else "ops"
    print(f"# launches {spec['match']}: {len(seconds)} in the trace of {launches:g} in the "
          f"window, {steps / launches:g} steps each, median {launch * 1e3:.4g} ms; "
          f"least {max(by_bytes, by_ops) * 1e3:.4g} ms a launch, bound by {bound}",
          file=sys.stderr)
    return 100.0 * max(by_bytes, by_ops) / launch
