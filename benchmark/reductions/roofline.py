"""A kernel's share of its roofline, in %.

The least time the chip could take for one unit of work is the larger of
bytes over peak bytes/s and operations over peak operations/s, both from
``peaks.json`` for this ``device_kind``; bytes and operations come from
``costs/<cost>.py`` (computed from the shapes, never read from the program).
That, times the units in the window, over the device seconds of the programs
whose name matches.
"""

import importlib
import sys

from benchmark import reduce as R


def reduce(spec, ctx):
    seconds, events = R.module_seconds(ctx["trace"], spec["match"], ctx["window"])
    if not events:
        return None
    cost = importlib.import_module(f"benchmark.costs.{spec['cost']}").cost(ctx["state"])
    peaks = ctx["peaks"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = cost["ops"] / peaks["flops_bf16_per_s"]
    bound = "bytes" if by_bytes >= by_ops else "ops"
    print(f"# roofline {spec['match']}: {cost['bytes']:.4g} B, {cost['ops']:.4g} ops a unit; "
          f"least {max(by_bytes, by_ops) * 1e3:.4g} ms, bound by {bound}; "
          f"measured {seconds / ctx['units'] * 1e3:.4g} ms a unit", file=sys.stderr)
    return 100.0 * max(by_bytes, by_ops) * ctx["units"] / seconds
