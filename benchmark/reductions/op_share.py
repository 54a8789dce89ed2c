"""Device seconds of the ops whose name matches, as % of the window.

Read from the line ``XLA Ops`` of the first device. There a ``%while`` or a
``%conditional`` encloses its children, so only leaves are counted: events
that enclose no other event.
"""

import re

from benchmark import reduce as R


def reduce(spec, ctx):
    rx = re.compile(spec["match"])
    lines = ctx["trace"].line(R.OPS)
    if not lines:
        return None                     # no device plane: not a chip run
    leaves = R.leaf_seconds(R.clip(lines[0], ctx["window"]))
    matched = [sec for name, sec in leaves.items() if rx.search(name)]
    if not matched:
        return None                     # the program has no op of that name
    return 100.0 * sum(matched) / ctx["window_s"]
