"""Device seconds of the programs whose name matches, as % of the window."""

from benchmark import reduce as R


def reduce(spec, ctx):
    seconds, events = R.module_seconds(ctx["trace"], spec["match"], ctx["window"])
    if not events:
        return None
    return 100.0 * seconds / ctx["window_s"]
