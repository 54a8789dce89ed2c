"""Share of the window, in %, in which no program ran on the device."""

from benchmark import reduce as R


def reduce(spec, ctx):
    if not ctx["trace"].devices:
        return None                     # no device plane: not a chip run
    return 100.0 * (1.0 - R.busy_seconds(ctx["trace"], ctx["window"]) / ctx["window_s"])
