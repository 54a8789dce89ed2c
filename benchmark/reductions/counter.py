"""What a series of the program's registry gained inside the window.

``value`` picks the number: ``count_per_unit`` (observations over units
completed) or ``sum_share`` (summed seconds as % of the window).
"""

from benchmark import reduce as R


def reduce(spec, ctx):
    n0, s0 = R.series_totals(ctx["counters_before"], spec["series"], spec.get("labels"))
    n1, s1 = R.series_totals(ctx["counters_after"], spec["series"], spec.get("labels"))
    if n1 == 0:
        return None                     # the program has no such series
    if spec["value"] == "count_per_unit":
        return (n1 - n0) / ctx["units"]
    if spec["value"] == "sum_share":
        return 100.0 * (s1 - s0) / ctx["window_s"]
    raise ValueError(f"unknown value {spec['value']!r}")
