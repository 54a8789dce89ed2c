"""A series of the program's registry over one phase of the run.

``phase`` picks the interval: ``setup`` (the totals of
``ctx["counters_before"]``, which ``run.py`` takes after the last warm-up
unit: everything from process start to the window) or ``window`` (what the
series gained inside the window). ``value`` picks the number: ``sum_s``
(summed seconds) or ``count_per_unit`` (observations over units completed).

Unlike ``counter.py``, a label filter that matches no series reads 0.0:
nothing happened. ``None`` (the line leaves the metric out) is for a program
that does not have the series: no series of that name at all, or none that
carries the labels the filter asks for (a program from before the label).
"""

from benchmark import reduce as R


def reduce(spec, ctx):
    labels = spec.get("labels") or {}
    if not any(s.get("n") == spec["series"] and set(labels) <= set(s.get("l", {}))
               for s in ctx["counters_after"]):
        return None
    count, total = R.series_totals(ctx["counters_before"], spec["series"], labels)
    if spec["phase"] == "window":
        n1, s1 = R.series_totals(ctx["counters_after"], spec["series"], labels)
        count, total = n1 - count, s1 - total
    elif spec["phase"] != "setup":
        raise ValueError(f"unknown phase {spec['phase']!r}")
    if spec["value"] == "sum_s":
        return float(total)
    if spec["value"] == "count_per_unit":
        return count / ctx["units"]
    raise ValueError(f"unknown value {spec['value']!r}")
