"""The reducer on a recorded trace and on hand-made events.

``data/predict_10m.xplane.pb`` is a real v5e trace (one ``predict`` of a
100-tree model over 10M rows, explore run before PR 26, annotated
``bench:predict``); the numbers asserted were read from it by hand."""

import os

import pytest

from benchmark import reduce as R

TRACE = os.path.join(os.path.dirname(__file__), "data", "predict_10m.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return R.Trace.from_file(TRACE)


def test_planes_and_annotation(trace):
    assert list(trace.devices) == ["/device:TPU:0"]
    assert [a[0] for a in trace.annotations] == ["bench:predict"]
    lo, hi = trace.window("bench:predict")
    assert (hi - lo) / 1e6 == pytest.approx(3933.79, abs=0.01)


def test_window_falls_back_to_the_span_of_the_programs(trace):
    lo, hi = trace.window("no-such-annotation")
    assert (hi - lo) / 1e6 == pytest.approx(2921.8, abs=0.05)


def test_module_seconds_by_name(trace):
    window = trace.window("bench:predict")
    seconds, events = R.module_seconds(trace, r"^jit_traverse", window)
    assert events == 1
    assert seconds * 1e3 == pytest.approx(2909.6, abs=0.05)
    assert R.module_seconds(trace, r"^jit_scan_fn", window) == (0.0, 0)


def test_busy_is_the_union_of_the_programs_and_idle_follows(trace):
    window = trace.window("bench:predict")
    busy = R.busy_seconds(trace, window)
    every, _ = R.module_seconds(trace, r".", window)
    assert busy == pytest.approx(every, rel=1e-9)        # programs never overlap
    assert busy * 1e3 == pytest.approx(2916.25, abs=0.05)
    idle = 100 * (1 - busy / ((window[1] - window[0]) / 1e9))
    assert idle == pytest.approx(25.87, abs=0.01)
    # against the span of the programs alone the device is nearly never idle
    span = trace.window("no-such-annotation")
    assert 100 * (1 - R.busy_seconds(trace, span) / ((span[1] - span[0]) / 1e9)) < 0.5


def test_a_clipped_window_counts_only_what_is_inside(trace):
    lo, hi = trace.window("bench:predict")
    half = (lo, (lo + hi) / 2)
    assert R.busy_seconds(trace, half) <= (half[1] - half[0]) / 1e9
    seconds, _ = R.module_seconds(trace, r"^jit_traverse", half)
    assert 0 < seconds < 2.0


def test_breakdown_counts_leaves_only(trace):
    window = trace.window("bench:predict")
    b = R.breakdown(trace, window)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 5
    names = [n for n, _ in b["device_ops"]]
    assert not any(n.startswith(("%while", "%conditional")) for n in names)
    assert all(len(n) <= 80 for n in names)
    # leaves cannot sum to more than the device was busy
    ops = trace.line(R.OPS)[0]
    leaves = sum(R.leaf_seconds(R.clip(ops, window)).values())
    assert leaves <= R.busy_seconds(trace, window) * 1.001
    assert sum(e - s for _, s, e in ops) / 1e9 > leaves       # the enclosing ops were left out
    # the longest gap is the host fetching and re-uploading the result
    name, seconds = b["idle_gaps"][0]
    assert name.startswith("bench:predict@2.9") and seconds == pytest.approx(1.0128, abs=1e-3)


def test_an_enclosing_while_is_not_a_leaf():
    events = [("%while.1", 0, 100), ("%fusion.a", 0, 40), ("%fusion.b", 50, 100),
              ("%copy", 120, 130), ("%conditional", 200, 300), ("%fusion.a", 210, 220)]
    assert R.leaf_seconds(events) == {"%fusion.a": 50e-9, "%fusion.b": 50e-9, "%copy": 10e-9}


def test_union_and_gaps_on_hand_made_events():
    assert R.union_ns([("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 32, 35)]) == 30
    trace = R.Trace({"/device:TPU:0": {R.MODULES: [("jit_f(1)", 10, 20), ("jit_g(2)", 60, 80)]}},
                    [("bench.window", 0, 100), ("bench.fit", 5, 50)])
    window = trace.window()
    assert window == (0, 100)
    assert R.busy_seconds(trace, window) == 30e-9
    gaps = R.idle_gaps(trace, window)
    assert [g[0].split("@")[0] for g in gaps] == ["bench.fit", "between-calls", "bench.fit"]
    assert [round(g[1] * 1e9) for g in gaps] == [40, 20, 10]


def test_several_devices_are_averaged():
    trace = R.Trace({"/device:TPU:0": {R.MODULES: [("jit_f(1)", 0, 40)]},
                     "/device:TPU:1": {R.MODULES: [("jit_f(1)", 0, 20)]}}, [("bench.window", 0, 100)])
    assert R.busy_seconds(trace, (0, 100)) == 30e-9
    assert R.module_seconds(trace, "^jit_f", (0, 100)) == (30e-9, 1)


def test_series_totals_reads_histograms_and_counters():
    wire = [{"n": "jax_compile_seconds", "l": {"event": "a"}, "t": "h", "n_obs": 3, "s": 1.5},
            {"n": "jax_compile_seconds", "l": {"event": "b"}, "t": "h", "n_obs": 2, "s": 0.5},
            {"n": "other", "l": {}, "t": "c", "v": 7.0}]
    assert R.series_totals(wire, "jax_compile_seconds") == (5, 2.0)
    assert R.series_totals(wire, "jax_compile_seconds", {"event": "a"}) == (3, 1.5)
    assert R.series_totals(wire, "jax_compile_seconds", {"event": ["a", "b"]}) == (5, 2.0)
    assert R.series_totals(wire, "other") == (7.0, 7.0)
    assert R.series_totals(wire, "absent") == (0, 0.0)


def test_reductions_on_the_recorded_trace(trace):
    from benchmark.costs import traverse
    from benchmark.reductions import counter, idle, module_share, roofline
    window = trace.window("bench:predict")
    ctx = {"trace": trace, "window": window, "window_s": (window[1] - window[0]) / 1e9, "units": 1,
           "state": {"rows": 10_000_000, "features": ["f"] * 8, "ntrees": 100, "depth": 6},
           "peaks": {"flops_bf16_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "counters_before": [], "counters_after": []}
    assert module_share.reduce({"match": "^jit_traverse"}, ctx) == pytest.approx(73.96, abs=0.01)
    assert module_share.reduce({"match": "^jit_scan_fn"}, ctx) is None
    assert idle.reduce({}, ctx) == pytest.approx(25.87, abs=0.01)
    cost = traverse.cost(ctx["state"])
    assert cost["bytes"] == 10_000_000 * 8 * 4 + 10_000_000 * 4 + 100 * (63 * 4 + 64) * 4
    share = roofline.reduce({"match": "^jit_traverse", "cost": "traverse"}, ctx)
    assert share == pytest.approx(100 * (cost["bytes"] / 819e9) / 2.9096225, rel=1e-6)
    assert 0 < share < 0.1
    assert counter.reduce({"series": "jax_compile_seconds", "value": "count_per_unit"}, ctx) is None
    empty = dict(ctx, trace=R.Trace({}, []))
    assert idle.reduce({}, empty) is None
