"""``reductions/phase_counter.py`` on hand-made wires, and the layer-metric
files that name it."""

import glob
import importlib
import os

import pytest

from benchmark.reductions import phase_counter
from benchmark.run import read_json as read

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
MANIFEST = read(ROOT, "BENCHMARK.json")


def hist(name, n_obs, seconds, **labels):
    return {"n": name, "l": labels, "t": "h", "b": [], "c": [], "s": seconds, "n_obs": n_obs}


def counter(name, value, **labels):
    return {"n": name, "l": labels, "t": "c", "v": value}


BACKEND = "backend_compile_duration"
BEFORE = [hist("jax_compile_seconds", 4, 2.0, event="jaxpr_trace_duration", fun="f"),
          hist("jax_compile_seconds", 3, 0.5, event=BACKEND, fun="jit(f)", cache="hit"),
          hist("jax_compile_seconds", 1, 7.0, event=BACKEND, fun="jit(g)", cache="unstored"),
          hist("jax_cache_saved_seconds", 3, 40.0, fun="jit(f)"),
          hist("jax_cache_saved_seconds", 0, 0.0, fun="jit(g)"),      # asked the cache, no hit
          counter("transfer_seconds_total", 1.5, dir="h2d", stage="prepare"),
          counter("transfer_seconds_total", 0.25, dir="h2d", stage="put")]
AFTER = [hist("jax_compile_seconds", 10, 2.6, event="jaxpr_trace_duration", fun="f"),
         hist("jax_compile_seconds", 9, 0.8, event=BACKEND, fun="jit(f)", cache="hit"),
         hist("jax_compile_seconds", 3, 9.0, event=BACKEND, fun="jit(g)", cache="unstored"),
         hist("jax_compile_seconds", 1, 4.0, event=BACKEND, fun="jit(h)", cache="off"),
         hist("jax_cache_saved_seconds", 9, 41.0, fun="jit(f)"),
         hist("jax_cache_saved_seconds", 0, 0.0, fun="jit(g)"),
         counter("transfer_seconds_total", 1.5, dir="h2d", stage="prepare"),
         counter("transfer_seconds_total", 0.25, dir="h2d", stage="put")]
CTX = {"counters_before": BEFORE, "counters_after": AFTER, "units": 2}
COMPILED = {"event": BACKEND, "cache": ["stored", "unstored", "off"]}


def spec(series, phase, value, labels=None):
    return {"kind": "phase_counter", "series": series, "phase": phase, "value": value,
            **({"labels": labels} if labels else {})}


@pytest.mark.parametrize("series,labels,phase,value,want", [
    ("jax_compile_seconds", COMPILED, "setup", "sum_s", 7.0),
    ("jax_compile_seconds", COMPILED, "window", "sum_s", 6.0),
    ("jax_compile_seconds", COMPILED, "window", "count_per_unit", 1.5),
    ("jax_compile_seconds", {"event": BACKEND, "cache": "hit"}, "setup", "count_per_unit", 1.5),
    ("jax_compile_seconds", {"event": BACKEND, "cache": "hit"}, "window", "sum_s", 0.3),
    ("jax_compile_seconds", None, "setup", "sum_s", 9.5),
    ("jax_cache_saved_seconds", None, "setup", "sum_s", 40.0),
    ("jax_cache_saved_seconds", {"fun": "jit(g)"}, "setup", "sum_s", 0.0),
    ("transfer_seconds_total", {"dir": "h2d"}, "setup", "sum_s", 1.75),
    ("transfer_seconds_total", {"dir": "h2d"}, "window", "sum_s", 0.0),
])
def test_both_phases_and_both_values(series, labels, phase, value, want):
    assert phase_counter.reduce(spec(series, phase, value, labels), CTX) == pytest.approx(want)


def test_no_match_reads_zero_and_no_series_reads_nothing():
    stored = {"event": BACKEND, "cache": "stored"}
    for phase in ("setup", "window"):
        for value in ("sum_s", "count_per_unit"):
            got = phase_counter.reduce(spec("jax_compile_seconds", phase, value, stored), CTX)
            assert got == 0.0 and isinstance(got, float)
            assert phase_counter.reduce(spec("no_such_seconds", phase, value), CTX) is None


def test_a_program_from_before_the_label_reads_nothing():
    old = [hist("jax_compile_seconds", 3, 0.5, event=BACKEND, fun="jit(f)")]
    ctx = {"counters_before": old, "counters_after": old, "units": 1}
    assert phase_counter.reduce(spec("jax_compile_seconds", "setup", "sum_s", COMPILED), ctx) is None
    assert phase_counter.reduce(
        spec("jax_compile_seconds", "setup", "sum_s", {"event": BACKEND}), ctx) == 0.5


def test_an_unknown_phase_or_value_is_refused():
    with pytest.raises(ValueError):
        phase_counter.reduce(spec("jax_compile_seconds", "warm_up", "sum_s"), CTX)
    with pytest.raises(ValueError):
        phase_counter.reduce(spec("jax_compile_seconds", "setup", "sum_share"), CTX)


FILES = sorted(glob.glob(os.path.join(HERE, "layer_metrics", "*.json")))
MINE = [f for f in FILES if read(f)["reduction"]["kind"] == "phase_counter"]


def test_the_set_up_readings_are_entered_in_pairs():
    names = {os.path.basename(f)[:-len(".json")] for f in MINE}
    bases = {"setup_trace_lower_s", "setup_cache_hit_s", "setup_compiled_s",
             "setup_cache_saved_s", "setup_upload_s"}
    assert names == {f"{b}.{s}" for b in bases for s in ("fit", "rows")} | {"recompiles.fit"}
    for base in bases:      # a pair's two files differ in nothing but the name
        assert read(HERE, "layer_metrics", base + ".fit.json") \
            == read(HERE, "layer_metrics", base + ".rows.json")


@pytest.mark.parametrize("path", MINE, ids=[os.path.basename(f) for f in MINE])
def test_layer_metric_file_loads_and_names_what_exists(path):
    name = os.path.basename(path)[:-len(".json")]
    spec_ = read(path)
    assert set(spec_) == {"reduction", "reads"} and spec_["reads"]
    reduction = importlib.import_module(f"benchmark.reductions.{spec_['reduction']['kind']}")
    assert callable(reduction.reduce)
    assert spec_["reduction"]["phase"] in ("setup", "window")
    assert spec_["reduction"]["value"] in ("sum_s", "count_per_unit")
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["source"] == "program_counter" and entry["workloads"]
    moved = next(e for e in MANIFEST["end_to_end"] if e["name"] == entry["moves"])
    assert entry["moves"] != "setup_s" and set(entry["workloads"]) <= set(moved["workloads"])
    assert isinstance(reduction.reduce(spec_["reduction"], CTX), float)
