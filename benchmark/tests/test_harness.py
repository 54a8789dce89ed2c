"""BENCHMARK.json against the files it names, the manifest's character
rules, and ``run.py`` end to end at a tiny size on the CPU."""

import importlib
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark.run import metrics_of as cell_metrics, read_json as read

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")


MANIFEST = read(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def metrics_of(group, cell):
    return cell_metrics(MANIFEST, group, cell)


def test_manifest_has_the_contracts_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark"] and MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= MANIFEST["run_seconds"] <= 51 and isinstance(MANIFEST["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/") and len(c["reduced"]) <= 16
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    assert [m["name"] for m in MANIFEST["end_to_end"] if "workloads" not in m] == ["setup_s"]


def test_names_units_and_lines_keep_to_the_character_rules():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in MANIFEST[group]]
        assert len(got) == len(set(got)), f"duplicate name in {group}"
        names += got
    names += [w[k] for w in MANIFEST["workloads"] for k in ("config", "traffic")]
    names += [k for c in MANIFEST["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher") for m in metrics)
    lines = [e["why"] for e in MANIFEST["configs"] + MANIFEST["workloads"]]
    lines += [c["source"] for c in MANIFEST["configs"]] + [m["layer"] for m in MANIFEST["per_layer"]]
    lines += MANIFEST["command"]
    assert all(1 <= len(s) <= 200 and "\n" not in s and "\t" not in s for s in lines)
    assert len({(w["config"], w["traffic"]) for w in MANIFEST["workloads"]}) == len(CELLS)


def test_every_file_under_the_benchmark_has_a_plain_name():
    for folder, _, files in os.walk(HERE):
        if "__pycache__" in folder or ".pytest_cache" in folder:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(folder, f), ROOT)
            assert FILE.match(rel), rel
            assert os.path.getsize(os.path.join(ROOT, rel)) < 2.1e6, rel


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    w = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    config = next(c for c in MANIFEST["configs"] if c["name"] == w["config"])
    cfg = read(ROOT, config["file"])
    assert cell == f"{w['config']}.{w['traffic']}"
    assert sorted(cfg["reduced"]) == sorted(config["reduced"])
    mix = read(HERE, "traffic", w["traffic"] + ".json")
    driver = importlib.import_module(f"benchmark.drivers.{mix['driver']}")
    assert all(hasattr(driver, f) for f in ("set_up", "unit", "metrics", "ANNOTATION"))
    generator = importlib.import_module(f"benchmark.datagen.{cfg['data']['generator']}")
    assert callable(generator.generate)
    module, _, name = cfg["estimator"].rpartition(".")
    assert hasattr(importlib.import_module(module), name)
    check = read(HERE, "checks", cell + ".json")
    assert callable(importlib.import_module(f"benchmark.refs.{check['ref']}").check)
    assert check["why"] and check["tol"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_what_the_contract_asks(cell):
    end_to_end = [m["name"] for m in metrics_of("end_to_end", cell)]
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    per_layer = metrics_of("per_layer", cell)
    assert per_layer
    for m in per_layer:
        assert m["moves"] in end_to_end and m["moves"] != "setup_s", m["name"]
        spec = read(HERE, "layer_metrics", m["name"] + ".json")
        assert spec["reads"]
        reduction = importlib.import_module(f"benchmark.reductions.{spec['reduction']['kind']}")
        assert callable(reduction.reduce)
        if "cost" in spec["reduction"]:
            assert callable(importlib.import_module(
                f"benchmark.costs.{spec['reduction']['cost']}").cost)


def test_every_config_is_used_and_every_metric_has_a_cell():
    assert {w["config"] for w in MANIFEST["workloads"]} == {c["name"] for c in MANIFEST["configs"]}
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS) and m.get("workloads", CELLS)
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(f"| {layer} |" in perf for layer in layers), layers


def test_peaks_name_their_source():
    for kind, row in read(HERE, "peaks.json").items():
        assert row["source"] and row["flops_bf16_per_s"] > 0 and row["hbm_bytes_per_s"] > 0, kind


def run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    return subprocess.run([sys.executable, os.path.join(HERE, "run.py"), *args],
                          capture_output=True, text=True, env=env, timeout=600, cwd=ROOT)


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_the_cell_and_prints_no_result(cell, trace):
    done = run("--workload", cell, "--seed", str(2 ** 31 + 11), "--seconds", "2",
               "--trace", trace, "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(last) == {"rehearsal"}             # never the keys of a result
    line = last["rehearsal"]
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    want = {m["name"] for m in metrics_of("per_layer" if trace == "1" else "end_to_end", cell)}
    assert set(line["metrics"]) <= want
    if trace == "0":
        assert set(line["metrics"]) == want
        assert all(v["value"] > 0 for v in line["metrics"].values())
    else:                                          # no device plane on the CPU: trace metrics stay out
        assert not any(m["source"] == "device_trace" and m["name"] in line["metrics"]
                       for m in MANIFEST["per_layer"])
    assert line["device"]["platform"] == "cpu"


def test_without_an_accelerator_there_is_no_result():
    done = run("--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert done.stdout.strip() == ""


def test_an_unknown_workload_is_refused():
    done = run("--workload", "no_such.cell", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert done.returncode != 0 and done.stdout.strip() == ""
