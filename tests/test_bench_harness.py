"""bench.py and chip_smoke.py without a chip, and the bench gate.

A measurement path that finds no chip fails: ``bench.py`` exits non-zero and
prints no record on any backend but ``tpu`` (there is no CPU form of its
numbers), and ``chip_smoke.py`` reports a result only from an accelerator.
The smoke script's phases are rehearsed here on the CPU at a tiny size, so a
wrong path, argument or control flow costs no chip time.
"""

import json
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(script, *argv, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(ROOT, script), *argv],
                          env=env, cwd=ROOT, timeout=timeout,
                          capture_output=True, text=True)


def _json_lines(stdout):
    return [json.loads(ln) for ln in stdout.splitlines()
            if ln.startswith("{")]


def test_bench_without_tpu_fails_and_prints_no_record():
    r = _run("bench.py", timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "tpu" in r.stderr


def test_chip_smoke_without_accelerator_fails_and_prints_nothing():
    """The default invocation never trains on the CPU."""
    r = _run("chip_smoke.py", timeout=120)
    assert r.returncode == 2
    assert r.stdout.strip() == ""


def test_chip_smoke_rehearsal_runs_every_phase_and_never_reports_ok():
    r = _run("chip_smoke.py", "--rehearse", timeout=600)
    assert r.returncode == 3, r.stderr[-3000:]
    lines = _json_lines(r.stdout)
    assert [ln["phase"] for ln in lines] == [
        "init", "sync", "ingest", "frame_airlines", "train_xgboost",
        "check_hist_mode", "check_split_mode", "check_tree_program",
        "train_gbm_7class", "frame_higgs", "train_glm",
        "train_deeplearning", "score", "serve"]
    assert not any(ln.get("ok") for ln in lines)
    assert all(ln["platform"] == "cpu" and ln["device_kind"]
               and ln["devices"] for ln in lines)


# -------------------------------------------------------- bench_gate tests

def _load_bench_gate():
    import importlib.util
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "bench_gate.py")
    spec = importlib.util.spec_from_file_location("bench_gate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench_gate = _load_bench_gate()


def _write(tmp_path, name, record):
    p = tmp_path / name
    p.write_text(json.dumps(record))
    return str(p)


def _record_json(tps, gbm_sec, **extra):
    return {"metric": "trees_per_sec_bench", "value": tps,
            "extra": {"gbm_sec": gbm_sec, "rows": 1000, **extra}}


def _gate(tmp_path, candidate, baselines):
    out = str(tmp_path / "report.txt")
    argv = [candidate, "--out", out]
    for b in baselines:
        argv += ["--baseline", b]
    rc = bench_gate.main(argv)
    report = open(out).read() if os.path.exists(out) else ""
    return rc, report


def test_gate_improvement_passes(tmp_path):
    base = _write(tmp_path, "BENCH_r01.json", _record_json(100.0, 10.0))
    cand = _write(tmp_path, "cand.json", _record_json(150.0, 7.0))
    rc, report = _gate(tmp_path, cand, [base])
    assert rc == 0
    assert "0 regression(s)" in report


def test_gate_in_tolerance_noise_passes(tmp_path):
    """-5% rate / +5% wall sits inside the default 10% band."""
    base = _write(tmp_path, "BENCH_r01.json", _record_json(100.0, 10.0))
    cand = _write(tmp_path, "cand.json", _record_json(95.0, 10.5))
    rc, report = _gate(tmp_path, cand, [base])
    assert rc == 0


def test_gate_regression_fails(tmp_path):
    base = _write(tmp_path, "BENCH_r01.json", _record_json(100.0, 10.0))
    cand = _write(tmp_path, "cand.json", _record_json(50.0, 30.0))
    rc, report = _gate(tmp_path, cand, [base])
    assert rc == 1
    assert "regress" in report
    # both the rate drop and the wall-clock blow-up are flagged
    assert "trees_per_sec_bench" in report and "gbm_sec" in report


def test_gate_new_metric_passes_as_new(tmp_path):
    base = _write(tmp_path, "BENCH_r01.json", _record_json(100.0, 10.0))
    cand = _write(tmp_path, "cand.json",
                  _record_json(100.0, 10.0, glm_sec=3.0))
    rc, report = _gate(tmp_path, cand, [base])
    assert rc == 0
    assert "1 new" in report


def test_gate_skips_unreadable_baseline(tmp_path, capsys):
    """A corrupt baseline round drops out with a note; the rest gate."""
    bad = _write(tmp_path, "BENCH_r01.json", {})
    (tmp_path / "BENCH_r02.json").write_text("not json {")
    good = _write(tmp_path, "BENCH_r03.json", _record_json(100.0, 10.0))
    cand = _write(tmp_path, "cand.json", _record_json(100.0, 10.0))
    rc, _ = _gate(tmp_path, cand,
                  [bad, str(tmp_path / "BENCH_r02.json"), good])
    assert rc == 0
    assert "skipping unreadable baseline" in capsys.readouterr().err


def test_gate_no_baselines_is_config_error(tmp_path):
    cand = _write(tmp_path, "cand.json", _record_json(100.0, 10.0))
    rc = bench_gate.main([cand, "--baseline",
                          str(tmp_path / "missing.json"),
                          "--out", str(tmp_path / "r.txt")])
    assert rc == 2


def test_gate_references_latest_round_not_alltime_best(tmp_path):
    """The r04/r05 scenario: an older round's metric beat the latest
    because the workload shape changed; a candidate equal to the latest
    round must still pass (best is context only)."""
    r04 = _write(tmp_path, "BENCH_r04.json", _record_json(500.0, 1.7))
    r05 = _write(tmp_path, "BENCH_r05.json", _record_json(100.0, 8.3))
    cand = _write(tmp_path, "cand.json", _record_json(100.0, 8.3))
    rc, report = _gate(tmp_path, cand, [r04, r05])
    assert rc == 0
    assert "500.000" in report               # all-time best shown as context
    rounds = bench_gate.load_baselines([r04, r05])
    rows = {r["name"]: r for r in bench_gate.evaluate(
        bench_gate.flatten(_record_json(100.0, 8.3)), rounds)}
    tps = rows["trees_per_sec_bench"]
    assert tps["status"] == "pass"
    assert tps["ref_file"] == "BENCH_r05.json"   # gated vs the latest round
    assert tps["best_file"] == "BENCH_r04.json"  # best is context only


def test_gate_flattens_multichip_entries(tmp_path):
    rec = {"bench": "multichip", "entries": [
        {"n_devices": 8, "trees_per_sec": 10.0, "wall_s": 5.0},
        {"n_devices": 32, "trees_per_sec": 30.0, "wall_s": 6.0}],
        "scaling_8_to_32": 3.0}
    flat = bench_gate.flatten(rec)
    assert flat == {"multichip_trees_per_sec_8dev": 10.0,
                    "multichip_wall_s_8dev": 5.0,
                    "multichip_trees_per_sec_32dev": 30.0,
                    "multichip_wall_s_32dev": 6.0,
                    "scaling_8_to_32": 3.0}
    base = _write(tmp_path, "MULTICHIP_r01.json", rec)
    worse = dict(rec, scaling_8_to_32=2.0)   # -33% > the 15% band
    cand = _write(tmp_path, "cand.json", worse)
    rc, report = _gate(tmp_path, cand, [base])
    assert rc == 1 and "scaling_8_to_32" in report


def test_gate_direction_classifier():
    assert bench_gate.classify("trees_per_sec_x") == "higher"
    assert bench_gate.classify("scaling_8_to_32") == "higher"
    assert bench_gate.classify("glm_higgs_shape_sec") == "lower"
    assert bench_gate.classify("bench_wall_s") == "lower"
    assert bench_gate.classify("rows") == "info"
    assert bench_gate.classify("xgboost_compile_s") == "info"
    assert bench_gate.classify("gbm_higgs_steady_s") == "info"
    assert bench_gate.classify("compiles_total") == "info"
    # serving metrics gate from their first recorded round
    assert bench_gate.classify("serve_p50_ms") == "lower"
    assert bench_gate.classify("serve_p99_ms") == "lower"
    assert bench_gate.classify("serve_latency_seconds") == "lower"
    assert bench_gate.classify("warmup_seconds") == "lower"
    assert bench_gate.classify("serve_qps") == "higher"
    # count-style metrics: dispatch/launch/recompile counts gate
    # lower-better from their first recorded round
    assert bench_gate.classify("treescan_launches_per_tree_scan") == "lower"
    assert bench_gate.classify("treescan_launches_per_tree_level") == "lower"
    assert bench_gate.classify("hist_dispatch_total") == "lower"
    assert bench_gate.classify("recompile_count") == "lower"
    # ... but the ledger echo compiles_total stays informational
    assert bench_gate.classify("compiles_total") == "info"
    # speedup ratios are higher-better
    assert bench_gate.classify("treescan_scan_vs_level_speedup") == "higher"
    assert bench_gate.classify("serve_packed_speedup_vs_numpy") == "higher"


def test_gate_count_metric_regression(tmp_path):
    """A launch-count blow-up (the treescan dispatch pin) regresses; a
    count that shrinks or holds passes."""
    rec = {"metric": "serve_qps", "value": 2000.0,
           "extra": {"treescan_launches_per_tree_scan": 2,
                     "serve_qps": 2000.0}}
    base = _write(tmp_path, "BENCH_r01.json", rec)
    worse = {"metric": "serve_qps", "value": 2000.0,
             "extra": {"treescan_launches_per_tree_scan": 20,
                       "serve_qps": 2000.0}}
    cand = _write(tmp_path, "cand.json", worse)
    rc, report = _gate(tmp_path, cand, [base])
    assert rc == 1 and "treescan_launches_per_tree_scan" in report
    same = {"metric": "serve_qps", "value": 2000.0,
            "extra": {"treescan_launches_per_tree_scan": 2,
                      "serve_qps": 2000.0}}
    cand2 = _write(tmp_path, "cand2.json", same)
    rc, _ = _gate(tmp_path, cand2, [base])
    assert rc == 0


def test_gate_serving_latency_regression(tmp_path):
    rec = {"metric": "serve_qps", "value": 2000.0,
           "extra": {"serve_p50_ms": 2.0, "serve_p99_ms": 5.0,
                     "serve_qps": 2000.0}}
    base = _write(tmp_path, "BENCH_r01.json", rec)
    worse = {"metric": "serve_qps", "value": 2000.0,
             "extra": {"serve_p50_ms": 4.0, "serve_p99_ms": 5.0,
                       "serve_qps": 2000.0}}
    cand = _write(tmp_path, "cand.json", worse)
    rc, report = _gate(tmp_path, cand, [base])
    assert rc == 1 and "serve_p50_ms" in report


def test_gate_absolute_floor_gates_new_metric(tmp_path):
    """autotune_vs_best carries an absolute 0.97 floor: it is GATED even
    on its first round (normal new metrics pass ungated), and a value
    below the floor regresses regardless of history."""
    base = _write(tmp_path, "BENCH_r01.json", _record_json(100.0, 10.0))
    good = _write(tmp_path, "cand.json",
                  _record_json(100.0, 10.0, autotune_vs_best=0.99))
    rc, report = _gate(tmp_path, good, [base])
    assert rc == 0
    assert "absolute floor" in report

    bad = _write(tmp_path, "cand2.json",
                 _record_json(100.0, 10.0, autotune_vs_best=0.90))
    rc, report = _gate(tmp_path, bad, [base])
    assert rc == 1
    assert "below absolute floor" in report


def test_gate_absolute_floor_beats_tolerance_band(tmp_path):
    """A bad prior round cannot drag the floor down: within-tolerance of
    a sub-floor baseline still regresses."""
    base = _write(tmp_path, "BENCH_r01.json",
                  _record_json(100.0, 10.0, autotune_vs_best=0.92))
    cand = _write(tmp_path, "cand.json",
                  _record_json(100.0, 10.0, autotune_vs_best=0.93))
    rc, report = _gate(tmp_path, cand, [base])
    assert rc == 1
    assert "below absolute floor" in report
