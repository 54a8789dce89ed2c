"""chip_smoke.py without a chip.

A measurement path that finds no chip fails: ``chip_smoke.py`` reports a
result only from an accelerator.  Its phases are rehearsed here on the CPU at
a tiny size, so a wrong path, argument or control flow costs no chip time.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(script, *argv, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(ROOT, script), *argv],
                          env=env, cwd=ROOT, timeout=timeout,
                          capture_output=True, text=True)


def _json_lines(stdout):
    return [json.loads(ln) for ln in stdout.splitlines()
            if ln.startswith("{")]


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
        "parity_hist_mode", "parity_split_mode", "parity_tree_program",
        "parity_tree_program_varbin", "train_gbm_7class", "frame_higgs", "train_glm",
        "train_deeplearning", "score", "serve"]
    assert not any(ln.get("ok") for ln in lines)
    assert all(ln["platform"] == "cpu" and ln["device_kind"]
               and ln["devices"] for ln in lines)
    # each parity phase trained both public values and compared them
    for ln in lines:
        if ln["phase"].startswith("parity_"):
            assert len(ln["fits"]) == 2 and ln["trees"] >= 1, ln


def _records(rng, trees=2, depth=3):
    levels = [(rng.integers(0, 8, (trees, 2 ** d)).astype(np.int32),
               rng.normal(size=(trees, 2 ** d)).astype(np.float32),
               rng.random((trees, 2 ** d)) < 0.5,
               np.ones((trees, 2 ** d), bool)) for d in range(depth)]
    return levels, rng.normal(size=(trees, 2 ** depth)).astype(np.float32)


@pytest.mark.parametrize("field,at_valid,reported", [
    (0, True, "feat"), (1, True, "thr"), (2, True, "na_left"),
    (0, False, None),       # a candidate at a node that does not split
    (3, True, "valid")])
def test_compare_trees_reports_what_differs(rng, field, at_valid, reported):
    """The comparison chip_smoke's parity and multichip phases share: what a
    node that splits holds must agree, what one that does not is not read."""
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    lv, values = _records(rng)
    same = chip_smoke.compare_trees((lv, values), (lv, values + 1e-6))
    assert same["trees"] == 2 and same["structure_differs"] == []
    assert 0 < same["leaf_max_abs_diff"] < 2e-6
    other = [tuple(a.copy() for a in level) for level in lv]
    if not at_valid:
        for side in (lv, other):
            side[2][3][1, 2] = False
    node = other[2][field]
    node[1, 2] = ~node[1, 2] if node.dtype == bool else node[1, 2] + 1
    differ = chip_smoke.compare_trees((lv, values), (other, values))
    assert differ["structure_differs"] == \
        ([(2, reported, 1)] if reported else [])
