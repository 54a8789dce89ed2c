"""Cost-model autotuner: decision lifecycle, cache persistence, and the
correctness net underneath it.

Five behaviours pin the design (see docs/operations.md "Autotuning"):
  1. the warm-start cache round-trips ACROSS processes — a fresh process
     serves source="cache" and never explores (zero re-measures);
  2. a corrupt or version-stale cache file silently degrades to
     model-seeded decisions — the tuner can never error a training path;
  3. a mesh rebuild (cluster_reinit epoch bump) drops every decision;
  4. a forced-wrong cost model self-corrects from measured device
     samples — the epsilon-greedy re-measure flips the choice;
  5. whatever the tuner picks beside a pinned knob, both values of that
     knob still grow the same trees; a fit with every knob pinned
     bypasses tuning entirely.

The suite-wide conftest pins H2O3_TPU_AUTOTUNE=off; these tests opt back
in per-test through the ``tuner_on`` fixture (explicit env save/restore,
because config() caches the environment).
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import h2o3_tpu


def _params(**kw):
    """Attribute bag standing in for SharedTreeParameters at resolve."""
    d = dict(hist_mode="auto", split_mode="auto", hist_layout="auto",
             sparse_depth_threshold=8, max_depth=10, nbins=64)
    d.update(kw)
    return types.SimpleNamespace(**d)


@pytest.fixture()
def tuner_on(tmp_path):
    """Autotuner on with an isolated cache dir; restores the suite's
    pinned-off environment (and the cached Config) afterwards."""
    from h2o3_tpu.runtime import autotune, config
    keys = ("H2O3_TPU_AUTOTUNE", "H2O3_TPU_AUTOTUNE_CACHE_DIR",
            "H2O3_TPU_AUTOTUNE_EXPLORE", "H2O3_TPU_DEVICE_TIMING")
    saved = {k: os.environ.get(k) for k in keys}
    os.environ.pop("H2O3_TPU_DEVICE_TIMING", None)    # shipped value: off
    os.environ["H2O3_TPU_AUTOTUNE"] = "on"
    os.environ["H2O3_TPU_AUTOTUNE_CACHE_DIR"] = str(tmp_path / "atcache")
    config.reload()
    autotune.reset()
    yield autotune
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    config.reload()
    autotune.reset()


# --------------------------------------------------------- off == before

def test_off_resolves_auto_to_historical_defaults():
    """With the tuner off (the suite default), every "auto" knob lands on
    the pre-tuner fixed default — bit-identical kernels to the seed."""
    from h2o3_tpu.runtime import autotune
    assert autotune.autotune_mode() == "off"
    k = autotune.resolve_tree_knobs(_params(), kind="gbm", F=8, N=4096)
    assert (k.hist_mode, k.split_mode) == ("subtract", "fused")
    assert k.hist_layout == "sparse"      # builder value: below-threshold
    assert k.sparse_depth_threshold == 8
    assert k.sig is None                  # tuner never engaged
    assert set(k.sources.values()) == {"default"}


def test_unknown_mode_reads_as_off(tuner_on):
    from h2o3_tpu.runtime import config
    os.environ["H2O3_TPU_AUTOTUNE"] = "bogus"
    config.reload()
    assert tuner_on.autotune_mode() == "off"


def test_user_pinned_knobs_pass_through(tuner_on):
    """Explicit values are never overridden — only "auto" knobs tune."""
    k = tuner_on.resolve_tree_knobs(
        _params(hist_mode="full", split_mode="separate"),
        kind="gbm", F=8, N=4096)
    assert (k.hist_mode, k.split_mode) == ("full", "separate")
    assert k.sources["hist_mode"] == "user"
    assert k.sources["split_mode"] == "user"


# ------------------------------------------------------- model decisions

def test_model_seeded_decision_and_table(tuner_on):
    k = tuner_on.resolve_tree_knobs(_params(), kind="gbm", F=8, N=65536)
    assert k.sig is not None
    assert k.sources["hist_mode"] in ("model", "explore")
    t = tuner_on.decision_table()
    assert t["mode"] == "on" and t["entries"] == 1
    row = t["decisions"][0]
    assert row["signature"] == k.sig
    assert row["source"] == "model"
    assert row["predicted_s"], "model must record per-candidate costs"


def test_checkpoint_pins_sparse_threshold(tuner_on):
    """Checkpoint continuations keep the params threshold: the resumed
    tree was depth-validated against it."""
    k = tuner_on.resolve_tree_knobs(_params(), kind="gbm", F=8, N=65536,
                                    checkpoint=True)
    assert k.sparse_depth_threshold == 8
    assert k.sources["sparse_depth_threshold"] == "default"


def test_pinned_knobs_bypass_tuner(tuner_on):
    """Nothing left to tune, nothing decided or recorded (chip_smoke.py's
    parity phases pin every knob so that two fits differ in one)."""
    k = tuner_on.resolve_tree_knobs(
        _params(hist_mode="full", split_mode="separate",
                hist_layout="dense", tree_program="scan"),
        kind="gbm", F=8, N=4096)
    assert (k.hist_mode, k.split_mode, k.hist_layout, k.tree_program) == \
        ("full", "separate", "dense", "scan")
    assert k.sig is None and set(k.sources.values()) <= {"user", "default"}
    assert tuner_on.decision_table()["entries"] == 0


# ------------------------------------------------- cache: cross-process

_CHILD = r"""
import json, sys
from h2o3_tpu.runtime import autotune
import types
p = types.SimpleNamespace(hist_mode="auto", split_mode="auto",
                          hist_layout="auto", sparse_depth_threshold=8,
                          max_depth=10, nbins=64)
sources = []
for _ in range(8):                       # well past explore_every=2
    k = autotune.resolve_tree_knobs(p, kind="gbm", F=8, N=65536)
    sources.append(k.sources["hist_mode"])
t = autotune.decision_table()
print(json.dumps({"sources": sources, "table": t}))
"""


def _run_child(cache_dir):
    env = os.environ.copy()
    env.update(JAX_PLATFORMS="cpu", H2O3_TPU_AUTOTUNE="on",
               H2O3_TPU_AUTOTUNE_CACHE_DIR=str(cache_dir),
               H2O3_TPU_AUTOTUNE_EXPLORE="2")
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_cache_round_trip_across_processes(tmp_path):
    """Process 1 decides from the model and persists; process 2 warm-
    starts with source="cache" and NEVER dispatches a re-measure — the
    acceptance bar for warm restarts."""
    cache = tmp_path / "atcache"
    first = _run_child(cache)
    assert first["table"]["decisions"][0]["source"] == "model"
    assert (cache / "autotune_cache.json").exists()

    second = _run_child(cache)
    row = second["table"]["decisions"][0]
    assert row["source"] == "cache"
    assert set(second["sources"]) == {"cache"}, \
        "warm-start resolves must all come from the cache"
    assert row["exploring"] is None, \
        "cache-sourced decisions never explore (zero re-measures)"
    assert row["choice"] == first["table"]["decisions"][0]["choice"]


def test_corrupt_cache_degrades_to_model(tuner_on, tmp_path):
    """Garbage in the cache file must never error — decisions fall back
    to the cost model."""
    cache_dir = tmp_path / "atcache"
    cache_dir.mkdir(parents=True, exist_ok=True)
    (cache_dir / "autotune_cache.json").write_text("{not json !!!")
    tuner_on.reset()
    k = tuner_on.resolve_tree_knobs(_params(), kind="gbm", F=8, N=65536)
    assert k.sig is not None
    assert tuner_on.decision_table()["decisions"][0]["source"] == "model"


def test_stale_cache_header_is_ignored(tuner_on, tmp_path):
    """A cache written by a different backend/jax version is dead weight,
    not an error and not a decision source."""
    cache_dir = tmp_path / "atcache"
    cache_dir.mkdir(parents=True, exist_ok=True)
    sig = tuner_on._signature("gbm", 8, 65536, 1, 10, 64)
    payload = {"header": {"version": 1, "backend": "tpu", "jax": "9.9.9"},
               "entries": {sig: {"choice": "full|separate|dense|t10",
                                 "predicted": {}, "measured": {}}}}
    (cache_dir / "autotune_cache.json").write_text(json.dumps(payload))
    tuner_on.reset()
    k = tuner_on.resolve_tree_knobs(_params(), kind="gbm", F=8, N=65536)
    row = tuner_on.decision_table()["decisions"][0]
    assert row["source"] == "model"
    assert k.hist_mode != "full" or row["choice"] != "full|separate|dense|t10"


def test_cache_of_the_old_model_version_is_ignored(tuner_on, tmp_path):
    """A file written before the model priced the compaction (header
    version 1, this backend, this jax) holds choices made under the old
    price: it degrades to the model like any stale header."""
    cache_dir = tmp_path / "atcache"
    cache_dir.mkdir(parents=True, exist_ok=True)
    sig = tuner_on._signature("gbm", 8, 65536, 1, 10, 64)
    old = "subtract|fused|sparse|t8|plevel"
    header = dict(tuner_on._cache_header(), version=1)
    assert header != tuner_on._cache_header()
    payload = {"header": header,
               "entries": {sig: {"choice": old, "source": "model",
                                 "predicted": {old: 1e-9}, "measured": {}}}}
    (cache_dir / "autotune_cache.json").write_text(json.dumps(payload))
    tuner_on.reset()
    k = tuner_on.resolve_tree_knobs(_params(), kind="gbm", F=8, N=65536)
    row = tuner_on.decision_table()["decisions"][0]
    assert row["source"] == "model" and k.sources["hist_mode"] == "model"
    # the same file under the current header IS served: the version alone
    # is what killed it
    payload["header"] = tuner_on._cache_header()
    (cache_dir / "autotune_cache.json").write_text(json.dumps(payload))
    tuner_on.reset()
    tuner_on.resolve_tree_knobs(_params(), kind="gbm", F=8, N=65536)
    row = tuner_on.decision_table()["decisions"][0]
    assert (row["source"], row["choice"]) == ("cache", old)


# --------------------------------------------------------- invalidation

def test_cluster_reinit_invalidates_decisions(tuner_on):
    """invalidate("cluster_reinit") drops the table AND marks the loaded
    cache file dead for this process — a geometry change can never serve
    a stale choice (the file stays for FRESH processes, whose signature
    includes the new mesh)."""
    tuner_on.resolve_tree_knobs(_params(), kind="gbm", F=8, N=65536)
    assert tuner_on.decision_table()["entries"] == 1
    epoch = tuner_on.decision_table()["epoch"]
    tuner_on.invalidate("cluster_reinit")
    t = tuner_on.decision_table()
    assert t["entries"] == 0 and t["epoch"] == epoch + 1
    # post-invalidate resolves re-decide from the model, not the file
    tuner_on.resolve_tree_knobs(_params(), kind="gbm", F=8, N=65536)
    assert tuner_on.decision_table()["decisions"][0]["source"] == "model"


# ------------------------------------------------- measured refinement

def _inverted(real):
    """``real`` with the finite costs mirrored: the worst candidate seeds."""
    def inverted(F, N, K, max_depth, nbins, candidates):
        costs = real(F, N, K, max_depth, nbins, candidates)
        finite = [v for v in costs.values() if v != float("inf")]
        top = max(finite) if finite else 1.0
        return {k: (v if v == float("inf") else top - v + 1e-9)
                for k, v in costs.items()}
    return inverted


def test_forced_wrong_model_self_corrects(tuner_on, monkeypatch):
    """Invert the cost model so it seeds the WORST candidate, then feed
    real-shaped device samples: once two candidates carry measurements
    the faster one wins permanently (source="measured")."""
    real = tuner_on._predict_costs
    monkeypatch.setattr(tuner_on, "_predict_costs", _inverted(real))
    os.environ["H2O3_TPU_AUTOTUNE_EXPLORE"] = "2"
    from h2o3_tpu.runtime import config
    config.reload()

    k = tuner_on.resolve_tree_knobs(_params(), kind="gbm", F=8, N=65536)
    wrong = tuner_on.decision_table()["decisions"][0]["choice"]
    # the true argmin under the real model — what measurement should find
    ent = tuner_on._DECISIONS[k.sig]
    truth = real(8, 65536, 1, 10, 64, list(ent["candidates"].values()))
    right = min((c for c in truth if truth[c] != float("inf")),
                key=truth.get)
    assert wrong != right, "inversion failed to mis-seed the model"

    # sampled device timings: the mis-seeded choice is slow, the true
    # best is fast (fed through the public measurement sink, as
    # xprof.maybe_device_sync would)
    tuner_on.activate(tuner_on.TreeKnobs(
        "subtract", "fused", "dense", 8, "level", {}, sig=k.sig, run_key=wrong))
    tuner_on.on_device_sample("tree_scan", 2.0)
    tuner_on.activate(tuner_on.TreeKnobs(
        "subtract", "fused", "dense", 8, "level", {}, sig=k.sig, run_key=right))
    tuner_on.on_device_sample("tree_scan", 0.1)

    row = tuner_on.decision_table()["decisions"][0]
    assert row["choice"] == right, "measured evidence must overturn model"
    assert row["source"] == "measured"
    # subsequent resolves serve the corrected choice (unless that very
    # resolve is itself an epsilon exploration of another candidate)
    k2 = tuner_on.resolve_tree_knobs(_params(), kind="gbm", F=8, N=65536)
    if "explore" not in k2.sources.values():
        assert k2.run_key == right
    assert tuner_on.decision_table()["decisions"][0]["choice"] == right
    tuner_on.deactivate()


def test_no_exploration_while_device_timing_is_off(tuner_on):
    """The shipped environment (H2O3_TPU_DEVICE_TIMING off) has no source
    of measurements, so the runner-up is never run: it would compile a
    second tree program and learn nothing."""
    from h2o3_tpu.runtime import xprof
    assert xprof.device_timing_mode() == "off"
    assert tuner_on._explore_every() == 16
    sources = [tuner_on.resolve_tree_knobs(_params(), kind="gbm", F=8,
                                           N=65536).sources
               for _ in range(40)]
    assert all("explore" not in s.values() for s in sources)
    row = tuner_on.decision_table()["decisions"][0]
    assert row["resolves"] == 40 and row["exploring"] is None
    assert row["source"] == "model"


def test_sampled_timing_explores_and_self_corrects(tuner_on, monkeypatch):
    """With H2O3_TPU_DEVICE_TIMING=sampled the forced-wrong lifecycle
    still runs end to end: the N-th resolve runs the runner-up
    (source "explore"), its sample and the choice's come back through the
    measurement sink, and the faster one wins (source="measured")."""
    from h2o3_tpu.runtime import config
    monkeypatch.setattr(tuner_on, "_predict_costs",
                        _inverted(tuner_on._predict_costs))
    os.environ["H2O3_TPU_AUTOTUNE_EXPLORE"] = "2"
    os.environ["H2O3_TPU_DEVICE_TIMING"] = "sampled"
    config.reload()

    k1 = tuner_on.resolve_tree_knobs(_params(), kind="gbm", F=8, N=65536)
    assert "explore" not in k1.sources.values()
    tuner_on.activate(k1)
    tuner_on.on_device_sample("tree_scan", 2.0)      # the wrong seed: slow
    k2 = tuner_on.resolve_tree_knobs(_params(), kind="gbm", F=8, N=65536)
    assert k2.sources["hist_mode"] == "explore"
    assert k2.run_key != k1.run_key
    assert tuner_on.decision_table()["decisions"][0]["exploring"] == \
        k2.run_key
    tuner_on.activate(k2)
    tuner_on.on_device_sample("tree_scan", 0.1)      # the runner-up: fast
    row = tuner_on.decision_table()["decisions"][0]
    assert (row["choice"], row["source"]) == (k2.run_key, "measured")
    tuner_on.deactivate()


def test_non_tree_phases_do_not_pollute(tuner_on):
    """map_reduce / serve phase samples on the driver thread must not be
    attributed to the active tree decision."""
    k = tuner_on.resolve_tree_knobs(_params(), kind="gbm", F=8, N=65536)
    tuner_on.activate(k)
    tuner_on.on_device_sample("map_reduce", 5.0)
    row = tuner_on.decision_table()["decisions"][0]
    assert not row["measured_s"]
    tuner_on.deactivate()


# ------------------------------------------------ whole-model correctness

def _tiny_frame(rng, n=600):
    from h2o3_tpu import Frame
    X = rng.normal(size=(n, 4))
    y = X[:, 0] * 0.6 - 0.3 * X[:, 1] + 0.1 * rng.normal(size=n)
    return Frame.from_numpy(
        {**{f"x{i}": X[:, i] for i in range(4)}, "y": y})


def test_both_hist_modes_agree_under_tuner(cl, rng, tuner_on):
    """The correctness net survives the tuner: with autotune on and the
    other knobs left to it, a subtract fit and a full-build fit (the
    oracle) grow the same trees."""
    from h2o3_tpu.models.tree.gbm import GBM
    from tree_parity import assert_same_trees
    fr = _tiny_frame(rng)
    kw = dict(response_column="y", ntrees=3, max_depth=3, nbins=16, seed=7,
              reproducible=True)
    m_s = GBM(hist_mode="subtract", **kw).train(fr)
    m_f = GBM(hist_mode="full", **kw).train(fr)
    assert tuner_on.decision_table()["entries"] >= 1      # it did engage
    assert_same_trees(m_s, m_f)


def test_tuned_auto_matches_pinned_choice_bitwise(cl, rng, tuner_on):
    """Whatever the tuner picks, training under it equals training with
    the same knobs pinned by hand — the tuner changes strategy, never
    results."""
    from h2o3_tpu.models.tree.gbm import GBM
    fr = _tiny_frame(rng)
    kw = dict(response_column="y", ntrees=4, max_depth=3, nbins=16,
              seed=11, reproducible=True)
    m_auto = GBM(**kw).train(fr)
    t = tuner_on.decision_table()
    rows = [d for d in t["decisions"]
            if d["signature"].startswith("gbm:")]
    assert rows, "training under the tuner must record a decision"
    hm, sm, layout, thr, prog = rows[0]["choice"].split("|")
    tuner_on.reset()
    m_pin = GBM(**kw, hist_mode=hm, split_mode=sm, hist_layout=layout,
                sparse_depth_threshold=int(thr[1:]),
                tree_program=prog[1:]).train(fr)
    a = np.asarray(m_auto.predict(fr).vec("predict").to_numpy())
    b = np.asarray(m_pin.predict(fr).vec("predict").to_numpy())
    np.testing.assert_array_equal(a, b)


# ------------------------------------------------------ reduce / serving

def test_reduce_mode_auto(tuner_on, cl):
    from h2o3_tpu.runtime.mapreduce import resolve_reduce_mode
    want = "hier" if cl.n_hosts > 1 else "flat"
    assert resolve_reduce_mode("auto") == want
    sigs = [d["signature"] for d in
            tuner_on.decision_table()["decisions"]]
    assert any(s.startswith("reduce:") for s in sigs)


def test_reduce_mode_auto_off_is_hier():
    """Suite default (tuner off): "auto" keeps the historical hier."""
    from h2o3_tpu.runtime.mapreduce import resolve_reduce_mode
    assert resolve_reduce_mode("auto") == "hier"


def test_serve_impl_auto(tuner_on):
    impl = tuner_on.resolve_serve_impl(depth=10, R=300, F=32, B=256)
    assert impl == "xla"                 # on every backend, tpu included
    sigs = [d["signature"] for d in
            tuner_on.decision_table()["decisions"]]
    assert any(s.startswith("serve:") for s in sigs)


# ------------------------------------------------------- peaks by device

def test_peaks_table_has_the_v5e_row():
    """Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s,
    16 GB of HBM; PERF_LEDGER.jsonl, PR 28: 12 M rows/s of a unique-index
    row scatter; keyed by the device_kind JAX reports for that chip."""
    from h2o3_tpu.runtime import autotune
    assert autotune._DEVICE_PEAKS["TPU v5 lite"] == (1.97e14, 8.19e11,
                                                     1.6e10, 1.2e7)
    assert autotune._peaks() == autotune._DEVICE_PEAKS["cpu"]
    assert all(len(row) == 4 and min(row) > 0
               for row in autotune._DEVICE_PEAKS.values())


def test_unknown_device_kind_is_an_error(monkeypatch):
    """A device that is not in the table is an error, not the CPU's row:
    both the roofline seed and the grid-batch memory gate refuse it."""
    from h2o3_tpu.runtime import autotune
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v9")
    monkeypatch.setattr(autotune, "_device", lambda: unknown)
    with pytest.raises(ValueError, match="TPU v9"):
        autotune._peaks()
    with pytest.raises(ValueError, match="TPU v9"):
        autotune.resolve_grid_batch(kind="gbm", F=8, N=4096, G=4,
                                    max_depth=5, nbins=64)


# ------------------------------------------- the price of the compaction

@pytest.fixture()
def v5e_row(monkeypatch):
    """The cost model as the v5e sees it (no chip needed: the model is
    arithmetic over the device's row)."""
    from h2o3_tpu.runtime import autotune
    monkeypatch.setattr(autotune, "_peaks",
                        lambda: autotune._DEVICE_PEAKS["TPU v5 lite"])
    return autotune


def _model_choice(at, F, N, max_depth, nbins, K=1):
    tuned = dict.fromkeys(("hist_mode", "split_mode", "hist_layout",
                           "sparse_depth_threshold", "tree_program"), True)
    cands = at._tree_candidates(F, N, K, max_depth, nbins, mono=None,
                                plan=None, tuned=tuned)
    costs = at._predict_costs(F, N, K, max_depth, nbins, cands)
    return min(costs, key=costs.get), costs


def test_v5e_model_builds_over_all_rows_where_the_dense_grid_fits(v5e_row):
    """The benchmark's headline shape (xgb_airlines40m.fit): with the
    scatters priced, the argmin is a full build on the dense grid, and the
    best subtract candidate is priced near what the chip took (33.6 s of
    scatters: ledger, PR 28)."""
    choice, costs = _model_choice(v5e_row, F=8, N=40_000_000, max_depth=6,
                                  nbins=256)
    assert choice.startswith("full|") and "|dense|" in choice
    sub = min(v for k, v in costs.items() if k.startswith("subtract|"))
    assert 25.0 < sub < 45.0
    assert costs[choice] < sub / 100


def test_v5e_model_keeps_subtract_sparse_past_the_dense_budget(v5e_row):
    """DRF's default depth: the dense grid passes the 64 MB histogram
    budget, every full candidate prices at +inf, and the only builder of
    those levels stays the choice."""
    choice, costs = _model_choice(v5e_row, F=8, N=40_000_000, max_depth=20,
                                  nbins=256)
    assert choice.startswith("subtract|") and "|sparse|" in choice
    assert all(v == float("inf") for k, v in costs.items()
               if k.startswith("full|"))


@pytest.mark.parametrize("log2_n", range(12, 28))
def test_v5e_subtract_level_costs_more_than_a_full_level(v5e_row, log2_n):
    """Per row the compaction costs ~165 ns and saves 2.4: no N turns that
    round.  Two-level trees differ by exactly the one level past the root
    (same root, same launches)."""
    kw = dict(split_mode="fused", hist_layout="dense", threshold=8,
              tree_program="level")
    N = 2 ** log2_n
    sub = v5e_row._predict_tree_cost(8, N, 1, 2, 256, hist_mode="subtract",
                                     **kw)
    full = v5e_row._predict_tree_cost(8, N, 1, 2, 256, hist_mode="full",
                                      **kw)
    assert sub > full
    # and the whole difference is the scatters less the half read saved
    from h2o3_tpu.models.tree.hist import hist_level_cost
    _, moved = hist_level_cost(N, 8, 257, 2, hist_mode="subtract")
    assert moved == 2.0 * N
    assert hist_level_cost(N, 8, 257, 1, hist_mode="subtract")[1] == 0.0
    assert hist_level_cost(N, 8, 257, 2, hist_mode="full")[1] == 0.0
    assert sub - full == pytest.approx(moved / 1.2e7, rel=0.02)


def test_grid_batch_reads_the_memory_budget_of_the_longer_row(v5e_row):
    """``resolve_grid_batch`` indexes the row, ``_predict_tree_cost``
    unpacks it: both follow the fourth figure.  A cohort whose resident
    state passes the v5e's 16 GB runs parallel, a small one batched."""
    assert v5e_row._peaks()[2] == 1.6e10
    kw = dict(kind="gbm", F=8, max_depth=5, nbins=64)
    assert v5e_row.resolve_grid_batch(N=4096, G=4, **kw) == "batched"
    assert v5e_row.resolve_grid_batch(N=2 ** 28, G=8, **kw) == "parallel"
