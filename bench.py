"""Benchmark suite: tpu_hist boosting (headline), DeepLearning, Rapids.

North star (BASELINE.json / SURVEY.md §6): the reference's XGBoost gpu_hist
benchmark gate trains 100 trees on airlines-10m in 22-52s on its GPU node
(compareBenchmarksStage.groovy:174-177) → ~1.9-4.5 trees/sec.  vs_baseline
divides our trees/sec by the best end of that interval (4.5), measured on an
airlines-shaped synthetic set: 10M rows, mixed numeric/categorical, binary
response, max_depth=6, nbins=256 — the same work shape gpu_hist does.

Secondary metrics (BASELINE.md):
 - DeepLearning samples/sec, MNIST shape (DeepLearning.java:648 rows/sec
   hook; no published reference value → no vs_baseline).
 - Rapids sort / merge wall-clock at 10M x 2 cols (reference Jenkins gate:
   sort 2-7 s, merge 4-10 s; vs_baseline divides the reference BEST time by
   ours, so >1 means faster than the reference's best).

One process, one chip.  Prints ONE JSON line: the headline record with an
"extra" dict carrying the secondary metrics and the device it ran on.
Without a ``tpu`` backend it exits non-zero and prints no record; a section
that raises ends the run the same way.  There is no CPU form of these
numbers (chip_smoke.py has a CPU rehearsal of the same path).
"""

import contextlib
import json
import os
import sys
import tempfile
import time

import numpy as np

from bench_util import sync_frame as _sync

REFERENCE_TREES_PER_SEC = 4.5     # best of the reference gpu_hist interval
REFERENCE_SORT_10M_S = 2.0        # best of Jenkins sort interval (10M rows)
REFERENCE_MERGE_10M_S = 4.0       # best of Jenkins merge interval (10M rows)
# H2O3_BENCH_ROWS/TREES: smoke-test overrides (CI runs the full shape)
N_ROWS = int(os.environ.get("H2O3_BENCH_ROWS", 10_000_000))
N_TREES = int(os.environ.get("H2O3_BENCH_TREES", 50))


def _ledger_totals():
    """(total_compiles, total_compile_s) from the xprof compile ledger."""
    from h2o3_tpu.runtime import xprof
    snap = xprof.ledger_snapshot()
    return snap["total_compiles"], snap["total_compile_s"]


@contextlib.contextmanager
def _compile_split(extra, section):
    """Split a bench section's wall clock into compile vs steady time via
    compile-ledger deltas, so the regression gate can tell "kernel got
    slower" from "compile got slower"."""
    c0, s0 = _ledger_totals()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        wall = time.perf_counter() - t0
        c1, s1 = _ledger_totals()
        if c1 > c0:
            extra[f"{section}_compile_s"] = round(s1 - s0, 3)
            extra[f"{section}_steady_s"] = round(
                max(wall - (s1 - s0), 0.0), 3)


def make_airlines_like(n, seed=0):
    rng = np.random.default_rng(seed)
    cols = {
        "year": rng.integers(1987, 2008, n).astype(np.float32),
        "month": rng.integers(1, 13, n).astype(np.float32),
        "day_of_week": rng.integers(1, 8, n).astype(np.float32),
        "crs_dep_time": rng.integers(0, 2400, n).astype(np.float32),
        "distance": np.abs(rng.normal(700, 500, n)).astype(np.float32),
        "carrier": rng.integers(0, 22, n),
        "origin": rng.integers(0, 300, n),
        "dest": rng.integers(0, 300, n),
    }
    logit = (0.002 * (cols["crs_dep_time"] / 100 - 12) ** 2
             - 0.0005 * cols["distance"] / 100
             + 0.2 * np.isin(cols["day_of_week"], (5, 7))
             + 0.1 * rng.normal(size=n))
    dep_delayed = rng.random(n) < 1 / (1 + np.exp(-logit))
    cols["dep_delayed_15min"] = np.where(dep_delayed, "YES", "NO").astype(object)
    types = {"carrier": "cat", "origin": "cat", "dest": "cat"}
    domains = {"carrier": [str(i) for i in range(22)],
               "origin": [str(i) for i in range(300)],
               "dest": [str(i) for i in range(300)]}
    return cols, types, domains


def bench_trees(Frame, T_CAT, XGBoost):
    cols, types, domains = make_airlines_like(N_ROWS)
    types = {k: (T_CAT if v == "cat" else v) for k, v in types.items()}
    fr = Frame.from_numpy(cols, types=types, domains=domains)
    config = dict(response_column="dep_delayed_15min", max_depth=6,
                  nbins=256, seed=1, score_tree_interval=10 ** 9)
    # warmup: one full scan chunk compiles the exact program the timed
    # run reuses
    XGBoost(ntrees=10, **config).train(fr)
    t0 = time.time()
    XGBoost(ntrees=N_TREES, **config).train(fr)
    dt = time.time() - t0
    del fr
    return N_TREES / dt


def bench_deeplearning(Frame, DeepLearning):
    """MNIST-shape MLP throughput (samples/sec/chip)."""
    n, d = min(60_000, max(N_ROWS, 4_096)), 784
    rng = np.random.default_rng(1)
    X = (rng.random((n, d)) * 255).astype(np.float32)
    y = rng.integers(0, 10, n)
    cols = {f"p{j}": X[:, j] for j in range(d)}
    cols["label"] = np.array([str(v) for v in y], dtype=object)
    fr = Frame.from_numpy(cols)
    # Large effective batch: the per-step FLOPs at batch 512 are ~3 us of
    # MXU — launch/stream overheads dominate and no batching knob in the
    # reference forbids it (its Hogwild default is minibatch=1 per THREAD).
    # bf16 matmuls + random-offset block sampling are the model defaults.
    kw = dict(response_column="label", hidden=(200, 200),
              mini_batch_size=8192, score_interval=1e9, stopping_rounds=0,
              seed=1)
    DeepLearning(epochs=2.0, **kw).train(fr)          # compile warmup
    epochs = 500.0 if N_ROWS >= 1_000_000 else 2.0    # smoke override
    t0 = time.time()
    DeepLearning(epochs=epochs, **kw).train(fr)
    dt = time.time() - t0
    del fr
    return epochs * n / dt


REFERENCE_GLM_HIGGS_S = 47.0      # best of the higgs GLM intervals
REFERENCE_GLM_HIGGS_ROWS = 11_000_000
# (COORDINATE_DESCENT 47-54 s, IRLSM 65-73 s —
#  compareBenchmarksStage.groovy:97-104; 11M rows x 28 numerics.
#  The conservative best-of-either-solver bound is scaled linearly to the
#  benched row count so reduced-shape smoke runs stay honest.)


def make_higgs_like(Frame, n, d=28, seed=3):
    """HIGGS shape: n rows x 28 dense numerics, binary response."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    beta = rng.normal(size=d) * 0.3
    logit = X @ beta - 0.2
    yy = rng.random(n) < 1 / (1 + np.exp(-logit))
    cols = {f"f{j}": X[:, j] for j in range(d)}
    cols["y"] = np.where(yy, "s", "b").astype(object)
    return Frame.from_numpy(cols)


def bench_glm(Frame, GLM, fr):
    """Higgs-shape binomial GLM (IRLSM, lambda=0): train-time seconds."""
    kw = dict(family="binomial", response_column="y", lambda_=0.0)
    GLM(**kw).train(fr)                               # warmup/compile
    t0 = time.time()
    GLM(**kw).train(fr)
    return time.time() - t0


def bench_glm_lambda_path(Frame, GLM, fr):
    """Higgs-shape GLM with a full regularization path (lambda_search).

    The reference GLM gate intervals (47-54 s COORDINATE_DESCENT on higgs,
    compareBenchmarksStage.groovy:97-104) are full solver runs including
    the lambda path — this line is the honest comparison the round-4
    lambda=0 line was not (VERDICT r4 weak #5).  100 lambdas, alpha=0.5,
    warm-started IRLSM down the path.
    """
    kw = dict(family="binomial", response_column="y", lambda_search=True,
              nlambdas=100, alpha=0.5)
    GLM(**kw).train(fr)                               # warmup/compile
    t0 = time.time()
    GLM(**kw).train(fr)
    return time.time() - t0


# --- GBM gate shapes (compareBenchmarksStage.groovy; 50-tree intervals) ---
REFERENCE_GBM_HIGGS_S = 72.0          # :45-52, 50 trees, 11M x 28 numerics
REFERENCE_GBM_HIGGS_ROWS = 11_000_000
REFERENCE_GBM_SPRINGLEAF_S = 52.0     # :35-43, 50 trees, 145k x ~1.9k wide
REFERENCE_GBM_SPRINGLEAF_ROWS = 145_000
REFERENCE_GBM_REDHAT_S = 21.0         # :25-33, 50 trees, 2.2M sparse/cat
REFERENCE_GBM_REDHAT_ROWS = 2_200_000
# The reference gate runs H2O GBM defaults: ntrees=50, max_depth=5,
# nbins=20 — the bench configs below pin the same work shape.
_GBM_GATE = dict(ntrees=50, max_depth=5, nbins=20, seed=1,
                 score_tree_interval=10 ** 9)


def _timed_gbm(GBM, fr, response, warmup_trees=10):
    cfg = dict(_GBM_GATE, response_column=response)
    GBM(**{**cfg, "ntrees": warmup_trees}).train(fr)  # compile
    t0 = time.time()
    GBM(**cfg).train(fr)
    return time.time() - t0


def make_springleaf_like(Frame, T_CAT, n, seed=5):
    """Springleaf shape: ~1.9k mostly-sparse columns, 145k rows.

    Mix modeled on the Kaggle set the gate uses: blocks of one-hot
    indicator columns (mutually exclusive — the EFB target), sparse count
    columns, dense numerics, and a few categoricals.
    """
    rng = np.random.default_rng(seed)
    cols, types, domains = {}, {}, {}
    # 60 one-hot groups x 20 indicators = 1200 exclusive sparse cols
    for g in range(60):
        which = rng.integers(0, 20, n)
        for j in range(20):
            cols[f"oh{g}_{j}"] = (which == j).astype(np.float32)
    # 400 sparse count columns (90% zero)
    nz = rng.random((n, 400)) < 0.1
    counts = rng.integers(1, 6, (n, 400)).astype(np.float32) * nz
    for j in range(400):
        cols[f"sp{j}"] = counts[:, j]
    # 280 dense numerics
    dense = rng.normal(size=(n, 280)).astype(np.float32)
    for j in range(280):
        cols[f"num{j}"] = dense[:, j]
    # 20 categoricals
    for j in range(20):
        card = int(rng.integers(3, 40))
        cols[f"cat{j}"] = rng.integers(0, card, n)
        types[f"cat{j}"] = "cat"
        domains[f"cat{j}"] = [str(i) for i in range(card)]
    logit = (0.8 * cols["oh0_3"] + 0.5 * (counts[:, 0] > 0)
             + 0.3 * dense[:, 0] - 0.5
             + 0.3 * rng.normal(size=n))
    cols["target"] = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)),
                              "1", "0").astype(object)
    return cols, types, domains


def make_redhat_like(Frame, T_CAT, n, seed=6):
    """Red Hat shape: 2.2M rows, ~38 boolean chars + high-card cats."""
    rng = np.random.default_rng(seed)
    cols, types, domains = {}, {}, {}
    for j in range(38):
        cols[f"char_{j}"] = (rng.random(n) < 0.3).astype(np.float32)
    for name, card in (("group", 7000), ("activity_category", 7),
                       ("char_a", 50), ("char_b", 100), ("char_c", 500)):
        cols[name] = rng.integers(0, card, n)
        types[name] = "cat"
        domains[name] = [str(i) for i in range(card)]
    cols["days"] = rng.integers(0, 800, n).astype(np.float32)
    logit = (0.4 * cols["char_0"] + 0.3 * cols["char_1"]
             - 0.2 * (cols["activity_category"] == 2)
             + 0.2 * rng.normal(size=n))
    cols["outcome"] = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)),
                               "1", "0").astype(object)
    return cols, types, domains


REFERENCE_PARSE_S = 4.9           # 580 MB / 5.8M rows on 5 nodes
REFERENCE_PARSE_MB = 580.0        # (h2o-docs/src/product/security.rst:1133)


def bench_parse(parse_csv, tmpdir):
    """Parse throughput: ~580 MB CSV -> Frame, single host.

    The reference number is a 5-node cluster parse of the same volume;
    vs_baseline divides its wall clock by ours (>1 = faster than the
    5-node reference).
    """
    import pyarrow as pa
    import pyarrow.csv as pacsv
    path = os.path.join(tmpdir, "parse_bench.csv")
    n = 5_800_000 if N_ROWS >= 1_000_000 else 100_000
    rng = np.random.default_rng(7)
    # float32 columns: realistic ~8-significant-digit cells (the
    # reference's 580 MB / 5.8M-row corpus is ~100 B/row)
    tbl = pa.table({
        **{f"n{j}": rng.normal(size=n).astype(np.float32)
           for j in range(8)},
        "i0": rng.integers(0, 100000, n),
        "c0": np.asarray(rng.integers(0, 50, n)).astype(str),
    })
    pacsv.write_csv(tbl, path)
    mb = os.path.getsize(path) / 1e6
    parse_csv(path)                                   # warmup
    t0 = time.time()
    fr = parse_csv(path)
    dt = time.time() - t0
    assert fr.nrows == n
    os.unlink(path)
    return dt, mb


def bench_rapids(Frame, sort, merge):
    n = N_ROWS
    rng = np.random.default_rng(2)
    big = Frame.from_numpy({
        "KEY": rng.integers(0, n, n).astype(np.float64),
        "X2": rng.random(n)})
    small = Frame.from_numpy({
        "KEY": rng.integers(0, n, n // 10).astype(np.float64),
        "Y2": rng.random(n // 10)})
    _sync(sort(big, "KEY"))                           # warmup/compile
    t0 = time.time()
    _sync(sort(big, "KEY"))
    dt_sort = time.time() - t0
    _sync(merge(big, small, "KEY", how="inner"))      # warmup/compile
    t0 = time.time()
    _sync(merge(big, small, "KEY", how="inner"))
    dt_merge = time.time() - t0
    return dt_sort, dt_merge




def main():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench: needs a tpu backend, found {dev.platform!r}; "
                 "no record written")
    import h2o3_tpu
    from h2o3_tpu import Frame
    from h2o3_tpu.frame.parse import parse_csv
    from h2o3_tpu.frame.vec import T_CAT
    from h2o3_tpu.models import GBM, GLM, XGBoost, DeepLearning
    from h2o3_tpu.rapids import sort, merge
    import bench_pieces

    h2o3_tpu.init()
    extra = {"platform": dev.platform, "device_kind": dev.device_kind,
             "device_count": len(jax.devices()),
             "rows": N_ROWS, "trees": N_TREES}
    with _compile_split(extra, "xgboost"):
        tps = bench_trees(Frame, T_CAT, XGBoost)

    sps = bench_deeplearning(Frame, DeepLearning)
    extra["deeplearning_samples_per_sec_mnist_shape"] = round(sps, 1)

    higgs_fr = make_higgs_like(Frame, N_ROWS)
    with _compile_split(extra, "glm"):
        dt_glm = bench_glm(Frame, GLM, higgs_fr)
    glm_base = REFERENCE_GLM_HIGGS_S * N_ROWS / REFERENCE_GLM_HIGGS_ROWS
    extra["glm_higgs_shape_sec"] = round(dt_glm, 3)
    extra["glm_vs_baseline"] = round(glm_base / dt_glm, 2)
    dt_path = bench_glm_lambda_path(Frame, GLM, higgs_fr)
    extra["glm_lambda_path_sec"] = round(dt_path, 3)
    extra["glm_lambda_path_vs_baseline"] = round(glm_base / dt_path, 2)

    with _compile_split(extra, "gbm_higgs"):
        dt = _timed_gbm(GBM, higgs_fr, "y")
    base = REFERENCE_GBM_HIGGS_S * min(N_ROWS, REFERENCE_GBM_HIGGS_ROWS) \
        / REFERENCE_GBM_HIGGS_ROWS
    extra["gbm_higgs_shape_sec"] = round(dt, 3)
    extra["gbm_higgs_vs_baseline"] = round(base / dt, 2)
    del higgs_fr

    for name, make, response, ref_s, ref_rows in (
            ("springleaf", make_springleaf_like, "target",
             REFERENCE_GBM_SPRINGLEAF_S, REFERENCE_GBM_SPRINGLEAF_ROWS),
            ("redhat", make_redhat_like, "outcome",
             REFERENCE_GBM_REDHAT_S, REFERENCE_GBM_REDHAT_ROWS)):
        n = min(ref_rows, N_ROWS)
        cols, ty, dom = make(Frame, T_CAT, n)
        fr = Frame.from_numpy(cols, types={k: T_CAT for k in ty},
                              domains=dom)
        dt = _timed_gbm(GBM, fr, response)
        extra[f"gbm_{name}_shape_sec"] = round(dt, 3)
        extra[f"gbm_{name}_vs_baseline"] = round(
            ref_s * n / ref_rows / dt, 2)
        del fr, cols

    with _compile_split(extra, "parse"):
        dt, mb = bench_parse(parse_csv, tempfile.gettempdir())
    extra["parse_csv_sec"] = round(dt, 3)
    extra["parse_csv_mb"] = round(mb, 1)
    extra["parse_mb_per_sec"] = round(mb / dt, 1)
    extra["parse_vs_baseline"] = round(
        (REFERENCE_PARSE_S * mb / REFERENCE_PARSE_MB) / dt, 2)

    dt_sort, dt_merge = bench_rapids(Frame, sort, merge)
    extra["rapids_sort_10m_sec"] = round(dt_sort, 3)
    extra["rapids_sort_vs_baseline"] = round(REFERENCE_SORT_10M_S / dt_sort,
                                             3)
    extra["rapids_merge_10m_sec"] = round(dt_merge, 3)
    extra["rapids_merge_vs_baseline"] = round(
        REFERENCE_MERGE_10M_S / dt_merge, 3)

    # online serving: packed fused-traversal latency/throughput through
    # the continuous micro-batcher
    sv = bench_pieces.serve_piece()
    extra["serve_p50_ms"] = round(sv["serve_p50_ms"], 3)
    extra["serve_p99_ms"] = round(sv["serve_p99_ms"], 3)
    extra["serve_qps"] = round(sv["serve_qps"], 1)
    extra["serve_packed_speedup_vs_numpy"] = round(sv["serve_speedup"], 2)

    # autotuner: cold/warm-cache "auto" knobs vs the best hand-set
    # configuration; the gate holds autotune_vs_best to an absolute 0.97
    # floor
    at = bench_pieces.autotune_piece()
    for k in ("autotune_hand_trees_per_sec", "autotune_cold_trees_per_sec",
              "autotune_warm_trees_per_sec"):
        extra[k] = round(at[k], 2)
    extra["autotune_vs_best"] = round(at["autotune_vs_best"], 3)

    # streaming ingest: end-to-end StreamingFrame + stream= training vs
    # parse-then-train; the gate holds stream_overlap_vs_baseline to an
    # absolute 1.176 floor (streamed <= 0.85x batch wall-clock)
    st = bench_pieces.stream_piece()
    for k in ("stream_batch_s", "stream_overlap_s",
              "stream_overlap_vs_baseline"):
        extra[k] = round(st[k], 3)

    # whole-tree scan fusion: kernel call sites per tree (O(1) in depth vs
    # one per level) and the deep-tree retrain-latency ratio
    ts = bench_pieces.treescan_piece()
    extra["treescan_launches_per_tree_scan"] = \
        ts["treescan_launches_per_tree_scan"]
    extra["treescan_launches_per_tree_level"] = \
        ts["treescan_launches_per_tree_level"]
    for k in ("treescan_cold_level_s", "treescan_cold_scan_s",
              "treescan_scan_vs_level_speedup"):
        extra[k] = round(ts[k], 3)
    for k in ("treescan_trees_per_sec_level", "treescan_trees_per_sec_scan"):
        extra[k] = round(ts[k], 2)

    # batched grid sweeps: one cohort program serves G members per chunk;
    # grid_batched_vs_sequential holds an absolute 4.0 floor in the gate
    gp = bench_pieces.grid_piece()
    extra["grid_launches_batched"] = gp["grid_launches_batched"]
    for k in ("grid_batched_vs_sequential", "grid_batched_wall_s",
              "grid_sequential_wall_s"):
        extra[k] = round(gp[k], 3)

    compiles, compile_s = _ledger_totals()
    extra["compiles_total"] = compiles
    extra["compile_s_total"] = round(compile_s, 3)
    print(json.dumps({
        "metric": "xgboost_trees_per_sec_airlines10m_shape",
        "value": round(tps, 3),
        "unit": "trees/sec",
        "vs_baseline": round(tps / REFERENCE_TREES_PER_SEC, 3),
        "extra": extra,
    }), flush=True)


if __name__ == "__main__":
    main()
