#!/usr/bin/env python3
"""Standing proof that h2o3_tpu's main path runs on one TPU chip.

One process drives ingest -> train -> score -> serve through the entry points
a user calls (``h2o3_tpu.init``, ``import_file``, ``Frame.from_numpy``, the
estimators' ``train``, ``model.predict``, ``model_performance``,
``serving.publish``, the REST server's realtime route), at the repository's
headline sizes, on data made from ``--seed``.  Every phase checks its own
output by the repository's own means (numpy references, two fits through
both values of a kernel knob compared tree by tree, the numpy
``ScoringModel``); a phase that raises or mismatches ends the run with a
non-zero exit code.  There is no retry and
no CPU form of the result.

    python chip_smoke.py                 # one chip; what the driver runs
    python chip_smoke.py --multichip     # four chips: the row-sharded mesh
                                         # against one chip, no other phase
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse
                                         # tiny sizes, any backend; checks the
                                         # control flow and never reports ok

Earlier lines are observations, one JSON object per phase, each naming the
platform, device kind and device count it came from.  The last line of
standard output, on success only, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Exit codes: 0 ok; 2 no accelerator (nothing printed); 3 rehearsal ran to its
end (no result printed); anything else: a phase failed.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import json
import os
import shutil
import sys
import tempfile
import time
import urllib.request

import jax
import numpy as np

# the airlines generator's logit is weak: its own AUC against the
# labels it draws is 0.534 (numpy, 2M rows), which is the ceiling a fit can
# approach; the floor asks the 10-tree fit to have found that signal
AUC_FLOOR_AIRLINES = 0.53
# higgs_arrays: a fitted logit scores 0.81-0.85 over seeds 0-2 (numpy, 1M
# rows); the generating coefficients are drawn from the seed
AUC_FLOOR_HIGGS_GLM = 0.75


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help="rows of the headline frames (default 10,000,000; "
                         "the ingest, parity and 7-class frames take a tenth)")
    ap.add_argument("--trees", type=int, default=None,
                    help="trees of the headline XGBoost fit (default 10)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip mesh against one chip")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever backend JAX finds; never "
                         "reports ok")
    args = ap.parse_args(argv)
    tiny = args.rehearse
    # 1024 pads to the same length on one device and on four, as 10M does
    args.rows = args.rows or (1_024 if tiny else 10_000_000)
    args.trees = args.trees or (2 if tiny else 10)
    # widths: the headline configuration, cut under --rehearse only
    args.tree_kw = dict(max_depth=2 if tiny else 6, nbins=16 if tiny else 256)
    args.dl_cols = 32 if tiny else 784
    return args


# ------------------------------------------------------------------ harness

class Smoke:
    """Phase bookkeeping: compile seconds from the ledger, steady seconds,
    peak device bytes, and the no-fallback check after every phase."""

    def __init__(self, dev, n_devices):
        self.tag = {"platform": dev.platform, "device_kind": dev.device_kind,
                    "devices": n_devices}
        self.phases = []

    def say(self, **fields):
        print(json.dumps({**fields, **self.tag}), flush=True)

    def peak_bytes(self):
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        return [s.get("peak_bytes_in_use") for s in stats]

    @staticmethod
    def _jax_compile_seconds():
        """(backend compile, trace + lowering) seconds of EVERY program JAX
        has compiled, ledgered or not (xprof's jax.monitoring listener); a
        persistent-cache hit counts its retrieval time as backend compile."""
        from h2o3_tpu.runtime import observability as obs

        def total(*events):         # over the series' other labels (fun, cache)
            return sum(s["s"] for s in obs.metrics_wire()
                       if s["n"] == "jax_compile_seconds"
                       and s["l"].get("event") in events)
        return (total("backend_compile_duration"),
                total("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration"))

    @contextlib.contextmanager
    def phase(self, name, **fields):
        from h2o3_tpu.runtime import observability as obs, xprof
        snap0 = xprof.ledger_snapshot()
        xla0, trace0 = self._jax_compile_seconds()
        t0 = time.perf_counter()
        out = {}
        yield out
        wall = time.perf_counter() - t0
        snap1 = xprof.ledger_snapshot()
        xla1, trace1 = self._jax_compile_seconds()
        compile_s = snap1["total_compile_s"] - snap0["total_compile_s"]
        falls = [e for e in obs.timeline_events(2000)
                 if e.get("kind") == "xprof_fallback"]
        if falls:
            raise AssertionError(f"{name}: xprof_fallback events {falls}")
        self.phases.append(name)
        # compile_s: the ledger's registered programs (trace + lower +
        # compile); steady_s is the rest of the wall, which still holds the
        # compiles of unregistered programs — xla_compile_s and
        # trace_lower_s count every program
        self.say(**{
            "phase": name, "wall_s": round(wall, 3),
            "compile_s": round(compile_s, 3),
            "steady_s": round(max(wall - compile_s, 0.0), 3),
            "compiles": snap1["total_compiles"] - snap0["total_compiles"],
            "xla_compile_s": round(xla1 - xla0, 3),
            "trace_lower_s": round(trace1 - trace0, 3),
            "peak_bytes_in_use": self.peak_bytes(), **fields, **out})


def make_multiclass(cols, k, rng):
    """A k-class response with signal in the airlines features."""
    score = (cols["crs_dep_time"] / 2400.0 + cols["distance"] / 3000.0
             + 0.3 * rng.normal(size=len(cols["distance"])))
    cuts = np.quantile(score, np.linspace(0, 1, k + 1)[1:-1])
    return np.array([f"c{i}" for i in range(k)], dtype=object)[
        np.searchsorted(cuts, score)]


def numpy_irls(X, y, iters=25):
    """Plain float32 IRLS for a binomial GLM with an intercept column."""
    X = np.concatenate([X, np.ones((len(X), 1), np.float32)], axis=1)
    beta = np.zeros(X.shape[1], np.float32)
    beta[-1] = np.log(y.mean() / (1 - y.mean()))
    for _ in range(iters):
        eta = X @ beta
        mu = 1 / (1 + np.exp(-eta))
        w = np.maximum(mu * (1 - mu), 1e-10).astype(np.float32)
        z = eta + (y - mu) / w
        gram = (X * w[:, None]).T @ X
        new = np.linalg.solve(gram.astype(np.float64),
                              ((X * w[:, None]).T @ z).astype(np.float64))
        done = np.max(np.abs(new - beta)) < 1e-6
        beta = new.astype(np.float32)
        if done:
            break
    return beta


def airlines_frame(n, seed):
    """The benchmark's airlines-shaped columns (categoricals and the label
    as integer codes beside their domains) and their frame."""
    from benchmark.datagen import airlines_like
    from h2o3_tpu import Frame
    from h2o3_tpu.frame.vec import T_CAT
    cols, domains, response = airlines_like.generate(n, seed)
    fr = Frame.from_numpy(cols, types={k: T_CAT for k in domains},
                          domains=domains)
    return cols, fr, [k for k in domains if k != response], domains


def higgs_arrays(n, seed, d=28):
    rng = np.random.default_rng(seed + 3)
    beta = rng.normal(size=d) * 0.3           # first: the same for every n
    X = rng.standard_normal(size=(n, d), dtype=np.float32)
    y = rng.random(n) < 1 / (1 + np.exp(-(X @ beta - 0.2)))
    return X, y


def higgs_frame(X, y):
    from h2o3_tpu import Frame
    cols = {f"f{j}": X[:, j] for j in range(X.shape[1])}
    cols["y"] = np.where(y, "s", "b").astype(object)
    return Frame.from_numpy(cols)


def tree_records(model):
    """Host copy of a single-class tree model's stacked trees."""
    st = model.output["stacked"]
    levels, values = jax.device_get((list(st.levels), st.values))
    return [tuple(np.asarray(a) for a in lv) for lv in levels], \
        np.asarray(values)


def compare_trees(a, b):
    """Two ``tree_records`` of the same shape, tree by tree: which nodes
    split must agree everywhere and how they split wherever they do (a
    node that does not split holds a candidate nothing reads); the leaves
    may differ by float32 rounding, which the caller bounds."""
    (lv_a, v_a), (lv_b, v_b) = a, b
    differ = []                          # (level, field, nodes that differ)
    for d, (x, y) in enumerate(zip(lv_a, lv_b)):
        valid = x[3] & y[3]
        for name, p, q in (("valid", x[3], y[3]),
                           ("feat", x[0][valid], y[0][valid]),
                           ("na_left", x[2][valid], y[2][valid]),
                           ("thr", x[1][valid], y[1][valid])):
            if not np.array_equal(p, q):
                differ.append((d, name, int(np.sum(p != q))))
    return {"trees": int(v_a.shape[0]), "structure_differs": differ,
            "leaf_max_abs_diff": float(np.abs(v_a - v_b).max())}


# --------------------------------------------------------------- one chip

def run_one_chip(args, S):
    import h2o3_tpu
    from h2o3_tpu import Frame, native, serving
    from h2o3_tpu.api.server import start_server
    from h2o3_tpu.export import mojo
    from h2o3_tpu.export.scoring import ScoringModel
    from h2o3_tpu.frame import parse
    from h2o3_tpu.frame.vec import T_CAT
    from h2o3_tpu.models import GBM, GLM, XGBoost, DeepLearning
    from h2o3_tpu.runtime import autotune, xprof
    import jax.numpy as jnp

    rng = np.random.default_rng(args.seed)
    n_small = max(args.rows // 10, 1_000)
    scratch = tempfile.mkdtemp(prefix="chip_smoke_")

    # -- sync semantics: one large matmul timed to block_until_ready and to
    # a one-element device->host fetch; they must tell the same time
    with S.phase("sync") as out:
        m = 1024 if args.rehearse else 8192
        a = jnp.ones((m, m), jnp.bfloat16)

        @jax.jit
        def chain(a):
            return jax.lax.fori_loop(
                0, 8, lambda _, x: (x @ a) * jnp.bfloat16(1.0 / m), a)

        def fetch_one(x):
            return np.asarray(jax.device_get(jnp.ravel(x)[:1]))
        fetch_one(jax.block_until_ready(chain(a)))    # compile both ways
        t0 = time.perf_counter()
        jax.block_until_ready(chain(a))
        t_block = time.perf_counter() - t0
        t0 = time.perf_counter()
        fetch_one(chain(a))
        t_fetch = time.perf_counter() - t0
        out.update(matmul_m=m, block_until_ready_s=round(t_block, 5),
                   one_element_fetch_s=round(t_fetch, 5))
        flops = 8 * 2 * m ** 3
        out["block_until_ready_tflops"] = round(flops / t_block / 1e12, 1)
        assert t_block > 0.5 * t_fetch, \
            f"block_until_ready returned early: {t_block} vs {t_fetch}"

    # -- ingest: CSV with numeric, categorical and NA cells, native tokenizer
    with S.phase("ingest", rows=n_small) as out:
        import pyarrow as pa
        import pyarrow.csv as pacsv
        assert native.load() is not None, \
            "native tokenizer did not build from h2o3_tpu/native/fastcsv.cpp"
        num = rng.normal(size=(n_small, 4)).astype(np.float32)
        na = rng.random((n_small, 4)) < 0.02
        cat = np.array(["AA", "DL", "UA", "WN", "B6"])[
            rng.integers(0, 5, n_small)]
        cat_na = rng.random(n_small) < 0.03
        path = os.path.join(scratch, "ingest.csv")
        pacsv.write_csv(pa.table({
            **{f"x{j}": pa.array(num[:, j], mask=na[:, j]) for j in range(4)},
            "k": pa.array(rng.integers(0, 1000, n_small)),
            "carrier": pa.array(cat, mask=cat_na),
            "label": pa.array(np.where(num[:, 0] > 0, "Y", "N")),
        }), path)
        fr = h2o3_tpu.import_file(path, destination_frame="smoke_ingest")
        assert parse.last_parse_stats, "import_file left the native path"
        assert fr.shape == (n_small, 7), fr.shape
        types = fr.types()
        assert [types[c] for c in ("x0", "k", "carrier", "label")] == \
            ["num", "num", "cat", "cat"], types
        x0 = np.where(na[:, 0], np.nan, num[:, 0])
        r = fr.vec("x0").rollups()
        assert r.nmissing == int(na[:, 0].sum()), (r.nmissing, na[:, 0].sum())
        assert abs(r.mean - np.nanmean(x0)) < 1e-4, (r.mean, np.nanmean(x0))
        assert fr.vec("carrier").nmissing() == int(cat_na.sum())
        out.update(csv_mb=round(os.path.getsize(path) / 1e6, 1),
                   parse_stats=dict(parse.last_parse_stats))
        h2o3_tpu.remove("smoke_ingest")
        del fr

    # -- headline: XGBoost on the airlines shape, knobs at "auto"
    with S.phase("frame_airlines", rows=args.rows):
        cols, fr_air, cat_cols, domains = airlines_frame(args.rows, args.seed)
    with S.phase("train_xgboost", rows=args.rows, trees=args.trees) as out:
        xgb = XGBoost(response_column="dep_delayed_15min", ntrees=args.trees,
                      seed=1, **args.tree_kw).train(fr_air)
        auc = float(xgb.training_metrics.auc)
        scan = xprof.ledger_snapshot()["programs"]["tree_scan"]
        out.update(
            auc=round(auc, 4),
            resolved={k: xgb.output.get(k) for k in (
                "hist_layout", "tree_program", "effective_max_depth")},
            autotune=[{k: d[k] for k in ("signature", "choice", "source")}
                      for d in autotune.decision_table()["decisions"]],
            tree_scan_compiles=scan["reasons"])
        assert xgb.output["ntrees_trained"] == args.trees
        assert args.rehearse or auc > AUC_FLOOR_AIRLINES, auc
        assert scan["reasons"].get("shape_change", 0) == 0, scan

    # -- the chip's kernels against the repo's oracles, on the chip: two
    # fits on the same data through both values of one knob, the other
    # knobs pinned (so the tuner decides nothing), compared tree by tree
    cols_s = {k: v[:n_small] for k, v in cols.items()}
    fr_s = Frame.from_numpy(cols_s, types={k: T_CAT for k in domains},
                            domains=domains)
    Xh, yh = higgs_arrays(args.rows, args.seed)
    fr_hs = higgs_frame(Xh[:n_small], yh[:n_small])
    pins = dict(hist_mode="subtract", split_mode="fused", hist_layout="dense",
                tree_program="level")
    for knob, pair, frame, resp, kernel in (
            ("hist_mode", ("subtract", "full"), fr_s, "dep_delayed_15min", ""),
            ("split_mode", ("fused", "separate"), fr_s, "dep_delayed_15min",
             ""),
            # both programs under both histogram kernels: the higgs frame's
            # columns all use every bin (uniform kernels), the airlines
            # frame packs (the variable-bin kernel, in the scan since PR 38)
            ("tree_program", ("scan", "level"), fr_hs, "y", ""),
            ("tree_program", ("scan", "level"), fr_s, "dep_delayed_15min",
             "_varbin")):
        with S.phase(f"parity_{knob}{kernel}", rows=n_small) as out:
            fits = [XGBoost(response_column=resp, ntrees=2, seed=1,
                            **{**pins, knob: value},
                            **args.tree_kw).train(frame) for value in pair]
            out.update(fits=list(pair),
                       auc=[round(float(m.training_metrics.auc), 4)
                            for m in fits],
                       tree_program=[m.output["tree_program"] for m in fits],
                       **compare_trees(*map(tree_records, fits)))
            assert not out["structure_differs"], out
            assert out["leaf_max_abs_diff"] < 1e-4, out
            if knob == "tree_program":
                assert out["tree_program"] == list(pair), out
    del fr_hs

    # -- K class trees per round as one batched build
    with S.phase("train_gbm_7class", rows=n_small) as out:
        cols7 = {k: v for k, v in cols_s.items() if k != "dep_delayed_15min"}
        cols7["cls"] = make_multiclass(cols_s, 7, rng)
        fr7 = Frame.from_numpy(
            cols7, types={k: T_CAT for k in cat_cols},
            domains={k: domains[k] for k in cat_cols})
        m7 = GBM(response_column="cls", ntrees=3, seed=1,
                 **args.tree_kw).train(fr7)
        ll = float(m7.training_metrics.logloss)
        out.update(logloss=round(ll, 4), nclass_trees=m7.output["nclass_trees"])
        assert m7.output["nclass_trees"] == 7
        assert np.isfinite(ll) and ll < np.log(7.0), ll
        del fr7, fr_s

    # -- dense algebra: binomial GLM (IRLSM) at the Higgs shape
    with S.phase("frame_higgs", rows=args.rows):
        fr_h = higgs_frame(Xh, yh)
    with S.phase("train_glm", rows=args.rows, cols=Xh.shape[1]) as out:
        kw = dict(family="binomial", response_column="y", lambda_=0.0)
        glm = GLM(**kw).train(fr_h)
        auc = float(glm.training_metrics.auc)
        n_ref = min(100_000, args.rows)
        ref = numpy_irls(Xh[:n_ref], yh[:n_ref].astype(np.float32))
        glm_s = GLM(**kw).train(higgs_frame(Xh[:n_ref], yh[:n_ref]))
        names = [f"f{j}" for j in range(Xh.shape[1])] + ["Intercept"]
        got_s = np.array([glm_s.coef[k] for k in names])
        got = np.array([glm.coef[k] for k in names])
        out.update(auc=round(auc, 4),
                   slice_max_abs_diff_vs_numpy=float(np.abs(got_s - ref).max()),
                   full_max_abs_diff_vs_slice=float(np.abs(got - got_s).max()))
        assert np.abs(got_s - ref).max() < 2e-3, (got_s, ref)
        assert np.isfinite(got).all(), got
        assert args.rehearse or auc > AUC_FLOOR_HIGGS_GLM, auc
        # the slice's own sampling error is ~0.01 per coefficient
        assert np.abs(got - got_s).max() < 0.05, (got, got_s)
    del fr_h, Xh, yh

    # -- DeepLearning at the MNIST shape, one epoch
    n_dl = min(60_000, args.rows)
    with S.phase("train_deeplearning", rows=n_dl, cols=args.dl_cols) as out:
        X = (rng.random((n_dl, args.dl_cols)) * 255).astype(np.float32)
        lab = np.argmax(X[:, :10] + 32 * rng.normal(size=(n_dl, 10)), axis=1)
        dcols = {f"p{j}": X[:, j] for j in range(args.dl_cols)}
        dcols["label"] = np.array([str(v) for v in lab], dtype=object)
        dl = DeepLearning(response_column="label", epochs=1.0,
                          seed=1).train(Frame.from_numpy(dcols))
        ll = float(dl.training_metrics.logloss)
        out.update(logloss=round(ll, 4))
        assert np.isfinite(ll), ll
        del X, dcols

    # -- score: predict the whole frame; a slice against the numpy scorer
    with S.phase("score", rows=args.rows) as out:
        pred = xgb.predict(fr_air)
        p1 = pred.vec("YES").to_numpy()
        perf = xgb.model_performance(fr_air)
        n_ref = min(10_000, args.rows)
        sm = ScoringModel(*mojo._extract(xgb))
        feats = [c for c in cols if c != "dep_delayed_15min"]
        ref = sm.predict({c: (cols[c][:n_ref].astype(str) if c in cat_cols
                              else cols[c][:n_ref]) for c in feats})
        diff = float(np.abs(p1[:n_ref] - ref["probabilities"][:, 1]).max())
        out.update(slice_rows=n_ref, max_abs_diff_vs_numpy=diff,
                   auc=round(float(perf.auc), 4))
        assert p1.shape == (args.rows,) and np.isfinite(p1).all()
        assert diff < 1e-5, diff
        assert abs(float(perf.auc) - float(xgb.training_metrics.auc)) < 1e-4, \
            (perf.auc, xgb.training_metrics.auc)

    # -- serve: publish, REST round trip in this process, /metrics readback
    with S.phase("serve") as out:
        entry = serving.publish(xgb.key, xgb)
        server = start_server(port=0)
        try:
            def post(payload):
                req = urllib.request.Request(
                    f"{server.url}/3/Predictions/realtime/{xgb.key}",
                    data=json.dumps(payload).encode(), method="POST",
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=120) as r:
                    return json.loads(r.read())

            def row(i):
                return {c: (str(cols[c][i]) if c in cat_cols
                            else float(cols[c][i])) for c in feats}
            n_big = min(1_000, args.rows)
            for i in range(3):
                got = post({"row": row(i)})["predictions"]
                assert abs(got[0]["probabilities"][1] - p1[i]) < 1e-5, \
                    (i, got, p1[i])
            big = post({"rows": [row(i) for i in range(n_big)]})
            pb = np.array([p["probabilities"][1]
                           for p in big["predictions"]])
            assert np.abs(pb - p1[:n_big]).max() < 1e-5
            with urllib.request.urlopen(f"{server.url}/metrics",
                                        timeout=30) as r:
                lat = [ln for ln in r.read().decode().splitlines()
                       if ln.startswith("serve_latency_seconds_sum")
                       or ln.startswith("serve_latency_seconds_count")]
            assert {ph for ph in ("queue", "device", "total")
                    if any(f'phase="{ph}"' in ln for ln in lat)} == \
                {"queue", "device", "total"}, lat
            out.update(impl=entry.scorer.impl,
                       warmup_s=round(entry.warmup_s, 3),
                       max_batch=entry.batcher.max_batch,
                       serve_latency_seconds=lat)
        finally:
            serving.shutdown_all()
            server.stop()
    shutil.rmtree(scratch, ignore_errors=True)


# -------------------------------------------------------------- four chips

def run_multichip(args, S):
    """The row-sharded mesh over four chips against one chip, same process:
    the airlines XGBoost fit and a GLM fit on each, then compared."""
    import h2o3_tpu
    from h2o3_tpu.models import GLM, XGBoost
    from h2o3_tpu.models.tree.binning import fit_bins
    from h2o3_tpu.models.tree.hist import make_hist_fn
    import jax.numpy as jnp

    devices = jax.devices()
    assert len(devices) == 4, f"--multichip needs 4 devices, found {devices}"
    Xh, yh = higgs_arrays(args.rows, args.seed)
    results, hists = {}, {}
    for label, devs in (("4chip", devices), ("1chip", devices[:1])):
        cl = h2o3_tpu.init(devices=devs, hosts=1)
        assert cl.n_row_shards == len(devs), cl.mesh
        with S.phase(f"{label}_frames", rows=args.rows) as out:
            cols, fr_air, _, _ = airlines_frame(args.rows, args.seed)
            fr_h = higgs_frame(Xh, yh)
            feats = [c for c in cols if c != "dep_delayed_15min"]
            codes = fit_bins(fr_air, feats, nbins=args.tree_kw["nbins"],
                             seed=1).codes
            on = {s.device for s in codes.addressable_shards}
            out.update(code_matrix_shape=list(codes.shape),
                       code_shards=len(codes.addressable_shards),
                       shard_devices=sorted(str(d) for d in on))
            assert on == set(devs) and \
                len(codes.addressable_shards) == len(devs), on
            # root histogram of the real code matrix, reduced both ways
            # (reduce_mode="check" raises if flat and staged psum differ)
            dist = fr_air.vec("distance")
            w = dist.valid_mask().astype(jnp.float32)
            g = jnp.nan_to_num(dist.data) * w / 1000.0
            hists[label] = np.asarray(make_hist_fn(
                1, len(feats), args.tree_kw["nbins"] + 1, codes.shape[1],
                reduce_mode="check")(
                    codes, jnp.zeros(codes.shape[1], jnp.int32), g, w, w))
            del codes
        with S.phase(f"{label}_train", rows=args.rows) as out:
            xgb = XGBoost(response_column="dep_delayed_15min",
                          ntrees=args.trees, seed=1,
                          **args.tree_kw).train(fr_air)
            glm = GLM(family="binomial", response_column="y",
                      lambda_=0.0).train(fr_h)
            out.update(auc=round(float(xgb.training_metrics.auc), 4),
                       tree_program=xgb.output.get("tree_program"),
                       bytes_in_use=[(d.memory_stats() or {}).get(
                           "bytes_in_use") for d in devs])
        results[label] = (tree_records(xgb), dict(glm.coef))
        del fr_air, fr_h, xgb, glm

    with S.phase("compare") as out:
        trees4, coef4 = results["4chip"]
        trees1, coef1 = results["1chip"]
        h4, h1 = hists["4chip"], hists["1chip"]
        out.update(
            **compare_trees(trees4, trees1),
            glm_coef_max_abs_diff=max(abs(coef4[k] - coef1[k])
                                      for k in coef4),
            root_hist_rel_diff=float(np.abs(h4 - h1).max()
                                     / np.abs(h1).max()),
            root_hist_counts_equal=bool(np.array_equal(h4[2], h1[2])))
        S.say(phase="compare_detail", **out)     # also when it fails below
        assert not out["structure_differs"], out["structure_differs"]
        assert out["root_hist_counts_equal"], "row counts per bin differ"
        # f32 tolerance: the same terms summed in another order
        assert out["leaf_max_abs_diff"] < 1e-4, out
        assert out["glm_coef_max_abs_diff"] < 1e-4, out
        assert out["root_hist_rel_diff"] < 1e-4, out


# --------------------------------------------------------------------- main

def main(argv=None) -> int:
    args = parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform == "cpu" and not args.rehearse:
        print("chip_smoke: JAX found no accelerator", file=sys.stderr)
        return 2
    if args.rehearse:
        # XLA:CPU reloads of cached executables are not safe everywhere;
        # a rehearsal neither needs nor warms the cache
        jax.config.update("jax_enable_compilation_cache", False)
    import h2o3_tpu
    from h2o3_tpu.runtime import config

    n_dev = 4 if args.multichip else 1
    cl = h2o3_tpu.init(devices=jax.devices()[:n_dev], hosts=1)
    S = Smoke(dev, len(jax.devices()))
    platform = cl.describe()["platform"]
    if not args.rehearse and not (dev.platform == platform == "tpu"):
        raise AssertionError(
            f"expected a tpu backend and mesh, found {dev.platform!r} and "
            f"{platform!r}")
    S.say(phase="init", mesh=dict(cl.mesh.shape), jax=jax.__version__,
          jaxlib=importlib.metadata.version("jaxlib"),
          libtpu=importlib.metadata.version("libtpu"),
          autotune=config.config().autotune,
          compile_cache_dir=os.environ.get("JAX_COMPILATION_CACHE_DIR")
          or jax.config.jax_compilation_cache_dir,
          seed=args.seed, rows=args.rows, trees=args.trees,
          rehearsal=args.rehearse)

    (run_multichip if args.multichip else run_one_chip)(args, S)

    h2o3_tpu.shutdown()
    if args.rehearse:
        print(f"chip_smoke: rehearsal ran {len(S.phases)} phases to the end; "
              "a rehearsal reports no result", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
