"""Online scoring plane: bitpacked traversal + micro-batcher + REST.

Parity strategy mirrors test_mojo: train real models in the cluster,
extract the portable arrays, and require the packed device program to
reproduce the numpy ``ScoringModel`` scores (which test_mojo already
pins to in-cluster ``Model.predict``) — including NA rows, categorical
splits, multinomial class groups and the isolation-forest path.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import Frame
from h2o3_tpu.export import mojo
from h2o3_tpu.export.scoring import ScoringModel
from h2o3_tpu.models import GBM, DRF, XGBoost, IsolationForest
from h2o3_tpu.serving import pack
from h2o3_tpu.serving.batcher import MicroBatcher
from h2o3_tpu.serving.kernel import PackedScorer


# ------------------------------------------------------------- pack unit

def _random_heap_group(rng, T, depth, F, full=False):
    """Synthetic heap-layout trees in the mojo export format."""
    arrays = {"values": rng.normal(size=(T, 2 ** depth))
              .astype(np.float32)}
    for d in range(depth):
        w = 2 ** d
        arrays[f"feat_{d}"] = rng.integers(0, F, (T, w))
        arrays[f"thr_{d}"] = rng.normal(size=(T, w)).astype(np.float32)
        arrays[f"na_left_{d}"] = rng.integers(0, 2, (T, w)).astype(bool)
        arrays[f"valid_{d}"] = (np.ones((T, w), dtype=bool) if full
                                else rng.random((T, w)) < 0.8)
    return arrays


def _heap_walk(arrays, depth, X):
    """Brute-force per-row heap descent (the pre-PR-11 semantics)."""
    n, T = X.shape[0], arrays["values"].shape[0]
    out = np.zeros((n, T), dtype=np.float32)
    for r in range(n):
        for t in range(T):
            i = 0
            for d in range(depth):
                if not arrays[f"valid_{d}"][t, i]:
                    break
                x = X[r, arrays[f"feat_{d}"][t, i]]
                if np.isnan(x):
                    right = not arrays[f"na_left_{d}"][t, i]
                else:
                    right = x >= arrays[f"thr_{d}"][t, i]
                i = 2 * i + int(right)
            else:
                d = depth
            out[r, t] = arrays["values"][t, i << (depth - d)]
    return out


@pytest.mark.parametrize("depth,full", [(0, True), (1, True), (3, False),
                                        (6, False), (9, False)])
def test_pack_traverse_matches_heap_walk(rng, depth, full):
    T, F, n = 7, 5, 40
    arrays = _random_heap_group(rng, T, depth, F, full=full)
    X = rng.normal(size=(n, F)).astype(np.float32)
    X[rng.random((n, F)) < 0.15] = np.nan
    i32, f32, roots = pack.pack_group(arrays, depth)
    got = pack.traverse(i32, f32, roots, X, depth)
    np.testing.assert_array_equal(got, _heap_walk(arrays, depth, X))


def test_pack_layout_invariants(rng):
    arrays = _random_heap_group(rng, 4, 5, 8)
    i32, f32, roots = pack.pack_group(arrays, 5)
    assert i32.dtype == np.int32 and f32.dtype == np.float32
    assert roots.shape == (4,) and roots[0] == 0
    leaf = (i32 >> pack.LEAF_BIT) & 1
    # every tree ends in at least one leaf; both children stay in-bounds
    delta = (i32.astype(np.int64) >> pack.DELTA_SHIFT) & pack.DELTA_MASK
    child = np.arange(i32.shape[0]) + delta
    assert (child[leaf == 0] + 1 < i32.shape[0]).all()
    assert (delta[leaf == 0] > 0).all()
    assert leaf.sum() >= 4


def test_pack_feature_id_overflow_rejected(rng):
    arrays = _random_heap_group(rng, 1, 1, 2, full=True)
    arrays["feat_0"] = np.full((1, 1), pack.MAX_FEATURES)
    with pytest.raises(ValueError, match="feature ids"):
        pack.pack_group(arrays, 1)


# -------------------------------------------------- trained-model parity

def _frames(rng, n=600):
    X = rng.normal(size=(n, 3))
    cat = np.array(["u", "v", "w"], dtype=object)[rng.integers(0, 3, n)]
    y_num = X @ [1.0, -2.0, 0.5] + (cat == "v") * 1.5 \
        + 0.1 * rng.normal(size=n)
    y_bin = np.where(y_num > 0, "yes", "no").astype(object)
    cols = {"x0": X[:, 0], "x1": X[:, 1], "x2": X[:, 2], "c": cat}
    return (Frame.from_numpy({**cols, "y": y_num}),
            Frame.from_numpy({**cols, "y": y_bin}), dict(cols))


def _scorer(model):
    meta, arrays = mojo._extract(model)
    return PackedScorer(ScoringModel(meta, arrays))


def _na_rows(data, rng, k=40):
    """Row dicts from the training columns, with missing cells."""
    n = len(next(iter(data.values())))
    rows = []
    for i in rng.integers(0, n, k):
        row = {c: (v[i].item() if hasattr(v[i], "item") else v[i])
               for c, v in data.items()}
        drop = rng.choice(list(data), rng.integers(0, 3), replace=False)
        for c in drop:
            row.pop(c)
        rows.append(row)
    return rows


def _cols_from_rows(rows, names):
    """Row dicts -> column dict the way featurize fills missing cells."""
    cols = {}
    for c in names:
        vals = [r.get(c) for r in rows]
        if any(isinstance(v, str) for v in vals):
            cols[c] = np.asarray(["" if v is None else v for v in vals],
                                 dtype=object)
        else:
            cols[c] = np.asarray([np.nan if v is None else v for v in vals],
                                 dtype=float)
    return cols


def _assert_parity(model, data, rng, classifier=True):
    ps = _scorer(model)
    rows = _na_rows(data, rng)
    X = ps.featurize(rows)
    # check mode raises on any packed-vs-ref divergence
    probs = ps.score(X, score_mode="check")
    # and the ref path IS the deployed numpy scorer
    sm_out = ps.ref.predict(_cols_from_rows(rows, list(data)))
    out = ps.predict_rows(rows)
    if classifier:
        np.testing.assert_allclose(probs, sm_out["probabilities"],
                                   rtol=1e-4, atol=1e-5)
        assert (out["predict"] == sm_out["predict"]).all()
    else:
        np.testing.assert_allclose(out["predict"], sm_out["predict"],
                                   rtol=1e-4, atol=1e-5)
    return ps


def test_packed_parity_gbm_binomial(cl, rng):
    _, fr_bin, data = _frames(rng)
    m = GBM(response_column="y", ntrees=8, seed=1).train(fr_bin)
    ps = _assert_parity(m, data, rng)
    assert ps.binomial and ps.n_class == 1


def test_packed_parity_gbm_regression(cl, rng):
    fr_num, _, data = _frames(rng)
    m = GBM(response_column="y", ntrees=6, seed=1).train(fr_num)
    _assert_parity(m, data, rng, classifier=False)


def test_packed_parity_gbm_multinomial(cl, rng):
    n = 400
    X = rng.normal(size=(n, 3))
    cls = np.argmax(X + 0.2 * rng.normal(size=(n, 3)), axis=1)
    data = {f"x{j}": X[:, j] for j in range(3)}
    fr = Frame.from_numpy({**data, "y": np.array(
        ["a", "b", "c"], dtype=object)[cls]})
    m = GBM(response_column="y", ntrees=5, seed=1).train(fr)
    ps = _assert_parity(m, data, rng)
    assert ps.n_class == 3


def test_packed_parity_drf(cl, rng):
    _, fr_bin, data = _frames(rng)
    m = DRF(response_column="y", ntrees=8, seed=1, max_depth=6).train(fr_bin)
    ps = _assert_parity(m, data, rng)
    assert ps.avg          # DRF averages, it does not boost


def test_packed_parity_xgboost(cl, rng):
    _, fr_bin, data = _frames(rng)
    m = XGBoost(response_column="y", ntrees=8, seed=1).train(fr_bin)
    _assert_parity(m, data, rng)


def test_packed_parity_isolation_forest(cl, rng):
    n = 400
    data = {"a": rng.normal(size=n), "b": rng.normal(size=n)}
    m = IsolationForest(ntrees=10, seed=2).train(Frame.from_numpy(data))
    ps = _assert_parity(m, data, rng, classifier=False)
    assert ps.family == "isolation"


def test_score_mode_knob_and_ref(cl, rng):
    _, fr_bin, data = _frames(rng)
    m = GBM(response_column="y", ntrees=5, seed=1).train(fr_bin)
    ps = _scorer(m)
    X = ps.featurize(_na_rows(data, rng, k=16))
    np.testing.assert_allclose(ps.score(X, score_mode="packed"),
                               ps.score(X, score_mode="ref"),
                               rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="score_mode"):
        ps.score(X, score_mode="bogus")


def test_pallas_interpret_impl_matches(cl, rng):
    _, fr_bin, data = _frames(rng)
    m = GBM(response_column="y", ntrees=5, seed=1).train(fr_bin)
    meta, arrays = mojo._extract(m)
    sm = ScoringModel(meta, arrays)
    xla = PackedScorer(sm, impl="xla")
    pli = PackedScorer(sm, impl="pallas_interpret")
    X = xla.featurize(_na_rows(data, rng, k=32))
    np.testing.assert_allclose(pli.score(X), xla.score(X),
                               rtol=1e-5, atol=1e-6)


def test_explicit_pallas_never_degrades_to_interpret(cl, rng):
    """impl="pallas" means the Mosaic kernel: on a backend where it cannot
    run it raises (no silent interpret mode); the interpret-mode twin has
    its own name."""
    _, fr_bin, data = _frames(rng)
    m = GBM(response_column="y", ntrees=3, seed=1).train(fr_bin)
    ps = PackedScorer(ScoringModel(*mojo._extract(m)), impl="pallas")
    X = ps.featurize(_na_rows(data, rng, k=8))
    with pytest.raises(ValueError, match="needs a tpu backend"):
        ps.score(X)


def test_auto_impl_records_its_choice(cl, rng, monkeypatch):
    """impl="auto" resolves to the XLA traversal on every backend and the
    decision shows in the autotuner's table and decision counter."""
    from h2o3_tpu.runtime import autotune, config, observability as obs
    monkeypatch.setenv("H2O3_TPU_AUTOTUNE", "on")
    config.reload()
    autotune.reset()
    try:
        _, fr_bin, data = _frames(rng)
        m = GBM(response_column="y", ntrees=3, seed=1).train(fr_bin)
        ps = PackedScorer(ScoringModel(*mojo._extract(m)))
        assert ps.impl == "auto"
        X = ps.featurize(_na_rows(data, rng, k=8))
        np.testing.assert_allclose(ps.score(X),
                                   ps.score(X, score_mode="ref"),
                                   rtol=1e-4, atol=1e-5)
        rows = [d for d in autotune.decision_table()["decisions"]
                if d["signature"].startswith("serve:")]
        assert rows and all(d["choice"] == "xla" for d in rows)
        assert obs.counter("autotune_decisions_total", knob="serve_impl",
                           choice="xla", source="model").value >= 1
    finally:
        monkeypatch.undo()
        config.reload()
        autotune.reset()


def test_scoring_model_iterative_traverse(cl, rng):
    """export/scoring.py now routes _traverse through the packed walk;
    the portable predict must keep matching in-cluster predict."""
    _, fr_bin, data = _frames(rng)
    m = GBM(response_column="y", ntrees=8, seed=1).train(fr_bin)
    meta, arrays = mojo._extract(m)
    sm = ScoringModel(meta, arrays)
    out = sm.predict(data)
    pred = m.predict(fr_bin)
    probs = np.stack([v.to_numpy() for v in pred.vecs[1:]], axis=1)
    np.testing.assert_allclose(out["probabilities"], probs, atol=2e-4)
    assert "_pack_cache" in sm.__dict__      # iterative walk engaged


# --------------------------------------------------------- micro-batcher

def test_microbatcher_concurrent_demux(cl, rng):
    _, fr_bin, data = _frames(rng)
    m = GBM(response_column="y", ntrees=5, seed=1).train(fr_bin)
    ps = _scorer(m)
    mb = MicroBatcher(ps, max_batch=32, tick_ms=2.0, queue_depth=4096)
    try:
        assert mb.warmup() > 0
        X = ps.featurize(_na_rows(data, rng, k=64))
        want = ps.score(X)
        outs = [None] * 16
        errs = []

        def client(i):
            lo, hi = 4 * i, 4 * i + 4
            try:
                outs[i] = mb.submit(X[lo:hi])
            except Exception as e:           # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=client, args=(i,)) for i in range(16)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert not errs
        got = np.concatenate(outs)
        np.testing.assert_allclose(got, want[:64], rtol=1e-5, atol=1e-6)
        # wide requests chunk through the same queue
        np.testing.assert_allclose(mb.submit(X), want, rtol=1e-5, atol=1e-6)
    finally:
        mb.close()


def test_microbatcher_queue_overflow(cl, rng):
    _, fr_bin, data = _frames(rng)
    m = GBM(response_column="y", ntrees=3, seed=1).train(fr_bin)
    ps = _scorer(m)
    mb = MicroBatcher(ps, max_batch=8, tick_ms=500.0, queue_depth=8)
    try:
        X = ps.featurize(_na_rows(data, rng, k=8))

        def fill():
            try:
                mb.submit(X)
            except RuntimeError:
                pass                       # close() errors the leftover

        done = threading.Thread(target=fill, daemon=True)
        done.start()                       # fills the queue for a while
        import time
        time.sleep(0.05)
        with pytest.raises(RuntimeError, match="queue full"):
            mb.submit(X)
    finally:
        mb.close()


def test_microbatcher_deadline_sheds(cl, rng):
    """A request that waits past H2O3_TPU_SERVE_DEADLINE_MS is shed at
    drain time (counted, never dispatched), not scored late."""
    from h2o3_tpu.runtime import observability as obs
    from h2o3_tpu.serving.batcher import DeadlineExceeded
    _, fr_bin, data = _frames(rng)
    m = GBM(response_column="y", ntrees=3, seed=1).train(fr_bin)
    ps = _scorer(m)
    # the tick lands the first drain well past the 50 ms deadline
    mb = MicroBatcher(ps, max_batch=8, tick_ms=300.0, queue_depth=64,
                      deadline_ms=50.0)
    try:
        before = obs.counter("serve_rejected_total",
                             reason="deadline").value
        X = ps.featurize(_na_rows(data, rng, k=2))
        with pytest.raises(DeadlineExceeded, match="deadline"):
            mb.submit(X)
        if obs.enabled():
            assert obs.counter("serve_rejected_total",
                               reason="deadline").value > before
    finally:
        mb.close()


def test_microbatcher_close_sheds_expired(cl, rng):
    """SIGTERM drain: close() sheds already-expired requests as deadline
    rejections instead of erroring them as a plain shutdown."""
    from h2o3_tpu.serving.batcher import DeadlineExceeded
    _, fr_bin, data = _frames(rng)
    m = GBM(response_column="y", ntrees=3, seed=1).train(fr_bin)
    ps = _scorer(m)
    mb = MicroBatcher(ps, max_batch=8, tick_ms=500.0, queue_depth=64,
                      deadline_ms=30.0)
    X = ps.featurize(_na_rows(data, rng, k=2))
    errs = []

    def client():
        try:
            mb.submit(X)
        except BaseException as e:           # noqa: BLE001
            errs.append(e)

    t = threading.Thread(target=client, daemon=True)
    t.start()
    import time
    time.sleep(0.1)                          # stale by close time
    mb.close()
    t.join(timeout=10)
    assert len(errs) == 1
    assert isinstance(errs[0], DeadlineExceeded)


def test_rest_deadline_returns_503(cl, rng):
    """The REST layer maps a shed request to HTTP 503 so clients retry
    elsewhere instead of treating it as a bad request."""
    from h2o3_tpu.api import start_server
    from h2o3_tpu import serving
    _, fr_bin, data = _frames(rng)
    m = GBM(response_column="y", ntrees=3, seed=1).train(fr_bin)
    s = start_server(port=0)
    try:
        ent = serving.ensure_published(m.key)
        ent.batcher.warmup()
        ent.batcher.tick_s = 0.3             # drain lands past...
        ent.batcher.deadline_s = 0.02        # ...a 20 ms deadline
        rows = _na_rows(data, rng, k=2)
        req = urllib.request.Request(
            s.url + f"/3/Predictions/realtime/{m.key}",
            data=json.dumps({"rows": rows}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req)
        assert e.value.code == 503
    finally:
        serving.shutdown_all()
        s.stop()


def test_microbatcher_close_errors_waiters(cl, rng):
    _, fr_bin, data = _frames(rng)
    m = GBM(response_column="y", ntrees=3, seed=1).train(fr_bin)
    ps = _scorer(m)
    mb = MicroBatcher(ps, max_batch=8, tick_ms=0.0, queue_depth=64)
    mb.close()
    with pytest.raises(RuntimeError, match="shut down"):
        mb.submit(ps.featurize(_na_rows(data, rng, k=2)))


# ---------------------------------------------------------------- REST

def test_rest_realtime_roundtrip(cl, rng):
    from h2o3_tpu.api import start_server
    from h2o3_tpu import serving
    _, fr_bin, data = _frames(rng)
    m = GBM(response_column="y", ntrees=5, seed=1).train(fr_bin)
    s = start_server(port=0)
    try:
        def post(path, payload):
            req = urllib.request.Request(
                s.url + path, data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req) as r:
                return json.loads(r.read())

        out = post(f"/3/Predictions/realtime/{m.key}/warmup", {})
        assert out["published"] and out["n_nodes"] > 0
        assert out["warmup_seconds"] > 0
        rows = _na_rows(data, rng, k=3)
        out = post(f"/3/Predictions/realtime/{m.key}", {"rows": rows})
        assert len(out["predictions"]) == 3
        for p in out["predictions"]:
            assert p["predict"] in ("yes", "no")
            assert abs(sum(p["probabilities"]) - 1.0) < 1e-5
        # single-row body + check-mode parity drill over REST
        out = post(f"/3/Predictions/realtime/{m.key}",
                   {"row": rows[0], "score_mode": "check"})
        assert out["predictions"][0]["predict"] in ("yes", "no")
        # unknown model -> 404
        with pytest.raises(urllib.error.HTTPError) as e:
            post("/3/Predictions/realtime/not_a_model", {"rows": rows})
        assert e.value.code == 404
    finally:
        serving.shutdown_all()
        s.stop()


@pytest.mark.heavy
def test_deploy_serve_sigterm_drains_realtime(cl, rng, tmp_path):
    """SIGTERM mid-request: the in-flight realtime prediction completes
    (REST drain + batcher shutdown) and the launcher exits 0.

    heavy: boots a full second interpreter + jax runtime (up to 90 s)."""
    import os
    import signal
    import subprocess
    import sys
    import time
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # a long tick guarantees the request is still queued when SIGTERM lands
    env["H2O3_TPU_SERVE_TICK_MS"] = "1500"
    port = "54397"
    p = subprocess.Popen(
        [sys.executable, "-m", "h2o3_tpu.deploy.serve", "--port", port],
        env=env, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        base = f"http://127.0.0.1:{port}"
        for _ in range(90):
            time.sleep(1)
            try:
                out = json.load(urllib.request.urlopen(
                    base + "/3/Cloud", timeout=2))
                assert out["cloud_healthy"]
                break
            except AssertionError:
                raise
            except Exception:
                continue
        else:
            raise AssertionError("launcher never served /3/Cloud")

        def post(path, payload, timeout=60):
            req = urllib.request.Request(
                base + path, data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return json.loads(r.read())

        n = 200
        X = rng.normal(size=(n, 2))
        csv = tmp_path / "serve.csv"
        with open(csv, "w") as f:
            f.write("a,b,y\n")
            for i in range(n):
                f.write(f"{X[i,0]},{X[i,1]},"
                        f"{'yes' if X[i,0] > 0 else 'no'}\n")
        post("/3/Parse", {"path": str(csv),
                          "destination_frame": "serve_train"})
        out = post("/3/ModelBuilders/gbm",
                   {"training_frame": "serve_train",
                    "response_column": "y", "ntrees": 3, "seed": 1})
        key = out["job"]["dest"]["name"]
        post(f"/3/Predictions/realtime/{key}/warmup", {})

        result = {}

        def inflight():
            result["out"] = post(f"/3/Predictions/realtime/{key}",
                                 {"row": {"a": 0.5, "b": -0.2}})

        t = threading.Thread(target=inflight)
        t.start()
        time.sleep(0.3)            # request sits in the 1.5 s tick window
        p.send_signal(signal.SIGTERM)
        t.join(timeout=30)
        assert not t.is_alive(), "in-flight request never completed"
        assert result["out"]["predictions"][0]["predict"] in ("yes", "no")
        assert p.wait(timeout=20) == 0
        log = p.stdout.read().decode()
        assert "h2o3_tpu REST drained" in log
    finally:
        if p.poll() is None:
            p.kill()
            p.wait()


def test_publish_journal_survives_coordinator_restart(cl, rng, tmp_path,
                                                      monkeypatch):
    """A journaled publish (`!serve/` record + saved artifact) brings the
    serving plane back after a coordinator restart: the registry is wiped
    AND the model is gone from the DKV, yet ``republish_journaled()``
    reloads the artifact and scoring output is unchanged."""
    monkeypatch.setenv("H2O3_TPU_RECOVERY_DIR", str(tmp_path))
    from h2o3_tpu import serving
    from h2o3_tpu.runtime import dkv
    from h2o3_tpu.serving import batcher
    _, fr_bin, data = _frames(rng)
    m = GBM(response_column="y", ntrees=6, seed=1).train(fr_bin)
    rows = _na_rows(data, rng, k=12)
    try:
        ent = batcher.publish(m.key, m, warm=False)
        ref = ent.predict_rows(rows)
        rec = dkv.get(batcher.SERVE_PREFIX + m.key)
        assert rec and rec["uri"].endswith(".model") and rec["warm"] is False

        # "restart": serving registry cleared and the model lost with it
        serving.shutdown_all()
        dkv.remove(m.key)
        assert batcher.republish_journaled() == [m.key]
        assert dkv.get(m.key) is not None      # Model.load re-registered it

        out = batcher.ensure_published(m.key).predict_rows(rows)
        assert (out["predict"] == ref["predict"]).all()
        np.testing.assert_allclose(out["probabilities"],
                                   ref["probabilities"], rtol=1e-5)
        # idempotent: everything already live
        assert batcher.republish_journaled() == []
        # unpublish retracts the journal so the model stays retired
        assert batcher.unpublish(m.key)
        assert dkv.get(batcher.SERVE_PREFIX + m.key) is None
        assert batcher.republish_journaled() == []
    finally:
        serving.shutdown_all()
        dkv.remove(batcher.SERVE_PREFIX + m.key)
        dkv.remove(m.key)
