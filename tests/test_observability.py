"""Telemetry plane unit tests: metric registry (counters / gauges /
mergeable histograms), wire + Prometheus rendering, span ok/error
recording, trace propagation + forest stitching, and the per-node
log-file handler lifecycle."""

import logging
import math
import os

import pytest

from h2o3_tpu.runtime import observability as obs


@pytest.fixture(autouse=True)
def _clean_registry():
    prev = obs.set_enabled(True)
    obs.reset_metrics()
    yield
    obs.reset_metrics()
    obs.set_enabled(prev)


# ------------------------------------------------------------------ metrics

def test_registry_identity_by_name_and_labels():
    a = obs.counter("reqs", op="put")
    assert obs.counter("reqs", op="put") is a          # same series
    assert obs.counter("reqs", op="get") is not a      # label split
    assert obs.counter("other", op="put") is not a     # name split
    # label values are stringified, so 1 and "1" are the same series
    assert obs.gauge("g", shard=1) is obs.gauge("g", shard="1")


def test_counter_gauge_semantics():
    c = obs.counter("n_ops")
    c.inc()
    c.inc(2.5)
    assert c.wire() == {"n": "n_ops", "l": {}, "t": "c", "v": 3.5}
    g = obs.gauge("mem", kind="in_use")
    g.set(100.0)
    g.set(40.0)
    assert g.value == 40.0                             # last-writer
    w = obs.gauge("mem", kind="peak")
    w.set_max(100.0)
    w.set_max(40.0)
    assert w.value == 100.0                            # watermark
    assert g.wire()["l"] == {"kind": "in_use"}


def test_histogram_bucketization_and_overflow():
    h = obs.histogram("lat")
    assert h.buckets == obs.LATENCY_BUCKETS
    h.observe(0.0003)       # lands in the <= 5e-4 slot
    h.observe(1e9)          # beyond the last edge -> +Inf overflow slot
    i = obs.LATENCY_BUCKETS.index(0.0005)
    assert h.counts[i] == 1
    assert h.counts[-1] == 1
    assert h.count == 2
    assert h.sum == pytest.approx(0.0003 + 1e9)
    w = h.wire()
    assert w["t"] == "h" and len(w["c"]) == len(w["b"]) + 1


def test_latency_buckets_are_log_spaced_and_monotone():
    b = obs.LATENCY_BUCKETS
    assert all(x < y for x, y in zip(b, b[1:]))
    assert b[0] == pytest.approx(1e-4)
    assert b[-1] == pytest.approx(500.0)


def test_histogram_merge_by_summation():
    h1 = obs.histogram("rpc")
    for v in (0.001, 0.002, 10.0):
        h1.observe(v)
    a, b = h1.wire(), h1.wire()
    merged = obs.merge_histograms([a, {"t": "c", "v": 1}, b])
    assert merged["n_obs"] == 6
    assert merged["s"] == pytest.approx(2 * h1.sum)
    assert merged["c"] == [x * 2 for x in h1.counts]
    bad = dict(b, b=[1.0, 2.0])
    with pytest.raises(ValueError, match="bucket edges differ"):
        obs.merge_histograms([a, bad])


def test_merge_wire_adds_node_label():
    obs.counter("x", op="put").inc()
    snap = obs.metrics_wire()
    merged = obs.merge_wire({"nodeA": snap, "nodeB": snap})
    assert len(merged) == 2
    assert {s["l"]["node"] for s in merged} == {"nodeA", "nodeB"}
    assert all(s["l"]["op"] == "put" for s in merged)


def test_enabled_switch_gates_instrumentation():
    obs.set_enabled(False)
    obs.inc("gated")
    obs.observe("gated_h", 0.1)
    obs.set_gauge("gated_g", 1.0)
    assert obs.metrics_wire() == []
    obs.set_enabled(True)
    obs.inc("gated")
    assert len(obs.metrics_wire()) == 1


# --------------------------------------------------------------- prometheus

def test_render_prometheus_text():
    obs.counter("dkv_rpc_failures", op="put").inc()
    obs.gauge("device_memory_bytes", device="0", kind="in_use").set(123.0)
    h = obs.histogram("dkv_rpc_seconds", op="get", side="client")
    h.observe(0.0002)
    h.observe(0.0002)
    h.observe(2.0)
    text = obs.render_prometheus(cluster=False)
    assert "# TYPE dkv_rpc_failures counter" in text
    assert "# TYPE device_memory_bytes gauge" in text
    assert "# TYPE dkv_rpc_seconds histogram" in text
    me = obs.node_name()
    assert f'dkv_rpc_failures{{node="{me}",op="put"}} 1.0' in text
    # histogram buckets are CUMULATIVE and end with +Inf == _count
    lines = [ln for ln in text.splitlines()
             if ln.startswith("dkv_rpc_seconds_bucket")]
    counts = [float(ln.rsplit(" ", 1)[1]) for ln in lines]
    assert counts == sorted(counts)
    assert 'le="+Inf"' in lines[-1] and counts[-1] == 3
    assert f'dkv_rpc_seconds_count{{node="{me}",op="get",side="client"}} 3' \
        in text
    # flat count() counters surface as h2o3_events_total{kind=...}
    obs.count("wal_records", 7)
    text = obs.render_prometheus(cluster=False)
    assert 'h2o3_events_total{kind="wal_records"' in text


def test_prom_label_escaping():
    assert obs._prom_labels({"msg": 'say "hi"'}) == r'{msg="say \"hi\""}'
    assert obs._prom_name("tree.phase-seconds") == "tree_phase_seconds"


# ------------------------------------------------------------------- traces

def test_span_records_ok_and_error():
    with obs.span("unit_ok", tag="a"):
        pass
    with pytest.raises(ValueError):
        with obs.span("unit_err", tag="b"):
            raise ValueError("boom")
    evs = {e["kind"]: e for e in obs.timeline_events(2000)}
    assert evs["unit_ok"]["ok"] is True
    assert "error" not in evs["unit_ok"]
    assert evs["unit_err"]["ok"] is False
    assert evs["unit_err"]["error"] == "ValueError"
    assert evs["unit_err"]["duration_s"] >= 0


def test_span_outside_trace_allocates_no_ids():
    with obs.span("unit_untraced"):
        assert obs.current_trace() is None
    ev = [e for e in obs.timeline_events(2000)
          if e["kind"] == "unit_untraced"][-1]
    assert "trace_id" not in ev and "span_id" not in ev


def test_trace_nesting_and_rpc_adoption():
    with obs.trace("unit_root"):
        ctx = obs.current_trace()
        assert ctx and ctx["trace_id"] and ctx["span_id"]
        with obs.span("unit_child"):
            inner = obs.current_trace()
            assert inner["trace_id"] == ctx["trace_id"]
            assert inner["span_id"] != ctx["span_id"]
        # the handler side adopts the wire context verbatim
        with obs.trace_context({"trace_id": "T", "span_id": "S"}):
            with obs.span("unit_remote"):
                pass
    assert obs.current_trace() is None
    evs = {e["kind"]: e for e in obs.timeline_events(2000)
           if e["kind"].startswith("unit_")}
    root, child = evs["unit_root"], evs["unit_child"]
    assert child["trace_id"] == root["trace_id"]
    assert child["parent_span"] == root["span_id"]
    remote = evs["unit_remote"]
    assert remote["trace_id"] == "T" and remote["parent_span"] == "S"


def test_trace_forest_stitching():
    events = [
        {"ts": 1.0, "kind": "job", "trace_id": "t1", "span_id": "a"},
        {"ts": 2.0, "kind": "tree_phase", "trace_id": "t1", "span_id": "b",
         "parent_span": "a"},
        {"ts": 3.0, "kind": "dkv_handle", "trace_id": "t1", "span_id": "c",
         "parent_span": "missing"},       # shipped span, parent un-shipped
        {"ts": 0.5, "kind": "job", "trace_id": "t0", "span_id": "z"},
        {"ts": 4.0, "kind": "noise"},     # no ids -> excluded
    ]
    forest = obs.trace_forest(events)
    assert [t["trace_id"] for t in forest] == ["t0", "t1"]  # by first ts
    t1 = forest[1]
    assert {s["span_id"] for s in t1["spans"]} == {"a", "c"}  # orphan=root
    a = next(s for s in t1["spans"] if s["span_id"] == "a")
    assert [s["span_id"] for s in a["children"]] == ["b"]


def test_span_disabled_is_transparent():
    obs.set_enabled(False)
    n0 = len(obs.timeline_events(2000))
    with obs.span("unit_gone"):
        pass
    assert len(obs.timeline_events(2000)) == n0


# ----------------------------------------------------------------- log file

def test_log_file_handler_lifecycle(tmp_path, monkeypatch):
    from h2o3_tpu.runtime import config
    template = str(tmp_path / "node_%h_%p.log")
    monkeypatch.setenv("H2O3_TPU_LOG_FILE", template)
    try:
        config.reload()
        path = template.replace("%h", __import__("socket").gethostname()) \
                       .replace("%p", str(os.getpid()))
        obs.log.warning("telemetry log-file smoke line")
        assert os.path.exists(path)
        assert "telemetry log-file smoke line" in open(path).read()
        # the ring handler keeps working alongside the file
        assert any("telemetry log-file smoke line" in ln
                   for ln in obs.recent_logs())
        obs.close_log_file()
        assert not any(isinstance(h, logging.FileHandler)
                       for h in obs.log.handlers)
        obs.close_log_file()               # idempotent
    finally:
        monkeypatch.delenv("H2O3_TPU_LOG_FILE", raising=False)
        config.reload()


# ---------------------------------------------------------------------- api

def test_api_timeline_limit_and_shape():
    from h2o3_tpu.api.server import Api
    for i in range(6):
        obs.record("unit_api_marker", i=i)
    out = Api().timeline(limit=4)
    assert len(out["events"]) == 4
    assert isinstance(out["counters"], dict)
    assert isinstance(out["nodes"], dict)
    assert isinstance(out["traces"], list)


# ---------------------------------------------------------- compile ledger

@pytest.fixture()
def xprof():
    from h2o3_tpu.runtime import xprof as xp
    xp.reset_ledger()
    yield xp
    xp.reset_ledger()


def test_register_program_compile_reasons(xprof):
    """One program, three compile reasons: first build, a new shape, and
    a cluster re-init epoch bump — each attributed in the ledger and the
    recompiles_total/compile_seconds series."""
    import jax
    import jax.numpy as jnp

    def f(x):
        return x * 2.0

    prog = xprof.register_program("unit_prog", jax.jit(f), orig=f)
    x = jnp.ones((8,), jnp.float32)
    assert float(prog(x)[0]) == 2.0
    ent = xprof.ledger_snapshot()["programs"]["unit_prog"]
    assert ent["compiles"] == 1 and ent["reasons"] == {"first": 1}
    assert ent["compile_s"] > 0.0
    prog(x)                                  # seen signature: no recompile
    assert xprof.ledger_snapshot()["programs"]["unit_prog"]["compiles"] == 1
    prog(jnp.ones((16,), jnp.float32))       # new signature
    ent = xprof.ledger_snapshot()["programs"]["unit_prog"]
    assert ent["compiles"] == 2 and ent["reasons"]["shape_change"] == 1
    xprof.invalidate("cluster_reinit")       # what cluster re-init does
    prog(x)                                  # stale executable was dropped
    ent = xprof.ledger_snapshot()["programs"]["unit_prog"]
    assert ent["compiles"] == 3 and ent["reasons"]["cluster_reinit"] == 1
    # XLA cost attribution published alongside the compile counters
    assert ent["flops"] is not None
    series = {s["n"] for s in obs.metrics_wire()}
    assert {"compile_seconds", "recompiles_total", "program_flops"} <= series


def test_program_passthrough_under_trace(xprof):
    """Inside an outer jit the wrapper must inline the ORIGINAL function
    (no nested-jit hop, no AOT compile, no ledger entry)."""
    import jax
    import jax.numpy as jnp

    def f(x):
        return x + 1.0

    prog = xprof.register_program("unit_traced", jax.jit(f), orig=f)

    @jax.jit
    def outer(x):
        return prog(x) * 3.0

    out = outer(jnp.ones((4,), jnp.float32))
    assert float(out[0]) == 6.0
    assert "unit_traced" not in xprof.ledger_snapshot()["programs"]


def test_program_fallback_never_breaks_seam(xprof):
    """Misuse of the wrapper itself flips it to permanent passthrough
    (with an xprof_fallback event) and the call still returns the answer;
    an error from the compiler is raised once and never retried."""
    import jax
    import jax.numpy as jnp

    # compile-stage failure: the registered object has no .lower
    def plain(x):
        return x + 1.0
    prog = xprof.register_program("unit_nolower", plain)
    assert float(prog(jnp.ones((2,), jnp.float32))[0]) == 2.0
    assert prog.fallback
    assert "unit_nolower" not in xprof.ledger_snapshot()["programs"]

    # call-stage failure: statics declared on the wrapper but not on the
    # jit — the compiled executable rejects the stripped arg list
    def g(x, k):
        return x * k
    prog2 = xprof.register_program("unit_mismatch", jax.jit(g),
                                   static_argnums=(1,))
    assert float(prog2(jnp.ones((2,), jnp.float32), 3)[0]) == 3.0
    assert prog2.fallback
    falls = [e for e in obs.timeline_events(500)
             if e.get("kind") == "xprof_fallback"]
    assert {e.get("program") for e in falls} >= {"unit_nolower",
                                                 "unit_mismatch"}

    # compiler refusal (here raised while lowering, as Mosaic does): it
    # surfaces where it happens, the plain jit is not tried after it
    attempts = []

    def refused(x):
        attempts.append(1)
        raise NotImplementedError("Only 2D gather is supported")
    prog3 = xprof.register_program("unit_refused", jax.jit(refused))
    with pytest.raises(NotImplementedError, match="2D gather"):
        prog3(jnp.ones((2,), jnp.float32))
    assert len(attempts) == 1 and not prog3.fallback
    assert "unit_refused" not in {
        e.get("program") for e in obs.timeline_events(500)
        if e.get("kind") == "xprof_fallback"}


def test_maybe_device_sync_modes(monkeypatch):
    """off records nothing; full syncs every call; sampled syncs every
    Nth; unknown mode strings read as off."""
    import jax.numpy as jnp
    from h2o3_tpu.runtime import config, xprof
    out = jnp.ones((4,), jnp.float32)

    def set_mode(mode, sample=None):
        monkeypatch.setenv("H2O3_TPU_DEVICE_TIMING", mode)
        if sample is not None:
            monkeypatch.setenv("H2O3_TPU_DEVICE_TIMING_SAMPLE", str(sample))
        config.reload()
        obs.set_enabled(True)        # reload re-reads the metrics switch

    try:
        set_mode("off")
        assert xprof.device_timing_mode() == "off"
        assert xprof.maybe_device_sync("unit_phase", 1, 0.0, out) is False
        set_mode("full")
        assert all(xprof.maybe_device_sync("unit_phase", s, 0.0, out)
                   for s in (1, 2, 3))
        set_mode("sampled", sample=2)
        synced = [xprof.maybe_device_sync("unit_phase", s, 0.0, out)
                  for s in (1, 2, 3, 4)]
        assert synced == [False, True, False, True]
        assert "tree_phase_device_seconds" in {
            s["n"] for s in obs.metrics_wire()}
        set_mode("bogus")
        assert xprof.device_timing_mode() == "off"
    finally:
        monkeypatch.delenv("H2O3_TPU_DEVICE_TIMING", raising=False)
        monkeypatch.delenv("H2O3_TPU_DEVICE_TIMING_SAMPLE", raising=False)
        config.reload()


# --------------------------------------------------------------- profiler

def test_device_trace_idempotent(tmp_path):
    """Double-start and stop-without-start are no-ops that record
    profiler_noop events instead of raising."""
    logdir = str(tmp_path / "trace")
    assert obs.profiler_active() is False
    if not obs.start_device_trace(logdir):
        pytest.skip("jax profiler unavailable on this backend")
    try:
        assert obs.profiler_active() is True
        assert obs.start_device_trace(logdir) is False     # already active
    finally:
        assert obs.stop_device_trace() is True
    assert obs.profiler_active() is False
    assert obs.stop_device_trace() is False                # nothing active
    noops = [e for e in obs.timeline_events(500)
             if e.get("kind") == "profiler_noop"]
    assert {e.get("reason") for e in noops} >= {"already_active",
                                                "not_active"}


def test_api_profiler_roundtrip(tmp_path):
    """POST /3/Profiler/start|stop idempotency + GET /3/Profiler/memory
    through the Api surface the REST routes dispatch to."""
    from h2o3_tpu.api.server import Api
    api = Api()
    out = api.profiler_start(logdir=str(tmp_path / "cap"))
    if not out["started"]:
        pytest.skip("jax profiler unavailable on this backend")
    try:
        assert out["active"] is True and out["logdir"].endswith("cap")
        again = api.profiler_start(logdir=str(tmp_path / "cap"))
        assert again["started"] is False and again["active"] is True
    finally:
        stop = api.profiler_stop()
    assert stop["stopped"] is True and stop["active"] is False
    assert api.profiler_stop()["stopped"] is False
    mem = api.profiler_memory()
    assert isinstance(mem, bytes) and len(mem) > 0         # pprof payload


def test_api_compile_ledger_and_metrics_scrape(xprof, cl, monkeypatch):
    """GET /3/Profiler/compiles returns the ledger; GET /metrics carries
    the compile series and refreshes device-memory gauges at scrape
    time (no heartbeat needed)."""
    import jax
    import jax.numpy as jnp
    from h2o3_tpu.api.server import Api

    def f(x):
        return x + 3.0

    prog = xprof.register_program("unit_rest_prog", jax.jit(f), orig=f)
    prog(jnp.ones((4,), jnp.float32))
    api = Api()
    snap = api.compile_ledger()
    assert snap["programs"]["unit_rest_prog"]["compiles"] == 1
    assert snap["total_compiles"] >= 1
    # scrape-time refresh: /metrics re-samples the device allocator stats
    # before rendering (CPU devices report none, so observe the call)
    sampled = []
    from h2o3_tpu.runtime import cluster as _cluster_mod
    monkeypatch.setattr(_cluster_mod, "sample_memory_gauges",
                        lambda: sampled.append(1) or 1)
    text = api.prometheus()
    assert "# TYPE compile_seconds histogram" in text
    assert 'program="unit_rest_prog"' in text
    assert "# TYPE recompiles_total counter" in text
    assert "# TYPE program_flops gauge" in text
    assert sampled, "scrape did not refresh device-memory gauges"


def test_acceptance_gbm_costs_and_reinit_recompiles(cl, rng, xprof):
    """ISSUE acceptance: a GBM train on the 8-device mesh plus the eager
    hist/split entry points yield nonzero compile_seconds and
    program_flops for hist and split programs in /metrics, and re-initing
    the cluster with a new geometry attributes the next compiles to
    recompiles_total{reason="cluster_reinit"}."""
    import jax.numpy as jnp
    import numpy as np
    import h2o3_tpu
    from h2o3_tpu import Frame
    from h2o3_tpu.models import GBM
    from h2o3_tpu.models.tree import hist

    n = 512
    X = rng.normal(size=(n, 3)).astype(np.float32)
    y = 2.0 * X[:, 0] - X[:, 1] + 0.1 * rng.normal(size=n)
    fr = Frame.from_numpy({"x0": X[:, 0], "x1": X[:, 1], "x2": X[:, 2],
                           "y": y})
    GBM(response_column="y", ntrees=2, max_depth=2, seed=7).train(fr)
    # the fused train traces hist/splits INSIDE tree_scan, so drive them
    # through their eager entry points too (the crosscheck/bench path)
    L, F, B = 2, 5, 7
    codes = jnp.asarray(rng.integers(0, B - 1, (F, n)), jnp.int32)
    leaf = jnp.zeros((n,), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    w = jnp.ones((n,), jnp.float32)
    H = hist.make_hist_fn(L, F, B, n, force_impl="einsum")(
        codes, leaf, g, w, w)
    hist.fused_best_splits(H, B - 1, 0.5, 1.0, 1e-5)
    progs = xprof.ledger_snapshot()["programs"]
    assert progs["tree_scan"]["compile_s"] > 0.0
    for name in ("hist_uniform", "fused_split"):
        assert progs[name]["compile_s"] > 0.0, name
        assert progs[name]["flops"], name
    text = obs.render_prometheus(cluster=False)
    assert 'program="hist_uniform"' in text
    assert 'program="fused_split"' in text
    assert "# TYPE program_flops gauge" in text
    # new geometry: compiled programs went stale; their next compile is
    # attributed to the re-init
    orig_hosts = cl.n_hosts
    new_hosts = 4 if orig_hosts != 4 else 2
    try:
        h2o3_tpu.init(hosts=new_hosts)
        hist.make_hist_fn(L, F, B, n, force_impl="einsum")(
            codes, leaf, g, w, w)
        ent = xprof.ledger_snapshot()["programs"]["hist_uniform"]
        assert ent["reasons"].get("cluster_reinit", 0) >= 1
        assert any(s["n"] == "recompiles_total"
                   and s["l"].get("reason") == "cluster_reinit"
                   for s in obs.metrics_wire())
    finally:
        h2o3_tpu.init(hosts=orig_hosts)


# --------------------------------------------------------- mesh data plane

def test_mesh_shape_gauge_and_collective_seconds(cl):
    """The hierarchical data plane surfaces its geometry and timings:
    publish_mesh_gauges() emits one mesh_shape gauge per mesh axis plus
    the device total, and map_reduce records a collective_seconds
    observation labelled with the collective schedule — all visible in
    the GET /metrics Prometheus text."""
    import jax.numpy as jnp
    import numpy as np
    from h2o3_tpu.runtime.cluster import publish_mesh_gauges
    from h2o3_tpu.runtime.mapreduce import map_reduce

    publish_mesh_gauges()        # re-emit: _clean_registry reset the gauges
    x = jnp.asarray(np.arange(64, dtype=np.float32))
    map_reduce(lambda d: jnp.sum(d), x, reduce_mode="hier")
    map_reduce(lambda d: jnp.sum(d), x, reduce_mode="flat")
    text = obs.render_prometheus(cluster=False)
    me = obs.node_name()
    assert "# TYPE mesh_shape gauge" in text
    assert f'mesh_shape{{axis="hosts",node="{me}"}} {float(cl.n_hosts)}' \
        in text
    assert f'mesh_shape{{axis="chips",node="{me}"}} ' \
        f'{float(cl.n_chips_per_host)}' in text
    assert f'mesh_shape{{axis="total",node="{me}"}} ' \
        f'{float(cl.n_row_shards)}' in text
    assert "# TYPE collective_seconds histogram" in text
    assert 'axis="chips+hosts"' in text      # staged hier schedule
    assert 'axis="rows"' in text             # flat oracle
    assert 'op="map_reduce"' in text


# ------------------------------------------------------------- autotuner

def test_autotune_series_and_rest_route(cl):
    """The autotuner's observability surface: every resolve increments
    autotune_decisions_total{knob,choice,source}, the table size is the
    autotune_cache_entries gauge, both render in GET /metrics, and
    GET /3/Profiler/autotune dumps the decision table (signature ->
    choice, source, predicted vs measured seconds)."""
    import json
    import types

    from h2o3_tpu.api.server import Api
    from h2o3_tpu.runtime import autotune, config

    saved = os.environ.get("H2O3_TPU_AUTOTUNE")
    try:
        os.environ["H2O3_TPU_AUTOTUNE"] = "on"
        config.reload()
        autotune.reset()
        p = types.SimpleNamespace(hist_mode="auto", split_mode="auto",
                                  hist_layout="auto",
                                  sparse_depth_threshold=8,
                                  max_depth=6, nbins=32)
        k = autotune.resolve_tree_knobs(p, kind="gbm", F=4, N=4096)
        assert k.sig is not None
        autotune.resolve_serve_impl(depth=8, R=100, F=16, B=128)

        text = obs.render_prometheus(cluster=False)
        assert "# TYPE autotune_decisions_total counter" in text
        assert 'knob="hist_mode"' in text
        assert 'source="model"' in text
        assert "# TYPE autotune_cache_entries gauge" in text
        me = obs.node_name()
        assert f'autotune_cache_entries{{node="{me}"}} 2.0' in text

        table = Api().autotune_table()
        json.dumps(table)                       # REST payload: plain data
        assert table["mode"] == "on" and table["entries"] == 2
        sigs = {d["signature"] for d in table["decisions"]}
        assert k.sig in sigs
        assert any(s.startswith("serve:") for s in sigs)
        row = next(d for d in table["decisions"]
                   if d["signature"] == k.sig)
        assert row["source"] == "model"
        assert set(row) >= {"signature", "choice", "source", "resolves",
                            "predicted_s", "measured_s", "exploring"}
    finally:
        if saved is None:
            os.environ.pop("H2O3_TPU_AUTOTUNE", None)
        else:
            os.environ["H2O3_TPU_AUTOTUNE"] = saved
        config.reload()
        autotune.reset()
