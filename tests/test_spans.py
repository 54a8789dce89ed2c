"""The span primitive and its call sites: one ``span()`` feeds the event
ring, a profiler annotation and ``span_seconds`` / ``span_self_seconds``;
``train()`` and ``predict()`` open the spans of docs/operations.md's table
under one trace; the compile listener says what was traced and what the
persistent cache did with each backend compile; an upload is timed where it
happens; a profiler trace's idle time is attributed to spans."""

import time

import numpy as np
import pytest

from h2o3_tpu.runtime import observability as obs
from h2o3_tpu.runtime import xprof


@pytest.fixture(autouse=True)
def _clean_registry(monkeypatch):
    # an empty set of ``fun`` labels: in a process that has traced 256
    # functions already, every new one would read "other"
    monkeypatch.setattr(xprof, "_funs", set())
    prev = obs.set_enabled(True)
    obs.reset_metrics()
    yield
    obs.reset_metrics()
    obs.set_enabled(prev)


def _span_series(name):
    """{span label: (observations, summed seconds)} of one span histogram."""
    return {s["l"]["span"]: (s["n_obs"], s["s"])
            for s in obs.metrics_wire() if s["n"] == name}


def _ring_since(mark):
    return [e for e in obs.timeline_events(2000) if e["ts"] >= mark]


# ---------------------------------------------------------------- the sinks

class _Annotation:
    """Stands in for jax.profiler.TraceAnnotation: records what is entered."""
    entered, left = [], []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.entered.append(self.name)

    def __exit__(self, *exc):
        self.left.append(self.name)


@pytest.fixture()
def annotations(monkeypatch):
    monkeypatch.setattr(obs, "_annotation_cls", _Annotation)
    _Annotation.entered, _Annotation.left = [], []
    return _Annotation


def test_a_span_feeds_ring_annotation_and_registry(annotations):
    mark = time.time()
    with obs.trace("unit_root", algo="x"):
        with obs.span("unit_leaf", rows=7):
            time.sleep(0.002)
    ring = {e["kind"]: e for e in _ring_since(mark)}
    assert ring["unit_leaf"]["rows"] == 7 and ring["unit_leaf"]["ok"] is True
    assert ring["unit_leaf"]["parent_span"] == ring["unit_root"]["span_id"]
    # perf_counter_ns, not a wall clock rounded to 0.1 ms
    assert ring["unit_leaf"]["duration_s"] >= 0.002
    assert ring["unit_leaf"]["duration_s"] != round(ring["unit_leaf"]["duration_s"], 4)
    assert annotations.entered == ["h2o3.unit_root", "h2o3.unit_leaf"]
    assert annotations.left == ["h2o3.unit_leaf", "h2o3.unit_root"]
    total, self_ = _span_series("span_seconds"), _span_series("span_self_seconds")
    assert set(total) == set(self_) == {"unit_root", "unit_leaf"}
    assert total["unit_leaf"] == (1, ring["unit_leaf"]["duration_s"])
    # the only label is the span's name: fields stay on the ring event
    assert all(set(s["l"]) == {"span"} for s in obs.metrics_wire()
               if s["n"].startswith("span_"))


def test_a_failing_span_still_feeds_every_sink(annotations):
    mark = time.time()
    with pytest.raises(KeyError):
        with obs.span("unit_fails"):
            raise KeyError("boom")
    (ev,) = [e for e in _ring_since(mark) if e["kind"] == "unit_fails"]
    assert ev["ok"] is False and ev["error"] == "KeyError"
    assert annotations.left == ["h2o3.unit_fails"]
    assert _span_series("span_seconds")["unit_fails"][0] == 1


def test_disabled_telemetry_feeds_no_sink(annotations):
    obs.set_enabled(False)
    mark = time.time()
    with obs.trace("unit_root"):
        with obs.span("unit_leaf"):
            assert obs.current_trace() is None
    assert _ring_since(mark) == []
    assert annotations.entered == []
    assert obs.metrics_wire() == []


def test_self_seconds_of_a_tree_sum_to_the_roots_duration():
    with obs.trace("unit_root"):
        time.sleep(0.001)
        with obs.span("unit_a"):
            time.sleep(0.001)
            with obs.span("unit_a1"):
                time.sleep(0.001)
            with obs.span("unit_a1"):        # a name may repeat
                time.sleep(0.001)
        with obs.span("unit_b"):
            time.sleep(0.001)
    total, self_ = _span_series("span_seconds"), _span_series("span_self_seconds")
    assert self_["unit_a1"] == total["unit_a1"]          # leaves: all self
    assert self_["unit_a"][1] == pytest.approx(
        total["unit_a"][1] - total["unit_a1"][1], abs=1e-12)
    assert self_["unit_root"][1] >= 0.001
    assert sum(s for _, s in self_.values()) == pytest.approx(
        total["unit_root"][1], abs=1e-9)


def test_the_wire_context_carries_ids_only():
    """The child accumulator rides the context dict but not the RPC
    envelope, and a context adopted from the wire has none."""
    with obs.trace("unit_root"):
        wire = obs.current_trace()
        assert set(wire) == {"trace_id", "span_id"}
    with obs.trace_context(wire):
        with obs.span("unit_remote"):
            pass
    assert _span_series("span_self_seconds")["unit_remote"][0] == 1


def test_a_span_decorates_a_function():
    @obs.span("unit_decorated")
    def work(x):
        return x + 1

    assert [work(1), work(2)] == [2, 3]
    assert _span_series("span_seconds")["unit_decorated"][0] == 2


def test_spans_are_host_events_of_a_profiler_trace(tmp_path):
    """The real annotation: ``h2o3.<kind>`` on a host plane of the trace
    ``start_device_trace`` takes, and the stop's summary (nothing to
    attribute without a TPU plane)."""
    import jax
    from h2o3_tpu.api.server import Api
    api = Api()
    if not api.profiler_start(logdir=str(tmp_path / "cap"))["started"]:
        pytest.skip("jax profiler unavailable on this backend")
    try:
        with obs.trace("unit_root"):
            with obs.span("unit_leaf"):
                jax.block_until_ready(jax.numpy.ones(8) + 1)
    finally:
        stop = api.profiler_stop()
    summary = stop["idle_by_span"]
    assert summary["by_span"] == {} and summary["unattributed_s"] == 0.0
    data = jax.profiler.ProfileData.from_file(summary["xplane"])
    names = {e.name for p in data.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert {"h2o3.unit_root", "h2o3.unit_leaf"} <= names
    (ev,) = [e for e in obs.timeline_events(50)
             if e["kind"] == "profiler_stop"][-1:]
    assert ev["idle_by_span"] == obs.profiler_summary() == summary


# ------------------------------------------------------------ the call sites

ENTRY = {"train", "train.validate", "train.datainfo", "job", "train.journal",
         "train.device_slot", "train.fit", "train.post_fit"}
GLM = {"glm.matrix", "glm.path", "glm.wait", "glm.finalize"}
TREE = {"binning.sketch", "binning.encode", "tree_chunk", "tree.finalize"}
PREDICT = ["predict.matrix", "predict.dispatch", "predict.wait",
           "predict.frame", "predict"]


@pytest.fixture(scope="module")
def frame(cl):
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.frame.vec import T_CAT, Vec
    rng = np.random.default_rng(7)
    X = rng.normal(size=(300, 3))
    y = (X[:, 0] + rng.normal(size=300) > 0).astype(np.int32)
    return Frame(["a", "b", "c", "y"],
                 [Vec.from_numpy(X[:, i]) for i in range(3)]
                 + [Vec.from_numpy(y, T_CAT, domain=["0", "1"])])


def _spans_of(call):
    mark = time.time()
    out = call()
    return out, [e for e in _ring_since(mark) if "span_id" in e]


def _glm(frame):
    from h2o3_tpu.models.glm import GLM
    return GLM(response_column="y", family="binomial", lambda_=0.0).train(frame)


def _gbm(frame):
    from h2o3_tpu.models.tree.gbm import GBM
    return GBM(response_column="y", ntrees=2, max_depth=2, nbins=16).train(frame)


def _compile_observations(fun):
    """Observations of ``jax_compile_seconds`` whose ``fun`` names ``fun``."""
    return sum(s["n_obs"] for s in obs.metrics_wire()
               if s["n"] == "jax_compile_seconds" and fun in s["l"].get("fun", ""))


@pytest.mark.parametrize("fit,names", [(_glm, ENTRY | GLM), (_gbm, ENTRY | TREE)],
                         ids=["glm", "gbm"])
def test_train_and_predict_open_the_spans_of_the_table(frame, fit, names):
    fit(frame)                  # tree_phase spans fire while a build compiles
    model, spans = _spans_of(lambda: fit(frame))
    assert {e["kind"] for e in spans} == names
    assert len({e["trace_id"] for e in spans}) == 1
    assert len(spans) <= 25                                # the budget
    by_id = {e["span_id"]: e for e in spans}
    (root,) = [e for e in spans if "parent_span" not in e]
    assert root["kind"] == "train" and root["algo"] == model.algo
    parent_of = {e["kind"]: by_id[e["parent_span"]]["kind"]
                 for e in spans if e is not root}
    assert parent_of["job"] == "train" and parent_of["train.fit"] == "job"
    assert all(parent_of[k] == "train.fit" for k in names - ENTRY)
    assert [e["op"] for e in spans if e["kind"] == "train.journal"] \
        == ["start", "done"]
    from h2o3_tpu.models.base import prediction_columns
    xprof.install_monitoring_listener()
    prediction_columns.clear_cache()
    model.predict(frame)        # whatever predict compiles, it compiles here
    moved = {d: obs.counter("transfer_bytes_total", dir=d).value
             for d in ("d2h", "h2d")}
    compiles = _compile_observations("prediction_columns")
    assert compiles > 0
    preds, spans = _spans_of(lambda: model.predict(frame))
    assert [e["kind"] for e in spans] == PREDICT           # ≤ 8, in this order
    assert len({e["trace_id"] for e in spans}) == 1
    # the result frame is built where the scores are: nothing crosses the
    # host link in either direction, and a second call compiles nothing
    assert {d: obs.counter("transfer_bytes_total", dir=d).value
            for d in ("d2h", "h2d")} == moved
    assert _compile_observations("prediction_columns") == compiles
    assert preds.nrows == frame.nrows
    assert all(v.data.sharding == frame.vecs[0].data.sharding for v in preds.vecs)


# ----------------------------------------------------- the compile listener

def test_the_compile_listener_names_the_traced_function():
    import jax
    xprof.install_monitoring_listener()

    def unit_probe_fn(x):
        return x * 2 + 1

    jax.block_until_ready(jax.jit(unit_probe_fn)(np.ones(3, np.float32)))
    mine = {(s["l"]["event"], s["l"]["fun"]) for s in obs.metrics_wire()
            if s["n"] == "jax_compile_seconds"
            and "unit_probe_fn" in s["l"].get("fun", "")}
    # jax names the traced function at the trace, its jit at what follows
    assert mine == {("jaxpr_trace_duration", "unit_probe_fn"),
                    ("jaxpr_to_mlir_module_duration", "jit(unit_probe_fn)"),
                    ("backend_compile_duration", "jit(unit_probe_fn)")}


def test_the_fun_label_is_cut_and_capped():
    assert xprof._fun_label("f" * 100) == "f" * 64
    assert xprof._fun_label(None) == "unknown"
    labels = {xprof._fun_label(f"fn{i}") for i in range(400)}
    assert len(xprof._funs) == 256
    assert "other" in labels and len(labels) == 256 - 2 + 1
    assert xprof._fun_label("fn0") == "fn0"          # a known one stays itself
    assert xprof._fun_label("never_seen") == "other"


# --------------------------------- what the cache did with a backend compile

CACHE = "/jax/compilation_cache/"
BACKEND = "/jax/core/compile/backend_compile_duration"


def _backend_compile(fun, cache, seconds=0.25, saved=5.0):
    """What jax emits, in its order, around one backend compile that the
    persistent cache answered so."""
    from jax import monitoring
    if cache != "off":
        monitoring.record_event(CACHE + "compile_requests_use_cache")
    if cache == "hit":
        monitoring.record_event(CACHE + "cache_hits")
        monitoring.record_event_duration_secs(CACHE + "compile_time_saved_sec", saved)
        monitoring.record_event_duration_secs(CACHE + "cache_retrieval_time_sec", 0.01)
    if cache == "stored":
        monitoring.record_event(CACHE + "cache_misses")
    monitoring.record_event_duration_secs(BACKEND, seconds, fun_name=fun)


def _backend_series(fun):
    """{cache label: (observations, seconds)} of ``fun``'s backend compiles."""
    return {s["l"]["cache"]: (s["n_obs"], s["s"]) for s in obs.metrics_wire()
            if s["n"] == "jax_compile_seconds" and s["l"]["fun"] == fun
            and s["l"]["event"] == "backend_compile_duration"}


def _saved(fun):
    return [(s["n_obs"], s["s"]) for s in obs.metrics_wire()
            if s["n"] == "jax_cache_saved_seconds" and s["l"] == {"fun": fun}]


def _compile_events(mark, fun):
    return [e for e in _ring_since(mark)
            if e["kind"] == "compile" and e["fun"] == fun]


@pytest.mark.parametrize("cache", ["hit", "stored", "unstored", "off"])
def test_the_listener_says_what_the_cache_did(cache):
    xprof.install_monitoring_listener()
    fun = f"jit(unit_{cache})"
    mark = time.time()
    with obs.trace("unit_root"):
        with obs.span("unit_compiles"):
            _backend_compile(fun, cache)
    assert _backend_series(fun) == {cache: (1, 0.25)}
    # the saved seconds of a hit; a function that asked in vain has the
    # series, empty; one that never asked has none
    assert _saved(fun) == {"hit": [(1, 5.0)], "off": []}.get(cache, [(0, 0.0)])
    ring = {e["kind"]: e for e in _ring_since(mark)}
    if cache == "hit":          # a retrieval is no event: the ring holds 2,000
        assert "compile" not in ring
    else:
        (ev,) = _compile_events(mark, fun)
        assert (ev["cache"], ev["duration_s"], ev["span"]) == (cache, 0.25, "unit_compiles")
        assert ev["parent_span"] == ring["unit_compiles"]["span_id"]
        assert ev["trace_id"] == ring["unit_root"]["trace_id"]
        assert "span_id" not in ev          # not a span: no node of the trace's tree
    # ... and nothing of a span's: self seconds mean what they meant
    assert set(_span_series("span_self_seconds")) == {"unit_root", "unit_compiles"}
    # the record is read once: the next compile on this thread starts clean
    _backend_compile(fun, "off")
    assert _backend_series(fun).get("off", (0, 0))[0] == (2 if cache == "off" else 1)


def test_a_slow_retrieval_saves_nothing_and_no_span_stamps_nothing():
    xprof.install_monitoring_listener()
    mark = time.time()
    _backend_compile("jit(unit_slow_hit)", "hit", saved=-0.5)
    assert _saved("jit(unit_slow_hit)") == [(1, 0.0)]
    _backend_compile("jit(unit_bare)", "unstored")
    (ev,) = _compile_events(mark, "jit(unit_bare)")
    assert not {"trace_id", "parent_span", "span"} & set(ev)
    # trace and lower events keep their two labels and leave the ring alone
    from jax import monitoring
    monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_trace_duration", 0.1, fun_name="unit_bare")
    (traced,) = [s for s in obs.metrics_wire() if s["l"].get("fun") == "unit_bare"]
    assert set(traced["l"]) == {"event", "fun"}
    assert [e["kind"] for e in _ring_since(mark)] == ["compile"]


def test_two_threads_compiling_at_once_keep_their_own_records():
    from concurrent.futures import ThreadPoolExecutor
    from jax import monitoring
    xprof.install_monitoring_listener()
    with ThreadPoolExecutor(1) as a, ThreadPoolExecutor(1) as b:
        def on(pool, fn, *args, **kw):
            pool.submit(fn, *args, **kw).result(timeout=30)

        # a asks and hits; b asks, compiles and stores; each ends after the
        # other has spoken
        on(a, monitoring.record_event, CACHE + "compile_requests_use_cache")
        on(b, monitoring.record_event, CACHE + "compile_requests_use_cache")
        on(a, monitoring.record_event, CACHE + "cache_hits")
        on(a, monitoring.record_event_duration_secs, CACHE + "compile_time_saved_sec", 3.0)
        on(b, monitoring.record_event, CACHE + "cache_misses")
        on(a, monitoring.record_event_duration_secs, BACKEND, 0.5, fun_name="jit(unit_a)")
        on(b, monitoring.record_event_duration_secs, BACKEND, 2.0, fun_name="jit(unit_b)")
        # a third compile on a, with no word from the cache
        on(a, monitoring.record_event_duration_secs, BACKEND, 1.0, fun_name="jit(unit_a)")
    assert _backend_series("jit(unit_a)") == {"hit": (1, 0.5), "off": (1, 1.0)}
    assert _backend_series("jit(unit_b)") == {"stored": (1, 2.0)}
    assert _saved("jit(unit_a)") == [(1, 3.0)] and _saved("jit(unit_b)") == [(0, 0.0)]


def test_with_telemetry_off_the_listeners_hear_nothing():
    xprof.install_monitoring_listener()
    obs.set_enabled(False)
    mark = time.time()
    for cache in ("hit", "stored", "unstored", "off"):
        _backend_compile("jit(unit_silent)", cache)
    assert obs.metrics_wire() == [] and _ring_since(mark) == []
    assert vars(xprof._pending) == {}         # nothing kept for a later compile
    obs.set_enabled(True)
    _backend_compile("jit(unit_silent)", "off")
    assert _backend_series("jit(unit_silent)") == {"off": (1, 0.25)}


def test_a_real_compile_is_stored_and_then_a_hit(tmp_path):
    """One jit under a cache directory of its own, with jax's floors at
    nothing: compiled and written, then (a second function object of the
    same name and body: the same cache key) retrieved."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    xprof.install_monitoring_listener()
    knobs = {"jax_enable_compilation_cache": True,
             "jax_compilation_cache_dir": str(tmp_path),
             "jax_persistent_cache_min_compile_time_secs": 0.0,
             "jax_persistent_cache_min_entry_size_bytes": -1}
    was = {k: getattr(jax.config, k) for k in knobs}

    def make():
        def unit_cached_fn(x):
            return x * 3 + 2
        return jax.jit(unit_cached_fn)

    fun = "jit(unit_cached_fn)"
    mark = time.time()
    try:
        for k, v in knobs.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        x = np.ones(5, np.float32)
        jax.block_until_ready(make()(x))
        first = _backend_series(fun)
        assert set(first) == {"stored"} and first["stored"][0] == 1
        assert _saved(fun) == [(0, 0.0)]
        jax.block_until_ready(make()(x))
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert {c: n for c, (n, _) in _backend_series(fun).items()} == {"stored": 1, "hit": 1}
    ((hits, saved_s),) = _saved(fun)
    assert hits == 1 and saved_s >= 0.0
    assert [e["cache"] for e in _compile_events(mark, fun)] == ["stored"]
    # GET /3/Profiler/compiles: one row a function, the most seconds first
    table = xprof.ledger_snapshot()["jax"]
    assert [r["seconds"] for r in table] == sorted((r["seconds"] for r in table), reverse=True)
    (row,) = [r for r in table if r["fun"] == fun]
    assert {c: n for c, (n, _) in row["by_cache"].items()} == {"stored": 1, "hit": 1}
    assert set(row["by_event"]) == {"jaxpr_to_mlir_module_duration", "backend_compile_duration"}
    assert row["by_event"]["backend_compile_duration"][0] == 2 and row["saved_s"] == saved_s
    assert row["seconds"] == pytest.approx(sum(s for _, s in row["by_event"].values()))


# ------------------------------------------------------------------- uploads

def test_an_upload_is_timed_where_it_happens(cl):
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.frame.vec import T_STR, Vec

    def seconds():
        return {s["l"]["stage"]: s["v"] for s in obs.metrics_wire()
                if s["n"] == "transfer_seconds_total" and s["l"]["dir"] == "h2d"}

    Vec.from_numpy(np.arange(1000, dtype=np.float64))
    one = seconds()
    assert set(one) == {"prepare", "put"} and all(v > 0 for v in one.values())
    Vec.from_numpy(np.array(["a", "b"], dtype=object), T_STR)     # stays on the host
    assert seconds() == one
    mark = time.time()
    Frame.from_numpy({"a": np.arange(50.0), "b": np.arange(50), "c": ["x", "y"] * 25})
    two = seconds()
    assert all(two[k] > one[k] for k in one)
    (ev,) = [e for e in _ring_since(mark) if e["kind"] == "frame.upload"]
    assert (ev["rows"], ev["cols"], ev["ok"]) == (50, 3, True)
    assert obs.counter("transfer_bytes_total", dir="h2d").value > 0


def test_parse_opens_the_same_upload_span(cl):
    from h2o3_tpu.frame.parse import parse_csv
    mark = time.time()
    fr = parse_csv(b"a,b\n1,x\n2,y\n3,x\n")
    (ev,) = [e for e in _ring_since(mark) if e["kind"] == "frame.upload"]
    assert (ev["rows"], ev["cols"]) == (fr.nrows, 2) == (3, 2)


# ------------------------------------------------------- idle time by span

MS = 1_000_000      # the trace's clock counts ns


def test_idle_goes_to_the_innermost_span_open_over_it():
    modules = [("jit_a", 10 * MS, 20 * MS), ("jit_b", 30 * MS, 40 * MS),
               ("jit_c", 70 * MS, 80 * MS)]
    spans = [("train", 0, 100 * MS),            # root
             ("train.fit", 5 * MS, 60 * MS),    # nested in it
             ("glm.wait", 22 * MS, 28 * MS)]    # a leaf inside the second gap
    out = xprof.attribute_idle(modules, spans)
    # gaps, cut where a span opens or closes:
    #   0-10:   0-5 train, 5-10 train.fit
    #   20-30:  20-22 train.fit, 22-28 glm.wait, 28-30 train.fit
    #   40-70:  40-60 train.fit, 60-70 train
    #   80-100: train
    assert out["by_span"] == {"train": pytest.approx(0.035),
                              "train.fit": pytest.approx(0.029),
                              "glm.wait": pytest.approx(0.006)}
    assert list(out["by_span"]) == ["train", "train.fit", "glm.wait"]
    assert out["unattributed_s"] == 0.0
    # the longest gap whole, named by the span open at its middle (55 ms)
    assert out["top"][0] == ("train.fit", pytest.approx(0.040), pytest.approx(0.030))
    assert [g[0] for g in out["top"]] == ["train.fit", "train", "train.fit", "glm.wait"]
    assert sum(out["by_span"].values()) == pytest.approx(0.1 - 0.030)


def test_idle_under_no_span_is_unattributed():
    modules = [("jit_a", 0, 10 * MS), ("jit_b", 50 * MS, 60 * MS)]
    spans = [("predict", 0, 12 * MS)]           # closes 2 ms into the gap
    out = xprof.attribute_idle(modules, spans)
    assert out["by_span"] == {"predict": pytest.approx(0.002)}
    assert out["unattributed_s"] == pytest.approx(0.038)
    assert out["top"] == [("", pytest.approx(0.010), pytest.approx(0.040))]


def test_one_long_gap_is_split_between_the_spans_that_cover_it():
    """The end of a ``predict``: no program runs after the traversal, and
    the one gap is the fetch, then the labels and the upload."""
    modules = [("jit_traverse", 0, 60 * MS)]
    spans = [("predict", 0, 100 * MS), ("predict.fetch", 61 * MS, 90 * MS),
             ("predict.frame", 90 * MS, 100 * MS)]
    out = xprof.attribute_idle(modules, spans)
    assert out["by_span"] == {"predict.fetch": pytest.approx(0.029),
                              "predict.frame": pytest.approx(0.010),
                              "predict": pytest.approx(0.001)}
    assert out["top"] == [("predict.fetch", pytest.approx(0.060), pytest.approx(0.040))]
    assert xprof.attribute_idle([], []) == {
        "by_span": {}, "unattributed_s": 0.0, "top": []}
