"""``refs/dl_fit.py`` against the system at the cell's rehearsal size on the
CPU, at the cell's own limits, and against controls that go through the same
``check`` and each have to come out as not correct: the reference itself in
float8 arithmetic, a step that drops half of its minibatch, an optimizer that
skips an accumulator, a sampler's copy whose codes are out of line with its
numerics, a first layer without a categorical's block, constants that do not
standardise, a model that learned nothing."""

import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.drivers import _common, fit
from benchmark.refs import dl_fit

CELL = "dl_airlines40m.fit"


@pytest.fixture(scope="module")
def dl():
    """(state, the fitted model, the cell's limits as a rehearsal has them)."""
    import h2o3_tpu
    h2o3_tpu.init()
    manifest = run.read_json(run.ROOT, "BENCHMARK.json")
    _, cfg, mix, check = run.resolve_cell(manifest, CELL, rehearse=True)
    generator = _common.load("datagen", cfg["data"]["generator"])
    state = fit.set_up(cfg, mix, 7, generator.generate(seed=7, **cfg["data"]["args"]))
    return state, fit.unit(state), check["tol"]


@pytest.fixture(scope="module")
def parts(dl):
    state, model, _ = dl
    data = dl_fit.Data(state)
    return data, dl_fit.System(model, data)


def test_reference_passes_on_a_real_fit_and_its_controls_fail(dl):
    state, model, tol = dl
    ok, detail = dl_fit.check(state, model, tol)
    assert ok, detail
    assert detail["failed"] == []
    assert detail["copy_max_abs"] < 1e-5 and detail["standardise_rel"] < 1e-5, detail
    assert "p1_max_abs" in detail["fp8_fails"], detail
    assert {"loss_rel", "grad_rel", "update_rel"} <= set(detail["half_minibatch_fails"]), detail
    assert detail["standardise_rel_none"] > 100 * tol["standardise_rel"]


@pytest.mark.parametrize("control,limit", [
    ({"bits": 3}, "p1_max_abs"),
    ({"keep": 64}, "grad_rel"),
    ({"skip": ("e_d",)}, "accum_rel"),
    ({"roll_codes": True}, "copy_max_abs"),
    ({"stats": "none"}, "standardise_rel"),
    ({"stats": "one_pass_float32"}, "standardise_rel"),
])
def test_a_control_in_the_programs_place_is_not_correct(dl, parts, control, limit):
    """The reference with one fault, through ``check`` at the cell's limits."""
    state, _, tol = dl
    data, system = parts
    if control.get("stats") == "none":
        control = {"stats": {f: (0.0, 1.0) for f in data.stats}}
    elif control.get("stats") == "one_pass_float32":
        # the rollups before PR 30: sum(x*x)/n - mean**2 in float32
        stats = {}
        for f in data.stats:
            x = data.cols[f].astype(np.float32)
            n = np.float32(len(x))
            mean = x.sum(dtype=np.float32) / n
            var = np.float32((x * x).sum(dtype=np.float32) / n - mean * mean) * n / (n - 1)
            stats[f] = (float(mean), float(np.sqrt(max(var, 0.0))))
        control = {"stats": stats}
    ok, detail = dl_fit.check(state, dl_fit.Twin(system, data, **control), tol)
    assert not ok and limit in detail["failed"], detail


def test_an_unfaulted_twin_is_correct(dl, parts):
    """The comparison itself: the reference in the program's place passes."""
    state, _, tol = dl
    ok, detail = dl_fit.check(state, dl_fit.Twin(parts[1], parts[0]), tol)
    assert ok and detail["grad_rel"] < 1e-12 and detail["copy_max_abs"] == 0.0, detail


def test_a_dropped_categorical_block_fails(dl, parts):
    """A first layer that never reads ``origin``'s block, in predict and in
    the step, under exported weights that are whole."""
    state, model, tol = dl
    data, system = parts
    block = [i for i, n in enumerate(system.coef_names) if n.startswith("origin.")]
    assert len(block) == 300
    ok, detail = dl_fit.check(state, dl_fit.Twin(system, data, blind=block), tol)
    assert not ok and {"p1_max_abs", "grad_rel"} <= set(detail["failed"]), detail
    assert detail["copy_max_abs"] == 0.0


def test_a_model_that_learned_nothing_fails_the_band(dl):
    state, model, tol = dl
    ok, detail = dl_fit.check(state, model, dict(tol, auc_band=[0.99, 1.0]))
    assert not ok and detail["failed"] == ["auc_band"]


def test_the_limits_file_names_every_limit():
    with open(os.path.join(run.HERE, "checks", CELL + ".json")) as f:
        tol = json.load(f)["tol"]
    assert set(dl_fit.LIMITS) <= set(tol)
    # a scoring block on the v5e is 128,000 rows: 16.9 GB / 16 over 8 B x (628 + 200 + 200 + 2)
    assert tol["predict_rows"] > 2 * 128_000 and tol["predict_rows"] % 128_000


def test_costs_follow_the_fit_geometry(dl):
    """The step count the cost assumes is the one the fit ran, and the
    parameter count the one the model has."""
    from benchmark.costs import dl_train, dl_train_flops
    state, model, _ = dl
    iters, per_iter, batch, sizes, columns = dl_train.geometry(state)
    steps = iters * per_iter
    assert steps * batch == model.output["samples_trained"]
    assert iters == len(model.scoring_history)
    assert sizes[0] == model.datainfo.nfeatures == model.output["weights"][0][0].shape[0]
    assert columns == 8 and sizes[1:] == [200, 200, 2]
    parameters = sum(W.size + b.size for W, b in model.output["weights"])
    cost = dl_train.cost(state)
    assert cost["bytes"] == steps * (24 * parameters + batch * 4 * 10)
    assert cost["ops"] == 6 * (8 * 200 + 200 * 200 + 200 * 2) * steps * batch
    assert cost["steps"] == steps
    assert dl_train_flops.cost(state) == dict(cost, bytes=0)


def test_launches_reads_a_trace_that_ends_before_the_window(dl):
    """Of the window's launches the trace holds three whole and one cut
    short: the median launch is a whole one; the launches and their steps
    come from the program's counters, so a window of three units whose
    iterations are twice as long as the cost's is still read rightly, and a
    program without the counters reports nothing."""
    from benchmark import reduce as R
    from benchmark.costs import dl_train
    from benchmark.reductions import launches, roofline
    state, _, _ = dl
    cost = dl_train.cost(state)
    ms = 1e6
    events = [("jit_dl_sample_copy(1)", 0, 200 * ms)]
    events += [(f"jit_dl_train_steps({i})", (200 + 70 * i) * ms, (260 + 70 * i) * ms)
               for i in range(3)]
    events.append(("jit_dl_train_steps(3)", 410 * ms, 425 * ms))       # the buffer's end
    trace = R.Trace({"/device:TPU:0": {R.MODULES: events, R.OPS: []}},
                    [("bench.window", 0, 1000 * ms)])
    peaks = {"hbm_bytes_per_s": 819e9, "flops_bf16_per_s": 197e12}
    made, each = 30, 32                 # launches in the window, steps in each

    def wire(launched):
        return [{"n": "dl_train_launches_total", "t": "c", "v": 5 + launched},
                {"n": "dl_optimizer_steps_total", "t": "c", "v": 80 + launched * each}]

    ctx = {"trace": trace, "window": trace.window(), "window_s": 1.0, "units": 3,
           "state": state, "peaks": peaks, "counters_before": wire(0),
           "counters_after": wire(made)}
    spec = {"match": "^jit_dl_train", "cost": "dl_train",
            "launches": "dl_train_launches_total", "steps": "dl_optimizer_steps_total"}
    assert launches.reduce(dict(spec, value="share"), ctx) == pytest.approx(100 * made * 0.060)
    least = cost["bytes"] / cost["steps"] * each / 819e9
    assert least > cost["ops"] / cost["steps"] * each / 197e12
    assert launches.reduce(dict(spec, value="roofline"), ctx) == pytest.approx(100 * least / 0.060)
    # the summed seconds take the four launches the trace holds for all of them
    assert roofline.reduce(spec, ctx) == pytest.approx(100 * cost["bytes"] / 819e9 * 3 / 0.195)
    assert launches.reduce(dict(spec, value="share", match="^jit_none"), ctx) is None
    assert launches.reduce(dict(spec, value="share"), dict(ctx, counters_after=wire(0))) is None
    assert launches.reduce(dict(spec, value="roofline"), dict(
        ctx, counters_before=[], counters_after=[])) is None
