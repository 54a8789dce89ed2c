"""The plain references against the system at a tiny size on the CPU, each
with one deliberately wrong input that has to fail."""

import copy

import numpy as np
import pytest

from benchmark import refs
from benchmark.drivers import _common, fit, score
from benchmark.refs import glm_fit, tree_fit, tree_score

XGB = {"estimator": "h2o3_tpu.models.XGBoost",
       "params": {"ntrees": 3, "max_depth": 4, "nbins": 64, "seed": 1,
                  "score_tree_interval": 10 ** 9},
       "data": {"generator": "airlines_like", "args": {"rows": 20_000}}}
GLM = {"estimator": "h2o3_tpu.models.GLM", "params": {"family": "binomial", "lambda_": 0.0},
       "data": {"generator": "higgs_like", "args": {"rows": 20_000, "cols": 6}}}
TREE_TOL = {"root_gain_ratio_min": 0.95, "auc_band": [0.5, 1.0], "auc_sample_rows": 5_000}


def draw(cfg, seed):
    return _common.load("datagen", cfg["data"]["generator"]).generate(seed=seed, **cfg["data"]["args"])


@pytest.fixture(scope="module", autouse=True)
def booted():
    import h2o3_tpu
    h2o3_tpu.init()


@pytest.fixture(scope="module")
def xgb_fit():
    state = fit.set_up(XGB, {}, 7, draw(XGB, 7))
    return state, fit.unit(state)


def test_root_split_and_auc_pass_on_a_real_fit(xgb_fit):
    state, model = xgb_fit
    ok, detail = tree_fit.check(state, model, TREE_TOL)
    assert ok, detail
    assert 0.95 <= detail["root_gain_ratio"] <= 1.05
    assert 0.5 < detail["auc"] < 1.0


def test_root_split_fails_when_the_tree_split_elsewhere(xgb_fit):
    state, model = xgb_fit
    wrong = copy.copy(model)
    wrong.output = dict(model.output)
    stacked = copy.copy(model.output["stacked"])
    feat, thr, na_left, valid = stacked.levels[0]
    # a legal split with next to no gain: the month, which the label ignores
    other, middle = np.asarray(feat).copy(), np.asarray(thr).copy()
    other[0, 0], middle[0, 0] = state["features"].index("month"), 6.5
    stacked.levels = [(other, middle, na_left, valid)] + list(stacked.levels[1:])
    wrong.output["stacked"] = stacked
    ok, detail = tree_fit.check(state, wrong, TREE_TOL)
    assert not ok and detail["root_gain_ratio"] < 0.95


def test_auc_band_fails_a_model_that_found_nothing(xgb_fit):
    state, model = xgb_fit
    ok, detail = tree_fit.check(state, model, dict(TREE_TOL, auc_band=[0.99, 1.0]))
    assert not ok and detail["root_gain_ratio"] >= 0.95


def test_auc_agrees_with_a_count_of_pairs():
    rng = np.random.default_rng(0)
    score_, label = rng.integers(0, 20, 300) / 20.0, rng.random(300) < 0.4
    pos, neg = score_[label], score_[~label]
    pairs = (pos[:, None] > neg[None, :]).sum() + 0.5 * (pos[:, None] == neg[None, :]).sum()
    assert refs.auc(score_, label) == pytest.approx(pairs / (len(pos) * len(neg)), abs=1e-12)


def test_score_equations_hold_at_the_fit_and_fail_beside_it():
    state = fit.set_up(GLM, {}, 7, draw(GLM, 7))
    model = fit.unit(state)
    ok, detail = glm_fit.check(state, model, {"score_max_abs": 1e-4})
    assert ok and detail["score_max_abs"] < 1e-5, detail

    class Off:
        coef = dict(model.coef, f0=model.coef["f0"] * 1.01)      # one coefficient 1% off
    ok, detail = glm_fit.check(state, Off, {"score_max_abs": 1e-4})
    assert not ok and detail["score_max_abs"] > 1e-4

    def bf16(v):
        return float((np.array(v, np.float32).view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32))

    class Rounded:      # coefficients as a fit in bf16 would return them
        coef = {k: bf16(v) for k, v in model.coef.items()}
    ok, detail = glm_fit.check(state, Rounded, {"score_max_abs": 1e-4})
    assert not ok, detail


def test_tree_walker_equals_predict_and_fails_on_a_changed_leaf():
    state = score.set_up(XGB, {"train_rows": 2_000, "ntrees": 5}, 7, draw(XGB, 7))
    predictions = score.unit(state)
    tol = {"p1_max_abs": 1e-5, "sample_rows": 1_000}
    ok, detail = tree_score.check(state, predictions, tol)
    assert ok and detail["p1_max_abs"] < 1e-6, detail

    model = state["model"]
    wrong = copy.copy(model)
    wrong.output = dict(model.output)
    stacked = copy.copy(model.output["stacked"])
    stacked.values = np.asarray(stacked.values) + 0.01          # every leaf moved
    wrong.output["stacked"] = stacked
    ok, detail = tree_score.check(dict(state, model=wrong), predictions, tol)
    assert not ok and detail["p1_max_abs"] > 1e-4

    short = state["make_frame"]({k: v[:100] for k, v in state["cols"].items()})
    ok, _ = tree_score.check(state, model.predict(short), tol)
    assert not ok                                                # rows are missing


def test_model_ready_waits_on_a_tree_model(xgb_fit):
    _, model = xgb_fit
    _common.model_ready(model)          # objects around arrays and plain values alike
