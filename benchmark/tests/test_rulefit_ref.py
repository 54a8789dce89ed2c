"""The reference of the cell rulefit_higgs11m.fit (refs/rulefit_fit.py)
against the system at the rehearsal's size on the CPU, under the cell's own
limits, and against wrong inputs that have to fail: a path stopped at an
earlier lambda, a generator off the configuration, a rule kept that was a
duplicate."""

import copy
import os

import pytest

from benchmark.drivers import _common, fit
from benchmark.refs import rulefit_fit
from benchmark.run import merged, read_json

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = read_json(HERE, "configs", "rulefit_higgs11m.json")
CFG = merged(CFG, CFG["rehearse"])
TOL = read_json(HERE, "checks", "rulefit_higgs11m.fit.json")["tol"]
SEED = 2 ** 31 + 7


@pytest.fixture(scope="module")
def fitted():
    import h2o3_tpu
    h2o3_tpu.init()
    data = _common.load("datagen", CFG["data"]["generator"]).generate(
        seed=SEED, **CFG["data"]["args"])
    state = fit.set_up(CFG, {}, SEED, data)
    return state, fit.unit(state)


def wrong(model, **output):
    other = copy.copy(model)
    other.output = dict(model.output, **output)
    return other


def test_a_real_fit_passes_and_both_controls_fail(fitted):
    state, model = fitted
    ok, detail = rulefit_fit.check(state, model, TOL)
    assert ok, detail
    assert detail["codes_wrong"] == 0 and not detail["rules_differ"]
    assert detail["groups"] == CFG["params"]["rule_generation_ntrees"]
    assert detail["bf16_fails"] == ["kkt_max_abs", "p1_max_abs"]
    assert detail["tail_fails"] == ["codes_wrong", "p1_max_abs"]


def test_a_path_stopped_early_fails(fitted):
    """A model fitted to its optimum at the path's middle lambda, which it
    reports: it meets the optimality conditions there, and fails because
    its lambda is not the path's last."""
    from h2o3_tpu.runtime import dkv
    state, model = fitted
    lambdas = [h["lambda"] for h in dkv.get(model.output["glm_key"]).scoring_history]
    early = _common.estimator(CFG, state, lambda_=lambdas[len(lambdas) // 2]).train(
        state["frame"])
    ok, detail = rulefit_fit.check(state, early, TOL)
    assert not ok and detail["failed"] == ["lambda_rel"], detail
    assert detail["kkt_max_abs"] <= TOL["kkt_max_abs"]


def test_a_generator_off_the_configuration_fails(fitted):
    """A forest grown at other settings than the configuration's (here: the
    configuration asks for more trees than the model's generator grew)."""
    state, model = fitted
    cfg = dict(state["cfg"], params=dict(
        state["cfg"]["params"],
        rule_generation_ntrees=state["cfg"]["params"]["rule_generation_ntrees"] + 1))
    ok, detail = rulefit_fit.check(dict(state, cfg=cfg), model, TOL)
    assert not ok and detail["failed"] == ["generator_wrong"]
    assert detail["generator"] == ["ntrees"]


def test_a_rule_kept_twice_fails(fitted):
    """A model whose list of rules holds one the reference drops (here: a
    rule of another tree listed again) reads as wrong codes."""
    state, model = fitted
    rules = list(model.output["rules"])
    ok, detail = rulefit_fit.check(state, wrong(model, rules=rules[:1] + rules), TOL)
    assert not ok and detail["rules_differ"] and "codes_wrong" in detail["failed"]
