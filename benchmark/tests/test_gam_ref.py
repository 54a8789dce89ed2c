"""The reference of the cell gam_higgs11m.fit (refs/gam_fit.py) against the
system at the rehearsal's size on the CPU, under the cell's own limits, and
against wrong inputs that have to fail: knots off the quantiles, smooths off
their centring, a fit at another smoothing strength."""

import copy
import os

import numpy as np
import pytest

from benchmark.drivers import _common, fit
from benchmark.refs import gam_fit
from benchmark.run import merged, read_json

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = read_json(HERE, "configs", "gam_higgs11m.json")
CFG = merged(CFG, CFG["rehearse"])
TOL = read_json(HERE, "checks", "gam_higgs11m.fit.json")["tol"]
SEED = 2 ** 31 + 5


@pytest.fixture(scope="module")
def fitted():
    import h2o3_tpu
    h2o3_tpu.init()
    data = _common.load("datagen", CFG["data"]["generator"]).generate(
        seed=SEED, **CFG["data"]["args"])
    state = fit.set_up(CFG, {}, SEED, data)
    return state, fit.unit(state)


def with_meta(model, change):
    """A copy of ``model`` whose every smooth's ``gam_meta`` entry is
    ``change(entry)``."""
    other = copy.copy(model)
    other.output = dict(model.output, gam_meta=[
        change(dict(m)) for m in model.output["gam_meta"]])
    return other


def test_a_real_fit_passes_and_both_controls_fail(fitted):
    state, model = fitted
    assert model.output["coded"]
    ok, detail = gam_fit.check(state, model, TOL)
    assert ok, detail
    assert detail["smooths"] == len(CFG["params"]["gam_columns"])
    assert detail["bf16_fails"] == ["score_max_abs", "p1_max_abs"]
    assert detail["tail_fails"] == ["p1_max_abs"]


def test_knots_off_the_quantiles_fail(fitted):
    """Knots moved by a bfloat16 rounding of their size."""
    state, model = fitted
    moved = with_meta(model, lambda m: dict(
        m, knots=np.asarray(m["knots"]) * (1 + gam_fit.BF16_REL)))
    ok, detail = gam_fit.check(state, moved, TOL)
    assert not ok and detail["failed"] == ["knots_max_abs"], detail


def test_smooths_off_their_centring_fail(fitted):
    """Every smooth's function raised by a hundredth, as a basis centred on
    other rows than the frame's would leave it: the centring fails, and so
    do the score equations, whose intercept no longer absorbs the mean."""
    state, model = fitted

    def shift(m):
        T = np.asarray(m["T"], np.float64)
        return dict(m, T=T + 0.01 * np.sign(T.sum(axis=0))[None, :])
    ok, detail = gam_fit.check(state, with_meta(model, shift), TOL)
    assert not ok and "centre_max_abs" in detail["failed"], detail


def test_a_fit_at_another_smoothing_strength_fails(fitted):
    """A model fitted to its optimum at ten times the configuration's
    scale meets its own score equations, and fails the configuration's."""
    state, model = fitted
    stiff = _common.estimator(CFG, state, scale=[10.0] * len(
        CFG["params"]["gam_columns"])).train(state["frame"])
    ok, detail = gam_fit.check(state, stiff, TOL)
    assert not ok and detail["failed"] == ["score_max_abs"], detail
