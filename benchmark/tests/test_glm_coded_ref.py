"""The reference of the cell glm_airlines40m.fit (refs/glm_fit_coded.py)
against the system at a tiny size on the CPU, and against wrong inputs that
have to fail: a fit stopped early, a Gram formed in bfloat16 products, a
model that scores with another coefficient, a scoring walk that writes its
last block wrong."""

import copy

import numpy as np
import pytest

from benchmark.drivers import _common, fit
from benchmark.refs import glm_fit_coded

CFG = {"estimator": "h2o3_tpu.models.GLM", "params": {"family": "binomial", "lambda_": 0.0},
       "data": {"generator": "airlines_like", "args": {"rows": 60_000}}}
TOL = {"score_max_abs": 2e-5, "gram_max_abs": 1e-5, "p1_max_abs": 1e-5, "logloss_abs": 1e-6}


@pytest.fixture(scope="module")
def fitted():
    import h2o3_tpu
    from h2o3_tpu.models import datainfo, glm
    h2o3_tpu.init()
    # 60,000 x 628 fits the 4 GiB a CPU is assumed to have, and GLM would take
    # the dense design; a 1 MB device makes it read the code form in blocks of
    # 1,024 rows, as the cell's 40M rows make it on the chip
    patch = pytest.MonkeyPatch()
    patch.setattr(datainfo, "device_memory_bytes", lambda: 1 << 20)
    patch.setattr(glm, "device_memory_bytes", lambda: 1 << 20)
    try:
        yield from _fitted()
    finally:
        patch.undo()


def _fitted():
    data = _common.load("datagen", "airlines_like").generate(seed=2 ** 31 + 5, **CFG["data"]["args"])
    state = fit.set_up(CFG, {}, 2 ** 31 + 5, data)
    model = fit.unit(state)
    assert type(model._score_matrix(state["frame"])).__name__ == "CodedDesign"
    yield state, model


def wrong(model, **output):
    other = copy.copy(model)
    other.output = dict(model.output, **output)
    return other


def test_every_reading_passes_on_a_real_fit_and_both_controls_fail(fitted):
    state, model = fitted
    ok, detail = glm_fit_coded.check(state, model, TOL)
    assert ok, detail
    assert detail["failed"] == [] and "score_max_abs" in detail["bf16_fails"]
    assert detail["score_max_abs"] < 1e-6 and detail["p1_max_abs"] < 1e-6
    assert detail["logloss_abs"] < 1e-7
    assert detail["bf16"]["score_max_abs"] > TOL["score_max_abs"]
    assert detail["tail_fails"] == ["p1_max_abs", "logloss_abs"]


def test_predict_is_the_blocked_scoring_program_of_the_timed_fit(fitted, monkeypatch):
    """The check's ``predict`` has to run the program that scored the timed
    fit: were it the dense form, a fault of the blocked walk would pass."""
    from h2o3_tpu.models import glm
    state, model = fitted
    made = []
    monkeypatch.setattr(glm, "_make_score", lambda *a: made.append(a) or (lambda *b: 1 / 0))
    with pytest.raises(ZeroDivisionError):
        glm_fit_coded.check(state, model, TOL)
    assert made and made[0][-1] < state["rows"]          # more than one block of rows


def test_a_scoring_walk_with_a_wrong_last_block_fails(fitted, monkeypatch):
    """The fault the blocked walk can have and the dense form cannot: the
    last block of rows, which is laid back over the one before, scored wrong.
    A fit with it fails by its own training metrics and by ``predict``."""
    import jax.numpy as jnp
    from h2o3_tpu.models import glm
    state, _ = fitted
    make = glm._make_score.__wrapped__

    def broken(*args):
        score, block = make(*args), args[-1]
        return lambda *a: (lambda out: out.at[-(block // 2):].set(
            jnp.float32(0.5)))(score(*a))

    monkeypatch.setattr(glm, "_make_score", broken)
    model = fit.unit(state)
    ok, detail = glm_fit_coded.check(state, model, TOL)
    assert not ok and set(detail["failed"]) >= {"p1_max_abs", "logloss_abs"}, detail


def test_a_fit_stopped_after_one_pass_fails_the_score_equations(fitted):
    state, _ = fitted
    early = _common.estimator(CFG, state, max_iterations=1).train(state["frame"])
    ok, detail = glm_fit_coded.check(state, early, TOL)
    assert not ok and "score_max_abs" in detail["failed"]


def test_a_gram_of_bfloat16_products_fails(fitted):
    state, model = fitted
    gram = np.asarray(model.output["gram"])
    rounded = gram * (1.0 + glm_fit_coded.BF16_REL * np.sign(np.sin(np.arange(gram.size)))
                      .reshape(gram.shape))
    ok, detail = glm_fit_coded.check(state, wrong(model, gram=rounded), TOL)
    assert not ok and detail["failed"] == ["gram_max_abs"]


def test_a_model_that_scores_with_another_coefficient_fails_predict(fitted):
    state, model = fitted
    beta = np.array(model.output["beta_std"])
    beta[0] += 0.01
    ok, detail = glm_fit_coded.check(state, wrong(model, beta_std=beta), TOL)
    assert not ok and detail["failed"] == ["p1_max_abs"]


def test_another_layout_is_refused(fitted):
    state, model = fitted
    names = list(model.output["coef_names"])
    ok, detail = glm_fit_coded.check(
        state, wrong(model, coef_names=names[1:] + names[:1]), TOL)
    assert not ok and "layout" in detail
