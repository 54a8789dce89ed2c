"""GAM with cr smooths on the code-form design against the plain reference
(``models/reference_gam.py``): the device knots, basis rows and products,
the penalized fit (coefficients, linear predictor, score equations),
``predict`` on a new frame, H2O-3's per-column parameters, the spans and
counter, and a warm refit that compiles nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o3_tpu import Frame
from h2o3_tpu.frame.vec import T_CAT
from h2o3_tpu.models import GAM
from h2o3_tpu.models import datainfo
from h2o3_tpu.models import glm as glm_mod
from h2o3_tpu.models import reference_gam as ref
from h2o3_tpu.models.datainfo import DataInfo
from h2o3_tpu.runtime import dkv
from h2o3_tpu.runtime import observability as obs

N = 4000
SMOOTHS = {"a": (10, 1.0), "b": (7, 0.5)}      # column -> (knots, scale)
# float32 against float64: knots are order statistics interpolated in
# float32 (1e-7 of their size), the basis and the fit's sums add a few
# float32 roundings (the system's score equations vanish to ~4e-8 here)
KNOT_ATOL = 1e-6
ROW_ATOL = 2e-6
FIT_ATOL = 2e-5
SCORE_ATOL = 1e-6


def _columns(seed, n=N, family="binomial"):
    rng = np.random.default_rng(seed)
    cols = {"a": rng.normal(size=n).astype(np.float32),
            "b": rng.uniform(-2, 2, n).astype(np.float32),
            "z": rng.normal(size=n).astype(np.float32)}
    eta = np.sin(2 * cols["a"]) + 0.5 * cols["b"] ** 2 - 0.5 + 0.3 * cols["z"]
    if family == "binomial":
        cols["y"] = (rng.random(n) < 1 / (1 + np.exp(-eta))).astype(np.int32)
    else:
        cols["y"] = (eta + 0.3 * rng.normal(size=n)).astype(np.float32)
    return cols


def _frame(cols):
    if cols["y"].dtype == np.int32:
        return Frame.from_numpy(cols, types={"y": T_CAT},
                                domains={"y": ["no", "yes"]})
    return Frame.from_numpy(cols)


def _gam(family="binomial", **kw):
    params = dict(gam_columns=list(SMOOTHS),
                  num_knots=[k for k, _ in SMOOTHS.values()],
                  scale=[s for _, s in SMOOTHS.values()])
    params.update(kw)
    return GAM(response_column="y", family=family, lambda_=0.0, **params)


def _functions(model):
    """Each smooth's values at its knots, the intercept and the linear
    coefficients of a fitted model: a cardinal spline's coefficients are
    its values at the knots, here G beta with G = T / col_scale."""
    coef = dkv.get(model.output["glm_key"]).coef
    v = {}
    for m in model.output["gam_meta"]:
        G = m["T"] / m["col_scale"][None, :]
        v[m["name"]] = G @ np.array([coef[f"{m['name']}_gam{j}"]
                                     for j in range(G.shape[1])])
    return v, coef["Intercept"], {"z": coef["z"]}


@pytest.fixture(scope="module")
def fitted(cl):
    cols = _columns(3)
    fr = _frame(cols)
    model = _gam().train(fr)
    return cols, fr, model


def test_takes_the_code_form_and_builds_no_dense_design(fitted, monkeypatch):
    _, fr, model = fitted
    assert model.output["coded"]
    glm = dkv.get(model.output["glm_key"])
    layout = glm.datainfo.coded_layout()
    assert layout == (("num", 1), ("spline", 9), ("spline", 6), ("one", 1)) \
        or layout == (("spline", 9), ("spline", 6), ("num", 1), ("one", 1))
    monkeypatch.setattr(DataInfo, "make_matrix", lambda *a, **k: pytest.fail(
        "the dense design was built"))
    assert isinstance(glm._score_matrix(fr), datainfo.CodedDesign)
    model.predict(fr)


def test_knots_basis_and_products_equal_reference(fitted):
    """The device knots against float64 quantiles, the basis rows that
    ``spline_rows`` builds against the reference's cardinal basis through
    the model's G, and ``coded_matvec`` / ``coded_rmatvec`` against the
    rows they stand for."""
    cols, fr, model = fitted
    glm = dkv.get(model.output["glm_key"])
    di = glm.datainfo
    for m in model.output["gam_meta"]:
        want = ref.knots(cols[m["name"]], SMOOTHS[m["name"]][0])
        np.testing.assert_allclose(m["knots"], want, rtol=0, atol=KNOT_ATOL)
    X = di.make_coded(fr)
    tables = di.spline_tables()
    layout = di.coded_layout()
    beta = jnp.asarray(glm.output["beta_std"])
    v = jnp.asarray(np.random.default_rng(0).normal(size=X.num.shape[0])
                    * (np.arange(X.num.shape[0]) < N), jnp.float32)

    @jax.jit
    def products(num, codes, tables, beta, v):
        return (datainfo.expand_coded(layout, num, codes, tables),
                datainfo.coded_matvec(layout, num, codes, beta, tables),
                datainfo.coded_rmatvec(layout, num, codes, v, tables))
    dense, matvec, rmatvec = (np.asarray(a) for a in
                              products(*X, tables, beta, v))
    dense = dense[:N]
    for spec in di.specs:
        if spec.spline is None:
            continue
        knots, G, _ = spec.spline
        want = ref.cardinal(cols[spec.name], knots) @ G
        got = dense[:, spec.offset:spec.offset + spec.width]
        np.testing.assert_allclose(got, want, rtol=0, atol=ROW_ATOL)
    np.testing.assert_allclose(
        matvec[:N], dense @ np.asarray(beta, np.float64), rtol=0,
        atol=ROW_ATOL * 10)
    np.testing.assert_allclose(
        rmatvec, dense.T @ np.asarray(v, np.float64)[:N], rtol=1e-5,
        atol=1e-3)


@pytest.mark.parametrize("family", ["binomial", "gaussian"])
def test_fit_equals_reference(cl, fitted, family):
    """The fitted functions at their knots, the intercept, the linear term
    and the linear predictor against the reference's penalized IRLS, and
    the penalized score equations at the returned coefficients (the
    gaussian fit cross-validated too: every fold's model scores the code
    form of the frame)."""
    if family == "binomial":
        cols, _, model = fitted
    else:
        cols = _columns(4, family=family)
        model = _gam(family=family, nfolds=2, seed=1).train(_frame(cols))
        assert model.cross_validation_metrics.r2 > 0.5
    v, b0, lin = _functions(model)
    want = ref.fit(cols, cols["y"], SMOOTHS, ["z"], family=family)
    for name in SMOOTHS:
        np.testing.assert_allclose(v[name], want["v"][name], atol=FIT_ATOL)
    assert b0 == pytest.approx(want["intercept"], abs=FIT_ATOL)
    assert lin["z"] == pytest.approx(want["linear"]["z"], abs=FIT_ATOL)
    eta = np.full(N, b0) + lin["z"] * cols["z"]
    for m in model.output["gam_meta"]:
        eta += ref.cardinal(cols[m["name"]], m["knots"]) @ v[m["name"]]
    np.testing.assert_allclose(eta, want["eta"], atol=FIT_ATOL * 10)
    got = ref.optimality(cols, cols["y"], SMOOTHS, v, b0, lin, family=family)
    assert got["score"] < SCORE_ATOL and got["centring"] < SCORE_ATOL, got


def test_predict_on_a_new_frame_equals_reference(fitted):
    """``predict`` of a frame the model never saw, with a missing value,
    against the reference's functions on it (a missing value imputed by
    the training mean, a value past the knots held at the end's)."""
    cols, _, model = fitted
    new = _columns(9, n=500)
    new["a"][:3] = [np.nan, 9.0, -9.0]
    got = model.predict(_frame(new)).vec("yes").to_numpy()
    v, b0, lin = _functions(model)
    eta = np.full(500, b0) + lin["z"] * new["z"]
    for m in model.output["gam_meta"]:
        x = np.where(np.isnan(new[m["name"]]), np.mean(cols[m["name"]]),
                     new[m["name"]])
        eta += ref.cardinal(x, m["knots"]) @ v[m["name"]]
    np.testing.assert_allclose(got, 1 / (1 + np.exp(-eta)), atol=1e-5)


ONE = dict(gam_columns=["a", "b"], num_knots=8, scale=0.7, bs="cr",
           family="gaussian", response_column="y")


@pytest.fixture(scope="module")
def with_categorical(cl):
    """A gaussian frame with a categorical predictor beside the smooths."""
    cols = _columns(11, n=1000, family="gaussian")
    cols["c"] = np.random.default_rng(11).integers(0, 4, 1000) \
        .astype(np.int32)
    cols["y"] = cols["y"] + 0.5 * cols["c"]
    return Frame.from_numpy(cols, types={"c": T_CAT},
                            domains={"c": ["p", "q", "r", "s"]})


@pytest.mark.parametrize("listed", ["num_knots", "scale", "bs"])
def test_one_entry_a_column_equals_one_number(with_categorical, listed):
    """H2O-3's per-column form: one entry per gam column, all alike, fits
    what the one number does, bit for bit."""
    fr = with_categorical
    each = dict(ONE)
    each[listed] = [ONE[listed]] * 2 if listed != "bs" else [0, 0]
    a = GAM(**ONE).train(fr)
    b = GAM(**each).train(fr)
    np.testing.assert_array_equal(
        dkv.get(a.output["glm_key"]).output["beta_std"],
        dkv.get(b.output["glm_key"]).output["beta_std"])
    with pytest.raises(ValueError, match="one per gam_columns"):
        GAM(response_column="y", gam_columns=["a", "b"],
            num_knots=[8, 8, 8]).train(fr)


def _observed(name):
    """{label values: observations} of a series of the registry."""
    return {tuple(sorted(m["l"].items())): m["n_obs"]
            for m in obs.metrics_wire() if m["n"] == name}


def test_a_refit_on_another_seed_compiles_nothing(cl, path_compiles):
    """The spline tables are operands: a fit on another frame of the same
    shape, whose knots all differ, takes the first fit's path program from
    the runner cache and compiles nothing at all.  It opens gam.knots,
    gam.basis and gam.glm once each, the GLM's spans beneath, and counts
    the basis columns its cr smooths add."""
    _gam().train(_frame(_columns(7)))
    once = path_compiles()
    assert sum(once[0].values()) <= 1
    hits = glm_mod._make_blocked_path_runner.cache_info().hits
    compiles, spans = _observed("jax_compile_seconds"), \
        _observed("span_seconds")
    columns = obs.counter("gam_basis_columns_total", bs="cr").value
    second = _gam().train(_frame(_columns(8)))
    assert _observed("jax_compile_seconds") == compiles
    assert path_compiles() == once
    assert glm_mod._make_blocked_path_runner.cache_info().hits == hits + 1
    assert second.output["coded"]
    grew = {k[0][1]: v - spans.get(k, 0)
            for k, v in _observed("span_seconds").items()}
    for span in ("gam.knots", "gam.basis", "gam.glm", "glm.wait",
                 "glm.finalize"):
        assert grew[span] == 1, span
    assert obs.counter("gam_basis_columns_total", bs="cr").value \
        == columns + 9 + 6


def test_a_categorical_beside_the_smooths_equals_the_host_path(
        with_categorical, monkeypatch):
    """A layout with a one-hot block beside the spline runs (the Gram's
    parts and the score put back in the layout's order) fits what the host
    path fits on the uploaded basis columns."""
    coded = GAM(**ONE).train(with_categorical)
    assert ("cat", 4) in dkv.get(coded.output["glm_key"]).datainfo \
        .coded_layout()
    monkeypatch.setattr(GAM, "_coded", lambda *a: False)
    host = GAM(**ONE).train(with_categorical)
    assert not host.output.get("coded")
    want = dkv.get(host.output["glm_key"]).coef
    for name, value in dkv.get(coded.output["glm_key"]).coef.items():
        assert value == pytest.approx(want[name], abs=1e-4), name
