"""GLM tests — golden comparisons against sklearn/numpy closed forms.

Mirrors the reference's pyunit_glm* strategy (h2o-py/tests/testdir_algos/glm):
coefficient recovery on synthetic data, family sanity, regularization,
weights, CV, and predict/save/load roundtrips.
"""

import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import Frame
from h2o3_tpu.models import GLM, GLMParameters


def _make_regression(rng, n=4000, p=5, noise=0.1):
    X = rng.normal(size=(n, p))
    beta = np.arange(1, p + 1, dtype=np.float64)
    y = X @ beta + 2.5 + noise * rng.normal(size=n)
    cols = {f"x{j}": X[:, j] for j in range(p)}
    cols["y"] = y
    return Frame.from_numpy(cols), beta


def _make_logistic(rng, n=4000, p=4):
    X = rng.normal(size=(n, p))
    beta = np.array([1.5, -2.0, 0.8, 0.0])
    logits = X @ beta - 0.5
    y = (rng.random(n) < 1 / (1 + np.exp(-logits))).astype(int)
    cols = {f"x{j}": X[:, j] for j in range(p)}
    cols["y"] = np.array(["no", "yes"], dtype=object)[y]
    return Frame.from_numpy(cols), X, y


def test_glm_ordinal_proportional_odds(cl, rng):
    """family=ordinal recovers latent slopes AND the true cutpoints."""
    n = 3000
    x1, x2 = rng.normal(size=n), rng.normal(size=n)
    latent = 1.5 * x1 - 1.0 * x2 + rng.logistic(size=n)
    yi = np.digitize(latent, [-1.5, 0.5, 2.0])
    labels = np.array(["lvl0", "lvl1", "lvl2", "lvl3"], dtype=object)[yi]
    fr = Frame.from_numpy({"x1": x1, "x2": x2, "y": labels})
    m = GLM(response_column="y", family="ordinal").train(fr)
    beta = dict(zip(m.output["coef_names"], m.output["beta_std"]))
    assert beta["x1"] == pytest.approx(1.5, abs=0.25)
    assert beta["x2"] == pytest.approx(-1.0, abs=0.25)
    th = m.output["ordinal_thresholds"]
    assert np.all(np.diff(th) > 0)
    np.testing.assert_allclose(th, [-1.5, 0.5, 2.0], atol=0.3)
    pred = m.predict(fr)
    probs = np.stack([pred.vec(c).to_numpy()
                      for c in ["lvl0", "lvl1", "lvl2", "lvl3"]], axis=1)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-4)
    acc = (pred.vec("predict").decoded() == labels).mean()
    assert acc > 0.45                    # 4 ordered classes, noisy latent
    with pytest.raises(ValueError, match="ordered levels"):
        GLM(response_column="x1", family="ordinal").train(fr)


def test_gaussian_matches_ols(cl, rng):
    fr, beta_true = _make_regression(rng)
    m = GLM(family="gaussian", lambda_=0.0, response_column="y").train(fr)
    coef = m.coef
    for j, b in enumerate(beta_true):
        assert abs(coef[f"x{j}"] - b) < 0.05, (j, coef)
    assert abs(coef["Intercept"] - 2.5) < 0.05
    assert m.training_metrics.r2 > 0.99


def test_binomial_matches_sklearn(cl, rng):
    from sklearn.linear_model import LogisticRegression
    fr, X, y = _make_logistic(rng)
    m = GLM(family="binomial", lambda_=0.0, response_column="y",
            max_iterations=100).train(fr)
    sk = LogisticRegression(penalty=None, max_iter=1000).fit(X, y)
    coef = m.coef
    for j in range(X.shape[1]):
        assert abs(coef[f"x{j}"] - sk.coef_[0, j]) < 0.05, (coef, sk.coef_)
    assert abs(coef["Intercept"] - sk.intercept_[0]) < 0.05
    assert m.training_metrics.auc > 0.85


def test_binomial_auc_against_sklearn(cl, rng):
    from sklearn.metrics import roc_auc_score
    fr, X, y = _make_logistic(rng)
    m = GLM(family="binomial", lambda_=0.0, response_column="y").train(fr)
    preds = m.predict(fr)
    p1 = preds.vec("yes").to_numpy()
    sk_auc = roc_auc_score(y, p1)
    assert abs(m.training_metrics.auc - sk_auc) < 0.01


def test_lasso_sparsifies(cl, rng):
    n, p = 2000, 10
    X = rng.normal(size=(n, p))
    y = 3 * X[:, 0] - 2 * X[:, 1] + 0.05 * rng.normal(size=n)
    cols = {f"x{j}": X[:, j] for j in range(p)}
    cols["y"] = y
    fr = Frame.from_numpy(cols)
    m = GLM(family="gaussian", alpha=1.0, lambda_=0.5,
            response_column="y").train(fr)
    coef = np.array([m.coef[f"x{j}"] for j in range(p)])
    assert np.sum(np.abs(coef) > 1e-6) <= 4          # mostly zeroed
    assert abs(coef[0]) > 1.0 and abs(coef[1]) > 0.5  # signal survives


def test_poisson(cl, rng):
    n = 3000
    x = rng.normal(size=n)
    lam = np.exp(0.7 * x + 1.0)
    y = rng.poisson(lam)
    fr = Frame.from_numpy({"x": x, "y": y.astype(float)})
    m = GLM(family="poisson", lambda_=0.0, response_column="y").train(fr)
    assert abs(m.coef["x"] - 0.7) < 0.05
    assert abs(m.coef["Intercept"] - 1.0) < 0.05


def test_gamma(cl, rng):
    n = 4000
    x = rng.normal(size=n)
    mu = np.exp(0.5 * x + 0.3)
    shape = 5.0
    y = rng.gamma(shape, mu / shape)
    fr = Frame.from_numpy({"x": x, "y": y})
    m = GLM(family="gamma", lambda_=0.0, response_column="y",
            max_iterations=100).train(fr)
    assert abs(m.coef["x"] - 0.5) < 0.1
    assert abs(m.coef["Intercept"] - 0.3) < 0.1


def test_multinomial(cl, rng):
    n = 3000
    centers = np.array([[2, 0], [-2, 1], [0, -2]])
    labels = rng.integers(0, 3, n)
    X = centers[labels] + rng.normal(size=(n, 2))
    fr = Frame.from_numpy({
        "x0": X[:, 0], "x1": X[:, 1],
        "y": np.array(["a", "b", "c"], dtype=object)[labels]})
    m = GLM(family="multinomial", lambda_=0.0, response_column="y").train(fr)
    assert m.training_metrics.accuracy > 0.85
    preds = m.predict(fr)
    assert preds.names == ["predict", "a", "b", "c"]
    probs = np.stack([preds.vec(c).to_numpy() for c in "abc"], axis=1)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-5)


def test_categorical_features_and_weights(cl, rng):
    n = 2000
    g = np.array(["u", "v", "w"], dtype=object)[rng.integers(0, 3, n)]
    x = rng.normal(size=n)
    eff = {"u": 0.0, "v": 1.0, "w": -1.0}
    y = x + np.array([eff[s] for s in g]) + 0.1 * rng.normal(size=n)
    fr = Frame.from_numpy({"g": g, "x": x, "y": y,
                           "wt": np.ones(n)})
    m = GLM(family="gaussian", lambda_=0.0, response_column="y",
            weights_column="wt").train(fr)
    # v and w effects relative to base level u
    assert abs(m.coef["g.v"] - 1.0) < 0.05
    assert abs(m.coef["g.w"] + 1.0) < 0.05
    assert m.training_metrics.r2 > 0.98


def test_cv_and_validation(cl, rng):
    fr, X, y = _make_logistic(rng, n=2500)
    train, valid = fr.split_frame([0.75], seed=7)
    m = GLM(family="binomial", lambda_=0.0, response_column="y",
            nfolds=3, seed=42).train(train, valid=valid)
    assert m.cross_validation_metrics is not None
    assert m.cross_validation_metrics.auc > 0.8
    assert m.validation_metrics.auc > 0.8
    assert len(m.output["cv_fold_models"]) == 3


def test_predict_save_load(cl, rng, tmp_path):
    fr, X, y = _make_logistic(rng, n=1000)
    m = GLM(family="binomial", lambda_=0.0, response_column="y").train(fr)
    preds = m.predict(fr)
    assert preds.names == ["predict", "no", "yes"]
    assert preds.nrows == fr.nrows
    path = m.save(str(tmp_path / "glm.bin"))
    h2o3_tpu.remove(m.key)
    m2 = h2o3_tpu.Model.load(path) if hasattr(h2o3_tpu, "Model") else None
    from h2o3_tpu.models import Model
    m2 = Model.load(path)
    p2 = m2.predict(fr)
    np.testing.assert_allclose(p2.vec("yes").to_numpy(),
                               preds.vec("yes").to_numpy(), rtol=1e-5)


def test_lambda_search(cl, rng):
    fr, beta_true = _make_regression(rng, n=1500)
    m = GLM(family="gaussian", lambda_search=True, nlambdas=10, alpha=1.0,
            response_column="y").train(fr)
    assert m.training_metrics.r2 > 0.95   # smallest lambda ~ unpenalized


def test_tweedie(cl, rng):
    n = 4000
    x = rng.normal(size=n)
    mu = np.exp(0.4 * x + 0.5)
    # tweedie p=1.5 via compound poisson-gamma simulation
    npois = rng.poisson(mu)
    y = np.array([rng.gamma(s, 1.0) if s > 0 else 0.0 for s in npois])
    fr = Frame.from_numpy({"x": x, "y": y})
    m = GLM(family="tweedie", tweedie_variance_power=1.5, lambda_=0.0,
            response_column="y", max_iterations=100).train(fr)
    assert abs(m.coef["x"] - 0.4) < 0.15


def test_lambda_path_fused_matches_host(cl):
    """The fused device lambda path must land where per-lambda host
    solves land (same warm-started IRLS/COD math, one program)."""
    import numpy as np
    from h2o3_tpu import Frame
    from h2o3_tpu.models import GLM
    rng = np.random.default_rng(8)
    n, d = 2000, 6
    X = rng.normal(size=(n, d)).astype(np.float32)
    beta = np.array([1.5, -1.0, 0.5, 0.0, 0.0, 0.0])
    yy = rng.random(n) < 1 / (1 + np.exp(-(X @ beta - 0.3)))
    cols = {f"x{j}": X[:, j] for j in range(d)}
    cols["y"] = np.where(yy, "1", "0").astype(object)
    fr = Frame.from_numpy(cols)
    m = GLM(response_column="y", family="binomial", lambda_search=True,
            nlambdas=12, alpha=0.5, seed=1).train(fr)
    # solved path: final (smallest-lambda) coefficients recover the truth
    coefs = m.coef
    assert abs(coefs["x0"]) > 0.8 and abs(coefs["x3"]) < 0.25
    assert len(m.scoring_history) == 12
    # per-lambda host solves at the path's own lambdas agree at the end
    m_host = GLM(response_column="y", family="binomial",
                 lambda_=[float(h["lambda"]) for h in m.scoring_history][-1],
                 alpha=0.5, seed=1).train(fr)
    for name in ("x0", "x1", "x2"):
        assert np.isclose(coefs[name], m_host.coef[name], atol=5e-3), name


# ------------------------------------------ one path program a signature
def _counts_frame(seed, n=600):
    """Three numerics and a positive count response, which every family
    below can fit."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 3))
    y = rng.poisson(np.exp(0.4 * X[:, 0] - 0.3 * X[:, 1] + 0.5)) + 0.5
    return {"x0": X[:, 0], "x1": X[:, 1], "x2": X[:, 2], "y": y}


def _rise(before, after):
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


@pytest.mark.parametrize("params", [
    dict(family="gaussian", lambda_=0.0),
    dict(family="poisson", lambda_=1e-3, alpha=0.5),
], ids=["l2", "l1"])
def test_a_second_fit_reuses_the_path_program(cl, path_compiles, params):
    """The dense design: the first fit traces, lowers and compiles the path
    program once; the second, of the same signature, dispatches it and
    gives the same coefficients bit for bit."""
    fr = Frame.from_numpy(_counts_frame(1))
    first = GLM(response_column="y", **params).train(fr)
    once = path_compiles()
    assert sum(once[0].values()) == 1 and once[1] > 0
    second = GLM(response_column="y", **params).train(fr)
    assert path_compiles() == once
    np.testing.assert_array_equal(second.output["beta_std"],
                                  first.output["beta_std"])


@pytest.mark.parametrize("a,b", [
    (dict(family="tweedie", tweedie_variance_power=1.2),
     dict(family="tweedie", tweedie_variance_power=1.6)),
    (dict(family="negativebinomial", theta=0.5),
     dict(family="negativebinomial", theta=2.0)),
    (dict(family="poisson", max_iterations=3),
     dict(family="poisson", max_iterations=4)),
    (dict(family="poisson", lambda_=1e-3, alpha=0.0),
     dict(family="poisson", lambda_=1e-3, alpha=0.5)),
], ids=["tweedie_power", "nb_theta", "max_iterations", "l1_on"])
def test_fits_that_differ_get_their_own_program(cl, path_compiles, a, b):
    """Arguments of one shape, a program each: a fit that differs in what
    the path program closes over compiles its own, and a fit like the
    first takes the first's again, with its coefficients bit for bit."""
    fr = Frame.from_numpy(_counts_frame(2))
    kw = dict(response_column="y", lambda_=0.0)
    first = GLM(**{**kw, **a}).train(fr)
    one = path_compiles()[0]
    other = GLM(**{**kw, **b}).train(fr)
    two = path_compiles()[0]
    again = GLM(**{**kw, **a}).train(fr)
    assert sum(_rise(one, two).values()) == 1
    assert path_compiles()[0] == two
    assert not np.array_equal(other.output["beta_std"],
                              first.output["beta_std"])
    np.testing.assert_array_equal(again.output["beta_std"],
                                  first.output["beta_std"])


def test_families_are_equal_by_class_and_parameters():
    from h2o3_tpu.models import glm
    fam = glm._make_family
    p = GLMParameters
    assert fam("tweedie", p(tweedie_variance_power=1.5)) \
        == fam("tweedie", p(tweedie_variance_power=1.5))
    assert len({fam("tweedie", p(tweedie_variance_power=1.5)),
                fam("tweedie", p(tweedie_variance_power=1.6)),
                fam("negativebinomial", p(theta=1.0)),
                fam("negativebinomial", p(theta=1.0)),
                fam("binomial", p()), fam("binomial", p()),
                fam("quasibinomial", p())}) == 5


def test_a_rebuilt_mesh_compiles_the_path_program_again(cl, path_compiles):
    """``cluster.init`` with another geometry clears the runners: the next
    fit compiles with ``reason="cluster_reinit"``, on one device and again
    back on the mesh, where it gives the first fit's coefficients."""
    import jax
    from h2o3_tpu.models import glm
    cols = _counts_frame(3)

    def fit():
        return np.asarray(GLM(family="poisson", lambda_=0.0,
                              response_column="y").train(
            Frame.from_numpy(cols)).output["beta_std"])

    first = fit()
    start = path_compiles()[0]
    try:
        h2o3_tpu.init(devices=jax.devices()[:1])
        assert glm._make_path_runner.cache_info().currsize == 0
        one = fit()
        mid = path_compiles()[0]
    finally:
        h2o3_tpu.init(devices=jax.devices())
    assert glm._make_path_runner.cache_info().currsize == 0
    again = fit()
    assert _rise(start, mid) == {"cluster_reinit": 1}
    assert _rise(mid, path_compiles()[0]) == {"cluster_reinit": 1}
    np.testing.assert_allclose(one, first, atol=1e-5)
    np.testing.assert_array_equal(again, first)
