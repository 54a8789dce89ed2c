"""GLM's IRLSM on the code-form design (categoricals as codes, one block of
rows expanded at a time) against the plain reference
(``models/reference_glm.py``) on the dense expansion: one pass's Gram,
X'Wz and deviance, whole fits, blocked scoring, p-values, one device
against the mesh, and a frame whose dense design passes the device.
``GLM.train`` takes the code form only where the dense design would not fit
the device (``glm._dense_design_fits``), so the tests that go through it
shrink the device the code reads (``small_device``), never a parameter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import Frame
from h2o3_tpu.frame.vec import T_CAT
from h2o3_tpu.models import datainfo, glm, glm_gram
from h2o3_tpu.models import reference_glm as ref
from h2o3_tpu.models.datainfo import DataInfo
from h2o3_tpu.models.glm import GLM, GLMParameters

DOMAINS = {"c5": list("abcde"), "c9": [f"L{i}" for i in range(9)]}

# float32 sums over a few hundred rows in another order (blocks, then the
# mesh's shards) than the reference's one product: a few ulp of the summed
# absolute terms; every comparison of sums below is relative to that
SUM_RTOL = 2e-5


def _frame(seed, n=600, family="binomial", na=True, domains=DOMAINS,
           weights=False):
    """Two numerics and two categoricals around each other, NA in both
    kinds (if asked), a response that depends on all four."""
    rng = np.random.default_rng(seed)
    cols = {"x0": rng.normal(size=n).astype(np.float32),
            "c5": rng.integers(0, len(domains["c5"]), n).astype(np.int32),
            "x1": rng.normal(2.0, 3.0, size=n).astype(np.float32),
            "c9": rng.integers(0, len(domains["c9"]), n).astype(np.int32)}
    eta = 0.8 * cols["x0"] + 0.3 * (cols["c5"] % 2) - 0.2 * (cols["c9"] % 3) \
        + 0.1 * (cols["x1"] - 2.0)
    cat = dict(domains)
    if family == "binomial":
        cols["y"] = (eta + rng.logistic(size=n) > 0).astype(np.int32)
        cat["y"] = ["no", "yes"]
    elif family == "poisson":
        cols["y"] = rng.poisson(np.exp(0.5 * eta)).astype(np.float32)
    else:
        cols["y"] = (eta + 0.5 * rng.normal(size=n)).astype(np.float32)
    if weights:
        cols["wt"] = rng.uniform(0.5, 2.0, n).astype(np.float32)
    if na:
        cols["x0"][::41] = np.nan
        cols["c5"][::29] = -1
        cols["c9"][::53] = -1
    return Frame.from_numpy(cols, types={k: T_CAT for k in cat}, domains=cat)


def _unseen_frame(seed, n=200, **kw):
    """A frame whose ``c5`` has another order and a level the training
    frame never had."""
    return _frame(seed, n, domains=dict(DOMAINS, c5=["e", "zz", "a", "c", "b", "d"]),
                  **kw)


def _dense(di, fr):
    """The frame's dense rows, expanded from the code form (equal to
    ``make_matrix``'s: tests/test_dl_coded.py), response and weights."""
    n = fr.nrows
    return (np.asarray(datainfo.expand_coded(di.coded_layout(),
                                             *di.make_coded(fr)))[:n],
            np.nan_to_num(np.asarray(di.response(fr))[:n]),
            np.asarray(di.weights(fr))[:n])


def _blocked_pass(di, fr, beta, family, block):
    """(Gram, X's, deviance) of the system's one IRLS pass over row blocks
    of ``block`` rows a shard."""
    fam = glm._make_family(family, GLMParameters())
    irls_gram = jax.jit(glm._make_irls_gram(fam, di.coded_layout(), block))
    y = jnp.nan_to_num(di.response(fr))
    return irls_gram(*di.make_coded(fr), y, di.weights(fr), jnp.zeros_like(y),
                     jnp.asarray(beta, jnp.float32))


def _force_gram(monkeypatch, kernel):
    """``kernel="pallas"``: the Gram kernel engages off the TPU as it does on
    one, wherever the layout has a one-hot block, and runs in Pallas'
    interpreter (``glm_gram.gram_parts``); ``"xla"``: what the CPU runs.
    The path runner's cache knows the kernel by the layout and the mesh, so
    a forced kernel builds its runners past it."""
    if kernel == "pallas":
        monkeypatch.setattr(glm_gram, "engages",
                            lambda layout: glm_gram._plan(layout) is not None)
        monkeypatch.setattr(glm, "_make_blocked_path_runner",
                            glm._make_blocked_path_runner.__wrapped__)


@pytest.fixture()
def small_device(monkeypatch):
    """A device of 16 KB, as the code reads it: no frame of these tests has a
    dense design that fits a quarter of it, so ``GLM.train`` and scoring take
    the code form (a block is 1,024 rows, the least ``block_rows`` gives).  At
    the memory the CPU is assumed to have they would all take the dense
    design, as a frame that fits a chip does."""
    monkeypatch.setattr(datainfo, "device_memory_bytes", lambda: 1 << 14)
    monkeypatch.setattr(glm, "device_memory_bytes", lambda: 1 << 14)
    monkeypatch.setattr(DataInfo, "make_matrix", lambda *a, **k: pytest.fail(
        "the dense design was built"))
    glm._make_score.cache_clear()
    yield
    glm._make_score.cache_clear()


def _assert_sums_close(got, want, terms):
    """``got`` equals ``want`` to the float32 summation tolerance of sums
    whose absolute terms add up to ``terms``."""
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=SUM_RTOL * float(np.max(np.abs(terms))))


# ------------------------------------------------------- (a) one IRLS pass
@pytest.mark.parametrize("standardize", [True, False])
@pytest.mark.parametrize("all_levels", [False, True])
@pytest.mark.parametrize("which", ["clean", "na_unseen"])
@pytest.mark.parametrize("rows", [640, 601])    # 640: 8 shards x 2 blocks of 40
@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_blocked_pass_equals_reference(cl, monkeypatch, standardize,
                                       all_levels, which, rows, kernel):
    _force_gram(monkeypatch, kernel)
    train = _frame(1, rows, na=False)
    di = DataInfo.fit(train, response_column="y", standardize=standardize,
                      use_all_factor_levels=all_levels)
    fr = train if which == "clean" else _unseen_frame(2, rows)
    X, y, w = _dense(di, fr)
    beta = np.random.default_rng(3).normal(0, 0.3, di.nfeatures).astype(np.float32)
    want_gram, want_xtwz, want_dev = ref.irls_stats(X, y, w, beta, 0.0, "binomial")
    gram, score, dev = _blocked_pass(di, fr, beta, "binomial", block=40)
    _assert_sums_close(gram, want_gram, np.abs(X).T @ np.abs(X))
    # X'Wz = X'WX beta + X's: the system keeps the score apart (glm.py)
    xtwz = np.asarray(gram, np.float64) @ beta + np.asarray(score)
    _assert_sums_close(xtwz, want_xtwz, np.abs(X).T @ (np.abs(X) @ np.abs(beta) + 1.0))
    np.testing.assert_allclose(float(dev), float(want_dev), rtol=SUM_RTOL)
    if which == "na_unseen":
        # the unseen level and the NAs light each block's last column
        c5 = next(s for s in di.specs if s.name == "c5")
        assert np.asarray(gram)[c5.offset + c5.width - 1,
                                c5.offset + c5.width - 1] > 0


def _coded_frame(seed, n, widths, numerics):
    """``numerics`` normal columns and categoricals of ``widths`` levels,
    NA in both kinds, a binary response that depends on each."""
    rng = np.random.default_rng(seed)
    cols, domains, eta = {}, {"y": ["no", "yes"]}, np.zeros(n)
    for i in range(numerics):
        cols[f"x{i}"] = rng.normal(size=n).astype(np.float32)
        eta += 0.5 * cols[f"x{i}"]
        cols[f"x{i}"][::37] = np.nan
    for i, width in enumerate(widths):
        domains[f"c{i}"] = [f"L{k}" for k in range(width)]
        cols[f"c{i}"] = rng.integers(0, width, n).astype(np.int32)
        eta += rng.normal(0, 0.5, width)[cols[f"c{i}"]]
        cols[f"c{i}"][::31 + i] = -1
    cols["y"] = (eta + rng.logistic(size=n) > 0).astype(np.int32)
    return Frame.from_numpy(cols, types={k: T_CAT for k in domains},
                            domains=domains)


@pytest.mark.parametrize("widths,numerics,all_levels", [
    ((3, 22, 130), 2, False),       # three pieces of the later blocks packed
    ((130,), 0, False),             # one categorical and the intercept
    ((22, 3), 0, True),
    ((60, 130, 5), 1, True),        # 130 meets 60 + 5 in a piece a product
], ids=["3_22_130", "one_cat", "cats_only_all_levels", "60_130_5"])
def test_gram_kernel_equals_xla_and_reference(cl, monkeypatch, widths,
                                              numerics, all_levels):
    """The Gram kernel (Pallas' interpreter, the 8-shard mesh) against the
    XLA product and the reference, on layouts whose widths are no multiple
    of 128; 601 rows in blocks of 40 a shard, the last laid back over
    zero-weight rows and the frame's padding."""
    fr = _coded_frame(17, 601, widths, numerics)
    di = DataInfo.fit(fr, response_column="y",
                      use_all_factor_levels=all_levels)
    X, y, w = _dense(di, fr)
    beta = np.random.default_rng(3).normal(0, 0.3, di.nfeatures).astype(np.float32)
    want, _, _ = ref.irls_stats(X, y, w, beta, 0.0, "binomial")
    xla = _blocked_pass(di, fr, beta, "binomial", block=40)
    _force_gram(monkeypatch, "pallas")
    assert glm_gram.engages(di.coded_layout())
    got = _blocked_pass(di, fr, beta, "binomial", block=40)
    terms = np.abs(X).T @ np.abs(X)
    _assert_sums_close(got[0], xla[0], terms)
    _assert_sums_close(got[0], want, terms)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(xla[1]))


def test_gram_kernel_plan():
    """The airlines layout's plan: the 300-level groups first, the 22-level
    block a later side only, whose three pieces share one 128-lane product;
    a layout whose accumulators pass the VMEM budget has no plan and keeps
    the XLA product."""
    plan = glm_gram._plan((("num", 5), ("cat", 22), ("cat", 300),
                           ("cat", 300), ("one", 1)))
    assert [(g.cat, g.rows, g.lanes, g.packed) for g in plan.groups] == [
        (1, 304, 384, False), (2, 304, 128, True), (0, 32, 0, False)]
    assert (plan.n_other, plan.side_rows, plan.tile) == (6, 32, 1024)
    assert glm_gram._plan((("cat", 3000), ("cat", 3000))) is None
    assert glm_gram._plan((("num", 3), ("one", 1))) is None


# ------------------------------------------------------------ (b) the fit
@pytest.mark.parametrize("family", ["binomial", "gaussian", "poisson"])
def test_fit_equals_reference(cl, small_device, family):
    fr = _frame(4, 900, family, weights=True)
    model = GLM(family=family, lambda_=0.0, response_column="y",
                weights_column="wt").train(fr)
    di = model.datainfo
    X, y, w = _dense(di, fr)
    betas, devs, passes = ref.fit(X, y, w, None, family, [0.0], alpha=0.5)
    # both stop when no coefficient moves by beta_epsilon = 1e-5: what is
    # left of each one's last step, and float32 solves of another form (the
    # system solves for the Newton step), are inside ten times that
    np.testing.assert_allclose(model.output["beta_std"], betas[-1], atol=1e-4)
    # the deviance is read one pass BEFORE the last update, in both
    np.testing.assert_allclose(model.output["residual_deviance"], devs[-1],
                               rtol=1e-4)
    assert abs(model.scoring_history[-1]["iteration"] - passes[-1]) <= 1


def test_elastic_net_path_equals_reference(cl, small_device):
    fr = _frame(5, 900, "binomial")
    lambdas = [0.05, 0.02, 0.01, 0.003, 0.001]
    model = GLM(family="binomial", lambda_=lambdas, alpha=0.5,
                response_column="y").train(fr)
    di = model.datainfo
    X, y, w = _dense(di, fr)
    betas, devs, _ = ref.fit(X, y, w, None, "binomial", lambdas, alpha=0.5)
    # coordinate descent stops at 1e-8 a sweep in both, IRLS at 1e-5
    np.testing.assert_allclose(model.output["beta_std"], betas[-1], atol=2e-4)
    assert (np.abs(betas[0]) < 1e-7).sum() >= 3     # the path starts sparse
    got = [h["deviance"] for h in model.scoring_history]
    np.testing.assert_allclose(got, devs, rtol=2e-4)


# ------------------------------------------------- (c) one block, many blocks
@pytest.mark.parametrize("family", ["binomial", "poisson"])
def test_one_block_equals_many(cl, family):
    fr = _frame(6, 1000, family)
    di = DataInfo.fit(fr, response_column="y")
    X, _, _ = _dense(di, fr)
    beta = np.random.default_rng(7).normal(0, 0.2, di.nfeatures).astype(np.float32)
    rows = fr.padded_rows // cl.n_row_shards
    one = _blocked_pass(di, fr, beta, family, block=rows)
    many = _blocked_pass(di, fr, beta, family, block=24)    # the last laid back
    assert rows % 24
    _assert_sums_close(many[0], one[0], np.abs(X).T @ np.abs(X))
    _assert_sums_close(many[1], one[1], np.abs(X).sum(0))
    np.testing.assert_allclose(float(many[2]), float(one[2]), rtol=SUM_RTOL)


# ------------------------------------------------------------ (d) scoring
def test_predict_and_performance_equal_reference_with_unseen_level(cl, small_device):
    fr = _frame(8, 700)
    model = GLM(family="binomial", lambda_=0.0, response_column="y").train(fr)
    on = _unseen_frame(9, 333)
    di = model.datainfo
    X, y, w = _dense(di, on)
    want = np.asarray(ref.predict(X, model.output["beta_std"], "binomial"))
    got = model.predict(on)
    np.testing.assert_allclose(got.vec("yes").to_numpy(), want, atol=2e-6)
    np.testing.assert_allclose(got.vec("no").to_numpy(), 1.0 - want, atol=2e-6)
    perf = model.model_performance(on)
    p = np.clip(want.astype(np.float64), 1e-15, 1 - 1e-15)
    logloss = -np.sum(w * (y * np.log(p) + (1 - y) * np.log1p(-p))) / w.sum()
    np.testing.assert_allclose(perf.logloss, logloss, rtol=1e-5)
    # and in blocks: 20,000 rows are 2,500 a shard, three blocks of 1,024
    # with the last laid back
    big = _unseen_frame(10, 20_000)
    Xb, _, _ = _dense(di, big)
    assert big.padded_rows // cl.n_row_shards > 2 * 1024
    assert isinstance(model._score_matrix(big), datainfo.CodedDesign)
    got = np.asarray(model._predict_raw(model._score_matrix(big)))[:20_000, 1]
    np.testing.assert_allclose(
        got, np.asarray(ref.predict(Xb, model.output["beta_std"], "binomial")),
        atol=2e-6)


# ----------------------------------------------------------- (e) p-values
def test_p_values_come_from_the_final_gram(cl, small_device):
    from scipy.stats import norm
    fr = _frame(11, 900, na=False)
    model = GLM(family="binomial", lambda_=0.0, response_column="y",
                compute_p_values=True).train(fr)
    di = model.datainfo
    X, y, w = _dense(di, fr)
    beta = np.asarray(model.output["beta_std"], np.float32)
    gram, _, _ = ref.irls_stats(X, y, w, beta, 0.0, "binomial")
    _assert_sums_close(model.output["gram"], gram, np.abs(X).T @ np.abs(X))
    # the NA columns of a frame without NAs are zero rows of the Gram:
    # standard errors of the columns some row lights
    lit = np.abs(X).sum(0) > 0
    se = np.sqrt(np.diag(np.linalg.inv(np.asarray(gram, np.float64)[lit][:, lit])))
    np.testing.assert_allclose(model.output["std_errs"][lit], se, rtol=1e-3)
    z = beta[lit] / se
    np.testing.assert_allclose(model.output["p_values"][lit],
                               2 * (1 - norm.cdf(np.abs(z))), atol=1e-4)


# ------------------------------------------------- (f) the mesh, one device
@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_mesh_equals_one_device(cl, small_device, monkeypatch, kernel):
    _force_gram(monkeypatch, kernel)
    cols_fr = _frame(12, 1000, weights=True)

    def fit_and_pass():
        fr = Frame.from_numpy(
            {n: cols_fr.vec(n).to_numpy() for n in cols_fr.names},
            types={"c5": T_CAT, "c9": T_CAT, "y": T_CAT},
            domains=dict(DOMAINS, y=["no", "yes"]))
        model = GLM(family="binomial", lambda_=0.0, response_column="y",
                    weights_column="wt").train(fr)
        return (np.asarray(model.output["beta_std"]), model.output["gram"],
                np.asarray(model.predict(fr).vec("yes").to_numpy()))

    assert cl.n_row_shards > 1
    mesh = fit_and_pass()
    try:
        h2o3_tpu.init(devices=jax.devices()[:1])
        one = fit_and_pass()
    finally:
        h2o3_tpu.init(devices=jax.devices())
    np.testing.assert_allclose(mesh[0], one[0], atol=2e-5)
    np.testing.assert_allclose(mesh[1], one[1], rtol=0,
                               atol=SUM_RTOL * np.abs(one[1]).max())
    np.testing.assert_allclose(mesh[2], one[2], atol=1e-5)


# ------------------------------------ (g) a design wider than the device
def _wide_frame(seed, n=2000, levels=150):
    rng = np.random.default_rng(seed)
    domains = {"a": [f"a{i}" for i in range(levels)],
               "b": [f"b{i}" for i in range(levels)], "y": ["no", "yes"]}
    cols = {"x": rng.normal(size=n).astype(np.float32),
            "a": rng.integers(0, levels, n).astype(np.int32),
            "b": rng.integers(0, levels, n).astype(np.int32)}
    effect = rng.normal(0, 0.5, levels)
    eta = cols["x"] + effect[cols["a"]] - effect[cols["b"]]
    cols["y"] = (eta + rng.logistic(size=n) > 0).astype(np.int32)
    return Frame.from_numpy(cols, types={k: T_CAT for k in domains},
                            domains=domains)


def test_trains_where_the_dense_design_passes_the_device(cl, small_device):
    """2,000 rows x 302 columns is 2.4 MB dense (0.3 MB a shard of the
    mesh); the device is said to have 16 KB, so the dense design is never
    asked for (``small_device`` fails the test if it is) and a block of the
    walk is 1,024 rows at most."""
    fr = _wide_frame(13)
    model = GLM(family="binomial", lambda_=1e-3, alpha=0.0,
                response_column="y").train(fr)
    assert model.datainfo.nfeatures == 302
    assert model.training_metrics.auc > 0.75
    assert np.isfinite(model.output["beta_std"]).all()
    pred = model.predict(fr).vec("yes").to_numpy()
    assert pred.shape == (2000,) and np.isfinite(pred).all()


def test_a_frame_that_fits_takes_the_dense_design_and_agrees(cl, monkeypatch):
    """The same fit on the dense design (the frame fits the 4 GiB the CPU is
    assumed to have) and on the code form (a 16 KB device): two programs,
    one model."""
    fr = _frame(15, 900, weights=True)
    kw = dict(family="binomial", lambda_=0.0, response_column="y",
              weights_column="wt")
    dense = GLM(**kw).train(fr)
    assert not isinstance(dense._score_matrix(fr), datainfo.CodedDesign)
    monkeypatch.setattr(datainfo, "device_memory_bytes", lambda: 1 << 14)
    monkeypatch.setattr(glm, "device_memory_bytes", lambda: 1 << 14)
    coded = GLM(**kw).train(fr)
    assert isinstance(coded._score_matrix(fr), datainfo.CodedDesign)
    np.testing.assert_allclose(coded.output["beta_std"],
                               dense.output["beta_std"], atol=1e-4)
    np.testing.assert_allclose(coded.training_metrics.logloss,
                               dense.training_metrics.logloss, rtol=1e-5)
    np.testing.assert_allclose(coded.predict(fr).vec("yes").to_numpy(),
                               dense.predict(fr).vec("yes").to_numpy(),
                               atol=1e-5)


@pytest.mark.parametrize("params,what", [
    ({"solver": "l_bfgs"}, "solver=l_bfgs"),
    ({"non_negative": True}, "non_negative"),
])
def test_dense_solvers_refuse_a_design_that_passes_the_device(cl, monkeypatch,
                                                              params, what):
    fr = _wide_frame(14)
    monkeypatch.setattr(glm, "device_memory_bytes", lambda: 1 << 18)
    monkeypatch.setattr(datainfo, "device_memory_bytes", lambda: 1 << 18)
    monkeypatch.setattr(DataInfo, "make_matrix", lambda *a, **k: pytest.fail(
        "allocated before the refusal"))
    with pytest.raises(ValueError) as e:
        GLM(family="binomial", lambda_=0.0, response_column="y",
            **params).train(fr)
    message = str(e.value)
    assert what in message and "solver='irlsm'" in message
    assert f"{fr.padded_rows // cl.n_row_shards * 302 * 4:,} bytes" in message


# -------------------------------------- (h) which Gram a fit's passes form
def _gram_kernel_counts():
    from h2o3_tpu.runtime import observability as obs
    return {k: obs.counter("glm_gram_kernel_total", kernel=k).value
            for k in ("pallas", "xla")}


def _one_rise(before, after):
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


def test_gram_kernel_counter_blocked(cl, small_device, monkeypatch):
    """``glm_gram_kernel_total{kernel}``: one increment a fit of the blocked
    runner, ``pallas`` where the kernel forms its Grams (forced here, as a
    TPU engages it), ``xla`` off the TPU and for a frame with no
    categorical."""
    fr = _frame(18, 700)
    kw = dict(family="binomial", lambda_=0.0, response_column="y")
    before = _gram_kernel_counts()
    xla = GLM(**kw).train(fr)
    mid = _gram_kernel_counts()
    assert _one_rise(before, mid) == {"xla": 1}
    _force_gram(monkeypatch, "pallas")
    kernel = GLM(**kw).train(fr)
    after = _gram_kernel_counts()
    assert _one_rise(mid, after) == {"pallas": 1}
    np.testing.assert_allclose(kernel.output["beta_std"],
                               xla.output["beta_std"], atol=2e-5)
    # 2,000 rows x 3 columns do not fit the 16 KB device dense either
    GLM(**kw).train(_coded_frame(18, 2000, (), 2))
    assert _one_rise(after, _gram_kernel_counts()) == {"xla": 1}


def test_gram_kernel_counter_dense(cl, monkeypatch):
    """The dense design's program forms its Gram by XLA's product."""
    _force_gram(monkeypatch, "pallas")
    fr = _frame(19, 500)
    before = _gram_kernel_counts()
    model = GLM(family="binomial", lambda_=0.0, response_column="y").train(fr)
    assert not isinstance(model._score_matrix(fr), datainfo.CodedDesign)
    assert _one_rise(before, _gram_kernel_counts()) == {"xla": 1}


# --------------------------------- (i) one path program a signature
@pytest.mark.parametrize("params", [
    dict(lambda_=0.0), dict(lambda_=1e-3, alpha=0.5)], ids=["l2", "l1"])
def test_a_second_fit_reuses_the_path_program(cl, small_device, path_compiles,
                                              params):
    """The code form: the second fit of one signature compiles nothing and
    gives the first's coefficients bit for bit."""
    fr = _frame(20, 700)
    kw = dict(family="binomial", response_column="y", **params)
    first = GLM(**kw).train(fr)
    once = path_compiles()
    assert sum(once[0].values()) == 1 and once[1] > 0
    second = GLM(**kw).train(fr)
    assert path_compiles() == once
    np.testing.assert_array_equal(second.output["beta_std"],
                                  first.output["beta_std"])


def test_another_layout_of_the_same_shapes_gets_its_own_program(
        cl, small_device, path_compiles):
    """The two categoricals' widths swapped: the same arguments' shapes, a
    layout of its own and so a program of its own; the first frame again
    takes the first program."""
    a = _frame(21, 700)
    b = _frame(21, 700, domains={"c5": DOMAINS["c9"], "c9": DOMAINS["c5"]})
    kw = dict(family="binomial", lambda_=0.0, response_column="y")
    first = GLM(**kw).train(a)
    one = path_compiles()[0]
    other = GLM(**kw).train(b)
    two = path_compiles()[0]
    again = GLM(**kw).train(a)
    di_a, di_b = first.datainfo, other.datainfo
    assert di_a.coded_layout() != di_b.coded_layout()
    assert [x.shape for x in di_a.make_coded(a)] \
        == [x.shape for x in di_b.make_coded(b)]
    assert sum(two.values()) == sum(one.values()) + 1
    assert path_compiles()[0] == two
    np.testing.assert_array_equal(again.output["beta_std"],
                                  first.output["beta_std"])
