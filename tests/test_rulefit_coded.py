"""RuleFit on the code-form rule design against the plain reference
(``models/reference_rulefit.py``): the rule codes ``jit_rule_codes`` writes
against the reference's dense rule matrix, the lasso path on raw rule
columns with penalty factors against the reference's path on the
standardised dense design at every lambda, ``predict``, the two forms'
equal objectives, ``remove_duplicates`` and the generator's parameters."""

import jax.numpy as jnp
import numpy as np
import pytest

from h2o3_tpu import Frame
from h2o3_tpu.frame.vec import T_CAT
from h2o3_tpu.models import RuleFit
from h2o3_tpu.models import glm as glm_mod
from h2o3_tpu.models import reference_glm
from h2o3_tpu.models import reference_rulefit as ref
from h2o3_tpu.models import rulefit
from h2o3_tpu.models.datainfo import DataInfo
from h2o3_tpu.runtime import dkv
from h2o3_tpu.runtime import observability as obs

N = 900
FEATURES = ["x0", "x1", "x2"]

# The system stops coordinate descent where no move of a sweep changes a
# gradient entry by 1e-6 (glm.CD_TOLERANCE), the reference where no
# coefficient moves by 1e-8: on this frame the system's solutions meet the
# lasso's optimality conditions to 3.5e-6 at worst (the reference's to
# 1.1e-7), and the two objectives agree to the 3e-7 of their float32 sums
KKT_ATOL = 1e-5
OBJ_RTOL = 1e-6
# The probabilities are unique at the optimum, but the objective is nearly
# flat along directions of rules that light nearly the same rows: at the
# system's 3.5e-6 off optimality a few rows' probabilities stand up to
# 5.7e-4 from the reference's, most under 1e-4
MU_ATOL = 2e-3


def _columns(seed, n=N):
    rng = np.random.default_rng(seed)
    cols = {f: rng.normal(size=n).astype(np.float32) for f in FEATURES}
    cols["x2"][::37] = np.nan
    eta = (1.5 * ((cols["x0"] > 0.3) & (cols["x1"] < 0.5))
           - 1.0 * (np.nan_to_num(cols["x2"]) > 1.0) + 0.5 * cols["x1"] - 0.6)
    cols["y"] = (eta + rng.logistic(size=n) > 0).astype(np.int32)
    return cols


def _frame(cols):
    return Frame.from_numpy(cols, types={"y": T_CAT},
                            domains={"y": ["no", "yes"]})


@pytest.fixture(scope="module")
def fitted(cl):
    """One fit of the gate's kind at a small size: DRF rules of lengths 2
    and 3 beside the linear terms; and its path, every lambda's
    coefficients on the solver's scale, as the path program returned
    them."""
    cols = _columns(5)
    fr = _frame(cols)
    paths, make = [], glm_mod._make_blocked_path_runner

    def recorded(*args, **kw):
        runner = make(*args, **kw)

        def run(*a):
            out = runner(*a)
            paths.append(np.asarray(out[0], np.float64))
            return out
        return run

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(glm_mod, "_make_blocked_path_runner", recorded)
        model = RuleFit(response_column="y", algorithm="drf",
                        min_rule_length=2, max_rule_length=3,
                        rule_generation_ntrees=6,
                        model_type="rules_and_linear", seed=3).train(fr)
    (path,) = paths
    return cols, fr, model, path


def _levels(model):
    gen = dkv.get(model.output["rule_model_key"])
    return [tuple(np.asarray(a) for a in lv)
            for lv in gen.output["stacked"].levels]


def _reference_design(cols, model):
    """(dense raw rule matrix of the model's rules, its standardised design
    [rules, linear, intercept], the linear numerics standardised)."""
    X = np.stack([cols[f] for f in FEATURES], axis=1)
    R = np.asarray(ref.rule_matrix(_levels(model), X, model.output["rules"]))
    Z = np.stack([cols[f] for f in FEATURES], axis=1)
    return R, np.asarray(ref.design(R, Z)), np.asarray(ref.standardised(Z)[0])


def _system_mu(model, beta_std, R, Zs):
    """The system's probabilities from its coefficients on its own scale
    (raw rule columns, standardised numerics), over the reference's rows."""
    beta = np.asarray(beta_std, np.float64)
    g = beta[model.output["rule_coef"]]
    n_rule = model.output["n_rule_columns"]
    eta = beta[-1] + R @ g + Zs @ beta[n_rule:n_rule + Zs.shape[1]]
    return 1.0 / (1.0 + np.exp(-eta))


def test_codes_equal_reference_rule_matrix(fitted):
    """Every row's code in every group lights exactly the rules the
    reference's walk puts it at: the one-hot of the codes over the kept
    rules IS the reference's rule matrix, bit for bit."""
    cols, fr, model, _ = fitted
    codes, counts = model._rule_codes(fr)
    codes = np.asarray(codes)[:N]
    R, _, _ = _reference_design(cols, model)
    depths = model.output["rule_remap"].shape[1]
    lo = model.output["rule_max_depth"] - depths + 1
    got = np.stack([codes[:, t * depths + d - lo] == k
                    for t, d, k in model.output["rules"]], axis=1)
    np.testing.assert_array_equal(got, R.astype(bool))
    # the counts the penalty factors come from are the columns' sums
    want = np.zeros(np.asarray(counts).shape)
    for (t, d, k), col in zip(model.output["rules"], R.T):
        want[t * depths + d - lo, k] = col.sum()
    np.testing.assert_array_equal(np.asarray(counts), want)


def test_path_equals_reference_at_every_lambda(fitted):
    """The system's path (raw rule columns under penalty factors s_j, the
    blocked L1 runner) against the reference's (standardised dense design,
    plain IRLS and coordinate descent) at the system's lambdas: the same
    largest lambda, and at every lambda the same probabilities and, within
    float32 sums, the same objective."""
    cols, fr, model, path = fitted
    glm = dkv.get(model.output["glm_key"])
    lambdas = [h["lambda"] for h in glm.scoring_history]
    R, X, Zs = _reference_design(cols, model)
    y = cols["y"].astype(np.float32)
    w = np.ones(N, np.float32)
    assert lambdas[0] == pytest.approx(ref.lambda_max(X, y, w), rel=1e-5)
    want = ref.lasso_path(X, y, w, lambdas)
    _, mean, sd = ref.standardised(R)
    n_rule = model.output["n_rule_columns"]
    assert len(path) == len(lambdas)
    for lam, beta, beta_ref in zip(lambdas, path, want):
        # the system's coefficients on the standardised design: b = g s,
        # the intercept taking sum_j g_j m_j
        g = beta[model.output["rule_coef"]]
        b = np.concatenate([g * sd, beta[n_rule:-1], [beta[-1] + g @ mean]])
        assert _kkt(X, y, b, lam) < KKT_ATOL
        assert ref.objective(X, y, w, b, lam) == pytest.approx(
            ref.objective(X, y, w, beta_ref, lam), rel=OBJ_RTOL)
        mu = _system_mu(model, beta, R, Zs)
        mu_ref = np.asarray(reference_glm.predict(X, beta_ref, "binomial"), np.float64)
        np.testing.assert_allclose(mu, mu_ref, rtol=0, atol=MU_ATOL)


def _kkt(X, y, b, lam):
    """The largest violation of the standardised lasso's optimality
    conditions at ``b`` (the intercept last), in float64: the intercept's
    gradient 0, an active coefficient's lambda times its sign, an inactive
    one's at most lambda."""
    X = np.asarray(X, np.float64)
    mu = 1.0 / (1.0 + np.exp(-(X @ b)))
    grad = X.T @ (np.asarray(y, np.float64) - mu) / len(mu)
    on = b[:-1] != 0
    return max(abs(grad[-1]),
               np.max(np.abs(grad[:-1][on] - lam * np.sign(b[:-1][on])),
                      initial=0.0),
               np.max(np.abs(grad[:-1][~on]) - lam, initial=0.0))


def test_predict_equals_reference(fitted):
    """``predict`` (the rule codes of the frame, then ``jit_glm_score``
    over the code form) against the reference's probabilities of the
    model's own coefficients on the dense rule matrix, and ``predict`` on
    a frame the model never saw."""
    cols, fr, model, _ = fitted
    R, _, Zs = _reference_design(cols, model)
    glm = dkv.get(model.output["glm_key"])
    want = _system_mu(model, glm.output["beta_std"], R, Zs)
    got = np.asarray(model.predict(fr).vec("yes").to_numpy(), np.float64)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    other = _columns(11, 300)
    R2 = np.asarray(ref.rule_matrix(_levels(model), np.stack(
        [other[f] for f in FEATURES], axis=1), model.output["rules"]))
    di = model.datainfo
    Z2 = np.stack([np.where(np.isnan(other[s.name]), s.mean, other[s.name])
                   for s in di.specs], axis=1)
    Z2 = (Z2 - [s.mean for s in di.specs]) / [s.sigma for s in di.specs]
    got2 = np.asarray(model.predict(_frame(other)).vec("yes").to_numpy())
    np.testing.assert_allclose(
        got2, _system_mu(model, glm.output["beta_std"], R2, Z2),
        rtol=0, atol=2e-6)


def test_standardisation_equivalence(fitted):
    """The penalty factor of a rule's raw column is its sample deviation,
    so the raw problem is the standardised one: the factors equal the
    reference's deviations, and the system's solution meets the
    standardised problem's optimality conditions at its lambda."""
    cols, fr, model, _ = fitted
    glm = dkv.get(model.output["glm_key"])
    R, X, _ = _reference_design(cols, model)
    sd = np.asarray(ref.standardised(R)[2], np.float64)
    n_rule = model.output["n_rule_columns"]
    # the system's factor of each kept rule, recomputed from its counts
    _, counts = model._rule_codes(fr)
    depths = model.output["rule_remap"].shape[1]
    lo = model.output["rule_max_depth"] - depths + 1
    share = np.array([np.asarray(counts)[t * depths + d - lo, k]
                      for t, d, k in model.output["rules"]]) / N
    factor = np.sqrt(share * (1 - share) * N / (N - 1))
    np.testing.assert_allclose(np.where(factor > 0, factor, 1.0), sd,
                               rtol=1e-6)
    # the standardised lasso's optimality conditions at the system's
    # coefficients and lambda
    beta = glm.output["beta_std"].astype(np.float64)
    g = beta[model.output["rule_coef"]]
    mean = np.asarray(ref.standardised(R)[1], np.float64)
    b = np.concatenate([g * sd, beta[n_rule:-1], [beta[-1] + g @ mean]])
    assert _kkt(X, cols["y"], b, model.output["lambda"]) < KKT_ATOL


def _forest(trees):
    """Stacked split tables of depth 2 over three features: ``trees`` the
    (root feature, root threshold, left child's split) of each tree; the
    right child never splits."""
    feat = [np.array([[f] for f, _, _ in trees], np.int32),
            np.array([[c[0], 0] for _, _, c in trees], np.int32)]
    thr = [np.array([[t] for _, t, _ in trees], np.float32),
           np.array([[c[1], 0.0] for _, _, c in trees], np.float32)]
    na = [np.zeros((len(trees), 1), bool), np.zeros((len(trees), 2), bool)]
    valid = [np.ones((len(trees), 1), bool),
             np.array([[True, False]] * len(trees))]
    return [tuple(a[d] for a in (feat, thr, na, valid)) for d in range(2)]


def test_remove_duplicates(cl):
    """A forest that repeats a tree: the second tree's rules are the
    first's, so with ``remove_duplicates`` its nodes light no column (code
    -1) and the counter counts them; an unsplit node's right child is the
    rule of no rows; without it every node is a rule."""
    levels = _forest([(0, 0.5, (1, -0.2)), (0, 0.5, (1, -0.2)),
                      (2, 0.0, (1, -0.2))])
    di = DataInfo.fit(_frame(_columns(1, 64)), response_column="y")
    builder = RuleFit(response_column="y", min_rule_length=1,
                      max_rule_length=2)
    rules, descr, remap, dupes = builder._enumerate(levels, di)
    # tree 0: its unsplit right child's left child holds the rows of the
    # right child, the depth-1 rule again, and its right child none; tree 1
    # repeats all six; tree 2 repeats its own depth-1 rule the same way and
    # tree 0's rule of no rows
    assert dupes == 1 + 6 + 2
    assert [r for r in rules if r[0] == 1] == []
    assert (remap[1] == -1).all()
    assert "(no rows)" in descr and "x0 < 0.5 & x1 >= -0.2" in descr
    X = np.random.default_rng(0).normal(size=(256, 3)).astype(np.float32)
    cols = tuple(jnp.asarray(X[:, i]) for i in range(3))
    codes, counts = rulefit.jit_rule_codes(cols, levels, jnp.asarray(remap),
                                           np.int32(256))
    codes = np.asarray(codes)
    assert (codes[:, 2:4] == -1).all()          # tree 1, both depths
    R = np.asarray(ref.rule_matrix(levels, X, rules))
    depths = remap.shape[1]
    got = np.stack([codes[:, t * depths + d - 1] == k for t, d, k in rules], 1)
    np.testing.assert_array_equal(got, R.astype(bool))
    keep_all = RuleFit(response_column="y", min_rule_length=1,
                       max_rule_length=2, remove_duplicates=False)
    rules_all, _, remap_all, dupes_all = keep_all._enumerate(levels, di)
    assert dupes_all == 0 and len(rules_all) == 3 * 6
    assert (remap_all >= 0).sum() == 3 * 6


def test_generator_parameters():
    """``algorithm="drf"`` (and ``"auto"``, H2O's default) grows DRF at its
    shipped defaults, with no learning rate; ``"gbm"`` keeps RuleFit's own
    rate and sample."""
    for algo in ("drf", "auto"):
        gen = RuleFit(response_column="y", algorithm=algo,
                      rule_generation_ntrees=50, seed=1)._generator(3)
        assert gen.algo == "drf"
        p = gen.params
        assert (p.ntrees, p.max_depth, p.sample_rate, p.mtries,
                p.learn_rate) == (50, 3, 0.632, -1, 1.0)
    gbm = RuleFit(response_column="y", algorithm="gbm")._generator(2).params
    assert (gbm.sample_rate, gbm.learn_rate, gbm.max_depth) == (0.7, 0.1, 2)
    with pytest.raises(ValueError, match="algorithm"):
        RuleFit(response_column="y", algorithm="xgboost")._generator(2)


def _span_counts():
    """{span: observations} of ``span_seconds``."""
    return {m["l"]["span"]: m["n_obs"] for m in obs.metrics_wire()
            if m["n"] == "span_seconds"}


def test_fit_in_code_form_spans_and_counters(cl, monkeypatch):
    """A fit and a predict never build a dense design; the fit opens the
    four spans and counts its rules, duplicates, linear terms and the
    path's coordinate-descent sweeps."""
    monkeypatch.setattr(DataInfo, "make_matrix", lambda *a, **k: pytest.fail(
        "the dense design was built"))
    before = {k: obs.counter("rulefit_rules_total", kind=k).value
              for k in ("rule", "duplicate", "linear")}
    sweeps = obs.counter("glm_cd_sweeps_total").value
    spans = _span_counts()
    fr = _frame(_columns(7, 400))
    model = RuleFit(response_column="y", algorithm="drf",
                    min_rule_length=3, max_rule_length=3,
                    rule_generation_ntrees=4, seed=2).train(fr)
    model.predict(fr)
    after = _span_counts()
    for span in ("rulefit.forest", "rulefit.rules", "rulefit.codes",
                 "rulefit.glm", "glm.wait"):
        assert after.get(span, 0) == spans.get(span, 0) + 1, span
    got = {k: obs.counter("rulefit_rules_total", kind=k).value - v
           for k, v in before.items()}
    assert got["rule"] == len(model.output["rules"])
    assert got["rule"] + got["duplicate"] == 4 * 8
    assert got["linear"] == 3
    assert obs.counter("glm_cd_sweeps_total").value > sweeps


def test_a_second_fit_reuses_the_lasso_program(cl, path_compiles):
    """RuleFit's grouped L1 path: a second fit of the same frame and seed
    grows the same rules, and so the same runs and layout, compiles no path
    program and gives the first's coefficients bit for bit."""
    fr = _frame(_columns(10, 400))
    kw = dict(response_column="y", algorithm="drf", min_rule_length=3,
              max_rule_length=3, rule_generation_ntrees=4, seed=2)
    sweeps = obs.counter("glm_cd_sweeps_total").value
    first = RuleFit(**kw).train(fr)
    assert obs.counter("glm_cd_sweeps_total").value > sweeps    # grouped
    once = path_compiles()
    assert sum(once[0].values()) == 1 and once[1] > 0
    second = RuleFit(**kw).train(fr)
    assert path_compiles() == once
    assert second.output["rules"] == first.output["rules"]
    np.testing.assert_array_equal(
        dkv.get(second.output["glm_key"]).output["beta_std"],
        dkv.get(first.output["glm_key"]).output["beta_std"])


def test_rules_only_and_linear_only(cl):
    """``model_type="rules"`` fits the rule groups alone, ``"linear"`` the
    linear terms alone and grows no forest; both score through the code
    form."""
    fr = _frame(_columns(9, 300))
    rules = RuleFit(response_column="y", algorithm="drf", model_type="rules",
                    max_rule_length=2, rule_generation_ntrees=3,
                    seed=1).train(fr)
    names = dkv.get(rules.output["glm_key"]).output["coef_names"]
    assert names[-1] == "Intercept" and all(n.startswith("T") for n in names[:-1])
    linear = RuleFit(response_column="y", model_type="linear").train(fr)
    assert linear.output["rule_remap"] is None and not linear.output["rules"]
    for m in (rules, linear):
        p = np.asarray(m.predict(fr).vec("yes").to_numpy())
        assert np.all((p > 0) & (p < 1))


def _lasso_objective(G, c, l1, b):
    return 0.5 * b @ G @ b - c @ b + np.sum(l1 * np.abs(b))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_descent_solves_the_lasso(seed):
    """``_group_coordinate_descent`` (a run of one-hot columns a step, the
    null-line moves) on a design of three runs that partition the rows,
    one that does not (a level that lights no column), two numerics and
    the intercept: its solution meets the lasso's optimality conditions,
    and its objective is the plain one-at-a-time descent's."""
    rng = np.random.default_rng(seed)
    n, runs = 600, ((0, 4), (4, 4), (8, 8), (16, 4))
    cols = []
    for g, (_, width) in enumerate(runs):
        code = rng.integers(0, width, n)
        if g == 3:
            code[code == 0] = -1                # a dropped rule: no column
        cols.append(code[:, None] == np.arange(width))
    X = np.concatenate(cols + [rng.normal(size=(n, 2)), np.ones((n, 1))],
                       axis=1).astype(np.float64)
    y = X[:, [1, 6, 9, 17, 20]] @ [0.8, -0.5, 0.4, 0.6, 0.7] \
        + rng.normal(size=n)
    G, c = X.T @ X / n, X.T @ y / n
    pen = np.ones(X.shape[1])
    pen[-1] = 0.0
    l1 = 0.02 * pen
    partition = np.array([True, True, True, False])
    f32 = [jnp.asarray(a, jnp.float32) for a in (G, c, l1, 0 * l1, pen)]
    b, sweeps = glm_mod._group_coordinate_descent(
        *f32, jnp.zeros(X.shape[1], jnp.float32), 500, runs,
        jnp.asarray(partition))
    b = np.asarray(b, np.float64)
    assert 1 < int(sweeps) < 500
    grad = G @ b - c
    on = (b != 0) & (pen > 0)
    assert abs(grad[-1]) < 1e-5
    assert np.max(np.abs(grad[on] + l1[on] * np.sign(b[on]))) < 1e-5
    assert np.max(np.abs(grad[~on & (pen > 0)]) - l1[~on & (pen > 0)],
                  initial=0.0) < 1e-5
    plain = np.asarray(glm_mod._coordinate_descent(
        *f32, jnp.zeros(X.shape[1], jnp.float32), 5000), np.float64)
    assert _lasso_objective(G, c, l1, b) <= \
        _lasso_objective(G, c, l1, plain) + 1e-6
