"""GBM/DRF tests — mirrors pyunit_gbm*/pyunit_drf* coverage plus golden
comparisons against sklearn's boosted/forest baselines on synthetic data."""

import numpy as np
import pytest

from h2o3_tpu import Frame
from h2o3_tpu.models.tree.gbm import GBM
from h2o3_tpu.models.tree.drf import DRF


def _friedman(rng, n=3000):
    """Friedman #1 regression surface (nonlinear + interactions)."""
    X = rng.random((n, 5))
    y = (10 * np.sin(np.pi * X[:, 0] * X[:, 1]) + 20 * (X[:, 2] - 0.5) ** 2
         + 10 * X[:, 3] + 5 * X[:, 4] + 0.5 * rng.normal(size=n))
    cols = {f"x{j}": X[:, j] for j in range(5)}
    cols["y"] = y
    return Frame.from_numpy(cols)


def _binary(rng, n=3000):
    X = rng.normal(size=(n, 4))
    logits = 2 * X[:, 0] * X[:, 1] + X[:, 2] ** 2 - 1
    y = (rng.random(n) < 1 / (1 + np.exp(-logits))).astype(int)
    cols = {f"x{j}": X[:, j] for j in range(4)}
    cols["y"] = np.array(["n", "y"], dtype=object)[y]
    return Frame.from_numpy(cols), y


def test_gbm_regression(cl, rng):
    fr = _friedman(rng)
    m = GBM(response_column="y", ntrees=40, max_depth=4, learn_rate=0.2,
            seed=1).train(fr)
    assert m.training_metrics.r2 > 0.9, m.training_metrics.describe()
    # prediction roundtrip
    preds = m.predict(fr)
    assert preds.nrows == fr.nrows


def test_gbm_binomial(cl, rng):
    fr, y = _binary(rng)
    m = GBM(response_column="y", ntrees=60, max_depth=5, learn_rate=0.2,
            seed=2).train(fr)
    assert m.training_metrics.auc > 0.9, m.training_metrics.describe()


def test_gbm_vs_sklearn(cl, rng):
    from sklearn.ensemble import HistGradientBoostingRegressor
    from sklearn.metrics import r2_score
    fr = _friedman(rng, n=4000)
    Xh = np.stack([fr.vec(f"x{j}").to_numpy() for j in range(5)], axis=1)
    yh = fr.vec("y").to_numpy()
    m = GBM(response_column="y", ntrees=60, max_depth=5, learn_rate=0.1,
            min_rows=5, seed=3).train(fr)
    ours = m.predict(fr).vec("predict").to_numpy()
    sk = HistGradientBoostingRegressor(
        max_iter=60, max_depth=5, learning_rate=0.1).fit(Xh, yh)
    sk_r2 = r2_score(yh, sk.predict(Xh))
    our_r2 = r2_score(yh, ours)
    assert our_r2 > sk_r2 - 0.05, (our_r2, sk_r2)


def test_gbm_multinomial(cl, rng):
    n = 3000
    centers = np.array([[2, 0], [-2, 1], [0, -2]])
    labels = rng.integers(0, 3, n)
    X = centers[labels] + rng.normal(size=(n, 2))
    fr = Frame.from_numpy({
        "x0": X[:, 0], "x1": X[:, 1],
        "y": np.array(["a", "b", "c"], dtype=object)[labels]})
    m = GBM(response_column="y", ntrees=20, max_depth=3, seed=4).train(fr)
    assert m.training_metrics.accuracy > 0.85
    preds = m.predict(fr)
    probs = np.stack([preds.vec(c).to_numpy() for c in "abc"], axis=1)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-4)


def test_gbm_categorical_and_na(cl, rng):
    n = 2000
    g = np.array(["a", "b", "c"], dtype=object)[rng.integers(0, 3, n)]
    x = rng.normal(size=n)
    x[rng.random(n) < 0.1] = np.nan          # missing values
    eff = {"a": 0.0, "b": 2.0, "c": -2.0}
    y = np.where(np.isnan(x), 1.0, x) + np.array([eff[s] for s in g])
    fr = Frame.from_numpy({"g": g, "x": x, "y": y})
    m = GBM(response_column="y", ntrees=30, max_depth=4, learn_rate=0.3,
            seed=5).train(fr)
    assert m.training_metrics.r2 > 0.85, m.training_metrics.describe()


def test_gbm_early_stopping(cl, rng):
    fr = _friedman(rng, n=1500)
    train, valid = fr.split_frame([0.8], seed=1)
    m = GBM(response_column="y", ntrees=200, max_depth=3, learn_rate=0.5,
            stopping_rounds=2, stopping_tolerance=1e-3,
            score_tree_interval=5, seed=6).train(train, valid=valid)
    assert m.output["ntrees_trained"] < 200


def test_gbm_poisson(cl, rng):
    n = 2500
    x = rng.normal(size=n)
    y = rng.poisson(np.exp(0.5 * x + 1.0)).astype(float)
    fr = Frame.from_numpy({"x": x, "y": y})
    m = GBM(response_column="y", ntrees=30, distribution="poisson",
            max_depth=3, seed=7).train(fr)
    preds = m.predict(fr).vec("predict").to_numpy()
    assert (preds > 0).all()                     # log link respected
    assert abs(preds.mean() - y.mean()) / y.mean() < 0.1


def test_drf_classification(cl, rng):
    fr, y = _binary(rng)
    m = DRF(response_column="y", ntrees=30, max_depth=10, seed=8).train(fr)
    assert m.training_metrics.auc > 0.9, m.training_metrics.describe()


def test_drf_regression(cl, rng):
    fr = _friedman(rng)
    m = DRF(response_column="y", ntrees=30, max_depth=10, seed=9).train(fr)
    assert m.training_metrics.r2 > 0.85, m.training_metrics.describe()


def test_drf_multinomial(cl, rng):
    n = 2000
    centers = np.array([[2, 0], [-2, 1], [0, -2]])
    labels = rng.integers(0, 3, n)
    X = centers[labels] + rng.normal(size=(n, 2))
    fr = Frame.from_numpy({
        "x0": X[:, 0], "x1": X[:, 1],
        "y": np.array(["a", "b", "c"], dtype=object)[labels]})
    m = DRF(response_column="y", ntrees=20, max_depth=8, seed=10).train(fr)
    assert m.training_metrics.accuracy > 0.85


def test_tree_save_load_predict(cl, rng, tmp_path):
    from h2o3_tpu.models import Model
    fr, y = _binary(rng, n=1000)
    m = GBM(response_column="y", ntrees=10, max_depth=3, seed=11).train(fr)
    p1 = m.predict(fr).vec("y").to_numpy()
    path = m.save(str(tmp_path / "gbm.bin"))
    m2 = Model.load(path)
    p2 = m2.predict(fr).vec("y").to_numpy()
    np.testing.assert_allclose(p1, p2, rtol=1e-5)


def test_fit_bins_inf_stays_in_own_feature(cl, rng):
    """+inf must encode to the FEATURE's top bin, not the padded edge
    width: the encode program pads every edge row to the global max with
    +inf, and searchsorted(side='right') counts the padding as <= inf —
    an unclipped code lands inside a NEIGHBORING feature's packed varbin
    segment (round-4 review finding)."""
    import h2o3_tpu
    from h2o3_tpu.models.tree.binning import fit_bins
    n = 2000
    a = rng.integers(0, 4, n).astype(np.float32)
    a[5] = np.inf
    a[7] = -np.inf
    b = rng.normal(size=n).astype(np.float32)
    fr = h2o3_tpu.Frame.from_numpy({"a": a, "b": b})
    bn = fit_bins(fr, ["a", "b"], nbins=64)
    codes = np.asarray(bn.codes)
    assert len(bn.edges[0]) < len(bn.edges[1])      # uneven edge widths
    assert codes[0, 5] == len(bn.edges[0])          # inf -> own top bin
    assert codes[0, 7] == 0                         # -inf -> bottom bin
    assert codes[0, :n].max() <= len(bn.edges[0])


def _float_sort_sketch(n, padded, ncols, nq):
    """The reference: the quantile sketch as it read before its sort took
    int32 keys, a stable sort of the masked float32 values."""
    import jax
    import jax.numpy as jnp

    def sketch(X, w):
        iota = jax.lax.broadcasted_iota(jnp.int32, (ncols, padded), 1)
        valid = (iota < n) & jnp.isfinite(X) & (w[None, :] > 0)
        m = jnp.sum(valid, axis=1)
        Xs = jnp.sort(jnp.where(valid, X, jnp.inf), axis=1, stable=True)
        lo = jnp.min(jnp.where(valid, X, jnp.inf), axis=1)
        hi = jnp.max(jnp.where(valid, X, -jnp.inf), axis=1)
        qs = jnp.arange(1, nq + 1, dtype=jnp.float32) / (nq + 1)
        pos = qs[None, :] * jnp.maximum(m[:, None] - 1, 0)
        p0 = jnp.floor(pos).astype(jnp.int32)
        frac = pos - p0
        v0 = jnp.take_along_axis(Xs, p0, axis=1)
        v1 = jnp.take_along_axis(
            Xs, jnp.minimum(p0 + 1, jnp.maximum(m[:, None] - 1, 0)), axis=1)
        return v0 * (1 - frac) + v1 * frac, lo, hi, m

    return jax.jit(sketch)


def _sketch_case(case, rng, n=1500):
    """Columns [C, n] float32, weights [n] or None, and how many finite
    rows past ``n`` the sketch's block carries."""
    f32 = np.float32
    w, tail = None, 0
    if case == "ties_rounded":
        cols = [rng.integers(-3, 4, n), np.round(rng.normal(size=n), 1),
                np.repeat(rng.normal(size=n // 100), 100)]
    elif case == "signed_zero_subnormal":
        tiny = np.finfo(f32).smallest_subnormal
        cols = [rng.choice(np.array([-0.0, 0.0], f32), n),
                rng.choice(np.array([-0.0, 0.0, tiny, -tiny, 7 * tiny,
                                     -1e-39, 1e-40, 1.0], f32), n),
                np.where(rng.random(n) < 0.5, -0.0, rng.normal(size=n))]
    elif case == "sign_only_and_mixed":
        cols = [-rng.exponential(size=n), rng.exponential(size=n),
                rng.normal(scale=1e3, size=n)]
    elif case == "nan_inf_masked":
        x = rng.normal(size=(3, n))
        x[0, rng.random(n) < 0.2] = np.nan
        x[1, rng.random(n) < 0.05] = np.inf
        x[1, rng.random(n) < 0.05] = -np.inf
        x[2, ::3] = np.nan
        x[2, 1::7] = -np.inf
        cols = list(x)
    elif case == "weights_masked":
        cols = list(rng.normal(size=(3, n)))
        w = rng.choice(np.array([-1.0, 0.0, 1.0, 2.5], f32), n)
        for c in cols:
            c[w <= 0] *= 1e4               # outliers that only the mask drops
    elif case == "padded_tail":
        cols, tail = list(rng.normal(size=(3, n))), 77
    else:                                   # "all_nan_and_single_row"
        x = np.full((3, n), np.nan)
        x[1, 17] = -2.5
        x[2] = rng.normal(size=n)
        cols = list(x)
    return np.stack(cols).astype(f32), w, tail


SKETCH_CASES = ["ties_rounded", "signed_zero_subnormal",
                "sign_only_and_mixed", "nan_inf_masked", "weights_masked",
                "padded_tail", "all_nan_and_single_row"]


@pytest.mark.parametrize("case", SKETCH_CASES)
def test_sketch_int_keys_equal_the_float_sort(cl, rng, monkeypatch, case):
    """The sketch sorts order-preserving int32 keys unstably; its edges are
    the stable float sort's as numbers (they may differ in the sign of a
    zero), its min, max and counts the same bits, and ``fit_bins``' edges
    and codes through ``encode_bins`` the same."""
    import jax.numpy as jnp
    import h2o3_tpu
    from h2o3_tpu.models.tree import binning
    from h2o3_tpu.runtime import observability as obs
    X, w, tail = _sketch_case(case, rng)
    C, n = X.shape
    nq = 63
    Xp = np.concatenate([X, rng.choice(np.array([-1e9, 0.0, 5.0], np.float32),
                                       (C, tail))], axis=1)
    wp = np.ones(n + tail, np.float32) if w is None \
        else np.concatenate([w, np.ones(tail, np.float32)])
    got = binning.run_sketch(jnp.asarray(Xp), jnp.asarray(wp), n, nq, "bins")
    want = _float_sort_sketch(n, n + tail, C, nq)(Xp, wp)
    (ge, glo, ghi, gm), (we, wlo, whi, wm) = (
        [np.asarray(a) for a in r] for r in (got, want))
    assert np.array_equal(ge, we, equal_nan=True)
    assert np.array_equal(glo.view(np.int32), wlo.view(np.int32))
    assert np.array_equal(ghi.view(np.int32), whi.view(np.int32))
    assert np.array_equal(gm, wm)

    names = [f"x{j}" for j in range(C)]
    fr = h2o3_tpu.Frame.from_numpy(dict(zip(names, X)))
    sorted_values = obs.counter("binning_sketch_values_total", caller="bins")
    before = sorted_values.value
    bn = binning.fit_bins(fr, names, nbins=nq + 1, weights=w)
    assert sorted_values.value - before == C * fr.padded_rows
    monkeypatch.setattr(binning, "_make_sketch_fn", _float_sort_sketch)
    ref = binning.fit_bins(fr, names, nbins=nq + 1, weights=w)
    for e, r in zip(bn.edges, ref.edges):
        assert np.array_equal(e, r)
    assert np.array_equal(np.asarray(bn.codes), np.asarray(ref.codes))


def test_gam_knots_equal_the_float_sort_and_np_quantile(cl, rng,
                                                        monkeypatch):
    """GAM's device knots run the same sketch: equal as numbers to the
    stable float sort's, and to ``np.quantile`` of the non-missing values
    up to the sketch's float32 positions."""
    import h2o3_tpu
    from h2o3_tpu.models import gam
    from h2o3_tpu.models.tree import binning
    from h2o3_tpu.runtime import observability as obs
    n = 2001
    cols = {"a": rng.normal(size=n), "b": np.round(rng.exponential(size=n), 1),
            "c": rng.choice(np.array([-0.0, 0.0, -1.5, 2.0, 3.25]), n)}
    cols["a"][::9] = np.nan
    fr = h2o3_tpu.Frame.from_numpy(cols)
    smooths = [{"cols": [c], "num_knots": k}
               for c, k in (("a", 10), ("b", 7), ("c", 5))]
    sorted_values = obs.counter("binning_sketch_values_total",
                                caller="gam_knots")
    before = sorted_values.value
    got = gam._device_knots(fr, smooths)
    # one sketch a knot count, each over its own columns
    assert sorted_values.value - before == len(smooths) * fr.padded_rows
    monkeypatch.setattr(binning, "_make_sketch_fn", _float_sort_sketch)
    want = gam._device_knots(fr, smooths)
    for s, g, r in zip(smooths, got, want):
        assert np.array_equal(g, r)
        x = cols[s["cols"][0]].astype(np.float32).astype(np.float64)
        x = x[~np.isnan(x)]
        q = np.unique(np.quantile(x, np.linspace(0, 1, s["num_knots"])))
        np.testing.assert_allclose(g, q, rtol=0, atol=1e-5 * np.ptp(x))


def test_depth_cap_multinomial_and_default_depth_drf(cl, rng):
    """Dense-level depth cap: a depth request above the cap must produce
    a working (capped) model on every scan driver — the multinomial
    stacking loop used the REQUESTED depth and crashed at trace time
    (round-4 review finding), and default-depth DRF (max_depth=20) must
    train (it Mosaic-OOM'd on chip before the cap existed)."""
    import h2o3_tpu
    from h2o3_tpu.models import GBM, DRF
    from h2o3_tpu.models.tree.shared import effective_max_depth
    n = 600
    x = rng.normal(size=n)
    y3 = np.array(["abc"[i % 3] for i in range(n)], dtype=object)
    fr = h2o3_tpu.Frame.from_numpy({"x": x, "x2": rng.normal(size=n),
                                    "y": y3})
    eff = effective_max_depth(18, 16, 2, fr.padded_rows)
    assert eff < 18
    m = GBM(ntrees=2, max_depth=18, nbins=16, response_column="y",
            seed=1).train(fr)                      # multinomial scan path
    assert len(m.output["stacked"][0].levels if isinstance(
        m.output["stacked"], list) else m.output["stacked"].levels) == eff
    m2 = DRF(ntrees=2, nbins=16, response_column="y", seed=1).train(fr)
    assert m2.predict(fr).nrows == n


def test_histogram_types(cl, rng):
    import h2o3_tpu
    from h2o3_tpu.models import GBM
    from h2o3_tpu.models.tree.binning import fit_bins
    import pytest
    n = 500
    x = rng.normal(size=n) ** 3          # skewed: quantile != uniform
    y = np.where(x > 0, "Y", "N").astype(object)
    fr = h2o3_tpu.Frame.from_numpy({"x": x, "y": y})
    edges = {}
    for ht in ("QuantilesGlobal", "UniformAdaptive", "Random"):
        b = fit_bins(fr, ["x"], nbins=16, seed=1, histogram_type=ht)
        edges[ht] = b.edges[0]
        m = GBM(response_column="y", ntrees=10, max_depth=3,
                learn_rate=0.3, histogram_type=ht, seed=1).train(fr)
        p = m.predict(fr).vec("Y").to_numpy()
        assert np.isfinite(p).all()
        # quantile edges resolve the skewed sign boundary well;
        # uniform/random are legitimately coarser near 0 on x**3 data
        floor = 0.95 if ht == "QuantilesGlobal" else 0.75
        assert np.mean((p > 0.5) == (x > 0)) > floor
    assert not np.array_equal(edges["QuantilesGlobal"],
                              edges["UniformAdaptive"])
    assert not np.array_equal(edges["UniformAdaptive"], edges["Random"])
    # uniform edges are equally spaced
    du = np.diff(edges["UniformAdaptive"])
    np.testing.assert_allclose(du, du[0], rtol=1e-4)
    with pytest.raises(ValueError, match="histogram_type"):
        fit_bins(fr, ["x"], histogram_type="nope")


def test_balance_classes(cl, rng):
    import h2o3_tpu
    from h2o3_tpu.models import GBM
    n = 600
    x = rng.normal(size=n)
    # 95/5 imbalance with a learnable boundary
    rare = rng.random(n) < 0.05
    y = np.where(rare, "POS", "NEG").astype(object)
    x = np.where(rare, x + 2.0, x)
    fr = h2o3_tpu.Frame.from_numpy({"x": x, "y": y})
    plain = GBM(response_column="y", ntrees=10, max_depth=3,
                seed=1).train(fr)
    bal = GBM(response_column="y", ntrees=10, max_depth=3,
              balance_classes=True, seed=1).train(fr)
    p0 = plain.predict(fr).vec("POS").to_numpy()
    p1 = bal.predict(fr).vec("POS").to_numpy()
    # balancing must push minority-class probabilities up overall
    assert p1[rare].mean() > p0[rare].mean()
    # recall of the rare class improves at the 0.5 threshold
    assert (p1[rare] > 0.5).mean() >= (p0[rare] > 0.5).mean()
    assert (p1[rare] > 0.5).mean() > 0.5
    # validation frame without the synthetic weights column still scores
    m = bal.model_performance(fr)
    assert m is not None
    # scoring DataInfo keeps the user's weights (None here), and the
    # builder params are restored so retraining on the raw frame works
    assert bal.datainfo.weights_column is None
    from h2o3_tpu.models import GBM as _G
    b2 = _G(response_column="y", ntrees=2, max_depth=2,
            balance_classes=True, seed=1)
    b2.train(fr)
    b2.train(fr)                       # second run must not KeyError
    assert b2.params.weights_column is None
    # in-training validation scoring works under balancing
    tr, va = fr.split_frame([0.7], seed=3)
    GBM(response_column="y", ntrees=3, max_depth=2, balance_classes=True,
        seed=1, score_tree_interval=1).train(tr, va)
    # explicit factors are honored and validated
    import pytest
    with pytest.raises(ValueError, match="class_sampling_factors"):
        GBM(response_column="y", balance_classes=True,
            class_sampling_factors=[1.0], ntrees=2).train(fr)


def test_monotone_constraints(cl, rng):
    import h2o3_tpu
    import pytest
    from h2o3_tpu.models import GBM, XGBoost
    n = 800
    x = rng.uniform(-3, 3, n)
    z = rng.normal(size=n)
    # noisy, non-monotone-looking sample of a monotone-increasing truth
    y = 2.0 * x + z * 2.0 + 1.5 * np.sin(2.5 * x)
    fr = h2o3_tpu.Frame.from_numpy({"x": x, "z": z, "y": y})
    grid = np.linspace(-3, 3, 60)
    probe = h2o3_tpu.Frame.from_numpy(
        {"x": grid, "z": np.zeros_like(grid)})
    for cls in (GBM, XGBoost):
        m = cls(response_column="y", ntrees=40, max_depth=4,
                learn_rate=0.2, monotone_constraints={"x": 1},
                seed=1).train(fr)
        p = m.predict(probe).vec("predict").to_numpy()
        assert (np.diff(p) >= -1e-5).all(), \
            f"{cls.__name__} predictions not monotone in x"
        # the unconstrained model on this noisy data is NOT monotone
        # (otherwise the assertion above is vacuous)
        m0 = cls(response_column="y", ntrees=40, max_depth=4,
                 learn_rate=0.2, seed=1).train(fr)
        p0 = m0.predict(probe).vec("predict").to_numpy()
        assert (np.diff(p0) < -1e-5).any()
        # decreasing constraint mirrors
        md = cls(response_column="y", ntrees=10, max_depth=3,
                 monotone_constraints={"x": -1}, seed=1).train(fr)
        pd_ = md.predict(probe).vec("predict").to_numpy()
        assert (np.diff(pd_) <= 1e-5).all()
    with pytest.raises(ValueError, match="categorical|unknown"):
        fr2 = h2o3_tpu.Frame.from_numpy({
            "g": np.array(["a", "b"] * 50, object),
            "y": rng.normal(size=100)})
        GBM(response_column="y", ntrees=2,
            monotone_constraints={"g": 1}).train(fr2)


def test_monotone_rejected_outside_gbm(cl, rng):
    import h2o3_tpu
    import pytest
    from h2o3_tpu.models import DRF, GBM
    fr = h2o3_tpu.Frame.from_numpy({"x": rng.normal(size=60),
                                    "y": rng.normal(size=60)})
    with pytest.raises(ValueError, match="only enforced"):
        DRF(response_column="y", ntrees=2,
            monotone_constraints={"x": 1}).train(fr)
    # 0 means unconstrained (reference semantics) — trains fine
    GBM(response_column="y", ntrees=2,
        monotone_constraints={"x": 0}).train(fr)
