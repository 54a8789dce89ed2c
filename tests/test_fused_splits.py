"""Fused coarse split search + batched K-tree growth parity suite.

Three layers of oracle checks for the one-launch-per-level pipeline:

1. ``fused_best_splits`` (single-pass winner-records path) vs
   ``best_splits`` (the multi-pass XLA oracle) — bit-exact off-TPU,
   across NA mass, L1/gamma/min_child_weight regularizers, feature
   masks, and deliberately tied gains.
2. ``make_multinomial_scan_fn(split_mode="fused")`` (one batched build
   for all K class trees) vs the sequential per-class loop — same RNG
   stream, same trees, same predictions, including shared row sampling
   and per-class column-sample masks.
3. Whole builds and whole fits both ways: the batched K-tree builder
   against a K-loop of separate builds and against the full-histogram
   oracle, and estimators trained with ``split_mode="fused"`` and
   ``"separate"`` compared tree by tree — the tier-1 smoke for the whole
   fused pipeline.

The dispatch-count test asserts the load-bearing property directly from
the jaxpr: a batched level issues ONE histogram kernel launch for all K
trees (vmap batches the grid, it does not replicate the call).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from h2o3_tpu.models.tree import hist, shared
from tree_parity import MODELS, check_pair


def _rand_hist(rng, L, F, B, na_mass=0.2):
    """Histogram block [3, L, F, B] with positive hessians/counts and an
    NA bucket carrying ``na_mass`` of the rows on average."""
    C = rng.integers(0, 40, size=(L, F, B)).astype(np.float32)
    C[..., -1] = rng.integers(0, int(40 * na_mass) + 1,
                              size=(L, F)).astype(np.float32)
    G = rng.normal(size=(L, F, B)).astype(np.float32) * np.sqrt(C + 1e-3)
    H = (C * rng.uniform(0.5, 1.5, size=(L, F, B))).astype(np.float32)
    G, H, C = (np.where(C > 0, a, 0.0).astype(np.float32)
               for a in (G, H, C))
    return jnp.asarray(np.stack([G, H, C]))


_REG_CONFIGS = [
    dict(reg_alpha=0.0, gamma=0.0, min_child_weight=0.0),
    dict(reg_alpha=0.7, gamma=0.0, min_child_weight=0.0),
    dict(reg_alpha=0.0, gamma=1.5, min_child_weight=0.0),
    dict(reg_alpha=0.0, gamma=0.0, min_child_weight=4.0),
    dict(reg_alpha=0.3, gamma=0.8, min_child_weight=2.0),
]


@pytest.mark.parametrize("cfg", _REG_CONFIGS,
                         ids=["plain", "l1", "gamma", "mcw", "all"])
def test_fused_matches_best_splits(cl, rng, cfg):
    """Off-TPU the fused path lowers to the XLA twin, which replays
    best_splits' op sequence — the outputs must be bit-identical."""
    L, F, nbins = 8, 6, 16
    H = _rand_hist(rng, L, F, nbins + 1)
    mask = jnp.asarray(rng.uniform(size=(L, F)) < 0.8, bool)
    mask = mask.at[:, 0].set(True)
    ref = best = None
    for fm in (None, mask):
        ref = jax.device_get(hist.best_splits(
            H, nbins, 0.5, 2.0, 1e-5, feat_mask=fm, **cfg))
        fus = jax.device_get(hist.fused_best_splits(
            H, nbins, 0.5, 2.0, 1e-5, feat_mask=fm, **cfg))
        for name, a, b in zip(("feat", "bin", "na_left", "gain", "valid",
                               "children"), ref, fus):
            assert np.array_equal(a, b), (name, fm is not None)


def test_fused_matches_best_splits_tied_gains(cl, rng):
    """Duplicated feature columns force exact gain ties; both searches
    must resolve to the same lowest flat (feature, bin) index."""
    L, F, nbins = 4, 6, 8
    H = np.asarray(_rand_hist(rng, L, 2, nbins + 1))
    H = jnp.asarray(np.concatenate([H, H, H], axis=2))   # f, f+2, f+4 tie
    ref = jax.device_get(hist.best_splits(H, nbins, 0.5, 1.0, 1e-5))
    fus = jax.device_get(hist.fused_best_splits(H, nbins, 0.5, 1.0, 1e-5))
    for name, a, b in zip(("feat", "bin", "na_left", "gain", "valid",
                           "children"), ref, fus):
        assert np.array_equal(a, b), name
    assert (np.asarray(ref[0]) < 2).all()      # ties resolve to first copy


def test_fused_batched_matches_per_tree(cl, rng):
    """fused_best_splits_batched flattens K trees into one records pass;
    per-tree slices must equal independent fused searches."""
    K, L, F, nbins = 3, 8, 5, 16
    HK = jnp.stack([_rand_hist(rng, L, F, nbins + 1) for _ in range(K)])
    maskK = jnp.asarray(rng.uniform(size=(K, F)) < 0.7, bool)
    maskK = maskK.at[:, 0].set(True)
    bat = jax.device_get(hist.fused_best_splits_batched(
        HK, nbins, 0.5, 2.0, 1e-5, feat_mask=maskK, reg_alpha=0.2))
    for k in range(K):
        one = jax.device_get(hist.fused_best_splits(
            HK[k], nbins, 0.5, 2.0, 1e-5,
            feat_mask=jnp.broadcast_to(maskK[k], (L, F)), reg_alpha=0.2))
        for name, a, b in zip(("feat", "bin", "na_left", "gain", "valid",
                               "children"), bat, one):
            assert np.array_equal(a[k], b), (k, name)


def _tiny_problem(rng, F=5, N=1024, K=3, nbins=16):
    codes = jnp.asarray(rng.integers(0, nbins + 1, size=(F, N)), jnp.int32)
    edges = jnp.asarray(np.sort(rng.normal(size=(F, nbins)), axis=1),
                        jnp.float32)
    Y = rng.integers(0, K, size=N)
    Y1 = jnp.asarray(np.eye(K)[Y], jnp.float32)
    w = jnp.ones(N, jnp.float32)
    return codes, edges, Y1, w


@pytest.mark.parametrize("mode", ["multinomial", "drf"])
def test_batched_scan_matches_separate(cl, rng, mode):
    """One batched K-tree build per round vs the sequential per-class
    loop, chained over 3 rounds, with shared row sampling
    (sample_rate=0.8) and per-class column masks
    (col_sample_rate_per_tree=0.7) — same RNG stream on both paths."""
    F, N, K, nbins, depth = 5, 1024, 3, 16, 4
    codes, edges, Y1, w = _tiny_problem(rng, F, N, K, nbins)
    kwargs = dict(hist_precision="f32", sample_rate=0.8,
                  col_sample_rate_per_tree=0.7)
    scal = (0.5, 1.0, 1e-5, 0.1, 0.8, 0.0, 0.0, 0.0)
    key = jax.random.PRNGKey(7)
    outs = {}
    for sm in ("separate", "fused"):
        fn = shared.make_multinomial_scan_fn(
            K, depth, nbins, F, N, split_mode=sm, mode=mode, **kwargs)
        outs[sm] = jax.device_get(fn(
            codes, Y1, w, jnp.zeros((N, K), jnp.float32), edges,
            key, 0, 3, *scal))
    (Fs, lvs, vs, cs), (Ff, lvf, vf, cf) = outs["separate"], outs["fused"]
    np.testing.assert_allclose(Fs, Ff, atol=1e-5)
    for d, (a, b) in enumerate(zip(lvs, lvf)):
        va, vb = np.asarray(a[3], bool), np.asarray(b[3], bool)
        assert np.array_equal(va, vb), (d, "valid")
        # feat/thr/na_left only matter where the node actually split: the
        # fused path picks an arbitrary (feat, bin) at masked-out leaves
        assert np.array_equal(np.asarray(a[0])[va], np.asarray(b[0])[va])
        np.testing.assert_allclose(np.asarray(a[1])[va],
                                   np.asarray(b[1])[va], atol=1e-5)
        assert np.array_equal(np.asarray(a[2])[va], np.asarray(b[2])[va])
    np.testing.assert_allclose(vs, vf, atol=1e-5)
    np.testing.assert_allclose(cs, cf, atol=1e-4)


def test_single_tree_scan_fused_bitexact(cl, rng):
    """K=1: the fused split search slots into the same build — outputs
    are bit-exact vs the separate best_splits path (no batching in play,
    identical RNG, identical arithmetic off-TPU)."""
    F, N, nbins, depth = 5, 1024, 16, 4
    codes, edges, _, w = _tiny_problem(rng, F, N, 3, nbins)
    y = jnp.asarray(np.random.default_rng(3).normal(size=N), jnp.float32)
    scal = (0.5, 1.0, 1e-5, 0.1, 0.8, 0.0, 0.0, 0.0)
    outs = []
    for sm in ("separate", "fused"):
        fn = shared.make_tree_scan_fn(
            "gaussian", 1.5, 0.5, 0.9, depth, nbins, F, N, "f32",
            0.8, 0.7, split_mode=sm)
        outs.append(jax.device_get(fn(
            codes, y, w, jnp.zeros(N, jnp.float32), edges,
            jax.random.PRNGKey(7), 0, 3, *scal)))
    assert np.array_equal(outs[0][0], outs[1][0])      # F carry
    assert np.array_equal(outs[0][2], outs[1][2])      # leaf values


def test_batched_build_both_ways(cl, rng):
    """One round of K trees on the same gradients, keys and column masks:
    the batched fused build against a K-loop of separate builds, and the
    batched build on subtracted histograms against the full-histogram
    oracle.  Which nodes split and where the rows end agree exactly, how a
    node splits wherever it does, leaves to float32 tolerance."""
    F, N, K, nbins, depth = 5, 1024, 3, 16, 4
    codes, edges, _, w = _tiny_problem(rng, F, N, K, nbins)
    g = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    h = jnp.asarray(rng.uniform(0.1, 1.0, size=(K, N)), jnp.float32)
    key = jax.random.PRNGKey(11)
    keys = jnp.stack([jax.random.fold_in(key, k) for k in range(K)])
    tms = jnp.asarray(rng.uniform(size=(K, F)) < 0.8, bool)
    tms = tms.at[:, 0].set(True)
    scal = (0.5, 1.0, 1e-5, 0.1, 0.8)

    def build(k=None, **kw):
        fn = shared.make_build_tree_fn(depth, nbins, F, N, "f32", **kw)
        pick = (lambda a: a) if k is None else (lambda a: a[k])
        levels, vals, _, leaf = fn(codes, pick(g), pick(h), w, edges,
                                   pick(keys), *scal, pick(tms), 0.2, 0.1,
                                   0.0)
        return jax.device_get([[tuple(lv) for lv in levels], vals, leaf])

    def same(a, b, k=None):
        pick = (lambda x: x) if k is None else (lambda x: x[k])
        for lv_a, lv_b in zip(a[0], b[0]):
            valid = np.asarray(pick(lv_a[3]), bool)
            assert np.array_equal(valid, lv_b[3])
            for i in (0, 2):
                assert np.array_equal(pick(lv_a[i])[valid], lv_b[i][valid])
            np.testing.assert_allclose(pick(lv_a[1])[valid],
                                       lv_b[1][valid], atol=1e-4, rtol=1e-5)
        assert np.array_equal(pick(a[2]), b[2])
        np.testing.assert_allclose(pick(a[1]), b[1], atol=1e-4, rtol=1e-4)

    fused = build(nk=K, split_mode="fused")
    for k in range(K):
        same(fused, build(k), k)
    same(fused, build(nk=K, split_mode="fused", hist_mode="full"))


def test_batched_level_single_hist_dispatch(cl, rng):
    """The load-bearing claim, verified by dispatch count in the traced
    program: one batched level over K trees contains exactly ONE
    histogram pallas_call (the vmap batching rule prepends K to the
    grid; it does not replicate the launch)."""
    F, N, K, nbins = 4, 1024, 3, 8
    B = nbins + 1
    lev = hist.make_batched_level_fn(1, K, F, B, N,
                                     bin_counts=(nbins,) * F,
                                     force_impl="pallas_interpret",
                                     subtract=False)
    codes = jnp.asarray(rng.integers(0, B, size=(F, N)), jnp.int32)
    leafK = jnp.zeros((K, N), jnp.int32)
    gK = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    hK = jnp.ones((K, N), jnp.float32)
    jaxpr = jax.make_jaxpr(lev)(codes, leafK, gK, hK, hK)
    n_calls = str(jaxpr).count("pallas_call")
    assert n_calls == 1, f"expected 1 hist launch for K={K}, got {n_calls}"


@pytest.mark.parametrize("model", MODELS)
def test_estimator_fused_separate_same_trees(cl, model):
    """Two fits through split_mode's two values grow the same trees: what
    the in-training split_mode="check" compared on its first round (GBM's
    K class trees and uplift's two arms ride the batched level program
    under "fused")."""
    check_pair(model, "split_mode", ("fused", "separate"),
               sample_rate=0.8, col_sample_rate_per_tree=0.7)


def test_split_mode_bogus_fails_fast(cl):
    from h2o3_tpu.models import GBM
    with pytest.raises(ValueError, match="split_mode"):
        GBM(response_column="y", split_mode="bogus")


@pytest.mark.slow
def test_drivers_fused_matches_separate(cl, rng):
    """Full-driver parity (slow tier): GBM multinomial, DART multinomial
    (legacy loop), DRF multiclass, and UpliftDRF each produce identical
    predictions under split_mode='fused' and 'separate'."""
    from h2o3_tpu import Frame
    from h2o3_tpu.models import GBM, DRF, UpliftDRF, XGBoost
    n = 1200
    centers = np.array([[2, 0], [-2, 1], [0, -2]])
    labels = rng.integers(0, 3, n)
    X = centers[labels] + rng.normal(size=(n, 2))
    fr = Frame.from_numpy({
        "x0": X[:, 0], "x1": X[:, 1],
        "y": np.array(["a", "b", "c"], dtype=object)[labels]})

    def probs(m):
        p = m.predict(fr)
        return np.stack([p.vec(c).to_numpy() for c in "abc"], axis=1)

    for mk in (
        lambda sm: GBM(response_column="y", ntrees=6, max_depth=3, seed=4,
                       col_sample_rate_per_tree=0.7, sample_rate=0.8,
                       split_mode=sm),
        lambda sm: XGBoost(response_column="y", ntrees=5, max_depth=3,
                           seed=4, booster="dart", rate_drop=0.3,
                           one_drop=True, split_mode=sm),
        lambda sm: DRF(response_column="y", ntrees=6, max_depth=4,
                       seed=10, col_sample_rate_per_tree=0.8,
                       split_mode=sm),
    ):
        a = probs(mk("separate").train(fr))
        b = probs(mk("fused").train(fr))
        np.testing.assert_allclose(a, b, atol=1e-5)

    treat = rng.integers(0, 2, n)
    base = 1 / (1 + np.exp(-X[:, 1]))
    eff = np.where(X[:, 0] > 0, 0.3, -0.05)
    yb = (rng.random(n) < np.clip(base + treat * eff, 0.01, 0.99))
    fru = Frame.from_numpy({
        "x0": X[:, 0], "x1": X[:, 1],
        "treatment": np.array(["control", "treatment"],
                              dtype=object)[treat],
        "y": np.array(["no", "yes"], dtype=object)[yb.astype(int)]})
    us, uf = (UpliftDRF(response_column="y", treatment_column="treatment",
                        ntrees=4, max_depth=4, seed=1, split_mode=sm)
              .train(fru).predict(fru).vec("uplift_predict").to_numpy()
              for sm in ("separate", "fused"))
    np.testing.assert_allclose(us, uf, atol=1e-5)
