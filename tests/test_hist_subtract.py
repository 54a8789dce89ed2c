"""Smaller-sibling histogram subtraction: exactness + regression pins.

The subtraction level driver (hist.make_subtract_level_fn) compacts each
parent's smaller child into a dense row prefix per shard, histograms only
that prefix and reconstructs the larger sibling as parent - small from a
per-shard carry.  These tests pin (a) histogram-level parity against the
full build across chained levels, shards, weights and NA bins, (b) that
the compaction loses no rows under extreme skew (terminal leaves), and
(c) whole-model parity: GBM / DRF / uplift grow IDENTICAL trees through
hist_mode="subtract" and the hist_mode="full" oracle (tier-1 CPU shapes,
including categorical varbin features) — plus a seed-determinism pin for
isolation forest, which shares shared.py's tree plumbing.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tree_parity import MODELS, check_pair
from h2o3_tpu.models.tree.hist import (make_hist_fn, make_subtract_level_fn,
                                       offset_codes)


def _chain_leaves(rng, N, depth, p_right=0.3):
    """Consistent leaf assignments per level (child of previous level)."""
    leaves = [np.zeros(N, np.int64)]
    for _ in range(1, depth):
        bit = (rng.random(N) < p_right).astype(np.int64)
        leaves.append(2 * leaves[-1] + bit)
    return leaves


def test_subtract_level_parity_chain(cl, rng):
    """Chained subtraction levels == full einsum build, with zero-weight
    rows and NA codes in the mix (8-shard CPU mesh)."""
    N, F, nbins, depth = 2048, 5, 16, 4
    B = nbins + 1
    codes_np = rng.integers(0, B, (F, N))            # includes NA code
    codes = jnp.asarray(codes_np, jnp.int32)
    g = jnp.asarray(rng.normal(size=N), jnp.float32)
    h = jnp.asarray(rng.random(N), jnp.float32)
    w = jnp.asarray((rng.random(N) > 0.15), jnp.float32)
    carry = None
    for d, leaf_np in enumerate(_chain_leaves(rng, N, depth)):
        leaf = jnp.asarray(leaf_np, jnp.int32)
        if d == 0:
            Hg, carry = make_subtract_level_fn(0, F, B, N)(
                codes, leaf, g, h, w)
        else:
            Hg, carry = make_subtract_level_fn(d, F, B, N)(
                codes, leaf, g, h, w, carry)
        Hf = make_hist_fn(2 ** d, F, B, N, force_impl="einsum")(
            codes, leaf, g, h, w)
        np.testing.assert_allclose(np.asarray(Hg), np.asarray(Hf),
                                   atol=1e-4, rtol=1e-5)
        assert carry.shape == (cl.n_row_shards, 3, 2 ** d, F, B)
        # carries sum to the global histogram (they ARE the pre-psum parts)
        np.testing.assert_allclose(np.asarray(carry).sum(axis=0),
                                   np.asarray(Hf), atol=1e-4, rtol=1e-5)


def test_subtract_level_varbin_parity(cl, rng):
    """The varbin (packed ragged bins, interpret Pallas) inner kernel
    through compaction + subtraction == dense einsum full build."""
    N, F, nbins = 2048, 5, 32
    B = nbins + 1
    bin_counts = (7, 32, 22, 3, 32)
    codes_np = np.stack([
        np.where(rng.random(N) < 0.1, nbins, rng.integers(0, bc, N))
        for bc in bin_counts])
    codes = jnp.asarray(codes_np, jnp.int32)
    gcodes = offset_codes(codes, bin_counts, nbins)
    g = jnp.asarray(rng.normal(size=N), jnp.float32)
    h = jnp.ones(N, jnp.float32)
    w = jnp.asarray((rng.random(N) > 0.1), jnp.float32)
    carry = None
    for d, leaf_np in enumerate(_chain_leaves(rng, N, 3)):
        leaf = jnp.asarray(leaf_np, jnp.int32)
        fn = make_subtract_level_fn(d, F, B, N, bin_counts=bin_counts,
                                    force_impl="pallas_interpret",
                                    precision="f32")
        if d == 0:
            Hg, carry = fn(gcodes, leaf, g, h, w)
        else:
            Hg, carry = fn(gcodes, leaf, g, h, w, carry)
        Hf = make_hist_fn(2 ** d, F, B, N, force_impl="einsum")(
            codes, leaf, g, h, w)
        np.testing.assert_allclose(np.asarray(Hg), np.asarray(Hf),
                                   atol=1e-4, rtol=1e-5)


def test_compaction_extreme_skew_no_row_loss(cl, rng):
    """Terminal-leaf shape: EVERY row routes to the left child, so the
    smaller sibling is the empty right child and the compacted prefix is
    empty — the left histogram must still be exactly the parent."""
    N, F, nbins = 1024, 3, 8
    B = nbins + 1
    codes = jnp.asarray(rng.integers(0, B, (F, N)), jnp.int32)
    g = jnp.asarray(rng.normal(size=N), jnp.float32)
    h = jnp.asarray(rng.random(N), jnp.float32)
    w = jnp.ones(N, jnp.float32)
    leaf0 = jnp.zeros(N, jnp.int32)
    H0, carry = make_subtract_level_fn(0, F, B, N)(codes, leaf0, g, h, w)
    H1, _ = make_subtract_level_fn(1, F, B, N)(codes, leaf0, g, h, w, carry)
    H1 = np.asarray(H1)
    np.testing.assert_allclose(H1[:, 0], np.asarray(H0)[:, 0],
                               atol=1e-5, rtol=1e-6)
    np.testing.assert_array_equal(H1[:, 1], 0.0)
    # the flip side: every row right
    leaf_r = jnp.ones(N, jnp.int32)
    H1r, _ = make_subtract_level_fn(1, F, B, N)(codes, leaf_r, g, h, w,
                                                carry)
    H1r = np.asarray(H1r)
    np.testing.assert_allclose(H1r[:, 1], np.asarray(H0)[:, 0],
                               atol=1e-5, rtol=1e-6)
    np.testing.assert_array_equal(H1r[:, 0], 0.0)


def test_build_tree_subtract_equals_full(cl, rng):
    """Whole-tree growth: subtraction path == full oracle (structure,
    routing and leaf values) on planted-signal data with NAs and
    zero-weight rows."""
    from h2o3_tpu.models.tree.shared import build_tree
    N, F, nbins, depth = 4096, 5, 32, 4
    codes_np = rng.integers(0, nbins, (F, N))
    codes_np[2] = np.where(rng.random(N) < 0.08, nbins, codes_np[2])
    codes = jnp.asarray(codes_np, jnp.int32)
    g_np = (np.where(codes_np[1] <= 12, -2.0, 2.0)
            + np.where(codes_np[3] <= 20, -0.7, 0.7)
            + 0.05 * rng.normal(size=N))
    g = jnp.asarray(g_np, jnp.float32)
    h = jnp.ones(N, jnp.float32)
    w = jnp.asarray((rng.random(N) > 0.1), jnp.float32)
    edges = [np.sort(rng.normal(size=nbins - 1)).astype(np.float32)
             for _ in range(F)]
    key = jax.random.PRNGKey(7)
    kw = dict(hist_precision="f32")
    t_f, leaf_f = build_tree(codes, g * w, h * w, w, edges, nbins, depth,
                             1.0, 5.0, 1e-5, 0.1, key, hist_mode="full",
                             **kw)
    t_s, leaf_s = build_tree(codes, g * w, h * w, w, edges, nbins, depth,
                             1.0, 5.0, 1e-5, 0.1, key, hist_mode="subtract",
                             **kw)
    np.testing.assert_array_equal(np.asarray(leaf_f), np.asarray(leaf_s))
    for d in range(depth):
        np.testing.assert_array_equal(np.asarray(t_f.feat[d]),
                                      np.asarray(t_s.feat[d]))
        np.testing.assert_array_equal(np.asarray(t_f.valid[d]),
                                      np.asarray(t_s.valid[d]))
        np.testing.assert_allclose(np.asarray(t_f.thr[d]),
                                   np.asarray(t_s.thr[d]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(t_f.values),
                               np.asarray(t_s.values), atol=1e-5)


def _airlines_tiny(rng, n=800, with_na=True):
    """Tiny airlines-shaped frame: numerics + categoricals (+ NAs)."""
    from h2o3_tpu import Frame
    from h2o3_tpu.frame.vec import T_CAT
    dist = np.abs(rng.normal(700, 500, n)).astype(np.float64)
    dep = rng.integers(0, 2400, n).astype(np.float64)
    if with_na:
        dist[rng.random(n) < 0.1] = np.nan
    carrier = rng.integers(0, 7, n)
    dow = rng.integers(0, 5, n)
    logit = (0.002 * (dep / 100 - 12) ** 2 - 0.0005 * dist / 100
             + 0.3 * (carrier == 2) + 0.1 * rng.normal(size=n))
    y = rng.random(n) < 1 / (1 + np.exp(-np.nan_to_num(logit)))
    cols = {"dep": dep, "dist": dist, "carrier": carrier, "dow": dow,
            "delayed": np.where(y, "YES", "NO").astype(object)}
    types = {"carrier": T_CAT, "dow": T_CAT}
    domains = {"carrier": [str(i) for i in range(7)],
               "dow": [str(i) for i in range(5)]}
    return Frame.from_numpy(cols, types=types, domains=domains)


def _assert_same_trees(m_s, m_f):
    """Tree-for-tree structural equality between two trained models."""
    trees_s, trees_f = list(m_s.output["trees"]), list(m_f.output["trees"])
    assert len(trees_s) == len(trees_f)
    for ts, tf in zip(trees_s, trees_f):
        ts_list = ts if isinstance(ts, list) else [ts]
        tf_list = tf if isinstance(tf, list) else [tf]
        for a, b in zip(ts_list, tf_list):
            for d in range(len(a.feat)):
                np.testing.assert_array_equal(np.asarray(a.feat[d]),
                                              np.asarray(b.feat[d]))
                np.testing.assert_array_equal(np.asarray(a.valid[d]),
                                              np.asarray(b.valid[d]))
                np.testing.assert_allclose(np.asarray(a.thr[d]),
                                           np.asarray(b.thr[d]), rtol=1e-6)
            np.testing.assert_allclose(np.asarray(a.values),
                                       np.asarray(b.values), atol=1e-5)


def test_gbm_subtract_parity_airlines(cl, rng):
    """Satellite: subtraction-path GBM == full build on a tiny airlines
    shape — identical split structure and predictions, NA buckets and
    categorical features included (reproducible=True pins f32 kernels)."""
    from h2o3_tpu.models.tree.gbm import GBM
    fr = _airlines_tiny(rng)
    kw = dict(response_column="delayed", ntrees=8, max_depth=4, nbins=16,
              min_rows=5, seed=11, reproducible=True)
    m_s = GBM(hist_mode="subtract", **kw).train(fr)
    m_f = GBM(hist_mode="full", **kw).train(fr)
    _assert_same_trees(m_s, m_f)
    np.testing.assert_allclose(
        m_s.predict(fr).vec("YES").to_numpy(),
        m_f.predict(fr).vec("YES").to_numpy(), atol=1e-6)


def test_gbm_subtract_parity_higgs_numeric(cl, rng):
    """Satellite: parity on a higgs-like all-numeric binary shape, with
    row sampling active (w=0 rows must not corrupt the compaction)."""
    from h2o3_tpu.models.tree.gbm import GBM
    from h2o3_tpu import Frame
    n = 1000
    X = rng.normal(size=(n, 4))
    y = (X[:, 0] * X[:, 1] + X[:, 2] ** 2 - 1
         + 0.3 * rng.normal(size=n)) > 0
    cols = {f"f{j}": X[:, j] for j in range(4)}
    cols["y"] = np.where(y, "s", "b").astype(object)
    fr = Frame.from_numpy(cols)
    kw = dict(response_column="y", ntrees=6, max_depth=4, nbins=32,
              sample_rate=0.7, min_rows=3, seed=5, reproducible=True)
    m_s = GBM(hist_mode="subtract", **kw).train(fr)
    m_f = GBM(hist_mode="full", **kw).train(fr)
    _assert_same_trees(m_s, m_f)


@pytest.mark.parametrize("model", MODELS)
def test_estimator_subtract_full_same_trees(cl, model):
    """Two fits through hist_mode's two values grow the same trees: what
    the in-training hist_mode="check" compared on its first tree."""
    check_pair(model, "hist_mode", ("subtract", "full"))


def test_hist_mode_validation(cl):
    from h2o3_tpu.models.tree.shared import resolve_hist_mode
    from h2o3_tpu.models.tree.xgboost import XGBoost
    with pytest.raises(ValueError, match="hist_mode"):
        resolve_hist_mode(type("P", (), {"hist_mode": "bogus"})())
    with pytest.raises(ValueError, match="hist_mode"):
        XGBoost(response_column="y", hist_mode="bogus")


def test_drf_subtract_equals_full(cl, rng):
    """Satellite: DRF (bootstrap + mtries through the shared scan driver)
    grows identical forests under both histogram modes."""
    from h2o3_tpu.models.tree.drf import DRF
    fr = _airlines_tiny(rng, n=600)
    kw = dict(response_column="delayed", ntrees=6, max_depth=4, nbins=16,
              min_rows=2, seed=7, reproducible=True)
    m_s = DRF(hist_mode="subtract", **kw).train(fr)
    m_f = DRF(hist_mode="full", **kw).train(fr)
    _assert_same_trees(m_s, m_f)
    np.testing.assert_allclose(
        m_s.predict(fr).vec("YES").to_numpy(),
        m_f.predict(fr).vec("YES").to_numpy(), atol=1e-6)


def test_uplift_subtract_equals_full(cl, rng):
    """Satellite: uplift DRF's two-arm histograms through the subtraction
    level driver == the full build, tree for tree."""
    from h2o3_tpu.models.tree.uplift import UpliftDRF
    from h2o3_tpu import Frame
    n = 600
    x0 = rng.normal(size=n)
    x1 = rng.normal(size=n)
    treat = rng.integers(0, 2, n)
    p = 1 / (1 + np.exp(-(0.5 * x0 + 0.8 * treat * (x1 > 0))))
    y = (rng.random(n) < p).astype(int)
    fr = Frame.from_numpy({
        "x0": x0, "x1": x1,
        "treatment": treat.astype(np.float64),
        "y": np.array(["no", "yes"], dtype=object)[y]})
    kw = dict(response_column="y", treatment_column="treatment", ntrees=3,
              max_depth=3, nbins=16, min_rows=5, seed=9, sample_rate=0.8,
              reproducible=True)
    m_s = UpliftDRF(hist_mode="subtract", **kw).train(fr)
    m_f = UpliftDRF(hist_mode="full", **kw).train(fr)
    _assert_same_trees(m_s, m_f)


def test_isofor_determinism_regression(cl, rng):
    """Isolation forest shares shared.py's tree plumbing but no histograms;
    pin that the reworked driver leaves it bit-deterministic per seed."""
    from h2o3_tpu.models.tree.isofor import IsolationForest
    from h2o3_tpu import Frame
    n = 500
    X = rng.normal(size=(n, 3))
    X[:10] += 6.0                                    # planted anomalies
    fr = Frame.from_numpy({f"x{j}": X[:, j] for j in range(3)})
    kw = dict(ntrees=10, sample_size=128, max_depth=6, seed=21)
    m1 = IsolationForest(**kw).train(fr)
    m2 = IsolationForest(**kw).train(fr)
    for t1, t2 in zip(m1.output["trees"], m2.output["trees"]):
        for d in range(len(t1.feat)):
            np.testing.assert_array_equal(np.asarray(t1.feat[d]),
                                          np.asarray(t2.feat[d]))
            np.testing.assert_array_equal(np.asarray(t1.thr[d]),
                                          np.asarray(t2.thr[d]))
        np.testing.assert_array_equal(np.asarray(t1.values),
                                      np.asarray(t2.values))
    s1 = m1.predict(fr).vecs[0].to_numpy()
    s2 = m2.predict(fr).vecs[0].to_numpy()
    np.testing.assert_array_equal(s1, s2)
    # anomalies rank above the bulk
    assert s1[:10].mean() > s1[10:].mean()
