"""Node-sparse deep-level layout: parity + regression pins.

Past the depth threshold the builder switches from the dense [2^d, F, B]
histogram grid to [A, F, B] slots keyed by ALIVE leaves
(hist.make_sparse_level_fn).  These tests pin (a) bit-identity of the
sparse kernel against the dense subtraction kernel when the slot map is
the identity, (b) the varbin inner kernel through the sparse body,
(c) dense-vs-sparse whole-tree parity through shared.make_build_tree_fn
under NA / skew / col-sampling / batched-K / dead-chain shapes including
the one-alive-leaf-at-depth-10 extreme, (d) the slot-assignment math
(atomic pair drop on overflow, determinism), (e) the dispatch-count pin
— 2 pallas launches per sparse level (hist + fused records), and
(f) driver-level parity: GBM / DRF / XGBoost / UpliftDRF grow IDENTICAL
trees through hist_layout="sparse" and the dense oracle.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from h2o3_tpu.models.tree import hist, shared
from tree_parity import MODELS, check_pair
from h2o3_tpu.models.tree.hist import (fused_best_splits,
                                       make_hist_fn,
                                       make_sparse_level_fn,
                                       make_subtract_level_fn,
                                       offset_codes, sparse_slot_maps)


def _chain_leaves(rng, N, depth, p_right=0.3):
    """Consistent leaf assignments per level (child of previous level)."""
    leaves = [np.zeros(N, np.int64)]
    for _ in range(1, depth):
        bit = (rng.random(N) < p_right).astype(np.int64)
        leaves.append(2 * leaves[-1] + bit)
    return leaves


# --------------------------------------------------------------- kernel layer

def test_sparse_level_identity_bit_parity(cl, rng):
    """All parents valid and A = 2^d makes the slot map the identity; the
    sparse level must then be BIT-identical to the dense subtraction
    level — histogram and per-shard carry both (same compaction prefix,
    same subtraction order)."""
    N, F, nbins, depth = 2048, 5, 16, 4
    B = nbins + 1
    codes = jnp.asarray(rng.integers(0, B, (F, N)), jnp.int32)
    g = jnp.asarray(rng.normal(size=N), jnp.float32)
    h = jnp.asarray(rng.random(N), jnp.float32)
    w = jnp.asarray((rng.random(N) > 0.15), jnp.float32)
    leaves = _chain_leaves(rng, N, depth)
    _, carry = make_subtract_level_fn(0, F, B, N)(
        codes, jnp.zeros(N, jnp.int32), g, h, w)
    for d in range(1, depth):
        leaf = jnp.asarray(leaves[d], jnp.int32)
        A_prev, A = 2 ** (d - 1), 2 ** d
        Hd, carry_d = make_subtract_level_fn(d, F, B, N)(
            codes, leaf, g, h, w, carry)
        ps = jnp.arange(A, dtype=jnp.int32) // 2
        Hs, carry_s = make_sparse_level_fn(A_prev, A, F, B, N)(
            codes, leaf, g, h, w, carry, ps)
        np.testing.assert_array_equal(np.asarray(Hs), np.asarray(Hd))
        np.testing.assert_array_equal(np.asarray(carry_s),
                                      np.asarray(carry_d))
        carry = carry_d


def test_sparse_level_varbin_parity(cl, rng):
    """The varbin (packed ragged bins, interpret Pallas) inner kernel
    through the sparse body == dense einsum full build at the identity
    slot map — the categorical-feature path below the depth threshold."""
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
    leaves = _chain_leaves(rng, N, 3)
    _, carry = make_subtract_level_fn(
        0, F, B, N, bin_counts=bin_counts, force_impl="pallas_interpret",
        precision="f32")(gcodes, jnp.zeros(N, jnp.int32), g, h, w)
    for d in (1, 2):
        leaf = jnp.asarray(leaves[d], jnp.int32)
        ps = jnp.arange(2 ** d, dtype=jnp.int32) // 2
        Hs, carry = make_sparse_level_fn(
            2 ** (d - 1), 2 ** d, F, B, N, bin_counts=bin_counts,
            force_impl="pallas_interpret", precision="f32")(
                gcodes, leaf, g, h, w, carry, ps)
        Hf = make_hist_fn(2 ** d, F, B, N, force_impl="einsum")(
            codes, leaf, g, h, w)
        np.testing.assert_allclose(np.asarray(Hs), np.asarray(Hf),
                                   atol=1e-4, rtol=1e-5)


def test_sparse_slot_maps_overflow_atomic(cl):
    """More alive children than slots: later pairs are dropped ATOMICALLY
    in slot order (both children or neither), dropped parents read the
    A_next sentinel in child_base, phantom slots are masked off by
    ``real`` — and the assignment is deterministic."""
    valid = np.ones(16, bool)
    valid[[2, 5, 11, 13]] = False                       # 12 alive parents
    out1 = jax.device_get(sparse_slot_maps(jnp.asarray(valid), 16))
    out2 = jax.device_get(sparse_slot_maps(jnp.asarray(valid), 16))
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)             # deterministic
    child_base, ps_of_slot, real = out1
    kept_parents = np.flatnonzero(valid)[:8]            # 8 pairs fit in 16
    rank = 0
    for p in range(16):
        if p in kept_parents:
            assert child_base[p] == 2 * rank
            assert ps_of_slot[2 * rank] == p
            assert ps_of_slot[2 * rank + 1] == p
            rank += 1
        else:
            assert child_base[p] == 16                  # dropped/invalid
    assert child_base[16] == 16                         # sentinel row
    assert real.all()                                   # 8 pairs fill 16
    # head-room case: the same parents with A_next=32 keep ALL 12 pairs
    # and the phantom tail is masked off
    child_base, ps_of_slot, real = jax.device_get(
        sparse_slot_maps(jnp.asarray(valid), 32))
    assert (child_base[np.flatnonzero(valid)] < 32).all()
    np.testing.assert_array_equal(real, np.arange(32) < 24)


def test_sparse_level_dispatch_count(cl):
    """The deep-level pin: one sparse histogram launch + one fused
    split-records launch per level — 2 pallas_calls, independent of how
    many leaves are alive."""
    Ap, A, F, nbins, N = 8, 16, 4, 16, 2048
    B = nbins + 1
    lev = make_sparse_level_fn(Ap, A, F, B, N, bin_counts=(nbins,) * F,
                               force_impl="pallas_interpret")

    def level(codes, sleaf, g, h, w, carry, ps):
        H, carry2 = lev(codes, sleaf, g, h, w, carry, ps)
        return fused_best_splits(H, nbins, 1.0, 1.0, 1e-5,
                                 force_impl="pallas"), carry2

    codes = jnp.zeros((F, N), jnp.int32)
    sleaf = jnp.zeros(N, jnp.int32)
    g = jnp.zeros(N, jnp.float32)
    carry = jnp.zeros((cl.n_row_shards, 3, Ap, F, B), jnp.float32)
    ps = jnp.arange(A, dtype=jnp.int32) // 2
    jaxpr = str(jax.make_jaxpr(level)(codes, sleaf, g, g, g, carry, ps))
    assert jaxpr.count("pallas_call") == 2


# ------------------------------------------------------------- build-tree fns

def _compare_builds(outs, md):
    """Dense-vs-sparse build parity: valid + routing exact, feat/na exact
    where valid (dense keeps candidate records on dead slots, sparse
    drops them), thresholds/values f32-close."""
    lv_d, v_d, leaf_d = outs["dense"]
    lv_s, v_s, leaf_s = outs["sparse"]
    for d in range(md):
        vd = np.asarray(lv_d[d][3], bool)
        vs = np.asarray(lv_s[d][3], bool)
        np.testing.assert_array_equal(vd, vs, err_msg=f"valid, level {d}")
        for name, i in (("feat", 0), ("na", 2)):
            a, b = np.asarray(lv_d[d][i]), np.asarray(lv_s[d][i])
            np.testing.assert_array_equal(a[vd], b[vd],
                                          err_msg=f"{name}, level {d}")
        a, b = np.asarray(lv_d[d][1]), np.asarray(lv_s[d][1])
        np.testing.assert_allclose(a[vd], b[vd], atol=1e-5, rtol=1e-5,
                                   err_msg=f"thr, level {d}")
    np.testing.assert_array_equal(np.asarray(leaf_d), np.asarray(leaf_s))
    np.testing.assert_allclose(np.asarray(v_d), np.asarray(v_s),
                               atol=1e-4, rtol=1e-4)


def _skewed_inputs(rng, F, N, nbins):
    base = rng.integers(0, nbins, size=(F, N))
    base[:, : N // 2] = 3                 # half the rows identical -> skew
    base[0, rng.integers(0, N, size=100)] = nbins            # NAs
    codes = jnp.asarray(base, jnp.int32)
    g = jnp.asarray(rng.normal(size=N), jnp.float32)
    h = jnp.ones(N, jnp.float32)
    w = jnp.asarray((rng.random(N) > 0.1).astype(np.float32))
    edges = jnp.asarray(rng.normal(size=(F, nbins)).cumsum(axis=1),
                        jnp.float32)
    return codes, g, h, w, edges


def test_build_tree_sparse_equals_dense(cl, rng):
    """Single tree, fused split search, column sampling, NAs, skewed
    codes: the sparse deep levels (threshold 3 of depth 7) grow the SAME
    tree as the dense grid."""
    F, N, nbins, md = 5, 2048, 16, 7
    codes, g, h, w, edges = _skewed_inputs(rng, F, N, nbins)
    key = jax.random.PRNGKey(7)
    tm = jnp.ones(F, bool)
    outs = {}
    for layout in ("dense", "sparse"):
        fn = shared.make_build_tree_fn(
            md, nbins, F, N, "f32", hist_mode="subtract",
            split_mode="fused", hist_layout=layout,
            sparse_depth_threshold=3)
        levels, vals, cover, leaf = fn(codes, g, h, w, edges, key, 0.5,
                                       2.0, 1e-5, 0.1, 0.7, tm, 0.1,
                                       0.01, 0.0)
        outs[layout] = jax.device_get([[list(l) for l in levels], vals,
                                       leaf])
    _compare_builds(outs, md)


def test_build_tree_sparse_batched_k3(cl, rng):
    """Batched K=3 trees through make_batched_sparse_level_fn: one
    launch per level for all K trees, same trees as the dense grid."""
    F, N, nbins, md, K = 5, 2048, 16, 7, 3
    codes, _, _, w, edges = _skewed_inputs(rng, F, N, nbins)
    gK = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    hK = jnp.ones((K, N), jnp.float32)
    keysK = jax.vmap(jax.random.PRNGKey)(jnp.arange(K))
    tmK = jnp.ones((K, F), bool)
    outs = {}
    for layout in ("dense", "sparse"):
        fn = shared.make_build_tree_fn(
            md, nbins, F, N, "f32", hist_mode="subtract",
            split_mode="fused", nk=K, hist_layout=layout,
            sparse_depth_threshold=3)
        levels, vals, cover, leaf = fn(codes, gK, hK, w, edges, keysK,
                                       0.5, 2.0, 1e-5, 0.1, 0.7, tmK,
                                       0.1, 0.01, 0.0)
        outs[layout] = jax.device_get([[list(l) for l in levels], vals,
                                       leaf])
    _compare_builds(outs, md)


def test_build_tree_sparse_dead_chains(cl, rng):
    """Constant features kill the root's children immediately: every
    deeper sparse level runs with (almost) no live slots, and the dead
    chains must stay dead on both layouts (terminality invariant)."""
    F, N, nbins = 5, 2048, 16
    codes = jnp.asarray(np.full((F, N), 2, np.int16))
    g = jnp.asarray(rng.normal(size=N), jnp.float32)
    h = jnp.ones(N, jnp.float32)
    w = jnp.asarray((rng.random(N) > 0.1).astype(np.float32))
    edges = jnp.asarray(rng.normal(size=(F, nbins)).cumsum(axis=1),
                        jnp.float32)
    key = jax.random.PRNGKey(3)
    tm = jnp.ones(F, bool)
    outs = {}
    for layout in ("dense", "sparse"):
        fn = shared.make_build_tree_fn(
            5, nbins, F, N, "f32", hist_mode="subtract",
            split_mode="separate", hist_layout=layout,
            sparse_depth_threshold=2)
        levels, vals, cover, leaf = fn(codes, g, h, w, edges, key, 0.0,
                                       1.0, 1e-5, 0.1, 1.0, tm, 0.0, 0.0,
                                       0.0)
        outs[layout] = jax.device_get([[list(l) for l in levels], vals,
                                       leaf])
    _compare_builds(outs, 5)


def test_build_tree_one_alive_leaf_depth_10(cl, rng):
    """Extreme leaf-count skew: gradients grow geometrically with the
    bin, so every level peels bins off the top and only 1-2 of the up to
    2^d nodes stay alive all the way to depth 10 — the shape the sparse
    layout exists for.  Parity must hold and the alive count per deep
    level must stay O(1), not O(2^d)."""
    F, N, nbins, md = 2, 2048, 32, 10
    codes_np = np.stack([rng.integers(0, nbins, N),
                         np.full(N, 3)])              # 2nd feature constant
    codes = jnp.asarray(codes_np, jnp.int32)
    g = jnp.asarray(-(1.7 ** codes_np[0]) / 100.0, jnp.float32)
    h = jnp.ones(N, jnp.float32)
    w = jnp.ones(N, jnp.float32)
    edges = jnp.asarray(
        np.stack([np.arange(nbins, dtype=np.float64)] * F), jnp.float32)
    key = jax.random.PRNGKey(5)
    tm = jnp.ones(F, bool)
    outs = {}
    for layout in ("dense", "sparse"):
        fn = shared.make_build_tree_fn(
            md, nbins, F, N, "f32", hist_mode="subtract",
            split_mode="fused", hist_layout=layout,
            sparse_depth_threshold=2)
        levels, vals, cover, leaf = fn(codes, g, h, w, edges, key, 1.0,
                                       1.0, 1e-5, 0.1, 1.0, tm, 0.0, 0.0,
                                       0.0)
        outs[layout] = jax.device_get([[list(l) for l in levels], vals,
                                       leaf])
    _compare_builds(outs, md)
    for d in range(1, md):
        n_alive = int(np.asarray(outs["sparse"][0][d][3], bool).sum())
        assert 1 <= n_alive <= 2, (d, n_alive)


def test_build_tree_sparse_varbin(cl, rng, monkeypatch):
    """Categorical (ragged-bin) features through the sparse deep levels:
    H2O3_TPU_HIST_IMPL=varbin forces the packed interpret-Pallas inner
    kernel off-TPU; dense and sparse layouts must still agree."""
    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", "varbin")
    F, N, nbins, md = 4, 2048, 32, 6
    bin_counts = (32, 32, 7, 5)
    codes_np = np.stack([
        np.where(rng.random(N) < 0.1, nbins, rng.integers(0, bc, N))
        for bc in bin_counts])
    codes = jnp.asarray(codes_np, jnp.int32)
    g = jnp.asarray(rng.normal(size=N), jnp.float32)
    h = jnp.ones(N, jnp.float32)
    w = jnp.asarray((rng.random(N) > 0.1).astype(np.float32))
    edges = jnp.asarray(rng.normal(size=(F, nbins)).cumsum(axis=1),
                        jnp.float32)
    key = jax.random.PRNGKey(11)
    tm = jnp.ones(F, bool)
    outs = {}
    for layout in ("dense", "sparse"):
        fn = shared.make_build_tree_fn(
            md, nbins, F, N, "f32", bin_counts=bin_counts,
            hist_mode="subtract", split_mode="fused", hist_layout=layout,
            sparse_depth_threshold=3)
        levels, vals, cover, leaf = fn(codes, g, h, w, edges, key, 0.5,
                                       2.0, 1e-5, 0.1, 1.0, tm, 0.0, 0.0,
                                       0.0)
        outs[layout] = jax.device_get([[list(l) for l in levels], vals,
                                       leaf])
    _compare_builds(outs, md)


def test_effective_depth_sparse_drops_memory_cap(cl):
    """The 64 MB dense wall (depth 10 at 256 bins, 32 features — the
    Kaggle-shape workload) does not apply to the sparse layout:
    effective depth becomes row-capped only, so depth-12/256-bin trains
    that the dense grid must truncate."""
    F, nbins, N = 32, 256, 8192
    assert shared.dense_mem_cap(nbins, F) == 10
    assert shared.effective_max_depth(12, nbins, F, N) == 10
    assert shared.effective_max_depth(
        12, nbins, F, N, hist_layout="sparse") == 12
    assert shared.effective_max_depth(
        12, nbins, F, N, hist_layout="auto") == 12


# ------------------------------------------------------------------- drivers

def _airlines(rng, n=800, with_na=True, multiclass=False):
    """Airlines-shaped frame: numerics + categoricals + NAs."""
    from h2o3_tpu import Frame
    from h2o3_tpu.frame.vec import T_CAT
    dist = np.abs(rng.normal(700, 500, n)).astype(np.float64)
    dep = rng.integers(0, 2400, n).astype(np.float64)
    if with_na:
        dist[rng.random(n) < 0.1] = np.nan
    carrier = rng.integers(0, 7, n)
    dow = rng.integers(0, 5, n)
    logit = (0.002 * (dep / 100 - 12) ** 2 - 0.0005 * np.nan_to_num(dist)
             / 100 + 0.3 * (carrier == 2) + 0.1 * rng.normal(size=n))
    if multiclass:
        y3 = np.digitize(logit, np.quantile(logit, [0.33, 0.66]))
        resp = np.array(["A", "B", "C"], dtype=object)[y3]
    else:
        yy = rng.random(n) < 1 / (1 + np.exp(-logit))
        resp = np.where(yy, "YES", "NO").astype(object)
    return Frame.from_numpy(
        {"dep": dep, "dist": dist, "carrier": carrier, "dow": dow,
         "delayed": resp},
        types={"carrier": T_CAT, "dow": T_CAT},
        domains={"carrier": [str(i) for i in range(7)],
                 "dow": [str(i) for i in range(5)]})


def _assert_same_routing(m_a, m_b):
    """Same trees node-for-node: valid flags exact, split features equal
    wherever the node is valid."""
    ta, tb = list(m_a.output["trees"]), list(m_b.output["trees"])
    assert len(ta) == len(tb)
    for xs, ys in zip(ta, tb):
        xs = xs if isinstance(xs, list) else [xs]
        ys = ys if isinstance(ys, list) else [ys]
        for a, b in zip(xs, ys):
            for d in range(len(a.feat)):
                va = np.asarray(a.valid[d])
                vb = np.asarray(b.valid[d])
                np.testing.assert_array_equal(va, vb)
                np.testing.assert_array_equal(
                    np.where(va, np.asarray(a.feat[d]), 0),
                    np.where(vb, np.asarray(b.feat[d]), 0))


def _assert_same_preds(m_a, m_b, fr, col, atol=1e-4):
    a = m_a.predict(fr).vec(col).to_numpy()
    b = m_b.predict(fr).vec(col).to_numpy()
    np.testing.assert_allclose(a, b, atol=atol, rtol=1e-4)


_DRIVER_KW = dict(response_column="delayed", ntrees=3, max_depth=6,
                  nbins=16, min_rows=2, seed=11, reproducible=True,
                  sparse_depth_threshold=2)


@pytest.mark.parametrize("model", MODELS)
def test_estimator_dense_sparse_same_trees(cl, model):
    """Two fits through hist_layout's two values (sparse from level 2 of
    6) grow the same trees: what the in-training hist_layout="check"
    compared on its first tree."""
    dense, sparse = check_pair(model, "hist_layout", ("dense", "sparse"),
                               max_depth=6, sparse_depth_threshold=2)
    assert dense.output["hist_layout"] == "dense"
    assert sparse.output["hist_layout"] == "sparse"


def test_gbm_sparse_whole_model_parity(cl, rng):
    from h2o3_tpu.models.tree.gbm import GBM
    fr = _airlines(rng)
    m_d = GBM(hist_layout="dense", **_DRIVER_KW).train(fr)
    m_s = GBM(hist_layout="sparse", **_DRIVER_KW).train(fr)
    assert m_s.output["hist_layout"] == "sparse"
    assert m_d.output["hist_layout"] == "dense"
    _assert_same_routing(m_d, m_s)
    _assert_same_preds(m_d, m_s, fr, "YES")


def test_gbm_multinomial_sparse_parity(cl, rng):
    """Batched K-tree (one launch per level for all class trees) through
    the sparse slot layout."""
    from h2o3_tpu.models.tree.gbm import GBM
    fr3 = _airlines(rng, multiclass=True)
    m_d = GBM(hist_layout="dense", **_DRIVER_KW).train(fr3)
    m_s = GBM(hist_layout="sparse", **_DRIVER_KW).train(fr3)
    _assert_same_routing(m_d, m_s)
    _assert_same_preds(m_d, m_s, fr3, "B")


def test_drf_sparse_whole_model_parity(cl, rng):
    from h2o3_tpu.models.tree.drf import DRF
    fr = _airlines(rng)
    m_d = DRF(hist_layout="dense", **_DRIVER_KW).train(fr)
    m_s = DRF(hist_layout="sparse", **_DRIVER_KW).train(fr)
    _assert_same_routing(m_d, m_s)
    _assert_same_preds(m_d, m_s, fr, "YES")


def test_xgboost_sparse_parity_and_fail_fast(cl, rng):
    from h2o3_tpu.models.tree.xgboost import XGBoost
    fr = _airlines(rng)
    m_d = XGBoost(hist_layout="dense", **_DRIVER_KW).train(fr)
    m_s = XGBoost(hist_layout="sparse", **_DRIVER_KW).train(fr)
    _assert_same_routing(m_d, m_s)
    _assert_same_preds(m_d, m_s, fr, "YES")
    with pytest.raises(ValueError, match="hist_layout"):
        XGBoost(response_column="y", hist_layout="bogus")


@pytest.mark.heavy
def test_depth12_256bin_trains_past_dense_wall(cl, rng):
    """The ISSUE-7 acceptance run: a depth-12, 256-bin, 32-feature GBM
    (and the batched-K=3 multinomial equivalent) trains under the 64 MB
    histogram budget with the sparse layout, where the dense layout must
    truncate at depth 10 (its memory cap at this geometry)."""
    from h2o3_tpu import Frame
    from h2o3_tpu.models.tree.gbm import GBM
    n, F = 3000, 32
    X = rng.normal(size=(n, F))
    y = X[:, :4].sum(axis=1) + 0.3 * rng.normal(size=n)
    cols = {f"x{i}": X[:, i] for i in range(F)}
    fr = Frame.from_numpy({**cols, "y": y})
    kw = dict(response_column="y", ntrees=1, max_depth=12, nbins=256,
              min_rows=1, seed=3, reproducible=True)
    with pytest.warns(UserWarning, match="capped to 10"):
        m_dense = GBM(hist_layout="dense", **kw).train(fr)
    assert m_dense.output["effective_max_depth"] == 10
    m_sparse = GBM(hist_layout="sparse", **kw).train(fr)
    assert m_sparse.output["effective_max_depth"] == 12
    tree = m_sparse.output["trees"][0]
    tree = tree[0] if isinstance(tree, list) else tree
    assert len(tree.feat) == 12
    # batched-K=3 multinomial at the same deep geometry
    y3 = np.array(["A", "B", "C"], dtype=object)[
        np.digitize(y, np.quantile(y, [0.33, 0.66]))]
    fr3 = Frame.from_numpy({**cols, "y": y3})
    m3 = GBM(hist_layout="sparse", **kw).train(fr3)
    assert m3.output["effective_max_depth"] == 12
    assert len(m3.output["trees"][0]) == 3           # K class trees


def test_uplift_sparse_whole_model_parity(cl, rng):
    from h2o3_tpu import Frame
    from h2o3_tpu.models.tree.uplift import UpliftDRF
    n = 800
    x0 = rng.normal(size=n)
    x1 = rng.normal(size=n)
    treat = rng.integers(0, 2, n)
    pp = 1 / (1 + np.exp(-(0.5 * x0 + 0.8 * treat * (x1 > 0))))
    yv = (rng.random(n) < pp).astype(int)
    fr = Frame.from_numpy({
        "x0": x0, "x1": x1, "treatment": treat.astype(np.float64),
        "y": np.array(["no", "yes"], dtype=object)[yv]})
    kw = dict(response_column="y", treatment_column="treatment", ntrees=3,
              max_depth=6, nbins=16, min_rows=5, seed=9, sample_rate=0.8,
              reproducible=True, sparse_depth_threshold=2)
    for sm in ("separate", "fused"):
        m_d = UpliftDRF(hist_layout="dense", split_mode=sm, **kw).train(fr)
        m_s = UpliftDRF(hist_layout="sparse", split_mode=sm,
                        **kw).train(fr)
        _assert_same_routing(m_d, m_s)
        pa = m_d.predict(fr).vec("uplift_predict").to_numpy()
        pb = m_s.predict(fr).vec("uplift_predict").to_numpy()
        np.testing.assert_allclose(pa, pb, atol=1e-4, rtol=1e-4)
    assert m_s.output["hist_layout"] == "sparse"
