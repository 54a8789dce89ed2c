"""tree_program="scan": the whole-tree lax.scan program vs the per-level
dispatch loop.

The scan-fused build must be BITWISE identical to the per-level program
on every knob combination it supports (padding slots are inert, masks
are pre-drawn with the level path's exact key sequence).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import Frame
from h2o3_tpu.models import DRF, GBM
from h2o3_tpu.models.tree.gbm import GBMParameters
from h2o3_tpu.models.tree.shared import (make_build_tree_fn,
                                         resolve_tree_program)
from tree_parity import check_pair


# ---------------------------------------------------------- build level

def _problem(rng, F=5, N=256, nbins=16):
    codes = jnp.asarray(rng.integers(0, nbins, (F, N)), jnp.int32)
    g = jnp.asarray(rng.normal(size=N), jnp.float32)
    h = jnp.ones(N, jnp.float32)
    w = jnp.ones(N, jnp.float32)
    edges = jnp.sort(jnp.asarray(rng.normal(size=(F, nbins)), jnp.float32),
                     axis=1)
    return codes, g, h, w, edges


def _args(rng, key=7, min_rows=1.0, col_rate=0.8, F=5):
    codes, g, h, w, edges = _problem(rng, F=F)
    tm = jnp.ones(F, bool)
    return (codes, g, h, w, edges, jax.random.PRNGKey(key), 0.0, min_rows,
            1e-5, 0.1, col_rate, tm, 0.0, 0.0, 0.0)


def _assert_trees_equal(a, b):
    la, va, ca, fa = a
    lb, vb, cb, fb = b
    for d, (x, y) in enumerate(zip(la, lb)):
        for i, nm in enumerate(("feat", "thr", "na_left", "valid")):
            np.testing.assert_array_equal(
                np.asarray(x[i]), np.asarray(y[i]),
                err_msg=f"level {d} {nm}")
    np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                  err_msg="values")
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb),
                                  err_msg="cover")
    np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb),
                                  err_msg="leaf")


@pytest.mark.parametrize("hm", ["subtract", "full"])
@pytest.mark.parametrize("sm", ["separate", "fused"])
def test_scan_matches_level_bitwise(cl, rng, hm, sm):
    F, N, nbins, md = 5, 256, 16, 4
    args = _args(rng)
    lv = make_build_tree_fn(md, nbins, F, N, "f32", hist_mode=hm,
                            split_mode=sm)
    sc = make_build_tree_fn(md, nbins, F, N, "f32", hist_mode=hm,
                            split_mode=sm, tree_program="scan")
    _assert_trees_equal(lv(*args), sc(*args))


def test_scan_matches_level_early_exit(cl, rng):
    """min_rows so large nothing past the root splits: the scan's dead
    predicate must reproduce the level loop's early-terminated tree
    (inert iterations emit the exact parent-passthrough leaves)."""
    F, N, nbins, md = 5, 256, 16, 5
    args = _args(rng, min_rows=200.0, col_rate=1.0)
    lv = make_build_tree_fn(md, nbins, F, N, "f32")
    sc = make_build_tree_fn(md, nbins, F, N, "f32", tree_program="scan")
    _assert_trees_equal(lv(*args), sc(*args))


@pytest.mark.parametrize("hm", ["subtract", "full"])
def test_scan_matches_level_batched(cl, rng, hm):
    """K-batched build (the multinomial / batched-DRF axis)."""
    F, N, nbins, md, K = 5, 256, 16, 4, 3
    codes, _, _, w, edges = _problem(rng, F=F)
    gK = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    hK = jnp.ones((K, N), jnp.float32)
    keysK = jax.random.split(jax.random.PRNGKey(11), K)
    tmK = jnp.ones((K, F), bool)
    args = (codes, gK, hK, w, edges, keysK, 0.0, 1.0, 1e-5, 0.1, 0.8,
            tmK, 0.0, 0.0, 0.0)
    lv = make_build_tree_fn(md, nbins, F, N, "f32", hist_mode=hm, nk=K,
                            split_mode="fused")
    sc = make_build_tree_fn(md, nbins, F, N, "f32", hist_mode=hm, nk=K,
                            split_mode="fused", tree_program="scan")
    lo, so = lv(*args), sc(*args)
    for i in range(4):
        for d, (x, y) in enumerate(zip(lo[0], so[0])):
            np.testing.assert_array_equal(np.asarray(x[i]),
                                          np.asarray(y[i]),
                                          err_msg=f"level {d} field {i}")
    for i in (1, 2, 3):
        np.testing.assert_array_equal(np.asarray(lo[i]), np.asarray(so[i]))


# ------------------------------------------- the variable-bin kernel

_RAGGED = (32, 5, 7, 32, 3)     # per-feature bins in use: the frame packs


def _ragged_problem(rng, bin_counts=_RAGGED, N=512, nbins=32, K=0):
    """Codes with ragged per-feature bin counts and NAs, as a frame with
    categoricals gives them; ``K`` > 0 for the batched K-tree build."""
    F = len(bin_counts)
    codes = jnp.asarray(np.stack([
        np.where(rng.random(N) < 0.1, nbins, rng.integers(0, bc, N))
        for bc in bin_counts]), jnp.int32)
    lead = (K,) if K else ()
    g = jnp.asarray(rng.normal(size=lead + (N,)), jnp.float32)
    h = jnp.ones(lead + (N,), jnp.float32)
    w = jnp.asarray(rng.random(N) > 0.1, jnp.float32)
    edges = jnp.sort(jnp.asarray(rng.normal(size=(F, nbins)), jnp.float32),
                     axis=1)
    key = jax.random.split(jax.random.PRNGKey(11), K) if K \
        else jax.random.PRNGKey(7)
    tm = jnp.ones(lead + (F,), bool)
    return (codes, g, h, w, edges, key, 0.5, 2.0, 1e-5, 0.1, 0.8, tm, 0.0,
            0.0, 0.0)


def _pallas_kernels(fn, args):
    """Names of the Pallas kernels in a build's jaxpr."""
    return set(re.findall(r"name=(hist_\w+)",
                          str(jax.make_jaxpr(fn.orig)(*args))))


@pytest.mark.parametrize("nk", [1, 3])
@pytest.mark.parametrize("hm", ["full", "subtract"])
def test_scan_matches_level_varbin(cl, rng, monkeypatch, hm, nk):
    """With the variable-bin kernel forced off-TPU (interpret Pallas) on a
    frame with ragged bin counts, the scan runs it at both of its sites
    and grows the level program's tree, bit for bit."""
    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", "varbin")
    F, N, nbins, md = len(_RAGGED), 512, 32, 4
    args = _ragged_problem(rng, K=nk if nk > 1 else 0)
    kw = dict(bin_counts=_RAGGED, hist_mode=hm, nk=nk, split_mode="fused")
    lv = make_build_tree_fn(md, nbins, F, N, "f32", **kw)
    sc = make_build_tree_fn(md, nbins, F, N, "f32", tree_program="scan",
                            **kw)
    assert _pallas_kernels(sc, args) == {"hist_varbin"}
    _assert_trees_equal(lv(*args), sc(*args))


def _same_program(a, b, args):
    return str(jax.make_jaxpr(a.orig)(*args)) == \
        str(jax.make_jaxpr(b.orig)(*args))


def test_scan_width_past_varbin_bounds_is_the_uniform_program(
        cl, rng, monkeypatch):
    """A scan whose width passes the rule's bounds (here the kernel's
    result no longer fits the VMEM bound, set low enough for a CPU test)
    is the build it is without ``bin_counts``, operation for operation,
    while the level program keeps the packed kernel on the levels that
    fit; both grow the same tree (a frame whose sums are exact in float32,
    since two kernels add in two orders)."""
    from h2o3_tpu.models.tree import hist, shared
    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", "varbin")
    F, N, nbins, md = len(_RAGGED), 512, 32, 5
    B = nbins + 1
    # 4 slots fit, the scan's 16 (hist_mode="full", depth 5) do not
    monkeypatch.setattr(hist, "_HIST_RESULT_VMEM_BYTES", F * B * 3 * 4 * 4)
    assert shared.hist_site_kernel(4, F, nbins, _RAGGED) == "varbin"
    assert shared.hist_site_kernel(16, F, nbins, _RAGGED) == "einsum"
    args = list(_ragged_problem(rng))
    args[1] = jnp.round(args[1] * 8) / 8        # exact sums in float32
    kw = dict(hist_mode="full", split_mode="fused")
    sc = make_build_tree_fn(md, nbins, F, N, "f32", bin_counts=_RAGGED,
                            tree_program="scan", **kw)
    plain = make_build_tree_fn(md, nbins, F, N, "f32", tree_program="scan",
                               **kw)
    assert _same_program(sc, plain, args)
    assert _pallas_kernels(sc, args) == set()
    lv = make_build_tree_fn(md, nbins, F, N, "f32", bin_counts=_RAGGED,
                            **kw)
    assert _pallas_kernels(lv, args) == {"hist_varbin"}
    _assert_trees_equal(lv(*args), sc(*args))


@pytest.mark.parametrize("hm", ["full", "subtract"])
def test_scan_without_varbin_is_the_uniform_program(cl, rng, monkeypatch,
                                                    hm):
    """On a frame whose columns all use every bin the packed kernel does
    not engage, forced or not: the scan build is the program it is without
    ``bin_counts`` (the parent's form), operation for operation."""
    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", "varbin")
    F, N, nbins, md = 5, 512, 32, 4
    full_bins = (nbins,) * F
    args = _ragged_problem(rng, bin_counts=full_bins)
    kw = dict(hist_mode=hm, split_mode="fused", tree_program="scan")
    sc = make_build_tree_fn(md, nbins, F, N, "f32", bin_counts=full_bins,
                            **kw)
    plain = make_build_tree_fn(md, nbins, F, N, "f32", **kw)
    assert _same_program(sc, plain, args)
    assert _pallas_kernels(sc, args) == set()


# ------------------------------------------------------- knob semantics

def test_scan_rejects_unsupported_shapes(cl):
    p = GBMParameters(response_column="y", tree_program="scan", max_depth=5)
    with pytest.raises(ValueError, match="mono"):
        resolve_tree_program(p, mono={"x0": 1})
    p1 = GBMParameters(response_column="y", tree_program="scan",
                       max_depth=1)
    with pytest.raises(ValueError, match="depth"):
        resolve_tree_program(p1)
    deep = GBMParameters(response_column="y", tree_program="scan",
                         max_depth=12, sparse_depth_threshold=3)
    with pytest.raises(ValueError, match="sparse"):
        resolve_tree_program(deep, hist_layout="sparse")
    with pytest.raises(ValueError, match="tree_program"):
        resolve_tree_program(
            GBMParameters(response_column="y", tree_program="bogus"))
    # "auto" under H2O3_TPU_AUTOTUNE=off is the historical level path
    assert resolve_tree_program(
        GBMParameters(response_column="y", max_depth=5)) == "level"


def test_build_fn_rejects_scan_with_engaged_sparse(cl):
    with pytest.raises(ValueError, match="sparse"):
        make_build_tree_fn(10, 16, 5, 4096, "f32", hist_layout="sparse",
                           sparse_depth_threshold=2, tree_program="scan")
    with pytest.raises(ValueError, match="depth"):
        make_build_tree_fn(1, 16, 5, 256, "f32", tree_program="scan")


# ------------------------------------------------------------- drivers

def _reg_frame(n=400, seed=0, key="scan_reg"):
    r = np.random.default_rng(seed)
    X = r.normal(size=(n, 5))
    y = X[:, 0] + 0.5 * X[:, 1] ** 2 + 0.1 * r.normal(size=n)
    cols = {f"x{j}": X[:, j] for j in range(5)}
    cols["y"] = y
    return Frame.from_numpy(cols, key=key)


def _multi_frame(n=400, seed=1, key="scan_multi"):
    r = np.random.default_rng(seed)
    centers = np.array([[2, 0], [-2, 1], [0, -2]])
    labels = r.integers(0, 3, n)
    X = centers[labels] + r.normal(size=(n, 2))
    return Frame.from_numpy(
        {"x0": X[:, 0], "x1": X[:, 1],
         "y": np.array(["a", "b", "c"], dtype=object)[labels]}, key=key)


_KW = dict(response_column="y", ntrees=5, max_depth=4, nbins=16, seed=7,
           reproducible=True)


def _pred(m, fr):
    return np.asarray(m.predict(fr).vec("predict").to_numpy())


def test_gbm_scan_bitwise(cl):
    fr = _reg_frame()
    m_lv = GBM(**_KW, tree_program="level").train(fr)
    m_sc = GBM(**_KW, tree_program="scan").train(fr)
    np.testing.assert_array_equal(_pred(m_lv, fr), _pred(m_sc, fr))
    assert m_sc.output["tree_program"] == "scan"
    assert m_lv.output["tree_program"] == "level"


@pytest.mark.parametrize("model", ["gbm_binomial", "gbm_3class", "drf"])
def test_estimator_scan_level_same_trees(cl, model):
    """Two fits through tree_program's two values grow the same trees, bit
    for bit (UpliftDRF always grows level-wise: no pair to compare)."""
    scan, level = check_pair(model, "tree_program", ("scan", "level"),
                             bitwise=True)
    assert scan.output["tree_program"] == "scan"
    assert level.output["tree_program"] == "level"


def _hist_kernel_counts():
    from h2o3_tpu.runtime import observability as obs
    return {(p, k): obs.counter("tree_hist_kernel_total", program=p,
                                kernel=k).value
            for p in ("scan", "level")
            for k in ("varbin", "uniform", "einsum")}


def _one_rise(before, after):
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


def test_hist_kernel_counter_scan_varbin(cl, monkeypatch):
    """``tree_hist_kernel_total{program, kernel}``: one increment a fit.
    Under ``tree_program="scan"`` with the variable-bin kernel forced on a
    frame with categoricals it names the scan and the packed kernel, and
    the fit grows the level program's trees."""
    monkeypatch.setenv("H2O3_TPU_HIST_IMPL", "varbin")
    r = np.random.default_rng(3)
    n = 400
    x0 = r.normal(size=n)
    c1 = r.integers(0, 3, n)
    c2 = r.integers(0, 5, n)
    fr = Frame.from_numpy(
        {"x0": x0,
         "c1": np.array(["a", "b", "c"], dtype=object)[c1],
         "c2": np.array(list("vwxyz"), dtype=object)[c2],
         "y": x0 + (c1 == 1) - 0.5 * (c2 == 3) + 0.1 * r.normal(size=n)},
        key="scan_counter_cat")
    kw = dict(response_column="y", ntrees=2, max_depth=3, nbins=32, seed=5,
              reproducible=True)
    before = _hist_kernel_counts()
    m_sc = GBM(**kw, tree_program="scan").train(fr)
    mid = _hist_kernel_counts()
    assert _one_rise(before, mid) == {("scan", "varbin"): 1}
    m_lv = GBM(**kw, tree_program="level").train(fr)
    assert _one_rise(mid, _hist_kernel_counts()) == {("level", "varbin"): 1}
    np.testing.assert_array_equal(_pred(m_lv, fr), _pred(m_sc, fr))


def test_hist_kernel_counter_level_einsum(cl):
    """Off the TPU, nothing forced: the level program's widest level runs
    the einsum."""
    fr = _reg_frame(key="scan_counter_num")
    before = _hist_kernel_counts()
    GBM(**_KW, tree_program="level").train(fr)
    assert _one_rise(before, _hist_kernel_counts()) == \
        {("level", "einsum"): 1}


def test_gbm_multinomial_scan_bitwise(cl):
    fr = _multi_frame()
    kw = dict(response_column="y", ntrees=4, max_depth=3, nbins=16,
              seed=3, reproducible=True)
    m_lv = GBM(**kw, tree_program="level").train(fr)
    m_sc = GBM(**kw, tree_program="scan").train(fr)
    np.testing.assert_array_equal(_pred(m_lv, fr), _pred(m_sc, fr))


def test_drf_scan_bitwise(cl):
    fr = _reg_frame(key="scan_drf")
    kw = dict(response_column="y", ntrees=4, max_depth=4, nbins=16,
              seed=5, reproducible=True)
    m_lv = DRF(**kw, tree_program="level").train(fr)
    m_sc = DRF(**kw, tree_program="scan").train(fr)
    np.testing.assert_array_equal(_pred(m_lv, fr), _pred(m_sc, fr))


def test_checkpoint_continuation_across_program_switch(cl):
    """A checkpoint grown under the level program continues bit-identically
    under the scan program (and vice versa) — the knob changes dispatch
    strategy, never trees, so snapshots/checkpoints are portable."""
    fr = _reg_frame(key="scan_ckpt")
    kw = dict(response_column="y", max_depth=3, nbins=16, min_rows=10,
              seed=11)
    prior = GBM(**kw, ntrees=3, tree_program="level").train(fr)
    cont_lv = GBM(**kw, ntrees=7, checkpoint=prior.key,
                  tree_program="level").train(fr)
    cont_sc = GBM(**kw, ntrees=7, checkpoint=prior.key,
                  tree_program="scan").train(fr)
    np.testing.assert_array_equal(_pred(cont_lv, fr), _pred(cont_sc, fr))
    # and a scan-grown prior continues under level
    prior_sc = GBM(**kw, ntrees=3, tree_program="scan").train(fr)
    cont_back = GBM(**kw, ntrees=7, checkpoint=prior_sc.key,
                    tree_program="level").train(fr)
    np.testing.assert_array_equal(_pred(cont_lv, fr), _pred(cont_back, fr))
