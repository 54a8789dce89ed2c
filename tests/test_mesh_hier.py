"""Hierarchical mesh data plane: flat-vs-hier parity + mesh lifecycle.

The data plane reduces over an explicit ``("hosts", "chips")`` mesh
(runtime/cluster.py): histogram partials psum around each host's ICI
ring first, then once across hosts over DCN (runtime/mapreduce.py).
These tests pin

  (a) BIT-parity of the staged schedule against the one-collective flat
      oracle for all four histogram builders (uniform, varbin, smaller-
      sibling subtraction, node-sparse slots) and the fused split search
      built on top — integer-valued stats reduce bitwise-identically
      under any association, so equality is exact, not allclose,
  (b) the ``reduce_mode="check"`` dispatcher (runs both whole programs,
      raises ReduceParityError on divergence) at the builder and the
      map_reduce layer,
  (c) cluster re-init: ``init(hosts=...)`` after a default boot detects
      the geometry change, rebuilds the mesh, flushes compiled caches
      and records a ``cluster_reinit`` event — the silent-stale-mesh
      regression,
  (d) the same parity on 16- and 32-virtual-device meshes in fresh
      subprocesses (the conftest mesh is fixed at 8), and
  (e) the host-kill chaos row: a training process on the 2-host mesh is
      hard-killed (exit 137, all procs of a virtual host die at once),
      a fresh process resume()s on the same mesh and predictions match
      the uninterrupted run — wired into tools/chaos.sh.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

import h2o3_tpu
from h2o3_tpu.models.tree.hist import (fused_best_splits, make_hist_fn,
                                       make_sparse_level_fn,
                                       make_subtract_level_fn,
                                       make_varbin_hist_fn, offset_codes)
from h2o3_tpu.runtime.mapreduce import (ReduceParityError,
                                        assert_reduce_parity,
                                        force_reduce_mode, map_reduce)


def _int_stats(rng, N, L):
    """Integer-valued f32 stats: psum order cannot change a single bit."""
    leaf = jnp.asarray(rng.integers(0, L, N), jnp.int32)
    g = jnp.asarray(rng.integers(-8, 8, N), jnp.float32)
    h = jnp.asarray(rng.integers(0, 4, N), jnp.float32)
    w = jnp.asarray(rng.integers(0, 2, N), jnp.float32)
    return leaf, g, h, w


def _assert_bitwise(a, b, what):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype, \
        f"{what}: shape/dtype mismatch {a.shape}/{a.dtype} vs {b.shape}/{b.dtype}"
    assert a.tobytes() == b.tobytes(), (
        f"{what}: flat and hier reductions are not bit-identical "
        f"(maxdiff {np.max(np.abs(a - b))})")


# ------------------------------------------------------- builder bit-parity

def test_uniform_hist_flat_vs_hier_bitwise(cl, rng):
    N, F, B, L = 1024, 4, 17, 4
    codes = jnp.asarray(rng.integers(0, B, (F, N)), jnp.int32)
    leaf, g, h, w = _int_stats(rng, N, L)
    Hf = make_hist_fn(L, F, B, N, force_impl="einsum",
                      reduce_mode="flat")(codes, leaf, g, h, w)
    Hh = make_hist_fn(L, F, B, N, force_impl="einsum",
                      reduce_mode="hier")(codes, leaf, g, h, w)
    _assert_bitwise(Hf, Hh, "uniform hist")


def test_varbin_hist_flat_vs_hier_bitwise(cl, rng):
    N, F, L = 1024, 4, 4
    bin_counts = (7, 16, 3, 11)
    nbins = max(bin_counts)
    B = nbins + 1
    codes = jnp.asarray(np.stack([
        np.where(rng.random(N) < 0.1, nbins, rng.integers(0, bc, N))
        for bc in bin_counts]), jnp.int32)
    gcodes = offset_codes(codes, bin_counts, nbins)
    leaf, g, h, w = _int_stats(rng, N, L)
    args = (L, F, bin_counts, B, N)
    kw = dict(force_impl="pallas_interpret", precision="f32")
    Hf = make_varbin_hist_fn(*args, reduce_mode="flat", **kw)(
        gcodes, leaf, g, h, w)
    Hh = make_varbin_hist_fn(*args, reduce_mode="hier", **kw)(
        gcodes, leaf, g, h, w)
    _assert_bitwise(Hf, Hh, "varbin hist")


def _chain_leaves(rng, N, depth, p_right=0.3):
    leaves = [np.zeros(N, np.int64)]
    for _ in range(1, depth):
        bit = (rng.random(N) < p_right).astype(np.int64)
        leaves.append(2 * leaves[-1] + bit)
    return leaves


def test_subtract_chain_flat_vs_hier_bitwise(cl, rng):
    """Two independent mode-chains (the carry is mode-specific state)
    must agree bitwise on the histogram AND the per-shard carry at every
    level — the carry is pre-psum, so it never crosses a collective."""
    N, F, B, depth = 1024, 4, 17, 3
    codes = jnp.asarray(rng.integers(0, B, (F, N)), jnp.int32)
    leaf0, g, h, w = _int_stats(rng, N, 1)
    carry_f = carry_h = None
    for d, leaf_np in enumerate(_chain_leaves(rng, N, depth)):
        leaf = jnp.asarray(leaf_np, jnp.int32)
        extra_f = () if d == 0 else (carry_f,)
        extra_h = () if d == 0 else (carry_h,)
        Hf, carry_f = make_subtract_level_fn(d, F, B, N, reduce_mode="flat")(
            codes, leaf, g, h, w, *extra_f)
        Hh, carry_h = make_subtract_level_fn(d, F, B, N, reduce_mode="hier")(
            codes, leaf, g, h, w, *extra_h)
        _assert_bitwise(Hf, Hh, f"subtract hist d={d}")
        _assert_bitwise(carry_f, carry_h, f"subtract carry d={d}")


def test_sparse_level_flat_vs_hier_bitwise(cl, rng):
    """Node-sparse slots at the identity slot map, both schedules."""
    N, F, B, depth = 1024, 4, 17, 3
    codes = jnp.asarray(rng.integers(0, B, (F, N)), jnp.int32)
    _, g, h, w = _int_stats(rng, N, 1)
    leaves = _chain_leaves(rng, N, depth)
    _, carry_f = make_subtract_level_fn(0, F, B, N, reduce_mode="flat")(
        codes, jnp.zeros(N, jnp.int32), g, h, w)
    carry_h = carry_f
    for d in range(1, depth):
        leaf = jnp.asarray(leaves[d], jnp.int32)
        A_prev, A = 2 ** (d - 1), 2 ** d
        ps = jnp.arange(A, dtype=jnp.int32) // 2
        Hf, carry_f = make_sparse_level_fn(
            A_prev, A, F, B, N, reduce_mode="flat")(
            codes, leaf, g, h, w, carry_f, ps)
        Hh, carry_h = make_sparse_level_fn(
            A_prev, A, F, B, N, reduce_mode="hier")(
            codes, leaf, g, h, w, carry_h, ps)
        _assert_bitwise(Hf, Hh, f"sparse hist d={d}")
        _assert_bitwise(carry_f, carry_h, f"sparse carry d={d}")


def test_fused_splits_flat_vs_hier_identical(cl, rng):
    """The fused split search on top of both schedules picks the same
    (feature, bin) winners with the same gains — the whole-level
    decision, not just the histogram, is schedule-invariant."""
    N, F, B, L = 1024, 4, 17, 4
    nbins = B - 1
    codes = jnp.asarray(rng.integers(0, B, (F, N)), jnp.int32)
    leaf, g, h, w = _int_stats(rng, N, L)
    outs = {}
    for mode in ("flat", "hier"):
        H = make_hist_fn(L, F, B, N, force_impl="einsum",
                         reduce_mode=mode)(codes, leaf, g, h, w)
        outs[mode] = fused_best_splits(H, nbins, 1.0, 1.0, 0.0)
    for i, (a, b) in enumerate(zip(outs["flat"], outs["hier"])):
        _assert_bitwise(a, b, f"fused splits output {i}")


# ------------------------------------------------------------- check mode

def test_check_mode_builder_smoke(cl, rng):
    """reduce_mode="check" runs both schedules in-builder and returns the
    hier result; any divergence would raise ReduceParityError."""
    N, F, B, L = 512, 3, 9, 2
    codes = jnp.asarray(rng.integers(0, B, (F, N)), jnp.int32)
    leaf, g, h, w = _int_stats(rng, N, L)
    Hc = make_hist_fn(L, F, B, N, force_impl="einsum",
                      reduce_mode="check")(codes, leaf, g, h, w)
    Hh = make_hist_fn(L, F, B, N, force_impl="einsum",
                      reduce_mode="hier")(codes, leaf, g, h, w)
    _assert_bitwise(Hc, Hh, "check-mode hist")


def test_check_mode_via_forced_env(cl, rng):
    """force_reduce_mode("check") flows through the default dispatch —
    the path H2O3_TPU_REDUCE_MODE=check takes in a real deployment."""
    N, F, B, L = 512, 3, 9, 2
    codes = jnp.asarray(rng.integers(0, B, (F, N)), jnp.int32)
    leaf, g, h, w = _int_stats(rng, N, L)
    with force_reduce_mode("check"):
        H = make_hist_fn(L, F, B, N, force_impl="einsum")(
            codes, leaf, g, h, w)
    assert np.asarray(H).shape == (3, L, F, B)


def test_map_reduce_check_mode(cl, rng):
    x = jnp.asarray(rng.integers(-50, 50, 512), jnp.float32)
    total = map_reduce(lambda d: jnp.sum(d), x, reduce_mode="check")
    assert float(total) == float(np.sum(np.asarray(x)))


def test_parity_assert_raises_on_divergence():
    with pytest.raises(ReduceParityError, match="divergence"):
        assert_reduce_parity(np.zeros(4, np.float32),
                             np.ones(4, np.float32), what="unit")
    with pytest.raises(ReduceParityError, match="structures"):
        assert_reduce_parity({"a": np.zeros(2)}, [np.zeros(2)], what="unit")


# ------------------------------------------------------- cluster re-init

def test_reinit_rebuilds_mesh_and_flushes_caches(cl, rng):
    """init(hosts=...) after the default boot must rebuild the mesh (not
    silently return the stale one), record a cluster_reinit event, and
    leave the data plane correct on the new geometry."""
    from h2o3_tpu.runtime import observability as obs
    from h2o3_tpu.runtime.cluster import cluster
    orig_hosts = cl.n_hosts
    new_hosts = 4 if orig_hosts != 4 else 2
    try:
        c2 = h2o3_tpu.init(hosts=new_hosts)
        assert c2.n_hosts == new_hosts
        assert dict(c2.mesh.shape)["hosts"] == new_hosts
        assert c2.n_row_shards == cl.n_row_shards     # same device count
        # a later default init() returns the REBUILT cluster, not a stale one
        assert h2o3_tpu.init() is c2
        ev = [e for e in obs.timeline_events(1000)
              if e.get("kind") == "cluster_reinit"]
        assert ev, "cluster_reinit event not recorded"
        # parity still holds on the rebuilt mesh (caches were flushed, so
        # these recompile against the new geometry)
        N, F, B, L = 512, 3, 9, 2
        codes = jnp.asarray(rng.integers(0, B, (F, N)), jnp.int32)
        leaf, g, h, w = _int_stats(rng, N, L)
        Hf = make_hist_fn(L, F, B, N, force_impl="einsum",
                          reduce_mode="flat")(codes, leaf, g, h, w)
        Hh = make_hist_fn(L, F, B, N, force_impl="einsum",
                          reduce_mode="hier")(codes, leaf, g, h, w)
        _assert_bitwise(Hf, Hh, "post-reinit hist")
    finally:
        restored = h2o3_tpu.init(hosts=orig_hosts)
        assert restored.n_hosts == orig_hosts


def test_reinit_same_geometry_is_cached(cl):
    """Re-stating the live geometry must NOT rebuild (frames keep their
    shardings; compiled programs stay hot)."""
    assert h2o3_tpu.init(hosts=cl.n_hosts) is h2o3_tpu.init()


def test_reinit_drops_every_cached_tree_builder(cl):
    """Regression (found on four real chips, PR 24): the flush went by a
    list of builder names that had drifted, so after a re-init at the same
    padded row count the scan-level builder handed out a program bound to
    the dead mesh.  Every lru-cached builder of the tree engine is
    dropped, whatever its name."""
    from h2o3_tpu.models.tree import hist, shared
    builders = {name: obj for mod in (hist, shared)
                for name, obj in vars(mod).items()
                if hasattr(obj, "cache_info")}
    assert {"_make_scan_level_fn", "_make_batched_scan_level_fn",
            "make_grid_scan_fn", "make_build_tree_fn"} <= set(builders)
    hist.make_scan_level_fn(2, 3, 9, 512)
    hist.make_subtract_level_fn(1, 3, 9, 512)
    assert builders["_make_scan_level_fn"].cache_info().currsize == 1
    new_hosts = 4 if cl.n_hosts != 4 else 2
    try:
        h2o3_tpu.init(hosts=new_hosts)
        stale = {n: b.cache_info().currsize for n, b in builders.items()
                 if b.cache_info().currsize}
        assert not stale, f"builders kept programs of the dead mesh: {stale}"
    finally:
        h2o3_tpu.init(hosts=cl.n_hosts)


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: init() configures no directory and
    leaves the cache entirely to JAX; unset: <checkout>/.jax_cache, derived
    from the package's own location (the path is part of the cache key, so
    it must not move).  The suite itself keeps the cache disabled."""
    import os
    import jax
    from h2o3_tpu.runtime import cluster
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        cluster._place_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        cluster._place_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(root, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_reinit_drops_autotune_decisions(cl):
    """Regression: _invalidate_compiled_caches must also flush the
    autotuner's per-signature mode decisions — they bind the mesh
    geometry exactly like compiled programs do, and a rebuilt mesh must
    never serve a choice tuned for the dead one."""
    import os
    from h2o3_tpu.runtime import autotune, config
    saved = os.environ.get("H2O3_TPU_AUTOTUNE")
    orig_hosts = cl.n_hosts
    new_hosts = 4 if orig_hosts != 4 else 2
    try:
        os.environ["H2O3_TPU_AUTOTUNE"] = "on"
        config.reload()
        autotune.reset()
        import types
        p = types.SimpleNamespace(hist_mode="auto", split_mode="auto",
                                  hist_layout="auto",
                                  sparse_depth_threshold=8,
                                  max_depth=6, nbins=32)
        k = autotune.resolve_tree_knobs(p, kind="gbm", F=4, N=4096)
        assert k.sig is not None
        assert autotune.decision_table()["entries"] == 1
        h2o3_tpu.init(hosts=new_hosts)
        assert autotune.decision_table()["entries"] == 0, \
            "mesh rebuild left stale autotune decisions behind"
        # fresh decisions on the new geometry carry its mesh signature
        k2 = autotune.resolve_tree_knobs(p, kind="gbm", F=4, N=4096)
        assert f"mesh{new_hosts}x" in k2.sig
    finally:
        h2o3_tpu.init(hosts=orig_hosts)
        if saved is None:
            os.environ.pop("H2O3_TPU_AUTOTUNE", None)
        else:
            os.environ["H2O3_TPU_AUTOTUNE"] = saved
        config.reload()
        autotune.reset()


# --------------------------------------- 16/32-device subprocess parity

_PARITY_SCRIPT = textwrap.dedent("""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import h2o3_tpu
    cl = h2o3_tpu.init()
    assert cl.n_row_shards == {n_dev}, cl.mesh.shape
    assert cl.n_hosts == {hosts}, cl.mesh.shape
    from h2o3_tpu.models.tree.hist import (fused_best_splits, make_hist_fn,
                                           make_subtract_level_fn)
    rng = np.random.default_rng(7)
    N, F, B, L = 2048, 4, 17, 4
    nbins = B - 1
    codes = jnp.asarray(rng.integers(0, B, (F, N)), jnp.int32)
    leaf = jnp.asarray(rng.integers(0, L, N), jnp.int32)
    g = jnp.asarray(rng.integers(-8, 8, N), jnp.float32)
    h = jnp.asarray(rng.integers(0, 4, N), jnp.float32)
    w = jnp.asarray(rng.integers(0, 2, N), jnp.float32)
    res = {{}}
    for mode in ("flat", "hier"):
        H = make_hist_fn(L, F, B, N, force_impl="einsum",
                         reduce_mode=mode)(codes, leaf, g, h, w)
        Hs, carry = make_subtract_level_fn(0, F, B, N, reduce_mode=mode)(
            codes, jnp.zeros(N, jnp.int32), g, h, w)
        res[mode] = (np.asarray(H), np.asarray(Hs), np.asarray(carry),
                     [np.asarray(o)
                      for o in fused_best_splits(H, nbins, 1.0, 1.0, 0.0)])
    for a, b in zip(res["flat"][:3], res["hier"][:3]):
        assert a.tobytes() == b.tobytes(), "hist/carry parity"
    for a, b in zip(res["flat"][3], res["hier"][3]):
        assert a.tobytes() == b.tobytes(), "fused splits parity"
    print("PARITY_OK", {n_dev}, {hosts})
""")


@pytest.mark.parametrize("n_dev,hosts", [(16, 2), (32, 4)])
def test_parity_on_larger_virtual_mesh(n_dev, hosts):
    """Flat-vs-hier bit-parity on 16/32 virtual devices.  Fresh
    subprocess: the in-process XLA device count is fixed at boot."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_dev}",
        "H2O3_TPU_HOSTS": str(hosts),
    })
    proc = subprocess.run(
        [sys.executable, "-c",
         _PARITY_SCRIPT.format(n_dev=n_dev, hosts=hosts)],
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, (
        f"rc={proc.returncode}\nstdout:\n{proc.stdout[-3000:]}\n"
        f"stderr:\n{proc.stderr[-3000:]}")
    assert f"PARITY_OK {n_dev} {hosts}" in proc.stdout


# ------------------------------------------------- host-kill chaos row

NTREES = 12
KILL_AT_CHUNK = 3


def _mesh_env(tmp_path, extra=None):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "H2O3_TPU_HOSTS": "2",
        "H2O3_TPU_REDUCE_MODE": "hier",
        "H2O3_TPU_RECOVERY_DIR": str(tmp_path),
        "H2O3_TPU_SNAPSHOT_INTERVAL": "0",
        "H2O3_TPU_SNAPSHOT_ASYNC": "0",
        "H2O3_TPU_LOG_STDERR": "1",
    })
    env.update(extra or {})
    return env


def _write_csv(path, seed=11, n=600):
    rng = np.random.default_rng(seed)
    X = rng.random((n, 4))
    y = (10 * np.sin(np.pi * X[:, 0]) + 5 * X[:, 1] ** 2
         + 3 * X[:, 2] + 0.1 * rng.normal(size=n))
    rows = np.column_stack([X, y])
    path.write_text("x0,x1,x2,x3,y\n" + "\n".join(
        ",".join(f"{v:.9g}" for v in r) for r in rows))
    return str(path)


_TRAIN = textwrap.dedent("""
    import sys
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import h2o3_tpu
    cl = h2o3_tpu.init()
    assert cl.n_hosts == 2, cl.mesh.shape
    from h2o3_tpu.frame.parse import import_file
    from h2o3_tpu.models import GBM
    fr = import_file(sys.argv[1], destination_frame="mesh_chaos_fr")
    m = GBM(response_column="y", ntrees={nt}, max_depth=3, learn_rate=0.2,
            seed=7, score_tree_interval=2).train(fr)
    np.save(sys.argv[2], m.predict(fr).to_numpy()[:, 0])
    print("TRAINED", m.output["ntrees_trained"])
""").format(nt=NTREES)

_RESUME = textwrap.dedent("""
    import sys
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import h2o3_tpu
    cl = h2o3_tpu.init()
    assert cl.n_hosts == 2, cl.mesh.shape
    from h2o3_tpu.frame.parse import import_file
    from h2o3_tpu.runtime import dkv, recovery
    fr = import_file(sys.argv[1], destination_frame="mesh_chaos_fr")
    done = recovery.resume()
    assert len(done) == 1, f"expected 1 resumed model, got {done}"
    m = dkv.get(done[0])
    print("RESUMED", m.output["ntrees_trained"])
    np.save(sys.argv[2], m.predict(fr).to_numpy()[:, 0])
""")


def _run(script, env, *args, expect_rc=0, timeout=420):
    proc = subprocess.run(
        [sys.executable, "-c", script, *args],
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == expect_rc, (
        f"rc={proc.returncode} (wanted {expect_rc})\n"
        f"stdout:\n{proc.stdout[-3000:]}\nstderr:\n{proc.stderr[-3000:]}")
    return proc


def test_mesh_host_kill_resume_verify(cl, tmp_path):
    """Host-kill chaos on the hierarchical mesh: the training process
    owns both virtual hosts, so a hard kill (exit 137) takes a whole
    mesh host down mid-collective.  A fresh process rebuilds the SAME
    2-host mesh, resume()s from the snapshot, and predictions match the
    uninterrupted run through the staged ICI+DCN reduce."""
    csv = _write_csv(tmp_path / "mesh_chaos.csv")
    base_dir = tmp_path / "base_recovery"
    base_dir.mkdir()
    base_npy = str(tmp_path / "base.npy")
    out = _run(_TRAIN, _mesh_env(base_dir), csv, base_npy)
    assert f"TRAINED {NTREES}" in out.stdout
    assert not list(base_dir.glob("job_*.json"))

    kill_dir = tmp_path / "kill_recovery"
    kill_dir.mkdir()
    kill_npy = str(tmp_path / "kill.npy")
    _run(_TRAIN,
         _mesh_env(kill_dir, {"H2O3_TPU_FAULT_INJECT":
                              f"tree_chunk:0:{KILL_AT_CHUNK}"}),
         csv, kill_npy, expect_rc=137)
    assert not os.path.exists(kill_npy)
    entries = list(kill_dir.glob("job_*.json"))
    assert len(entries) == 1
    entry = json.loads(entries[0].read_text())
    assert entry["status"] == "running"
    assert entry["snapshot_cursor"]["trees_done"] == 2 * (KILL_AT_CHUNK - 1)

    res_npy = str(tmp_path / "resumed.npy")
    out = _run(_RESUME, _mesh_env(kill_dir), csv, res_npy)
    assert f"RESUMED {NTREES}" in out.stdout
    np.testing.assert_allclose(np.load(res_npy), np.load(base_npy),
                               rtol=1e-4, atol=1e-4)
