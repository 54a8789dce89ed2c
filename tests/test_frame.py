"""Frame/Vec/parse tests — analog of water/fvec tests + parser pyunits."""

import io

import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu.frame.vec import T_CAT, T_NUM, T_STR, T_TIME
from h2o3_tpu.runtime.mapreduce import map_partitions, map_reduce


CSV = """id,age,city,income,signup,comment
1,34,ny,55000.5,2021-01-02,hello
2,28,sf,72000,2021-02-03,world
3,,ny,NA,2021-03-04,foo
4,45,la,91000,2021-04-05,bar
5,51,sf,,2021-05-06,baz
"""


def make_frame(cl):
    return h2o3_tpu.upload_string(CSV, destination_frame="f1")


def test_parse_types(cl):
    f = make_frame(cl)
    assert f.shape == (5, 6)
    t = f.types()
    assert t["id"] == T_NUM and t["age"] == T_NUM and t["income"] == T_NUM
    assert t["city"] == T_CAT
    assert t["signup"] == T_TIME
    assert sorted(f.vec("city").domain) == ["la", "ny", "sf"]


def test_rollups(cl):
    f = make_frame(cl)
    age = f.vec("age")
    r = age.rollups()
    assert r.nmissing == 1
    assert r.vmin == 28 and r.vmax == 51
    np.testing.assert_allclose(r.mean, np.mean([34, 28, 45, 51]), rtol=1e-6)
    np.testing.assert_allclose(
        r.sigma, np.std([34, 28, 45, 51], ddof=1), rtol=1e-5)


@pytest.mark.parametrize("batched", [False, True])
def test_rollups_of_a_column_far_from_zero(cl, batched):
    """A year-like column (mean 1997, deviation 6) and a wide one with NA:
    mean and deviation of the float32 rollups agree with float64 to 1e-5 of
    a deviation, through the per-column kernel and the frame's batched one
    (the one-pass ``sum(x*x)/n - mean**2`` was 1 % off here at 4,096 rows
    and 12 % at 40M: PERF.md section 6, PR 30)."""
    rng = np.random.default_rng(3)
    n = 200_000
    cols = {"year": rng.integers(1987, 2008, n).astype(np.float32),
            "wide": np.abs(rng.normal(700, 500, n)).astype(np.float32)}
    cols["wide"][::13] = np.nan
    f = h2o3_tpu.Frame.from_numpy(cols)
    if batched:
        f.warm_rollups()
        assert all(v._rollups is not None for v in f.vecs)
    for name, x in cols.items():
        x = x[~np.isnan(x)].astype(np.float64)
        r = f.vec(name).rollups()
        assert r.nmissing == n - len(x)
        dev = x.std(ddof=1)
        assert abs(r.mean - x.mean()) <= 1e-5 * dev, (name, r.mean, x.mean())
        assert abs(r.sigma - dev) <= 1e-5 * dev, (name, r.sigma, dev)


def test_padding_and_sharding(cl):
    f = make_frame(cl)
    v = f.vec("age")
    assert v.padded_len % cl.row_multiple() == 0
    assert v.data.sharding.spec[0] == ("hosts", "chips")
    back = v.to_numpy()
    assert len(back) == 5
    assert np.isnan(back[2])


def test_cat_decode_roundtrip(cl):
    f = make_frame(cl)
    city = f.vec("city").decoded()
    assert list(city) == ["ny", "sf", "ny", "la", "sf"]


def test_frame_munging(cl):
    f = make_frame(cl)
    g = f[["age", "income"]]
    assert g.names == ["age", "income"]
    h = f.drop("comment")
    assert "comment" not in h.names
    sub = f.filter(np.array([True, False, True, False, True]))
    assert sub.nrows == 3
    assert list(sub.vec("id").to_numpy()) == [1, 3, 5]


def test_split_frame(cl):
    big = h2o3_tpu.Frame.from_numpy(
        {"x": np.arange(1000, dtype=np.float32)}, key="big")
    a, b = big.split_frame([0.75], seed=1)
    assert a.nrows + b.nrows == 1000
    assert 650 < a.nrows < 850


def test_matrix(cl):
    f = make_frame(cl)
    m = f.matrix(["age", "income"])
    assert m.shape == (f.padded_rows, 2)
    assert m.sharding.spec[0] == ("hosts", "chips")


def test_dkv(cl):
    make_frame(cl)
    assert "f1" in h2o3_tpu.ls()
    assert h2o3_tpu.get_frame("f1").nrows == 5
    h2o3_tpu.remove("f1")
    with pytest.raises(KeyError):
        h2o3_tpu.get_frame("f1")


def test_map_reduce(cl, rng):
    x = h2o3_tpu.Vec.from_numpy(rng.normal(size=1000).astype(np.float32))
    valid = x.valid_mask()

    def msum(data, mask):
        import jax.numpy as jnp
        return jnp.sum(jnp.where(mask, data, 0.0))

    total = map_reduce(msum, x.data, valid)
    np.testing.assert_allclose(float(total), float(np.sum(x.to_numpy())),
                               rtol=1e-4)


def test_map_partitions(cl, rng):
    x = h2o3_tpu.Vec.from_numpy(np.arange(64, dtype=np.float32))
    doubled = map_partitions(lambda d: d * 2, x.data)
    np.testing.assert_allclose(np.asarray(doubled)[:64], np.arange(64) * 2)


def test_string_column_host_side(cl):
    f = make_frame(cl)
    c = f.vec("comment")
    assert c.type == T_CAT or c.type == T_STR  # low-card text may be cat
    vals = list(c.decoded())
    assert vals == ["hello", "world", "foo", "bar", "baz"]


def test_time_precision_roundtrip(cl):
    # float32 device storage must not destroy sub-minute timestamp resolution
    f = h2o3_tpu.upload_string(
        "t\n2021-01-02 00:00:00\n2021-01-02 00:01:00\n2021-01-02 00:01:30\n")
    t = f.vec("t")
    assert t.type == T_TIME
    ms = t.to_numpy()
    assert ms[1] - ms[0] == 60_000.0 and ms[2] - ms[1] == 30_000.0
    # device payload is rebased seconds: distinct and well-conditioned
    dev = np.asarray(t.data)[:3]
    np.testing.assert_allclose(dev, [0.0, 60.0, 90.0], atol=1e-3)


def test_split_frame_ratios_sum_to_one(cl):
    big = h2o3_tpu.Frame.from_numpy({"x": np.arange(1000, dtype=np.float32)})
    parts = big.split_frame([0.1] * 10, seed=3)
    assert len(parts) == 10
    assert sum(p.nrows for p in parts) == 1000


def test_from_numpy_explicit_cat(cl):
    f = h2o3_tpu.Frame.from_numpy({"c": np.array(["a", "b", "a"])},
                                  types={"c": T_CAT})
    assert f.vec("c").domain == ["a", "b"]
    assert list(f.vec("c").decoded()) == ["a", "b", "a"]


def test_all_missing_column_rollups(cl):
    f = h2o3_tpu.upload_string("x,y\nNA,1\nNA,2\n", col_types={"x": T_NUM})
    r = f.vec("x").rollups()
    assert r.nmissing == 2
    assert np.isnan(r.mean) and np.isnan(r.vmin)


def test_reinit_geometry_change_rebuilds(cl):
    # re-init with a different geometry rebuilds the mesh (recording a
    # cluster_reinit event) instead of raising or silently returning the
    # stale cached one — see tests/test_mesh_hier.py for the full contract
    from h2o3_tpu.runtime import observability as obs
    try:
        c2 = h2o3_tpu.init(model_axis=4)
        assert dict(c2.mesh.shape)["model"] == 4
        assert any(e.get("kind") == "cluster_reinit"
                   for e in obs.timeline_events(1000))
    finally:
        restored = h2o3_tpu.init(model_axis=1)
        assert dict(restored.mesh.shape)["model"] == 1
        assert restored.n_row_shards == cl.n_row_shards


def test_spill_and_transparent_restore(cl, rng):
    from h2o3_tpu.runtime import cleaner, dkv
    fr = h2o3_tpu.Frame.from_numpy(
        {"a": rng.normal(size=100), "g": np.array(["x", "y"], object)[
            rng.integers(0, 2, 100)]}, key="spillme")
    a0 = fr.vec("a").to_numpy().copy()
    freed = fr.spill()
    assert freed > 0
    assert fr.vec("a").is_spilled and fr.vec("g").is_spilled
    assert fr.vec("a")._device is None
    # host reads serve from the spill buffer without touching HBM
    np.testing.assert_array_equal(fr.vec("a").to_numpy(), a0)
    assert fr.vec("a").is_spilled
    assert fr.vec("a").padded_len >= 100
    # device access transparently restores, dtype preserved
    assert fr.vec("a").data is not None
    assert not fr.vec("a").is_spilled
    np.testing.assert_array_equal(fr.vec("a").to_numpy(), a0)
    assert fr.vec("g").data.dtype == np.int32     # cat codes restored
    # cleaner targets LRU frames and skips excluded keys
    fr2 = h2o3_tpu.Frame.from_numpy({"b": rng.normal(size=50)},
                                    key="hot")
    fr2.vec("b")                                   # touch: most recent
    got = cleaner.spill_until(1 << 40, exclude=["hot"])
    assert got > 0 and fr.vec("a").is_spilled
    assert not fr2.vec("b").is_spilled
    dkv.remove("spillme"); dkv.remove("hot")


def test_frame_munging_sugar(cl):
    left = h2o3_tpu.Frame.from_numpy({
        "k": np.array([3.0, 1.0, 2.0]), "v": np.array([30.0, 10.0, 20.0])})
    right = h2o3_tpu.Frame.from_numpy({
        "k": np.array([1.0, 2.0]), "w": np.array([100.0, 200.0])})
    s = left.sort("k")
    np.testing.assert_array_equal(s.vec("k").to_numpy(), [1.0, 2.0, 3.0])
    m = left.merge(right, "k")
    assert m.nrows == 2 and "w" in m.names
    g = left.group_by("k", {"v": ["sum"]})
    assert g.nrows == 3
    c = left.cor(["k", "v"])
    assert abs(c["matrix"][0, 1] - 1.0) < 1e-6   # v = 10*k exactly
    sc = left.scale()
    assert abs(float(np.mean(sc.vec("v").to_numpy()))) < 1e-6
    v = left.var(["k", "v"])
    assert abs(v["matrix"][0, 0] - 1.0) < 1e-6   # var of 1,2,3
    na = h2o3_tpu.Frame.from_numpy({"a": np.array([1.0, np.nan, 3.0])})
    imp = na.impute("a", method="median", combine_method="lo")
    assert np.isfinite(imp.vec("a").to_numpy()).all()


def test_assign_and_deep_copy(cl):
    fr = h2o3_tpu.Frame.from_numpy({"a": np.arange(4.0)}, key="orig_k")
    out = h2o3_tpu.assign(fr, "alias1")
    # true rebind: same frame object, old binding released
    assert out is fr and fr.key == "alias1"
    assert "alias1" in h2o3_tpu.ls() and "orig_k" not in h2o3_tpu.ls()
    cp = h2o3_tpu.deep_copy(fr, "copy_x")
    # device payloads are immutable and shared; wrappers independent
    assert cp.vec("a") is not fr.vec("a")
    np.testing.assert_array_equal(cp.vec("a").to_numpy(),
                                  fr.vec("a").to_numpy())
    # spilled columns stay spilled through deep_copy (no HBM restore)
    fr.spill()
    cp2 = h2o3_tpu.deep_copy(fr, "copy_y")
    assert fr.vec("a").is_spilled and cp2.vec("a").is_spilled
    np.testing.assert_array_equal(cp2.vec("a").to_numpy(),
                                  np.arange(4.0))
    h2o3_tpu.remove("alias1")
    h2o3_tpu.remove("copy_x"); h2o3_tpu.remove("copy_y")


def test_load_dataset(cl):
    import pytest
    pytest.importorskip("sklearn")
    iris = h2o3_tpu.load_dataset("iris")
    assert iris.shape == (150, 5)
    assert iris.vec("class").domain is not None
    assert len(iris.vec("class").domain) == 3
    assert iris.key in h2o3_tpu.ls()          # DKV-registered like loaders
    from h2o3_tpu.models import GBM
    m = GBM(response_column="class", ntrees=3, max_depth=3,
            seed=1).train(iris)
    assert m.training_metrics is not None
    with pytest.raises(ValueError, match="available"):
        h2o3_tpu.load_dataset("nope")


def test_describe_and_progress_toggles(cl):
    import logging
    fr = h2o3_tpu.Frame.from_numpy({"a": np.arange(5.0)})
    assert fr.describe() == fr.summary()
    lg = logging.getLogger("h2o3_tpu")
    before = lg.level
    h2o3_tpu.no_progress()
    assert lg.level == logging.WARNING
    h2o3_tpu.show_progress()
    assert lg.level == before        # restores the PRIOR level exactly
