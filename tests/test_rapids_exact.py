"""``Frame.merge`` / ``Frame.sort`` (and ``(merge …)`` / ``(sort …)`` through
the Rapids evaluator) against the plain reference ``tests/reference_munge.py``
on seeded tables: results compared EXACTLY, integers and carried float32
values bit for bit, row order included.  Keys past 2^24 that differ by 1 are
in every table: a float32 payload merges those (the parent of PR 39 did).
"""

import numpy as np
import pytest

from h2o3_tpu import Frame
from h2o3_tpu.frame.vec import T_CAT, T_STR, INT_NA, takes_exact_int
import reference_munge as ref
from h2o3_tpu.rapids import device as dev, filter_rows
from h2o3_tpu.runtime import observability as obs

HOWS = ("inner", "left", "right", "outer")
BASE = 100_000_000          # keys sit just under it: float32's spacing there is 8


def tables(seed, nl=900, nr=500, na=True):
    """Left and right host tables: an integer key near 1e8 with duplicates
    on both sides and keys that match nothing, a second small key, a
    categorical key whose domains differ left and right, float32 values."""
    rng = np.random.default_rng(seed)
    lk = (BASE - rng.integers(0, 300, nl)).astype(np.float64)
    rk = (BASE - rng.integers(100, 400, nr)).astype(np.float64)
    if na:
        lk[rng.integers(0, nl, 12)] = np.nan
        rk[rng.integers(0, nr, 9)] = np.nan
    llab = np.array(["ant", "bee", "cat", "dog"], object)[rng.integers(0, 4, nl)]
    rlab = np.array(["bee", "cat", "dog", "eel", "fox"], object)[rng.integers(0, 5, nr)]
    if na:
        llab[rng.integers(0, nl, 7)] = None
        rlab[rng.integers(0, nr, 5)] = None
    left = {"key": lk, "k2": rng.integers(0, 3, nl).astype(np.float64), "lab": llab,
            "v1": rng.random(nl).astype(np.float32)}
    right = {"key": rk, "k2": rng.integers(0, 3, nr).astype(np.float64), "lab": rlab,
             "v2": rng.random(nr).astype(np.float32)}
    return left, right


def frame_of(cols, keep):
    cols = {n: cols[n] for n in keep}
    labels = {n: np.array(["NA" if v is None else v for v in c], object)
              for n, c in cols.items() if np.asarray(c).dtype == object}
    fr = Frame.from_numpy({n: labels.get(n, c) for n, c in cols.items()})
    for n in labels:              # None -> code -1
        v = fr.vec(n)
        codes = v.to_numpy().copy()
        codes[[lbl is None for lbl in cols[n]]] = -1
        dom = [d for d in v.domain]
        fr = fr.with_vec(n, type(v).from_numpy(codes, T_CAT, domain=dom))
    return fr


def read(name, **labels):
    """A counter's value, summed over the series whose labels match."""
    return sum(s["v"] for s in obs.metrics_wire() if s["n"] == name
               and all(s["l"].get(k) == v for k, v in labels.items()))


def assert_same(frame, want):
    """Every column of the frame equals the reference's, row for row: labels
    as labels (None NA), numbers bit for bit (NaN where NaN)."""
    assert frame.names == list(want)
    for name, col in want.items():
        v = frame.vec(name)
        col = np.asarray(col)
        assert v.nrows == len(col), name
        if v.type == T_CAT:
            got = v.decoded()
            assert [g for g in got] == [c for c in col], name
        elif v.data is None:        # host-only: strings as they are, None NA
            assert list(v.host_data) == list(col), name
        elif col.dtype == np.float32:
            got = v.to_numpy()
            assert got.dtype == np.float32, name
            np.testing.assert_array_equal(got.view(np.int32), col.view(np.int32), name)
        else:
            np.testing.assert_array_equal(np.asarray(v.to_numpy(), np.float64),
                                          col.astype(np.float64), name)


@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("seed", [1, 2])
def test_merge_on_integer_keys_past_2_24(cl, how, seed):
    left, right = tables(seed)
    L, R = frame_of(left, ["key", "v1"]), frame_of(right, ["key", "v2"])
    assert L.vec("key").is_exact_int and R.vec("key").is_exact_int
    want = ref.reference_merge({n: left[n] for n in L.names},
                               {n: right[n] for n in R.names}, "key", how)
    assert_same(L.merge(R, "key", how=how), want)


@pytest.mark.parametrize("how", HOWS)
def test_merge_two_keys(cl, how):
    left, right = tables(3)
    L, R = frame_of(left, ["key", "k2", "v1"]), frame_of(right, ["key", "k2", "v2"])
    want = ref.reference_merge({n: left[n] for n in L.names},
                               {n: right[n] for n in R.names}, ["key", "k2"], how)
    assert_same(L.merge(R, ["key", "k2"], how=how), want)


@pytest.mark.parametrize("how", HOWS)
def test_merge_categorical_key_with_different_domains(cl, how):
    left, right = tables(4)
    L, R = frame_of(left, ["lab", "v1"]), frame_of(right, ["lab", "v2"])
    assert L.vec("lab").domain != R.vec("lab").domain
    want = ref.reference_merge({n: left[n] for n in L.names},
                               {n: right[n] for n in R.names}, "lab", how)
    got = L.merge(R, "lab", how=how)
    if how == "outer":      # rbind unifies the domains; labels must still agree
        assert sorted(got.vec("lab").domain) == ["NA", "ant", "bee", "cat", "dog", "eel", "fox"]
    assert_same(got, want)


def test_merge_exact_integer_key_meets_float32_key(cl):
    """The left key needs 27 bits, the right table's (small values) is held
    as float32: the float32 side compares as integers, halves match
    nothing."""
    left = {"key": np.array([BASE - 1, 5, 7, BASE - 2, 6], np.float64),
            "v1": np.arange(5, dtype=np.float32)}
    right = {"key": np.array([5.0, 6.5, 7.0, 5.0]), "v2": np.arange(4, dtype=np.float32)}
    L, R = Frame.from_numpy(left), Frame.from_numpy(right)
    assert L.vec("key").is_exact_int and not R.vec("key").is_exact_int
    assert_same(L.merge(R, "key", how="left"), ref.reference_merge(left, right, "key", "left"))
    assert_same(R.merge(L, "key", how="inner"), ref.reference_merge(right, left, "key", "inner"))


def test_merge_one_host_sync_and_rows_counted(cl):
    left, right = tables(5, na=False)
    L, R = frame_of(left, ["key", "v1"]), frame_of(right, ["key", "v2"])
    L.merge(R, "key")                   # compiled
    before = {k: read(*k[:1], **dict(k[1:])) for k in [
        ("rapids_host_syncs_total", ("op", "merge")),
        ("transfer_bytes_total", ("dir", "d2h")),
        ("rapids_rows_total", ("op", "merge"), ("side", "out"))]}
    out = L.merge(R, "key")
    after = {k: read(*k[:1], **dict(k[1:])) for k in before}
    gained = {k[0]: after[k] - before[k] for k in before}
    assert gained == {"rapids_host_syncs_total": 1, "transfer_bytes_total": 8,
                      "rapids_rows_total": out.nrows}
    kinds = [e["kind"] for e in obs.timeline_events(50)]
    assert [k for k in kinds if k.startswith(("merge.", "rapids."))][-5:] == [
        "merge.keys", "merge.match", "merge.count", "merge.gather", "rapids.merge"]


def counted(call, op):
    """What one ``call`` adds to the two gather counters of ``op``."""
    names = ("rapids_gathers_total", "rapids_gathered_columns_total")
    before = [read(n, op=op) for n in names]
    call()
    return [read(n, op=op) - b for n, b in zip(names, before)]


@pytest.mark.parametrize("how,gathers,values", [
    ("inner", 3, 5),        # left: start, key, v1 in 1; right: srow, then v2
    ("left", 3, 6)])        # cnt rides the left stack too
def test_merge_gathers_counted_on_the_cell_shape(cl, how, gathers, values):
    left, right = tables(5, na=False)
    L, R = frame_of(left, ["key", "v1"]), frame_of(right, ["key", "v2"])
    assert counted(lambda: L.merge(R, "key", how=how), "merge") == [gathers, values]


def test_sort_gathers_counted(cl):
    left, _ = tables(8)
    L = frame_of(left, ["key", "k2", "lab", "v1"])
    assert counted(lambda: L.sort("key"), "sort") == [1, 4]
    wide = Frame.from_numpy({f"c{i}": np.arange(20.0) + i for i in range(9)})
    assert counted(lambda: wide.sort("c0", ascending=False), "sort") == [2, 9]
    assert counted(lambda: filter_rows(wide, np.arange(20) % 3 == 0), "filter") == [2, 9]


def wide_tables(seed, nl=700, nr=400):
    """Tables of 11 device columns a side (exact-integer, float32 and
    categorical payloads, NaN and NA among them) and a string column, which
    stays on the host, on an integer key with duplicates, misses and NAs."""
    rng = np.random.default_rng(seed)

    def side(n, lo, tag):
        cols = {"key": (BASE - rng.integers(lo, lo + 300, n)).astype(np.float64)}
        cols["key"][rng.integers(0, n, 9)] = np.nan
        for i in range(4):
            f = rng.standard_normal(n).astype(np.float32)
            f[rng.integers(0, n, 5)] = np.nan
            cols[f"{tag}f{i}"] = f
            cols[f"{tag}i{i}"] = (BASE + rng.integers(0, 1000, n)).astype(np.float64)
        cols[f"{tag}n"] = rng.integers(0, 50, n).astype(np.float64)
        cols[f"{tag}lab"] = np.array(["ant", "bee", "cat"], object)[rng.integers(0, 3, n)]
        cols[f"{tag}s"] = np.array([f"{tag}{j}" for j in range(n)], object)
        return cols
    return side(nl, 0, "l"), side(nr, 100, "r")


@pytest.mark.parametrize("how", HOWS)
def test_merge_wide_frames_with_a_host_only_column(cl, how):
    left, right = wide_tables(11)
    L = Frame.from_numpy(left, types={"ls": T_STR})
    R = Frame.from_numpy(right, types={"rs": T_STR})
    assert len(dev.device_columns(L)[0]) == 11 and L.vec("ls").data is None
    assert L.vec("li0").is_exact_int and not L.vec("ln").is_exact_int
    assert_same(L.merge(R, "key", how=how), ref.reference_merge(left, right, "key", how))


def payload_columns(k, n, seed):
    """``k`` columns of ``n`` rows, int32 and float32 in turn, holding what a
    move must not touch: NaNs of two bit patterns, -0.0, ``INT_NA``."""
    rng = np.random.default_rng(seed)
    cols = []
    for i in range(k):
        if i % 2:
            c = rng.integers(-2 ** 31, 2 ** 31, n, dtype=np.int64).astype(np.int32)
            c[rng.integers(0, n, 6)] = INT_NA
        else:
            c = rng.standard_normal(n).astype(np.float32)
            bits = c.view(np.int32)
            bits[rng.integers(0, n, 6)] = 0x7FC00000                # the quiet NaN
            bits[rng.integers(0, n, 6)] = np.int32(-0x3FFFF)        # 0xFFFC0001: sign and payload set
            bits[rng.integers(0, n, 6)] = np.int32(-2 ** 31)        # -0.0
        cols.append(c)
    return cols


@pytest.mark.parametrize("index_kind", ["ascending", "random"])
@pytest.mark.parametrize("k", [1, 2, 8, 9, 17])
def test_gather_columns_moves_every_bit(cl, k, index_kind):
    import jax
    import jax.numpy as jnp
    n, rng = 5000, np.random.default_rng(k)
    cols = payload_columns(k, n, seed=100 + k)
    if index_kind == "ascending":       # repeats and skips, as a join's left rows
        index = np.sort(rng.integers(0, n, 6000))
        assert (np.diff(index) == 0).any() and (np.diff(index) > 1).any()
    else:
        index = rng.integers(0, n, 6000)
    index = index.astype(np.int32)
    on_device = [jnp.asarray(c) for c in cols]
    moved = jax.jit(dev.gather_columns)(on_device, jnp.asarray(index))
    assert len(moved) == k
    for c, m in zip(cols, moved):
        assert m.dtype == c.dtype
        np.testing.assert_array_equal(np.asarray(m).view(np.int32), c[index].view(np.int32))
    traced = str(jax.make_jaxpr(dev.gather_columns)(on_device, jnp.asarray(index)))
    assert traced.count(" gather[") == -(-k // 8)      # groups of at most 8


@pytest.mark.parametrize("meets", ["binop", "ifelse", "cbind"])
def test_merge_output_meets_columns_made_elsewhere(cl, meets):
    """A join's columns are padded like any column of as many rows
    (``pad_rows(m)``: the coarse length of ``jit_merge_gather`` stays inside
    ``merge``), so they meet a ``from_numpy`` column (or a prediction)
    elementwise: join, train, predict, then ``joined - predicted``."""
    from h2o3_tpu.rapids import ops
    from h2o3_tpu.rapids.ast import rapids
    from h2o3_tpu.runtime import dkv
    left, right = tables(6, na=False)
    L, R = frame_of(left, ["key", "v1"]), frame_of(right, ["key", "v2"])
    out = L.merge(R, "key")
    assert {v.padded_len for v in out.vecs} == {cl.pad_rows(out.nrows)}
    z = np.arange(out.nrows, dtype=np.float32) / 7
    extra = Frame.from_numpy({"z": z})
    v1 = out.vec("v1").to_numpy().astype(np.float32)
    if meets == "binop":
        dkv.put("joinedF", out), dkv.put("extraF", extra)
        got = rapids('(- (cols joinedF "v1") (cols extraF "z"))')
        np.testing.assert_array_equal(got.vecs[0].to_numpy().astype(np.float32), v1 - z)
    elif meets == "ifelse":
        got = ops.ifelse(extra.vec("z"), out.vec("v1"), extra.vec("z"))
        np.testing.assert_array_equal(got.to_numpy().astype(np.float32),
                                      np.where(z != 0, v1, z))
    else:
        both = out.cbind(extra)
        assert {v.padded_len for v in both.vecs} == {cl.pad_rows(out.nrows)}
        np.testing.assert_array_equal(both.vec("key").to_numpy(), out.vec("key").to_numpy())


@pytest.mark.parametrize("op", ["==", "!=", "<", "<=", ">", ">="])
def test_comparison_of_exact_integers_is_exact(cl, op):
    """``(== id 99999999)`` keeps ONE id: both sides exact (an int32 column,
    a whole scalar or a second int32 column) compare as int32, NA as NaN
    does (true under ``!=`` alone).  Arithmetic stays float32 (ROADMAP R28)."""
    import operator
    from h2o3_tpu.rapids.ast import rapids
    from h2o3_tpu.runtime import dkv
    key = (BASE - np.arange(1, 41)).astype(np.float64)
    key[7] = np.nan
    other = key[::-1].copy()
    fr = Frame.from_numpy({"key": key, "other": other})
    assert fr.vec("key").is_exact_int and fr.vec("other").is_exact_int
    dkv.put("idsF", fr)
    py = {"==": operator.eq, "!=": operator.ne, "<": operator.lt,
          "<=": operator.le, ">": operator.gt, ">=": operator.ge}[op]
    with np.errstate(invalid="ignore"):
        got = rapids(f'({op} (cols idsF "key") {BASE - 20})').vecs[0].to_numpy()
        np.testing.assert_array_equal(got, py(key, BASE - 20).astype(np.float64))
        got = rapids(f'({op} (cols idsF "key") (cols idsF "other"))').vecs[0].to_numpy()
        np.testing.assert_array_equal(got, py(key, other).astype(np.float64))
    if op == "==":          # the filter a user writes, and what float32 would keep
        kept = rapids(f'(rows idsF (== (cols idsF "key") {BASE - 20}))')
        assert kept.vec("key").to_numpy().tolist() == [BASE - 20]
        f32 = key.astype(np.float32)
        assert (f32 == np.float32(BASE - 20)).sum() > 1


@pytest.mark.parametrize("by,ascending", [
    (["key"], True), (["key"], False), (["k2", "key"], [True, False]),
    (["lab", "key"], [False, True]), (["v1"], False), (["k2", "lab", "key"], [False, False, True])])
def test_sort_multi_key(cl, by, ascending):
    left, _ = tables(7)
    L = frame_of(left, ["key", "k2", "lab", "v1"])
    want = ref.reference_sort({n: left[n] for n in L.names}, by, ascending)
    assert_same(L.sort(by, ascending=ascending), want)


def test_sort_makes_no_host_sync(cl):
    left, _ = tables(8)
    L = frame_of(left, ["key", "v1"])
    L.sort("key")
    before = read("rapids_host_syncs_total")
    L.sort("key", ascending=False)
    assert read("rapids_host_syncs_total") == before
    kinds = [e["kind"] for e in obs.timeline_events(20)]
    assert kinds[-3:] == ["sort.order", "sort.gather", "rapids.sort"]


@pytest.mark.parametrize("how", ["inner", "left"])
def test_rapids_ast_merge_and_sort(cl, how):
    from h2o3_tpu.rapids.ast import rapids
    from h2o3_tpu.runtime import dkv
    left, right = tables(9)
    L, R = frame_of(left, ["key", "v1"]), frame_of(right, ["key", "v2"])
    dkv.put("exactL", L), dkv.put("exactR", R)
    all_x = "TRUE" if how == "left" else "FALSE"
    got = rapids(f'(merge exactL exactR {all_x} "key")')
    assert_same(got, ref.reference_merge({n: left[n] for n in L.names},
                                         {n: right[n] for n in R.names}, "key", how))
    got = rapids('(sort exactL "key" FALSE)')
    assert_same(got, ref.reference_sort({n: left[n] for n in L.names}, "key", False))


def test_group_by_keeps_keys_past_2_24_apart(cl):
    key = np.array([BASE - 1, BASE - 2, BASE - 1, BASE - 3, BASE - 2, BASE - 1], np.float64)
    fr = Frame.from_numpy({"key": key, "x": np.arange(6, dtype=np.float32)})
    out = fr.group_by("key", {"x": ["count", "sum"]})
    np.testing.assert_array_equal(out.vec("key").to_numpy(), [BASE - 3, BASE - 2, BASE - 1])
    np.testing.assert_array_equal(out.vec("count_x").to_numpy(), [1, 2, 3])
    np.testing.assert_array_equal(out.vec("sum_x").to_numpy(), [3, 5, 7])


def test_payload_rule(cl):
    """2^24 stays float32, 2^24 + 1 becomes int32, a column with a fraction
    stays float32, 2^31 stays float32; NA lives behind ``isna``; a model gets
    the same design matrix from either form of the same values."""
    small = np.array([0.0, 1 << 24, -5.0, np.nan])
    big = np.array([0.0, (1 << 24) + 1, -5.0, np.nan])
    assert not takes_exact_int(small) and takes_exact_int(big)
    assert not takes_exact_int(np.array([0.5, (1 << 24) + 1]))
    assert not takes_exact_int(np.array([0.0, float(1 << 31)]))
    assert takes_exact_int(np.array([-(1 << 31) + 1, 3], np.int64))
    assert not takes_exact_int(np.array([np.nan, np.nan]))
    vs, vb = (Frame.from_numpy({"x": a}).vec("x") for a in (small, big))
    assert str(vs.data.dtype) == "float32" and not vs.is_exact_int
    assert str(vb.data.dtype) == "int32" and vb.is_exact_int
    assert int(vb.data[3]) == INT_NA
    np.testing.assert_array_equal(np.asarray(vb.isna())[:4], [False, False, False, True])
    np.testing.assert_array_equal(vb.to_numpy(), big)
    assert vb.rollups().nmissing == 1           # rollups read the float32 view
    # both forms of the SAME values (whole numbers float32 holds: multiples of 8 near 1e8)
    from h2o3_tpu.frame.vec import Vec, T_NUM
    from h2o3_tpu.models.datainfo import DataInfo
    vals = (BASE - 8 * np.arange(40)).astype(np.float64)
    vals[5] = np.nan
    y = (np.arange(40) % 2).astype(np.float64)
    exact = Frame.from_numpy({"x": vals, "y": y})
    assert exact.vec("x").is_exact_int
    buf = np.full(exact.padded_rows, np.nan, np.float32)
    buf[:40] = vals
    rounded = exact.with_vec("x", Vec(jnp_array(buf, cl), T_NUM, 40))
    assert not rounded.vec("x").is_exact_int
    designs = []
    for fr in (exact, rounded):
        di = DataInfo.fit(fr, response_column="y")
        designs.append(np.asarray(di.make_matrix(fr)))
    np.testing.assert_array_equal(designs[0], designs[1])
    np.testing.assert_array_equal(np.asarray(exact.vec("x").numeric_data()),
                                  np.asarray(rounded.vec("x").numeric_data()))


def jnp_array(buf, cl):
    from h2o3_tpu.runtime.cluster import put_sharded
    return put_sharded(buf, cl.row_sharding)


def test_csv_id_column_keeps_its_integers(cl, tmp_path):
    from h2o3_tpu.frame.parse import parse_csv
    ids = BASE - np.arange(50)
    path = tmp_path / "ids.csv"
    path.write_text("id,x\n" + "".join(f"{i},{k * 0.5}\n" for k, i in enumerate(ids)))
    fr = parse_csv(str(path))
    assert fr.vec("id").is_exact_int and not fr.vec("x").is_exact_int
    np.testing.assert_array_equal(fr.vec("id").to_numpy(), ids)
