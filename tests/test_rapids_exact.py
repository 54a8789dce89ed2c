"""``Frame.merge`` / ``Frame.sort`` (and ``(merge …)`` / ``(sort …)`` through
the Rapids evaluator) against the plain reference ``tests/reference_munge.py``
on seeded tables: results compared EXACTLY, integers and carried float32
values bit for bit, row order included.  Keys past 2^24 that differ by 1 are
in every table: a float32 payload merges those (the parent of PR 39 did).
"""

import numpy as np
import pytest

from h2o3_tpu import Frame
from h2o3_tpu.frame.vec import T_CAT, INT_NA, takes_exact_int
import reference_munge as ref
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

    def read(name, **labels):
        return sum(s["v"] for s in obs.metrics_wire() if s["n"] == name
                   and all(s["l"].get(k) == v for k, v in labels.items()))
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

    def syncs():
        return sum(s["v"] for s in obs.metrics_wire()
                   if s["n"] == "rapids_host_syncs_total")
    before = syncs()
    L.sort("key", ascending=False)
    assert syncs() == before
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
