"""``refs/merge_join.py`` against the system at a tiny size on the CPU and
against wrong inputs that have to fail; the generator's shape; the cost."""

import numpy as np
import pytest

from benchmark.costs import merge as merge_cost
from benchmark.datagen import merge_tables
from benchmark.drivers import merge
from benchmark.refs import merge_join

TOL = {"rows_abs": 0, "key_mismatches": 0, "value_bit_mismatches": 0}
MIX = {"readback_rows": 512}
KEY_BASE = 99_990_000           # keys near 1e8: float32's spacing there is 8


@pytest.fixture(scope="module", autouse=True)
def booted():
    import h2o3_tpu
    h2o3_tpu.init()


@pytest.fixture(scope="module")
def joined():
    state = merge.set_up({}, MIX, 11, merge_tables.generate(rows=4000, seed=11, key_base=KEY_BASE))
    return state, merge.unit(state)


def test_generator_gives_the_stated_shape_from_the_seed_alone():
    tables, domains, response = merge_tables.generate(rows=10_000, seed=3)
    again, _, _ = merge_tables.generate(rows=10_000, seed=3)
    other, _, _ = merge_tables.generate(rows=10_000, seed=4)
    assert all(np.array_equal(tables[s][c], again[s][c]) for s in tables for c in tables[s])
    assert not np.array_equal(tables["right"]["key"], other["right"]["key"])
    assert domains == {} and response is None
    right = tables["right"]["key"]
    values, counts = np.unique(right[right < 10_000], return_counts=True)
    assert (counts == 1).sum() == 8_900 and (counts == 2).sum() == 100
    assert (right >= 10_000).sum() == 900 and right.max() < 11_000
    left = tables["left"]["key"]
    assert left.min() >= 0 and left.max() < 10_000 and left.dtype == np.int64
    matched = np.isin(left, values).mean()
    assert 0.88 < matched < 0.92
    assert tables["left"]["v1"].dtype == tables["right"]["v2"].dtype == np.float32


def test_the_join_passes_with_both_controls_failing(joined):
    state, out = joined
    ok, detail = merge_join.check(state, out, TOL)
    assert ok, detail
    assert detail["rows"] == out.nrows and detail["rows_abs"] == 0
    assert detail["key_mismatches"] == 0 and detail["value_bit_mismatches"] == 0
    assert detail["f32_keys_fails"] and detail["tail_fails"]
    assert detail["f32_keys_rows"] > 3 * out.nrows      # eight keys a float32 near 1e8


def test_a_join_on_keys_rounded_to_float32_fails(joined):
    """What a float32 payload computes: the same tables with their keys
    rounded as float32 holds them, joined by the system itself."""
    from h2o3_tpu import Frame
    state, _ = joined
    rounded = {side: Frame.from_numpy({
        **cols, "key": merge_join.as_float32_holds(cols["key"])})
        for side, cols in state["tables"].items()}
    wrong = rounded["left"].merge(rounded["right"], by="key", how="inner")
    ok, detail = merge_join.check(state, wrong, TOL)
    assert not ok and detail["rows_abs"] > 0


def test_a_join_in_another_order_fails(joined):
    state, out = joined
    ok, detail = merge_join.check(state, out.sort("v1"), TOL)
    assert not ok and detail["rows_abs"] == 0 and detail["key_mismatches"] > 0


def test_set_up_stops_a_system_that_cannot_hold_the_keys(monkeypatch):
    tables = merge_tables.generate(rows=2000, seed=5, key_base=KEY_BASE)
    monkeypatch.setattr(merge, "keys_held", lambda frame, keys, rows: False)
    with pytest.raises(RuntimeError, match="cannot hold"):
        merge.set_up({}, MIX, 5, tables)


def test_keys_held_tells_a_rounded_payload(joined):
    import jax.numpy as jnp
    from h2o3_tpu import Frame
    from h2o3_tpu.frame.vec import Vec, T_NUM
    state, _ = joined
    keys = state["tables"]["left"]["key"]
    rows = np.arange(0, len(keys), 7)
    assert merge.keys_held(state["left"], keys, rows)
    rounded = Frame(["key"], [Vec(jnp.asarray(keys.astype(np.float32)), T_NUM, len(keys))])
    assert not merge.keys_held(rounded, keys, rows)


def test_cost_counts_tables_output_and_sorts():
    cost = merge_cost.cost({"rows": 100, "right_rows": 60, "out_rows": 90})
    assert cost == {"bytes": 4 * (200 + 120) + 4 * 270 + 8 * (5 * 160 + 4 * 190), "ops": 0}
