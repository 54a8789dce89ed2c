"""An inner equi-join of two tables on one integer key, in plain numpy on
int64 host columns: its own sort-merge, no code of the system.

Semantics held: NA keys never match (these tables have none); the output is
in LEFT-row order; a left row's several matches are adjacent and in
right-row order; many-to-many keys give every pair. A join computes no value:
every tolerance is 0. ``check`` holds EVERY row of the window's last result
to that: the row count, ``key`` exactly, ``v1`` and ``v2`` bit for bit.

Two controls go through the same verdict and must fail: ``f32_keys_fails``
(the join on keys rounded to float32, which is what a float32 payload
computes: eight keys become one near 1e8; its row count is compared, its
rows would not fit the host) and ``tail_fails`` (the result with the last
1 % of its rows NA, as an output a gather never wrote to its end).

The sorts are of packed (key, row) pairs, ``key << 32 | row`` in one uint64
``np.sort``, which is a stable order by key; keys are shifted to start at 0
first, so they must span less than 2^31 and a table hold less than 2^32
rows.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def by_key(key):
    """(keys sorted, rows in that order), ties in row order."""
    packed = np.sort((key.astype(np.uint64) << np.uint64(32))
                     | np.arange(len(key), dtype=np.uint64))
    return (packed >> np.uint64(32)).astype(np.int64), \
        (packed & np.uint64(0xFFFFFFFF)).astype(np.int64)


def match(left_key, right_key):
    """Per left row the number of right rows with its key and the place of
    the first in the right table's sorted order; and that order."""
    low = min(int(left_key.min()), int(right_key.min()))
    span = max(int(left_key.max()), int(right_key.max())) - low
    if span >= 2 ** 31 or max(len(left_key), len(right_key)) >= 2 ** 32:
        raise ValueError("keys span 2^31 or more: the packed sort cannot hold them")
    with ThreadPoolExecutor(2) as pool:
        right = pool.submit(by_key, right_key - low)
        left_sorted, left_rows = by_key(left_key - low)
        right_sorted, right_rows = right.result()
    lo = np.searchsorted(right_sorted, left_sorted, side="left")
    hi = np.searchsorted(right_sorted, left_sorted, side="right")
    count, first = np.empty(len(left_key), np.int64), np.empty(len(left_key), np.int64)
    count[left_rows] = hi - lo
    first[left_rows] = lo
    return count, first, right_rows


def join_index(left_key, right_key):
    """(left row, right row) of every output row of the inner join."""
    count, first, right_rows = match(left_key, right_key)
    li = np.repeat(np.arange(len(left_key)), count)
    offset = np.arange(len(li)) - np.repeat(np.cumsum(count) - count, count)
    return li, right_rows[first[li] + offset]


def joined_rows(left_key, right_key):
    """The join's row count alone."""
    return int(match(left_key, right_key)[0].sum())


def verdict(got, want, tol):
    """``got``: the result's row count and, if that is right, its columns;
    ``want``: the reference's. Mismatches are counted, each against its
    limit (all 0)."""
    detail = {"rows": got["rows"], "rows_abs": abs(got["rows"] - want["rows"])}
    if detail["rows_abs"] > tol["rows_abs"] or "key" not in got:
        return False, detail
    detail["key_mismatches"] = int((got["key"] != want["key"]).sum())
    detail["value_bit_mismatches"] = int(sum(
        (got[c].view(np.int32) != want[c].view(np.int32)).sum() for c in ("v1", "v2")))
    return (detail["key_mismatches"] <= tol["key_mismatches"]
            and detail["value_bit_mismatches"] <= tol["value_bit_mismatches"]), detail


def as_float32_holds(key):
    """Integer keys as a float32 payload compares them."""
    return key.astype(np.float32).astype(np.int64)


def check(state, result, tol):
    left, right = state["tables"]["left"], state["tables"]["right"]
    with ThreadPoolExecutor(1) as pool:
        rounded = pool.submit(joined_rows, as_float32_holds(left["key"]),
                              as_float32_holds(right["key"]))
        li, ri = join_index(left["key"], right["key"])
        want = {"rows": len(li), "key": left["key"][li], "v1": left["v1"][li],
                "v2": right["v2"][ri]}
        del li, ri
        got = {"rows": result.nrows}
        if got["rows"] == want["rows"]:
            key = np.asarray(result.vec("key").to_numpy())
            whole = np.isfinite(key) & (key == np.rint(key))
            got["key"] = np.where(whole, key, -1).astype(np.int64)   # NA or a fraction: no key
            got.update({c: np.ascontiguousarray(result.vec(c).to_numpy(), np.float32)
                        for c in ("v1", "v2")})
        ok, detail = verdict(got, want, tol)
        controls = {"f32_keys_fails": not verdict({"rows": rounded.result()}, want, tol)[0]}
    detail["f32_keys_rows"] = rounded.result()
    if "key" in got:
        cut = got["rows"] - max(got["rows"] // 100, 1)
        tail = {"rows": got["rows"], "key": got["key"].copy(),
                **{c: got[c].copy() for c in ("v1", "v2")}}
        tail["key"][cut:] = -1
        tail["v1"][cut:] = tail["v2"][cut:] = np.nan
        controls["tail_fails"] = not verdict(tail, want, tol)[0]
    detail.update(controls)
    return ok and all(controls.values()) and len(controls) == 2, detail
