"""Plain reference for ``merge`` and ``sort``: numpy on int64 / float64 host
columns, a loop-free sort-merge (``argsort`` + ``searchsorted``), no code of
the system.  ``tests/test_rapids_exact.py`` holds the device programs of
``rapids/device.py`` to it exactly, row order included.

A column is a 1-D array: integers (int64, no NA), floats (NaN is NA), or
categorical labels as an object / string array (None is NA).  Keys are
compared as they are held: an integer key exactly, a float key as the float
it is, labels as labels.

Semantics, and where they leave ``water/rapids/BinaryMerge.java`` /
``RadixOrder.java``:

- NA keys never match (as BinaryMerge).
- ``inner`` / ``left``: output in LEFT-row order, a left row's several
  matches adjacent and in right-row order; many-to-many keys give every
  pair.  BinaryMerge emits rows in the order of the left table's radix-sorted
  keys; this system keeps the order the caller's left table has, so a join
  against a lookup table leaves a frame row-aligned with what it was.
- ``left`` keeps left rows whose key is NA or unmatched, NA in the right
  columns (BinaryMerge ``allLeft``).
- ``right`` is ``left`` from the other side: in RIGHT-row order, key columns
  first, then the left table's other columns, then the right table's
  (BinaryMerge swaps the tables the same way and keeps its sorted order).
- ``outer``: the ``left`` result followed by the right rows that matched no
  left row, in right-row order, NA in the left columns; right rows with an NA
  key are dropped (H2O-3's ``AstMerge`` has no full outer join at all:
  ``all.x`` and ``all.y`` together are refused).
- ``sort``: stable; NA last under either direction (RadixOrder puts NA first
  ascending); ties keep row order under either direction.  Labels sort as
  strings; the system sorts a categorical by level number, which is the same
  order wherever the domain is sorted (``Frame.from_numpy`` makes it so).
"""

import numpy as np


def _is_na(col):
    col = np.asarray(col)
    if col.dtype.kind == "f":
        return np.isnan(col)
    if col.dtype.kind in "OUS":
        return np.array([v is None for v in col], bool) if col.dtype == object \
            else np.zeros(len(col), bool)
    return np.zeros(len(col), bool)


def _codes(columns):
    """One int64 code per row and key tuple, equal where every key of the
    tuple is equal, over the rows of all ``columns`` groups together; and
    per row whether any key is NA."""
    n = len(columns[0])
    na = np.zeros(n, bool)
    code = np.zeros(n, np.int64)
    for col in columns:
        col = np.asarray(col)
        bad = _is_na(col)
        na |= bad
        safe = col.copy()
        if bad.any():
            safe[bad] = safe[~bad][0] if (~bad).any() else (
                "" if col.dtype.kind in "OUS" else 0)
        if safe.dtype == object:
            safe = safe.astype(str)
        _, inverse = np.unique(safe, return_inverse=True)
        code = code * (int(inverse.max()) + 1 if n else 1) + inverse
        _, code = np.unique(code, return_inverse=True)     # keep the codes small
    return code.astype(np.int64), na


def join_index(left_keys, right_keys, how="inner"):
    """(left row, right row) of every output row of an ``inner`` or ``left``
    join; right row -1 where a left join's row matched nothing."""
    nl, nr = len(left_keys[0]), len(right_keys[0])
    code, na = _codes([np.concatenate([np.asarray(l), np.asarray(r)])
                       for l, r in zip(left_keys, right_keys)])
    lcode, rcode = code[:nl], code[nl:]
    lna, rna = na[:nl], na[nl:]
    rrows = np.flatnonzero(~rna)
    rorder = rrows[np.argsort(rcode[rrows], kind="stable")]   # right-row order within a key
    rsorted = rcode[rorder]
    lo = np.searchsorted(rsorted, lcode, side="left")
    hi = np.searchsorted(rsorted, lcode, side="right")
    count = np.where(lna, 0, hi - lo)
    emit = np.maximum(count, 1) if how == "left" else count
    li = np.repeat(np.arange(nl), emit)
    first = np.cumsum(emit) - emit
    offset = np.arange(len(li)) - first[li]
    matched = count[li] > 0
    ri = np.full(len(li), -1, np.int64)
    ri[matched] = rorder[(lo[li] + offset)[matched]]
    return li, ri


def _take(col, index):
    """Rows ``index`` of a column, NA where the index is -1."""
    col = np.asarray(col)
    out = col[np.maximum(index, 0)]
    if (index < 0).any():
        if col.dtype.kind in "iub":
            out = out.astype(np.float64)
        out = out.copy()
        out[index < 0] = None if out.dtype == object else np.nan
    return out


def reference_merge(left, right, by, how="inner"):
    """``left`` and ``right``: {name: column}.  Returns {name: column}: the
    key columns, the left table's other columns, the right table's."""
    by = [by] if isinstance(by, str) else list(by)
    lrest = [n for n in left if n not in by]
    rrest = [n for n in right if n not in by]
    if how == "right":
        out = reference_merge(right, left, by, "left")
        return {n: out[n] for n in by + lrest + rrest}
    li, ri = join_index([left[k] for k in by], [right[k] for k in by],
                        "left" if how == "outer" else how)
    out = {n: _take(left[n], li) for n in by + lrest}
    out.update({n: _take(right[n], ri) for n in rrest})
    if how == "outer":
        _, rna = _codes([right[k] for k in by])
        rows, matches = join_index([right[k] for k in by], [left[k] for k in by], "left")
        hit = np.zeros(len(rna), bool)
        hit[rows[matches >= 0]] = True
        extra = np.flatnonzero(~hit & ~rna)
        none = np.full(len(extra), -1, np.int64)
        tail = {n: _take(right[n], extra) for n in by}
        tail.update({n: _take(left[n], none) for n in lrest})
        tail.update({n: _take(right[n], extra) for n in rrest})
        out = {n: _concat(out[n], tail[n]) for n in out}
    return out


def _concat(a, b):
    if a.dtype.kind in "iub" and b.dtype.kind == "f" or \
            a.dtype.kind == "f" and b.dtype.kind in "iub":
        a, b = a.astype(np.float64), b.astype(np.float64)
    return np.concatenate([a, b])


def sort_index(keys, ascending=True):
    """Row order of a stable multi-key sort, NA last under either
    direction."""
    asc = [ascending] * len(keys) if isinstance(ascending, bool) else list(ascending)
    order = np.arange(len(keys[0]))
    for key, up in reversed(list(zip(keys, asc))):      # least significant first
        code, na = _codes([np.asarray(key)])            # ranks: order-preserving
        rank = np.where(na, np.iinfo(np.int64).max, code if up else -code)
        order = order[np.argsort(rank[order], kind="stable")]
    return order


def reference_sort(cols, by, ascending=True):
    """``cols``: {name: column}.  Returns {name: column} in sorted order."""
    by = [by] if isinstance(by, str) else list(by)
    order = sort_index([cols[k] for k in by], ascending)
    return {n: np.asarray(c)[order] for n, c in cols.items()}
