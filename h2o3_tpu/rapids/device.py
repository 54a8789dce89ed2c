"""Device-side munging programs: row order, dense rank, join matching, row moves.

Reference semantics: ``water/rapids/RadixOrder.java`` (distributed MSB radix
sort over 100M rows) and ``water/rapids/BinaryMerge.java`` (per-MSB-bucket
binary merge with row expansion).  TPU redesign: every step is a named jitted
program built from what the chip does well — ``lax.sort`` carrying its
payload, cumulative sums and maxima, one stacked gather per row index — and
from nothing it does badly: no frame-sized scatter (some 165 ns a row on a
v5e, PERF.md), no ``segment_*`` table with a segment per row, no per-row
binary search (log N dependent gathers a row), no gather per value (a TPU
gather is paid per index, not per value moved: ``gather_columns``).  Where a
scatter would invert a permutation or spread counts over output slots, a
second sort does.

Keys are compared in their own dtype (``typed_key``): int32 for
exact-integer and categorical columns, float32 for the rest, so two keys
that differ by 1 at 1e8 stay two keys.  NA and padding sort last under
either direction and never match in a join.

The programs, by the names the device trace shows:

- ``jit_sort_rows``: the order of one stable ``lax.sort`` over all key
  columns (``lex_order``) and the stacked gather of the device columns.
- ``jit_dense_rank``: group ids for ``group_by``: a sort, a cumulative sum
  over the group boundaries, a second sort back to row order.
- ``jit_merge_match``: both tables' keys -> per left row the number of
  matching right rows and where they start, and the output's row count.
- ``jit_merge_gather``: the expansion of those counts into output slots,
  one stacked gather of the left side's values by the slot's left row, the
  right row, and one of the right columns by it, at a coarse padded length
  (``merge_padded_rows``) that joins of nearby sizes share.
- ``jit_merge_trim``: those columns cut to ``pad_rows`` of the output's rows,
  the padded length every other column of as many rows has.
- ``jit_take_rows``: the gather behind ``gather_rows`` (filters, the rows
  of an outer join that matched nothing).

Host syncs are counted, not claimed: ``ops`` raises
``rapids_host_syncs_total{op}`` at each (one a merge: the output's row count;
none a sort; a frame with host-only columns pays one more for their index).
So are gathers: ``rapids_gathers_total{op}`` beside
``rapids_gathered_columns_total{op}`` (``note_gathers``).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..frame.frame import Frame
from ..frame.vec import Vec, T_CAT, T_TIME, INT_NA
from ..runtime import observability as obs
from ..runtime.cluster import cluster, fetch

_INT_MAX = np.int32(np.iinfo(np.int32).max)

# what a column's payload is to these programs, and the NA it is filled with
CAT, INT, F32 = "cat", "int", "f32"
F32_AS_INT = "f32_as_int"       # a float32 key joined to an exact-integer one
_FILL = {CAT: np.int32(-1), INT: INT_NA, F32: np.float32(np.nan)}


def column_kind(vec: Vec) -> str:
    if vec.data is None:
        raise TypeError(f"column of type {vec.type} is host-only")
    if vec.type == T_CAT:
        return CAT
    return INT if vec.is_exact_int else F32


def typed_key(x: jax.Array, kind: str, remap: Optional[jax.Array] = None) -> jax.Array:
    """A payload as the programs compare it: int32 with NA as ``INT_NA``
    (categorical codes, through ``remap`` onto a shared domain where the two
    sides of a join differ; exact integers as they are) or float32 with NA
    as the one positive NaN and -0.0 as 0.0.  ``F32_AS_INT``: a float32 key
    met by an exact-integer one compares as that integer where it is whole,
    and as NA where it is not (no integer equals it).  Traceable."""
    if kind == CAT:
        code = x if remap is None else remap[jnp.clip(x, 0, None)]
        return jnp.where(x < 0, INT_NA, code)
    if kind == INT:
        return x
    if kind == F32_AS_INT:
        whole = (x == jnp.round(x)) & (jnp.abs(x) < 2.0 ** 31)
        return jnp.where(whole, jnp.where(whole, x, 0).astype(jnp.int32), INT_NA)
    return jnp.where(jnp.isnan(x), jnp.nan, x + 0.0)


def _key_isna(k: jax.Array) -> jax.Array:
    return k == INT_NA if k.dtype == jnp.int32 else jnp.isnan(k)


def lex_order(keys: Sequence[jax.Array],
              ascending: Optional[Sequence[bool]] = None) -> jax.Array:
    """Row order sorting lexicographically by the typed ``keys`` (first key
    primary): ONE stable ``lax.sort`` over all of them with the row index as
    the carried operand.  NA and padding stay last under either direction:
    an int32 key becomes ``k - 1`` ascending and ``~k`` descending, both of
    which wrap ``INT_NA`` alone onto the largest int32; a float32 key is
    negated to descend and its NaN made positive, which ``lax.sort``'s total
    order puts past +inf.  Traceable."""
    asc = [True] * len(keys) if ascending is None else list(ascending)
    ordered = []
    for k, a in zip(keys, asc):
        if k.dtype == jnp.int32:
            ordered.append(k - 1 if a else ~k)
        else:
            ordered.append(jnp.where(jnp.isnan(k), jnp.nan, k if a else -k))
    rows = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    return jax.lax.sort((*ordered, rows), num_keys=len(ordered),
                        is_stable=True)[-1]


def _constrain(x: jax.Array, sharding) -> jax.Array:
    return x if sharding is None else jax.lax.with_sharding_constraint(x, sharding)


# the most columns one gather moves: a stack's columns are the sublanes an
# index fetches, and a tile has eight of 4 bytes.  On a v5e, 46M ascending
# indices into 100M rows (tools/chip_gather_check.py, PR 40): a stack of 8
# costs 15.2 ns an index, one of 3 13.5, a column alone 23.9; ONE stack of
# 16 did not compile beside the tables, so 16 has no reading
_GATHER_GROUP = 8


def _gather_groups(cols) -> list:
    """The positions in ``cols`` that each gather of ``gather_columns``
    moves: the 4-byte payloads in runs of at most ``_GATHER_GROUP``, any
    other payload alone."""
    wide = [i for i, c in enumerate(cols) if c.dtype.itemsize == 4]
    return [wide[at: at + _GATHER_GROUP]
            for at in range(0, len(wide), _GATHER_GROUP)] + \
        [[i] for i, c in enumerate(cols) if c.dtype.itemsize != 4]


def note_gathers(op: str, *column_sets) -> None:
    """Count what a program of ``op`` was dispatched to gather, one
    ``gather_columns`` per set of columns: the gathers, and the values they
    move.  The ratio of the two counters is how far the stacking engages."""
    obs.inc("rapids_gathers_total",
            sum(len(_gather_groups(cols)) for cols in column_sets), op=op)
    obs.inc("rapids_gathered_columns_total",
            sum(len(cols) for cols in column_sets), op=op)


def gather_columns(cols, index: jax.Array) -> tuple:
    """Every column of ``cols`` (1-D, of one length) at ``index``, moved bit
    for bit.  A TPU gather costs per index, not per value, so the 4-byte
    payloads (int32 as they are, float32 reinterpreted as int32 and back:
    NaN payloads, -0.0 and ``INT_NA`` pass untouched) are stacked as
    ``[rows, k]``, gathered ONCE and unstacked; one column alone is
    ``c[index]``.  Traceable."""
    out = list(cols)
    for group in _gather_groups(cols):
        if len(group) == 1:
            out[group[0]] = cols[group[0]][index]
            continue
        moved = jnp.stack([jax.lax.bitcast_convert_type(cols[i], jnp.int32)
                           for i in group], axis=1)[index]
        for j, i in enumerate(group):
            out[i] = jax.lax.bitcast_convert_type(moved[:, j], cols[i].dtype)
    return tuple(out)


@functools.partial(jax.jit, static_argnames=("kinds", "ascending", "sharding"))
def sort_rows(keys, cols, *, kinds, ascending, sharding=None):
    """(row order, every column in that order).  Padding rows hold NA keys
    and the largest row numbers, so they stay the last rows of every
    column."""
    order = lex_order([typed_key(k, kind) for k, kind in zip(keys, kinds)],
                      ascending)
    return order, tuple(_constrain(c, sharding)
                        for c in gather_columns(cols, order))


@functools.partial(jax.jit, static_argnames=("kinds",))
def dense_rank(keys, nrows, *, kinds):
    """(rank per row, number of groups G): the 0-based lexicographic dense
    rank of each row's key tuple among the rows whose keys are all present;
    a row with any NA key, and padding, gets G.  One sort with the row index
    carried, a cumulative sum over the group boundaries, one sort back."""
    ks = [typed_key(k, kind) for k, kind in zip(keys, kinds)]
    rows = jnp.arange(ks[0].shape[0], dtype=jnp.int32)
    absent = rows >= nrows
    for k in ks:
        absent = absent | _key_isna(k)
    *sk, srow = jax.lax.sort((absent.astype(jnp.int32), *ks, rows),
                             num_keys=len(ks) + 1, is_stable=True)
    present = sk[0] == 0
    new = jnp.zeros(rows.shape[0] - 1, bool)
    for s in sk[1:]:
        new = new | (s[1:] != s[:-1])
    new = jnp.concatenate([jnp.ones(1, bool), new]) & present
    groups = jnp.sum(new, dtype=jnp.int32)
    rank = jnp.where(present, jnp.cumsum(new, dtype=jnp.int32) - 1, groups)
    return jax.lax.sort((srow, rank), num_keys=1)[1], groups


@functools.partial(jax.jit, static_argnames=("fills", "p_out", "sharding"))
def take_rows(cols, index, n_out, forced_na, *, fills, p_out, sharding=None):
    """Output row j < ``n_out`` is row ``index[j]`` of every column; rows
    past ``n_out``, and those ``forced_na`` marks (or None), hold each
    column's NA.  ``index`` may be shorter or longer than ``p_out``."""
    def fitted(a):
        short = p_out - a.shape[0]
        return a[:p_out] if short <= 0 else jnp.pad(a, (0, short))
    live = jnp.arange(p_out) < n_out
    if forced_na is not None:
        live = live & ~fitted(forced_na)
    index = fitted(index)
    if cols:
        index = jnp.clip(index, 0, cols[0].shape[0] - 1)
    return index, live, tuple(
        _constrain(jnp.where(live, c, _FILL[f]), sharding)
        for c, f in zip(gather_columns(cols, index), fills))


@jax.jit
def kept_first(mask):
    """Row order with the rows ``mask`` keeps first, in row order."""
    rows = jnp.arange(mask.shape[0], dtype=jnp.int32)
    return jax.lax.sort(((~mask).astype(jnp.int32), rows), num_keys=1,
                        is_stable=True)[1]


def wrap_rows(frame: Frame, device_cols, n_out: int, host_index) -> Frame:
    """A frame of ``n_out`` rows around gathered device columns; host-only
    and TIME columns (exact host payloads) are gathered on the host by
    ``host_index()`` -> (row index, NA mask), fetched once if any needs it."""
    device_cols = iter(device_cols)
    idx = na = None
    vecs = []
    for v in frame.vecs:
        if v.data is None or v.type == T_TIME:
            if idx is None:
                idx, na = host_index()
            payload = v.host_data
            col = payload[np.clip(idx, 0, max(len(payload) - 1, 0))]
            if na.any():
                col = np.array(col, copy=True)
                col[na] = np.nan if v.type == T_TIME else None
            vecs.append(Vec.from_numpy(col, v.type))
        else:
            vecs.append(Vec(next(device_cols), v.type, n_out, domain=v.domain))
    return Frame(frame.names, vecs)


def device_columns(frame: Frame) -> Tuple[tuple, tuple]:
    """(payloads, kinds) of the columns a device program moves: all but
    string / UUID columns and TIME, whose exact values are on the host."""
    vs = [v for v in frame.vecs if v.data is not None and v.type != T_TIME]
    return tuple(v.data for v in vs), tuple(column_kind(v) for v in vs)


def has_host_columns(frame: Frame) -> bool:
    return any(v.data is None or v.type == T_TIME for v in frame.vecs)


def note_host_index(op: str, *arrays) -> list:
    """Fetch what a frame's host-only columns are gathered by: a counted
    host sync of ``op``."""
    out = [np.asarray(fetch(a)) for a in arrays]
    obs.inc("rapids_host_syncs_total", op=op)
    obs.inc("transfer_bytes_total", sum(a.nbytes for a in out), dir="d2h")
    return out


def gather_rows(frame: Frame, order: jax.Array, n_out: int,
                na_mask: Optional[jax.Array] = None, op: str = "gather") -> Frame:
    """New Frame whose row j is ``frame`` row ``order[j]``: one
    ``jit_take_rows`` over every device column.

    ``order`` may be longer/shorter than the output padding; rows at j >=
    n_out become NA padding.  ``na_mask`` additionally forces NA output rows.
    String/UUID/TIME columns gather host-side (they keep exact host
    payloads); everything else stays on device.
    """
    cl = cluster()
    cols, fills = device_columns(frame)
    index, live, out = take_rows(
        cols, order.astype(jnp.int32), np.int32(n_out), na_mask, fills=fills,
        p_out=cl.pad_rows(n_out), sharding=cl.row_sharding)
    note_gathers(op, cols)

    def host_index():
        idx, ok = note_host_index(op, index, live)
        return idx[:n_out], ~ok[:n_out]
    return wrap_rows(frame, out, n_out, host_index)


# -------------------------------------------------------------------- merge
def merge_padded_rows(m: int, left_padded: int) -> int:
    """The padded length ``jit_merge_gather`` computes a join's output of
    ``m`` rows at: ``m`` rounded up to a whole number of steps of 1/64 of the
    left table's padded rows (a step is itself padded to the cluster's row
    multiple, so the length is never under ``pad_rows(m)``).  The length is
    part of that program's signature and ``m`` differs with every user's
    data, so joins of nearby sizes share one executable.  It stays inside
    ``ops.merge``: ``merge_trim`` cuts the columns to ``pad_rows(m)`` before
    any ``Vec`` holds them."""
    step = cluster().pad_rows(-(-left_padded // 64))
    return max(-(-m // step), 1) * step


def _out_counts(cnt, nl, how):
    """Output rows per left row from its match count (-1: NA key or
    padding): an inner join emits its matches, a left join at least one row
    for every real left row."""
    if how == "left":
        return jnp.where(jnp.arange(cnt.shape[0]) < nl, jnp.maximum(cnt, 1), 0)
    return jnp.maximum(cnt, 0)


@functools.partial(jax.jit, static_argnames=("lkinds", "rkinds", "how"))
def merge_match(lkeys, rkeys, lremaps, rremaps, nl, nr, *, lkinds, rkinds, how):
    """Per left row, in left-row order: ``cnt`` the number of right rows
    with its key (-1 where its key has an NA or the row is padding: NA never
    matches) and ``start``, where those right rows begin in ``srow``; ``srow``,
    the rows of [right; left] sorted by key; and the output's row count for
    ``how``, as int32 and, against its overflow, as float32.

    One stable sort of both tables' keys with the right table first, so that
    each key's right rows stand ahead of its left rows, both in row order;
    cumulative sums and maxima over the group boundaries give every left row
    its group's right rows; a second sort keyed on the left row number
    carries (cnt, start) back to left-row order, where a scatter would."""
    lk = [typed_key(k, kind, t) for k, kind, t in zip(lkeys, lkinds, lremaps)]
    rk = [typed_key(k, kind, t) for k, kind, t in zip(rkeys, rkinds, rremaps)]
    pl, pr = lk[0].shape[0], rk[0].shape[0]
    rows = jnp.arange(pr + pl, dtype=jnp.int32)
    *sk, srow = jax.lax.sort(
        (*[jnp.concatenate([r, l]) for r, l in zip(rk, lk)], rows),
        num_keys=len(lk), is_stable=True)
    is_right = srow < pr
    absent = jnp.where(is_right, srow >= nr, srow - pr >= nl)
    new = jnp.zeros(pr + pl - 1, bool)
    for s in sk:
        absent = absent | _key_isna(s)
        new = new | (s[1:] != s[:-1])
    new = jnp.concatenate([jnp.ones(1, bool), new])
    start = jax.lax.cummax(jnp.where(new, rows, 0))
    right = (is_right & ~absent).astype(jnp.int32)
    seen = jnp.cumsum(right, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(new, seen - right, 0))
    cnt = jnp.where(absent, -1, seen - before)
    _, cnt, start = jax.lax.sort(
        (jnp.where(is_right, _INT_MAX, srow - pr), cnt, start), num_keys=1)
    cnt, start = cnt[:pl], start[:pl]
    out = _out_counts(cnt, nl, how)
    return (cnt, start, srow, jnp.sum(out, dtype=jnp.int32),
            jnp.sum(out.astype(jnp.float32)))


def expand_counts(counts: jax.Array, p_out: int) -> Tuple[jax.Array, jax.Array]:
    """For each of ``p_out`` output slots: the row that owns it (row i owns
    ``counts[i]`` consecutive slots, in row order) and its offset among that
    row's slots.  Slots past ``sum(counts)`` get ``len(counts)``.

    The running totals ``ends`` and the slot numbers are merged by ONE sort
    (an end as ``2 * end``, slot j as ``2 * j + 1``, so an end sorts ahead
    of the slot of its number); in merged order the ends seen so far are a
    slot's owner and the last end its owner's first slot; a second sort
    drops the ends and leaves the slots in order.  Traceable; the total
    must be under 2^30."""
    ends = jnp.cumsum(counts, dtype=jnp.int32)
    slots = jnp.arange(p_out, dtype=jnp.int32)
    merged = jax.lax.sort(jnp.concatenate([2 * ends, 2 * slots + 1]))
    is_end = (merged & 1) == 0
    number = merged >> 1
    owner = jnp.cumsum(is_end, dtype=jnp.int32)
    first = jax.lax.cummax(jnp.where(is_end, number, 0))
    _, owner, offset = jax.lax.sort(
        (jnp.where(is_end, _INT_MAX, number), owner, number - first), num_keys=1)
    return owner[:p_out], offset[:p_out]


def merge_left_carried(start, cnt, lcols, how) -> tuple:
    """What ``jit_merge_gather`` moves by the output slot's left row."""
    return (start, *lcols) + ((cnt,) if how == "left" else ())


@functools.partial(jax.jit, static_argnames=(
    "how", "p_out", "lfills", "rfills", "sharding"))
def merge_gather(cnt, start, srow, lcols, rcols, nl, m, *, how, p_out,
                 lfills, rfills, sharding=None):
    """The join's output columns, ``p_out`` padded rows of which ``m`` are
    real: each left row's slots from ``expand_counts``, then per slot the
    left row, ONE gather by it of all the left side carries (``start``, the
    left columns and, for a left join alone, ``cnt``: an inner join gives
    slots only to rows that matched, so its every live slot is matched), the
    right row ``srow[start + offset]`` (none, and NA in the right columns,
    where a left join's row matched nothing) and one gather of the right
    columns by it.  Also returns the two row indices (-1: no right row),
    which host-only columns are gathered by."""
    pl = cnt.shape[0]
    owner, offset = expand_counts(_out_counts(cnt, nl, how), p_out)
    live = jnp.arange(p_out) < m
    li = jnp.where(live, jnp.minimum(owner, pl - 1), 0)
    start_l, *lmoved = gather_columns(
        merge_left_carried(start, cnt, lcols, how), li)
    matched = live
    if how == "left":
        matched = live & (lmoved.pop() > 0)
    ri = srow[jnp.where(matched, start_l + offset, 0)]
    lout = tuple(_constrain(jnp.where(live, c, _FILL[f]), sharding)
                 for c, f in zip(lmoved, lfills))
    rout = tuple(_constrain(jnp.where(matched, c, _FILL[f]), sharding)
                 for c, f in zip(gather_columns(rcols, ri), rfills))
    return lout, rout, li, jnp.where(matched, ri, -1)


@functools.partial(jax.jit, static_argnames=("p", "sharding"))
def merge_trim(cols, *, p, sharding=None):
    """``jit_merge_gather``'s output columns cut to ``p`` = ``pad_rows(m)``
    rows: a column of ``m`` rows has that padded length wherever it was
    made, so a join's result meets any other frame's columns elementwise.
    A copy of the output (3 ms at 3 x 91M rows on a v5e), compiled per
    ``p`` in well under a second, where the gather program takes minutes.
    Its output is allocated at dispatch, while the gather still runs: a
    join's peak is the gather's plus this copy (PERF.md section 6, PR 39)."""
    return jax.tree.map(lambda c: _constrain(c[:p], sharding), cols)
