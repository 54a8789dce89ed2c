"""Munging primitives over sharded Frames (the water/rapids Ast* analogs).

sort/merge/group_by/filter run device-side, as the named programs of
device.py (the RadixOrder/BinaryMerge redesign).  ``sort`` is one program
and no host sync; ``merge`` two programs around the one sync its output's row
count needs (``rapids_host_syncs_total{op}`` counts them, and one more where
a frame has host-only columns to gather); ``filter_rows`` one sync for its
row count; ``group_by`` fetches group-count-sized arrays.  Spans:
``rapids.sort`` (``sort.order``, ``sort.gather``) and ``rapids.merge``
(``merge.keys``, ``merge.match``, ``merge.count``, ``merge.gather``);
``rapids_rows_total{op, side}`` counts rows in and out,
``rapids_gathers_total{op}`` the device gathers a program was dispatched with
and ``rapids_gathered_columns_total{op}`` the values they move.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..frame.frame import Frame
from ..frame import lineage
from ..frame.vec import Vec, T_CAT, T_NUM, T_STR, T_TIME, exact_int_host
from ..runtime import observability as obs
from ..runtime.cluster import cluster, fetch
from . import device as dev


def sort(frame: Frame, by: Union[str, Sequence[str]],
         ascending: Union[bool, Sequence[bool]] = True) -> Frame:
    """Multi-key sort — AstSort / RadixOrder analog: one device program
    (``jit_sort_rows``), no host sync.  Stable; keys compared in their own
    dtype; NA last under either direction."""
    by = [by] if isinstance(by, str) else list(by)
    asc = [ascending] * len(by) if isinstance(ascending, bool) \
        else list(ascending)
    if len(asc) != len(by):
        raise ValueError("ascending must match by")
    with obs.trace("rapids.sort", rows=frame.nrows, keys=len(by)):
        with obs.span("sort.order"):
            keys = tuple(frame.vec(c).data for c in by)
            kinds = tuple(dev.column_kind(frame.vec(c)) for c in by)
            cols, _ = dev.device_columns(frame)
            order, moved = dev.sort_rows(
                keys, cols, kinds=kinds, ascending=tuple(bool(a) for a in asc),
                sharding=cluster().row_sharding)
            dev.note_gathers("sort", cols)
        with obs.span("sort.gather"):
            def host_index():
                idx, = dev.note_host_index("sort", order)
                return idx[: frame.nrows], np.zeros(frame.nrows, bool)
            out = dev.wrap_rows(frame, moved, frame.nrows, host_index)
        obs.inc("rapids_rows_total", frame.nrows, op="sort", side="in")
        obs.inc("rapids_rows_total", frame.nrows, op="sort", side="out")
    return lineage.derive(out, frame, {"op": "sort", "by": by,
                                       "ascending": [bool(a) for a in asc]})


def filter_rows(frame: Frame, mask) -> Frame:
    """Boolean row filter — AstRowSlice analog (device compaction: the kept
    rows first by one sort, then the shared gather)."""
    if isinstance(mask, Vec):
        m = (mask.data != 0) & ~mask.isna()
    else:
        host = np.zeros(frame.padded_rows, bool)
        host[: frame.nrows] = np.asarray(mask)[: frame.nrows].astype(bool)
        m = jnp.asarray(host)
    m = m & (jnp.arange(frame.padded_rows) < frame.nrows)
    n_out = int(jnp.sum(m))
    return dev.gather_rows(frame, dev.kept_first(m), n_out, op="filter")


def rbind(*frames: Frame) -> Frame:
    """Stack frames vertically — AstRBind analog."""
    base = frames[0]
    for fr in frames[1:]:
        if fr.names != base.names:
            raise ValueError("rbind: column names differ")
    vecs = []
    for i, name in enumerate(base.names):
        vs = [fr.vecs[i] for fr in frames]
        t = vs[0].type
        if t == T_CAT:
            # unify domains
            domain = []
            seen = {}
            for v in vs:
                for lbl in (v.domain or []):
                    if lbl not in seen:
                        seen[lbl] = len(domain)
                        domain.append(lbl)
            codes = []
            for v in vs:
                remap = np.array([seen[lbl] for lbl in (v.domain or [])],
                                 dtype=np.int32)
                c = v.to_numpy()
                codes.append(np.where(c < 0, -1,
                                      remap[np.clip(c, 0, None)]))
            vecs.append(Vec.from_numpy(np.concatenate(codes), T_CAT,
                                       domain=domain))
        elif vs[0].data is None:
            vecs.append(Vec.from_numpy(
                np.concatenate([v.host_data for v in vs]), t))
        else:
            vecs.append(Vec.from_numpy(
                np.concatenate([v.host_data if t == T_TIME else v.to_numpy()
                                for v in vs]), t))
    return Frame(base.names, vecs)


def cbind(*frames: Frame) -> Frame:
    """Stack frames horizontally — AstCBind analog."""
    names, vecs = [], []
    for fr in frames:
        for n, v in zip(fr.names, fr.vecs):
            nn = n
            k = 0
            while nn in names:
                k += 1
                nn = f"{n}{k}"
            names.append(nn)
            vecs.append(v)
    return Frame(names, vecs)


def unique(vec: Vec) -> np.ndarray:
    """Distinct values — AstUnique analog."""
    if vec.type == T_CAT:
        codes = np.unique(vec.to_numpy())
        return np.asarray([vec.domain[c] for c in codes if c >= 0])
    x = vec.to_numpy()
    return np.unique(x[~np.isnan(x)])


def table(vec: Vec, weights: Optional[Vec] = None) -> Dict[str, float]:
    """Value counts — AstTable analog (device segment-sum for cats)."""
    if vec.type == T_CAT:
        K = len(vec.domain or [])
        codes = vec.data
        w = (vec.valid_mask() & (codes >= 0)).astype(jnp.float32)
        if weights is not None:
            w = w * weights.numeric_data()
        gid = jnp.where(codes >= 0, codes, K)
        counts = np.asarray(jax.ops.segment_sum(
            w, gid, num_segments=K + 1))[:K]
        return {vec.domain[i]: float(counts[i]) for i in range(K)}
    vals, counts = np.unique(vec.to_numpy()[~np.isnan(vec.to_numpy())],
                             return_counts=True)
    return {str(v): int(c) for v, c in zip(vals, counts)}


def ifelse(cond, yes, no) -> Vec:
    """Vectorized conditional — AstIfElse analog."""
    c = cond.values() if isinstance(cond, Vec) else jnp.asarray(cond)
    y = yes.values() if isinstance(yes, Vec) else yes
    n = no.values() if isinstance(no, Vec) else no
    nrows = cond.nrows if isinstance(cond, Vec) else len(np.asarray(cond))
    out = jnp.where(c != 0, y, n)
    return Vec(out.astype(jnp.float32), T_NUM, nrows)


def hist(vec: Vec, breaks: int = 20) -> Tuple[np.ndarray, np.ndarray]:
    """Histogram counts — AstHist analog (device bucketize + segment-sum)."""
    r = vec.rollups()
    lo, hi = r.vmin, r.vmax
    if not np.isfinite(lo) or not np.isfinite(hi) or hi <= lo:
        return np.zeros(breaks), np.linspace(0, 1, breaks + 1)
    edges = np.linspace(lo, hi, breaks + 1)
    x = vec.numeric_data()
    idx = jnp.clip(((x - lo) / (hi - lo) * breaks).astype(jnp.int32),
                   0, breaks - 1)
    valid = vec.valid_mask() & ~jnp.isnan(x)
    gid = jnp.where(valid, idx, breaks)
    counts = np.asarray(jax.ops.segment_sum(
        jnp.ones_like(x), gid, num_segments=breaks + 1))[:breaks]
    return counts, edges


def interaction(frame: Frame, factors: Sequence[str], pairwise: bool = True,
                max_factors: int = 100, min_occurrence: int = 1) -> Frame:
    """Categorical interaction columns — hex/Interaction analog.

    ``pairwise``: one column per factor pair; otherwise a single column
    over the full tuple.  Levels rank by frequency; beyond ``max_factors``
    (or under ``min_occurrence``) they collapse into "other".
    """
    from itertools import combinations
    factors = list(factors)
    for f in factors:
        if frame.vec(f).type != T_CAT:
            raise ValueError(f"interaction factor {f!r} must be categorical")
    if pairwise and len(factors) >= 2:
        groups = list(combinations(factors, 2))
    else:
        groups = [tuple(factors)]
    out = frame
    for grp in groups:
        labels = None
        for f in grp:
            v = frame.vec(f)
            dec = v.decoded()
            part = np.asarray(["NA" if x is None else str(x) for x in dec],
                              dtype=object)
            labels = part if labels is None else \
                np.asarray([a + "_" + b for a, b in zip(labels, part)],
                           dtype=object)
        uniq, counts = np.unique(labels, return_counts=True)
        order = np.argsort(-counts)
        keep = [u for u, c in zip(uniq[order], counts[order])
                if c >= min_occurrence][:max_factors]
        keepset = set(keep)
        col = np.asarray([x if x in keepset else "other" for x in labels],
                         dtype=object)
        out = out.with_vec("_".join(grp), Vec.from_numpy(col, T_CAT))
    return out


def impute(frame: Frame, column: str, method: str = "mean",
           combine_method: str = "interpolate") -> Frame:
    """Fill a column's NAs in place of a new frame — AstImpute analog.

    ``method``: mean | median | mode.  Numeric columns use mean/median;
    categorical use mode (most frequent level).
    """
    v = frame.vec(column)
    if method not in ("mean", "median", "mode"):
        raise ValueError(f"impute method {method!r}: mean | median | mode")
    if v.type != T_CAT and method == "mode":
        raise ValueError("impute method='mode' is for categorical columns")
    if v.type == T_CAT:
        t = table(v)
        if not t:
            return frame
        mode_lbl = max(t, key=t.get)
        code = (v.domain or []).index(mode_lbl)
        data = jnp.where(v.data < 0, code, v.data)
        newv = Vec(data, T_CAT, v.nrows, domain=v.domain)
        return _impute_lin(frame.with_vec(column, newv), frame,
                           column, method, combine_method)
    qmethod = {"interpolate": "linear", "lo": "lower",
               "hi": "higher", "low": "lower", "high": "higher",
               "average": "linear"}.get(combine_method, "linear")
    if v.type == T_TIME:
        # fill in the EXACT host ms payload and rebuild (keeps time_base)
        host = np.array(v.to_numpy(), copy=True)
        finite = np.isfinite(host)
        if not finite.any():
            return frame
        fill = float(np.nanquantile(host, 0.5, method=qmethod)) \
            if method == "median" else float(host[finite].mean())
        host[~finite] = fill
        return _impute_lin(frame.with_vec(column, Vec.from_numpy(host, T_TIME)),
                           frame, column, method, combine_method)
    if method == "median":
        x = v.to_numpy()
        fill = float(np.nanquantile(x, 0.5, method=qmethod)) \
            if np.isfinite(x).any() else 0.0
    else:
        fill = v.mean()
    x = v.numeric_data()     # a mean or a median need not be whole: float32
    data = jnp.where(jnp.isnan(x), jnp.float32(fill), x)
    return _impute_lin(frame.with_vec(column, Vec(data, v.type, v.nrows)),
                       frame, column, method, combine_method)


def _impute_lin(out: Frame, base: Frame, column: str, method: str,
                combine_method: str) -> Frame:
    return lineage.derive(out, base, {"op": "impute", "column": column,
                                      "method": method,
                                      "combine_method": combine_method})


def cut(vec: Vec, breaks: Sequence[float],
        labels: Optional[Sequence[str]] = None,
        include_lowest: bool = False, right: bool = True) -> Vec:
    """Numeric -> categorical by interval — AstCut analog."""
    edges = jnp.asarray(list(breaks), jnp.float32)
    x = vec.numeric_data()
    idx = jnp.searchsorted(edges, x, side="left" if right else "right") - 1
    nb = len(breaks) - 1
    if include_lowest:
        idx = jnp.where(x == edges[0], 0, idx)
    bad = jnp.isnan(x) | (idx < 0) | (idx >= nb)
    codes = jnp.where(bad, -1, idx).astype(jnp.int32)
    if labels is None:
        b = list(breaks)
        if right:
            lb0 = "[" if include_lowest else "("
            labels = [f"{lb0 if i == 0 else '('}{b[i]},{b[i+1]}]"
                      for i in range(nb)]
        else:
            labels = [f"[{b[i]},{b[i+1]})" for i in range(nb)]
    return Vec(codes, T_CAT, vec.nrows, domain=list(labels))


def scale(frame: Frame, center: bool = True,
          scale_: bool = True) -> Frame:
    """Standardize numeric columns — AstScale analog (device pass)."""
    vecs = []
    for v in frame.vecs:
        if v.type == T_NUM:
            r = v.rollups()
            mu = r.mean if center else 0.0
            sd = r.sigma if (scale_ and r.sigma and r.sigma > 0) else 1.0
            vecs.append(Vec((v.numeric_data() - mu) / sd, T_NUM, v.nrows))
        else:
            vecs.append(v)
    return lineage.derive(Frame(frame.names, vecs), frame,
                          {"op": "scale", "center": bool(center),
                           "scale": bool(scale_)})


# ---------------------------------------------------------------- group-by
_AGGS = ("count", "sum", "mean", "min", "max", "var", "sd")


def group_by(frame: Frame, by: Union[str, Sequence[str]],
             aggs: Dict[str, Sequence[str]]) -> Frame:
    """Grouped aggregation — AstGroup analog, device segment-sums.

    ``aggs``: {column: [agg, ...]} with aggs from count/sum/mean/min/max/
    var/sd.  Group ids come from a device lexicographic dense-rank
    (``jit_dense_rank``, keys in their own dtype); every
    aggregate is a ``segment_sum``/``segment_min``/``segment_max`` with the
    rank as segment id (O(N) HBM, no [N, G] one-hot).  Rows with NA in any
    key column are dropped, mirroring AstGroup's default NA handling.
    """
    by = [by] if isinstance(by, str) else list(by)
    for col, fns in aggs.items():
        for fn in fns:
            if fn not in _AGGS:
                raise ValueError(f"unknown agg {fn!r} (have {_AGGS})")
    keys = tuple(frame.vec(c).data for c in by)
    kinds = tuple(dev.column_kind(frame.vec(c)) for c in by)
    # rows with an NA in any key take the overflow rank G (AstGroup drops
    # them); a partial-NA tuple consumes no rank below it
    gid, G = dev.dense_rank(keys, np.int32(frame.nrows), kinds=kinds)
    G = int(G)
    if G <= 0:
        return Frame.from_numpy(
            {**{n: np.array([], object) for n in by},
             **{f"{fn}_{c}": np.array([]) for c, fns in aggs.items()
                for fn in fns}})
    nseg = G + 1

    # one representative row per group, for key decode
    rep = jax.ops.segment_max(jnp.arange(frame.padded_rows, dtype=jnp.int32),
                              gid, num_segments=nseg)[:G]
    out_cols: Dict[str, np.ndarray] = {}
    types: Dict[str, str] = {}
    domains: Dict[str, Sequence[str]] = {}
    for name in by:
        v = frame.vec(name)
        if v.type == T_CAT:
            codes = np.asarray(fetch(v.data[rep]))
            out_cols[name] = codes.astype(np.int32)
            types[name] = T_CAT
            domains[name] = v.domain or []
        elif v.is_exact_int:
            out_cols[name] = exact_int_host(np.asarray(fetch(v.data[rep])))
        else:
            out_cols[name] = np.asarray(fetch(v.data[rep]), np.float64)

    counts = None
    for col, fns in aggs.items():
        x = frame.vec(col).numeric_data()
        ok = (~jnp.isnan(x)).astype(jnp.float32)
        xz = jnp.nan_to_num(x)
        s1 = jax.ops.segment_sum(xz * ok, gid, num_segments=nseg)
        n = jax.ops.segment_sum(ok, gid, num_segments=nseg)
        mean = s1 / jnp.maximum(n, 1e-30)
        n_h = np.asarray(n, np.float64)[:G]
        s1_h = np.asarray(s1, np.float64)[:G]
        counts = n_h if counts is None else counts
        if any(f in ("min", "max") for f in fns):
            big = jnp.float32(3.4e38)
            mn = np.asarray(jax.ops.segment_min(
                jnp.where(jnp.isnan(x), big, x), gid,
                num_segments=nseg))[:G]
            mx = np.asarray(jax.ops.segment_max(
                jnp.where(jnp.isnan(x), -big, x), gid,
                num_segments=nseg))[:G]
        if any(f in ("var", "sd") for f in fns):
            # residual pass: numerically stable vs (E[x^2] - E[x]^2) in f32
            resid = (xz - mean[gid]) * ok
            ss = np.asarray(jax.ops.segment_sum(
                resid * resid, gid, num_segments=nseg), np.float64)[:G]
        for fn in fns:
            key = f"{fn}_{col}"
            if fn == "count":
                out_cols[key] = n_h
            elif fn == "sum":
                out_cols[key] = s1_h
            elif fn == "mean":
                out_cols[key] = s1_h / np.maximum(n_h, 1e-300)
            elif fn == "min":
                out_cols[key] = mn
            elif fn == "max":
                out_cols[key] = mx
            else:
                var = ss / np.maximum(n_h - 1, 1e-300)
                out_cols[key] = np.sqrt(var) if fn == "sd" else var
    return Frame.from_numpy(out_cols, types=types, domains=domains)


# -------------------------------------------------------------------- merge
def _na_vec(template: Vec, n: int) -> Vec:
    """All-NA vec of the template's type (outer-join fill)."""
    if template.type == T_CAT:
        return Vec.from_numpy(np.full(n, -1, np.int32), T_CAT,
                              domain=template.domain)
    if template.data is None:
        return Vec(None, template.type, n,
                   host_data=np.array([None] * n, dtype=object))
    if template.type == T_TIME:
        return Vec.from_numpy(np.full(n, np.nan), T_TIME)
    return Vec.from_numpy(np.full(n, np.nan), template.type)


# a join's output is refused past this many rows: ``expand_counts`` doubles
# slot numbers in int32, and no column of such a length fits a chip
_MERGE_MAX_ROWS = 1 << 30


def _merge_keys(left: Frame, right: Frame, by: List[str]):
    """Both sides' keys as ``jit_merge_match`` takes them: its arguments
    (keys, remaps and row counts of both sides) and the static kinds.
    A categorical key whose domains differ gets, per side, a small table
    from its codes onto the union of the two domains (built on the host,
    applied on the device); an exact-integer key meeting a float32 one
    makes the float32 side compare as integers."""
    lkeys, rkeys, lkinds, rkinds, lremaps, rremaps = [], [], [], [], [], []
    for name in by:
        lv, rv = left.vec(name), right.vec(name)
        if (lv.data is None) or (rv.data is None):
            raise TypeError(f"merge key {name!r} is a string column; "
                            "convert to categorical first")
        if (lv.type == T_CAT) != (rv.type == T_CAT):
            raise TypeError(f"merge key {name!r} has mismatched types")
        lk, rk = dev.column_kind(lv), dev.column_kind(rv)
        lt = rt = None
        if lk == dev.CAT and (lv.domain or []) != (rv.domain or []):
            shared: Dict[str, int] = {}
            for lbl in (lv.domain or []) + (rv.domain or []):
                shared.setdefault(lbl, len(shared))
            lt, rt = (jnp.asarray(np.array(
                [shared[lbl] for lbl in (v.domain or [])] or [0], np.int32))
                for v in (lv, rv))
        elif {lk, rk} == {dev.INT, dev.F32}:
            lk, rk = (dev.F32_AS_INT if k == dev.F32 else k for k in (lk, rk))
        lkeys.append(lv.data), rkeys.append(rv.data)
        lkinds.append(lk), rkinds.append(rk)
        lremaps.append(lt), rremaps.append(rt)
    return (tuple(lkeys), tuple(rkeys), tuple(lremaps), tuple(rremaps),
            np.int32(left.nrows), np.int32(right.nrows)), \
        {"lkinds": tuple(lkinds), "rkinds": tuple(rkinds)}


def _unmatched_right(left: Frame, right: Frame, by: List[str]) -> Frame:
    """Right rows whose key (no NA in it) matches NO left row, in right-row
    order: the match program with the sides exchanged."""
    args, kinds = _merge_keys(right, left, by)
    cnt = dev.merge_match(*args, **kinds, how="inner")[0]
    return filter_rows(right, Vec((cnt == 0).astype(jnp.float32), T_NUM,
                                  right.nrows))


def merge(left: Frame, right: Frame, by: Union[str, Sequence[str]],
          how: str = "inner") -> Frame:
    """Join — AstMerge / BinaryMerge analog, a device sort-merge.

    Single- or multi-key equi-join on keys compared in their own dtype
    (exact integers and categorical levels exactly, float32 as float32).
    Semantics: NA keys never match; the output is in left-row order;
    a left row's several matches are adjacent, in right-row order;
    many-to-many keys are expanded (every pair).  ``left`` keeps every left
    row (NA in the right columns where nothing matched); ``right`` is the
    left join from the other side (so in right-row order) with the columns
    laid out as usual; ``outer`` is the left join followed by the right rows
    that matched nothing (those with an NA key are dropped), in right-row
    order.

    Two device programs around the one host sync that the output's row count
    needs: ``jit_merge_match`` and ``jit_merge_gather`` (device.py).  The
    gather runs at the coarse ``dev.merge_padded_rows(m, ...)``;
    ``jit_merge_trim`` cuts its columns to ``pad_rows(m)``, like every other
    column of ``m`` rows.
    """
    by = [by] if isinstance(by, str) else list(by)
    if how == "right":
        # all.y: a left join from the other side, columns re-laid out to
        # the conventional (left cols, right-only cols) order
        out = merge(right, left, by, how="left")
        lcols = [n for n in left.names if n not in by]
        rcols = [n for n in right.names if n not in by]
        return out[by + [c for c in lcols if c in out.names]
                   + [c for c in rcols if c in out.names]]
    if how == "outer":
        li = merge(left, right, by, how="left")
        extra = _unmatched_right(left, right, by)
        if extra.nrows == 0:
            return li
        # align to the left-join layout, NA-filling left-only columns with
        # TYPE-correct NA vecs (cat -> -1 codes with the left domain)
        cols = li.names
        aligned = []
        for c in cols:
            if c in extra.names:
                aligned.append(extra.vec(c))
            else:
                aligned.append(_na_vec(left.vec(c), extra.nrows))
        return rbind(li, Frame(cols, aligned))
    if how not in ("inner", "left"):
        raise ValueError("merge supports how='inner'|'left'|'right'|'outer'")
    cl = cluster()
    with obs.trace("rapids.merge", how=how, left_rows=left.nrows,
                   right_rows=right.nrows):
        with obs.span("merge.keys"):
            rsub = right[[n for n in right.names if n not in by]]
            lcols, lfills = dev.device_columns(left)
            rcols, rfills = dev.device_columns(rsub)
            args, kinds = _merge_keys(left, right, by)
        with obs.span("merge.match"):
            cnt, start, srow, total, total_f = dev.merge_match(
                *args, **kinds, how=how)
        with obs.span("merge.count"):
            # the one host sync: the output's length is a shape
            m, m_f = (a.item() for a in jax.device_get((total, total_f)))
            obs.inc("rapids_host_syncs_total", op="merge")
            obs.inc("transfer_bytes_total", total.nbytes + total_f.nbytes, dir="d2h")
            if m_f >= _MERGE_MAX_ROWS:
                raise ValueError(
                    f"merge: the output would have about {m_f:.3g} rows; a "
                    f"join is materialised whole and stops at {_MERGE_MAX_ROWS}")
        with obs.span("merge.gather"):
            lout, rout, li, ri = dev.merge_gather(
                cnt, start, srow, lcols, rcols, np.int32(left.nrows),
                np.int32(m), how=how, lfills=lfills, rfills=rfills,
                p_out=dev.merge_padded_rows(m, left.padded_rows),
                sharding=cl.row_sharding)
            dev.note_gathers("merge", dev.merge_left_carried(
                start, cnt, lcols, how), (srow,), rcols)
            lout, rout = dev.merge_trim(
                (lout, rout), p=cl.pad_rows(m), sharding=cl.row_sharding)
            @functools.lru_cache(None)
            def fetched():          # once, and only if a host-only column asks
                return [a[:m] for a in dev.note_host_index("merge", li, ri)]

            def host_index(side):
                return fetched()[side], fetched()[side] < 0
            out = dev.wrap_rows(left, lout, m, lambda: host_index(0))
            if rsub.ncols:
                out = cbind(out, dev.wrap_rows(rsub, rout, m, lambda: host_index(1)))
        obs.inc("rapids_rows_total", left.nrows, op="merge", side="in")
        obs.inc("rapids_rows_total", right.nrows, op="merge", side="in")
        obs.inc("rapids_rows_total", m, op="merge", side="out")
    return out


def var(frame: Frame, cols: Optional[Sequence[str]] = None,
        use: str = "complete.obs") -> Dict[str, np.ndarray]:
    """Covariance matrix — h2o.var / CovarianceTask analog.

    ``use``: "complete.obs" drops rows with any NA across the selected
    columns (the reference's default for frames); "everything"
    propagates NaN like R.  Device path: masked mean-centering, then
    one X^T X matmul (MXU) over the row-sharded matrix.
    """
    cols = list(cols) if cols is not None else \
        [n for n in frame.names if frame.vec(n).is_numeric]
    M = frame.matrix(cols)                     # [padded, F]
    # categorical codes use -1 as the NA sentinel; align with numeric NaN
    is_cat = np.array([frame.vec(c).type == T_CAT for c in cols])
    if is_cat.any():
        M = jnp.where(jnp.asarray(is_cat)[None, :] & (M == -1), jnp.nan, M)
    valid = frame.valid_mask()
    finite = jnp.isfinite(M)
    if use == "complete.obs":
        row_ok = valid & finite.all(axis=1)
    elif use == "everything":
        row_ok = valid
    else:
        raise ValueError(f"unknown use={use!r}")
    n = float(row_ok.sum())
    if n < 2:                                  # R/h2o return NA here
        return {"columns": cols,
                "matrix": np.full((len(cols), len(cols)), np.nan)}
    Mz = jnp.where(row_ok[:, None], jnp.where(finite, M, jnp.nan), 0.0)
    # complete.obs rows carry no NaN; "everything" lets NaN propagate
    # per column pair, matching R's semantics
    mean = Mz.sum(axis=0) / n
    D = (Mz - mean) * row_ok.astype(M.dtype)[:, None]
    C = jnp.einsum("rf,rg->fg", D, D,
                   precision=jax.lax.Precision.HIGHEST) / (n - 1.0)
    return {"columns": cols, "matrix": np.asarray(C, dtype=np.float64)}


def cor(frame: Frame, cols: Optional[Sequence[str]] = None,
        use: str = "complete.obs") -> Dict[str, np.ndarray]:
    """Pearson correlation matrix — h2o.cor analog (from ``var``)."""
    v = var(frame, cols, use=use)
    C = v["matrix"]
    sd = np.sqrt(np.diag(C))
    with np.errstate(invalid="ignore", divide="ignore"):
        R = np.clip(C / np.outer(sd, sd), -1.0, 1.0)
    return {"columns": v["columns"], "matrix": R}
