"""DataInfo: the shared featurization layer feeding every algorithm.

Reference: ``hex/DataInfo.java`` (h2o-algos, ~1.5k LoC) — converts a Frame
into the algorithm's numeric view: categorical one-hot/enum expansion,
standardization, NA imputation, interaction terms; shared by GLM/DL/GAM/
CoxPH/KMeans.  Test-time adaptation (``Model.adaptTestForTrain``,
hex/Model.java:1683) aligns incoming frames to the training layout.

TPU-native redesign: featurization is a single fused XLA program per frame —
categorical codes expand to one-hot via a broadcast compare (an MXU-friendly
dense [rows, features] block), numerics are imputed/standardized in the same
pass, and the result is a row-sharded float32 matrix.  The fitted state
(domains, means, sigmas, layout) is a small host-side dataclass that also
performs test adaptation, guaranteeing train/test layout agreement.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from ..frame.frame import Frame
from ..frame.vec import Vec, T_CAT, T_NUM, T_TIME
from ..runtime.cluster import ROW_AXES, cluster


MEAN_IMPUTATION = "mean_imputation"
SKIP = "skip"


class CodedDesign(NamedTuple):
    """The design in code form: what ``make_matrix`` expands, unexpanded.

    ``num``: [padded, P_num] float32, the numeric columns imputed and
    standardised as ``make_matrix`` does it (a spline's column imputed
    alone: its basis is built from the raw value).  ``codes``: [padded,
    P_cat] int32, per categorical the column WITHIN its one-hot block that
    the row lights (its NA column is the block's last), or -1 where the row
    lights none (the dropped first level, a code past the domain).
    ``expand_coded(layout, num, codes, DataInfo.spline_tables())`` gives
    ``make_matrix``'s rows."""
    num: jax.Array
    codes: jax.Array


def spline_rows(x: jax.Array, knots: jax.Array, G: jax.Array,
                FG: jax.Array) -> jax.Array:
    """[rows, S M] basis rows of S cubic regression splines of a run, smooth
    by smooth, from the raw values ``x`` [rows, S].  ``knots`` [S, K] are
    each smooth's ascending knots; a row's value at knot interval j (clipped
    to the knots' range, as a natural spline extends no further) is
    ``a_m G[j] + a_p G[j+1] + c_m FG[j] + c_p FG[j+1]`` with the cardinal
    spline's linear and cubic weights, so ``G`` [S, K, M] maps the knots'
    values to the columns and ``FG`` [S, K, M] their second derivatives
    (``F G``).  ``G`` the identity and ``FG`` ``F`` give the cardinal basis
    itself.  Every value comes by selects over the K knots and float32
    products: no gather, and no product on the MXU.

    Every step works on [rows, S M] arrays, column s M + m of smooth s,
    with a smooth's raw value and knots repeated M times: a [rows, S, M]
    array's minor dimension of M (padded to a tile of 8 rows) costs a
    relayout copy of each such array on its way to [rows, S M] (four of
    them took 1.75 of a GAM fit's 8.4 s on a v5e)."""
    S, K = knots.shape
    M = G.shape[2]
    xc = jnp.repeat(x, M, axis=1)
    kw = jnp.repeat(knots, M, axis=0).T                 # [K, S M]
    xc = jnp.clip(xc, kw[0], kw[-1])
    lo = jnp.broadcast_to(kw[0], xc.shape)
    hi = jnp.broadcast_to(kw[1], xc.shape)
    past = []
    for i in range(1, K - 1):
        p = xc >= kw[i]
        lo = jnp.where(p, kw[i], lo)
        hi = jnp.where(p, kw[i + 1], hi)
        past.append(p)
    h = hi - lo
    am, ap = (hi - xc) / h, (xc - lo) / h
    cm = ((hi - xc) ** 3 / h - h * (hi - xc)) / 6.0
    cp = ((xc - lo) ** 3 / h - h * (xc - lo)) / 6.0

    def at(table, shift):        # table[s, j + shift, m] for every row
        rows = table.transpose(1, 0, 2).reshape(K, S * M)
        got = jnp.broadcast_to(rows[shift], xc.shape)
        for i, p in enumerate(past, 1):
            got = jnp.where(p, rows[i + shift], got)
        return got

    return (am * at(G, 0) + ap * at(G, 1) + cm * at(FG, 0)
            + cp * at(FG, 1))


def spline_run(num, i_num, tables, i_spl):
    """The basis rows of the spline run whose tables are ``tables[i_spl]``
    and whose raw columns start at ``num``'s column ``i_num``, and the
    number of those columns."""
    knots, G, FG = tables[i_spl]
    S = knots.shape[0]
    return spline_rows(num[:, i_num:i_num + S], knots, G, FG), S


def expand_coded(layout: Tuple[Tuple[str, int], ...], num: jax.Array,
                 codes: jax.Array, tables: tuple = ()) -> jax.Array:
    """[rows, nfeatures] dense rows of a code-form design, in
    ``make_matrix``'s column order.  ``layout`` is ``DataInfo.coded_layout``
    (static, hashable), so this traces inside any jitted program: a
    minibatch or a block of rows is expanded where it is used, the frame
    never.  A layout of some of the runs, in their order, expands those
    alone (the ``"cat"`` runs take the codes' columns in turn, the ``"num"``
    and ``"spline"`` runs the numerics', each ``"spline"`` run the next of
    ``tables``, ``DataInfo.spline_tables()``: operands of the program and
    no constants of its trace)."""
    cols, i_num, i_cat, i_spl = [], 0, 0, 0
    for kind, width in layout:
        if kind == "num":
            cols.append(num[:, i_num:i_num + width])
            i_num += width
        elif kind == "spline":
            rows, used = spline_run(num, i_num, tables, i_spl)
            cols.append(rows)
            i_num, i_spl = i_num + used, i_spl + 1
        elif kind == "cat":
            block = jnp.arange(width, dtype=jnp.int32)
            cols.append((codes[:, i_cat, None] == block[None, :])
                        .astype(jnp.float32))
            i_cat += 1
        else:                           # the intercept's column of ones
            cols.append(jnp.ones((num.shape[0], width), jnp.float32))
    if not cols:
        return jnp.zeros((num.shape[0], 0), jnp.float32)
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)


def coded_matvec(layout: Tuple[Tuple[str, int], ...], num: jax.Array,
                 codes: jax.Array, beta: jax.Array,
                 tables: tuple = ()) -> jax.Array:
    """``expand_coded(layout, num, codes, tables) @ beta`` [rows], run by
    run and with no expansion of a one-hot block: its product is the
    coefficient its code selects (a compare, a select and an add a column,
    which fuse into one pass over the codes; every float32 product exact).
    A spline run's rows are built and multiplied elementwise."""
    out, at, i_num, i_cat, i_spl = 0.0, 0, 0, 0, 0
    for kind, width in layout:
        b = beta[at:at + width]
        if kind == "num":
            out = out + jnp.sum(num[:, i_num:i_num + width] * b, axis=1)
            i_num += width
        elif kind == "spline":
            rows, used = spline_run(num, i_num, tables, i_spl)
            out = out + jnp.sum(rows * b, axis=1)
            i_num, i_spl = i_num + used, i_spl + 1
        elif kind == "cat":
            lit = codes[:, i_cat, None] == jnp.arange(width, dtype=jnp.int32)
            out = out + jnp.sum(jnp.where(lit, b, 0.0), axis=1)
            i_cat += 1
        else:
            out = out + jnp.sum(b)
        at += width
    return out


def coded_rmatvec(layout: Tuple[Tuple[str, int], ...], num: jax.Array,
                  codes: jax.Array, v: jax.Array,
                  tables: tuple = ()) -> jax.Array:
    """``expand_coded(layout, num, codes, tables).T @ v`` [nfeatures] for a
    row vector ``v``, run by run, as ``coded_matvec``."""
    parts, i_num, i_cat, i_spl = [], 0, 0, 0
    for kind, width in layout:
        if kind == "num":
            parts.append(jnp.sum(num[:, i_num:i_num + width] * v[:, None],
                                 axis=0))
            i_num += width
        elif kind == "spline":
            rows, used = spline_run(num, i_num, tables, i_spl)
            parts.append(jnp.sum(rows * v[:, None], axis=0))
            i_num, i_spl = i_num + used, i_spl + 1
        elif kind == "cat":
            lit = codes[:, i_cat, None] == jnp.arange(width, dtype=jnp.int32)
            parts.append(jnp.sum(jnp.where(lit, v[:, None], 0.0), axis=0))
            i_cat += 1
        else:
            parts.append(jnp.full((width,), jnp.sum(v)))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


# ------------------------------------------------------------- row blocks
#
# A client of the code form expands a BLOCK of rows where it uses it.  What
# follows is what every such client needs: how many rows a block may hold
# on this device, and the walk over a row-shard's blocks, for a result per
# row (scoring) and for sums over the rows (a Gram).  Both run on a row
# shard's own rows (``over_row_shards``, or a ``shard_map`` of the
# caller's).

@functools.cache
def device_memory_bytes() -> int:
    """The first local device's memory (asked once a process); where the
    backend reports none (the CPU), a 4 GiB device is assumed."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit") or 4 << 30)


def block_rows(row_bytes: int, rows: int, share: int = 16) -> int:
    """Rows one block holds: as many as keep ``row_bytes`` a row (the
    expanded row and what is computed from it) inside one ``share``-th of
    the device, a multiple of 1,024, so that a frame which fills the chip
    still has room for its blocks; never more than ``rows``."""
    budget = device_memory_bytes() // share
    block = max(budget // max(row_bytes, 1) // 1024, 1) * 1024
    return min(block, rows)


def over_row_shards(shard, in_specs, out_specs):
    """``shard`` applied to every row shard's part of its arguments: a
    ``shard_map`` over the row axes of the live mesh.  On a mesh of one row
    shard there is nothing to map and ``shard`` itself is returned, which
    spares a program that is traced anew on every call (GLM's path program)
    the lowering of the map, 10 ms of a 0.17 s fit on a v5e's host."""
    if cluster().n_row_shards == 1:
        return shard
    return shard_map(shard, mesh=cluster().mesh, in_specs=in_specs,
                     out_specs=out_specs)


def sum_over_row_shards(sums):
    """The shards' ``sums`` (a tree) added up, inside ``over_row_shards``."""
    if cluster().n_row_shards == 1:
        return sums
    return jax.lax.psum(sums, ROW_AXES)


def _varying_like(x: jax.Array, rows: jax.Array) -> jax.Array:
    """``x`` made to vary over the mesh axes ``rows`` varies over, so that
    it can be the carry of a scan whose body reads ``rows``: inside a
    ``shard_map`` the row axes, outside one nothing."""
    axes = tuple(a for a in ROW_AXES if a in jax.typeof(rows).vma)
    return jax.lax.pcast(x, axes, to="varying") if axes else x


def _block_starts(rows: int, block: int) -> jax.Array:
    """First rows of the blocks that cover ``rows`` rows, were none laid
    back: the walks below lay the last one back over the one before it
    (``min(start, rows - block)``), so that every block is whole."""
    return jnp.arange(-(-rows // block)) * block


def map_row_blocks(rows_of, block: int, *arrays: jax.Array):
    """``rows_of(*blocks)`` for every block of ``block`` rows of a shard's
    ``arrays``, as one array over all its rows.  ``rows_of`` returns one
    array whose leading axis is the block's rows."""
    rows = arrays[0].shape[0]
    if rows <= block:
        return rows_of(*arrays)

    def one(out, start):
        start = jnp.minimum(start, rows - block)
        got = rows_of(*(jax.lax.dynamic_slice_in_dim(a, start, block)
                        for a in arrays))
        return jax.lax.dynamic_update_slice_in_dim(out, got, start, 0), None

    like = jax.eval_shape(rows_of, *(a[:block] for a in arrays))
    out = _varying_like(jnp.zeros((rows,) + like.shape[1:], like.dtype),
                        arrays[0])
    return jax.lax.scan(one, out, _block_starts(rows, block))[0]


def sum_row_blocks(sums_of, block: int, w: jax.Array, *arrays: jax.Array):
    """The sum over a shard's blocks of ``sums_of(w_block, *blocks)``, a
    tree of arrays each of which is a sum over the block's rows in which a
    row of weight 0 counts for nothing.  The rows that the last block,
    laid back, shares with the one before it get weight 0 there, as the
    frame's padding has it from ``DataInfo.weights``."""
    rows = w.shape[0]
    if rows <= block:
        return sums_of(w, *arrays)

    def one(total, fresh):
        start = jnp.minimum(fresh, rows - block)
        wb = jax.lax.dynamic_slice_in_dim(w, start, block)
        wb = jnp.where(start + jnp.arange(block) >= fresh, wb, 0.0)
        got = sums_of(wb, *(jax.lax.dynamic_slice_in_dim(a, start, block)
                            for a in arrays))
        return jax.tree.map(jnp.add, total, got), None

    like = jax.eval_shape(sums_of, w[:block], *(a[:block] for a in arrays))
    zero = jax.tree.map(
        lambda l: _varying_like(jnp.zeros(l.shape, l.dtype), w), like)
    return jax.lax.scan(one, zero, _block_starts(rows, block))[0]


@dataclasses.dataclass
class ColumnSpec:
    name: str
    type: str                       # T_NUM / T_TIME / T_CAT
    domain: Optional[List[str]]     # cat labels (training-time)
    mean: float                     # imputation value / centering
    sigma: float                    # scaling (1.0 when not standardizing)
    time_base: float = 0.0
    offset: int = 0                 # first output column index
    width: int = 1                  # number of output columns
    # a categorical with a column for EVERY level of ``domain`` and none for
    # missing values, whatever ``use_all_factor_levels`` says: a group of
    # RuleFit's rules, whose codes its caller builds and never miss
    all_levels: bool = False
    # a numeric column that enters the design as a cubic regression spline's
    # ``width`` basis columns (GAM's cr smooth): (knots [K], G [K, width],
    # FG [K, width]) as ``spline_rows`` reads them; its values are imputed
    # by ``mean`` and never standardised (``sigma`` 1)
    spline: Optional[tuple] = None


def _same_spline_run(prev: Optional[ColumnSpec], s: ColumnSpec) -> bool:
    """Whether spline ``s`` joins the run of the spec before it: both are
    splines of one width."""
    return prev is not None and prev.spline is not None \
        and prev.width == s.width


def _numeric_values(vec: Vec, s: ColumnSpec) -> jax.Array:
    """A numeric column's device values, a time column moved onto the
    training frame's time base."""
    x = vec.values()
    if s.type == T_TIME and abs(vec.time_base - s.time_base) > 0:
        x = x + (vec.time_base - s.time_base) / 1000.0
    return x


def _standardized(specs, arrs, standardize: bool) -> jax.Array:
    """[C, padded] float32: a run of numeric columns imputed with their
    training means and, if asked, standardised — ONE batched block, since
    per-column eager ops cost a dispatch each.  A spline's column is
    imputed and left as it is."""
    X = jnp.stack(arrs, axis=0).astype(jnp.float32)
    means = jnp.asarray([s.mean for s in specs], jnp.float32)[:, None]
    X = jnp.where(jnp.isnan(X), means, X)
    if standardize:
        sigmas = jnp.asarray([s.sigma for s in specs], jnp.float32)[:, None]
        centres = means if all(s.spline is None for s in specs) else \
            jnp.asarray([0.0 if s.spline is not None else s.mean
                         for s in specs], jnp.float32)[:, None]
        X = (X - centres) / sigmas
    return X


@dataclasses.dataclass
class DataInfo:
    """Fitted featurization: layout + per-column adaptation state."""

    specs: List[ColumnSpec]
    response_column: Optional[str]
    response_domain: Optional[List[str]]
    weights_column: Optional[str]
    offset_column: Optional[str]
    standardize: bool
    use_all_factor_levels: bool
    missing_values_handling: str
    add_intercept: bool
    nfeatures: int
    response_mean: float = 0.0
    response_sigma: float = 1.0

    # ------------------------------------------------------------ properties
    @property
    def coef_names(self) -> List[str]:
        names = []
        for s in self.specs:
            if s.all_levels:
                names += [f"{s.name}.{lbl}" for lbl in s.domain]
            elif s.spline is not None:
                names += [f"{s.name}_gam{j}" for j in range(s.width)]
            elif s.type == T_CAT:
                lo = 0 if self.use_all_factor_levels else 1
                names += [f"{s.name}.{lbl}" for lbl in s.domain[lo:]]
                names.append(f"{s.name}.missing(NA)")
            else:
                names.append(s.name)
        if self.add_intercept:
            names.append("Intercept")
        return names

    @property
    def has_splines(self) -> bool:
        return any(s.spline is not None for s in self.specs)

    @property
    def nclasses(self) -> int:
        return len(self.response_domain) if self.response_domain else 1

    @property
    def is_classifier(self) -> bool:
        return self.response_domain is not None

    # -------------------------------------------------------------- fitting
    @staticmethod
    def fit(frame: Frame, response_column: Optional[str] = None,
            ignored_columns: Sequence[str] = (),
            weights_column: Optional[str] = None,
            offset_column: Optional[str] = None,
            standardize: bool = True,
            use_all_factor_levels: bool = False,
            missing_values_handling: str = MEAN_IMPUTATION,
            add_intercept: bool = True,
            force_classification: bool = False) -> "DataInfo":
        skip = set(ignored_columns) | {response_column, weights_column,
                                       offset_column, None}
        # one batched pass for every column's rollups — the per-column
        # lazy path costs a dispatch round trip per column (wide frames)
        frame.warm_rollups()
        specs: List[ColumnSpec] = []
        offset = 0
        for name, vec in zip(frame.names, frame.vecs):
            if name in skip or vec.data is None:   # str/uuid never featurized
                continue
            if vec.type == T_CAT:
                dom = list(vec.domain or [])
                lo = 0 if use_all_factor_levels else 1
                width = max(len(dom) - lo, 0) + 1          # +1 NA bucket
                specs.append(ColumnSpec(name, T_CAT, dom, 0.0, 1.0,
                                        offset=offset, width=width))
            else:
                r = vec.rollups()
                mean = r.mean if np.isfinite(r.mean) else 0.0
                sigma = r.sigma if (standardize and np.isfinite(r.sigma)
                                    and r.sigma > 0) else 1.0
                specs.append(ColumnSpec(name, vec.type, None, mean, sigma,
                                        time_base=vec.time_base,
                                        offset=offset, width=1))
            offset += specs[-1].width
        if not specs:
            raise ValueError("no usable feature columns")

        resp_domain = None
        rmean, rsigma = 0.0, 1.0
        if response_column is not None:
            rv = frame.vec(response_column)
            if rv.type == T_CAT:
                resp_domain = list(rv.domain or [])
            elif force_classification:
                vals = np.unique(rv.to_numpy())
                vals = vals[np.isfinite(vals)]
                resp_domain = [str(int(v)) if v == int(v) else str(v)
                               for v in vals]
            else:
                rr = rv.rollups()
                rmean = rr.mean if np.isfinite(rr.mean) else 0.0
                rsigma = rr.sigma if np.isfinite(rr.sigma) and rr.sigma > 0 else 1.0
        nfeat = offset + (1 if add_intercept else 0)
        return DataInfo(specs, response_column, resp_domain, weights_column,
                        offset_column, standardize, use_all_factor_levels,
                        missing_values_handling, add_intercept, nfeat,
                        response_mean=rmean, response_sigma=rsigma)

    def with_groups(self, groups: Sequence[Tuple[str, List[str]]],
                    keep_specs: bool = True) -> "DataInfo":
        """This layout with categoricals of all their levels in front
        (``ColumnSpec.all_levels``), one per ``(name, level names)`` of
        ``groups``, and this layout's columns after them unless
        ``keep_specs`` is False.  Its code form is the caller's: the groups'
        codes come first in ``CodedDesign.codes``, then ``make_coded``'s of
        this layout (RuleFit's rule design)."""
        specs, at = [], 0
        for name, levels in groups:
            specs.append(ColumnSpec(name, T_CAT, list(levels), 0.0, 1.0,
                                    offset=at, width=len(levels),
                                    all_levels=True))
            at += len(levels)
        if keep_specs:
            specs += [dataclasses.replace(s, offset=s.offset + at)
                      for s in self.specs]
            at = self.nfeatures - int(self.add_intercept) + at
        return dataclasses.replace(self, specs=specs,
                                   nfeatures=at + int(self.add_intercept))

    def with_splines(self, splines: Dict[str, tuple]) -> "DataInfo":
        """This layout with each numeric column named in ``splines`` entering
        as a cubic regression spline's basis: name -> (knots, G, FG), as
        ``ColumnSpec.spline`` holds them (GAM's cr smooths)."""
        specs, at = [], 0
        for s in self.specs:
            t = splines.get(s.name)
            if t is not None:
                s = dataclasses.replace(s, sigma=1.0, spline=t,
                                        width=int(t[1].shape[1]))
            specs.append(dataclasses.replace(s, offset=at))
            at += s.width
        return dataclasses.replace(self, specs=specs,
                                   nfeatures=at + int(self.add_intercept))

    def spline_groups(self) -> List[List[ColumnSpec]]:
        """The spline columns as ``coded_layout``'s ``"spline"`` runs have
        them: side by side and of one width."""
        groups: List[List[ColumnSpec]] = []
        for s, prev in zip(self.specs, [None] + self.specs[:-1]):
            if s.spline is not None:
                if _same_spline_run(prev, s):
                    groups[-1].append(s)
                else:
                    groups.append([s])
        return groups

    def spline_tables(self) -> tuple:
        """Per ``"spline"`` run of ``coded_layout``, its (knots [S, K],
        G [S, K, M], FG [S, K, M]) as float32 device arrays: the operands
        that ``expand_coded`` and the programs built on it take.  Empty for
        a layout with no spline."""
        return tuple(tuple(jnp.asarray(np.stack([s.spline[k] for s in g]),
                                       jnp.float32) for k in range(3))
                     for g in self.spline_groups())

    # ---------------------------------------------------------- application
    def make_matrix(self, frame: Frame, standardize: Optional[bool] = None) -> jax.Array:
        """[padded_rows, nfeatures] float32 design matrix, row-sharded.

        One fused XLA pass: numeric impute+standardize, categorical one-hot
        with NA bucket, optional intercept column.  Unseen test levels map to
        the NA bucket (the reference's adaptTestForTrain ``skipMissing`` /
        makeNA path, hex/Model.java:1683).

        Memoized in the Frame's ``_matrix_cache`` (so ``Frame.spill()``
        evicts it under HBM pressure like every other device view): repeated
        train/predict over the same Frame reuse one device matrix.  Runs of
        numeric columns are processed as ONE batched block — per-column
        eager ops cost a dispatch each, which adds up over hundreds of
        columns.
        """
        standardize = self.standardize if standardize is None else standardize
        if self.has_splines:
            return expand_coded(self.coded_layout(),
                                *self.make_coded(frame, standardize),
                                self.spline_tables())
        key = ("__design__", standardize, self._design_signature())
        hit = frame._matrix_cache.get(key)
        if hit is not None:
            return hit
        cl = cluster()
        cols = []          # list of [padded, k] blocks in spec order
        num_run: list = []

        def flush_numeric():
            if not num_run:
                return
            specs_r, arrs = zip(*num_run)
            num_run.clear()
            cols.append(_standardized(specs_r, arrs, standardize).T)

        for s in self.specs:
            vec = frame.vec(s.name)
            if s.type == T_CAT:
                flush_numeric()
                codes = self._aligned_codes(vec, s)
                lo = 0 if self.use_all_factor_levels else 1
                width = s.width - 1
                levels = jnp.arange(lo, lo + width, dtype=jnp.int32)
                onehot = (codes[:, None] == levels[None, :]).astype(jnp.float32)
                na = (codes < 0).astype(jnp.float32)[:, None]
                cols.append(jnp.concatenate([onehot, na], axis=1))
            else:
                num_run.append((s, _numeric_values(vec, s)))
        flush_numeric()
        if self.add_intercept:
            cols.append(jnp.ones((frame.padded_rows, 1), jnp.float32))
        mat = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
        from ..runtime.cluster import put_sharded
        mat = put_sharded(mat, cl.matrix_sharding)
        frame._matrix_cache[key] = mat
        return mat

    def coded_layout(self) -> Tuple[Tuple[str, int], ...]:
        """The expanded layout as runs in column order: ``("num", k)`` for
        k numeric columns side by side, ``("cat", width)`` for one
        categorical's block (NA column included), ``("spline", S M)`` for S
        splines of M columns each side by side (``spline_tables`` holds a
        run's tables, which give S and M), ``("one", 1)`` for the
        intercept.  The widths sum to ``nfeatures``."""
        runs: List[Tuple[str, int]] = []
        for s, prev in zip(self.specs, [None] + self.specs[:-1]):
            if s.spline is not None:
                if runs[-1:] and _same_spline_run(prev, s):
                    runs[-1] = ("spline", runs[-1][1] + s.width)
                else:
                    runs.append(("spline", s.width))
            elif s.type == T_CAT:
                runs.append(("cat", s.width))
            elif runs and runs[-1][0] == "num":
                runs[-1] = ("num", runs[-1][1] + 1)
            else:
                runs.append(("num", 1))
        if self.add_intercept:
            runs.append(("one", 1))
        return tuple(runs)

    def make_coded(self, frame: Frame,
                   standardize: Optional[bool] = None) -> CodedDesign:
        """The design in code form (``CodedDesign``), row-sharded.

        For clients whose first operation on the design is a product with a
        weight matrix: a categorical of L levels costs them 4 bytes a row
        here, not 4 L.  Layout, standardisation, NA and unseen-level
        handling are ``make_matrix``'s: ``expand_coded(self.coded_layout(),
        *self.make_coded(frame))`` equals ``self.make_matrix(frame)``.
        Memoized in the Frame's ``_matrix_cache`` under one key per array,
        so ``Frame.spill()`` evicts and counts both."""
        standardize = self.standardize if standardize is None else standardize
        keys = [("__coded__", part, standardize, self._design_signature())
                for part in ("num", "codes")]
        hit = [frame._matrix_cache.get(k) for k in keys]
        if hit[0] is not None and hit[1] is not None:
            return CodedDesign(*hit)
        num_specs = [s for s in self.specs if s.type != T_CAT]
        cat_specs = [s for s in self.specs if s.type == T_CAT]
        padded = frame.padded_rows
        if num_specs:
            arrs = [_numeric_values(frame.vec(s.name), s) for s in num_specs]
            num = _standardized(num_specs, arrs, standardize).T
        else:
            num = jnp.zeros((padded, 0), jnp.float32)
        lo = 0 if self.use_all_factor_levels else 1
        local = []
        for s in cat_specs:
            c = self._aligned_codes(frame.vec(s.name), s)
            lit = (c >= lo) & (c < lo + s.width - 1)
            local.append(jnp.where(c < 0, s.width - 1,
                                   jnp.where(lit, c - lo, -1)))
        codes = (jnp.stack(local, axis=1).astype(jnp.int32) if local
                 else jnp.zeros((padded, 0), jnp.int32))
        from ..runtime.cluster import put_sharded
        sharding = cluster().matrix_sharding
        design = CodedDesign(put_sharded(num, sharding),
                             put_sharded(codes, sharding))
        for k, a in zip(keys, design):
            frame._matrix_cache[k] = a
        return design

    def _design_signature(self) -> tuple:
        """Memo key for the design layout, computed once per DataInfo.
        The key is the signature TUPLE itself (hashable), not its hash():
        a 64-bit hash collision between two layouts over the same Frame
        would silently return the wrong cached design matrix."""
        sig = self.__dict__.get("_design_sig")
        if sig is None:
            sig = (
                tuple((s.name, s.type, tuple(s.domain or ()), s.mean,
                       s.sigma, s.time_base, s.offset, s.width,
                       s.spline is not None)
                      for s in self.specs),
                self.use_all_factor_levels, self.add_intercept,
                self.missing_values_handling)
            object.__setattr__(self, "_design_sig", sig)
        return sig

    def _aligned_codes(self, vec: Vec, s: ColumnSpec) -> jax.Array:
        """Map a (possibly differently-coded) cat Vec onto training codes."""
        if vec.type != T_CAT:
            # numeric column where a cat was expected: treat values as codes
            return jnp.where(vec.isna(), -1,
                             vec.data).astype(jnp.int32)
        if vec.domain == s.domain:
            return vec.data
        remap = np.full(max(len(vec.domain or []), 1), -1, dtype=np.int32)
        lookup = {lbl: i for i, lbl in enumerate(s.domain)}
        for i, lbl in enumerate(vec.domain or []):
            remap[i] = lookup.get(lbl, -1)
        remap_dev = jnp.asarray(remap)
        codes = vec.data
        return jnp.where(codes < 0, -1, remap_dev[jnp.clip(codes, 0, None)])

    def response(self, frame: Frame) -> jax.Array:
        """Response as float32 [padded]: cat codes for classifiers else values.

        Memoized per frame (spill-evicted): the eager op chain costs a
        dispatch per op."""
        key = ("__response__", self.response_column,
               tuple(self.response_domain) if self.response_domain is not None
               else None, self._design_signature())
        hit = frame._matrix_cache.get(key)
        if hit is not None:
            return hit
        out = self._response_uncached(frame)
        frame._matrix_cache[key] = out
        return out

    def _response_uncached(self, frame: Frame) -> jax.Array:
        rv = frame.vec(self.response_column)
        if self.response_domain is not None:
            if rv.type == T_CAT:
                spec = ColumnSpec(self.response_column, T_CAT,
                                  self.response_domain, 0.0, 1.0)
                return self._aligned_codes(rv, spec).astype(jnp.float32)
            # numeric response trained as classification (force_classification)
            vals = np.array([float(v) for v in self.response_domain],
                            dtype=np.float32)
            vals_dev = jnp.asarray(vals)
            x = rv.numeric_data()
            code = jnp.argmin(jnp.abs(x[:, None] - vals_dev[None, :]), axis=1)
            exact = jnp.any(x[:, None] == vals_dev[None, :], axis=1)
            return jnp.where(exact, code, -1).astype(jnp.float32)
        return rv.numeric_data()

    def weights(self, frame: Frame) -> jax.Array:
        """Row weights x validity mask — 0 on padding and (optionally) NA rows.

        Memoized per frame (spill-evicted), like ``response``."""
        key = ("__weights__", self.weights_column, self.response_column,
               tuple(self.response_domain) if self.response_domain is not None
               else None, self.missing_values_handling,
               self._design_signature())
        hit = frame._matrix_cache.get(key)
        if hit is not None:
            return hit
        out = self._weights_uncached(frame)
        frame._matrix_cache[key] = out
        return out

    def _weights_uncached(self, frame: Frame) -> jax.Array:
        w = frame.valid_mask().astype(jnp.float32)
        if self.weights_column is not None:
            w = w * jnp.nan_to_num(frame.vec(self.weights_column).numeric_data())
        if self.response_column is not None:
            y = self.response(frame)
            w = w * jnp.where(jnp.isnan(y) | (y < -0.5) if self.response_domain
                              else jnp.isnan(y), 0.0, 1.0)
        if self.missing_values_handling == SKIP:
            for s in self.specs:
                vec = frame.vec(s.name)
                if s.type == T_CAT:
                    w = w * (self._aligned_codes(vec, s) >= 0)
                else:
                    w = w * ~vec.isna()
        return w

    def offsets(self, frame: Frame) -> Optional[jax.Array]:
        if self.offset_column is None:
            return None
        return jnp.nan_to_num(frame.vec(self.offset_column).numeric_data())
