"""Quantile binning: the feature-discretization prepass for histogram trees.

Reference: ``hex/tree/DHistogram.java:48`` computes per-column min/max and
bins on the fly per node; XGBoost's ``hist``/``gpu_hist`` (the perf target,
h2o-extensions/xgboost) instead quantile-sketches each feature ONCE and
trains on small integer bin codes.  The TPU design follows the sketch
approach: static shapes, int codes, all histogram work becomes dense matmuls.

Layout: each feature gets ``nbins`` regular bins; bin ``nbins`` is reserved
for NA (the missing bucket).  Categorical codes are their own bins (capped at
``nbins``, the reference's nbins_cats analog).  Edges are float32 split
thresholds usable directly at prediction time.

Perf note: a host-loop sketch pays a fetch per feature plus a separately
compiled searchsorted dispatch per feature.  Binning is instead TWO cached
compiled programs: one masked-sort sketch over all numeric columns, one
encode pass over all features; the only device->host traffic is the small
[C, nbins-1] edge matrix.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...frame.frame import Frame
from ...frame.vec import T_CAT
from ...runtime import observability as obs


@dataclasses.dataclass
class BinnedFrame:
    """Device-resident binned design block + host-side bin metadata.

    Codes are FEATURE-MAJOR [F, padded_rows]: rows in the lane dimension.
    A row-major [N, F] block would tile-pad F up to 128 lanes (16x HBM blowup
    for narrow tabular data); feature-major keeps the hot array dense.
    """

    codes: jax.Array            # [F, padded_rows] int32 bin codes
    edges: List[np.ndarray]     # per-feature ascending split thresholds
    names: List[str]            # feature column names
    is_cat: List[bool]
    cat_domains: List[Optional[List[str]]]
    nbins: int                  # regular bins; code == nbins means NA

    @property
    def nfeatures(self) -> int:
        return len(self.names)

    @property
    def na_bin(self) -> int:
        return self.nbins

    @property
    def bin_counts(self) -> tuple:
        """Per-feature count of bins actually in use (codes < this;
        DHistogram's per-column bin sizing).  Cats: min(card, nbins);
        numerics: len(edges)+1 regions."""
        out = []
        for e, cat, dom in zip(self.edges, self.is_cat, self.cat_domains):
            if cat:
                out.append(max(min(len(dom or []) or 1, self.nbins), 1))
            else:
                out.append(min(len(e) + 1, self.nbins))
        return tuple(out)


def _order_key(x):
    """float32 -> int32 keys whose signed order is the floats' order
    (-0.0 just below +0.0): a negative float's magnitude bits are flipped,
    so a larger magnitude gives a smaller key.  Its own inverse up to the
    bitcast: ``_order_value``."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def _order_value(k):
    """``_order_key``'s inverse: the key's sign is the float's, so the
    same xor restores its bits."""
    return jax.lax.bitcast_convert_type(k ^ ((k >> 31) & 0x7FFFFFFF),
                                        jnp.float32)


@functools.lru_cache(maxsize=None)
def _make_sketch_fn(n: int, padded: int, ncols: int, nq: int):
    """One compiled program: exact masked quantiles + min/max for a stacked
    [C, padded] block of numeric columns.

    Rows beyond ``n``, non-finite values, and rows with weight <= 0 are
    masked to +inf before an ascending device sort; quantile k then linearly
    interpolates positions q_k * (m_c - 1) within each column's m_c valid
    rows (numpy's default interpolation, so edges match the old host
    np.quantile sketch on unweighted data).

    The sort orders one int32 key a value (``_order_key``), unstably: a
    stable float sort is lowered with an s32 iota beside the values as a
    tie-breaker, twice the bytes every compare-exchange stage moves, and
    only the sorted values are read.  The keys order -0.0 before +0.0,
    which a float comparison holds equal, so an edge can differ from a
    float sort's only in the sign of a zero.
    """

    def sketch(X, w):
        iota = jax.lax.broadcasted_iota(jnp.int32, (ncols, padded), 1)
        valid = (iota < n) & jnp.isfinite(X) & (w[None, :] > 0)
        m = jnp.sum(valid, axis=1)                       # [C] valid counts
        Xm = jnp.where(valid, X, jnp.inf)
        Ks = jax.lax.sort(_order_key(Xm), dimension=1,   # invalid -> tail
                          is_stable=False)
        lo = jnp.min(jnp.where(valid, X, jnp.inf), axis=1)
        hi = jnp.max(jnp.where(valid, X, -jnp.inf), axis=1)
        qs = jnp.arange(1, nq + 1, dtype=jnp.float32) / (nq + 1)
        pos = qs[None, :] * jnp.maximum(m[:, None] - 1, 0)   # [C, nq]
        p0 = jnp.floor(pos).astype(jnp.int32)
        frac = pos - p0
        v0 = _order_value(jnp.take_along_axis(Ks, p0, axis=1))
        v1 = _order_value(jnp.take_along_axis(
            Ks, jnp.minimum(p0 + 1, jnp.maximum(m[:, None] - 1, 0)), axis=1))
        edges = v0 * (1 - frac) + v1 * frac
        return edges, lo, hi, m

    return jax.jit(sketch)


def run_sketch(X, w, n: int, nq: int, caller: str):
    """Dispatch ``_make_sketch_fn``'s program on the stacked [C, padded]
    block ``X`` (weights ``w``, ``n`` real rows, ``nq`` interior
    quantiles); ``binning_sketch_values_total{caller}`` counts the values
    its sort orders.  Returns the device ``(edges, lo, hi, m)``."""
    ncols, padded = (int(d) for d in X.shape)
    obs.inc("binning_sketch_values_total", ncols * padded, caller=caller)
    return _make_sketch_fn(n, padded, ncols, nq)(X, w)


@functools.lru_cache(maxsize=None)
def _make_encode_fn(padded: int, ecounts: tuple, is_cat: tuple,
                    nbins: int):
    """One compiled program encoding all features to bin codes.

    Numerics: blocked compare-count (== searchsorted side="right") against
    +inf-padded edge rows, clipped to each feature's edge count; NaN -> the
    NA bin.  Cats: code as bin, clamped to ``nbins - 1``; negative (NA
    sentinel) or NaN -> NA bin.

    Features are processed in GROUPS (all cats at once; numerics bucketed
    by edge width), not per-feature: the per-feature unrolled program
    compiled in O(F) (23 s at 481 columns, minutes at springleaf's ~1,900)
    while the grouped one stays O(log emax) programs with one static
    row-permutation gather at the end.
    """
    F = len(is_cat)
    cat_idx = [f for f in range(F) if is_cat[f]]
    num_idx = [f for f in range(F) if not is_cat[f]]
    emax = max([1] + [ecounts[f] for f in num_idx])
    groups: dict = {}
    for f in num_idx:
        w = 1
        while w < max(ecounts[f], 1):
            w *= 4
        groups.setdefault(min(w, emax), []).append(f)
    order = list(cat_idx) + [f for w in sorted(groups) for f in groups[w]]
    iperm = np.argsort(np.asarray(order, np.int64)).astype(np.int32)
    counts_np = np.asarray(ecounts, np.int32)

    def encode(X, E):
        pieces = []
        if cat_idx:
            Xc = X[jnp.asarray(cat_idx)]
            xi = jnp.where(jnp.isnan(Xc), -1.0, Xc).astype(jnp.int32)
            pieces.append(jnp.where(xi < 0, nbins,
                                    jnp.minimum(xi, nbins - 1)))
        for w in sorted(groups):
            idx = groups[w]
            Cg = len(idx)
            Xg = X[jnp.asarray(idx)]
            Eg = E[jnp.asarray(idx), :w]                  # [Cg, w]
            blk = int(min(padded,
                          max(1024, 67_108_864 // max(Cg * w, 1))))
            nblk = -(-padded // blk)
            pad = nblk * blk - padded
            Xb = jnp.pad(Xg, [(0, 0), (0, pad)]) \
                .reshape(Cg, nblk, blk).transpose(1, 0, 2)

            def body(_, xr, _Eg=Eg):
                # fused broadcast-compare + reduce (never materializes
                # [Cg, w, blk]); side="right" == count of edges <= x
                cb = jnp.sum(xr[:, None, :] >= _Eg[:, :, None],
                             axis=1, dtype=jnp.int32)
                return _, cb

            _, cb = jax.lax.scan(body, None, Xb)          # [nblk, Cg, blk]
            c = cb.transpose(1, 0, 2).reshape(Cg, -1)[:, :padded]
            # +inf rows also count the +inf edge PADDING — clip to the
            # feature's own edge count
            c = jnp.minimum(c, jnp.asarray(counts_np[idx])[:, None])
            pieces.append(jnp.where(jnp.isnan(Xg), nbins, c))
        out = pieces[0] if len(pieces) == 1 \
            else jnp.concatenate(pieces, axis=0)
        return out[jnp.asarray(iperm)].astype(jnp.int32)

    return jax.jit(encode)


def fit_bins(frame: Frame, features: List[str], nbins: int = 64,
             sample: int = 1_000_000, seed: int = 0,
             weights=None,
             histogram_type: str = "quantiles_global") -> BinnedFrame:
    """Sketch each feature's bin edges and encode the frame as bin codes.

    ``histogram_type`` (SharedTree histogram_type analog, hex/tree
    DHistogram): "quantiles_global" (default; XGBoost's approx sketch),
    "uniform_adaptive" (equal-width over the observed range) or
    "random" (uniform-random split points; drawn ONCE per model — the
    frame is encoded a single time, so unlike the reference's per-tree
    redraw, ensembles share these edges; vary ``seed`` for diversity
    across models).  Quantiles are EXACT over all weight>0 rows while the
    numeric stack fits a ~2 GB device budget (a device sort costs less
    than the old 1M-row host sample did in transfer); beyond that a
    strided ``sample``-row device subsample bounds memory.  ``weights``
    (host or device [>=nrows]) restricts the sketch to rows with
    weight > 0 — keeps CV's zero-weight holdout rows out of the bin edges.
    """
    htype = histogram_type.lower().replace("_", "")
    if htype in ("auto", "quantilesglobal"):
        htype = "quantiles"
    elif htype == "uniformadaptive":
        htype = "uniform"
    elif htype != "random":
        raise ValueError(
            f"unknown histogram_type {histogram_type!r}: use "
            "QuantilesGlobal, UniformAdaptive or Random")
    rng = np.random.default_rng(seed)
    n = frame.nrows

    vecs = [frame.vec(name) for name in features]
    is_cat = [v.type == T_CAT for v in vecs]
    domains = [v.domain if c else None for v, c in zip(vecs, is_cat)]
    num_idx = [f for f, c in enumerate(is_cat) if not c]

    with obs.span("binning.sketch", rows=n, columns=len(num_idx)):
        num_edges = _sketch_edges(vecs, num_idx, n, nbins, sample, weights,
                                  htype, rng)

    edges_list = []
    for f, cat in enumerate(is_cat):
        if cat:
            card = vecs[f].cardinality
            edges_list.append(np.arange(
                0.5, min(card, nbins) - 0.5 + 1e-9, 1.0, dtype=np.float32))
        else:
            edges_list.append(num_edges[f])

    with obs.span("binning.encode", rows=n, columns=len(features)):
        codes = encode_bins(frame, features, edges_list, is_cat, nbins)
    return BinnedFrame(codes=codes, edges=edges_list, names=list(features),
                       is_cat=is_cat, cat_domains=domains, nbins=nbins)


def _sketch_edges(vecs, num_idx, n: int, nbins: int, sample: int, weights,
                  htype: str, rng) -> dict:
    """``fit_bins``' sketch: numeric feature index -> ascending edges, from
    one device program over the stacked numeric block and the host fetch of
    its small result.  Exact quantiles when the stack fits a device
    budget; above it, a strided row subsample (the old host sketch's
    ``sample`` bound, kept on device) caps sort memory — rows are
    unordered, so a stride is as good a sample as a uniform draw."""
    num_edges: dict = {}
    if num_idx:
        full_padded = int(vecs[num_idx[0]].data.shape[0])
        budget_rows = max(int(2e9) // (4 * len(num_idx)), sample)
        stride = 1 if full_padded <= budget_rows \
            else -(-full_padded // max(sample, 1))
        X = jnp.stack([vecs[f].values()[::stride].astype(jnp.float32)
                       for f in num_idx], axis=0)
        padded = int(X.shape[1])
        n_eff = min(-(-n // stride), padded)
        if weights is not None:
            wv = jnp.asarray(weights, jnp.float32)[::stride]
            if wv.shape[0] < padded:
                wv = jnp.pad(wv, (0, padded - wv.shape[0]))
            wv = wv[:padded]
        else:
            wv = jnp.ones((padded,), jnp.float32)
        edges_q, lo, hi, m = (np.asarray(a, np.float64) for a in
                              jax.device_get(run_sketch(  # ONE batched fetch
                                  X, wv, n_eff, nbins - 1, "bins")))
        if weights is not None and stride > 1:
            # The strided subsample ran BEFORE the w>0 mask; when live rows
            # are rare or correlated with row order (stacked CV folds,
            # sorted frames) it can see few/zero live rows and a feature
            # silently gets degenerate edges.  Re-sketch from the live rows
            # when some column's valid count is far below what ITS OWN
            # finite population could supply — a mostly-NaN column with a
            # small count is expected and must not fire the re-sketch.
            iota_ok = jax.lax.broadcasted_iota(jnp.int32, X.shape, 1) < n_eff
            fin = np.asarray(jax.device_get(
                jnp.sum(jnp.isfinite(X) & iota_ok, axis=1)))
            wl = np.asarray(jax.device_get(jnp.asarray(weights)))[:n] > 0
            n_live = int(wl.sum())
            want = min(n_live, sample)
            starved = (m < np.maximum(want // 4, nbins)) & \
                (fin >= 2 * np.maximum(m, 1))
            if n_live and starved.any():
                idx = np.flatnonzero(wl)
                if len(idx) > sample:
                    idx = idx[:: -(-len(idx) // sample)]
                idx_d = jnp.asarray(idx, jnp.int32)
                X2 = jnp.stack([jnp.take(vecs[f].values(), idx_d)
                                .astype(jnp.float32) for f in num_idx],
                               axis=0)
                edges_q, lo, hi, m = (
                    np.asarray(a, np.float64) for a in jax.device_get(
                        run_sketch(X2, jnp.ones((len(idx),), jnp.float32),
                                   len(idx), nbins - 1, "bins")))
        for i, f in enumerate(num_idx):
            if m[i] == 0:
                e = np.zeros(0, dtype=np.float32)
            elif htype == "uniform":
                e = np.unique(np.linspace(lo[i], hi[i], nbins + 1)[1:-1]
                              .astype(np.float32))
            elif htype == "random":
                e = np.unique(np.sort(
                    rng.uniform(lo[i], hi[i], nbins - 1)).astype(np.float32))
            else:
                e = np.unique(edges_q[i].astype(np.float32))
                e = e[np.isfinite(e)]
            num_edges[f] = e
    return num_edges


def edges_matrix(edges_list, nbins: int) -> np.ndarray:
    """Dense [F, nbins] threshold table for on-device split lookup.

    Row f holds feature f's edges, right-padded by repeating the last edge
    (short rows only matter for invalid splits, which traversal ignores).
    """
    F = len(edges_list)
    mat = np.zeros((F, nbins), np.float32)
    for f, e in enumerate(edges_list):
        if len(e):
            mat[f, : len(e)] = e
            mat[f, len(e):] = e[-1]
    return mat


def encode_bins(frame: Frame, features: List[str], edges_list, is_cat,
                nbins: int) -> jax.Array:
    """Encode columns as bin codes — ONE cached device program per
    geometry (padded length, feature count, edge width, cat pattern)."""
    vecs = [frame.vec(name) for name in features]
    X = jnp.stack([v.values().astype(jnp.float32) for v in vecs], axis=0)
    ecounts = tuple(len(e) for e in edges_list)
    # E width covers every NUMERIC group bucket (next pow-4 of the widest
    # numeric) AND every categorical edge row stored alongside
    emax = max([1] + [c for c, cat in zip(ecounts, is_cat) if not cat])
    w = 1
    while w < emax:
        w *= 4
    w = max(w, max(ecounts, default=1), 1)
    E = np.full((len(features), w), np.inf, np.float32)
    for f, e in enumerate(edges_list):
        E[f, : len(e)] = e
    enc = _make_encode_fn(int(X.shape[1]), ecounts,
                          tuple(bool(c) for c in is_cat), nbins)
    return enc(X, jnp.asarray(E))
