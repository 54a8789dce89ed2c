"""EFB: exclusive feature bundling — the wide/sparse tree path.

Reference handling of wide sparse frames: sparse chunk codecs
(``water/fvec/NewChunk.java:1133`` — CX chunks) and XGBoost's CSR bridge
(``hex/tree/xgboost/matrix/SparseMatrixFactory.java``).  Both keep the
per-feature loop; on a TPU the histogram kernel's cost is the PACKED bin-row
count ``sum(pad8(B_f + 2))`` (linear in slots, flat in depth), so
the winning move is LightGBM-style Exclusive Feature Bundling: mutually
exclusive sparse features (never non-default on the same row) share ONE
working feature whose bin axis concatenates the members' non-default bins.
A 1,900-column one-hot/sparse frame collapses to a handful of ~nbins-wide
bundles — the kernel, the partition select-chain, and the per-level split
scan all shrink by the bundling factor.

Bundles exist ONLY in the working space (histogram + partition).  Split
search "unbundles": member f's default-bin mass (its per-feature MODE bin
d_f — under quantile edges even a 0/1 column's zero usually lands in bin 1,
not 0) is reconstructed as ``leaf_total - sum(f's packed slots)``: every row
non-default in another member is default in f, by exclusivity.  Candidate
gains are therefore EXACT per original feature and the recorded tree stores
original (feature, threshold) pairs — prediction, TreeSHAP, MOJO export and
varimp are untouched.  In working space the chosen split becomes a bin
RANGE with an optional complement (default mass can sit on either side of
the cut), handled by ``partition_ranged``.

Mechanics are deliberately layered on the existing kernels: a bundle is just
a working feature with a large ``bin_count``, so the varbin Pallas kernel
(hist.py) and the parent-sibling subtraction drive it unchanged.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class BundlePlan(NamedTuple):
    """Static bundling decision (hashable — it keys the jit caches).

    ``working``: per working feature, either ``("raw", orig_idx, B_f)`` or
    ``("bundle", members)`` with members a tuple of
    ``(orig_idx, start_slot, B_f, default_bin)``; a bundle's slot 0 is the
    shared all-default bin, member f owns slots
    ``[start_slot, start_slot + B_f - 2]`` holding its non-default original
    bins in ascending order (the default bin d_f is skipped).
    """

    working: tuple
    bin_counts: tuple            # per working feature: bins in use

    @property
    def n_working(self) -> int:
        return len(self.working)


@functools.lru_cache(maxsize=None)
def _plan_stats_fn(F: int, nrows: int, S: int, stride: int, nbins: int):
    """Device prepass for the bundle planner: per-feature NA count, sample
    mode bin, non-default count, and the BIT-PACKED non-default sample
    mask.  The raw [F, S] code sample is hundreds of MB to fetch per
    train(); the packed mask is ~S/8 bytes per feature — one small
    fetch."""

    def stats(codes):
        sub = jax.lax.slice(codes, (0, 0), (F, nrows), (1, stride))
        na_cnt = jnp.sum(codes[:, :nrows] == nbins, axis=1)
        # mode bin via per-bin compare-count (B small static loop on
        # device; avoids materializing [F, S, B])
        counts = jax.lax.map(
            lambda b: jnp.sum((sub == b).astype(jnp.int32), axis=1),
            jnp.arange(nbins + 1))                      # [B, F]
        d_bin = jnp.argmax(counts, axis=0).astype(jnp.int32)
        Z = sub != d_bin[:, None]
        nz = jnp.sum(Z, axis=1)
        S8 = (S + 7) // 8 * 8
        Zp8 = jnp.pad(Z, [(0, 0), (0, S8 - S)]).reshape(F, S8 // 8, 8)
        weights = jnp.asarray([128, 64, 32, 16, 8, 4, 2, 1], jnp.int32)
        Zp = jnp.sum(Zp8.astype(jnp.int32) * weights, axis=2) \
            .astype(jnp.uint8)
        return na_cnt, d_bin, nz, Zp

    return jax.jit(stats)


def plan_bundles(codes, bin_counts, nbins: int, nrows: int,
                 sample: int = 16384, min_features: int = 32,
                 min_reduction: float = 0.85) -> Optional[BundlePlan]:
    """Greedy conflict-free packing of sparse features into bundles.

    ``codes``: [F, padded] device bin codes (NA == nbins).  Per-feature
    default bin = the sample MODE bin; exclusivity (never two members
    non-default on one row) is checked on a strided ~``sample``-row
    subsample (LightGBM's greedy bundling, conflict budget 0 on the
    sample).  Features with any NA code among the first ``nrows`` rows, or
    with non-default rate > 50%, stay unbundled.  Returns None unless the
    packed kernel cost drops below ``min_reduction`` of the unbundled
    cost — bundling only engages where it wins.
    """
    F = len(bin_counts)
    if F < min_features:
        return None
    stride = max(1, -(-nrows // sample))
    S = len(range(0, nrows, stride))
    na_cnt, d_bin, nz, Zp = jax.device_get(
        _plan_stats_fn(F, nrows, S, stride, nbins)(codes))
    d_bin = np.asarray(d_bin, np.int64)
    nz = np.asarray(nz)
    Zp = np.asarray(Zp)
    cand = [f for f in range(F)
            if na_cnt[f] == 0 and bin_counts[f] >= 2
            and d_bin[f] < nbins
            and bin_counts[f] - 1 <= nbins - 1
            and nz[f] <= 0.5 * S]
    if len(cand) < 4:
        return None
    # greedy: heaviest features first, into the first conflict-free bundle
    # with slot room (width cap = nbins so bundles fit the B = nbins+1
    # axis).  Conflict masks are bit-packed so a probe is a ~S/8-byte
    # AND — cheap enough to probe EVERY bundle: a capped probe count (the
    # first version's max_probe=64) made a few hundred non-exclusive
    # features fill the head of the bundle list and starve every later
    # exclusive feature of its match (observed on the springleaf shape:
    # 1200 one-hot columns, zero bundles formed).
    order = sorted(cand, key=lambda f: -int(nz[f]))
    bundles = []           # [members: [(f, B_f, d_f)], packed mask, width]
    for f in order:
        need = bin_counts[f] - 1
        placed = False
        for b in bundles:
            if b[2] + need > nbins:          # cheap width check
                continue
            if not np.bitwise_and(b[1], Zp[f]).any():
                b[0].append((f, bin_counts[f], int(d_bin[f])))
                b[1] |= Zp[f]
                b[2] += need
                placed = True
                break
        if not placed:
            bundles.append([[(f, bin_counts[f], int(d_bin[f]))],
                            Zp[f].copy(), 1 + need])
    bundled = {f for b in bundles if len(b[0]) > 1 for f, _, _ in b[0]}
    if not bundled:
        return None
    working, wbins = [], []
    for f in range(F):
        if f not in bundled:
            working.append(("raw", f, int(bin_counts[f])))
            wbins.append(int(bin_counts[f]))
    for b in bundles:
        if len(b[0]) > 1:
            # re-pack member starts in orig-feature order (determinism)
            members, start = [], 1
            for f, bf, df in sorted(b[0]):
                members.append((f, start, int(bf), df))
                start += bf - 1
            working.append(("bundle", tuple(members)))
            wbins.append(start)

    def packed_cost(bcs):
        return sum(((min(b, nbins) + 2) + 7) // 8 * 8 for b in bcs)

    # engage whenever the packed kernel cost meaningfully drops: besides
    # the VPU slot count, the working-feature count drives varbin kernel
    # COMPILE time (statically unrolled per-feature compares) and the
    # per-level split-search width, so even a ~15% slot reduction wins
    if packed_cost(wbins) > min_reduction * packed_cost(bin_counts):
        return None
    return BundlePlan(tuple(working), tuple(wbins))


@functools.lru_cache(maxsize=None)
def _apply_fn(plan: BundlePlan):
    def apply(codes):
        pieces = []
        for w in plan.working:
            if w[0] == "raw":
                pieces.append(codes[w[1]])
            else:
                idx = jnp.asarray([m[0] for m in w[1]], jnp.int32)
                starts = jnp.asarray([m[1] for m in w[1]],
                                     jnp.int32)[:, None]
                dfs = jnp.asarray([m[3] for m in w[1]], jnp.int32)[:, None]
                mc = jnp.take(codes, idx, axis=0)          # [m, N]
                # slot = start + (#non-default orig bins < c): bins above
                # the skipped default shift down by one
                mapped = jnp.where(mc == dfs, 0,
                                   starts + mc - (mc > dfs))
                pieces.append(jnp.max(mapped, axis=0))
        return jnp.stack(pieces, axis=0).astype(jnp.int32)
    return jax.jit(apply)


def apply_bundles(codes, plan: BundlePlan):
    """[F, N] original codes -> [F_w, N] working codes (one compiled
    program per plan).  Conflict rows (possible off-sample) resolve to the
    highest-mapped member — the LightGBM conflict tolerance."""
    return _apply_fn(plan)(codes)


@functools.lru_cache(maxsize=None)
def efb_maps(plan: BundlePlan, B: int):
    """Static working-space maps for the mixed split search.

    Dense group: working/orig index vectors.  Bundle group, per slot s of
    the [Fb, B-1] regular-bin axis: the owning member's slot range
    [seg_a, seg_b), its original feature, the slot's ORIGINAL bin, whether
    the default bin sits at-or-below it (addD -> default mass joins the
    left child), and the member's first-above-default slot (candidate-B
    anchor, where the cut lands exactly on the default bin).
    """
    dense_w = [i for i, w in enumerate(plan.working) if w[0] == "raw"]
    dense_orig = [plan.working[i][1] for i in dense_w]
    bundle_w = [i for i, w in enumerate(plan.working) if w[0] == "bundle"]
    Fb = len(bundle_w)
    shape = (Fb, B - 1)
    seg_a = np.zeros(shape, np.int32)
    seg_b = np.zeros(shape, np.int32)
    ofeat = np.zeros(shape, np.int32)
    obin = np.zeros(shape, np.int32)
    dflt = np.zeros(shape, np.int32)
    addD = np.zeros(shape, bool)
    is_slot = np.zeros(shape, bool)
    is_candB = np.zeros(shape, bool)
    first_above = np.zeros(shape, np.int32)
    for j, wi in enumerate(bundle_w):
        for f, start, bf, df in plan.working[wi][1]:
            end = start + bf - 1
            nd_bins = [b for b in range(bf) if b != df]
            fa = start + sum(1 for b in nd_bins if b < df)   # first slot > df
            for k, b in enumerate(nd_bins):
                s = start + k
                seg_a[j, s] = start
                seg_b[j, s] = end
                ofeat[j, s] = f
                obin[j, s] = b
                dflt[j, s] = df
                addD[j, s] = b > df
                is_slot[j, s] = True
                first_above[j, s] = fa
            if fa < end:
                is_candB[j, fa] = True
    return {
        "dense_w": np.asarray(dense_w, np.int32),
        "dense_orig": np.asarray(dense_orig, np.int32),
        "bundle_w": np.asarray(bundle_w, np.int32),
        "seg_a": seg_a, "seg_b": seg_b, "ofeat": ofeat, "obin": obin,
        "dflt": dflt, "addD": addD, "is_slot": is_slot,
        "is_candB": is_candB, "first_above": first_above,
    }


def best_splits_mixed(H, nbins: int, plan: BundlePlan, reg_lambda,
                      min_rows, min_split_improvement, feat_mask,
                      reg_alpha: float = 0.0, gamma: float = 0.0,
                      min_child_weight: float = 0.0):
    """Best split per leaf over a mixed working space.

    ``H``: [3, L, F_w, B] working histogram.  Dense (raw) features run the
    exact ``best_splits`` scan on their sub-block; bundled members are
    scanned per-slot with the reconstructed default mass.  Returns
    (ofeat, obin, na_left, gain, valid, children, wfeat, lo, hi, inv) —
    the first six in ORIGINAL feature space for the recorded tree, the
    last four in WORKING space for ``partition_ranged`` (right child =
    ``inv XOR (lo < code <= hi)``).
    """
    from .hist import best_splits, _score
    maps = efb_maps(plan, nbins + 1)
    L = H.shape[1]

    outs = []        # (gain, ofeat, obin, na_left, children, wfeat, lo,
    #                   hi, inv)
    if len(maps["dense_w"]):
        dw = jnp.asarray(maps["dense_w"])
        Hd = H[:, :, maps["dense_w"], :]
        fm = feat_mask[:, maps["dense_w"]] if feat_mask is not None else None
        feat_d, bin_d, nal_d, gain_d, _, ch_d = best_splits(
            Hd, nbins, reg_lambda, min_rows, min_split_improvement, fm,
            reg_alpha, gamma, min_child_weight)
        wfeat_d = dw[feat_d]
        ofeat_d = jnp.asarray(maps["dense_orig"])[feat_d]
        outs.append((gain_d, ofeat_d, bin_d, nal_d, ch_d, wfeat_d,
                     bin_d, jnp.full((L,), nbins, jnp.int32),
                     jnp.zeros((L,), bool)))

    if len(maps["bundle_w"]):
        Hb = H[:, :, maps["bundle_w"], :]          # [3, L, Fb, B]
        G, Hs, C = Hb[0], Hb[1], Hb[2]
        Fb, B = G.shape[-2], G.shape[-1]
        cums = (jnp.cumsum(G, -1), jnp.cumsum(Hs, -1), jnp.cumsum(C, -1))
        tots = tuple(c[..., -1] for c in cums)     # [L, Fb] leaf totals
        parent = _score(tots[0], tots[1], reg_lambda, reg_alpha)

        seg_a = jnp.asarray(maps["seg_a"])         # [Fb, B-1]
        seg_b = jnp.asarray(maps["seg_b"])
        first_above = jnp.asarray(maps["first_above"])
        is_slot = jnp.asarray(maps["is_slot"])
        is_candB = jnp.asarray(maps["is_candB"])
        addD = jnp.asarray(maps["addD"])

        def seg_stats(cum):
            # per slot s: member prefix P(s) (incl. s), prefix EXCL. s, and
            # member total S_f, via gathers at static boundaries
            a = jnp.broadcast_to(seg_a[None] - 1, (L, Fb, B - 1))
            b = jnp.broadcast_to(seg_b[None] - 1, (L, Fb, B - 1))
            cumA = jnp.take_along_axis(cum, jnp.maximum(a, 0), axis=-1)
            cumB = jnp.take_along_axis(cum, jnp.maximum(b, 0), axis=-1)
            P = cum[..., :-1] - cumA
            S = cumB - cumA
            return P, S

        PG, SG = seg_stats(cums[0])
        PH, SH = seg_stats(cums[1])
        PC, SC = seg_stats(cums[2])
        totG, totH, totC = (t[..., None] for t in tots)
        DG, DH, DC = totG - SG, totH - SH, totC - SC   # default-in-f mass

        def gains(GL, HL, CL):
            GR, HR, CR = totG - GL, totH - HL, totC - CL
            g = 0.5 * (_score(GL, HL, reg_lambda, reg_alpha)
                       + _score(GR, HR, reg_lambda, reg_alpha)
                       - parent[..., None]) - gamma
            ok = (CL >= min_rows) & (CR >= min_rows) & \
                (HL >= min_child_weight) & (HR >= min_child_weight)
            return jnp.where(ok, g, -jnp.inf), (GL, HL, CL, GR, HR, CR)

        if feat_mask is not None:
            bm = feat_mask[:, maps["bundle_w"]][..., None]
        else:
            bm = jnp.ones((L, Fb, 1), bool)
        aD = addD[None].astype(jnp.float32)
        # candidate A (cut after slot s's ORIGINAL bin): left = member
        # slots <= s, plus the default mass when d_f is below the cut
        gA, chA = gains(PG + aD * DG, PH + aD * DH, PC + aD * DC)
        gA = jnp.where(is_slot[None] & bm, gA, -jnp.inf)
        # candidate B (cut exactly after the default bin), evaluated at the
        # member's first-above-default slot: left = slots below d_f + D
        a = jnp.broadcast_to(seg_a[None] - 1, (L, Fb, B - 1))
        sm1 = jnp.maximum(jnp.arange(B - 1, dtype=jnp.int32) - 1, 0)

        def pexcl(cum):
            cumA = jnp.take_along_axis(cum, jnp.maximum(a, 0), axis=-1)
            cumS = jnp.take_along_axis(
                cum, jnp.broadcast_to(sm1, (L, Fb, B - 1)), axis=-1)
            first = jnp.arange(B - 1, dtype=jnp.int32) == seg_a[None]
            return jnp.where(first, 0.0, cumS - cumA)

        gB, chB = gains(pexcl(cums[0]) + DG, pexcl(cums[1]) + DH,
                        pexcl(cums[2]) + DC)
        gB = jnp.where(is_candB[None] & bm, gB, -jnp.inf)

        def pick_best(gain3, ch3):
            flat = gain3.reshape(L, -1)
            best = jnp.argmax(flat, axis=1)
            gsel = jnp.take_along_axis(flat, best[:, None], 1)[:, 0]

            def sel(x):
                return jnp.take_along_axis(x.reshape(L, -1),
                                           best[:, None], 1)[:, 0]
            j = (best // (B - 1)).astype(jnp.int32)
            s = (best % (B - 1)).astype(jnp.int32)
            ch = jnp.stack([sel(c) for c in ch3], axis=1)
            return gsel, j, s, ch

        obins = jnp.asarray(maps["obin"])
        dflts = jnp.asarray(maps["dflt"])
        wl = jnp.asarray(maps["bundle_w"])
        gA_s, jA, sA, chA_s = pick_best(gA, chA)
        gB_s, jB, sB, chB_s = pick_best(gB, chB)
        # candidate-A partition rule: default-above cut -> right child is
        # the contiguous tail range; default-below -> LEFT child is the
        # head range, expressed as the complement (inv)
        aD_A = addD[jA, sA]
        loA = jnp.where(aD_A, sA, seg_a[jA, sA] - 1)
        hiA = jnp.where(aD_A, seg_b[jA, sA] - 1, sA)
        invA = ~aD_A
        ofA = jnp.asarray(maps["ofeat"])[jA, sA]
        obA = obins[jA, sA]
        wfA = wl[jA]
        nalA = aD_A                       # NaN at predict follows default
        # candidate B: right = slots strictly above the default
        ofB = jnp.asarray(maps["ofeat"])[jB, sB]
        obB = dflts[jB, sB]
        wfB = wl[jB]
        loB = first_above[jB, sB] - 1
        hiB = seg_b[jB, sB] - 1
        invB = jnp.zeros_like(loB, bool)
        nalB = jnp.ones_like(invB)

        useB = gB_s > gA_s
        gain_b = jnp.maximum(gA_s, gB_s)

        def w2(bv, av):
            cond = useB[:, None] if av.ndim == 2 else useB
            return jnp.where(cond, bv, av)
        of_b = w2(ofB, ofA)
        ob_b = w2(obB, obA)
        nal_b = w2(nalB, nalA)
        ch_b = w2(chB_s, chA_s)
        wf_b = w2(wfB, wfA)
        lo_b = w2(loB, loA)
        hi_b = w2(hiB, hiA)
        inv_b = w2(invB, invA)
        outs.append((gain_b, of_b, ob_b, nal_b, ch_b, wf_b, lo_b, hi_b,
                     inv_b))

    if len(outs) == 1:
        gain, ofeat, obin, na_left, children, wfeat, lo, hi, inv = outs[0]
    else:
        gd, gb = outs[0][0], outs[1][0]
        use_b = gb > gd

        def mix(i):
            av, bv = outs[0][i], outs[1][i]
            cond = use_b[:, None] if av.ndim == 2 else use_b
            return jnp.where(cond, bv, av)
        gain, ofeat, obin, na_left, children, wfeat, lo, hi, inv = \
            (mix(i) for i in range(9))

    # leaf totals are identical across working features; reuse working 0
    totG_all = jnp.sum(H[0, :, 0, :], axis=-1)
    totH_all = jnp.sum(H[1, :, 0, :], axis=-1)
    totC_all = jnp.sum(H[2, :, 0, :], axis=-1)
    valid = jnp.isfinite(gain) & (gain > min_split_improvement) & \
        (totC_all >= 2 * min_rows)
    gl = jnp.where(valid, children[:, 0], totG_all)
    hl = jnp.where(valid, children[:, 1], totH_all)
    cl = jnp.where(valid, children[:, 2], totC_all)
    gr = jnp.where(valid, children[:, 3], 0.0)
    hr = jnp.where(valid, children[:, 4], 0.0)
    cr = jnp.where(valid, children[:, 5], 0.0)
    children = jnp.stack([gl, hl, cl, gr, hr, cr], axis=1)
    return (ofeat.astype(jnp.int32), obin.astype(jnp.int32), na_left, gain,
            valid, children, wfeat.astype(jnp.int32),
            lo.astype(jnp.int32), hi.astype(jnp.int32), inv)
