"""Uplift DRF: treatment-effect forests on the tpu_hist kernels.

Reference: ``hex/tree/uplift/UpliftDRF.java`` + the uplift histogram columns
in ``hex/tree/DHistogram.java:80-85`` (per-bin response sums split by the
treatment flag) and the ``Divergence`` criteria (KL, Euclidean,
ChiSquared).  Prediction = p(y=1|treated) - p(y=1|control) per leaf,
averaged over the forest; quality is AUUC (qini) over the uplift ranking.

TPU-native redesign: the treatment/control histograms are TWO passes of the
same tpu_hist kernel with masked stat planes ((y*t, t, w*t) and the control
complement) — no new kernel; the divergence split search is a fused jnp
pass with the same cumulative-prefix structure as best_splits.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...frame.frame import Frame
from ...frame.vec import T_CAT
from ...runtime import dkv
from ...runtime.job import Job
from ..base import Model, ModelBuilder
from ..datainfo import DataInfo
from .binning import fit_bins, edges_matrix
from .hist import (make_batched_level_fn, make_batched_sparse_level_fn,
                   make_hist_fn, make_sparse_level_fn,
                   make_subtract_level_fn, partition, partition_right,
                   sparse_slot_budget, sparse_slot_maps, table_lookup)
from .shared import (SharedTreeModel, SharedTree, SharedTreeParameters,
                     StackedTrees, Tree, TreeList, dense_mem_cap,
                     traverse_jit)

_EPS = 1e-6


@dataclasses.dataclass
class UpliftDRFParameters(SharedTreeParameters):
    treatment_column: str = ""
    uplift_metric: str = "KL"            # KL | euclidean | chi_squared
    ntrees: int = 50
    max_depth: int = 10
    min_rows: float = 10.0
    sample_rate: float = 0.632
    mtries: int = -2                     # all features by default


def _divergence(pt, pc, metric: str):
    pt = jnp.clip(pt, _EPS, 1 - _EPS)
    pc = jnp.clip(pc, _EPS, 1 - _EPS)
    if metric == "KL":
        return pt * jnp.log(pt / pc) + (1 - pt) * jnp.log((1 - pt)
                                                          / (1 - pc))
    if metric == "euclidean":
        return (pt - pc) ** 2 + ((1 - pt) - (1 - pc)) ** 2
    if metric == "chi_squared":
        return (pt - pc) ** 2 / pc + ((1 - pt) - (1 - pc)) ** 2 / (1 - pc)
    raise ValueError(f"unknown uplift_metric {metric!r}")


def _uplift_best_splits(Ht, Hc, nbins: int, metric: str, min_rows: float,
                        feat_mask=None):
    """Best divergence-gain split per leaf.

    ``Ht``/``Hc``: [3, L, F, B] with planes (sum w*y, sum w, sum w) for the
    treatment / control subsets (B includes the NA bin; NA routes left).
    Gain = weighted child divergence - parent divergence
    (UpliftDRF's Divergence.value).
    """
    y1t, nt = Ht[0], Ht[1]
    y1c, ncn = Hc[0], Hc[1]
    # fold the NA bin into bin 0 (NA goes left always)
    def fold(a):
        return a[..., :-1].at[..., 0].add(a[..., -1])
    y1t, nt, y1c, ncn = fold(y1t), fold(nt), fold(y1c), fold(ncn)
    cy1t, cnt = jnp.cumsum(y1t, -1), jnp.cumsum(nt, -1)
    cy1c, cnc = jnp.cumsum(y1c, -1), jnp.cumsum(ncn, -1)
    tot_y1t, tot_nt = cy1t[..., -1], cnt[..., -1]          # [L, F]
    tot_y1c, tot_nc = cy1c[..., -1], cnc[..., -1]
    n_tot = tot_nt + tot_nc
    d_parent = _divergence(tot_y1t / jnp.maximum(tot_nt, _EPS),
                           tot_y1c / jnp.maximum(tot_nc, _EPS), metric)

    # split after bin b: left = bins <= b (b in [0, nbins-2])
    ly1t, lnt = cy1t[..., :-1], cnt[..., :-1]
    ly1c, lnc = cy1c[..., :-1], cnc[..., :-1]
    ry1t, rnt = tot_y1t[..., None] - ly1t, tot_nt[..., None] - lnt
    ry1c, rnc = tot_y1c[..., None] - ly1c, tot_nc[..., None] - lnc
    dl = _divergence(ly1t / jnp.maximum(lnt, _EPS),
                     ly1c / jnp.maximum(lnc, _EPS), metric)
    dr = _divergence(ry1t / jnp.maximum(rnt, _EPS),
                     ry1c / jnp.maximum(rnc, _EPS), metric)
    nl = lnt + lnc
    nr = rnt + rnc
    gain = (nl * dl + nr * dr) / jnp.maximum(n_tot[..., None], _EPS) \
        - d_parent[..., None]
    ok = (nl >= min_rows) & (nr >= min_rows) & (lnt > 0) & (lnc > 0) \
        & (rnt > 0) & (rnc > 0)
    gain = jnp.where(ok, gain, -jnp.inf)
    if feat_mask is not None:
        m = feat_mask if feat_mask.ndim == 2 else feat_mask[None, :]
        gain = jnp.where(m[..., None], gain, -jnp.inf)

    L, F = d_parent.shape
    flat = gain.reshape(L, -1)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], 1)[:, 0]
    feat = (best // (nbins - 1)).astype(jnp.int32)
    bin_ = (best % (nbins - 1)).astype(jnp.int32)
    valid = jnp.isfinite(best_gain) & (best_gain > 0)
    return feat, bin_, valid, best_gain


class UpliftDRFModel(SharedTreeModel):
    algo = "upliftdrf"

    def _predict_raw(self, X: jax.Array) -> jax.Array:
        T = self.output["ntrees_trained"]
        st_t: StackedTrees = self.output["stacked_pt"]
        st_c: StackedTrees = self.output["stacked_pc"]
        pt = traverse_jit(st_t.levels, st_t.values, X) / max(T, 1)
        pc = traverse_jit(st_c.levels, st_c.values, X) / max(T, 1)
        return jnp.stack([pt - pc, pt, pc], axis=1)

    def predict(self, frame: Frame) -> Frame:
        from ...frame.vec import Vec, T_NUM
        raw = np.asarray(self._predict_raw(self._score_matrix(frame)))
        raw = raw[: frame.nrows]
        return Frame(["uplift_predict", "p_y1_ct1", "p_y1_ct0"],
                     [Vec.from_numpy(raw[:, j], T_NUM) for j in range(3)])

    def model_performance(self, frame: Optional[Frame] = None):
        if frame is None:
            return self.training_metrics
        from ...metrics.uplift import uplift_metrics
        p = self.params
        pred = np.asarray(self._predict_raw(
            self._score_matrix(frame)))[: frame.nrows, 0]
        y = np.asarray(self.datainfo.response(frame))[: frame.nrows]
        t = frame.vec(p.treatment_column)
        treat = np.asarray(t.to_numpy(), np.float64)
        return uplift_metrics(pred, y, treat)


class UpliftDRF(SharedTree):
    """Treatment-effect forest — hex/tree/uplift/UpliftDRF analog."""

    algo = "upliftdrf"
    model_class = UpliftDRFModel
    _force_classification = True

    def __init__(self, params: Optional[UpliftDRFParameters] = None, **kw):
        super().__init__(params or UpliftDRFParameters(**kw))

    def _make_datainfo(self, frame: Frame) -> DataInfo:
        p = self.params
        if not p.treatment_column:
            raise ValueError("upliftdrf requires treatment_column")
        return DataInfo.fit(
            frame, response_column=p.response_column,
            ignored_columns=tuple(p.ignored_columns)
            + (p.treatment_column,),
            weights_column=p.weights_column, standardize=False,
            missing_values_handling="mean_imputation",
            force_classification=True)

    def _fit(self, job: Job, frame: Frame, di: DataInfo,
             valid: Optional[Frame]) -> UpliftDRFModel:
        p: UpliftDRFParameters = self.params
        y = jnp.nan_to_num(di.response(frame))
        w = di.weights(frame)
        tvec = frame.vec(p.treatment_column)
        if tvec.type == T_CAT:
            treat = (tvec.data == (len(tvec.domain) - 1)) \
                .astype(jnp.float32)
        else:
            treat = (jnp.nan_to_num(tvec.numeric_data()) > 0).astype(jnp.float32)
        binned = fit_bins(frame, [s.name for s in di.specs], nbins=p.nbins,
                          histogram_type=p.histogram_type,
                          seed=p.effective_seed())
        codes = binned.codes
        edges_mat = jnp.asarray(edges_matrix(binned.edges, p.nbins),
                                jnp.float32)
        F, N = codes.shape
        B = p.nbins + 1
        rng = jax.random.PRNGKey(p.effective_seed())
        # Treatment/control histograms ride the shared subtraction level
        # driver: the two stat triples share one leaf assignment, so each
        # level compacts the smaller siblings twice (once per arm) and
        # reconstructs the larger arm histograms from the per-shard parent
        # carries — the same <= N/2 row stream as GBM/DRF.  hist_mode="full"
        # keeps the oracle (the old always-full build).  "auto"
        # knobs route through the cost-model autotuner (K=2: the two
        # arms ride the batched level program as the class axis)
        from ...runtime import autotune
        knobs = autotune.resolve_tree_knobs(p, kind=self.algo, F=F, N=N,
                                            K=2)
        autotune.activate(knobs)
        if knobs.sparse_depth_threshold != p.sparse_depth_threshold:
            p = dataclasses.replace(
                p, sparse_depth_threshold=knobs.sparse_depth_threshold)
        hist_mode = knobs.hist_mode
        level_fns = [make_subtract_level_fn(d, F, B, N)
                     for d in range(p.max_depth)] \
            if hist_mode == "subtract" else None
        full_fns = [make_hist_fn(2 ** d, F, B, N)
                    for d in range(p.max_depth)] \
            if hist_mode == "full" else None
        # split_mode="fused": the two arms ride the batched level program
        # as the K axis (K=2, shared leaf routing, per-arm stat planes) —
        # one hist launch per level instead of two; the divergence split
        # search itself stays _uplift_best_splits.
        split_mode = knobs.split_mode
        bfns = [make_batched_level_fn(
                    d, 2, F, B, N, subtract=(hist_mode != "full"))
                for d in range(p.max_depth)] \
            if split_mode != "separate" else None
        # hist_layout="sparse": levels at/below the clamped threshold key
        # histograms by ALIVE-leaf slots [A, F, B] instead of the dense
        # [2^d, F, B] grid (both arms share one slot map — the leaf
        # assignment is shared).
        hist_layout = knobs.hist_layout
        # tree_program: uplift's bespoke two-arm grow_tree loop has no
        # scan-fused build (its divergence split search interleaves both
        # treatment arms between levels), so any scan request silently
        # rides the per-level program.  The tuner never tunes the knob
        # for kind="uplift"; this covers an explicit tree_program="scan".
        tree_program = "level"
        t0 = max(1, min(p.sparse_depth_threshold, dense_mem_cap(p.nbins, F)))
        sparse_from0 = t0 if (hist_layout == "sparse"
                              and p.max_depth > t0) else p.max_depth
        A_cap = sparse_slot_budget(F, B)
        A_lv = {d: min(2 ** d, A_cap)
                for d in range(sparse_from0, p.max_depth)}
        Ap_lv = {d: (2 ** (d - 1) if d == sparse_from0 else A_lv[d - 1])
                 for d in range(sparse_from0, p.max_depth)}
        sparse_fns = {d: make_sparse_level_fn(Ap_lv[d], A_lv[d], F, B, N)
                      for d in range(sparse_from0, p.max_depth)}
        sparse_bfns = {d: make_batched_sparse_level_fn(
                           Ap_lv[d], A_lv[d], 2, F, B, N)
                       for d in range(sparse_from0, p.max_depth)} \
            if split_mode != "separate" else None

        def _slot_maps(d, prev_valid, slot_of_leaf, leaf_of_slot):
            # slot assignment + dense<->slot index maps for sparse level d
            # (shared.make_build_tree_fn's helper, at uplift's geometry)
            A = A_lv[d]
            sidx = jnp.arange(A, dtype=jnp.int32)
            child_base, ps_of_slot, real = sparse_slot_maps(prev_valid, A)
            l2 = jnp.arange(2 ** d, dtype=jnp.int32)
            if d == sparse_from0:
                sol = jnp.minimum(child_base[l2 >> 1] + (l2 & 1), A)
                los = 2 * ps_of_slot + (sidx & 1)
            else:
                sol = jnp.minimum(child_base[slot_of_leaf[l2 >> 1]]
                                  + (l2 & 1), A)
                los = 2 * leaf_of_slot[ps_of_slot] + (sidx & 1)
            return child_base, ps_of_slot, real, sol, los

        def _sleaf_of_leaf(slot_of_leaf, leaf, L):
            # boundary only: dense leaf id -> slot id, one MXU lookup
            return table_lookup(slot_of_leaf[None].astype(jnp.float32),
                                leaf, L)[0].astype(jnp.int32)

        def _pad_slot_tables(feat_s, bin_s, na_s, valid_s):
            # sentinel row (slot A): valid=False -> dead rows flow left
            def z(a):
                return jnp.concatenate([a, jnp.zeros((1,), a.dtype)])
            return z(feat_s), z(bin_s), z(na_s), z(valid_s)

        col_rate = 1.0 if p.mtries == -2 else \
            max(min(p.mtries if p.mtries > 0 else int(np.sqrt(F)), F), 1) / F

        @jax.jit
        def leaf_stats(leaf, wv):
            nseg = 2 ** p.max_depth
            y1t = jax.ops.segment_sum(wv * y * treat, leaf,
                                      num_segments=nseg)
            nt = jax.ops.segment_sum(wv * treat, leaf, num_segments=nseg)
            y1c = jax.ops.segment_sum(wv * y * (1 - treat), leaf,
                                      num_segments=nseg)
            nc = jax.ops.segment_sum(wv * (1 - treat), leaf,
                                     num_segments=nseg)
            pt = jnp.where(nt > 0, y1t / jnp.maximum(nt, _EPS), 0.0)
            pc = jnp.where(nc > 0, y1c / jnp.maximum(nc, _EPS), 0.0)
            return pt.astype(jnp.float32), pc.astype(jnp.float32)

        def grow_tree(wv, keys, mode, batched=False, layout="dense"):
            """One uplift tree's level loop under the given hist_mode."""
            leaf = jnp.zeros(N, jnp.int32)
            levels = []
            # terminality invariant (see shared.make_build_tree_fn): a dead
            # node's descendants stay dead — required by the node-sparse
            # exporters AND by the sparse layout (dead chains get no slots)
            alive = jnp.ones((1,), bool)
            gt, nt = wv * y * treat, wv * treat
            gc, nc = wv * y * (1 - treat), wv * (1 - treat)
            if batched:
                gA, nA = jnp.stack([gt, gc]), jnp.stack([nt, nc])
            sparse_from = sparse_from0 if (layout == "sparse"
                                           and mode == "subtract") \
                else p.max_depth
            Ht_carry = Hc_carry = HA_carry = None
            valid = valid_s = slot_of_leaf = leaf_of_slot = None
            sleaf = right = None
            for d in range(p.max_depth):
                L = 2 ** d
                mask = jax.random.uniform(keys[d], (L, F)) < col_rate
                mask = mask.at[:, 0].set(mask[:, 0] | ~mask.any(axis=1))
                if d >= sparse_from:
                    A = A_lv[d]
                    if d == sparse_from:
                        # boundary: slots from the last DENSE level's valid
                        # flags; the dense subtract carry is consumed
                        # unchanged (its slot space = dense parent space)
                        (child_base, ps_of_slot, real, slot_of_leaf,
                         leaf_of_slot) = _slot_maps(d, valid, None, None)
                        sleaf = _sleaf_of_leaf(slot_of_leaf, leaf, L)
                    else:
                        (child_base, ps_of_slot, real, slot_of_leaf,
                         leaf_of_slot) = _slot_maps(d, valid_s,
                                                    slot_of_leaf,
                                                    leaf_of_slot)
                        sleaf = jnp.minimum(jnp.take(child_base, sleaf)
                                            + right, A)
                    if batched:
                        # both arms share the slot map (shared leaf
                        # assignment) — one launch covers both
                        sleafA = jnp.broadcast_to(sleaf, (2, N))
                        psA = jnp.broadcast_to(ps_of_slot, (2, A))
                        HA, HA_carry = sparse_bfns[d](codes, sleafA, gA,
                                                      nA, nA, HA_carry,
                                                      psA)
                        Ht, Hc = HA[0], HA[1]
                    else:
                        Ht, Ht_carry = sparse_fns[d](codes, sleaf, gt, nt,
                                                     nt, Ht_carry,
                                                     ps_of_slot)
                        Hc, Hc_carry = sparse_fns[d](codes, sleaf, gc, nc,
                                                     nc, Hc_carry,
                                                     ps_of_slot)
                    # col mask DRAWN dense (bit-identical RNG to the dense
                    # layout), gathered to slots
                    mask_s = mask[leaf_of_slot]
                    feat_s, bin_s, valid_s, gain = _uplift_best_splits(
                        Ht, Hc, p.nbins, p.uplift_metric, p.min_rows,
                        mask_s)
                    # phantom slots past the live range carry no rows
                    valid_s = valid_s & real
                    na_s = jnp.ones_like(valid_s)
                    # expand slot records to the dense [2^d] level contract
                    mapped = slot_of_leaf < A
                    slc = jnp.minimum(slot_of_leaf, A - 1)
                    feat = jnp.where(mapped, feat_s[slc], 0)
                    bin_ = jnp.where(mapped, bin_s[slc], 0)
                    valid = mapped & valid_s[slc]
                    na_left = jnp.ones_like(valid)
                    thr = edges_mat[feat, jnp.clip(bin_, 0, p.nbins - 1)]
                    fp, bp, nap, vp = _pad_slot_tables(feat_s, bin_s,
                                                       na_s, valid_s)
                    right = partition_right(codes, sleaf, fp, bp, nap, vp,
                                            jnp.int32(p.nbins))
                    leaf = 2 * leaf + right
                    levels.append((feat, thr, na_left, valid))
                    continue
                if batched:
                    # both arms in ONE launch per level: arm = batched-K
                    # axis; the shared leaf broadcasts, so both arms pick
                    # identical smaller-sibling compactions
                    leafA = jnp.broadcast_to(leaf, (2, N))
                    if mode == "subtract":
                        if d == 0:
                            HA, HA_carry = bfns[0](codes, leafA, gA, nA,
                                                   nA)
                        else:
                            HA, HA_carry = bfns[d](codes, leafA, gA, nA,
                                                   nA, HA_carry)
                    else:
                        HA = bfns[d](codes, leafA, gA, nA, nA)
                    Ht, Hc = HA[0], HA[1]
                elif mode == "subtract":
                    if d == 0:
                        Ht, Ht_carry = level_fns[0](codes, leaf, gt, nt, nt)
                        Hc, Hc_carry = level_fns[0](codes, leaf, gc, nc, nc)
                    else:
                        Ht, Ht_carry = level_fns[d](codes, leaf, gt, nt, nt,
                                                    Ht_carry)
                        Hc, Hc_carry = level_fns[d](codes, leaf, gc, nc, nc,
                                                    Hc_carry)
                else:
                    Ht = full_fns[d](codes, leaf, gt, nt, nt)
                    Hc = full_fns[d](codes, leaf, gc, nc, nc)
                feat, bin_, valid, gain = _uplift_best_splits(
                    Ht, Hc, p.nbins, p.uplift_metric, p.min_rows, mask)
                valid = valid & alive
                alive = jnp.stack([valid, valid], axis=1).reshape(-1)
                na_left = jnp.ones_like(valid)
                thr = edges_mat[feat, jnp.clip(bin_, 0, p.nbins - 1)]
                leaf = partition(codes, leaf, feat, bin_, na_left, valid,
                                 jnp.int32(p.nbins))
                levels.append((feat, thr, na_left, valid))
            return levels, leaf

        trees_t: List[Tree] = []
        trees_c: List[Tree] = []
        from ...runtime import failure
        for t_i in range(p.ntrees):
            rng, ks, km = jax.random.split(rng, 3)
            wv = w
            if p.sample_rate < 1.0:
                wv = w * jax.random.bernoulli(ks, p.sample_rate, w.shape)
            keys = jax.random.split(km, p.max_depth)
            if sparse_from0 < p.max_depth:
                # kill/resume while node-sparse deep levels are live
                failure.maybe_inject("deep_level")
            levels, leaf = grow_tree(
                wv, keys, hist_mode, batched=(split_mode == "fused"),
                layout=hist_layout)
            pt_vals, pc_vals = leaf_stats(leaf, wv)
            lv = [tuple(x) if not isinstance(x, tuple) else x
                  for x in levels]
            trees_t.append(Tree([x[0] for x in lv], [x[1] for x in lv],
                                [x[2] for x in lv], [x[3] for x in lv],
                                pt_vals))
            trees_c.append(Tree([x[0] for x in lv], [x[1] for x in lv],
                                [x[2] for x in lv], [x[3] for x in lv],
                                pc_vals))
            job.update((t_i + 1) / p.ntrees, f"tree {t_i + 1}/{p.ntrees}")

        model = UpliftDRFModel(job.dest_key or dkv.make_key(self.algo),
                               p, di)
        model.output["stacked_pt"] = StackedTrees.from_trees(trees_t)
        model.output["stacked_pc"] = StackedTrees.from_trees(trees_c)
        model.output["trees"] = TreeList(model.output["stacked_pt"])
        model.output["ntrees_trained"] = p.ntrees
        model.output["edges"] = binned.edges
        model.output["init_score"] = 0.0
        model.output["nclass_trees"] = 1
        model.output["hist_layout"] = hist_layout
        model.output["tree_program"] = tree_program

        from ...metrics.uplift import uplift_metrics
        X = model._design(frame)
        pred = np.asarray(model._predict_raw(X))[: frame.nrows, 0]
        model.training_metrics = uplift_metrics(
            pred, np.asarray(y)[: frame.nrows],
            np.asarray(treat)[: frame.nrows])
        return model
