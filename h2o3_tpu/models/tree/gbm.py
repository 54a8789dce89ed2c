"""GBM: gradient boosting machine on the tpu_hist kernels.

Reference: ``hex/tree/gbm/GBM.java:220`` (GBMDriver; buildNextKTrees:464,
growTrees:608, fitBestConstants:534) — per iteration: compute
pseudo-residuals (an MRTask), grow K trees layer-by-layer via
ScoreBuildHistogram2, fit leaf constants, score every score_tree_interval.

TPU-native redesign: the residual pass is one fused elementwise program
(distributions.py grad_hess), tree growth is the hist->split->partition
pipeline (hist.py), and leaf fitting is the Newton step from the final-level
leaf aggregation — numerically equivalent to fitBestConstants' per-
distribution formulas.  Multinomial grows K trees per iteration on softmax
gradients (buildNextKTrees's K-tree loop).
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...frame.frame import Frame
from ...runtime import dkv
from ...runtime import observability as obs
from ...runtime.job import Job
from ..datainfo import DataInfo
from ..distributions import make_distribution, Multinomial
from ..scorekeeper import stop_early, metric_direction
from .binning import fit_bins, edges_matrix
from .shared import (SharedTree, SharedTreeModel, SharedTreeParameters,
                     StackedTrees, Tree, TreeList, build_tree,
                     chunk_schedule, count_hist_kernel, dense_mem_cap,
                     make_build_tree_fn, make_tree_scan_fn, stack_trees,
                     traverse_jit)
from ...metrics.core import make_metrics


@dataclasses.dataclass
class GBMParameters(SharedTreeParameters):
    # custom loss UDF (water/udf/CDistributionFunc analog); see
    # distributions.CustomDistribution for the protocol
    custom_distribution_func: Optional[object] = None


class GBMModel(SharedTreeModel):
    algo = "gbm"

    def _predict_raw(self, X: jax.Array) -> jax.Array:
        F = self._raw_scores(X)
        dist = make_distribution(
            self.output["distribution"],
            nclasses=self.datainfo.nclasses,
            tweedie_power=self.params.tweedie_power,
            quantile_alpha=self.params.quantile_alpha,
            huber_alpha=self.params.huber_alpha,
            custom_distribution_func=getattr(
                self.params, "custom_distribution_func", None))
        if self.datainfo.is_classifier and self.datainfo.nclasses > 2:
            return jax.nn.softmax(F, axis=1)
        if self.datainfo.is_classifier:
            p1 = jnp.clip(dist.linkinv(F), 0.0, 1.0)
            return jnp.stack([1 - p1, p1], axis=1)
        return dist.linkinv(F)


class GBM(SharedTree):
    algo = "gbm"
    model_class = GBMModel
    # grid cohorts batch through the fused single-class path below
    # (grid_batch.py reuses _prep_targets/_interval_score/_finalize_fused)
    _grid_batchable = True

    def __init__(self, params: Optional[GBMParameters] = None, **kw):
        super().__init__(params or GBMParameters(**kw))

    def _finalize_fused(self, model, di, dist, F, y, w, valid, history,
                        binned, init_host, ntrees, stacked, trees):
        """Shared fused-path epilogue (single-class and multinomial)."""
        model.output["stacked"] = stacked
        model.output["trees"] = trees
        model.output["init_score"] = init_host
        model.output["ntrees_trained"] = ntrees
        model.output["edges"] = binned.edges
        model.scoring_history = history
        im = getattr(model, "_interval_metrics", None)
        if im is not None and im[0] == ntrees:
            # the final interval already scored this exact ensemble state
            model.training_metrics = im[1]
            if valid is not None and im[2] is not None:
                model.validation_metrics = im[2]
            elif valid is not None:
                model.validation_metrics = model.model_performance(valid)
            return model
        model.training_metrics = make_metrics(
            di, self._scores_to_preds(F, dist, di), y, w)
        if valid is not None:
            model.validation_metrics = model.model_performance(valid)
        return model

    def _fit(self, job: Job, frame: Frame, di: DataInfo,
             valid: Optional[Frame]) -> GBMModel:
        p: GBMParameters = self.params
        K = di.nclasses if (di.is_classifier and di.nclasses > 2) else 1
        dist = make_distribution(p.distribution, nclasses=di.nclasses,
                                 tweedie_power=p.tweedie_power,
                                 quantile_alpha=p.quantile_alpha,
                                 huber_alpha=p.huber_alpha,
                                 custom_distribution_func=getattr(
                                     p, "custom_distribution_func", None))
        multinomial = isinstance(dist, Multinomial) or K > 1
        if multinomial and getattr(p, "custom_distribution_func",
                                   None) is not None:
            raise ValueError(
                "custom_distribution_func is not supported for multinomial "
                "responses (the K-tree softmax path has its own gradients)")
        y = di.response(frame)
        w = di.weights(frame)
        from .shared import (resolve_checkpoint, checkpoint_binned,
                             prior_stacked, resolve_mono)
        y, f0_dev = self._prep_targets(y, w, dist)
        mono = resolve_mono(p, di)
        if mono is not None and multinomial:
            raise ValueError(
                "monotone_constraints: multinomial is not supported")
        prior = resolve_checkpoint(p, di, self.algo)
        if prior is not None:
            binned = checkpoint_binned(frame, di, prior, p.nbins)
        else:
            binned = fit_bins(frame, [s.name for s in di.specs],
                              nbins=p.nbins, seed=p.effective_seed(),
                              weights=w if p.weights_column else None,
                              histogram_type=p.histogram_type)
        codes = binned.codes
        edges_mat = jnp.asarray(
            edges_matrix(binned.edges, p.nbins), jnp.float32)
        N = codes.shape[1]
        # EFB: wide/sparse frames train on bundled working codes (efb.py);
        # the recorded trees stay in original feature space
        from .shared import maybe_bundle
        plan, wcodes, Fw, wbin_counts = maybe_bundle(binned, p, mono,
                                                     frame.nrows)
        # resolve the kernel-strategy knobs ONCE, up front: the layout
        # changes the effective-depth cap (node-sparse levels drop the
        # dense 64 MB histogram bound), so checkpoint validation and the
        # recorded depth must see the resolved layout, not the raw knob.
        # "auto" knobs route through the cost-model autotuner (a no-op
        # resolving to the fixed defaults with H2O3_TPU_AUTOTUNE=off);
        # activate() scopes sampled device timings to this decision.
        from ...runtime import autotune
        knobs = autotune.resolve_tree_knobs(
            p, kind=self.algo, F=Fw, N=N, K=K if multinomial else 1,
            mono=mono, plan=plan, checkpoint=prior is not None)
        autotune.activate(knobs)
        hist_mode, split_mode, hist_layout = (
            knobs.hist_mode, knobs.split_mode, knobs.hist_layout)
        tree_program = knobs.tree_program
        if knobs.sparse_depth_threshold != p.sparse_depth_threshold:
            # the tuned threshold must flow to EVERY consumer (effective
            # depth, scan factories, checkpoint validation, the params
            # echo records the effective value)
            p = dataclasses.replace(
                p, sparse_depth_threshold=knobs.sparse_depth_threshold)
        if prior is not None:
            from .shared import validate_checkpoint_depth
            validate_checkpoint_depth(prior, 0 if multinomial else None,
                                      p, Fw, N, hist_layout=hist_layout)
        seed = p.effective_seed()
        rng = jax.random.PRNGKey(seed)
        nprng = np.random.default_rng(seed)

        model = self.model_class(job.dest_key or dkv.make_key(self.algo),
                                 p, di)
        model.output["distribution"] = dist.name if not multinomial \
            else "multinomial"
        model.output["binning"] = {"nbins": p.nbins}
        model.output["nclass_trees"] = K
        from .shared import record_effective_depth
        eff_depth = record_effective_depth(model, p, Fw, N,
                                           hist_layout=hist_layout)
        # deep_level chaos hook fires only when sparse levels actually run
        sparse_deep = (hist_layout == "sparse" and eff_depth
                       > max(1, min(p.sparse_depth_threshold,
                                    dense_mem_cap(p.nbins, Fw))))
        if plan is not None:
            model.output["efb_bundles"] = sum(
                1 for w in plan.working if w[0] == "bundle")

        if valid is not None:
            Xv = model._design(valid)
            y_v, w_v = di.response(valid), di.weights(valid)

        if multinomial:
            yi = jnp.clip(y.astype(jnp.int32), 0, K - 1)
            Y1 = jax.nn.one_hot(yi, K, dtype=jnp.float32)
            base = jnp.sum(w[:, None] * Y1, axis=0) / jnp.maximum(jnp.sum(w), 1e-12)
            init = jnp.log(jnp.clip(base, 1e-10, 1.0))
            if prior is not None:
                init = jnp.asarray(prior.output["init_score"], jnp.float32)
            F = jnp.broadcast_to(init[None, :], (N, K)).astype(jnp.float32)
            F_v = jnp.broadcast_to(init[None, :], (Xv.shape[0], K)) \
                if valid is not None else None
            init_host = np.asarray(init)
        else:
            f0 = f0_dev if prior is None else prior.output["init_score"]
            F = jnp.broadcast_to(jnp.asarray(f0, jnp.float32), (N,))
            F_v = jnp.broadcast_to(jnp.asarray(f0, jnp.float32),
                                   (Xv.shape[0],)) \
                if valid is not None else None
            init_host = float(f0)
        # Commit F to the replicated sharding the scan chunk outputs use:
        # an uncommitted F0 and a committed chunk-output F key DIFFERENT
        # jit executables for the same scan program, i.e. a silent
        # recompile between chunk 1 and chunk 2.
        from jax.sharding import NamedSharding, PartitionSpec
        from ...runtime.cluster import cluster
        F = jax.device_put(F, NamedSharding(cluster().mesh, PartitionSpec()))
        prior_nt = 0
        if prior is not None:
            # continue from the checkpoint: F starts at its predictions
            prior_nt = prior.output["ntrees_trained"]
            # decorrelate the PRNG stream from the prior run: without this,
            # a fixed seed regenerates the SAME per-tree keys and the
            # continuation's row/column samples duplicate the prior trees'
            rng = jax.random.fold_in(rng, prior_nt)
            X_ck = model._design(frame)
            if multinomial:
                for k in range(K):
                    st = prior_stacked(prior, k)
                    F = F.at[:, k].add(traverse_jit(st.levels, st.values,
                                                    X_ck))
                    if valid is not None:
                        F_v = F_v.at[:, k].add(
                            traverse_jit(st.levels, st.values, Xv))
            else:
                st = prior_stacked(prior)
                F = F + traverse_jit(st.levels, st.values, X_ck)
                if valid is not None:
                    F_v = F_v + traverse_jit(st.levels, st.values, Xv)

        @jax.jit
        def grads_single(y, F):
            return dist.grad_hess(y, F)

        @jax.jit
        def grads_multi(Y1, F):
            Pr = jax.nn.softmax(F, axis=1)
            return Pr - Y1, jnp.maximum(Pr * (1 - Pr), 1e-10)

        # DART booster (XGBoost estimator): drop a random subset of prior
        # trees when computing gradients, then renormalize (libxgboost dart)
        dart = getattr(p, "booster", "gbtree") == "dart"
        X_tr = model._design(frame) if dart else None
        lr_build = 1.0 if dart else p.learn_rate

        def drop_sum(idx):
            if multinomial:
                outs = []
                for k in range(K):
                    levels, vals = stack_trees([trees[i][k] for i in idx])
                    outs.append(traverse_jit(levels, vals, X_tr))
                return jnp.stack(outs, axis=1)
            levels, vals = stack_trees([trees[i] for i in idx])
            return traverse_jit(levels, vals, X_tr)

        trees = []
        history = []
        metric_name, maximize = metric_direction(
            p.stopping_metric, di.is_classifier)
        fused = not multinomial and not dart
        fused_multi = multinomial and not dart
        model.output["tree_program"] = tree_program
        # the DART rounds below build without bin_counts (uniform kernels)
        count_hist_kernel(
            tree_program, p.max_depth, p.nbins, Fw, N,
            bin_counts=wbin_counts if fused or fused_multi else None,
            hist_mode=hist_mode, hist_layout=hist_layout,
            sparse_depth_threshold=p.sparse_depth_threshold)

        if fused_multi:
            # multinomial fast path: K class trees per round, a whole
            # scoring interval of rounds per dispatch
            from .shared import make_multinomial_scan_fn
            scan_fn = make_multinomial_scan_fn(
                K, p.max_depth, p.nbins, Fw, N,
                p.effective_hist_precision, p.sample_rate, p.col_sample_rate_per_tree,
                bin_counts=wbin_counts, plan=plan, hist_mode=hist_mode,
                split_mode=split_mode, hist_layout=hist_layout,
                sparse_depth_threshold=p.sparse_depth_threshold,
                tree_program=tree_program)
            scalars = (p.reg_lambda, p.min_rows, p.min_split_improvement,
                       p.learn_rate, p.col_sample_rate, p.reg_alpha, p.gamma,
                       p.min_child_weight)
            chunks_k = [[prior_stacked(prior, k)] if prior is not None
                        else [] for k in range(K)]
            from ...runtime import failure
            for chunk_no, (c, t_new, score_now) in enumerate(chunk_schedule(
                    p.ntrees - prior_nt, p.score_tree_interval,
                    fence=getattr(self, "_stream_fence", None))):
                t_done = prior_nt + t_new
                # chaos matrix: kill/resume mid-multinomial-round — each
                # chunk is a batch of K-tree rounds on the fused path
                failure.maybe_inject("ktree_round")
                if sparse_deep:
                    # kill/resume while node-sparse deep levels are live
                    failure.maybe_inject("deep_level")
                from ...runtime import xprof
                t0 = time.perf_counter()
                with obs.span("tree_chunk", job=job.key, chunk=chunk_no,
                              trees=c, classes=K):
                    F, lv, vals, cov = scan_fn(wcodes, Y1, w, F, edges_mat,
                                               rng, chunk_no, c, *scalars)
                # true device time for the whole K-tree chunk (sampled
                # block-until-ready; no-op with H2O3_TPU_DEVICE_TIMING=off)
                xprof.maybe_device_sync("tree_chunk", chunk_no, t0, F)
                for k in range(K):
                    lv_k = [tuple(lvd[i][:, k] for i in range(4))
                            for lvd in lv]
                    chunk = StackedTrees(lv_k, vals[:, k], cov[:, k])
                    chunks_k[k].append(chunk)
                    if valid is not None:
                        F_v = F_v.at[:, k].add(
                            traverse_jit(chunk.levels, chunk.values, Xv))
                job.update(t_done / p.ntrees, f"tree {t_done}/{p.ntrees}")
                from ...runtime import snapshot
                from .shared import tree_snapshot_state_multi
                snapshot.maybe_snapshot(
                    job, model,
                    {"trees_done": t_done, "granularity": "tree_chunk"},
                    lambda c=[list(ch) for ch in chunks_k]:
                        tree_snapshot_state_multi(c, init_host,
                                                  binned.edges))
                if not score_now:
                    continue
                vstate = (F_v, y_v, w_v) if valid is not None else None
                if self._interval_score(model, t_done, F, y, w, di, dist,
                                        history, vstate, metric_name,
                                        maximize):
                    break
            from .shared import TreeListMulti
            with obs.span("tree.finalize"):
                stacks = [StackedTrees.concat(ch) for ch in chunks_k]
                return self._finalize_fused(
                    model, di, dist, F, y, w, valid, history, binned,
                    init_host, stacks[0].ntrees, stacked=stacks,
                    trees=TreeListMulti(stacks))

        if fused:
            # fast path: scan a whole scoring interval of trees per dispatch
            scan_fn = make_tree_scan_fn(
                dist.name, p.tweedie_power, p.quantile_alpha, p.huber_alpha,
                p.max_depth, p.nbins, Fw, N, p.effective_hist_precision,
                p.sample_rate, p.col_sample_rate_per_tree,
                bin_counts=wbin_counts, mono=mono, plan=plan,
                custom_fn=getattr(p, "custom_distribution_func", None),
                hist_mode=hist_mode, split_mode=split_mode,
                hist_layout=hist_layout,
                sparse_depth_threshold=p.sparse_depth_threshold,
                tree_program=tree_program)
            scalars = (p.reg_lambda, p.min_rows, p.min_split_improvement,
                       p.learn_rate, p.col_sample_rate, p.reg_alpha, p.gamma,
                       p.min_child_weight)
            chunks = [prior_stacked(prior)] if prior is not None else []
            from ...runtime import failure
            for chunk_no, (c, t_new, score_now) in enumerate(chunk_schedule(
                    p.ntrees - prior_nt, p.score_tree_interval,
                    fence=getattr(self, "_stream_fence", None))):
                t_done = prior_nt + t_new
                if sparse_deep:
                    # kill/resume while node-sparse deep levels are live
                    failure.maybe_inject("deep_level")
                from ...runtime import xprof
                t0 = time.perf_counter()
                with obs.span("tree_chunk", job=job.key, chunk=chunk_no,
                              trees=c):
                    F, lv, vals, cov = scan_fn(wcodes, y, w, F, edges_mat,
                                               rng, chunk_no, c, *scalars, 0)
                # true device time for the whole tree chunk (sampled
                # block-until-ready; no-op with H2O3_TPU_DEVICE_TIMING=off)
                xprof.maybe_device_sync("tree_chunk", chunk_no, t0, F)
                chunk = StackedTrees(lv, vals, cov)
                chunks.append(chunk)
                job.update(t_done / p.ntrees, f"tree {t_done}/{p.ntrees}")
                from ...runtime import snapshot
                from .shared import tree_snapshot_state
                snapshot.maybe_snapshot(
                    job, model,
                    {"trees_done": t_done, "granularity": "tree_chunk"},
                    lambda c=list(chunks): tree_snapshot_state(
                        c, init_host, binned.edges))
                if valid is not None:
                    F_v = F_v + traverse_jit(chunk.levels, chunk.values, Xv)
                if not score_now:
                    continue
                vstate = (F_v, y_v, w_v) if valid is not None else None
                if self._interval_score(model, t_done, F, y, w, di, dist,
                                        history, vstate, metric_name,
                                        maximize):
                    break
            with obs.span("tree.finalize"):
                stacked = StackedTrees.concat(chunks)
                return self._finalize_fused(
                    model, di, dist, F, y, w, valid, history, binned,
                    init_host, stacked.ntrees, stacked=stacked,
                    trees=TreeList(stacked))

        if prior is not None:
            # materialized per-tree list continuation (DART / multinomial).
            # Copy the Tree objects: DART rescales trees[i].values in place,
            # which must not corrupt the checkpoint model still in the DKV.
            for t_prior in list(prior.output["trees"]):
                if isinstance(t_prior, list):
                    trees.append([dataclasses.replace(tc) for tc in t_prior])
                else:
                    trees.append(dataclasses.replace(t_prior))
        for t in range(prior_nt, p.ntrees):
            rng, ks, kc = jax.random.split(rng, 3)
            w_eff = w
            if p.sample_rate < 1.0:
                w_eff = w * jax.random.bernoulli(ks, p.sample_rate, (N,))
            tree_mask = None
            if p.col_sample_rate_per_tree < 1.0:
                m = nprng.random(binned.nfeatures) < p.col_sample_rate_per_tree
                if not m.any():
                    m[nprng.integers(binned.nfeatures)] = True
                tree_mask = m

            drop_idx = []
            S_D = None
            if dart and trees and nprng.random() >= getattr(p, "skip_drop", 0.0):
                md = nprng.random(len(trees)) < getattr(p, "rate_drop", 0.0)
                if getattr(p, "one_drop", False) and not md.any():
                    md[nprng.integers(len(trees))] = True
                drop_idx = list(np.flatnonzero(md))
                if drop_idx:
                    S_D = drop_sum(drop_idx)
            F_eff = F - S_D if S_D is not None else F

            if dart:
                kdrop, nu = len(drop_idx), p.learn_rate
                if kdrop:
                    if getattr(p, "normalize_type", "tree") == "forest":
                        a_scale = b_scale = 1.0 / (1.0 + nu)
                    else:
                        a_scale = kdrop / (kdrop + nu)
                        b_scale = 1.0 / (kdrop + nu)
                else:
                    a_scale, b_scale = 1.0, nu

            if multinomial:
                g, h = grads_multi(Y1, F_eff)
                # preserve the sequential loop's key sequence: one split
                # per class tree, whether or not the round is batched
                kks = []
                for k in range(K):
                    rng, kk = jax.random.split(rng)
                    kks.append(kk)
                from .hist import table_lookup
                if split_mode == "fused":
                    # DART candidate round on the batched path: ONE build
                    # grows all K class trees (one launch per level)
                    fnK = make_build_tree_fn(
                        p.max_depth, p.nbins, binned.nfeatures, N,
                        p.effective_hist_precision, hist_mode=hist_mode,
                        nk=K, split_mode="fused", hist_layout=hist_layout,
                        sparse_depth_threshold=p.sparse_depth_threshold,
                        tree_program=tree_program)
                    tmK = jnp.broadcast_to(
                        jnp.asarray(tree_mask, bool) if tree_mask
                        is not None else jnp.ones(binned.nfeatures, bool),
                        (K, binned.nfeatures))
                    levels, valsK, coverK, leafK = fnK(
                        codes, (g * w_eff[:, None]).T,
                        (h * w_eff[:, None]).T, w_eff, edges_mat,
                        jnp.stack(kks), p.reg_lambda, p.min_rows,
                        p.min_split_improvement, lr_build,
                        p.col_sample_rate, tmK, p.reg_alpha, p.gamma,
                        p.min_child_weight)
                    if dart:
                        valsK = valsK * b_scale
                    ktrees = [Tree([lv[0][k] for lv in levels],
                                   [lv[1][k] for lv in levels],
                                   [lv[2][k] for lv in levels],
                                   [lv[3][k] for lv in levels], valsK[k],
                                   cover=coverK[k]) for k in range(K)]
                    dF = jax.vmap(
                        lambda v, l: table_lookup(v[None, :], l,
                                                  v.shape[0])[0])(
                        valsK, leafK)
                    F = F + dF.T
                else:
                    ktrees = []
                    for k in range(K):
                        tree, leaf = build_tree(
                            codes, g[:, k] * w_eff, h[:, k] * w_eff, w_eff,
                            edges_mat, p.nbins,
                            p.max_depth, p.reg_lambda, p.min_rows,
                            p.min_split_improvement, lr_build, kks[k],
                            p.col_sample_rate, tree_mask,
                            p.reg_alpha, p.gamma, p.min_child_weight,
                            hist_precision=p.effective_hist_precision,
                            hist_mode=hist_mode, split_mode=split_mode,
                            hist_layout=hist_layout,
                            sparse_depth_threshold=p.sparse_depth_threshold,
                            tree_program=tree_program)
                        if dart:
                            tree.values = tree.values * b_scale
                        ktrees.append(tree)
                        dF = table_lookup(jnp.asarray(tree.values)[None, :],
                                          leaf, len(tree.values))[0]
                        F = F.at[:, k].add(dF)
                trees.append(ktrees)
                if dart and drop_idx:
                    for i in drop_idx:
                        for k in range(K):
                            trees[i][k].values = trees[i][k].values * a_scale
                    F = F - (1.0 - a_scale) * S_D
                if valid is not None and not dart:
                    for k in range(K):
                        levels, vals = stack_trees([ktrees[k]])
                        F_v = F_v.at[:, k].add(traverse_jit(levels, vals, Xv))
            else:
                g, h = grads_single(y, F_eff)
                tree, leaf = build_tree(
                    codes, g * w_eff, h * w_eff, w_eff, edges_mat, p.nbins,
                    p.max_depth, p.reg_lambda, p.min_rows,
                    p.min_split_improvement, lr_build, kc,
                    p.col_sample_rate, tree_mask,
                    p.reg_alpha, p.gamma, p.min_child_weight, mono=mono,
                    hist_precision=p.effective_hist_precision,
                    hist_mode=hist_mode, split_mode=split_mode,
                    hist_layout=hist_layout,
                    sparse_depth_threshold=p.sparse_depth_threshold,
                    tree_program=tree_program)
                tree.values = tree.values * b_scale
                trees.append(tree)
                from .hist import table_lookup
                F = F + table_lookup(jnp.asarray(tree.values)[None, :],
                                     leaf, len(tree.values))[0]
                if drop_idx:
                    for i in drop_idx:
                        trees[i].values = trees[i].values * a_scale
                    F = F - (1.0 - a_scale) * S_D
            job.update((t + 1) / p.ntrees, f"tree {t + 1}/{p.ntrees}")

            if ((t + 1) % p.score_tree_interval == 0) or t == p.ntrees - 1:
                if dart and valid is not None:
                    # DART rescales prior trees, so F_v can't be incremental
                    if multinomial:
                        for k in range(K):
                            levels, vals = stack_trees(
                                [tr[k] for tr in trees])
                            F_v = F_v.at[:, k].set(
                                init_host[k] + traverse_jit(levels, vals, Xv))
                    else:
                        levels, vals = stack_trees(trees)
                        F_v = init_host + traverse_jit(levels, vals, Xv)
                vstate = (F_v, y_v, w_v) if valid is not None else None
                self._score_and_log(model, t + 1, F, y, w, di, dist, history,
                                    vstate)
                if p.stopping_rounds:
                    key = (f"valid_{metric_name}" if valid is not None
                           else metric_name)
                    series = [hh.get(key) for hh in history
                              if hh.get(key) is not None]
                    if series and stop_early(series, p.stopping_rounds,
                                             p.stopping_tolerance, maximize):
                        break

        with obs.span("tree.finalize"):
            model.output["trees"] = trees
            model.output["init_score"] = init_host
            model.output["ntrees_trained"] = len(trees)
            model.output["edges"] = binned.edges
            model.scoring_history = history
            # F already holds the final training scores — no tree
            # re-traversal
            model.training_metrics = make_metrics(
                di, self._scores_to_preds(F, dist, di), y, w)
            if valid is not None:
                model.validation_metrics = model.model_performance(valid)
        return model
