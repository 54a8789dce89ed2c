"""DRF: distributed random forest on the tpu_hist kernels.

Reference: ``hex/tree/drf/DRF.java:30`` — the bootstrap+mtries variant of
SharedTree: each tree trains on a row sample (rate 1-1/e by default) with
per-split random feature subsets (mtries); predictions are the average of
per-tree leaf estimates (class probability / mean response).

TPU-native redesign: the "mean response per leaf" fit is expressed through
the same Newton machinery as GBM by setting grad=-y, hess=1 (leaf value
= sum(w*y)/sum(w)); mtries is a per-(leaf, feature) random mask pushed into
the split-search kernel; trees average instead of sum (init 0, divide by T).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...frame.frame import Frame
from ...runtime import dkv
from ...runtime.job import Job
from ..datainfo import DataInfo
from ..scorekeeper import stop_early, metric_direction
from .binning import fit_bins, edges_matrix
from .shared import (SharedTree, SharedTreeModel, SharedTreeParameters,
                     StackedTrees, TreeList, chunk_schedule,
                     count_hist_kernel, dense_mem_cap,
                     make_multinomial_scan_fn, make_tree_scan_fn,
                     traverse_jit)
from ...metrics.core import make_metrics


@dataclasses.dataclass
class DRFParameters(SharedTreeParameters):
    ntrees: int = 50
    max_depth: int = 20
    min_rows: float = 1.0
    sample_rate: float = 0.632           # DRF.java default (1 - 1/e)
    mtries: int = -1                     # -1: sqrt(F) cls / F/3 reg
    learn_rate: float = 1.0              # no shrinkage in a forest


class DRFModel(SharedTreeModel):
    algo = "drf"

    def _predict_raw(self, X: jax.Array) -> jax.Array:
        K = self.output.get("nclass_trees", 1)
        T = self.output["ntrees_trained"]
        F = self._raw_scores(X) / max(T, 1)
        if self.datainfo.is_classifier and K > 1:
            probs = jnp.clip(F, 0.0, 1.0)
            s = jnp.sum(probs, axis=1, keepdims=True)
            return probs / jnp.maximum(s, 1e-12)
        if self.datainfo.is_classifier:
            p1 = jnp.clip(F, 0.0, 1.0)
            return jnp.stack([1 - p1, p1], axis=1)
        return F


class DRF(SharedTree):
    algo = "drf"
    model_class = DRFModel
    # stays on the wave path: the forest driver's mtries/OOB bookkeeping
    # and per-class bootstrap sharing diverge from the fused GBM chunk
    # loop the batched cohort trainer mirrors
    _grid_batchable = False

    def __init__(self, params: Optional[DRFParameters] = None, **kw):
        super().__init__(params or DRFParameters(**kw))

    def _fit(self, job: Job, frame: Frame, di: DataInfo,
             valid: Optional[Frame]) -> DRFModel:
        p: DRFParameters = self.params
        K = di.nclasses if (di.is_classifier and di.nclasses > 2) else 1
        y = di.response(frame)
        w = di.weights(frame)
        from .shared import (resolve_checkpoint, checkpoint_binned,
                             prior_stacked)
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
        Fnum = binned.nfeatures
        y = jnp.where(jnp.isnan(y), 0.0, y)
        N = codes.shape[1]
        from .shared import maybe_bundle
        plan, wcodes, Fw, wbin_counts = maybe_bundle(binned, p, None,
                                                     frame.nrows)
        # resolve the kernel-strategy knobs ONCE, up front — the layout
        # changes the effective-depth cap, so checkpoint validation and
        # the recorded depth must see the resolved layout (see gbm.py);
        # "auto" knobs route through the cost-model autotuner
        from ...runtime import autotune
        knobs = autotune.resolve_tree_knobs(
            p, kind=self.algo, F=Fw, N=N, K=K,
            plan=plan, checkpoint=prior is not None)
        autotune.activate(knobs)
        hist_mode, split_mode, hist_layout = (
            knobs.hist_mode, knobs.split_mode, knobs.hist_layout)
        tree_program = knobs.tree_program
        if knobs.sparse_depth_threshold != p.sparse_depth_threshold:
            p = dataclasses.replace(
                p, sparse_depth_threshold=knobs.sparse_depth_threshold)
        if prior is not None:
            from .shared import validate_checkpoint_depth
            validate_checkpoint_depth(prior, 0 if K > 1 else None,
                                      p, Fw, N, hist_layout=hist_layout)
        rng = jax.random.PRNGKey(p.effective_seed())

        # mtries resolves against the WORKING feature count: the per-split
        # mask is drawn over working features, so a rate computed from the
        # original count would collapse to ~1 feature/split under bundling
        if p.mtries == -1:
            m = math.isqrt(Fw) if di.is_classifier else max(Fw // 3, 1)
            col_rate = max(min(m, Fw), 1) / Fw
        elif p.mtries == -2:
            col_rate = 1.0
        else:
            col_rate = max(min(p.mtries, Fw), 1) / Fw

        model = DRFModel(job.dest_key or dkv.make_key(self.algo), p, di)
        model.output["nclass_trees"] = K
        from .shared import record_effective_depth
        eff_depth = record_effective_depth(model, p, Fw, N,
                                           hist_layout=hist_layout)
        # deep_level chaos hook fires only when sparse levels actually run
        sparse_deep = (hist_layout == "sparse" and eff_depth
                       > max(1, min(p.sparse_depth_threshold,
                                    dense_mem_cap(p.nbins, Fw))))

        if K > 1:
            yi = jnp.clip(y.astype(jnp.int32), 0, K - 1)
            Y1 = jax.nn.one_hot(yi, K, dtype=jnp.float32)
            targets = [Y1[:, k] for k in range(K)]
        elif di.is_classifier:
            targets = [y]
        else:
            targets = [y]

        F_sum = jnp.zeros((N, K), jnp.float32) if K > 1 \
            else jnp.zeros((N,), jnp.float32)
        # commit to the chunk-output sharding — see gbm.py (avoids a second
        # jit executable keyed on uncommitted-vs-committed F)
        from jax.sharding import NamedSharding, PartitionSpec
        from ...runtime.cluster import cluster
        F_sum = jax.device_put(F_sum,
                               NamedSharding(cluster().mesh, PartitionSpec()))
        if valid is not None:
            Xv = model._design(valid)
            y_v, w_v = di.response(valid), di.weights(valid)
            F_v = jnp.zeros((Xv.shape[0], K), jnp.float32) if K > 1 \
                else jnp.zeros((Xv.shape[0],), jnp.float32)
        prior_nt = 0
        if prior is not None:
            prior_nt = prior.output["ntrees_trained"]
            # decorrelate the continuation's bootstrap keys from the prior
            # run (same-seed continuation must not regrow identical trees)
            rng = jax.random.fold_in(rng, prior_nt)
            X_ck = model._design(frame)
            for k in range(K):
                st = prior_stacked(prior, k if K > 1 else None)
                dF = traverse_jit(st.levels, st.values, X_ck)
                F_sum = F_sum.at[:, k].add(dF) if K > 1 else F_sum + dF
                if valid is not None:
                    dFv = traverse_jit(st.levels, st.values, Xv)
                    F_v = F_v.at[:, k].add(dFv) if K > 1 else F_v + dFv

        history = []
        metric_name, maximize = metric_direction(p.stopping_metric,
                                                 di.is_classifier)
        # mean-fit via the scan driver: grad = -y, hess = 1 -> leaf = mean(y);
        # a whole scoring interval of trees is one device dispatch.  The same
        # per-tree keys are reused across classes so every class sees the
        # same bootstrap sample per iteration (DRF.java samples once/tree).
        model.output["tree_program"] = tree_program
        count_hist_kernel(
            tree_program, p.max_depth, p.nbins, Fw, N,
            bin_counts=wbin_counts, hist_mode=hist_mode,
            hist_layout=hist_layout,
            sparse_depth_threshold=p.sparse_depth_threshold)
        # batched multiclass: one K-tree build per round (one hist + one
        # split launch per level for all K class trees) instead of K
        # sequential scans — identical keys (same fold_in structure), so
        # the sequential path below stays its oracle
        batched = split_mode == "fused" and K > 1
        if batched:
            scan_fn_k = make_multinomial_scan_fn(
                K, p.max_depth, p.nbins, Fw, N,
                p.effective_hist_precision, p.sample_rate, 1.0,
                bin_counts=wbin_counts, hist_mode=hist_mode,
                split_mode="fused", mode="drf", hist_layout=hist_layout,
                sparse_depth_threshold=p.sparse_depth_threshold,
                tree_program=tree_program)
        else:
            scan_fn = make_tree_scan_fn(
                "drf", 0.0, 0.0, 0.0, p.max_depth, p.nbins, Fw, N,
                p.effective_hist_precision, p.sample_rate, 1.0,
                bin_counts=wbin_counts, plan=plan, hist_mode=hist_mode,
                split_mode=split_mode, hist_layout=hist_layout,
                sparse_depth_threshold=p.sparse_depth_threshold,
                tree_program=tree_program)
        scalars = (p.reg_lambda, p.min_rows, p.min_split_improvement, 1.0,
                   col_rate, p.reg_alpha, p.gamma, p.min_child_weight)
        chunks = [[] for _ in range(K)]
        if prior is not None:
            for k in range(K):
                chunks[k].append(prior_stacked(prior, k if K > 1 else None))
        from ...runtime import failure
        for chunk_no, (c, t_new, score_now) in enumerate(chunk_schedule(
                p.ntrees - prior_nt, p.score_tree_interval,
                fence=getattr(self, "_stream_fence", None))):
            t_done = prior_nt + t_new
            if sparse_deep:
                # kill/resume while node-sparse deep levels are live
                failure.maybe_inject("deep_level")
            if batched:
                # chaos matrix: kill/resume mid-K-tree-round on the
                # batched path
                failure.maybe_inject("ktree_round")
                F_sum, lv, vals, cov = scan_fn_k(wcodes, Y1, w, F_sum,
                                                 edges_mat, rng, chunk_no,
                                                 c, *scalars)
                for k in range(K):
                    lv_k = [tuple(lvd[i][:, k] for i in range(4))
                            for lvd in lv]
                    chunk = StackedTrees(lv_k, vals[:, k], cov[:, k])
                    chunks[k].append(chunk)
                    if valid is not None:
                        F_v = F_v.at[:, k].add(
                            traverse_jit(chunk.levels, chunk.values, Xv))
            else:
                for k in range(K):
                    Fk0 = F_sum[:, k] if K > 1 else F_sum
                    # same (rng, chunk_no) across classes -> same bootstrap
                    # per iteration (DRF.java samples once per tree); the
                    # salt decorrelates each class tree's per-split feature
                    # subsets
                    Fk, lv, vals, cov = scan_fn(wcodes, targets[k], w, Fk0,
                                                edges_mat, rng, chunk_no, c,
                                                *scalars, k)
                    chunks[k].append(StackedTrees(lv, vals, cov))
                    if K > 1:
                        F_sum = F_sum.at[:, k].set(Fk)
                        if valid is not None:
                            F_v = F_v.at[:, k].add(
                                traverse_jit(lv, vals, Xv))
                    else:
                        F_sum = Fk
                        if valid is not None:
                            F_v = F_v + traverse_jit(lv, vals, Xv)
            job.update(t_done / p.ntrees, f"tree {t_done}/{p.ntrees}")
            from ...runtime import snapshot
            from .shared import (tree_snapshot_state,
                                 tree_snapshot_state_multi)
            init0 = np.zeros(K) if K > 1 else 0.0
            snapshot.maybe_snapshot(
                job, model,
                {"trees_done": t_done, "granularity": "tree_chunk"},
                (lambda c=[list(ch) for ch in chunks]:
                    tree_snapshot_state_multi(c, init0, binned.edges))
                if K > 1 else
                (lambda c=list(chunks[0]): tree_snapshot_state(
                    c, init0, binned.edges)))
            if not score_now:
                continue

            avg = F_sum / t_done
            raw = self._avg_to_preds(avg, di, K)
            m = make_metrics(di, raw, y, w)
            entry = {"iteration": t_done, **m.describe()}
            if valid is not None:
                mv = make_metrics(
                    di, self._avg_to_preds(F_v / t_done, di, K), y_v, w_v)
                entry.update({f"valid_{k2}": v for k2, v
                              in mv.describe().items()})
            history.append(entry)
            if p.stopping_rounds:
                key = (f"valid_{metric_name}" if valid is not None
                       else metric_name)
                series = [hh.get(key) for hh in history
                          if hh.get(key) is not None]
                if series and stop_early(series, p.stopping_rounds,
                                         p.stopping_tolerance, maximize):
                    break

        stacks = [StackedTrees.concat(ch) for ch in chunks]
        ntrees_trained = stacks[0].ntrees
        if K > 1:
            from .shared import TreeListMulti
            model.output["stacked"] = stacks
            model.output["trees"] = TreeListMulti(stacks)
        else:
            model.output["stacked"] = stacks[0]
            model.output["trees"] = TreeList(stacks[0])
        model.output["init_score"] = np.zeros(K) if K > 1 else 0.0
        model.output["ntrees_trained"] = ntrees_trained
        model.output["edges"] = binned.edges
        model.scoring_history = history
        # F_sum already holds the final ensemble scores — no re-traversal
        model.training_metrics = make_metrics(
            di, self._avg_to_preds(F_sum / max(ntrees_trained, 1), di, K),
            y, w)
        if valid is not None:
            model.validation_metrics = model.model_performance(valid)
        return model

    @staticmethod
    def _avg_to_preds(avg, di, K):
        if di.is_classifier and K > 1:
            pr = jnp.clip(avg, 0.0, 1.0)
            return pr / jnp.maximum(jnp.sum(pr, axis=1, keepdims=True), 1e-12)
        if di.is_classifier:
            p1 = jnp.clip(avg, 0.0, 1.0)
            return jnp.stack([1 - p1, p1], axis=1)
        return avg
