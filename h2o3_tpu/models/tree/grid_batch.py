"""Batched grid cohorts: G same-shape grid members in ONE compiled program.

Reference: ``hex/grid/GridSearch.java`` runs every hyperparameter combo as
an independent training job.  On a TPU that is G dispatch streams for G
programs whose traced shape is IDENTICAL whenever the combo only varies
scalar hyperparameters (eta, sample rates, lambda/alpha/gamma, min_rows,
min_child_weight, min_split_improvement, seed) — everything that enters
the kernels as an operand, not a shape.

TPU-native redesign: partition the combo list into shape-compatible
COHORTS (same max_depth/nbins/ntrees/layout/..., see ``BATCHABLE``) and
grow each cohort with ``make_grid_scan_fn`` — the grid analog of the
multinomial K-tree batch: one histogram launch and one split launch per
level for ALL G members, per-member PRNG via vmapped key chains, scalar
hyperparameters as ``[G]`` operands.  A G-loop of sequential builds is
the bitwise oracle (the batched build's nk contract + the vmapped
threefry contract; tests/test_grid_batch.py).

Successive halving (``search_criteria={"successive_halving": True}``)
retires losing members mid-train through the traced ``alive [G]`` mask:
a retired member's row weights zero out, every split goes invalid, its
leaf values are zero and its margin column freezes — zero recompiles,
since ``alive`` is an operand of the one compiled program.

Anything shape-changing or path-changing (multinomial, EFB bundling,
sparse layout, DART, monotone constraints, CV folds, checkpoints) falls
back to the scheduler-parallel wave path in ``grid.py`` — raised here as
``CohortFallback`` with the reason.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: per-member knobs that batch as ``[G]`` operands (or per-member host
#: state, for ``seed``) — anything else changes the traced program and
#: therefore partitions cohorts
BATCHABLE = frozenset({
    "learn_rate", "sample_rate", "col_sample_rate",
    "col_sample_rate_per_tree", "reg_lambda", "reg_alpha", "gamma",
    "min_child_weight", "min_rows", "min_split_improvement", "seed",
})


class CohortFallback(Exception):
    """This cohort cannot ride the batched path — reroute its members
    through the scheduler-parallel wave path (the reason is the arg)."""


def _eligibility(builder_cls, p) -> Optional[str]:
    """Param-level disqualifiers, checked before any device work.
    Returns the fallback reason, or None when the member may batch."""
    if not getattr(builder_cls, "_grid_batchable", False):
        return f"{getattr(builder_cls, 'algo', builder_cls.__name__)} " \
               "has no batched-cohort trainer"
    if getattr(p, "nfolds", 0) and p.nfolds > 1:
        return "nfolds (CV folds already multiply the build)"
    if getattr(p, "checkpoint", None) is not None \
            or getattr(p, "warm_start", None) is not None:
        return "checkpoint/warm_start continuation"
    if getattr(p, "balance_classes", False):
        return "balance_classes"
    if getattr(p, "monotone_constraints", None):
        return "monotone_constraints"
    if getattr(p, "custom_distribution_func", None) is not None:
        return "custom_distribution_func"
    if getattr(p, "booster", "gbtree") == "dart":
        return "dart booster (per-tree drop state is sequential)"
    if str(getattr(p, "histogram_type", "auto")).lower() == "random":
        return "random histogram_type (per-seed bin edges cannot share " \
               "one binning)"
    if str(getattr(p, "split_mode", "auto")).lower() not in ("auto",
                                                             "fused"):
        return "split_mode (batched builds are fused-only)"
    if str(getattr(p, "hist_layout", "auto")).lower() not in ("auto",
                                                              "dense"):
        return "hist_layout (batched builds are dense-only)"
    if str(getattr(p, "efb", "auto")).lower() == "on":
        return "efb=on (bundled working codes are per-plan)"
    if getattr(p, "calibrate_model", False):
        return "calibrate_model"
    if getattr(p, "export_checkpoints_dir", None):
        return "export_checkpoints_dir"
    if getattr(p, "stream", False):
        return "stream mode"
    return None


def plan_cohorts(builder_cls, base_params: dict,
                 combos: Sequence[dict]) -> Tuple[List[List[int]],
                                                  List[Tuple[int, str]]]:
    """Partition combo indices into batchable cohorts.

    Returns ``(cohorts, rest)``: cohorts are index lists (len >= 2) whose
    members agree on every non-``BATCHABLE`` parameter; ``rest`` carries
    ``(index, reason)`` for members that must take the wave path
    (ineligible params, bad combos, or no shape-compatible partner).
    """
    groups: Dict[tuple, List[int]] = {}
    rest: List[Tuple[int, str]] = []
    for i, combo in enumerate(combos):
        try:
            b = builder_cls(**{**base_params, **combo})
        except Exception as e:                          # noqa: BLE001
            rest.append((i, f"builder rejected params: {e!r}"))
            continue
        reason = _eligibility(builder_cls, b.params)
        if reason is not None:
            rest.append((i, reason))
            continue
        key = tuple(sorted((k, repr(v)) for k, v in combo.items()
                           if k not in BATCHABLE))
        groups.setdefault(key, []).append(i)
    cohorts = []
    for key, members in groups.items():
        if len(members) >= 2:
            cohorts.append(members)
        else:
            rest.append((members[0],
                         "singleton cohort (no shape-compatible partner)"))
    return cohorts, rest


def _halving_rungs(G: int, ntrees: int, eta: float) -> List[Tuple[int,
                                                                  int]]:
    """Successive-halving schedule: ``[(tree_count, keep), ...]`` with
    geometric tree budgets and survivor counts (classic SHA: G members
    at ntrees/eta^R, keep G/eta each rung, final survivors train to
    completion).  Rung boundaries snap UP to the next scoring fence at
    run time (retirement decisions need fresh interval metrics)."""
    if eta <= 1.0 or G < 2:
        return []
    R = int(math.floor(math.log(G) / math.log(eta) + 1e-9))
    rungs = []
    for i in range(R):
        trees = int(math.ceil(ntrees / eta ** (R - i)))
        keep = int(math.ceil(G / eta ** (i + 1)))
        if trees >= ntrees or keep >= G:
            continue
        rungs.append((trees, keep))
    return rungs


def train_cohort(builder_cls, base_params: dict, combos: Sequence[dict],
                 frame, valid=None, search_criteria: Optional[dict] = None,
                 deadline: Optional[float] = None
                 ) -> List[Tuple[Optional[object], Optional[str]]]:
    """Train G shape-compatible grid members as ONE batched program.

    Mirrors GBM's fused single-class driver with the member axis G where
    the multinomial driver has the class axis K: shared binning/DataInfo/
    init (identical across members by cohort construction), per-member
    Jobs + recovery journals (resolved seeds journaled, so a killed
    cohort resumes each member through the normal sequential path), ONE
    device lease around the chunk loop, per-member unbatch into
    ``StackedTrees`` chunks, snapshots, interval scoring, early stopping
    and successive halving via the host-side alive mask.

    Returns ``[(model, None) | (None, error_str)]`` aligned with
    ``combos``.  Raises ``CohortFallback`` (before any journal exists)
    when a train-time property disqualifies the whole cohort.
    """
    from ...runtime import autotune, dkv, recovery, snapshot, xprof
    from ...runtime import observability as obs
    from ...runtime import scheduler as _sched
    from ...runtime.job import DONE, RUNNING, Job
    from .. import parallel
    from ..distributions import make_distribution
    from ..scorekeeper import METRIC_MAXIMIZE, metric_direction
    from .binning import edges_matrix, fit_bins
    from .shared import (StackedTrees, TreeList, chunk_schedule,
                         count_hist_kernel, effective_max_depth,
                         make_grid_scan_fn,
                         maybe_bundle, record_effective_depth,
                         traverse_jit, tree_snapshot_state)

    G = len(combos)
    if G < 2:
        raise CohortFallback("singleton cohort")
    builders = []
    for combo in combos:
        b = builder_cls(**{**base_params, **combo})
        # resolve seed=-1 ONCE and pin it: the journaled params must
        # regrow the same trees on per-member resume
        b.params = dataclasses.replace(b.params,
                                       seed=b.params.effective_seed())
        builders.append(b)
    rep = builders[0]
    p0 = rep.params
    rep._validate(frame)
    di = rep._make_datainfo(frame)
    if di.is_classifier and di.nclasses > 2:
        raise CohortFallback(
            "multinomial response (class trees already occupy the batch "
            "axis)")
    dist = make_distribution(p0.distribution, nclasses=di.nclasses,
                             tweedie_power=p0.tweedie_power,
                             quantile_alpha=p0.quantile_alpha,
                             huber_alpha=p0.huber_alpha)
    y = di.response(frame)
    w = di.weights(frame)
    y, f0_dev = rep._prep_targets(y, w, dist)
    # shared binning: quantile/uniform edges are seed-independent, so one
    # binning serves every member bitwise (random histograms fell back)
    binned = fit_bins(frame, [s.name for s in di.specs], nbins=p0.nbins,
                      seed=p0.seed,
                      weights=w if p0.weights_column else None,
                      histogram_type=p0.histogram_type)
    edges_mat = jnp.asarray(edges_matrix(binned.edges, p0.nbins),
                            jnp.float32)
    N = binned.codes.shape[1]
    plan, wcodes, Fw, _wbc = maybe_bundle(binned, p0, None, frame.nrows)
    if plan is not None:
        raise CohortFallback("EFB bundling engaged")
    knobs = autotune.resolve_tree_knobs(p0, kind=rep.algo, F=Fw, N=N, K=1,
                                        mono=None, plan=None,
                                        checkpoint=False)
    autotune.activate(knobs)
    hist_layout = knobs.hist_layout
    if hist_layout != "dense":
        # _eligibility already rerouted an explicit "sparse", so this is
        # auto-resolution picking the node-sparse layout as a perf
        # choice.  Layouts are bitwise-equal at equal effective depth
        # (tests/test_sparse_levels.py), and they only diverge through
        # the dense memory cap — so pin the cohort to dense whenever
        # dense can grow the same depth, and fall back only when it
        # genuinely caps the tree shallower.
        d_dense = effective_max_depth(p0.max_depth, p0.nbins, Fw, N,
                                      "dense")
        d_sparse = effective_max_depth(p0.max_depth, p0.nbins, Fw, N,
                                       "sparse",
                                       knobs.sparse_depth_threshold)
        if d_dense != d_sparse:
            raise CohortFallback(
                f"hist_layout={hist_layout} grows depth {d_sparse} but "
                f"dense caps at {d_dense} (batched cohorts are "
                "dense-only)")
        hist_layout = "dense"
    if knobs.split_mode != "fused":
        raise CohortFallback(f"split_mode={knobs.split_mode}")
    tree_program = knobs.tree_program
    if knobs.sparse_depth_threshold != p0.sparse_depth_threshold:
        for i, b in enumerate(builders):
            b.params = dataclasses.replace(
                b.params,
                sparse_depth_threshold=knobs.sparse_depth_threshold)
        p0 = builders[0].params
    try:
        scan_fn = make_grid_scan_fn(
            G, dist.name, p0.tweedie_power, p0.quantile_alpha,
            p0.huber_alpha, p0.max_depth, p0.nbins, Fw, N,
            p0.effective_hist_precision, hist_mode=knobs.hist_mode,
            tree_program=tree_program)
    except ValueError as e:
        raise CohortFallback(str(e))
    # one program grows the whole cohort: one count (no bin_counts here)
    count_hist_kernel(tree_program, p0.max_depth, p0.nbins, Fw, N,
                      hist_mode=knobs.hist_mode)

    algo = rep.algo
    obs.set_gauge("grid_cohort_size", float(G), algo=algo)
    obs.record("grid_cohort_start", algo=algo, size=G,
               tree_program=tree_program)

    models, jobs, journals = [], [], []
    for g, b in enumerate(builders):
        dest = dkv.make_key(algo)
        m = b.model_class(dest, b.params, di)
        m.output["distribution"] = dist.name
        m.output["binning"] = {"nbins": p0.nbins}
        m.output["nclass_trees"] = 1
        m.output["tree_program"] = tree_program
        m.output["grid_cohort"] = {"size": G, "member": g}
        record_effective_depth(m, b.params, Fw, N, hist_layout="dense")
        job = Job(f"{algo} train", dest_key=dest)
        models.append(m)
        jobs.append(job)
    # per-member journals AFTER every fallback check: a rerouted cohort
    # must not leave 'running' entries for the wave path to double-train
    for b, job in zip(builders, jobs):
        j = recovery.journal_start(b, frame, job)
        job.journal_uri = j
        journals.append(j)
        job.status = RUNNING
        job.start_time = time.time()
        job._mirror()

    if valid is not None:
        Xv = models[0]._design(valid)
        y_v, w_v = di.response(valid), di.weights(valid)
    f0 = float(f0_dev)
    from jax.sharding import NamedSharding, PartitionSpec
    from ...runtime.cluster import cluster
    # commit F to the replicated sharding the chunk outputs use — the
    # same silent-recompile trap the single-member driver decoded
    F = jax.device_put(
        jnp.broadcast_to(jnp.asarray(f0, jnp.float32), (G, N)),
        NamedSharding(cluster().mesh, PartitionSpec()))
    rng0G = jnp.stack([jax.random.PRNGKey(b.params.seed)
                       for b in builders])

    def arr(name):
        return jnp.asarray([float(getattr(b.params, name))
                            for b in builders], jnp.float32)

    head = (arr("reg_lambda"), arr("min_rows"),
            arr("min_split_improvement"), arr("learn_rate"),
            arr("col_sample_rate"), arr("sample_rate"),
            arr("col_sample_rate_per_tree"))
    tail = (arr("reg_alpha"), arr("gamma"), arr("min_child_weight"))
    metric_name, maximize = metric_direction(p0.stopping_metric,
                                             di.is_classifier)

    sc = dict(search_criteria or {})
    h_metric = sc.get("halving_metric") or metric_name
    h_maximize = METRIC_MAXIMIZE.get(h_metric, False) \
        if h_metric != metric_name else maximize
    rungs = _halving_rungs(G, p0.ntrees,
                           float(sc.get("halving_eta", 3.0))) \
        if sc.get("successive_halving") else []

    chunks: List[list] = [[] for _ in range(G)]
    histories: List[list] = [[] for _ in range(G)]
    alive = np.ones(G, bool)           # still growing trees
    failed: List[Optional[str]] = [None] * G
    nt = np.zeros(G, np.int64)         # trees trained per member
    Fvs = [jnp.broadcast_to(jnp.asarray(f0, jnp.float32),
                            (Xv.shape[0],))] * G if valid is not None \
        else None
    t_start = time.time()

    def member_failed(g: int, e: BaseException) -> None:
        failed[g] = repr(e)
        alive[g] = False
        obs.record("grid_member_failed", algo=algo, member=g,
                   error=repr(e))

    # the grid_member chaos/fault point fires per member here, exactly
    # like the wave path's per-build injection — a failing member becomes
    # a failed_entries row while its cohort siblings keep training
    from ...runtime import failure
    for g in range(G):
        try:
            failure.maybe_inject("grid_member")
        except Exception as e:                          # noqa: BLE001
            member_failed(g, e)

    prev_deadline = parallel.get_deadline()
    if deadline is not None:
        parallel.set_deadline(deadline)
    try:
        with _sched.device_slot():
            for chunk_no, (c, t_new, score_now) in enumerate(
                    chunk_schedule(p0.ntrees, p0.score_tree_interval)):
                if not alive.any():
                    break
                t_done = t_new
                aliveJ = jnp.asarray(alive)
                t0c = time.perf_counter()
                with obs.span("tree_chunk", job=jobs[0].key,
                              chunk=chunk_no, trees=c, cohort=G):
                    F, lv, vals, cov = scan_fn(wcodes, y, w, F, edges_mat,
                                               rng0G, chunk_no, c, *head,
                                               aliveJ, *tail)
                xprof.maybe_device_sync("tree_chunk", chunk_no, t0c, F)
                live = [g for g in range(G) if alive[g]]
                for g in live:
                    try:
                        lv_g = [tuple(lvd[i][:, g] for i in range(4))
                                for lvd in lv]
                        chunk = StackedTrees(lv_g, vals[:, g], cov[:, g])
                        chunks[g].append(chunk)
                        nt[g] = t_done
                        jobs[g].update(t_done / p0.ntrees,
                                       f"tree {t_done}/{p0.ntrees}")
                        snapshot.maybe_snapshot(
                            jobs[g], models[g],
                            {"trees_done": int(t_done),
                             "granularity": "tree_chunk"},
                            lambda cs=list(chunks[g]): tree_snapshot_state(
                                cs, f0, binned.edges))
                        if valid is not None:
                            Fvs[g] = Fvs[g] + traverse_jit(
                                chunk.levels, chunk.values, Xv)
                    except Exception as e:              # noqa: BLE001
                        member_failed(g, e)
                if not score_now:
                    continue
                for g in live:
                    if not alive[g]:
                        continue
                    try:
                        vstate = (Fvs[g], y_v, w_v) \
                            if valid is not None else None
                        if builders[g]._interval_score(
                                models[g], int(t_done), F[g], y, w, di,
                                dist, histories[g], vstate, metric_name,
                                maximize):
                            alive[g] = False    # member's own early stop
                    except Exception as e:              # noqa: BLE001
                        member_failed(g, e)
                # successive halving: at each rung fence, keep the best
                # `keep` members by metric; the rest retire through the
                # alive mask (same compiled program — zero recompiles)
                while rungs and t_done >= rungs[0][0]:
                    _, keep = rungs.pop(0)
                    live_now = [g for g in range(G)
                                if alive[g] and failed[g] is None]
                    if len(live_now) <= keep:
                        continue
                    key = f"valid_{h_metric}" if valid is not None \
                        else h_metric
                    worst = math.inf if h_maximize else -math.inf

                    def rank(g):
                        hh = histories[g][-1] if histories[g] else {}
                        v = hh.get(key)
                        return worst if v is None else v

                    ranked = sorted(live_now, key=rank,
                                    reverse=h_maximize)
                    for g in ranked[keep:]:
                        alive[g] = False
                        models[g].output["halving"] = {
                            "retired_at": int(t_done), "rung_keep": keep}
                        obs.inc("grid_members_retired_total", algo=algo)
                        obs.record("grid_member_retired", algo=algo,
                                   member=g, trees=int(t_done))
    except parallel.DeadlineExceeded:
        # cooperative max_runtime_secs: every member freezes at this
        # chunk fence and finalizes with the trees grown so far
        obs.record("grid_cohort_deadline", algo=algo,
                   trees=int(nt.max(initial=0)))
    finally:
        parallel.set_deadline(prev_deadline)

    results: List[Tuple[Optional[object], Optional[str]]] = []
    for g in range(G):
        if failed[g] is None and not chunks[g]:
            failed[g] = "DeadlineExceeded('max_runtime_secs deadline " \
                        "before the first tree chunk')"
        if failed[g] is not None:
            recovery.journal_fail(journals[g], failed[g])
            jobs[g].fail(RuntimeError(failed[g]))
            results.append((None, failed[g]))
            continue
        try:
            stacked = StackedTrees.concat(chunks[g])
            m = builders[g]._finalize_fused(
                models[g], di, dist, F[g], y, w, valid, histories[g],
                binned, f0, stacked.ntrees, stacked=stacked,
                trees=TreeList(stacked))
            m.output.setdefault("run_time_s", time.time() - t_start)
            m.output.setdefault("training_frame_rows", frame.nrows)
            builders[g]._post_fit(m, frame, valid)
            jobs[g].status = DONE
            jobs[g].progress = 1.0
            jobs[g].end_time = time.time()
            jobs[g]._done.set()
            jobs[g]._mirror()
            recovery.journal_done(journals[g])
            results.append((m, None))
        except Exception as e:                          # noqa: BLE001
            recovery.journal_fail(journals[g], repr(e))
            jobs[g].fail(e)
            results.append((None, repr(e)))
    return results
