"""Cost-model-driven autotuner: the performance knobs choose themselves.

The tree pipeline carries seven interacting performance knobs
(``hist_mode``, ``hist_layout``, ``split_mode``,
``sparse_depth_threshold``, ``tree_program``, ``reduce_mode``, the
serving traversal ``impl``) whose best setting flips
with (shape, depth, K, mesh geometry) — the GPU tree-boosting literature
shows the histogram/split strategy genuinely inverts with bin count and
depth.  PR 10's compile ledger already publishes the signals a tuner
needs (``program_flops`` / ``program_bytes_accessed`` per seam, sampled
``tree_phase_device_seconds``), so this module closes the loop, TVM-style:

1. **Signature** — each build is keyed by
   ``(kind, F, log2(N), K, max_depth, nbins, mesh geometry, backend)``.
   Decisions are per signature, not per process: two jobs with the same
   shape share one decision; a different mesh is a different signature.

2. **Cost model seed** — every candidate configuration is scored by a
   roofline-style estimate built from the per-level histogram bytes/flops
   and compaction scatter rows the kernels in ``models/tree/hist.py``
   report (``hist_level_cost`` / ``split_search_passes``), each divided
   by the device's own figure for it (``_DEVICE_PEAKS``) and calibrated
   against the ledger's measured ``cost_analysis()`` figures when
   available.  The model's argmin is served immediately
   (``source="model"``) — no warm-up builds.

3. **Measured refinement** — with ``H2O3_TPU_DEVICE_TIMING`` sampling on,
   ``xprof.maybe_device_sync`` feeds true dispatch→ready seconds back via
   ``on_device_sample``; every ``autotune_explore_every``-th resolve of a
   model-seeded signature then runs the runner-up candidate instead
   (epsilon-greedy, deterministic counter — no RNG; with the sampling
   off no measurement can come back, so nothing is explored), so an early
   mis-prediction self-corrects: once two candidates carry measurements
   the faster one wins permanently (``source="measured"``).

4. **Warm-start cache** — decisions persist as JSON under
   ``<H2O3_TPU_RECOVERY_DIR>/autotune/`` (WAL-adjacent, atomic
   tmp+rename), keyed by signature + backend + jax version, so a fresh
   cluster skips straight to ``source="cache"`` and never re-measures.
   A corrupt or version-stale file silently degrades to model-seeded
   decisions — the tuner can never error a training path.  A
   ``cluster_reinit`` epoch bump (``invalidate()``, wired into
   ``cluster._invalidate_compiled_caches``) drops every in-memory
   decision AND the loaded file snapshot: a geometry change can never
   serve a stale choice.

The master switch is ``H2O3_TPU_AUTOTUNE`` = ``on`` (default) | ``off`` |
``cache_only``.  ``off`` resolves every ``"auto"`` knob to the historical
fixed default (subtract / fused / sparse-below-threshold / hier), giving
bit-identical kernels to the pre-tuner tree — tier-1 pins it.
``cache_only`` serves cached + model decisions but never explores.
Every value the tuner can pick is compared with its oracle value in the
parity suites (tests/test_hist_subtract.py, test_fused_splits.py,
test_sparse_levels.py, test_tree_scan.py) and, on the chip, by
``chip_smoke.py``'s ``parity_*`` phases.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
import threading
from typing import Dict, List, Optional, Tuple

from . import observability as obs

_lock = threading.RLock()

# signature -> decision entry; dropped wholesale by invalidate()
_DECISIONS: Dict[str, dict] = {}

# mirrors the xprof ledger epoch discipline: invalidate() bumps it and
# marks any already-loaded cache file dead for the rest of the process
_EPOCH = 0
_file_loaded = False
_file_dead = False

# threshold candidates the model ranks for sparse_depth_threshold="auto"
# (the default 8 is always a candidate, so "off" and "on" agree when the
# model finds no better setting)
_THRESHOLD_CANDIDATES = (4, 6, 8, 10)

# the int sentinel meaning "tune me": the dataclass default.  Any other
# user-set value is treated as pinned (see docs/operations.md).
DEFAULT_SPARSE_THRESHOLD = 8

# device_kind -> (peak flop/s, peak HBM bytes/s, device memory bytes,
# rows/s of a unique-index row scatter): the roofline seed, the
# batched-grid resident-state budget (a cohort holds G members' F vectors,
# gradients and level histograms at once, so batching loses outright when
# that estimate blows the memory) and the price of the smaller-sibling
# compaction (hist.hist_level_cost counts its rows).  A device kind that is
# not in the table is an error, not a default.
_DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s,
    # 16 GB HBM per chip.  Scatter: PERF_LEDGER.jsonl, PR 28,
    # xgb_airlines40m.fit: `.at[:, target].set(unique_indices=True)` of the
    # [8, 40M] code planes 16.50 s and of the [3, 40M] g/h/w planes 16.15 s
    # over 5 levels, 12.1 and 12.4 M rows/s (re-read with hist_mode pinned
    # in PR 29, PERF.md section 6: 16.52 s and 16.16 s)
    "TPU v5 lite": (1.97e14, 8.19e11, 1.6e10, 1.2e7),
    # the CPU test mesh: coarse, only candidate *ranking* matters (the same
    # two scatters at 4M rows under XLA:CPU read 40 and 82 M rows/s)
    "cpu": (5.0e10, 5.0e10, 8.0e9, 5.0e7),
}

# per-dispatch overhead for the tree_program dimension: each kernel
# program the build launches separately costs roughly this much in
# driver/dispatch latency.  The level-unrolled build pays it 2*depth
# times per tree (hist + split records per level), the scan-fused build
# O(1) times — this term is what makes the padded-width scan win on deep
# trees at modest N.
_DISPATCH_OVERHEAD_S = 5e-4

# thread-local measurement scope: the decision entry whose chosen config
# is currently executing on this thread (drivers activate it at resolve)
_tls = threading.local()


# ------------------------------------------------------------------ mode

def autotune_mode() -> str:
    """Effective ``H2O3_TPU_AUTOTUNE``: ``on`` | ``off`` | ``cache_only``
    (unknown values read as ``off`` — misconfiguration never tunes)."""
    from .config import config
    mode = config().autotune
    return mode if mode in ("on", "cache_only") else "off"


def _explore_every() -> int:
    from .config import config
    return max(int(config().autotune_explore_every), 2)


# ------------------------------------------------------------- signature

def _device():
    """The device the kernels are built for: the live mesh's first (the
    same one every kernel seam keys its branch on), else jax's default."""
    from .cluster import _cluster
    if _cluster is not None:
        return _cluster.mesh.devices.flat[0]
    import jax
    return jax.devices()[0]


def _backend() -> str:
    return _device().platform


def _jax_version() -> str:
    try:
        import jax
        return jax.__version__
    except Exception:                    # noqa: BLE001
        return "unknown"


def _mesh_geometry() -> Tuple[int, int, int]:
    """(hosts, chips, model) of the live mesh; falls back to the flat
    device count so the tuner works before (or without) cluster init."""
    try:
        from .cluster import _cluster
        if _cluster is not None:
            s = dict(_cluster.mesh.shape)
            return (s.get("hosts", 1), s.get("chips", 1), s.get("model", 1))
    except Exception:                    # noqa: BLE001
        pass
    try:
        import jax
        return (1, jax.device_count(), 1)
    except Exception:                    # noqa: BLE001
        return (1, 1, 1)


def _signature(kind: str, F: int, N: int, K: int, max_depth: int,
               nbins: int) -> str:
    hosts, chips, model = _mesh_geometry()
    nb = int(math.log2(max(N, 1))) if N else 0
    return (f"{kind}:F{F}:N2^{nb}:K{K}:d{max_depth}:b{nbins}"
            f":mesh{hosts}x{chips}x{model}:{_backend()}")


# ------------------------------------------------------------ cost model

def _peaks() -> Tuple[float, float, float, float]:
    """(peak flop/s, peak HBM bytes/s, device memory bytes, scatter rows/s)
    of the device the kernels are built for."""
    kind = _device().device_kind
    try:
        return _DEVICE_PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no peaks recorded for device_kind {kind!r}; add a sourced "
            f"row to autotune._DEVICE_PEAKS (known: "
            f"{sorted(_DEVICE_PEAKS)})") from None


def _ledger_calibration() -> float:
    """Bytes-per-second scale factor from the compile ledger: when the
    tree scan program reports ``bytes_accessed`` and a measured device
    time exists, trust achieved bandwidth over the roofline constant."""
    try:
        from . import xprof
        snap = xprof.ledger_programs()
        for name in ("tree_scan", "tree_scan_multinomial", "tree_build"):
            ent = snap.get(name)
            if ent and ent.get("bytes_accessed"):
                # achieved bandwidth unknown without a paired wall time;
                # the ledger figure still rescales CPU-vs-TPU sanely
                return 1.0
    except Exception:                    # noqa: BLE001
        pass
    return 1.0


def _predict_tree_cost(F: int, N: int, K: int, max_depth: int, nbins: int,
                       *, hist_mode: str, split_mode: str,
                       hist_layout: str, threshold: int,
                       tree_program: str = "level") -> float:
    """Roofline seconds for one K-tree build under one candidate config.

    Per-level byte/flop counts and the rows a subtract level's compaction
    scatters come from ``hist.hist_level_cost`` /
    ``hist.split_search_passes`` so the estimate lives next to the
    kernels it models; infeasible configs (dense grid over the histogram
    budget) price at +inf and can never win.  The scatters feed the
    kernel, so their seconds add to the roofline term.

    ``tree_program="scan"`` runs every level past the root at the padded
    width 2^(max_depth-1) (one fixed-width program) but dispatches O(1)
    kernel programs instead of 2*depth — the ``_DISPATCH_OVERHEAD_S``
    term carries that tradeoff, so deep trees at modest N pick the scan
    and wide shallow frames keep per-level programs."""
    from ..models.tree.hist import hist_level_cost, split_search_passes
    peak_f, peak_b, _, scatter_rows_s = _peaks()
    B = nbins + 1
    total_bytes = 0.0
    total_flops = 0.0
    total_scatter_rows = 0.0
    for d in range(max_depth):
        layout_d = ("sparse" if hist_layout == "sparse" and d >= threshold
                    else "dense")
        width = 2 ** (max_depth - 1) if (tree_program == "scan" and d > 0) \
            else 2 ** d
        cost = hist_level_cost(N, F, B, width, K,
                               layout=layout_d, hist_mode=hist_mode)
        if cost is None:
            return float("inf")
        level_bytes, scatter_rows = cost
        total_bytes += level_bytes * split_search_passes(split_mode)
        total_scatter_rows += scatter_rows
        # one multiply-add per (row, feature, class) scatter contribution
        rows = N if (hist_mode == "full" or d == 0) else N // 2
        total_flops += 2.0 * rows * F * K
    launches = 2 if tree_program == "scan" else 2 * max_depth
    return (max(total_flops / peak_f, total_bytes / peak_b)
            * _ledger_calibration()
            + total_scatter_rows / scatter_rows_s
            + launches * _DISPATCH_OVERHEAD_S)


def _tree_candidates(F: int, N: int, K: int, max_depth: int, nbins: int,
                     *, mono, plan, tuned: dict) -> List[dict]:
    """Joint candidate configs over the knobs being tuned; knobs pinned by
    the user keep their pinned value in every candidate.  The same
    feature-compat downgrades the shared.py resolvers apply constrain the
    space, so a candidate is always runnable."""
    from ..models.tree.shared import dense_mem_cap, sparse_layout_active
    hist_modes = (("subtract", "full") if tuned.get("hist_mode")
                  else (tuned.get("_hist_mode_pin", "subtract"),))
    split_modes = (("fused", "separate") if tuned.get("split_mode")
                   else (tuned.get("_split_mode_pin", "fused"),))
    if mono is not None or plan is not None:
        split_modes = ("separate",)
    # the scan-fused program composes with the dense layout only (under
    # either histogram kernel, uniform or variable-bin: the builder asks
    # shared.hist_site_kernel at the scan's width) and needs >= 2
    # effective levels.  The depth gate is conservative
    # w.r.t. the builder (row cap from N <= n_padded), so a tuner-picked
    # "scan" can never hit the builder's fail-fast validation.
    row_cap = max(1, int(math.ceil(math.log2(max(N, 2)))) + 1)
    from ..models.tree.shared import dense_mem_cap as _dmc
    scan_ok = (mono is None and plan is None
               and min(max_depth, row_cap, _dmc(nbins, F)) >= 2)
    progs = (("level", "scan") if tuned.get("tree_program")
             else (tuned.get("_tree_program_pin", "level"),))
    out = []
    for hm in hist_modes:
        layouts: Tuple[Tuple[str, int], ...]
        sparse_ok = sparse_layout_active("auto", hm, mono=mono, plan=plan)
        cap = max(1, dense_mem_cap(nbins, F))
        if tuned.get("hist_layout"):
            layouts = (("dense", max_depth),)
            if sparse_ok:
                cands = (_THRESHOLD_CANDIDATES
                         if tuned.get("sparse_depth_threshold")
                         else (tuned.get("_threshold_pin",
                                         DEFAULT_SPARSE_THRESHOLD),))
                layouts += tuple(("sparse", min(t, cap)) for t in cands
                                 if t < max_depth)
        else:
            pin = tuned.get("_hist_layout_pin", "sparse")
            t_pin = min(tuned.get("_threshold_pin",
                                  DEFAULT_SPARSE_THRESHOLD), cap)
            layouts = ((pin, t_pin if pin == "sparse" else max_depth),)
            if pin == "sparse" and tuned.get("sparse_depth_threshold") \
                    and sparse_ok:
                layouts = tuple(("sparse", min(t, cap))
                                for t in _THRESHOLD_CANDIDATES
                                if t < max_depth) or layouts
        for sm in split_modes:
            for layout, thr in dict.fromkeys(layouts):
                if layout == "sparse" and not sparse_ok:
                    continue
                for tp in progs:
                    if tp == "scan" and (layout == "sparse"
                                         or not scan_ok):
                        continue
                    out.append({"hist_mode": hm, "split_mode": sm,
                                "hist_layout": layout,
                                "sparse_depth_threshold": int(thr),
                                "tree_program": tp})
    # dedupe while keeping model-preferred ordering stable
    seen, uniq = set(), []
    for c in out:
        k = _cand_key(c)
        if k not in seen:
            seen.add(k)
            uniq.append(c)
    return uniq


def _cand_key(c: dict) -> str:
    # stale cached choices keyed without the |p segment fall through
    # _decide's candidate-membership re-pick — no migration needed
    return (f"{c['hist_mode']}|{c['split_mode']}|{c['hist_layout']}"
            f"|t{c['sparse_depth_threshold']}"
            f"|p{c.get('tree_program', 'level')}")


def _predict_costs(F: int, N: int, K: int, max_depth: int, nbins: int,
                   candidates: List[dict]) -> Dict[str, float]:
    """Per-candidate roofline seconds (tests monkeypatch this to force a
    wrong model and prove measured refinement self-corrects)."""
    return {
        _cand_key(c): _predict_tree_cost(
            F, N, K, max_depth, nbins, hist_mode=c["hist_mode"],
            split_mode=c["split_mode"], hist_layout=c["hist_layout"],
            threshold=c["sparse_depth_threshold"],
            tree_program=c.get("tree_program", "level"))
        for c in candidates
    }


# ------------------------------------------------------------- decisions

def _note_decision(knobs: dict, source: str) -> None:
    for knob, choice in knobs.items():
        obs.inc("autotune_decisions_total", knob=knob, choice=str(choice),
                source=source)


def _publish_cache_gauge() -> None:
    obs.set_gauge("autotune_cache_entries", float(len(_DECISIONS)))


def _measured_best(ent: dict) -> Optional[str]:
    """Candidate key with the lowest measured EMA, when at least two
    candidates carry measurements (one measurement proves nothing about
    the alternatives)."""
    meas = {k: v["ema"] for k, v in ent["measured"].items() if v["n"] > 0}
    if len(meas) < 2:
        return None
    return min(meas, key=meas.get)


def _decide(sig: str, candidates: List[dict], predicted: Dict[str, float],
            mode: str) -> dict:
    """Look up / create the decision entry for ``sig`` and pick the config
    to RUN this resolve (usually the decision; sometimes the epsilon
    exploration of the runner-up).  The runner-up is run only where its
    measurement can come back: ``xprof.maybe_device_sync`` is the one
    source of samples and returns at once while the device timing is off,
    so an exploration then would trace and compile a second tree program
    and learn nothing."""
    from . import xprof
    ent = _DECISIONS.get(sig)
    if ent is None:
        cached = _load_cached_entry(sig)
        if cached is not None:
            ent = cached
        else:
            best = min(predicted, key=predicted.get)
            ent = {"sig": sig, "choice": best, "source": "model",
                   "predicted": predicted, "measured": {}, "resolves": 0,
                   "explore": None, "epoch": _EPOCH}
        _DECISIONS[sig] = ent
        ent["candidates"] = {_cand_key(c): c for c in candidates}
        _publish_cache_gauge()
    ent.setdefault("candidates", {_cand_key(c): c for c in candidates})
    for c in candidates:                 # constraint set may have grown
        ent["candidates"].setdefault(_cand_key(c), c)
    ent["resolves"] += 1
    run_key = ent["choice"]
    ent["explore"] = None
    if (mode == "on" and ent["source"] in ("model", "measured")
            and obs.enabled() and xprof.device_timing_mode() != "off"
            and len(ent["candidates"]) > 1
            and ent["resolves"] % _explore_every() == 0):
        # deterministic epsilon-greedy: re-measure the best *other*
        # candidate by predicted cost so a mis-seeded model gets evidence
        others = {k: v for k, v in ent["predicted"].items()
                  if k != ent["choice"] and k in ent["candidates"]
                  and v != float("inf")}
        if others:
            run_key = min(others, key=others.get)
            ent["explore"] = run_key
    if run_key not in ent["candidates"]:
        run_key = ent["choice"] = min(
            (k for k in ent["candidates"]),
            key=lambda k: ent["predicted"].get(k, float("inf")))
    return {"entry": ent, "run_key": run_key,
            "run": ent["candidates"][run_key]}


def on_device_sample(phase: str, seconds: float) -> None:
    """Measurement sink for ``xprof.maybe_device_sync``: attribute one
    true device-phase timing to the config currently executing under the
    active decision scope, and let the evidence overturn the model."""
    scope = getattr(_tls, "scope", None)
    if scope is None or autotune_mode() != "on" \
            or not phase.startswith("tree"):
        return
    sig, run_key = scope
    with _lock:
        ent = _DECISIONS.get(sig)
        if ent is None or ent["source"] == "cache":
            return
        m = ent["measured"].setdefault(run_key, {"ema": 0.0, "n": 0})
        m["ema"] = seconds if m["n"] == 0 \
            else 0.7 * m["ema"] + 0.3 * seconds
        m["n"] += 1
        best = _measured_best(ent)
        if best is not None and best != ent["choice"]:
            old = ent["choice"]
            ent["choice"] = best
            ent["source"] = "measured"
            obs.record("autotune_flip", sig=sig, old=old, new=best)
            _note_decision({"config": best}, "measured")
        elif best is not None:
            ent["source"] = "measured"
    _save_cache()


@contextlib.contextmanager
def _measurement_scope(sig: Optional[str], run_key: Optional[str]):
    prev = getattr(_tls, "scope", None)
    _tls.scope = (sig, run_key) if sig is not None else None
    try:
        yield
    finally:
        _tls.scope = prev


def activate(knobs: "TreeKnobs") -> None:
    """Pin the measurement scope for the calling (driver) thread: device
    samples taken until the next ``activate``/``deactivate`` on this
    thread attribute to this resolve's running config."""
    _tls.scope = (knobs.sig, knobs.run_key) if knobs.sig else None


def deactivate() -> None:
    _tls.scope = None


# ------------------------------------------------------------ tree knobs

@dataclasses.dataclass(frozen=True)
class TreeKnobs:
    """One resolve's effective kernel-strategy knobs (builder values)."""
    hist_mode: str
    split_mode: str
    hist_layout: str                     # dense | sparse
    sparse_depth_threshold: int
    tree_program: str                    # level | scan
    sources: dict                        # knob -> user|default|model|...
    sig: Optional[str] = None            # signature when the tuner engaged
    run_key: Optional[str] = None        # config key actually running


def resolve_tree_knobs(params, *, kind: str, F: int, N: int, K: int = 1,
                       mono=None, plan=None,
                       checkpoint: bool = False) -> TreeKnobs:
    """The drivers' single up-front knob resolution point.

    Explicit knob values (anything but ``"auto"``) pass straight through
    the shared.py resolvers untouched.  ``"auto"`` knobs resolve to the historical
    fixed defaults when the tuner is off (bit-identical kernels), or to
    the per-signature decision when it is on.  Checkpoint continuations
    pin ``sparse_depth_threshold`` to the params value so resumed trees
    keep the depth ledger they were validated against."""
    from ..models.tree.shared import (resolve_hist_layout,
                                      resolve_hist_mode,
                                      resolve_split_mode,
                                      resolve_tree_program)
    hm_raw = str(getattr(params, "hist_mode", "auto")).lower()
    sm_raw = str(getattr(params, "split_mode", "auto")).lower()
    hl_raw = str(getattr(params, "hist_layout", "auto")).lower()
    tp_raw = str(getattr(params, "tree_program", "auto")).lower()
    thr_raw = int(getattr(params, "sparse_depth_threshold",
                          DEFAULT_SPARSE_THRESHOLD))
    max_depth = int(getattr(params, "max_depth", 5))
    nbins = int(getattr(params, "nbins", 64))

    # the baseline resolution every path starts from (validation +
    # feature-compat downgrades live in shared.py, exactly as before)
    hist_mode = resolve_hist_mode(params)
    split_mode = resolve_split_mode(params, mono=mono, plan=plan)
    hist_layout = resolve_hist_layout(params, hist_mode=hist_mode,
                                      mono=mono, plan=plan)
    tree_program = resolve_tree_program(params, hist_layout=hist_layout,
                                        mono=mono, plan=plan, F=F)
    sources = {
        "hist_mode": "default" if hm_raw == "auto" else "user",
        "split_mode": "default" if sm_raw == "auto" else "user",
        "hist_layout": "default" if hl_raw == "auto" else "user",
        "sparse_depth_threshold":
            "default" if thr_raw == DEFAULT_SPARSE_THRESHOLD else "user",
        "tree_program": "default" if tp_raw == "auto" else "user",
    }
    tuned = {
        "hist_mode": hm_raw == "auto",
        "split_mode": sm_raw == "auto",
        "hist_layout": hl_raw == "auto",
        "sparse_depth_threshold":
            thr_raw == DEFAULT_SPARSE_THRESHOLD and not checkpoint
            and hist_layout in ("sparse", "auto"),
        # uplift's bespoke two-arm grow loop has no scan-fused build, so
        # its signature never tunes tree_program (the pin stays "level")
        "tree_program": tp_raw == "auto" and kind != "uplift",
        "_hist_mode_pin": hist_mode,
        "_split_mode_pin": split_mode,
        "_hist_layout_pin": hist_layout,
        "_threshold_pin": thr_raw,
        "_tree_program_pin": tree_program,
    }
    mode = autotune_mode()
    # off bypasses everything; so does a fit with every knob pinned
    if (mode == "off"
            or not any(tuned[k] for k in ("hist_mode", "split_mode",
                                          "hist_layout",
                                          "sparse_depth_threshold",
                                          "tree_program"))):
        return TreeKnobs(hist_mode, split_mode, hist_layout, thr_raw,
                         tree_program, sources)

    sig = _signature(kind, F, N, K, max_depth, nbins)
    with _lock:
        candidates = _tree_candidates(F, N, K, max_depth, nbins, mono=mono,
                                      plan=plan, tuned=tuned)
        if not candidates:
            return TreeKnobs(hist_mode, split_mode, hist_layout, thr_raw,
                             tree_program, sources)
        predicted = _predict_costs(F, N, K, max_depth, nbins, candidates)
        picked = _decide(sig, candidates, predicted, mode)
        ent, run = picked["entry"], picked["run"]
        knobs_out = {}
        for knob in ("hist_mode", "split_mode", "hist_layout",
                     "sparse_depth_threshold", "tree_program"):
            if tuned[knob]:
                knobs_out[knob] = run[knob]
                sources[knob] = ("explore" if picked["run_key"] ==
                                 ent["explore"] else ent["source"])
        _note_decision(knobs_out, ent["source"])
    _save_cache()
    return TreeKnobs(
        knobs_out.get("hist_mode", hist_mode),
        knobs_out.get("split_mode", split_mode),
        knobs_out.get("hist_layout", hist_layout),
        int(knobs_out.get("sparse_depth_threshold", thr_raw)),
        knobs_out.get("tree_program", tree_program),
        sources, sig=sig, run_key=picked["run_key"])


def resolve_grid_batch(*, kind: str, F: int, N: int, G: int,
                       max_depth: int, nbins: int, K: int = 1) -> str:
    """``grid_batch="auto"``: price ONE batched G-member cohort program
    against G scheduler-parallel builds; returns ``"batched"`` or
    ``"parallel"``.

    The batched program does the same histogram/split compute but pays
    the per-level dispatch overhead once instead of G times — so it wins
    on dispatch-bound shapes — while holding G x the model state (F
    vector, gradients, level histograms + carry) resident at once, so it
    loses when that estimate blows the device memory budget.  The choice
    key carries a ``|g{G}`` segment (cohort size is part of the
    decision, like ``|p`` for tree_program).  Off-mode keeps the same
    fixed model decision without recording: the knob is a performance
    choice, not a correctness one, and the wave path stays the oracle."""
    common = dict(hist_mode="subtract", split_mode="fused",
                  hist_layout="dense",
                  threshold=DEFAULT_SPARSE_THRESHOLD)
    batched = _predict_tree_cost(F, N, K * G, max_depth, nbins, **common)
    seq = G * _predict_tree_cost(F, N, K, max_depth, nbins, **common)
    B = nbins + 1
    W = 2 ** max(max_depth - 1, 0)
    # resident cohort state: F/g/h/w row vectors plus the level
    # histogram and its subtraction carry, x G members x K class trees
    state = float(G) * K * (16.0 * N + 2 * 3.0 * W * F * B * 4.0)
    budget = _peaks()[2]
    choice = "parallel" if (state > budget
                            or not math.isfinite(batched)
                            or batched >= seq) else "batched"
    if autotune_mode() == "off":
        return choice
    key = f"{choice}|g{G}"
    with _lock:
        sig = _signature(kind, F, N, K, max_depth, nbins) + ":grid"
        ent = _DECISIONS.get(sig)
        if ent is None:
            _DECISIONS[sig] = ent = {
                "sig": sig, "choice": key, "source": "model",
                "predicted": {f"batched|g{G}": batched,
                              f"parallel|g{G}": seq},
                "measured": {}, "resolves": 0, "explore": None,
                "epoch": _EPOCH, "candidates": {}}
            _note_decision({"grid_batch": key}, "model")
            _publish_cache_gauge()
        ent["resolves"] += 1
    _save_cache()
    return choice


# -------------------------------------------------- reduce / serve knobs

def resolve_reduce_mode_auto() -> str:
    """``reduce_mode="auto"``: hier when a DCN (multi-host) stage exists
    — the staged psum moves an already-reduced tensor across hosts — and
    flat on a single host, where the extra stage is pure overhead.  Off
    keeps the historical fixed default (``hier``)."""
    if autotune_mode() == "off":
        return "hier"
    hosts, _, _ = _mesh_geometry()
    choice = "hier" if hosts > 1 else "flat"
    with _lock:
        sig = f"reduce:mesh{hosts}:{_backend()}"
        if sig not in _DECISIONS:
            _DECISIONS[sig] = {"sig": sig, "choice": choice,
                               "source": "model", "predicted": {},
                               "measured": {}, "resolves": 0,
                               "explore": None, "epoch": _EPOCH,
                               "candidates": {}}
            _note_decision({"reduce_mode": choice}, "model")
            _publish_cache_gauge()
        _DECISIONS[sig]["resolves"] += 1
    return choice


def resolve_serve_impl(*, depth: int, R: int, F: int, B: int) -> str:
    """``serve impl="auto"``: the XLA gather traversal on every backend.
    The Pallas traversal indexes 1-D node planes with ``jnp.take``, which
    Mosaic refuses ("Only 2D gather is supported"), so it cannot be the
    choice on ``tpu`` until the kernel is rebuilt on 2-D planes.  Decision
    recorded per batch signature so the /3/Profiler/autotune table shows
    what serving actually runs."""
    choice = "xla"
    if autotune_mode() == "off":
        return choice
    with _lock:
        sig = f"serve:d{depth}:R{R}:F{F}:B{B}:{_backend()}"
        if sig not in _DECISIONS:
            _DECISIONS[sig] = {"sig": sig, "choice": choice,
                               "source": "model", "predicted": {},
                               "measured": {}, "resolves": 0,
                               "explore": None, "epoch": _EPOCH,
                               "candidates": {}}
            _note_decision({"serve_impl": choice}, "model")
            _publish_cache_gauge()
        _DECISIONS[sig]["resolves"] += 1
    return choice


# ----------------------------------------------------------------- cache

def _cache_dir() -> Optional[str]:
    from .config import config
    d = config().autotune_cache_dir
    if d:
        return d
    from . import recovery
    base = recovery.recovery_dir()
    return os.path.join(base, "autotune") if base else None


def _cache_path() -> Optional[str]:
    d = _cache_dir()
    return os.path.join(d, "autotune_cache.json") if d else None


def _cache_header() -> dict:
    # version 2: the model prices the compaction scatters (PR 29); a file
    # written under version 1 holds choices made without that price
    return {"version": 2, "backend": _backend(), "jax": _jax_version()}


_file_entries: Dict[str, dict] = {}


def _load_cache_file() -> None:
    """Read the persisted decision table once; corrupt or version-stale
    files silently degrade to model-seeded decisions (never an error)."""
    global _file_loaded
    if _file_loaded or _file_dead:
        return
    _file_loaded = True
    path = _cache_path()
    if not path:
        return
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data, dict) or \
                data.get("header") != _cache_header():
            return
        entries = data.get("entries")
        if isinstance(entries, dict):
            _file_entries.update({k: v for k, v in entries.items()
                                  if isinstance(v, dict) and "choice" in v})
    except Exception:                    # noqa: BLE001 — degrade, never err
        return


def _load_cached_entry(sig: str) -> Optional[dict]:
    _load_cache_file()
    raw = _file_entries.get(sig)
    if raw is None:
        return None
    ent = {"sig": sig, "choice": str(raw["choice"]), "source": "cache",
           "predicted": {k: float(v) for k, v in
                         (raw.get("predicted") or {}).items()},
           "measured": {k: dict(v) for k, v in
                        (raw.get("measured") or {}).items()},
           "resolves": 0, "explore": None, "epoch": _EPOCH}
    return ent


def _save_cache() -> None:
    """Atomically persist the decision table (tmp + rename, the WAL
    pattern).  No recovery dir configured means in-memory only."""
    path = _cache_path()
    if not path:
        return
    with _lock:
        entries = {
            sig: {"choice": ent["choice"], "source": ent["source"],
                  "predicted": {k: v for k, v in ent["predicted"].items()
                                if v != float("inf")},
                  "measured": ent["measured"]}
            for sig, ent in _DECISIONS.items()
        }
    payload = {"header": _cache_header(), "entries": entries}
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except Exception:                    # noqa: BLE001 — cache is best-effort
        pass


# ----------------------------------------------------------- maintenance

def invalidate(reason: str = "cluster_reinit") -> None:
    """Drop every memoized decision (and the loaded cache-file snapshot):
    called from ``cluster._invalidate_compiled_caches`` so a mesh rebuild
    can never serve a choice tuned for the dead geometry.  Fresh
    processes re-read the persisted cache; this process will not."""
    global _EPOCH, _file_loaded, _file_dead
    with _lock:
        _EPOCH += 1
        _DECISIONS.clear()
        _file_entries.clear()
        _file_loaded = False
        if reason == "cluster_reinit":
            _file_dead = True
        _publish_cache_gauge()
    obs.record("autotune_invalidate", reason=reason)


def reset() -> None:
    """Tests only: full reset including the cache-file dead flag."""
    global _EPOCH, _file_loaded, _file_dead
    with _lock:
        _EPOCH += 1
        _DECISIONS.clear()
        _file_entries.clear()
        _file_loaded = False
        _file_dead = False
        _publish_cache_gauge()
    _tls.scope = None


def decision_table() -> dict:
    """Plain-data decision table for ``GET /3/Profiler/autotune``:
    signature -> choice, source, predicted vs measured seconds."""
    with _lock:
        rows = []
        for sig, ent in _DECISIONS.items():
            meas = {k: round(v["ema"], 6)
                    for k, v in ent["measured"].items() if v["n"]}
            rows.append({
                "signature": sig,
                "choice": ent["choice"],
                "source": ent["source"],
                "resolves": ent["resolves"],
                "predicted_s": {k: (None if v == float("inf")
                                    else round(v, 6))
                                for k, v in ent["predicted"].items()},
                "measured_s": meas,
                "exploring": ent["explore"],
            })
        return {"mode": autotune_mode(), "epoch": _EPOCH,
                "entries": len(rows), "decisions": rows,
                "cache_file": _cache_path()}
