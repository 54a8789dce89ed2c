"""Per-piece chip profiling harness.

Measurement rules, encoded so a chip session starts productive:
 - every piece is timed as a ``lax.fori_loop`` of REPS dependent
   invocations inside ONE jit, then divided, so per-dispatch host cost
   stays out of a per-kernel number — the carry feeds back into an operand
   so XLA cannot CSE or reorder the calls;
 - a timed region ends in ``block_until_ready`` (bench_util.py);
 - operand layouts: inputs are produced on device (iota/prng) so pallas
   custom-call layout constraints don't charge a relayout to the kernel.

Prints one JSON line per piece.  Shape mirrors bench.py's airlines-10M
workload; H2O3_PIECES_ROWS overrides for smoke runs.

Usage (chip): python bench_pieces.py
CPU smoke:    JAX_PLATFORMS=cpu H2O3_PIECES_ROWS=100000 python bench_pieces.py
"""

import json
import os
import sys
import time

import numpy as np

N_ROWS = int(os.environ.get("H2O3_PIECES_ROWS", 10_000_000))
REPS = int(os.environ.get("H2O3_PIECES_REPS", 20))
BIN_COUNTS = (21, 12, 7, 256, 256, 22, 256, 256)
F, NBINS = 8, 256
B = NBINS + 1


def main():
    import jax
    import jax.numpy as jnp

    import h2o3_tpu
    cl = h2o3_tpu.init()
    platform = jax.devices()[0].platform
    n = N_ROWS - (N_ROWS % (512 * cl.n_row_shards))

    from h2o3_tpu.models.tree.hist import (make_varbin_hist_fn,
                                           make_hist_fn, offset_codes,
                                           best_splits)

    def emit(piece, ms, **extra):
        print(json.dumps({"piece": piece, "ms": round(ms, 3),
                          "platform": platform, "rows": n, **extra}),
              flush=True)

    # shared sync + fori_loop amortization (bench_util.py)
    from bench_util import timed_amortized

    def timed(fn_build, *args):
        return timed_amortized(fn_build, *args, reps=REPS)

    # device-generated inputs (no host transfer, producer-fused layouts)
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    codes = jnp.stack([
        jax.random.randint(ks[f], (n,), 0, min(bc, NBINS), dtype=jnp.int32)
        for f, bc in enumerate(BIN_COUNTS)], axis=0)
    gcodes = offset_codes(codes, BIN_COUNTS, NBINS)
    g = jax.random.normal(ks[0], (n,), jnp.float32)
    h = jnp.abs(jax.random.normal(ks[1], (n,), jnp.float32)) + 0.1
    w = jnp.ones((n,), jnp.float32)

    # --- histogram levels: varbin (bench path) vs uniform
    # off-TPU smoke: interpret-mode pallas (slow but same code path)
    force = "" if platform == "tpu" else "pallas_interpret"
    for L in (1, 2, 4, 8, 16, 32):
        leaf = jax.random.randint(ks[2], (n,), 0, L, dtype=jnp.int32)
        fn = make_varbin_hist_fn(L, F, BIN_COUNTS, B, n, force_impl=force)

        def run_vb(acc, gc, lf, gg, hh, ww, _fn=fn):
            H = _fn(gc, lf, gg + acc * 0.0, hh, ww)
            return H[0, 0, 0, 0] * 1e-30

        emit(f"varbin_hist_L{L}", timed(run_vb, gcodes, leaf, g, h, w),
             kernel="varbin+int16+bf16")
    for L in (1, 32):
        leaf = jax.random.randint(ks[3], (n,), 0, L, dtype=jnp.int32)
        fn = make_hist_fn(L, F, B, n)

        def run_u(acc, cc, lf, gg, hh, ww, _fn=fn):
            H = _fn(cc, lf, gg + acc * 0.0, hh, ww)
            return H[0, 0, 0, 0] * 1e-30

        emit(f"uniform_hist_L{L}", timed(run_u, codes, leaf, g, h, w))

    # --- split search on a realistic histogram
    leaf32 = jax.random.randint(ks[4], (n,), 0, 32, dtype=jnp.int32)
    H = make_varbin_hist_fn(32, F, BIN_COUNTS, B, n, force_impl=force)(
        gcodes, leaf32, g, h, w)

    def run_split(acc, Hh):
        out = best_splits(Hh + acc * 0.0, NBINS, 1.0, 1.0, 0.0)
        return out[3].reshape(-1)[0].astype(jnp.float32) * 1e-30

    emit("best_splits_L32", timed(run_split, H))

    # --- whole-ensemble scoring (50 trees, depth 6)
    from h2o3_tpu.models.tree.shared import StackedTrees, traverse
    T, depth = 50, 6
    rng = np.random.default_rng(0)
    levels = []
    for d in range(depth):
        width = 2 ** d
        levels.append((
            jnp.asarray(rng.integers(0, F, (T, width)), jnp.int32),
            jnp.asarray(rng.normal(size=(T, width)), jnp.float32),
            jnp.asarray(rng.random((T, width)) < 0.5),
            jnp.ones((T, width), bool)))
    values = jnp.asarray(rng.normal(size=(T, 2 ** depth)) * 0.1,
                         jnp.float32)
    X = jax.random.normal(ks[5], (n, F), jnp.float32)

    def run_traverse(acc, Xx):
        s = traverse(levels, values, Xx + acc * 0.0)
        return s[0] * 1e-30

    t_ms = timed(run_traverse, X)
    emit("traverse_50trees_d6", t_ms,
         trees_per_sec_scoring=round(T / (t_ms / 1e3), 1))

    # --- rapids sort / merge (device)
    from h2o3_tpu.rapids import sort as _sort  # noqa: F401 — warm import
    keys_col = jax.random.randint(ks[6], (n,), 0, n, dtype=jnp.int32)

    def run_sort(acc, kk):
        out = jnp.sort(kk + acc.astype(jnp.int32) * 0)
        return out[0].astype(jnp.float32) * 1e-30

    emit("device_sort", timed(run_sort, keys_col))

    # --- projected end-to-end: one tree = 6 varbin levels + partition
    print(json.dumps({"piece": "NOTE",
                      "note": "tree total ~= sum(varbin_hist_L{1..32}) "
                              "+ 6x partition + split search"}),
          flush=True)


def hist_piece():
    """Standalone per-level histogram comparison: uniform vs varbin vs
    smaller-sibling subtraction (hist.make_subtract_level_fn), without the
    ~1091 s full bench.

    Per level d (children L = 2^d) three JSON lines land:
      - ``uniform_L*``   — the uniform kernel over ALL rows at the parent
        slot count (what the pre-varbin driver paid per level),
      - ``varbin_L*``    — the varbin kernel over ALL rows (the masked
        left-sibling path every level below the root paid before this
        round),
      - ``subtract_L*``  — compaction + varbin over the <= N/2
        smaller-sibling prefix + reconstruction (the shipping default),
    plus a ``hist_summary`` line with the varbin/subtract speedup per
    level.  Skews the per-level splits (70/30) so the compacted side is a
    realistic minority, and chains the carries level to level exactly like
    the tree driver.

    Usage (chip): python bench_pieces.py hist
    CPU smoke:    JAX_PLATFORMS=cpu H2O3_PIECES_ROWS=200000 \\
                  python bench_pieces.py hist
    (CPU runs the same Pallas kernels in interpret mode — relative
    numbers are methodology checks, not projections.)
    """
    import jax
    import jax.numpy as jnp

    import h2o3_tpu
    from bench_util import timed_amortized
    cl = h2o3_tpu.init()
    platform = jax.devices()[0].platform
    n = N_ROWS - (N_ROWS % (512 * cl.n_row_shards))

    from h2o3_tpu.models.tree.hist import (make_hist_fn, make_varbin_hist_fn,
                                           make_subtract_level_fn,
                                           offset_codes)

    def emit(**rec):
        print(json.dumps({**rec, "platform": platform, "rows": n}),
              flush=True)

    force = "" if platform == "tpu" else "pallas_interpret"
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 16)
    codes = jnp.stack([
        jax.random.randint(ks[f], (n,), 0, min(bc, NBINS), dtype=jnp.int32)
        for f, bc in enumerate(BIN_COUNTS)], axis=0)
    gcodes = offset_codes(codes, BIN_COUNTS, NBINS)
    g = jax.random.normal(ks[8], (n,), jnp.float32)
    h = jnp.abs(jax.random.normal(ks[9], (n,), jnp.float32)) + 0.1
    w = jnp.ones((n,), jnp.float32)

    # consistent leaf chain (child of the previous level's leaf, 70/30
    # split) + the subtraction carries, built once outside the timed loop
    leaves, carries = [jnp.zeros(n, jnp.int32)], []
    Hg, carry = make_subtract_level_fn(
        0, F, B, n, bin_counts=BIN_COUNTS, force_impl=force)(
        gcodes, leaves[0], g, h, w)
    carries.append(carry)
    summary = {}
    for d in range(1, 6):
        Lp = 2 ** (d - 1)
        bit = (jax.random.uniform(ks[10 + (d % 6)], (n,)) < 0.3) \
            .astype(jnp.int32)
        leaf = 2 * leaves[-1] + bit
        leaves.append(leaf)

        ufn = make_hist_fn(Lp, F, B, n, force_impl=force, precision="f32") \
            if force else make_hist_fn(Lp, F, B, n)

        def run_u(acc, lf, _fn=ufn):
            H = _fn(codes, lf, g + acc * 0.0, h, w)
            return H[0, 0, 0, 0] * 1e-30

        ms_u = timed_amortized(run_u, leaf >> 1, reps=REPS)
        emit(piece=f"uniform_L{2 ** d}", ms=round(ms_u, 3))

        vfn = make_varbin_hist_fn(Lp, F, BIN_COUNTS, B, n, force_impl=force)

        def run_v(acc, lf, _fn=vfn):
            H = _fn(gcodes, lf, g + acc * 0.0, h, w)
            return H[0, 0, 0, 0] * 1e-30

        ms_v = timed_amortized(run_v, leaf >> 1, reps=REPS)
        emit(piece=f"varbin_L{2 ** d}", ms=round(ms_v, 3),
             kernel="all-rows (masked-sibling path)")

        sfn = make_subtract_level_fn(d, F, B, n, bin_counts=BIN_COUNTS,
                                     force_impl=force)

        def run_s(acc, lf, cr, _fn=sfn):
            H, _ = _fn(gcodes, lf, g + acc * 0.0, h, w, cr)
            return H[0, 0, 0, 0] * 1e-30

        ms_s = timed_amortized(run_s, leaf, carries[-1], reps=REPS)
        emit(piece=f"subtract_L{2 ** d}", ms=round(ms_s, 3),
             kernel="compact+varbin+reconstruct")
        summary[f"L{2 ** d}"] = round(ms_v / ms_s, 2) if ms_s > 0 else None
        _, carry = sfn(gcodes, leaf, g, h, w, carries[-1])
        carries.append(carry)

    emit(piece="hist_summary", varbin_over_subtract=summary,
         note="ratio > 1: subtraction beats the all-rows masked path")


def splits_piece():
    """Standalone split-search comparison: multi-pass best_splits vs the
    fused winner-records path vs the batched-K fused path, per level of
    a depth-6 build, without the full bench.

    Per level d (leaf slots L = 2^d) three JSON lines land:
      - ``split_separate_L*`` — best_splits, the multi-pass XLA oracle
        (~15 [L, F, B] intermediates through HBM per level),
      - ``split_fused_L*``    — fused_best_splits on the platform's
        shipping impl (winner-records Pallas kernel on TPU, the
        bit-identical XLA twin elsewhere),
      - ``split_batched_K3_L*`` — fused_best_splits_batched over K=3
        class histograms flattened into ONE records pass (per-tree ms is
        the number to compare against split_fused_L*).
    The histograms chain level to level off one leaf chain (70/30
    splits) so each level's H carries realistic occupancy, and the timed
    carry feeds back into the operand so XLA cannot CSE the calls.

    A final ``ktree_dispatch`` line counts pallas_call equations in the
    traced batched level program (hist + split search for all K trees):
    the acceptance is 2 launches per level TOTAL — one histogram kernel
    (vmap batches the grid over K) and one records kernel (K*L leaves
    flatten into rows) — independent of K.

    Usage (chip): python bench_pieces.py splits
    CPU smoke:    JAX_PLATFORMS=cpu H2O3_PIECES_ROWS=200000 \\
                  python bench_pieces.py splits
    (Off-TPU the fused path ships the XLA twin; pass
    H2O3_SPLITS_INTERPRET=1 to time the Pallas kernel in interpret mode
    instead — a methodology check, not a projection.)
    """
    import jax
    import jax.numpy as jnp

    import h2o3_tpu
    from bench_util import timed_amortized
    cl = h2o3_tpu.init()
    platform = jax.devices()[0].platform
    n = N_ROWS - (N_ROWS % (512 * cl.n_row_shards))

    from h2o3_tpu.models.tree.hist import (
        make_varbin_hist_fn, make_batched_level_fn, offset_codes,
        best_splits, fused_best_splits, fused_best_splits_batched)

    def emit(**rec):
        print(json.dumps({**rec, "platform": platform, "rows": n}),
              flush=True)

    force = "" if platform == "tpu" else "pallas_interpret"
    fsplit = "pallas_interpret" if (platform != "tpu" and
                                    os.environ.get("H2O3_SPLITS_INTERPRET")) \
        else ""
    impl = "pallas" if platform == "tpu" else \
        ("pallas_interpret" if fsplit else "xla_twin")
    K = 3
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 16)
    codes = jnp.stack([
        jax.random.randint(ks[f], (n,), 0, min(bc, NBINS), dtype=jnp.int32)
        for f, bc in enumerate(BIN_COUNTS)], axis=0)
    gcodes = offset_codes(codes, BIN_COUNTS, NBINS)
    gK = jax.random.normal(ks[8], (K, n), jnp.float32)
    hK = jnp.abs(jax.random.normal(ks[9], (K, n), jnp.float32)) + 0.1
    w = jnp.ones((n,), jnp.float32)

    leaf = jnp.zeros(n, jnp.int32)
    summary = {}
    for d in range(6):
        L = 2 ** d
        if d:
            bit = (jax.random.uniform(ks[10 + d], (n,)) < 0.3) \
                .astype(jnp.int32)
            leaf = 2 * leaf + bit
        vfn = make_varbin_hist_fn(L, F, BIN_COUNTS, B, n, force_impl=force)
        HK = jnp.stack([vfn(gcodes, leaf, gK[k], hK[k], w)
                        for k in range(K)])
        H = HK[0]

        def run_sep(acc, Hh):
            out = best_splits(Hh + acc * 0.0, NBINS, 1.0, 1.0, 1e-5)
            return out[3].reshape(-1)[0].astype(jnp.float32) * 1e-30

        ms_sep = timed_amortized(run_sep, H, reps=REPS)
        emit(piece=f"split_separate_L{L}", ms=round(ms_sep, 3))

        def run_fus(acc, Hh):
            out = fused_best_splits(Hh + acc * 0.0, NBINS, 1.0, 1.0, 1e-5,
                                    force_impl=fsplit)
            return out[3].reshape(-1)[0].astype(jnp.float32) * 1e-30

        ms_fus = timed_amortized(run_fus, H, reps=REPS)
        emit(piece=f"split_fused_L{L}", ms=round(ms_fus, 3), impl=impl)

        def run_bat(acc, Hh):
            out = fused_best_splits_batched(Hh + acc * 0.0, NBINS, 1.0,
                                            1.0, 1e-5, force_impl=fsplit)
            return out[3].reshape(-1)[0].astype(jnp.float32) * 1e-30

        ms_bat = timed_amortized(run_bat, HK, reps=REPS)
        emit(piece=f"split_batched_K{K}_L{L}", ms=round(ms_bat, 3),
             ms_per_tree=round(ms_bat / K, 3), impl=impl)
        summary[f"L{L}"] = {
            "fused_speedup": round(ms_sep / ms_fus, 2) if ms_fus else None,
            "batched_per_tree_vs_fused":
                round(ms_fus / (ms_bat / K), 2) if ms_bat else None}

    emit(piece="splits_summary", per_level=summary,
         note="fused_speedup > 1: single-pass records path beats the "
              "multi-pass XLA search; batched_per_tree_vs_fused > 1: "
              "flattening K trees into one launch amortizes dispatch")

    # dispatch-count proof for the batched K-tree level: ONE histogram
    # launch + ONE records launch regardless of K (count from the traced
    # program, not a projection)
    lev = make_batched_level_fn(1, K, F, B, n, bin_counts=BIN_COUNTS,
                                force_impl=force or "pallas",
                                subtract=False)
    leafK = jnp.broadcast_to(leaf, (K, n))
    wK = jnp.broadcast_to(w, (K, n))

    def batched_level(c, lf, gg, hh, ww):
        Hh = lev(c, lf, gg, hh, ww)
        return fused_best_splits_batched(Hh, NBINS, 1.0, 1.0, 1e-5,
                                         force_impl="pallas")

    n_calls = str(jax.make_jaxpr(batched_level)(
        gcodes, leafK, gK, hK, wK)).count("pallas_call")
    emit(piece="ktree_dispatch", pallas_calls_per_level=n_calls, K=K,
         expect=2, ok=n_calls == 2,
         note="1 hist kernel (vmap batches the grid over K) + 1 records "
              "kernel (K*L leaves flatten into rows)")


def deep_piece():
    """Deep-level layout comparison: the dense [2^d, F, B] grid vs the
    node-sparse [A, F, B] slot layout, depth 6 -> 12 at 64 and 256 bins.

    Per (nbins, depth) two JSON lines land, each timing ONE level program
    (histogram + fused split search, the per-level unit of work):

      - ``deep_dense_b*_d*``  — make_subtract_level_fn at the full level
        width 2^d; where the dense grid exceeds the 64 MB histogram
        budget the line carries ``over_budget: true`` and is NOT timed
        (that is the wall the sparse layout removes),
      - ``deep_sparse_b*_d*`` — make_sparse_level_fn at the slot width
        A = min(2^d, sparse_slot_budget(F, B)): histogram bytes follow
        the ALIVE-bounded slot axis, plateauing at the budget instead of
        doubling per level.

    A ``deep_summary_b*`` line tabulates the per-depth byte ratio and a
    final ``deep_dispatch`` line counts pallas_call equations in the
    traced sparse level program — the acceptance is 2 launches per level
    (one sparse histogram kernel + one winner-records kernel) no matter
    how many leaves are alive.

    Usage (chip): python bench_pieces.py deep
    CPU smoke:    JAX_PLATFORMS=cpu H2O3_PIECES_ROWS=50000 \\
                  H2O3_PIECES_REPS=2 python bench_pieces.py deep
    (Off-TPU the inner histogram ships the einsum impl — same level
    program structure, smoke-scale numbers only; chip numbers are the
    deliverable.)
    """
    import jax
    import jax.numpy as jnp

    import h2o3_tpu
    from bench_util import timed_amortized
    cl = h2o3_tpu.init()
    platform = jax.devices()[0].platform
    n = N_ROWS - (N_ROWS % (512 * cl.n_row_shards))
    shards = cl.n_row_shards

    from h2o3_tpu.models.tree.hist import (
        fused_best_splits, make_sparse_level_fn, make_subtract_level_fn,
        offset_codes, sparse_slot_budget)

    def emit(**rec):
        print(json.dumps({**rec, "platform": platform, "rows": n}),
              flush=True)

    CAP = 64 * 1024 * 1024
    on_tpu = platform == "tpu"
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 16)
    g = jax.random.normal(ks[8], (n,), jnp.float32)
    h = jnp.abs(jax.random.normal(ks[9], (n,), jnp.float32)) + 0.1
    w = jnp.ones((n,), jnp.float32)

    for nbins in (64, 256):
        B_ = nbins + 1
        # varbin packed kernel on chip; einsum inner for CPU smoke
        bc = tuple(min(c, nbins) for c in BIN_COUNTS) if on_tpu else None
        codes = jnp.stack([
            jax.random.randint(ks[f], (n,), 0, min(c, nbins),
                               dtype=jnp.int32)
            for f, c in enumerate(BIN_COUNTS)], axis=0)
        hc = offset_codes(codes, bc, nbins) if bc else codes
        A_cap = sparse_slot_budget(F, B_)
        mem = {}
        for d in range(6, 13):
            Ld = 2 ** d
            dense_bytes = F * B_ * 3 * Ld * 4
            sp_A = min(Ld, A_cap)
            sp_Ap = min(Ld // 2, A_cap)
            sparse_bytes = F * B_ * 3 * sp_A * 4
            mem[f"d{d}"] = {"dense_mb": round(dense_bytes / 2 ** 20, 1),
                            "sparse_mb": round(sparse_bytes / 2 ** 20, 1)}

            if dense_bytes <= CAP:
                dfn = make_subtract_level_fn(d, F, B_, n, bin_counts=bc)
                leaf = jax.random.randint(ks[10], (n,), 0, Ld,
                                          dtype=jnp.int32)
                dcarry = jnp.zeros((shards, 3, Ld // 2, F, B_),
                                   jnp.float32)

                def run_d(acc, lf, cr, _fn=dfn, _b=nbins):
                    H, _ = _fn(hc, lf, g + acc * 0.0, h, w, cr)
                    out = fused_best_splits(H, _b, 1.0, 1.0, 1e-5)
                    return out[3].reshape(-1)[0].astype(jnp.float32) \
                        * 1e-30

                ms = timed_amortized(run_d, leaf, dcarry, reps=REPS)
                emit(piece=f"deep_dense_b{nbins}_d{d}", ms=round(ms, 3),
                     slots=Ld, hist_bytes=dense_bytes)
            else:
                emit(piece=f"deep_dense_b{nbins}_d{d}", ms=None,
                     slots=Ld, hist_bytes=dense_bytes, over_budget=True,
                     note="dense grid exceeds the 64 MB histogram budget")

            sfn = make_sparse_level_fn(sp_Ap, sp_A, F, B_, n,
                                       bin_counts=bc)
            sleaf = jax.random.randint(ks[11], (n,), 0, sp_A,
                                       dtype=jnp.int32)
            ps = jnp.minimum(jnp.arange(sp_A, dtype=jnp.int32) // 2,
                             sp_Ap - 1)
            scarry = jnp.zeros((shards, 3, sp_Ap, F, B_), jnp.float32)

            def run_s(acc, lf, cr, _fn=sfn, _ps=ps, _b=nbins):
                H, _ = _fn(hc, lf, g + acc * 0.0, h, w, cr, _ps)
                out = fused_best_splits(H, _b, 1.0, 1.0, 1e-5)
                return out[3].reshape(-1)[0].astype(jnp.float32) * 1e-30

            ms = timed_amortized(run_s, sleaf, scarry, reps=REPS)
            emit(piece=f"deep_sparse_b{nbins}_d{d}", ms=round(ms, 3),
                 slots=sp_A, hist_bytes=sparse_bytes,
                 mem_ratio=round(dense_bytes / sparse_bytes, 2))

        # the alive-bounded case the layout exists for: a skewed deep
        # tree with ~256 alive leaves runs the SAME level program at
        # EVERY depth — time and bytes stop depending on d entirely,
        # while the dense grid doubles per level above
        A_alive = 256
        afn = make_sparse_level_fn(A_alive, A_alive, F, B_, n,
                                   bin_counts=bc)
        sleaf = jax.random.randint(ks[12], (n,), 0, A_alive,
                                   dtype=jnp.int32)
        ps = jnp.minimum(jnp.arange(A_alive, dtype=jnp.int32) // 2,
                         A_alive - 1)
        acarry = jnp.zeros((shards, 3, A_alive, F, B_), jnp.float32)

        def run_a(acc, lf, cr, _fn=afn, _ps=ps, _b=nbins):
            H, _ = _fn(hc, lf, g + acc * 0.0, h, w, cr, _ps)
            out = fused_best_splits(H, _b, 1.0, 1.0, 1e-5)
            return out[3].reshape(-1)[0].astype(jnp.float32) * 1e-30

        ms = timed_amortized(run_a, sleaf, acarry, reps=REPS)
        emit(piece=f"deep_sparse_alive{A_alive}_b{nbins}", ms=round(ms, 3),
             slots=A_alive, hist_bytes=F * B_ * 3 * A_alive * 4,
             note="256 alive leaves: identical level cost at EVERY "
                  "depth 8..12+ — hist bytes follow alive leaves, "
                  "not 2^d")

        emit(piece=f"deep_summary_b{nbins}", slot_budget=A_cap,
             per_depth_mb=mem,
             alive256_mb=round(F * B_ * 3 * A_alive * 4 / 2 ** 20, 1),
             note="sparse bytes are alive-bounded (plateau at the slot "
                  "budget in the worst case); dense doubles per level "
                  "and blows the 64 MB cap at depth 12 x 256 bins")

    # dispatch-count proof: 2 pallas launches per sparse level (hist +
    # records), independent of the alive-slot count — from the traced
    # program, not a projection
    Ap_, A_ = 8, 16
    lev = make_sparse_level_fn(
        Ap_, A_, F, B, n, bin_counts=BIN_COUNTS,
        force_impl="pallas" if on_tpu else "pallas_interpret")
    sleaf = jnp.zeros((n,), jnp.int32)
    carry = jnp.zeros((shards, 3, Ap_, F, B), jnp.float32)
    ps = jnp.arange(A_, dtype=jnp.int32) // 2

    def sparse_level(c, lf, gg, hh, ww, cr, pp):
        H, _ = lev(c, lf, gg, hh, ww, cr, pp)
        return fused_best_splits(H, NBINS, 1.0, 1.0, 1e-5,
                                 force_impl="pallas")

    gcodes = offset_codes(jnp.zeros((F, n), jnp.int32), BIN_COUNTS, NBINS)
    n_calls = str(jax.make_jaxpr(sparse_level)(
        gcodes, sleaf, g, h, w, carry, ps)).count("pallas_call")
    emit(piece="deep_dispatch", pallas_calls_per_level=n_calls, expect=2,
         ok=n_calls == 2,
         note="1 sparse hist kernel + 1 records kernel per deep level")


def parse_piece():
    """Standalone ingest bench: bench.py's 568 MB parse line (same file,
    same warmup methodology) without the ~1091 s full suite.

    Usage:      python bench_pieces.py parse
    CPU smoke:  JAX_PLATFORMS=cpu H2O3_BENCH_ROWS=100000 \\
                python bench_pieces.py parse

    Prints one JSON line with MB/s, vs_baseline (reference: 580 MB in
    4.9 s on 5 nodes), and the pipeline's per-stage wall times
    (mmap / scan / tokenize / device / decode / vec).
    """
    import tempfile

    import h2o3_tpu
    import bench
    from h2o3_tpu.frame.parse import parse_csv, last_parse_stats
    h2o3_tpu.init()
    dt, mb = bench.bench_parse(parse_csv, tempfile.gettempdir())
    print(json.dumps({
        "piece": "parse", "sec": round(dt, 3), "mb": round(mb, 1),
        "mb_per_sec": round(mb / dt, 1),
        "vs_baseline": round(
            (bench.REFERENCE_PARSE_S * mb / bench.REFERENCE_PARSE_MB) / dt,
            2),
        "stages": dict(last_parse_stats)}), flush=True)


def obs_piece():
    """Telemetry-overhead bench: the hist level loop (the subtract-path
    chain hist_piece times) run three ways — bare, wrapped in the
    ``level_phase`` span hooks with telemetry ON, and wrapped with
    telemetry OFF (``H2O3_TPU_METRICS=0`` fast path).

    The hooks are host-side (span event + latency histogram per phase),
    so their cost must disappear against a real kernel dispatch: the
    acceptance bar is < 2% overhead with telemetry enabled.

    Usage (chip): python bench_pieces.py obs
    CPU smoke:    JAX_PLATFORMS=cpu H2O3_PIECES_ROWS=200000 \\
                  python bench_pieces.py obs
    """
    import time as _time

    import jax
    import jax.numpy as jnp

    import h2o3_tpu
    from h2o3_tpu.models.tree.hist import (make_subtract_level_fn,
                                           offset_codes)
    from h2o3_tpu.models.tree.shared import level_phase
    from h2o3_tpu.runtime import observability as obs

    cl = h2o3_tpu.init()
    platform = jax.devices()[0].platform
    n = N_ROWS - (N_ROWS % (512 * cl.n_row_shards))
    force = "" if platform == "tpu" else "pallas_interpret"
    reps = max(REPS // 4, 3)

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 16)
    codes = jnp.stack([
        jax.random.randint(ks[f], (n,), 0, min(bc, NBINS), dtype=jnp.int32)
        for f, bc in enumerate(BIN_COUNTS)], axis=0)
    gcodes = offset_codes(codes, BIN_COUNTS, NBINS)
    g = jax.random.normal(ks[8], (n,), jnp.float32)
    h = jnp.abs(jax.random.normal(ks[9], (n,), jnp.float32)) + 0.1
    w = jnp.ones((n,), jnp.float32)

    # the same leaf/carry chain hist_piece uses (70/30 splits), built and
    # warmed up outside the timed loops so only steady-state dispatch is
    # measured
    chain = []
    leaf = jnp.zeros(n, jnp.int32)
    fn0 = make_subtract_level_fn(0, F, B, n, bin_counts=BIN_COUNTS,
                                 force_impl=force)
    _, carry = fn0(gcodes, leaf, g, h, w)
    for d in range(1, 6):
        bit = (jax.random.uniform(ks[10 + (d % 6)], (n,)) < 0.3) \
            .astype(jnp.int32)
        leaf = 2 * leaf + bit
        fn_d = make_subtract_level_fn(d, F, B, n, bin_counts=BIN_COUNTS,
                                      force_impl=force)
        H, next_carry = fn_d(gcodes, leaf, g, h, w, carry)   # warmup
        jax.block_until_ready(H)
        chain.append((fn_d, leaf, carry))
        carry = next_carry

    def run_loop(instrument: bool) -> float:
        t0 = _time.perf_counter()
        for _ in range(reps):
            for d, (fn_d, lf, cr) in enumerate(chain, start=1):
                if instrument:
                    with level_phase("hist", d):
                        H, _ = fn_d(gcodes, lf, g, h, w, cr)
                else:
                    H, _ = fn_d(gcodes, lf, g, h, w, cr)
                jax.block_until_ready(H)
        return (_time.perf_counter() - t0) * 1e3 / (reps * len(chain))

    def emit(**rec):
        print(json.dumps({**rec, "platform": platform, "rows": n,
                          "reps": reps}), flush=True)

    run_loop(False)                                   # loop warmup
    ms_plain = run_loop(False)
    prev = obs.set_enabled(True)
    ms_on = run_loop(True)
    obs.set_enabled(False)
    ms_off = run_loop(True)
    obs.set_enabled(prev)

    emit(piece="obs_plain", ms=round(ms_plain, 4))
    emit(piece="obs_enabled", ms=round(ms_on, 4))
    emit(piece="obs_disabled", ms=round(ms_off, 4))
    pct_on = 100.0 * (ms_on - ms_plain) / ms_plain
    pct_off = 100.0 * (ms_off - ms_plain) / ms_plain
    emit(piece="obs_summary",
         overhead_pct_enabled=round(pct_on, 3),
         overhead_pct_disabled=round(pct_off, 3),
         ok=bool(pct_on < 2.0),
         note="span+histogram hooks on the hist level loop; bar is < 2%")


def xprof_piece():
    """Device-timing overhead bench: the same subtract-path level loop as
    ``obs_piece``, dispatched through the compile-ledger ``_Program``
    wrappers three ways — ``H2O3_TPU_DEVICE_TIMING=off`` (baseline),
    ``sampled`` (every Nth dispatch block-until-ready into
    ``tree_phase_device_seconds``), and ``full`` (every dispatch).

    ``sampled`` is the mode training keeps on, so its cost must vanish
    against a real kernel dispatch: the acceptance bar is < 2% overhead
    vs ``off``.  Also proves the ledger side: the loop's programs appear
    in ``ledger_snapshot()`` and the sampled run lands observations in
    ``tree_phase_device_seconds``.

    Usage (chip): python bench_pieces.py xprof
    CPU smoke:    JAX_PLATFORMS=cpu H2O3_PIECES_ROWS=200000 \\
                  python bench_pieces.py xprof
    """
    import time as _time

    import jax
    import jax.numpy as jnp

    import h2o3_tpu
    from h2o3_tpu.models.tree.hist import (make_subtract_level_fn,
                                           offset_codes)
    from h2o3_tpu.runtime import config as _config
    from h2o3_tpu.runtime import observability as obs
    from h2o3_tpu.runtime import xprof

    cl = h2o3_tpu.init()
    platform = jax.devices()[0].platform
    n = N_ROWS - (N_ROWS % (512 * cl.n_row_shards))
    force = "" if platform == "tpu" else "pallas_interpret"
    reps = max(REPS // 4, 3)

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 16)
    codes = jnp.stack([
        jax.random.randint(ks[f], (n,), 0, min(bc, NBINS), dtype=jnp.int32)
        for f, bc in enumerate(BIN_COUNTS)], axis=0)
    gcodes = offset_codes(codes, BIN_COUNTS, NBINS)
    g = jax.random.normal(ks[8], (n,), jnp.float32)
    h = jnp.abs(jax.random.normal(ks[9], (n,), jnp.float32)) + 0.1
    w = jnp.ones((n,), jnp.float32)

    # same warmed leaf/carry chain as obs_piece; the level fns are
    # _Program wrappers, so every eager call below goes through the
    # ledger dispatch path that maybe_device_sync hooks
    chain = []
    leaf = jnp.zeros(n, jnp.int32)
    fn0 = make_subtract_level_fn(0, F, B, n, bin_counts=BIN_COUNTS,
                                 force_impl=force)
    _, carry = fn0(gcodes, leaf, g, h, w)
    for d in range(1, 6):
        bit = (jax.random.uniform(ks[10 + (d % 6)], (n,)) < 0.3) \
            .astype(jnp.int32)
        leaf = 2 * leaf + bit
        fn_d = make_subtract_level_fn(d, F, B, n, bin_counts=BIN_COUNTS,
                                      force_impl=force)
        H, next_carry = fn_d(gcodes, leaf, g, h, w, carry)   # warmup
        jax.block_until_ready(H)
        chain.append((fn_d, leaf, carry))
        carry = next_carry

    prev_env = os.environ.get("H2O3_TPU_DEVICE_TIMING")
    prev_enabled = obs.set_enabled(True)

    def set_mode(mode: str) -> None:
        os.environ["H2O3_TPU_DEVICE_TIMING"] = mode
        _config.reload()                 # re-reads env; resets telemetry
        obs.set_enabled(True)            # timing only records when on

    def run_loop() -> float:
        t0 = _time.perf_counter()
        for _ in range(reps):
            for fn_d, lf, cr in chain:
                H, _ = fn_d(gcodes, lf, g, h, w, cr)
                jax.block_until_ready(H)
        return (_time.perf_counter() - t0) * 1e3 / (reps * len(chain))

    def emit(**rec):
        print(json.dumps({**rec, "platform": platform, "rows": n,
                          "reps": reps}), flush=True)

    try:
        set_mode("off")
        run_loop()                                    # loop warmup
        ms_off = run_loop()
        set_mode("sampled")
        ms_sampled = run_loop()
        set_mode("full")
        ms_full = run_loop()
    finally:
        if prev_env is None:
            os.environ.pop("H2O3_TPU_DEVICE_TIMING", None)
        else:
            os.environ["H2O3_TPU_DEVICE_TIMING"] = prev_env
        _config.reload()
        obs.set_enabled(prev_enabled)

    series = {s["n"] for s in obs.metrics_wire()}
    snap = xprof.ledger_snapshot()
    emit(piece="xprof_off", ms=round(ms_off, 4))
    emit(piece="xprof_sampled", ms=round(ms_sampled, 4))
    emit(piece="xprof_full", ms=round(ms_full, 4))
    pct_sampled = 100.0 * (ms_sampled - ms_off) / ms_off
    pct_full = 100.0 * (ms_full - ms_off) / ms_off
    emit(piece="xprof_summary",
         overhead_pct_sampled=round(pct_sampled, 3),
         overhead_pct_full=round(pct_full, 3),
         device_series="tree_phase_device_seconds" in series,
         ledger_programs=len(snap["programs"]),
         ledger_compiles=snap["total_compiles"],
         ok=bool(pct_sampled < 2.0),
         note="sampled block-until-ready on the per-level loop; "
              "bar is < 2% vs off")


def mesh_piece():
    """Hierarchical-mesh data-plane proofs: the staged ICI+DCN schedule
    vs the flat oracle, on whatever mesh the process booted with.

    Three kinds of JSON lines:
      - ``mesh_collective_proof`` (one per reduce_mode) — compiled-HLO
        evidence: the flat schedule lowers to ONE all-reduce whose
        replica group spans every device; the hier schedule lowers to
        TWO all-reduces whose groups are (a) each host's chips and
        (b) one rank per host — the dispatch-count pin that the staged
        collective is really two stages,
      - ``mesh_dcn_bytes`` — the cost-model arithmetic for a level-
        histogram payload: an all-reduce over p ranks moves
        2*bytes*(p-1)/p per rank, so the hier DCN stage has n_hosts
        participants moving one ALREADY-REDUCED tensor per host, where
        the flat ring has all n_devices ranks eligible to cross DCN,
      - ``mesh_psum_flat`` / ``mesh_psum_hier`` — measured ms per
        reduction of that payload (amortized fori-style, REPS deps).

    The {8,16,32}-device trees/sec curve lives in ``bench.py
    --multichip`` (fresh subprocess per device count); this piece proves
    the schedule, not the scaling.

    Usage (chip): python bench_pieces.py mesh
    CPU smoke:    JAX_PLATFORMS=cpu H2O3_TPU_HOSTS=2 \\
                  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
                  python bench_pieces.py mesh
    """
    import re
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import h2o3_tpu
    from bench_util import timed_amortized
    from h2o3_tpu.runtime.cluster import ROW_AXIS, cluster
    from jax import shard_map
    from h2o3_tpu.runtime.mapreduce import psum_shards

    cl = h2o3_tpu.init()
    platform = jax.devices()[0].platform
    hosts, chips = cl.n_hosts, cl.n_chips_per_host
    n_dev = cl.n_row_shards
    n = max(512 * n_dev, N_ROWS // 100 - (N_ROWS // 100) % (512 * n_dev))

    def emit(**rec):
        print(json.dumps({**rec, "platform": platform,
                          "mesh": dict(cl.mesh.shape)}), flush=True)

    # level-histogram payload: [3 planes, L leaves, F feats, B bins] f32
    L = 32
    payload_bytes = 3 * L * F * B * 4

    def make_program(mode):
        def body(x):
            partial = jnp.sum(x) * jnp.ones((3, L, F, B), jnp.float32)
            return psum_shards(partial, mode)
        return jax.jit(shard_map(
            body, mesh=cl.mesh, in_specs=P(ROW_AXIS), out_specs=P(),
            check_vma=False))

    x = jnp.ones((n,), jnp.float32)
    for mode in ("flat", "hier"):
        f = make_program(mode)
        txt = f.lower(x).compile().as_text()
        ars = [ln for ln in txt.splitlines() if "all-reduce" in ln
               and "replica_groups" in ln]
        groups = []
        for ln in ars:
            m = re.search(r"replica_groups=(\{\{.*?\}\})", ln)
            if m:
                groups.append(m.group(1)[:120])
        emit(piece="mesh_collective_proof", reduce_mode=mode,
             all_reduces=len(ars), replica_groups=groups,
             expect=("1 group spanning all devices" if mode == "flat"
                     else "stage 1: per-host chip rings; "
                          "stage 2: one rank per host"))

        def run(acc, xx, _f=f):
            return _f(xx + acc * 0.0)[0, 0, 0, 0] * 1e-30

        ms = timed_amortized(run, x, reps=REPS)
        emit(piece=f"mesh_psum_{mode}", ms=round(ms, 3),
             payload_bytes=payload_bytes)

    # all-reduce over p ranks moves 2*bytes*(p-1)/p per rank; in the flat
    # schedule every one of the n_dev ranks' transfers may cross DCN, in
    # the staged schedule only the n_hosts-rank second stage touches DCN
    # and its operand was already reduced chips-fold on ICI.
    flat_dcn = 2 * payload_bytes * (n_dev - 1) / n_dev * hosts
    hier_dcn = 2 * payload_bytes * (hosts - 1) / hosts * hosts \
        if hosts > 1 else 0.0
    emit(piece="mesh_dcn_bytes", payload_bytes=payload_bytes,
         n_devices=n_dev, hosts=hosts, chips_per_host=chips,
         flat_dcn_bytes=int(flat_dcn), hier_dcn_bytes=int(hier_dcn),
         dcn_reduction=round(flat_dcn / hier_dcn, 2) if hier_dcn else None,
         model="ring all-reduce: 2*B*(p-1)/p per rank; DCN ranks: "
               "flat=all chips on every host, hier=one per host")


def serve_piece():
    """Online-scoring latency bench: the packed fused-traversal program
    vs the ``ScoringModel`` numpy scorer, plus the continuous
    micro-batcher's request-level p50/p99/QPS.

    The bench ensemble is a binomial-GBM-shaped forest (trees/depth via
    H2O3_SERVE_TREES / H2O3_SERVE_DEPTH, default 300 x depth 10 over 32
    features — the airlines-shape serving profile) scored at B=256.
    Acceptance: packed >= 5x the numpy scorer at B=256.

    Usage (chip): python bench_pieces.py serve
    CPU smoke:    JAX_PLATFORMS=cpu python bench_pieces.py serve
    """
    import threading
    import time as _time

    import jax

    import h2o3_tpu
    from h2o3_tpu.export.scoring import ScoringModel
    from h2o3_tpu.serving.batcher import MicroBatcher
    from h2o3_tpu.serving.kernel import PackedScorer

    h2o3_tpu.init()
    platform = jax.devices()[0].platform
    T = int(os.environ.get("H2O3_SERVE_TREES", 300))
    depth = int(os.environ.get("H2O3_SERVE_DEPTH", 10))
    Fs, Bb = 32, 256
    rng = np.random.default_rng(7)

    # synthetic binomial-GBM export: ~85%-split heap trees, f32 planes
    arrays = {}
    valid_prev = np.ones((T, 1), bool)
    for d in range(depth):
        W = 2 ** d
        arrays[f"feat_{d}"] = rng.integers(0, Fs, (T, W)).astype(np.int32)
        arrays[f"thr_{d}"] = rng.normal(size=(T, W)).astype(np.float32)
        arrays[f"na_left_{d}"] = rng.integers(0, 2, (T, W)).astype(bool)
        exist = np.repeat(valid_prev, 2, axis=1) if d else \
            np.ones((T, 1), bool)
        v = (rng.random((T, W)) < 0.85) & exist
        arrays[f"valid_{d}"] = v
        valid_prev = v
    arrays["values"] = (rng.normal(size=(T, 2 ** depth)) * 0.1) \
        .astype(np.float32)
    meta = {
        "algo": "gbm", "family": "tree", "tree_average": False,
        "nclass_trees": 1, "ntrees": T, "depth": depth,
        "link": "identity", "init_score": 0.0, "default_threshold": 0.5,
        "datainfo": {
            "specs": [{"name": f"x{i}", "type": "num", "domain": None,
                       "mean": 0.0, "sigma": 1.0, "offset": i, "width": 1}
                      for i in range(Fs)],
            "response_domain": ["no", "yes"], "response_column": "y",
            "use_all_factor_levels": False, "standardize": False,
            "add_intercept": False, "nfeatures": Fs,
        },
    }
    sm = ScoringModel(meta, arrays)
    ps = PackedScorer(sm)
    X = rng.normal(size=(Bb, Fs)).astype(np.float32)
    X[rng.random((Bb, Fs)) < 0.02] = np.nan
    cols = {f"x{i}": X[:, i] for i in range(Fs)}

    def emit(piece, **rec):
        print(json.dumps({"piece": piece, "platform": platform,
                          "trees": T, "depth": depth, "batch": Bb,
                          **rec}), flush=True)

    def timed_ms(fn, reps):
        fn()                                       # warm (AOT compile)
        t0 = _time.perf_counter()
        for _ in range(reps):
            fn()
        return (_time.perf_counter() - t0) * 1e3 / reps

    reps = max(REPS, 20)
    ref_ms = timed_ms(lambda: sm._score(cols, Bb), max(reps // 4, 5))
    packed_ms = timed_ms(lambda: ps.score(X), reps)
    speedup = ref_ms / packed_ms if packed_ms else float("inf")
    emit("serve_ref", ms=round(ref_ms, 4),
         note="ScoringModel numpy scorer (featurize + packed walk)")
    emit("serve_packed", ms=round(packed_ms, 4),
         n_nodes=ps.packed.n_nodes,
         packed_mb=round(ps.packed.nbytes() / 2 ** 20, 2))
    emit("serve_speedup", speedup=round(speedup, 2), ok=bool(speedup >= 5),
         note="acceptance bar: packed >= 5x numpy at B=256")

    # request-level latency through the continuous micro-batcher:
    # closed-loop clients, single-row requests (the REST realtime shape)
    mb = MicroBatcher(ps, max_batch=Bb, tick_ms=1.0, queue_depth=8192)
    mb.warmup()
    lat: list = []
    lat_lock = threading.Lock()
    n_clients, n_reqs = 8, 50
    rows1 = [np.ascontiguousarray(X[i % Bb:i % Bb + 1])
             for i in range(n_clients * n_reqs)]

    def client(c):
        mine = []
        for i in range(n_reqs):
            xi = rows1[c * n_reqs + i]
            t0 = _time.perf_counter()
            mb.submit(xi)
            mine.append((_time.perf_counter() - t0) * 1e3)
        with lat_lock:
            lat.extend(mine)

    t0 = _time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = _time.perf_counter() - t0
    mb.close()
    p50 = float(np.percentile(lat, 50))
    p99 = float(np.percentile(lat, 99))
    qps = len(lat) / wall
    emit("serve_latency", serve_p50_ms=round(p50, 3),
         serve_p99_ms=round(p99, 3), serve_qps=round(qps, 1),
         clients=n_clients, requests=len(lat),
         note="single-row closed-loop clients through the micro-batcher")
    return {"serve_ref_ms": ref_ms, "serve_packed_ms": packed_ms,
            "serve_speedup": speedup, "serve_p50_ms": p50,
            "serve_p99_ms": p99, "serve_qps": qps}


def remat_piece():
    """Partial-vs-full recovery bench (the shard-lineage data plane).

    Times recovering ONE lost shard of a 4-host frame from lineage
    (survivor copy + a single ranged re-parse of the dead host's byte
    range) against the pre-lineage recovery unit: a full re-import of
    the source file.  ``remat_partial_vs_baseline`` is the speedup the
    gate tracks — the partial path must stay well under a full ingest.

    Usage:      python bench_pieces.py remat
    CPU smoke:  JAX_PLATFORMS=cpu H2O3_PIECES_ROWS=120000 \\
                python bench_pieces.py remat
    """
    import tempfile

    import h2o3_tpu
    h2o3_tpu.init(hosts=4)
    from h2o3_tpu.frame import lineage
    from h2o3_tpu.frame.parse import import_file
    from h2o3_tpu.runtime import dkv, remat

    rows = min(N_ROWS, 500_000)
    rng = np.random.default_rng(11)
    body = np.column_stack([rng.random((rows, 4)).astype(np.float32),
                            rng.random(rows).astype(np.float32)])
    path = os.path.join(tempfile.gettempdir(), f"remat_bench_{rows}.csv")
    with open(path, "w") as f:
        f.write("x0,x1,x2,x3,y\n")
        f.write("\n".join(",".join(f"{v:.7g}" for v in r) for r in body))
        f.write("\n")
    mb = os.path.getsize(path) / 1e6

    import_file(path, destination_frame="remat_bench_fr")
    rec = lineage.get_record("remat_bench_fr")
    assert rec is not None and rec["n_shards"] == 4, "no lineage record"

    t0 = time.perf_counter()
    remat.recover_frame("remat_bench_fr", lost={1})
    partial = time.perf_counter() - t0
    s1 = rec["shards"][1]
    assert remat.last_stats["reparsed"] == [[s1["lo"], s1["hi"]]], \
        "partial recovery touched more than the lost shard's byte range"

    dkv.remove("remat_bench_fr")
    t0 = time.perf_counter()
    import_file(path, destination_frame="remat_bench_fr")
    full = time.perf_counter() - t0

    dkv.remove("remat_bench_fr")
    lineage.drop_record("remat_bench_fr")
    os.remove(path)
    print(json.dumps({
        "piece": "remat", "rows": rows, "mb": round(mb, 1),
        "remat_partial_s": round(partial, 3),
        "remat_full_s": round(full, 3),
        "remat_partial_vs_baseline": round(full / partial, 2)
        if partial else float("inf")}), flush=True)


def sched_piece():
    """Fair-share co-residency bench: small-job makespan beside a
    pod-holding large job, fair-share vs FIFO-behind-the-big-job.

    Synthetic chip-holding jobs (sleeps) isolate scheduler behavior
    from kernel throughput: the large job holds its chips for
    H2O3_SCHED_BIG_S seconds, each small job for H2O3_SCHED_SMALL_S.
    Fair-share gives the large job half the mesh (device_budget=0.5)
    so the smalls co-reside and finish in ~SMALL_S; the FIFO baseline
    gives it the full pod, so the smalls queue out the whole large job
    first.  Metrics feed tools/bench_gate.py: the makespans gate
    lower-is-better, ``sched_fair_vs_baseline`` higher-is-better.

    Usage: python bench_pieces.py sched    (host-side only; no chips)
    """
    import time as _time

    from h2o3_tpu.runtime.job import Job
    from h2o3_tpu.runtime.scheduler import ClusterScheduler

    BIG_S = float(os.environ.get("H2O3_SCHED_BIG_S", 2.0))
    SMALL_S = float(os.environ.get("H2O3_SCHED_SMALL_S", 0.3))
    N_SMALL = int(os.environ.get("H2O3_SCHED_SMALLS", 3))

    def hold(seconds):
        def fn(job):
            end = _time.monotonic() + seconds
            while _time.monotonic() < end:
                _time.sleep(0.01)
        return fn

    def small_makespan(big_budget):
        s = ClusterScheduler(capacity=8, queue_limit=64, elastic=False)
        try:
            big = Job("sched-bench big")
            s.submit(big, hold(BIG_S), device_budget=big_budget,
                     user="bench-big")
            t0 = _time.monotonic()
            smalls = []
            for i in range(N_SMALL):
                j = Job(f"sched-bench small {i}")
                s.submit(j, hold(SMALL_S), device_budget=1,
                         user=f"bench-small-{i}")
                smalls.append(j)
            for j in smalls:
                j.join()
            span = _time.monotonic() - t0
            big.join()
            return span
        finally:
            s.stop()

    def emit(piece, **rec):
        print(json.dumps({"piece": piece, **rec}), flush=True)

    fifo = small_makespan(1.0)      # pod-holding: smalls wait it out
    fair = small_makespan(0.5)      # half the mesh: smalls co-reside
    ratio = fifo / fair if fair else float("inf")
    emit("sched_fifo", sched_small_makespan_fifo_s=round(fifo, 3),
         big_s=BIG_S, small_s=SMALL_S, n_small=N_SMALL,
         note="baseline: large job holds the full pod")
    emit("sched_fair", sched_small_makespan_fair_s=round(fair, 3),
         note="large job at device_budget=0.5; smalls co-resident")
    emit("sched_speedup", sched_fair_vs_baseline=round(ratio, 2),
         ok=bool(fair < fifo),
         note="acceptance bar: fair-share makespan below FIFO")
    return {"sched_small_makespan_fifo_s": fifo,
            "sched_small_makespan_fair_s": fair,
            "sched_fair_vs_baseline": ratio}


def autotune_piece():
    """Cost-model autotuner bench: cold-cache vs warm-cache vs best
    hand-set trees/s on one GBM signature.

    Three trainings of the same airlines-shaped regression GBM:
      * best hand-set — each hand-tunable (hist_mode, split_mode)
        combination timed steady-state, best throughput kept;
      * auto, cold cache — knobs "auto" with an empty cache dir, so the
        roofline model seeds the choice at trace time;
      * auto, warm cache — tuner state reset but the cache file kept,
        so the choice comes back source="cache" with zero re-measures.

    ``autotune_vs_best`` (warm auto / best hand-set) is the gate metric:
    tools/bench_gate.py holds it to an absolute floor of 0.97 — the
    tuner is never allowed to be meaningfully slower than the best
    hand-set configuration on a seen signature.

    Usage (chip): python bench_pieces.py autotune
    CPU smoke:    JAX_PLATFORMS=cpu H2O3_PIECES_ROWS=50000 \\
                  python bench_pieces.py autotune
    """
    import shutil
    import tempfile
    import time as _time

    import jax

    import h2o3_tpu
    from h2o3_tpu import Frame
    from h2o3_tpu.models.tree.gbm import GBM
    from h2o3_tpu.runtime import autotune
    from h2o3_tpu.runtime import config as _cfg

    h2o3_tpu.init()
    platform = jax.devices()[0].platform
    rows = min(N_ROWS, 200_000)
    trees = int(os.environ.get("H2O3_AUTOTUNE_TREES", 16))
    reps = int(os.environ.get("H2O3_AUTOTUNE_REPS", 3))
    rng = np.random.default_rng(5)
    Fs = 8
    X = rng.normal(size=(rows, Fs)).astype(np.float64)
    y = (X[:, 0] * 0.7 - X[:, 1] ** 2 * 0.2
         + 0.1 * rng.normal(size=rows))
    fr = Frame.from_numpy(
        {**{f"x{i}": X[:, i] for i in range(Fs)}, "y": y})
    kw = dict(response_column="y", ntrees=trees, max_depth=6, nbins=64,
              min_rows=10, seed=3)

    def timed(**knob_kw):
        t0 = _time.perf_counter()
        GBM(**kw, **knob_kw).train(fr)
        return _time.perf_counter() - t0

    def tps(**knob_kw):
        """Steady-state trees/s: warm the jit caches once, then take
        the best of ``reps`` timed trainings."""
        GBM(**kw, **knob_kw).train(fr)
        return trees / min(timed(**knob_kw) for _ in range(reps))

    saved = {k: os.environ.get(k) for k in
             ("H2O3_TPU_AUTOTUNE", "H2O3_TPU_AUTOTUNE_CACHE_DIR")}
    cache_dir = tempfile.mkdtemp(prefix="autotune_bench_")
    try:
        # hand-set sweep (tuner off: the knobs mean what they say)
        os.environ["H2O3_TPU_AUTOTUNE"] = "off"
        _cfg.reload()
        autotune.reset()
        hand = {}
        for hm, sm in (("subtract", "fused"), ("full", "fused"),
                       ("subtract", "separate")):
            hand[f"{hm}|{sm}"] = tps(hist_mode=hm, split_mode=sm)
        best_key = max(hand, key=hand.get)
        bhm, bsm = best_key.split("|")

        os.environ["H2O3_TPU_AUTOTUNE"] = "on"
        os.environ["H2O3_TPU_AUTOTUNE_CACHE_DIR"] = cache_dir
        _cfg.reload()
        autotune.reset()
        cold = tps()                       # model-seeded decision
        autotune.reset()                   # drop memory, keep the file
        # warm-cache vs best-hand-set: interleaved timings so host-side
        # drift (GC, turbo, noisy neighbors) hits both sides equally —
        # the choices usually name the SAME kernels, and the gate ratio
        # must reflect the tuner's decision, not the clock's mood
        GBM(**kw).train(fr)                          # warm: cache hit
        GBM(**kw, hist_mode=bhm, split_mode=bsm).train(fr)
        t_warm, t_hand = float("inf"), float("inf")
        for _ in range(reps):
            t_hand = min(t_hand, timed(hist_mode=bhm, split_mode=bsm))
            t_warm = min(t_warm, timed())
        warm = trees / t_warm
        hand[best_key] = max(hand[best_key], trees / t_hand)
        ratio = t_hand / t_warm if t_warm else float("inf")
        table = autotune.decision_table()
        warm_sources = sorted({d["source"] for d in table["decisions"]
                               if d["signature"].startswith("gbm")}) \
            or ["none"]
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        _cfg.reload()
        autotune.reset()
        shutil.rmtree(cache_dir, ignore_errors=True)

    print(json.dumps({
        "piece": "autotune", "platform": platform, "rows": rows,
        "trees": trees,
        "autotune_hand_best": best_key,
        "autotune_hand_trees_per_sec": round(hand[best_key], 2),
        "autotune_cold_trees_per_sec": round(cold, 2),
        "autotune_warm_trees_per_sec": round(warm, 2),
        "autotune_vs_best": round(ratio, 3),
        "warm_sources": warm_sources,
        "note": "gate: autotune_vs_best >= 0.97 absolute floor"}),
        flush=True)
    return {"autotune_hand_trees_per_sec": hand[best_key],
            "autotune_cold_trees_per_sec": cold,
            "autotune_warm_trees_per_sec": warm,
            "autotune_vs_best": ratio}


def stream_piece():
    """Streaming-ingest overlap bench: end-to-end wall-clock of
    (StreamingFrame + stream= GBM training) vs (parse fully, then
    train) on the same synthetic CSV.

    The streamed run starts boosting once half the rows have landed
    (H2O3_TPU_STREAM_MIN_ROWS = rows/2, quantized via
    H2O3_TPU_STREAM_ROUND_ROWS so repeat runs reuse compiled shapes):
    early trees train on the landed prefix while the rest of the file
    tokenizes, so ingest disappears from the critical path and the
    prefix segments are cheaper than full-frame rounds.  Both paths are
    run once to warm the jit caches, then timed.

    ``stream_overlap_vs_baseline`` (batch / streamed, higher is better)
    is the gate metric: tools/bench_gate.py holds it to an absolute
    floor of 1.176 — streamed end-to-end must stay at or under 0.85x of
    parse-then-train wall-clock.

    Usage (chip): python bench_pieces.py stream
    CPU smoke:    JAX_PLATFORMS=cpu H2O3_PIECES_ROWS=120000 \\
                  python bench_pieces.py stream
    """
    import tempfile
    import time as _time

    import jax

    import h2o3_tpu
    from h2o3_tpu.frame.parse import parse_csv
    from h2o3_tpu.models.tree.gbm import GBM
    from h2o3_tpu.runtime import config as _cfg
    from h2o3_tpu.runtime import dkv

    h2o3_tpu.init()
    platform = jax.devices()[0].platform
    rows = min(N_ROWS, int(os.environ.get("H2O3_STREAM_ROWS", 400_000)))
    trees = int(os.environ.get("H2O3_STREAM_TREES", 24))
    rng = np.random.default_rng(11)
    Fs = 8
    path = os.path.join(tempfile.gettempdir(), f"stream_bench_{rows}.csv")
    if not os.path.exists(path):
        with open(path, "w") as f:
            f.write(",".join(f"x{i}" for i in range(Fs)) + ",g,y\n")
            block = 50_000
            for lo in range(0, rows, block):
                n = min(block, rows - lo)
                X = rng.normal(size=(n, Fs))
                g = rng.integers(0, 12, size=n)
                yv = (X[:, 0] * 0.7 - X[:, 1] ** 2 * 0.2 + 0.05 * g
                      + 0.2 * rng.normal(size=n)) > 0
                for r_ in range(n):
                    f.write(",".join(f"{v:.5f}" for v in X[r_]) +
                            f",lvl{g[r_]},c{int(yv[r_])}\n")
    kw = dict(response_column="y", ntrees=trees, max_depth=6, nbins=64,
              min_rows=10, seed=7, score_tree_interval=4)

    saved = {k: os.environ.get(k) for k in
             ("H2O3_TPU_STREAM_MIN_ROWS", "H2O3_TPU_STREAM_ROUND_ROWS",
              "H2O3_TPU_STREAM_GROW_MIN_FRAC",
              "H2O3_TPU_STREAM_BUFFER_ROWS", "H2O3_PARSE_RANGE_MIN")}
    # smoke-sized files must still land as MANY ranges (the default
    # 4 MB ranged-parse threshold would make the whole file one range
    # and the watermark a single step)
    os.environ["H2O3_PARSE_RANGE_MIN"] = str(
        min(1 << 22, max(65536, os.path.getsize(path) // 16)))
    os.environ["H2O3_TPU_STREAM_MIN_ROWS"] = str(rows // 2)
    os.environ["H2O3_TPU_STREAM_ROUND_ROWS"] = str(rows // 2)
    os.environ["H2O3_TPU_STREAM_GROW_MIN_FRAC"] = "0.25"
    # backpressure at 3/4 of the file: landing can never run more than
    # that ahead of training, so the first segment ALWAYS boosts on the
    # half-frame prefix while the tail is still in flight — the overlap
    # being measured, made deterministic across file sizes — and the
    # landed-fraction tree budget lets ~3/4 of the trees train on the
    # cheap prefix before the cut
    os.environ["H2O3_TPU_STREAM_BUFFER_ROWS"] = str(3 * rows // 4)
    _cfg.reload()

    def batch_run(tag):
        t0 = _time.perf_counter()
        fr = parse_csv(path, destination_frame=tag)
        m = GBM(**kw).train(fr)
        dt = _time.perf_counter() - t0
        dkv.remove(tag)
        return dt, m

    def stream_run(tag):
        t0 = _time.perf_counter()
        sf = h2o3_tpu.stream_file(path, destination_frame=tag)
        m = GBM(**kw, stream=True).train(sf)
        sf.frame()               # model AND fully-landed frame ready
        dt = _time.perf_counter() - t0
        dkv.remove(tag)
        return dt, m

    try:
        batch_run("stb_warm")       # warm jit caches: full-frame shapes
        stream_run("sts_warm")      # ... and the half-frame segment
        reps = int(os.environ.get("H2O3_STREAM_REPS", 2))
        batch_s = min(batch_run(f"stb_t{i}")[0] for i in range(reps))
        stream_s, m = min((stream_run(f"sts_t{i}") for i in range(reps)),
                          key=lambda r: r[0])
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        _cfg.reload()
    ratio = batch_s / stream_s
    print(json.dumps({
        "piece": "stream", "platform": platform, "rows": rows,
        "trees": trees,
        "stream_batch_s": round(batch_s, 3),
        "stream_overlap_s": round(stream_s, 3),
        "stream_overlap_vs_baseline": round(ratio, 3),
        "stream_segments": m.output.get("stream_segments"),
        "stream_coverage": m.output.get("stream_coverage"),
        "note": "gate: stream_overlap_vs_baseline >= 1.176 absolute "
                "floor (streamed <= 0.85x batch wall-clock)"}),
        flush=True)
    return {"stream_batch_s": batch_s, "stream_overlap_s": stream_s,
            "stream_overlap_vs_baseline": ratio,
            "stream_segments": m.output.get("stream_segments")}




def treescan_piece():
    """Whole-tree scan-fusion bench: tree_program="scan" vs "level" on
    the deep-tree shape (max_depth 10, small N — the regime where
    per-level dispatch and the unrolled 2*depth-kernel program dominate
    a tree's cost).

    Two proofs land:
      * dispatch pin — ``count_kernel_launches`` (runtime/xprof.py)
        counts kernel dispatch SITES in the traced build program.  The
        level program carries one histogram launch per level (grows
        with depth); the scan program is pinned O(1) regardless of
        depth (one scan-carried hist body + one level-0 seed).  Both
        counts are emitted at depth 6 and 10; the gate holds the scan
        count lower-better from this round on.
      * trees/s — the same deep GBM trained under both programs.
        ``treescan_cold_*`` includes compile (the scan program is one
        small scan body instead of 2*depth unrolled kernels — this is
        the serving-adjacent retrain-latency win);
        ``treescan_trees_per_sec_*`` is steady-state post-warmup.

    ``treescan_scan_vs_level_speedup`` (cold scan / cold level, higher
    is better) is the headline gate metric.

    Usage (chip): python bench_pieces.py treescan
    CPU smoke:    JAX_PLATFORMS=cpu H2O3_PIECES_ROWS=30000 \\
                  python bench_pieces.py treescan
    """
    import time as _time

    import jax
    import jax.numpy as jnp

    import h2o3_tpu
    from h2o3_tpu import Frame
    from h2o3_tpu.models.tree.gbm import GBM
    from h2o3_tpu.models.tree.shared import make_build_tree_fn
    from h2o3_tpu.runtime.xprof import count_kernel_launches

    h2o3_tpu.init()
    platform = jax.devices()[0].platform
    rows = min(N_ROWS, 30_000)
    trees = int(os.environ.get("H2O3_TREESCAN_TREES", 16))
    cold_trees = int(os.environ.get("H2O3_TREESCAN_COLD_TREES", 4))
    depth = int(os.environ.get("H2O3_TREESCAN_DEPTH", 10))
    nbins = 64
    Fs = 8

    # ---- dispatch pin: launches per tree from the traced jaxpr
    rng = np.random.default_rng(9)
    Nb = 4096
    codes = jnp.asarray(rng.integers(0, nbins, (Fs, Nb)), jnp.int32)
    g = jnp.asarray(rng.normal(size=Nb), jnp.float32)
    hh = jnp.ones(Nb, jnp.float32)
    ww = jnp.ones(Nb, jnp.float32)
    edges = jnp.sort(jnp.asarray(rng.normal(size=(Fs, nbins)),
                                 jnp.float32), axis=1)
    args = (codes, g, hh, ww, edges, jax.random.PRNGKey(1), 0.0, 1.0,
            1e-5, 0.1, 1.0, jnp.ones(Fs, bool), 0.0, 0.0, 0.0)
    launches = {}
    for md in (6, depth):
        for prog in ("level", "scan"):
            fn = make_build_tree_fn(md, nbins, Fs, Nb, "f32",
                                    tree_program=prog)
            launches[f"{prog}_d{md}"] = count_kernel_launches(fn, *args)

    # ---- trees/s on the deep shape, both programs
    X = rng.normal(size=(rows, Fs)).astype(np.float64)
    y = (np.sin(3 * X[:, 0]) + X[:, 1] * X[:, 2]
         + 0.1 * rng.normal(size=rows))
    fr = Frame.from_numpy(
        {**{f"x{i}": X[:, i] for i in range(Fs)}, "y": y})
    # dense layout pinned on both sides: the scan program composes with
    # dense uniform kernels only (node-sparse slot maps reshape per
    # level), and an apples-to-apples comparison needs one layout
    kw = dict(response_column="y", ntrees=trees, max_depth=depth,
              nbins=nbins, min_rows=5, seed=3, hist_layout="dense",
              score_tree_interval=trees)

    def cold(prog):
        """Fresh-program retrain: compile + a short boost (the
        serving-adjacent retrain-latency shape — compile cost is the
        point, so the tree count stays small)."""
        jax.clear_caches()
        from h2o3_tpu.models.tree import hist as _h, shared as _s
        for f in (_h.make_hist_fn, _h.make_subtract_level_fn,
                  _h.make_batched_level_fn, _h.make_scan_level_fn,
                  _h.make_batched_scan_level_fn, _s.make_build_tree_fn,
                  _s.make_tree_scan_fn):
            f.cache_clear()
        t0 = _time.perf_counter()
        GBM(**{**kw, "ntrees": cold_trees,
               "score_tree_interval": cold_trees},
            tree_program=prog).train(fr)
        return _time.perf_counter() - t0

    def steady(prog):
        GBM(**kw, tree_program=prog).train(fr)      # warm the caches
        best = float("inf")
        for _ in range(3):
            t0 = _time.perf_counter()
            GBM(**kw, tree_program=prog).train(fr)
            best = min(best, _time.perf_counter() - t0)
        return best

    cold_level = cold("level")
    cold_scan = cold("scan")
    steady_level = steady("level")
    steady_scan = steady("scan")
    speedup = cold_level / cold_scan if cold_scan else float("inf")

    rec = {
        "piece": "treescan", "platform": platform, "rows": rows,
        "trees": trees, "depth": depth,
        "treescan_launches_per_tree_scan": launches[f"scan_d{depth}"],
        "treescan_launches_per_tree_level": launches[f"level_d{depth}"],
        "treescan_launches_scan_d6": launches["scan_d6"],
        "treescan_launches_level_d6": launches["level_d6"],
        "cold_trees": cold_trees,
        "treescan_cold_level_s": round(cold_level, 3),
        "treescan_cold_scan_s": round(cold_scan, 3),
        "treescan_trees_per_sec_level": round(trees / steady_level, 2),
        "treescan_trees_per_sec_scan": round(trees / steady_scan, 2),
        "treescan_scan_vs_level_speedup": round(speedup, 3),
        "launches_depth_independent": bool(
            launches[f"scan_d{depth}"] == launches["scan_d6"]),
        "note": "dispatch pin: scan launches O(1) in depth vs "
                "one-per-level; speedup = fresh-program retrain "
                "(compile + short boost) level/scan wall",
    }
    print(json.dumps(rec), flush=True)
    return rec


def grid_piece():
    """Batched grid sweep bench: G same-shape members as ONE program.

    Two proofs land:
      * dispatch pin — ``count_kernel_launches`` over the traced chunk
        programs.  The batched G-member cohort program carries the SAME
        dispatch-site count as ONE sequential member's program (the
        model axis rides the kernels' ``nk`` batch dim, it adds no
        launches), so a sequential G-member sweep pays G× the dispatches
        per chunk while the cohort pays 1×.
        ``grid_batched_vs_sequential`` = G·L_seq / L_batched is that
        call-site ratio — a count from the traced program, not a time.
        Also pinned: the batched count is
        G-INDEPENDENT (G=2 and G=8 trace to identical counts).
      * wall clocks + bitwise parity — the same G-member sweep trained
        batched (grid_batch="on") vs the sequential wave path ("off"),
        warm.  On the CPU host the kernels are compute-bound, so the
        wall ratio sits near 1 (recorded as context); the parity check
        is the real assertion — every batched member's predictions are
        BITWISE equal to its sequential twin's.

    Usage (chip): python bench_pieces.py grid
    CPU smoke:    JAX_PLATFORMS=cpu H2O3_PIECES_ROWS=20000 \\
                  python bench_pieces.py grid
    """
    import time as _time

    import jax
    import jax.numpy as jnp

    import h2o3_tpu
    from h2o3_tpu import Frame
    from h2o3_tpu.models.grid import GridSearch
    from h2o3_tpu.models.tree.gbm import GBM
    from h2o3_tpu.models.tree.shared import (make_grid_scan_fn,
                                             make_tree_scan_fn)
    from h2o3_tpu.runtime.xprof import count_kernel_launches

    h2o3_tpu.init()
    platform = jax.devices()[0].platform
    rows = min(N_ROWS, 20_000)
    G = int(os.environ.get("H2O3_GRID_MEMBERS", 8))
    trees = int(os.environ.get("H2O3_GRID_TREES", 16))
    depth = 5
    nbins = 64
    Fs = 8

    # ---- dispatch pin: launch sites per chunk from the traced jaxprs
    rng = np.random.default_rng(17)
    Nb = 4096
    nchunk = 5
    codes = jnp.asarray(rng.integers(0, nbins, (Fs, Nb)), jnp.int32)
    yv = jnp.asarray(rng.normal(size=Nb), jnp.float32)
    wv = jnp.ones(Nb, jnp.float32)
    F0 = jnp.zeros(Nb, jnp.float32)
    edges = jnp.sort(jnp.asarray(rng.normal(size=(Fs, nbins)),
                                 jnp.float32), axis=1)
    seq_fn = make_tree_scan_fn("gaussian", 1.5, 0.5, 0.9, depth, nbins,
                               Fs, Nb, "f32", 1.0, 1.0)
    seq_args = (codes, yv, wv, F0, edges, jax.random.PRNGKey(1), 0,
                nchunk, 1.0, 10.0, 1e-5, 0.1, 1.0, 0.0, 0.0, 0.0, 0)
    L_seq = count_kernel_launches(seq_fn, *seq_args,
                                  static_argnums=(7,))
    L_grid = {}
    for g in (2, G):
        gfn = make_grid_scan_fn(g, "gaussian", 1.5, 0.5, 0.9, depth,
                                nbins, Fs, Nb, "f32")
        arr = lambda v, n=g: jnp.full((n,), v, jnp.float32)
        gargs = (codes, yv, wv,
                 jnp.zeros((g, Nb), jnp.float32), edges,
                 jnp.stack([jax.random.PRNGKey(i) for i in range(g)]),
                 0, nchunk, arr(1.0), arr(10.0), arr(1e-5), arr(0.1),
                 arr(1.0), arr(1.0), arr(1.0),
                 jnp.ones((g,), bool), arr(0.0), arr(0.0), arr(0.0))
        L_grid[g] = count_kernel_launches(gfn, *gargs,
                                          static_argnums=(7,))
    dispatch_ratio = G * L_seq / L_grid[G]

    # ---- wall clocks + bitwise parity, batched vs the wave path
    X = rng.normal(size=(rows, Fs)).astype(np.float64)
    yr = (np.sin(3 * X[:, 0]) + X[:, 1] * X[:, 2]
          + 0.1 * rng.normal(size=rows))
    fr = Frame.from_numpy(
        {**{f"x{i}": X[:, i] for i in range(Fs)}, "y": yr})
    lrs = [round(0.02 + 0.03 * i, 3) for i in range(G)]
    hp = {"learn_rate": lrs}
    kw = dict(response_column="y", ntrees=trees, max_depth=depth,
              nbins=nbins, seed=3, score_tree_interval=trees,
              hist_layout="dense", reproducible=True)

    def sweep(mode):
        GridSearch(GBM, hp, grid_batch=mode, **kw).train(fr)  # warm
        t0 = _time.perf_counter()
        g = GridSearch(GBM, hp, grid_batch=mode, **kw).train(fr)
        return _time.perf_counter() - t0, g

    wall_b, g_on = sweep("on")
    wall_s, g_off = sweep("off")
    assert all(m.output.get("grid_cohort", {}).get("size") == G
               for m in g_on.models), "cohort did not engage"
    GBM(learn_rate=lrs[0], **kw).train(fr)                    # warm
    t0 = _time.perf_counter()
    GBM(learn_rate=lrs[0], **kw).train(fr)
    wall_1 = _time.perf_counter() - t0

    by_lr = lambda g: {m.params.learn_rate: m for m in g.models}
    mo, mf = by_lr(g_on), by_lr(g_off)
    bitwise = all(
        np.array_equal(mo[k].predict(fr).to_numpy()[:, 0],
                       mf[k].predict(fr).to_numpy()[:, 0]) for k in mo)
    assert bitwise, "batched cohort diverged from the sequential path"

    rec = {
        "piece": "grid", "platform": platform, "rows": rows,
        "trees": trees, "grid_members": G,
        "grid_launches_batched": L_grid[G],
        "grid_launches_sequential_member": L_seq,
        "grid_batched_vs_sequential": round(dispatch_ratio, 3),
        "grid_launches_g_independent": bool(L_grid[2] == L_grid[G]),
        "grid_batched_wall_s": round(wall_b, 3),
        "grid_sequential_wall_s": round(wall_s, 3),
        "grid_one_member_wall_s": round(wall_1, 3),
        "grid_bitwise_equal": bitwise,
        "note": "dispatch pin: one batched cohort program serves G "
                "members per chunk at a single member's launch count "
                "(ratio = G on any platform); walls are CPU-host "
                "context — compute-bound there, dispatch-bound on chip",
    }
    print(json.dumps(rec), flush=True)
    return rec


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "parse":
        parse_piece()
    elif len(sys.argv) > 1 and sys.argv[1] == "hist":
        hist_piece()
    elif len(sys.argv) > 1 and sys.argv[1] == "splits":
        splits_piece()
    elif len(sys.argv) > 1 and sys.argv[1] == "deep":
        deep_piece()
    elif len(sys.argv) > 1 and sys.argv[1] == "obs":
        obs_piece()
    elif len(sys.argv) > 1 and sys.argv[1] == "xprof":
        xprof_piece()
    elif len(sys.argv) > 1 and sys.argv[1] == "mesh":
        mesh_piece()
    elif len(sys.argv) > 1 and sys.argv[1] == "serve":
        serve_piece()
    elif len(sys.argv) > 1 and sys.argv[1] == "sched":
        sched_piece()
    elif len(sys.argv) > 1 and sys.argv[1] == "remat":
        remat_piece()
    elif len(sys.argv) > 1 and sys.argv[1] == "autotune":
        autotune_piece()
    elif len(sys.argv) > 1 and sys.argv[1] == "stream":
        stream_piece()
    elif len(sys.argv) > 1 and sys.argv[1] == "treescan":
        treescan_piece()
    elif len(sys.argv) > 1 and sys.argv[1] == "grid":
        grid_piece()
    else:
        main()
