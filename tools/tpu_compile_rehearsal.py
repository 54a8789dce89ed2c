"""Compile the whole device programs for a DESCRIBED TPU v5e, without a chip.

The TPU compiler is installed beside JAX and compiles for a topology that is
described and not attached (``jax.experimental.topologies``).  Every kernel
seam picks its branch from ``cluster().mesh.devices.flat[0].platform`` and
``init(devices=...)`` accepts any device list, so booting the cluster over
the described devices steers the real ``tpu`` branches from a CPU-only host;
each program is then lowered on ``ShapeDtypeStruct``s that carry
``NamedSharding``s on that mesh and compiled.  What Mosaic or XLA:TPU would
refuse on the chip, it refuses here.  Nothing runs: this says nothing about
results or times on the device.

One JSON line per program: seconds to lower + compile ON THIS HOST, the
``tpu_custom_call``, ``all-reduce`` and ``gather`` counts in the compiled text, and the
compiler's memory analysis (bytes per device).

    JAX_PLATFORMS=cpu python tools/tpu_compile_rehearsal.py            # all
    JAX_PLATFORMS=cpu python tools/tpu_compile_rehearsal.py tree_build glm_path
    JAX_PLATFORMS=cpu python tools/tpu_compile_rehearsal.py --chips 4 tree_build
    JAX_PLATFORMS=cpu python tools/tpu_compile_rehearsal.py rule_codes glm_path_rulefit

The cheap single-kernel compiles live in tests/test_tpu_compile.py (tier-1);
these take tens of seconds each and are run by hand before a chip call.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the airlines geometry of benchmark/configs/xgb_airlines40m.json at 10M rows
# (fit_bins' bin counts on benchmark/datagen/airlines_like.py)
BIN_COUNTS = (21, 12, 7, 256, 256, 22, 256, 256)
F, NBINS, DEPTH = 8, 256, 6
N_AIRLINES = 10_000_000
N_HIGGS, P_HIGGS = 10_000_000, 29          # 28 numerics + intercept

# scalar operands of the tree programs, in call order: reg_lambda, min_rows,
# min_split_improvement, learn_rate, col_sample_rate, reg_alpha, gamma,
# min_child_weight (python floats trace as weak f32 scalars)
SCALARS = (1.0, 1.0, 1e-5, 0.3, 1.0, 0.0, 0.0, 1.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("programs", nargs="*",
                    help="program names (default: all)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding, PartitionSpec as P

    import h2o3_tpu
    from h2o3_tpu.models import base as base_mod
    from h2o3_tpu.models import datainfo as datainfo_mod
    from h2o3_tpu.models import deeplearning as dl_mod
    from h2o3_tpu.models import glm as glm_mod
    from h2o3_tpu.models.tree import hist, shared
    from h2o3_tpu.runtime import autotune
    from h2o3_tpu.runtime.cluster import ROW_AXIS
    from h2o3_tpu.serving import kernel as serve_kernel

    # a described-chip executable cannot be read back from the persistent
    # cache without a chip; keep the rehearsal out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cl = h2o3_tpu.init(devices=list(topo.devices[:args.chips]), hosts=1)
    dev = cl.mesh.devices.flat[0]
    mesh = cl.mesh
    rows, rep = NamedSharding(mesh, P(ROW_AXIS)), NamedSharding(mesh, P())
    cols = NamedSharding(mesh, P(None, ROW_AXIS))
    mat = NamedSharding(mesh, P(ROW_AXIS, None))

    def sds(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def tree_args(n, f, nk=1):
        """Operands of a ``make_build_tree_fn`` program."""
        lead = (nk,) if nk > 1 else ()
        row_k = NamedSharding(mesh, P(None, ROW_AXIS)) if nk > 1 else rows
        return (sds((f, n), jnp.int32, cols),
                sds(lead + (n,), jnp.float32, row_k),
                sds(lead + (n,), jnp.float32, row_k),
                sds((n,), jnp.float32, rows),
                sds((f, NBINS), jnp.float32),
                sds(lead + (2,), jnp.uint32),
                *SCALARS[:5], sds(lead + (f,), jnp.bool_), *SCALARS[5:])

    n = cl.pad_rows(N_AIRLINES)
    knobs = dict(hist_mode="subtract", split_mode="fused")

    def tree_build():
        fn = shared.make_build_tree_fn(DEPTH, NBINS, F, n, "bf16",
                                       bin_counts=BIN_COUNTS, **knobs)
        return fn, tree_args(n, F)

    def tree_build_scan():
        fn = shared.make_build_tree_fn(DEPTH, NBINS, F, n, "bf16",
                                       bin_counts=BIN_COUNTS,
                                       tree_program="scan", **knobs)
        return fn, tree_args(n, F)

    def tree_build_k7():
        n1 = cl.pad_rows(1_000_000)
        fn = shared.make_build_tree_fn(DEPTH, NBINS, F, n1, "bf16",
                                       bin_counts=BIN_COUNTS, nk=7, **knobs)
        return fn, tree_args(n1, F, nk=7)

    def tree_scan():
        """The chunk program the 10M-row XGBoost fit dispatches, under the
        knobs the autotuner resolves for that signature."""
        import types
        p = types.SimpleNamespace(
            hist_mode="auto", split_mode="auto", hist_layout="auto",
            tree_program="auto", sparse_depth_threshold=8,
            max_depth=DEPTH, nbins=NBINS)
        k = autotune.resolve_tree_knobs(p, kind="xgboost", F=F, N=n)
        print(json.dumps({"autotune": {
            "hist_mode": k.hist_mode, "split_mode": k.split_mode,
            "hist_layout": k.hist_layout, "tree_program": k.tree_program,
            "sources": k.sources}}), flush=True)
        fn = shared.make_tree_scan_fn(
            "bernoulli", 1.5, 0.5, 0.9, DEPTH, NBINS, F, n, "bf16", 1.0, 1.0,
            bin_counts=BIN_COUNTS, hist_mode=k.hist_mode,
            split_mode=k.split_mode, hist_layout=k.hist_layout,
            sparse_depth_threshold=k.sparse_depth_threshold,
            tree_program=k.tree_program)
        return fn, (sds((F, n), jnp.int32, cols),
                    sds((n,), jnp.float32, rows), sds((n,), jnp.float32, rows),
                    sds((n,), jnp.float32), sds((F, NBINS), jnp.float32),
                    sds((2,), jnp.uint32), 0, 5, *SCALARS, 0)

    def sparse_level():
        Ap, A = 128, 256
        fn = hist.make_sparse_level_fn(Ap, A, F, NBINS + 1, n,
                                       bin_counts=BIN_COUNTS)
        return fn, (sds((F, n), jnp.int16, cols),
                    sds((n,), jnp.int32, rows),
                    *(sds((n,), jnp.float32, rows),) * 3,
                    sds((cl.n_row_shards, 3, Ap, F, NBINS + 1), jnp.float32,
                        NamedSharding(mesh, P(ROW_AXIS))),
                    sds((A,), jnp.int32))

    def grid_scan():
        G, n1 = 4, cl.pad_rows(1_000_000)
        fn = shared.make_grid_scan_fn(G, "bernoulli", 1.5, 0.5, 0.9, DEPTH,
                                      NBINS, F, n1, "bf16")
        g = sds((G,), jnp.float32)
        return fn, (sds((F, n1), jnp.int32, cols),
                    sds((n1,), jnp.float32, rows),
                    sds((n1,), jnp.float32, rows),
                    sds((G, n1), jnp.float32), sds((F, NBINS), jnp.float32),
                    sds((G, 2), jnp.uint32), 0, 5,
                    g, g, g, g, g, g, g, sds((G,), jnp.bool_), g, g, g)

    def serve_xla():
        depth, trees, batch = DEPTH, 100, 256
        impl = autotune.resolve_serve_impl(depth=depth, R=trees, F=F, B=batch)
        nn = trees * (2 ** (depth + 1) - 1)
        fn = jax.jit(serve_kernel._traverse_impl(impl, depth, trees, F,
                                                 batch))
        return fn, (sds((nn,), jnp.int32), sds((nn,), jnp.float32),
                    sds((trees,), jnp.int32), sds((batch, F), jnp.float32))

    def traverse():
        # xgb_airlines40m.score's predict: 100 trees of depth 6 over 40M x 8
        trees, n = 100, cl.pad_rows(40_000_000)
        levels = [(sds((trees, 2 ** d), jnp.int32),
                   sds((trees, 2 ** d), jnp.float32),
                   sds((trees, 2 ** d), jnp.bool_),
                   sds((trees, 2 ** d), jnp.bool_)) for d in range(DEPTH)]
        return shared.traverse_jit, (
            levels, sds((trees, 2 ** DEPTH), jnp.float32),
            sds((n, F), jnp.float32, mat))

    def prediction_columns():
        # the same predict's result frame: the blocked walk's scores, padded
        # to 312,576 blocks of 128 rows, cut to the frame's 40M
        return base_mod.prediction_columns, (
            sds((312_576 * 128, 2), jnp.float32, mat), sds((), jnp.int32),
            sds((), jnp.float32), True, cl.pad_rows(40_000_000), rows)

    # GLM's programs read the design in code form and size their row blocks
    # from the device's memory, which the code asks of the first LOCAL
    # device: here a CPU, so say what one v5e reports
    datainfo_mod.device_memory_bytes = lambda: 16_900_000_000

    def glm_path():
        # the dense design, as a frame whose expansion fits the device runs it
        fam = glm_mod._make_family("binomial", glm_mod.GLMParameters())
        fn = glm_mod._make_path_runner(fam, False, 50)
        nh = cl.pad_rows(N_HIGGS)
        vec = sds((nh,), jnp.float32, rows)
        return fn, (sds((nh, P_HIGGS), jnp.float32, mat), vec, vec, vec,
                    sds((1,), jnp.float32), sds((), jnp.float32),
                    sds((P_HIGGS,), jnp.float32), sds((P_HIGGS,), jnp.float32),
                    sds((), jnp.float32), sds((), jnp.float32))

    def glm_path_blocked(layout, n, l1_mode=False, runs=()):
        fam = glm_mod._make_family("binomial", glm_mod.GLMParameters())
        n = cl.pad_rows(n)
        fn = glm_mod._make_blocked_path_runner(
            fam, l1_mode, 50, layout, glm_mod._fit_block_rows(layout, n),
            runs=runs)
        width = sum(w for _, w in layout)
        vec, coef = sds((n,), jnp.float32, rows), sds((width,), jnp.float32)
        return fn, (sds((n, sum(w for k, w in layout if k == "num")),
                        jnp.float32, mat),
                    sds((n, sum(k == "cat" for k, _ in layout)), jnp.int32,
                        mat),
                    vec, vec, vec, sds((1,), jnp.float32),
                    sds((), jnp.float32), coef, coef, sds((), jnp.float32),
                    sds((), jnp.float32)) + (
                        (sds((len(runs),), jnp.bool_),) if runs else ())

    # DeepLearning at the benchmark's dl_airlines40m geometry: 5 numerics,
    # categoricals of 22 / 300 / 300 levels (first level dropped, NA column
    # added), intercept; hidden 200 x 200, bf16, ADADELTA, minibatch 128
    dl_layout = (("num", 5), ("cat", 22), ("cat", 300), ("cat", 300),
                 ("one", 1))
    dl_sizes = (sum(w for _, w in dl_layout), 200, 200, 2)
    n_dl, dl_batch = 40_000_000, 128
    dl_params = [(sds((i, o), jnp.float32), sds((o,), jnp.float32))
                 for i, o in zip(dl_sizes[:-1], dl_sizes[1:])]

    def dl_design(n, targets=0):
        return (sds((n, 5 + targets), jnp.float32, mat),
                sds((n, 3), jnp.int32, mat))

    def dl_sample_copy():
        fn = dl_mod._sample_copy_fn(n_dl, dl_batch, True)
        n = cl.pad_rows(n_dl)
        vec = sds((n,), jnp.float32, rows)
        return fn, (*dl_design(n), vec, vec, sds((2,), jnp.uint32))

    def dl_train_steps():
        cfg = dl_mod._StepConfig(dl_layout, "rectifier", 0.0, (),
                                 "cross_entropy", True, False, 2, 0.0, 0.0,
                                 ("adadelta", 0.99, 1e-8), jnp.bfloat16)
        fn, tx = dl_mod._build_train_steps(cfg, dl_batch,
                                           n_dl // 10 // dl_batch, n_dl)
        state = jax.eval_shape(tx.init, dl_params)
        return fn, (dl_params, state, sds((2,), jnp.uint32), 0,
                    *dl_design(n_dl + dl_batch, targets=2))

    def dl_score():
        block = dl_mod._score_block_rows(dl_sizes, cl.pad_rows(n_dl))
        fn = dl_mod._make_score(dl_layout, "rectifier", "softmax", block)
        return fn, (dl_params, *dl_design(cl.pad_rows(n_dl)))

    def glm_path_airlines():
        # glm_airlines40m.fit: 96 blocks of 419,840 rows a pass on one chip
        return glm_path_blocked(dl_layout, n_dl)

    def glm_score():
        n = cl.pad_rows(n_dl)
        block = datainfo_mod.block_rows(2 * 4 * (dl_sizes[0] + 2),
                                        n // cl.n_row_shards)
        fn = glm_mod._make_score(dl_layout, "binomial", True, block)
        return fn, (sds((dl_sizes[0],), jnp.float32), sds((0,), jnp.float32),
                    *dl_design(n))

    # RuleFit at rulefit_higgs11m's geometry: 50 depth-3 trees over 11M x 28,
    # their rules as 50 groups of 8 levels beside the 28 numerics, every
    # group a partition of the rows (the lasso's null lines)
    n_rf, trees_rf = 11_000_000, 50
    rf_layout = (("cat", 8),) * trees_rf + (("num", 28), ("one", 1))

    def rule_codes():
        from h2o3_tpu.models import rulefit as rulefit_mod
        n = cl.pad_rows(n_rf)
        levels = [(sds((trees_rf, 2 ** d), jnp.int32),
                   sds((trees_rf, 2 ** d), jnp.float32),
                   sds((trees_rf, 2 ** d), jnp.bool_),
                   sds((trees_rf, 2 ** d), jnp.bool_)) for d in range(3)]
        return rulefit_mod.jit_rule_codes, (
            tuple(sds((n,), jnp.float32, rows) for _ in range(28)), levels,
            sds((trees_rf, 1, 8), jnp.int32), sds((), jnp.int32))

    def glm_path_rulefit():
        # the lasso path in L1 mode, a rule group a step, with the groups'
        # null-line moves
        return glm_path_blocked(rf_layout, n_rf, l1_mode=True,
                                runs=tuple((8 * g, 8)
                                           for g in range(trees_rf)))

    # the merge gate's tables (benchmark/configs/munge_100m_x2.json): 100M
    # rows a side, an int32 key and a float32 value, some 91M rows out
    from h2o3_tpu.rapids import device as munge
    n_munge = cl.pad_rows(100_000_000)
    p_munge = munge.merge_padded_rows(91_000_000, n_munge)
    key, val, count = (sds((n_munge,), t, rows) for t in (jnp.int32, jnp.float32, jnp.int32))

    def merge_match():
        return munge.merge_match, (
            (key,), (key,), (None,), (None,), sds((), jnp.int32), sds((), jnp.int32)), \
            dict(lkinds=("int",), rkinds=("int",), how="inner")

    def merge_gather():
        return munge.merge_gather, (
            count, count, sds((2 * n_munge,), jnp.int32), (key, val), (val,),
            sds((), jnp.int32), sds((), jnp.int32)), dict(
                how="inner", p_out=p_munge, lfills=("int", "f32"), rfills=("f32",),
                sharding=rows)

    def merge_trim():
        out_key, out_val = (sds((p_munge,), t, rows) for t in (jnp.int32, jnp.float32))
        return munge.merge_trim, (((out_key, out_val), (out_val,)),), dict(
            p=cl.pad_rows(91_000_000), sharding=rows)

    def sort_rows():
        return munge.sort_rows, ((key, val), (key, val)), dict(
            kinds=("int", "f32"), ascending=(False, True), sharding=rows)

    programs = {f.__name__: f for f in (
        tree_build, tree_build_scan, tree_build_k7, tree_scan, sparse_level,
        grid_scan, serve_xla, traverse, prediction_columns, glm_path,
        glm_path_airlines, glm_score, rule_codes, glm_path_rulefit,
        dl_sample_copy, dl_train_steps,
        dl_score, merge_match, merge_gather, merge_trim, sort_rows)}
    unknown = [p for p in args.programs if p not in programs]
    if unknown:
        ap.error(f"unknown program(s) {unknown}; known: {sorted(programs)}")

    failed = 0
    for name in args.programs or list(programs):
        rec = {"program": name, "platform": dev.platform,
               "device_kind": dev.device_kind, "devices": cl.n_devices,
               "attached": False}
        t0 = time.perf_counter()
        try:
            fn, operands, *static = programs[name]()
            compiled = getattr(fn, "jitted", fn).lower(
                *operands, **(static[0] if static else {})).compile()
        except Exception as e:              # noqa: BLE001 — report, go on
            rec.update(ok=False, error=f"{type(e).__name__}: {e}"[:600])
            failed += 1
        else:
            text = compiled.as_text()
            ma = compiled.memory_analysis()
            rec.update(ok=True,
                       host_compile_s=round(time.perf_counter() - t0, 1),
                       tpu_custom_calls=text.count("tpu_custom_call"),
                       all_reduces=text.count("all-reduce("),
                       gathers=text.count(" gather("),
                       temp_gb=round(ma.temp_size_in_bytes / 1e9, 3),
                       argument_gb=round(ma.argument_size_in_bytes / 1e9, 3),
                       output_gb=round(ma.output_size_in_bytes / 1e9, 3))
        print(json.dumps(rec), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
