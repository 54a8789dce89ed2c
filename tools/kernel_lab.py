"""Kernel lab: A/B variants of the varbin histogram one-hot build.

The varbin kernel costs ~27.7 ms/level on chip (10M rows, airlines bins),
flat in L — so the per-slot one-hot build (compare + cast + concatenate)
is the whole cost, ~2.4 ops/slot effective.  The concatenate is a pure
VMEM copy of the [Q8, R] one-hot per row block; these variants remove it:

  concat   — shipped kernel (baseline): pieces list -> jnp.concatenate -> dot
  perfdot  — no concatenate: per-feature dot accumulated into out slices
  scratch  — compares write straight into a VMEM scratch at static offsets,
             then ONE dot

All share the stat/A build; parity is asserted against the shipped kernel
before timing.  Timing uses bench_util.py methodology (fori_loop of REPS
dependent calls in one jit, block_until_ready sync).

Usage (chip): python tools/kernel_lab.py
CPU check:    JAX_PLATFORMS=cpu H2O3_LAB_ROWS=100000 python tools/kernel_lab.py
"""

import functools
import json
import os
import time

import numpy as np

N_ROWS = int(os.environ.get("H2O3_LAB_ROWS", 10_000_000))
REPS = int(os.environ.get("H2O3_LAB_REPS", 20))
BIN_COUNTS = (21, 12, 7, 256, 256, 22, 256, 256)
F, NBINS = 8, 256
B = NBINS + 1


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    import h2o3_tpu
    cl = h2o3_tpu.init()
    platform = jax.devices()[0].platform
    interp = platform != "tpu"
    n = N_ROWS - (N_ROWS % (512 * cl.n_row_shards))

    from h2o3_tpu.models.tree.hist import (make_varbin_hist_fn, offset_codes,
                                           varbin_layout)

    offsets, seg_rows, Q8, _ = varbin_layout(BIN_COUNTS, B)
    L = 32
    L3 = 3 * L
    R = int(min(4096, max(512, (4_194_304 // max(Q8 * 2, 1)) // 128 * 128)))
    R = min(R, max(512, ((n + 511) // 512) * 512))
    nblk = (n + R - 1) // R
    pad_to = nblk * R
    dt = jnp.bfloat16
    code_dt = jnp.int16

    def build_A(leaf_i32, ST_f32):
        cols = jax.lax.broadcasted_iota(jnp.int32, (R, L3), 1)
        l_of, s_of = cols // 3, cols % 3
        match = leaf_i32[:, None] == l_of
        sv = jnp.where(s_of == 0, ST_f32[0][:, None],
                       jnp.where(s_of == 1, ST_f32[1][:, None],
                                 ST_f32[2][:, None]))
        return jnp.where(match, sv, 0.0).astype(dt)

    def make_variant(kind):
        def kernel(codes_ref, leaf_ref, st_ref, out_ref, *scr):
            i = pl.program_id(0)

            @pl.when(i == 0)
            def _():
                out_ref[:] = jnp.zeros_like(out_ref)

            A = build_A(leaf_ref[0].astype(jnp.int32),
                        st_ref[:].astype(jnp.float32))
            codes = codes_ref[:].astype(jnp.int32)
            if kind == "concat":
                pieces = []
                for f in range(F):
                    q_of = jax.lax.broadcasted_iota(
                        jnp.int32, (int(seg_rows[f]), 1), 0) + int(offsets[f])
                    pieces.append((codes[f, :][None, :] == q_of).astype(dt))
                OHT = jnp.concatenate(pieces, axis=0)
                out_ref[:] += jnp.dot(OHT, A,
                                      preferred_element_type=jnp.float32)
            elif kind == "perfdot":
                for f in range(F):
                    q_of = jax.lax.broadcasted_iota(
                        jnp.int32, (int(seg_rows[f]), 1), 0) + int(offsets[f])
                    piece = (codes[f, :][None, :] == q_of).astype(dt)
                    out_ref[int(offsets[f]):int(offsets[f] + seg_rows[f]),
                            :] += jnp.dot(
                        piece, A, preferred_element_type=jnp.float32)
            elif kind == "scratch":
                oh = scr[0]
                for f in range(F):
                    q_of = jax.lax.broadcasted_iota(
                        jnp.int32, (int(seg_rows[f]), 1), 0) + int(offsets[f])
                    oh[int(offsets[f]):int(offsets[f] + seg_rows[f]), :] = (
                        codes[f, :][None, :] == q_of).astype(dt)
                out_ref[:] += jnp.dot(oh[:], A,
                                      preferred_element_type=jnp.float32)

        scratch = [pltpu.VMEM((Q8, R), dt)] if kind == "scratch" else []
        call = pl.pallas_call(
            kernel,
            grid=(nblk,),
            in_specs=[
                pl.BlockSpec((F, R), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, R), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((3, R), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((Q8, L3), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((Q8, L3), jnp.float32),
            scratch_shapes=scratch,
            interpret=interp,
        )

        @jax.jit
        def run(gcodes, leaf, g, h, w):
            pad = pad_to - n

            def padr(x, fill):
                if pad == 0:
                    return x
                return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                               constant_values=fill)
            ST = jnp.stack([g, h, w], axis=0).astype(dt)
            return call(padr(gcodes.astype(code_dt), -1),
                        padr(leaf[None].astype(code_dt), -1),
                        padr(ST, 0))

        return run

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    codes = jnp.stack([
        jax.random.randint(ks[f], (n,), 0, min(bc, NBINS), dtype=jnp.int32)
        for f, bc in enumerate(BIN_COUNTS)], axis=0)
    gcodes = offset_codes(codes, BIN_COUNTS, NBINS)
    g = jax.random.normal(ks[0], (n,), jnp.float32)
    h = jnp.abs(jax.random.normal(ks[1], (n,), jnp.float32)) + 0.1
    w = jnp.ones((n,), jnp.float32)
    leaf = jax.random.randint(ks[2], (n,), 0, L, dtype=jnp.int32)

    def sync(x):
        np.asarray(jax.device_get(jnp.ravel(x)[:1]))

    def timed(run):
        @jax.jit
        def reps(gc, lf, gg, hh, ww):
            def body(i, acc):
                out = run(gc, lf, gg + acc * 0.0, hh, ww)
                return out[0, 0] * 1e-30
            return jax.lax.fori_loop(0, REPS, body, jnp.float32(0.0))

        out = reps(gcodes, leaf, g, h, w); sync(out)
        out = reps(gcodes, leaf, g, h, w); sync(out)
        t0 = time.perf_counter()
        out = reps(gcodes, leaf, g, h, w); sync(out)
        return (time.perf_counter() - t0) / REPS * 1e3

    ref = None
    for kind in ("concat", "perfdot", "scratch"):
        try:
            run = make_variant(kind)
            out = np.asarray(run(gcodes, leaf, g, h, w))
            if ref is None:
                ref = out
            ok = bool(np.allclose(out, ref, rtol=2e-2, atol=1e-2))
            ms = timed(run)
            print(json.dumps({"variant": kind, "ms": round(ms, 3),
                              "parity": ok, "platform": platform,
                              "rows": n, "L": L}), flush=True)
        except Exception as e:  # noqa: BLE001 — lab tool: report and go on
            print(json.dumps({"variant": kind,
                              "error": repr(e)[:300]}), flush=True)


if __name__ == "__main__":
    main()
