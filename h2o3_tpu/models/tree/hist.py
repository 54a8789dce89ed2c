"""tpu_hist: the histogram / split-search / partition kernels for tree algos.

Reference hot loop: ``hex/tree/DHistogram.java:48,67-95`` (per-(leaf, column,
bin) accumulate of w/wY/wYY into one double[]), driven by
``ScoreBuildHistogram2.java:62,119-235`` (two node-local passes: score rows ->
leaf assignment, then histogram build parallel over columns x row-ranges),
reduced across the cluster by elementwise array add (MRTask tree-reduce).
The XGBoost extension's CUDA ``gpu_hist`` is the performance target
(BASELINE.json: "gpu_hist via xgboost4j-gpu -> Pallas/XLA tpu_hist").

TPU-native redesign: scatter-adds are serialized on a vector machine, so the
histogram becomes DENSE MATMULS on the MXU: one-hot(leaf) x (g,h,w) planes
contracted with one-hot(bin codes) via einsum, blocked over rows to bound
memory, shard_mapped over the mesh's ("hosts", "chips") row axes with the
cross-device reduce staged ICI-then-DCN by runtime/mapreduce.psum_shards
(replacing both the LocalMR pass and the MRTask tree; ``reduce_mode``
picks flat/hier/check — see runtime/mapreduce.py).
Split search and row partition are fused elementwise/gather passes.  All
shapes static per tree level; one compile per (depth, F, B) geometry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...runtime.cluster import cluster, ROW_AXES, ROW_AXIS
from ...runtime.mapreduce import checked_pair, psum_shards, \
    resolve_reduce_mode


def _row_sds(shape, dtype):
    """ShapeDtypeStruct carrying the rows-varying VMA mark."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(ROW_AXES))


def _ledger(name, jitted, orig=None, **kw):
    """Register a compiled seam with the compile ledger (runtime/xprof).

    Deferred import: hist is importable without the runtime observability
    stack loaded.  The wrapper is call-compatible with the jitted product
    (transparent under a trace; AOT + timed compile when eager)."""
    from ...runtime import xprof
    return xprof.register_program(name, jitted, orig=orig, **kw)


def _named_kernel(name: str, **pallas_kwargs):
    """``pl.pallas_call(..., name=name)``, launched under
    ``jax.named_scope(name)``.

    XLA:TPU names a custom call's instruction after the innermost scope of
    its ``op_name`` (``.../branch_0_fun/pallas_call`` gave
    ``%branch_0_fun.3``), and the profiler's ``XLA Ops`` line shows that
    name.  ``name=`` alone gives ``%<name>.N``, but under ``vmap`` (the
    K-tree builds) its scope reads ``vmap(<name>)``; with the explicit
    scope around it the innermost one stays ``<name>`` there too
    (tests/test_tpu_compile.py pins both)."""
    call = pl.pallas_call(name=name, **pallas_kwargs)

    def launch(*operands):
        with jax.named_scope(name):
            return call(*operands)
    return launch


def _reduce_mode_dispatch(builder):
    """Resolve ``reduce_mode`` in front of a cached builder.

    ``""`` resolves to the configured mode so the LRU only ever caches
    concretely-scheduled programs; ``"check"`` returns a flat/hier
    checked pair (mapreduce.checked_pair) built from two cache entries.
    ``cache_clear`` is preserved — conftest's compiled-program release
    hook and cluster re-init both call it through the public name.
    """
    @functools.wraps(builder)
    def wrapper(*args, reduce_mode: str = "", **kw):
        mode = resolve_reduce_mode(reduce_mode or None)
        if mode == "check":
            return checked_pair(
                builder(*args, reduce_mode="flat", **kw),
                builder(*args, reduce_mode="hier", **kw),
                what=builder.__name__)
        return builder(*args, reduce_mode=mode, **kw)
    wrapper.cache_clear = builder.cache_clear
    return wrapper

def _make_pallas_hist(L: int, F: int, B: int, n_local: int,
                      interpret: bool = False, precision: str = "bf16"):
    """tpu_hist kernel: histogram as an in-VMEM one-hot matmul.

    The XLA einsum path materializes the [rows, F*B] one-hot in HBM every
    level (~N*F*B*4 bytes of traffic — bandwidth-bound); here the one-hot
    tile lives only in VMEM and feeds the MXU directly, so HBM traffic per
    level is just codes + (leaf,g,h,w).  Grid: (bin tiles, row blocks) —
    row blocks innermost so each [F*TB, 3L] output tile stays resident
    while rows stream through (replacing DHistogram's per-node scatter-adds
    and gpu_hist's shared-memory atomics).
    """
    R = int(min(4096, max(256, ((n_local + 255) // 256) * 256)))
    L3 = 3 * L
    # the A build materializes [R, L3] intermediates (int32 iota + f32
    # selects + bf16 A ~ 12 B/elem) on the 16M scoped-VMEM stack; deep
    # trees (large L) must shrink the row block (found on chip: L=256,
    # R=4096 -> 18.6M scoped alloc, Mosaic OOM)
    R = int(min(R, max(256, (6_291_456 // (12 * L3)) // 256 * 256)))
    nblk = (n_local + R - 1) // R
    pad_to = nblk * R
    # bins per tile -> [F*TB, R] one-hot tile.  The [TB, F, R] compare
    # intermediate is laid out with F in the sublane dim, which pads to a
    # multiple of 8 — size TB against the PADDED F or small-F geometries
    # blow the 16M scoped-VMEM stack (observed: F=3 -> 22M alloc).  Also cap
    # the padded intermediate itself at 8M so wide-F geometries stay inside
    # the scoped-VMEM budget.
    F8 = (F + 7) // 8 * 8
    TB = max(1, min(512 // F8, 2_097_152 // (F8 * R)))
    # never build one-hot tiles wider than the bin range (small-B
    # pass: TB=64 for B=17 wasted 3.7x of the kernel's dominant VPU work)
    TB = min(TB, (B + 7) // 8 * 8)
    FBT = F * TB
    n_fb = (B + TB - 1) // TB

    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32

    def _build_A(LS):
        # A[r, 3*l+s] = S[r, s] where leaf[r] == l, else 0.
        # (A 3-D match*stat form would halve the op count but Mosaic cannot
        # shape-cast [R, L, p] minor dims back to [R, L*p].)
        leaf = LS[0].astype(jnp.int32)
        cols = jax.lax.broadcasted_iota(jnp.int32, (R, L3), 1)
        l_of, s_of = cols // 3, cols % 3
        match = leaf[:, None] == l_of
        sv = jnp.where(s_of == 0, LS[1][:, None],
                       jnp.where(s_of == 1, LS[2][:, None],
                                 LS[3][:, None]))
        return jnp.where(match, sv, 0.0).astype(dt)

    def kernel(codes_ref, ls_ref, out_ref, a_scratch):
        i = pl.program_id(0)                       # row block (outer)
        j = pl.program_id(1)                       # bin tile (inner)

        @pl.when(j == 0)
        def _():
            # built once per row block, reused across all bin tiles
            a_scratch[:] = _build_A(ls_ref[:])

        @pl.when((i == 0) & (j == 0))
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        # OHT[b*F+f, r] = (codes[f, r] == j*TB + b) via broadcast compare —
        # no materialized int32 repeat, one VPU pass straight to bf16
        # (bf16/int16 compares are not supported by the target's VPU)
        b_of = jax.lax.broadcasted_iota(jnp.int32, (TB, 1, 1), 0) + j * TB
        OHT = (codes_ref[:][None] == b_of).astype(dt).reshape(FBT, R)
        # the WHOLE histogram is one output block (index map is constant),
        # so every grid step revisits it consecutively — the accumulation
        # is safe under Pallas TPU's revisiting rule, and the block never
        # round-trips through HBM
        out_ref[pl.ds(j * FBT, FBT), :] += jnp.dot(
            OHT, a_scratch[:], preferred_element_type=jnp.float32)

    def kernel_deep(codes_ref, ls_ref, out_ref):
        # fallback for deep trees where the whole histogram exceeds VMEM:
        # out tile [FBT, L3] is stationary across the inner row loop
        # (consecutive revisits — safe), A rebuilt per step
        j = pl.program_id(0)                       # bin tile (outer)
        i = pl.program_id(1)                       # row block (inner)

        @pl.when(i == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        A = _build_A(ls_ref[:])
        b_of = jax.lax.broadcasted_iota(jnp.int32, (TB, 1, 1), 0) + j * TB
        OHT = (codes_ref[:][None] == b_of).astype(dt).reshape(FBT, R)
        out_ref[:] += jnp.dot(OHT, A, preferred_element_type=jnp.float32)

    out_bytes = n_fb * FBT * L3 * 4
    a_bytes = R * L3 * (2 if precision == "bf16" else 4)
    if out_bytes + a_bytes <= 8 * 1024 * 1024:
        call = _named_kernel(
            "hist_uniform", kernel=kernel,
            grid=(nblk, n_fb),
            in_specs=[
                pl.BlockSpec((F, R), lambda i, j: (0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((4, R), lambda i, j: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((n_fb * FBT, L3), lambda i, j: (0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=_row_sds((n_fb * FBT, L3), jnp.float32),
            scratch_shapes=[pltpu.VMEM((R, L3), dt)],
            interpret=interpret,
        )
    else:
        call = _named_kernel(
            "hist_uniform_deep", kernel=kernel_deep,
            grid=(n_fb, nblk),
            in_specs=[
                pl.BlockSpec((F, R), lambda j, i: (0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((4, R), lambda j, i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((FBT, L3), lambda j, i: (j, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=_row_sds((n_fb * FBT, L3), jnp.float32),
            interpret=interpret,
        )

    def local(codes, leaf, g, h, w):
        pad = pad_to - n_local

        def padr(x):
            if pad == 0:
                return x
            return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
        LS = jnp.stack([leaf.astype(jnp.float32), g, h, w], axis=0)
        out = call(padr(codes), padr(LS))[: B * F]
        # [B*F, 3L] rows ordered (b*F + f), cols (l*3 + s) -> [3, L, F, B]
        return out.reshape(B, F, L, 3).transpose(3, 2, 1, 0)

    return local


def varbin_layout(bin_counts, B: int):
    """Packed ragged bin-axis layout: per-feature [offset, B_f regular bins,
    NA slot], each segment 8-padded (sublane alignment).

    Returns (offsets[F], segment row counts [F], total rows Q8, and the
    dense gather map [F, B+1] -> packed row, with empty bins pointing at
    padding slots that provably stay zero).
    """
    offsets, rows = [], []
    q = 0
    for bf in bin_counts:
        bf = min(bf, B - 1)              # regular bins; NA gets slot bf
        # pad to sublane multiple with at least ONE spare slot: empty dense
        # bins map to the spare, which no code ever matches (stays zero)
        seg = ((bf + 2) + 7) // 8 * 8
        offsets.append(q)
        rows.append(seg)
        q += seg
    qmap = np.zeros((len(bin_counts), B + 1), np.int32)
    for f, bf in enumerate(bin_counts):
        bf = min(bf, B - 1)
        for b in range(B + 1):
            if b < bf:                   # regular bin
                qmap[f, b] = offsets[f] + b
            elif b == B:                 # NA bin (dense index B-1... see below)
                qmap[f, b] = offsets[f] + bf
            else:                        # empty bin -> padded zero slot
                qmap[f, b] = offsets[f] + rows[f] - 1
    return (np.asarray(offsets, np.int32), np.asarray(rows, np.int32),
            q, qmap)


def _make_pallas_varbin_hist(L: int, F: int, bin_counts, B: int,
                             n_local: int, interpret: bool = False,
                             precision: str = "bf16"):
    """tpu_hist with a PACKED per-feature bin axis.

    The uniform kernel compares every feature row against every global bin
    id — O(F * B) VPU work per row even when most features use a fraction
    of the bins (a 22-carrier categorical against 257 slots).  Reference
    DHistogram sizes bins per column (DHistogram.java:48 min/max driven);
    here each feature gets exactly pad8(B_f+1) one-hot rows, built by a
    statically unrolled per-feature compare against its own code row, so
    VPU cost drops from F*B to sum(B_f).  Codes must arrive PRE-OFFSET
    (code + offset_f, NA -> offset_f + B_f): the build driver does that
    once per tree.
    """
    offsets, seg_rows, Q8, _ = varbin_layout(bin_counts, B)
    R = int(min(4096, max(512, (4_194_304 // max(Q8 * 2, 1))
                          // 128 * 128)))
    R = min(R, max(512, ((n_local + 511) // 512) * 512))
    L3 = 3 * L
    # deep-tree guard: A-build intermediates are [R, L3] (~12 B/elem) on
    # the scoped-VMEM stack — see _make_pallas_hist
    R = int(min(R, max(512, (6_291_456 // (12 * L3)) // 128 * 128)))
    nblk = (n_local + R - 1) // R
    pad_to = nblk * R
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    # stream codes+leaf as int16 and stats as bf16 —
    # halves the kernel's HBM input bytes.  The VPU cannot compare
    # sub-32-bit ints (Mosaic), so values upcast in-VMEM after the DMA;
    # int16 only when every id fits (packed bin ids < Q8, leaf < L).
    code_dt = jnp.int16 if max(Q8, L) < 32_000 else jnp.int32
    stat_dt = dt

    def kernel(codes_ref, leaf_ref, st_ref, out_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        leaf = leaf_ref[0].astype(jnp.int32)
        # [3, R] stat_dt -> f32: Mosaic's apply-vector-layout pass only
        # supports non-no-op minor-dim insertion ([R] -> [R, 1]) for 32-bit
        # types, and the sv select below does exactly that broadcast.  The
        # upcast is VMEM-local; A still feeds the MXU as bf16.  (Found on
        # chip: the AOT gate's MLIR verifier passes this, the backend
        # layout pass rejects it.)
        ST = st_ref[:].astype(jnp.float32)
        cols = jax.lax.broadcasted_iota(jnp.int32, (R, L3), 1)
        l_of, s_of = cols // 3, cols % 3
        match = leaf[:, None] == l_of
        sv = jnp.where(s_of == 0, ST[0][:, None],
                       jnp.where(s_of == 1, ST[1][:, None],
                                 ST[2][:, None]))
        A = jnp.where(match, sv, 0.0).astype(dt)
        codes = codes_ref[:].astype(jnp.int32)         # [F, R]
        pieces = []
        for f in range(F):
            q_of = jax.lax.broadcasted_iota(
                jnp.int32, (int(seg_rows[f]), 1), 0) + int(offsets[f])
            pieces.append((codes[f, :][None, :] == q_of).astype(dt))
        OHT = jnp.concatenate(pieces, axis=0)          # [Q8, R]
        out_ref[:] += jnp.dot(OHT, A, preferred_element_type=jnp.float32)

    call = _named_kernel(
        "hist_varbin", kernel=kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((F, R), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, R), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((3, R), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((Q8, L3), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_row_sds((Q8, L3), jnp.float32),
        interpret=interpret,
    )

    def local(gcodes, leaf, g, h, w):
        pad = pad_to - n_local

        def padr(x, fill):
            if pad == 0:
                return x
            return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)],
                           constant_values=fill)
        # casts fuse into the per-level leaf/grad producers; gcodes are
        # already code_dt from offset_codes (no per-level copy)
        ST = jnp.stack([g, h, w], axis=0).astype(stat_dt)
        return call(padr(gcodes.astype(code_dt), -1),
                    padr(leaf[None].astype(code_dt), -1),
                    padr(ST, 0))                       # [Q8, pL]

    return local


def offset_codes(codes, bin_counts, nbins: int):
    """codes [F, N] (NA == nbins) -> packed global bin ids for the varbin
    kernel.  Done once per tree by the build driver.  Emitted as int16
    when every packed id fits — the ids persist in HBM across all levels
    of the tree, so the narrow dtype halves the histogram kernel's
    dominant streaming input for the whole build."""
    offsets, _, Q8, _ = varbin_layout(bin_counts, nbins + 1)
    off = jnp.asarray(offsets)[:, None]
    bf = jnp.asarray([min(b, nbins) for b in bin_counts],
                     jnp.int32)[:, None]
    out = jnp.where(codes >= nbins, off + bf, codes + off)
    if Q8 < 32_000:
        out = out.astype(jnp.int16)
    return out


@functools.lru_cache(maxsize=None)
def _make_varbin_hist_fn(L: int, F: int, bin_counts: tuple, B: int,
                         n_padded: int, force_impl: str = "",
                         precision: str = "bf16", reduce_mode: str = "hier"):
    """Variable-bin histogram with the DENSE output contract of
    make_hist_fn: (gcodes, leaf, g, h, w) -> H[3, L, F, B].

    ``gcodes`` must be pre-offset (offset_codes).  The packed [Q8, 3L]
    kernel result is re-expanded through the static qmap gather (tiny).
    """
    cl = cluster()
    n_local = n_padded // cl.n_row_shards
    _, _, Q8, qmap = varbin_layout(bin_counts, B)
    if force_impl == "pallas_interpret":
        inner = _make_pallas_varbin_hist(L, F, bin_counts, B, n_local,
                                         interpret=True, precision=precision)
    else:
        inner = _make_pallas_varbin_hist(L, F, bin_counts, B, n_local,
                                         precision=precision)
    qmap_dense = jnp.asarray(qmap[:, list(range(B - 1)) + [B]])  # [F, B]
    # dense layout [.., F, B]: regular bins 0..B-2 then NA at B-1

    def local_hist(gcodes, leaf, g, h, w):
        out = inner(gcodes, leaf, g, h, w)             # [Q8, 3L]
        H = out[qmap_dense.reshape(-1)]                # [F*B, 3L]
        H = H.reshape(F, B, L, 3).transpose(3, 2, 0, 1)
        return psum_shards(H, reduce_mode)

    specs_in = (P(None, ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS),
                P(ROW_AXIS))
    f = shard_map(local_hist, mesh=cl.mesh, in_specs=specs_in, out_specs=P(),
                  check_vma=False)
    return _ledger("hist_varbin", jax.jit(f), orig=f)


make_varbin_hist_fn = _reduce_mode_dispatch(_make_varbin_hist_fn)


def _make_einsum_hist(L: int, F: int, B: int, n_local: int):
    """Portable XLA path (CPU mesh tests, non-TPU backends, and the
    deep-level fallback where [R, 3*L] exceeds scoped VMEM)."""
    blk = max((4 * 1024 * 1024) // max(F * B, 1), 256)
    # deep levels: the [blk, L] leaf one-hot / [blk, 3, L] stats
    # intermediates must stay bounded too
    blk = max(min(blk, 8_388_608 // max(L, 1)), 64)
    blk = min(n_local, blk)
    nblk = (n_local + blk - 1) // blk
    pad_to = nblk * blk

    def local(codes, leaf, g, h, w):
        def padr(x, fill=0):
            return jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                           + [(0, pad_to - n_local)], constant_values=fill)
        codes = padr(codes).reshape(F, nblk, blk).transpose(1, 0, 2)
        leaf = padr(leaf).reshape(nblk, blk)
        S = jnp.stack([g, h, w], axis=1)          # [n, 3]
        S = jnp.pad(S, [(0, pad_to - n_local), (0, 0)]) \
            .reshape(nblk, blk, 3)

        def body(acc, args):
            c, lf, s = args
            Pl = jax.nn.one_hot(lf, L, dtype=jnp.float32)       # [blk, L]
            OH = jax.nn.one_hot(c, B, dtype=jnp.float32)        # [F, blk, B]
            PS = jnp.einsum("rl,rs->rsl", Pl, s)                # [blk,p,L]
            acc = acc + jnp.einsum("rsl,frb->slfb", PS, OH)
            return acc, None
        H0 = jnp.zeros((3, L, F, B), jnp.float32)
        H0 = jax.lax.pcast(H0, ROW_AXES, to='varying')
        H, _ = jax.lax.scan(body, H0, (codes, leaf, S))
        return H

    return local


# the whole [F*B, 3L] float32 result a Pallas histogram kernel may stage
# through VMEM for its custom call
_HIST_RESULT_VMEM_BYTES = 12 * 1024 * 1024


def hist_kernel_kind(L: int, F: int, B: int, *, varbin: bool,
                     on_tpu: bool) -> str:
    """The kernel that histograms ONE site of ``L`` slots: ``"varbin"`` |
    ``"uniform"`` | ``"einsum"``.

    The one rule: the tree builders ask it for every level of the
    level-unrolled program and for the scan program's width
    (shared.make_build_tree_fn), the factories below for their einsum
    fallback, and the tree driver's counter for what it reports
    (``tree_hist_kernel_total``), so a bound moved here moves them all.

    ``varbin`` says the frame's packed per-feature bins may carry the site
    (shared.varbin_kernel_engages: on the TPU, or forced, and packing
    pays).  The packed kernel has no einsum fallback, its minimum row block
    must keep the ``[R, 3L]`` A-build intermediates inside scoped VMEM
    (``3L <= 1024``) and its whole result stages through VMEM.  Past that,
    and for frames that do not pack, the uniform kernel; the portable
    einsum off the TPU, where the result passes the VMEM bound, or where
    even the uniform kernel's minimum row block's ``[R, 3L]`` overflows the
    16M scoped-VMEM stack (``3L > 2048``)."""
    fits_vmem = F * B * 3 * L * 4 <= _HIST_RESULT_VMEM_BYTES
    if varbin and 3 * L <= 1024 and fits_vmem:
        return "varbin"
    if not on_tpu or not fits_vmem or 3 * L > 2048:
        return "einsum"
    return "uniform"


@functools.lru_cache(maxsize=None)
def _make_hist_fn(L: int, F: int, B: int, n_padded: int,
                  force_impl: str = "", precision: str = "bf16",
                  reduce_mode: str = "hier"):
    """Compiled histogram: (codes[N,F], leaf[N], g[N], h[N], w[N]) ->
    H[3, L, F, B] with planes (sum g, sum h, sum w), psum'd over the mesh.

    ``B`` here includes the NA bin (= nbins + 1).  On TPU the local pass is
    the Pallas tpu_hist kernel; elsewhere (CPU test mesh) an equivalent
    einsum program.  ``force_impl`` ("pallas_interpret" | "einsum") pins the
    implementation for cross-checking.
    """
    cl = cluster()
    n_local = n_padded // cl.n_row_shards
    platform = cl.mesh.devices.flat[0].platform
    if force_impl == "pallas_interpret":
        inner = _make_pallas_hist(L, F, B, n_local, interpret=True,
                                  precision=precision)
    elif force_impl == "einsum" or hist_kernel_kind(
            L, F, B, varbin=False, on_tpu=platform == "tpu") == "einsum":
        # off the TPU and at very deep levels: the portable path
        inner = _make_einsum_hist(L, F, B, n_local)
    else:
        inner = _make_pallas_hist(L, F, B, n_local, precision=precision)

    def local_hist(codes, leaf, g, h, w):
        return psum_shards(inner(codes, leaf, g, h, w), reduce_mode)

    specs_in = (P(None, ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS),
                P(ROW_AXIS))
    # check_vma=False: the kernel mixes varying refs with grid-constant
    # iotas, which the vma checker can't see through pallas_call
    f = shard_map(local_hist, mesh=cl.mesh, in_specs=specs_in, out_specs=P(),
                  check_vma=False)
    return _ledger("hist_uniform", jax.jit(f), orig=f)


make_hist_fn = _reduce_mode_dispatch(_make_hist_fn)


def _local_hist_impl(L: int, F: int, B: int, n_local: int, bin_counts=None,
                     force_impl: str = "", precision: str = "bf16"):
    """Per-shard local histogram (PRE-psum) at an (L, n_local) geometry.

    The kernel-selection rules of make_hist_fn / make_varbin_hist_fn
    factored out so the subtraction level driver can run the same kernels
    over a compacted (smaller-sibling) row prefix.  With ``bin_counts`` the
    varbin kernel is used (codes must be pre-offset packed ids) and the
    packed [Q8, 3L] result is re-expanded to the dense [3, L, F, B]
    contract; otherwise the uniform Pallas kernel with the einsum fallback
    (CPU mesh, deep levels: ``hist_kernel_kind``, as make_hist_fn).
    ``force_impl="pallas"`` pins the REAL (non-interpret) kernel off-TPU —
    the AOT Mosaic export gate needs it to lower the true code path from a
    CPU host (tests/test_mosaic_lowering.py).
    """
    platform = cluster().mesh.devices.flat[0].platform
    if bin_counts is not None:
        _, _, _, qmap = varbin_layout(bin_counts, B)
        interpret = force_impl == "pallas_interpret" or \
            (platform != "tpu" and force_impl != "pallas")
        raw = _make_pallas_varbin_hist(L, F, bin_counts, B, n_local,
                                       interpret=interpret,
                                       precision=precision)
        qmap_dense = jnp.asarray(
            np.asarray(qmap)[:, list(range(B - 1)) + [B]].reshape(-1))

        def inner(codes, leaf, g, h, w):
            out = raw(codes, leaf, g, h, w)                # [Q8, 3L]
            H = out[qmap_dense]                            # [F*B, 3L]
            return H.reshape(F, B, L, 3).transpose(3, 2, 0, 1)

        return inner
    if force_impl == "pallas_interpret":
        return _make_pallas_hist(L, F, B, n_local, interpret=True,
                                 precision=precision)
    if force_impl != "pallas" and (
            force_impl == "einsum" or hist_kernel_kind(
                L, F, B, varbin=False, on_tpu=platform == "tpu") == "einsum"):
        return _make_einsum_hist(L, F, B, n_local)
    return _make_pallas_hist(L, F, B, n_local, precision=precision)


@functools.lru_cache(maxsize=None)
def _make_subtract_level_fn(d: int, F: int, B: int, n_padded: int,
                            bin_counts=None, force_impl: str = "",
                            precision: str = "bf16",
                            reduce_mode: str = "hier"):
    """Level-``d`` histogram via smaller-sibling row COMPACTION + parent
    subtraction — DHistogram / LightGBM / gpu_hist's classic halving,
    TPU-shaped (arXiv:1706.08359 §3.2).

    The masked-left subtraction this replaces still streamed ALL N rows
    through the one-hot kernel every level (the stats were zeroed, the VPU
    compare work was not).  Here each shard (a) picks, per parent, the
    child with fewer LOCAL physical rows, (b) compacts those rows into a
    dense prefix of length ``n_local // 2`` (sum over parents of
    min(left, right) can never exceed half the shard — the bound is exact
    because orientation is per-shard), (c) histograms only the prefix at
    the parent-slot geometry, and (d) reconstructs the larger siblings as
    ``H_parent_local - H_small_local`` in f32 before the cross-shard psum.
    The compaction itself is three cumsum-positioned unique-index scatters
    (code planes, leaf plane, g/h/w planes).  On a TPU v5e they are NOT
    bandwidth-bound: the two [planes, n] scatters run at ~12 M rows/s each
    (ledger, PR 28, ``xgb_airlines40m.fit``: 3.30 s and 3.23 s per level of
    40M rows, 0.39 GB/s of 819), ~165 ns a row against the 2.4 ns a row the
    halved kernel saves.  ``hist_level_cost`` prices them, so the tuner
    takes this builder only where the dense grid cannot hold the level.

    The per-shard parent histogram needed for the subtraction rides along
    as a carry: each call returns ``(H_global, H_carry)`` where ``H_carry``
    is the [n_shards, 3, L, F, B] stack of pre-psum shard-local histograms
    that the NEXT level consumes.  ``d == 0`` takes
    ``(codes, leaf, g, h, w)`` (full build, all rows in leaf 0); ``d >= 1``
    additionally takes the previous level's carry.  Accumulation stays f32
    end to end (kernel outputs f32; h/w planes of the reconstructed side
    are clamped at 0 — see the driver's rounding note), so the dense
    [3, 2^d, F, B] contract matches the full build to f32 tolerance and
    split search is unchanged.
    """
    cl = cluster()
    n_local = n_padded // cl.n_row_shards
    Lp = 2 ** max(d - 1, 0)            # parent slots the kernel histograms
    Lc = 2 ** d                        # children at this level
    cap = n_local // 2 if d > 0 else n_local
    inner = _local_hist_impl(Lp, F, B, cap, bin_counts=bin_counts,
                             force_impl=force_impl, precision=precision)
    specs_row = (P(None, ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS),
                 P(ROW_AXIS))

    if d == 0:
        def local0(codes, leaf, g, h, w):
            Hl = inner(codes, leaf, g, h, w)
            return psum_shards(Hl, reduce_mode), Hl[None]

        f = shard_map(local0, mesh=cl.mesh, in_specs=specs_row,
                      out_specs=(P(), P(ROW_AXIS)), check_vma=False)
        return _ledger("hist_subtract", jax.jit(f), orig=f)

    def locald(codes, leaf, g, h, w, carry):
        Hp = carry[0]                              # this shard's [3,Lp,F,B]
        # local physical row count per child — orientation only (weighted
        # counts can't bound the compaction buffer: w=0 sampled-out rows
        # still occupy kernel lanes).  The compare fuses into the reduce.
        cidx = jax.lax.broadcasted_iota(jnp.int32, (Lc, 1), 0)
        cnt = jnp.sum(cidx == leaf[None, :], axis=1, dtype=jnp.int32)
        small_is_left = cnt[0::2] <= cnt[1::2]                 # [Lp]
        chosen_child = jnp.stack(
            [small_is_left, ~small_is_left], axis=1).reshape(-1)   # [Lc]
        # per-row smaller-sibling flag via the MXU one-hot product —
        # not a per-row gather, which serializes on the TPU
        chosen = table_lookup(
            chosen_child.astype(jnp.float32)[None], leaf, Lc)[0] > 0.5
        # dense-prefix positions; unchosen rows target the out-of-bounds
        # slot ``cap`` and are dropped by the scatter
        target = jnp.where(chosen,
                           jnp.cumsum(chosen.astype(jnp.int32)) - 1, cap)
        ccodes = jnp.zeros((F, cap), codes.dtype) \
            .at[:, target].set(codes, mode="drop", unique_indices=True)
        pleaf = jnp.zeros((cap,), jnp.int32) \
            .at[target].set((leaf >> 1).astype(jnp.int32), mode="drop",
                            unique_indices=True)
        st = jnp.zeros((3, cap), jnp.float32) \
            .at[:, target].set(
                jnp.stack([g, h, w]).astype(jnp.float32), mode="drop",
                unique_indices=True)
        Hs = inner(ccodes, pleaf, st[0], st[1], st[2])     # [3, Lp, F, B]
        Ho = Hp - Hs
        # clamp the h/w planes at 0: per-level kernel routing can pair
        # differently-rounded kernels across the subtraction (bf16 vs f32),
        # and negative hessian/weight sums would corrupt best_splits
        Ho = Ho.at[1:].max(0.0)
        sl = small_is_left[None, :, None, None]
        Hl_ = jnp.where(sl, Hs, Ho)
        Hr_ = jnp.where(sl, Ho, Hs)
        Hloc = jnp.stack([Hl_, Hr_], axis=2).reshape(3, Lc, F, B)
        return psum_shards(Hloc, reduce_mode), Hloc[None]

    f = shard_map(locald, mesh=cl.mesh,
                  in_specs=specs_row + (P(ROW_AXIS),),
                  out_specs=(P(), P(ROW_AXIS)), check_vma=False)
    return _ledger("hist_subtract", jax.jit(f), orig=f)


make_subtract_level_fn = _reduce_mode_dispatch(_make_subtract_level_fn)


@functools.lru_cache(maxsize=None)
def _make_batched_level_fn(d: int, K: int, F: int, B: int, n_padded: int,
                           bin_counts=None, force_impl: str = "",
                           precision: str = "bf16", subtract: bool = True,
                           reduce_mode: str = "hier"):
    """Level-``d`` histograms for K trees in ONE kernel launch.

    The K-class multinomial round used to issue K separate level programs
    (K dispatches + K traced copies); here the per-tree local pass is
    ``jax.vmap``-ed over a leading K axis, which Pallas lowers to a single
    ``pallas_call`` with K prepended to the grid — one launch per level
    regardless of K (the batching rule leaves the shared ``codes`` operand
    unbatched, so the dominant streaming input is NOT duplicated K times).
    Per-tree row compaction (``subtract=True``, mirroring
    make_subtract_level_fn) stays plain vmapped XLA: each tree picks its
    own smaller siblings, so codes/leaf/stat planes diverge per tree after
    the scatter and batch cleanly into the kernel.

    ``subtract=False`` is the full-rebuild contract (hist_mode="full") at
    a K axis — the crosscheck oracle for the batched path.

    Shapes: codes [F, N] shared; leaf/g/h/w [K, N]; ``d >= 1`` subtract
    additionally takes carry [n_shards, K, 3, Lp, F, B].  Returns
    H [K, 3, 2^d, F, B] (psum'd) and, for subtract, the next carry.
    """
    cl = cluster()
    n_local = n_padded // cl.n_row_shards
    Lc = 2 ** d
    Lp = 2 ** max(d - 1, 0)
    specs_k = (P(None, ROW_AXIS),) * 5

    if not subtract:
        inner = _local_hist_impl(Lc, F, B, n_local, bin_counts=bin_counts,
                                 force_impl=force_impl, precision=precision)

        def localf(codes, leafK, gK, hK, wK):
            Hl = jax.vmap(inner, in_axes=(None, 0, 0, 0, 0))(
                codes, leafK, gK, hK, wK)
            return psum_shards(Hl, reduce_mode)

        f = shard_map(localf, mesh=cl.mesh, in_specs=specs_k, out_specs=P(),
                      check_vma=False)
        return _ledger("hist_batched", jax.jit(f), orig=f)

    cap = n_local // 2 if d > 0 else n_local
    inner = _local_hist_impl(Lp, F, B, cap, bin_counts=bin_counts,
                             force_impl=force_impl, precision=precision)

    if d == 0:
        def local0(codes, leafK, gK, hK, wK):
            Hl = jax.vmap(inner, in_axes=(None, 0, 0, 0, 0))(
                codes, leafK, gK, hK, wK)
            return psum_shards(Hl, reduce_mode), Hl[None]

        f = shard_map(local0, mesh=cl.mesh, in_specs=specs_k,
                      out_specs=(P(), P(ROW_AXIS)), check_vma=False)
        return _ledger("hist_batched", jax.jit(f), orig=f)

    def locald(codes, leafK, gK, hK, wK, carry):
        HpK = carry[0]                             # [K, 3, Lp, F, B]

        def one(leaf, g, h, w, Hp):
            # per-tree smaller-sibling compaction — the exact
            # make_subtract_level_fn body, codes closed over (shared)
            cidx = jax.lax.broadcasted_iota(jnp.int32, (Lc, 1), 0)
            cnt = jnp.sum(cidx == leaf[None, :], axis=1, dtype=jnp.int32)
            small_is_left = cnt[0::2] <= cnt[1::2]
            chosen_child = jnp.stack(
                [small_is_left, ~small_is_left], axis=1).reshape(-1)
            chosen = table_lookup(
                chosen_child.astype(jnp.float32)[None], leaf, Lc)[0] > 0.5
            target = jnp.where(
                chosen, jnp.cumsum(chosen.astype(jnp.int32)) - 1, cap)
            ccodes = jnp.zeros((F, cap), codes.dtype) \
                .at[:, target].set(codes, mode="drop", unique_indices=True)
            pleaf = jnp.zeros((cap,), jnp.int32) \
                .at[target].set((leaf >> 1).astype(jnp.int32), mode="drop",
                                unique_indices=True)
            st = jnp.zeros((3, cap), jnp.float32) \
                .at[:, target].set(
                    jnp.stack([g, h, w]).astype(jnp.float32), mode="drop",
                    unique_indices=True)
            Hs = inner(ccodes, pleaf, st[0], st[1], st[2])
            Ho = Hp - Hs
            Ho = Ho.at[1:].max(0.0)
            sl = small_is_left[None, :, None, None]
            Hl_ = jnp.where(sl, Hs, Ho)
            Hr_ = jnp.where(sl, Ho, Hs)
            return jnp.stack([Hl_, Hr_], axis=2).reshape(3, Lc, F, B)

        HlocK = jax.vmap(one)(leafK, gK, hK, wK, HpK)
        return psum_shards(HlocK, reduce_mode), HlocK[None]

    f = shard_map(locald, mesh=cl.mesh, in_specs=specs_k + (P(ROW_AXIS),),
                  out_specs=(P(), P(ROW_AXIS)), check_vma=False)
    return _ledger("hist_batched", jax.jit(f), orig=f)


make_batched_level_fn = _reduce_mode_dispatch(_make_batched_level_fn)


@functools.lru_cache(maxsize=None)
def _make_scan_level_fn(W: int, F: int, B: int, n_padded: int,
                        bin_counts=None, force_impl: str = "",
                        precision: str = "bf16", reduce_mode: str = "hier"):
    """Depth-generic subtract-level histogram for the scan-fused build.

    The per-level factory (make_subtract_level_fn) closes over the level
    index ``d`` — one compiled program per depth, one dispatch per level.
    The whole-tree ``lax.scan`` needs ONE program whose shapes do not
    change across iterations, so this variant runs the identical
    smaller-sibling compaction at a FIXED child width ``W`` (the deepest
    scanned level's 2^d) with parent width ``W // 2``, through the packed
    variable-bin kernel where ``bin_counts`` is given (``codes`` are then
    the pre-offset packed ids, as for make_subtract_level_fn).  Shallower
    levels simply leave their padding slots empty: a slot with zero local
    rows has ``cnt == 0`` on both children, contributes an all-False chosen
    mask (exact +0.0 histogram), and reconstructs to exact +0.0 on the
    large side (``0 - 0`` clamped) — so padded slots are bitwise inert
    and the live prefix matches the per-level program (see the blocking
    caveat in shared.resolve_tree_program).

    ``dead`` is the scan-carried early-exit predicate (no alive leaf
    anywhere): the compaction + kernel launch is skipped under a
    ``lax.cond`` and the level degenerates to the pure parent
    passthrough — which IS what the live branch computes when every row
    sits on an even child (sibling side empty -> Hs = +0.0, large side
    = clamp(Hp)), so taking the branch never changes a bit.

    Returns ``(H_global [3, W, F, B], carry [n_shards, 3, W//2, F, B])``
    — the carry keeps only the first W//2 child slots, which covers
    every live slot of any non-final level (2^d <= W/2 below the last
    iteration; the final carry is discarded).
    """
    if W < 2 or W & (W - 1):
        raise ValueError(f"scan level width must be a power of two >= 2, "
                         f"got {W}")
    cl = cluster()
    n_local = n_padded // cl.n_row_shards
    Wp = W // 2
    cap = n_local // 2
    inner = _local_hist_impl(Wp, F, B, cap, bin_counts=bin_counts,
                             force_impl=force_impl, precision=precision)
    specs_row = (P(None, ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS),
                 P(ROW_AXIS))

    def _live(codes, leaf, g, h, w, Hp):
        # make_subtract_level_fn's locald body at the (W, Wp) geometry
        cidx = jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)
        cnt = jnp.sum(cidx == leaf[None, :], axis=1, dtype=jnp.int32)
        small_is_left = cnt[0::2] <= cnt[1::2]                 # [Wp]
        chosen_child = jnp.stack(
            [small_is_left, ~small_is_left], axis=1).reshape(-1)   # [W]
        chosen = table_lookup(
            chosen_child.astype(jnp.float32)[None], leaf, W)[0] > 0.5
        target = jnp.where(chosen,
                           jnp.cumsum(chosen.astype(jnp.int32)) - 1, cap)
        ccodes = jnp.zeros((F, cap), codes.dtype) \
            .at[:, target].set(codes, mode="drop", unique_indices=True)
        pleaf = jnp.zeros((cap,), jnp.int32) \
            .at[target].set((leaf >> 1).astype(jnp.int32), mode="drop",
                            unique_indices=True)
        st = jnp.zeros((3, cap), jnp.float32) \
            .at[:, target].set(
                jnp.stack([g, h, w]).astype(jnp.float32), mode="drop",
                unique_indices=True)
        Hs = inner(ccodes, pleaf, st[0], st[1], st[2])     # [3, Wp, F, B]
        Ho = Hp - Hs
        Ho = Ho.at[1:].max(0.0)
        sl = small_is_left[None, :, None, None]
        Hl_ = jnp.where(sl, Hs, Ho)
        Hr_ = jnp.where(sl, Ho, Hs)
        return jnp.stack([Hl_, Hr_], axis=2).reshape(3, W, F, B)

    def _skip(codes, leaf, g, h, w, Hp):
        # all rows on even children: the live branch reduces to exactly
        # this (Hs = +0.0, clamped parent on the left, zeros right)
        Hoc = Hp.at[1:].max(0.0)
        return jnp.stack([Hoc, jnp.zeros_like(Hp)],
                         axis=2).reshape(3, W, F, B)

    def locald(codes, leaf, g, h, w, carry, dead):
        Hp = carry[0]                              # this shard's [3,Wp,F,B]
        Hloc = jax.lax.cond(dead, _skip, _live, codes, leaf, g, h, w, Hp)
        return psum_shards(Hloc, reduce_mode), Hloc[:, :Wp][None]

    f = shard_map(locald, mesh=cl.mesh,
                  in_specs=specs_row + (P(ROW_AXIS), P()),
                  out_specs=(P(), P(ROW_AXIS)), check_vma=False)
    return _ledger("hist_scan", jax.jit(f), orig=f)


make_scan_level_fn = _reduce_mode_dispatch(_make_scan_level_fn)


@functools.lru_cache(maxsize=None)
def _make_batched_scan_level_fn(W: int, K: int, F: int, B: int,
                                n_padded: int, bin_counts=None,
                                force_impl: str = "",
                                precision: str = "bf16",
                                reduce_mode: str = "hier"):
    """K-tree batched variant of ``make_scan_level_fn`` — one launch per
    scan iteration regardless of K (the vmap batching rule keeps the
    shared ``codes`` operand unbatched, mirroring make_batched_level_fn).
    ``dead`` is all-trees-dead; an individually finished tree inside a
    live level already produces the bitwise parent passthrough on its
    own (its rows all sit on even children), so no per-tree predicate is
    needed.  Shapes: leaf/g/h/w [K, N]; carry [n_shards, K, 3, W//2, F,
    B]; returns H [K, 3, W, F, B] plus the next carry."""
    if W < 2 or W & (W - 1):
        raise ValueError(f"scan level width must be a power of two >= 2, "
                         f"got {W}")
    cl = cluster()
    n_local = n_padded // cl.n_row_shards
    Wp = W // 2
    cap = n_local // 2
    inner = _local_hist_impl(Wp, F, B, cap, bin_counts=bin_counts,
                             force_impl=force_impl, precision=precision)
    specs_k = (P(None, ROW_AXIS),) * 5

    def locald(codes, leafK, gK, hK, wK, carry, dead):
        HpK = carry[0]                             # [K, 3, Wp, F, B]

        def one(leaf, g, h, w, Hp):
            cidx = jax.lax.broadcasted_iota(jnp.int32, (W, 1), 0)
            cnt = jnp.sum(cidx == leaf[None, :], axis=1, dtype=jnp.int32)
            small_is_left = cnt[0::2] <= cnt[1::2]
            chosen_child = jnp.stack(
                [small_is_left, ~small_is_left], axis=1).reshape(-1)
            chosen = table_lookup(
                chosen_child.astype(jnp.float32)[None], leaf, W)[0] > 0.5
            target = jnp.where(
                chosen, jnp.cumsum(chosen.astype(jnp.int32)) - 1, cap)
            ccodes = jnp.zeros((F, cap), codes.dtype) \
                .at[:, target].set(codes, mode="drop", unique_indices=True)
            pleaf = jnp.zeros((cap,), jnp.int32) \
                .at[target].set((leaf >> 1).astype(jnp.int32), mode="drop",
                                unique_indices=True)
            st = jnp.zeros((3, cap), jnp.float32) \
                .at[:, target].set(
                    jnp.stack([g, h, w]).astype(jnp.float32), mode="drop",
                    unique_indices=True)
            Hs = inner(ccodes, pleaf, st[0], st[1], st[2])
            Ho = Hp - Hs
            Ho = Ho.at[1:].max(0.0)
            sl = small_is_left[None, :, None, None]
            Hl_ = jnp.where(sl, Hs, Ho)
            Hr_ = jnp.where(sl, Ho, Hs)
            return jnp.stack([Hl_, Hr_], axis=2).reshape(3, W, F, B)

        def _live(codes, leafK, gK, hK, wK, HpK):
            return jax.vmap(one)(leafK, gK, hK, wK, HpK)

        def _skip(codes, leafK, gK, hK, wK, HpK):
            def pas(Hp):
                Hoc = Hp.at[1:].max(0.0)
                return jnp.stack([Hoc, jnp.zeros_like(Hp)],
                                 axis=2).reshape(3, W, F, B)
            return jax.vmap(pas)(HpK)

        HlocK = jax.lax.cond(dead, _skip, _live,
                             codes, leafK, gK, hK, wK, HpK)
        return psum_shards(HlocK, reduce_mode), HlocK[:, :, :Wp][None]

    f = shard_map(locald, mesh=cl.mesh,
                  in_specs=specs_k + (P(ROW_AXIS), P()),
                  out_specs=(P(), P(ROW_AXIS)), check_vma=False)
    return _ledger("hist_scan_batched", jax.jit(f), orig=f)


make_batched_scan_level_fn = _reduce_mode_dispatch(_make_batched_scan_level_fn)


def sparse_slot_budget(F: int, B: int,
                       cap_bytes: int = 64 * 1024 * 1024) -> int:
    """Static slot capacity for node-sparse deep levels.

    The dense grid hits its memory wall where ``F*B*3*2^d*4`` exceeds the
    64 MB histogram budget (shared.effective_max_depth).  The sparse layout
    sizes its slot axis so the SAME budget holds at every depth: the
    largest multiple of 8 (the f32 sublane tile) slots whose [A, F, B]
    triple-plane grid fits ``cap_bytes``, clamped to [16, 4096].  Levels
    whose full child width 2^d is smaller than this use 2^d directly."""
    a = cap_bytes // (F * B * 3 * 4)
    return int(max(16, min(4096, (a // 8) * 8)))


# Unique-index scatters of a [planes, n] stack that one compacting level
# runs per tree: the code planes and the g/h/w planes.  Each costs the same
# per source row whatever its plane count (8 planes 3.30 s, 3 planes 3.23 s
# per 40M rows on the v5e).  The leaf plane's 1-D scatter lowers to another
# op, 17x faster there (0.19 s), and is left out of the price.
_COMPACTION_STACK_SCATTERS = 2


def hist_level_cost(n_rows: int, F: int, B: int, width: int, K: int = 1,
                    *, layout: str = "dense",
                    hist_mode: str = "subtract",
                    cap_bytes: int = 64 * 1024 * 1024):
    """``(bytes, scatter_rows)`` of ONE level's histogram build — the cost
    atom ``runtime/autotune.py`` seeds its model from, kept next to the
    kernels it prices so a kernel change updates the model in one place.
    The model divides each term by its own device figure (HBM bandwidth;
    rows per second of a unique-index row scatter).

    ``bytes`` is the kernel's roofline traffic.  Reads: int32 codes + f32
    g/h/w per contributing row per feature (past the root a subtract level
    reads the compacted smaller siblings, <= n/2 rows; the full oracle
    reads every row).  Writes: the [width|A, F, B] triple-plane grid, f32.

    ``scatter_rows`` is what the compaction moves to build that prefix:
    every one of the level's ``n_rows`` source rows through each stack
    scatter, per tree.  Zero at the root and under ``full``.  The scatters
    run three to four orders of magnitude under the HBM roofline (ledger,
    PR 28: 6.72 s a level at 40M rows x 8 on a v5e, for 0.097 s of kernel
    saved), which is why they are counted in rows, not bytes.

    Returns ``None`` when the dense grid for ``width`` leaves exceeds the
    histogram budget — that config cannot run and the model must price it
    out."""
    compacts = hist_mode != "full" and width > 1
    rows = n_rows // 2 if compacts else n_rows
    read = rows * F * (4 + 3 * 4) * max(K, 1)
    slots = width if layout == "dense" else min(width, sparse_slot_budget(
        F, B, cap_bytes))
    grid = slots * F * B * 3 * 4 * max(K, 1)
    if layout == "dense" and grid > cap_bytes * max(K, 1):
        return None
    if layout == "sparse":
        # slot-map gathers: a small constant factor over the dense write
        # path, paid for unbounded depth
        grid = int(grid * 1.15) + rows * 4
    scatter_rows = (float(_COMPACTION_STACK_SCATTERS * n_rows * max(K, 1))
                    if compacts else 0.0)
    return float(read + grid), scatter_rows


def split_search_passes(split_mode: str) -> float:
    """Histogram re-read factor of the split search: the fused
    winner-record kernel reads the grid once; the separate multi-pass
    oracle scans it ~3x (gains, argmax, record)."""
    return 1.0 if split_mode == "fused" else 3.0


def sparse_slot_maps(valid_prev, A_next: int):
    """Child-slot assignment for the next node-sparse level.

    ``valid_prev`` [Ap] holds the previous level's split decisions in that
    level's own slot (or dense-leaf) space.  Both children of every valid
    slot get a contiguous slot pair (even = left), in slot order.  Returns

    - ``child_base`` [Ap+1]: first child slot of each previous slot
      (``A_next`` when the slot gets no pair — invalid, past the slot
      budget, or the appended sentinel row),
    - ``ps_of_slot`` [A_next]: each slot's parent slot (pairs share it;
      phantom slots past the live range point at 0 and are masked off),
    - ``real`` [A_next]: live-slot mask (phantom slots are never written
      by any row and their split records are discarded).

    When a level has more alive children than ``A_next`` slots, later
    pairs are dropped ATOMICALLY in slot order and those children become
    terminal leaves — the deterministic num_leaves-style degradation the
    operations guide documents (tests/test_sparse_levels.py shows it)."""
    Ap = valid_prev.shape[0]
    idx = jnp.cumsum(valid_prev.astype(jnp.int32)) - 1          # [Ap]
    kept = valid_prev & (2 * idx + 1 < A_next)
    base = jnp.where(kept, 2 * idx, A_next).astype(jnp.int32)
    child_base = jnp.concatenate(
        [base, jnp.full((1,), A_next, jnp.int32)])              # [Ap+1]
    half = jnp.zeros((A_next // 2,), jnp.int32) \
        .at[jnp.where(kept, idx, A_next // 2)] \
        .set(jnp.arange(Ap, dtype=jnp.int32), mode="drop")
    ps_of_slot = jnp.repeat(half, 2)
    real = jnp.arange(A_next) < 2 * jnp.sum(kept.astype(jnp.int32))
    return child_base, ps_of_slot, real


def _sparse_local_body(A_prev: int, A: int, F: int, cap: int, inner):
    """Per-shard node-sparse level body shared by the single-tree and
    batched-K wrappers: smaller-sibling compaction labeled by PARENT SLOT
    (not dense parent id), subtraction against the slot-space carry, then
    a slot-axis gather into this level's [A] slot space."""

    def body(codes, sleaf, g, h, w, Hp, ps_of_slot):
        side = jnp.arange(A, dtype=jnp.int32) & 1               # [A]
        # local physical row count per slot — orientation only, exactly as
        # the dense subtract kernel counts per dense child
        sidx = jax.lax.broadcasted_iota(jnp.int32, (A, 1), 0)
        cnt = jnp.sum(sidx == sleaf[None, :], axis=1, dtype=jnp.int32)
        # fold to per-parent-slot left/right counts (tiny [A] scatter-add;
        # phantom slots contribute 0 rows so pointing them at parent 0 is
        # harmless)
        cl_ = jnp.zeros((A_prev,), jnp.int32).at[ps_of_slot].add(
            jnp.where(side == 0, cnt, 0), mode="drop")
        cr_ = jnp.zeros((A_prev,), jnp.int32).at[ps_of_slot].add(
            jnp.where(side == 1, cnt, 0), mode="drop")
        small_is_left = cl_ <= cr_                              # [A_prev]
        chosen_slot = jnp.where(side == 0, small_is_left[ps_of_slot],
                                ~small_is_left[ps_of_slot])     # [A]
        # per-row (smaller-sibling?, parent slot) in ONE one-hot product
        # over the A+1-wide slot table; the sentinel row (slot A — nodes
        # whose chain died or overflowed) is never chosen, so dead rows
        # stay out of the histogram entirely
        tbl = jnp.stack([
            jnp.concatenate([chosen_slot.astype(jnp.float32),
                             jnp.zeros((1,), jnp.float32)]),
            jnp.concatenate([ps_of_slot.astype(jnp.float32),
                             jnp.zeros((1,), jnp.float32)])])
        t = table_lookup(tbl, sleaf, A + 1)                     # [2, N]
        chosen = t[0] > 0.5
        prow = t[1].astype(jnp.int32)
        target = jnp.where(chosen,
                           jnp.cumsum(chosen.astype(jnp.int32)) - 1, cap)
        ccodes = jnp.zeros((F, cap), codes.dtype) \
            .at[:, target].set(codes, mode="drop", unique_indices=True)
        pleaf = jnp.zeros((cap,), jnp.int32) \
            .at[target].set(prow, mode="drop", unique_indices=True)
        st = jnp.zeros((3, cap), jnp.float32) \
            .at[:, target].set(
                jnp.stack([g, h, w]).astype(jnp.float32), mode="drop",
                unique_indices=True)
        Hs = inner(ccodes, pleaf, st[0], st[1], st[2])     # [3, A_prev,F,B]
        Ho = Hp - Hs
        Ho = Ho.at[1:].max(0.0)
        # gather each slot's histogram from its parent row: the smaller
        # child reads Hs, the larger its reconstruction — a slot-axis
        # gather over A blocks, NOT a per-row op
        Hs_g = jnp.take(Hs, ps_of_slot, axis=1)
        Ho_g = jnp.take(Ho, ps_of_slot, axis=1)
        return jnp.where(chosen_slot[None, :, None, None], Hs_g, Ho_g)

    return body


@functools.lru_cache(maxsize=None)
def _make_sparse_level_fn(A_prev: int, A: int, F: int, B: int,
                          n_padded: int, bin_counts=None,
                          force_impl: str = "", precision: str = "bf16",
                          reduce_mode: str = "hier"):
    """Node-sparse deep-level histogram: [A, F, B] slots for ALIVE leaves
    instead of the dense [2^d, F, B] grid (ROADMAP item 1 — the CSR move
    the GPU tree-boosting literature sizes deep levels by).

    Below the depth threshold the smaller-sibling compaction already
    streams <= N/2 rows, but the dense slot grid kept histogram bytes at
    F*B*3*2^d*4 — the 64 MB wall that capped depth.  Here the level is
    keyed by slot ids: rows carry ``sleaf`` [N] in [0, A] (A = "no slot":
    terminal chains and budget overflow), the carry is the PREVIOUS
    level's per-shard slot-space histograms [n_shards, 3, A_prev, F, B],
    and ``ps_of_slot`` [A] (replicated) maps each slot to its parent's
    slot — at the dense->sparse boundary the "previous slot space" is just
    the dense parent id space, so the first sparse level consumes the
    dense subtract carry unchanged.  When every parent is valid and
    A = 2^d the slot map is the identity and the output is bit-identical
    to make_subtract_level_fn; with dead chains the compaction prefix
    differs (dead rows are dropped rather than histogrammed), so parity
    is structural + f32-tolerance (tests/test_sparse_levels.py).

    Returns ``(H_global [3, A, F, B], carry [n_shards, 3, A, F, B])``.
    """
    cl = cluster()
    n_local = n_padded // cl.n_row_shards
    cap = n_local // 2
    inner = _local_hist_impl(A_prev, F, B, cap, bin_counts=bin_counts,
                             force_impl=force_impl, precision=precision)
    body = _sparse_local_body(A_prev, A, F, cap, inner)

    def locald(codes, sleaf, g, h, w, carry, ps_of_slot):
        Hloc = body(codes, sleaf, g, h, w, carry[0], ps_of_slot)
        return psum_shards(Hloc, reduce_mode), Hloc[None]

    specs_in = (P(None, ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS), P(ROW_AXIS),
                P(ROW_AXIS), P(ROW_AXIS), P())
    f = shard_map(locald, mesh=cl.mesh, in_specs=specs_in,
                  out_specs=(P(), P(ROW_AXIS)), check_vma=False)
    return _ledger("hist_sparse", jax.jit(f), orig=f)


make_sparse_level_fn = _reduce_mode_dispatch(_make_sparse_level_fn)


@functools.lru_cache(maxsize=None)
def _make_batched_sparse_level_fn(A_prev: int, A: int, K: int, F: int,
                                  B: int, n_padded: int, bin_counts=None,
                                  force_impl: str = "",
                                  precision: str = "bf16",
                                  reduce_mode: str = "hier"):
    """K-tree node-sparse level in ONE kernel launch — the
    make_batched_level_fn contract at the sparse slot geometry.

    Each tree has its own slot assignment (per-tree valid flags), so
    ``sleaf``/``ps_of_slot`` carry a leading [K]; the per-tree body is
    vmapped and Pallas prepends K to the grid exactly as the dense
    batched path does, keeping the launch count at one hist + one records
    kernel per level regardless of K.  Shapes: codes [F, N] shared;
    sleaf/g/h/w [K, N]; carry [n_shards, K, 3, A_prev, F, B];
    ps_of_slot [K, A] replicated.  Returns (H [K, 3, A, F, B], carry)."""
    cl = cluster()
    n_local = n_padded // cl.n_row_shards
    cap = n_local // 2
    inner = _local_hist_impl(A_prev, F, B, cap, bin_counts=bin_counts,
                             force_impl=force_impl, precision=precision)
    body = _sparse_local_body(A_prev, A, F, cap, inner)

    def locald(codes, sleafK, gK, hK, wK, carry, psK):
        HlocK = jax.vmap(body, in_axes=(None, 0, 0, 0, 0, 0, 0))(
            codes, sleafK, gK, hK, wK, carry[0], psK)
        return psum_shards(HlocK, reduce_mode), HlocK[None]

    specs_in = (P(None, ROW_AXIS),) * 5 + (P(ROW_AXIS), P())
    f = shard_map(locald, mesh=cl.mesh, in_specs=specs_in,
                  out_specs=(P(), P(ROW_AXIS)), check_vma=False)
    return _ledger("hist_batched_sparse", jax.jit(f), orig=f)


make_batched_sparse_level_fn = \
    _reduce_mode_dispatch(_make_batched_sparse_level_fn)


def _soft_threshold(G, alpha):
    return jnp.sign(G) * jnp.maximum(jnp.abs(G) - alpha, 0.0)


def _score(G, H, lam, alpha=0.0):
    Gt = _soft_threshold(G, alpha)
    return Gt * Gt / (H + lam)


def newton_value(g, h, reg_lambda: float, reg_alpha: float):
    """Soft-thresholded Newton node value — the ONE formula shared by
    split rejection, bound propagation and leaf fitting (they must stay
    numerically identical for monotone enforcement to be consistent)."""
    num = jnp.sign(g) * jnp.maximum(jnp.abs(g) - reg_alpha, 0.0)
    return -num / (h + reg_lambda + 1e-12)


@functools.partial(jax.jit, static_argnames=("nbins",))
def best_splits(Hist, nbins: int, reg_lambda: float, min_rows: float,
                min_split_improvement: float, feat_mask=None,
                reg_alpha: float = 0.0, gamma: float = 0.0,
                min_child_weight: float = 0.0, mono=None):
    """Best split per leaf from H[3, L, F, B] (B = nbins regular + 1 NA bin).

    Tries NA-left and NA-right (XGBoost's sparsity-aware default direction;
    the reference tracks NA in DHistogram the same way).  Returns per-leaf
    (feat, bin, na_left, gain, valid).  ``feat_mask`` [L, F] (or [F]) disables
    features per leaf (DRF mtries / column sampling).

    ``reg_alpha`` / ``gamma`` / ``min_child_weight`` give the exact XGBoost
    objective: gain = 1/2(scoreL + scoreR - parent) - gamma with L1
    soft-thresholded numerators and a hessian-sum child constraint
    (libxgboost split_evaluator; h2o drives it via
    hex/tree/xgboost/XGBoostModel.java:260-298 tree_method=hist params).
    """
    G, Hs, C = Hist[0], Hist[1], Hist[2]           # [L, F, B]
    g_na, h_na, c_na = G[..., -1], Hs[..., -1], C[..., -1]
    Gr, Hr, Cr = G[..., :-1], Hs[..., :-1], C[..., :-1]
    cumG = jnp.cumsum(Gr, -1)
    cumH = jnp.cumsum(Hr, -1)
    cumC = jnp.cumsum(Cr, -1)
    totG = cumG[..., -1] + g_na                    # [L, F]
    totH = cumH[..., -1] + h_na
    totC = cumC[..., -1] + c_na
    parent = _score(totG, totH, reg_lambda, reg_alpha)   # [L, F]

    # candidate split after bin b (left = bins <= b), b in [0, nbins-2]
    GL, HL, CL = cumG[..., :-1], cumH[..., :-1], cumC[..., :-1]
    GR = totG[..., None] - GL - g_na[..., None]
    HR = totH[..., None] - HL - h_na[..., None]
    CR = totC[..., None] - CL - c_na[..., None]

    def gain_with_na(gl, hl, cl, gr, hr, cr):
        g = 0.5 * (_score(gl, hl, reg_lambda, reg_alpha)
                   + _score(gr, hr, reg_lambda, reg_alpha)
                   - parent[..., None]) - gamma
        ok = (cl >= min_rows) & (cr >= min_rows) & \
            (hl >= min_child_weight) & (hr >= min_child_weight)
        if mono is not None:
            # monotone constraints (XGBoost split_evaluator order test):
            # reject candidates whose child values break the direction
            vl = newton_value(gl, hl, reg_lambda, reg_alpha)
            vr = newton_value(gr, hr, reg_lambda, reg_alpha)
            c = mono[None, :, None]
            ok = ok & ~(((c > 0) & (vl > vr)) | ((c < 0) & (vl < vr)))
        return jnp.where(ok, g, -jnp.inf)

    gain_naL = gain_with_na(GL + g_na[..., None], HL + h_na[..., None],
                            CL + c_na[..., None], GR, HR, CR)
    gain_naR = gain_with_na(GL, HL, CL, GR + g_na[..., None],
                            HR + h_na[..., None], CR + c_na[..., None])
    na_left_better = gain_naL >= gain_naR
    gain = jnp.maximum(gain_naL, gain_naR)         # [L, F, nbins-1]
    if feat_mask is not None:
        m = feat_mask if feat_mask.ndim == 2 else feat_mask[None, :]
        gain = jnp.where(m[..., None], gain, -jnp.inf)

    L, F = parent.shape
    flat = gain.reshape(L, -1)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], 1)[:, 0]
    feat = (best // (nbins - 1)).astype(jnp.int32)
    bin_ = (best % (nbins - 1)).astype(jnp.int32)
    na_left = jnp.take_along_axis(
        na_left_better.reshape(L, -1), best[:, None], 1)[:, 0]
    valid = jnp.isfinite(best_gain) & \
        (best_gain > min_split_improvement) & (totC >= 2 * min_rows).any(-1)

    # child sufficient statistics at the chosen split (G, H, C per side) —
    # lets the final level derive Newton leaf values with no extra data pass
    def pick(a):
        return jnp.take_along_axis(a.reshape(L, -1), best[:, None], 1)[:, 0]
    gl, hl, cl = pick(GL), pick(HL), pick(CL)
    gr, hr, cr = pick(GR), pick(HR), pick(CR)
    gna, hna, cna = pick(jnp.broadcast_to(g_na[..., None], GL.shape)), \
        pick(jnp.broadcast_to(h_na[..., None], HL.shape)), \
        pick(jnp.broadcast_to(c_na[..., None], CL.shape))
    gl = jnp.where(na_left, gl + gna, gl)
    hl = jnp.where(na_left, hl + hna, hl)
    cl = jnp.where(na_left, cl + cna, cl)
    gr = jnp.where(na_left, gr, gr + gna)
    hr = jnp.where(na_left, hr, hr + hna)
    cr = jnp.where(na_left, cr, cr + cna)
    # terminal (invalid) nodes: everything routes to the left child
    ftot = jnp.take_along_axis(totG, feat[:, None], 1)[:, 0]
    htot = jnp.take_along_axis(totH, feat[:, None], 1)[:, 0]
    ctot = jnp.take_along_axis(totC, feat[:, None], 1)[:, 0]
    gl = jnp.where(valid, gl, ftot)
    hl = jnp.where(valid, hl, htot)
    cl = jnp.where(valid, cl, ctot)
    gr = jnp.where(valid, gr, 0.0)
    hr = jnp.where(valid, hr, 0.0)
    cr = jnp.where(valid, cr, 0.0)
    children = jnp.stack([gl, hl, cl, gr, hr, cr], axis=1)   # [L, 6]
    return feat, bin_, na_left, best_gain, valid, children


# --------------------------------------------------------- fused split search
#
# best_splits above materializes ~15 [L, F, B] intermediates (cumsums, both
# NA-direction gain planes, child stats) through HBM every level — at bench
# shape that read-back was projected comparable to the histogram kernel
# itself below the root (a projection; see PERF.md for what the chip
# says).  The fused path replaces it with a single-pass Pallas kernel that reads the [3, L, F, B] block ONCE
# into VMEM, computes cumulative G/H/C via an upper-triangular one-hot
# matmul on the MXU, evaluates both NA-direction boundary gains, takes the
# per-(leaf, feature) argmax on-chip, and writes only a compact
# [L*F, 16]-float winner-record block back out.  A tiny XLA epilogue
# (finish_splits) then reduces records over features and reproduces
# best_splits' exact output tuple.  The split search itself cannot live
# inside the histogram kernel's epilogue: gains need the GLOBALLY psum'd
# histogram and the hist kernel is per-shard — the fusion here removes the
# multi-pass XLA materialization, not the (unavoidable) single H block.
#
# Record planes (lane k of the [L*F, 16] block):
#   0 gain   best boundary gain for this (leaf, feature), NA-resolved
#   1 bin    argmax bin (first index on ties — matches best_splits' argmax)
#   2 na_left
#   3-5  GL/HL/CL at the best bin, EXCLUDING the NA bucket
#   6-8  g/h/c of the NA bucket
#   9-11 totG/totH/totC (NA included)
# Lanes 12-15 pad the record row to the lane-tile multiple.
#
# The XLA twin (_split_records_xla) evaluates gains with the same formula
# and jnp.cumsum, making it BIT-identical to best_splits — it is the
# default off-TPU so CPU crosschecks compare exactly.  On chip the kernel's
# matmul cumsum accumulates in a different order than jnp.cumsum (both
# f32-exact per element, ±1 ulp on the sums), so exactly-tied gains are the
# one legitimate divergence source (as between hist_mode's two values).

_REC_PLANES = 12


def _per_leaf(x, extra_dims: int):
    """Broadcast a per-leaf ``[L]`` parameter against ``extra_dims``
    trailing axes; scalars pass through untouched, so the scalar path
    stays trace-identical to the pre-batched code."""
    return x.reshape(x.shape + (1,) * extra_dims) \
        if getattr(x, "ndim", 0) else x


def _split_records_xla(Hist, reg_lambda, min_rows, reg_alpha, gamma,
                       min_child_weight):
    """Per-(leaf, feature) winner records [L, F, 12] — XLA path, bit-
    identical gains to best_splits (same op sequence, jnp.cumsum).

    Regularization/constraint params accept scalars or per-leaf ``[L]``
    arrays (the batched grid plane flattens G members into the leaf axis
    with per-member lambda/alpha/gamma/min_rows/min_child_weight)."""
    G, Hs, C = Hist[0], Hist[1], Hist[2]
    g_na, h_na, c_na = G[..., -1], Hs[..., -1], C[..., -1]
    cumG = jnp.cumsum(G[..., :-1], -1)
    cumH = jnp.cumsum(Hs[..., :-1], -1)
    cumC = jnp.cumsum(C[..., :-1], -1)
    totG = cumG[..., -1] + g_na
    totH = cumH[..., -1] + h_na
    totC = cumC[..., -1] + c_na
    lam1, alpha1 = _per_leaf(reg_lambda, 1), _per_leaf(reg_alpha, 1)
    lam2, alpha2 = _per_leaf(reg_lambda, 2), _per_leaf(reg_alpha, 2)
    gamma2 = _per_leaf(gamma, 2)
    rows2, mcw2 = _per_leaf(min_rows, 2), _per_leaf(min_child_weight, 2)
    parent = _score(totG, totH, lam1, alpha1)
    GL, HL, CL = cumG[..., :-1], cumH[..., :-1], cumC[..., :-1]
    GR = totG[..., None] - GL - g_na[..., None]
    HR = totH[..., None] - HL - h_na[..., None]
    CR = totC[..., None] - CL - c_na[..., None]

    def gain_with_na(gl, hl, cl, gr, hr, cr):
        g = 0.5 * (_score(gl, hl, lam2, alpha2)
                   + _score(gr, hr, lam2, alpha2)
                   - parent[..., None]) - gamma2
        ok = (cl >= rows2) & (cr >= rows2) & \
            (hl >= mcw2) & (hr >= mcw2)
        return jnp.where(ok, g, -jnp.inf)

    gain_naL = gain_with_na(GL + g_na[..., None], HL + h_na[..., None],
                            CL + c_na[..., None], GR, HR, CR)
    gain_naR = gain_with_na(GL, HL, CL, GR + g_na[..., None],
                            HR + h_na[..., None], CR + c_na[..., None])
    na_left_better = gain_naL >= gain_naR
    gain = jnp.maximum(gain_naL, gain_naR)         # [L, F, nbins-1]
    bin_ = jnp.argmax(gain, axis=-1)

    def pick(a):
        return jnp.take_along_axis(a, bin_[..., None], -1)[..., 0]

    return jnp.stack(
        [pick(gain), bin_.astype(jnp.float32),
         pick(na_left_better).astype(jnp.float32),
         pick(GL), pick(HL), pick(CL), g_na, h_na, c_na,
         totG, totH, totC], axis=-1)               # [L, F, 12]


def _make_pallas_split_records(LF: int, B: int, interpret: bool = False,
                               per_row: bool = False):
    """Split-records kernel: (G2, H2, C2 [LF, B], scal [1, 8] SMEM) ->
    rec [LF, 16].  One (leaf, feature) pair per sublane row; bins in
    lanes; grid over row blocks.  Rows must arrive padded to the block
    multiple (padding rows emit garbage records the caller slices off).

    ``per_row=True`` swaps the broadcast SMEM scalar block for a
    row-aligned ``[LF, 8]`` VMEM block (lanes 0-4 = lam/alpha/gamma/
    min_rows/mcw per record row) — per-leaf regularization for the
    batched grid plane.  The kernel math broadcasts [RS, 1] columns
    against [RS, B] planes, so the compute body is shared."""
    nbins = B - 1
    Bpad = (B + 127) // 128 * 128
    # ~24 live [RS, Bpad] f32 intermediates on the scoped-VMEM stack
    RS = int(max(8, min(1024, (6_291_456 // (96 * Bpad)) // 8 * 8)))
    nblk = (LF + RS - 1) // RS

    def kernel(g_ref, h_ref, c_ref, sc_ref, out_ref):
        if per_row:
            lam = sc_ref[:, 0:1]                   # [RS, 1] columns
            alpha = sc_ref[:, 1:2]
            gamma = sc_ref[:, 2:3]
            min_rows = sc_ref[:, 3:4]
            mcw = sc_ref[:, 4:5]
        else:
            lam = sc_ref[0, 0]
            alpha = sc_ref[0, 1]
            gamma = sc_ref[0, 2]
            min_rows = sc_ref[0, 3]
            mcw = sc_ref[0, 4]
        Gb, Hb, Cb = g_ref[:], h_ref[:], c_ref[:]
        biota = jax.lax.broadcasted_iota(jnp.int32, (RS, B), 1)

        def lane(x, k):                            # extract lane k -> [RS, 1]
            return jnp.sum(jnp.where(biota == k, x, 0.0), axis=1,
                           keepdims=True)

        gna, hna, cna = lane(Gb, nbins), lane(Hb, nbins), lane(Cb, nbins)
        reg = biota < nbins
        # lane cumsum as an upper-triangular 0/1 matmul; HIGHEST because
        # the default TPU matmul rounds f32 operands to bf16 (the 0/1 side
        # is exact, so full passes recover exact f32 partial sums)
        U = (jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
             <= jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)) \
            .astype(jnp.float32)

        def cum(x):
            return jax.lax.dot_general(
                jnp.where(reg, x, 0.0), U, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)

        cumG, cumH, cumC = cum(Gb), cum(Hb), cum(Cb)
        totG = lane(cumG, nbins - 1) + gna         # [RS, 1]
        totH = lane(cumH, nbins - 1) + hna
        totC = lane(cumC, nbins - 1) + cna

        def score(Gv, Hv):
            Gt = jnp.sign(Gv) * jnp.maximum(jnp.abs(Gv) - alpha, 0.0)
            return Gt * Gt / (Hv + lam)

        parent = score(totG, totH)
        cand = biota <= nbins - 2                  # split after bin b
        GL, HL, CL = cumG, cumH, cumC
        GR = totG - GL - gna
        HR = totH - HL - hna
        CR = totC - CL - cna

        def gain_dir(gl, hl, cl, gr, hr, cr):
            gn = 0.5 * (score(gl, hl) + score(gr, hr) - parent) - gamma
            ok = (cl >= min_rows) & (cr >= min_rows) & \
                (hl >= mcw) & (hr >= mcw)
            return jnp.where(ok & cand, gn, -jnp.inf)

        gL = gain_dir(GL + gna, HL + hna, CL + cna, GR, HR, CR)
        gR = gain_dir(GL, HL, CL, GR + gna, HR + hna, CR + cna)
        nab = (gL >= gR).astype(jnp.float32)
        gain = jnp.maximum(gL, gR)
        # first-index lane argmax (ties -> lowest bin, like jnp.argmax)
        m = jnp.max(gain, axis=1, keepdims=True)
        idx = jnp.min(jnp.where(gain == m, biota, B), axis=1, keepdims=True)
        sel = biota == idx

        def pick(x):
            return jnp.sum(jnp.where(sel, x, 0.0), axis=1, keepdims=True)

        recs = (pick(gain), idx.astype(jnp.float32), pick(nab),
                pick(GL), pick(HL), pick(CL), gna, hna, cna,
                totG, totH, totC)
        oiota = jax.lax.broadcasted_iota(jnp.int32, (RS, 16), 1)
        out = jnp.zeros((RS, 16), jnp.float32)
        for k, v in enumerate(recs):
            out = jnp.where(oiota == k, v, out)
        out_ref[:] = out

    sc_spec = pl.BlockSpec((RS, 8), lambda i: (i, 0),
                           memory_space=pltpu.VMEM) if per_row else \
        pl.BlockSpec((1, 8), lambda i: (0, 0), memory_space=pltpu.SMEM)
    return _named_kernel(
        "hist_split_records", kernel=kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((RS, B), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((RS, B), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((RS, B), lambda i: (i, 0), memory_space=pltpu.VMEM),
            sc_spec,
        ],
        out_specs=pl.BlockSpec((RS, 16), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((nblk * RS, 16), jnp.float32),
        interpret=interpret,
    ), RS


def split_records(Hist, nbins: int, reg_lambda, min_rows, reg_alpha=0.0,
                  gamma=0.0, min_child_weight=0.0, force_impl: str = ""):
    """Per-(leaf, feature) winner records [L, F, 12] from H[3, L, F, B].

    On TPU the Pallas kernel; elsewhere the bit-identical XLA twin.
    ``force_impl``: "xla" | "pallas" | "pallas_interpret" pin the path.
    Regularization params accept scalars or per-leaf ``[L]`` arrays
    (batched grid members flattened into the leaf axis)."""
    cl = cluster()
    platform = cl.mesh.devices.flat[0].platform
    use_kernel = force_impl in ("pallas", "pallas_interpret") or \
        (force_impl == "" and platform == "tpu")
    if not use_kernel:
        return _split_records_xla(Hist, reg_lambda, min_rows, reg_alpha,
                                  gamma, min_child_weight)
    interpret = force_impl == "pallas_interpret" or platform != "tpu"
    _, L, F, B = Hist.shape
    per_leaf = any(getattr(x, "ndim", 0) for x in
                   (reg_lambda, min_rows, reg_alpha, gamma,
                    min_child_weight))
    call, RS = _make_pallas_split_records(L * F, B, interpret=interpret,
                                          per_row=per_leaf)
    pad = (L * F + RS - 1) // RS * RS - L * F
    planes = Hist.reshape(3, L * F, B)
    if pad:
        planes = jnp.pad(planes, [(0, 0), (0, pad), (0, 0)])
    if per_leaf:
        def as_l(x):
            return jnp.broadcast_to(jnp.asarray(x, jnp.float32), (L,))
        cols = jnp.stack([as_l(reg_lambda), as_l(reg_alpha), as_l(gamma),
                          as_l(min_rows), as_l(min_child_weight)],
                         axis=1)                       # [L, 5]
        rows = jnp.repeat(cols, F, axis=0)             # row l*F+f -> leaf l
        if pad:
            rows = jnp.pad(rows, [(0, pad), (0, 0)])
        sc = jnp.zeros((L * F + pad, 8), jnp.float32).at[:, :5].set(rows)
    else:
        sc = jnp.zeros((1, 8), jnp.float32).at[0, :5].set(
            jnp.stack([reg_lambda, reg_alpha, gamma, min_rows,
                       min_child_weight]).astype(jnp.float32))
    # the H block is replicated post-psum; run the kernel replicated too
    # (pallas_call must not meet the GSPMD partitioner un-shard_mapped)
    rec = shard_map(call, mesh=cl.mesh, in_specs=(P(), P(), P(), P()),
                    out_specs=P(), check_vma=False)(
        planes[0], planes[1], planes[2], sc)
    return rec[:L * F, :_REC_PLANES].reshape(L, F, _REC_PLANES)


def finish_splits(rec, min_rows, min_split_improvement, feat_mask=None):
    """Reduce winner records over features into best_splits' exact output
    tuple (feat, bin, na_left, gain, valid, children[L, 6]).  The child
    statistics reproduce best_splits' arithmetic ORDER (GR formed before
    the NA resolution), keeping the XLA fused path bitwise-identical."""
    L, F, _ = rec.shape
    gain = rec[..., 0]
    if feat_mask is not None:
        m = feat_mask if feat_mask.ndim == 2 else feat_mask[None, :]
        gain = jnp.where(m, gain, -jnp.inf)
    feat = jnp.argmax(gain, axis=1).astype(jnp.int32)

    def pick(i):
        return jnp.take_along_axis(rec[..., i], feat[:, None], 1)[:, 0]

    best_gain = jnp.take_along_axis(gain, feat[:, None], 1)[:, 0]
    bin_ = pick(1).astype(jnp.int32)
    na_left = pick(2) > 0.5
    glx, hlx, clx = pick(3), pick(4), pick(5)
    gna, hna, cna = pick(6), pick(7), pick(8)
    ftot, htot, ctot = pick(9), pick(10), pick(11)
    valid = jnp.isfinite(best_gain) & \
        (best_gain > min_split_improvement) & \
        (rec[..., 11] >= _per_leaf(2 * min_rows, 1)).any(-1)
    gr0 = ftot - glx - gna
    hr0 = htot - hlx - hna
    cr0 = ctot - clx - cna
    gl = jnp.where(na_left, glx + gna, glx)
    hl = jnp.where(na_left, hlx + hna, hlx)
    cl = jnp.where(na_left, clx + cna, clx)
    gr = jnp.where(na_left, gr0, gr0 + gna)
    hr = jnp.where(na_left, hr0, hr0 + hna)
    cr = jnp.where(na_left, cr0, cr0 + cna)
    gl = jnp.where(valid, gl, ftot)
    hl = jnp.where(valid, hl, htot)
    cl = jnp.where(valid, cl, ctot)
    gr = jnp.where(valid, gr, 0.0)
    hr = jnp.where(valid, hr, 0.0)
    cr = jnp.where(valid, cr, 0.0)
    children = jnp.stack([gl, hl, cl, gr, hr, cr], axis=1)
    return feat, bin_, na_left, best_gain, valid, children


def _fused_best_splits_impl(Hist, nbins: int, reg_lambda, min_rows,
                            min_split_improvement, feat_mask=None,
                            reg_alpha=0.0, gamma=0.0, min_child_weight=0.0,
                            force_impl: str = ""):
    rec = split_records(Hist, nbins, reg_lambda, min_rows, reg_alpha,
                        gamma, min_child_weight, force_impl=force_impl)
    return finish_splits(rec, min_rows, min_split_improvement, feat_mask)


_FUSED_SPLIT_PROGRAM = None


def _fused_split_program():
    """Lazy compile-ledger registration of the fused split program:
    traced callers (the build loop) inline the plain impl exactly as
    before; eager callers (crosschecks, benches) get the AOT path with
    timed compiles and cost gauges."""
    global _FUSED_SPLIT_PROGRAM
    if _FUSED_SPLIT_PROGRAM is None:
        _FUSED_SPLIT_PROGRAM = _ledger(
            "fused_split",
            jax.jit(_fused_best_splits_impl,
                    static_argnames=("nbins", "force_impl")),
            static_argnums=(1,), static_argnames=("nbins", "force_impl"),
            orig=_fused_best_splits_impl)
    return _FUSED_SPLIT_PROGRAM


def fused_best_splits(Hist, nbins: int, reg_lambda, min_rows,
                      min_split_improvement, feat_mask=None,
                      reg_alpha=0.0, gamma=0.0, min_child_weight=0.0,
                      force_impl: str = ""):
    """Drop-in best_splits replacement via the single-pass records path.

    Same output tuple; no ``mono`` support (callers gate monotone builds
    to the separate path).  Selection equivalence with best_splits' flat
    f-major argmax: per-(l, f) first-max over bins then first-max over
    features picks the same (f, b) — both resolve ties toward the lowest
    flat index.  Call inside jit (traces inline; the records kernel is the
    only launch)."""
    return _fused_split_program()(
        Hist, nbins, reg_lambda, min_rows, min_split_improvement,
        feat_mask, reg_alpha, gamma, min_child_weight,
        force_impl=force_impl)


def fused_best_splits_batched(HistK, nbins: int, reg_lambda, min_rows,
                              min_split_improvement, feat_mask=None,
                              reg_alpha=0.0, gamma=0.0,
                              min_child_weight=0.0, force_impl: str = ""):
    """Batched-K fused split search: H [K, 3, L, F, B] -> per-tree tuples
    with leading K axes.  The K*L leaves flatten into one records-kernel
    launch (one dispatch for all K trees); ``feat_mask`` is [K, L, F] or
    [K, F].  Per-leaf reductions (argmax, valid's any(-1)) are row-local,
    so flattening K into L is exact.  Regularization params accept
    scalars or per-member ``[K]`` arrays (batched grid sweeps); the flat
    row order is K-major (row k*L+l), so ``repeat(x, L)`` aligns member
    k's parameter with its leaves."""
    K, _, L, F, B = HistK.shape
    Hflat = jnp.moveaxis(HistK, 1, 0).reshape(3, K * L, F, B)
    fm = None
    if feat_mask is not None:
        fm = feat_mask if feat_mask.ndim == 3 else \
            jnp.broadcast_to(feat_mask[:, None, :], (K, L, F))
        fm = fm.reshape(K * L, F)

    def perk(x):                                   # [K] -> [K*L] (K-major)
        return jnp.repeat(x, L) if getattr(x, "ndim", 0) else x

    feat, bin_, na_left, gain, valid, children = fused_best_splits(
        Hflat, nbins, perk(reg_lambda), perk(min_rows),
        perk(min_split_improvement), feat_mask=fm,
        reg_alpha=perk(reg_alpha), gamma=perk(gamma),
        min_child_weight=perk(min_child_weight), force_impl=force_impl)
    return (feat.reshape(K, L), bin_.reshape(K, L),
            na_left.reshape(K, L), gain.reshape(K, L),
            valid.reshape(K, L), children.reshape(K, L, 6))


def table_lookup(tables, idx, L: int):
    """Row-wise lookup t[:, idx] for a small table t [K, L], as a
    [K, L] x [L, N] product with the one-hot of ``idx`` instead of a
    per-row gather.  The one-hot is built [L, N] (minor dim = rows) so
    nothing lane-pads; f32 keeps the lookup exact for finite float tables
    (an infinite entry turns its whole row into NaN: 0 x inf).

    What the tree build uses to fetch a level's split parameters and leaf
    values by every row's node, and what the per-level ensemble walk
    (``shared._traverse_levels``: ensembles too deep or frames too wide for
    ``traverse_block``, and every backend but the TPU) uses the same way.  One look-up is a pass over all
    N rows, so a walk built on it costs a pass per tree and level.
    """
    oh = (jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
          == idx[None, :]).astype(jnp.float32)
    # HIGHEST: the default TPU matmul rounds f32 operands to bf16, which
    # would corrupt thresholds/leaf values; the one-hot side is exact 0/1,
    # so full-precision passes recover the exact f32 table entries
    return jnp.dot(tables.astype(jnp.float32), oh,
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


@jax.jit
def partition_ranged(codes, leaf, feat, lo, hi, inv, na_left, valid,
                     na_bin: jnp.int32):
    """``partition`` with a bin RANGE right-child condition:
    right = inv XOR (lo < code <= hi).  EFB bundle splits are member
    sub-ranges of the bundled bin axis (efb.py); ``inv`` flips the rule
    when the member's default mass sits on the right of the cut (then the
    LEFT child is the contiguous range).  A plain prefix split is lo=bin,
    hi=+inf, inv=False."""
    L = feat.shape[0]
    tables = jnp.stack([feat.astype(jnp.float32), lo.astype(jnp.float32),
                        hi.astype(jnp.float32), inv.astype(jnp.float32),
                        na_left.astype(jnp.float32),
                        valid.astype(jnp.float32)], axis=0)      # [6, L]
    t = table_lookup(tables, leaf, L)                            # [6, N]
    f = t[0].astype(jnp.int32)
    blo = t[1].astype(jnp.int32)
    bhi = t[2].astype(jnp.int32)
    iv = t[3] > 0.5
    nl = t[4] > 0.5
    v = t[5] > 0.5
    Fdim = codes.shape[0]
    fiota = jax.lax.broadcasted_iota(jnp.int32, (Fdim, 1), 0)
    c = jnp.sum(jnp.where(f[None, :] == fiota, codes, 0), axis=0)
    is_na = c == na_bin
    right = jnp.where(is_na, ~nl, iv ^ ((c > blo) & (c <= bhi)))
    right = right & v
    return (2 * leaf + right.astype(jnp.int32)).astype(jnp.int32)


@jax.jit
def partition(codes, leaf, feat, bin_, na_left, valid, na_bin: jnp.int32):
    """Send rows to child leaves: new_leaf = 2*leaf + went_right.

    ``codes`` is feature-major [F, N]; the per-row chosen-feature value is a
    select-chain over the (small) feature dim — a cross-sublane dynamic
    gather here would make XLA materialize a row-major transpose, whose
    lane padding costs 16x the array's HBM footprint.  The per-leaf split
    parameters are fetched via one MXU one-hot product (table_lookup), not
    gathers.  Terminal (invalid-split) leaves route everything left so
    descendants stay consistent; the leaf-value gather resolves them.
    """
    L = feat.shape[0]
    tables = jnp.stack([feat.astype(jnp.float32), bin_.astype(jnp.float32),
                        na_left.astype(jnp.float32),
                        valid.astype(jnp.float32)], axis=0)      # [4, L]
    t = table_lookup(tables, leaf, L)                            # [4, N]
    f = t[0].astype(jnp.int32)
    b = t[1].astype(jnp.int32)
    nl = t[2] > 0.5
    v = t[3] > 0.5
    Fdim = codes.shape[0]
    fiota = jax.lax.broadcasted_iota(jnp.int32, (Fdim, 1), 0)
    c = jnp.sum(jnp.where(f[None, :] == fiota, codes, 0), axis=0)
    is_na = c == na_bin
    right = jnp.where(is_na, ~nl, c > b)
    right = right & v
    return (2 * leaf + right.astype(jnp.int32)).astype(jnp.int32)


@jax.jit
def partition_right(codes, leaf, feat, bin_, na_left, valid,
                    na_bin: jnp.int32):
    """The ``partition`` routing decision alone — the went-right bit per
    row, without the dense ``2*leaf + right`` relabeling.  The node-sparse
    deep levels route rows through A+1-entry SLOT tables (instead of the
    2^d dense tables, whose one-hot product would reintroduce the dense
    per-row cost), then apply the bit to both the dense leaf id and the
    slot id; the sentinel slot's table row is valid=False so dead rows
    keep flowing left, matching dense terminality."""
    L = feat.shape[0]
    tables = jnp.stack([feat.astype(jnp.float32), bin_.astype(jnp.float32),
                        na_left.astype(jnp.float32),
                        valid.astype(jnp.float32)], axis=0)      # [4, L]
    t = table_lookup(tables, leaf, L)                            # [4, N]
    f = t[0].astype(jnp.int32)
    b = t[1].astype(jnp.int32)
    nl = t[2] > 0.5
    v = t[3] > 0.5
    Fdim = codes.shape[0]
    fiota = jax.lax.broadcasted_iota(jnp.int32, (Fdim, 1), 0)
    c = jnp.sum(jnp.where(f[None, :] == fiota, codes, 0), axis=0)
    is_na = c == na_bin
    right = jnp.where(is_na, ~nl, c > b)
    return (right & v).astype(jnp.int32)


# ------------------------------------------------------------ ensemble walk
#
# Scoring rows through a stacked ensemble of shallow trees (shared.traverse).
# A block of rows is read once and stays on the core while every tree is
# walked over it: a tree is its 2^D - 1 node predicates, each one compare of
# a feature's tile against a scalar, folded from the leaves up by selects.

_I32_MIN, _I32_MAX = -2 ** 31, 2 ** 31 - 1
_WALK_TILE = 128                 # sublane rows a fold works on: 16 registers
_WALK_BLOCK = 256                # most sublane rows (of 128 rows) a block
_WALK_VMEM = 24 * 1024 * 1024    # a block's feature tiles and their keys
_WALK_SMEM_WORDS = 128 * 1024    # a launch's node tables (SMEM holds 256 K)
_WALK_UNROLL = 6                 # levels a fold is written out for


def _order_key(x):
    """int32 image of a float32 whose integer order is the float order
    (-0.0 beside +0.0).  Where NaN lands is the caller's to say."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    b = jnp.where(b == _I32_MIN, 0, b)
    return b ^ ((b >> 31) & _I32_MAX)


def _nan_keys(x):
    """(keys with NaN below everything, keys with NaN above everything)."""
    key, nan = _order_key(x), x != x
    return jnp.where(nan, _I32_MIN, key), jnp.where(nan, _I32_MAX, key)


def walk_block_rows(F: int) -> int:
    """Sublane rows (of 128 rows each) of the widest row block whose F
    feature tiles (double buffered) and 2F key tiles fit the kernel's VMEM;
    0 where not even one register's rows fit: too wide for the blocked
    walk."""
    return min(_WALK_BLOCK, _WALK_VMEM // (4 * F * 128 * 4) // 8 * 8)


def _walk_tables(levels, values, F: int):
    """The stacked levels as the blocked walk's scalars, a row a tree with its
    nodes in level order: which key tile a node compares (feature f
    with NaN low where NA goes left, F + f with NaN high where it goes
    right), the threshold's key, and the leaves.  A node that does not split
    (or whose threshold is NaN) gets a key above every row's, so it sends
    all rows left and validity costs the walk nothing."""
    feat, thr, na_left, valid = (
        jnp.concatenate([lv[i] for lv in levels], axis=1) for i in range(4))
    na_left, valid = na_left.astype(bool), valid.astype(bool)
    tile = jnp.clip(feat, 0, F - 1) + F * (valid & ~na_left)
    thr = thr.astype(jnp.float32)
    key = jnp.where(valid & (thr == thr), _order_key(thr), _I32_MAX)
    return tile.astype(jnp.int32), key, values.astype(jnp.float32)


def _fold(lo: int, hi: int, root, went_right, leaf):
    """The value, for every row of a tile, of the subtree under node
    ``root`` of level ``lo``, cut off at level ``hi``: ``went_right(d, i)``
    is the predicate of node i of level d, ``leaf(i)`` what stands at node i
    of level ``hi``.  Depth first, so that hi - lo partial results are
    live, not 2^(hi - lo)."""
    def value(d, i):
        if d == hi:
            return leaf(i)
        return jnp.where(went_right(d, i), value(d + 1, 2 * i + 1),
                         value(d + 1, 2 * i))
    return value(lo, root)


def _tree_value(D: int, t, went_right, leaf):
    """Tree t's value for every row of a tile.  ``went_right(o)`` and
    ``leaf(o)`` read the walk's tables at offset o.  A tree deeper than
    ``_WALK_UNROLL`` is folded in two stages, so that the code stays short:
    the levels above say which of the subtrees below a row ends in, and a
    loop over those subtrees keeps the value of that one."""
    def node(d, i):
        return went_right(t * (2 ** D - 1) + 2 ** d - 1 + i)

    def value(i):
        return leaf(t * 2 ** D + i)
    top = max(0, D - _WALK_UNROLL)
    if not top:
        return _fold(0, D, 0, node, value)
    under = _fold(0, top, 0, node, lambda i: jnp.int32(i))

    def keep(j, v):
        return jnp.where(under == j, _fold(top, D, j, node, value), v)
    return jax.lax.fori_loop(0, 2 ** top, keep,
                             jnp.zeros(under.shape, jnp.float32))


def _make_pallas_traverse_block(T: int, D: int, F: int, K: int, RB: int,
                                nblk: int, carry: bool,
                                interpret: bool = False):
    """(tile[T*(2^D-1)], key[...], leaf[T*2^D], x[F, nblk*RB, 128]
    [, acc[nblk*RB, 128]]) -> margin[nblk*RB, 128]: the sum of T trees'
    leaves per row, on top of ``acc`` (whose buffer the margin takes) when a
    launch carries an earlier chunk of trees on.  A fold works on K sublane
    rows at a time."""

    def kernel(tile_ref, key_ref, leaf_ref, x_ref, *rest):
        *acc_ref, out_ref, keys_ref = rest

        def keys_of(f, _):
            keys_ref[f], keys_ref[F + f] = _nan_keys(x_ref[f])
            return _
        jax.lax.fori_loop(0, F, keys_of, 0)

        def walk_tile(s, _):
            rows = pl.ds(pl.multiple_of(s * K, K), K)

            def add_tree(t, acc):
                return acc + _tree_value(
                    D, t,
                    lambda o: keys_ref[tile_ref[o], rows, :] >= key_ref[o],
                    lambda o: leaf_ref[o])

            acc = acc_ref[0][rows, :] if carry else \
                jnp.zeros((K, 128), jnp.float32)
            out_ref[rows, :] = jax.lax.fori_loop(0, T, add_tree, acc)
            return _
        jax.lax.fori_loop(0, RB // K, walk_tile, 0)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    margin = pl.BlockSpec((RB, 128), lambda i: (i, 0),
                          memory_space=pltpu.VMEM)
    return _named_kernel(
        "traverse_block", kernel=kernel, grid=(nblk,),
        in_specs=[smem, smem, smem,
                  pl.BlockSpec((F, RB, 128), lambda i: (0, i, 0),
                               memory_space=pltpu.VMEM)]
        + ([margin] if carry else []),
        out_specs=margin,
        out_shape=_row_sds((nblk * RB, 128), jnp.float32),
        input_output_aliases={4: 0} if carry else {},
        scratch_shapes=[pltpu.VMEM((2 * F, RB, 128), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_WALK_VMEM + 16 * 1024 * 1024),
        interpret=interpret)


def traverse_block(levels, values, X, interpret: bool = False):
    """Sum of leaf values over stacked trees for X [N, F], by the blocked
    walk, a kernel for the TPU (``shared.traverse`` says when it runs).
    Float32 leaves summed in tree order, as the per-level walk sums them.
    ``interpret`` runs the kernel in Pallas' interpreter, for tests off the
    chip."""
    cl = cluster()
    X = X.astype(jnp.float32)
    N, F = X.shape
    T, D = values.shape[0], len(levels)
    shards = cl.n_row_shards
    n_local = -(-N // shards)
    cap = walk_block_rows(F)
    K = min(_WALK_TILE, cap)
    R = -(-n_local // (128 * K)) * K
    nblk = -(-R // (cap // K * K))
    RB = -(-R // (nblk * K)) * K
    # an ensemble whose tables outgrow SMEM goes in equal chunks of whole
    # trees, one launch each (a loop, so the kernel is compiled once), every
    # launch adding to the margin of the one before; the trees that fill the
    # last chunk up (fewer than there are launches) send every row to a leaf
    # of 0.0
    chunks = -(-T // max(1, _WALK_SMEM_WORDS // (3 * 2 ** D)))
    Tc = -(-T // chunks)
    tables = tuple(
        jnp.pad(a, [(0, chunks * Tc - T), (0, 0)], constant_values=fill)
        for a, fill in zip(_walk_tables(levels, values, F),
                           (0, _I32_MAX, 0.0)))
    call = _make_pallas_traverse_block(Tc, D, F, K, RB, nblk,
                                       carry=chunks > 1, interpret=interpret)

    def local(x, *tables):
        # column by column, so that XLA writes the padded [F, rows / 128,
        # 128] tiles in one pass over X (a transpose, a pad and a reshape
        # of the whole matrix cost two frame-sized copies)
        x = jnp.stack([jnp.pad(x[:, f], (0, nblk * RB * 128 - n_local))
                       for f in range(F)]).reshape(F, nblk * RB, 128)
        if chunks == 1:
            acc = call(*(a.reshape(-1) for a in tables), x)
        else:
            acc, _ = jax.lax.scan(
                lambda acc, chunk: (call(*chunk, x, acc), None),
                jnp.zeros((nblk * RB, 128), jnp.float32),
                tuple(a.reshape(chunks, -1) for a in tables))
        return acc.reshape(-1)[:n_local]

    if N % shards:
        X = jnp.pad(X, [(0, n_local * shards - N), (0, 0)])
    out = shard_map(local, mesh=cl.mesh,
                    in_specs=(P(ROW_AXIS, None), P(), P(), P()),
                    out_specs=P(ROW_AXIS), check_vma=False)(X, *tables)
    return out[:N]
