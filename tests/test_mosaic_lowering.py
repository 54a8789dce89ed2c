"""No-hardware Mosaic lowering gate (export level).

Interpret mode lies: the real Mosaic compiler rejects programs interpret
mode accepts (f32 iotas, unit-minor-dim iota vectors).  This gate
cross-platform-lowers every histogram-kernel geometry of the airlines shape
(benchmark/configs/xgb_airlines40m.json) via
``jax.export(..., platforms=["tpu"])`` on the CPU host: Pallas runs its
TPU lowering + the Mosaic MLIR verifier at export time, so an illegal iota
form / op signature in ``hist.py`` fails HERE, without a chip.  What export
cannot see is the Mosaic *compiler* pass pipeline (layout inference,
scoped-VMEM budgets): tests/test_tpu_compile.py compiles the same kernels
for a described v5e and covers that.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import export as jexport

import h2o3_tpu


@pytest.fixture(scope="module", autouse=True)
def _init():
    h2o3_tpu.init()


# the airlines shape: 8 features, nbins=256 -> B=257, depth 6.
# bin_counts mirror fit_bins on benchmark/datagen/airlines_like.py:
# small-cardinality
# numerics (year/month/day), full-bin numerics, a 22-level cat, capped cats.
BENCH_BIN_COUNTS = (21, 12, 7, 256, 256, 22, 256, 256)
F, B, NBINS = 8, 257, 256
N_PADDED = 10_000_000 - (10_000_000 % (8 * 512))  # divisible by mesh*tile
BENCH_LEVELS = (1, 4, 32)                          # depth-6 level widths


def _lower_tpu(jitted, *arg_shapes):
    args = [jax.ShapeDtypeStruct(s, d) for s, d in arg_shapes]
    exp = jexport.export(jitted, platforms=["tpu"])(*args)
    assert len(exp.mlir_module_serialized) > 0
    return exp


def _stat_shapes(n):
    return ((F, n), jnp.int16), ((n,), jnp.int32), \
        ((n,), jnp.float32), ((n,), jnp.float32), ((n,), jnp.float32)


def test_varbin_int16_bf16_kernel_lowers_for_tpu():
    """The varbin kernel path (int16 codes + bf16 stats) at every level
    width of a depth-6 build."""
    from h2o3_tpu.models.tree.hist import make_varbin_hist_fn
    for L in BENCH_LEVELS:
        fn = make_varbin_hist_fn(L, F, BENCH_BIN_COUNTS, B, N_PADDED)
        _lower_tpu(fn, *_stat_shapes(N_PADDED))


def test_varbin_f32_kernel_lowers_for_tpu():
    """reproducible=True forces f32 stat streaming — lower that too."""
    from h2o3_tpu.models.tree.hist import make_varbin_hist_fn
    fn = make_varbin_hist_fn(8, F, BENCH_BIN_COUNTS, B, N_PADDED,
                             precision="f32")
    _lower_tpu(fn, *_stat_shapes(N_PADDED))


def test_uniform_kernel_lowers_for_tpu():
    """The uniform-bin kernel (hist_type without per-feature bins), both
    the shallow and deep-L variants."""
    from h2o3_tpu.models.tree.hist import make_hist_fn
    for L in (1, 32):
        fn = make_hist_fn(L, F, B, N_PADDED)
        codes = ((F, N_PADDED), jnp.int32)
        rest = _stat_shapes(N_PADDED)[1:]
        _lower_tpu(fn, codes, *rest)


def test_subtract_level_lowers_for_tpu():
    """The smaller-sibling subtraction level program — count one-hot,
    cumsum-scatter compaction, varbin kernel over the N/2 prefix,
    reconstruction — as ONE exported TPU program at bench geometry.
    The compaction is plain XLA (scatter), but it composes with the
    Pallas custom call inside one shard_mapped jit; this proves the whole
    per-level program lowers for TPU from a CPU host."""
    from h2o3_tpu.models.tree.hist import make_subtract_level_fn
    from h2o3_tpu.runtime.cluster import cluster
    shards = cluster().n_row_shards
    for d in (1, 5):
        Lp = 2 ** (d - 1)
        fn = make_subtract_level_fn(d, F, B, N_PADDED,
                                    bin_counts=BENCH_BIN_COUNTS,
                                    force_impl="pallas")
        codes = ((F, N_PADDED), jnp.int16)
        leaf, g, h, w = _stat_shapes(N_PADDED)[1:]
        carry = ((shards, 3, Lp, F, B), jnp.float32)
        _lower_tpu(fn, codes, leaf, g, h, w, carry)


def test_sparse_level_lowers_for_tpu():
    """The node-sparse deep-level program — slot-table lookup (MXU
    one-hot product), parent-slot compaction, varbin kernel over the
    N/2 prefix, subtraction + slot-axis gather — as ONE exported TPU
    program at bench deep-level geometry (slot widths past the dense
    threshold, where hist_layout='auto' engages)."""
    from h2o3_tpu.models.tree.hist import make_sparse_level_fn
    from h2o3_tpu.runtime.cluster import cluster
    shards = cluster().n_row_shards
    for Ap, A in ((128, 256), (256, 512)):
        fn = make_sparse_level_fn(Ap, A, F, B, N_PADDED,
                                  bin_counts=BENCH_BIN_COUNTS,
                                  force_impl="pallas")
        codes = ((F, N_PADDED), jnp.int16)
        sleaf, g, h, w = _stat_shapes(N_PADDED)[1:]
        carry = ((shards, 3, Ap, F, B), jnp.float32)
        ps = ((A,), jnp.int32)
        _lower_tpu(fn, codes, sleaf, g, h, w, carry, ps)


def test_batched_sparse_level_lowers_for_tpu():
    """The batched-K sparse level (one launch for all K class trees at
    deep-level slot geometry) lowers for TPU — K prepends to the Pallas
    grid exactly as the dense batched kernel does."""
    from h2o3_tpu.models.tree.hist import make_batched_sparse_level_fn
    from h2o3_tpu.runtime.cluster import cluster
    shards = cluster().n_row_shards
    K, Ap, A = 3, 128, 256
    fn = make_batched_sparse_level_fn(Ap, A, K, F, B, N_PADDED,
                                      bin_counts=BENCH_BIN_COUNTS,
                                      force_impl="pallas")
    codes = ((F, N_PADDED), jnp.int16)
    rowK = ((K, N_PADDED), jnp.float32)
    sleafK = ((K, N_PADDED), jnp.int32)
    carry = ((shards, K, 3, Ap, F, B), jnp.float32)
    psK = ((K, A), jnp.int32)
    _lower_tpu(fn, codes, sleafK, rowK, rowK, rowK, carry, psK)


def test_split_records_kernel_lowers_for_tpu():
    """The fused coarse split search's winner-records kernel (triangular
    one-hot matmul cumsum + on-chip per-(leaf, feature) argmax) at every
    level width of a depth-6 build, plus the batched-K multinomial shape
    (K trees flatten into the leaf-row axis, so K*L*F rows is just a
    bigger grid of the same geometry)."""
    from functools import partial
    from h2o3_tpu.models.tree.hist import split_records

    for L in BENCH_LEVELS + (3 * 32,):             # K=3 classes at depth 5
        fn = jax.jit(partial(split_records, nbins=NBINS, reg_lambda=0.5,
                             min_rows=10.0, reg_alpha=0.1, gamma=0.1,
                             min_child_weight=1.0, force_impl="pallas"))
        _lower_tpu(fn, ((3, L, F, B), jnp.float32))


def test_export_catches_known_mosaic_violation():
    """Meta-test: the gate actually rejects an iota form that interpret
    mode accepts and the chip's compiler does not — proving the gate sees
    Mosaic verification, not just StableHLO emission."""
    from jax.experimental import pallas as pl

    def bad_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] + jax.lax.broadcasted_iota(
            jnp.float32, (128, 1), 0)

    def f(x):
        return pl.pallas_call(bad_kernel, out_shape=jax.ShapeDtypeStruct(
            (128, 1), jnp.float32))(x)

    with pytest.raises(Exception, match="iota|Verification"):
        jexport.export(jax.jit(f), platforms=["tpu"])(
            jax.ShapeDtypeStruct((128, 1), jnp.float32))
