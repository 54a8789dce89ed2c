"""Compile the main path's kernels for a DESCRIBED TPU v5e, without a chip.

The TPU compiler beside JAX compiles for a topology that is described and
not attached, so what Mosaic or XLA:TPU would refuse on the chip (a slice
off the tiling, a kernel over its scoped VMEM, an op Mosaic does not lower)
is refused here, at no chip time.  Interpret mode and the export-level gate
(tests/test_mosaic_lowering.py) cannot see those.

Every kernel seam chooses its branch from the platform of the live mesh's
first device, so the module boots the cluster over one described device
(``h2o3_tpu.init(devices=...)``), which steers the real ``tpu`` branches
with no option in the program, and puts the CPU test mesh back afterwards.
Shapes are the airlines geometry (8 features, 256 bins, depth 6) at the 10M
rows chip_smoke.py trains on.

The whole-program compiles (tens of seconds each) are
``tools/tpu_compile_rehearsal.py``.  Nothing here runs on a device: a
compile that passes is not a chip run.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import h2o3_tpu

BIN_COUNTS = (21, 12, 7, 256, 256, 22, 256, 256)
F, NBINS = 8, 256
B = NBINS + 1
N = 10_000_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip executable is written to the persistent cache but
    cannot be read back without a chip; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _boot(devices):
    """Cluster over described ``devices``; yields (cluster, sds)."""
    cl = h2o3_tpu.init(devices=list(devices), hosts=1)
    assert cl.describe()["platform"] == "tpu"

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(cl.mesh, P(*spec)))
    return cl, sds


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    from h2o3_tpu.runtime.cluster import ROW_AXIS
    cl, sds = _boot(topo.devices[:1])
    yield cl, sds, ROW_AXIS
    h2o3_tpu.init(devices=jax.devices())       # the CPU test mesh again


def _hist_operands(sds, rows, n, code_dtype):
    return (sds((F, n), code_dtype, None, rows), sds((n,), jnp.int32, rows),
            sds((n,), jnp.float32, rows), sds((n,), jnp.float32, rows),
            sds((n,), jnp.float32, rows))


def _compile(fn, *operands):
    compiled = getattr(fn, "jitted", fn).lower(*operands).compile()
    return compiled, compiled.as_text()


@pytest.mark.parametrize("L", [1, 32])
def test_uniform_hist_kernel_compiles(one_chip, L):
    from h2o3_tpu.models.tree.hist import make_hist_fn
    _, sds, rows = one_chip
    _, text = _compile(make_hist_fn(L, F, B, N),
                       *_hist_operands(sds, rows, N, jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("L,precision", [(1, "bf16"), (32, "bf16"),
                                         (8, "f32")])
def test_varbin_hist_kernel_compiles(one_chip, L, precision):
    """The packed per-feature kernel: int16 codes, bf16 or f32 stats."""
    from h2o3_tpu.models.tree.hist import make_varbin_hist_fn
    _, sds, rows = one_chip
    fn = make_varbin_hist_fn(L, F, BIN_COUNTS, B, N, precision=precision)
    _, text = _compile(fn, *_hist_operands(sds, rows, N, jnp.int16))
    assert "tpu_custom_call" in text


def test_kernels_compile_at_their_vmem_bounds(one_chip):
    """The widest levels each kernel is still given (hist.py's 12 MiB /
    3L <= 2048 and shared.py's 3L <= 1024 gates): the uniform kernel's
    stationary-tile variant at L=512 with a shrunk row block, and the
    varbin kernel at L=256."""
    from h2o3_tpu.models.tree.hist import make_hist_fn, make_varbin_hist_fn
    _, sds, rows = one_chip
    f = 6
    _, text = _compile(make_hist_fn(512, f, B, N),
                       sds((f, N), jnp.int32, None, rows),
                       *_hist_operands(sds, rows, N, jnp.int32)[1:])
    assert "tpu_custom_call" in text
    _, text = _compile(make_varbin_hist_fn(256, F, BIN_COUNTS, B, N),
                       *_hist_operands(sds, rows, N, jnp.int16))
    assert "tpu_custom_call" in text


def test_deep_level_falls_to_einsum_by_its_size_bound(one_chip):
    """3L > 2048: the A-build intermediates cannot fit scoped VMEM, which
    is the bound the threshold expresses; the portable path compiles."""
    from h2o3_tpu.models.tree.hist import make_hist_fn
    _, sds, rows = one_chip
    n = 1 << 20
    _, text = _compile(make_hist_fn(1024, 3, 33, n),
                       *(sds((3, n), jnp.int32, None, rows),
                         *_hist_operands(sds, rows, n, jnp.int32)[1:]))
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("L", [32, 7 * 32])
def test_split_records_kernel_compiles(one_chip, L):
    """The fused split search's winner-records kernel at the deepest level
    of a depth-6 build, alone and with 7 class trees in the leaf axis."""
    from h2o3_tpu.models.tree.hist import split_records
    _, sds, _ = one_chip
    fn = jax.jit(functools.partial(
        split_records, nbins=NBINS, reg_lambda=1.0, min_rows=1.0,
        reg_alpha=0.0, gamma=0.0, min_child_weight=1.0))
    _, text = _compile(fn, sds((3, L, F, B), jnp.float32))
    assert "tpu_custom_call" in text


def test_serve_traversal_auto_resolves_to_compiles(one_chip):
    """What ``impl="auto"`` resolves to compiles for the chip; the Pallas
    traversal is refused by Mosaic, which is why "auto" is not it."""
    from h2o3_tpu.runtime import autotune
    from h2o3_tpu.serving import kernel
    _, sds, _ = one_chip
    depth, trees, batch = 6, 100, 256
    nodes = trees * (2 ** (depth + 1) - 1)
    operands = (sds((nodes,), jnp.int32), sds((nodes,), jnp.float32),
                sds((trees,), jnp.int32), sds((batch, F), jnp.float32))
    impl = autotune.resolve_serve_impl(depth=depth, R=trees, F=F, B=batch)
    _compile(jax.jit(kernel._traverse_impl(impl, depth, trees, F, batch)),
             *operands)
    pallas = kernel._make_pallas_traverse(depth, trees, F, 128)
    with pytest.raises(NotImplementedError, match="gather"):
        _compile(jax.jit(pallas), *operands)


V5E_BYTES = 16_900_000_000      # what one v5e reports as its bytes_limit
HIGGS_LAYOUT = (("num", 28), ("one", 1))
AIRLINES_LAYOUT = (("num", 5), ("cat", 22), ("cat", 300), ("cat", 300),
                   ("one", 1))


@pytest.fixture()
def v5e_memory(monkeypatch):
    """Blocks sized as on the chip: the code reads the memory of the first
    LOCAL device, which here is a CPU (4 GiB assumed)."""
    from h2o3_tpu.models import datainfo
    monkeypatch.setattr(datainfo, "device_memory_bytes", lambda: V5E_BYTES)


def _glm_path_dense(sds, rows, n=None):
    """GLM's IRLSM path program on the dense design, 28 numerics and the
    intercept: what a frame whose expansion fits the device runs."""
    from h2o3_tpu.models import glm
    n = SMALL_N if n is None else n
    p = 29
    fam = glm._make_family("binomial", glm.GLMParameters())
    vec, coef = sds((n,), jnp.float32, rows), sds((p,), jnp.float32)
    scalar = sds((), jnp.float32)
    return glm._make_path_runner(fam, False, 50), (
        sds((n, p), jnp.float32, rows, None), vec, vec, vec,
        sds((1,), jnp.float32), scalar, coef, coef, scalar, scalar)


def _glm_path(sds, rows, layout=AIRLINES_LAYOUT, n=None):
    """GLM's IRLSM path program on the code-form design of ``layout``."""
    from h2o3_tpu.models import glm
    n = SMALL_N if n is None else n
    p = sum(width for _, width in layout)
    nums = sum(w for kind, w in layout if kind == "num")
    cats = sum(1 for kind, _ in layout if kind == "cat")
    fam = glm._make_family("binomial", glm.GLMParameters())
    vec, coef = sds((n,), jnp.float32, rows), sds((p,), jnp.float32)
    scalar = sds((), jnp.float32)
    fn = glm._make_blocked_path_runner(fam, False, 50, layout,
                                       glm._fit_block_rows(layout, n))
    return fn, (sds((n, nums), jnp.float32, rows, None),
                sds((n, cats), jnp.int32, rows, None), vec, vec, vec,
                sds((1,), jnp.float32), scalar, coef, coef, scalar, scalar)


def _glm_score(sds, rows, layout=AIRLINES_LAYOUT, n=None):
    from h2o3_tpu.models import datainfo, glm
    n = SMALL_N if n is None else n
    p = sum(width for _, width in layout)
    fn = glm._make_score(layout, "binomial", True,
                         datainfo.block_rows(2 * 4 * (p + 2), n))
    return fn, (sds((p,), jnp.float32), sds((0,), jnp.float32),
                sds((n, 5), jnp.float32, rows, None),
                sds((n, 3), jnp.int32, rows, None))


def test_glm_irls_path_compiles_at_higgs_shape(one_chip):
    """The whole IRLS path program on the dense design at 10M x 28 (+
    intercept) fits one chip's 16 GB with room for the frame beside it."""
    _, sds, rows = one_chip
    fn, operands = _glm_path_dense(sds, rows, N)
    compiled, _ = _compile(fn, *operands)
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 8e9


def test_glm_blocked_path_is_one_block_where_the_expansion_fits(one_chip,
                                                                v5e_memory):
    """The code-form program on an all-numeric frame of the same size is
    ONE block (no scan), and compiles."""
    from h2o3_tpu.models import glm
    _, sds, rows = one_chip
    assert glm._fit_block_rows(HIGGS_LAYOUT, N) == N
    fn, operands = _glm_path(sds, rows, HIGGS_LAYOUT, N)
    compiled, _ = _compile(fn, *operands)
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 8e9


def test_glm_programs_hold_no_frame_sized_expansion(one_chip, v5e_memory):
    """The IRLSM path and the scoring program at 40M rows of the airlines
    shape (5 numerics + 3 categoricals, 628 expanded columns): beside the
    code-form design with y, w and the offset (2.4 GB as the chip tiles
    it) neither holds the [rows, 628] expansion (100 GB); the walk's block
    and its operands stay inside a quarter of the chip, a scoring block
    inside a sixteenth."""
    _, sds, rows = one_chip
    n = 40_000_000
    fn, operands = _glm_path(sds, rows, AIRLINES_LAYOUT, n)
    path, text = _compile(fn, *operands)
    ma = path.memory_analysis()
    assert ma.argument_size_in_bytes < 2.5e9
    assert ma.temp_size_in_bytes < V5E_BYTES / 4
    # the Gram kernel builds the one-hot tiles in VMEM: no block of bf16
    # operands [rows, 622] goes through HBM (PR 42)
    assert "glm_gram" in text and not re.search(r"bf16\[\d+,622\]", text)
    fn, operands = _glm_score(sds, rows, AIRLINES_LAYOUT, n)
    score, _ = _compile(fn, *operands)
    ma = score.memory_analysis()
    assert ma.argument_size_in_bytes < 2.0e9
    assert ma.temp_size_in_bytes < V5E_BYTES / 16
    assert ma.output_size_in_bytes == n * 2 * 4


# ----------------------------------------------------- names in the trace
#
# What the benchmark's reductions find by name (PERF.md §3): the XLA module
# of each program on the profiler's ``XLA Modules`` line, and the
# instruction of each Pallas kernel on its ``XLA Ops`` line.

SMALL_N = 1 << 16
SCALARS = (1.0, 1.0, 1e-5, 0.3, 1.0, 0.0, 0.0, 1.0)


def _tree_build_operands(sds, rows, nk=1):
    lead = (nk,) if nk > 1 else ()
    row_k = (None, rows) if nk > 1 else (rows,)
    return (sds((F, SMALL_N), jnp.int32, None, rows),
            sds(lead + (SMALL_N,), jnp.float32, *row_k),
            sds(lead + (SMALL_N,), jnp.float32, *row_k),
            sds((SMALL_N,), jnp.float32, rows), sds((F, NBINS), jnp.float32),
            sds(lead + (2,), jnp.uint32),
            *SCALARS[:5], sds(lead + (F,), jnp.bool_), *SCALARS[5:])


def _tree_scan(sds, rows):
    from h2o3_tpu.models.tree import shared
    fn = shared.make_tree_scan_fn(
        "bernoulli", 1.5, 0.5, 0.9, 3, NBINS, F, SMALL_N, "bf16", 1.0, 1.0,
        bin_counts=BIN_COUNTS, hist_mode="subtract", split_mode="fused",
        tree_program="scan")
    vec = sds((SMALL_N,), jnp.float32, rows)
    return fn, (sds((F, SMALL_N), jnp.int32, None, rows), vec, vec,
                sds((SMALL_N,), jnp.float32), sds((F, NBINS), jnp.float32),
                sds((2,), jnp.uint32), 0, 2, *SCALARS, 0)


def _tree_build(sds, rows, nk=1):
    from h2o3_tpu.models.tree import shared
    fn = shared.make_build_tree_fn(3, NBINS, F, SMALL_N, "bf16",
                                   bin_counts=BIN_COUNTS, nk=nk,
                                   hist_mode="subtract", split_mode="fused")
    return fn, _tree_build_operands(sds, rows, nk)


def _traverse(sds, rows, trees=4, depth=3, n=SMALL_N):
    from h2o3_tpu.models.tree import shared
    levels = [(sds((trees, 2 ** d), jnp.int32), sds((trees, 2 ** d), jnp.float32),
               sds((trees, 2 ** d), jnp.bool_), sds((trees, 2 ** d), jnp.bool_))
              for d in range(depth)]
    return shared.traverse_jit, (levels, sds((trees, 2 ** depth), jnp.float32),
                                 sds((n, F), jnp.float32, rows, None))


# xgb_airlines40m.score's ensemble, at the 10M rows of this file's geometry
_traverse_cell = functools.partial(_traverse, trees=100, depth=6, n=N)


def _sketch(sds, rows):
    from h2o3_tpu.models.tree import binning
    return binning._make_sketch_fn(SMALL_N, SMALL_N, 5, NBINS - 1), (
        sds((5, SMALL_N), jnp.float32, None, rows),
        sds((SMALL_N,), jnp.float32, rows))


@pytest.mark.parametrize("backend", ["test", "tpu"])
def test_sketch_sorts_one_int32_key_unstably(request, backend):
    """The quantile sketch's one sort carries a single s32 operand and no
    ``is_stable``: a stable float sort is emitted with an s32 iota beside
    the values, twice the bytes a compare-exchange stage moves."""
    from h2o3_tpu.models.tree import binning
    if backend == "tpu":
        _, sds, rows = request.getfixturevalue("one_chip")
        fn, operands = _sketch(sds, rows)
    else:
        fn = binning._make_sketch_fn(1000, 1024, 5, NBINS - 1)
        operands = (jax.ShapeDtypeStruct((5, 1024), jnp.float32),
                    jax.ShapeDtypeStruct((1024,), jnp.float32))
    _, text = _compile(fn, *operands)
    sorts = re.findall(r"= (\S+) sort\(([^)]*)\)([^\n]*)", text)
    assert len(sorts) == 1
    shape, args, attrs = sorts[0]
    assert shape.startswith("s32[5,") and "," not in args
    assert "is_stable" not in attrs


def _encode(sds, rows):
    from h2o3_tpu.models.tree import binning
    is_cat = (False,) * 5 + (True,) * 3
    ecounts = (255,) * 5 + (21, 255, 255)
    return binning._make_encode_fn(SMALL_N, ecounts, is_cat, NBINS), (
        sds((F, SMALL_N), jnp.float32, None, rows), sds((F, 256), jnp.float32))


def _hist_kernel(builder):
    def program(sds, rows):
        from h2o3_tpu.models.tree import hist
        return builder(hist, sds, rows)
    return program


def _scan_level(hist, sds, rows, K=0, bin_counts=None):
    W, n = 4, 1 << 12       # the compaction around the kernel compiles slowly
    lead = (K,) if K else ()
    row_k = (None, rows) if K else (rows,)
    make = hist.make_batched_scan_level_fn if K else hist.make_scan_level_fn
    fn = make(W, *lead, F, B, n, bin_counts=bin_counts)
    # the packed kernel reads offset_codes' int16 ids
    return fn, (sds((F, n), jnp.int16 if bin_counts else jnp.int32, None,
                    rows),
                sds(lead + (n,), jnp.int32, *row_k),
                *(sds(lead + (n,), jnp.float32, *row_k),) * 3,
                sds((1,) + lead + (3, W // 2, F, B), jnp.float32, rows),
                sds((), jnp.bool_))


def _split_records(hist, sds, rows):
    fn = jax.jit(functools.partial(
        hist.split_records, nbins=NBINS, reg_lambda=1.0, min_rows=1.0,
        reg_alpha=0.0, gamma=0.0, min_child_weight=1.0))
    return fn, (sds((3, 32, F, B), jnp.float32),)


# DeepLearning at the benchmark's dl_airlines40m geometry: 5 numerics and
# categoricals of 22 / 300 / 300 levels in code form, hidden 200 x 200
DL_LAYOUT = (("num", 5), ("cat", 22), ("cat", 300), ("cat", 300), ("one", 1))
DL_SIZES = (628, 200, 200, 2)
DL_BATCH = 128


def _dl_params(sds):
    return [(sds((i, o), jnp.float32), sds((o,), jnp.float32))
            for i, o in zip(DL_SIZES[:-1], DL_SIZES[1:])]


def _dl_design(sds, rows, n, targets=0):
    """Numerics (with ``targets`` more columns: the sampler's label and
    weight) and codes."""
    return (sds((n, 5 + targets), jnp.float32, rows, None),
            sds((n, 3), jnp.int32, rows, None))


def _dl_train(sds, rows, n=SMALL_N, steps=16):
    from h2o3_tpu.models import deeplearning as dl
    cfg = dl._StepConfig(DL_LAYOUT, "rectifier", 0.0, (), "cross_entropy",
                         True, False, 2, 0.0, 0.0, ("adadelta", 0.99, 1e-8),
                         jnp.bfloat16)
    fn, tx = dl._build_train_steps(cfg, DL_BATCH, steps, n)
    params = _dl_params(sds)
    return fn, (params, jax.eval_shape(tx.init, params),
                sds((2,), jnp.uint32), 0,
                *_dl_design(sds, rows, n + DL_BATCH, targets=2))


def _dl_score(sds, rows, n=SMALL_N):
    from h2o3_tpu.models import deeplearning as dl
    block = dl._score_block_rows(DL_SIZES, n)
    fn = dl._make_score(DL_LAYOUT, "rectifier", "softmax", block)
    return fn, (_dl_params(sds), *_dl_design(sds, rows, n))


def test_deeplearning_programs_hold_no_frame_sized_expansion(one_chip):
    """The training-interval and the scoring program at 40M rows of the
    airlines shape: beside the code-form design (2.24 GB with its targets)
    neither holds a [rows, 628] expansion (100 GB) or a [rows, 200]
    activation (32 GB); a scoring block and its activations stay under a
    sixteenth of the chip."""
    _, sds, rows = one_chip
    n = 40_000_000
    fn, operands = _dl_train(sds, rows, n, n // 10 // DL_BATCH)
    train, _ = _compile(fn, *operands)
    ma = train.memory_analysis()
    assert ma.argument_size_in_bytes < 2.5e9 and ma.temp_size_in_bytes < 1e9
    fn, operands = _dl_score(sds, rows, n)
    score, _ = _compile(fn, *operands)
    ma = score.memory_analysis()
    assert ma.argument_size_in_bytes < 2.5e9 and ma.temp_size_in_bytes < 1.1e9
    assert ma.output_size_in_bytes == n * 2 * 4


MODULES = {
    "jit_run": _glm_path_dense, "jit_run@blocked": _glm_path,
    "jit_scan_fn": _tree_scan,
    "jit_build": _tree_build,
    "jit_buildK": functools.partial(_tree_build, nk=3),
    "jit_traverse": _traverse, "jit_sketch": _sketch, "jit_encode": _encode,
    "jit_dl_train_steps": _dl_train, "jit_dl_score": _dl_score,
    "jit_glm_score": _glm_score,
}
KERNELS = {
    # the blocked IRLSM program's Gram (PR 42)
    "glm_gram": _glm_path,
    "hist_uniform": _hist_kernel(lambda hist, sds, rows: (
        hist.make_hist_fn(1, F, B, SMALL_N),
        _hist_operands(sds, rows, SMALL_N, jnp.int32))),
    # the stationary-tile variant: the whole histogram is over 8 MiB
    "hist_uniform_deep": _hist_kernel(lambda hist, sds, rows: (
        hist.make_hist_fn(512, 6, B, SMALL_N),
        (sds((6, SMALL_N), jnp.int32, None, rows),
         *_hist_operands(sds, rows, SMALL_N, jnp.int32)[1:]))),
    "hist_varbin": _hist_kernel(lambda hist, sds, rows: (
        hist.make_varbin_hist_fn(8, F, BIN_COUNTS, B, SMALL_N),
        _hist_operands(sds, rows, SMALL_N, jnp.int16))),
    "hist_split_records": _hist_kernel(_split_records),
    # the blocked ensemble walk inside jit_traverse, at the score cell's shape
    "traverse_block": _traverse_cell,
    # deeper than the levels a fold is written out for: the two-stage fold
    "traverse_block@deep": functools.partial(_traverse, trees=20, depth=10),
    # an ordinary ensemble whose tables pass SMEM: a loop of three launches,
    # each taking the margin of the one before (and its buffer)
    "traverse_block@chunks": functools.partial(_traverse, trees=100,
                                               depth=10),
    # where PR 26's trace read %branch_0_fun: the live branch of lax.cond
    "hist_uniform@cond": _hist_kernel(_scan_level),
    # under vmap the scope of name= alone reads vmap(hist_uniform)
    "hist_uniform@vmap": _hist_kernel(
        functools.partial(_scan_level, K=3)),
    # the scan program's body under the packed kernel (PR 38): the same two
    # scopes, so that hist_kernel_share goes on finding it
    "hist_varbin@cond": _hist_kernel(
        functools.partial(_scan_level, bin_counts=BIN_COUNTS)),
    "hist_varbin@vmap": _hist_kernel(
        functools.partial(_scan_level, K=3, bin_counts=BIN_COUNTS)),
}


@pytest.mark.parametrize("kind,name", [("module", m) for m in MODULES]
                         + [("kernel", k) for k in KERNELS]
                         + [("kernel", "serve_traverse")])
def test_names_the_trace_reductions_match(one_chip, kind, name):
    """The XLA module names the ``*_share`` metrics match stay as
    they are, and every Pallas kernel's instruction is ``%<its name>.N``
    in what the chip's compiler emits (``hist_kernel_share`` matches
    ``^%hist_``)."""
    _, sds, rows = one_chip
    if kind == "module":
        fn, operands = MODULES[name](sds, rows)
        hlo = getattr(fn, "jitted", fn).lower(*operands) \
            .compiler_ir("hlo").as_hlo_text()
        assert re.match(r"HloModule (\w+)", hlo).group(1) == name.split("@")[0]
    elif name == "serve_traverse":
        # Mosaic refuses this kernel (see above), so nothing is emitted
        # to read a name from: its pallas_call carries name=
        from h2o3_tpu.serving import kernel
        depth, trees, batch = 6, 100, 256
        nodes = trees * (2 ** (depth + 1) - 1)
        jaxpr = jax.make_jaxpr(
            kernel._make_pallas_traverse(depth, trees, F, 128))(
            sds((nodes,), jnp.int32), sds((nodes,), jnp.float32),
            sds((trees,), jnp.int32), sds((batch, F), jnp.float32))
        calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        assert [str(e.params["name"]) for e in calls] == [name]
    else:
        fn, operands = KERNELS[name](sds, rows)
        _, text = _compile(fn, *operands)
        found = re.findall(r"%([\w.]+?)\.\d+ = [^\n]*tpu_custom_call", text)
        assert found and set(found) == {name.split("@")[0]}


# the row-sharded mesh last: it boots its own cluster and hands the CPU test
# mesh back, which ends what the ``one_chip`` fixture set up

def test_four_chip_histogram_has_kernel_and_all_reduce(topo,
                                                       no_persistent_cache):
    """The row-sharded mesh: each chip runs the kernel on its rows and the
    histograms meet in an all-reduce."""
    from h2o3_tpu.models.tree.hist import make_varbin_hist_fn
    from h2o3_tpu.runtime.cluster import ROW_AXIS
    cl, sds = _boot(topo.devices)
    try:
        assert cl.n_row_shards == 4
        n = cl.pad_rows(N)
        compiled, text = _compile(
            make_varbin_hist_fn(32, F, BIN_COUNTS, B, n),
            *_hist_operands(sds, ROW_AXIS, n, jnp.int16))
        assert "tpu_custom_call" in text and "all-reduce" in text
        ma = compiled.memory_analysis()
        assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16e9
        # the ensemble walk at the score cell's shape, X row-sharded: each
        # chip walks its own rows, and nothing crosses chips
        fn, operands = _traverse_cell(sds, ROW_AXIS)
        compiled, text = _compile(fn, *operands)
        assert "%traverse_block" in text and "tpu_custom_call" in text
        assert not re.search(r"all-(reduce|gather|to-all)|collective-permute",
                             text)
        assert compiled.output_shardings.shard_shape((N,)) == (N // 4,)
    finally:
        h2o3_tpu.init(devices=jax.devices())
