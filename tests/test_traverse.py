"""``shared.traverse``: the blocked walk (``hist.traverse_block``, the Pallas
kernel in interpret mode) and the per-level walk, against a float64 numpy
walk of the same stacked arrays (the semantics of
``benchmark/refs.walk_trees``, written out here)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from h2o3_tpu.models.tree import hist, shared

CROSSOVER = shared.TRAVERSE_BLOCK_DEPTH
FLT_MAX = float(np.finfo(np.float32).max)
# the widest frame of which a block's 4F tiles still hold a register's rows
WIDEST = hist._WALK_VMEM // (4 * 128 * 4 * 8)


def walk(levels, values, X):
    """Leaf index of every (tree, row): a row goes right where its value is
    at least the threshold (NA: where NA does not go left) and the node
    splits at all, else left."""
    rows = np.arange(len(X))
    leaves = np.zeros((values.shape[0], len(X)), np.int64)
    for t in range(values.shape[0]):
        node = np.zeros(len(X), np.int64)
        for feat, thr, na_left, valid in levels:
            x = X[rows, feat[t][node]].astype(np.float64)
            right = np.where(np.isnan(x), ~na_left[t][node],
                             x >= thr[t][node].astype(np.float64))
            node = 2 * node + (right & valid[t][node])
        leaves[t] = node
    return leaves


def ensemble(rng, T, D, F, N):
    """Stacked levels with every special case in them: NaN rows under NA-left
    and NA-right nodes, nodes that do not split at every level, thresholds at
    -inf and +inf, and rows whose value equals the threshold they meet."""
    X = rng.normal(size=(N, F)).astype(np.float32)
    X[rng.random((N, F)) < 0.15] = np.nan
    levels = []
    for d in range(D):
        shape = (T, 2 ** d)
        thr = rng.normal(size=shape).astype(np.float32)
        # thresholds taken from the data, so that rows meet x == thr
        thr[:, ::3] = np.nan_to_num(X[rng.integers(0, N, shape), 0])[:, ::3]
        thr[rng.random(shape) < 0.06] = np.inf
        thr[rng.random(shape) < 0.06] = -np.inf
        valid = rng.random(shape) < 0.85
        valid[0, 0] = d % 2 == 0      # tree 0: its first node alternates
        levels.append((rng.integers(0, F, shape).astype(np.int32), thr,
                       rng.random(shape) < 0.5, valid))
    levels[0][0][:, 0] = 0            # the roots read column 0, whose values
    X[0, 0] = levels[0][1][0, 0] if np.isfinite(levels[0][1][0, 0]) else 0.0
    values = (0.1 * rng.normal(size=(T, 2 ** D))).astype(np.float32)
    return levels, values, X


def on_device(levels):
    return [tuple(jnp.asarray(a) for a in lv) for lv in levels]


def margins(leaves, values):
    """(float64 sum, float32 sum in tree order) of the leaves reached."""
    picked = np.take_along_axis(values, leaves, axis=1)        # [T, N]
    acc = np.zeros(leaves.shape[1], np.float32)
    for row in picked:
        acc = acc + row
    return picked.astype(np.float64).sum(axis=0), acc


_walk_here = functools.partial(hist.traverse_block, interpret=True)
_block = jax.jit(_walk_here)
_levels = jax.jit(shared._traverse_levels)


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """``shared.traverse`` choosing as on the TPU, the kernel it then calls
    in interpret mode."""
    monkeypatch.setattr(shared, "_on_tpu", lambda: True)
    monkeypatch.setattr(shared, "traverse_block", _walk_here)
    shared.traverse_jit.clear_cache()
    yield
    shared.traverse_jit.clear_cache()


# (depth, F, T, N): every depth 1-8 and the crossover with its neighbours,
# every F, T and N of the issue's list at least twice
CASES = [(1, 1, 1, 1), (2, 8, 3, 127), (3, 28, 100, 1025), (4, 8, 1, 4099),
         (5, 1, 3, 1025), (6, 8, 100, 4099), (7, 28, 3, 127), (8, 8, 1, 1),
         (6, 28, 100, 1), (3, 1, 100, 4099),
         (CROSSOVER - 1, 8, 3, 1025), (CROSSOVER, 8, 3, 127),
         (CROSSOVER + 1, 8, 1, 1025)]


@pytest.mark.parametrize("D,F,T,N", CASES)
def test_blocked_walk_equals_the_numpy_walk(cl, D, F, T, N):
    levels, values, X = ensemble(np.random.default_rng(D * 1000 + N), T, D, F, N)
    leaves = walk(levels, values, X)
    want64, want32 = margins(leaves, values)
    lv, Xd = on_device(levels), jnp.asarray(X)
    got = np.asarray(_block(lv, jnp.asarray(values), Xd))
    assert got.shape == (N,)
    np.testing.assert_allclose(got, want64, rtol=0, atol=1e-6 * max(1, T // 10))
    assert np.array_equal(got, want32)             # float32, in tree order
    # leaf indices: an ensemble whose leaves are their own index, weighted
    # by the tree, sums to the same integer only if every row ends where
    # the numpy walk ends it
    weight = np.arange(1, T + 1, dtype=np.float32)[:, None]
    coded = weight * np.arange(2 ** D, dtype=np.float32)[None, :]
    got = np.asarray(_block(lv, jnp.asarray(coded), Xd))
    assert np.array_equal(got, (weight * leaves).sum(axis=0))


@pytest.mark.parametrize("D,F,T,N", CASES)
def test_both_walks_give_the_same_bits(cl, as_on_the_chip, D, F, T, N):
    """Wherever both run.  The per-level walk looks a threshold up through
    a product with a one-hot, so it meets +-inf as +-FLT_MAX: the same
    answer for every row but one that holds exactly +-FLT_MAX."""
    levels, values, X = ensemble(np.random.default_rng(D * 1000 + N), T, D, F, N)
    assert not np.isin(X, (FLT_MAX, -FLT_MAX)).any()
    lv, vals, Xd = on_device(levels), jnp.asarray(values), jnp.asarray(X)
    by_level = np.asarray(_levels(lv, vals, Xd))
    assert np.array_equal(by_level, np.asarray(_block(lv, vals, Xd)))
    assert np.array_equal(by_level, margins(walk(levels, values, X), values)[1])
    # the entry point takes one of the two
    assert shared.traverse_path(D, F) == ("block" if D <= CROSSOVER else "level")
    assert np.array_equal(by_level, np.asarray(shared.traverse_jit(lv, vals, Xd)))


@pytest.mark.parametrize("T,fit,launches,each", [(7, 3, 3, 3), (5, 2.67, 3, 2),
                                                 (4, 1, 4, 1)])
def test_an_ensemble_past_smem_goes_in_chunks_of_trees(cl, monkeypatch, T, fit,
                                                       launches, each):
    """A loop of launches, each adding to the margin of the one before: same
    bits as one launch, whatever the chunking, and no launch's tables pass
    the bound (``fit`` trees' worth of words)."""
    D, F, N = 4, 8, 1025
    levels, values, X = ensemble(np.random.default_rng(5), T, D, F, N)
    args = (on_device(levels), jnp.asarray(values), jnp.asarray(X))
    whole = np.asarray(_block(*args))
    monkeypatch.setattr(hist, "_WALK_SMEM_WORDS", int(fit * 3 * 2 ** D))

    def chunked(*a):               # a new function: nothing traced before
        return _walk_here(*a)
    jaxpr = jax.make_jaxpr(chunked)(*args)
    (mapped,) = [e for e in jaxpr.eqns if e.primitive.name == "shard_map"]
    (loop,) = [e for e in mapped.params["jaxpr"].eqns
               if e.primitive.name == "scan"]
    assert loop.params["length"] == launches
    assert str(loop.params["jaxpr"]).count("pallas_call") == 1
    tables = [v.aval.shape for v in loop.invars if v.aval.ndim == 2
              and v.aval.shape[0] == launches]
    assert sorted(tables) == sorted([(launches, each * (2 ** D - 1))] * 2
                                    + [(launches, each * 2 ** D)])
    assert np.array_equal(whole, np.asarray(jax.jit(chunked)(*args)))


def test_rows_are_padded_to_the_block_not_to_a_fixed_size(cl):
    """A small frame (a validation frame, a chunk's scoring) is one block of
    its own size; a large one is cut into blocks that waste under a fold
    tile each."""
    def kernel_rows(N, F=8):
        levels, values, _ = ensemble(np.random.default_rng(0), 2, 3, F, 8)
        jaxpr = jax.make_jaxpr(_walk_here)(
            on_device(levels), jnp.asarray(values), jnp.zeros((N, F)))
        (mapped,) = [e for e in jaxpr.eqns if e.primitive.name == "shard_map"]
        (call,) = [e for e in mapped.params["jaxpr"].eqns
                   if e.primitive.name == "pallas_call"]
        return call.outvars[0].aval.shape[0] * 128
    per_tile = hist._WALK_TILE * 128
    shards = cl.n_row_shards
    assert kernel_rows(1) == per_tile
    assert kernel_rows(100_000) == -(-100_000 // (shards * per_tile)) * per_tile
    big = shards * (hist._WALK_BLOCK * 128 + 1)
    assert kernel_rows(big) - big // shards <= 2 * per_tile


def test_path_is_chosen_from_depth_and_width_alone(cl, monkeypatch):
    # the blocked walk is a kernel for the TPU: nothing takes it elsewhere
    assert not shared._on_tpu()
    assert shared.traverse_path(6, 8) == "level"
    monkeypatch.setattr(shared, "_on_tpu", lambda: True)
    assert shared.traverse_path(6, 8) == "block"
    assert shared.traverse_path(CROSSOVER, 28) == "block"
    assert shared.traverse_path(CROSSOVER + 1, 8) == "level"
    assert shared.traverse_path(20, 8) == "level"
    # a block's 4F tiles must hold one register's rows at least
    assert shared.traverse_path(6, WIDEST) == "block"
    assert shared.traverse_path(6, WIDEST + 1) == "level"
    # and the deepest blocked tree's tables fit one launch
    assert 3 * 2 ** CROSSOVER <= hist._WALK_SMEM_WORDS


def test_a_wide_frame_is_folded_on_narrower_tiles(cl):
    """Past 4F tiles of 64 sublane rows in VMEM the fold tile shrinks with
    the block; same answers."""
    F = 600
    assert 8 <= hist.walk_block_rows(F) < hist._WALK_TILE
    levels, values, X = ensemble(np.random.default_rng(8), 2, 3, F, 1025)
    got = np.asarray(_block(on_device(levels), jnp.asarray(values),
                            jnp.asarray(X)))
    assert np.array_equal(got, margins(walk(levels, values, X), values)[1])


def test_a_frame_too_wide_for_a_block_is_walked_by_level(cl, monkeypatch,
                                                         as_on_the_chip):
    monkeypatch.setattr(hist, "_WALK_VMEM", 16 * 4 * 128 * 4 * 8)
    assert shared.traverse_path(2, 16) == "block"
    assert shared.traverse_path(2, 17) == "level"
    levels, values, X = ensemble(np.random.default_rng(9), 2, 2, 17, 9)
    hlo = shared.traverse_jit.lower(on_device(levels), jnp.asarray(values),
                                    jnp.asarray(X)).as_text()
    assert "dot_general" in hlo                    # table_lookup's product
    got = np.asarray(shared.traverse_jit(on_device(levels), jnp.asarray(values),
                                         jnp.asarray(X)))
    assert np.array_equal(got, margins(walk(levels, values, X), values)[1])


@pytest.mark.parametrize("nclass,path", [(2, "block"), (3, "block"),
                                         (2, "level")])
def test_dispatch_counter_rises_once_a_predict_and_class(cl, request, nclass,
                                                         path):
    """``traverse_dispatch_total{path}``: one increment per ``_raw_scores``
    call per class tree stack, under the walk that ran: the blocked one as
    on the chip, the per-level one as on this backend."""
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.models.tree.gbm import GBM
    from h2o3_tpu.runtime import observability as obs
    depth = 3
    if path == "block":
        request.getfixturevalue("as_on_the_chip")
    rng = np.random.default_rng(3)
    n = 5000
    X = rng.normal(size=(n, 3)).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.normal(size=n) > 0).astype(int) \
        + (nclass == 3) * (X[:, 1] > 0.5).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(3)}
    cols["y"] = np.array([f"c{v}" for v in y])
    fr = Frame.from_numpy(cols)
    # another tree count a path, so that no case is served the other's program
    model = GBM(response_column="y", ntrees=3 if path == "block" else 2,
                max_depth=depth, min_rows=1, seed=1).train(fr)
    stacks = model.output["stacked"]
    stacks = stacks if isinstance(stacks, list) else [stacks]
    assert all(shared.traverse_path(st.depth, 3) == path for st in stacks)

    def count():
        return {p: obs.counter("traverse_dispatch_total", path=p).value
                for p in ("block", "level")}
    before = count()
    model.predict(fr)
    after = count()
    other = "level" if path == "block" else "block"
    assert after[path] - before[path] == len(stacks)
    assert after[other] == before[other]
    assert len(stacks) == (1 if nclass == 2 else nclass)
