"""tpu_hist Pallas kernel parity tests (interpret mode vs einsum reference).

The CPU test mesh exercises the einsum path in normal runs; these tests pin
``force_impl`` to run the actual Pallas kernel through the interpreter and
cross-check it bit-for-bit-ish against the portable program, over geometries
that cover: single/multi row blocks, single/multi bin tiles, L=1..32, the
deep-tree fallback kernel, and weighted/NA rows.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from h2o3_tpu.models.tree.hist import make_hist_fn


GEOMETRIES = [
    # (N, F, B, L): small single-block
    (512, 3, 17, 1),
    # multiple row blocks
    (4096, 5, 17, 8),
    # multiple bin tiles (B > TB)
    (2048, 4, 129, 4),
    # airlines-shape: many bins, deeper level
    (4096, 8, 257, 16),
    # wide-ish features
    (1024, 30, 33, 2),
]


@pytest.mark.parametrize("N,F,B,L", GEOMETRIES)
def test_pallas_matches_einsum(cl, rng, N, F, B, L):
    codes = jnp.asarray(rng.integers(0, B, (F, N)), jnp.int32)
    leaf = jnp.asarray(rng.integers(0, L, N), jnp.int32)
    g = jnp.asarray(rng.normal(size=N), jnp.float32)
    h = jnp.asarray(rng.random(N), jnp.float32)
    w = jnp.asarray((rng.random(N) > 0.1), jnp.float32)
    He = make_hist_fn(L, F, B, N, force_impl="einsum")(codes, leaf, g, h, w)
    Hp = make_hist_fn(L, F, B, N, force_impl="pallas_interpret",
                      precision="f32")(codes, leaf, g, h, w)
    np.testing.assert_allclose(np.asarray(He), np.asarray(Hp),
                               atol=1e-3, rtol=1e-5)


def test_pallas_deep_fallback_matches(cl, rng):
    """Geometry big enough to trigger the VMEM-fallback kernel variant."""
    N, F, B, L = 2048, 8, 257, 512
    codes = jnp.asarray(rng.integers(0, B, (F, N)), jnp.int32)
    leaf = jnp.asarray(rng.integers(0, L, N), jnp.int32)
    g = jnp.asarray(rng.normal(size=N), jnp.float32)
    h = jnp.asarray(rng.random(N), jnp.float32)
    w = jnp.ones(N, jnp.float32)
    He = make_hist_fn(L, F, B, N, force_impl="einsum")(codes, leaf, g, h, w)
    Hp = make_hist_fn(L, F, B, N, force_impl="pallas_interpret",
                      precision="f32")(codes, leaf, g, h, w)
    np.testing.assert_allclose(np.asarray(He), np.asarray(Hp),
                               atol=1e-3, rtol=1e-5)


def test_varbin_hist_matches_dense(cl, rng):
    """Packed per-feature bin axis == dense histogram, bit-for-bit-ish."""
    from h2o3_tpu.models.tree.hist import (make_hist_fn, make_varbin_hist_fn,
                                           offset_codes)
    N, F, nbins, L = 2048, 5, 64, 4
    bin_counts = (7, 64, 22, 3, 40)        # mixed cardinalities
    B = nbins + 1
    codes_np = np.stack([
        np.where(rng.random(N) < 0.1, nbins,       # NA
                 rng.integers(0, bc, N))
        for bc in bin_counts])
    codes = jnp.asarray(codes_np, jnp.int32)
    leaf = jnp.asarray(rng.integers(0, L, N), jnp.int32)
    g = jnp.asarray(rng.normal(size=N), jnp.float32)
    h = jnp.asarray(rng.random(N), jnp.float32)
    w = jnp.asarray((rng.random(N) > 0.1), jnp.float32)
    He = np.asarray(make_hist_fn(L, F, B, N, force_impl="einsum")(
        codes, leaf, g, h, w))
    gcodes = offset_codes(codes, bin_counts, nbins)
    Hv = np.asarray(make_varbin_hist_fn(
        L, F, bin_counts, B, N, force_impl="pallas_interpret",
        precision="f32")(gcodes, leaf, g, h, w))
    np.testing.assert_allclose(He, Hv, atol=1e-3, rtol=1e-5)


def test_hist_totals_and_na_bin(cl, rng):
    """Histogram marginals equal direct sums; NA codes land in the last bin."""
    N, F, B, L = 1024, 4, 9, 2
    nbins = B - 1
    codes_np = rng.integers(0, B, (F, N))
    codes = jnp.asarray(codes_np, jnp.int32)
    leaf = jnp.asarray(rng.integers(0, L, N), jnp.int32)
    g = jnp.asarray(rng.normal(size=N), jnp.float32)
    h = jnp.asarray(rng.random(N), jnp.float32)
    w = jnp.ones(N, jnp.float32)
    H = np.asarray(make_hist_fn(L, F, B, N, force_impl="einsum")(
        codes, leaf, g, h, w))
    # sum over (leaf, bin) recovers the global sum for every feature
    np.testing.assert_allclose(H[0].sum(axis=(0, 2)),
                               [float(jnp.sum(g))] * F, rtol=1e-4)
    # NA bin counts = rows with code == nbins
    for f in range(F):
        na_count = (codes_np[f] == nbins).sum()
        assert H[2, :, f, nbins].sum() == pytest.approx(na_count)
