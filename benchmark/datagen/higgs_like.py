"""Synthetic frame of the shape of HIGGS: dense float32 numerics and a
binary label drawn from a logistic model.

After ``bench.py:make_higgs_like``, with an integer label plus ``domains``
and the rows drawn in parallel chunks. The generating coefficients come from
a fixed stream, not from the seed, so that every seed poses a fit of the same
difficulty (the same number of IRLS iterations); features and label noise
come from the seed.
"""

import numpy as np

from benchmark.datagen import _chunks

RESPONSE = "y"


def generate(rows, seed, cols=28):
    """(columns, domains of the categorical columns, response name)."""
    beta = (np.random.default_rng(3).normal(size=cols) * 0.3).astype(np.float32)

    def piece(rng, n):
        # Box-Muller from float32 uniforms: numpy's float32 normals hold the
        # GIL, so chunks of them do not draw in parallel. A row is a column.
        half = (cols + 1) // 2
        r = np.sqrt(-2 * np.log1p(-rng.random((half, n), dtype=np.float32)))
        a = np.float32(2 * np.pi) * rng.random((half, n), dtype=np.float32)
        X = np.concatenate([r * np.cos(a), r * np.sin(a)])[:cols]
        p = 1 / (1 + np.exp(-(beta @ X - 0.2)))
        out = {f"f{j}": X[j] for j in range(cols)}
        out[RESPONSE] = (rng.random(n, dtype=np.float32) < p).astype(np.int32)
        return out

    return _chunks.draw(piece, rows, seed), {RESPONSE: ["b", "s"]}, RESPONSE
