"""Row chunks drawn in parallel: a generator's columns for ``rows`` rows are
the concatenation of CHUNKS pieces, piece k from the stream ``(seed, k)``.
The number of pieces is fixed, so the data depend on the seed alone and not
on how many threads or cores drew them (numpy releases the GIL in its
draws)."""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNKS = 16
THREADS = 8


def draw(piece, rows, seed):
    """``piece(rng, n) -> {column: array}`` over CHUNKS streams, joined."""
    bounds = np.linspace(0, rows, CHUNKS + 1).astype(np.int64)
    sizes = np.diff(bounds)
    with ThreadPoolExecutor(THREADS) as pool:
        parts = list(pool.map(
            lambda k: piece(np.random.default_rng([seed, k]), int(sizes[k])),
            range(CHUNKS)))
        names = list(parts[0])
        joined = list(pool.map(
            lambda name: np.concatenate([p[name] for p in parts]), names))
    return dict(zip(names, joined))
