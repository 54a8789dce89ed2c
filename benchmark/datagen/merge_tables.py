"""Two tables of the shape of H2O-3's merge gate (100M rows x 2 columns
each), after h2oai/db-benchmark's join task "big inner on int": an integer
key and one float32 value a side.

Left: ``key`` drawn uniformly with replacement from [0, span), ``v1`` uniform
float32. Right, in seeded random order: 89 % of its rows hold distinct keys
of [0, span), 2 % hold 1 % further keys of [0, span) twice each, 9 % hold
keys of [span, 1.1 span), which match nothing; ``v2`` uniform float32. With
``span = rows`` about 90 % of left rows match, one in 90 of those twice.
``key_base`` shifts every key (a tiny rehearsal keeps its keys past 2^24
with it). Keys are int64 on the host, as a reference wants them.

The distinct keys are the first 0.9 ``rows`` values of a seeded affine
bijection of [0, span) (``(a i + b) mod span``, ``a`` coprime to ``span``):
distinct by construction, with no 100M-element shuffle; the rows' order is
then one true seeded shuffle.
"""

import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _left(seed, rows, span, key_base):
    rng = np.random.default_rng([seed, 1])
    return {"key": key_base + rng.integers(0, span, rows, dtype=np.int64),
            "v1": rng.random(rows, dtype=np.float32)}


def _right(seed, rows, span, key_base):
    rng = np.random.default_rng([seed, 2])
    once, twice = round(0.89 * rows), round(0.01 * rows)
    nothing = rows - once - 2 * twice
    if once + twice > span:
        raise ValueError(f"{once + twice} distinct keys do not fit [0, {span})")
    a = int(rng.integers(1, span))
    while math.gcd(a, span) != 1:
        a += 1
    b = int(rng.integers(0, span))
    pool = (a * np.arange(once + twice, dtype=np.int64) + b) % span
    key = np.concatenate([pool, pool[once:],
                          span + rng.integers(0, max(span // 10, 1), nothing, dtype=np.int64)])
    rng.shuffle(key)
    return {"key": key_base + key, "v2": rng.random(rows, dtype=np.float32)}


def generate(rows, seed, key_base=0):
    """({"left": columns, "right": columns}, {}, None): both tables have
    ``rows`` rows; there are no categorical domains and no response."""
    with ThreadPoolExecutor(2) as pool:
        left = pool.submit(_left, seed, rows, rows, key_base)
        right = pool.submit(_right, seed, rows, rows, key_base)
        return {"left": left.result(), "right": right.result()}, {}, None
