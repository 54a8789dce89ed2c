"""Synthetic frame of the shape of H2O-3's airlines gate: five numeric and
three categorical predictors (22 / 300 / 300 levels), binary label.

After ``bench.py:make_airlines_like``, with the label and the categoricals as
integer codes plus ``domains`` (an object-dtype label makes the same frame
cost twice as long to build) and the rows drawn in parallel chunks. The logit
is weak on purpose: its own AUC against the labels it draws is 0.534 (numpy,
2M rows), which is the ceiling a fit can approach.
"""

import numpy as np

from benchmark.datagen import _chunks

RESPONSE = "dep_delayed_15min"


def _piece(rng, n):
    cols = {
        "year": rng.integers(1987, 2008, n).astype(np.float32),
        "month": rng.integers(1, 13, n).astype(np.float32),
        "day_of_week": rng.integers(1, 8, n).astype(np.float32),
        "crs_dep_time": rng.integers(0, 2400, n).astype(np.float32),
        "distance": np.abs(rng.normal(700, 500, n)).astype(np.float32),
        "carrier": rng.integers(0, 22, n, dtype=np.int32),
        "origin": rng.integers(0, 300, n, dtype=np.int32),
        "dest": rng.integers(0, 300, n, dtype=np.int32),
    }
    logit = (0.002 * (cols["crs_dep_time"] / 100 - 12) ** 2
             - 0.0005 * cols["distance"] / 100
             + 0.2 * np.isin(cols["day_of_week"], (5, 7))
             + 0.1 * rng.normal(size=n))
    cols[RESPONSE] = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.int32)
    return cols


def generate(rows, seed):
    """(columns, domains of the categorical columns, response name)."""
    domains = {"carrier": [str(i) for i in range(22)],
               "origin": [str(i) for i in range(300)],
               "dest": [str(i) for i in range(300)],
               RESPONSE: ["NO", "YES"]}
    return _chunks.draw(_piece, rows, seed), domains, RESPONSE
