"""Traffic ``merge``: ``left.merge(right, by="key", how="inner")`` through
``Frame.merge``, back to back, each ended when every output column is on the
device. Both tables are uploaded once in set-up. ``rows_per_s`` is the left
table's rows x merges completed over the window's wall time.

Set-up reads a seeded sample of each table's keys back from the device and
stops if any differs from the host's: a system that cannot hold the keys
(float32 has 24 bits, a key over 100M rows needs 27) would go on to join rows
whose keys differ, and to an output several times too large for the chip.
"""

import jax
import numpy as np

from benchmark.drivers import _common

ANNOTATION = "bench.merge"


def keys_held(frame, host_keys, rows):
    """Whether the device's keys at ``rows`` are the host's."""
    held = np.asarray(jax.device_get(frame.vec("key").data[rows]))
    return bool(np.array_equal(held.astype(np.int64), host_keys[rows]))


def set_up(cfg, mix, seed, data):
    from h2o3_tpu import Frame
    tables, _, _ = data
    frames = {side: Frame.from_numpy(cols) for side, cols in tables.items()}
    for side, frame in frames.items():
        _common.sync_frame(frame)
        n = frame.nrows
        rows = np.sort(np.random.default_rng([seed, 3]).choice(
            n, min(n, mix["readback_rows"]), replace=False))
        if not keys_held(frame, tables[side]["key"], rows):
            raise RuntimeError(
                f"the {side} table's keys came back from the device changed "
                f"(payload {frame.vec('key').data.dtype}): this system cannot hold "
                f"integer keys of this size, and a join on them would be wrong")
    return {"seed": seed, "tables": tables, "rows": frames["left"].nrows,
            "right_rows": frames["right"].nrows, **frames}


def unit(state):
    out = state["left"].merge(state["right"], by="key", how="inner")
    _common.sync_frame(out)
    state["out_rows"] = out.nrows       # costs/merge.py: what the join really wrote
    return out


def metrics(state, units, elapsed):
    return {"rows_per_s": state["rows"] * units / elapsed}
