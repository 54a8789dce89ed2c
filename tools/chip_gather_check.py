"""Chip readings behind ``rapids/device.py:gather_columns`` (PR 40; run through
the chip tool): at the merge gate's shapes (a 100M-row left table, the real
left row of every output slot of a merge of the seed's tables) the seconds of

- the parent's left side: four 1-D gathers (``cnt``, ``start``, ``key``,
  ``v1`` at ``li``), and one of them alone;
- ``gather_columns`` over 3, 4 and 8 columns by the same index (8 by its
  first half, beside 3 at as many slots; ONE stack of 16 did not compile
  beside the tables even at a quarter of the slots, 17.97 GB of 15.75: PR
  40), and one column and 3 by a random index, as a sort's order is;
- a sort of left rows + slots with 3, 4 and 5 operands (what a value would
  cost that rode ``expand_counts``' second sort instead).

One JSON line a reading: first call (with compile), then three steady calls.
Exits 2 unless JAX found a TPU: a line of its output is a chip reading or
nothing."""
import json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import numpy as np
import jax
import jax.numpy as jnp
import h2o3_tpu
from h2o3_tpu import Frame
from h2o3_tpu.rapids import device as dev
from benchmark.datagen import merge_tables

rows = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000_000
seed = int(sys.argv[2]) if len(sys.argv) > 2 else 2147483701
device = jax.devices()[0]
if device.platform != "tpu":
    print(f"chip_gather_check: needs a tpu device, JAX found {jax.devices()}", file=sys.stderr)
    sys.exit(2)
h2o3_tpu.init(devices=[device])
tables, _, _ = merge_tables.generate(rows=rows, seed=seed)
L, R = Frame.from_numpy(tables["left"]), Frame.from_numpy(tables["right"])
del tables
key, v1 = L.vec("key").data, L.vec("v1").data
matched = dev.merge_match((key,), (R.vec("key").data,), (None,), (None,), np.int32(rows),
                          np.int32(rows), lkinds=(dev.INT,), rkinds=(dev.INT,), how="inner")
cnt, start, m = matched[0], matched[1], int(matched[3])
p_out = dev.merge_padded_rows(m, L.padded_rows)
del R, matched


@jax.jit
def slots(cnt, m):
    """``li`` and the sort operands as ``jit_merge_gather`` makes them."""
    owner, offset = dev.expand_counts(jnp.maximum(cnt, 0), p_out)
    li = jnp.where(jnp.arange(p_out) < m, jnp.minimum(owner, cnt.shape[0] - 1), 0)
    return li, offset


li, offset = slots(cnt, np.int32(m))


def report(name, fn, *operands, indices=p_out, **more):
    secs = []
    try:
        for _ in range(4):
            t = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            secs.append(time.perf_counter() - t)
    except Exception as e:              # noqa: BLE001: report, go on
        more["error"] = f"{type(e).__name__}: {e}"[:300]
    steady = min(secs[1:]) if len(secs) > 1 else None
    print(json.dumps({"device_kind": device.device_kind, "reading": name, "rows": rows,
                      "indices": indices, "first_s": secs[0] if secs else None,
                      "steady_s": secs[1:],
                      "ns_per_index": steady and 1e9 * steady / indices,
                      "peak_bytes": (device.memory_stats() or {}).get("peak_bytes_in_use"),
                      **more}), flush=True)


report("one 1-D gather", jax.jit(lambda c, i: c[i]), key, li)
report("four 1-D gathers", jax.jit(lambda cs, i: [c[i] for c in cs]),
       (cnt, start, key, v1), li)
stacked = jax.jit(dev.gather_columns)
report("gather_columns of 3", stacked, (start, key, v1), li)
report("gather_columns of 4", stacked, (start, key, v1, cnt), li)
half = li[: p_out // 2]
report("gather_columns of 8, half the slots", stacked, (start, key, v1, cnt) * 2, half,
       indices=p_out // 2)
report("gather_columns of 3, half the slots", stacked, (start, key, v1), half,
       indices=p_out // 2)
del half

scattered = jax.random.randint(jax.random.PRNGKey(seed % 2 ** 31), (p_out,), 0, rows)
report("one 1-D gather, random index", jax.jit(lambda c, i: c[i]), key, scattered)
report("gather_columns of 3, random index", stacked, (start, key, v1), scattered)
del scattered

# a sort of left rows + slots carrying 2, 3 and 4 payloads beside its key
n = start.shape[0] + p_out
merged = jnp.concatenate([li, jnp.arange(start.shape[0], dtype=jnp.int32)])
payload = jnp.concatenate([offset, start])
del li, offset
for extra in (2, 3, 4):
    report(f"lax.sort of {n} rows, {1 + extra} operands",
           jax.jit(lambda k, p, extra=extra: jax.lax.sort(
               (k, *(p + j for j in range(extra))), num_keys=1)),
           merged, payload, indices=n)
