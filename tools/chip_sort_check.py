"""Chip check of Frame.sort at the merge gate's size (PR 39; run through the chip tool): sort(left, "key") and a
two-key descending sort of 100M x 2 against reference_sort, exactly; seconds
of each (first call = with compile, then three steady calls). Exits 2 unless
JAX found a TPU: a line of its output is a chip reading or nothing."""
import json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]      # the reference lives beside its tests
import numpy as np
import jax
import h2o3_tpu
from h2o3_tpu import Frame
import reference_munge as ref
from benchmark.datagen import merge_tables

rows = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000_000
seed = int(sys.argv[2]) if len(sys.argv) > 2 else 2147483701
device = jax.devices()[0]
if device.platform != "tpu":
    print(f"chip_sort_check: needs a tpu device, JAX found {jax.devices()}", file=sys.stderr)
    sys.exit(2)
h2o3_tpu.init(devices=[device])
tables, _, _ = merge_tables.generate(rows=rows, seed=seed)
left = dict(tables["left"])
left["k2"] = (left["key"] % 7).astype(np.float64)        # a second, small key: float32 payload
L = Frame.from_numpy(left)
jax.block_until_ready([v.data for v in L.vecs])


def timed(by, asc):
    out = []
    for i in range(4):
        t = time.perf_counter()
        s = L.sort(by, ascending=asc)
        jax.block_until_ready([v.data for v in s.vecs])
        out.append(time.perf_counter() - t)
    return s, out


for by, asc in ((["key"], True), (["k2", "key"], [False, False])):
    s, secs = timed(by, asc)
    t = time.perf_counter()
    want = ref.reference_sort(left, by, asc)
    ref_s = time.perf_counter() - t
    same = all(np.array_equal(np.asarray(s.vec(n).to_numpy()).astype(want[n].dtype), want[n])
               for n in left)
    bits = np.array_equal(np.asarray(s.vec("v1").to_numpy()).view(np.int32), want["v1"].view(np.int32))
    print(json.dumps({"device_kind": device.device_kind, "sort_by": by, "ascending": asc, "rows": rows, "first_s": secs[0],
                      "steady_s": secs[1:], "equal_to_reference": bool(same and bits),
                      "reference_s": ref_s,
                      "peak_bytes": (device.memory_stats() or {}).get("peak_bytes_in_use")}),
          flush=True)
