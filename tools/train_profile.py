"""Stage-level timing of the bench.py XGBoost train path on the chip.

Where end-to-end trees/s falls short of what bench_pieces.py's kernel sum
would give, this script finds the rest by timing each stage of the exact
train() pipeline separately:

  ingest     Frame.from_numpy (host->device push of the 10M x 9 table)
  fit_bins   quantile edge fit + 10M x 8 quantization to codes
  compile    first scan_fn chunk (10 trees) — compile + first exec
  chunk      steady-state scan_fn chunk (10 trees per dispatch)
  finalize   training-metrics path on the final margin F

Usage (chip): python tools/train_profile.py
Smoke:        JAX_PLATFORMS=cpu H2O3_TP_ROWS=100000 python tools/train_profile.py
"""

import json
import os
import time

import numpy as np

N_ROWS = int(os.environ.get("H2O3_TP_ROWS", 10_000_000))


def main():
    import jax
    import jax.numpy as jnp

    import h2o3_tpu
    from h2o3_tpu import Frame
    from h2o3_tpu.frame.vec import T_CAT

    h2o3_tpu.init()
    import bench as B

    def stamp(stage, t0, **extra):
        dt = time.perf_counter() - t0
        print(json.dumps({"stage": stage, "s": round(dt, 3), **extra}),
              flush=True)
        return time.perf_counter()

    cols, types, domains = B.make_airlines_like(N_ROWS)
    types = {k: (T_CAT if v == "cat" else v) for k, v in types.items()}

    t0 = time.perf_counter()
    fr = Frame.from_numpy(cols, types=types, domains=domains)
    for v in fr.vecs:                       # force the push
        if v.data is not None:
            np.asarray(v.data[:1])
    t0 = stamp("ingest", t0)

    from h2o3_tpu.models.tree.binning import fit_bins, edges_matrix
    names = [n for n in fr.names if n != "dep_delayed_15min"]
    binned = fit_bins(fr, names, nbins=256, seed=1)
    np.asarray(binned.codes[:1, :1])
    t0 = stamp("fit_bins", t0, nfeatures=binned.nfeatures,
               bin_counts=list(binned.bin_counts))

    from h2o3_tpu.models.tree.shared import make_tree_scan_fn
    codes = binned.codes
    N = codes.shape[1]
    y = (np.asarray(cols["dep_delayed_15min"]) == "YES").astype(np.float32)
    y = jnp.asarray(y)
    if N > y.shape[0]:
        y = jnp.pad(y, (0, N - y.shape[0]))
    w = jnp.ones((N,), jnp.float32)
    edges_mat = jnp.asarray(edges_matrix(binned.edges, 256), jnp.float32)
    scan_fn = make_tree_scan_fn(
        "bernoulli", 1.5, 0.5, 0.9, 6, 256, binned.nfeatures, N,
        "bf16", 1.0, 1.0, hier=False, bin_counts=binned.bin_counts)
    scalars = (1.0, 1.0, 0.0, 0.3, 1.0, 0.0, 0.0, 0.0)
    F0 = jnp.zeros((N,), jnp.float32)
    rng = jax.random.PRNGKey(1)

    chunk_counter = [0]

    def run_chunk(F):
        cn = chunk_counter[0]
        chunk_counter[0] += 1
        F, lv, vals, cov = scan_fn(codes, y, w, F, edges_mat,
                                   rng, cn, 10, *scalars, 0)
        return F, (lv, vals, cov)

    F, out = run_chunk(F0)
    np.asarray(F[:1])
    t0 = stamp("compile+first_chunk", t0)

    for rep in range(3):
        F, out = run_chunk(F)
        np.asarray(F[:1])
        t0 = stamp(f"chunk_{rep}", t0, trees=10,
                   ms_per_tree=None)

    # finalize path: metrics from the final margin (no traverse)
    t0 = time.perf_counter()
    p = jax.nn.sigmoid(F)
    auc_in = np.asarray(jnp.stack([1 - p, p], axis=1))
    t0 = stamp("fetch_probs_10m", t0)


if __name__ == "__main__":
    main()
