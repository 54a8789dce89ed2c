"""Frame: a named columnar table of Vecs, row-sharded over the mesh.

Reference: ``water/fvec/Frame.java:65`` — a Frame is an ordered set of column
names + Vec keys, lockable for R/W coherence, living in the DKV.  Columns are
chunked identically (VectorGroup, Vec.java:1528) so row i of every column is
on the same node.

TPU-native redesign: every Vec payload is a ``jax.Array`` sharded with the
same NamedSharding over the mesh "rows" axis, which gives the VectorGroup
row-alignment property by construction.  There is no lock protocol — Frames
are functionally immutable (mutation returns a new Frame), which is what XLA
wants anyway.  ``matrix()`` materializes a [rows, features] design block for
the algorithms (the hot path feeding the MXU) and caches it on the Frame the
way the reference caches rollups.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime.cluster import cluster
from ..runtime import dkv
from ..runtime import observability as obs
from .vec import Vec, T_CAT, T_NUM, T_STR, T_TIME


class Frame:
    def __init__(self, names: Sequence[str], vecs: Sequence[Vec],
                 key: Optional[str] = None):
        if len(names) != len(vecs):
            raise ValueError("names/vecs length mismatch")
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate column names: {list(names)}")
        nrows = {v.nrows for v in vecs}
        if len(nrows) > 1:
            raise ValueError(f"vecs disagree on nrows: {nrows}")
        self.names: List[str] = list(names)
        self.vecs: List[Vec] = list(vecs)
        self.nrows: int = vecs[0].nrows if vecs else 0
        self.key = key
        self._matrix_cache: Dict[tuple, jax.Array] = {}
        self._atime = time.monotonic()       # LRU clock for the Cleaner
        self._lineage: Optional[dict] = None  # frame/lineage.py provenance
        if key is not None:
            dkv.put(key, self)

    # ------------------------------------------------------------- accessors
    @property
    def ncols(self) -> int:
        return len(self.vecs)

    @property
    def shape(self):
        return (self.nrows, self.ncols)

    @property
    def padded_rows(self) -> int:
        return self.vecs[0].padded_len if self.vecs else 0

    def vec(self, name: str) -> Vec:
        self._atime = time.monotonic()
        try:
            return self.vecs[self.names.index(name)]
        except ValueError:
            raise KeyError(f"no column {name!r} in frame (have {self.names})")

    def __getitem__(self, cols) -> "Frame":
        if isinstance(cols, str):
            cols = [cols]
        from . import lineage
        return lineage.derive(Frame(cols, [self.vec(c) for c in cols]),
                              self, {"op": "cols", "cols": list(cols)})

    def types(self) -> Dict[str, str]:
        return {n: v.type for n, v in zip(self.names, self.vecs)}

    def valid_mask(self) -> jax.Array:
        return self.vecs[0].valid_mask()

    # ------------------------------------------------------------ construct
    @staticmethod
    def from_numpy(arrays: Dict[str, np.ndarray], key: Optional[str] = None,
                   types: Optional[Dict[str, str]] = None,
                   domains: Optional[Dict[str, Sequence[str]]] = None) -> "Frame":
        """Build a Frame from host columns (tests' TestFrameBuilder analog)."""
        types = types or {}
        domains = domains or {}
        names, vecs = [], []
        rows = len(next(iter(arrays.values()))) if arrays else 0
        with obs.span("frame.upload", rows=rows, cols=len(arrays)):
            for name, arr in arrays.items():
                arr = np.asarray(arr)
                vtype = types.get(name)
                domain = domains.get(name)
                if vtype is None:
                    if arr.dtype == object or arr.dtype.kind in "US":
                        labels, codes = np.unique(arr.astype(str), return_inverse=True)
                        vtype, domain, arr = T_CAT, [str(l) for l in labels], codes
                    else:
                        vtype = T_NUM
                names.append(name)
                vecs.append(Vec.from_numpy(arr, vtype, domain=domain))
        return Frame(names, vecs, key=key)

    # --------------------------------------------------------------- munging
    def cbind(self, other: "Frame") -> "Frame":
        if other.nrows != self.nrows:
            raise ValueError("cbind: row counts differ")
        return Frame(self.names + other.names, self.vecs + other.vecs)

    def rename(self, mapping: Dict[str, str]) -> "Frame":
        from . import lineage
        return lineage.derive(
            Frame([mapping.get(n, n) for n in self.names], self.vecs),
            self, {"op": "rename", "mapping": dict(mapping)})

    def drop(self, cols: Sequence[str]) -> "Frame":
        cols = set([cols] if isinstance(cols, str) else cols)
        keep = [(n, v) for n, v in zip(self.names, self.vecs) if n not in cols]
        from . import lineage
        return lineage.derive(
            Frame([n for n, _ in keep], [v for _, v in keep]),
            self, {"op": "drop", "cols": sorted(cols)})

    def with_vec(self, name: str, vec: Vec) -> "Frame":
        if name in self.names:
            vecs = list(self.vecs)
            vecs[self.names.index(name)] = vec
            return Frame(self.names, vecs)
        return Frame(self.names + [name], self.vecs + [vec])

    def rows(self, index: np.ndarray) -> "Frame":
        """Row subset by integer index (host-driven gather, re-sharded)."""
        index = np.asarray(index)
        out = []
        for v in self.vecs:
            if v.data is None:
                out.append(Vec.from_numpy(v.host_data[: v.nrows][index], v.type))
            else:
                col = v.to_numpy()[index]
                out.append(Vec.from_numpy(col, v.type, domain=v.domain,
                                          time_base=v.time_base))
        from . import lineage
        return lineage.derive_rows(Frame(self.names, out), self, index)

    def filter(self, mask: np.ndarray) -> "Frame":
        mask = np.asarray(mask, dtype=bool)
        return self.rows(np.nonzero(mask[: self.nrows])[0])

    def split_frame(self, ratios: Sequence[float], seed: int = 0) -> List["Frame"]:
        """Random row split — analog of h2o.split_frame (random uniform)."""
        rng = np.random.default_rng(seed)
        u = rng.random(self.nrows)
        bounds = np.cumsum(list(ratios))
        if len(bounds) == 0 or bounds[-1] < 1.0 - 1e-9:
            bounds = np.append(bounds, 1.0)
        bounds[-1] = np.inf  # last piece takes everything remaining
        pieces, lo = [], 0.0
        from . import lineage
        for i, hi in enumerate(bounds):
            p = self.filter((u >= lo) & (u < hi))
            # a (ratios, seed, piece) triple replays smaller than the
            # row index the filter recorded — override it
            lineage.derive(p, self, {"op": "split",
                                     "ratios": [float(r) for r in ratios],
                                     "seed": int(seed), "piece": i})
            pieces.append(p)
            lo = hi
        return pieces

    # ---------------------------------------------------------- device views
    def matrix(self, cols: Optional[Sequence[str]] = None,
               dtype=jnp.float32) -> jax.Array:
        """[padded_rows, len(cols)] design block; cats as raw codes (-1 NA).

        The MXU feed: column Vec payloads stacked into one row-sharded 2-D
        array.  Cached per column-set (the reference caches the per-algo
        DataInfo adaptation similarly, hex/DataInfo.java).
        """
        self._atime = time.monotonic()
        cols = list(cols) if cols is not None else list(self.names)
        ck = (tuple(cols), str(dtype))
        hit = self._matrix_cache.get(ck)
        if hit is not None:
            return hit
        cl = cluster()
        parts = []
        for c in cols:
            v = self.vec(c)
            if v.data is None:
                raise TypeError(f"column {c!r} of type {v.type} is host-only")
            parts.append(v.values().astype(dtype))
        mat = jnp.stack(parts, axis=1)
        from ..runtime.cluster import put_sharded
        mat = put_sharded(mat, cl.matrix_sharding)
        self._matrix_cache[ck] = mat
        return mat

    # ---------------------------------------------------------------- export
    def to_pandas(self):
        import pandas as pd
        return pd.DataFrame({n: v.decoded() for n, v in zip(self.names, self.vecs)})

    # ------------------------------------------------- munging sugar
    # h2o-py's H2OFrame carries the munging verbs as methods; the device
    # implementations live in rapids/ops.py and these delegate.
    def sort(self, by, ascending=True) -> "Frame":
        from ..rapids import ops
        return ops.sort(self, by, ascending=ascending)

    def merge(self, other: "Frame", by, how: str = "inner") -> "Frame":
        from ..rapids import ops
        return ops.merge(self, other, by, how=how)

    def group_by(self, by, aggs) -> "Frame":
        from ..rapids import ops
        return ops.group_by(self, by, aggs)

    def impute(self, column: str, method: str = "mean",
               combine_method: str = "interpolate") -> "Frame":
        from ..rapids import ops
        return ops.impute(self, column, method=method,
                          combine_method=combine_method)

    def scale(self, center: bool = True, scale: bool = True) -> "Frame":
        from ..rapids import ops
        return ops.scale(self, center=center, scale_=scale)

    def cor(self, cols=None, use: str = "complete.obs"):
        from ..rapids import ops
        return ops.cor(self, cols, use=use)

    def var(self, cols=None, use: str = "complete.obs"):
        from ..rapids import ops
        return ops.var(self, cols, use=use)

    def spill(self) -> int:
        """Evict all device payloads to host RAM (Cleaner analog)."""
        freed = sum(int(m.nbytes) for m in self._matrix_cache.values())
        self._matrix_cache.clear()
        return freed + sum(v.spill() for v in self.vecs)

    def to_numpy(self) -> np.ndarray:
        return np.stack([np.asarray(v.to_numpy(), dtype=np.float64)
                         for v in self.vecs], axis=1)

    def head(self, n: int = 10):
        return self.to_pandas().head(n)

    def describe(self) -> "Dict[str, dict]":
        """h2o-py H2OFrame.describe() alias for summary()."""
        return self.summary()

    def warm_rollups(self) -> None:
        """Batch-compute rollups for every device column that lacks them —
        ONE fused program + ONE fetch (RollupStats' lazy-compute contract,
        but frame-wide: per-column eager rollups cost a dispatch and a
        device->host fetch each)."""
        from .vec import RollupStats, _batch_rollup_kernel
        # membership test must NOT touch v.data: the getter transparently
        # restores spilled payloads, and restoring ALL columns up-front
        # would defeat the spill mechanism (blocks restore lazily below)
        todo = [v for v in self.vecs
                if v._rollups is None
                and (v._device is not None or v._spill is not None)
                and not (v.type == T_TIME and v.host_data is not None)]
        if len(todo) < 2:
            return
        import jax
        # block the stack: a single [C, padded] copy of a wide frame near
        # HBM capacity would defeat the Vec spill mechanism it exists for
        blk = max(2, 268_435_456 // (4 * max(todo[0].padded_len, 1)))
        for lo in range(0, len(todo), blk):
            chunk = todo[lo: lo + blk]
            X = jnp.stack([v.numeric_data() for v in chunk], axis=0)
            cnt, mean, var, vmin, vmax, nzero = (
                np.asarray(a) for a in jax.device_get(
                    _batch_rollup_kernel(X, chunk[0].nrows)))
            for i, v in enumerate(chunk):
                n = int(cnt[i])
                v._rollups = RollupStats(
                    nrows=v.nrows, nmissing=v.nrows - n,
                    mean=float(mean[i]) if n else float("nan"),
                    sigma=(float(np.sqrt(max(float(var[i]), 0.0)))
                           if n > 1 else float("nan")),
                    vmin=float(vmin[i]) if n else float("nan"),
                    vmax=float(vmax[i]) if n else float("nan"),
                    nzero=int(nzero[i]))

    def summary(self) -> Dict[str, dict]:
        self.warm_rollups()
        out = {}
        for name, v in zip(self.names, self.vecs):
            if v.data is None:
                out[name] = {"type": v.type, "missing": v.rollups().nmissing}
            else:
                r = v.rollups()
                out[name] = {"type": v.type, "min": r.vmin, "max": r.vmax,
                             "mean": r.mean, "sigma": r.sigma,
                             "missing": r.nmissing, "zeros": r.nzero,
                             "cardinality": v.cardinality}
        return out

    def __repr__(self):
        return f"<Frame {self.key or ''} {self.nrows}x{self.ncols} {self.names[:8]}>"
