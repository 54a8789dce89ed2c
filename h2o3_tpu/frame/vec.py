"""Vec: one column of a distributed Frame.

Reference: ``water/fvec/Vec.java:157`` — a Vec is column metadata + an ESPC
row layout + per-chunk DKV keys, with logical types T_BAD/T_UUID/T_STR/T_NUM/
T_CAT/T_TIME (Vec.java:207-212) and lazily computed, cached ``RollupStats``
(min/max/mean/sigma/histogram; fvec/RollupStats.java:19-30).  Chunks use 20+
compression codecs chosen at write time (fvec/NewChunk.java:1133).

TPU-native redesign: a Vec's payload is ONE row-sharded ``jax.Array`` padded
to the cluster row multiple — XLA wants flat dtypes and static shapes, so the
codec zoo collapses to three payloads: float32 for numeric/time, int32 codes
for categoricals, and int32 values for a numeric column of whole numbers past
float32's exact range (``takes_exact_int``: the ``C4Chunk`` analog; a row id
or a join key over 100M rows needs 27 bits, and float32 has 24).  Missing
values are NaN (float32), code -1 (categorical) or ``INT_NA`` (exact
integers); ``Vec.isna()`` is the one accessor that knows which.  One payload
a column: ``numeric_data()`` is the float32 view of any of them.
Strings/UUIDs stay host-side (numpy object arrays) — they never participate in
device compute (SURVEY.md §7 "keep string columns host-side only").
Rollups are computed lazily in a single fused XLA pass and cached, exactly
mirroring the reference's RollupStats contract.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime.cluster import cluster

# Logical column types — mirrors Vec.java:207-212.
T_BAD = "bad"
T_NUM = "num"
T_CAT = "cat"
T_TIME = "time"
T_STR = "str"
T_UUID = "uuid"

_DEVICE_TYPES = (T_NUM, T_CAT, T_TIME, T_BAD)

# an exact-integer column's missing value: no value the rule admits
INT_NA = np.int32(-(1 << 31))
_F32_EXACT = 1 << 24            # float32 holds every integer up to here
_I32_LIMIT = 1 << 31


def takes_exact_int(arr: np.ndarray) -> bool:
    """The one rule for a numeric column's payload: int32, exact, where
    every value is a whole number (or NA) and the largest magnitude is over
    2^24 and under 2^31; float32, as ever, for all else (fractions, whole
    numbers float32 already holds exactly, and magnitudes from 2^31 on,
    which still round: ROADMAP "Cannot run yet").  It reads the values as
    the caller holds them, before any cast."""
    if arr.size == 0 or arr.dtype.kind not in "iuf":
        return False
    if arr.dtype.kind in "iu":
        top = max(abs(int(arr.min())), abs(int(arr.max())))
        return _F32_EXACT < top < _I32_LIMIT
    with np.errstate(invalid="ignore"):
        lo, hi = np.fmin.reduce(arr), np.fmax.reduce(arr)   # NaN-skipping
        top = max(-float(lo), float(hi))
        if not _F32_EXACT < top < _I32_LIMIT:                # NaN compares False
            return False
        return bool(np.all((arr == np.rint(arr)) | np.isnan(arr)))


def exact_int_host(payload: np.ndarray) -> np.ndarray:
    """An exact-integer payload as host values: float64, which holds every
    int32 exactly, with NaN for NA."""
    return np.where(payload == INT_NA, np.nan, payload.astype(np.float64))


def encode_domain(svals: np.ndarray, domain: Sequence[str],
                  na_mask: Optional[np.ndarray] = None) -> np.ndarray:
    """int32 codes of string values against an ORDERED domain; values not
    in the domain (and ``na_mask`` positions) code as -1.

    Vectorized via argsort + searchsorted — the per-cell dict lookup this
    replaces was a multi-second Python loop at parse-bench scale.
    """
    svals = np.asarray(svals)
    if svals.dtype.kind not in "US":
        svals = svals.astype(str)
    dom = np.asarray(list(domain), dtype=str)
    if len(dom) == 0:
        codes = np.full(len(svals), -1, np.int32)
    else:
        sorter = np.argsort(dom)
        pos = np.searchsorted(dom, svals, sorter=sorter)
        pos = np.clip(pos, 0, len(dom) - 1)
        hits = sorter[pos]
        codes = np.where(dom[hits] == svals, hits, -1).astype(np.int32)
    if na_mask is not None:
        codes[na_mask] = -1
    return codes


@dataclasses.dataclass
class RollupStats:
    """Lazily computed column statistics (fvec/RollupStats.java:19-30)."""

    nrows: int
    nmissing: int
    mean: float
    sigma: float
    vmin: float
    vmax: float
    nzero: int

    @property
    def is_constant(self) -> bool:
        return self.nrows - self.nmissing > 0 and self.vmin == self.vmax


def _ledger(name, jitted, orig=None, **kw):
    """Register a compiled frame seam with the compile ledger
    (runtime/xprof) — the parse/rollup side of the ledger."""
    from ..runtime import xprof
    return xprof.register_program(name, jitted, orig=orig, **kw)


def _moments(x, present, nf, axis):
    """(mean, variance over n - 1) of the present values along ``axis`` in
    float32, by the corrected two-pass sum: a first mean ``m0``, then the
    sums of ``d = x - m0`` and of ``d * d``.  The one-pass form this
    replaces, ``sum(x * x) / n - mean ** 2``, subtracts two numbers of the
    size of ``mean ** 2``: for a column like a year (mean 1997, deviation 6)
    float32 left the deviation 11-13 % off at 40M rows (PERF.md section 6,
    PR 30).  ``x`` holds 0 where a value is absent; a column with a
    non-finite value keeps the first mean and a NaN variance."""
    keep = axis is not None
    m0 = jnp.sum(x, axis=axis, dtype=jnp.float32, keepdims=keep) / (
        nf[:, None] if keep else nf)
    d = jnp.where(present, x - m0, 0.0)
    sd = jnp.sum(d, axis=axis, dtype=jnp.float32)
    sdd = jnp.sum(d * d, axis=axis, dtype=jnp.float32)
    m0 = m0[:, 0] if keep else m0
    mean = jnp.where(jnp.isfinite(m0), m0 + sd / nf, m0)
    var = jnp.maximum(sdd - sd * sd / nf, 0.0) / jnp.maximum(nf - 1.0, 1.0)
    return mean, var


def _batch_rollup_kernel_impl(X, n: int):
    """Rollups for a whole [C, padded] column block in ONE fused pass —
    per-column eager rollups cost a dispatch and a fetch each, which a
    wide frame pays hundreds of times."""
    iota = jax.lax.broadcasted_iota(jnp.int32, X.shape, 1)
    present = (iota < n) & ~jnp.isnan(X)
    x = jnp.where(present, X, 0.0)
    cnt = jnp.sum(present, axis=1)
    nf = jnp.maximum(cnt, 1).astype(jnp.float32)
    mean, var = _moments(x, present, nf, axis=1)
    big = jnp.float32(np.finfo(np.float32).max)
    vmin = jnp.min(jnp.where(present, X, big), axis=1)
    vmax = jnp.max(jnp.where(present, X, -big), axis=1)
    nzero = jnp.sum(present & (X == 0.0), axis=1)
    return cnt, mean, var, vmin, vmax, nzero


_batch_rollup_kernel = _ledger(
    "frame_rollup_batch",
    jax.jit(_batch_rollup_kernel_impl, static_argnames=("n",)),
    static_argnums=(1,), static_argnames=("n",),
    orig=_batch_rollup_kernel_impl)


def _rollup_kernel_impl(data, valid):
    """One fused pass computing all rollup stats for a numeric column."""
    present = valid & ~jnp.isnan(data)
    x = jnp.where(present, data, 0.0)
    n = jnp.sum(present)
    nf = jnp.maximum(n, 1).astype(jnp.float32)
    mean, var = _moments(x, present, nf, axis=None)
    big = jnp.float32(np.finfo(np.float32).max)
    vmin = jnp.min(jnp.where(present, data, big))
    vmax = jnp.max(jnp.where(present, data, -big))
    nzero = jnp.sum(present & (data == 0.0))
    return n, mean, var, vmin, vmax, nzero


_rollup_kernel = _ledger("frame_rollup", jax.jit(_rollup_kernel_impl),
                         orig=_rollup_kernel_impl)


class Vec:
    """One column: device payload (or host payload for str/uuid) + metadata."""

    def __init__(self, data, vtype: str, nrows: int,
                 domain: Optional[Sequence[str]] = None,
                 host_data: Optional[np.ndarray] = None,
                 time_base: float = 0.0):
        self.type = vtype
        self.nrows = int(nrows)
        self.domain = list(domain) if domain is not None else None
        self.host_data = host_data          # str/uuid payload (numpy object)
        self.time_base = time_base          # TIME: ms-since-epoch of code 0
        self._spill = None                  # host copy while evicted from HBM
        self._atime = 0.0                   # LRU clock (shared via aliasing)
        self.data = data                    # padded row-sharded jax.Array
        self._rollups: Optional[RollupStats] = None

    # ------------------------------------------------------------ HBM spill
    # The reference's Cleaner evicts cold chunks from the K/V cache to disk
    # (water/Cleaner.java:12); here the scarce tier is HBM and the spill
    # target is host RAM: spill() fetches the device payload to numpy and
    # drops the jax.Array, and the next .data access transparently places
    # it back onto the row sharding.

    @property
    def data(self):
        self._atime = time.monotonic()
        if self._device is None and self._spill is not None:
            from ..runtime.cluster import cluster, put_sharded
            self._device = put_sharded(self._spill, cluster().row_sharding)
            self._spill = None
        return self._device

    @data.setter
    def data(self, value):
        self._device = value
        self._spill = None

    @property
    def is_spilled(self) -> bool:
        return self._device is None and self._spill is not None

    def spill(self) -> int:
        """Evict the device payload to host RAM; returns bytes freed."""
        if self._device is None:
            return 0
        from ..runtime.cluster import fetch
        freed = int(self._device.nbytes)
        self._spill = np.asarray(fetch(self._device))
        self._device = None
        return freed

    # ------------------------------------------------------------------ ctor
    @staticmethod
    def from_numpy(arr: np.ndarray, vtype: str = T_NUM,
                   domain: Optional[Sequence[str]] = None,
                   time_base: Optional[float] = None) -> "Vec":
        """Build a Vec from host data, padding + sharding onto the mesh.

        TIME input is float64 ms-since-epoch.  The device payload is rebased
        to ``(ms - time_base) / 1000`` seconds in float32 (well-conditioned
        for modeling; ~seconds resolution over year ranges) while the exact
        float64 ms stay host-side for round-trips.
        """
        t_entry = time.perf_counter_ns()
        cl = cluster()
        arr = np.asarray(arr)
        n = len(arr)
        if vtype in (T_STR, T_UUID):
            return Vec(None, vtype, n, host_data=np.asarray(arr, dtype=object))
        from ..runtime import observability as obs
        from ..runtime.cluster import put_sharded
        padded = cl.pad_rows(n)
        host_data = None
        if vtype == T_CAT:
            if arr.dtype == object or arr.dtype.kind in "US":
                labels = list(domain) if domain is not None else \
                    [str(u) for u in np.unique(arr.astype(str))]
                arr = encode_domain(arr, labels)
                domain = labels
            buf = np.full(padded, -1, dtype=np.int32)
            buf[:n] = arr.astype(np.int32)
        elif vtype == T_NUM and takes_exact_int(arr):
            buf = np.full(padded, INT_NA, dtype=np.int32)
            if arr.dtype.kind == "f":
                with np.errstate(invalid="ignore"):
                    buf[:n] = np.where(np.isnan(arr), INT_NA, arr).astype(np.int32)
            else:
                buf[:n] = arr.astype(np.int32)
            obs.inc("vec_exact_int_columns_total")
        else:
            vals = arr.astype(np.float64)
            if vtype == T_TIME:
                host_data = vals
                if time_base is None:
                    finite = vals[np.isfinite(vals)]
                    time_base = float(finite.min()) if len(finite) else 0.0
                vals = (vals - time_base) / 1000.0
            buf = np.full(padded, np.nan, dtype=np.float32)
            buf[:n] = vals.astype(np.float32)
        t_put = time.perf_counter_ns()
        data = put_sharded(buf, cl.row_sharding)
        t_done = time.perf_counter_ns()
        obs.inc("transfer_bytes_total", buf.nbytes, dir="h2d")
        # prepare: the host's numpy from entry to the padded buffer; put: the
        # call that hands the buffer to the runtime (docs/operations.md says
        # how much of the copy that call waits for)
        obs.inc("transfer_seconds_total", (t_put - t_entry) / 1e9,
                dir="h2d", stage="prepare")
        obs.inc("transfer_seconds_total", (t_done - t_put) / 1e9,
                dir="h2d", stage="put")
        return Vec(data, vtype, n, domain=domain, host_data=host_data,
                   time_base=time_base or 0.0)

    # ----------------------------------------------------------------- props
    @property
    def is_numeric(self) -> bool:
        return self.type in (T_NUM, T_TIME)

    @property
    def is_categorical(self) -> bool:
        return self.type == T_CAT

    @property
    def is_exact_int(self) -> bool:
        """Whether this numeric column holds int32 values (``takes_exact_int``)
        and not float32; read off the payload, so there is no second flag
        to drift."""
        payload = self._spill if self._spill is not None else self._device
        return (self.type == T_NUM and payload is not None
                and payload.dtype == np.int32)

    @property
    def cardinality(self) -> int:
        return len(self.domain) if self.domain is not None else -1

    @property
    def padded_len(self) -> int:
        if self._spill is not None:          # serve from host, no restore
            return int(self._spill.shape[0])
        return int(self.data.shape[0]) if self.data is not None else self.nrows

    def valid_mask(self) -> jax.Array:
        """Boolean [padded] mask of real (non-padding) rows."""
        idx = jnp.arange(self.padded_len)
        return idx < self.nrows

    def isna(self) -> jax.Array:
        """Boolean [padded] mask of missing values (padding rows hold the
        payload's NA too): the one place that knows each payload's
        sentinel."""
        if self.data is None:
            raise TypeError(f"Vec of type {self.type} has no device payload")
        if self.type == T_CAT:
            return self.data < 0
        if self.is_exact_int:
            return self.data == INT_NA
        return jnp.isnan(self.data)

    # --------------------------------------------------------------- rollups
    def rollups(self) -> RollupStats:
        """Lazy cached stats — the RollupStats contract (RollupStats.java:19)."""
        if self._rollups is None:
            if self.data is None:
                miss = int(sum(1 for v in self.host_data[: self.nrows] if v is None))
                self._rollups = RollupStats(self.nrows, miss, float("nan"),
                                            float("nan"), float("nan"),
                                            float("nan"), 0)
            elif self.type == T_TIME and self.host_data is not None:
                x = self.host_data[: self.nrows]
                ok = np.isfinite(x)
                n = int(ok.sum())
                self._rollups = RollupStats(
                    nrows=self.nrows, nmissing=self.nrows - n,
                    mean=float(np.mean(x[ok])) if n else float("nan"),
                    sigma=float(np.std(x[ok], ddof=1)) if n > 1 else float("nan"),
                    vmin=float(np.min(x[ok])) if n else float("nan"),
                    vmax=float(np.max(x[ok])) if n else float("nan"),
                    nzero=int((x[ok] == 0).sum()))
            else:
                x = self.numeric_data()
                n, mean, var, vmin, vmax, nzero = _rollup_kernel(x, self.valid_mask())
                n = int(n)
                self._rollups = RollupStats(
                    nrows=self.nrows, nmissing=self.nrows - n,
                    mean=float(mean) if n else float("nan"),
                    sigma=float(np.sqrt(max(float(var), 0.0))) if n > 1 else float("nan"),
                    vmin=float(vmin) if n else float("nan"),
                    vmax=float(vmax) if n else float("nan"),
                    nzero=int(nzero))
        return self._rollups

    def numeric_data(self) -> jax.Array:
        """Payload as float32 with NaN missing (cat codes -1 -> NaN, exact
        integers rounded to float32): what models, rollups and ``DataInfo``
        read."""
        if self.data is None:
            raise TypeError(f"Vec of type {self.type} has no device payload")
        if self.type == T_CAT or self.is_exact_int:
            return jnp.where(self.isna(), jnp.nan, self.data.astype(jnp.float32))
        return self.data

    def values(self) -> jax.Array:
        """The payload as a model's raw-value design reads it: a numeric
        column in float32 whichever form it is held in, a categorical's
        int32 codes (-1 NA) as they are."""
        return self.numeric_data() if self.is_exact_int else self.data

    def mean(self) -> float:
        return self.rollups().mean

    def sigma(self) -> float:
        return self.rollups().sigma

    def min(self) -> float:
        return self.rollups().vmin

    def max(self) -> float:
        return self.rollups().vmax

    def nmissing(self) -> int:
        return self.rollups().nmissing

    # ---------------------------------------------------------------- export
    def to_numpy(self) -> np.ndarray:
        """Materialize the logical (unpadded) column on host.

        TIME returns the exact float64 ms-since-epoch kept host-side, an
        exact-integer column its integers in float64 (NaN for NA).
        """
        if self.type == T_TIME and self.host_data is not None:
            return self.host_data[: self.nrows]
        if self._spill is None and self.data is None:
            return self.host_data[: self.nrows]
        if self._spill is not None:          # serve from host, no restore
            host = self._spill[: self.nrows]
        else:
            from ..runtime.cluster import fetch
            host = fetch(self.data)[: self.nrows]
        return exact_int_host(host) if self.is_exact_int else host

    def canonical_host(self) -> np.ndarray:
        """Engine-independent host form for lineage hashing/replicas:
        num -> float32 (int32 where the column is held exactly), cat ->
        int32 codes (-1 NA), time -> float64
        ms-since-epoch, str/uuid -> object (None NA).  A re-materialized
        shard is correct iff its canonical bytes match the original's."""
        arr = self.to_numpy()
        if self.is_exact_int:                # the payload itself, NA as INT_NA
            return np.where(np.isnan(arr), INT_NA, arr).astype(np.int32)
        if self.type == T_CAT:
            return np.ascontiguousarray(arr, dtype=np.int32)
        if self.type == T_TIME:
            return np.ascontiguousarray(arr, dtype=np.float64)
        if self.type in (T_STR, T_UUID):
            return np.asarray(arr, dtype=object)
        return np.ascontiguousarray(arr, dtype=np.float32)

    def decoded(self) -> np.ndarray:
        """Host column with categorical codes mapped back to labels."""
        arr = self.to_numpy()
        if self.type == T_CAT and self.domain is not None:
            dom = np.asarray(self.domain, dtype=object)
            out = np.empty(len(arr), dtype=object)
            ok = arr >= 0
            out[ok] = dom[arr[ok]]
            out[~ok] = None
            return out
        return arr
