"""Distributed parse: files -> typed, sharded Frame.

Reference: ``water/parser/ParseDataset.java:31,60,133,688`` — a two-phase
parse: (1) ``ParseSetup`` samples raw bytes to guess separator/header/column
types; (2) ``MultiFileParseTask`` (an MRTask) tokenizes each raw chunk on its
home node, writes compressed NewChunks, and merges categorical domains
cluster-wide in the reduce (ParseDataset.java:501-600).

TPU-native redesign: the hot path is a parallel mmap'd pipeline — the file
is mapped (never copied), split at newline-aligned byte ranges, and the
native tokenizer (``native/fastcsv.cpp``) fans the ranges over a bounded
thread pool (ctypes releases the GIL).  As each range's tokenization lands,
its numeric columns start their async device transfer, so ``device_put`` of
early ranges hides tokenization of later ones; text columns take a
vectorized host pass (fixed-width byte gather + ``np.unique``) instead of
per-cell Python.  pandas' C reader and the stdlib tokenizer remain the
strict fallback engines.  Type guessing (phase 1) mirrors ParseSetup:
numeric > time > categorical > string, with a cardinality heuristic for
cat-vs-str.  Categorical domains are unified globally by construction
(single host pass) — the analog of the reference's domain-merge reduce.
"""

from __future__ import annotations

import csv
import io
import os
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from .frame import Frame
from .vec import Vec, T_CAT, T_NUM, T_STR, T_TIME, takes_exact_int
from ..runtime import dkv
from ..runtime import observability as obs

_NA = {"", "na", "n/a", "nan", "null", "none", "?", "-", "NA", "NaN", "NULL", "None"}

# Per-stage wall times of the most recent native-path parse on this process
# (measurement hook + test assertion surface): mmap, scan,
# tokenize, device-dispatch, decode/typing, total.
last_parse_stats: Dict[str, float] = {}


class _DeviceChunks(list):
    """Per-range on-device float32 pieces of one numeric column, in row
    order — produced by the tokenize/transfer overlap, concatenated on
    device at Vec-assembly time."""

# cat-vs-str heuristic: mostly-unique, high-cardinality text is a string column
_STR_UNIQUE_RATIO = 0.95
_STR_MIN_CARD = 100


def _guess_numeric(sample: Sequence[str]) -> bool:
    seen = False
    for s in sample:
        if s in _NA:
            continue
        seen = True
        try:
            float(s)
        except ValueError:
            return False
    return seen


def _parse_time_column(values: np.ndarray):
    """Try to parse an object column as datetimes -> ms since epoch (f64)."""
    try:
        import pandas as pd
        with np.errstate(all="ignore"):
            # dtype=object: pandas 3 would infer its arrow-backed string
            # dtype, and pyarrow's conversion segfaults now and then when it
            # first runs on a REST handler thread (PERF.md section 7)
            dt = pd.to_datetime(pd.Series(values, dtype=object),
                                errors="coerce", format="mixed")
        ok = dt.notna().to_numpy()
        real = np.array([v not in _NA for v in values.astype(str)])
        if real.sum() == 0 or ok[real].mean() < 0.9:
            return None
        # robust to pandas ns/us/ms internal resolution
        ms = dt.to_numpy().astype("datetime64[ms]").astype("int64").astype(np.float64)
        ms[~ok] = np.nan
        return ms
    except Exception:
        return None


def _column_to_vec(values: np.ndarray, name: str,
                   coltype: Optional[str] = None) -> Vec:
    """Type-guess one parsed column and build its Vec (ParseSetup analog)."""
    values = np.asarray(values)
    if values.dtype.kind in "ifb" and coltype in (None, T_NUM):
        return Vec.from_numpy(values, T_NUM)    # uncast: the payload rule reads them
    if values.dtype.kind == "M":  # datetime64 from pandas
        ms = values.astype("datetime64[ms]").astype("int64").astype(np.float64)
        ms[np.isnat(values)] = np.nan
        return Vec.from_numpy(ms, T_TIME)
    svals = values.astype(str)
    na = np.isin(svals, list(_NA))
    if coltype in (None, T_NUM):
        sample = [s for s in svals[~na][:1000]]
        if _guess_numeric(sample):
            out = np.full(len(svals), np.nan, dtype=np.float64)
            ok = ~na
            try:
                out[ok] = svals[ok].astype(np.float64)
                return Vec.from_numpy(out, T_NUM)
            except ValueError:
                pass
    if coltype in (None, T_TIME):
        ms = _parse_time_column(values)
        if ms is not None:
            return Vec.from_numpy(ms, T_TIME)
    nz = svals[~na]
    uniq = np.unique(nz)
    if coltype != T_CAT and (coltype == T_STR or (
            len(uniq) >= _STR_MIN_CARD and
            len(uniq) > _STR_UNIQUE_RATIO * max(len(nz), 1))):
        host = svals.astype(object)
        host[na] = None
        return Vec(None, T_STR, len(host), host_data=host)
    # vectorized factorization: uniq is sorted, so searchsorted IS the
    # code lookup (the per-cell dict loop cost seconds at bench scale)
    codes = np.searchsorted(uniq, svals).astype(np.int32)
    codes[na] = -1
    return Vec.from_numpy(codes, T_CAT, domain=[str(u) for u in uniq])


_GATHER_MAX_WIDTH = 512          # cells wider than this take the slow loop


def _decode_text_column(body, offs: np.ndarray, j: int) -> np.ndarray:
    """Decode one column's raw cell bytes (native tokenizer offsets) to
    Python strings, applying RFC-4180 quote unescaping.

    Vectorized: the native fixed-width gather packs the cells into an
    ``|S width|`` column decoded in one ``np.char.decode`` call; only
    cells holding escaped quotes (or trailing NUL bytes, which the S
    dtype cannot represent) fall back to per-cell handling.  ``body``
    may be bytes or a zero-copy uint8 view (mmap).
    """
    from .. import native
    nrows = len(offs)
    starts = offs[:, j, 0]
    ends = offs[:, j, 1]
    width = int((ends - starts).max()) if nrows else 0
    if 0 < width <= _GATHER_MAX_WIDTH:
        fixed = native.gather_cells(body, starts, ends, width)
        if fixed is not None:
            col = np.char.decode(fixed, "utf-8", "replace").astype(object)
            redo = np.char.find(fixed, b'""') >= 0
            # trailing NULs vanish under the S dtype: re-decode those too
            redo |= np.char.str_len(fixed) != np.minimum(
                np.maximum(ends - starts, 0), width)
            if redo.any():
                view = memoryview(body)
                for i in np.flatnonzero(redo):
                    cell = bytes(view[starts[i]:ends[i]]) \
                        .decode(errors="replace")
                    col[i] = cell.replace('""', '"')
            return col
    view = memoryview(body) if not isinstance(body, bytes) else body
    col = np.empty(nrows, dtype=object)
    for i in range(nrows):
        s, e = offs[i, j]
        cell = bytes(view[s:e]).decode(errors="replace")
        if '""' in cell:
            cell = cell.replace('""', '"')
        col[i] = cell
    return col


def _pandas_safe() -> bool:
    """pandas 3.x's pyarrow-backed string arrays segfault when first
    constructed on a non-main thread in a jax-initialized process (this
    image; reproduced via REST-handler-thread read_csv).  The pandas
    reader is therefore main-thread-only; handler threads use the native
    tokenizer or the stdlib fallback."""
    import threading
    return threading.current_thread() is threading.main_thread()


def _parse_csv_native(path_or_buf, header, sep, col_names,
                      col_types: Optional[Dict[str, str]] = None,
                      overlap_device: bool = True,
                      on_range=None):
    """Native tokenizer path — the parallel mmap'd pipeline.

    Paths are mmap'd (no full-file ``read()`` copy); buffers/streams get a
    zero-copy uint8 view.  Newline-aligned byte ranges tokenize in
    parallel (``native.parse_view``); as each range completes, its
    pure-numeric columns are dispatched to the device as float32 chunks,
    overlapping transfer of early ranges with tokenization of later ones.
    Text-flagged columns fall out as vectorized host decodes.

    Returns (names, cols) — ``cols`` values are numpy arrays or
    ``_DeviceChunks`` (already on device, row order) — or None when the
    native library is unavailable or the input doesn't fit its fast path.
    """
    from .. import native
    if native.load() is None:
        return None
    sepc = sep if sep is not None else ","
    if len(sepc) != 1:
        return None
    col_types = col_types or {}
    stats: Dict[str, float] = {}
    t_all = time.perf_counter()
    mm = None
    if isinstance(path_or_buf, str):
        import mmap as _mmap
        t0 = time.perf_counter()
        with open(path_or_buf, "rb") as f:
            try:
                mm = _mmap.mmap(f.fileno(), 0, access=_mmap.ACCESS_READ)
            except ValueError:           # empty file: defer to fallbacks
                return None
        view = np.frombuffer(mm, np.uint8)
        first_nl = mm.find(b"\n")
        stats["mmap_s"] = round(time.perf_counter() - t0, 4)
    else:
        data = path_or_buf if isinstance(path_or_buf, bytes) else None
        if data is None:
            data = path_or_buf.read()
            if isinstance(data, str):
                data = data.encode()
        if not len(data):
            return None
        view = np.frombuffer(data, np.uint8)
        first_nl = data.find(b"\n")
    first = bytes(view[: first_nl if first_nl >= 0 else len(view)]) \
        .decode(errors="replace")
    head_cells = [c.strip().strip('"') for c in first.split(sepc)]
    has_header = (not _guess_numeric(head_cells)) if header is None \
        else bool(header)
    body = view[first_nl + 1:] if has_header and first_nl >= 0 else view
    if not len(body):
        return None
    ncols = native.ncols_of(body, sepc)
    if not ncols:
        return None
    if col_names:                        # explicit names override a header
        names = list(col_names)
    elif has_header:
        names = head_cells
    else:
        names = [f"C{i+1}" for i in range(ncols)]
    if len(names) != ncols:
        return None

    # tokenize -> device-transfer overlap: numeric columns of each
    # completed range start their (async) placement while later ranges
    # are still tokenizing on the pool
    dev_chunks: List[Optional[list]] = [
        [] if (overlap_device and col_types.get(nm) in (None, T_NUM))
        else None
        for nm in names]
    dev_time = [0.0]
    consumer = on_range                   # external per-range hook, if any

    def _on_range(row_lo, nrows, Vt, Ft):
        from ..runtime import failure
        failure.maybe_inject("parse_range")
        if consumer is not None:
            consumer(row_lo, nrows, Vt, Ft)
        if not overlap_device:
            return
        t0 = time.perf_counter()
        try:
            import jax.numpy as jnp
        except Exception:
            for j in range(ncols):
                dev_chunks[j] = None
            return
        for j in range(ncols):
            if dev_chunks[j] is None:
                continue
            # text seen, or whole numbers float32 would round (the payload
            # rule reads a column whole): the column is host-bound
            if Ft[:, j].any() or takes_exact_int(Vt[:, j]):
                dev_chunks[j] = None
                continue
            dev_chunks[j].append(
                (row_lo, jnp.asarray(np.asarray(Vt[:, j], np.float32))))
        dev_time[0] += time.perf_counter() - t0

    # the range hook is wired unconditionally: overlap_device only gates
    # the device-chunk dispatch INSIDE it, so external consumers (the
    # streaming ingest plane, lineage stamping) see every landed range
    # regardless of the device-overlap setting
    out = native.parse_view(body, sepc, ncols=ncols,
                            on_range=_on_range, stats=stats)
    if out is None:
        return None
    vals, flags, offs, consumed = out
    if consumed != len(body):
        return None              # unterminated quote etc.: defer to pandas
    nrows = len(vals)
    t0 = time.perf_counter()
    cols = {}
    for j, name in enumerate(names):
        chunks = dev_chunks[j]
        if chunks is not None and nrows and \
                sum(int(c.shape[0]) for _, c in chunks) == nrows:
            cols[name] = _DeviceChunks(
                c for _, c in sorted(chunks, key=lambda rc: rc[0]))
        elif flags[:, j].any():
            # numeric cells keep their text form for uniform type guessing
            cols[name] = _decode_text_column(body, offs, j)
        else:
            cols[name] = vals[:, j]
    stats["device_s"] = round(dev_time[0], 4)
    stats["decode_s"] = round(time.perf_counter() - t0, 4)
    stats["native_total_s"] = round(time.perf_counter() - t_all, 4)
    stats["rows"] = nrows
    stats["bytes"] = int(len(view))
    last_parse_stats.clear()
    last_parse_stats.update(stats)
    return names, cols


def parse_csv(path_or_buf, destination_frame: Optional[str] = None,
              header: Optional[bool] = None, sep: Optional[str] = None,
              col_types: Optional[Dict[str, str]] = None,
              col_names: Optional[List[str]] = None,
              on_range=None) -> Frame:
    """Parse a CSV file/buffer into a sharded Frame (ParseDataset.parse).

    Tokenization order: the native C++ fast path (numeric cells never
    become Python objects), then pandas' reader, then the stdlib fallback.

    ``on_range(row_lo, nrows, vals, flags)`` fires per newline-aligned
    byte range as the native tokenizer lands it (completion order, pool
    threads) — the streaming-ingest overlap seam.  Fallback engines parse
    whole-file and never fire it.
    """
    col_types = col_types or {}
    last_parse_stats.clear()             # fallbacks leave no stale stats
    # read streams ONCE up front so the native attempt cannot exhaust a
    # non-seekable input before a fallback runs; paths are mmap'd inside
    # the native pipeline (no full-file copy)
    source = path_or_buf
    raw: Optional[bytes] = None
    if isinstance(path_or_buf, bytes):
        raw = source = path_or_buf
    elif not isinstance(path_or_buf, str):
        raw = path_or_buf.read()
        if isinstance(raw, str):
            raw = raw.encode()
        source = raw
    names = cols = None
    try:
        parsed = _parse_csv_native(source, header, sep, col_names,
                                   col_types=col_types, on_range=on_range)
        if parsed is not None:
            names, cols = parsed
    except Exception:
        names = cols = None
    if names is None:
        pd_src = io.BytesIO(raw) if raw is not None else path_or_buf
        eff_header = header
        if header is None:
            # same first-line guess the native path (and stdlib fallback)
            # use, so parse results don't depend on which engine ran
            if raw is not None:
                first = raw.split(b"\n", 1)[0].decode(errors="replace")
            else:
                with open(path_or_buf, "r", errors="replace") as fh_:
                    first = fh_.readline()
            sepc = sep if sep is not None else ","
            cells = [c.strip().strip('"') for c in first.strip().split(sepc)]
            eff_header = not _guess_numeric(cells)
        use_pandas = _pandas_safe()
        if use_pandas:
            try:
                import pandas as pd
                df = pd.read_csv(
                    pd_src, sep=sep if sep is not None else ",",
                    header=0 if eff_header else None,
                    na_values=sorted(_NA), keep_default_na=True, engine="c",
                    low_memory=False)
                if col_names:
                    df.columns = col_names
                names = [str(c) for c in df.columns]
                cols = {n: df[n].to_numpy() for n in names}
            except ImportError:
                use_pandas = False
        if not use_pandas:
            sd = io.StringIO(raw.decode(errors="replace")) \
                if raw is not None else path_or_buf
            names, cols = _parse_csv_stdlib(sd, header, sep, col_names)
    t0 = time.perf_counter()
    first = cols[names[0]] if names else ()
    rows = sum(len(piece) for piece in first) \
        if isinstance(first, _DeviceChunks) else len(first)
    with obs.span("frame.upload", rows=rows, cols=len(names)):
        vecs = [_assemble_vec(cols[n], n, col_types.get(n)) for n in names]
    if last_parse_stats:
        last_parse_stats["vec_s"] = round(time.perf_counter() - t0, 4)
        obs.record("parse", **last_parse_stats)
    key = destination_frame or dkv.make_key(
        os.path.basename(str(path_or_buf)) if isinstance(path_or_buf, str)
        else "frame")
    fr = Frame(names, vecs, key=key)
    if isinstance(path_or_buf, str):
        from . import lineage
        lineage.record_parse(fr, path_or_buf, header=header, sep=sep,
                             col_types=col_types, col_names=col_names)
    return fr


def _assemble_vec(col, name: str, coltype: Optional[str]) -> Vec:
    """Vec from one parsed column: device chunks concatenate in place
    (their transfer already overlapped tokenization); host arrays go
    through the type guesser."""
    if isinstance(col, _DeviceChunks):
        import jax.numpy as jnp
        data = jnp.concatenate(list(col)) if len(col) > 1 else col[0]
        return _device_numeric_vec(data)
    return _column_to_vec(col, name, coltype)


def _parse_csv_stdlib(path_or_buf, header, sep, col_names):
    """Dependency-free fallback tokenizer (CsvParser analog)."""
    if isinstance(path_or_buf, str):
        fh = open(path_or_buf, "r", newline="")
    else:
        fh = path_or_buf
    try:
        sample = fh.read(64 * 1024)
        fh.seek(0)
        try:
            dialect = csv.Sniffer().sniff(sample, delimiters=sep or ",;\t| ")
        except csv.Error:  # e.g. single-column files
            class dialect(csv.excel):
                delimiter = sep or ","
        rows = list(csv.reader(fh, dialect))
    finally:
        if isinstance(path_or_buf, str):
            fh.close()
    if not rows:
        raise ValueError("empty file")
    if header is None:
        header = not _guess_numeric(rows[0])
    if header:
        names, rows = [str(c) for c in rows[0]], rows[1:]
    else:
        names = col_names or [f"C{i+1}" for i in range(len(rows[0]))]
    ncol = len(names)
    cols = {n: np.array([r[i] if i < len(r) else "" for r in rows], dtype=object)
            for i, n in enumerate(names) if i < ncol}
    return names, cols


def _open_decompressed(uri: str) -> io.TextIOBase:
    """Open a (possibly remote, possibly compressed) source as text.

    Compression by extension — gzip/zip/bz2/xz; zip reads the first entry
    (ZipUtil.java behavior).  Remote schemes route through the Persist SPI.
    """
    from .. import persist
    raw = persist.open_read(uri)
    base = uri.lower()
    if base.endswith(".gz"):
        import gzip
        return io.TextIOWrapper(gzip.GzipFile(fileobj=raw), newline="")
    if base.endswith(".zip"):
        import zipfile
        zf = zipfile.ZipFile(raw)
        names = [n for n in zf.namelist() if not n.endswith("/")]
        if not names:
            raise ValueError(f"{uri}: empty zip archive")
        return io.TextIOWrapper(zf.open(names[0]), newline="")
    if base.endswith(".bz2"):
        import bz2
        return io.TextIOWrapper(bz2.BZ2File(raw), newline="")
    if base.endswith(".xz"):
        import lzma
        return io.TextIOWrapper(lzma.LZMAFile(raw), newline="")
    return io.TextIOWrapper(raw, newline="")


def _expand_paths(path) -> List[str]:
    """Expand a path / glob / directory / URI / list into source URIs."""
    from .. import persist
    paths = path if isinstance(path, (list, tuple)) else [path]
    out: List[str] = []
    for p in paths:
        matches = persist.list_uris(p)
        if matches:
            out.extend(matches)
        elif persist.exists(p):
            out.append(p)
        else:
            raise FileNotFoundError(p)
    return out


def parse_files(paths: Sequence[str],
                destination_frame: Optional[str] = None,
                header: Optional[bool] = None, sep: Optional[str] = None,
                col_types: Optional[Dict[str, str]] = None,
                col_names: Optional[List[str]] = None,
                chunksize: int = 1_000_000) -> Frame:
    """Parse many CSV shards into ONE Frame — MultiFileParseTask analog.

    Local uncompressed shards take the same ranged-parallel mmap pipeline
    as ``parse_csv`` (``_parse_csv_native``): numeric columns arrive as
    on-device chunks whose transfer overlapped tokenization.  Remote or
    compressed shards stream through pandas in ``chunksize``-row chunks.
    Numeric chunks are ``device_put`` immediately and the host copy
    dropped, so host RSS stays bounded (the reference keeps raw chunks in
    the DKV and parses in place — ParseDataset.java:688).
    Text/categorical columns accumulate host-side: their global domain
    must be built before codes exist, mirroring the reference's
    cluster-wide categorical domain merge (ParseDataset.java:501-600).
    """
    import jax.numpy as jnp
    col_types = col_types or {}
    try:
        import pandas as pd
    except ImportError:
        pd = None
    dev_chunks: Dict[str, list] = {}
    host_chunks: Dict[str, list] = {}
    names: Optional[List[str]] = None

    def eat(df_names, df_cols):
        nonlocal names
        if names is None:
            names = list(df_names)
            for n in names:
                dev_chunks[n] = []
                host_chunks[n] = []
        elif list(df_names) != names:
            raise ValueError(
                f"shard schema mismatch: {df_names} vs {names}")
        for n in names:
            raw_col = df_cols[n]
            if isinstance(raw_col, _DeviceChunks):
                # ranged native pipeline already placed these on device
                if host_chunks[n]:     # column went host in an earlier shard
                    host_chunks[n].extend(np.asarray(c) for c in raw_col)
                else:
                    dev_chunks[n].extend(raw_col)
                continue
            arr = np.asarray(raw_col)
            want = col_types.get(n)
            if arr.dtype.kind in "if" and want in (None, T_NUM) \
                    and not host_chunks[n] and not takes_exact_int(arr):
                dev_chunks[n].append(jnp.asarray(arr, jnp.float32))
            else:
                if dev_chunks[n]:      # late type widening: pull back
                    host_chunks[n] = [np.asarray(c) for c in dev_chunks[n]]
                    dev_chunks[n] = []
                host_chunks[n].append(arr)

    def _ranged_ok(uri: str) -> bool:
        return "://" not in uri and not uri.lower().endswith(
            (".gz", ".zip", ".bz2", ".xz"))

    for uri in paths:
        if _ranged_ok(uri):
            # pandas treats header=None as "every shard has a header":
            # mirror that so engine choice can't change the result
            parsed = None
            try:
                parsed = _parse_csv_native(
                    uri, header in (None, True), sep, col_names,
                    col_types=col_types)
            except Exception:
                parsed = None
            if parsed is not None:
                eat(*parsed)
                continue
        fh = _open_decompressed(uri)
        if pd is not None:
            reader = pd.read_csv(
                fh, sep=sep if sep is not None else ",",
                header=0 if header in (None, True) else None,
                na_values=sorted(_NA), keep_default_na=True, engine="c",
                chunksize=chunksize)
            for df in reader:
                if col_names:
                    df.columns = col_names
                eat([str(c) for c in df.columns],
                    {str(c): df[c].to_numpy() for c in df.columns})
        else:
            snames, scols = _parse_csv_stdlib(fh, header, sep, col_names)
            eat(snames, scols)
        fh.close()
    if names is None:
        raise ValueError("no data parsed")
    vecs = []
    for n in names:
        if dev_chunks[n]:
            data = jnp.concatenate(dev_chunks[n]) if len(dev_chunks[n]) > 1 \
                else dev_chunks[n][0]
            vecs.append(_device_numeric_vec(data))
        else:
            col = np.concatenate(host_chunks[n]) if len(host_chunks[n]) > 1 \
                else host_chunks[n][0]
            vecs.append(_column_to_vec(col, n, col_types.get(n)))
    key = destination_frame or dkv.make_key(
        os.path.basename(str(paths[0])) or "frame")
    return Frame(names, vecs, key=key)


def _device_numeric_vec(data) -> Vec:
    """Vec from an already-on-device f32 column (pads + row-shards)."""
    import jax.numpy as jnp
    from ..runtime.cluster import cluster, put_sharded
    cl = cluster()
    n = int(data.shape[0])
    padded = cl.pad_rows(n)
    if padded > n:
        data = jnp.concatenate(
            [data, jnp.full(padded - n, jnp.nan, jnp.float32)])
    return Vec(put_sharded(data, cl.row_sharding), T_NUM, n)


def parse_svmlight(path: str,
                   destination_frame: Optional[str] = None) -> Frame:
    """SVMLight sparse format -> dense Frame (parser/SVMLightParser analog).

    Lines: ``<target> <idx>:<val> ...`` (1-based indices per the format).
    """
    targets, rows, max_idx = [], [], 0
    fh = _open_decompressed(path)
    for line in fh:
        line = line.split("#", 1)[0].strip()
        if not line:
            continue
        parts = line.split()
        targets.append(float(parts[0]))
        pairs = []
        for tok in parts[1:]:
            i, _, v = tok.partition(":")
            idx = int(i)
            pairs.append((idx, float(v)))
            max_idx = max(max_idx, idx)
        rows.append(pairs)
    fh.close()
    # index base detection: the format spec is 1-based, but 0-based files
    # are common (sklearn dump_svmlight_file defaults to zero_based=True)
    min_idx = min((i for pairs in rows for i, _ in pairs), default=1)
    base = 0 if min_idx == 0 else 1
    n, d = len(rows), max_idx + 1 - base
    X = np.zeros((n, d), np.float32)
    for r, pairs in enumerate(rows):
        for idx, v in pairs:
            X[r, idx - base] = v
    names = ["target"] + [f"C{j+1}" for j in range(d)]
    vecs = [Vec.from_numpy(np.asarray(targets, np.float64), T_NUM)]
    vecs += [Vec.from_numpy(X[:, j], T_NUM) for j in range(d)]
    return Frame(names, vecs, key=destination_frame or dkv.make_key("svm"))


def parse_arff(path: str, destination_frame: Optional[str] = None) -> Frame:
    """ARFF -> Frame (parser/ARFFParser analog): @attribute-driven types."""
    names, types, domains = [], [], []
    data_lines = []
    in_data = False
    fh = _open_decompressed(path)
    for line in fh:
        s = line.strip()
        if not s or s.startswith("%"):
            continue
        low = s.lower()
        if in_data:
            data_lines.append(s)
        elif low.startswith("@attribute"):
            rest = s.split(None, 1)[1]
            if rest.startswith('"') or rest.startswith("'"):
                q = rest[0]
                name = rest[1:rest.index(q, 1)]
                spec = rest[rest.index(q, 1) + 1:].strip()
            else:
                name, _, spec = rest.partition(" ")
                spec = spec.strip()
            names.append(name)
            if spec.startswith("{"):
                types.append(T_CAT)
                domains.append([v.strip().strip("'\"")
                                for v in spec.strip("{}").split(",")])
            elif spec.lower() in ("numeric", "real", "integer"):
                types.append(T_NUM)
                domains.append(None)
            elif spec.lower().startswith("date"):
                types.append(T_TIME)
                domains.append(None)
            else:
                types.append(T_STR)
                domains.append(None)
        elif low.startswith("@data"):
            in_data = True
    fh.close()
    rows = list(csv.reader(data_lines))
    cols = {}
    for i, n in enumerate(names):
        cols[n] = np.array([r[i].strip() if i < len(r) else ""
                            for r in rows], dtype=object)
    vecs = []
    for n, t, dom in zip(names, types, domains):
        if t == T_CAT:
            lookup = {s: i for i, s in enumerate(dom)}
            codes = np.array([lookup.get(v, -1) for v in cols[n]], np.int32)
            vecs.append(Vec.from_numpy(codes, T_CAT, domain=dom))
        elif t == T_NUM:
            vals = np.array([np.nan if v in _NA else float(v)
                             for v in cols[n]], np.float64)
            vecs.append(Vec.from_numpy(vals, T_NUM))
        else:
            vecs.append(_column_to_vec(cols[n], n, t))
    return Frame(names, vecs, key=destination_frame or dkv.make_key("arff"))


def arrow_table_to_vecs(table):
    """Arrow table -> (names, vecs) under the standard type mapping:
    numerics -> T_NUM, dictionary/string -> categorical/string via the
    standard guesser, timestamps -> T_TIME (ms since epoch).  Shared by
    ``parse_arrow``, the streaming row-group path, and the parquet
    re-materialization branch in ``runtime/remat.py`` so all three land
    bitwise-identical columns."""
    import pyarrow as pa
    names, vecs = [], []
    for col_name in table.column_names:
        col = table.column(col_name)
        pa_type = col.type
        names.append(str(col_name))
        if pa.types.is_timestamp(pa_type) or pa.types.is_date(pa_type):
            ms = col.cast(pa.timestamp("ms")).to_numpy(
                zero_copy_only=False).astype("datetime64[ms]") \
                .astype("int64").astype(np.float64)
            nulls = col.is_null().to_numpy(zero_copy_only=False)
            ms[nulls] = np.nan
            vecs.append(Vec.from_numpy(ms, T_TIME))
        elif pa.types.is_floating(pa_type) or pa.types.is_integer(pa_type) \
                or pa.types.is_boolean(pa_type):
            arr = col.cast(pa.float64()).to_numpy(zero_copy_only=False)
            vecs.append(Vec.from_numpy(arr, T_NUM))
        else:
            arr = np.asarray(col.to_pylist(), dtype=object)
            arr = np.asarray(["" if v is None else str(v) for v in arr],
                             dtype=object)
            vecs.append(_column_to_vec(arr, str(col_name)))
    return names, vecs


def read_parquet_groups(raw, on_group=None):
    """Ranged parquet read: one ``read_row_group`` per group instead of a
    whole-table ``read_table``.  ``on_group(group_no, row_lo, table)``
    fires as each group lands — the columnar streaming seam, mirroring
    the CSV ``on_range`` hook (same ``parse_group`` fault-injection
    point).  Returns the concatenated table, bitwise equal to a
    whole-table read."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    pf = pq.ParquetFile(raw)
    if pf.metadata.num_row_groups == 0:
        return pf.read()
    from ..runtime import failure
    parts, row_lo = [], 0
    for gi in range(pf.metadata.num_row_groups):
        tbl = pf.read_row_group(gi)
        failure.maybe_inject("parse_group")
        if on_group is not None:
            on_group(gi, row_lo, tbl)
        row_lo += tbl.num_rows
        parts.append(tbl)
    return pa.concat_tables(parts)


def parse_arrow(path: str, fmt: str,
                destination_frame: Optional[str] = None,
                on_group=None) -> Frame:
    """Columnar formats via pyarrow — the h2o-parsers/{parquet,orc} analog.

    ``fmt``: parquet | orc | feather.  Parquet reads row group by row
    group (``read_parquet_groups``), firing ``on_group`` per landed group
    and stamping a row-group-granularity lineage record so parquet frames
    re-materialize partially after a host loss, exactly like CSV parses.
    """
    from .. import persist
    raw = persist.open_read(path)
    if fmt == "parquet":
        table = read_parquet_groups(raw, on_group=on_group)
    elif fmt == "orc":
        import pyarrow.orc as porc
        table = porc.ORCFile(raw).read()
    elif fmt == "feather":
        import pyarrow.feather as pf
        table = pf.read_table(raw)
    else:
        raise ValueError(f"unknown arrow format {fmt!r}")
    names, vecs = arrow_table_to_vecs(table)
    # register only when a destination was requested: multi-file imports
    # build unregistered shards and register just the rbind result
    fr = Frame(names, vecs, key=destination_frame)
    if fmt == "parquet" and destination_frame:
        from . import lineage
        lineage.record_parse_columnar(fr, path)
    return fr


def import_file(path, destination_frame: Optional[str] = None,
                **kw) -> Frame:
    """h2o.import_file analog — see ``_import_file_impl``.  The returned
    frame carries ``source_uri`` provenance so the recovery journal can
    re-import it after a coordinator restart (Recovery.java:72 contract)."""
    fr = _import_file_impl(path, destination_frame=destination_frame, **kw)
    fr.source_uri = path if isinstance(path, str) else list(path)
    return fr


def _import_file_impl(path, destination_frame: Optional[str] = None,
                      **kw) -> Frame:
    """h2o.import_file analog (h2o-py/h2o/h2o.py import_file -> /3/Parse).

    Accepts a single path, a glob pattern, a directory, a list of paths, or
    a persist URI (``gcs://…``, ``file://…``); gzip/zip/bz2/xz shards
    decompress transparently; ``.svm``/``.svmlight``, ``.arff``,
    ``.parquet``, ``.orc``, ``.feather``, ``.avro``, ``.xlsx`` and legacy
    ``.xls`` route to format parsers.
    """
    paths = _expand_paths(path)
    low = paths[0].lower()
    for ext, fn in ((".svm", parse_svmlight), (".svmlight", parse_svmlight),
                    (".arff", parse_arff)):
        if low.endswith(ext) or low.endswith(ext + ".gz"):
            if len(paths) > 1:
                raise ValueError(f"multi-file {ext} import not supported")
            return fn(paths[0], destination_frame=destination_frame)
    for ext, fmt in ((".parquet", "parquet"), (".pq", "parquet"),
                     (".orc", "orc"), (".feather", "feather")):
        if low.endswith(ext):
            if len(paths) == 1:
                return parse_arrow(
                    paths[0], fmt,
                    destination_frame=destination_frame
                    or dkv.make_key(fmt))
            from ..rapids.ops import rbind
            frames = [parse_arrow(p2, fmt) for p2 in paths]
            out = rbind(*frames)
            out.key = destination_frame or dkv.make_key(fmt)
            dkv.put(out.key, out)
            return out
    fmt_parsers = {}
    from .avro import parse_avro
    from .xls import parse_xls, parse_xlsx
    fmt_parsers[".avro"] = parse_avro
    fmt_parsers[".xlsx"] = parse_xlsx
    fmt_parsers[".xls"] = parse_xls
    for ext, fn in fmt_parsers.items():
        if low.endswith(ext):
            if len(paths) == 1:
                return fn(paths[0], destination_frame=destination_frame)
            from ..rapids.ops import rbind
            out = rbind(*[fn(p2) for p2 in paths])
            out.key = destination_frame or dkv.make_key(ext.strip("."))
            dkv.put(out.key, out)
            return out
    import jax

    def _rangeable(p: str) -> bool:
        """Byte-range-capable source: local files and the cloud persist
        backends with real range reads (GCS/S3/HDFS/file)."""
        if p.lower().endswith((".gz", ".zip", ".bz2", ".xz")):
            return False
        scheme = p.split("://", 1)[0] if "://" in p else ""
        return scheme in ("", "file", "gs", "gcs", "s3", "hdfs")

    if jax.process_count() > 1 and all(_rangeable(p) for p in paths):
        # pod-scale ingest: tokenize on the hosts that own the byte ranges
        # (MultiFileParseTask analog) instead of replicating the full
        # tokenization on every process
        from .dparse import parse_files_distributed
        return parse_files_distributed(
            paths, destination_frame=destination_frame, **kw)
    if len(paths) == 1 and "://" not in paths[0] \
            and not any(paths[0].lower().endswith(e)
                        for e in (".gz", ".zip", ".bz2", ".xz")):
        return parse_csv(paths[0], destination_frame=destination_frame, **kw)
    return parse_files(paths, destination_frame=destination_frame, **kw)


def export_file(frame: Frame, uri: str, header: bool = True) -> str:
    """Write a Frame to any persist URI — h2o.export_file analog.

    Format by extension: ``.parquet``/``.feather`` via pyarrow, else CSV.
    """
    from .. import persist
    low = uri.lower()
    if low.endswith((".parquet", ".pq", ".feather")):
        import pyarrow as pa
        cols = {}
        for n, v in zip(frame.names, frame.vecs):
            col = v.decoded()
            if v.type == T_TIME:
                cols[n] = np.asarray(col, "float64").astype("datetime64[ms]")
            else:
                cols[n] = col
        table = pa.table(cols)
        fh = persist.open_write(uri)
        if low.endswith(".feather"):
            import pyarrow.feather as pf
            pf.write_feather(table, fh)
        else:
            import pyarrow.parquet as pq
            pq.write_table(table, fh)
        fh.close()
        return uri
    cols = [v.decoded() for v in frame.vecs]
    fh = persist.open_write(uri)
    out = io.TextIOWrapper(fh, newline="")
    wr = csv.writer(out)
    if header:
        wr.writerow(frame.names)
    for i in range(frame.nrows):
        wr.writerow(["" if (c[i] is None or (isinstance(c[i], float)
                                             and np.isnan(c[i]))) else c[i]
                     for c in cols])
    out.flush()
    out.close()
    return uri


def upload_string(text: str, **kw) -> Frame:
    return parse_csv(io.StringIO(text), **kw)


def from_pandas(df, destination_frame: Optional[str] = None) -> Frame:
    """Build a Frame from a pandas DataFrame — the h2o.H2OFrame(df) path.

    dtype mapping: numeric/bool -> num (bool as 0/1), datetime64 ->
    time, pandas categorical -> cat preserving the category order,
    object/string -> the parser's type guesser (_column_to_vec), so
    mixed string columns come out num/time/cat/str exactly like a CSV
    import of the same data.
    """
    import pandas as pd
    names, vecs = [], []
    for c in df.columns:
        s = df[c]
        name = str(c)
        if isinstance(s.dtype, pd.CategoricalDtype):
            domain = [str(v) for v in s.cat.categories]
            # pandas already stores int codes with -1 = NA: pass through
            vec = Vec.from_numpy(s.cat.codes.to_numpy(np.int32), T_CAT,
                                 domain=domain)
        elif s.dtype.kind == "b":
            vec = Vec.from_numpy(
                s.to_numpy(dtype=np.float64, na_value=np.nan), T_NUM)
        elif s.dtype.kind in "iuf":
            vec = Vec.from_numpy(s.to_numpy(dtype=np.float64,
                                            na_value=np.nan), T_NUM)
        elif s.dtype.kind == "M":
            vec = _column_to_vec(s.to_numpy(), name)
        else:
            vals = np.asarray(["" if v is None or v is pd.NA else v
                               for v in s.to_numpy()], dtype=object)
            vec = _column_to_vec(vals, name)
        names.append(name)
        vecs.append(vec)
    return Frame(names, vecs,
                 key=destination_frame or dkv.make_key("pandas"))


def H2OFrame(python_obj, destination_frame: Optional[str] = None) -> Frame:
    """h2o.H2OFrame constructor analog: accepts a pandas DataFrame, a
    dict of columns, a list of rows (first row = header if strings),
    or a 2-D numpy array."""
    try:
        import pandas as pd
        if isinstance(python_obj, pd.DataFrame):
            return from_pandas(python_obj, destination_frame)
    except ImportError:
        pass
    if isinstance(python_obj, dict):
        names, vecs = [], []
        for k, v in python_obj.items():
            arr = np.asarray(v)
            if arr.dtype == object:
                arr = np.asarray(["" if x is None else x for x in arr],
                                 dtype=object)
            names.append(str(k))
            vecs.append(_column_to_vec(arr, str(k)))
        return Frame(names, vecs,
                     key=destination_frame or dkv.make_key("pyobj"))
    arr = np.asarray(python_obj, dtype=object)
    one_d = arr.ndim == 1
    if one_d:
        arr = arr[:, None]
    # header heuristic only for 2-D input: a 1-D list is pure data
    if not one_d and arr.shape[0] and             all(isinstance(v, str) for v in arr[0]):
        header, body = [str(v) for v in arr[0]], arr[1:]
    else:
        header, body = [f"C{j + 1}" for j in range(arr.shape[1])], arr
    names, vecs = [], []
    for j, name in enumerate(header):
        vals = np.asarray(["" if v is None else v for v in body[:, j]],
                          dtype=object)
        names.append(name)
        vecs.append(_column_to_vec(vals, name))
    return Frame(names, vecs,
                 key=destination_frame or dkv.make_key("pyobj"))
