"""Parse + Persist tests: multi-file globs, compression, SVMLight/ARFF,
persist URIs (mock GCS root), frame/model import-export round trips.

Mirrors the reference's parser pyunits (h2o-py/tests/testdir_parser) and
the PersistGcs fake-server tests.
"""

import gzip
import os
import zipfile

import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu import Frame, import_file, export_file


@pytest.fixture()
def shards(tmp_path):
    """Three gz CSV shards of one logical dataset."""
    paths = []
    rng = np.random.default_rng(0)
    for i in range(3):
        rows = ["x,y,g"]
        for r in range(100):
            rows.append(f"{rng.normal():.6f},{i * 100 + r},{'ab'[r % 2]}")
        p = tmp_path / f"shard{i}.csv.gz"
        with gzip.open(p, "wt") as f:
            f.write("\n".join(rows))
        paths.append(str(p))
    return paths


def test_multifile_glob_import(cl, shards, tmp_path):
    fr = import_file(str(tmp_path / "shard*.csv.gz"))
    assert fr.shape == (300, 3)
    assert fr.types() == {"x": "num", "y": "num", "g": "cat"}
    y = np.sort(fr.vec("y").to_numpy())
    np.testing.assert_array_equal(y, np.arange(300.0))


def test_import_directory(cl, shards, tmp_path):
    fr = import_file(str(tmp_path))
    assert fr.nrows == 300


def test_import_list_and_chunked(cl, shards):
    fr = h2o3_tpu.parse_files(shards, chunksize=37)
    assert fr.nrows == 300
    assert fr.vec("x").data is not None      # numeric stayed on device


def test_zip_import(cl, tmp_path):
    p = tmp_path / "data.zip"
    with zipfile.ZipFile(p, "w") as z:
        z.writestr("inner.csv", "a,b\n1,2\n3,4\n")
    fr = import_file(str(p))
    assert fr.shape == (2, 2)
    np.testing.assert_array_equal(fr.vec("a").to_numpy(), [1.0, 3.0])


def test_svmlight(cl, tmp_path):
    p = tmp_path / "d.svm"
    p.write_text("1 1:0.5 3:2.0\n-1 2:1.5 # comment\n")
    fr = import_file(str(p))
    assert fr.names == ["target", "C1", "C2", "C3"]
    np.testing.assert_array_equal(fr.vec("target").to_numpy(), [1.0, -1.0])
    np.testing.assert_array_equal(fr.vec("C3").to_numpy(), [2.0, 0.0])


def test_arff(cl, tmp_path):
    p = tmp_path / "d.arff"
    p.write_text("""% comment
@relation test
@attribute num1 numeric
@attribute cls {red,green,blue}
@attribute note string
@data
1.5,red,hello
2.5,blue,world
?,green,!
""")
    fr = import_file(str(p))
    assert fr.types() == {"num1": "num", "cls": "cat", "note": "str"}
    assert fr.vec("cls").domain == ["red", "green", "blue"]
    x = fr.vec("num1").to_numpy()
    assert x[0] == 1.5 and np.isnan(x[2])


def test_parquet_orc_feather(cl, tmp_path, rng):
    fr = Frame.from_numpy({
        "a": rng.normal(size=40),
        "g": np.array(["x", "y"], dtype=object)[rng.integers(0, 2, 40)]})
    for ext in ("parquet", "feather"):
        uri = str(tmp_path / f"t.{ext}")
        export_file(fr, uri)
        back = import_file(uri)
        np.testing.assert_allclose(back.vec("a").to_numpy(),
                                   fr.vec("a").to_numpy(), rtol=1e-9)
        assert list(back.vec("g").decoded()) == list(fr.vec("g").decoded())
    # ORC import (written via pyarrow directly)
    import pyarrow as pa
    import pyarrow.orc as porc
    porc.write_table(pa.table({"v": np.arange(5.0)}),
                     str(tmp_path / "t.orc"))
    orc_fr = import_file(str(tmp_path / "t.orc"))
    np.testing.assert_array_equal(orc_fr.vec("v").to_numpy(),
                                  np.arange(5.0))
    # avro now has a real parser (frame/avro.py); truncated input is a
    # clean parse error, not a missing-library gate
    with pytest.raises(ValueError, match="truncated avro"):
        (tmp_path / "x.avro").write_bytes(b"Obj\x01")
        import_file(str(tmp_path / "x.avro"))


def test_export_roundtrip(cl, tmp_path, rng):
    fr = Frame.from_numpy({
        "a": rng.normal(size=20),
        "g": np.array(["u", "v"], dtype=object)[rng.integers(0, 2, 20)]})
    uri = str(tmp_path / "out.csv")
    export_file(fr, uri)
    back = import_file(uri)
    np.testing.assert_allclose(back.vec("a").to_numpy(),
                               fr.vec("a").to_numpy(), rtol=1e-6)
    assert list(back.vec("g").decoded()) == list(fr.vec("g").decoded())


def test_gcs_mock_uri_roundtrip(cl, tmp_path, rng, monkeypatch):
    monkeypatch.setenv("H2O3_TPU_GCS_ROOT", str(tmp_path / "gcs"))
    fr = Frame.from_numpy({"a": rng.normal(size=10)})
    export_file(fr, "gcs://bucket/dir/data.csv")
    assert (tmp_path / "gcs" / "bucket" / "dir" / "data.csv").exists()
    back = import_file("gcs://bucket/dir/data.csv")
    np.testing.assert_allclose(back.vec("a").to_numpy(),
                               fr.vec("a").to_numpy(), rtol=1e-6)


def test_model_save_load_uri(cl, tmp_path, rng, monkeypatch):
    monkeypatch.setenv("H2O3_TPU_GCS_ROOT", str(tmp_path / "gcs"))
    from h2o3_tpu.models import GLM
    n = 500
    X = rng.normal(size=(n, 3))
    y = X @ [1.0, -2.0, 0.5] + 0.01 * rng.normal(size=n)
    fr = Frame.from_numpy({**{f"x{j}": X[:, j] for j in range(3)}, "y": y})
    m = GLM(response_column="y", family="gaussian").train(fr)
    uri = "gcs://models/glm1.bin"
    h2o3_tpu.save_model(m, uri)
    m2 = h2o3_tpu.load_model(uri)
    p1 = m.predict(fr).vec("predict").to_numpy()
    p2 = m2.predict(fr).vec("predict").to_numpy()
    np.testing.assert_allclose(p1, p2, rtol=1e-6)


def test_sql_import(cl, tmp_path):
    import sqlite3
    import h2o3_tpu
    db = str(tmp_path / "t.db")
    conn = sqlite3.connect(db)
    conn.execute("CREATE TABLE users (age REAL, city TEXT, income REAL)")
    conn.executemany(
        "INSERT INTO users VALUES (?,?,?)",
        [(30 + i, ["sf", "nyc", "la"][i % 3], 50000 + i * 1000)
         for i in range(50)])
    conn.commit()
    fr = h2o3_tpu.import_sql_table(conn, "users")
    assert fr.shape == (50, 3)
    assert fr.types() == {"age": "num", "city": "cat", "income": "num"}
    fr2 = h2o3_tpu.import_sql_select(
        f"sqlite://{db}", "SELECT age, income FROM users WHERE age > 50")
    assert fr2.nrows == 29 and fr2.names == ["age", "income"]
    with pytest.raises(NotImplementedError, match="DB-API"):
        h2o3_tpu.import_sql_table("jdbc:postgresql://x/y", "users")


def test_from_pandas_and_h2oframe(cl):
    import pandas as pd
    df = pd.DataFrame({
        "num": [1.5, 2.5, None],
        "i": [1, 2, 3],
        "b": [True, False, True],
        "cat": pd.Categorical(["lo", "hi", None],
                              categories=["lo", "mid", "hi"]),
        "s": ["x", "y", "zzz-long-un1que"],
        "t": pd.to_datetime(["2020-01-01", "2020-06-01", "2021-01-01"]),
        "mixed": ["1", "2", "oops"],
    })
    fr = h2o3_tpu.from_pandas(df)
    t = fr.types()
    assert t["num"] == "num" and t["i"] == "num" and t["b"] == "num"
    assert t["cat"] == "cat" and t["t"] == "time"
    assert fr.vec("cat").domain == ["lo", "mid", "hi"]
    x = fr.vec("num").to_numpy()
    assert x[1] == 2.5 and np.isnan(x[2])
    np.testing.assert_array_equal(fr.vec("b").to_numpy(), [1.0, 0.0, 1.0])
    codes = fr.vec("cat").data
    assert int(np.asarray(codes)[2]) == -1          # NaN category -> NA
    assert t["mixed"] in ("cat", "str")             # not numeric
    # H2OFrame: dict, list-of-rows with header, 2-D array
    f2 = h2o3_tpu.H2OFrame({"a": [1.0, 2.0], "g": ["u", "v"]})
    assert f2.shape == (2, 2) and f2.types()["g"] == "cat"
    f3 = h2o3_tpu.H2OFrame([["a", "b"], [1, 2], [3, 4]])
    assert f3.names == ["a", "b"] and f3.nrows == 2
    np.testing.assert_array_equal(f3.vec("a").to_numpy(), [1.0, 3.0])
    f4 = h2o3_tpu.H2OFrame(np.arange(6.0).reshape(3, 2))
    assert f4.names == ["C1", "C2"] and f4.nrows == 3
    # pandas round trip
    back = fr.to_pandas()
    assert list(back.columns) == list(df.columns)


def test_h2oframe_edges(cl):
    import pandas as pd
    # nullable boolean with NA
    fb = h2o3_tpu.from_pandas(pd.DataFrame(
        {"b": pd.Series([True, None, False], dtype="boolean")}))
    x = fb.vec("b").to_numpy()
    assert x[0] == 1.0 and np.isnan(x[1]) and x[2] == 0.0
    # dict with None stays numeric with NaN (no "None" category)
    f = h2o3_tpu.H2OFrame({"a": [1.0, 2.0, None]})
    assert f.types()["a"] == "num"
    a = f.vec("a").to_numpy()
    assert a[1] == 2.0 and np.isnan(a[2])
    assert f.key is not None                 # registered in the DKV
    # 1-D string list is data, not a header
    f1 = h2o3_tpu.H2OFrame(["a", "b", "c"])
    assert f1.nrows == 3 and f1.names == ["C1"]


def test_distributed_parse_single_process_parity(cl, tmp_path):
    """parse_files_distributed (nproc=1 degenerate) matches parse_files
    cell-for-cell on every column type, including boundary-line handling
    across uneven multi-file shards."""
    rng = np.random.default_rng(0)
    for k, nrows in enumerate((700, 150, 1201)):
        with open(tmp_path / f"part{k}.csv", "w") as f:
            f.write("num,cat,when,txt,resp\n")
            for i in range(nrows):
                num = "" if (i % 97 == 0) else f"{rng.normal():.4f}"
                f.write(f"{num},lvl{k}_{i % (3 + k)},"
                        f"2024-0{k+1}-{(i % 27) + 1:02d},id_{k}_{i},"
                        f"{'Y' if (i % 3) else 'N'}\n")
    from h2o3_tpu.frame import dparse
    import h2o3_tpu.frame.parse as P
    paths = sorted(str(p) for p in tmp_path.glob("part*.csv"))
    fr = dparse.parse_files_distributed(paths)
    fr2 = P.parse_files(paths)
    assert fr.shape == fr2.shape == (2051, 5)
    assert fr.types() == fr2.types() == {
        "num": "num", "cat": "cat", "when": "time", "txt": "str",
        "resp": "cat"}
    assert np.allclose(fr.vec("num").to_numpy(), fr2.vec("num").to_numpy(),
                       equal_nan=True)
    assert list(fr.vec("cat").decoded()) == list(fr2.vec("cat").decoded())
    assert np.allclose(fr.vec("when").to_numpy(),
                       fr2.vec("when").to_numpy(), equal_nan=True)
    assert list(fr.vec("txt").to_numpy()) == list(fr2.vec("txt").to_numpy())
    assert dparse.last_stats["bytes_tokenized"] > 0


# ------------------------------------------- ranged-parallel parse pipeline

def _pipeline_csv(tmp_path, nrows=1200, header=True, quoted=False,
                  name="pipe.csv"):
    """A fixture CSV exercising every column type the pipeline handles:
    numeric with NAs, categorical, time, free text, and negative floats."""
    rng = np.random.default_rng(7)
    lines = ["num,cat,when,txt,neg"] if header else []
    for i in range(nrows):
        num = "" if i % 53 == 0 else f"{rng.normal():.5f}"
        cat = f"lvl{i % 5}"
        when = f"2024-03-{(i % 27) + 1:02d}"
        txt = f'"say ""{i}"" twice"' if (quoted and i % 7 == 0) \
            else f"id_{i}"
        lines.append(f"{num},{cat},{when},{txt},{-1.5 * (i % 11):.2f}")
    p = tmp_path / name
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def _assert_frames_identical(fa, fb):
    assert fa.names == fb.names
    assert fa.types() == fb.types()
    for n in fa.names:
        va, vb = fa.vec(n), fb.vec(n)
        assert va.domain == vb.domain
        xa, xb = va.to_numpy(), vb.to_numpy()
        if xa.dtype == object:
            assert list(xa) == list(xb)
        else:
            np.testing.assert_array_equal(xa, xb)


def _parse_ranged(path, monkeypatch, threads=4, **kw):
    """Parse with the ranged-parallel path forced on (tiny range floor)."""
    import h2o3_tpu.frame.parse as P
    monkeypatch.setenv("H2O3_PARSE_THREADS", str(threads))
    monkeypatch.setenv("H2O3_PARSE_RANGE_MIN", "1")
    try:
        return P.parse_csv(path, **kw)
    finally:
        monkeypatch.delenv("H2O3_PARSE_THREADS")
        monkeypatch.delenv("H2O3_PARSE_RANGE_MIN")


def test_ranged_vs_single_thread_parity(cl, tmp_path, monkeypatch):
    """Ranged-parallel output is identical (names, types, values, domains)
    to the single-threaded native path on the same file — the splits land
    mid-row by construction and must be realigned to line starts."""
    from h2o3_tpu import native
    if native.load() is None:
        pytest.skip("native tokenizer unavailable")
    import h2o3_tpu.frame.parse as P
    path = _pipeline_csv(tmp_path)
    ranged = _parse_ranged(path, monkeypatch, threads=4)
    assert P.last_parse_stats.get("ranges", 0) > 1   # really went parallel
    monkeypatch.setenv("H2O3_PARSE_THREADS", "1")
    single = P.parse_csv(path)
    assert P.last_parse_stats.get("ranges") == 1
    _assert_frames_identical(ranged, single)
    assert ranged.types() == {"num": "num", "cat": "cat", "when": "time",
                              "txt": "str", "neg": "num"}
    assert np.isnan(ranged.vec("num").to_numpy()[0])          # NA cell
    assert ranged.vec("cat").domain == [f"lvl{i}" for i in range(5)]


def test_ranged_parity_many_tiny_ranges(cl, tmp_path, monkeypatch):
    """16 ranges over a small file: nearly every byte cut splits mid-row."""
    from h2o3_tpu import native
    if native.load() is None:
        pytest.skip("native tokenizer unavailable")
    import h2o3_tpu.frame.parse as P
    path = _pipeline_csv(tmp_path, nrows=97)
    ranged = _parse_ranged(path, monkeypatch, threads=16)
    monkeypatch.setenv("H2O3_PARSE_THREADS", "1")
    _assert_frames_identical(ranged, P.parse_csv(path))


def test_mmap_vs_bytes_input_equivalence(cl, tmp_path, monkeypatch):
    """The mmap'd path route and the bytes/stream route produce identical
    frames; the path route reports its mmap stage in the parse stats."""
    import io
    import h2o3_tpu.frame.parse as P
    path = _pipeline_csv(tmp_path)
    content = open(path, "rb").read()
    from_path = P.parse_csv(path)
    stats = dict(P.last_parse_stats)
    from_bytes = P.parse_csv(content)
    from_stream = P.parse_csv(io.BytesIO(content))
    _assert_frames_identical(from_path, from_bytes)
    _assert_frames_identical(from_path, from_stream)
    if stats:                                 # native engine engaged
        assert "mmap_s" in stats and stats["rows"] == from_path.nrows


def test_quoted_fields_parallel_and_fallback(cl, tmp_path, monkeypatch):
    """Benign quotes (escaped "" payloads, no hidden newlines) keep the
    ranged path; quoted embedded newlines/separators still parse correctly
    through whatever engine handles them."""
    import h2o3_tpu.frame.parse as P
    # benign quoting: ranged vs single parity including "" unescaping
    path = _pipeline_csv(tmp_path, quoted=True, name="q.csv")
    ranged = _parse_ranged(path, monkeypatch, threads=4)
    monkeypatch.setenv("H2O3_PARSE_THREADS", "1")
    single = P.parse_csv(path)
    monkeypatch.delenv("H2O3_PARSE_THREADS")
    _assert_frames_identical(ranged, single)
    assert 'say "0" twice' in list(ranged.vec("txt").to_numpy())
    # hostile quoting: newline + separator inside a quoted cell
    p2 = tmp_path / "q2.csv"
    p2.write_text('a,b\n1,"x,\ny"\n2,"plain"\n3,last\n')
    fr = _parse_ranged(str(p2), monkeypatch, threads=4)
    assert fr.shape == (3, 2)
    np.testing.assert_array_equal(fr.vec("a").to_numpy(), [1.0, 2.0, 3.0])
    vals = list(fr.vec("b").decoded() if fr.vec("b").domain
                else fr.vec("b").to_numpy())
    assert "x,\ny" in vals and "plain" in vals


def test_header_and_no_header_paths(cl, tmp_path, monkeypatch):
    """Header autodetect, explicit no-header, and all-numeric headerless
    files agree between the ranged and single-threaded engines."""
    import h2o3_tpu.frame.parse as P
    # headerless all-numeric: C1..Cn names
    p = tmp_path / "nh.csv"
    p.write_text("\n".join(f"{i},{i * 0.5},{i % 3}" for i in range(400))
                 + "\n")
    fr = _parse_ranged(str(p), monkeypatch)
    assert fr.names == ["C1", "C2", "C3"] and fr.nrows == 400
    np.testing.assert_array_equal(fr.vec("C1").to_numpy(),
                                  np.arange(400.0))
    # header=False forces the text first line into the data
    p2 = tmp_path / "h2.csv"
    p2.write_text("a,b\n1,2\n3,4\n")
    fr2 = P.parse_csv(str(p2), header=False)
    assert fr2.nrows == 3
    # autodetected header vs the same file parsed ranged
    path = _pipeline_csv(tmp_path, name="hd.csv")
    auto = _parse_ranged(path, monkeypatch)
    explicit = P.parse_csv(path, header=True)
    _assert_frames_identical(auto, explicit)


def test_parse_stage_timings_recorded(cl, tmp_path):
    """The native pipeline records per-stage wall times (the ingest
    layer's measurement surface) and observability keeps the parse record."""
    from h2o3_tpu import native
    if native.load() is None:
        pytest.skip("native tokenizer unavailable")
    import h2o3_tpu.frame.parse as P
    path = _pipeline_csv(tmp_path, nrows=300, name="tm.csv")
    P.parse_csv(path)
    st = P.last_parse_stats
    for k in ("mmap_s", "scan_s", "tokenize_s", "device_s", "decode_s",
              "native_total_s", "vec_s", "rows", "bytes", "ranges"):
        assert k in st, k
    assert st["rows"] == 300
