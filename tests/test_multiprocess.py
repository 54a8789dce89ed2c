"""Multi-process distributed runtime test — the multiNodeUtils.sh analog.

The reference's core distributed test pattern (SURVEY.md §4,
``scripts/multiNodeUtils.sh:21-26``) spawns real JVMs on localhost and runs
jobs across them.  Here: N real Python processes each with 4 virtual CPU
devices run ``jax.distributed.initialize`` against a localhost coordinator,
boot one 8-device global mesh, and execute the same SPMD training programs —
XLA collectives cross the process boundary exactly as they would cross
ICI/DCN on a TPU pod, and the coordinator DKV service carries the control
plane.
"""

import json
import os
import socket
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_worker_env() -> dict:
    """Environment of a worker process: CPU-only by environment (it never
    needs a chip, whatever the parent holds), 4 virtual devices, this
    checkout first on the path."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=4")
    env["XLA_FLAGS"] = " ".join(flags)
    ambient = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
    env["PYTHONPATH"] = os.pathsep.join([ROOT] + ambient)
    return env

WORKER = r"""
import json, os, sys

pid = int(sys.argv[1])
nproc = int(sys.argv[2])
coord = sys.argv[3]
out_path = sys.argv[4]

import jax
# initialize BEFORE anything can touch the XLA backend
jax.distributed.initialize(coordinator_address=coord, num_processes=nproc,
                           process_id=pid)

import numpy as np
import h2o3_tpu
from h2o3_tpu import Frame
from h2o3_tpu.frame.vec import T_CAT
from h2o3_tpu.models import GBM, GLM
from h2o3_tpu.runtime import dkv

cl = h2o3_tpu.init(coordinator=coord, num_processes=nproc, process_id=pid)
assert jax.process_count() == nproc, jax.process_count()
assert cl.n_devices == 4 * nproc, cl.n_devices

# identical data everywhere — SPMD: every process executes the same program
rng = np.random.default_rng(0)
n = 4000
x1 = rng.normal(size=n).astype(np.float32)
x2 = rng.normal(size=n).astype(np.float32)
c1 = rng.integers(0, 4, n)
logit = 1.2 * x1 - 0.8 * x2 + 0.5 * (c1 == 2)
y = rng.random(n) < 1 / (1 + np.exp(-logit))
fr = Frame.from_numpy(
    {"x1": x1, "x2": x2, "c1": c1,
     "y": np.where(y, "YES", "NO").astype(object)},
    types={"c1": T_CAT}, domains={"c1": [str(i) for i in range(4)]})

# rollups ride a cross-process psum
mean_x1 = fr.vec("x1").mean()

glm = GLM(response_column="y", family="binomial", lambda_=0.0,
          seed=1).train(fr)
glm_auc = glm.training_metrics.describe()["auc"]

gbm = GBM(response_column="y", ntrees=4, max_depth=3, nbins=16,
          seed=1).train(fr)
gbm_auc = gbm.training_metrics.describe()["auc"]

# control plane: each process publishes a result; all read each other's
dkv.put(f"mp_result_{pid}", {"auc": float(glm_auc)})
from jax.experimental import multihost_utils
multihost_utils.sync_global_devices("dkv_published")
peers = {}
for other in range(nproc):
    v = dkv.get(f"mp_result_{other}")
    peers[other] = None if v is None else v["auc"]

with open(out_path, "w") as f:
    json.dump({"pid": pid, "mean_x1": float(mean_x1),
               "glm_auc": float(glm_auc), "gbm_auc": float(gbm_auc),
               "peers": peers}, f)
"""


WORKER_PARSE = r"""
import glob, json, os, sys

pid = int(sys.argv[1])
nproc = int(sys.argv[2])
coord = sys.argv[3]
out_path = sys.argv[4]
data_glob = sys.argv[5]

import jax
jax.distributed.initialize(coordinator_address=coord, num_processes=nproc,
                           process_id=pid)

import numpy as np
import h2o3_tpu
from h2o3_tpu.frame import dparse
from h2o3_tpu.models import GLM

cl = h2o3_tpu.init(coordinator=coord, num_processes=nproc, process_id=pid)

fr = h2o3_tpu.import_file(data_glob, destination_frame="airlines_mp")
mean_num = fr.vec("num").mean()                 # rides a cross-process psum
span_stats = dict(dparse.last_stats)
glm = GLM(response_column="resp", family="binomial", lambda_=0.0,
          seed=1).train(fr)
auc = glm.training_metrics.describe()["auc"]

cat_codes = fr.vec("cat").to_numpy()            # process_allgather round-trip

# quoted-newline file: the byte split is unsafe -> replicated fallback
qpath = os.path.join(os.path.dirname(data_glob), "qdata.csv")
fq = h2o3_tpu.import_file(qpath, destination_frame="quoted_mp")
q_stats = dict(dparse.last_stats)

with open(out_path, "w") as f:
    json.dump({"pid": pid, "shape": list(fr.shape), "types": fr.types(),
               "mean_num": float(mean_num), "auc": float(auc),
               "domain": fr.vec("cat").domain,
               "mixed_domain": fr.vec("mixedcat").domain,
               "cat_head": [int(v) for v in cat_codes[:5]],
               "txt_head": [str(v) for v in fr.vec("txt").to_numpy()[:3]],
               "stats": span_stats,
               "q_shape": list(fq.shape),
               "q_cell": str(fq.vec("note").to_numpy()[250]),
               "q_suspect": bool(q_stats.get("suspect"))}, f)
"""


def _write_parse_files(tmp_path, nrows_list=(3000, 800, 4200)):
    """Uneven CSV shards; cat levels differ per file to force domain merge.

    ``mixedcat`` holds numeric-looking tokens ("3", "007") everywhere except
    the tail of the last file ("x9") — process 0's spans tokenize it as pure
    float while process 1 sees text, forcing the supplemental raw-token
    domain round (source spellings must survive, no "3.0" float round-trip).
    """
    import numpy as np
    rng = np.random.default_rng(7)
    total_rows = 0
    last = len(nrows_list) - 1
    for k, nrows in enumerate(nrows_list):
        with open(tmp_path / f"part{k}.csv", "w") as f:
            f.write("num,cat,mixedcat,txt,resp\n")
            for i in range(nrows):
                num = "" if i % 131 == 0 else f"{rng.normal():.4f}"
                cat = f"lvl{k}_{i % (3 + k)}"
                if k == last and i >= nrows - 200:
                    mixed = "x9"
                else:
                    mixed = "007" if i % 2 else "3"
                y = "Y" if rng.random() < 0.5 else "N"
                f.write(f"{num},{cat},{mixed},id_{k}_{i},{y}\n")
        total_rows += nrows
    # quoted-newline dataset: one RFC-4180 field with embedded linebreaks
    # sized to straddle the 2-process byte midpoint, so a span boundary
    # lands inside the quotes and the split MUST be detected as unsafe
    blob = "\n".join(f"wrapped line {j}" for j in range(120))
    with open(tmp_path / "qdata.csv", "w") as f:
        f.write('id,note\n')
        for i in range(500):
            if i == 250:
                f.write(f'{i},"{blob}"\n')
            else:
                f.write(f'{i},plain_{i}\n')
    return total_rows


def test_distributed_parse_two_processes(tmp_path):
    """2 processes parse a multi-file CSV, each tokenizing only its own
    byte ranges (ParseDataset.java:688 MultiFileParseTask analog), then
    train on the result."""
    nproc = 2
    total_rows = _write_parse_files(tmp_path)
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    worker_py = tmp_path / "worker_parse.py"
    worker_py.write_text(WORKER_PARSE)
    procs, outs = [], []
    for pid in range(nproc):
        out = tmp_path / f"pout_{pid}.json"
        outs.append(out)
        env = _cpu_worker_env()
        procs.append(subprocess.Popen(
            [sys.executable, str(worker_py), str(pid), str(nproc), coord,
             str(out), str(tmp_path / "part*.csv")],
            env=env, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    logs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        logs.append(stdout)
    for pid, p in enumerate(procs):
        assert p.returncode == 0, f"worker {pid} failed:\n{logs[pid][-4000:]}"
    results = [json.loads(o.read_text()) for o in outs]
    r0, r1 = results
    assert r0["shape"] == [total_rows, 5]
    assert r0["shape"] == r1["shape"]
    assert r0["types"] == {"num": "num", "cat": "cat", "mixedcat": "cat",
                           "txt": "str", "resp": "cat"}
    # SPMD: identical global results on every process
    assert abs(r0["mean_num"] - r1["mean_num"]) < 1e-6
    assert abs(r0["auc"] - r1["auc"]) < 1e-6
    assert r0["domain"] == r1["domain"]
    assert r0["cat_head"] == r1["cat_head"]
    assert r0["txt_head"] == ["id_0_0", "id_0_1", "id_0_2"]
    # domain merge saw every file's distinct levels (3 + 4 + 5)
    assert len(r0["domain"]) == 12
    # mixed numeric/text column keeps SOURCE token spellings in the merged
    # domain — never float round-trips like "3.0"/"7.0"
    assert sorted(r0["mixed_domain"]) == ["007", "3", "x9"]
    assert r0["mixed_domain"] == r1["mixed_domain"]
    # quoted-newline input: at least one process detected the unsafe split
    # (the boundary lands inside the quoted blob) and ALL fell back to the
    # replicated parse, which handles the quoting correctly
    assert r0["q_suspect"] or r1["q_suspect"]
    assert r0["q_shape"] == [500, 2] and r1["q_shape"] == [500, 2]
    expected_blob = "\n".join(f"wrapped line {j}" for j in range(120))
    assert r0["q_cell"] == expected_blob == r1["q_cell"]
    # NO single-host tokenization: each process touched only its byte span
    total = r0["stats"]["total_bytes"]
    for r in results:
        st = r["stats"]
        assert st["total_bytes"] == total
        assert 0 < st["bytes_tokenized"] < 0.7 * total, st
        assert 0 < st["rows_local"] < total_rows, st
    combined = sum(r["stats"]["bytes_tokenized"] for r in results)
    assert combined >= 0.9 * total             # headers/partial lines only
    assert sum(r["stats"]["rows_local"] for r in results) == total_rows


WORKER_CHAOS = r"""
import json, os, sys, time

pid = int(sys.argv[1])
nproc = int(sys.argv[2])
coord = sys.argv[3]
out_path = sys.argv[4]
csv_path = sys.argv[5]

import jax
jax.distributed.initialize(coordinator_address=coord, num_processes=nproc,
                           process_id=pid)

import h2o3_tpu
from h2o3_tpu.models import GBM
from h2o3_tpu.runtime import dkv, failure, heartbeat

cl = h2o3_tpu.init(coordinator=coord, num_processes=nproc, process_id=pid)
# fast liveness for the test: 0.1s stamps, watchdog sweeping every 0.2s
heartbeat.start(interval=0.1)
failure.stop()
failure.start(poll=0.2, hb_interval=0.1)

fr = h2o3_tpu.import_file(csv_path, destination_frame="chaos_fr")
job = GBM(response_column="resp", ntrees=40, max_depth=3, nbins=16,
          seed=1, score_tree_interval=10**6).train_async(fr)
result = {"pid": pid, "failed": False}
try:
    job.join(timeout=300)
except BaseException as e:
    result["failed"] = True
    result["error_type"] = type(e).__name__
    result["error"] = repr(e)[:300]
    result["job_status"] = job.status

# wait for the watchdog to confirm the death (may lag the XLA error)
deadline = time.time() + 30
while time.time() < deadline and not failure.any_dead():
    time.sleep(0.2)
result["dead_detected"] = failure.any_dead()
result["failure_keys"] = dkv.keys(failure.FAILURES_PREFIX)

with open(out_path, "w") as f:
    json.dump(result, f)
# the backend may be wedged in a dead collective: skip teardown entirely
os._exit(0)
"""


def test_chaos_worker_death_recovery(tmp_path):
    """Kill one worker mid-train via the fault-injection hook; the
    survivor's watchdog aborts the job with a clear error and the journal
    stays resumable; a fresh (restarted) cluster resurrects the model via
    recovery.resume().  Matches water/HeartBeatThread.java:145 +
    hex/faulttolerance/Recovery.java:72-81 — and goes beyond the
    reference, which cannot abort cleanly on member loss."""
    import numpy as np
    nproc = 2
    rng = np.random.default_rng(11)
    n = 4000
    csv_path = tmp_path / "chaos.csv"
    with open(csv_path, "w") as f:
        f.write("x1,x2,resp\n")
        for i in range(n):
            x1, x2 = rng.normal(), rng.normal()
            yv = "Y" if rng.random() < 1 / (1 + np.exp(-(1.5 * x1 - x2))) \
                else "N"
            f.write(f"{x1:.5f},{x2:.5f},{yv}\n")
    recovery_dir = tmp_path / "recovery"
    recovery_dir.mkdir()
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    worker_py = tmp_path / "worker_chaos.py"
    worker_py.write_text(WORKER_CHAOS)
    procs, outs = [], []
    for pid in range(nproc):
        out = tmp_path / f"cout_{pid}.json"
        outs.append(out)
        env = _cpu_worker_env()
        env["H2O3_TPU_RECOVERY_DIR"] = str(recovery_dir)
        # process 1 is hard-killed at its 2nd tree chunk
        env["H2O3_TPU_FAULT_INJECT"] = "tree_chunk:1:2"
        procs.append(subprocess.Popen(
            [sys.executable, str(worker_py), str(pid), str(nproc), coord,
             str(out), str(csv_path)],
            env=env, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    logs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        logs.append(stdout)
    # the injected victim dies 137; the survivor exits cleanly
    assert procs[1].returncode == 137, logs[1][-2000:]
    assert procs[0].returncode == 0, logs[0][-4000:]
    r0 = json.loads(outs[0].read_text())
    assert r0["failed"], r0
    assert r0["job_status"] == "FAILED"
    assert r0["dead_detected"], r0
    assert any(k.startswith("!failures/") for k in r0["failure_keys"]), r0
    # the journal entry survived as 'running' -> resumable
    entries = list(recovery_dir.glob("job_*.json"))
    assert entries, "no journal entry written"
    states = [json.loads(e.read_text())["status"] for e in entries]
    assert "running" in states, states
    # ---- phase B: "restarted cluster" (this pytest process, 8-dev mesh)
    from h2o3_tpu.runtime import failure, recovery as rec
    import h2o3_tpu
    h2o3_tpu.init()
    failure.reset()
    h2o3_tpu.import_file(str(csv_path), destination_frame="chaos_fr")
    done = rec.resume(str(recovery_dir))
    assert len(done) == 1, done
    from h2o3_tpu.runtime import dkv as _dkv
    model = _dkv.get(done[0])
    assert model is not None and model.output["ntrees_trained"] == 40
    assert not list(recovery_dir.glob("job_*.json"))


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_cluster(tmp_path):
    nproc = 2
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    worker_py = tmp_path / "worker.py"
    worker_py.write_text(WORKER)
    procs = []
    outs = []
    for pid in range(nproc):
        out = tmp_path / f"out_{pid}.json"
        outs.append(out)
        env = _cpu_worker_env()
        procs.append(subprocess.Popen(
            [sys.executable, str(worker_py), str(pid), str(nproc), coord,
             str(out)],
            env=env, cwd=ROOT, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    logs = []
    for p in procs:
        try:
            stdout, _ = p.communicate(timeout=540)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        logs.append(stdout)
    for pid, p in enumerate(procs):
        assert p.returncode == 0, (
            f"worker {pid} failed:\n{logs[pid][-4000:]}")
    results = [json.loads(o.read_text()) for o in outs]
    # SPMD: every process computed the same global result
    assert abs(results[0]["mean_x1"] - results[1]["mean_x1"]) < 1e-6
    assert abs(results[0]["glm_auc"] - results[1]["glm_auc"]) < 1e-6
    assert abs(results[0]["gbm_auc"] - results[1]["gbm_auc"]) < 1e-6
    assert results[0]["glm_auc"] > 0.7
    assert results[0]["gbm_auc"] > 0.7
    # control plane: cross-process DKV resolution
    for r in results:
        assert r["peers"]["0"] is not None or r["peers"].get(0) is not None
        vals = list(r["peers"].values())
        assert all(v is not None for v in vals), r["peers"]


def test_dkv_tls_and_atomics(cl, tmp_path):
    """TLS-wrapped control plane + atomic CAS/incr (single-process)."""
    import os
    import subprocess
    import socket
    import struct
    import pickle
    import threading
    from h2o3_tpu.runtime import dkv
    cert, key = str(tmp_path / "c.pem"), str(tmp_path / "k.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-keyout", key,
         "-out", cert, "-days", "1", "-nodes", "-subj", "/CN=localhost"],
        capture_output=True, check=True)
    os.environ["H2O3_TPU_TLS_CERT"] = cert
    os.environ["H2O3_TPU_TLS_KEY"] = key
    from h2o3_tpu.runtime import config as _cfg
    _cfg.reload()
    try:
        dkv.detach()
        port = dkv.serve(port=0)
        dkv.attach("127.0.0.1", port)
        dkv._rpc("put", key="tls_test", value=42)
        assert dkv._rpc("get", key="tls_test") == 42
        # remote-side atomics
        assert dkv._rpc("cas", key="c1", expected=None, new="a")
        assert not dkv._rpc("cas", key="c1", expected="b", new="x")
        assert dkv._rpc("incr", key="n1", delta=2.5) == 2.5
        # a plaintext client gets no handshake
        try:
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=3) as s:
                payload = pickle.dumps({"op": "ping"})
                s.sendall(struct.pack("<Q", len(payload)) + payload)
                s.settimeout(3)
                data = s.recv(8)
                assert not data or len(data) < 8
        except (ConnectionError, socket.timeout, OSError):
            pass
    finally:
        dkv.detach()
        os.environ.pop("H2O3_TPU_TLS_CERT", None)
        os.environ.pop("H2O3_TPU_TLS_KEY", None)
        _cfg.reload()

    # local atomics under contention
    assert dkv.cas("casme", None, "v1")
    assert dkv.cas("casme", "v1", "v2") and dkv.get("casme") == "v2"

    def worker():
        for _ in range(500):
            dkv.incr("ctr_t", 1)
    ts = [threading.Thread(target=worker) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert dkv.get("ctr_t") == 4000


def test_heartbeat_liveness(cl):
    import time
    from h2o3_tpu.runtime import dkv, heartbeat
    name = heartbeat.start(interval=0.05)
    try:
        time.sleep(0.2)
        m = heartbeat.members(interval=0.05)
        assert m[name]["status"] == "alive"
        assert m[name]["pid"] > 0
        # a peer that stopped stamping decays to suspect, then dead
        dkv.put(heartbeat.PREFIX + "ghost",
                {"ts": time.time() - 0.3, "pid": 1})
        m = heartbeat.members(interval=0.05)
        assert m["ghost"]["status"] == "suspect"
        dkv.put(heartbeat.PREFIX + "ghost",
                {"ts": time.time() - 1.0, "pid": 1})
        assert heartbeat.members(interval=0.05)["ghost"]["status"] == "dead"
        # stamps dead >100 intervals are garbage-collected entirely
        dkv.put(heartbeat.PREFIX + "ghost",
                {"ts": time.time() - 60.0, "pid": 1})
        assert "ghost" not in heartbeat.members(interval=0.05)
    finally:
        heartbeat.stop()
        dkv.remove(heartbeat.PREFIX + "ghost")
    # clean stop removes this node's stamp (departure, not failure)
    assert name not in heartbeat.members(interval=0.05)
