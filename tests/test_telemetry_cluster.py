"""Cross-process telemetry acceptance: a worker process attaches to this
process's DKV coordinator, heartbeats, and trains a tiny GBM; the
coordinator's merged view must then show (a) the worker's shipped metric
series next to the coordinator's own under per-node labels in one
Prometheus exposition, and (b) one stitched trace — the worker's job
span, its tree spans, and the coordinator-side ``dkv_handle`` spans all
sharing a trace_id across the RPC boundary."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from h2o3_tpu.runtime import dkv, heartbeat
from h2o3_tpu.runtime import observability as obs

_WORKER = textwrap.dedent("""
    import json
    import sys
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import h2o3_tpu
    h2o3_tpu.init()
    from h2o3_tpu import Frame
    from h2o3_tpu.models import GBM
    from h2o3_tpu.runtime import dkv, heartbeat
    from h2o3_tpu.runtime import observability as obs

    dkv.attach("127.0.0.1", int(sys.argv[1]))
    heartbeat.start(0.3)
    rng = np.random.default_rng(3)
    X = rng.random((400, 4))
    y = 3.0 * X[:, 0] + np.sin(4 * X[:, 1]) + 0.1 * rng.normal(size=400)
    fr = Frame.from_numpy({"x0": X[:, 0], "x1": X[:, 1], "x2": X[:, 2],
                           "x3": X[:, 3], "y": y})
    m = GBM(response_column="y", ntrees=3, max_depth=3, seed=7).train(fr)
    assert heartbeat.reship()    # stamp now carries the post-train registry
    job_evs = [e for e in obs.timeline_events(2000)
               if e["kind"] == "job" and e.get("trace_id")]
    print("WORKER_DONE", json.dumps({
        "trace_id": job_evs[-1]["trace_id"],
        "node": heartbeat.node_name(),
        "ntrees": m.output["ntrees_trained"]}))
    # join the beat thread but LEAVE the stamp behind — the coordinator-
    # side merge assertions read it after this process exits
    heartbeat.stop(remove=False)
""")


def test_worker_metrics_and_trace_stitch_across_processes(tmp_path):
    obs.set_enabled(True)
    port = dkv.serve("127.0.0.1", 0)
    worker_node = None
    try:
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "H2O3_TPU_RECOVERY_DIR": str(tmp_path),
            "H2O3_TPU_SNAPSHOT_INTERVAL": "0",
        })
        proc = subprocess.run(
            [sys.executable, "-c", _WORKER, str(port)],
            env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
            capture_output=True, text=True, timeout=420)
        assert proc.returncode == 0, (
            f"worker rc={proc.returncode}\nstdout:\n{proc.stdout[-3000:]}"
            f"\nstderr:\n{proc.stderr[-3000:]}")
        info = json.loads(proc.stdout.split("WORKER_DONE", 1)[1])
        worker_node, trace_id = info["node"], info["trace_id"]
        assert info["ntrees"] == 3

        # -- the worker's stamp landed here with metrics + an event tail
        stamps = obs.cluster_stamps()
        assert worker_node in stamps
        stamp = stamps[worker_node]
        assert stamp.get("metrics"), "worker shipped no metric snapshot"
        shipped_names = {s["n"] for s in stamp["metrics"]}
        assert "dkv_rpc_seconds" in shipped_names
        assert "span_seconds" in shipped_names
        # the compile ledger rides the same snapshot: the worker's train
        # compiled at least the tree-scan program, so its compile series
        # and cost gauges land on the coordinator without extra plumbing
        assert "compile_seconds" in shipped_names
        assert "recompiles_total" in shipped_names

        # -- one scrape covers both processes, split by the node label
        text = obs.render_prometheus(cluster=True)
        me = obs.node_name()
        worker_lines = [ln for ln in text.splitlines()
                        if f'node="{worker_node}"' in ln]
        assert any(ln.startswith("dkv_rpc_seconds_bucket")
                   for ln in worker_lines)
        assert any(ln.startswith("span_seconds_bucket")
                   and 'span="tree_phase"' in ln for ln in worker_lines)
        assert any(ln.startswith("compile_seconds_bucket")
                   for ln in worker_lines)
        assert any(ln.startswith("recompiles_total{")
                   and 'reason="first"' in ln for ln in worker_lines)
        # the coordinator side of the same RPCs, under its own label
        assert any(ln.startswith("dkv_handle_seconds_bucket")
                   and f'node="{me}"' in ln for ln in text.splitlines())

        # -- trace stitching: worker job/tree spans and coordinator
        #    dkv_handle spans form ONE tree, keyed by the job's trace_id
        events = obs.timeline_events(2000) + list(stamp.get("events") or [])
        forest = obs.trace_forest(events)
        target = [t for t in forest if t["trace_id"] == trace_id]
        assert target, f"job trace {trace_id} not stitched"

        def kinds(spans):
            out = set()
            for s in spans:
                out.add(s["kind"])
                out |= kinds(s["children"])
            return out

        got = kinds(target[0]["spans"])
        assert "job" in got                      # worker root span
        assert "tree_chunk" in got               # worker tree work
        assert "dkv_handle" in got, (            # coordinator, via envelope
            f"no coordinator-side span joined the trace: {sorted(got)}")
    finally:
        if worker_node:
            try:
                dkv.remove(heartbeat.PREFIX + worker_node)
            except Exception:            # noqa: BLE001
                pass
        dkv.detach()
