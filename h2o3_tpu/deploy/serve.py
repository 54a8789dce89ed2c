"""Launcher: boot the runtime + REST server — the water.H2OApp analog.

Single host:   python -m h2o3_tpu.deploy.serve --port 54321
Multi-host:    ... --coordinator host:port --num-processes N --process-id I
Pod-native:    ... --discover <headless-service> --cluster-size N
               (DNS-record clouding, H2OCluster.java analog; an Indexed
               Job sets H2O3_TPU_POD_INDEX for race-free ordinals)
(REST serves from process 0; workers join the mesh and block.)
"""

from __future__ import annotations

import argparse
import os
import signal
import sys
import threading


def main(argv=None):
    ap = argparse.ArgumentParser("h2o3_tpu.deploy.serve")
    from h2o3_tpu.runtime.config import config
    ap.add_argument("--port", type=int, default=config().port)
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 (multi-host)")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--discover", default=None, metavar="SERVICE",
                    help="headless-service DNS discovery instead of an "
                         "explicit --coordinator (k8s pod clouding)")
    ap.add_argument("--cluster-size", type=int, default=None,
                    help="expected process count for --discover")
    ap.add_argument("--discover-port", type=int, default=None,
                    help="rendezvous port (default 8476); for --flatfile "
                         "it also disambiguates this process's rank when "
                         "several members share the host")
    ap.add_argument("--flatfile", default=None,
                    help="cloud from a host:port member file (assisted "
                         "clustering analog; polled until --cluster-size "
                         "lines exist)")
    ap.add_argument("--username", default="")
    ap.add_argument("--password", default="")
    ap.add_argument("--auth", default=None,
                    help="authenticator spec (static:/hash_file:/cmd:/"
                         "module:) — see h2o3_tpu.api.auth")
    ap.add_argument("--https", action="store_true")
    ap.add_argument("--https-cert", default=None)
    ap.add_argument("--https-key", default=None)
    args = ap.parse_args(argv)
    if args.discover and not args.coordinator:
        from h2o3_tpu.runtime.discovery import discover
        (args.coordinator, args.num_processes,
         args.process_id) = discover(args.discover,
                                     port=args.discover_port or 8476,
                                     expected=args.cluster_size)
    elif args.flatfile and not args.coordinator:
        from h2o3_tpu.runtime.discovery import from_flatfile
        # own_port only when EXPLICITLY given: a defaulted port would
        # satisfy the multi-member-per-host ambiguity guard with the
        # wrong member instead of erroring
        (args.coordinator, args.num_processes,
         args.process_id) = from_flatfile(args.flatfile,
                                          expected=args.cluster_size,
                                          own_port=args.discover_port)
    if args.num_processes is not None and args.num_processes <= 1:
        # an EXPLICIT 1-member cloud needs no rendezvous/control plane —
        # boot the plain single-host path.  num_processes=None stays
        # multi-host: the TPU environment auto-detects slice topology.
        args.coordinator = None

    import jax
    import h2o3_tpu
    cl = h2o3_tpu.init(coordinator=args.coordinator,
                       num_processes=args.num_processes,
                       process_id=args.process_id)
    server = None
    if jax.process_index() == 0:
        from h2o3_tpu.api.server import start_server
        server = start_server(port=args.port, username=args.username,
                              password=args.password, auth=args.auth,
                              https=args.https, https_cert=args.https_cert,
                              https_key=args.https_key)
        print(f"h2o3_tpu serving on {server.url} "
              f"(mesh: {dict(cl.mesh.shape)})", flush=True)
        if os.environ.get("H2O3_TPU_RECOVERY_DIR"):
            # relaunched coordinator: re-import journaled frames from
            # their source URIs and retrain interrupted jobs
            from h2o3_tpu.runtime import recovery
            resumed = recovery.resume()
            if resumed:
                print(f"h2o3_tpu recovery resumed {len(resumed)} job(s): "
                      f"{resumed}", flush=True)
            # bring the serving plane back too: every `!serve/`-journaled
            # model is re-published into the micro-batcher registry
            from h2o3_tpu.serving import batcher as _serving_batcher
            republished = _serving_batcher.republish_journaled()
            if republished:
                print(f"h2o3_tpu serving re-published "
                      f"{len(republished)} model(s): {republished}",
                      flush=True)
    else:
        print(f"h2o3_tpu worker {jax.process_index()} joined "
              f"(mesh: {dict(cl.mesh.shape)})", flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    stop.wait()
    # graceful rollout (k8s sends SIGTERM first): stop accepting new
    # requests and drain in-flight handlers — bounded by
    # H2O3_TPU_REST_DRAIN_TIMEOUT — then stop the serving batchers and
    # detach the cluster, so pod restarts never drop scoring requests
    if server is not None:
        try:
            server.stop()
            print("h2o3_tpu REST drained", flush=True)
        except Exception as e:          # noqa: BLE001 — still detach
            print(f"h2o3_tpu REST drain failed: {e!r}", flush=True)
    try:
        from h2o3_tpu.serving import batcher as _serving_batcher
        _serving_batcher.shutdown_all()
    except Exception:                   # noqa: BLE001 — optional plane
        pass
    h2o3_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
