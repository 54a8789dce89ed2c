"""Cluster runtime: the TPU-native analog of H2O's "cloud".

The reference (h2o-core/src/main/java/water/H2O.java, water/Paxos.java:27,
water/HeartBeatThread.java:16) forms a cloud of JVMs via multicast heartbeats
and a mutual-knowledge consensus, then locks membership at the first job.

On TPU the topology is known at launch: a pod slice is gang-scheduled, so no
consensus protocol is needed (SURVEY.md §5 "Distributed communication
backend").  The Cluster here is a thin, explicit object: a
``jax.sharding.Mesh`` over the available devices plus named shardings used by
the data plane.  Multi-process operation uses ``jax.distributed.initialize``
(the analog of flatfile-based clouding); within a process everything is SPMD
over the mesh and all reductions are XLA collectives over ICI instead of the
reference's MRTask RPC tree (water/MRTask.java:739-760).

Axis names — the mesh is an explicit ``("hosts", "chips", "model")``
hierarchy so collectives can be staged over the physical topology:
  * ``"hosts"`` — the DCN axis: one slot per host (real hosts under
    multi-process SPMD; VIRTUAL hosts carved out of the local devices via
    ``H2O3_TPU_HOSTS`` / ``init(hosts=...)`` for CI and laptops).
  * ``"chips"`` — the ICI axis: a host's chips, where psums ride the ring.
  * ``"model"`` — optional axis for feature/model sharding (the TP analog
    for very wide Gram matrices, SURVEY.md §2.10).
  * ``ROW_AXIS`` — the data "rows" axis every Frame is sharded over — is
    now the FLATTENED PRODUCT ``("hosts", "chips")``: PartitionSpecs,
    shard_map specs and ``psum`` all accept the tuple, so existing call
    sites keep working unchanged while ``runtime/mapreduce.py`` can stage
    the reduce per physical axis (ICI first, then DCN).
"""

from __future__ import annotations

import dataclasses
import os
import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HOST_AXIS = "hosts"
CHIP_AXIS = "chips"
MODEL_AXIS = "model"
# the flattened data axis: hosts-major product, one name for call sites
ROW_AXES = (HOST_AXIS, CHIP_AXIS)
ROW_AXIS = ROW_AXES

_lock = threading.Lock()
_cluster: "Cluster | None" = None


@dataclasses.dataclass
class Cluster:
    """A booted cluster: device mesh + canonical shardings.

    Analog of the reference's ``H2O.CLOUD`` (water/H2O.java) — but instead of
    a membership list plus a key-homing hash (water/Key.java:175-181), data
    placement is expressed as JAX shardings over the mesh.
    """

    mesh: Mesh

    # -- canonical shardings -------------------------------------------------
    @property
    def row_sharding(self) -> NamedSharding:
        """Sharding for 1-D row vectors (one Vec's payload)."""
        return NamedSharding(self.mesh, P(ROW_AXIS))

    @property
    def matrix_sharding(self) -> NamedSharding:
        """Sharding for [rows, features] matrices: rows split, features local."""
        return NamedSharding(self.mesh, P(ROW_AXIS, None))

    @property
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    # -- geometry ------------------------------------------------------------
    @property
    def n_row_shards(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in ROW_AXES]))

    @property
    def n_hosts(self) -> int:
        return self.mesh.shape[HOST_AXIS]

    @property
    def n_chips_per_host(self) -> int:
        return self.mesh.shape[CHIP_AXIS]

    @property
    def n_devices(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    def row_multiple(self) -> int:
        """Rows are padded to a multiple of this (shards x 8 sublanes)."""
        return self.n_row_shards * 8

    def pad_rows(self, n: int) -> int:
        m = self.row_multiple()
        return ((max(n, 1) + m - 1) // m) * m

    def describe(self) -> dict:
        """Cluster status — the `/3/Cloud` analog (water/api/CloudHandler)."""
        from . import dkv
        return {
            "devices": [str(d) for d in self.mesh.devices.flat],
            "platform": self.mesh.devices.flat[0].platform,
            "mesh_shape": dict(self.mesh.shape),
            "process_index": jax.process_index(),
            "process_count": jax.process_count(),
            # control-plane durability/fencing facts (epoch, WAL, role)
            "control_plane": dkv.wal_stats(),
        }


def _resolve_hosts(hosts: int | None, n_row: int) -> int:
    """Host-axis size: explicit param > H2O3_TPU_HOSTS > process count > 1.

    Auto-resolved sizes that don't divide the row-shard count degrade to a
    single (flat) host with a telemetry event; an explicit ``hosts=``
    argument that doesn't divide is a caller error.
    """
    explicit = hosts is not None
    if hosts is None:
        from .config import config
        hosts = config().mesh_hosts or jax.process_count() or 1
    if hosts < 1:
        hosts = 1
    if n_row % hosts:
        if explicit:
            raise ValueError(
                f"hosts={hosts} must divide the row-shard count {n_row}")
        from .observability import log, record
        log.warning("mesh: hosts=%d does not divide %d row shards; "
                    "falling back to a single flat host", hosts, n_row)
        record("mesh_hosts_fallback", requested=hosts, n_row_shards=n_row)
        hosts = 1
    return hosts


def _build_mesh(devices: list, hosts: int, model_axis: int) -> Mesh:
    """(hosts, chips, model) grid over ``devices``.

    Real multi-host topologies go through ``create_hybrid_device_mesh`` so
    the chips axis maps onto each host's ICI ring and the hosts axis onto
    DCN.  CPU/virtual devices lack the ``slice_index``/coords attributes it
    needs, so single-host (and any failure) falls back to a process-sorted
    reshape — hosts-major, which still keeps each virtual host's chips
    contiguous.
    """
    n = len(devices)
    chips = n // model_axis // hosts
    if jax.process_count() > 1 and hosts == jax.process_count():
        try:
            from jax.experimental import mesh_utils
            grid = mesh_utils.create_hybrid_device_mesh(
                (1, chips * model_axis), (hosts, 1), devices=devices)
            grid = np.asarray(grid).reshape(hosts, chips, model_axis)
            return Mesh(grid, (HOST_AXIS, CHIP_AXIS, MODEL_AXIS))
        except Exception as e:            # noqa: BLE001 — CPU/virtual mesh
            from .observability import log
            log.warning("mesh: create_hybrid_device_mesh unavailable (%r); "
                        "using process-sorted reshape", e)
    devs = sorted(devices, key=lambda d: (d.process_index, d.id))
    grid = np.array(devs).reshape(hosts, chips, model_axis)
    return Mesh(grid, (HOST_AXIS, CHIP_AXIS, MODEL_AXIS))


def _invalidate_compiled_caches() -> None:
    """Drop compiled programs that closed over a previous mesh.

    The cached tree builders bind the live mesh at trace time via
    ``shard_map``; after a rebuild those executables reference dead
    devices.  Clearing the builder LRUs plus jax's global jit cache forces
    a retrace against the new mesh.  The xprof compile ledger is marked
    first, so every recompile this flush causes is attributed to
    ``recompiles_total{reason="cluster_reinit"}``.
    """
    from . import xprof
    xprof.invalidate("cluster_reinit")
    # the autotuner's per-signature mode decisions bind the mesh geometry
    # the same way the compiled programs do: drop them with the caches,
    # or a rebuilt mesh could be served a choice tuned for the dead one
    from . import autotune
    autotune.invalidate("cluster_reinit")
    # every cached builder of the tree engine and of GLM, found by what it
    # is and not by name: a list of names drifts (the scan-level and grid
    # builders were missing from it, and a mesh rebuilt at the same padded
    # row count was then handed a level program bound to the dead mesh)
    import importlib
    for mod_name in ("..models.tree.hist", "..models.tree.shared",
                     "..models.glm"):
        mod = importlib.import_module(mod_name, package=__package__)
        for obj in list(vars(mod).values()):
            clear = getattr(obj, "cache_clear", None)
            if callable(clear):
                clear()
    jax.clear_caches()


def publish_mesh_gauges(cl: "Cluster | None" = None) -> None:
    """(Re-)emit the ``mesh_shape`` gauge, one series per mesh axis.

    Separate helper (rather than inline in ``init``) so tests that reset
    the metric registry can re-emit without re-booting the cluster.
    """
    from . import observability as obs
    cl = cl if cl is not None else _cluster
    if cl is None:
        return
    for axis, size in cl.mesh.shape.items():
        obs.set_gauge("mesh_shape", size, axis=axis)
    obs.set_gauge("mesh_shape", cl.n_devices, axis="total")


def _place_compile_cache() -> None:
    """Point JAX's persistent compilation cache at a fixed directory.

    The cache key includes the directory, so it must not move between
    runs: with ``JAX_COMPILATION_CACHE_DIR`` set the choice is entirely
    JAX's (nothing is configured here); otherwise the cache lives in
    ``.jax_cache`` beside the package, i.e. at the root of the checkout.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))


def init(devices=None, model_axis: int | None = None,
         coordinator: str | None = None,
         num_processes: int | None = None, process_id: int | None = None,
         hosts: int | None = None) -> Cluster:
    """Boot (or return) the cluster — analog of ``h2o.init()``.

    Single-host: builds a mesh over the local devices.  Multi-host: pass
    ``coordinator`` (+ ``num_processes``/``process_id`` or rely on the TPU
    environment) to run ``jax.distributed.initialize`` first; the mesh then
    spans all hosts' devices and collectives ride ICI/DCN.

    ``hosts`` sizes the DCN axis of the mesh (default: ``H2O3_TPU_HOSTS``,
    else the process count).  Re-calling with a geometry that differs from
    the booted mesh REBUILDS it (with a ``cluster_reinit`` warning event and
    a compiled-cache flush) instead of silently returning the stale mesh.
    """
    global _cluster
    with _lock:
        if _cluster is None:
            _place_compile_cache()      # before the first compile
        if _cluster is not None:
            if coordinator is not None:
                raise RuntimeError(
                    "cluster already booted; the distributed control plane "
                    "cannot be re-initialized in-process — call "
                    "h2o3_tpu.shutdown() first")
            cur = _cluster.mesh
            if devices is None and hosts is None and model_axis is None:
                return _cluster           # default call: hand back the boot
            req_devices = list(devices) if devices is not None \
                else list(cur.devices.flat)
            # unspecified axes keep their live size: a partial re-init
            # (say init(hosts=4)) must not implicitly reset the others
            req_model = model_axis if model_axis is not None \
                else cur.shape[MODEL_AXIS]
            n = len(req_devices)
            if req_model < 1 or n % req_model:
                raise ValueError(
                    f"model_axis={req_model} must divide device count {n}")
            req_hosts = _resolve_hosts(hosts, n // req_model)
            if (req_devices == list(cur.devices.flat)
                    and req_model == cur.shape[MODEL_AXIS]
                    and req_hosts == cur.shape[HOST_AXIS]):
                return _cluster           # same geometry re-stated
            # geometry changed: the old behaviour either silently returned
            # the cached mesh or refused — rebuild instead, loudly
            from .observability import log, record
            log.warning("cluster re-init: mesh %s -> devices=%d hosts=%d "
                        "model_axis=%d; rebuilding and flushing compiled "
                        "caches", dict(cur.shape), n, req_hosts, req_model)
            record("cluster_reinit", old_shape=dict(cur.shape),
                   new_devices=n, new_hosts=req_hosts,
                   new_model_axis=req_model)
            _invalidate_compiled_caches()
            _cluster = None
            devices, hosts, model_axis = req_devices, req_hosts, req_model
        if coordinator is not None:
            # `jax.process_count()` would itself initialize the XLA
            # backend, after which jax.distributed.initialize refuses to
            # run — consult the distributed global state instead (callers
            # like the multiprocess tests may have initialized already).
            # num_processes=None stays valid: the TPU environment
            # auto-detects the slice topology.
            if num_processes != 1 \
                    and not jax.distributed.is_initialized():
                jax.distributed.initialize(coordinator_address=coordinator,
                                           num_processes=num_processes,
                                           process_id=process_id)
            # control plane (SURVEY §5): coordinator hosts the DKV service
            # one port above the jax.distributed rendezvous; workers attach.
            from . import dkv
            host, _, port = coordinator.rpartition(":")
            dkv_port = int(port) + 1
            if jax.process_index() == 0:
                dkv.serve(host="0.0.0.0" if host not in
                          ("127.0.0.1", "localhost") else host,
                          port=dkv_port)
            else:
                dkv.attach(host, dkv_port)
        if devices is None:
            devices = jax.devices()
        if model_axis is None:
            model_axis = 1
        devices = list(devices)
        n = len(devices)
        if model_axis < 1 or n % model_axis:
            raise ValueError(f"model_axis={model_axis} must divide device count {n}")
        n_hosts = _resolve_hosts(hosts, n // model_axis)
        mesh = _build_mesh(devices, n_hosts, model_axis)
        _cluster = Cluster(mesh=mesh)
    from . import extensions, failure, heartbeat, xprof
    extensions.load_all()
    heartbeat.start()
    failure.start()                 # dead-member watchdog: detection ACTS
    publish_mesh_gauges(_cluster)
    xprof.install_monitoring_listener()   # /jax/core/compile backstop
    return _cluster


def _guardrail_fraction() -> float:
    from .config import config
    return config().hbm_guardrail_fraction


def sample_memory_gauges() -> int:
    """Sample per-device allocator stats into telemetry gauges.

    Rides the same ``memory_stats()`` probe as ``_check_hbm_budget``;
    called from the heartbeat so every stamp ships fresh numbers.
    ``device_memory_bytes{device,kind}`` carries ``in_use``/``limit``
    plus an ``in_use_peak`` high-watermark (the WaterMeter analog).
    Returns how many devices reported stats (CPU backends report none).
    """
    from . import observability as obs
    sampled = 0
    for dev in jax.local_devices():
        try:
            stats = dev.memory_stats() or {}
        except Exception:               # noqa: BLE001 — backend-optional
            continue
        in_use = stats.get("bytes_in_use")
        if in_use is None:
            continue
        d = str(dev.id)
        obs.set_gauge("device_memory_bytes", in_use, device=d, kind="in_use")
        obs.gauge("device_memory_bytes", device=d,
                  kind="in_use_peak").set_max(in_use)
        limit = stats.get("bytes_limit")
        if limit:
            obs.set_gauge("device_memory_bytes", limit, device=d,
                          kind="limit")
        peak = stats.get("peak_bytes_in_use")
        if peak:
            obs.gauge("device_memory_bytes", device=d,
                      kind="in_use_peak").set_max(peak)
        sampled += 1
    return sampled


def _check_hbm_budget(nbytes: int, sharding=None, shape=None) -> None:
    """Fail fast with a clear message instead of an opaque XLA OOM.

    The reference spills cold chunks to disk (water/Cleaner.java:12); here
    frames must fit in HBM, so oversized placements get an actionable
    error naming the array and the per-device budget.
    """
    try:
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            return
        stats = dev.memory_stats() or {}
        limit = stats.get("bytes_limit")
        in_use = stats.get("bytes_in_use", 0)
        if not limit:
            return
        if sharding is not None and shape is not None:
            try:
                per_dev = int(np.prod(sharding.shard_shape(tuple(shape)))
                              * max(nbytes // max(int(np.prod(shape)), 1), 1))
            except Exception:
                per_dev = nbytes / max(cluster().n_row_shards, 1)
        else:
            per_dev = nbytes / max(cluster().n_row_shards, 1)
        frac = _guardrail_fraction()
        if in_use + per_dev > frac * limit:
            # pressure: let the Cleaner evict cold frames to host RAM,
            # then re-read the allocator before giving up.  Single-process
            # only: the trigger is process-LOCAL memory_stats, and spilling
            # fetches via collectives — divergent triggers across hosts
            # would deadlock, so multi-host keeps the fail-fast behaviour.
            if jax.process_count() == 1:
                from . import cleaner
                n_shards = max(cluster().n_row_shards, 1)
                deficit = int((in_use + per_dev - frac * limit) * n_shards)
                try:
                    freed = cleaner.spill_until(deficit)
                except Exception:     # noqa: BLE001 — spill is best-effort
                    freed = 0
                if freed > 0:
                    in_use = (dev.memory_stats() or {}).get("bytes_in_use",
                                                            in_use)
        if in_use + per_dev > frac * limit:
            raise MemoryError(
                f"placing {nbytes / 1e9:.2f} GB ({per_dev / 1e9:.2f} GB/"
                f"device) would exceed {frac:.0%} of HBM "
                f"({limit / 1e9:.2f} GB/device, {in_use / 1e9:.2f} GB in "
                f"use). Reduce rows/columns, drop unused frames "
                f"(h2o3_tpu.remove), or add devices to the mesh.")
    except MemoryError:
        raise
    except Exception:
        return                            # stats unavailable: no guardrail


def put_sharded(buf: "np.ndarray", sharding) -> "jax.Array":
    """Place a host buffer onto the mesh under ``sharding``.

    Single-process: plain ``device_put``.  Multi-process SPMD: every process
    holds the same full buffer, so build the global array from per-shard
    callbacks — ``device_put``'s cross-process equality check rejects NaN
    padding (NaN != NaN) and non-addressable shards.
    """
    if hasattr(buf, "nbytes") and not isinstance(buf, jax.Array):
        # already-placed jax.Arrays are counted in bytes_in_use; only
        # fresh host->device placements consume new HBM
        _check_hbm_budget(int(buf.nbytes), sharding,
                          getattr(buf, "shape", None))
    if jax.process_count() == 1:
        return jax.device_put(buf, sharding)
    if isinstance(buf, jax.Array) and not isinstance(buf, np.ndarray):
        # already a (possibly global) device array: reshard collectively
        if buf.sharding == sharding:
            return buf
        return jax.jit(lambda x: x, out_shardings=sharding)(buf)
    buf = np.asarray(buf)
    return jax.make_array_from_callback(buf.shape, sharding,
                                        lambda idx: buf[idx])


def fetch(x) -> np.ndarray:
    """Host numpy copy of a (possibly multi-process global) array.

    Row-sharded arrays span non-addressable devices under multi-process
    SPMD; ``process_allgather`` rides the collective plane to reassemble
    them on every host.
    """
    if not hasattr(x, "sharding") or jax.process_count() == 1 \
            or x.is_fully_addressable or x.sharding.is_fully_replicated:
        return np.asarray(x)
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def cluster() -> Cluster:
    """The booted cluster, booting a default one on first use."""
    if _cluster is None:
        return init()
    return _cluster


def shutdown() -> None:
    global _cluster
    with _lock:
        from . import dkv, failure, heartbeat
        failure.stop()
        heartbeat.stop()
        dkv.detach()        # stop the DKV service / forget the coordinator
        _cluster = None
