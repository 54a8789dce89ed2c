"""Elastic fair-share scheduler: admission, packing, cancel, degraded-mode
requeue, membership/quarantine, and restart re-admission.  (Process-kill
variants live in test_chaos.py.)"""

import threading
import time

import numpy as np
import pytest

import h2o3_tpu
from h2o3_tpu.runtime import dkv, failure, heartbeat, recovery
from h2o3_tpu.runtime import observability as obs
from h2o3_tpu.runtime import scheduler as sched_mod
from h2o3_tpu.runtime.job import (CANCELLED, DONE, FAILED, RUNNING, Job,
                                  JobScheduler, scheduler)
from h2o3_tpu.runtime.scheduler import (PRIORITY_ADMIN, PRIORITY_BUILD,
                                        PRIORITY_INTERACTIVE,
                                        ClusterScheduler, Quarantine)


def _binary_frame(seed, n, dest):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=n).astype(np.float32)
    y = np.where(x + 0.3 * rng.normal(size=n) > 0, "Y", "N")
    return h2o3_tpu.H2OFrame({"x": x, "y": y.astype(object)},
                             destination_frame=dest)


# ------------------------------------------------------------ budget mapping
def test_budget_chip_mapping():
    s = ClusterScheduler(capacity=8, queue_limit=4)
    try:
        assert s._chips_for(None, 8) == 4        # default fraction 0.5
        assert s._chips_for(0.125, 8) == 1
        assert s._chips_for(1.0, 8) == 8
        assert s._chips_for(3, 8) == 3
        assert s._chips_for(100, 8) == 8         # capped at the mesh
        with pytest.raises(ValueError):
            s._chips_for(0, 8)
        with pytest.raises(ValueError):
            s._chips_for(-1.5, 8)
        # submit validates the budget before touching the queue
        with pytest.raises(ValueError):
            s.submit(Job("bad budget"), lambda j: None, device_budget=-2)
    finally:
        s.stop()


def test_fit_hosts():
    assert sched_mod._fit_hosts(1, 8) == 1
    assert sched_mod._fit_hosts(2, 8) == 2
    assert sched_mod._fit_hosts(3, 8) == 2       # 3 does not divide 8
    assert sched_mod._fit_hosts(5, 8) == 4
    assert sched_mod._fit_hosts(8, 8) == 8
    assert sched_mod._fit_hosts(2, 6) == 2


# ----------------------------------------------------------- packing + order
def test_small_jobs_pack_beside_large_job():
    s = ClusterScheduler(capacity=8, queue_limit=16)
    order, lock = [], threading.Lock()
    big_started, big_release = threading.Event(), threading.Event()

    def big_fn(job):
        with lock:
            order.append("big-start")
        big_started.set()
        big_release.wait(30)
        with lock:
            order.append("big-end")

    def small_fn(name):
        def fn(job):
            with lock:
                order.append(name)
        return fn

    big = Job("big train")
    try:
        s.submit(big, big_fn, device_budget=0.5, user="alice")
        assert big_started.wait(10)
        smalls = [Job(f"small {i}") for i in range(3)]
        for i, j in enumerate(smalls):
            s.submit(j, small_fn(f"s{i}"), device_budget=1, user=f"u{i}")
        for j in smalls:
            j.join(timeout=30)
        # the smalls completed WHILE the big job still held its chips:
        # concurrency is real, not FIFO-behind-the-big-job
        assert big.status == RUNNING
        assert all(j.status == DONE for j in smalls)
    finally:
        big_release.set()
    big.join(timeout=30)
    assert order[0] == "big-start" and order[-1] == "big-end"
    assert set(order[1:-1]) == {"s0", "s1", "s2"}
    s.stop()


def test_priority_then_fair_share_then_fifo():
    s = ClusterScheduler(capacity=1, queue_limit=16)
    order, lock = [], threading.Lock()
    started, release = threading.Event(), threading.Event()

    def blocker_fn(job):
        started.set()
        release.wait(30)

    def named(name):
        def fn(job):
            with lock:
                order.append(name)
        return fn

    blocker = Job("blocker")
    try:
        s.submit(blocker, blocker_fn, device_budget=1)
        assert started.wait(10)
        ja, jb, jadm = Job("build a"), Job("build b"), Job("admin ping")
        s.submit(ja, named("a"), priority=PRIORITY_BUILD,
                 device_budget=1, user="a")
        s.submit(jb, named("b"), priority=PRIORITY_BUILD,
                 device_budget=1, user="b")
        s.submit(jadm, named("admin"), priority=PRIORITY_ADMIN,
                 device_budget=1, user="a")
        with s._cv:                  # tenant "a" has burned chip-seconds
            s._usage["a"] = 100.0
            s._usage["b"] = 0.0
    finally:
        release.set()
    for j in (ja, jb, jadm, blocker):
        j.join(timeout=30)
    # admin priority first, then the under-served tenant, then FIFO
    assert order == ["admin", "b", "a"]
    s.stop()


# ---------------------------------------------------------------- admission
def test_admission_queue_full_rejects():
    s = ClusterScheduler(capacity=1, queue_limit=2)
    started, release = threading.Event(), threading.Event()
    blocker = Job("blocker")
    q1, q2 = Job("q1"), Job("q2")
    try:
        s.submit(blocker, lambda j: (started.set(), release.wait(30)),
                 device_budget=1)
        assert started.wait(10)
        s.submit(q1, lambda j: None, device_budget=1)
        s.submit(q2, lambda j: None, device_budget=1)
        before = obs.counter("sched_admission_rejected_total",
                             reason="queue_full").value
        overflow = Job("q3")
        with pytest.raises(RuntimeError, match="admission queue full"):
            s.submit(overflow, lambda j: None, device_budget=1)
        if obs.enabled():
            assert obs.counter("sched_admission_rejected_total",
                               reason="queue_full").value == before + 1
        dkv.remove(overflow.key)
        q1.cancel()
        q2.cancel()
        assert q1.status == CANCELLED and q2.status == CANCELLED
    finally:
        release.set()
    blocker.join(timeout=30)
    s.stop()


# ------------------------------------------------------------------- cancel
def test_cancel_queued_job_never_runs():
    s = ClusterScheduler(capacity=1, queue_limit=8)
    started, release = threading.Event(), threading.Event()
    ran = []
    blocker, victim = Job("blocker"), Job("victim")
    try:
        s.submit(blocker, lambda j: (started.set(), release.wait(30)),
                 device_budget=1)
        assert started.wait(10)
        s.submit(victim, lambda j: ran.append(1), device_budget=1)
        victim.cancel()
        assert victim.status == CANCELLED
        assert victim.join() is None
        assert not ran                              # fn never executed
        # its WAL-mirrored scheduling record is gone too
        assert dkv.get(sched_mod.SCHED_PREFIX + victim.key) is None
    finally:
        release.set()
    blocker.join(timeout=30)
    assert not ran
    s.stop()


def test_legacy_jobscheduler_cancel_and_escaped_exception():
    js = JobScheduler(workers=1)
    started, release = threading.Event(), threading.Event()
    ran = []
    blocker = Job("blocker")
    try:
        js.submit(blocker, lambda j: (started.set(), release.wait(30)))
        assert started.wait(10)
        victim = Job("victim")
        js.submit(victim, lambda j: ran.append(1))
        victim.cancel()
        assert victim.status == CANCELLED and not ran

        # an exception that escapes Job.run entirely (run itself blows
        # up before any bookkeeping) must still reach the job: joiners
        # are released with the error, never left hanging
        weird = Job("weird")

        def boom_run(fn):
            raise RuntimeError("escaped worker exception")

        weird.run = boom_run
        js.submit(weird, lambda j: None)
    finally:
        release.set()
    blocker.join(timeout=30)
    with pytest.raises(RuntimeError, match="escaped worker exception"):
        weird.join(timeout=30)
    assert weird.status == FAILED and not ran
    js.stop()


def test_sched_assign_injection_reaches_job_fail(cl, monkeypatch):
    failure.reset()
    monkeypatch.setenv("H2O3_TPU_FAULT_INJECT", "sched_assign:0:1:raise")
    s = ClusterScheduler(capacity=4, queue_limit=8)
    job = Job("doomed")
    try:
        s.submit(job, lambda j: "ok", device_budget=1)
        with pytest.raises(failure.InjectedFault):
            job.join(timeout=30)
        assert job.status == FAILED
    finally:
        failure.reset()
        s.stop()
        dkv.remove(sched_mod.SCHED_PREFIX + job.key)


# -------------------------------------------------------------- degraded mode
def test_node_death_requeues_job_with_retry_budget(cl, tmp_path, monkeypatch):
    """A host death mid-job requeues the SAME Job from its journal onto
    the surviving mesh: joiners still get the model, retries == 1."""
    from h2o3_tpu.models import GBM
    monkeypatch.setenv("H2O3_TPU_RECOVERY_DIR", str(tmp_path))
    failure.reset()
    fr = _binary_frame(11, 400, "sched_requeue_fr")
    builder = GBM(response_column="y", ntrees=3, max_depth=2, seed=2)
    job = Job("victim train")
    uri = recovery.journal_start(builder, fr, job)
    assert uri
    job.journal_uri = uri
    started, wedge = threading.Event(), threading.Event()

    def wedged_fn(j):
        started.set()
        wedge.wait(60)     # models a worker blocked in a dead collective

    s = scheduler()        # module singleton: the watchdog path reaches it
    ghost = "sched_ghost_requeue"
    try:
        s.submit(job, wedged_fn, device_budget=0.5, retry_budget=1,
                 user="tenant")
        assert started.wait(15)
        dkv.put(heartbeat.PREFIX + ghost,
                {"ts": time.time() - 1.0, "interval": 0.05, "pid": 1})
        newly = failure.check(hb_interval=0.05)
        assert ghost in newly
        model = job.join(timeout=300)
        assert job.status == DONE
        assert job.retries == 1
        assert model is not None
        assert model.output["ntrees_trained"] == 3
        if obs.enabled():
            assert obs.counter("sched_requeue_total",
                               reason="node_dead").value >= 1
    finally:
        wedge.set()
        failure.reset()
        dkv.remove(heartbeat.PREFIX + ghost)
        dkv.remove(failure.FAILURES_PREFIX + ghost)


def test_node_death_without_retry_budget_fails(cl):
    failure.reset()
    s = scheduler()
    started, wedge = threading.Event(), threading.Event()
    job = Job("doomed train")
    ghost = "sched_ghost_fatal"
    try:
        s.submit(job, lambda j: (started.set(), wedge.wait(60)),
                 device_budget=1, retry_budget=0)
        assert started.wait(15)
        dkv.put(heartbeat.PREFIX + ghost,
                {"ts": time.time() - 1.0, "interval": 0.05, "pid": 1})
        failure.check(hb_interval=0.05)
        with pytest.raises(failure.NodeFailedError):
            job.join(timeout=30)
        assert job.status == FAILED
    finally:
        wedge.set()
        failure.reset()
        dkv.remove(heartbeat.PREFIX + ghost)
        dkv.remove(failure.FAILURES_PREFIX + ghost)
        dkv.remove(sched_mod.SCHED_PREFIX + job.key)


# ------------------------------------------------------------- restart path
def test_readmit_restores_queue_after_restart(cl, tmp_path, monkeypatch):
    """Journal entry + WAL-mirrored !sched/ record ⇒ readmit() re-submits
    the job with its original priority/budget/tenant after a restart."""
    from h2o3_tpu.models import GBM
    monkeypatch.setenv("H2O3_TPU_RECOVERY_DIR", str(tmp_path))
    failure.reset()
    fr = _binary_frame(5, 300, "sched_readmit_fr")
    builder = GBM(response_column="y", ntrees=2, max_depth=2, seed=5)
    orig = Job("original train")
    uri = recovery.journal_start(builder, fr, orig)
    assert uri
    # the scheduling record a WAL rehydration would restore
    dkv.put(sched_mod.SCHED_PREFIX + orig.key, {
        "job": orig.key, "state": "running",
        "priority": PRIORITY_INTERACTIVE, "device_budget": 1.0,
        "retry_budget": 1, "user": "alice"})
    jobs = sched_mod.readmit(block=True)
    assert len(jobs) == 1
    j = jobs[0]
    assert j.status == DONE
    assert j.priority == PRIORITY_INTERACTIVE
    assert j.user == "alice"
    assert j.result is not None
    # superseded record removed; journal consumed by the resumed run
    assert dkv.get(sched_mod.SCHED_PREFIX + orig.key) is None
    assert not list(tmp_path.glob("job_*.json"))


# --------------------------------------------------------------- membership
def test_quarantine_entry_and_exit():
    q = Quarantine(window_s=10.0, max_flaps=2)
    assert q.note_join("h1", now=0.0)
    assert q.note_join("h1", now=1.0)
    assert not q.note_join("h1", now=2.0)        # 3rd flap in the window
    assert q.is_quarantined("h1", now=3.0)
    assert "h1" in q.active(3.0)
    assert not q.note_join("h1", now=5.0)        # still quarantined
    # after the window (and join history) expires, admitted again
    assert q.note_join("h1", now=30.0)
    assert not q.is_quarantined("h1", now=30.0)
    assert q.describe(30.0)["quarantined"] == []


def test_observe_members_flap_bounded():
    s = ClusterScheduler(capacity=8, queue_limit=4, elastic=False)
    s.quarantine = Quarantine(window_s=60.0, max_flaps=2)
    alive = {"status": "alive"}
    armed = 0

    def observe(members, now):
        nonlocal armed
        s.observe_members(members=members, now=now)
        with s._cv:
            if s._pending_rebuild:
                armed += 1
                s._pending_rebuild = False       # fence consumed

    try:
        observe({"h0": alive}, 0.0)              # seeding: no rebuild
        assert armed == 0
        # kill/rejoin h1 three times inside one window
        observe({"h0": alive, "h1": alive}, 1.0)
        observe({"h0": alive}, 2.0)
        observe({"h0": alive, "h1": alive}, 3.0)
        observe({"h0": alive}, 4.0)
        observe({"h0": alive, "h1": alive}, 5.0)
        observe({"h0": alive}, 6.0)
        observe({"h0": alive, "h1": alive}, 7.0)
        # rebuilds bounded by the quarantine policy, not the flap count
        assert armed == 2
        assert "h1" in s.quarantine.active(7.0)
        # window expiry readmits the (now stable) host
        observe({"h0": alive}, 119.0)
        observe({"h0": alive, "h1": alive}, 120.0)
        assert armed == 3
    finally:
        s.stop()


# ------------------------------------------------------- heartbeat edge cases
def test_members_mixed_per_stamp_intervals():
    now = time.time()
    stamps = {
        "mx_fast_alive": {"ts": now - 0.25, "interval": 0.1, "pid": 1},
        "mx_slow_alive": {"ts": now - 0.25, "interval": 5.0, "pid": 2},
        "mx_suspect": {"ts": now - 0.5, "interval": 0.1, "pid": 3},
        "mx_dead": {"ts": now - 2.0, "interval": 0.1, "pid": 4},
    }
    try:
        for name, stamp in stamps.items():
            dkv.put(heartbeat.PREFIX + name, stamp)
        view = heartbeat.members(now=now)
        # each stamp classifies in units of its OWN interval: the same
        # 0.25 s age is 2.5 fast intervals (alive edge) but a fraction
        # of a slow one
        assert view["mx_fast_alive"]["status"] == "alive"
        assert view["mx_slow_alive"]["status"] == "alive"
        assert view["mx_suspect"]["status"] == "suspect"
        assert view["mx_dead"]["status"] == "dead"
    finally:
        for name in stamps:
            dkv.remove(heartbeat.PREFIX + name)


def test_members_gc_removes_long_dead_stamps():
    now = time.time()
    key = heartbeat.PREFIX + "mx_long_gone"
    dkv.put(key, {"ts": now - 11.0, "interval": 0.1, "pid": 9})
    view = heartbeat.members(now=now)      # 110 intervals > the 100 GC bar
    assert "mx_long_gone" not in view
    assert dkv.get(key) is None            # removed from the DKV itself


# ------------------------------------------------------------------ REST/API
def test_scheduler_rest_status(cl):
    from h2o3_tpu.api.server import Api
    out = Api().scheduler_status()
    d = out["scheduler"]
    for k in ("capacity_chips", "used_chips", "free_chips", "queue_limit",
              "elastic", "pending_rebuild", "known_hosts",
              "fair_share_usage", "quarantine", "queued", "running"):
        assert k in d
    assert d["capacity_chips"] >= 1
    assert isinstance(d["queued"], list) and isinstance(d["running"], list)
