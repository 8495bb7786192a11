"""Config validation, leader election, metrics registry, stats monitor,
and the Settings-driven server assembly.
"""
import json
import os
import subprocess
import sys
import time

import pytest

from cook_tpu.config import ConfigError, Settings
from cook_tpu.scheduler.leader import FileLeaderElector, StandaloneElector
from cook_tpu.scheduler.monitor import StatsMonitor, starved_stats
from cook_tpu.state.limits import ShareStore
from cook_tpu.state.model import Job, new_uuid
from cook_tpu.state.store import JobStore
from cook_tpu.obs.metrics import Registry
from cook_tpu.utils.metrics import ConsoleReporter, JsonlReporter


# -- config ------------------------------------------------------------
def test_settings_defaults():
    s = Settings.from_dict({})
    assert s.port == 12321 and s.scheduler.max_jobs_considered == 1024
    assert s.clusters[0].kind == "mock"


def test_settings_full_roundtrip(tmp_path):
    cfg = {
        "port": 1234,
        "pools": [{"name": "gpu", "dru_mode": "gpu"}],
        "clusters": [{"kind": "kube", "name": "k1", "hosts": 2}],
        "scheduler": {"max_jobs_considered": 64},
        "auth": {"scheme": "header", "admins": ["root"]},
        "rate_limits": {"user_submit": {"tokens_per_sec": 10,
                                        "max_tokens": 100,
                                        "enforce": True}},
    }
    p = tmp_path / "cfg.json"
    p.write_text(json.dumps(cfg))
    s = Settings.from_file(str(p))
    assert s.port == 1234 and s.pools[0].dru_mode == "gpu"
    assert s.rate_limits["user_submit"].enforce is True
    assert s.public()["auth"] == {"scheme": "header"}


@pytest.mark.parametrize("bad", [
    {"port": 0},
    {"nonsense_key": 1},
    {"clusters": [{"kind": "marathon"}]},
    {"pools": [{"name": "x", "dru_mode": "weird"}]},
    {"auth": {"scheme": "kerberos"}},
    {"scheduler": {"scaleback": 1.5}},
    {"rate_limits": {"bogus": {}}},
    {"clusters": [{"name": "a"}, {"name": "a"}]},
    {"scheduler": {"launch_fanout_workers": 0}},
    {"scheduler": {"pipeline_depth": -1}},
    {"scheduler": {"pipeline_depth": 9}},
    {"scheduler": {"heartbeat_timeout_s": 0}},
    {"scheduler": {"overload_escalate_after": 0}},
    {"clusters": [{"kind": "agent", "liveness_grace_s": -1.0}]},
])
def test_settings_validation_errors(bad):
    with pytest.raises(ConfigError):
        Settings.from_dict(bad)


def test_launch_pipeline_settings():
    s = Settings.from_dict({})
    assert s.scheduler.launch_fanout_workers == 8
    assert s.launch_group_commit is True
    s = Settings.from_dict({"launch_group_commit": False,
                            "scheduler": {"launch_fanout_workers": 1}})
    assert s.launch_group_commit is False
    assert s.scheduler.launch_fanout_workers == 1


def test_build_scheduler_from_settings():
    from cook_tpu.rest.server import build_scheduler
    store, coord, api = build_scheduler({
        "clusters": [{"kind": "kube", "name": "k1", "hosts": 2}],
        "pools": [{"name": "extra"}]})
    assert {p.name for p in coord.pools.all()} == {"default", "extra"}
    assert coord.clusters.get("k1") is not None
    assert api.plugins is not None


def test_build_scheduler_wires_launch_pipeline():
    from cook_tpu.rest.server import build_scheduler
    store, coord, api = build_scheduler({
        "dev_mode": True,
        "clusters": [{"kind": "agent", "name": "agents"}],
        "scheduler": {"launch_fanout_workers": 3}})
    assert store.group_commit is True
    assert coord.clusters.get("agents").fanout_workers == 3
    store2, coord2, _ = build_scheduler({
        "launch_group_commit": False,
        "clusters": [{"kind": "mock", "hosts": 1}]})
    assert store2.group_commit is False


def test_pipeline_depth_settings_and_wiring():
    """pipeline_depth flows Settings -> SchedulerConfig -> the enabled
    ResidentPool, and native_consume flips the process-wide consume
    fold switch (restored after the test — it is global state)."""
    from cook_tpu.native import consumefold
    from cook_tpu.rest.server import build_scheduler
    s = Settings.from_dict({})
    assert s.scheduler.pipeline_depth == 2
    assert s.scheduler.native_consume is True
    s = Settings.from_dict({"scheduler": {"pipeline_depth": 0,
                                          "native_consume": False}})
    assert s.scheduler.pipeline_depth == 0
    assert s.scheduler.native_consume is False
    was = consumefold.enabled()
    try:
        _, coord, _ = build_scheduler({
            "clusters": [{"kind": "mock", "hosts": 1}],
            "scheduler": {"pipeline_depth": 3}})
        assert coord.config.pipeline_depth == 3
        assert coord._resident["default"].pipeline_depth == 3
        assert consumefold.enabled() is True
        _, coord2, _ = build_scheduler({
            "clusters": [{"kind": "mock", "hosts": 1}],
            "scheduler": {"native_consume": False}})
        assert consumefold.enabled() is False
        assert coord2.config.pipeline_depth == 2
    finally:
        consumefold.set_enabled(was)


def test_heartbeat_timeout_settings_and_wiring():
    """heartbeat_timeout_s flows settings -> HeartbeatWatcher AND
    SchedulerConfig (no more hard-coded 15-minute constant in the
    assembled server)."""
    from cook_tpu.rest.server import build_scheduler
    from cook_tpu.scheduler.heartbeat import HEARTBEAT_TIMEOUT_S
    s = Settings.from_dict({})
    assert s.scheduler.heartbeat_timeout_s == HEARTBEAT_TIMEOUT_S
    s = Settings.from_dict({"scheduler": {"heartbeat_timeout_s": 42.0}})
    assert s.scheduler.heartbeat_timeout_s == 42.0
    _, coord, _ = build_scheduler({
        "clusters": [{"kind": "mock", "hosts": 1}],
        "scheduler": {"heartbeat_timeout_s": 42.0}})
    assert coord.heartbeats.timeout_s == 42.0
    assert coord.config.heartbeat_timeout_s == 42.0
    # default assembly keeps Cook's 15-minute production default
    _, coord2, _ = build_scheduler({"clusters": [{"kind": "mock"}]})
    assert coord2.heartbeats.timeout_s == HEARTBEAT_TIMEOUT_S


def test_build_scheduler_wires_liveness_and_overload():
    from cook_tpu.rest.server import build_scheduler
    _, coord, _ = build_scheduler({
        "dev_mode": True,
        "clusters": [{"kind": "agent", "name": "agents",
                      "agent_heartbeat_timeout_s": 7.0,
                      "liveness_grace_s": 2.0}],
        "scheduler": {"overload_cycle_p99_ms": 123.0}})
    trk = coord.clusters.get("agents").liveness
    assert trk is not None
    assert trk.lease_s == 7.0 and trk.grace_s == 2.0
    assert coord.overload is not None
    assert coord.overload.cycle_p99_ms == 123.0
    # both layers are opt-out: the legacy raw-cutoff sweep and an
    # always-full-fidelity coordinator must stay configurable
    _, coord2, _ = build_scheduler({
        "dev_mode": True,
        "clusters": [{"kind": "agent", "name": "agents",
                      "liveness_enabled": False}],
        "scheduler": {"overload_enabled": False}})
    assert coord2.clusters.get("agents").liveness is None
    assert coord2.overload is None


def test_build_scheduler_wires_optimizer():
    from cook_tpu.rest.server import build_scheduler
    from cook_tpu.scheduler.optimizer import CapacityPlanningOptimizer
    store, coord, api = build_scheduler({
        "clusters": [{"kind": "mock", "hosts": 1}],
        "optimizer": {"optimizer": "capacity-planning",
                      "interval_s": 5.0}})
    cyc = coord.optimizer_cycle
    assert cyc is not None and cyc.interval_s == 5.0
    assert isinstance(cyc.optimizer, CapacityPlanningOptimizer)
    schedule = cyc.cycle()
    assert 0 in schedule
    # absent config -> no cycle
    _, coord2, _ = build_scheduler({"clusters": [{"kind": "mock"}]})
    assert coord2.optimizer_cycle is None


# -- leader election ---------------------------------------------------
def test_standalone_elector():
    calls = []
    e = StandaloneElector("http://me")
    e.start(lambda: calls.append(1))
    assert e.is_leader() and calls == [1]
    assert e.current_leader() == "http://me"


def test_file_elector_single_winner(tmp_path):
    path = str(tmp_path / "leader.lock")
    won = []
    e1 = FileLeaderElector(path, "http://a", retry_interval_s=0.05,
                           on_loss=lambda: won.append("lost-a"))
    e2 = FileLeaderElector(path, "http://b", retry_interval_s=0.05,
                           on_loss=lambda: won.append("lost-b"))
    e1.start(lambda: won.append("a"))
    deadline = time.monotonic() + 5
    while "a" not in won and time.monotonic() < deadline:
        time.sleep(0.01)
    e2.start(lambda: won.append("b"))
    time.sleep(0.3)
    assert won == ["a"]            # e2 never acquires while e1 holds
    assert e1.is_leader() and not e2.is_leader()
    assert e2.current_leader() == "http://a"
    # e1 releases; e2 takes over
    e1.stop()
    deadline = time.monotonic() + 5
    while "b" not in won and time.monotonic() < deadline:
        time.sleep(0.01)
    assert "b" in won and e2.is_leader()
    e2.stop()


def test_file_elector_loss_on_lease_deletion(tmp_path):
    path = str(tmp_path / "leader.lock")
    events = []
    e = FileLeaderElector(path, "http://a", retry_interval_s=0.05,
                          on_loss=lambda: events.append("loss"))
    e.start(lambda: events.append("lead"))
    deadline = time.monotonic() + 5
    while "lead" not in events and time.monotonic() < deadline:
        time.sleep(0.01)
    os.unlink(path)               # the ZK-session-expired analog
    deadline = time.monotonic() + 5
    while "loss" not in events and time.monotonic() < deadline:
        time.sleep(0.01)
    assert events == ["lead", "loss"]


def test_cross_process_exclusion(tmp_path):
    """A second PROCESS cannot take the lock (fcntl is per-process)."""
    path = str(tmp_path / "leader.lock")
    e = FileLeaderElector(path, "http://parent", retry_interval_s=0.05)
    e.start(lambda: None)
    deadline = time.monotonic() + 5
    while not e.is_leader() and time.monotonic() < deadline:
        time.sleep(0.01)
    code = (
        "import fcntl, os, sys\n"
        f"fd = os.open({path!r}, os.O_RDWR)\n"
        "try:\n"
        "    fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)\n"
        "    sys.exit(1)\n"
        "except OSError:\n"
        "    sys.exit(0)\n")
    r = subprocess.run([sys.executable, "-c", code])
    assert r.returncode == 0
    e.stop()


# -- metrics -----------------------------------------------------------
def test_metric_kinds():
    reg = Registry()
    reg.counter("c").inc(5)
    reg.counter("c").inc(-2)
    reg.meter("m").mark(10)
    # unit-wide buckets: the bucket-interpolated p95 is good to one unit
    unit = tuple(float(b) for b in range(1, 101))
    for v in range(100):
        reg.histogram("h", buckets=unit).update(v)
    with reg.timer("t").time():
        pass
    snap = reg.snapshot()
    assert snap["c"]["value"] == 3
    assert snap["m"]["count"] == 10
    assert snap["h"]["count"] == 100 and 94 <= snap["h"]["p95"] <= 96
    assert snap["t"]["count"] == 1


def test_jsonl_reporter(tmp_path):
    reg = Registry()
    reg.counter("x").inc()
    path = str(tmp_path / "metrics.jsonl")
    rep = JsonlReporter(reg, path, interval_s=0.05)
    rep.start()
    time.sleep(0.2)
    rep.stop()
    rows = [json.loads(l) for l in open(path)]
    assert rows and rows[0]["metrics"]["x"]["value"] == 1


# -- stats monitor -----------------------------------------------------
def mkjob(user, mem=100, cpus=1, **kw):
    return Job(uuid=new_uuid(), user=user, command="x", mem=mem, cpus=cpus,
               **kw)


def test_starved_hungry_satisfied():
    store = JobStore()
    shares = ShareStore()
    shares.set("default", "default", mem=500, cpus=5)
    reg = Registry()
    mon = StatsMonitor(store, shares, reg)

    # alice: running 100 MB (below 500 share), waiting more → starved
    a_run, a_wait = mkjob("alice"), mkjob("alice")
    # bob: running 600 MB (over share), waiting → hungry
    b_runs = [mkjob("bob", mem=300) for _ in range(2)]
    b_wait = mkjob("bob")
    # carol: running only → satisfied
    c_run = mkjob("carol")
    store.create_jobs([a_run, a_wait, *b_runs, b_wait, c_run])
    for j in (a_run, *b_runs, c_run):
        store.create_instance(j.uuid, "h", "mock")

    out = mon.collect("default")
    assert out["starved"] == ["alice"]
    assert out["hungry"] == ["bob"]
    assert out["satisfied"] == ["carol"]
    assert reg.counter("starved.users.pool-default").value == 1
    assert reg.counter("running.alice.mem.pool-default").value == 100

    # alice's waiting job gets killed → she leaves starved; counters clear
    store.kill_job(a_wait.uuid)
    out = mon.collect("default")
    assert out["starved"] == []
    assert reg.counter("starved.alice.mem.pool-default").value == 0


def test_starvation_amount_is_capped_by_share():
    running = {"u": {"mem": 100.0, "cpus": 1.0}}
    waiting = {"u": {"mem": 10_000.0, "cpus": 100.0, "jobs": 5}}
    shares = ShareStore()
    shares.set("u", "default", mem=500, cpus=5)
    out = starved_stats(running, waiting, shares, "default")
    assert out["u"]["mem"] == 400.0 and out["u"]["cpus"] == 4.0


def test_graphite_reporter_plaintext_protocol():
    import socket
    import threading

    from cook_tpu.utils.metrics import GraphiteReporter

    received = []
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]

    def accept():
        conn, _ = srv.accept()
        with conn:
            buf = b""
            while True:
                chunk = conn.recv(4096)
                if not chunk:
                    break
                buf += chunk
            received.append(buf.decode())

    t = threading.Thread(target=accept)
    t.start()
    reg = Registry()
    reg.counter("match.cycles").inc(7)
    reg.timer("cycle ms").update(12.5)
    rep = GraphiteReporter(reg, "127.0.0.1", port, prefix="cook")
    rep.publish(reg.snapshot())
    t.join(timeout=5)
    srv.close()
    lines = received[0].strip().splitlines()
    assert any(line.startswith("cook.match.cycles 7.0 ") for line in lines)
    # spaces in metric names are sanitized, 3 fields per line
    assert all(len(line.split(" ")) == 3 for line in lines)
    assert any("cycle_ms" in line for line in lines)


def test_ha_failover_end_to_end(tmp_path):
    """The master/slave flow (test_master_slave.py in the reference):
    leader A persists jobs to the event log; on leadership loss the
    standby B acquires the lease, rebuilds the store from the log, and
    schedules the surviving queue."""
    from cook_tpu.backends.base import ClusterRegistry
    from cook_tpu.backends.mock import MockCluster, MockHost
    from cook_tpu.scheduler.coordinator import Coordinator
    from cook_tpu.scheduler.leader import FileLeaderElector
    from cook_tpu.state.model import Job, JobState, new_uuid
    from cook_tpu.state.store import JobStore

    import threading

    lock = str(tmp_path / "leader.lock")
    log = str(tmp_path / "events.log")

    # --- scheduler A wins leadership and accepts jobs ---
    became_a = threading.Event()
    el_a = FileLeaderElector(lock, "http://a", retry_interval_s=0.05,
                             on_loss=lambda: None)
    el_a.start(became_a.set)
    assert became_a.wait(5) and el_a.is_leader()

    store_a = JobStore(log_path=log)
    jobs = [Job(uuid=new_uuid(), user="alice", command="true",
                mem=10, cpus=1) for _ in range(5)]
    store_a.create_jobs(jobs)
    # one job even gets killed pre-failover; the log must carry that
    store_a.kill_job(jobs[4].uuid)

    # --- A dies (lease released); B takes over ---
    became_b = threading.Event()
    el_b = FileLeaderElector(lock, "http://b", retry_interval_s=0.05,
                             on_loss=lambda: None)
    el_b.start(became_b.set)
    time.sleep(0.2)
    assert not el_b.is_leader()          # A still holds the lease
    el_a.stop()
    assert became_b.wait(5) and el_b.is_leader()
    assert el_b.current_leader() == "http://b"

    # --- B rebuilds from the log and schedules the queue ---
    store_b = JobStore.restore(log_path=log)
    assert len(store_b.jobs) == 5
    assert store_b.jobs[jobs[4].uuid].state == JobState.COMPLETED
    cluster = MockCluster([MockHost("h0", mem=1000, cpus=16)])
    reg = ClusterRegistry()
    reg.register(cluster)
    coord_b = Coordinator(store_b, reg)
    stats = coord_b.match_cycle()
    assert stats.matched == 4            # the 4 surviving jobs run
    el_b.stop()


def test_server_resident_match_config(tmp_path):
    """scheduler.resident_match wires the device-resident path into the
    built coordinator for every active pool."""
    from cook_tpu.config import Settings
    from cook_tpu.rest.server import build_scheduler

    cfg = Settings.from_dict({
        "scheduler": {"resident_match": True},
        "clusters": [{"kind": "mock", "name": "m", "hosts": 2}],
    })
    store, coord, api = build_scheduler(cfg)
    try:
        assert "default" in coord._resident
        from cook_tpu.state.model import Job, new_uuid
        job = Job(uuid=new_uuid(), user="alice", command="true",
                  mem=64.0, cpus=1.0)
        store.create_jobs([job])
        coord.match_cycle()
        coord.drain_resident()
        assert job.state.value == "running"
    finally:
        coord.stop()


def test_unmet_device_claims_fail_the_boot():
    """A shard or placement claim beyond the visible devices is a boot
    error, never a quiet fall back to one device."""
    import jax

    from cook_tpu.config import ConfigError
    from cook_tpu.rest.server import _resident_devices, build_scheduler

    devs = jax.devices()
    n = len(devs)
    with pytest.raises(ConfigError, match="resident_shard_devices"):
        _resident_devices(n + 1, {})
    with pytest.raises(ConfigError, match="placed on device"):
        _resident_devices(0, {"p": n})
    shard, placed = _resident_devices(2, {"p": n - 1})
    assert shard == devs[:2] and placed == {"p": devs[n - 1]}
    assert _resident_devices(0, {}) == (None, {})
    with pytest.raises(ConfigError, match="resident_shard_devices"):
        build_scheduler({"scheduler": {"resident_shard_devices": n + 1}})
