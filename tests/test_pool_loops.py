"""One match loop and one consume worker per pool (Cook runs one match
loop per pool, scheduler.clj:1557-1578).

- four pools on four devices through `rest.server.serve` with federation
  placement: each pool's captured cycles judged against the float64
  reference at a tiny size (the benchmark's oracle);
- a pool whose consume is held does not stop the other pools' cycles;
- four pools' consumes run on four distinct threads whatever
  PYTHONHASHSEED is;
- a pool's loop starts when the pool joins the active set and exits
  when it leaves, and no cycle runs on a retired pool.
"""
import json
import os
import subprocess
import sys
import threading
import time

import jax
import pytest

from cook_tpu.backends.base import ClusterRegistry
from cook_tpu.backends.mock import MockCluster, MockHost
from cook_tpu.scheduler.coordinator import Coordinator, SchedulerConfig
from cook_tpu.state.model import InstanceStatus, Job, JobState, new_uuid
from cook_tpu.state.pools import Pool, PoolRegistry
from cook_tpu.state.store import JobStore
from cook_tpu.utils.metrics import registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
POOLS = ("batch", "research", "prod", "dev")


def build(pools=POOLS, interval=0.01, hosts_per_pool=4, later=(),
          cpus=64, runtime_s=1e6, with_cluster=False):
    """A coordinator with an async resident pool per name in `pools`,
    each on a device of its own; `later` pools have hosts but are not
    registered yet."""
    store = JobStore()
    reg_pools = PoolRegistry(pools[0])
    hosts = []
    for p in pools + later:
        if p in pools:
            reg_pools.add(Pool(name=p))
        hosts += [MockHost(f"{p}-h{i}", mem=100_000, cpus=cpus, pool=p)
                  for i in range(hosts_per_pool)]
    cluster = MockCluster(hosts,
                          runtime_fn=lambda s: (runtime_s, True, None))
    reg = ClusterRegistry()
    reg.register(cluster)
    coord = Coordinator(store, reg, pools=reg_pools,
                        config=SchedulerConfig(match_interval_s=interval))
    devs = jax.devices()
    for i, p in enumerate(pools):
        coord.enable_resident(p, synchronous=False,
                              device=devs[i % len(devs)])
    if with_cluster:
        return store, coord, cluster
    return store, coord


def submit(store, pool, n=32):
    store.create_jobs([Job(uuid=new_uuid(), user=f"u{i % 4}",
                           command="true", mem=100, cpus=1, pool=pool)
                       for i in range(n)])


def cycles(pool) -> float:
    return registry.counter("match_cycles_total", pool=pool).value


def loop_threads() -> set:
    return {t.name for t in threading.enumerate()
            if t.name.startswith("match-") and t.is_alive()}


def wait_for(cond, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return cond()


def test_four_pools_on_four_devices_through_the_server():
    """The served path, four pools placed by `federation.groups.<g>.
    devices`: every pool's captured cycles match the float64 reference
    (DRU order, placements) and the backend's ledger."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "tests",
                                      "faults.py"),
         "none", "2150000123", "tiny_fleet.json", "4"],
        capture_output=True, text=True, env=env, timeout=600, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["compared"]
    assert out["samples"]["cycles_checked"] == 4 * 3
    for name in ("dru_gap", "fit_gap", "placement_faults",
                 "input_mismatch", "cycles_checked_missing"):
        c = out["compared"][name]
        assert c["value"] <= c["limit"], (name, c)
    line = next(ln for ln in p.stderr.splitlines()
                if ln.startswith("diag: pool -> chip "))
    assert json.loads(line[len("diag: pool -> chip "):]) == {
        "batch": [0], "research": [2], "prod": [3], "dev": [1]}


def test_held_consume_does_not_stop_the_other_pools():
    store, coord = build()
    for p in POOLS:
        submit(store, p)
    held = threading.Event()
    entered = threading.Event()
    orig = coord._consume_cycle

    def consume(pool, *args):
        if pool == "batch":
            entered.set()
            held.wait(60)
        return orig(pool, *args)

    coord._consume_cycle = consume
    coord.run()
    try:
        assert entered.wait(120), "batch's consume never started"
        # batch's loop blocks once its two backpressure slots are taken
        assert wait_for(lambda: all(cycles(p) >= 1 for p in POOLS))
        time.sleep(0.5)
        before = {p: cycles(p) for p in POOLS}
        time.sleep(1.5)
        after = {p: cycles(p) for p in POOLS}
        assert after["batch"] - before["batch"] == 0, (before, after)
        for p in POOLS[1:]:
            assert after[p] - before[p] >= 3, (p, before, after)
        assert loop_threads() >= {f"match-{p}" for p in POOLS}
    finally:
        held.set()
        coord.stop()


CONSUME_THREADS = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
from tests.test_pool_loops import POOLS, build, submit
from cook_tpu import obs

threads = {}
obs.profiler.add_listener(
    lambda r: r["kind"] == "consume"
    and threads.setdefault(r["pool"], set()).add(r["thread"]))
store, coord = build()
for _ in range(3):
    for p in POOLS:
        submit(store, p, 8)
        coord.match_cycle(p)
coord.drain_resident()
coord.stop()
print(json.dumps({p: sorted(t) for p, t in threads.items()}))
"""


def test_each_pool_consumes_on_its_own_thread_whatever_the_hash_seed():
    """Under the old hash routing both seeds put two of these pools on
    one consume worker."""
    procs = []
    for seed in ("0", "1"):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED=seed,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        procs.append(subprocess.Popen(
            [sys.executable, "-c", CONSUME_THREADS, ROOT], env=env,
            cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    for proc in procs:
        out, err = proc.communicate(timeout=600)
        assert proc.returncode == 0, err[-3000:]
        threads = json.loads(out.strip().splitlines()[-1])
        assert sorted(threads) == sorted(POOLS)
        assert all(len(t) == 1 for t in threads.values()), threads
        assert {t[0] for t in threads.values()} == \
            {f"resident-consumer-{p}" for p in POOLS}


def test_consume_records_carry_their_wait_and_thread():
    """Each async consume record names its worker thread and how long
    the cycle waited for it (`queued_ms`, also the consume_queued_ms
    histogram); each match record names the thread that ran it."""
    from cook_tpu import obs
    store, coord = build(pools=("batch",))
    recs = []
    obs.profiler.add_listener(recs.append)
    hist = registry.histogram("consume_queued_ms", pool="batch")
    n0 = hist.count
    try:
        for _ in range(3):
            submit(store, "batch", 8)
            coord.match_cycle("batch")
        coord.drain_resident()
    finally:
        obs.profiler.remove_listener(recs.append)
        coord.stop()
    cons = [r for r in recs if r["kind"] == "consume"
            and r["pool"] == "batch"]
    match = [r for r in recs if r["kind"] == "match"
             and r["pool"] == "batch"]
    assert len(cons) == 3 and len(match) == 3
    assert all(r["thread"] == "resident-consumer-batch" for r in cons)
    assert all(r["attrs"]["queued_ms"] >= 0.0 for r in cons)
    assert all(r["thread"] == threading.current_thread().name
               for r in match)
    assert hist.count == n0 + 3


def _leave_inactive(coord, pool):
    coord.pools.get(pool).state = "inactive"


def _leave_by_federation(coord, pool):
    # a membership reload narrows the ownership filter
    coord.pool_filter = lambda p: p != pool


@pytest.mark.parametrize("leave", [_leave_inactive, _leave_by_federation],
                         ids=["inactive", "federation"])
def test_loop_starts_and_exits_with_its_pool(leave):
    store, coord = build(pools=("batch", "research"), later=("extra",))
    coord.run()
    try:
        assert wait_for(lambda: loop_threads() >= {"match-batch",
                                                   "match-research"})
        # a pool added at runtime gets a loop of its own
        n0 = cycles("extra")
        coord.pools.add(Pool(name="extra"))
        coord.enable_resident("extra", synchronous=False)
        submit(store, "extra", 4)
        assert wait_for(lambda: "match-extra" in loop_threads())
        assert wait_for(lambda: cycles("extra") >= n0 + 3)
        assert registry.gauge("match_loops").value == 3
        # it leaves the active set and is retired: its loop exits
        # before the state goes, and no cycle runs on it afterwards
        leave(coord, "extra")
        assert coord.retire_resident("extra")
        assert "match-extra" not in loop_threads()
        n = cycles("extra")
        time.sleep(3 * coord.MATCH_LOOP_SCAN_S)
        assert cycles("extra") == n
        assert "match-extra" not in loop_threads()
        assert registry.gauge("match_loops").value == 2
        # the other pools kept cycling
        m = cycles("research")
        assert wait_for(lambda: cycles("research") > m)
    finally:
        coord.stop()
    assert not loop_threads() & {"match-batch", "match-research"}


def test_pool_loops_under_thread_switch_stress():
    """Eight pools' match loops and consume workers, with the backend
    ending their tasks under them and the interpreter switching threads
    every 10 us: every job runs exactly once, and no launch finds its
    host full. A lost update in state the loops share would over-commit
    a host (the backend fails the launch, 99000) or launch a job twice."""
    pools = tuple(f"s{i}" for i in range(8))
    store, coord, cluster = build(pools=pools, interval=0.002,
                                  hosts_per_pool=2, cpus=4, runtime_s=2.0,
                                  with_cluster=True)
    jobs = []
    for p in pools:
        batch = [Job(uuid=new_uuid(), user=f"u{i % 4}", command="true",
                     mem=100, cpus=1, pool=p) for i in range(40)]
        store.create_jobs(batch)
        jobs += batch
    done = threading.Event()

    def tick():
        while not done.wait(0.01):
            cluster.advance(1.0)

    ticker = threading.Thread(target=tick, daemon=True)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ticker.start()
        coord.run()
        assert wait_for(lambda: all(j.state == JobState.COMPLETED
                                    for j in jobs), timeout=120)
    finally:
        done.set()
        ticker.join(timeout=10)
        coord.stop()
        sys.setswitchinterval(old)
    assert not ticker.is_alive()
    assert not loop_threads() & {f"match-{p}" for p in pools}
    for j in jobs:
        assert [(i.status, i.reason_code) for i in j.instances] == \
            [(InstanceStatus.SUCCESS, None)], (j.pool, j.instances)
