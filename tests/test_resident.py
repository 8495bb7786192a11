"""Device-resident match path (scheduler/resident.py).

Verifies the kernel<->production bridge: delta shipping keeps the
device state exactly equal to a from-scratch rebuild after arbitrary
store churn, the resident cycle launches the same work the legacy cycle
does, and the capacity accounting never leaks across launch/complete/
kill/retry races.
"""
import numpy as np
import pytest

from cook_tpu.backends.base import ClusterRegistry
from cook_tpu.backends.mock import MockCluster, MockHost
from cook_tpu.scheduler.coordinator import Coordinator, SchedulerConfig
from cook_tpu.state.limits import QuotaStore, RateLimiter, ShareStore
from cook_tpu.state.model import (Group, InstanceStatus, Job, JobState,
                                  new_uuid)
from cook_tpu.state.store import JobStore


def mkjob(user="alice", mem=100, cpus=1, **kw):
    return Job(uuid=new_uuid(), user=user, command="true", mem=mem,
               cpus=cpus, **kw)


def build(hosts=None, runtime_fn=None, config=None, quotas=None,
          n_hosts=2, **kw):
    store = JobStore()
    cluster = MockCluster(hosts or [
        MockHost(f"h{i}", mem=1000, cpus=16) for i in range(n_hosts)
    ], runtime_fn=runtime_fn)
    reg = ClusterRegistry()
    reg.register(cluster)
    coord = Coordinator(store, reg, config=config, quotas=quotas, **kw)
    return store, cluster, coord


def fetch_state(rp):
    import jax
    return jax.tree.map(np.asarray, rp.state)


def assert_state_matches_rebuild(coord, pool="default"):
    """THE invariant: after any event sequence, the delta-maintained
    device state describes the same scheduling problem as a fresh
    rebuild from the store (same multiset of valid pending/running
    rows, same host availability)."""
    rp = coord._resident[pool]
    from cook_tpu.scheduler.resident import ResidentPool, _NeedResync
    try:
        rp.flush()   # fold queued events in, no new match
        # fold host-universe drift too: a fully-consumed host drops out
        # of offers and reappears when capacity frees WITHOUT a
        # generation bump — production picks that up at the next light
        # rung's probe; do it here so fresh-only hosts below are real
        # failures, not the blind window
        if not rp.reconcile_hosts():
            rp.resync()
    except _NeedResync:
        # row capacity outgrown mid-churn: production falls back to a
        # full rebuild (which re-sizes) — mirror that here
        rp.resync()
    live = fetch_state(rp)
    fresh = ResidentPool(coord, pool, synchronous=True)
    ref = fetch_state(fresh)

    def rows(state, block, fields, key_fields):
        v = state[block]["valid"]
        out = set()
        for i in np.flatnonzero(v):
            out.add(tuple(round(float(state[block][f][i]), 4)
                          for f in key_fields))
        return out

    pend_key = ("user", "mem", "cpus", "gpus", "priority", "ports")
    run_key = ("user", "mem", "cpus", "priority")
    assert rows(live, "pend", None, pend_key) == \
        rows(ref, "pend", None, pend_key)
    assert rows(live, "run", None, run_key) == \
        rows(ref, "run", None, run_key)
    # host availability: per-host equality on the shared universe
    # (rebuild reads the backend's truth; the live state chained on
    # device). A FULLY-consumed host emits no offer — backends skip
    # zero-availability hosts — so a fresh rebuild can lack a host the
    # live state legitimately still holds; such live-only hosts must be
    # at (near) zero availability, nothing else.
    common = sorted(rp.host_ids.keys() & fresh.host_ids.keys())
    li = [rp.host_ids[h] for h in common]
    fi = [fresh.host_ids[h] for h in common]
    for f in ("mem", "cpus", "gpus"):
        np.testing.assert_allclose(live["host"][f][li],
                                   ref["host"][f][fi], atol=1e-3)
    for h in rp.host_ids.keys() - fresh.host_ids.keys():
        i = rp.host_ids[h]
        assert live["host"]["mem"][i] <= 1e-3, (h, live["host"]["mem"][i])
        assert live["host"]["cpus"][i] <= 1e-3, (h, live["host"]["cpus"][i])
    # the live state must never MISS an offered host (the reconcile
    # above folded any legitimate reappearance window)
    assert not (fresh.host_ids.keys() - rp.host_ids.keys()), \
        fresh.host_ids.keys() - rp.host_ids.keys()


def test_resident_basic_launch_and_complete():
    store, cluster, coord = build()
    coord.enable_resident()
    job = mkjob()
    store.create_jobs([job])
    stats = coord.match_cycle()
    assert stats.matched == 1
    assert job.state == JobState.RUNNING
    cluster.advance(120.0)
    assert job.state == JobState.COMPLETED and job.success
    coord.match_cycle()
    assert_state_matches_rebuild(coord)


def test_resident_equals_legacy_launch_set():
    """Same store scenario through legacy, inline-resident, AND the
    double-buffered pipelined resident path -> same launched jobs
    (the pipelined consume lags dispatch by a cycle, so it drains
    before counting)."""
    def scenario(coord, store):
        jobs = [mkjob(user=f"u{i % 3}", mem=50 + 10 * (i % 5), cpus=1)
                for i in range(40)]
        store.create_jobs(jobs)
        coord.match_cycle()
        if hasattr(coord, "drain_resident"):
            coord.drain_resident()
        return {j.uuid for j in jobs if j.state == JobState.RUNNING}

    store_a, _, coord_a = build(n_hosts=4)
    launched_legacy = scenario(coord_a, store_a)
    store_b, _, coord_b = build(n_hosts=4)
    coord_b.enable_resident()
    launched_res = scenario(coord_b, store_b)
    assert len(launched_legacy) == len(launched_res)
    store_c, _, coord_c = build(n_hosts=4)
    coord_c.enable_resident(pipeline_depth=1)
    launched_pip = scenario(coord_c, store_c)
    assert len(launched_legacy) == len(launched_pip)


def test_pipelined_resident_matches_inline_across_cycles():
    """Differential oracle for the double-buffer: several cycles of
    rolling submissions produce the IDENTICAL launch set through the
    pipelined path and the classic inline path — the device-side
    invalidation + chained capacity make the overlap invisible to
    assignments."""
    def scenario(coord, store):
        for c in range(4):
            jobs = [mkjob(user=f"u{(c * 7 + i) % 3}",
                          mem=50 + 10 * ((c + i) % 5), cpus=1)
                    for i in range(12)]
            store.create_jobs(jobs)
            coord.match_cycle()
        coord.drain_resident()
        return {u for u, j in store.jobs.items()
                if j.state == JobState.RUNNING}

    store_a, _, coord_a = build(n_hosts=4)
    coord_a.enable_resident()
    inline = scenario(coord_a, store_a)
    store_b, _, coord_b = build(n_hosts=4)
    coord_b.enable_resident(pipeline_depth=1)
    pipelined = scenario(coord_b, store_b)
    assert len(inline) == len(pipelined)
    # deterministic seed-0 workload: the assignments, not just the
    # count, must agree (uuids differ per store; compare by job NAME
    # would need names — compare multiset of (user, mem) instead)
    sig = lambda store, uuids: sorted(
        (store.jobs[u].user, store.jobs[u].mem) for u in uuids)
    assert sig(store_a, inline) == sig(store_b, pipelined)
    assert_state_matches_rebuild(coord_b)
    coord_a.stop()
    coord_b.stop()


def test_resident_failure_retry_then_success():
    fates = iter([(10.0, False, 1003), (10.0, True, None)])
    store, cluster, coord = build(runtime_fn=lambda spec: next(fates))
    coord.enable_resident()
    job = mkjob(max_retries=2)
    store.create_jobs([job])
    coord.match_cycle()
    cluster.advance(11)
    assert job.state == JobState.WAITING
    coord.match_cycle()   # novel-host: retry must land on the other host
    assert job.state == JobState.RUNNING
    assert job.instances[1].hostname != job.instances[0].hostname
    cluster.advance(11)
    assert job.state == JobState.COMPLETED and job.success
    coord.match_cycle()
    assert_state_matches_rebuild(coord)


def test_resident_kill_while_pending():
    store, cluster, coord = build()
    coord.enable_resident()
    jobs = [mkjob() for _ in range(5)]
    store.create_jobs(jobs)
    store.kill_job(jobs[0].uuid)
    coord.match_cycle()
    assert jobs[0].state == JobState.COMPLETED
    assert all(j.state == JobState.RUNNING for j in jobs[1:])
    assert_state_matches_rebuild(coord)


def test_resident_quota_enforced():
    quotas = QuotaStore()
    quotas.set("alice", "default", cpus=2)
    store, cluster, coord = build(quotas=quotas, n_hosts=4)
    coord.enable_resident()
    jobs = [mkjob(cpus=1) for _ in range(6)]
    store.create_jobs(jobs)
    stats = coord.match_cycle()
    assert stats.matched == 2
    running = [j for j in jobs if j.state == JobState.RUNNING]
    assert len(running) == 2


def test_resident_constraint_mask():
    hosts = [MockHost("special", mem=1000, cpus=16,
                      attributes={"rack": "a"}),
             MockHost("other", mem=1000, cpus=16,
                      attributes={"rack": "b"})]
    store, cluster, coord = build(hosts=hosts)
    coord.enable_resident()
    job = mkjob(constraints=[["rack", "EQUALS", "a"]])
    store.create_jobs([job])
    coord.match_cycle()
    assert job.state == JobState.RUNNING
    assert job.instances[0].hostname == "special"


def test_resident_group_unique_placement():
    hosts = [MockHost(f"h{i}", mem=1000, cpus=16) for i in range(3)]
    store, cluster, coord = build(hosts=hosts)
    coord.enable_resident()
    g = Group(uuid=new_uuid(), name="g",
              host_placement={"type": "unique"})
    jobs = [mkjob(group=g.uuid) for _ in range(3)]
    store.create_jobs(jobs, groups=[g])
    coord.match_cycle()
    used = [j.instances[0].hostname for j in jobs
            if j.state == JobState.RUNNING]
    assert len(used) == len(set(used)) == 3


def test_resident_churn_state_equivalence():
    """Random submit/kill/complete/retry churn; after every few cycles
    the delta-maintained device state must equal a fresh rebuild."""
    rng = np.random.default_rng(7)
    fates = {}

    def runtime(spec):
        return fates.get(spec.job_uuid, (30.0, True, None))

    store, cluster, coord = build(
        n_hosts=6, runtime_fn=runtime,
        config=SchedulerConfig(max_jobs_considered=64))
    coord.enable_resident()
    live_jobs = []
    for step in range(12):
        n_new = int(rng.integers(1, 8))
        jobs = [mkjob(user=f"u{int(rng.integers(0, 4))}",
                      mem=float(rng.integers(20, 200)),
                      cpus=float(rng.integers(1, 4)),
                      max_retries=2) for _ in range(n_new)]
        for j in jobs:
            if rng.random() < 0.3:
                fates[j.uuid] = (float(rng.integers(5, 40)),
                                 bool(rng.random() < 0.5), 1003)
        store.create_jobs(jobs)
        live_jobs.extend(jobs)
        if live_jobs and rng.random() < 0.5:
            store.kill_job(live_jobs[
                int(rng.integers(0, len(live_jobs)))].uuid)
        coord.match_cycle()
        cluster.advance(float(rng.integers(0, 25)))
        if step % 3 == 2:
            coord.match_cycle()
            assert_state_matches_rebuild(coord)
    # steady state: everything eventually completes
    for _ in range(30):
        coord.match_cycle()
        cluster.advance(50.0)
    assert all(j.state != JobState.RUNNING or j.active_instances
               for j in live_jobs)


def test_resident_async_consumer():
    """Asynchronous consume: dispatch returns before writeback; drain
    makes all effects visible; no double-launch across the lag."""
    store, cluster, coord = build(n_hosts=4)
    coord.enable_resident(synchronous=False)
    jobs = [mkjob() for _ in range(20)]
    store.create_jobs(jobs)
    coord.match_cycle()
    coord.drain_resident()
    running = [j for j in jobs if j.state == JobState.RUNNING]
    assert len(running) == 20
    # a second cycle must not double-launch anything
    coord.match_cycle()
    coord.drain_resident()
    assert all(len(j.instances) == 1 for j in jobs)
    coord.stop()


def test_rebuild_gives_no_credit_for_a_task_the_backend_already_ended():
    """A task that ended at the backend, with its status not yet applied
    to the store, is free capacity in the offers a full rebuild reads
    but still running in the store: its terminal, applied after the
    rebuild, must not credit the host a second time (two jobs would then
    be matched onto room for one, and the backend fail one of them)."""
    store, cluster, coord = build(n_hosts=1)
    coord.enable_resident()
    a = mkjob(mem=100, cpus=16)
    store.create_jobs([a])
    assert coord.match_cycle().matched == 1
    held = []
    cluster.set_status_callback(
        lambda *args, **kw: held.append((args, kw)))
    assert cluster.advance(100.0) == 1     # ended at the backend only
    assert a.state == JobState.RUNNING
    coord._resident["default"].resync()
    for args, kw in held:                  # the status lands late
        coord._status_entry(*args, **kw)
    cluster.set_status_callback(coord._status_entry)
    assert a.state == JobState.COMPLETED
    b, c = mkjob(mem=100, cpus=16), mkjob(mem=100, cpus=16)
    store.create_jobs([b, c])
    assert coord.match_cycle().matched == 1
    insts = [i for j in (b, c) for i in j.instances]
    assert len(insts) == 1 and insts[0].status == InstanceStatus.RUNNING
    assert_state_matches_rebuild(coord)


def test_retention_gc_between_resident_cycles():
    """The retention loop (r5) retires completed jobs while the
    resident path cycles: retire events are invisible to the mirrors
    by design (completed jobs hold no resident rows), so the
    delta-maintained device state must still equal a fresh rebuild
    after retirement, and subsequent cycles must keep launching."""
    store, cluster, coord = build(
        n_hosts=4, runtime_fn=lambda s: (5.0, True, None))
    coord.enable_resident()
    for round_no in range(4):
        store.create_jobs([mkjob() for _ in range(8)])
        coord.match_cycle()
        cluster.advance(10.0)       # everything completes
        coord.match_cycle()         # absorb completions
        # retire immediately: -1 keeps this off the same-millisecond
        # edge of the strict end < cutoff comparison
        n = store.gc_completed(older_than_ms=-1)
        assert n > 0, f"round {round_no}: nothing retired"
        assert_state_matches_rebuild(coord)
    # the store is bounded: only the latest unretired churn remains
    assert len(store.jobs) <= 16
    coord.stop()


def test_consume_trace_and_queue_wait_metrics():
    """Per-consume phase records (coordinator.consume_trace) are the
    raw material for the bench's MEASURED co-located histogram: every
    consumed cycle appends one record whose phases sum ≈ its total,
    keyed by the dispatch cycle number; async mode also publishes the
    producer's queue-backpressure wait."""
    store, cluster, coord = build(n_hosts=4)
    coord.enable_resident()
    store.create_jobs([mkjob() for _ in range(8)])
    for _ in range(3):
        coord.match_cycle()
    trace = list(coord.consume_trace)
    assert len(trace) == 3
    assert [r["cycle"] for r in trace] == [0, 1, 2]
    for r in trace:
        assert r["pool"] == "default"
        for k in ("total_ms", "readback_ms", "loop_ms", "txn_ms",
                  "backend_ms"):
            assert r[k] >= 0.0, (k, r)
        phase_sum = (r["readback_ms"] + r["loop_ms"] + r["txn_ms"]
                     + r["backend_ms"])
        assert phase_sum <= r["total_ms"] + 1.0, r
    assert trace[0]["matched"] == 8

    # async mode: the producer's put on the depth-2 consume queue is
    # timed — the bench subtracts it as consumer backpressure
    store2, cluster2, coord2 = build(n_hosts=4)
    coord2.enable_resident(synchronous=False)
    store2.create_jobs([mkjob() for _ in range(4)])
    coord2.match_cycle()
    assert coord2.metrics["match.default.queue_wait_ms"] >= 0.0
    coord2.drain_resident()
    assert any(r["matched"] == 4 for r in coord2.consume_trace)
    coord2.stop()
    coord.stop()


def test_resident_ports_assignment():
    hosts = [MockHost("h0", mem=1000, cpus=16, port_range=(31000, 31003))]
    store, cluster, coord = build(hosts=hosts)
    coord.enable_resident()
    jobs = [mkjob(ports=2) for _ in range(3)]
    store.create_jobs(jobs)
    coord.match_cycle()
    running = [j for j in jobs if j.state == JobState.RUNNING]
    # 4 free ports -> exactly 2 jobs of 2 ports land
    assert len(running) == 2
    got = [p for j in running for p in j.instances[0].ports]
    assert len(got) == len(set(got)) == 4
    for j in running:
        env_ports = {j.instances[0].ports[0], j.instances[0].ports[1]}
        assert len(env_ports) == 2


def test_resident_host_set_change_resyncs():
    store, cluster, coord = build(n_hosts=2)
    coord.enable_resident()
    jobs = [mkjob(cpus=16) for _ in range(4)]
    store.create_jobs(jobs)
    coord.match_cycle()
    assert sum(j.state == JobState.RUNNING for j in jobs) == 2
    from cook_tpu.backends.mock import MockHost as MH
    cluster.add_host(MH("h-new", mem=4000, cpus=64))
    coord.match_cycle()   # detects generation bump, resyncs, matches
    assert sum(j.state == JobState.RUNNING for j in jobs) == 4


def test_resident_accepts_plugin_config():
    """r4: the resident path supports launch plugins (the r3 refusal is
    gone — fast and full-featured are no longer disjoint modes)."""
    from cook_tpu.plugins import (CachedLaunchFilter, LaunchFilter,
                                  PluginRegistry)
    store, cluster, coord = build()
    coord.plugins = PluginRegistry(
        launch=CachedLaunchFilter(LaunchFilter()))
    coord.enable_resident()
    j = mkjob()
    store.create_jobs([j])
    store.commit_jobs([j.uuid])
    stats = coord.match_cycle()
    assert stats.matched == 1


def test_resident_launch_filter_defers_then_launches():
    """Launch-filter parity (plugins/launch.clj:59-121): a deferred job
    is refused at consume, its row parks until the cache expiry, and it
    launches once the filter accepts."""
    import time as _time

    from cook_tpu.plugins import (CachedLaunchFilter, LaunchFilter,
                                  PluginRegistry, accepted, deferred)

    class Gate(LaunchFilter):
        def __init__(self):
            self.open = False

        def check_job_launch(self, job):
            return accepted() if self.open else deferred(for_s=0.05)

    gate = Gate()
    store, cluster, coord = build()
    coord.plugins = PluginRegistry(
        launch=CachedLaunchFilter(gate, age_out_s=0.2))
    coord.enable_resident()
    job = mkjob()
    store.create_jobs([job])
    stats = coord.match_cycle()
    # matched on device but refused at consume; capacity credited back
    assert stats.matched == 0
    assert job.state == JobState.WAITING
    rp = coord._resident["default"]
    assert job.uuid in rp._deferred
    gate.open = True
    _time.sleep(0.3)          # past the defer expiry (age_out_s/4 floor)
    coord.match_cycle()       # drain revalidates the row
    stats = coord.match_cycle()
    assert job.state == JobState.RUNNING
    assert_state_matches_rebuild(coord)


def test_resident_launch_filter_age_out_forces_launch():
    """A job deferred past age_out_s launches regardless — plugins
    can't starve a job forever (launch.clj age-out)."""
    import time as _time

    from cook_tpu.plugins import (CachedLaunchFilter, LaunchFilter,
                                  PluginRegistry, deferred)

    class Never(LaunchFilter):
        def check_job_launch(self, job):
            return deferred(for_s=0.02)

    store, cluster, coord = build()
    coord.plugins = PluginRegistry(
        launch=CachedLaunchFilter(Never(), age_out_s=0.1))
    coord.enable_resident()
    job = mkjob()
    store.create_jobs([job])
    coord.match_cycle()
    assert job.state == JobState.WAITING
    deadline = _time.monotonic() + 5.0
    while job.state == JobState.WAITING and _time.monotonic() < deadline:
        _time.sleep(0.05)
        coord.match_cycle()
    assert job.state == JobState.RUNNING


def test_resident_adjuster_pool_migration():
    """Adjuster parity (plugins/adjustment.clj): a per-cycle adjuster
    migrating a user's jobs out of the pool removes them from this
    pool's resident state."""
    from cook_tpu.plugins import JobAdjuster, PluginRegistry

    class Mover(JobAdjuster):
        def adjust_job(self, job):
            if job.user == "bob":
                job.pool = "gpu-pool"
            return job

    store, cluster, coord = build()
    coord.plugins = PluginRegistry(adjuster=Mover())
    coord.enable_resident()
    a, b = mkjob(user="alice"), mkjob(user="bob")
    store.create_jobs([a, b])
    stats = coord.match_cycle()
    assert stats.matched == 1
    assert a.state == JobState.RUNNING
    assert b.state == JobState.WAITING
    rp = coord._resident["default"]
    assert b.uuid not in rp.pend_row     # lives in gpu-pool's cycle now


def test_resident_data_locality_bonus():
    """Data-locality parity (data_locality.clj:192): a dataset job's
    sparse bonus row steers it to the low-cost host."""
    from cook_tpu.scheduler.data_locality import DataLocalityCosts

    hosts = [MockHost("far", mem=1000, cpus=16),
             MockHost("near", mem=1000, cpus=16)]
    store, cluster, coord = build(hosts=hosts)
    coord.data_locality = DataLocalityCosts(
        fetcher=lambda uuids: {u: {"near": 0.0, "far": 1.0}
                               for u in uuids},
        weight=0.9)
    coord.enable_resident()
    job = mkjob(datasets=[{"dataset": {"bucket": "b"}}])
    # pre-warm the cost cache (the fetch is async on the drain cadence;
    # a job matched before costs arrive places without the bonus, like
    # the reference's background cost updater)
    coord.data_locality.update([job])
    store.create_jobs([job])
    coord.match_cycle()
    assert job.state == JobState.RUNNING
    assert job.instances[0].hostname == "near"


def test_resident_estimated_completion_lane():
    """Estimated-completion parity (constraints.clj:200-247): a job
    whose scaled expected runtime outlives a host's remaining lifetime
    must land elsewhere, via the device time-lane."""
    import time as _time

    from cook_tpu.scheduler.coordinator import (EstimatedCompletionConfig,
                                                SchedulerConfig)

    now_s = _time.time()
    # dying: 29 of 30 lifetime minutes elapsed -> ~60 s left
    hosts = [MockHost("dying", mem=1000, cpus=16,
                      attributes={"host-start-time":
                                  str(now_s - 29 * 60)}),
             MockHost("fresh", mem=1000, cpus=16,
                      attributes={"host-start-time": str(now_s)})]
    cfg = SchedulerConfig(estimated_completion=EstimatedCompletionConfig(
        expected_runtime_multiplier=1.0, host_lifetime_mins=30.0))
    store, cluster, coord = build(hosts=hosts, config=cfg)
    coord.enable_resident()
    rp = coord._resident["default"]
    assert rp.with_est
    long_job = mkjob(expected_runtime_ms=10 * 60 * 1000)   # 10 min
    store.create_jobs([long_job])
    coord.match_cycle()
    assert long_job.state == JobState.RUNNING
    assert long_job.instances[0].hostname == "fresh"
    # an unconstrained job may still use the dying host
    quick = mkjob()
    store.create_jobs([quick])
    coord.match_cycle()
    assert quick.state == JobState.RUNNING


def test_resident_rebuild_grows_sparse_caps():
    """A rebuild whose constrained-job demand exceeds forb_cap grows
    the cap and retries instead of wedging in a resync loop."""
    hosts = [MockHost(f"h{i}", mem=1000, cpus=16,
                      attributes={"rack": "a"}) for i in range(2)]
    store, cluster, coord = build(hosts=hosts)
    jobs = [mkjob(cpus=1, constraints=[["rack", "EQUALS", "a"]])
            for _ in range(12)]
    store.create_jobs(jobs)
    coord.enable_resident(forb_cap=2)      # far under the 12 needed
    rp = coord._resident["default"]
    assert rp.forb_cap >= 12
    stats = coord.match_cycle()
    assert stats.matched > 0


def test_resident_pools_pinned_per_device():
    """SURVEY §2.5.1 per-pool parallel loops: one Coordinator, one
    resident pool per (virtual) device, full launch/complete flow on
    each — the production path's multi-chip story (VERDICT r3 #6)."""
    import jax

    from cook_tpu.state.pools import Pool, PoolRegistry

    devs = jax.devices()
    n = min(4, len(devs))
    if n < 2:
        pytest.skip("needs >=2 devices (conftest forces 8 CPU devices)")
    store = JobStore()
    pools = PoolRegistry("pool0")
    hosts = []
    for p in range(n):
        pools.add(Pool(name=f"pool{p}"))
        hosts += [MockHost(f"p{p}h{i}", mem=1000, cpus=16,
                           pool=f"pool{p}") for i in range(2)]
    cluster = MockCluster(hosts, runtime_fn=lambda s: (5.0, True, None))
    reg = ClusterRegistry()
    reg.register(cluster)
    coord = Coordinator(store, reg, pools=pools)
    for p in range(n):
        coord.enable_resident(f"pool{p}", device=devs[p])
    jobs = [mkjob(pool=f"pool{i % n}") for i in range(4 * n)]
    store.create_jobs(jobs)
    launched = 0
    for p in range(n):
        launched += coord.match_cycle(f"pool{p}").matched
    assert launched == 4 * n
    assert cluster.advance(10.0) == 4 * n
    placements = {
        p: next(iter(
            coord._resident[f"pool{p}"].state["pend"]["mem"].devices()))
        for p in range(n)}
    assert len(set(placements.values())) == n


def test_resident_late_installed_adjuster_forces_rebuild():
    """A match-affecting plugin installed AFTER enable_resident must
    fully apply (rebuild with adjusted mirrors), not half-apply via the
    consume path only — the mirrors would otherwise bin-pack with
    unadjusted sizes while launch uses adjusted ones."""
    from cook_tpu.plugins import JobAdjuster, PluginRegistry

    class ClampMem(JobAdjuster):
        # idempotent, like every legal in-place adjuster: the reference
        # re-applies adjusters each cycle to the same store-backed jobs
        def adjust_job(self, job):
            job.mem = max(job.mem, 200.0)
            return job

    store, cluster, coord = build()
    coord.enable_resident()
    rp = coord._resident["default"]
    job = mkjob(mem=100)
    store.create_jobs([job])
    coord.match_cycle()
    assert job.state == JobState.RUNNING
    # install the adjuster live: the next cycle must detect the config
    # change and rebuild with adjusted values
    coord.plugins = PluginRegistry(adjuster=ClampMem())
    assert rp.resync_due()
    j2 = mkjob(mem=100)
    store.create_jobs([j2])
    coord.match_cycle()
    assert j2.state == JobState.RUNNING
    assert j2.mem == 200.0   # adjusted value everywhere (store mutated)
    coord.match_cycle()      # insts event drains; row freed
    assert j2.uuid not in rp.pend_row


def test_resident_light_resync_corrects_membership_drift():
    """The periodic resync is now a LIGHT membership reconcile (no
    rebuild, no in-flight drain): simulate missed store events and
    check the interval backstop repairs both directions — missed
    creates start matching, missed terminals free rows and credit
    capacity back."""
    store, cluster, coord = build(n_hosts=4)
    coord.enable_resident(resync_interval=8, full_resync_every=1000)
    rp = coord._resident["default"]
    jobs = [mkjob() for _ in range(4)]
    store.create_jobs(jobs)
    coord.match_cycle()
    assert all(j.state == JobState.RUNNING for j in jobs)

    # missed CREATE events: drop the listener while submitting
    store._listeners.remove(coord._resident_listener)
    missed = [mkjob() for _ in range(3)]
    store.create_jobs(missed)
    # missed TERMINAL events too: completions the pool never hears
    cluster.advance(120.0)
    assert all(j.state == JobState.COMPLETED for j in jobs)
    store.add_listener(coord._resident_listener)

    coord.match_cycle()
    assert all(j.state == JobState.WAITING for j in missed)  # drifted
    for _ in range(10):     # cross the resync_interval boundary
        coord.match_cycle()
    assert rp._light_since_full >= 1
    assert all(j.state == JobState.RUNNING for j in missed)
    coord.match_cycle()
    assert_state_matches_rebuild(coord)


def test_resident_periodic_full_rebuild_rollover():
    """Every full_resync_every'th periodic resync is a FULL rebuild
    (f32-drift backstop); the lights in between must not reset the
    counter, and the rebuild must preserve correctness under load."""
    store, cluster, coord = build(n_hosts=4)
    coord.enable_resident(resync_interval=4, full_resync_every=3)
    rp = coord._resident["default"]
    jobs = [mkjob() for _ in range(8)]
    store.create_jobs(jobs)
    reasons = []
    for _ in range(30):
        r = rp.resync_reason()
        if r:
            reasons.append(r)
        coord.match_cycle()
        cluster.advance(2.0)
    # periodic cadence fired repeatedly; every 3rd one was full
    assert "light" in reasons and "full" in reasons
    lights_between = 0
    max_lights = 0
    for r in reasons:
        if r == "light":
            lights_between += 1
            max_lights = max(max_lights, lights_between)
        elif r == "full":
            lights_between = 0
    assert max_lights <= 2      # full_resync_every=3 -> <=2 lights
    assert_state_matches_rebuild(coord)


def test_resident_incremental_host_add_no_rebuild():
    """Host joins reconcile incrementally: the new host takes a slot,
    constrained rows gain its column, and NO full rebuild happens (a
    2.4 s stall at 100k scale, measured)."""
    hosts = [MockHost(f"h{i}", mem=1000, cpus=16,
                      attributes={"rack": "a"}) for i in range(2)]
    store, cluster, coord = build(hosts=hosts)
    coord.enable_resident()
    rp = coord._resident["default"]
    builds = rp._build_count
    # saturate both hosts, plus a rack-b job that can't place yet
    jobs = [mkjob(cpus=16) for _ in range(2)]
    rack_b = mkjob(constraints=[["rack", "EQUALS", "b"]])
    store.create_jobs(jobs + [rack_b])
    coord.match_cycle()
    assert rack_b.state == JobState.WAITING
    from cook_tpu.backends.mock import MockHost as MH
    cluster.add_host(MH("h-new", mem=2000, cpus=32,
                        attributes={"rack": "b"}))
    coord.match_cycle()    # host reconcile + match
    coord.match_cycle()
    assert rack_b.state == JobState.RUNNING
    assert rack_b.instances[0].hostname == "h-new"
    assert rp._build_count == builds   # incremental: no rebuild
    assert_state_matches_rebuild(coord)


def test_resident_incremental_host_remove_and_rejoin():
    """Host leaves: tombstoned in place (no index shift, no rebuild),
    no new matches there; rejoining reuses the slot with fresh
    capacity."""
    hosts = [MockHost(f"h{i}", mem=1000, cpus=16) for i in range(3)]
    store, cluster, coord = build(hosts=hosts)
    coord.enable_resident()
    rp = coord._resident["default"]
    builds = rp._build_count
    jobs = [mkjob(cpus=4) for _ in range(3)]
    store.create_jobs(jobs)
    coord.match_cycle()
    assert all(j.state == JobState.RUNNING for j in jobs)
    victims = cluster.remove_host("h1")
    coord.match_cycle()    # reconcile: h1 tombstoned; lost task retries
    idx_before = rp._host_index_all["h1"]
    for _ in range(3):
        coord.match_cycle()
    # everything re-ran on the two live hosts
    assert all(j.state == JobState.RUNNING for j in jobs)
    assert all(j.instances[-1].hostname != "h1" for j in jobs)
    # rejoin reuses the tombstoned slot
    from cook_tpu.backends.mock import MockHost as MH
    cluster.add_host(MH("h1", mem=1000, cpus=16))
    coord.match_cycle()
    assert rp._host_index_all["h1"] == idx_before
    assert rp._build_count == builds
    extra = [mkjob(cpus=8) for _ in range(4)]
    store.create_jobs(extra)
    coord.match_cycle()
    assert sum(j.state == JobState.RUNNING for j in extra) >= 3
    assert_state_matches_rebuild(coord)


def test_resident_host_slot_overflow_falls_back_to_rebuild():
    """More fresh hosts than Hcap slots -> the reconcile reports
    impossible and the coordinator runs the full rebuild."""
    store, cluster, coord = build(n_hosts=2)
    coord.enable_resident()
    rp = coord._resident["default"]
    builds = rp._build_count
    from cook_tpu.backends.mock import MockHost as MH
    for i in range(rp.Hcap + 1):   # exceed the host slot budget
        cluster.add_host(MH(f"flood-{i}", mem=100, cpus=2))
    coord.match_cycle()
    assert rp._build_count == builds + 1   # full rebuild happened
    jobs = [mkjob() for _ in range(4)]
    store.create_jobs(jobs)
    coord.match_cycle()
    assert all(j.state == JobState.RUNNING for j in jobs)


def test_resident_host_rejoin_stale_terminal_no_overcommit():
    """A task's host dies, the host rejoins at full capacity, and only
    THEN the stale terminal arrives: its credit must not inflate the
    rejoined host's row past truth (the row was just re-based from the
    backend's offer)."""
    hosts = [MockHost(f"h{i}", mem=100, cpus=8) for i in range(2)]
    store, cluster, coord = build(hosts=hosts)
    coord.enable_resident()
    rp = coord._resident["default"]
    job = mkjob(mem=40, cpus=4)
    store.create_jobs([job])
    coord.match_cycle()
    assert job.state == JobState.RUNNING
    tid = job.instances[0].task_id
    host = job.instances[0].hostname
    # host vanishes WITHOUT reporting the task (mock removal emits the
    # failure; drop the resident listener so the pool never hears it —
    # the delayed-grace scenario)
    store._listeners.remove(coord._resident_listener)
    cluster.remove_host(host)
    store.add_listener(coord._resident_listener)
    coord.match_cycle()       # tombstones the host row
    from cook_tpu.backends.mock import MockHost as MH
    cluster.add_host(MH(host, mem=100, cpus=8))
    coord.match_cycle()       # rejoin: re-base from offer, null records
    # the stale terminal now drains (listener re-attached above caught
    # nothing; simulate the late event via a light resync membership
    # fix + a direct credit attempt)
    coord.match_cycle()
    idx = rp._host_index_all[host]
    st = fetch_state(rp)
    assert st["host"]["mem"][idx] <= 100 + 1e-3   # never above capacity
    assert st["host"]["cpus"][idx] <= 8 + 1e-3
    assert_state_matches_rebuild(coord)


def test_resident_host_relabel_refreshes_masks():
    """A surviving host whose attributes change between cycles (e.g. a
    re-rack) must re-base: constraint masks refresh against the new
    labels without a full rebuild."""
    hosts = [MockHost("h0", mem=1000, cpus=16, attributes={"rack": "a"}),
             MockHost("h1", mem=1000, cpus=16, attributes={"rack": "a"})]
    store, cluster, coord = build(hosts=hosts)
    coord.enable_resident()
    rp = coord._resident["default"]
    builds = rp._build_count
    job = mkjob(constraints=[["rack", "EQUALS", "b"]])
    store.create_jobs([job])
    coord.match_cycle()
    assert job.state == JobState.WAITING   # no rack-b host yet
    with cluster._lock:
        cluster.hosts["h1"].attributes["rack"] = "b"
        cluster.bump_offer_generation()
    coord.match_cycle()
    coord.match_cycle()
    assert job.state == JobState.RUNNING
    assert job.instances[0].hostname == "h1"
    assert rp._build_count == builds       # incremental, no rebuild


def test_resident_queued_credit_dropped_after_rebase():
    """A credit queued against a cycle BEFORE a host re-base must drop
    at drain: the re-base already restored the row from backend truth,
    so applying it would overcommit the host (review r4 finding)."""
    hosts = [MockHost("h0", mem=100, cpus=8,
                      attributes={"zone": "z1"})]
    store, cluster, coord = build(hosts=hosts)
    coord.enable_resident()
    rp = coord._resident["default"]
    idx = rp.host_ids["h0"]
    # a stale credit from an old cycle (e.g. a refused launch whose
    # consume raced the re-base)
    rp.queue_credit(idx, 40.0, 4.0, 0.0, 1, 0, as_of=rp.cycle_no - 1)
    # relabel -> sig change -> re-base from the fresh offer
    with cluster._lock:
        cluster.hosts["h0"].attributes["zone"] = "z2"
        cluster.bump_offer_generation()
    coord.match_cycle()
    st = fetch_state(rp)
    assert st["host"]["mem"][idx] <= 100 + 1e-3
    assert st["host"]["cpus"][idx] <= 8 + 1e-3
    # sanity: a POST-rebase credit still applies
    rp.queue_credit(idx, -10.0, -1.0, 0.0, -1, 0, as_of=rp.cycle_no)
    coord.match_cycle()
    st = fetch_state(rp)
    assert st["host"]["mem"][idx] <= 90 + 1e-3


def test_preempt_kill_ordered_behind_queued_launch():
    """Rebalancer preemption kills must ride the async launch queue
    (advisor r4 medium): a victim whose launch transaction committed
    but whose backend hand-off is still queued would otherwise get a
    no-op direct kill and run as a zombie the store believes dead."""
    import threading
    import time as _time

    store, cluster, coord = build()
    coord.enable_resident(synchronous=False)
    rp = coord._resident["default"]
    events = []
    gate = threading.Event()
    orig_launch = cluster.launch_tasks
    orig_kill = cluster.kill_task
    orig_preempt = cluster.preempt_task

    def slow_launch(pool, specs):
        gate.wait(5.0)   # hold the launcher so the kill enqueues behind
        events.append(("launch", [s.task_id for s in specs]))
        orig_launch(pool, specs)

    def rec_kill(tid):
        events.append(("kill", tid))
        orig_kill(tid)

    def rec_preempt(tid):
        events.append(("preempt", tid))
        orig_preempt(tid)

    cluster.launch_tasks = slow_launch
    cluster.kill_task = rec_kill
    cluster.preempt_task = rec_preempt
    job = mkjob()
    store.create_jobs([job])
    coord.match_cycle()
    # wait for the launch transaction to commit (txn BEFORE enqueue)
    for _ in range(500):
        if job.instances:
            break
        _time.sleep(0.01)
    tid = job.instances[0].task_id
    # the rebalancer's kill path while the launch sits in the queue
    coord._backend_kill(tid, preempt=True)
    gate.set()
    coord.drain_resident()
    # the backend must have seen a (re)kill AFTER the launch posted:
    # the task cannot survive as a zombie
    kinds = [k for k, _ in events]
    launch_at = kinds.index("launch")
    assert any(k in ("kill", "preempt") for k in kinds[launch_at + 1:]), \
        events
    assert tid not in cluster.known_task_ids()
    coord.stop()


def test_enable_resident_twice_retires_old_launcher():
    """Re-enabling a pool (advisor r4): the previous launcher thread
    must exit and nothing queued on it may be dropped."""
    store, cluster, coord = build()
    coord.enable_resident(synchronous=False)
    old_threads = [t for t in coord._threads
                   if t.name == "resident-launcher-default"]
    assert len(old_threads) == 1
    jobs = [mkjob() for _ in range(3)]
    store.create_jobs(jobs)
    coord.match_cycle()
    # re-enable while launches may still be in flight: the old queue
    # drains first, then the thread retires
    coord.enable_resident(synchronous=False)
    coord.drain_resident()
    assert all(j.state == JobState.RUNNING for j in jobs)
    old_threads[0].join(timeout=5)
    assert not old_threads[0].is_alive()
    # the replacement pool still schedules
    more = [mkjob() for _ in range(2)]
    store.create_jobs(more)
    coord.match_cycle()
    coord.drain_resident()
    assert all(j.state == JobState.RUNNING for j in more)
    coord.stop()


def test_light_resync_probes_host_signatures():
    """Live-host attribute relabels that don't bump offer_generation
    (advisor r4): the LIGHT rung follows its membership reconcile with
    an O(H) reconcile_hosts, so the stale window is resync_interval
    cycles, not the full-rebuild period."""
    hosts = [MockHost("h0", mem=1000, cpus=16, attributes={"zone": "z1"}),
             MockHost("h1", mem=1000, cpus=16, attributes={"zone": "z2"})]
    store, cluster, coord = build(hosts=hosts)
    coord.enable_resident(resync_interval=4)
    job = mkjob(constraints=[("zone", "EQUALS", "z3")])
    store.create_jobs([job])
    coord.match_cycle()
    assert job.state == JobState.WAITING
    # relabel WITHOUT an offer_generation bump (in-place attr change)
    with cluster._lock:
        cluster.hosts["h0"].attributes["zone"] = "z3"
    for _ in range(6):   # cross the light-resync boundary
        coord.match_cycle()
    assert job.state == JobState.RUNNING


def test_background_rebuild_keeps_cycling_and_swaps():
    """VERDICT r5 #1: the full rebuild builds on a thread while cycles
    keep consuming on the old state, then swaps at a cycle boundary.
    No launch is lost or doubled across the swap, and the swapped
    state equals a fresh rebuild."""
    import threading
    import time as _time

    store, cluster, coord = build(n_hosts=4)
    coord.enable_resident(synchronous=True, background_rebuild=True,
                          resync_interval=4, full_resync_every=1)
    rp = coord._resident["default"]
    gate = threading.Event()
    entered = threading.Event()

    def hook(shadow):
        entered.set()
        assert gate.wait(10.0)

    rp._bg_build_hook = hook
    first = [mkjob() for _ in range(4)]
    store.create_jobs(first)
    for _ in range(5):   # cross the periodic-full boundary
        coord.match_cycle()
    assert entered.wait(5.0), "background build never started"
    assert rp.rebuilding()
    assert rp._build_count == 1     # the live state was NOT rebuilt
    # cycles keep launching while the build is held open
    during = [mkjob() for _ in range(3)]
    store.create_jobs(during)
    coord.match_cycle()
    assert all(j.state == JobState.RUNNING for j in during)
    # a kill during the build window must not resurrect after the swap
    doomed = mkjob(mem=10_000)      # unschedulable, stays WAITING
    store.create_jobs([doomed])
    coord.match_cycle()
    store.kill_job(doomed.uuid)
    gate.set()
    for _ in range(200):
        if rp.rebuild_ready():
            break
        _time.sleep(0.01)
    assert rp.rebuild_ready()
    # submitted after the build snapshot, before the swap: the swap's
    # membership catch-up must pick them up
    late = [mkjob() for _ in range(2)]
    store.create_jobs(late)
    coord.match_cycle()             # swap + match in one cycle
    assert rp._build_count == 2     # the shadow was installed
    assert rp._bg is None
    assert all(j.state == JobState.RUNNING for j in late)
    # nothing doubled anywhere across the swap
    assert all(len(j.instances) <= 1 for j in first + during + late)
    assert doomed.state == JobState.COMPLETED and not doomed.instances
    cluster.advance(200.0)
    coord.match_cycle()
    assert_state_matches_rebuild(coord)


def test_background_rebuild_urgent_stays_inline():
    """Consumer failures force an INLINE rebuild even with the
    background path on: cycling on suspect state while a build runs
    is not safe."""
    store, cluster, coord = build()
    coord.enable_resident(synchronous=True, background_rebuild=True)
    rp = coord._resident["default"]
    store.create_jobs([mkjob()])
    coord.match_cycle()
    builds = rp._build_count
    rp.request_resync()
    assert rp.resync_reason() == "full-urgent"
    coord.match_cycle()
    assert rp._build_count == builds + 1   # rebuilt inline, this cycle
    assert rp._bg is None
    assert_state_matches_rebuild(coord)


def test_sharded_resident_pool_equals_single_device_oracle():
    """VERDICT r5 #2: ONE production pool spans devices — host/forb
    tensors shard over the mesh, dispatch runs the distributed scan —
    and the launch set equals the single-device oracle, unique-host
    groups included."""
    import jax

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device CPU mesh")

    def scenario(coord, store):
        g = Group(uuid=new_uuid(), name="g",
                  host_placement={"type": "unique"})
        jobs = [mkjob(user=f"u{i % 3}", mem=50 + 10 * (i % 5),
                      cpus=1 + (i % 3)) for i in range(24)]
        gjobs = [mkjob(group=g.uuid) for _ in range(4)]
        store.create_jobs(jobs + gjobs, groups=[g])
        coord.match_cycle()
        return jobs + gjobs, gjobs

    store_a, _, coord_a = build(n_hosts=6)
    coord_a.enable_resident()
    all_a, _ = scenario(coord_a, store_a)
    hosts_a = sorted(j.instances[0].hostname for j in all_a if j.instances)

    store_b, cluster_b, coord_b = build(n_hosts=6)
    coord_b.enable_resident(devices=devs[:8])
    all_b, gjobs_b = scenario(coord_b, store_b)
    hosts_b = sorted(j.instances[0].hostname for j in all_b if j.instances)

    assert hosts_a == hosts_b
    gh = [j.instances[0].hostname for j in gjobs_b if j.instances]
    assert len(gh) == len(set(gh)), gh   # unique placement held
    # the sharded pool keeps scheduling across completions and churn
    cluster_b.advance(200.0)
    coord_b.match_cycle()
    assert_state_matches_rebuild(coord_b)
    # tensors really shard: host lanes live across all 8 devices
    assert len(coord_b._resident["default"]
               .state["host"]["mem"].sharding.device_set) == 8


def test_resident_listener_shards_by_pool_without_plugins():
    """With >1 resident pools and no plugins configured, store events
    route to the owning pool's mirror only — delivery runs under the
    store lock, so broadcast made every launch txn pay O(pools)
    enqueues plus drain-side filtering. Unattributable kinds ("gc")
    still broadcast, and scheduling behavior is unchanged: each pool
    launches exactly its own jobs."""
    from cook_tpu.state.pools import Pool, PoolRegistry

    store = JobStore()
    pools = PoolRegistry("pool0")
    hosts = []
    for p in range(2):
        pools.add(Pool(name=f"pool{p}"))
        hosts += [MockHost(f"p{p}h{i}", mem=1000, cpus=16,
                           pool=f"pool{p}") for i in range(2)]
    cluster = MockCluster(hosts, runtime_fn=lambda s: (5.0, True, None))
    reg = ClusterRegistry()
    reg.register(cluster)
    coord = Coordinator(store, reg, pools=pools)
    coord.enable_resident("pool0")
    coord.enable_resident("pool1")

    seen = {"pool0": [], "pool1": []}
    for pname, rp in coord._resident.items():
        orig = rp.on_event

        def rec(kind, data, _p=pname, _orig=orig):
            seen[_p].append(kind)
            _orig(kind, data)

        rp.on_event = rec

    a = mkjob(user="alice", pool="pool0")
    b = mkjob(user="bob", pool="pool1")
    store.create_jobs([a, b])
    assert seen["pool0"].count("job") == 1
    assert seen["pool1"].count("job") == 1

    assert coord.match_cycle("pool0").matched == 1
    assert coord.match_cycle("pool1").matched == 1
    assert a.instances[0].hostname.startswith("p0")
    assert b.instances[0].hostname.startswith("p1")
    # the launch batches ("insts") went only to their owner
    assert seen["pool0"].count("insts") == 1
    assert seen["pool1"].count("insts") == 1

    # a kind with no attributable pool broadcasts to every mirror
    ghost = mkjob()
    store.create_jobs([ghost], committed=False)
    store.gc_uncommitted(older_than_ms=-1)
    assert seen["pool0"].count("gc") == 1
    assert seen["pool1"].count("gc") == 1

    # completions still land (sharded "status"/"statuses" delivery)
    assert cluster.advance(10.0) == 2
    assert a.state == JobState.COMPLETED and a.success
    assert b.state == JobState.COMPLETED and b.success


def test_resident_listener_broadcasts_with_plugins():
    """An adjuster can VIRTUALLY migrate a job between pools at sync
    time (_adjusted), so the owning mirror is unknowable at emit time:
    any configured plugins must disable sharded delivery and keep the
    broadcast path."""
    from cook_tpu.plugins import JobAdjuster, PluginRegistry
    from cook_tpu.state.pools import Pool, PoolRegistry

    class Identity(JobAdjuster):
        def adjust_job(self, job):
            return job

    store = JobStore()
    pools = PoolRegistry("pool0")
    hosts = []
    for p in range(2):
        pools.add(Pool(name=f"pool{p}"))
        hosts += [MockHost(f"p{p}h{i}", mem=1000, cpus=16,
                           pool=f"pool{p}") for i in range(2)]
    cluster = MockCluster(hosts)
    reg = ClusterRegistry()
    reg.register(cluster)
    coord = Coordinator(store, reg, pools=pools)
    coord.plugins = PluginRegistry(adjuster=Identity())
    coord.enable_resident("pool0")
    coord.enable_resident("pool1")

    seen = {"pool0": [], "pool1": []}
    for pname, rp in coord._resident.items():
        orig = rp.on_event

        def rec(kind, data, _p=pname, _orig=orig):
            seen[_p].append(kind)
            _orig(kind, data)

        rp.on_event = rec

    store.create_jobs([mkjob(pool="pool0")])
    assert seen["pool0"].count("job") == 1
    assert seen["pool1"].count("job") == 1
