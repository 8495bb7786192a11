"""End-to-end job-lifecycle tracing + cycle flight recorder (obs/).

Unit tier: traceparent grammar, ring/LRU bounds, tree assembly, the
zero-allocation disabled path, attr sampling, exporters, and the
Prometheus/Graphite renderer edge cases that ride along in this PR.

Integration tier: one REST submit must yield ONE connected span tree
— submit → store txn → match-cycle phases → launch txn → completion —
on BOTH the legacy match path and the pipelined device-resident path,
plus cross-process propagation through a live agent daemon over HTTP.
"""
from __future__ import annotations

import json
import time

import pytest

from cook_tpu import obs
from cook_tpu.obs.metrics import Meter, Registry
from cook_tpu.utils.metrics import GraphiteReporter


@pytest.fixture(autouse=True)
def clean_tracer():
    obs.tracer.reset()
    obs.tracer.enabled = True
    yield
    obs.tracer.reset()
    obs.tracer.enabled = True


def wait_until(fn, timeout=20.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = fn()
        if v:
            return v
        time.sleep(interval)
    raise AssertionError(f"condition not met within {timeout}s")


# ----------------------------------------------------------------------
# traceparent grammar

def test_traceparent_roundtrip():
    tid, sid = obs.new_trace_id(), obs.new_span_id()
    assert len(tid) == 32 and len(sid) == 16
    tp = obs.make_traceparent(tid, sid)
    assert obs.parse_traceparent(tp) == (tid, sid)


@pytest.mark.parametrize("bad", [
    "", "garbage", None, 42,
    "01-" + "a" * 32 + "-" + "b" * 16 + "-01",    # unknown version
    "00-" + "a" * 31 + "-" + "b" * 16 + "-01",    # short trace id
    "00-" + "A" * 32 + "-" + "b" * 16 + "-01",    # uppercase hex
    "00-" + "a" * 32 + "-" + "b" * 15 + "-01",    # short span id
])
def test_traceparent_rejects_malformed(bad):
    assert obs.parse_traceparent(bad) is None


# ----------------------------------------------------------------------
# tracer bounds: ring + per-trace LRU

def test_flight_ring_evicts_oldest():
    t = obs.Tracer(ring_capacity=4)
    for i in range(6):
        t.record_cycle(f"cycle.{i}", float(i), float(i) + 1.0)
    recent = t.recent()
    assert [s["name"] for s in recent] == \
        ["cycle.5", "cycle.4", "cycle.3", "cycle.2"]
    assert t.recent(limit=2)[0]["name"] == "cycle.5"
    assert t.stats()["ring"] == 4 and t.stats()["finished"] == 6


def test_per_trace_lru_eviction():
    t = obs.Tracer(max_traces=2)
    tids = [obs.new_trace_id() for _ in range(3)]
    for tid in tids:
        t.record("s", trace_id=tid, start_ms=0.0, end_ms=1.0)
    assert t.trace(tids[0]) == []          # oldest trace evicted
    assert len(t.trace(tids[1])) == 1 and len(t.trace(tids[2])) == 1
    assert t.stats()["dropped"] == 1 and t.stats()["traces"] == 2


def test_max_spans_per_trace_drops_overflow():
    t = obs.Tracer(max_spans_per_trace=2)
    tid = obs.new_trace_id()
    for i in range(3):
        t.record(f"s{i}", trace_id=tid, start_ms=float(i),
                 end_ms=float(i) + 1.0)
    assert [s["name"] for s in t.trace(tid)] == ["s0", "s1"]
    assert t.stats()["dropped"] == 1


def test_tree_assembly_nests_and_orders_siblings():
    t = obs.Tracer()
    tid = obs.new_trace_id()
    root = t.record("root", trace_id=tid, start_ms=0.0, end_ms=10.0)
    # children recorded out of start-time order
    b = t.record("b", trace_id=tid, parent_id=root, start_ms=5.0,
                 end_ms=6.0)
    a = t.record("a", trace_id=tid, parent_id=root, start_ms=1.0,
                 end_ms=2.0)
    t.record("a.1", trace_id=tid, parent_id=a, start_ms=1.2, end_ms=1.5)
    tree = t.tree(tid)
    assert len(tree) == 1 and tree[0]["name"] == "root"
    assert [n["name"] for n in tree[0]["children"]] == ["a", "b"]
    assert [n["name"] for n in tree[0]["children"][0]["children"]] == \
        ["a.1"]
    assert b != a


# ----------------------------------------------------------------------
# live spans + the disabled path

def test_span_context_manager_records_and_tags_errors():
    t = obs.Tracer()
    with t.start_span("ok", attrs={"k": 1}) as sp:
        tid = sp.trace_id
    with pytest.raises(RuntimeError):
        with t.start_span("boom", parent=sp):
            raise RuntimeError("x")
    spans = {s["name"]: s for s in t.trace(tid)}
    assert spans["ok"]["attrs"] == {"k": 1}
    assert spans["boom"]["parent"] == sp.span_id
    assert spans["boom"]["attrs"]["error"] == "RuntimeError"
    sp.finish()    # idempotent: already finished by __exit__
    assert len(t.trace(tid)) == 2


def test_disabled_tracer_is_zero_cost_noop():
    t = obs.Tracer(enabled=False)
    sp = t.start_span("x")
    assert sp is obs.NOOP_SPAN and sp is t.start_span("y")
    assert sp.traceparent == ""
    with sp:
        sp.set_attr("k", 1)
    assert t.record("x", trace_id=obs.new_trace_id(),
                    start_ms=0, end_ms=1) == ""
    t.record_cycle("c", 0.0, 1.0, phases=[("p", 0.0, 0.5)])
    assert t.stats() == {"finished": 0, "dropped": 0, "ring": 0,
                         "traces": 0, "enabled": False}


def test_attr_sampling_keeps_one_in_n_bodies():
    t = obs.Tracer(attr_sample_every=2)
    tid = obs.new_trace_id()
    for i in range(4):
        t.record(f"s{i}", trace_id=tid, start_ms=0.0, end_ms=1.0,
                 attrs={"i": i})
    kept = [("attrs" in s) for s in t.trace(tid)]
    assert kept == [False, True, False, True]
    # flight entries always keep attrs: they ARE the recorder payload
    t.record_cycle("c", 0.0, 1.0, attrs={"pool": "p"})
    assert t.recent(1)[0]["attrs"] == {"pool": "p"}


def test_listener_failure_is_contained():
    t = obs.Tracer()
    seen = []

    def bad(span):
        raise ValueError("exporter died")

    t.add_listener(bad)
    t.add_listener(seen.append)
    t.record("s", trace_id=obs.new_trace_id(), start_ms=0, end_ms=1)
    assert [s["name"] for s in seen] == ["s"]
    t.remove_listener(bad)
    t.remove_listener(bad)    # double remove is a no-op


# ----------------------------------------------------------------------
# exporters

def test_span_jsonl_exporter(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    exp = obs.SpanJsonlExporter(path)
    t = obs.Tracer()
    t.add_listener(exp)
    tid = obs.new_trace_id()
    t.record("a", trace_id=tid, start_ms=1.0, end_ms=2.0)
    t.record_cycle("cycle.match", 0.0, 3.0, phases=[("ship", 0.0, 1.0)])
    exp.close()
    exp({"name": "late"})     # post-close write must not raise
    with open(path) as f:
        lines = [json.loads(ln) for ln in f.read().splitlines()]
    assert [ln["name"] for ln in lines] == ["a", "cycle.match"]
    assert lines[0]["trace"] == tid
    assert lines[1]["children"] == [{"name": "ship", "t0": 0.0,
                                     "t1": 1.0}]


def test_to_chrome_trace_shapes():
    flight = {"name": "cycle.match", "span": "s1", "parent": "",
              "t0": 10.0, "t1": 12.0, "attrs": {"pool": "default"},
              "children": [{"name": "ship", "t0": 10.0, "t1": 11.0}]}
    indexed = {"name": "job.submit", "trace": "t" * 32, "span": "s2",
               "parent": "", "t0": 5.0, "t1": 6.0}
    out = obs.to_chrome_trace([flight, indexed])
    assert out["displayTimeUnit"] == "ms"
    events = out["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["args"]["name"] for e in meta} == {"default", "t" * 32}
    by_name = {e["name"]: e for e in xs}
    assert by_name["cycle.match"]["ts"] == 10_000.0
    assert by_name["cycle.match"]["dur"] == 2000.0
    # phase child rides the parent's track
    assert by_name["ship"]["tid"] == by_name["cycle.match"]["tid"]
    assert by_name["job.submit"]["tid"] != by_name["cycle.match"]["tid"]


# ----------------------------------------------------------------------
# satellite: Prometheus exposition (Registry.render) / GraphiteReporter
# edge cases

def test_render_prometheus_sanitises_names_and_digits():
    reg = Registry()
    reg.counter("match.default.cycle-ms").inc(3)
    reg.counter("9lives").inc()
    text = reg.render()
    assert "cook_match_default_cycle_ms 3" in text
    assert "cook__9lives 1" in text           # digit-led name prefixed
    assert text.endswith("\n")


def test_render_prometheus_quantiles_and_meter():
    reg = Registry()
    for v in range(1, 8):
        reg.timer("cycle").update(float(v))
    reg.meter("done").mark(10)
    text = reg.render()
    # exact reservoir quantiles, linear interpolation over 1..7
    assert 'cook_cycle{quantile="0.5"} 4' in text
    assert 'cook_cycle{quantile="0.95"} 6.7' in text
    assert 'cook_cycle{quantile="0.99"} 6.94' in text
    assert "cook_cycle_count 7" in text
    assert "cook_cycle_mean 4" in text
    assert "cook_done_total 10" in text
    assert "cook_done_rate 0.166667" in text  # 10 events / 60 s window


def test_render_prometheus_empty_snapshot_and_missing_quantiles():
    assert Registry().render() == "\n"
    # a timer with no samples renders no quantile lines, a fresh
    # histogram only its zero buckets
    reg = Registry()
    reg.timer("t")
    reg.histogram("h")
    text = reg.render()
    assert "quantile" not in text
    assert "cook_t_count 0" in text and "cook_h_count 0" in text


def test_graphite_flatten_skips_type_and_collapses_value():
    out: list = []
    GraphiteReporter._flatten("cook", {
        "c": {"type": "counter", "value": 2.0},
        "t": {"type": "timer", "count": 3, "p50": 1.5},
        "flag": {"type": "counter", "value": True},   # bools excluded
    }, out)
    assert ("cook.c", 2.0) in out                     # collapsed
    assert ("cook.t.count", 3.0) in out
    assert ("cook.t.p50", 1.5) in out
    assert all("type" not in name for name, _ in out)
    assert all(name != "cook.flag" for name, _ in out)


# ----------------------------------------------------------------------
# satellite: Meter sliding window on a deque

def test_meter_window_trims_old_events():
    clock = [0.0]
    m = Meter(window_s=10.0, clock=lambda: clock[0])
    m.mark(5)
    clock[0] = 4.0
    m.mark(3)
    assert m.rate == pytest.approx(0.8)       # both inside the window
    clock[0] = 11.0
    m.mark(2)                                 # trims the t=0 event
    assert len(m._events) == 2
    assert m.rate == pytest.approx(0.5)       # 3 + 2 over 10s
    assert m.count == 10.0                    # lifetime total unaffected


def test_metric_registry_snapshot_roundtrips_through_prometheus():
    reg = Registry()
    reg.counter("cycles").inc(2)
    reg.timer("cycle_ms").update(3.0)
    assert reg.snapshot()["cycles"] == {"type": "counter", "value": 2.0}
    text = reg.render()
    assert "cook_cycles 2" in text
    assert 'cook_cycle_ms{quantile="0.5"} 3' in text


# ----------------------------------------------------------------------
# integration: one REST submit -> ONE connected trace tree

def _assert_connected(spans, trace_id, root_sid):
    """Every span belongs to trace_id and parents into the tree."""
    ids = {s["span"] for s in spans}
    for s in spans:
        assert s["trace"] == trace_id
        assert s["parent"] == root_sid or s["parent"] in ids or \
            s["parent"] == "", f"orphan span {s}"


def _submit_and_trace(stack, cycle_fn):
    from cook_tpu.state.model import JobState

    client = stack.client("alice")
    uuid = client.submit(command="t", mem=64, cpus=1)
    cycle_fn()
    stack.cluster.advance(120)
    wait_until(
        lambda: stack.store.jobs[uuid].state == JobState.COMPLETED)
    return uuid, stack.admin._request("GET", f"/trace/{uuid}")


@pytest.fixture
def live_stack():
    from cook_tpu.backends.mock import MockHost
    from tests.livestack import Stack

    s = Stack([MockHost("h0", mem=1024, cpus=32)])
    yield s
    s.stop()


def test_e2e_trace_legacy_path(live_stack):
    s = live_stack
    uuid, resp = _submit_and_trace(s, s.coord.match_cycle)
    ctx = obs.parse_traceparent(resp["traceparent"])
    assert ctx is not None and resp["trace_id"] == ctx[0]
    spans = resp["spans"]
    names = {sp["name"] for sp in spans}
    assert {"job.submit", "store.create_jobs", "match.cycle",
            "tensorize_match", "launch_txn", "backend_launch",
            "job.complete"} <= names
    _assert_connected(spans, ctx[0], ctx[1])
    by_name = {sp["name"]: sp for sp in spans}
    assert by_name["job.submit"]["span"] == ctx[1]      # the root
    assert by_name["match.cycle"]["parent"] == ctx[1]
    assert by_name["launch_txn"]["parent"] == \
        by_name["match.cycle"]["span"]
    assert by_name["match.cycle"]["attrs"]["path"] == "legacy"
    # assembled tree: one root, the submit span
    tree = resp["tree"]
    assert len(tree) == 1 and tree[0]["name"] == "job.submit"


def test_e2e_trace_resident_pipelined(live_stack):
    s = live_stack
    s.coord.enable_resident(pipeline_depth=1)

    def cycle():
        # pipeline_depth=1 double-buffers: cycle N's launch consumes
        # while N+1 matches, so pump twice then drain the tail
        s.coord.match_cycle()
        s.coord.match_cycle()
        s.coord.drain_resident()

    uuid, resp = _submit_and_trace(s, cycle)
    ctx = obs.parse_traceparent(resp["traceparent"])
    spans = resp["spans"]
    names = {sp["name"] for sp in spans}
    assert {"job.submit", "store.create_jobs", "match.cycle",
            "readback", "launch_loop", "launch_txn", "backend_launch",
            "job.complete"} <= names
    _assert_connected(spans, ctx[0], ctx[1])
    by_name = {sp["name"]: sp for sp in spans}
    assert by_name["match.cycle"]["attrs"]["path"] == "resident"
    # the flight recorder saw the resident cycle spans
    flight_names = {sp["name"] for sp in obs.tracer.recent(64)}
    assert "cycle.match" in flight_names
    assert "cycle.consume" in flight_names


def test_trace_endpoint_404s(live_stack):
    from cook_tpu.client import JobClientError
    from cook_tpu.state.model import Job, new_uuid

    s = live_stack
    with pytest.raises(JobClientError):
        s.admin._request("GET", f"/trace/{new_uuid()}")
    # store-submitted job: no REST stamp, no trace
    job = Job(uuid=new_uuid(), user="u", command="t", mem=1, cpus=1)
    s.store.create_jobs([job])
    with pytest.raises(JobClientError):
        s.admin._request("GET", f"/trace/{job.uuid}")


def test_debug_flight_and_metrics_endpoints(live_stack):
    import urllib.request

    s = live_stack
    s.client("alice").submit(command="t", mem=64, cpus=1)
    s.coord.match_cycle()
    # /debug/flight is on the auth bypass list: scrape it raw
    with urllib.request.urlopen(s.server.url + "/debug/flight?limit=8") \
            as r:
        flight = json.loads(r.read())
    assert flight["tracer"]["enabled"] is True
    assert any(sp["name"] == "cycle.match" for sp in flight["spans"])
    assert all("children" in sp for sp in flight["spans"])
    # /debug carries the locked coordinator metrics snapshot
    debug = s.admin._request("GET", "/debug")
    assert "metrics" in debug
    snap = s.coord.metrics_snapshot()
    assert isinstance(snap, dict) and snap is not s.coord.metrics


def test_inbound_traceparent_header_is_honoured(live_stack):
    import urllib.request

    s = live_stack
    tid, sid = obs.new_trace_id(), obs.new_span_id()
    body = json.dumps({"jobs": [{"command": "t", "mem": 64,
                                 "cpus": 1}]}).encode()
    req = urllib.request.Request(
        s.server.url + "/jobs", data=body, method="POST",
        headers={"Content-Type": "application/json",
                 "X-Cook-User": "alice",
                 "traceparent": obs.make_traceparent(tid, sid)})
    with urllib.request.urlopen(req) as r:
        uuid = json.loads(r.read())["jobs"][0]
    resp = s.admin._request("GET", f"/trace/{uuid}")
    # the job joined the CALLER's trace; its submit span parents into
    # the caller's span
    assert resp["trace_id"] == tid
    by_name = {sp["name"]: sp for sp in resp["spans"]}
    assert by_name["job.submit"]["parent"] == sid


# ----------------------------------------------------------------------
# integration: cross-process propagation through a live agent daemon

def test_trace_propagates_through_live_agent_daemon(tmp_path):
    from cook_tpu.agent.daemon import AgentDaemon
    from cook_tpu.backends.agent import AgentCluster
    from cook_tpu.backends.base import ClusterRegistry
    from cook_tpu.rest.api import CookApi
    from cook_tpu.rest.auth import AuthConfig
    from cook_tpu.rest.server import ApiServer
    from cook_tpu.scheduler.coordinator import Coordinator
    from cook_tpu.state.model import Job, JobState, new_uuid
    from cook_tpu.state.store import JobStore

    store = JobStore()
    cluster = AgentCluster(heartbeat_timeout_s=5.0, agent_token="hunter2")
    reg = ClusterRegistry()
    reg.register(cluster)
    coord = Coordinator(store, reg)
    api = CookApi(store, coordinator=coord,
                  auth=AuthConfig(scheme="header", agent_token="hunter2"))
    server = ApiServer(api, port=0).start()
    daemon = None
    try:
        daemon = AgentDaemon(server.url, hostname="a1", mem=1000.0,
                             cpus=4.0, sandbox_root=str(tmp_path / "a1"),
                             heartbeat_interval_s=0.3,
                             agent_token="hunter2").start()
        wait_until(lambda: "a1" in cluster.agents)
        # stamp trace context the way rest/api.py does at submit
        tid, root_sid = obs.new_trace_id(), obs.new_span_id()
        job = Job(uuid=new_uuid(), user="alice", command="true",
                  mem=100, cpus=1,
                  traceparent=obs.make_traceparent(tid, root_sid))
        store.create_jobs([job])
        assert coord.match_cycle().matched == 1
        wait_until(lambda: job.state == JobState.COMPLETED)
        # the daemon's locally-timed spans came back over HTTP status
        # posts and folded into the SAME trace
        wait_until(lambda: {"agent.launch", "agent.run"} <=
                   {sp["name"] for sp in obs.tracer.trace(tid)})
        spans = obs.tracer.trace(tid)
        by_name = {sp["name"]: sp for sp in spans}
        assert {"match.cycle", "launch_txn", "backend_launch",
                "job.complete"} <= set(by_name)
        _assert_connected(spans, tid, root_sid)
        # agent spans parent into the coordinator's backend_launch span
        # (the span id carried by LaunchSpec.traceparent over the wire)
        assert by_name["agent.launch"]["parent"] == \
            by_name["backend_launch"]["span"]
        assert by_name["agent.run"]["parent"] == \
            by_name["backend_launch"]["span"]
        assert by_name["agent.run"]["attrs"]["hostname"] == "a1"
    finally:
        if daemon is not None:
            daemon.stop()
        server.stop()


# ----------------------------------------------------------------------
# cycle profiler (obs/profiler.py): ring bound, zero-cost disabled
# commit, critical-path attribution, /debug/profile, JSONL rotation

def _fake_rec(kind="consume", pool="p", phases=()):
    """A CycleRec with hand-built phase bounds: (name, dur_ms) pairs
    laid out back-to-back from the record's start.  The record is
    backdated by the total phase time so commit()'s wall_ms (real
    elapsed since t0) reflects the synthetic phases."""
    rec = obs.CycleRec(kind, pool)
    total_s = sum(d for _n, d in phases) / 1e3
    rec.t0 -= total_s
    rec.t0_ms -= total_s * 1e3
    pc = rec.t0
    built = []
    for name, dur_ms in phases:
        built.append((name, pc, pc + dur_ms / 1e3, dur_ms / 2.0))
        pc += dur_ms / 1e3
    rec.phases = built
    return rec


@pytest.fixture
def clean_profiler():
    from cook_tpu.obs import profiler
    profiler.reset()
    old_ring = profiler._ring.maxlen
    profiler.enabled = True
    yield profiler
    profiler.configure(ring=old_ring, enabled=True)
    profiler.reset()


def test_profiler_ring_is_bounded(clean_profiler):
    prof = clean_profiler
    prof.configure(ring=8)
    for i in range(100):
        prof.commit(_fake_rec(phases=[("fold", 1.0)]), cycle=i)
    snap = prof.snapshot()
    assert snap["ring"] == 8
    assert snap["committed"] == 100
    # the ring kept exactly the NEWEST records
    kept = prof.worst(100)
    assert len(kept) == 8
    assert {e["attrs"]["cycle"] for e in kept} == set(range(92, 100))


def test_profiler_disabled_commit_allocates_nothing(clean_profiler):
    import tracemalloc

    prof = clean_profiler
    rec = _fake_rec(phases=[("fold", 1.0), ("frame", 2.0)])
    prof.enabled = False
    prof.commit(rec)              # warm any lazy internals
    tracemalloc.start()
    for _ in range(200):
        prof.commit(rec)
    snapshot = tracemalloc.take_snapshot()
    tracemalloc.stop()
    ours = [s for s in snapshot.statistics("lineno")
            if "obs/profiler" in (s.traceback[0].filename or "")]
    assert sum(s.size for s in ours) == 0, ours
    assert prof.snapshot()["committed"] == 0


def test_profiler_blame_names_dominant_phase(clean_profiler):
    """Cross-validation oracle: with a construction where one phase is
    the largest in EVERY cycle, the blame rollup's dominant must equal
    the phase-mean argmax — same dominant story from both ledgers."""
    prof = clean_profiler
    for _ in range(20):
        prof.commit(_fake_rec(phases=[
            ("readback", 1.0), ("fold", 2.0), ("frame", 3.0),
            ("launch_txn", 10.0), ("backend_launch", 2.0)]))
    snap = prof.snapshot()["kinds"]["consume"]
    assert snap["dominant"] == "launch_txn"
    assert snap["blame"]["launch_txn"]["share"] == 1.0
    means = {p: st["mean_ms"] for p, st in snap["phases"].items()}
    assert max(means, key=means.get) == snap["dominant"]
    assert snap["phases"]["launch_txn"]["count"] == 20
    assert 9.0 < snap["phases"]["launch_txn"]["mean_ms"] < 11.0


def test_profiler_overlap_phases_never_blamed(clean_profiler):
    """The match tail's consume/queue_wait overlap the consume record's
    own work — blaming them would double-count every consume-bound
    cycle."""
    prof = clean_profiler
    prof.commit(_fake_rec(kind="match", phases=[
        ("drain", 1.0), ("dispatch", 2.0), ("consume", 50.0)]))
    prof.commit(_fake_rec(kind="match", phases=[
        ("drain", 1.0), ("dispatch", 2.0), ("queue_wait", 50.0)]))
    blame = prof.snapshot()["kinds"]["match"]["blame"]
    assert set(blame) == {"dispatch"}
    # but the overlap phases still get stats (operators still see them)
    assert prof.snapshot()["kinds"]["match"]["phases"][
        "consume"]["count"] == 1


def test_profiler_chrome_trace_and_worst(clean_profiler):
    prof = clean_profiler
    prof.commit(_fake_rec(phases=[("fold", 1.0)]), cycle=1)
    prof.commit(_fake_rec(phases=[("fold", 30.0)]), cycle=2)
    worst = prof.worst(1)
    assert len(worst) == 1 and worst[0]["attrs"]["cycle"] == 2
    chrome = prof.chrome_trace(2)
    xs = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"cycle.consume", "fold"}


def test_profiler_listener_gets_entries_outside_lock(clean_profiler):
    prof = clean_profiler
    got = []

    def listener(entry):
        # re-entering a profiler read here would deadlock if listeners
        # fired under the lock; this is the runtime witness for the
        # R13 static rule
        prof.snapshot()
        got.append(entry)

    prof.add_listener(listener)
    try:
        prof.commit(_fake_rec(phases=[("fold", 1.0)]))
    finally:
        prof.remove_listener(listener)
    assert len(got) == 1 and got[0]["crit"] == "fold"


def test_e2e_profiler_sees_resident_cycles(live_stack, clean_profiler):
    """The coordinator hot path commits both cycle kinds, and the
    record's phase ledger matches the metrics the bench reads — the
    live half of the blame-vs-bench cross-validation."""
    s = live_stack
    s.coord.enable_resident(pipeline_depth=0)
    s.client("alice").submit(command="t", mem=64, cpus=1)
    s.coord.match_cycle()
    snap = clean_profiler.snapshot()
    assert snap["committed"] >= 2
    assert {"match", "consume"} <= set(snap["kinds"])
    consume_phases = set(snap["kinds"]["consume"]["phases"])
    assert {"readback", "fold", "frame", "launch_txn", "bookkeep",
            "backend_launch"} <= consume_phases
    # phase sums reconcile with the coordinator's own metrics ledger
    m = s.coord.metrics_snapshot()
    key = next(k for k in m if k.endswith("launch_txn_ms"))
    prof_mean = snap["kinds"]["consume"]["phases"]["launch_txn"][
        "mean_ms"]
    assert abs(prof_mean - m[key]) < max(5.0, 0.5 * m[key])


def test_debug_profile_endpoint(live_stack, clean_profiler):
    import urllib.request

    s = live_stack
    s.coord.enable_resident(pipeline_depth=0)
    s.client("alice").submit(command="t", mem=64, cpus=1)
    s.coord.match_cycle()
    # /debug/profile is on the auth bypass list: scrape it raw
    with urllib.request.urlopen(
            s.server.url + "/debug/profile?worst=2") as r:
        body = json.loads(r.read())
    assert body["enabled"] is True and body["committed"] >= 2
    assert body["kinds"]["consume"]["dominant"]
    assert 0 < len(body["worst"]) <= 2
    assert all(e["phases"] for e in body["worst"])
    with urllib.request.urlopen(
            s.server.url + "/debug/profile?chrome=4") as r:
        chrome = json.loads(r.read())
    assert chrome["displayTimeUnit"] == "ms"
    assert any(e["ph"] == "X" for e in chrome["traceEvents"])


def test_span_jsonl_rotation(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    exp = obs.SpanJsonlExporter(path, max_mb=0.0005)   # ~524 bytes
    span = {"name": "x" * 80, "trace": "t" * 32, "t0": 1.0, "t1": 2.0}
    line_len = len(json.dumps(span, separators=(",", ":"))) + 1
    for _ in range(20):
        exp(span)
    exp.close()
    import os
    assert os.path.exists(path + ".1"), "no rotation happened"
    for p in (path, path + ".1"):
        size = os.path.getsize(p)
        assert size <= 524 + line_len, f"{p} exceeds the bound: {size}"
        with open(p) as f:
            for ln in f.read().splitlines():
                assert json.loads(ln)["name"] == "x" * 80
    # generations overlap-free and nothing lost beyond the replaced gen
    with open(path) as f:
        n_cur = len(f.read().splitlines())
    with open(path + ".1") as f:
        n_old = len(f.read().splitlines())
    assert n_cur + n_old <= 20
    assert n_cur >= 1 and n_old >= 1
