"""The cycle profiler on the device trace's clock, and the waits it
records where they happen (obs/profiler.py).

- A CycleRec is a host-plane event per segment while a JAX profiler
  session records, with its phase and cycle number as stats; with no
  session it constructs no annotation at all.
- The bulk launch transaction's shard-lock and fsync waits are child
  spans of the consume's launch_txn phase.
- The resident consume writes one tracer record per launched traced
  job; /trace builds the phase children at read time.
- Submit handler and ingest commit times land in registry histograms;
  a resync that grows a device table is counted where it happens.
"""
from __future__ import annotations

import glob
import importlib
import time

import pytest

from cook_tpu import obs
from cook_tpu.obs.metrics import registry

# the module (cook_tpu.obs re-exports the instance under its name)
profiler_mod = importlib.import_module("cook_tpu.obs.profiler")


@pytest.fixture
def clean_profiler():
    from cook_tpu.obs import profiler
    profiler.reset()
    profiler.enabled = True
    yield profiler
    profiler.configure(enabled=True)
    profiler.reset()


def _host_events(log_dir: str, names) -> list:
    """(name, start_ns, stats dict) of the host-plane events named in
    `names` in the trace under `log_dir`, by start time."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, ev.start_ns, dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def test_cycle_phases_are_host_events_in_a_trace(tmp_path, clean_profiler):
    import jax
    import jax.numpy as jnp

    got = []
    clean_profiler.add_listener(got.append)
    jax.profiler.start_trace(str(tmp_path))
    try:
        m = clean_profiler.cycle("match", "p", 41)
        m.stamp("drain")
        jnp.ones(8).block_until_ready()
        m.stamp("dispatch")
        c = clean_profiler.cycle("consume", "p", 41)
        c.stamp("readback")
        with c.phase("resync"):
            time.sleep(0.001)
        c.stamp("launch_txn")
        clean_profiler.commit(c, cycle=41)
        clean_profiler.commit(m, cycle=42)
        with obs.annotation("match.tick_wait"):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
        clean_profiler.remove_listener(got.append)
    evs = _host_events(str(tmp_path), {"match", "consume",
                                       "match.tick_wait"})
    for kind in ("match", "consume"):
        rec = next(e for e in got if e["kind"] == kind)
        phased = [st["phase"] for name, _t, st in evs
                  if name == kind and "phase" in st]
        assert phased == [p["name"] for p in rec["phases"]]
        assert all(st["cycle"] == 41 for name, _t, st in evs
                   if name == kind)
    assert any(name == "match.tick_wait" for name, _t, _s in evs)


class _SpyAnnotation:
    """Stands in for jax.profiler.TraceAnnotation with no session."""
    checks = 0
    built = 0

    @classmethod
    def is_enabled(cls):
        cls.checks += 1
        return False

    def __init__(self, *a, **kw):
        type(self).built += 1


def test_no_annotation_is_built_outside_a_session(monkeypatch,
                                                  clean_profiler):
    monkeypatch.setattr(profiler_mod, "_TA", _SpyAnnotation)
    _SpyAnnotation.checks = _SpyAnnotation.built = 0
    rec = clean_profiler.cycle("consume", "p", 3)
    rec.stamp("readback")
    rec.stamp("fold")
    with rec.phase("resync"):
        pass
    clean_profiler.commit(rec)
    with obs.annotation("match.tick_wait"):
        pass
    assert _SpyAnnotation.built == 0
    # one check when the record opens, one per stamp, one at each edge
    # of the phase block, one for the bare annotation
    assert _SpyAnnotation.checks == 1 + 2 + 2 + 1


def test_child_spans_name_their_phase(clean_profiler):
    rec = clean_profiler.cycle("consume", "p", 1)
    rec.stamp("fold")
    a = rec.now()
    time.sleep(0.002)
    rec.child("fsync_wait", a, rec.now())
    rec.set_attr("fsync_rounds", 1)
    rec.stamp("launch_txn")
    got = []
    clean_profiler.add_listener(got.append)
    clean_profiler.commit(rec, cycle=1)
    clean_profiler.remove_listener(got.append)
    (child,) = got[0]["children"]
    assert child["name"] == "fsync_wait" and child["phase"] == "launch_txn"
    assert child["ms"] >= 2.0
    assert got[0]["attrs"] == {"fsync_rounds": 1, "cycle": 1}


@pytest.fixture
def live_stack():
    from cook_tpu.backends.mock import MockHost
    from tests.livestack import Stack

    s = Stack([MockHost("h0", mem=4096, cpus=64)])
    yield s
    s.stop()


def test_launch_txn_waits_sum_within_its_wall(live_stack, clean_profiler):
    s = live_stack
    s.coord.enable_resident(pipeline_depth=0)
    for _ in range(3):
        s.client("alice").submit(command="t", mem=64, cpus=1)
    got = []
    clean_profiler.add_listener(got.append)
    s.coord.match_cycle()
    clean_profiler.remove_listener(got.append)
    cons = [e for e in got if e["kind"] == "consume"
            and e["attrs"]["matched"] > 0]
    assert cons
    for e in cons:
        txn = next(p for p in e["phases"] if p["name"] == "launch_txn")
        kids = e["children"]
        assert {k["name"] for k in kids} == {"lock_wait", "fsync_wait"}
        assert all(k["phase"] == "launch_txn" for k in kids)
        assert sum(k["ms"] for k in kids) <= txn["ms"] + 1e-3
        assert e["attrs"]["fsync_rounds"] in (0, 1)


def test_consume_records_one_span_per_traced_job(live_stack,
                                                 clean_profiler,
                                                 monkeypatch):
    s = live_stack
    obs.tracer.reset()
    obs.tracer.enabled = True
    s.coord.enable_resident(pipeline_depth=0)
    n = 4
    uuids = [s.client("alice").submit(command="t", mem=64, cpus=1)
             for _ in range(n)]
    calls = []
    real_record = obs.tracer.record
    real_consume = s.coord._consume_cycle
    in_consume = []

    def record(name, *a, **kw):
        if in_consume:
            calls.append(name)
        return real_record(name, *a, **kw)

    def consume(*a, **kw):
        in_consume.append(1)
        try:
            return real_consume(*a, **kw)
        finally:
            in_consume.pop()

    monkeypatch.setattr(obs.tracer, "record", record)
    monkeypatch.setattr(s.coord, "_consume_cycle", consume)
    assert s.coord.match_cycle().matched == n
    assert calls == ["match.cycle"] * n
    # /trace still shows the consume's phase children, built at read
    # time, with the launch span id the backend received
    resp = s.admin._request("GET", f"/trace/{uuids[0]}")
    by_name = {sp["name"]: sp for sp in resp["spans"]}
    assert {"readback", "launch_loop", "launch_txn",
            "backend_launch"} <= set(by_name)
    cyc = by_name["match.cycle"]
    for name in ("readback", "launch_loop", "launch_txn",
                 "backend_launch"):
        assert by_name[name]["parent"] == cyc["span"]
        assert cyc["t0"] <= by_name[name]["t0"] <= by_name[name]["t1"]
    assert by_name["backend_launch"]["span"] == \
        obs.launch_span_id(cyc["attrs"]["task"])
    # a second read builds the same ids
    again = s.admin._request("GET", f"/trace/{uuids[0]}")
    assert {sp["span"] for sp in again["spans"]} == \
        {sp["span"] for sp in resp["spans"]}


def test_submit_and_ingest_commit_histograms(live_stack):
    from cook_tpu.rest.ingest import IngestBatcher
    from cook_tpu.state.model import Job, new_uuid
    from cook_tpu.state.store import JobStore

    def count(name):
        return registry.snapshot().get(name, {}).get("count", 0)

    before = count("rest_submit_ms")
    live_stack.client("alice").submit(command="t", mem=64, cpus=1)
    assert count("rest_submit_ms") == before + 1
    before = count("ingest_commit_ms")
    batcher = IngestBatcher(JobStore(), workers=1)
    try:
        batcher.submit_and_wait([Job(uuid=new_uuid(), user="u",
                                     command="t", mem=1, cpus=1)])
    finally:
        batcher.stop()
    assert count("ingest_commit_ms") == before + 1


def test_cap_growth_is_counted_on_the_resync_cycle(live_stack,
                                                   clean_profiler):
    from cook_tpu.state.model import Job, new_uuid

    s = live_stack
    s.coord.enable_resident(pipeline_depth=0)
    rp = s.coord._resident[s.coord.pools.default_pool]
    s.coord.match_cycle()
    pcap0 = rp.Pcap

    def grown():
        return registry.snapshot().get(
            "resident_cap_grow_total;cap=Pcap", {}).get("value", 0.0)

    before = grown()
    s.store.create_jobs([Job(uuid=new_uuid(), user="bob", command="t",
                             mem=1e6, cpus=1)
                         for _ in range(pcap0 + 1)])
    got = []
    clean_profiler.add_listener(got.append)
    s.coord.match_cycle()
    clean_profiler.remove_listener(got.append)
    assert rp.Pcap > pcap0
    assert grown() == before + 1
    (m,) = [e for e in got if e["kind"] == "match"]
    assert m["attrs"]["Pcap"] == rp.Pcap
    assert m["attrs"]["resync"] in ("overflow", "full", "full-urgent")
