"""The borg-fleet configuration and the readers of its per-layer metrics.

borg-fleet is borg-cell's machines four times over, one Borg cell per
pool, with each pool placed on a chip of its own; the readers divide per
pool (`pool_busy_ms`) and read the consume records' `queued_ms` attr
(`consume_queued_ms`), and find nothing to read in a program or a run
that lacks what they read.
"""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness, workload  # noqa: E402

POOLS = ["batch", "research", "prod", "dev"]


def _cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return harness.find_cell(bench, name)


def test_fleet_draws_at_a_scaled_down_count():
    _c, config, traffic = _cell("fleet-saturated")
    traffic = {**traffic, "pending": 20, "running": 30,
               "runtime_s": config["task_runtime_s"]}
    draws = workload.draw(config, traffic, 2 ** 31 + 11)
    assert [d.pool for d in draws] == POOLS
    for d in draws:
        assert len(d.pending) == 20 and len(d.running) == 30
        assert sum(s.count for s in d.shapes) == 12583
        # the running set sits on its own pool's hosts only
        assert {h.split("-c")[0] for h in d.hosts} == {d.pool}
    assert config["default_pool"] == "batch"
    assert config["federation"]["groups"]["fleet"]["pools"] == POOLS


def test_fleet_pools_on_four_distinct_devices():
    from cook_tpu.parallel.federation import place_pools
    _c, config, _t = _cell("fleet-saturated")
    devices = config["federation"]["groups"]["fleet"]["devices"]
    placed = place_pools(workload.pool_names(config), devices)
    assert placed == {"batch": 0, "research": 2, "prod": 3, "dev": 1}
    assert harness.server_config(config, "events.log", 1)["federation"] \
        == config["federation"]


def test_fleet_host_shapes_are_borg_cells_per_pool():
    _c, fleet, _t = _cell("fleet-saturated")
    _c, borg, _t = _cell("headline-saturated")
    want = [{k: v for k, v in s.items() if k != "cluster"}
            for s in borg["cluster"]["host_shapes"]]
    for pool in POOLS:
        mine = [s for s in fleet["cluster"]["host_shapes"]
                if s["pool"] == pool]
        assert [s["cluster"] for s in mine] == \
            [f"{pool}-{s['cluster']}" for s in borg["cluster"]["host_shapes"]]
        assert [{k: v for k, v in s.items() if k not in ("cluster", "pool")}
                for s in mine] == want
    assert sum(s["count"] for s in fleet["cluster"]["host_shapes"]) == 50332
    for key in ("default_share", "guarantees", "task_runtime_s"):
        assert fleet[key] == borg[key]
    assert fleet["cluster"]["users"] == borg["cluster"]["users"]
    assert fleet["scheduler"] == borg["scheduler"]


def test_pool_busy_divides_per_pool():
    read = harness._reader("pool_busy_ms.fleet")
    tr = {"busy_s": 0.363, "window_s": 8.83,
          "busy_by_chip": {f"/device:TPU:{i}": 0.363 for i in range(4)},
          "match_cycles": 24,
          "match_cycles_by_pool": {p: 6 for p in POOLS}}
    assert abs(read({"trace": tr}) - 60.5) < 1e-9
    # one pool on one chip: the same as device_busy_ms
    one = {**tr, "busy_by_chip": {"/device:TPU:0": 0.363},
           "match_cycles_by_pool": {"batch": 6}}
    assert abs(read({"trace": one}) - 60.5) < 1e-9
    assert read({"trace": None}) is None
    assert read({"trace": {**tr, "match_cycles_by_pool": {}}}) is None


def test_consume_queued_reads_the_worst_pool():
    read = harness._reader("consume_queued_ms.fleet")

    def rec(pool, queued, matched=1):
        a = {"matched": matched}
        if queued is not None:
            a["queued_ms"] = queued
        return {"kind": "consume", "pool": pool, "attrs": a}

    recs = [rec("batch", 1.0), rec("batch", 3.0), rec("dev", 0.5),
            rec("dev", 40.0, matched=0),
            {"kind": "match", "pool": "dev", "attrs": {"queued_ms": 9.0}}]
    assert read({"records": recs}) == 2.0
    # a program whose consume records carry no such attr
    assert read({"records": [rec("batch", None)]}) is None


TINY_RUN = r"""
import json, os, sys, time
T_START = time.perf_counter()
root = sys.argv[1]
sys.path.insert(0, root)
from benchmark import harness


class NoTrace:
    # the CPU has no device trace: the traced run's readers then find
    # no trace, as on a chip run whose profiler saw nothing
    def __init__(self, *a):
        pass

    def result(self, records):
        return None


harness.Tracer = NoTrace
with open(os.path.join(root, "BENCHMARK.json")) as f:
    bench = json.load(f)
with open(os.path.join(root, "benchmark", "tests", "data",
                       "tiny_fleet.json")) as f:
    tiny = json.load(f)
out = harness.run_cell(bench, "fleet-saturated", 2150000777, 4.0, True,
                       T_START, require_tpu=False,
                       files=(tiny["config"], tiny["traffic"]))
print(json.dumps(out), flush=True)
os._exit(0)
"""


def test_tiny_fleet_run_reads_queued_from_records():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", TINY_RUN, ROOT],
                       capture_output=True, text=True, env=env,
                       timeout=600, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["compared"]
    m = out["metrics"]
    assert m["consume_queued_ms.fleet"]["value"] >= 0.0
    assert m["consume_queued_ms.fleet"]["unit"] == "ms"
    assert m["consume_ms.fleet"]["value"] > 0.0
    # the consume's per-record readers hold across pools
    assert m["consume_cpu_ms.fleet"]["value"] > 0.0
    assert m["launch_txn_ms.fleet"]["value"] > 0.0
    assert m["launch_fsync_ms.fleet"]["value"] >= 0.0
    assert m["readback_ms.fleet"]["value"] >= 0.0
    # no device trace: the trace readers give nothing, and the line
    # leaves them out
    assert "pool_busy_ms.fleet" not in m
    assert "device_idle_pct.fleet" not in m
