"""Several pools through the served path, at a tiny size on the CPU.

A configuration may declare pools, a federation block and a pool per
host shape (data/tiny_pools.json: two pools on one device;
data/tiny_fleet.json: four pools, one per device of four). Every pool is
judged: its cycles are captured and compared, and a fault planted in the
last pool alone makes the run not correct. The pools must sit on the
cell's devices, one to a device where the cell has more than one. A configuration without pools draws and
serves exactly what it did before pools existed: the golden values
below were recorded from the harness before pools were added.
"""
import hashlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import generator, harness, workload  # noqa: E402

CYCLE_NUMBERS = ("dru_gap", "fit_gap", "placement_faults", "input_mismatch",
                 "head_already_launched", "cycles_checked_missing")


def _run(fault, seed, data, chips=1, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(HERE, "faults.py"), fault, str(seed),
         data, str(chips)], capture_output=True, text=True, env=env,
        timeout=600, cwd=ROOT)


def _result(p):
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _placed(stderr):
    line = next(ln for ln in stderr.splitlines()
                if ln.startswith("diag: pool -> chip "))
    return json.loads(line[len("diag: pool -> chip "):])


def test_two_pools_sound_run_checks_every_pool():
    p = _run("none", 7, "tiny_pools.json")
    out = _result(p)
    assert out["correct"] is True, out["compared"]
    assert out["samples"]["cycles_checked"] == 2 * harness.N_CAPTURES
    assert out["compared"]["cycles_checked_missing"]["value"] == 0
    assert _placed(p.stderr) == {"batch": [0], "research": [0]}
    # both pools launched in the window
    for pool in ("batch", "research"):
        line = next(ln for ln in p.stderr.splitlines()
                    if ln.startswith(f"diag: pool {pool}:"))
        n = int(line.split("launches in the window ", 1)[1].split(";")[0])
        assert n > 0, line


@pytest.mark.parametrize("fault", ["state_unchanged", "altered_answer"])
def test_fault_in_the_last_pool_is_caught(fault):
    out = _result(_run(fault, 7, "tiny_pools.json"))
    assert out["correct"] is False
    # the last pool's captured cycles fail, not only the backend ledger
    bad = {k for k, c in out["compared"].items() if c["value"] > c["limit"]}
    assert bad & set(CYCLE_NUMBERS), out["compared"]


def test_each_pool_on_its_own_device():
    p = _run("none", 13, "tiny_fleet.json", chips=4, devices=4)
    out = _result(p)
    assert out["correct"] is True, out["compared"]
    assert out["samples"]["cycles_checked"] == 4 * harness.N_CAPTURES
    placed = _placed(p.stderr)
    assert sorted(placed) == ["batch", "dev", "prod", "research"]
    assert sorted(d for ds in placed.values() for d in ds) == [0, 1, 2, 3]


def _collide(cfg):
    # crc32 puts pa and pc on device 3 and no pool on device 2
    names = ["pa", "pb", "pc", "pd"]
    cfg["default_pool"] = names[0]
    cfg["pools"] = [{"name": n} for n in names]
    for s, n in zip(cfg["cluster"]["host_shapes"], names):
        s["cluster"] = s["pool"] = n
    cfg["federation"]["groups"]["fleet"]["pools"] = names
    return {"pa": [3], "pb": [1], "pc": [3], "pd": [0]}


def _no_federation(cfg):
    # without a federation block every pool stays on the first device
    del cfg["federation"]
    return {"batch": [0], "research": [0], "prod": [0], "dev": [0]}


@pytest.mark.parametrize("edit", [_collide, _no_federation],
                         ids=["crc32_collision", "no_federation"])
def test_two_pools_on_one_device_stop_the_run(tmp_path, edit):
    with open(os.path.join(HERE, "data", "tiny_fleet.json")) as f:
        tiny = json.load(f)
    placed = edit(tiny["config"])
    path = tmp_path / "placed.json"
    path.write_text(json.dumps(tiny))
    p = _run("none", 13, str(path), chips=4, devices=4)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "two pools share a chip" in p.stderr
    assert _placed(p.stderr) == placed


def test_placement_check():
    harness.check_placement({"a": [0], "b": [1]}, [0, 1])
    # a cell of one chip holds every pool on it
    harness.check_placement({"a": [0], "b": [0]}, [0])
    with pytest.raises(harness.PlacementError, match="share a chip"):
        harness.check_placement({"a": [0], "b": [0]}, [0, 1])
    with pytest.raises(harness.PlacementError, match="hold no pool"):
        harness.check_placement({"a": [0], "b": [1]}, [0, 1, 2, 3])
    with pytest.raises(harness.PlacementError, match="outside"):
        harness.check_placement({"a": [0], "b": [5]}, [0, 1])
    with pytest.raises(harness.PlacementError, match="outside"):
        harness.check_placement({"a": [2]}, [0])


def test_server_config_passes_pools_through():
    with open(os.path.join(HERE, "data", "tiny_fleet.json")) as f:
        cfg = json.load(f)["config"]
    out = harness.server_config(cfg, "events.log", 1)
    for key in ("default_pool", "pools", "federation"):
        assert out[key] == cfg[key]
    assert [c["pool"] for c in out["clusters"]] == \
        ["batch", "research", "prod", "dev"]
    with open(os.path.join(HERE, "data", "tiny.json")) as f:
        plain = json.load(f)["config"]
    out = harness.server_config(plain, "events.log", 1)
    assert set(out) == {"port", "log_path", "auth", "scheduler", "clusters"}
    assert all("pool" not in c for c in out["clusters"])


def test_pool_streams_differ_and_repeat():
    with open(os.path.join(HERE, "data", "tiny_pools.json")) as f:
        tiny = json.load(f)
    traffic = {**tiny["traffic"], "runtime_s": [10, 30]}
    a = workload.draw(tiny["config"], traffic, 2 ** 31 + 5)
    b = workload.draw(tiny["config"], traffic, 2 ** 31 + 5)
    assert [d.pool for d in a] == ["batch", "research"]
    assert [d.pending for d in a] == [d.pending for d in b]
    assert a[0].pending != a[1].pending
    assert len(a[1].pending) == traffic["pending"]
    for d in a:
        # the running set sits on its own pool's hosts only
        assert {h.split("-host-")[0] for h in d.hosts} == {d.pool}
    ids = [t for d in a for t in d.task_ids]
    assert len(set(ids)) == len(ids)


# -- the accepted cells draw and serve what they did before pools ------

def _h(obj) -> str:
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()) \
        .hexdigest()[:16]


GOLDEN = {
    ("borg-cell", 1): {
        "server_config": "891dc2486b1a04b3", "backlog": "be82a5bff30f483e",
        "running": "b90de65a8faa6c1e", "rest": "18c8a264d55e3a84",
        "placement": "e4eea516d3f22e91", "residual": "58abeebd425d9126",
        "captures": "b0494c404d63ad2a", "posts": "d7d3972d99d89b3f"},
    ("borg-cell", 3000000019): {
        "server_config": "891dc2486b1a04b3", "backlog": "da9359701c9f49da",
        "running": "ca19e57a3a3b403d", "rest": "a10108a80d1ed587",
        "placement": "963c4f93e072508c", "residual": "de21317bf738f098",
        "captures": "accbfc66a590418b", "posts": "e5f60a797723af67",
        "draw": {"pending": "0f9e7962edc98850",
                 "running": "9550765205dd392d",
                 "hosts": "a5e6938e1e09ecdf",
                 "remaining": "cbe250f1ba96e6a8"}},
    ("cook-default", 1): {
        "server_config": "5f748664bfb44ce4", "backlog": "eb087632b935a430",
        "running": "e4eb6d7bb63edc71", "rest": "e6e5932880d0d026",
        "placement": "be6aa8d8e6e2a9b9", "residual": "58abeebd425d9126",
        "arrivals": "6dc686b950713cfa", "captures": "b0494c404d63ad2a",
        "posts": "d7d3972d99d89b3f"},
    ("cook-default", 3000000019): {
        "server_config": "5f748664bfb44ce4", "backlog": "f30897075239615b",
        "running": "88bbfb30b35929ca", "rest": "c67f1a04c8a4362b",
        "placement": "1f53ac22d45a4f26", "residual": "de21317bf738f098",
        "arrivals": "12e11f8a2ae090e7", "captures": "accbfc66a590418b",
        "posts": "e5f60a797723af67",
        "draw": {"pending": "26b4836021e41097",
                 "running": "7e5f359b26276f94",
                 "hosts": "80a3a1bb1ac065af",
                 "remaining": "2e603ac2be1161c5"}},
}


@pytest.mark.parametrize("config_name,seed", sorted(GOLDEN))
def test_draws_and_server_config_match_the_parent(config_name, seed):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["config"] == config_name)
    _c, config, traffic = harness.find_cell(bench, cell["name"])
    traffic = {**traffic, "runtime_s": config["task_runtime_s"]}
    n_users = len(workload.user_names(config))
    got = {"server_config": _h(harness.server_config(config, "events.log",
                                                     1234))}
    for s in ("backlog", "running", "rest"):
        got[s] = _h(workload.JobStream(seed, s, traffic, n_users).take(4096))
    run = workload.JobStream(seed, "running", traffic, n_users).take(4096)
    rng = workload.rng(seed, "placement")
    got["placement"] = _h(workload.place_running(
        rng, run, workload.host_shapes(config)))
    got["residual"] = _h(workload.residual_runtimes(
        rng, 4096, config["task_runtime_s"]).tolist())
    if traffic["mode"] == "open":
        got["arrivals"] = _h(generator.open_schedule(
            workload.rng(seed, "arrivals"), traffic, -5.0, 33.0, n_users))
    got["captures"] = _h(workload.rng(seed, "captures").uniform(
        0.15, 0.85, 3).tolist())
    got["posts"] = _h(workload.rng(seed, "posts").integers(
        0, 500, 64).tolist())
    want = dict(GOLDEN[(config_name, seed)])
    if "draw" in want:
        (d,) = workload.draw(config, traffic, seed)
        assert d.pool is None
        got["draw"] = {"pending": _h(d.pending), "running": _h(d.running),
                       "hosts": _h(d.hosts), "remaining": _h(d.remaining)}
    assert got == want
