"""The reference and its control at a size a test run holds.

The program's own fused cycle (ops/cycle.rank_and_match, on the CPU
backend) is judged against the float64 reference: it must agree. The
control, the reference computed in bfloat16 in the program's place,
must fail a limit. Planted faults in the program's answer must fail.
"""
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmark import oracle  # noqa: E402

LIMITS = json.load(open(os.path.join(os.path.dirname(__file__), "..",
                                     "limits.json")))
F32_MAX = np.float32(np.finfo(np.float32).max)


def _inputs(seed, R=1024, P=4096, H=512, U=40, share=(65536.0, 32.0)):
    rng = np.random.default_rng(seed)
    f32, i32 = np.float32, np.int32

    def rows(n, p_valid):
        return dict(
            user=rng.integers(0, U, n).astype(i32),
            mem=rng.integers(256, 4097, n).astype(f32),
            cpus=(rng.integers(1, 9, n) / 2).astype(f32),
            gpus=np.zeros(n, f32), priority=np.full(n, 50, i32),
            start_time=rng.integers(100, 110, n).astype(i32),
            valid=rng.random(n) < p_valid,
            mem_share=np.full(n, share[0], f32),
            cpus_share=np.full(n, share[1], f32),
            gpu_share=np.full(n, F32_MAX))
    run, pend = rows(R, 0.8), rows(P, 0.6)
    pend.update(group=np.full(P, -1, i32), unique_group=np.zeros(P, bool),
                ports=np.zeros(P, i32), forb_slot=np.full(P, -1, i32),
                est_s=np.zeros(P, i32), bonus_slot=np.full(P, -1, i32))
    cap_m = np.full(H, 65536, f32)
    cap_c = np.full(H, 32, f32)
    host = dict(mem=cap_m - rng.integers(0, 40000, H).astype(f32),
                cpus=cap_c - rng.integers(0, 24, H).astype(f32),
                gpus=np.zeros(H, f32), cap_mem=cap_m, cap_cpus=cap_c,
                cap_gpus=np.zeros(H, f32), valid=np.arange(H) < H - 12,
                task_slots=np.full(H, 10_000, i32),
                ports=np.full(H, 100, i32))
    forb = np.zeros((4, H), bool)
    q = (np.full(64, np.inf, f32), np.full(64, np.inf, f32),
         np.full(64, 1e9, f32))
    return run, pend, host, forb, q


def _program_capture(seed, limit=300, C=512, **kw):
    """What the harness captures after one device cycle: the state the
    program leaves (matched rows invalid, hosts depleted) and its
    compact outputs."""
    import jax
    from cook_tpu.ops import cycle as cycle_ops
    from cook_tpu.ops import match as match_ops
    run, pend, host, forb, (qm, qc, qn) = _inputs(seed, **kw)
    hosts = match_ops.Hosts(**{k: host[k] for k in (
        "mem", "cpus", "gpus", "cap_mem", "cap_cpus", "cap_gpus", "valid",
        "task_slots")})
    res = jax.tree.map(np.asarray, cycle_ops.rank_and_match(
        run["user"], run["mem"], run["cpus"], run["priority"],
        run["start_time"], run["valid"], run["mem_share"],
        run["cpus_share"], pend["user"], pend["mem"], pend["cpus"],
        pend["gpus"], pend["priority"], pend["start_time"], pend["valid"],
        pend["mem_share"], pend["cpus_share"], pend["group"],
        pend["unique_group"], hosts, (forb, pend["forb_slot"]), qm, qc, qn,
        num_considerable=C, num_groups=1, sequential=True,
        considerable_limit=np.int32(limit), pend_ports=pend["ports"],
        host_ports=host["ports"]))
    matched = (res.cons_idx >= 0) & (res.cons_host >= 0)
    pend_out = dict(pend, valid=pend["valid"].copy())
    pend_out["valid"][res.cons_idx[matched]] = False
    host_out = dict(host, mem=res.mem_left, cpus=res.cpus_left,
                    gpus=res.gpus_left, task_slots=res.slots_left)
    cap = {"pend": pend_out, "run": run, "host": host_out, "forb": forb,
           "cons_idx": res.cons_idx, "cons_host": res.cons_host,
           "qm": qm, "qc": qc, "qn": qn, "limit": limit}
    return cap, (run, pend, host)


def _expected(cap, U=40):
    """What the benchmark would expect of this capture: the rows as
    drawn, identity user ids, the drawn start seconds, every valid host
    at its capacity."""
    inp = oracle.rebuild_inputs(cap)
    exp = {}
    for t in ("pend", "run"):
        d = inp[t]
        exp[t] = {"known": d["valid"].astype(bool),
                  "user": d["user"].astype(np.int64),
                  "mem": d["mem"].astype(float),
                  "cpus": d["cpus"].astype(float),
                  "lo_s": np.full(d["valid"].shape[0], 100, np.int64),
                  "hi_s": np.full(d["valid"].shape[0], 110, np.int64)}
    exp["pend"]["launched"] = np.zeros(inp["pend"]["valid"].shape[0], bool)
    h = inp["host"]
    exp["host"] = {"known": h["valid"].astype(bool),
                   "cap_mem": h["cap_mem"].astype(float),
                   "cap_cpus": h["cap_cpus"].astype(float)}
    n = cap["qm"].shape[0]
    exp["user_of_id"] = np.where(np.arange(n) < U, np.arange(n), -1)
    exp["share"] = np.array([inp["pend"]["mem_share"][0],
                             inp["pend"]["cpus_share"][0]], np.float64)
    return exp


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_program_agrees_with_the_reference(seed):
    cap, (_run, pend, host) = _program_capture(seed)
    inp = oracle.rebuild_inputs(cap)
    # the rebuilt inputs are the inputs
    assert np.array_equal(inp["pend"]["valid"], pend["valid"])
    assert np.array_equal(inp["host"]["mem"], host["mem"].astype(float))
    assert np.array_equal(inp["host"]["cpus"], host["cpus"].astype(float))
    j = oracle.check_cycle(cap, _expected(cap))
    assert j["head"] == 300 and j["placed"] > 0
    assert j["dru_gap"] <= LIMITS["dru_gap"]
    assert j["fit_gap"] <= LIMITS["fit_gap"]
    assert j["placement_faults"] == 0
    assert j["input_mismatch"] == 0
    assert j["head_already_launched"] == 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails(seed):
    cap, _ = _program_capture(seed)
    exp = _expected(cap)
    ctl = oracle.with_answer(cap, *oracle.control_answer(cap, exp))
    c = oracle.check_cycle(ctl, exp)
    assert c["dru_gap"] > LIMITS["dru_gap"] \
        or c["fit_gap"] > LIMITS["fit_gap"] or c["placement_faults"]


def test_with_answer_reproduces_the_program():
    cap, _ = _program_capture(6)
    inp = oracle.rebuild_inputs(cap)
    again = oracle.with_answer(cap, inp["dev_rows"], inp["dev_hosts"])
    for f in ("mem", "cpus", "task_slots"):
        assert np.array_equal(again["host"][f], cap["host"][f])
    assert np.array_equal(again["pend"]["valid"], cap["pend"]["valid"])


def test_unset_share_reference_keeps_the_order():
    # with no share set the divisor is float32's largest value; the
    # reference must still order by cumulative usage
    cap, _ = _program_capture(4, share=(float(F32_MAX), float(F32_MAX)))
    exp = _expected(cap)
    ref = oracle.reference_inputs(oracle.rebuild_inputs(cap), exp)
    dru, queue = oracle.rank(ref)
    assert np.all(np.diff(dru[queue]) >= 0) and dru[queue[-1]] > 0
    assert oracle.check_cycle(cap, exp)["dru_gap"] <= LIMITS["dru_gap"]


@pytest.mark.parametrize("field,value", [
    ("share", None), ("priority", 7), ("start_time", 5000),
    ("cap_mem", 1024.0), ("quota", 10.0)])
def test_program_made_inputs_are_checked(field, value):
    # every setting the reference would otherwise take from the program
    # is held to what the benchmark configured
    cap, _ = _program_capture(7)
    exp = _expected(cap)
    if field == "share":
        exp["share"] = exp["share"] * 2
    elif field == "cap_mem":
        cap["host"]["cap_mem"] = cap["host"]["cap_mem"].copy()
        cap["host"]["cap_mem"][3] = value
    elif field == "quota":
        cap["qm"] = cap["qm"].copy()
        cap["qm"][2] = value
    else:
        cap["pend"][field] = cap["pend"][field].copy()
        cap["pend"][field][np.flatnonzero(cap["pend"]["valid"])[0]] = value
    assert oracle.check_cycle(cap, exp)["input_mismatch"] > 0


def _judge_altered(change):
    cap, _ = _program_capture(5)
    exp = _expected(cap)
    inp = oracle.rebuild_inputs(cap)
    rows, hosts = list(inp["dev_rows"]), list(inp["dev_hosts"])
    rows, hosts = change(inp, rows, hosts)
    return oracle.judge(oracle.reference_inputs(inp, exp), rows, hosts)


def test_half_the_head_left_out_fails():
    j = _judge_altered(lambda inp, r, h: (r[::2], h[::2]))
    assert j["dru_gap"] > LIMITS["dru_gap"]


def test_placements_dropped_fail():
    j = _judge_altered(lambda inp, r, h: (
        r, [-1 if k % 2 else x for k, x in enumerate(h)]))
    assert j["placement_faults"] > 0


def test_altered_placement_fails():
    def alter(inp, r, h):
        h = list(h)
        valid = np.flatnonzero(inp["host"]["valid"])
        h[0] = int(valid[(np.searchsorted(valid, h[0]) + 7) % len(valid)])
        return r, h
    j = _judge_altered(alter)
    assert j["fit_gap"] > LIMITS["fit_gap"] or j["placement_faults"] > 0


def test_read_log(tmp_path):
    p = tmp_path / "events.log"
    p.write_text(
        '{"t":1,"k":"jobs","items":[{"uuid":"a"},{"uuid":"b"}]}\n'
        '{"t":2,"k":"insts","items":[{"j":"a","i":"t1","h":"h","b":"m"}]}\n'
        '{"t":3,"k":"insts","items":[{"j":"a","i":"t2","h":"h","b":"m"}]}\n'
        '{"t":4,"k":"jobs","items":[{"uu')
    created, launches = oracle.read_log(str(p))
    assert created == {"a", "b"} and launches == {"a": 2}
