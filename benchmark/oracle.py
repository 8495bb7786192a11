"""The plain reference and the comparison that decides `correct`.

Nothing here imports the program. Three groups of checks:

1. Device decisions. A captured match cycle holds the resident state
   the device program left behind and its compact outputs. The inputs
   it ran on are rebuilt exactly from them (sizes are exact in float32,
   so adding back what the cycle depleted restores each host). Every
   field the reference reads from those inputs is first checked against
   what the benchmark itself submitted and configured (`expected`, built
   by the harness from its own job records and the cell's files), and
   the settings are then replaced by the benchmark's own: the default
   share, Cook's default priority, no quota, and each host's capacity
   from the configuration's host shapes. A float64 reference of the same
   semantics then runs:

   - rank: every user's tasks (running first, then pending) ordered by
     (-priority, start, index); each task's DRU is its user's
     cumulative max(mem / mem share, cpus / cpus share); one queue
     sorted by (DRU, user, position within user);
   - considerable: pending jobs in queue order whose user stays within
     quota, capped at the cycle's considerable limit;
   - match: jobs in queue order, each onto the feasible host of best
     cpuMemBinPacker fitness (mean post-assignment mem and cpu
     utilisation), depleting the host.

   Compared numbers: `dru_gap`, the widest float64 DRU gap, relative to
   the head's largest DRU, at a queue position where the program's
   order differs from the reference's; `fit_gap`, the widest float64
   fitness shortfall of a host the program chose against the best
   feasible host given the program's own earlier placements in the
   cycle; `placement_faults`, placements onto a host without room plus
   jobs left unplaced while a host had room; `input_mismatch`, rows and
   hosts whose fields differ from what the benchmark submitted and
   configured; `head_already_launched`, head jobs the backend had
   received before the cycle was dispatched.

2. Store and backend truth (the backend ledger): launches of unknown,
   seeded-running or already launched jobs, and launches onto a host
   without room, all counted by the benchmark's own arithmetic.

3. Durability: every 201-acknowledged job and every launched job is
   read back from the event log by a cold parse of the file.
"""
from __future__ import annotations

import json

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16
BIG = 1e9            # a gap where one side has no job at that position
INT_MAX = np.iinfo(np.int32).max
DEFAULT_PRIORITY = 50    # Cook's default job priority; the jobs set none
SECS = 2 ** 30           # the device keeps whole seconds modulo this
SLACK_S = 2              # rounding of those seconds, either side


# ---------------------------------------------------------------------
# rebuilding one cycle's inputs from its capture

def rebuild_inputs(cap: dict) -> dict:
    """Inputs of the device cycle, from the state it produced: matched
    rows were valid before it, and each host had what the matched jobs
    took from it added back."""
    pend = {k: np.asarray(v) for k, v in cap["pend"].items()}
    host = {k: np.asarray(v) for k, v in cap["host"].items()}
    idx = np.asarray(cap["cons_idx"])
    hst = np.asarray(cap["cons_host"])
    m = (idx >= 0) & (hst >= 0)
    rows, hs = idx[m], hst[m]
    pend["valid"] = pend["valid"].copy()
    pend["valid"][rows] = True
    H = host["mem"].shape[0]
    f64 = np.float64
    host_in = dict(host)
    host_in["mem"] = host["mem"].astype(f64) + np.bincount(
        hs, pend["mem"][rows].astype(f64), H)
    host_in["cpus"] = host["cpus"].astype(f64) + np.bincount(
        hs, pend["cpus"][rows].astype(f64), H)
    host_in["gpus"] = host["gpus"].astype(f64) + np.bincount(
        hs, pend["gpus"][rows].astype(f64), H)
    host_in["task_slots"] = host["task_slots"].astype(np.int64) \
        + np.bincount(hs, minlength=H)
    host_in["ports"] = host["ports"].astype(np.int64) + np.bincount(
        hs, pend["ports"][rows].astype(np.int64), H)
    return {"pend": pend, "run": {k: np.asarray(v)
                                  for k, v in cap["run"].items()},
            "host": host_in, "forb": np.asarray(cap["forb"]),
            "qm": np.asarray(cap["qm"]), "qc": np.asarray(cap["qc"]),
            "qn": np.asarray(cap["qn"]), "limit": int(cap["limit"]),
            "C": int(idx.shape[0]),
            "dev_rows": idx[idx >= 0].tolist(),
            "dev_hosts": hst[idx >= 0].tolist()}


def _within(start, lo, hi):
    """Device seconds (modulo SECS) inside [lo, hi] wall seconds."""
    off = (start.astype(np.int64) - lo % SECS + SLACK_S) % SECS
    return off <= hi - lo + 2 * SLACK_S


def input_mismatch(inp: dict, exp: dict) -> int:
    """Valid rows and hosts whose fields differ from what the benchmark
    submitted and configured: a row's user, mem, cpus, submit or start
    second, priority and share; a job's gpus, ports and constraints
    (the cells' jobs have none); a user's quota (none is set); a host's
    capacity and what is left of it; a configured host the device
    lacks."""
    bad = 0
    uid = exp["user_of_id"]
    share = np.asarray(exp["share"]).astype(np.float32)   # as kept there
    for t in ("pend", "run"):
        d, e = inp[t], exp[t]
        user = d["user"].astype(np.int64)
        known_id = (user >= 0) & (user < uid.shape[0])
        ok = (e["known"]
              & known_id & (uid[np.where(known_id, user, 0)] == e["user"])
              & (d["mem"] == e["mem"]) & (d["cpus"] == e["cpus"])
              & (d["gpus"] == 0) & (d["priority"] == DEFAULT_PRIORITY)
              & _within(d["start_time"], e["lo_s"], e["hi_s"])
              & (d["mem_share"] == share[0])
              & (d["cpus_share"] == share[1]))
        if t == "pend":
            ok &= (d["ports"] == 0) & (d["forb_slot"] < 0)
        bad += int(np.count_nonzero(d["valid"].astype(bool) & ~ok))
    # no quota: none may bind, whatever the whole pool holds
    h, eh = inp["host"], exp["host"]
    in_use = uid >= 0
    bad += int(np.count_nonzero(in_use & (
        (inp["qm"] < eh["cap_mem"].sum()) | (inp["qc"] < eh["cap_cpus"].sum())
        | (inp["qn"] < inp["pend"]["valid"].shape[0]
           + inp["run"]["valid"].shape[0]))))
    hv = h["valid"].astype(bool)
    cm = h["cap_mem"].astype(np.float64)
    cc = h["cap_cpus"].astype(np.float64)
    ok = (eh["known"] & (cm == eh["cap_mem"]) & (cc == eh["cap_cpus"])
          & (h["cap_gpus"] == 0) & (h["gpus"] == 0)
          & (h["mem"] >= 0) & (h["mem"] <= cm)
          & (h["cpus"] >= 0) & (h["cpus"] <= cc))
    bad += int(np.count_nonzero(hv & ~ok))
    bad += int(np.count_nonzero(eh["known"] & ~hv))
    return bad


def reference_inputs(inp: dict, exp: dict) -> dict:
    """The inputs the reference runs on: the program's rows and what its
    hosts have left, with every setting the benchmark's own (the default
    share, the default priority, no quota, configured capacities)."""
    ref = dict(inp)
    for t in ("pend", "run"):
        d = dict(inp[t])
        n = d["valid"].shape[0]
        d["mem_share"] = np.full(n, float(exp["share"][0]))
        d["cpus_share"] = np.full(n, float(exp["share"][1]))
        d["priority"] = np.full(n, DEFAULT_PRIORITY, np.int64)
        ref[t] = d
    ref["host"] = dict(inp["host"], cap_mem=exp["host"]["cap_mem"],
                       cap_cpus=exp["host"]["cap_cpus"],
                       valid=exp["host"]["known"])
    U = inp["qm"].shape[0]
    ref["qm"], ref["qc"], ref["qn"] = (np.full(U, np.inf),) * 3
    return ref


def check_cycle(cap: dict, exp: dict) -> dict:
    """Every compared number of one captured cycle."""
    inp = rebuild_inputs(cap)
    j = judge(reference_inputs(inp, exp), inp["dev_rows"], inp["dev_hosts"])
    j["input_mismatch"] = input_mismatch(inp, exp)
    j["head_already_launched"] = int(np.count_nonzero(
        exp["pend"]["launched"][np.asarray(inp["dev_rows"], np.int64)]))
    return j


# ---------------------------------------------------------------------
# the reference, in float64 (or bfloat16 for the control)

def _segment_cumsum(vals, starts, dtype):
    """Per-user inclusive sums: one running sum over every task in user
    order, less the running sum before each user's first task — in
    float64 exact for these sizes, in bfloat16 as coarse as that type
    makes it."""
    v = vals.astype(dtype)
    total = np.cumsum(v, dtype=dtype)
    first = np.flatnonzero(starts)
    seg = np.cumsum(starts) - 1
    base = (total - v)[first][seg]
    return (total - base).astype(dtype)


def rank(inp: dict, dtype=np.float64):
    """(pending DRU, pending queue order of valid rows)."""
    r, p = inp["run"], inp["pend"]
    R = r["user"].shape[0]
    user = np.concatenate([r["user"], p["user"]]).astype(np.int64)
    valid = np.concatenate([r["valid"], p["valid"]]).astype(bool)
    prio = np.concatenate([r["priority"], p["priority"]]).astype(np.int64)
    start = np.concatenate([r["start_time"],
                            p["start_time"]]).astype(np.int64)
    n = user.shape[0]
    idx = np.arange(n)
    key = np.where(valid, user, INT_MAX)
    perm = np.lexsort((idx, start, -prio, key))
    s_key = key[perm]
    starts = np.r_[True, s_key[1:] != s_key[:-1]]
    work = np.float64 if dtype == np.float64 else np.float32
    vals = {}
    for f in ("mem", "cpus"):
        v = np.concatenate([r[f], p[f]]).astype(np.float64)[perm]
        vals[f] = np.where(valid[perm], v, 0.0)
    cm = _segment_cumsum(vals["mem"], starts, dtype).astype(work)
    cc = _segment_cumsum(vals["cpus"], starts, dtype).astype(work)
    ms = np.concatenate([r["mem_share"], p["mem_share"]]).astype(work)[perm]
    cs = np.concatenate([r["cpus_share"],
                         p["cpus_share"]]).astype(work)[perm]
    s_dru = np.maximum(cm / ms, cc / cs)
    if dtype != np.float64:
        s_dru = s_dru.astype(dtype).astype(np.float64)
    s_dru = np.where(valid[perm], s_dru.astype(np.float64), np.inf)
    start_idx = np.maximum.accumulate(np.where(starts, idx, -1))
    within = idx - start_idx
    order = perm[np.lexsort((within, user[perm], s_dru))]
    dru = np.empty(n)
    dru[perm] = s_dru
    rank_of = np.empty(n, np.int64)
    rank_of[order] = idx
    prank = np.where(p["valid"], rank_of[R:], INT_MAX)
    queue = np.argsort(prank, kind="stable")
    nvalid = int(p["valid"].sum())
    return dru[R:], queue[:nvalid]


def considerable(inp: dict, queue) -> list:
    """The head the match walks: queue order, within quota, capped."""
    r, p = inp["run"], inp["pend"]
    U = inp["qm"].shape[0]
    rv = r["valid"].astype(bool)
    ru = np.where(rv, r["user"], U)
    use = {f: np.bincount(ru, np.where(rv, r[f], 0.0).astype(np.float64),
                          U + 1)[:U] for f in ("mem", "cpus")}
    use["n"] = np.bincount(ru, minlength=U + 1)[:U].astype(np.float64)
    q = {"mem": inp["qm"].astype(np.float64),
         "cpus": inp["qc"].astype(np.float64),
         "n": inp["qn"].astype(np.float64)}
    cum = {k: np.zeros(U) for k in q}
    cap = min(inp["C"], inp["limit"])
    head = []
    for row in queue.tolist():
        if len(head) >= cap:
            break
        u = int(p["user"][row])
        add = {"mem": float(p["mem"][row]), "cpus": float(p["cpus"][row]),
               "n": 1.0}
        for k in q:
            cum[k][u] += add[k]
        if all(use[k][u] + cum[k][u] - q[k][u] <= 0 for k in q):
            head.append(row)
    return head


def _fitness(cap_m, cap_c, left_m, left_c, jm, jc):
    with np.errstate(divide="ignore", invalid="ignore"):
        fm = np.where(cap_m > 0, (cap_m - left_m + jm) / cap_m, 0.0)
        fc = np.where(cap_c > 0, (cap_c - left_c + jc) / cap_c, 0.0)
    return 0.5 * (fm + fc)


def _feasible(inp, row, left_m, left_c, left_g, slots, ports):
    p, h = inp["pend"], inp["host"]
    jm, jc, jg = float(p["mem"][row]), float(p["cpus"][row]), \
        float(p["gpus"][row])
    ok = h["valid"].astype(bool) & (slots > 0)
    slot = int(p["forb_slot"][row])
    if slot >= 0:
        ok &= ~inp["forb"][slot].astype(bool)
    ok &= int(p["ports"][row]) <= ports
    ok &= (left_m + 1e-6 >= jm) & (left_c + 1e-6 >= jc)
    gpu_host = h["cap_gpus"] > 0
    ok &= np.where(jg > 0, gpu_host & (left_g + 1e-6 >= jg), ~gpu_host)
    return ok, jm, jc, jg


def match(inp: dict, head: list, dtype=np.float64) -> list:
    """Greedy best-fit of the head in order (the reference's own
    placements; with dtype bfloat16 the control's)."""
    h = inp["host"]
    left_m = h["mem"].astype(dtype)
    left_c = h["cpus"].astype(dtype)
    left_g = h["gpus"].astype(np.float64)
    slots = h["task_slots"].astype(np.int64)
    ports = h["ports"].astype(np.int64)
    cap_m, cap_c = h["cap_mem"].astype(dtype), h["cap_cpus"].astype(dtype)
    out = []
    for row in head:
        ok, jm, jc, jg = _feasible(inp, row, left_m.astype(np.float64),
                                   left_c.astype(np.float64), left_g,
                                   slots, ports)
        if not ok.any():
            out.append(-1)
            continue
        fit = _fitness(cap_m, cap_c, left_m, left_c, dtype(jm),
                       dtype(jc)).astype(np.float64)
        best = int(np.argmax(np.where(ok, fit, -1.0)))
        out.append(best)
        left_m[best] -= dtype(jm)
        left_c[best] -= dtype(jc)
        left_g[best] -= jg
        slots[best] -= 1
        ports[best] -= int(inp["pend"]["ports"][row])
    return out


def judge(inp: dict, rows: list, hosts: list) -> dict:
    """Score a head (queue-ordered pending rows) and its placements
    against the float64 reference."""
    dru, queue = rank(inp)
    ref = considerable(inp, queue)
    scale = max([abs(dru[r]) for r in ref if np.isfinite(dru[r])] or [0.0])
    scale = scale if scale > 0 else 1.0
    dru_gap = 0.0
    for k in range(max(len(rows), len(ref))):
        a = rows[k] if k < len(rows) else None
        b = ref[k] if k < len(ref) else None
        if a == b:
            continue
        if a is None or b is None \
                or not (np.isfinite(dru[a]) and np.isfinite(dru[b])):
            gap = BIG
        else:
            gap = abs(dru[a] - dru[b]) / scale
        dru_gap = max(dru_gap, gap)
    h = inp["host"]
    left_m = h["mem"].astype(np.float64).copy()
    left_c = h["cpus"].astype(np.float64).copy()
    left_g = h["gpus"].astype(np.float64).copy()
    slots = h["task_slots"].astype(np.int64).copy()
    ports = h["ports"].astype(np.int64).copy()
    cap_m = h["cap_mem"].astype(np.float64)
    cap_c = h["cap_cpus"].astype(np.float64)
    fit_gap, faults = 0.0, 0
    for row, hst in zip(rows, hosts):
        ok, jm, jc, jg = _feasible(inp, row, left_m, left_c, left_g,
                                   slots, ports)
        if hst < 0:
            faults += int(ok.any())
            continue
        if not ok[hst]:
            faults += 1
        else:
            fit = _fitness(cap_m, cap_c, left_m, left_c, jm, jc)
            fit_gap = max(fit_gap, float(np.max(fit[ok]) - fit[hst]))
        left_m[hst] -= jm
        left_c[hst] -= jc
        left_g[hst] -= jg
        slots[hst] -= 1
        ports[hst] -= int(inp["pend"]["ports"][row])
    return {"dru_gap": dru_gap, "fit_gap": fit_gap,
            "placement_faults": faults, "head": len(rows),
            "placed": sum(1 for x in hosts if x >= 0)}


def control_answer(cap: dict, exp: dict):
    """The control: the reference computed in bfloat16 in the program's
    place. Its head and placements."""
    ref = reference_inputs(rebuild_inputs(cap), exp)
    _dru, queue = rank(ref, BF16)
    head = considerable(ref, queue)
    return head, match(ref, head, BF16)


def with_answer(cap: dict, rows: list, hosts: list) -> dict:
    """The capture as it would read had the device answered with this
    head and these placements: the same inputs, the answer's rows gone
    from the pending set and its placements taken from the hosts."""
    inp = rebuild_inputs(cap)
    C = np.asarray(cap["cons_idx"]).shape[0]
    idx = np.full(C, -1, np.int32)
    hst = np.full(C, -1, np.int32)
    idx[:len(rows)] = rows
    hst[:len(hosts)] = hosts
    m = (idx >= 0) & (hst >= 0)
    pend = {k: np.asarray(v) for k, v in cap["pend"].items()}
    pend["valid"] = inp["pend"]["valid"].copy()
    pend["valid"][idx[m]] = False
    host = {k: np.asarray(v) for k, v in cap["host"].items()}
    H = host["mem"].shape[0]
    for f in ("mem", "cpus", "gpus"):
        host[f] = (inp["host"][f] - np.bincount(
            hst[m], pend[f][idx[m]].astype(np.float64), H)
        ).astype(host[f].dtype)
    host["task_slots"] = (inp["host"]["task_slots"] - np.bincount(
        hst[m], minlength=H)).astype(host["task_slots"].dtype)
    host["ports"] = (inp["host"]["ports"] - np.bincount(
        hst[m], pend["ports"][idx[m]].astype(np.int64), H)
    ).astype(host["ports"].dtype)
    return dict(cap, pend=pend, host=host, cons_idx=idx, cons_host=hst)


# ---------------------------------------------------------------------
# a captured cycle on disk (run.py --save-cycles; the control; tests)

def save_cycle(path: str, cap: dict, exp: dict) -> None:
    flat = {}
    for top, d in (("", cap), ("exp.", exp)):
        for k, v in d.items():
            if isinstance(v, dict):
                flat.update({f"{top}{k}.{f}": np.asarray(x)
                             for f, x in v.items()})
            else:
                flat[f"{top}{k}"] = np.asarray(v)
    np.savez_compressed(path, **flat)


def load_cycle(path: str):
    """(capture, expected) as save_cycle wrote them."""
    cap: dict = {}
    exp: dict = {}
    with np.load(path) as z:
        for k in z.files:
            d = cap
            parts = k.split(".")
            if parts[0] == "exp":
                d, parts = exp, parts[1:]
            if len(parts) == 2:
                d.setdefault(parts[0], {})[parts[1]] = z[k]
            else:
                d[parts[0]] = z[k]
    cap["limit"] = int(cap["limit"])
    return cap, exp


# ---------------------------------------------------------------------
# durability: a cold parse of the event log

def read_log(path: str):
    """(uuids of created jobs, uuid -> launch records) from the event
    log file; a torn final line ends the read."""
    created, launches = set(), {}
    with open(path, "rb") as f:
        for line in f:
            try:
                ev = json.loads(line)
            except ValueError:
                break
            k = ev.get("k")
            if k == "jobs":
                created.update(it["uuid"] for it in ev["items"])
            elif k == "job":
                created.add(ev["job"]["uuid"])
            elif k == "insts":
                for it in ev["items"]:
                    launches[it["j"]] = launches.get(it["j"], 0) + 1
            elif k == "inst":
                launches[ev["job"]] = launches.get(ev["job"], 0) + 1
    return created, launches
