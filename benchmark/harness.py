"""One run of one cell: seed, boot, warm up, measure, check.

The cell's configuration and traffic are found by name in
`benchmark/configs/` and `benchmark/traffic/`; its per-layer metrics by
name in `benchmark/metrics/`. Nothing here names a cell.

A run, in one process that owns the chips:

1. draws each pool's backlog, running set and REST job stream from the
   seed (`workload.draw`), and writes the backlogs and running sets
   into a fresh durable event log (inside the checkout, on local disk);
2. boots the real scheduler in-process through `rest.server.serve`
   with the mock backend replaced by `backend.BenchCluster`; the
   restore path replays the log and the first resident build sees the
   whole backlog; reads which chip each pool's resident state lives on
   and stops unless the pools cover the cell's chips and no others, one
   to a chip where the cell has several (`check_placement`); sets the configuration's default share
   in each pool through `POST /share` and has each resident pool
   rebuilt, since rows built at restore keep the share they were built
   with;
3. drives the cell's traffic for its warm-up seconds, then measures for
   `--seconds`; with `--trace 1` a middle part of the window is traced;
4. after the window: follows the window's jobs through a drain while
   arrivals continue, reads peak device memory, stops the server, and
   checks correctness (`oracle`): `N_CAPTURES` cycles of every pool,
   each judged against that pool's own jobs, hosts and share.

A configuration without pools runs as one pool, the server's default,
exactly as before pools existed.
"""
from __future__ import annotations

import http.client
import importlib.util
import json
import logging
import os
import shutil
import socket
import sys
import threading
import time

import numpy as np

from benchmark import generator, oracle, tracing, workload
from benchmark.compile_meter import CompileMeter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
WORK = os.path.join(ROOT, ".bench_work")
N_CAPTURES = 3


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(bench: dict, name: str, files=None):
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    if files is not None:
        return cell, files[0], files[1]
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def metric_names(bench: dict, cell: str, section: str) -> list:
    return [m for m in bench[section]
            if cell in m.get("workloads", [cell])]


def _reader(name: str):
    """The reader of per-layer metric `name` (`base.suffix` shares the
    reader `metrics/base.py`)."""
    base = name.split(".", 1)[0]
    path = os.path.join(HERE, "metrics", base + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_metric_{base}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Capture:
    """Keeps copies of the resident state right after chosen match
    cycles, with the cycle's compact outputs, the inputs the host passed
    and the program's row and host maps of that moment. A cycle is taken
    when its dispatch starts at or after the next due time. The device
    copies (pending, running, hosts, constraint masks) are dispatched on
    the cycle thread, since the next cycle donates the state, and read
    back after the window; the maps are C-level copies under the pool's
    mirror lock."""

    KEYS = ("pend", "run", "host", "forb")

    def __init__(self, rp):
        self.rp = rp
        self.due: list = []          # perf_counter times, ascending
        self.taken: list = []
        self._orig = rp.dispatch
        rp.dispatch = self._dispatch

    def _dispatch(self, *a, **kw):
        t = time.perf_counter()
        out = self._orig(*a, **kw)
        if self.due and t >= self.due[0]:
            self.due.pop(0)
            self._take(t, out, a, kw)
        return out

    def _take(self, t, out, a, kw):
        import jax
        import jax.numpy as jnp
        _bundle, qm, qc, qn = a[:4]
        state = {k: jax.tree.map(jnp.copy, self.rp.state[k])
                 for k in self.KEYS}
        with self.rp.mirror_lock:
            row_uuid = list(self.rp.row_uuid)
            run_row = dict(self.rp.run_row)
            host_names = list(self.rp.host_names)
        self.taken.append({
            "t": t, "state": state,
            "cons_idx": out.cons_idx, "cons_host": out.cons_host,
            "qm": np.array(qm), "qc": np.array(qc), "qn": np.array(qn),
            "limit": int(kw.get("considerable_limit", a[4] if len(a) > 4
                                else 0)),
            "row_uuid": row_uuid, "run_row": run_row,
            "host_names": host_names})

    def wait_taken(self, n: int, timeout: float) -> bool:
        deadline = time.perf_counter() + timeout
        while len(self.taken) < n and time.perf_counter() < deadline:
            time.sleep(0.01)
        return len(self.taken) >= n


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devs[0].platform}; the "
                       "benchmark never measures on another platform")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs


ADMIN = "bench-admin"     # the operator who sets the default share


def server_config(config: dict, log_path: str, port: int) -> dict:
    """The server's configuration file for a cell. A configuration's
    `default_pool`, `pools` and `federation` pass through unchanged,
    and each mock cluster serves its host shape's `pool`; without them
    the file is what it was before pools existed."""
    clusters = []
    for s in workload.host_shapes(config):
        c = {"kind": "mock", "name": s.cluster, "hosts": s.count,
             "host_mem": s.mem, "host_cpus": s.cpus}
        if s.pool is not None:
            c["pool"] = s.pool
        clusters.append(c)
    out = {"port": port, "log_path": log_path,
           "auth": {"scheme": "header", "admins": [ADMIN]},
           "scheduler": dict(config["scheduler"]),
           "clusters": clusters}
    for key in ("default_pool", "pools", "federation"):
        if key in config:
            out[key] = config[key]
    return out


class PlacementError(RuntimeError):
    """The pools do not sit on the cell's chips as the cell asks."""


def check_placement(placed: dict, chips: list) -> None:
    """`placed` = {pool: [device id]}, `chips` = the ids of the cell's
    chips. Every pool's state lies on the cell's chips and every chip
    holds some; a cell of more than one chip holds one pool to a chip
    (a cell of one chip holds every pool on it)."""
    used = [d for ds in placed.values() for d in ds]
    outside = set(used) - set(chips)
    if outside:
        raise PlacementError(f"pools on chips {sorted(outside)} outside "
                             f"the cell's {sorted(chips)}: {placed}")
    if len(chips) > 1 and len(used) != len(set(used)):
        raise PlacementError(f"two pools share a chip: {placed}")
    idle = set(chips) - set(used)
    if idle:
        raise PlacementError(f"{len(idle)} of the cell's {len(chips)} "
                             f"chips hold no pool: {placed}")


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             fault=None, fault_pool=None, save_dir=None,
             files=None) -> dict:
    """One run. `files` = (config, traffic) in place of the cell's own
    files (tests at a small size); `fault` = fn(coordinator, resident
    pool) called once the server is up with the resident pool of
    `fault_pool` (None: a configuration without pools) (tests that
    break the timed path); `require_tpu=False` skips the look for a
    chip (tests)."""
    cell, config, traffic = find_cell(bench, cell_name, files)
    traffic = {**traffic, "runtime_s": config["task_runtime_s"]}
    import jax
    if require_tpu:
        devs = check_devices(int(cell["chips"]))
    else:
        devs = jax.devices()
    meter = CompileMeter()
    work = os.path.join(WORK, cell_name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        return _run(bench, cell, config, traffic, seed, seconds, trace,
                    t_start, devs, meter, work, fault, fault_pool,
                    save_dir)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _run(bench, cell, config, traffic, seed, seconds, trace, t_start,
         devs, meter, work, fault, fault_pool, save_dir) -> dict:
    from cook_tpu import obs
    from cook_tpu.backends.base import LaunchSpec
    from cook_tpu.obs.metrics import registry
    from cook_tpu.rest import server as srv
    from benchmark import backend

    users = workload.user_names(config)
    # -- 1. the seeded store ------------------------------------------
    draws = workload.draw(config, traffic, seed)
    pools = [d.pool for d in draws]          # [None]: no pools
    pending = [j for d in draws for j in d.pending]
    running = [j for d in draws for j in d.running]
    hosts = [h for d in draws for h in d.hosts]
    task_ids = [t for d in draws for t in d.task_ids]
    remaining = {t: r for d in draws for t, r in d.remaining.items()}
    # uuid -> pool of every job the benchmark submits (None: no pools)
    pool_of = {j[0]: d.pool for d in draws for j in d.pending + d.running}
    jobs = {j[0]: (users[j[1]], j[2], j[3], j[4])
            for j in pending + running}
    cluster_of, caps, caps_of = {}, {}, {p: {} for p in pools}
    for s in workload.host_shapes(config):
        for i in range(s.count):
            h = f"{s.cluster}-host-{i}"
            cluster_of[h] = s.cluster
            caps[h] = caps_of[s.pool][h] = (s.mem, s.cpus)
    log_path = os.path.join(work, "events.log")
    seed_wall = time.time()
    workload.seed_store(log_path, users, pending, running, hosts,
                        cluster_of, task_ids, pool_of)
    seed_window = (int(seed_wall), int(time.time()))
    seed_specs: dict = {}
    for j, h, t in zip(running, hosts, task_ids):
        seed_specs.setdefault(cluster_of[h], []).append(
            LaunchSpec(task_id=t, job_uuid=j[0], hostname=h,
                       command="true", mem=j[2], cpus=j[3]))
    ledger = backend.Ledger(jobs, {j[0] for j in running}, caps, pool_of)
    ledger.task_job.update(zip(task_ids, (j[0] for j in running)))

    # -- 2. boot ------------------------------------------------------
    cfg_path = os.path.join(work, "config.json")
    port = _free_port()
    with open(cfg_path, "w") as f:
        json.dump(server_config(config, log_path, port), f)
    records: list = []
    obs.profiler.add_listener(records.append)
    t_seeded = time.perf_counter()
    with backend.installed(ledger, seed_specs, remaining):
        served = srv.serve(["--config", cfg_path])
    t_booted = time.perf_counter()
    logging.getLogger().setLevel(logging.WARNING)
    logging.getLogger("cook_tpu").setLevel(logging.WARNING)
    served_stopped = False
    try:
        coord = served.coord
        rps = {p: coord._resident[coord.pools.default_pool if p is None
                                  else p] for p in pools}
        placed = {p: sorted(d.id for d in rp.devices())
                  for p, rp in rps.items()}
        names = {rps[p].pool: ids for p, ids in placed.items()}
        print(f"diag: pool -> chip {json.dumps(names)}", file=sys.stderr)
        check_placement(placed,
                        [d.id for d in devs[:int(cell["chips"])]])
        if fault is not None:
            fault(coord, rps[fault_pool])
        caps_boot = {p: (rp.Pcap, rp.Rcap, rp.Hcap)
                     for p, rp in rps.items()}
        caps_taken = {p: Capture(rp) for p, rp in rps.items()}
        # the operator sets the default share in each pool; rows built
        # at restore keep the share they were built with, so each pool
        # rebuilds
        for p, rp in rps.items():
            _post_share(port, config["default_share"], p)
            rp.request_resync()
        t_share = time.perf_counter()
        # traffic starts once a cycle that began after the rebuild was
        # asked for has dispatched in every pool (the second dispatch
        # from now): the first cycle has compiled or loaded, so a cold
        # run's compile cannot pile up a backlog that changes the shapes
        # of later runs; the copies taken compile the capture's copy
        # programs
        for cap in caps_taken.values():
            cap.due = [t_share, t_share]
        for cap in caps_taken.values():
            if not cap.wait_taken(2, 900):
                raise RuntimeError("no match cycle was dispatched after "
                                   "boot")
            cap.taken.clear()

        def registrar(pool):
            def register(batch, user):
                # a POST's jobs belong to the user who sends it, in the
                # pool it names
                for u, _ui, m, c, r in batch:
                    jobs[u] = (user, m, c, r)
                    pool_of[u] = pool
            return register

        warm = float(traffic["warm_s"])
        drain = float(traffic["drain_s"])
        t0 = time.perf_counter() + warm
        gens = []
        for d in draws:
            p = d.pool
            stream = workload.JobStream(seed, "rest", traffic, len(users),
                                        p)
            if traffic["mode"] == "open":
                sched = generator.open_schedule(
                    workload.rng(seed, "arrivals", p), traffic, -warm,
                    seconds + drain, len(users))
                gens.append(generator.OpenLoop(
                    port, sched, stream, users, registrar(p), t0,
                    int(traffic["workers"]), p))
            else:
                gens.append(generator.TopUp(
                    port, stream, workload.rng(seed, "posts", p), users,
                    registrar(p), len(d.pending),
                    lambda p=p: ledger.launched_in(p),
                    int(traffic["pending"]), int(traffic["post_jobs"]),
                    int(traffic["workers"]), p))
        gen = generator.Several(gens)
        gen.start()
        backend.BenchCluster.clock_running.set()
        time.sleep(max(0.0, t0 - time.perf_counter()))
        if traffic["mode"] != "open":
            _settle(coord, float(traffic["settle_s"]))

        # -- 3. the window --------------------------------------------
        t0 = time.perf_counter()
        wall0 = time.time() * 1e3
        setup_s = t0 - t_start
        c0 = meter.snap()
        pend0 = {p: len(rp.pend_row) for p, rp in rps.items()}
        grow0 = _cap_grow(registry)
        ing0 = _hist_counts(registry.histogram("ingest_wait_ms"))
        n_rec0 = len(records)
        for p, cap in caps_taken.items():
            crng = workload.rng(seed, "captures", p)
            cap.due = sorted((t0 + seconds * crng.uniform(
                0.15, 0.85, N_CAPTURES)).tolist())
        tracer = Tracer(t0, seconds, work) if trace else None
        time.sleep(seconds)
        t1 = time.perf_counter()
        pend1 = {p: len(rp.pend_row) for p, rp in rps.items()}
        grow1 = _cap_grow(registry)
        ing1 = _hist_counts(registry.histogram("ingest_wait_ms"))
        n_rec1 = len(records)

        # -- 4. drain, stop -------------------------------------------
        if traffic["mode"] == "open":
            gen.join(drain + 60)
            time.sleep(max(0.0, t1 + drain - time.perf_counter()))
        else:
            gen.stop(60)
        t_end = time.perf_counter()      # jobs are followed until here
        got_caps = {p: cap.wait_taken(N_CAPTURES, 30)
                    for p, cap in caps_taken.items()}
        coord.drain_resident()
        mem_by_chip = {d.id: int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devs[:int(cell["chips"])]}
        mem_peak = max(mem_by_chip.values())
        user_ids = dict(coord.interner.items())
        served.stop()
        served_stopped = True
        obs.profiler.remove_listener(records.append)

        # -- 5. results -----------------------------------------------
        window_recs = [r for r in records[n_rec0:n_rec1]
                       if wall0 <= r["t0_ms"] < wall0 + seconds * 1e3]
        n_compiles = meter.overlapping(t0, t1)
        _diagnose(window_recs, meter, c0, n_compiles, coord, t_start,
                  t_seeded, t_booted, t0)
        launches = {p: sum(1 for t in ledger.pool_receipts.get(p, ())
                           if t0 <= t < t1) for p in pools}
        for p, rp in rps.items():
            _diagnose_pool(window_recs, placed[p], caps_boot[p], rp,
                           pend0[p], pend1[p], launches[p])
        grew = {k: grow1[k] - grow0[k] for k in grow0}
        taken_at = {rps[p].pool: [round(c["t"] - t0, 2) for c in cap.taken]
                    for p, cap in caps_taken.items()}
        print(f"diag: resident_cap_grow_total in the window {grew}; window "
              f"from wall clock {wall0 / 1e3:.3f} s; captures at {taken_at} "
              f"s", file=sys.stderr)
        print(f"diag: memory peak per chip {mem_by_chip}", file=sys.stderr)
        tr = tracer.result(records) if tracer else None
        if tr is not None:
            print(f"diag: traced {tr['window_s']:.3f} s: device busy per "
                  f"chip {tr['busy_by_chip']} s; match cycles begun in "
                  f"it {tr['match_cycles_by_pool']}",
                  file=sys.stderr)
        ctx = {"mode": traffic["mode"], "seconds": seconds,
               "records": window_recs,
               "compiles": n_compiles,
               "ingest_wait": _hist_delta(ing0, ing1),
               "posts": [p for p in gen.posts if t0 <= p.due < t1],
               "t_end": t_end, "trace": tr}
        e2e, attempted, failed = _end_to_end(traffic, gen, ledger, t0, t1,
                                             seconds, t_end)
        e2e["setup_s"] = setup_s
        truth = Truth(jobs, users, user_ids, caps_of,
                      config["default_share"], seed_window, gen.posts,
                      ledger, pool_of)
        cycles = [truth.cycle(c, p) for p, cap in caps_taken.items()
                  if got_caps[p] for c in cap.taken]
        if save_dir:
            os.makedirs(save_dir, exist_ok=True)
            for k, (c, exp) in enumerate(cycles):
                oracle.save_cycle(os.path.join(save_dir, f"cycle{k}.npz"),
                                  c, exp)
        acked = [u for p in gen.posts if p.status == 201 for u in p.uuids]
        compared = correctness(cycles, N_CAPTURES * len(pools), ledger,
                               log_path, acked)
        section = "per_layer" if trace else "end_to_end"
        metrics = {}
        for m in metric_names(bench, cell["name"], section):
            v = _reader(m["name"])(ctx) if trace else e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        d0 = devs[0]
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(devs), "memory_peak_bytes": mem_peak}
        out = {"correct": verdict(compared),
               "attempted": attempted, "failed": failed,
               "metrics": metrics, "device": device}
        if tr is not None:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            out["breakdown"] = {"device_ops": tr["device_ops"],
                                "idle_gaps": tr["idle_gaps"]}
        out["samples"] = {**e2e.pop("samples"),
                          "cycles_checked": len(cycles)}
        out["compared"] = {k: {"value": v, "limit": lim}
                           for k, (v, lim) in compared.items()}
        return out
    finally:
        if not served_stopped:
            served.stop()


def _post_share(port: int, share: dict, pool=None) -> None:
    """POST /share for the default user in `pool` (None: the server's
    default pool), as the deployment's operator."""
    body = {"user": "default", "share": share}
    if pool is not None:
        body["pool"] = pool
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("POST", "/share", body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json",
                              "X-Cook-User": ADMIN})
        resp = conn.getresponse()
        body = resp.read()
    finally:
        conn.close()
    if resp.status != 201:
        raise RuntimeError(f"POST /share: {resp.status} {body[:200]!r}")


def _settle(coord, settle_s: float, give_up_s: float = 90.0) -> None:
    """Hold the warm-up until the overload controller's shed level has
    stood still for `settle_s`: under a standing backlog it climbs one
    rung every few seconds, and a window that starts mid-climb measures
    a different cycle. (An open loop starts on its schedule instead.)"""
    if coord.overload is None:
        return
    start = last = time.perf_counter()
    level = coord.overload.level
    while True:
        time.sleep(0.25)
        now = time.perf_counter()
        if coord.overload.level != level:
            level, last = coord.overload.level, now
        if now - last >= settle_s or now - start >= give_up_s:
            return


def _diagnose(recs, meter, c0, n_compiles, coord, t_start, t_seeded,
              t_booted, t0) -> None:
    """Where the set-up went and what the window's cycles looked like
    (stderr, before the compared numbers)."""
    match = [r["wall_ms"] for r in recs if r["kind"] == "match"]
    cons = [r for r in recs if r["kind"] == "consume"]
    matched = [(r.get("attrs") or {}).get("matched", 0) for r in cons]
    level = coord.overload.level if coord.overload is not None else None
    print(f"diag: set-up {t0 - t_start:.3f} s (draw+seed store "
          f"{t_seeded - t_start:.3f}, boot {t_booted - t_seeded:.3f}, "
          f"warm-up {t0 - t_booted:.3f}); compiles before the window "
          f"{c0[1]} ({c0[0]:.3f} s, {c0[2]} cache hits), in or across "
          f"the window {n_compiles}; slow compiles "
          f"{meter.slow}", file=sys.stderr)
    print(f"diag: window: {len(match)} match cycles, mean wall "
          f"{_mean(match):.3f} ms; {len(cons)} consumes, mean wall "
          f"{_mean([r['wall_ms'] for r in cons]):.3f} ms, mean matched "
          f"{_mean(matched):.1f}; overload level {level}", file=sys.stderr)


def _diagnose_pool(recs, chips, caps_boot, rp, pend0, pend1,
                   launches) -> None:
    """One pool's line: its chip, its caps at boot and at the end and
    the cycles of the window that changed them, its pending rows, its
    launches and cycles in the window."""
    name = rp.pool
    mine = [r for r in recs if r.get("pool") == name]
    match = [r for r in mine if r["kind"] == "match"]
    cons = [r for r in mine if r["kind"] == "consume"]
    grew = [{k: v for k, v in (r.get("attrs") or {}).items()
             if k in ("Pcap", "Rcap", "Hcap", "resync")}
            for r in match if "resync" in (r.get("attrs") or {})]
    print(f"diag: pool {name}: chip {chips}; caps (Pcap, Rcap, Hcap) at "
          f"boot {caps_boot}, at the end {(rp.Pcap, rp.Rcap, rp.Hcap)}, "
          f"changed in the window {grew}; pending rows at window start "
          f"{pend0}, end {pend1}; launches in the window {launches}; "
          f"{len(match)} match cycles, mean wall "
          f"{_mean([r['wall_ms'] for r in match]):.3f} ms; {len(cons)} "
          f"consumes, mean wall {_mean([r['wall_ms'] for r in cons]):.3f}"
          f" ms", file=sys.stderr)


def _mean(xs) -> float:
    return sum(xs) / len(xs) if xs else float("nan")


def _cap_grow(registry) -> dict:
    return {k: registry.counter("resident_cap_grow_total", cap=k).value
            for k in ("Pcap", "Rcap", "Hcap")}


def _hist_counts(h) -> list:
    lines: list = []
    h.render_into(lines, "h", "")
    out = []
    for line in lines:
        if line.startswith("h_bucket"):
            le = line.split('le="', 1)[1].split('"', 1)[0]
            out.append((float("inf") if le == "+Inf" else float(le),
                        int(line.rsplit(" ", 1)[1])))
    return out


def _hist_delta(a: list, b: list) -> list:
    """(upper bound, count in bucket) over the window."""
    cum = [(ub, cb - ca) for (ub, ca), (_u, cb) in zip(a, b)]
    out, prev = [], 0
    for ub, c in cum:
        out.append((ub, c - prev))
        prev = c
    return out


class Tracer:
    """Traces the middle of the window on a thread of its own; the
    reduction runs after the window. The wall clock at the trace's
    start only counts the match cycles that began in the traced part;
    idle gaps are named from the trace's own host events."""

    def __init__(self, t0: float, seconds: float, work: str):
        self.a, self.b = t0 + 0.35 * seconds, t0 + 0.65 * seconds
        self.log_dir = os.path.join(work, "trace")
        self.wall = None
        self.error = None
        self._th = threading.Thread(target=self._body, daemon=True)
        self._th.start()

    def _body(self):
        import jax
        from jax.profiler import ProfileOptions, TraceAnnotation
        try:
            time.sleep(max(0.0, self.a - time.perf_counter()))
            opts = ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            try:
                self.wall = time.time() * 1e3
                with TraceAnnotation(tracing.MARKER):
                    time.sleep(max(0.0, self.b - time.perf_counter()))
            finally:
                jax.profiler.stop_trace()
        except Exception as e:       # reported by result()
            self.error = e

    def result(self, records: list) -> dict:
        self._th.join()
        if self.error is not None:
            raise self.error
        from benchmark import scopes
        path = tracing.find_xplane(self.log_dir)
        res = tracing.reduce(tracing.load(path))
        res["device_ops"] = scopes.op_times(path)[:10]
        res["idle_gaps"] = [[label, sec] for label, sec, _beside in
                            scopes.label_gaps(path, res.pop("gaps"))]
        w1 = self.wall + res["window_s"] * 1e3
        traced = [r["pool"] for r in records if r["kind"] == "match"
                  and self.wall <= r["t0_ms"] < w1]
        res["match_cycles"] = len(traced)
        res["match_cycles_by_pool"] = {p: traced.count(p)
                                       for p in sorted(set(traced))}
        return res


def _pct(vals, q) -> float:
    return float(np.percentile(np.asarray(vals, np.float64), q))


def _end_to_end(traffic, gen, ledger, t0, t1, seconds, t_end):
    posts = [p for p in gen.posts if t0 <= p.due < t1]
    if traffic["mode"] == "open":
        lat_l, failed, n_jobs = [], 0, 0
        for p in posts:
            ok = p.status == 201
            n_jobs += len(p.uuids)
            for u in p.uuids:
                got = ledger.launched.get(u) if ok else None
                if got is None or got > t_end:
                    failed += 1
                    # not launched by the end of the drain: its latency
                    # is at least the time it was followed for
                    lat_l.append((t_end - p.due) * 1e3)
                else:
                    lat_l.append((got - p.due) * 1e3)
        e2e = {"launch_p50_ms": _pct(lat_l, 50),
               "launch_p95_ms": _pct(lat_l, 95),
               "samples": {"jobs": len(lat_l), "posts": len(posts)}}
        return e2e, n_jobs, failed
    n = sum(1 for t in ledger.receipts if t0 <= t < t1)
    failed = sum(len(p.uuids) for p in posts if p.status != 201)
    e2e = {"decisions_per_s": n / seconds,
           "samples": {"launches": n, "posts": len(posts)}}
    return e2e, sum(len(p.uuids) for p in posts), failed


class Truth:
    """What the benchmark itself submitted and configured, in the shape
    the oracle compares a captured cycle of one pool with (`expected`):
    for every pending and running row of the capture, the job the
    program's row map names, if the benchmark submitted it to that
    pool, as the benchmark drew it, and the wall seconds within which
    the program stamped its submit or start; the benchmark's user behind
    each of the program's user ids; the capacity of each host the
    configuration gives the pool; the configuration's default share."""

    START_BEFORE_S = 10     # a launch's instance is created this far
    #                         at most before the backend receives it

    def __init__(self, jobs, users, user_ids, caps_of, share,
                 seed_window, posts, ledger, pool_of):
        self.jobs = jobs
        self.user_index = {u: i for i, u in enumerate(users)}
        self.user_ids = user_ids
        self.caps_of = caps_of          # pool -> {host: (mem, cpus)}
        self.share = share              # {"mem", "cpus"}, every pool
        self.pool_of = pool_of          # uuid -> pool (None: no pools)
        self.seed_window = seed_window
        self.ledger = ledger
        off = time.time() - time.perf_counter()
        self.posted = {}
        for p in posts:
            done = p.done if p.done is not None else time.perf_counter()
            w = (int(p.sent + off), int(done + off) + 1)
            for u in p.uuids:
                self.posted[u] = w
        self.off = off

    def _rows(self, n, pairs, window, pool):
        e = {"known": np.zeros(n, bool), "user": np.full(n, -1, np.int64),
             "mem": np.zeros(n), "cpus": np.zeros(n),
             "lo_s": np.zeros(n, np.int64), "hi_s": np.zeros(n, np.int64)}
        for row, u, key in pairs:
            j = self.jobs.get(u)
            if j is None or self.pool_of.get(u) != pool:
                continue
            e["known"][row] = True
            e["user"][row] = self.user_index.get(j[0], -1)
            e["mem"][row], e["cpus"][row] = j[1], j[2]
            e["lo_s"][row], e["hi_s"][row] = window(u, key)
        return e

    def _started(self, u, task):
        if task.startswith("seeded-"):
            return self.seed_window
        got = self.ledger.launched.get(u)
        if got is None:
            return (0, -1)              # never received: no window
        wall = int(got + self.off)
        return (wall - self.START_BEFORE_S, wall + 1)

    def cycle(self, c, pool=None):
        """(capture, expected) of one taken cycle of `pool` (None: a
        configuration without pools), read back."""
        import jax
        st = jax.device_get(c["state"])
        cap = {"pend": st["pend"], "run": st["run"], "host": st["host"],
               "forb": st["forb"], "cons_idx": np.asarray(c["cons_idx"]),
               "cons_host": np.asarray(c["cons_host"]),
               "qm": c["qm"], "qc": c["qc"], "qn": c["qn"],
               "limit": c["limit"]}
        P = cap["pend"]["valid"].shape[0]
        R = cap["run"]["valid"].shape[0]
        H = cap["host"]["valid"].shape[0]
        pend_pairs = [(row, u, None) for row, u in enumerate(c["row_uuid"])
                      if u is not None]
        pend = self._rows(P, pend_pairs, lambda u, _k: self.posted.get(
            u, self.seed_window), pool)
        launched = self.ledger.launched
        pend["launched"] = np.zeros(P, bool)
        for row, u, _k in pend_pairs:
            pend["launched"][row] = launched.get(u, c["t"]) < c["t"]
        run = self._rows(R, [(row, self.ledger.task_job.get(t), t)
                             for t, row in c["run_row"].items()],
                         self._started, pool)
        caps = self.caps_of[pool]
        host = {"known": np.zeros(H, bool), "cap_mem": np.zeros(H),
                "cap_cpus": np.zeros(H)}
        for row, name in enumerate(c["host_names"]):
            if name in caps:
                host["known"][row] = True
                host["cap_mem"][row], host["cap_cpus"][row] = caps[name]
        uid = np.full(cap["qm"].shape[0], -1, np.int64)
        for name, i in self.user_ids.items():
            if 0 <= i < uid.shape[0]:
                uid[i] = self.user_index.get(name, -1)
        share = self.share
        return cap, {"pend": pend, "run": run, "host": host,
                     "user_of_id": uid,
                     "share": np.array([share["mem"], share["cpus"]],
                                       np.float64)}


def correctness(cycles, n_due, ledger, log_path, acked) -> dict:
    """{name: (value, limit)} of every number compared: the captured
    cycles (`(capture, expected)` pairs, `n_due` of them asked for), the
    backend ledger, and the event log read back for the acknowledged
    and launched jobs."""
    limits = load_json(HERE, "limits.json")
    worst = {"dru_gap": 0.0, "fit_gap": 0.0}
    total = {"placement_faults": 0, "input_mismatch": 0,
             "head_already_launched": 0}
    for cap, exp in cycles:
        j = oracle.check_cycle(cap, exp)
        for k in worst:
            worst[k] = max(worst[k], float(j[k]))
        for k in total:
            total[k] += int(j[k])
    out = {"cycles_checked_missing": (n_due - len(cycles), 0)}
    out.update({k: (v, limits[k]) for k, v in worst.items()})
    out.update({k: (v, 0) for k, v in total.items()})
    out["launch_overcommit"] = (ledger.overcommit, 0)
    out["launch_unknown"] = (ledger.unknown, 0)
    out["launch_not_pending"] = (ledger.not_pending, 0)
    out["launch_double"] = (ledger.double, 0)
    created, launches = oracle.read_log(log_path)
    out["acked_not_durable"] = (sum(1 for u in acked if u not in created),
                                0)
    out["launch_not_durable"] = (sum(1 for u in ledger.launched
                                     if u not in launches), 0)
    out["launch_records_double"] = (sum(1 for n in launches.values()
                                        if n > 1), 0)
    return out


def verdict(compared: dict) -> bool:
    return all(v <= lim for v, lim in compared.values())
