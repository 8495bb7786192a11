"""One run of one cell: seed, boot, warm up, measure, check.

The cell's configuration and traffic are found by name in
`benchmark/configs/` and `benchmark/traffic/`; its per-layer metrics by
name in `benchmark/metrics/`. Nothing here names a cell.

A run, in one process that owns the chip:

1. draws the backlog, the running set and the REST job stream from the
   seed, and writes the backlog and running set into a fresh durable
   event log (inside the checkout, on local disk);
2. boots the real scheduler in-process through `rest.server.serve`
   with the mock backend replaced by `backend.BenchCluster`; the
   restore path replays the log and the first resident build sees the
   whole backlog; sets the configuration's default share through
   `POST /share` and has the resident pool rebuilt, since rows built
   at restore keep the share they were built with;
3. drives the cell's traffic for its warm-up seconds, then measures for
   `--seconds`; with `--trace 1` a middle part of the window is traced;
4. after the window: follows the window's jobs through a drain while
   arrivals continue, reads peak device memory, stops the server, and
   checks correctness (`oracle`).
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


def _rng(seed: int, tag: str):
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32,
                                  workload._tag(tag)])


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
    return {"port": port, "log_path": log_path,
            "auth": {"scheme": "header", "admins": [ADMIN]},
            "scheduler": dict(config["scheduler"]),
            "clusters": [{"kind": "mock", "name": s.cluster,
                          "hosts": s.count, "host_mem": s.mem,
                          "host_cpus": s.cpus}
                         for s in workload.host_shapes(config)]}


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             fault=None, save_dir=None, files=None) -> dict:
    """One run. `files` = (config, traffic) in place of the cell's own
    files (tests at a small size); `fault` = fn(coordinator, resident
    pool) called once the server is up (tests that break the timed
    path); `require_tpu=False` skips the look for a chip (tests)."""
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
                    t_start, devs, meter, work, fault, save_dir)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _run(bench, cell, config, traffic, seed, seconds, trace, t_start,
         devs, meter, work, fault, save_dir) -> dict:
    from cook_tpu import obs
    from cook_tpu.backends.base import LaunchSpec
    from cook_tpu.obs.metrics import registry
    from cook_tpu.rest import server as srv
    from benchmark import backend

    users = workload.user_names(config)
    shapes = workload.host_shapes(config)
    # -- 1. the seeded store ------------------------------------------
    pending = workload.JobStream(seed, "backlog", traffic,
                                 len(users)).take(int(traffic["pending"]))
    running = workload.JobStream(seed, "running", traffic,
                                 len(users)).take(int(traffic["running"]))
    rng = _rng(seed, "placement")
    hosts = workload.place_running(rng, running, shapes)
    task_ids = [f"seeded-{i:06d}" for i in range(len(running))]
    remaining = dict(zip(task_ids, workload.residual_runtimes(
        rng, len(running), config["task_runtime_s"]).tolist()))
    jobs = {j[0]: (users[j[1]], j[2], j[3], j[4])
            for j in pending + running}
    cluster_of, caps = {}, {}
    for s in shapes:
        for i in range(s.count):
            h = f"{s.cluster}-host-{i}"
            cluster_of[h] = s.cluster
            caps[h] = (s.mem, s.cpus)
    log_path = os.path.join(work, "events.log")
    seed_wall = time.time()
    workload.seed_store(log_path, users, pending, running, hosts,
                        cluster_of, task_ids)
    seed_window = (int(seed_wall), int(time.time()))
    seed_specs: dict = {}
    for j, h, t in zip(running, hosts, task_ids):
        seed_specs.setdefault(cluster_of[h], []).append(
            LaunchSpec(task_id=t, job_uuid=j[0], hostname=h,
                       command="true", mem=j[2], cpus=j[3]))
    ledger = backend.Ledger(jobs, {j[0] for j in running}, caps)
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
        rp = coord._resident[coord.pools.default_pool]
        if fault is not None:
            fault(coord, rp)
        caps_boot = (rp.Pcap, rp.Rcap, rp.Hcap)
        cap = Capture(rp)
        # the operator sets the default share; rows built at restore
        # keep the share they were built with, so the pool rebuilds
        _post_share(port, config["default_share"])
        rp.request_resync()
        t_share = time.perf_counter()
        # traffic starts once a cycle that began after the rebuild was
        # asked for has dispatched (the second dispatch from now): the
        # first cycle has compiled or loaded, so a cold run's compile
        # cannot pile up a backlog that changes the shapes of later
        # runs; the copies taken compile the capture's copy programs
        cap.due = [t_share, t_share]
        if not cap.wait_taken(2, 900):
            raise RuntimeError("no match cycle was dispatched after boot")
        cap.taken.clear()

        def register(batch, user):
            # a POST's jobs belong to the user who sends it
            for u, _ui, m, c, r in batch:
                jobs[u] = (user, m, c, r)

        warm = float(traffic["warm_s"])
        drain = float(traffic["drain_s"])
        stream = workload.JobStream(seed, "rest", traffic, len(users))
        t0 = time.perf_counter() + warm
        if traffic["mode"] == "open":
            sched = generator.open_schedule(
                _rng(seed, "arrivals"), traffic, -warm, seconds + drain,
                len(users))
            gen = generator.OpenLoop(port, sched, stream, users, register,
                                     t0, int(traffic["workers"]))
        else:
            gen = generator.TopUp(
                port, stream, _rng(seed, "posts"), users, register,
                len(pending), lambda: len(ledger.launched),
                int(traffic["pending"]), int(traffic["post_jobs"]),
                int(traffic["workers"]))
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
        pend0 = len(rp.pend_row)
        ing0 = _hist_counts(registry.histogram("ingest_wait_ms"))
        n_rec0 = len(records)
        crng = _rng(seed, "captures")
        cap.due = sorted((t0 + seconds * crng.uniform(0.15, 0.85,
                                                      N_CAPTURES)).tolist())
        tracer = Tracer(t0, seconds, work) if trace else None
        time.sleep(seconds)
        t1 = time.perf_counter()
        pend1 = len(rp.pend_row)
        ing1 = _hist_counts(registry.histogram("ingest_wait_ms"))
        n_rec1 = len(records)

        # -- 4. drain, stop -------------------------------------------
        if traffic["mode"] == "open":
            gen.join(drain + 60)
            time.sleep(max(0.0, t1 + drain - time.perf_counter()))
        else:
            gen.stop(60)
        t_end = time.perf_counter()      # jobs are followed until here
        got_caps = cap.wait_taken(N_CAPTURES, 30)
        coord.drain_resident()
        mem_peak = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devs[:int(cell["chips"])])
        user_ids = dict(coord.interner.items())
        served.stop()
        served_stopped = True
        obs.profiler.remove_listener(records.append)

        # -- 5. results -----------------------------------------------
        window_recs = [r for r in records[n_rec0:n_rec1]
                       if wall0 <= r["t0_ms"] < wall0 + seconds * 1e3]
        n_compiles = meter.overlapping(t0, t1)
        _diagnose(window_recs, meter, c0, n_compiles, rp, coord, t_start,
                  t_seeded, t_booted, t0)
        print(f"diag: caps at boot {caps_boot}; pending rows at window "
              f"start {pend0}, end {pend1}; window from wall clock "
              f"{wall0 / 1e3:.3f} s; captures at "
              f"{[round(c['t'] - t0, 2) for c in cap.taken]} s",
              file=sys.stderr)
        tr = tracer.result(records) if tracer else None
        ctx = {"mode": traffic["mode"], "seconds": seconds,
               "records": window_recs,
               "compiles": n_compiles,
               "ingest_wait": _hist_delta(ing0, ing1),
               "posts": [p for p in gen.posts if t0 <= p.due < t1],
               "t_end": t_end, "trace": tr}
        e2e, attempted, failed = _end_to_end(traffic, gen, ledger, t0, t1,
                                             seconds, t_end)
        e2e["setup_s"] = setup_s
        truth = Truth(jobs, users, user_ids, caps, config["default_share"],
                      seed_window, gen.posts, ledger)
        cycles = [truth.cycle(c) for c in cap.taken] if got_caps else []
        if save_dir:
            os.makedirs(save_dir, exist_ok=True)
            for k, (c, exp) in enumerate(cycles):
                oracle.save_cycle(os.path.join(save_dir, f"cycle{k}.npz"),
                                  c, exp)
        acked = [u for p in gen.posts if p.status == 201 for u in p.uuids]
        compared = correctness(cycles, N_CAPTURES, ledger, log_path, acked)
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
        out["samples"] = e2e.pop("samples")
        out["compared"] = {k: {"value": v, "limit": lim}
                           for k, (v, lim) in compared.items()}
        return out
    finally:
        if not served_stopped:
            served.stop()


def _post_share(port: int, share: dict) -> None:
    """POST /share for the default user, as the deployment's operator."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("POST", "/share", body=json.dumps(
            {"user": "default", "share": share}).encode(), headers={
                "Content-Type": "application/json", "X-Cook-User": ADMIN})
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


def _diagnose(recs, meter, c0, n_compiles, rp, coord, t_start, t_seeded,
              t_booted, t0) -> None:
    """Where the set-up went and what the window's cycles looked like
    (stderr, before the compared numbers)."""
    def mean(xs):
        return sum(xs) / len(xs) if xs else float("nan")
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
          f"{mean(match):.3f} ms; {len(cons)} consumes, mean wall "
          f"{mean([r['wall_ms'] for r in cons]):.3f} ms, mean matched "
          f"{mean(matched):.1f}; overload level {level}; caps Pcap "
          f"{rp.Pcap} Rcap {rp.Rcap} Hcap {rp.Hcap}; pending rows "
          f"{len(rp.pend_row)}", file=sys.stderr)


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
    reduction runs after the window."""

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
        phases = []
        for r in records:
            for ph in r["phases"]:
                lo = r["t0_ms"] + ph["off_ms"]
                phases.append((f"{r['kind']}.{ph['name']}", lo,
                               lo + ph["ms"]))
        res = tracing.reduce(tracing.load(tracing.find_xplane(
            self.log_dir)), self.wall, phases)
        w1 = self.wall + res["window_s"] * 1e3
        res["match_cycles"] = sum(1 for r in records if r["kind"] == "match"
                                  and self.wall <= r["t0_ms"] < w1)
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
    the oracle compares a captured cycle with (`expected`): for every
    pending and running row of the capture, the job the program's row
    map names, as the benchmark drew it, and the wall seconds within
    which the program stamped its submit or start; the benchmark's user
    behind each of the program's user ids; each host's configured
    capacity; the default share."""

    START_BEFORE_S = 10     # a launch's instance is created this far
    #                         at most before the backend receives it

    def __init__(self, jobs, users, user_ids, caps, share, seed_window,
                 posts, ledger):
        self.jobs = jobs
        self.user_index = {u: i for i, u in enumerate(users)}
        self.user_ids = user_ids
        self.caps = caps
        self.share = np.array([share["mem"], share["cpus"]], np.float64)
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

    def _rows(self, n, pairs, window):
        e = {"known": np.zeros(n, bool), "user": np.full(n, -1, np.int64),
             "mem": np.zeros(n), "cpus": np.zeros(n),
             "lo_s": np.zeros(n, np.int64), "hi_s": np.zeros(n, np.int64)}
        for row, u, key in pairs:
            j = self.jobs.get(u)
            if j is None:
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

    def cycle(self, c):
        """(capture, expected) of one taken cycle, read back."""
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
            u, self.seed_window))
        launched = self.ledger.launched
        pend["launched"] = np.zeros(P, bool)
        for row, u, _k in pend_pairs:
            pend["launched"][row] = launched.get(u, c["t"]) < c["t"]
        run = self._rows(R, [(row, self.ledger.task_job.get(t), t)
                             for t, row in c["run_row"].items()],
                         self._started)
        host = {"known": np.zeros(H, bool), "cap_mem": np.zeros(H),
                "cap_cpus": np.zeros(H)}
        for row, name in enumerate(c["host_names"]):
            if name in self.caps:
                host["known"][row] = True
                host["cap_mem"][row], host["cap_cpus"][row] = \
                    self.caps[name]
        uid = np.full(cap["qm"].shape[0], -1, np.int64)
        for name, i in self.user_ids.items():
            if 0 <= i < uid.shape[0]:
                uid[i] = self.user_index.get(name, -1)
        return cap, {"pend": pend, "run": run, "host": host,
                     "user_of_id": uid, "share": self.share}


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
