"""Jobs, cluster and durable store of one cell, made from the seed.

Everything a run schedules is drawn here from `--seed` and the cell's
configuration and traffic files: the seeded backlog, the running set
already on the hosts at boot, and the stream of jobs the generator
submits over REST. The same seed gives the same jobs in the same order.

A configuration may declare several pools (`pools`, in the server's
format) and give each host shape a `pool`. Each pool then has its own
backlog, running set and REST stream, the traffic file's counts and
rates are per pool, and every stream is fixed by (seed, stream, pool).
A configuration without pools draws exactly what it drew before pools
existed: the same stream keys, the same jobs.

Sizes are exact in float32 (integer MB, cpus in steps of 0.5), so host
capacity sums are exact on the device and in the float64 reference.
"""
from __future__ import annotations

import uuid as uuidlib
import zlib
from dataclasses import dataclass

import numpy as np


@dataclass
class HostShape:
    cluster: str        # mock cluster name; hosts are <cluster>-host-<i>
    count: int
    mem: float
    cpus: float
    pool: str = None    # the pool its hosts serve; None: no pools


def host_shapes(config: dict) -> list:
    return [HostShape(s["cluster"], int(s["count"]), float(s["mem"]),
                      float(s["cpus"]), s.get("pool"))
            for s in config["cluster"]["host_shapes"]]


def pool_names(config: dict) -> list:
    """The configuration's pools in their order, or [None] for a
    configuration without pools (one pool, the server's default)."""
    return [p["name"] for p in config.get("pools", ())] or [None]


def rng(seed: int, tag: str, pool: str = None):
    """The generator of stream `tag` (of `pool`) for `seed`."""
    key = [seed & 0xFFFFFFFF, seed >> 32, _tag(tag)]
    if pool is not None:
        key.append(zlib.crc32(pool.encode()))
    return np.random.default_rng(key)


def user_names(config: dict) -> list:
    return [f"u{i:03d}" for i in range(int(config["cluster"]["users"]))]


class JobStream:
    """The i-th job of a stream is fixed by (seed, stream name, pool,
    i), whatever the timing of the run that asks for it."""

    BLOCK = 4096

    def __init__(self, seed: int, name: str, traffic: dict, n_users: int,
                 pool: str = None):
        self._rng = rng(seed, name, pool)
        self._traffic = traffic
        self._n_users = n_users
        self._buf: list = []

    def take(self, n: int) -> list:
        while len(self._buf) < n:
            self._buf.extend(draw_jobs(self._rng, self.BLOCK,
                                       self._traffic, self._n_users))
        out, self._buf = self._buf[:n], self._buf[n:]
        return out


def _tag(name: str) -> int:
    return int.from_bytes(name.encode()[:8].ljust(8, b"\0"), "little")


def draw_jobs(rng, n: int, traffic: dict, n_users: int) -> list:
    """n jobs as (uuid, user index, mem MB, cpus, runtime s)."""
    mlo, mhi = traffic["mem_mb"]
    clo, chi = traffic["cpus"]
    rlo, rhi = traffic["runtime_s"]
    users = rng.integers(0, n_users, n)
    mem = rng.integers(int(mlo), int(mhi) + 1, n).astype(np.float64)
    cpus = rng.integers(int(round(clo * 2)), int(round(chi * 2)) + 1,
                        n) / 2.0
    runtime = rng.uniform(rlo, rhi, n)
    ids = rng.bytes(16 * n)
    return [(str(uuidlib.UUID(bytes=ids[16 * i:16 * i + 16], version=4)),
             int(users[i]), float(mem[i]), float(cpus[i]),
             float(runtime[i])) for i in range(n)]


def residual_runtimes(rng, n: int, runtime_s) -> np.ndarray:
    """Remaining runtimes of tasks found running in a steady state:
    their runtimes are length-biased (a long task is more likely to be
    running at a given moment) and each is at a uniform point of its
    run. For runtimes uniform on [a, b] the length-biased density is
    proportional to r, so r = sqrt(a^2 + u (b^2 - a^2))."""
    a, b = float(runtime_s[0]), float(runtime_s[1])
    r = np.sqrt(a * a + rng.uniform(0, 1, n) * (b * b - a * a))
    return rng.uniform(0, 1, n) * r


def place_running(rng, jobs: list, shapes: list) -> list:
    """Put the running set on hosts without exceeding any capacity:
    a random start host per job, then the next host with room."""
    names, cap_m, cap_c = [], [], []
    for s in shapes:
        for i in range(s.count):
            names.append(f"{s.cluster}-host-{i}")
            cap_m.append(s.mem)
            cap_c.append(s.cpus)
    left_m = np.asarray(cap_m)
    left_c = np.asarray(cap_c)
    H = len(names)
    starts = rng.integers(0, H, len(jobs))
    out = []
    for (uuid, _u, mem, cpus, _r), h in zip(jobs, starts.tolist()):
        for k in range(H):
            j = (h + k) % H
            if left_m[j] >= mem and left_c[j] >= cpus:
                left_m[j] -= mem
                left_c[j] -= cpus
                out.append(names[j])
                break
        else:
            raise ValueError("the running set does not fit the cluster")
    return out


@dataclass
class PoolDraw:
    """What one pool starts with: its host shapes, its backlog, its
    running set with the host and task id of each job, and each task's
    remaining runtime."""
    pool: str           # None: a configuration without pools
    shapes: list
    pending: list
    running: list
    hosts: list
    task_ids: list
    remaining: dict


def draw(config: dict, traffic: dict, seed: int) -> list:
    """[PoolDraw] of every pool, in the configuration's order; the
    running set is placed on its own pool's hosts only. Task ids are
    numbered over all pools."""
    n_users = len(user_names(config))
    shapes = host_shapes(config)
    pools = pool_names(config)
    stray = {s.cluster for s in shapes if s.pool not in pools}
    if stray:
        raise ValueError(f"host shapes {sorted(stray)} name no pool of "
                         f"{pools}")
    out, n_tasks = [], 0
    for pool in pools:
        mine = [s for s in shapes if s.pool == pool]
        pending = JobStream(seed, "backlog", traffic, n_users,
                            pool).take(int(traffic["pending"]))
        running = JobStream(seed, "running", traffic, n_users,
                            pool).take(int(traffic["running"]))
        r = rng(seed, "placement", pool)
        hosts = place_running(r, running, mine)
        task_ids = [f"seeded-{n_tasks + i:06d}"
                    for i in range(len(running))]
        n_tasks += len(running)
        remaining = dict(zip(task_ids, residual_runtimes(
            r, len(running), config["task_runtime_s"]).tolist()))
        out.append(PoolDraw(pool, mine, pending, running, hosts, task_ids,
                            remaining))
    return out


def seed_store(log_path: str, users: list, pending: list, running: list,
               hosts: list, cluster_of: dict, task_ids: list,
               pool_of: dict) -> None:
    """Write the backlog and the running set into a fresh event log
    through the store's own transactions, as a leader that takes over a
    loaded cluster finds them. The server's restore path replays it.
    `pool_of` (uuid -> pool) names each job's pool; a job of pool None
    goes to the server's default pool."""
    from cook_tpu.state.model import InstanceStatus, Job
    from cook_tpu.state.store import JobStore

    def make(u, ui, m, c):
        p = pool_of[u]
        kw = {} if p is None else {"pool": p}
        return Job(uuid=u, user=users[ui], command="true", mem=m, cpus=c,
                   **kw)

    store = JobStore.restore(None, log_path=log_path)
    try:
        chunk = 20_000
        every = pending + running
        for lo in range(0, len(every), chunk):
            store.create_jobs([make(u, ui, m, c)
                               for u, ui, m, c, _r in every[lo:lo + chunk]])
        items = [(job[0], h, cluster_of[h], t)
                 for job, h, t in zip(running, hosts, task_ids)]
        for lo in range(0, len(items), chunk):
            store.create_instances_bulk(items[lo:lo + chunk])
            store.update_instances_bulk(
                [(t, InstanceStatus.RUNNING, None)
                 for t in task_ids[lo:lo + chunk]])
    finally:
        log = getattr(store, "_log", None)
        if log is not None:
            log.close()
