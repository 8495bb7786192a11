"""Client-side load: bulk POSTs to /jobs/bulk over keep-alive HTTP.

Two shapes, chosen by the traffic file's `mode`:

- `open`: an open loop. Poisson arrivals of bulk POSTs, each of a
  uniform number of jobs from one uniformly drawn user, sent on a fixed
  schedule whatever the server does. Every POST is timed from when it
  was due, and how late the generator sent it is recorded.
- `saturated`: the backlog is held at a target. Workers top it up by
  what the backend launched, in POSTs of a fixed size, each from one
  uniformly drawn user.

All times are `time.perf_counter()` seconds.
"""
from __future__ import annotations

import http.client
import json
import queue
import threading
import time

import numpy as np


class Post:
    __slots__ = ("due", "sent", "done", "status", "uuids")

    def __init__(self, due, uuids):
        self.due = due
        self.sent = None
        self.done = None
        self.status = 0
        self.uuids = uuids


class Client:
    """One keep-alive connection (one per worker thread)."""

    def __init__(self, port: int):
        self.port = port
        self.conn = None

    def post(self, user: str, jobs: list) -> int:
        body = json.dumps({"jobs": [
            {"uuid": u, "command": "true", "mem": m, "cpus": c}
            for u, _ui, m, c, _r in jobs]}).encode()
        for attempt in (0, 1):
            try:
                if self.conn is None:
                    self.conn = http.client.HTTPConnection(
                        "127.0.0.1", self.port, timeout=120)
                self.conn.request("POST", "/jobs/bulk", body=body, headers={
                    "Content-Type": "application/json",
                    "X-Cook-User": user})
                resp = self.conn.getresponse()
                resp.read()
                return resp.status
            except (OSError, http.client.HTTPException):
                # a stale keep-alive connection: reopen once
                self.close()
                if attempt:
                    return 0
        return 0

    def close(self):
        if self.conn is not None:
            self.conn.close()
            self.conn = None


def open_schedule(rng, traffic: dict, start: float, end: float,
                  n_users: int) -> list:
    """(due offset s, user index, n jobs) for every POST due in
    [start, end), Poisson at the traffic's job rate."""
    lo, hi = traffic["post_jobs"]
    mean_jobs = (lo + hi) / 2.0
    rate_posts = float(traffic["rate_jobs_per_s"]) / mean_jobs
    n = int((end - start) * rate_posts * 1.5) + 64
    gaps = rng.exponential(1.0 / rate_posts, n)
    due = start + np.cumsum(gaps)
    sizes = rng.integers(int(lo), int(hi) + 1, n)
    users = rng.integers(0, n_users, n)
    keep = due < end
    return list(zip(due[keep].tolist(), users[keep].tolist(),
                    sizes[keep].tolist()))


class OpenLoop:
    """Sends a fixed schedule; `t0` anchors offset 0 (the window start)."""

    def __init__(self, port, schedule, stream, users, register, t0,
                 workers: int):
        self.port = port
        self.users = users
        self.register = register
        self.workers = workers
        self.posts: list = []
        self._q: queue.Queue = queue.Queue()
        self._items = [(t0 + off, users[u], stream.take(n))
                       for off, u, n in schedule]
        self._threads: list = []

    def start(self):
        self._threads = [threading.Thread(target=self._dispatch,
                                          daemon=True)]
        self._threads += [threading.Thread(target=self._work, daemon=True)
                          for _ in range(self.workers)]
        for t in self._threads:
            t.start()

    def _dispatch(self):
        for due, user, jobs in self._items:
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            self.register(jobs, user)
            p = Post(due, [j[0] for j in jobs])
            self.posts.append(p)
            self._q.put((p, user, jobs))
        for _ in range(self.workers):
            self._q.put(None)

    def _work(self):
        client = Client(self.port)
        try:
            while True:
                item = self._q.get()
                if item is None:
                    return
                p, user, jobs = item
                p.sent = time.perf_counter()
                p.status = client.post(user, jobs)
                p.done = time.perf_counter()
        finally:
            client.close()

    def join(self, timeout: float):
        deadline = time.perf_counter() + timeout
        for t in self._threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        return not any(t.is_alive() for t in self._threads)


class TopUp:
    """Holds the pending backlog at `target` jobs: a worker sends the
    next POST whenever the backlog (seeded, plus submitted, less failed
    POSTs and launches; POSTs in flight count as submitted) is at least
    one POST short of the target."""

    def __init__(self, port, stream, rng, users, register, base: int,
                 launched, target: int, post_jobs: int, workers: int):
        self.port = port
        self.stream = stream
        self.rng = rng
        self.users = users
        self.register = register
        self.base = base                # pending jobs seeded at boot
        self.launched = launched        # () -> jobs the backend received
        self.submitted = 0
        self.failed = 0
        self.target = target
        self.post_jobs = post_jobs
        self.workers = workers
        self.posts: list = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: list = []

    def start(self):
        self._threads = [threading.Thread(target=self._work, daemon=True)
                         for _ in range(self.workers)]
        for t in self._threads:
            t.start()

    def _next(self):
        with self._lock:
            backlog = self.base + self.submitted - self.failed \
                - self.launched()
            short = self.target - backlog
            if short < self.post_jobs:
                return None
            jobs = self.stream.take(self.post_jobs)
            user = self.users[int(self.rng.integers(len(self.users)))]
            self.register(jobs, user)
            self.submitted += len(jobs)
            p = Post(time.perf_counter(), [j[0] for j in jobs])
            self.posts.append(p)
            return p, user, jobs

    def _work(self):
        client = Client(self.port)
        try:
            while not self._stop.is_set():
                item = self._next()
                if item is None:
                    time.sleep(0.002)
                    continue
                p, user, jobs = item
                p.sent = p.due
                p.status = client.post(user, jobs)
                p.done = time.perf_counter()
                if p.status != 201:
                    with self._lock:
                        self.failed += len(jobs)
        finally:
            client.close()

    def stop(self, timeout: float) -> bool:
        self._stop.set()
        deadline = time.perf_counter() + timeout
        for t in self._threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        return not any(t.is_alive() for t in self._threads)
