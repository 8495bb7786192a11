"""The benchmark's stand-in for the compute cluster.

A subclass of the program's mock cluster, installed in place of it for
the boot, that adds what the benchmark needs from the backend side:

- each task runs for the runtime its job was drawn with (traffic file);
- the seeded running set is on the hosts from the first offer, so host
  usage and completions match what the restored store says is running;
- every launch the backend receives is stamped on the host clock and
  checked against the benchmark's own ledger of host usage (float64):
  a launch onto a host without room, of a job the benchmark never
  submitted, of a seeded running job, or of a job launched before is
  counted, never trusted to the program's own guards.

Completions go through the batched status channel, as agents post them
(`/agents/status/bulk`). The backend's clock stands still until the
harness starts the traffic (`BenchCluster.clock_running`), so the
seeded tasks' remaining runtimes count from then, whatever the boot
took.

The server builds its mock clusters from the mock module's class, with
no hook for per-task runtimes or a launch ledger, so `installed` puts
`BenchCluster` in its place for the boot.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager

from cook_tpu.backends import mock as mock_mod
from cook_tpu.backends.base import LaunchSpec


class Ledger:
    """What the backend saw, shared by every cluster of one run."""

    def __init__(self, jobs: dict, seeded_running: set, caps: dict):
        self.jobs = jobs                  # uuid -> (user, mem, cpus, runtime)
        self.seeded_running = seeded_running
        self.caps = caps                  # hostname -> (mem, cpus)
        self.used: dict = {}              # hostname -> [mem, cpus]
        self.launched: dict = {}          # uuid -> perf_counter at receipt
        self.task_job: dict = {}          # task id -> job uuid
        self.receipts: list = []          # perf_counter per launch, in order
        self.overcommit = 0
        self.unknown = 0
        self.not_pending = 0
        self.double = 0
        self.lock = threading.Lock()

    def receive(self, specs) -> None:
        now = time.perf_counter()
        with self.lock:
            for s in specs:
                u = s.job_uuid
                self.task_job[s.task_id] = u
                if u not in self.jobs:
                    self.unknown += 1
                elif u in self.seeded_running:
                    self.not_pending += 1
                elif u in self.launched:
                    self.double += 1
                else:
                    self.launched[u] = now
                self.receipts.append(now)
                cap = self.caps.get(s.hostname)
                used = self.used.setdefault(s.hostname, [0.0, 0.0])
                if cap is None or used[0] + s.mem > cap[0] + 1e-6 \
                        or used[1] + s.cpus > cap[1] + 1e-6:
                    self.overcommit += 1
                    continue
                used[0] += s.mem
                used[1] += s.cpus

    def release(self, spec) -> None:
        with self.lock:
            used = self.used.get(spec.hostname)
            if used is not None:
                used[0] -= spec.mem
                used[1] -= spec.cpus


class BenchCluster(mock_mod.MockCluster):
    ledger: Ledger = None
    seed_specs: dict = {}       # cluster name -> [LaunchSpec] at boot
    remaining: dict = {}        # task id -> remaining runtime at boot
    clock_running = threading.Event()

    def __init__(self, hosts, name="mock", runtime_fn=None,
                 bulk_status=False):
        super().__init__(hosts, name=name, runtime_fn=self._runtime,
                         bulk_status=True)
        specs = self.seed_specs.get(name, [])
        if specs:
            # no status callback is attached yet: the store already
            # holds these instances as RUNNING
            self._seeding = True
            try:
                self.launch_tasks("default", specs)
            finally:
                self._seeding = False

    def _runtime(self, spec: LaunchSpec):
        rem = self.remaining.get(spec.task_id)
        if rem is None:
            rem = self.ledger.jobs[spec.job_uuid][3]
        return rem, True, None

    def launch_tasks(self, pool, specs):
        if getattr(self, "_seeding", False):
            with self.ledger.lock:
                for s in specs:
                    used = self.ledger.used.setdefault(s.hostname,
                                                       [0.0, 0.0])
                    used[0] += s.mem
                    used[1] += s.cpus
        else:
            self.ledger.receive(specs)
        super().launch_tasks(pool, specs)

    def advance(self, dt):
        if not self.clock_running.is_set():
            return 0
        return super().advance(dt)

    def _release(self, spec):
        self.ledger.release(spec)
        super()._release(spec)


@contextmanager
def installed(ledger: Ledger, seed_specs: dict, remaining: dict):
    """Boot with BenchCluster in place of the mock cluster class."""
    BenchCluster.ledger = ledger
    BenchCluster.seed_specs = seed_specs
    BenchCluster.remaining = remaining
    BenchCluster.clock_running = threading.Event()
    orig = mock_mod.MockCluster
    mock_mod.MockCluster = BenchCluster
    try:
        yield
    finally:
        mock_mod.MockCluster = orig
