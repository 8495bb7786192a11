"""Always-on cycle phase ledger with critical-path attribution.

The tracer (trace.py) answers "where did THIS job's time go"; the
profiler answers "where does a scheduler CYCLE spend its wall-clock in
production, and which phase is on the critical path".  It is designed
to run enabled on every cycle:

* the coordinator opens a :class:`CycleRec` per cycle and routes every
  phase boundary through ``rec.stamp()`` / ``rec.phase()`` — the SAME
  stamps it already needed for ``self.metrics`` — so enabling the
  ledger adds no extra clock reads to the hot path;
* ``commit()`` is the gated half: disabled it returns immediately with
  zero allocation; enabled it appends one small dict to a bounded ring
  and folds the phase timings into streaming per-(kind, phase)
  histograms plus a blame ledger (which phase was the cycle's
  critical path, i.e. its largest wall segment), all under ONE lock;
* listeners (the ``profile_jsonl`` exporter) are invoked OUTSIDE the
  lock — cookcheck R13 enforces both disciplines.

Every record carries wall AND ``thread_time`` CPU per phase, so a
phase that is long but idle (blocked on the device, on a queue, on
fsync) is distinguishable from one burning the cycle thread.  Waits
measured where they happen (the launch transaction's shard-lock and
group-commit waits) ride along as child spans of the phase that was
open (``CycleRec.child``, reached from the store through the record
the thread exposes, :func:`current_record`).

The device trace's clock: while a JAX profiler session is recording,
each segment between two boundaries is also a host-plane event named
after the record's kind with its phase and cycle number as stats
(:func:`annotation`), so a device trace can be read against the
cycle's phases without any wall-clock mapping.  With no session the
cost is one ``is_enabled()`` check per boundary.

Served by ``GET /debug/profile``; the K worst cycles export as
Chrome-trace/Perfetto JSON via :meth:`CycleProfiler.chrome_trace`.
"""
from __future__ import annotations

import collections
import hashlib
import sys
import threading
import time
from typing import Optional

from cook_tpu.obs.export import to_chrome_trace

# Match-side tail phases overlap the consume record's own phases (the
# sync tail IS the consume cycle; the async tail is time blocked on
# the hand-off queue), so critical-path attribution skips them —
# otherwise every consume-bound cycle would be blamed twice.
OVERLAP_PHASES = frozenset({"consume", "queue_wait"})

# log2 bucket bounds in ms: ~15.6 us .. ~16.4 s
_BUCKET_MS = tuple(2.0 ** i for i in range(-6, 15))

# jax.profiler.TraceAnnotation once jax is loaded (obs itself never
# imports jax: the agent and the CLI run without it)
_TA = None


def _trace_annotation():
    global _TA
    if _TA is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _TA = TraceAnnotation
    return _TA


class _NoAnnotation:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass


_NO_ANNOTATION = _NoAnnotation()


def annotation(name: str, **stats):
    """A host-plane trace event ``name`` (``stats`` attached) while a
    JAX profiler session is recording, else a shared no-op context:
    one ``is_enabled()`` check."""
    ta = _TA or _trace_annotation()
    if ta is not None and ta.is_enabled():
        return ta(name, **stats)
    return _NO_ANNOTATION


_local = threading.local()


def current_record() -> Optional["CycleRec"]:
    """The cycle record this thread exposes (``with rec.exposed():``),
    else None — how the store attaches its waits to the caller's
    phase without a parameter on every transaction."""
    return getattr(_local, "rec", None)


class _Exposed:
    __slots__ = ("_rec", "_prev")

    def __init__(self, rec: "CycleRec"):
        self._rec = rec

    def __enter__(self) -> "CycleRec":
        self._prev = getattr(_local, "rec", None)
        _local.rec = self._rec
        return self._rec

    def __exit__(self, exc_type, exc, tb) -> None:
        _local.rec = self._prev


class _Phase:
    """Handle for a ``with rec.phase(name):`` block.

    Measures exactly its own extent (wall + thread-CPU), appends it to
    the record, and advances the record's stamp boundary to the block
    end — so a following ``stamp()`` covers only what came after.
    ``.ms`` / ``.cpu_ms`` are readable after exit (the resync metric
    reads them).
    """

    __slots__ = ("_rec", "_name", "_pc0", "_ct0", "ms", "cpu_ms")

    def __init__(self, rec: "CycleRec", name: str):
        self._rec = rec
        self._name = name
        self.ms = 0.0
        self.cpu_ms = 0.0

    def __enter__(self) -> "_Phase":
        rec = self._rec
        if rec._ann is not None:
            rec._close(None)      # the unrecorded gap before the block
        rec._open()
        self._pc0 = time.perf_counter()
        self._ct0 = time.thread_time()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pc1 = time.perf_counter()
        ct1 = time.thread_time()
        self.ms = (pc1 - self._pc0) * 1e3
        self.cpu_ms = (ct1 - self._ct0) * 1e3
        rec = self._rec
        rec.phases.append((self._name, self._pc0, pc1, self.cpu_ms))
        rec._last, rec._clast = pc1, ct1
        if rec._ann is not None:
            rec._close(self._name)
        rec._open()


class CycleRec:
    """One cycle's phase ledger — the blessed stamp API (cookcheck R13).

    Always a real object (never a no-op): the coordinator's
    ``self.metrics`` phase keys are unconditional, so the stamps must
    be too.  Only :meth:`CycleProfiler.commit` is gated on enablement.
    """

    __slots__ = ("kind", "pool", "cycle", "thread", "t0", "t0_ms", "_c0",
                 "_last", "_clast", "phases", "children", "attrs", "_ta",
                 "_ann")

    def __init__(self, kind: str, pool: str, cycle: Optional[int] = None):
        self.kind = kind
        self.pool = pool
        self.cycle = cycle
        # the thread that runs the cycle (a pool's match loop or
        # consume worker)
        self.thread = threading.current_thread().name
        self.t0 = time.perf_counter()
        self.t0_ms = time.time() * 1e3
        self._c0 = time.thread_time()
        self._last = self.t0
        self._clast = self._c0
        # (name, pc0, pc1, cpu_ms) — perf_counter bounds + thread CPU
        self.phases: list = []
        # (name, pc0, pc1) spans inside a phase; None until the first
        self.children: Optional[list] = None
        self.attrs: Optional[dict] = None
        self._ta = _TA or _trace_annotation()
        self._ann = None
        self._open()

    # -- the device trace's view of the segments ------------------------

    def _open(self) -> None:
        ta = self._ta
        if ta is not None and ta.is_enabled():
            self._ann = ta(self.kind)
            self._ann.__enter__()

    def _close(self, phase: Optional[str]) -> None:
        ann, self._ann = self._ann, None
        if phase is not None:
            ann.set_metadata(phase=phase)
        if self.cycle is not None:
            ann.set_metadata(cycle=self.cycle)
        ann.__exit__(None, None, None)

    @staticmethod
    def now() -> float:
        """Blessed raw ``perf_counter`` read for per-item sub-timings
        that are not cycle phases (e.g. the legacy path's per-job txn
        bounds, converted to wall via :meth:`wall_ms`)."""
        return time.perf_counter()

    def stamp(self, name: str) -> float:
        """Close the segment since the previous boundary as phase
        ``name``; returns the boundary's ``perf_counter`` value so
        callers can wall-anchor derived spans."""
        pc = time.perf_counter()
        ct = time.thread_time()
        self.phases.append((name, self._last, pc, (ct - self._clast) * 1e3))
        self._last, self._clast = pc, ct
        if self._ann is not None:
            self._close(name)
        self._open()
        return pc

    def phase(self, name: str) -> _Phase:
        """Context manager measuring exactly its own block (used for
        optional segments like resync that must not swallow the
        surrounding gap)."""
        return _Phase(self, name)

    def child(self, name: str, pc0: float, pc1: float) -> None:
        """A span inside the open phase (``perf_counter`` bounds), e.g.
        a lock or fsync wait; ``commit()`` names the phase it fell in."""
        if self.children is None:
            self.children = []
        self.children.append((name, pc0, pc1))

    def set_attr(self, key: str, value) -> None:
        """An attr of the committed record (the caller's ``commit``
        keywords win on a clash)."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value

    def exposed(self) -> _Exposed:
        """``with rec.exposed():`` — :func:`current_record` returns
        this record on this thread inside the block."""
        return _Exposed(self)

    # -- derived reads -------------------------------------------------

    def ms(self, name: str) -> float:
        """Total wall ms recorded under phase ``name``."""
        return sum(b - a for n, a, b, _c in self.phases if n == name) * 1e3

    def cpu_ms(self, name: str) -> float:
        return sum(c for n, _a, _b, c in self.phases if n == name)

    def elapsed_ms(self) -> float:
        return (time.perf_counter() - self.t0) * 1e3

    def wall_ms(self, pc: float) -> float:
        """Map a ``perf_counter`` value to epoch wall ms (anchored at
        the record's start)."""
        return self.t0_ms + (pc - self.t0) * 1e3

    def walls(self) -> list:
        """Phases as ``(name, wall_t0_ms, wall_t1_ms)`` triples — the
        shape ``tracer.record_cycle`` embeds as children."""
        return [(n, self.wall_ms(a), self.wall_ms(b))
                for n, a, b, _c in self.phases]


class _PhaseStat:
    """Streaming per-(kind, phase) aggregate: count/sum/max plus log2
    bucket counts for quantile estimates.  Mutated only under the
    profiler lock."""

    __slots__ = ("n", "sum_ms", "sum_cpu", "max_ms", "buckets")

    def __init__(self):
        self.n = 0
        self.sum_ms = 0.0
        self.sum_cpu = 0.0
        self.max_ms = 0.0
        self.buckets = [0] * (len(_BUCKET_MS) + 1)

    def observe(self, ms: float, cpu_ms: float) -> None:
        self.n += 1
        self.sum_ms += ms
        self.sum_cpu += cpu_ms
        if ms > self.max_ms:
            self.max_ms = ms
        lo, hi = 0, len(_BUCKET_MS)
        while lo < hi:
            mid = (lo + hi) // 2
            if ms <= _BUCKET_MS[mid]:
                hi = mid
            else:
                lo = mid + 1
        self.buckets[lo] += 1

    def _quantile(self, q: float) -> float:
        target = q * self.n
        acc = 0
        for i, c in enumerate(self.buckets):
            acc += c
            if acc >= target:
                return _BUCKET_MS[i] if i < len(_BUCKET_MS) \
                    else self.max_ms
        return self.max_ms

    def snapshot(self) -> dict:
        if self.n == 0:
            return {"count": 0}
        return {"count": self.n,
                "mean_ms": round(self.sum_ms / self.n, 4),
                "p50_ms": round(self._quantile(0.50), 4),
                "p95_ms": round(self._quantile(0.95), 4),
                "max_ms": round(self.max_ms, 4),
                "cpu_mean_ms": round(self.sum_cpu / self.n, 4)}


class CycleProfiler:
    """Process-wide cycle ledger: bounded ring + streaming phase stats
    + critical-path blame shares.

    Lock discipline (cookcheck R13): ring/stat/blame mutation happens
    under ``self._lock``; listeners run OUTSIDE it so a slow JSONL
    write never stalls the cycle thread.
    """

    def __init__(self, ring: int = 2048, enabled: bool = True):
        self.enabled = enabled
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=ring)
        self._stats: dict = {}     # (kind, phase) -> _PhaseStat
        self._blame: dict = {}     # (kind, phase) -> [crit_cycles, ms]
        self._cycles: dict = {}    # kind -> committed count
        self._committed = 0
        self._listeners: list = []

    # -- the hot path --------------------------------------------------

    def cycle(self, kind: str, pool: str,
              cycle: Optional[int] = None) -> CycleRec:
        """Open a record for one cycle.  Always real — see CycleRec."""
        return CycleRec(kind, pool, cycle)

    def commit(self, rec: CycleRec, **attrs) -> None:
        """Fold a finished record into the ledger.  Disabled: returns
        before allocating anything (the zero-cost always-on bargain)."""
        if rec._ann is not None:
            rec._close(None)      # the tail after the last boundary
        if not self.enabled:
            return
        end = time.perf_counter()
        wall_ms = (end - rec.t0) * 1e3
        cpu_ms = (time.thread_time() - rec._c0) * 1e3
        phases = []
        crit_name, crit_ms = "", -1.0
        for name, a, b, cpu in rec.phases:
            ms = (b - a) * 1e3
            phases.append({"name": name, "ms": round(ms, 4),
                           "cpu_ms": round(cpu, 4),
                           "off_ms": round((a - rec.t0) * 1e3, 4)})
            if name not in OVERLAP_PHASES and ms > crit_ms:
                crit_name, crit_ms = name, ms
        entry = {"kind": rec.kind, "pool": rec.pool, "thread": rec.thread,
                 "t0_ms": round(rec.t0_ms, 3),
                 "wall_ms": round(wall_ms, 4),
                 "cpu_ms": round(cpu_ms, 4),
                 "phases": phases, "crit": crit_name}
        if rec.children:
            entry["children"] = [
                {"name": name, "phase": next(
                    (n for n, a, b, _c in rec.phases if a <= c0 < b), ""),
                 "ms": round((c1 - c0) * 1e3, 4),
                 "off_ms": round((c0 - rec.t0) * 1e3, 4)}
                for name, c0, c1 in rec.children]
        if rec.attrs:
            attrs = {**rec.attrs, **attrs}
        if attrs:
            entry["attrs"] = attrs
        with self._lock:
            self._committed += 1
            self._cycles[rec.kind] = self._cycles.get(rec.kind, 0) + 1
            self._ring.append(entry)
            for name, a, b, cpu in rec.phases:
                key = (rec.kind, name)
                stat = self._stats.get(key)
                if stat is None:
                    stat = self._stats[key] = _PhaseStat()
                stat.observe((b - a) * 1e3, cpu)
            if crit_name:
                bl = self._blame.get((rec.kind, crit_name))
                if bl is None:
                    bl = self._blame[(rec.kind, crit_name)] = [0, 0.0]
                bl[0] += 1
                bl[1] += crit_ms
        for fn in tuple(self._listeners):
            try:
                fn(entry)
            except Exception:
                pass   # an exporter must never take down the scheduler

    # -- reads ---------------------------------------------------------

    def snapshot(self) -> dict:
        """The ``/debug/profile`` body: per-kind phase stats, blame
        shares (fraction of cycles each phase critically bounded, with
        the overlap tails excluded), the dominant phase per kind, and
        a decisions/s estimate over the ring window."""
        with self._lock:
            entries = list(self._ring)
            stats = {k: s.snapshot() for k, s in self._stats.items()}
            blame = {k: tuple(v) for k, v in self._blame.items()}
            cycles = dict(self._cycles)
            committed = self._committed
        kinds: dict = {}
        for kind, n in sorted(cycles.items()):
            phase_stats = {p: snap for (k, p), snap in stats.items()
                           if k == kind}
            total_crit = sum(c for (k, _p), (c, _ms) in blame.items()
                             if k == kind)
            shares = {}
            for (k, p), (c, ms) in blame.items():
                if k == kind and total_crit:
                    shares[p] = {"cycles": c,
                                 "share": round(c / total_crit, 4),
                                 "ms": round(ms, 2)}
            dominant = max(shares, key=lambda p: shares[p]["cycles"]) \
                if shares else ""
            kinds[kind] = {"cycles": n, "phases": phase_stats,
                           "blame": shares, "dominant": dominant}
        return {"enabled": self.enabled, "committed": committed,
                "ring": len(entries), "kinds": kinds,
                "decisions_per_s": self._rate(entries)}

    @staticmethod
    def _rate(entries: list) -> float:
        """Matched-jobs/s over the ring's consume records."""
        t_lo, t_hi, matched = None, None, 0
        for e in entries:
            if e["kind"] != "consume":
                continue
            t0, t1 = e["t0_ms"], e["t0_ms"] + e["wall_ms"]
            t_lo = t0 if t_lo is None or t0 < t_lo else t_lo
            t_hi = t1 if t_hi is None or t1 > t_hi else t_hi
            matched += int((e.get("attrs") or {}).get("matched", 0))
        if t_lo is None or t_hi is None or t_hi <= t_lo:
            return 0.0
        return round(matched / ((t_hi - t_lo) / 1e3), 2)

    def rate(self) -> float:
        with self._lock:
            entries = list(self._ring)
        return self._rate(entries)

    def consume_spans(self, spans: list) -> list:
        """The children of each resident ``match.cycle`` job span in
        ``spans`` — ``readback``, ``launch_loop``, ``launch_txn``,
        ``backend_launch`` — built at read time from the consume record
        of the span's (pool, cycle) while the ring still holds it. The
        consume writes one span per launched job; its phases are the
        same for every job of the cycle, so they are not copied per
        job. ``launch_txn`` keeps the id the durable ``insts`` record
        carries, ``backend_launch`` the id of the launch traceparent
        (:func:`launch_span_id`)."""
        want: dict = {}
        for s in spans:
            a = s.get("attrs")
            if s.get("name") == "match.cycle" and a \
                    and a.get("path") == "resident":
                want.setdefault((a.get("pool"), a.get("cycle")), []).append(s)
        if not want:
            return []
        with self._lock:
            recs = {(e["pool"], e["attrs"].get("cycle")): e
                    for e in self._ring
                    if e["kind"] == "consume" and "attrs" in e}
        out = []
        for key, job_spans in want.items():
            e = recs.get(key)
            if e is None:
                continue
            t0 = e["t0_ms"]
            ends = {p["name"]: t0 + p["off_ms"] + p["ms"]
                    for p in e["phases"]}
            rb1 = ends.get("readback", t0)
            loop1 = ends.get("frame", rb1)
            txn1 = ends.get("launch_txn", loop1)
            end = t0 + e["wall_ms"]
            txn_sid = e["attrs"].get("txn_span", "")
            for s in job_spans:
                sid = s["span"]
                for name, span_id, a, b in (
                        ("readback", _derived_id(sid, "readback"), t0, rb1),
                        ("launch_loop", _derived_id(sid, "launch_loop"),
                         rb1, loop1),
                        ("launch_txn", txn_sid
                         or _derived_id(sid, "launch_txn"), loop1, txn1),
                        ("backend_launch",
                         launch_span_id(s["attrs"].get("task", "")),
                         txn1, end)):
                    out.append({"name": name, "trace": s["trace"],
                                "span": span_id, "parent": sid,
                                "t0": round(a, 3), "t1": round(b, 3)})
        return out

    def worst(self, k: int = 8) -> list:
        """The K slowest cycles currently in the ring, worst first."""
        with self._lock:
            entries = list(self._ring)
        entries.sort(key=lambda e: e["wall_ms"], reverse=True)
        return entries[:max(0, int(k))]

    def chrome_trace(self, k: int = 8) -> dict:
        """The K worst cycles as Chrome-trace/Perfetto JSON."""
        spans = []
        for e in self.worst(k):
            attrs = dict(e.get("attrs") or {})
            attrs["pool"] = e["pool"]
            attrs["crit"] = e["crit"]
            spans.append({
                "name": f"cycle.{e['kind']}", "t0": e["t0_ms"],
                "t1": e["t0_ms"] + e["wall_ms"], "attrs": attrs,
                "children": [
                    {"name": p["name"],
                     "t0": e["t0_ms"] + p["off_ms"],
                     "t1": e["t0_ms"] + p["off_ms"] + p["ms"],
                     "attrs": {"cpu_ms": p["cpu_ms"]}}
                    for p in e["phases"]]})
        return to_chrome_trace(spans, tid_key="pool")

    # -- listeners / lifecycle ----------------------------------------

    def add_listener(self, fn) -> None:
        self._listeners.append(fn)

    def remove_listener(self, fn) -> None:
        try:
            self._listeners.remove(fn)
        except ValueError:
            pass

    def configure(self, ring: Optional[int] = None,
                  enabled: Optional[bool] = None) -> None:
        with self._lock:
            if ring is not None and ring != self._ring.maxlen:
                self._ring = collections.deque(self._ring, maxlen=ring)
        if enabled is not None:
            self.enabled = enabled

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._stats.clear()
            self._blame.clear()
            self._cycles.clear()
            self._committed = 0


def launch_span_id(task_id: str) -> str:
    """The span id of a resident launch's ``backend_launch`` span: the
    first 16 hex digits of its task id, so the launch traceparent the
    backend receives and the span built at read time agree without
    keeping the id per job."""
    return task_id.replace("-", "")[:16]


def _derived_id(span_id: str, name: str) -> str:
    return hashlib.blake2b(f"{span_id}/{name}".encode(),
                           digest_size=8).hexdigest()


# Process-wide default, mirroring obs.trace.tracer.
profiler = CycleProfiler()
