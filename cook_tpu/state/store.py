"""Durable job store: the framework's single source of truth.

Plays the role Datomic plays in the reference (datomic.clj, schema.clj
transaction functions, metatransaction/): an in-memory entity map fed by
*transaction functions* that enforce the legal state machines, an
append-only event log for durability, snapshot+replay recovery, and a
tx-report stream (listeners) that reacts to completed jobs the way
monitor-tx-report-queue does (scheduler.clj:373-435).

Storage layout: every mutation is appended as one JSON event to the log
(cook_tpu.native.eventlog provides a C++ writer; the pure-Python writer
is the fallback). A restarted leader replays snapshot + tail to rebuild
all in-memory state — the reference's restart path (SURVEY.md §5
checkpoint/resume).

Concurrency: transactions are sharded by pool. Each pool maps to one
of ``store_shards`` shard locks and a transaction holds only the
owning pool's shard lock(s), so the per-pool consume lanes and the
parallel agent fan-out drive truly concurrent launch/status
transactions instead of serializing through one mutex (the reference
serializes everything through the Datomic transactor + kill-lock,
compute_cluster.clj:21-42 — the single-writer bottleneck this store
deliberately diverges from; see PARITY.md). Cross-pool state (the
group map, epoch mints, snapshot/rotation quiesce, state_hash) runs
in a global section that takes EVERY shard lock in index order and
then the global lock. Shard→global is the only legal order, entered
only through the blessed helpers _pool_section / _pools_section /
_global_section (cookcheck rule R9 pins this). Reads are dict reads
of immutable-ish dataclasses and may be slightly stale, like
Datomic's snapshot reads; per-key dict mutations on the shared maps
(jobs, task_to_job, _pending) are GIL-atomic and keyed by uuid/pool,
so shards never write each other's keys.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import logging
import os
import queue
import re
import threading
import time
import zlib

from cook_tpu import chaos
from cook_tpu.chaos import procfault
from cook_tpu.native import consumefold
from cook_tpu.obs.profiler import annotation, current_record
from cook_tpu.utils.lockwitness import witness_condition, witness_lock
from dataclasses import asdict, dataclass
from typing import Any, Callable, Iterable, Optional

from cook_tpu.state.model import (
    Group, Instance, InstanceStatus, Job, JobState, REASON_BY_CODE,
    REASON_UNKNOWN, VALID_INSTANCE_TRANSITIONS, new_uuid, now_ms,
)

log = logging.getLogger(__name__)


class TransactionError(Exception):
    """Illegal transition / constraint violation; transaction rejected."""


# Bound encoder for the hot event kinds: json.dumps(obj, separators=...)
# re-creates an encoder (and re-validates its options) on every call;
# binding .encode once keeps the C fast path and skips that setup on
# paths that serialize thousands of records per cycle.
_ENC = json.JSONEncoder(separators=(",", ":")).encode

# Precomputed middle fragment of the hand-built "status" line, keyed by
# status: '","s":"<value>","r":'. The status vocabulary is a small
# closed enum, so the per-record f-string interpolation of constant key
# text (a third of bulk writeback cost at 10k statuses) collapses to
# dict lookup + concat.
_STATUS_FRAG = {s: f'","s":"{s.value}","r":' for s in InstanceStatus}

# printable ASCII minus '"' and '\': a string matching this needs no
# JSON escaping, so the hand-built event lines can splice it verbatim
_PLAIN_JSON = re.compile(r'^[ !#-\[\]-~]*$').match

# byte twins of the status-line fragments for the zero-copy segment
# path (_append_segments): the record is assembled writer-side from
# these preencoded pieces, so Python never materializes (or encodes)
# the joined line at all — the only copy is the native writer's one
# buffer splice under its own mutex.
_STATUS_FRAG_B = {s: v.encode() for s, v in _STATUS_FRAG.items()}
_B_NULL = b"null"
_B_P_TRUE = b',"p":true,"e":'
_B_P_FALSE = b',"p":false,"e":'


def _encode_insts_line(t_ms: int, span_id: str, rows, epoch: int) -> str:
    """Hand-build the "insts" launch event line from (job_uuid,
    task_id, hostname, backend) rows — the launch-txn half of the
    fixed-shape fast encoders (see update_instances_bulk). Byte-shape
    matches the bound-encoder output; any row with a string that would
    need JSON escaping (hostnames come from agent registration) drops
    the whole line back to _ENC."""
    head = f'{{"t":{t_ms},"k":"insts"'
    if span_id:
        head += f',"sp":"{span_id}"'
    tail = (f',"ep":{epoch}' if epoch else "") + "}"
    if _PLAIN_JSON(span_id):
        parts = []
        for j, i, h, b in rows:
            if not (_PLAIN_JSON(h) and _PLAIN_JSON(b)
                    and _PLAIN_JSON(j) and _PLAIN_JSON(i)):
                break
            parts.append('{"j":"' + j + '","i":"' + i + '","h":"' + h
                         + '","b":"' + b + '"}')
        else:
            return head + ',"items":[' + ",".join(parts) + "]" + tail
    ev = {"t": t_ms, "k": "insts"}
    if span_id:
        ev["sp"] = span_id
    ev["items"] = [{"j": j, "i": i, "h": h, "b": b}
                   for j, i, h, b in rows]
    if epoch:
        ev["ep"] = epoch
    return _ENC(ev)


def _encode_insts_segments(t_ms: int, span_id: str, rows,
                           epoch: int) -> Optional[list]:
    """Byte-segment twin of _encode_insts_line for the zero-copy append
    path: the same "insts" record as a list of preencoded bytes
    segments (final segment newline-terminated) handed straight to the
    writer's scatter-gather append. Concatenated, the segments are
    byte-identical to the string encoder's output — which is what
    keeps replay (and the sharded-vs-unsharded differential oracle)
    byte-exact across encoder choices. Returns None when any string
    would need JSON escaping; the caller falls back to the bound
    encoder exactly like _encode_insts_line does."""
    if not _PLAIN_JSON(span_id):
        return None
    head = f'{{"t":{t_ms},"k":"insts"'
    if span_id:
        head += f',"sp":"{span_id}"'
    segs = [(head + ',"items":[').encode()]
    sep = b""
    for j, i, h, b in rows:
        if not (_PLAIN_JSON(h) and _PLAIN_JSON(b)
                and _PLAIN_JSON(j) and _PLAIN_JSON(i)):
            return None
        segs.append(sep + b'{"j":"' + j.encode() + b'","i":"'
                    + i.encode() + b'","h":"' + h.encode()
                    + b'","b":"' + b.encode() + b'"}')
        sep = b","
    segs.append(("]" + (f',"ep":{epoch}' if epoch else "")
                 + "}\n").encode())
    return segs


_HAVE_SYNC_RANGE = hasattr(os, "sync_file_range")


def _writeback_hint(fd: int) -> None:
    """Start ASYNC writeback of the file's dirty pages without waiting.

    The checkpoint writer calls this at every chunk boundary. A blocking
    per-chunk fsync forces a full ordered-journal commit per chunk on
    the SAME filesystem the event log lives on — every launch-txn
    group-commit fdatasync that lands during the ~76 MB snapshot queues
    behind those commits (the fsync-tail p99 miss). SYNC_FILE_RANGE_WRITE
    only *initiates* writeback and returns immediately, so dirty pages
    drain in the background, nothing parks in the journal between
    chunks, and the final full fsync before the atomic rename (which IS
    still required for durability) becomes a cheap catch-up instead of
    a monolithic flush. Falls back to fsync where the syscall does not
    exist (non-Linux); durability is unchanged either way — only the
    final fsync is load-bearing.
    """
    if _HAVE_SYNC_RANGE:
        try:
            # offset 0 / nbytes 0 = "from start through end of file"
            os.sync_file_range(fd, 0, 0, os.SYNC_FILE_RANGE_WRITE)
            return
        except OSError:
            pass
    os.fsync(fd)


class SnapshotTicket:
    """Completion handle for an off-critical-path checkpoint
    (JobStore.snapshot_async / rotate_log(wait=False)). The snapshot
    thread stores the recorded log position (or the raised exception)
    and sets the event; callers that need the durability point wait on
    it, everyone else just drops the ticket."""

    def __init__(self):
        self._event = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None):
        """Block until the checkpoint is durable; return the recorded
        log position. Re-raises whatever the snapshot raised."""
        if not self._event.wait(timeout):
            raise TimeoutError("snapshot still in flight")
        if self._error is not None:
            raise self._error
        return self._result


class NotLeaderError(TransactionError):
    """Write rejected by the leadership fence; the API maps this to 503
    + leader hint so clients fail over transparently."""


class StaleEpochError(NotLeaderError):
    """Write rejected by the DURABLE epoch fence: the store's epoch
    ledger records a fencing epoch newer than this node's, i.e. a
    successor leader has minted since we last did. Unlike append_gate
    (an in-memory elector liveness verdict, racy by construction), this
    verdict is read from disk at append time — a partitioned old leader
    that still holds open sockets cannot commit after its successor's
    mint, no matter what its elector thread believes."""


class PoolBusyError(RuntimeError):
    """Pool migration refused: the pool still has RUNNING jobs. Raised
    INSIDE migrate_pool_out's global section so the verdict is atomic
    with the export — a route-level pre-scan alone races the match
    cycle (a waiting job can launch between the scan and the fence,
    exporting a live instance whose agent still reports to the source
    group). Carries the offending uuids for the 409 body."""

    def __init__(self, pool: str, running: list):
        super().__init__(
            f"pool {pool!r} has {len(running)} RUNNING job(s)")
        self.pool = pool
        self.running = running


class _GroupCommitBarrier:
    """Cross-lane fsync coalescer: leader/follower group commit above a
    single log writer (the transactor-ack amortization the reference
    gets for free from Datomic's group commit).

    Every transaction's durability barrier joins a *round*; the first
    waiter of a round becomes the leader and performs ONE writer.sync()
    covering every append made before the round started, so N
    concurrent committers (per-pool consume lanes, ingest workers, the
    REST pool) pay ~1 fsync per drain instead of one each. A waiter
    that arrives while a round's sync is already in flight cannot know
    whether that sync started after its append, so it waits for the
    NEXT round — never weaker than a direct sync.

    One barrier per writer object (lazily attached by the store):
    rotation installs a fresh writer and therefore a fresh barrier, so
    a round can never sync a different writer than the one its waiters
    appended to. The native writer's el_sync already coalesces on the
    syncer thread's durable watermark; this barrier extends the same
    amortization to the pure-Python fallback writer (which otherwise
    fsyncs once per transaction) and collapses the per-lane sync calls
    into one.

    Error contract: a failed sync completes its round (waiters must not
    hang) with the exception recorded; the leader and every follower of
    that round re-raise it, taking the same still-live-writer verdict
    path in JobStore._barrier as an un-coalesced failure.
    """

    __slots__ = ("_cv", "_completed", "_in_flight", "_errs",
                 "_on_round", "rounds", "waits")

    def __init__(self, on_round: Optional[Callable[[], None]] = None):
        self._cv = witness_condition("_GroupCommitBarrier._cv")
        self._completed = 0        # rounds fully synced
        self._in_flight = False    # a leader is currently syncing
        self._errs: dict[int, BaseException] = {}
        self._on_round = on_round  # metrics hook, called once per round
        self.rounds = 0            # underlying writer.sync() calls
        self.waits = 0             # transactions that joined a round

    def sync(self, writer) -> bool:
        """True when this caller led the round (ran writer.sync())."""
        cv = self._cv
        with cv:
            self.waits += 1
            # First round whose sync STARTS after this point; its
            # completion makes this caller's prior appends durable.
            target = self._completed + (2 if self._in_flight else 1)
            while self._completed < target:
                if self._in_flight:
                    cv.wait()
                    continue
                # lead: by construction completed == target - 1 here
                rnd = self._completed + 1
                self._in_flight = True
                cv.release()
                err: Optional[BaseException] = None
                try:
                    writer.sync()
                except BaseException as e:   # noqa: BLE001 — re-raised
                    err = e
                finally:
                    cv.acquire()
                    self._completed = rnd
                    self._in_flight = False
                    self.rounds += 1
                    if err is not None:
                        self._errs[rnd] = err
                    # errors older than the previous round have no
                    # live waiters left (every waiter's target is at
                    # most completed+2 at registration time)
                    for k in [k for k in self._errs if k < rnd - 1]:
                        del self._errs[k]
                    cv.notify_all()
                if self._on_round is not None:
                    try:
                        self._on_round()
                    except Exception:
                        pass
                if err is not None:
                    raise err
                return True
            err = self._errs.get(target)
            if err is not None:
                raise err
            return False


@dataclass
class SnapshotView:
    """One pool's consistent state, yielded by JobStore.snapshot_view.

    pending: the LIVE pending-by-pool index dict (uuid -> Job).
      Read-only, and only valid inside the snapshot_view block — it is
      not a copy (copying a 100k-entry dict costs ~300 ms; key-view set
      ops on the live dict are a few ms).
    running: [(Instance, Job), ...] for the pool's RUNNING instances
      (this list IS a copy and survives the block).
    seq: the store's event cursor (count of listener emissions) at
      snapshot time — lets a consumer totally order views against its
      own event stream. The resident swap catch-up itself is
      truth-driven and does not consult it; the atomicity test pins
      the cursor's ordering guarantee.
    """

    pending: dict
    running: list
    seq: int


class JobStore:
    def __init__(self, log_path: Optional[str] = None,
                 log_writer=None, store_shards: int = 4):
        self._lock = witness_lock("JobStore._lock", reentrant=True)
        # pool-sharded transaction locks: pool name -> crc32 % N shard.
        # A transaction holds only its pool's shard lock; cross-pool
        # sections hold all of them + self._lock (shard→global order,
        # entered ONLY through _pool_section/_pools_section/
        # _global_section — cookcheck R9). store_shards=1 degenerates
        # to the pre-sharding single-mutex behavior (the A/B baseline).
        self.store_shards = max(1, int(store_shards))
        self._shard_locks = [witness_lock("JobStore._shard_locks[*]",
                                          reentrant=True, rank=i)
                             for i in range(self.store_shards)]
        # leaf lock for the listener-emission cursor: _emit runs under
        # a SHARD lock now, and two shards' cursors must not race
        self._seq_lock = witness_lock("JobStore._seq_lock")
        # per-shard /debug evidence (mutated under the shard's lock)
        self._shard_txns = [0] * self.store_shards
        self._shard_wait_ms = [0.0] * self.store_shards
        self._shard_hold_ms = [0.0] * self.store_shards
        self._shard_txns_by_pool: dict[str, int] = {}
        # lazily-bound metrics registry handles (one histogram pair per
        # shard, one counter per pool) so the hot path never pays a
        # labeled-family lookup
        self._shard_hist_cache: list = [None] * self.store_shards
        self._shard_pool_counters: dict = {}
        # zero-copy segment encoder toggle (Settings.store_native_encoder):
        # hot transactions build preencoded byte segments appended via
        # the writer's scatter-gather path; off = the string encoders.
        # Both produce byte-identical logs (the differential oracle
        # pins it); the toggle exists for A/B and as a belt-and-braces
        # fallback.
        self.native_encoder: bool = True
        self.jobs: dict[str, Job] = {}
        self.groups: dict[str, Group] = {}
        self.task_to_job: dict[str, str] = {}
        self._listeners: list[Callable[[str, dict], None]] = []
        # runtime-tunable rebalancer params (the reference stores these
        # in Datomic, adjustable live — rebalancer.clj:520-542)
        self.rebalancer_config: dict = {}
        # pending-by-pool index: pool -> {uuid -> Job} for committed
        # WAITING jobs, maintained incrementally by _reindex so
        # pending_jobs() is O(pool pending), not an O(all jobs) scan
        # per cycle (the reference's get-pending-job-ents walks a
        # Datomic index the same way, tools.clj:319)
        self._pending: dict[str, dict[str, Job]] = {}
        # incremental per-user running aggregates, maintained at every
        # job state transition (through _reindex) so /usage is
        # O(active users) per call, not an O(all jobs) scan — the last
        # non-incremental scan in the store (VERDICT r3 weak #6).
        # _usage: pool -> user -> [mem, cpus, gpus, jobs];
        # _usage_jobs: pool -> uuid -> the (user, mem, cpus, gpus)
        # snapshot counted in, so un-counting is exact even if an
        # adjuster mutates the job while it runs. Keyed by pool FIRST
        # so running_jobs(pool) iterates only under the pool's shard
        # lock — a flat map would be mutated by other shards
        # mid-iteration.
        self._usage: dict[str, dict[str, list]] = {}
        self._usage_jobs: dict[str, dict[str, tuple]] = {}
        # listener-emission cursor for snapshot_view (monotonic count of
        # _emit calls; bumped under the store lock)
        self._event_seq: int = 0
        # leader epoch stamped into every log entry (the lease's
        # leaseTransitions count): replay drops entries from an epoch
        # older than the newest seen, closing the TOCTOU window where a
        # stalled deposed leader physically appends after its successor
        # trimmed + replayed the log. 0 = epochless (single-node dev).
        self.epoch: int = 0
        self._replay_max_epoch = 0
        # durable epoch ledger (<log>.epoch, append-only JSONL): every
        # leader acquisition APPENDS a mint record before taking log
        # authorship, and every write transaction stat()s the ledger —
        # a newer mint than our own epoch fences the write at append
        # time (StaleEpochError). (size, mtime_ns) caching keeps the
        # steady-state cost to one stat per gate check.
        self._epoch_ledger_stat: Optional[tuple] = None
        self._epoch_ledger_max: int = 0
        # pool-scoped fences (live pool migration): a mint record
        # carrying {"pools": [...]} fences ONLY those pools — writes
        # to a migrated-away pool reject while every other pool keeps
        # flowing at the old epoch. Kept out of _epoch_ledger_max so a
        # pool-scoped mint never fences the whole source store.
        self._epoch_pool_fences: dict = {}
        # durable membership ledger (<log>.membership, append-only
        # JSONL beside the epoch ledger): live fleet reconfiguration
        # appends a "begin" record carrying the full target view (the
        # crash-resume payload) before applying a membership change,
        # and a "commit"/"abort" record after — each fsync'd file+dir
        # through _append_membership_locked, the one blessed writer
        # (pinned by cookcheck R8). Logless stores keep the records in
        # _membership_mem so the federation layer behaves identically
        # without a log.
        self._membership_mem: list = []
        self._log_path = log_path
        self._log = log_writer
        if log_path and log_writer is None:
            self._log = _make_log_writer(log_path)
        # cross-lane group commit (launch pipeline): when enabled,
        # _barrier coalesces concurrent committers' sync calls into
        # leader/follower rounds on a per-writer _GroupCommitBarrier.
        # Off = one sync per transaction (the pre-coalescing behavior);
        # wired from Settings.launch_group_commit by the server.
        self.group_commit: bool = True
        self._barrier_init_lock = witness_lock("JobStore._barrier_init_lock")
        # delta-snapshot bookkeeping: every transaction that mutates a
        # job marks its uuid dirty (through _reindex /
        # update_progress); retirement/GC records a tombstone. A FULL
        # snapshot swaps the sets out and anoints itself the chain
        # base (_delta_base_id, stamped into the file as snap_id);
        # snapshot_delta serializes only the swapped-out dirty jobs
        # against that base. The chain is process-local by design: the
        # first checkpoint after a restart is always full (base_id is
        # None), so no cross-restart dirty accounting exists to get
        # wrong.
        self._dirty_jobs: set[str] = set()
        self._dirty_tombstones: set[str] = set()
        self._delta_base_id: Optional[str] = None
        self._delta_base_path: Optional[str] = None
        self._delta_seq = 1
        # wall time restore() spent rebuilding this store (0 for a
        # store that was never restored) — /debug evidence and the
        # crash-soak's recovery-time gate
        self.restore_ms = 0.0
        # dedicated checkpoint thread (lazy): snapshot_async and
        # rotate_log(wait=False) hand the chunked serialization + flush
        # to it, with its own fd, so the calling thread — and the
        # group-commit fdatasync path — never waits on snapshot I/O.
        # One thread, one queue: checkpoints are serialized in
        # submission order, which also makes overlapping rotation
        # continuations impossible.
        self._snap_q: Optional[queue.Queue] = None
        self._snap_thread: Optional[threading.Thread] = None

    def _reindex(self, job: Job) -> None:
        """Maintain the pending-by-pool index after any mutation that can
        change (committed, state, pool)."""
        d = self._pending.setdefault(job.pool, {})
        if job.committed and job.state == JobState.WAITING:
            d[job.uuid] = job
        else:
            d.pop(job.uuid, None)
        self._account_usage(job)
        # every mutating transaction funnels through here, so this is
        # the one choke point for delta-snapshot dirty tracking
        # (update_progress, which skips _reindex, marks explicitly)
        self._dirty_jobs.add(job.uuid)

    def _account_usage(self, job: Job) -> None:
        """Fold a (possible) RUNNING transition into the per-user
        aggregates; idempotent per state."""
        if job.state == JobState.RUNNING:
            m = self._usage_jobs.setdefault(job.pool, {})
            if job.uuid not in m:
                m[job.uuid] = (job.user, job.mem, job.cpus, job.gpus)
                u = self._usage.setdefault(job.pool, {}).setdefault(
                    job.user, [0.0, 0.0, 0.0, 0])
                u[0] += job.mem
                u[1] += job.cpus
                u[2] += job.gpus
                u[3] += 1
        else:
            self._uncount_usage(job.pool, job.uuid)

    def _uncount_usage(self, pool: str, uuid: str) -> None:
        rec = self._usage_jobs.get(pool, {}).pop(uuid, None)
        if rec is None:
            return
        user, mem, cpus, gpus = rec
        u = self._usage.get(pool, {}).get(user)
        if u is None:
            return
        u[0] -= mem
        u[1] -= cpus
        u[2] -= gpus
        u[3] -= 1
        if u[3] <= 0:   # prune so /usage stays O(ACTIVE users)
            self._usage[pool].pop(user, None)

    def _deindex(self, job: Job) -> None:
        self._pending.get(job.pool, {}).pop(job.uuid, None)
        self._uncount_usage(job.pool, job.uuid)

    # ------------------------------------------------------------------
    # pool-sharded lock tiers (see the module docstring). These three
    # contextmanagers are the ONLY sites allowed to acquire a shard
    # lock — cookcheck R9 flags any other acquisition, any shard
    # section entered while holding the global lock, and any nested
    # shard sections outside these helpers.
    @contextlib.contextmanager
    def _pool_section(self, pool: str, txn: bool = False):
        """One pool's critical section: holds exactly the owning shard
        lock. self._lock may be taken briefly INSIDE for cross-pool
        shared state (shard→global order) — never the other way
        around. txn=True records lock-wait/hold evidence and counts
        the transaction (skipped during replay: a restore applies
        millions of events through the transaction functions and must
        not pay metrics on each)."""
        idx = zlib.crc32(pool.encode()) % self.store_shards
        lk = self._shard_locks[idx]
        if not txn or getattr(self, "_replaying", False):
            with lk:
                yield
            return
        t0 = time.perf_counter()
        lk.acquire()
        t1 = time.perf_counter()
        try:
            self._shard_txns[idx] += 1
            self._shard_wait_ms[idx] += (t1 - t0) * 1e3
            self._shard_txns_by_pool[pool] = \
                self._shard_txns_by_pool.get(pool, 0) + 1
            yield
        finally:
            t2 = time.perf_counter()
            self._shard_hold_ms[idx] += (t2 - t1) * 1e3
            lk.release()
            self._observe_shard(idx, pool, (t1 - t0) * 1e3,
                                (t2 - t1) * 1e3)

    @contextlib.contextmanager
    def _pools_section(self, pools, txn: bool = False, rec=None):
        """Multi-shard section for cross-pool batches (a mixed-pool
        create_jobs / commit_jobs): acquires the deduped shard locks
        in ascending index order — the fixed order that keeps two
        concurrent batches deadlock-free. An empty pool set acquires
        nothing (an all-invalid batch still runs its writability
        check). ``rec``: the caller's cycle record, which gets the
        lock wait as a ``lock_wait`` child span."""
        idxs = sorted({zlib.crc32(p.encode()) % self.store_shards
                       for p in pools})
        record = txn and not getattr(self, "_replaying", False)
        t0 = time.perf_counter()
        if rec is None:
            for i in idxs:
                self._shard_locks[i].acquire()
        else:
            with annotation("store.lock_wait"):
                for i in idxs:
                    self._shard_locks[i].acquire()
        t1 = time.perf_counter()
        if rec is not None:
            rec.child("lock_wait", t0, t1)
        try:
            if record:
                for i in idxs:
                    self._shard_txns[i] += 1
                    self._shard_wait_ms[i] += (t1 - t0) * 1e3
                for p in set(pools):
                    self._shard_txns_by_pool[p] = \
                        self._shard_txns_by_pool.get(p, 0) + 1
            yield
        finally:
            t2 = time.perf_counter()
            for i in reversed(idxs):
                if record:
                    self._shard_hold_ms[i] += (t2 - t1) * 1e3
                self._shard_locks[i].release()
            if record:
                for i in idxs:
                    self._observe_shard(i, None, (t1 - t0) * 1e3,
                                        (t2 - t1) * 1e3)
                for p in set(pools):
                    self._pool_txn_counter(p).inc()

    @contextlib.contextmanager
    def _global_section(self):
        """Cross-pool exclusive section: every shard lock in index
        order, THEN the global lock — quiesces all transactions. The
        snapshot / rotation / epoch-mint / state_hash tier."""
        for lk in self._shard_locks:
            lk.acquire()
        self._lock.acquire()
        try:
            yield
        finally:
            self._lock.release()
            for lk in reversed(self._shard_locks):
                lk.release()

    def _observe_shard(self, idx: int, pool: Optional[str],
                       wait_ms: float, hold_ms: float) -> None:
        """Registry-side shard evidence, recorded AFTER the lock is
        released so the labeled-family bookkeeping never extends a
        hold. One histogram pair per shard, one txn counter per pool
        (pool is a bounded operator-defined label — R7-clean)."""
        h = self._shard_hist_cache[idx]
        if h is None:
            from cook_tpu.obs.metrics import registry as metrics_registry
            h = (metrics_registry.histogram(
                    "store_shard_lock_wait_ms", shard=str(idx)),
                 metrics_registry.histogram(
                    "store_shard_lock_hold_ms", shard=str(idx)))
            self._shard_hist_cache[idx] = h
        h[0].observe(wait_ms)
        h[1].observe(hold_ms)
        if pool is not None:
            self._pool_txn_counter(pool).inc()

    def _pool_txn_counter(self, pool: str):
        c = self._shard_pool_counters.get(pool)
        if c is None:
            from cook_tpu.obs.metrics import registry as metrics_registry
            c = metrics_registry.counter("store_shard_txns_total",
                                         pool=pool)
            self._shard_pool_counters[pool] = c
        return c

    def shard_stats(self) -> dict:
        """Per-shard transaction/lock evidence (the /debug store.shards
        block; live_smoke scrapes it)."""
        return {
            "count": self.store_shards,
            "native_encoder": bool(self.native_encoder),
            "txns": list(self._shard_txns),
            "lock_wait_ms": [round(x, 3) for x in self._shard_wait_ms],
            "lock_hold_ms": [round(x, 3) for x in self._shard_hold_ms],
            "txns_by_pool": dict(self._shard_txns_by_pool),
        }

    # ------------------------------------------------------------------
    # event log plumbing
    def _append_raw(self, line: str) -> None:
        """Append a pre-serialized event line (same gate semantics as
        _append; the caller must have included the epoch stamp). The
        bulk transactions build their fixed-shape lines by hand —
        json.dumps of a fresh dict per status is a third of the bulk
        writeback cost at 10k statuses."""
        if self._log is None or getattr(self, "_replaying", False):
            return
        # backstop re-check: a thread that passed the entry check and
        # then stalled (GC/process pause) mid-critical-section must not
        # write the shared log after the fence closed. Raising here can
        # leave partial in-memory state on THIS (fenced) node — far
        # better than a split-brain log write a successor already
        # replayed past; see _check_writable for the primary gate.
        gate = getattr(self, "append_gate", None)
        if gate is not None and not gate():
            raise NotLeaderError("write fenced: not the leader")
        self._fence_stale_epoch()
        if chaos.controller.enabled:
            a = chaos.controller.act("store.append")
            if a.kind == "torn":
                # persist a truncated record, then fail the transaction
                # — disk-wise this is a crash mid-append (the writer
                # still terminates the line, so restore sees a
                # complete-but-corrupt final record, the case _replay's
                # torn-tail recovery must skip; an UNterminated tail is
                # already handled by _trim_torn_tail)
                self._log.append(line[:max(1, len(line) // 2)])
                raise OSError("chaos[store.append]: torn write")
            if a.kind == "error":
                raise OSError("chaos[store.append]: write failed")
            if a.kind == "delay":
                time.sleep(a.delay_s)
        self._log.append(line)

    def _append_raw_many(self, lines: list) -> None:
        """Append many pre-serialized lines with ONE gate check and one
        writer call (append_many batches the writer's internal lock and
        buffer splice). Chaos fault injection keeps per-record
        semantics: when the controller is armed, fall back to per-line
        _append_raw so a seeded torn/error/delay schedule lands on the
        same record it would have hit before batching."""
        if not lines:
            return
        if self._log is None or getattr(self, "_replaying", False):
            return
        if chaos.controller.enabled:
            for ln in lines:
                self._append_raw(ln)
            return
        # backstop re-check, same contract as _append_raw
        gate = getattr(self, "append_gate", None)
        if gate is not None and not gate():
            raise NotLeaderError("write fenced: not the leader")
        self._fence_stale_epoch()
        w = self._log
        if hasattr(w, "append_many"):
            w.append_many(lines)
        else:
            for ln in lines:
                w.append(ln)

    def _append_segments(self, segs: list, nlines: int) -> None:
        """Zero-copy append chokepoint: hand preencoded byte segments
        to the writer without ever joining them into Python str lines.
        The segments must concatenate to exactly `nlines` newline-
        terminated records, byte-identical to what the dict→json.dumps
        path would have produced (the differential oracle holds the
        two paths to the same replayed state_hash). Chaos falls back
        to per-line _append_raw so seeded torn/error/delay schedules
        land on the same record they always did."""
        if not segs or not nlines:
            return
        if self._log is None or getattr(self, "_replaying", False):
            return
        if chaos.controller.enabled:
            for ln in b"".join(segs).decode("utf-8").splitlines():
                self._append_raw(ln)
            return
        # backstop re-check, same contract as _append_raw
        gate = getattr(self, "append_gate", None)
        if gate is not None and not gate():
            raise NotLeaderError("write fenced: not the leader")
        self._fence_stale_epoch()
        w = self._log
        if hasattr(w, "append_segments"):
            w.append_segments(segs, nlines)
        elif hasattr(w, "append_many"):
            w.append_many(b"".join(segs).decode("utf-8").splitlines())
        else:
            for ln in b"".join(segs).decode("utf-8").splitlines():
                w.append(ln)

    def _epoch_suffix(self) -> str:
        return f',"ep":{self.epoch}' if self.epoch else ""

    def _append(self, kind: str, data: dict,
                t_ms: Optional[int] = None) -> None:
        # t_ms: transactions that stamp wall-clock times into live
        # state pass the SAME value here, so the durable event and the
        # in-memory state agree to the millisecond and a replayed store
        # hashes identically to the live one (state_hash is the
        # delta-restore oracle; a 1 ms skew between two now_ms() calls
        # in one transaction would fail it spuriously)
        if self._log is None or getattr(self, "_replaying", False):
            return
        ev = {"t": t_ms if t_ms is not None else now_ms(),
              "k": kind, **data}
        if self.epoch:
            ev["ep"] = self.epoch
        self._append_raw(json.dumps(ev, separators=(",", ":")))

    def _check_writable(self, pools=None) -> None:
        """Primary write-fencing gate, evaluated at TRANSACTION ENTRY
        (inside the store lock, before any in-memory mutation): a
        fenced (deposed or stalled) leader must neither append to the
        shared log nor ack. NotLeaderError maps to HTTP 503 + leader
        hint, which clients follow. The durable epoch fence runs here
        too, so a superseded leader rejects BEFORE mutating in-memory
        state (the append-time backstop in _append_raw can only reject
        after). ``pools`` names the pools the transaction touches, so
        a pool that migrated to another leader group (pool-scoped mint)
        rejects here while unrelated pools keep writing."""
        if getattr(self, "_replaying", False):
            return
        gate = getattr(self, "append_gate", None)
        if gate is not None and not gate():
            raise NotLeaderError("write fenced: not the leader")
        self._fence_stale_epoch(pools=pools)

    @property
    def _epoch_ledger_path(self) -> Optional[str]:
        return f"{self._log_path}.epoch" if self._log_path else None

    def _fence_stale_epoch(self, pools=None) -> None:
        """Durable append-time fence (tentpole of the epoch-fenced
        failover design, docs/robustness.md): reject the write when the
        epoch ledger records a mint newer than our own epoch. Cost is
        one stat() per check; the ledger is only re-read when its
        (size, mtime_ns) changed — i.e. once per takeover. Epochless
        stores (epoch 0: in-memory, dev single-node, pre-HA logs) are
        exempt; the fence arms at the first mint_epoch.

        The GLOBAL comparison uses the max over UNSCOPED mint records
        only: a pool-scoped mint (live migration handing one pool to
        another leader group) must fence exactly the named pools, not
        depose the minting store wholesale. Per-pool fences apply when
        the caller names the pools its transaction touches — and they
        arm even at epoch 0: a store that fenced a pool away via its
        own migrate_pool_out must refuse that pool's writes whether or
        not it ever minted a takeover epoch (the epochless exemption
        is about not deposing dev stores, not about un-fencing a
        migration)."""
        if not self.epoch and not self._epoch_pool_fences:
            return
        path = self._epoch_ledger_path
        if not path:
            return
        try:
            st = os.stat(path)
        except OSError:
            return
        key = (st.st_size, st.st_mtime_ns)
        if key != self._epoch_ledger_stat:
            unscoped, fences = _read_epoch_fences(path)
            self._epoch_ledger_max = unscoped
            self._epoch_pool_fences = fences
            self._epoch_ledger_stat = key
        if self.epoch and self._epoch_ledger_max > self.epoch:
            from cook_tpu.obs.metrics import registry as metrics_registry
            metrics_registry.counter(
                "stale_epoch_writes_rejected_total").inc()
            raise StaleEpochError(
                f"write fenced: epoch {self.epoch} superseded by "
                f"{self._epoch_ledger_max} in epoch ledger")
        if pools and self._epoch_pool_fences:
            for p in pools:
                fence = self._epoch_pool_fences.get(p, 0)
                if fence > self.epoch:
                    from cook_tpu.obs.metrics import \
                        registry as metrics_registry
                    metrics_registry.counter(
                        "stale_epoch_writes_rejected_total").inc()
                    raise StaleEpochError(
                        f"write fenced: pool {p!r} migrated away at "
                        f"epoch {fence} (ours {self.epoch})")

    def _emit(self, kind: str, data: dict) -> None:
        if getattr(self, "_replaying", False):
            return
        with self._seq_lock:    # leaf lock: emits race across shards
            self._event_seq += 1
        for fn in list(self._listeners):
            try:
                fn(kind, data)
            except Exception:
                pass

    def _barrier(self) -> int:
        """Durability barrier, called once at the end of every public
        transaction: block until everything appended so far is
        fdatasync'd (the transactor ack the reference relies on before
        HTTP 201-ing a submission). The native writer group-commits;
        the Python fallback fsyncs per transaction.

        Runs OUTSIDE the store lock (r5): every public transaction
        still calls it before RETURNING, so acks (HTTP 201, backend
        launch hand-off) wait for durability exactly as before — but
        concurrent committers now overlap their fsyncs into one group
        commit instead of serializing the whole store on disk latency
        (measured: the launch-txn p99 tail and the rotation-checkpoint
        lock convoy both rode this). A read may observe a transaction
        for the few ms before its fsync completes; the only store
        listener is the in-process resident mirror, which dies with
        the process, so no externally-visible effect can precede
        durability. Rotation's segment swap keeps its own barrier
        UNDER the lock and syncs the old segment before swapping, so
        an in-flight committer whose barrier lands on the new writer
        is still covered.

        Writer-swap safety: every path that closes or replaces the
        writer (rotate_log, reload_from, follow_log) syncs it UNDER
        the store lock first, so a straggler whose captured handle
        turns out closed knows its appends are already durable — a
        sync failure is only re-raised when the handle is still the
        live writer (checked under the lock, so a mid-swap window
        resolves before the verdict).

        Returns the fsync rounds this caller led: 1 when it ran the
        writer's sync itself, 0 when another round covered it."""
        if getattr(self, "_replaying", False):
            return 0
        w = self._log
        if w is None or not hasattr(w, "sync"):
            return 0
        try:
            if chaos.controller.enabled:
                a = chaos.controller.act("store.fsync")
                if a.kind == "delay":
                    time.sleep(a.delay_s)
                elif a.kind:
                    # raised INSIDE the try so the injected fsync
                    # failure takes the same still-live-writer verdict
                    # path as a real one — and BEFORE the group
                    # barrier, so a seeded schedule lands on the same
                    # transaction it would have hit without coalescing
                    raise OSError("chaos[store.fsync]: injected failure")
            if self.group_commit:
                return int(self._writer_barrier(w).sync(w))
            w.sync()
            return 1
        except Exception:
            with self._lock:
                still_live = w is self._log
            if still_live:
                raise
            return 0

    def _writer_barrier(self, w) -> _GroupCommitBarrier:
        """The writer's group-commit barrier, attached lazily. One
        barrier per writer OBJECT: rotation/reload install a fresh
        writer and so a fresh barrier, which keeps a round from ever
        syncing a different writer than the one its waiters appended
        to (stragglers on the old segment coalesce among themselves,
        and the swap already synced the old segment under the lock)."""
        b = getattr(w, "_gc_barrier", None)
        if b is None:
            with self._barrier_init_lock:
                b = getattr(w, "_gc_barrier", None)
                if b is None:
                    b = _GroupCommitBarrier(on_round=self._count_round)
                    w._gc_barrier = b
        return b

    @staticmethod
    def _count_round() -> None:
        from cook_tpu.obs.metrics import registry as metrics_registry
        metrics_registry.counter("launch_group_fsyncs_total").inc()

    def group_commit_stats(self) -> dict:
        """{rounds, waits} of the CURRENT writer's barrier (bench and
        the CI amortization floor read this; cumulative-across-
        rotations counts live in launch_group_fsyncs_total)."""
        b = getattr(self._log, "_gc_barrier", None) if self._log else None
        if b is None:
            return {"rounds": 0, "waits": 0}
        return {"rounds": b.rounds, "waits": b.waits}

    def add_listener(self, fn: Callable[[str, dict], None]) -> None:
        """tx-report-queue equivalent: fn(kind, data) after each commit."""
        self._listeners.append(fn)

    # ------------------------------------------------------------------
    # transaction functions (the schema.clj:949-1235 equivalents)
    def create_jobs(self, jobs: Iterable[Job], groups: Iterable[Group] = (),
                    committed: bool = True) -> list[str]:
        """Batch submission with commit-latch semantics: either the whole
        batch becomes visible (committed) or none of it does
        (rest/api.clj:659 make-commit-latch, :1805 create-jobs!)."""
        jobs = list(jobs)
        groups = list(groups)
        with self._pools_section({j.pool for j in jobs}, txn=True):
            self._check_writable(pools={j.pool for j in jobs})
            # duplicate check FIRST, before any mutation (group member
            # lists included): a rejected batch must leave no trace, so
            # the coalescing ingest layer can retry its requests
            # individually after a combined-transaction 409. Also
            # rejects duplicates WITHIN the batch — previously the last
            # spec silently won.
            seen = set()
            for job in jobs:
                if job.uuid in self.jobs or job.uuid in seen:
                    raise TransactionError(f"duplicate job uuid {job.uuid}")
                seen.add(job.uuid)
            # groups are cross-pool shared state: mutate the group map
            # under the global lock (shard→global order — this nesting
            # is the blessed direction)
            with self._lock:
                for g in groups:
                    if g.uuid in self.groups:
                        existing = self.groups[g.uuid]
                        existing.jobs.extend(j.uuid for j in jobs
                                             if j.group == g.uuid)
                    else:
                        g.jobs.extend(j.uuid for j in jobs
                                      if j.group == g.uuid)
                        self.groups[g.uuid] = g
                        self._append("group", {"group": asdict(g)})
                # jobs referencing an existing group not in this batch
                batch_groups = {g.uuid for g in groups}
                for job in jobs:
                    if job.group and job.group not in batch_groups \
                            and job.group in self.groups:
                        self.groups[job.group].jobs.append(job.uuid)
            items = []
            for job in jobs:
                job.committed = committed
                job.submit_time_ms = job.submit_time_ms or now_ms()
                self.jobs[job.uuid] = job
                items.append(_job_dict(job))
                self._reindex(job)
            if items and self._log is not None \
                    and not getattr(self, "_replaying", False):
                # one batched "jobs" record + ONE encoder call for the
                # whole submission: the per-job json.dumps of a "job"
                # event dominated bulk ingest (~87 ms / 1024 jobs on
                # the e2e bench refill). Replay handles "jobs"
                # alongside the legacy per-job "job" kind.
                ev = {"t": now_ms(), "k": "jobs", "items": items}
                if self.epoch:
                    ev["ep"] = self.epoch
                line = _ENC(ev)
                if self.native_encoder:
                    self._append_segments([(line + "\n").encode()], 1)
                else:
                    self._append_raw(line)
                # mid-ingest kill point: the batch is appended but not
                # yet fsync'd or acked — on restart an acked (201)
                # submission must replay intact, an unacked one may
                # vanish entirely (tests/test_crash_soak.py)
                procfault.kill_point("store.ingest_txn")
            for job in jobs:
                self._emit("job", {"obj": job})
            out = [j.uuid for j in jobs]
        self._barrier()
        return out

    def commit_jobs(self, uuids: Iterable[str]) -> None:
        """Flip the commit latch (metatransaction commit)."""
        uuids = list(uuids)
        pools = {self.jobs[u].pool for u in uuids}
        with self._pools_section(pools, txn=True):
            self._check_writable(pools=pools)
            flipped = []
            for u in uuids:
                job = self.jobs[u]
                if not job.committed:
                    job.committed = True
                    self._append("commit", {"job": u})
                    self._reindex(job)
                    flipped.append(job)
            for job in flipped:
                self._emit("commit", {"obj": job})
        self._barrier()

    def set_rebalancer_config(self, cfg: dict, merge: bool = False) -> None:
        """Durably update the live rebalancer params (the Datomic-stored
        knobs of rebalancer.clj:520-542). merge=True folds cfg into the
        current config under the store lock, so concurrent partial
        updates can't lose each other's keys."""
        with self._global_section():
            self._check_writable()
            merged = {**self.rebalancer_config, **cfg} if merge \
                else dict(cfg)
            self.rebalancer_config = merged
            self._append("rebalancer_config", {"cfg": dict(merged)})
        self._barrier()

    def gc_uncommitted(self, older_than_ms: int) -> list[str]:
        """Drop uncommitted jobs older than the cutoff
        (clear-uncommitted-jobs-on-schedule, tools.clj:757)."""
        with self._global_section():
            self._check_writable()
            cutoff = now_ms() - older_than_ms
            dead = [u for u, j in self.jobs.items()
                    if not j.committed and j.submit_time_ms < cutoff]
            for u in dead:
                self._deindex(self.jobs[u])
                del self.jobs[u]
                self._dirty_jobs.discard(u)
                self._dirty_tombstones.add(u)
                self._append("gc", {"job": u})
            for u in dead:
                self._emit("gc", {"job": u})
        self._barrier()
        return dead

    def gc_completed(self, older_than_ms: int,
                     limit: int = 200_000) -> int:
        """Retention GC for COMPLETED jobs — the role the reference
        delegates to the Datomic layer (deployments excise old
        history out-of-process; in-repo Cook only clears uncommitted
        jobs). This store is both the transactor and the database, so
        it must own the retention role itself: without it, every
        completed job lives forever in memory and in every checkpoint
        — the deployment-shaped longevity bench measured 34 GB RSS and
        4.8 GB snapshots after ~7M tasks (docs/benchmarks.md §Round 5
        longevity).

        Drops completed jobs whose last activity (latest instance end
        time, else submit time) is older than the cutoff: removed from
        memory, the live indexes, and task_to_job; their groups'
        member lists are pruned (an emptied group retires with its
        last job). One compact batch event per locked chunk (2k
        retirees) keeps replay and followers identical. Queries for a
        retired uuid
        return not-found — the same observable behavior Datomic
        excision gives the reference's API."""
        cutoff = now_ms() - older_than_ms

        def expired(j: Job) -> bool:
            if j.state != JobState.COMPLETED:
                return False
            if any(i.active for i in j.instances):
                # zombie window: a killed job whose backend kill is
                # still queued — retiring it would drop the eventual
                # terminal status on the floor (task_to_job gone)
                return False
            end = j.end_time_ms or 0
            for inst in j.instances:
                if inst.end_time_ms:
                    end = max(end, inst.end_time_ms)
            if end == 0:   # legacy records predating end_time_ms
                end = j.submit_time_ms or 0
            return end < cutoff

        # Phase A: collect candidates from a pointer-copy of the job
        # map — the O(all jobs) field scan runs with NO lock held (the
        # first pass after enabling retention on a grown store walks
        # millions of entries; holding the lock across it would be the
        # exact stop-the-world convoy the r5 rotation redesign
        # removed). Racy reads are fine: every candidate is
        # re-validated under the lock before it is retired.
        with self._lock:
            self._check_writable()
            items = list(self.jobs.items())
        candidates = [u for u, j in items if expired(j)]
        del items
        # Phase B: retire in small locked chunks, re-validating each
        # candidate (retry_job can reopen a completed job between the
        # scan and its chunk; a reopened or re-activated job must not
        # be retired).
        retired_total = 0
        CHUNK = 2000
        cap = min(len(candidates), limit)
        for lo in range(0, cap, CHUNK):
            with self._global_section():
                self._check_writable()
                chunk = [u for u in candidates[lo:min(lo + CHUNK, cap)]
                         if (j := self.jobs.get(u)) is not None
                         and expired(j)]
                for u in chunk:
                    self._retire_job(u)
                if chunk:
                    self._append("retire", {"jobs": chunk})
                    self._emit("retire", {"jobs": chunk})
            retired_total += len(chunk)
        self._barrier()
        return retired_total

    def _retire_job(self, uuid: str) -> None:
        """Remove one job and its references from live state (caller
        holds the lock; shared by gc_completed and replay)."""
        job = self.jobs.pop(uuid, None)
        if job is None:
            return
        self._dirty_jobs.discard(uuid)
        self._dirty_tombstones.add(uuid)
        self._deindex(job)
        for inst in job.instances:
            self.task_to_job.pop(inst.task_id, None)
        if job.group:
            g = self.groups.get(job.group)
            if g is not None:
                try:
                    g.jobs.remove(uuid)
                except ValueError:
                    pass
                if not g.jobs:
                    self.groups.pop(job.group, None)

    def allowed_to_start(self, job_uuid: str) -> bool:
        """Guard evaluated inside the launch transaction
        (:job/allowed-to-start? schema.clj:1170): job must exist, be
        committed, waiting, and have no active instance."""
        job = self.jobs.get(job_uuid)
        return bool(job and job.committed and job.state == JobState.WAITING
                    and not job.active_instances)

    def create_instance(self, job_uuid: str, hostname: str, backend: str,
                        task_id: Optional[str] = None,
                        span_id: str = "") -> Instance:
        """Atomically guard allowed-to-start and write the new instance +
        job state (:instance/create schema.clj:949; launch txn
        scheduler.clj:762-777).  ``span_id`` (the coordinator's launch-
        txn span) rides on the durable event so the log carries trace
        context; replay ignores unknown keys."""
        t_ms = now_ms()
        # pool lookup outside the lock: per-key dict reads are atomic,
        # and a vanished job fails the same allowed-to-start guard it
        # always did once inside the owning shard's section
        j0 = self.jobs.get(job_uuid)
        if j0 is None:
            raise TransactionError(f"job {job_uuid} not allowed to start")
        with self._pool_section(j0.pool, txn=True):
            self._check_writable(pools=(j0.pool,))
            if not self.allowed_to_start(job_uuid):
                raise TransactionError(f"job {job_uuid} not allowed to start")
            job = self.jobs[job_uuid]
            inst = Instance(task_id=task_id or new_uuid(), job_uuid=job_uuid,
                            hostname=hostname, backend=backend,
                            start_time_ms=t_ms)
            job.instances.append(inst)
            self.task_to_job[inst.task_id] = job_uuid
            self._update_job_state(job)
            self._reindex(job)
            ev = {"t": t_ms, "k": "inst", "job": job_uuid,
                  "task": inst.task_id, "host": hostname,
                  "backend": backend}
            if span_id:
                ev["sp"] = span_id
            if self.epoch:
                ev["ep"] = self.epoch
            if self.native_encoder:
                self._append_segments([(_ENC(ev) + "\n").encode()], 1)
            else:
                self._append_raw(_ENC(ev))
            # mid-launch-txn kill point (classic path): see
            # create_instances_bulk for the recovery contract
            procfault.kill_point("store.launch_txn")
            # appended under the shard lock but before the cross-shard
            # barrier round — crash-soak schedule G's window
            procfault.kill_point("store.shard_append")
            self._emit("inst", {"obj": job, "inst": inst})
        # same appended-but-unacked window as the bulk path: the lock
        # is released, a concurrent lane's round leader may or may not
        # have synced this line yet (crash-soak schedule F)
        procfault.kill_point("store.launch_group_commit")
        self._barrier()
        return inst

    def create_instances_bulk(self, items, origin=None,
                              span_id: str = "") -> list:
        """Launch transaction for a whole match cycle in ONE store
        transaction: items is [(job_uuid, hostname, backend), ...] or
        [(job_uuid, hostname, backend, task_id), ...]; returns a
        same-length list of Instance | None (None = the
        allowed-to-start guard refused that job — it was killed or
        already launched since matching). One log record, one
        durability barrier, one listener emission for the batch — the
        per-cycle writeback cost the reference pays as a single Datomic
        transact of all task txns (launch-matched-tasks!
        scheduler.clj:762-777).

        Caller-supplied task ids (4-tuples) let the consume lane build
        the LaunchSpec and its CKS1 wire segment BEFORE the
        transaction, so the locked section stops paying spec encoding
        and the agent wire reuses the same bytes (zero double-encode).
        A supplied id that already exists is refused like a failed
        guard — the pre-encoded spec must never be re-keyed."""
        t_ms = now_ms()
        items = list(items)
        # shard routing from a lock-free pool lookup; a job that
        # vanishes (or changes nothing else — pool is immutable) before
        # the section is re-checked by allowed_to_start inside it
        pools = {j.pool for it in items
                 if (j := self.jobs.get(it[0])) is not None}
        # a consume that exposes its cycle record gets this batch's
        # lock and fsync waits as children of its launch_txn phase
        rec = current_record()
        with self._pools_section(pools, txn=True, rec=rec):
            self._check_writable(pools=pools)
            out = []
            created = []
            log_rows = []
            for item in items:
                job_uuid, hostname, backend = item[:3]
                tid = item[3] if len(item) > 3 and item[3] else None
                if not self.allowed_to_start(job_uuid) \
                        or (tid is not None and tid in self.task_to_job):
                    out.append(None)
                    continue
                job = self.jobs[job_uuid]
                if job.pool not in pools:
                    # created between routing and locking — its shard
                    # is not held; refuse like a failed guard
                    out.append(None)
                    continue
                inst = Instance(task_id=tid or new_uuid(),
                                job_uuid=job_uuid,
                                hostname=hostname, backend=backend,
                                start_time_ms=t_ms)
                job.instances.append(inst)
                self.task_to_job[inst.task_id] = job_uuid
                self._update_job_state(job)
                self._reindex(job)
                out.append(inst)
                created.append((job, inst))
                log_rows.append((job_uuid, inst.task_id, hostname,
                                 backend))
            if log_rows:
                # "sp" = the cycle's launch-txn span id: the durable
                # batch record carries trace context (replay-safe —
                # _apply_event ignores unknown keys). The line is
                # hand-built from fixed-shape fragments (same contract
                # as update_instances_bulk): uuids are hex, but host /
                # backend names arrive from agent registration, so any
                # string that could need JSON escaping drops the whole
                # batch back to the bound encoder.
                segs = _encode_insts_segments(t_ms, span_id, log_rows,
                                              self.epoch) \
                    if self.native_encoder else None
                if segs is not None:
                    self._append_segments(segs, 1)
                else:
                    self._append_raw(
                        _encode_insts_line(t_ms, span_id, log_rows,
                                           self.epoch))
                # mid-launch-txn kill point: appended but not yet
                # fsync'd/acked — on restart these instances replay as
                # UNKNOWN (or the torn tail drops them) and restart
                # reconciliation must resolve them without a double
                # launch (tests/test_crash_soak.py)
                procfault.kill_point("store.launch_txn")
                # appended under the shard locks but before the
                # cross-shard barrier round (schedule G window)
                procfault.kill_point("store.shard_append")
            if created:
                self._emit("insts", {"items": created, "origin": origin})
        if log_rows:
            # between the cross-lane append and the shared barrier: a
            # SIGKILL here leaves the batch appended (possibly synced
            # by a concurrent lane's round leader) but never acked —
            # crash-soak schedule F pins zero lost / zero duplicated
            # instances across restart reconciliation for this window
            procfault.kill_point("store.launch_group_commit")
        if rec is None:
            self._barrier()
            return out
        t0 = time.perf_counter()
        with annotation("store.fsync_wait"):
            led = self._barrier()
        rec.child("fsync_wait", t0, time.perf_counter())
        rec.set_attr("fsync_rounds", led)
        return out

    def update_instance(self, task_id: str, status: InstanceStatus,
                        reason_code: Optional[int] = None,
                        preempted: bool = False,
                        exit_code: Optional[int] = None,
                        sandbox: Optional[str] = None,
                        output_url: Optional[str] = None) -> Optional[Job]:
        """The heart of the write path (:instance/update-state
        schema.clj:1103 via write-status-to-datomic scheduler.clj:213):
        apply a status update, ignore illegal transitions, recompute the
        owning job's state in the same transaction."""
        j0_uuid = self.task_to_job.get(task_id)
        j0 = self.jobs.get(j0_uuid) if j0_uuid is not None else None
        if j0 is None:
            return None
        with self._pool_section(j0.pool, txn=True):
            self._check_writable()
            job_uuid = self.task_to_job.get(task_id)
            if job_uuid is None:
                return None
            job = self.jobs[job_uuid]
            inst = next(i for i in job.instances if i.task_id == task_id)
            if status == inst.status:
                return job
            if status not in VALID_INSTANCE_TRANSITIONS[inst.status]:
                # illegal transition: drop, like the txn fn no-op
                return job
            inst.status = status
            if reason_code is not None:
                inst.reason_code = reason_code
            if preempted:
                inst.preempted = True
            if exit_code is not None:
                inst.exit_code = exit_code
            if sandbox is not None:
                inst.sandbox_directory = sandbox
            if output_url is not None:
                inst.output_url = output_url
            t_ms = now_ms()
            if status in (InstanceStatus.SUCCESS, InstanceStatus.FAILED):
                inst.end_time_ms = t_ms
            was = job.state
            self._update_job_state(job, t_ms=t_ms)
            self._reindex(job)
            self._append("status", {"task": task_id, "s": status.value,
                                    "r": reason_code, "p": preempted,
                                    "e": exit_code}, t_ms=t_ms)
            self._emit("status", {"obj": job, "inst": inst, "was": was})
            if job.state == JobState.COMPLETED and was != JobState.COMPLETED:
                self._emit("job-completed", {"job": job_uuid})
        self._barrier()
        return job

    def update_instances_bulk(self, updates) -> int:
        """Batched status writeback: updates is [(task_id, status,
        reason_code), ...] or [(task_id, status, reason_code, extras),
        ...] where extras may carry exit_code/sandbox/output_url (the
        sandbox/exit-code publisher data). One lock acquisition, one
        durability barrier, one listener emission; each update still
        runs the full transition-enforcing state machine. This is the
        store half of the sharded in-order status path at scale — a
        backend that completes thousands of tasks per cycle must not
        pay a fsync per status."""
        applied = []
        t_ms = now_ms()
        updates = list(updates)
        # shard routing from lock-free task→job→pool lookups; a task
        # that resolves only after the section is locked gets skipped
        # by the in-loop pool guard (its shard is not held) and will be
        # retried by the status pipeline's next fold
        pools = {j.pool for it in updates
                 if (u := self.task_to_job.get(it[0])) is not None
                 and (j := self.jobs.get(u)) is not None}
        with self._pools_section(pools, txn=True):
            self._check_writable()
            # per-txn constant fragments of the hand-built status line;
            # the per-status middle comes from _STATUS_FRAG. The native
            # encoder builds the same line as preencoded byte segments
            # (byte-identical — the differential oracle replays both).
            head = f'{{"t":{t_ms},"k":"status","task":"'
            tail = self._epoch_suffix() + "}"
            use_segs = bool(self.native_encoder)
            head_b = head.encode()
            tail_nl_b = (tail + "\n").encode()
            rows = []
            lines = []
            for item in updates:
                task_id, status, reason_code = item[:3]
                extras = item[3] if len(item) > 3 and item[3] else {}
                job_uuid = self.task_to_job.get(task_id)
                if job_uuid is None:
                    continue
                job = self.jobs[job_uuid]
                if job.pool not in pools:
                    continue
                inst = next((i for i in job.instances
                             if i.task_id == task_id), None)
                if inst is None or status == inst.status:
                    continue
                if status not in VALID_INSTANCE_TRANSITIONS[inst.status]:
                    continue
                inst.status = status
                if reason_code is not None:
                    inst.reason_code = reason_code
                    if reason_code in (2000, 2003):
                        inst.preempted = True
                exit_code = extras.get("exit_code")
                if exit_code is not None:
                    inst.exit_code = exit_code
                if extras.get("sandbox") is not None:
                    inst.sandbox_directory = extras["sandbox"]
                if extras.get("output_url") is not None:
                    inst.output_url = extras["output_url"]
                if status in (InstanceStatus.SUCCESS, InstanceStatus.FAILED):
                    inst.end_time_ms = t_ms
                was = job.state
                self._update_job_state(job, t_ms=t_ms)
                self._reindex(job)
                # hand-built fixed-shape line (see _append_raw); task
                # ids are store-generated uuids and status values are
                # enum literals, but reason/exit codes come from opaque
                # backend tuples — coerce to int so a bool/str can't
                # write a malformed line into the durable log. All
                # constant key text is precomputed (head/tail per txn,
                # _STATUS_FRAG per status); lines are appended in ONE
                # writer call below.
                if use_segs:
                    rows.append((task_id.encode(),
                                 _STATUS_FRAG_B[status], reason_code,
                                 inst.preempted, exit_code))
                else:
                    lines.append(
                        head + task_id + _STATUS_FRAG[status]
                        + (str(int(reason_code)) if reason_code is not None
                           else "null")
                        + (',"p":true,"e":' if inst.preempted
                           else ',"p":false,"e":')
                        + (str(int(exit_code)) if exit_code is not None
                           else "null")
                        + tail)
                applied.append((job, inst, was))
            if use_segs:
                # native consume fast path: the whole batch's lines are
                # assembled in ONE buffer behind the consumefold
                # chokepoint (C++ when available, byte-identical Python
                # otherwise) instead of n per-item bytes concats — the
                # writer splices a single segment either way
                if rows:
                    self._append_segments(
                        [consumefold.fold_status_lines(
                            head_b, tail_nl_b, rows)], len(rows))
            else:
                self._append_raw_many(lines)
            if applied:
                self._emit("statuses", {"items": applied})
            for job, inst, was in applied:
                if job.state == JobState.COMPLETED \
                        and was != JobState.COMPLETED:
                    self._emit("job-completed", {"job": job.uuid})
        self._barrier()
        return len(applied)

    def update_progress(self, task_id: str, sequence: int, percent: int,
                        message: str) -> bool:
        """Progress pipeline writeback (progress.clj:33-121): highest
        sequence wins, duplicates dropped."""
        j0_uuid = self.task_to_job.get(task_id)
        j0 = self.jobs.get(j0_uuid) if j0_uuid is not None else None
        if j0 is None:
            return False
        with self._pool_section(j0.pool, txn=True):
            self._check_writable()
            job_uuid = self.task_to_job.get(task_id)
            if job_uuid is None:
                return False
            job = self.jobs[job_uuid]
            inst = next(i for i in job.instances if i.task_id == task_id)
            if sequence <= getattr(inst, "_progress_seq", -1):
                return False
            inst._progress_seq = sequence
            inst.progress = percent
            if message:
                inst.progress_message = message
            self._dirty_jobs.add(job_uuid)
            self._append("progress", {"task": task_id, "q": sequence,
                                      "pc": percent, "m": message})
        self._barrier()
        return True

    def retry_job(self, job_uuid: str, retries: int,
                  failed_only: bool = True) -> None:
        """/retry endpoint semantics (rest/api.clj retries handler;
        schema.clj:1213-1235 retry txn fns): raise max_retries and, if the
        job completed with failures, reopen it as waiting."""
        job0 = self.jobs[job_uuid]   # KeyError contract preserved
        with self._pool_section(job0.pool, txn=True):
            self._check_writable()
            job = self.jobs[job_uuid]
            job.max_retries = retries
            if (job.state == JobState.COMPLETED and not job.success
                    and job.retries_remaining() > 0):
                job.state = JobState.WAITING
                job.success = None
                job.end_time_ms = None
            self._reindex(job)
            self._append("retry", {"job": job_uuid, "n": retries})
            self._emit("retry", {"obj": job})
        self._barrier()

    def kill_job(self, job_uuid: str) -> list[str]:
        """Mark a job killed: complete it and return active task ids the
        backend must kill (kill-job mesos.clj:272)."""
        job0 = self.jobs.get(job_uuid)
        if job0 is None:
            return []
        with self._pool_section(job0.pool, txn=True):
            self._check_writable()
            job = self.jobs.get(job_uuid)
            if job is None or job.state == JobState.COMPLETED:
                return []
            to_kill = [i.task_id for i in job.active_instances]
            t_ms = now_ms()
            job.state = JobState.COMPLETED
            job.success = False
            if job.end_time_ms is None:
                job.end_time_ms = t_ms
            self._reindex(job)
            self._append("kill", {"job": job_uuid}, t_ms=t_ms)
            self._emit("kill", {"obj": job, "to_kill": list(to_kill)})
            self._emit("job-completed", {"job": job_uuid})
        self._barrier()
        return to_kill

    # ------------------------------------------------------------------
    def _update_job_state(self, job: Job,
                          t_ms: Optional[int] = None) -> None:
        """:job/update-state (schema.clj:1065): derive job state from its
        instances + retry budget. t_ms: the caller's transaction
        timestamp, so the completion clock matches the durable event's
        (see _append)."""
        if job.state == JobState.COMPLETED:
            return
        if any(i.active for i in job.instances):
            job.state = JobState.RUNNING
            return
        if any(i.status == InstanceStatus.SUCCESS for i in job.instances):
            job.state = JobState.COMPLETED
            job.success = True
            if job.end_time_ms is None:
                job.end_time_ms = t_ms if t_ms is not None else now_ms()
            return
        if job.retries_remaining() <= 0:
            job.state = JobState.COMPLETED
            job.success = False
            if job.end_time_ms is None:
                job.end_time_ms = t_ms if t_ms is not None else now_ms()
            return
        job.state = JobState.WAITING

    # ------------------------------------------------------------------
    # queries (tools.clj:298-582 equivalents)
    def pending_jobs(self, pool: Optional[str] = None) -> list[Job]:
        # under the owning shard's lock: a concurrent submission
        # mutating the index mid-iteration would raise (background
        # rebuilds read this from a non-cycle thread)
        if pool is None:
            with self._global_section():
                return [j for d in self._pending.values()
                        for j in d.values()]
        with self._pool_section(pool):
            return list(self._pending.get(pool, {}).values())

    def pending_count(self, pool: Optional[str] = None) -> int:
        """O(pools) size probe for the admission/overload layer — the
        full pending_jobs() copy is too expensive to poll every couple
        of seconds on a deep backlog."""
        if pool is None:
            with self._global_section():
                return sum(len(d) for d in self._pending.values())
        with self._pool_section(pool):
            return len(self._pending.get(pool, {}))

    def running_jobs(self, pool: Optional[str] = None) -> list[Job]:
        """O(running), not O(all jobs ever): served from the per-pool
        _usage_jobs index (exactly the RUNNING uuids, maintained at
        every transition) — a long-lived leader accumulates hundreds of
        thousands of completed jobs, and this scan sits on the rank/
        rebalance/reconcile paths."""
        if pool is None:
            with self._global_section():
                return [self.jobs[u]
                        for d in self._usage_jobs.values() for u in d]
        with self._pool_section(pool):
            return [self.jobs[u]
                    for u in self._usage_jobs.get(pool, {})]

    def running_instances(self, pool: Optional[str] = None) -> list[Instance]:
        return [i for j in self.running_jobs(pool) for i in j.active_instances]

    def user_usage(self, pool: Optional[str] = None) -> dict[str, dict]:
        """Per-user running resource totals (/usage, rest/api.clj:2648).
        Served from the incremental aggregates — O(active users) per
        call, so a /usage poll can't become an O(all jobs) scan at
        100k-job scale."""
        section = (self._pool_section(pool) if pool is not None
                   else self._global_section())
        with section:
            pools = ([self._usage.get(pool, {})] if pool is not None
                     else list(self._usage.values()))
            out: dict[str, dict] = {}
            for by_user in pools:
                for user, (mem, cpus, gpus, jobs) in by_user.items():
                    u = out.setdefault(user, {"mem": 0.0, "cpus": 0.0,
                                              "gpus": 0.0, "jobs": 0})
                    u["mem"] += mem
                    u["cpus"] += cpus
                    u["gpus"] += gpus
                    u["jobs"] += jobs
        return out

    def adopt_epoch(self, lease_epoch: int) -> None:
        """Take over log authorship: stamp future entries with at least
        lease_epoch, and strictly above any epoch seen during replay
        (a stalled previous leader's late appends then drop at the next
        replay)."""
        self.epoch = max(lease_epoch, self._replay_max_epoch + 1)

    def mint_epoch(self, owner: str = "", floor: int = 0,
                   pools=None) -> int:
        """Mint a monotone fencing epoch and PERSIST it in the epoch
        ledger before taking log authorship — the durable half of the
        failover fence. Strictly above: any elector lease epoch
        (floor), our own prior epoch, every epoch seen in replay, and
        every mint already in the ledger. The ledger append is fsync'd
        (file + directory) BEFORE this returns, so by the time the new
        leader's first transaction commits, any deposed leader's next
        _fence_stale_epoch() stat observes the mint and rejects —
        combined with the per-record "ep" stamp + replay-side drop,
        this closes the split-brain window end to end. Returns the
        minted epoch.

        ``pools`` mints a POOL-SCOPED fence instead (live migration
        handoff): the record carries the pool names, the minter's own
        epoch does NOT advance, and only writes touching those pools
        reject afterwards — the durable "this pool left the building"
        marker between drain and adoption. A later unscoped mint
        (e.g. the rollback path re-adopting a failed migration) lifts
        pool fences naturally by raising self.epoch above them.

        Runs in the global section: a mint must quiesce every shard —
        a straggler transaction stamping the OLD epoch after a newer
        mint would append a record replay drops, losing an acked
        txn."""
        with self._global_section():
            new = self._mint_epoch_locked(owner, floor, pools)
        procfault.kill_point("store.epoch_mint")
        return new

    def _mint_epoch_locked(self, owner: str = "", floor: int = 0,
                           pools=None) -> int:
        """Mint body, caller holds the global section (mint_epoch, and
        migrate_pool_out's atomic export+fence)."""
        pools = sorted(pools) if pools else None
        path = self._epoch_ledger_path
        ledger_max = _read_epoch_ledger(path) if path else 0
        new = max(floor, self.epoch, self._replay_max_epoch,
                  ledger_max) + 1
        if path:
            body = {"epoch": new, "owner": owner, "t": now_ms()}
            if pools:
                body["pools"] = pools
            rec = json.dumps(body, separators=(",", ":"))
            fd = os.open(path,
                         os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                         0o644)
            try:
                os.write(fd, (rec + "\n").encode("utf-8"))
                os.fsync(fd)
            finally:
                os.close(fd)
            _fsync_dir(os.path.dirname(os.path.abspath(path)))
            st = os.stat(path)
            self._epoch_ledger_stat = (st.st_size, st.st_mtime_ns)
            if pools:
                for p in pools:
                    self._epoch_pool_fences[p] = max(
                        self._epoch_pool_fences.get(p, 0), new)
            else:
                self._epoch_ledger_max = new
        if not pools:
            self.epoch = new
        return new

    # ------------------------------------------------------------------
    # membership ledger (live fleet reconfiguration): the durable
    # intent/commit journal for membership-epoch changes. A reload
    # appends {"phase": "begin", "target": <full groups view>} BEFORE
    # touching any routing table, so a coordinator SIGKILLed mid-reload
    # resumes (or aborts) from the ledger on restart instead of wedging
    # the fleet; "commit"/"abort" close the record. Same fsync
    # discipline as the epoch ledger: file then directory, before the
    # append returns.
    @property
    def _membership_ledger_path(self) -> Optional[str]:
        return f"{self._log_path}.membership" if self._log_path else None

    def membership_records(self) -> list:
        """Every durable membership-ledger record, oldest first (the
        in-memory tail for logless stores)."""
        path = self._membership_ledger_path
        if path:
            return _read_membership_ledger(path)
        return list(self._membership_mem)

    def append_membership(self, phase: str, action: str = "",
                          target=None, owner: str = "",
                          mepoch: int = 0, detail: str = "") -> int:
        """Append one fsync'd membership-epoch record and return its
        membership epoch. ``phase`` is "begin" (allocates the next
        epoch: max over the ledger + 1), or "commit"/"abort" (pass the
        begin's ``mepoch`` through). ``target`` on a begin record is
        the FULL target groups view — not a diff — so resume never
        needs the crashed coordinator's memory. Runs in the global
        section for the same reason mint_epoch does: a membership swap
        must not interleave with an in-flight epoch mint's ledger
        stat-cache update."""
        with self._global_section():
            new = self._append_membership_locked(
                phase, action, target, owner, mepoch, detail)
        procfault.kill_point("store.membership")
        return new

    def _append_membership_locked(self, phase: str, action: str = "",
                                  target=None, owner: str = "",
                                  mepoch: int = 0,
                                  detail: str = "") -> int:
        """Membership append body, caller holds the global section.
        The one blessed membership-ledger writer (cookcheck R8)."""
        path = self._membership_ledger_path
        prior = (_read_membership_ledger(path) if path
                 else list(self._membership_mem))
        top = max((int(r.get("mepoch", 0)) for r in prior), default=0)
        new = int(mepoch) if mepoch else top + 1
        body: dict = {"mepoch": new, "phase": phase, "t": now_ms()}
        if action:
            body["action"] = action
        if owner:
            body["owner"] = owner
        if detail:
            body["detail"] = detail
        if target is not None:
            body["target"] = target
        if not path:
            self._membership_mem.append(body)
            return new
        rec = json.dumps(body, separators=(",", ":"))
        fd = os.open(path,
                     os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                     0o644)
        try:
            os.write(fd, (rec + "\n").encode("utf-8"))
            os.fsync(fd)
        finally:
            os.close(fd)
        _fsync_dir(os.path.dirname(os.path.abspath(path)))
        return new

    # ------------------------------------------------------------------
    # live pool migration (fleet federation): export a pool's jobs out
    # of this store / adopt them into another. Paired with pool-scoped
    # mint_epoch(pools=[...]) fences: the source appends "fedmove"
    # (durable before the fence mint), the destination appends
    # "fedadopt", and replay applies both — so either store restores to
    # exactly its post-migration state and state_hash stays a valid
    # restore oracle across the handoff.
    def migrate_pool_out(self, pool: str, fence_owner: str = "",
                         force: bool = False,
                         span_id: str = "") -> dict:
        """Export-and-remove one pool for live migration to another
        leader group. Returns the portable payload: the pool's jobs as
        event-log dicts plus the group specs they reference (a group
        spanning pools splits — each store keeps its own members, the
        same shape _retire_job leaves behind). Runs in the global
        section: the full-jobs scan needs every shard quiesced, and a
        migration is a rare admin op — latency is not the constraint
        here, atomicity is.

        ``fence_owner`` (non-empty) mints the pool-scoped epoch fence
        INSIDE the same section, so export and fence are atomic: a
        submission thread queued on the locks lands after both and
        rejects at its _check_writable — no job can slip into the pool
        between "exported" and "fenced" and be acked by a store whose
        cycles will never serve it again.

        Unless ``force``, RUNNING jobs abort the export with
        PoolBusyError — checked HERE (not just at the route) because
        only inside this section is the verdict atomic with the fence;
        launches take the pool shard lock, which the global section
        excludes.

        ``span_id`` (the migration span, one per handoff) rides on the
        durable "fedmove" record so the export is joinable to the
        cross-group trace tree — replay ignores it."""
        t_ms = now_ms()
        with self._global_section():
            self._check_writable(pools=(pool,))
            if not force:
                running = sorted(
                    u for u, j in self.jobs.items()
                    if j.pool == pool and j.state == JobState.RUNNING)
                if running:
                    raise PoolBusyError(pool, running)
            uuids = [u for u, j in self.jobs.items() if j.pool == pool]
            items = []
            group_ids = []
            for u in uuids:
                job = self.jobs[u]
                items.append(_job_dict(job))
                if job.group and job.group not in group_ids:
                    group_ids.append(job.group)
            groups = [asdict(self.groups[g]) for g in group_ids
                      if g in self.groups]
            if uuids:
                # the event carries the FULL export (not just uuids):
                # a crash after the fence but before the destination
                # adopted leaves the payload recoverable from this
                # log record instead of only in a dead process's memory
                ev = {"pool": pool, "jobs": list(uuids),
                      "items": items, "groups": groups}
                if span_id:
                    ev["span"] = span_id
                self._append("fedmove", ev, t_ms=t_ms)
                # exported-but-not-fsynced window: a crash here replays
                # the move (or drops the torn tail and keeps the pool)
                # — either way one store owns every job
                procfault.kill_point("store.fedmove")
                for u in uuids:
                    self._retire_job(u)
                self._emit("retire", {"jobs": list(uuids)})
            fence = self._mint_epoch_locked(
                fence_owner, pools=(pool,)) if fence_owner else 0
        self._barrier()
        return {"pool": pool, "jobs": items, "groups": groups,
                "count": len(items), "fence_epoch": fence}

    def _adopt_pool_state(self, items, groups) -> list:
        """Shared mutation body for import_pool and "fedadopt" replay —
        one code path, so the live store and a replayed one land on the
        same state_hash. Caller holds the lock."""
        for gd in groups:
            gd = dict(gd)
            gd["jobs"] = []
            g = Group(**gd)
            if g.uuid not in self.groups:
                # member list rebuilt below: _replay_job re-links each
                # adopted job into its group in item order
                self.groups[g.uuid] = g
        out = []
        for d in items:
            job = _job_from_dict(dict(d))   # copy: it pops "instances"
            if job.uuid in self.jobs:
                continue
            self._replay_job(job)
            out.append(job.uuid)
        return out

    def import_pool(self, pool: str, items, groups=(),
                    span_id: str = "") -> list:
        """Adopt a migrated pool's jobs (the payload migrate_pool_out
        returned on the source). Idempotent per uuid — a retried adopt
        after a lost HTTP response re-delivers the same payload and
        inserts nothing twice.  ``span_id`` (the adopt span) rides on
        the durable "fedadopt" record, mirroring the source side's
        "fedmove" span stamp."""
        t_ms = now_ms()
        with self._global_section():
            self._check_writable(pools=(pool,))
            kept = [dict(d) for d in items
                    if d.get("uuid") not in self.jobs]
            adopted_ids = {d.get("uuid") for d in kept}
            gspecs = []
            for gd in groups:
                gd = dict(gd)
                gd["jobs"] = [u for u in (gd.get("jobs") or ())
                              if u in adopted_ids]
                if gd["jobs"] and gd.get("uuid") not in self.groups:
                    gspecs.append(gd)
            adopted = self._adopt_pool_state(kept, gspecs)
            if adopted:
                ev = {"pool": pool, "items": kept, "groups": gspecs}
                if span_id:
                    ev["span"] = span_id
                self._append("fedadopt", ev, t_ms=t_ms)
                procfault.kill_point("store.fedadopt")
                for u in adopted:
                    self._emit("job", {"obj": self.jobs[u]})
        self._barrier()
        return adopted

    def log_lines(self) -> int:
        """Lines appended to the current log segment (0 when no log) —
        the rotation trigger for the snapshot loop."""
        return self._log.lines() if self._log else 0

    @contextlib.contextmanager
    def snapshot_view(self, pool: str):
        """Consistent per-pool view for resident-state reconciliation
        and background rebuilds, held open under the store lock.

        ATOMICITY INVARIANT (owned here; relied on by
        scheduler/resident.py reconcile_membership and the background
        rebuild): every transaction mutates a pool's state AND notifies
        listeners (_emit) inside the same critical section under that
        pool's shard lock. A snapshot taken under the shard lock
        therefore sees no state whose event has not already been
        delivered to every registered listener — a listener that queues
        events can diff its own queue + mirrors against this view and
        never mistake a fresh launch for a missed one (which would
        double-deplete a host).
        Tested in tests/test_state.py::test_snapshot_view_atomicity.

        The yielded SnapshotView.pending is the live index (see its
        docstring); do all key-view set work inside the block.
        """
        with self._pool_section(pool):
            yield SnapshotView(
                pending=self._pending.get(pool, {}),
                running=[(i, self.jobs[i.job_uuid])
                         for i in self.running_instances(pool)],
                seq=self._event_seq)

    def get_job(self, uuid: str) -> Optional[Job]:
        return self.jobs.get(uuid)

    def get_instance(self, task_id: str) -> Optional[Instance]:
        ju = self.task_to_job.get(task_id)
        if ju is None:
            return None
        return next((i for i in self.jobs[ju].instances
                     if i.task_id == task_id), None)

    # ------------------------------------------------------------------
    # snapshot / replay (checkpoint-resume; the restarted-leader path)
    def snapshot(self, path: str) -> int:
        """Atomic snapshot recording the current log position, so restore
        replays only the tail written after this point. Returns the
        recorded log position (for callers/tests that want the exact
        coverage point).

        Locking: the log position is recorded FIRST, then jobs are
        serialized in small locked chunks and the JSON dump runs with
        no lock held — a monolithic under-lock dump would stall every
        write transaction for seconds at 100k-job scale. A job mutated
        after the position was recorded may serialize with LATER state;
        replaying the tail re-applies those events, and every event
        application is idempotent/transition-guarded, so the restore
        converges to the same state.

        Framing: the JSON document is followed by a `#crc <hex> <len>`
        trailer line (crc32 + byte length of the document). restore()
        verifies it, so a torn or bit-rotted snapshot is DETECTED and
        recovery falls back (previous snapshot, then longer log
        replay) instead of loading garbage. The previous good snapshot
        survives as `<path>.prev` (hardlink taken before the rename).

        A full snapshot also anoints itself the base of a fresh delta
        chain (snap_id in the header; see snapshot_delta) and sweeps
        the delta files of the chain it obsoletes."""
        with self._global_section():
            lines0 = self._log.lines() if self._log else 0
            genesis = getattr(self, "_log_genesis", None)
            snap_id = new_uuid()
            items = list(self.jobs.items())
            groups = {u: asdict(g) for u, g in self.groups.items()}
            rcfg = dict(self.rebalancer_config)
            # swap the dirty sets out in the SAME critical section as
            # the log-position capture: mutations landing after lines0
            # re-mark their jobs and belong to the next delta; on a
            # failed write the swapped-out sets merge back so no
            # mutation is ever lost to the chain
            dirty0 = self._dirty_jobs
            self._dirty_jobs = set()
            tombs0 = self._dirty_tombstones
            self._dirty_tombstones = set()
        # chunk sizing is a lock-convoy trade-off measured on the e2e
        # bench: every chunk boundary re-acquires self._lock behind
        # live transactions (which hold it across their fsync), so 55
        # small chunks at 110k jobs convoyed a background checkpoint to
        # ~45 s under full-rate cycling. 8k-job chunks cut the acquires
        # 4x while each hold stays ~30 ms — invisible next to a launch
        # txn. The per-chunk writeback HINT below starts the 76 MB
        # dirty-page flush early and asynchronously, so the event log's
        # group-commit fdatasync neither queues behind one giant
        # ordered-journal commit at the end nor behind a blocking
        # per-chunk fsync in the middle (see _writeback_hint).
        CHUNK = 8000
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                crc = 0
                nbytes = 0

                def w(s: str) -> None:
                    # accumulate the frame CRC as we stream, so the
                    # trailer costs no extra pass over the document
                    nonlocal crc, nbytes
                    f.write(s)
                    b = s.encode()
                    crc = zlib.crc32(b, crc)
                    nbytes += len(b)

                # streamed per-chunk C-encoder writes, NOT one
                # json.dump or one giant json.dumps: dump() goes
                # through the pure-Python iterencode (measured 4.0 s /
                # 87M calls at 110k jobs), and a single dumps() holds
                # the GIL for its whole ~0.7 s run — observed as a
                # phase spike INSIDE live match cycles during rotation
                # checkpoints. Chunked dumps keeps the C encoder's
                # speed with ~ms GIL holds, so a checkpoint never
                # starves (or gets starved by) the cycle/consumer
                # threads. Key order matters: log_lines/log_genesis
                # lead so _read_snapshot_genesis can header-sniff.
                w('{"log_lines": %d, "log_genesis": %s, '
                  '"snap_id": %s, "jobs": {'
                  % (lines0, json.dumps(genesis), json.dumps(snap_id)))
                first = True
                for lo in range(0, len(items), CHUNK):
                    # global section per chunk: a job owned by ANY
                    # shard may appear in this chunk, and serializing
                    # it while its shard mutates it mid-_job_dict
                    # would tear the record (replay's transition
                    # guards would then diverge state_hash)
                    with self._global_section():
                        part = {u: _job_dict(j)
                                for u, j in items[lo:lo + CHUNK]}
                    blob = json.dumps(part)
                    if blob != "{}":
                        if not first:
                            w(",")
                        w(blob[1:-1])
                        first = False
                        f.flush()
                        _writeback_hint(f.fileno())  # spread the flush
                                                     # without blocking
                w('}, "groups": %s, "rebalancer_config": %s}'
                  % (json.dumps(groups), json.dumps(rcfg)))
                f.write("\n#crc %08x %d\n" % (crc, nbytes))
                f.flush()
                # durable before visible: rotate_log DESTROYS the old
                # log segment on the strength of this snapshot, so it
                # must hit disk (file + directory entry) before
                # rotation proceeds — otherwise a crash can leave a
                # fsync'd new segment next to a page-cache-only
                # snapshot and lose every acked txn between the
                # previous snapshot and lines0
                os.fsync(f.fileno())
            # keep the outgoing snapshot reachable as <path>.prev: the
            # torn-snapshot fallback (restore) and nothing else reads
            # it; hardlink so the retention costs no copy
            if os.path.exists(path):
                prev_tmp = path + ".prev.tmp"
                try:
                    try:
                        os.unlink(prev_tmp)
                    except OSError:
                        pass
                    os.link(path, prev_tmp)
                    os.replace(prev_tmp, path + ".prev")
                except OSError:
                    pass
            procfault.kill_point("store.snapshot")
            os.replace(tmp, path)
            _fsync_dir(os.path.dirname(os.path.abspath(path)))
        except BaseException:
            # the chain must not lose the swapped-out dirty marks: a
            # later delta against the OLD base still needs them
            with self._lock:
                self._dirty_jobs |= dirty0
                self._dirty_tombstones |= tombs0
            raise
        with self._lock:
            self._delta_base_id = snap_id
            self._delta_base_path = path
            self._delta_seq = 1
        self._sweep_deltas(path)
        return lines0

    def snapshot_delta(self, path: str) -> int:
        """Incremental checkpoint: serialize only the jobs mutated
        since the last checkpoint (full or delta) into
        `<path>.delta-<seq>`, CRC-framed and atomically renamed, plus
        the tombstones of jobs retired since. Groups and the
        rebalancer config are small and ride along whole.

        Falls back to a FULL snapshot when this process has no chain
        base yet (first checkpoint after a restart/rotation) — the
        chain is process-local, so there is no cross-restart dirty
        bookkeeping to corrupt. restore() applies base → deltas in seq
        order → log tail; a delta whose base_id does not match the
        loaded snapshot (stale chain) or whose CRC fails simply ends
        the chain early, and the log replays from the last good
        position — always correct, just slower.

        Returns the recorded log position, like snapshot()."""
        with self._lock:
            base_id = self._delta_base_id
            if base_id is None or self._delta_base_path != path:
                base_id = None
        if base_id is None:
            return self.snapshot(path)
        with self._global_section():
            lines0 = self._log.lines() if self._log else 0
            genesis = getattr(self, "_log_genesis", None)
            seq = self._delta_seq
            dirty0 = self._dirty_jobs
            self._dirty_jobs = set()
            tombs0 = self._dirty_tombstones
            self._dirty_tombstones = set()
            jobs = {u: _job_dict(self.jobs[u])
                    for u in dirty0 if u in self.jobs}
            groups = {u: asdict(g) for u, g in self.groups.items()}
            rcfg = dict(self.rebalancer_config)
        body = json.dumps(
            {"base_id": base_id, "seq": seq, "log_lines": lines0,
             "log_genesis": genesis, "jobs": jobs,
             "tombstones": sorted(tombs0), "groups": groups,
             "rebalancer_config": rcfg},
            separators=(",", ":"))
        delta_path = "%s.delta-%d" % (path, seq)
        tmp = delta_path + ".tmp"
        try:
            with open(tmp, "w") as f:
                f.write(body)
                b = body.encode()
                f.write("\n#crc %08x %d\n" % (zlib.crc32(b), len(b)))
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, delta_path)
            _fsync_dir(os.path.dirname(os.path.abspath(delta_path)))
        except BaseException:
            with self._lock:
                self._dirty_jobs |= dirty0
                self._dirty_tombstones |= tombs0
            raise
        with self._lock:
            self._delta_seq = seq + 1
        return lines0

    def delta_chain_length(self) -> int:
        """Deltas written against the current base (0 right after a
        full snapshot) — the server's chain-cap trigger."""
        with self._lock:
            return self._delta_seq - 1 if self._delta_base_id else 0

    def _sweep_deltas(self, path: str) -> None:
        """Drop the delta files a fresh full snapshot just obsoleted.
        Stale survivors (crash between rename and sweep) are harmless:
        their base_id no longer matches and restore ignores them."""
        import glob
        for p in glob.glob(glob.escape(path) + ".delta-*"):
            if p.endswith(".tmp"):
                continue
            try:
                os.unlink(p)
            except OSError:
                pass

    def state_hash(self) -> str:
        """Deterministic digest of the durable state (jobs, groups,
        rebalancer config) — the restore-equivalence oracle: a store
        rebuilt from snapshot+deltas+tail must hash identically to one
        rebuilt from the log alone."""
        with self._global_section():
            doc = {
                "jobs": {u: _job_dict(self.jobs[u])
                         for u in sorted(self.jobs)},
                "groups": {u: asdict(self.groups[u])
                           for u in sorted(self.groups)},
                "rebalancer_config": self.rebalancer_config,
            }
        return hashlib.sha256(
            json.dumps(doc, sort_keys=True, separators=(",", ":"),
                       default=str).encode()).hexdigest()

    # -- off-critical-path checkpointing ------------------------------
    def _ensure_snap_thread(self) -> None:
        if self._snap_thread is not None and self._snap_thread.is_alive():
            return
        self._snap_q = queue.Queue()
        self._snap_thread = threading.Thread(
            target=self._snapshot_worker, daemon=True,
            name="store-snapshot")
        self._snap_thread.start()

    def _snapshot_worker(self) -> None:
        while True:
            item = self._snap_q.get()
            try:
                if item is None:
                    return
                fn, ticket = item
                try:
                    ticket._result = fn()
                except BaseException as e:     # delivered via wait()
                    log.exception("background checkpoint failed")
                    ticket._error = e
                finally:
                    ticket._event.set()
            finally:
                self._snap_q.task_done()

    def snapshot_async(self, path: str) -> SnapshotTicket:
        """Checkpoint on the dedicated snapshot thread and return a
        SnapshotTicket immediately.

        The serialization + flush runs with its own fd on the
        "store-snapshot" thread, taking the SAME chunked-lock
        consistent view snapshot() takes — write transactions
        interleave with it and their group-commit fdatasyncs never
        wait for snapshot I/O on the calling thread. Tickets run one
        at a time in submission order (one worker), so back-to-back
        calls cannot interleave chunk writes to the same path."""
        self._ensure_snap_thread()
        ticket = SnapshotTicket()
        self._snap_q.put((lambda: self.snapshot(path), ticket))
        return ticket

    def snapshot_delta_async(self, path: str) -> SnapshotTicket:
        """snapshot_delta on the dedicated snapshot thread (same
        ordering contract as snapshot_async). Falls back to a full
        snapshot inside when no chain base exists yet."""
        self._ensure_snap_thread()
        ticket = SnapshotTicket()
        self._snap_q.put((lambda: self.snapshot_delta(path), ticket))
        return ticket

    def drain_snapshots(self, timeout: Optional[float] = None) -> None:
        """Block until every queued background checkpoint has finished
        (tests and orderly shutdown). Does not propagate their errors —
        use the tickets for that."""
        t = self._snap_thread
        if t is None or not t.is_alive():
            return
        sentinel = SnapshotTicket()
        self._snap_q.put((lambda: None, sentinel))
        sentinel._event.wait(timeout)

    def rotate_log(self, snapshot_path: str,
                   wait: bool = True) -> Optional[SnapshotTicket]:
        """Compaction: park the current segment aside, restart the log
        from a fresh GENESIS line, then checkpoint — segment-chain
        order, so the only full-stop stall writers ever pay is the
        few-millisecond segment swap, never an O(all jobs)
        serialization (VERDICT r4 weak #4: the previous designs held
        the store lock across multi-second snapshots, or rewrote a
        snapshot-sized tail inside the exclusive window).

        Order of operations and why each crash window is safe:
        1. (exclusive, ~ms) barrier; hardlink the live segment to
           `<log>.pre-<new-genesis>`; atomically swap in a fresh
           segment whose first line is the genesis marker; reopen the
           writer. A crash before the swap leaves the old segment the
           live log (rotation simply didn't happen; the pre-link is a
           harmless orphan swept at the next rotation). A crash after
           leaves snapshot(old genesis) + pre-segment + new segment —
           restore() replays the CHAIN: pre-segment (by offset when
           the snapshot matches its genesis) then the new segment.
        2. (chunked lock — writers interleave) snapshot. It records
           the NEW genesis + offset, covering everything the
           pre-segment held.
        3. unlink the pre-segment: fully covered by step 2's durable
           snapshot.

        Followers stay correct throughout: their genesis-change resync
        restores through the same chain. Only the leader may rotate.

        wait=False returns a SnapshotTicket right after step 1's O(ms)
        exclusive swap; steps 2-3 (checkpoint + pre-segment unlink) run
        on the dedicated snapshot thread. A crash before the background
        checkpoint lands is exactly the step-1->2 crash window above —
        the pre-segment survives and the next rotation (or restore)
        covers it."""
        if not self._log_path:
            raise ValueError("rotate_log needs a log-backed store")
        with self._lock:
            self._check_writable()
        # finish a rotation interrupted between swap and checkpoint
        # FIRST: its pre-segment is only on the restore chain for the
        # CURRENT genesis, so another swap would orphan it un-covered
        self._sweep_pre_segments(snapshot_path)
        d = os.path.dirname(os.path.abspath(self._log_path))
        # global section: the segment swap must quiesce every shard —
        # an append racing the writer swap could land on the closed
        # handle
        with self._global_section():
            self._check_writable()
            # flush the group-commit buffer: the pre-link must name a
            # complete on-disk segment (no appends can race: lock held)
            self._barrier()
            genesis = new_uuid()
            pre_path = f"{self._log_path}.pre-{genesis}"
            # link BEFORE touching the live writer: any failure here
            # propagates with the writer open and the segment intact
            os.link(self._log_path, pre_path)
            tmp = self._log_path + ".rot"
            with open(tmp, "w") as f:
                f.write(json.dumps({"t": now_ms(), "k": "genesis",
                                    "g": genesis},
                                   separators=(",", ":")) + "\n")
                f.flush()
                os.fsync(f.fileno())
            old_log = self._log
            try:
                if old_log is not None:
                    old_log.close()
                os.replace(tmp, self._log_path)
                _fsync_dir(d)
                self._log = _make_log_writer(self._log_path, trim=False)
            except Exception:
                # never leave the store on a silently-closed writer:
                # reopen against whichever complete segment the rename
                # left at log_path; if even the reopen fails, install
                # the loud sentinel so every transaction errors
                # explicitly instead of acking writes that will never
                # reach disk
                try:
                    self._log = _make_log_writer(self._log_path,
                                                 trim=False)
                except Exception:
                    self._log = _FailedLogWriter(self._log_path)
                raise
            self._log_genesis = genesis
        # mid-rotation kill point: the step-1→2 crash window — segment
        # swapped, covering checkpoint not yet taken. Restore must
        # replay the .pre-<genesis> chain (tests/test_crash_soak.py
        # arms this site)
        procfault.kill_point("store.rotate")
        # 2) checkpoint against the fresh incarnation (chunked lock;
        # write transactions interleave). Durable (file+dir fsync)
        # before step 3 destroys the pre-segment it covers.
        def _finish() -> int:
            lines0 = self.snapshot(snapshot_path)
            # 3) the pre-segment is covered; drop it
            try:
                os.unlink(pre_path)
            except OSError:
                pass
            _fsync_dir(d)
            return lines0

        if wait:
            _finish()
            return None
        self._ensure_snap_thread()
        ticket = SnapshotTicket()
        self._snap_q.put((_finish, ticket))
        return ticket

    def _sweep_pre_segments(self, snapshot_path: str) -> None:
        """Cover-and-delete any `.pre-*` segments left by a rotation
        that crashed between its swap and its checkpoint. This store's
        in-memory state includes their events (boot-time restore
        replays the chain), so one snapshot covers them all."""
        import glob
        pres = glob.glob(glob.escape(self._log_path) + ".pre-*")
        if not pres:
            return
        self.snapshot(snapshot_path)
        for p in pres:
            try:
                os.unlink(p)
            except OSError:
                pass
        _fsync_dir(os.path.dirname(os.path.abspath(self._log_path)))

    @classmethod
    def restore(cls, path: Optional[str] = None,
                log_path: Optional[str] = None,
                trim_tail: bool = True,
                open_writer: bool = True,
                store_shards: int = 4,
                _retries: int = 2) -> "JobStore":
        """Rebuild: snapshot (if any) + replay of the event-log tail
        beyond the snapshot's recorded position. With no snapshot the
        whole log replays from empty.

        trim_tail=False: do NOT truncate a torn final line — required
        when another process (the live leader, in an HA deployment
        sharing the log) may be mid-append: truncating under its
        O_APPEND writer would glue its continuation to the preceding
        line and corrupt the log. The replay simply stops before an
        unterminated final line instead.

        Corruption tolerance (snapshot side): the snapshot's CRC
        frame is verified before anything loads; a torn/corrupt
        primary falls back to `<path>.prev` (the previous good
        snapshot, kept as a hardlink), and failing that to an empty
        store + full log replay — recovery degrades to slower, never
        to wrong. After the base loads, the delta chain
        (`<path>.delta-<seq>`, written by snapshot_delta) applies in
        sequence order while base_id matches and frames verify; the
        log tail then replays from the last good recorded position."""
        t0 = time.perf_counter()
        offset = 0
        snap_genesis = None
        store = cls(store_shards=store_shards)
        store._restored_from = None
        store._restore_deltas = 0
        data = None
        if path:
            for cand in (path, path + ".prev"):
                if not os.path.exists(cand):
                    continue
                try:
                    data = _load_framed_json(cand)
                    if not isinstance(data.get("jobs"), dict):
                        raise ValueError("snapshot missing jobs table")
                except Exception:
                    log.warning(
                        "restore: snapshot %s unreadable or fails its "
                        "CRC frame; falling back", cand, exc_info=True)
                    data = None
                    continue
                store._restored_from = cand
                break
            if data is None and os.path.exists(path):
                log.warning("restore: no loadable snapshot at %s; "
                            "replaying the full log from empty", path)
        header_genesis = None
        if data is not None:
            offset = int(data.get("log_lines", 0))
            snap_genesis = header_genesis = data.get("log_genesis")
            for u, jd in data["jobs"].items():
                job = _job_from_dict(jd)
                store.jobs[u] = job
                for inst in job.instances:
                    store.task_to_job[inst.task_id] = u
                store._reindex(job)
            for u, gd in data.get("groups", {}).items():
                store.groups[u] = Group(**gd)
            store.rebalancer_config = dict(
                data.get("rebalancer_config", {}))
            # delta chain: always probed against the PRIMARY path —
            # base_id matching makes stale or other-chain deltas
            # no-ops (and lets a .prev fallback correctly pick up the
            # chain that was written against it)
            offset, snap_genesis = store._apply_delta_chain(
                path, data.get("snap_id"), offset, snap_genesis)
        consumed = offset
        if log_path and os.path.exists(log_path):
            if trim_tail:
                _trim_torn_tail(log_path)
            # rotation detection: the snapshot's line offset only means
            # anything against the log incarnation it was taken from.
            # A genesis mismatch (the log was rotated since, or the
            # snapshot predates a rotation) invalidates the offset —
            # replay the WHOLE log over the snapshot state instead (all
            # event applications are idempotent/transition-guarded).
            log_genesis = _read_log_genesis(log_path)
            if snap_genesis != log_genesis:
                # segment chain: a rotation that crashed (or is still
                # running its checkpoint) between the segment swap and
                # the covering snapshot leaves the old segment parked
                # at .pre-<new genesis>. Its events are in neither the
                # snapshot nor the new segment — replay it FIRST (by
                # offset when the snapshot matches its genesis), then
                # the new segment. Torn final line possible (the
                # swapped-out leader may have died mid-append): skip
                # it, it was never acked.
                pre = (f"{log_path}.pre-{log_genesis}"
                       if log_genesis else None)
                pre_replayed = False
                if pre and os.path.exists(pre):
                    try:
                        pre_off = (offset if snap_genesis
                                   == _read_log_genesis(pre) else 0)
                        store._replay(pre, pre_off,
                                      allow_partial_tail=True)
                        pre_replayed = True
                    except FileNotFoundError:
                        # the leader's rotation step 3 unlinked the
                        # pre-segment between our exists() check and
                        # the open — same completion race as the
                        # snapshot TOCTOU below (any partially-applied
                        # pre events are discarded with this store
                        # object on the retry)
                        pass
                if not pre_replayed and path and _retries > 0 and \
                        store._restored_from == path and \
                        _read_snapshot_genesis(path) != header_genesis:
                    # TOCTOU: the rotation COMPLETED between our
                    # snapshot load (seconds at 100k jobs) and the pre
                    # read — the pre-segment is gone because the fresh
                    # checkpoint now covers it. Replaying only the new
                    # segment over the STALE snapshot would silently
                    # drop the old segment's tail; restart from the
                    # fresh snapshot instead.
                    return cls.restore(path, log_path,
                                       trim_tail=trim_tail,
                                       open_writer=open_writer,
                                       store_shards=store_shards,
                                       _retries=_retries - 1)
                offset = 0
            consumed = store._replay(log_path, offset,
                                     allow_partial_tail=not trim_tail)
        # the exact resume point for incremental followers: seeding
        # from the writer's later line count would skip events appended
        # between replay-finish and writer-open
        store._replayed_offset = consumed
        store._snapshot_path = path
        # seed the live genesis even when the offset seek skipped the
        # genesis line itself — otherwise the next snapshot records
        # log_genesis: null against a genesis-stamped log and every
        # later restore full-replays instead of seeking
        if log_path and os.path.exists(log_path):
            store._log_genesis = _read_log_genesis(log_path)
        if log_path:
            store._log_path = log_path
            if open_writer:
                store._log = _make_log_writer(log_path, trim=trim_tail)
        # recovery-time evidence for /debug and the crash-soak gate
        store.restore_ms = (time.perf_counter() - t0) * 1e3
        return store

    def _apply_delta_chain(self, path: str, snap_id: Optional[str],
                           offset: int, snap_genesis):
        """Apply `<path>.delta-<seq>` files in sequence order on top of
        the loaded base snapshot. The chain ends at the first missing
        seq, CRC/parse failure, or base_id mismatch — whatever the
        deltas did not cover, the log tail replay does (the caller
        replays from the returned position), so ending early is always
        correct. Returns the (log offset, log genesis) recorded by the
        last applied delta."""
        if not snap_id:
            return offset, snap_genesis
        seq = 1
        while True:
            dp = "%s.delta-%d" % (path, seq)
            if not os.path.exists(dp):
                break
            try:
                d = _load_framed_json(dp)
            except Exception:
                log.warning("restore: delta %s torn/corrupt; ending "
                            "chain (log replay covers the rest)", dp,
                            exc_info=True)
                break
            if d.get("base_id") != snap_id or d.get("seq") != seq:
                log.warning("restore: delta %s belongs to another "
                            "chain; ignoring it and the rest", dp)
                break
            for u, jd in d.get("jobs", {}).items():
                job = _job_from_dict(jd)
                old = self.jobs.get(u)
                if old is not None:
                    self._deindex(old)
                self.jobs[u] = job
                for inst in job.instances:
                    self.task_to_job[inst.task_id] = u
                self._reindex(job)
            for u in d.get("tombstones", ()):
                self._retire_job(u)
            # groups/rebalancer config ride whole in every delta, so
            # the LAST applied delta's copy is authoritative
            self.groups = {u: Group(**gd)
                           for u, gd in d.get("groups", {}).items()}
            self.rebalancer_config = dict(
                d.get("rebalancer_config", {}))
            offset = int(d.get("log_lines", offset))
            snap_genesis = d.get("log_genesis", snap_genesis)
            self._restore_deltas = seq
            seq += 1
        return offset, snap_genesis

    def reload_from(self, snapshot_path: Optional[str] = None) -> None:
        """Re-replay snapshot + log INTO this store, in place.

        The leader-takeover path: a standby built its store at process
        start, but the (now dead) leader kept appending to the shared
        event log afterwards — on takeLeadership the standby must see
        every job/instance the old leader persisted before it can
        schedule (the reference gets this for free from Datomic;
        mesos.clj:153-223 + reconcile). Not needed on a fresh start;
        harmless then (replays to the same state)."""
        if not self._log_path:
            return
        fresh = JobStore.restore(snapshot_path, log_path=self._log_path,
                                 store_shards=self.store_shards)
        with self._global_section():
            old_log = self._log
            # sync the outgoing writer UNDER the lock before swapping:
            # a committer that appended to it and released the lock may
            # still be on its way to _barrier — its handle will be
            # closed, and the barrier's swapped-writer tolerance relies
            # on the closer having made those appends durable first
            if old_log is not None and hasattr(old_log, "sync"):
                try:
                    old_log.sync()
                except Exception:
                    log.warning("reload_from: outgoing writer sync "
                                "failed", exc_info=True)
            self.jobs = fresh.jobs
            self.groups = fresh.groups
            self.task_to_job = fresh.task_to_job
            self.rebalancer_config = fresh.rebalancer_config
            self._pending = fresh._pending
            self._usage = fresh._usage
            self._usage_jobs = fresh._usage_jobs
            self._replay_max_epoch = fresh._replay_max_epoch
            self._log = fresh._log
            # the wholesale state swap invalidates any in-process
            # delta chain: force the next checkpoint to be full
            self._dirty_jobs = set()
            self._dirty_tombstones = set()
            self._delta_base_id = None
            self._delta_base_path = None
            self._delta_seq = 1
        if old_log is not None:
            try:
                old_log.close()
            except Exception:
                pass

    def _replay(self, log_path: str, offset: int,
                allow_partial_tail: bool = False) -> int:
        """Apply events [offset:] through the normal transaction fns with
        logging/listeners suppressed. Returns the line offset consumed
        up to (the resume point for incremental followers)."""
        self._replaying = True
        consumed = offset
        try:
            with open(log_path) as f:
                for lineno, line in enumerate(f):
                    if lineno < offset:
                        continue
                    if allow_partial_tail and not line.endswith("\n"):
                        # in-flight append by a live writer: not ours yet
                        break
                    consumed = lineno + 1
                    if not line.strip():
                        continue
                    try:
                        ev = json.loads(line)
                    except ValueError:
                        # UNterminated torn tails are truncated before
                        # replay (_trim_torn_tail); a complete-but-
                        # corrupt FINAL record is the other crash shape
                        # (power cut mid-append on a filesystem that
                        # persisted the newline first): log + skip it —
                        # the transaction it encoded never acked.
                        # Anything corrupt MID-log means real damage
                        # and must surface.
                        if f.read().strip():
                            raise
                        log.warning(
                            "replay: dropping corrupt final record at "
                            "line %d of %s", lineno + 1, log_path)
                        break
                    self._apply_event(ev)
        finally:
            self._replaying = False
        return consumed

    def follow_log(self, interval_s: float = 2.0):
        """Read-replica mode: incrementally apply new shared-log events
        on a timer, so an api-only node's reads stay fresh instead of
        frozen at its boot-time restore (the role Datomic's live peer
        index gives the reference's api-only nodes). Never writes.
        Returns a stop() callable.

        Incremental: a persistent binary handle streams only NEW bytes
        per tick (a from-zero rescan would be O(total log) every tick).
        The handle's position always sits at a COMPLETE-line boundary —
        an unterminated trailing fragment is seeked back over, never
        buffered. That makes a takeover's torn-tail repair harmless
        even when the file regrows within one tick: the repair
        truncates exactly the fragment we never consumed, so the new
        leader's appends continue from our position. Each line advances
        the applied counter only AFTER it is applied; a failing line is
        seeked back to and retried next tick."""
        if not self._log_path:
            raise ValueError("follow_log needs a log_path")
        # a follower must never append: drop any writer handle. Sync
        # it first UNDER the lock — an in-flight committer between its
        # append and its (post-lock) barrier must find its lines
        # already durable when its barrier sees the writer gone,
        # otherwise its ack covers page-cache-only data.
        with self._global_section():
            old = self._log
            if old is not None:
                if hasattr(old, "sync"):
                    old.sync()
                self._log = None
        if old is not None:
            try:
                old.close()
            except Exception:
                pass
        stop = threading.Event()
        state = {"applied": getattr(self, "_replayed_offset", 0),
                 "f": None,
                 "genesis": getattr(self, "_log_genesis", None)}

        def full_resync(reason: str):
            log.warning("log follower: %s; full state resync", reason)
            if state["f"] is not None:
                state["f"].close()
                state["f"] = None
            fresh = JobStore.restore(
                getattr(self, "_snapshot_path", None),
                log_path=self._log_path, trim_tail=False,
                open_writer=False, store_shards=self.store_shards)
            with self._global_section():
                self.jobs = fresh.jobs
                self.groups = fresh.groups
                self.task_to_job = fresh.task_to_job
                self.rebalancer_config = fresh.rebalancer_config
                self._pending = fresh._pending
                self._usage = fresh._usage
                self._usage_jobs = fresh._usage_jobs
                self._replay_max_epoch = fresh._replay_max_epoch
                self._log_genesis = getattr(fresh, "_log_genesis", None)
            state["applied"] = fresh._replayed_offset
            state["genesis"] = getattr(fresh, "_log_genesis", None)

        def tick():
            path = self._log_path
            # incarnation check EVERY tick: a rotation that regrows the
            # file past our byte offset before the next tick would slip
            # past the size-shrink check below, silently resuming
            # mid-stream in the new incarnation
            if os.path.exists(path) and \
                    _read_log_genesis(path) != state["genesis"]:
                full_resync("log genesis changed (rotation)")
                return
            if state["f"] is None:
                if not os.path.exists(path):
                    return
                f = open(path, "rb")
                for _ in range(state["applied"]):
                    if not f.readline():
                        break
                state["f"] = f
            f = state["f"]
            if os.path.getsize(path) < f.tell():
                # file shrank below our consumed boundary: the log was
                # genuinely truncated (beyond the benign torn-tail
                # fragment, which we never consume). Line numbering no
                # longer matches — resuming by count would silently
                # skip or mis-apply events.
                full_resync(f"{path} shrank below consumed offset")
                return
            start = f.tell()
            chunk = f.read()
            if not chunk:
                return
            pos = 0          # offset into chunk of next unconsumed line
            while True:
                nl = chunk.find(b"\n", pos)
                if nl == -1:
                    break    # trailing fragment: not ours yet
                raw = chunk[pos:nl]
                if raw.strip():
                    try:
                        ev = json.loads(raw)
                        with self._global_section():
                            self._replaying = True
                            try:
                                self._apply_event(ev)
                            finally:
                                self._replaying = False
                    except Exception:
                        log.exception("log follow: bad event; retrying "
                                      "next tick")
                        break
                pos = nl + 1
                state["applied"] += 1
            f.seek(start + pos)

        def loop():
            while not stop.wait(interval_s):
                try:
                    tick()
                except Exception:
                    log.exception("log follow failed")
            if state["f"] is not None:
                state["f"].close()

        t = threading.Thread(target=loop, daemon=True,
                             name="log-follower")
        t.start()

        def stopper():
            stop.set()
            t.join(timeout=5)

        return stopper

    def _replay_job(self, job: Job) -> None:
        """Shared replay body for the "job" (legacy, one per line) and
        "jobs" (batched) event kinds."""
        if job.uuid in self.jobs:
            return
        self.jobs[job.uuid] = job
        for inst in job.instances:
            self.task_to_job[inst.task_id] = job.uuid
        self._reindex(job)
        # group membership: create_jobs extends an EXISTING group's
        # member list without logging a group event, so replay must
        # reconstruct it from the job's group ref — otherwise a
        # replica's member list diverges and retention retires a group
        # the leader still holds
        if job.group:
            g = self.groups.get(job.group)
            if g is not None and job.uuid not in g.jobs:
                g.jobs.append(job.uuid)

    def _apply_event(self, ev: dict) -> None:
        k = ev["k"]
        # epoch fencing on replay: an entry stamped with a leader epoch
        # older than the newest epoch already seen was written by a
        # deposed leader that stalled past the fence check — drop it
        # (the live successor's entries carry the higher epoch).
        ep = ev.get("ep", 0)
        if ep:
            if ep < self._replay_max_epoch:
                log.warning("replay: dropping stale-epoch event "
                            "(ep=%d < %d): %s", ep,
                            self._replay_max_epoch, ev.get("k"))
                return
            self._replay_max_epoch = ep
        if k == "genesis":
            self._log_genesis = ev.get("g")
            return
        if k == "job":
            self._replay_job(_job_from_dict(ev["job"]))
        elif k == "jobs":
            # batched submission record (one line per create_jobs call;
            # the legacy per-job "job" kind above still replays for
            # logs written before the batch encoder)
            for d in ev.get("items", ()):
                self._replay_job(_job_from_dict(d))
        elif k == "group":
            g = Group(**ev["group"])
            if g.uuid not in self.groups:
                self.groups[g.uuid] = g
        elif k == "commit":
            job = self.jobs.get(ev["job"])
            if job:
                job.committed = True
                self._reindex(job)
        elif k == "gc":
            job = self.jobs.pop(ev["job"], None)
            if job is not None:
                self._deindex(job)
        elif k == "retire":
            for u in ev.get("jobs", ()):
                self._retire_job(u)
        elif k == "fedmove":
            # pool migrated to another leader group: its jobs left this
            # store's state (they live on in the destination's log)
            for u in ev.get("jobs", ()):
                self._retire_job(u)
        elif k == "fedadopt":
            self._adopt_pool_state(ev.get("items", ()),
                                   ev.get("groups", ()))
        elif k == "rebalancer_config":
            self.rebalancer_config = dict(ev.get("cfg", {}))
        elif k == "inst":
            job = self.jobs.get(ev["job"])
            if job and not any(i.task_id == ev["task"] for i in job.instances):
                inst = Instance(task_id=ev["task"], job_uuid=ev["job"],
                                hostname=ev["host"], backend=ev["backend"],
                                start_time_ms=ev.get("t", 0))
                job.instances.append(inst)
                self.task_to_job[inst.task_id] = job.uuid
                self._update_job_state(job)
                self._reindex(job)
        elif k == "insts":
            for it in ev.get("items", []):
                job = self.jobs.get(it["j"])
                if job and not any(i.task_id == it["i"]
                                   for i in job.instances):
                    inst = Instance(task_id=it["i"], job_uuid=it["j"],
                                    hostname=it["h"], backend=it["b"],
                                    start_time_ms=ev.get("t", 0))
                    job.instances.append(inst)
                    self.task_to_job[inst.task_id] = job.uuid
                    self._update_job_state(job)
                    self._reindex(job)
        elif k == "status":
            st = InstanceStatus(ev["s"])
            # was-state capture BEFORE applying: the clock backfill
            # below must only fire when THIS event performed the
            # transition. Snapshot-at-position replay re-applies events
            # the snapshot may already contain — for a job that failed,
            # was retried, and re-completed, an unguarded backfill
            # would drag the final end time back to the earlier
            # failure's timestamp and the restored store would diverge
            # from the leader (ADVICE r5).
            inst0 = self.get_instance(ev["task"])
            was_inst_end = inst0.end_time_ms if inst0 is not None else None
            ju = self.task_to_job.get(ev["task"])
            job0 = self.jobs.get(ju) if ju else None
            was_completed = job0 is not None \
                and job0.state == JobState.COMPLETED
            self.update_instance(ev["task"], st,
                                 reason_code=ev.get("r"),
                                 preempted=bool(ev.get("p")),
                                 exit_code=ev.get("e"))
            # replay parity: completion clocks come from the event's
            # original timestamp, not replay wall-clock — otherwise a
            # restart refreshes the retention window and silently
            # changes user-visible end times for every job completed
            # since the last snapshot (same backfill as "kill" below)
            if ev.get("t") and st in (InstanceStatus.SUCCESS,
                                      InstanceStatus.FAILED):
                job = self.jobs.get(ju) if ju else None
                if job is not None:
                    for i in job.instances:
                        if i.task_id == ev["task"] and i.end_time_ms \
                                and was_inst_end is None:
                            i.end_time_ms = ev["t"]
                    if not was_completed \
                            and job.state == JobState.COMPLETED:
                        job.end_time_ms = ev["t"]
        elif k == "progress":
            self.update_progress(ev["task"], ev["q"], ev["pc"], ev.get("m", ""))
        elif k == "retry":
            if ev["job"] in self.jobs:
                self.retry_job(ev["job"], ev["n"])
        elif k == "kill":
            # same was-state guard as "status": only the kill that
            # actually completes the job may stamp its end time — a
            # replayed kill over an already-completed job (snapshot
            # contains it, or an earlier kill in the tail) is a no-op
            j0 = self.jobs.get(ev["job"])
            was_completed = j0 is not None \
                and j0.state == JobState.COMPLETED
            self.kill_job(ev["job"])
            j = self.jobs.get(ev["job"])
            if j is not None and not was_completed \
                    and j.state == JobState.COMPLETED and ev.get("t"):
                j.end_time_ms = ev["t"]


_JOB_FIELDS = None
_INST_FIELDS = None


def _job_dict(job: Job) -> dict:
    """Shallow field walk instead of dataclasses.asdict: asdict deep-
    copies recursively (~100 us/job) and dominates the submission path
    at scale; the log line is serialized under the store lock anyway, so
    references are safe."""
    global _JOB_FIELDS, _INST_FIELDS
    if _JOB_FIELDS is None:
        import dataclasses
        _JOB_FIELDS = tuple(f.name for f in dataclasses.fields(Job))
        _INST_FIELDS = tuple(f.name for f in dataclasses.fields(Instance))
    jd = job.__dict__
    d = {k: jd[k] for k in _JOB_FIELDS}
    d["state"] = job.state.value
    d["instances"] = [
        {**{k: i.__dict__[k] for k in _INST_FIELDS},
         "status": i.status.value}
        for i in job.instances
    ]
    return d


def _job_from_dict(d: dict) -> Job:
    insts = [
        Instance(**{**i, "status": InstanceStatus(i["status"])})
        for i in d.pop("instances", [])
    ]
    d["state"] = JobState(d["state"])
    job = Job(**{**d, "instances": insts})
    return job


def _load_framed_json(path: str) -> dict:
    """Load a snapshot/delta file, verifying the `#crc <hex> <len>`
    trailer when present. The document body is newline-free JSON, so
    the trailer's leading newline is unambiguous. Files from before
    the framing (no trailer) load unchecked — json parsing itself
    still rejects truncation. Raises ValueError on CRC mismatch,
    length mismatch, or unparsable content."""
    with open(path, "rb") as f:
        raw = f.read()
    body = raw
    tail = raw.rfind(b"\n#crc ")
    if tail != -1:
        parts = raw[tail + 1:].split()
        if len(parts) == 3:
            body = raw[:tail]
            want_crc = int(parts[1], 16)
            want_len = int(parts[2])
            if len(body) != want_len:
                raise ValueError("%s: framed length %d != actual %d"
                                 % (path, want_len, len(body)))
            if zlib.crc32(body) != want_crc:
                raise ValueError("%s: CRC mismatch" % path)
    return json.loads(body)


def _read_snapshot_genesis(path: str):
    """log_genesis recorded in a snapshot file, WITHOUT loading the
    (possibly 100 MB) document: snapshot() writes the dict with
    log_lines/log_genesis first, so the value sits in the first bytes.
    Used by restore()'s rotation-TOCTOU check, where re-loading the
    whole snapshot just to learn its genesis would double the cost of
    every retried restore. Returns None for null/absent/unparseable."""
    try:
        with open(path, "rb") as f:
            head = f.read(4096).decode("utf-8", "replace")
    except OSError:
        return None
    m = re.search(r'"log_genesis"\s*:\s*(?:"([^"]*)"|null)', head)
    return m.group(1) if m and m.group(1) is not None else None


def _read_log_genesis(path: str):
    """First-line genesis id of a log, or None for never-rotated logs."""
    try:
        with open(path, "rb") as f:
            first = f.readline(4096)
        ev = json.loads(first)
        return ev.get("g") if ev.get("k") == "genesis" else None
    except (OSError, ValueError):
        return None


def _read_epoch_ledger(path: str) -> int:
    """Max epoch recorded in the ledger (0 when missing/empty). The
    ledger is append-only JSONL; a torn final record (crash mid-mint)
    is skipped — a mint that never fsync'd never fenced anyone, so the
    crashed candidate simply re-mints above the last durable entry."""
    top = 0
    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    top = max(top, int(json.loads(line).get("epoch", 0)))
                except (ValueError, TypeError):
                    continue
    except OSError:
        return 0
    return top


def _read_epoch_fences(path: str) -> tuple:
    """(max unscoped epoch, {pool: max pool-scoped epoch}) from the
    ledger. Splitting the two is what keeps a pool-scoped mint (live
    migration) from fencing the whole source store: the global fence
    compares against unscoped mints only, while migrated pools carry
    their own per-pool fence. Torn/garbage lines skip, same contract
    as _read_epoch_ledger."""
    top = 0
    fences: dict = {}
    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                    ep = int(rec.get("epoch", 0))
                except (ValueError, TypeError):
                    continue
                pools = rec.get("pools")
                if pools:
                    for p in pools:
                        fences[p] = max(fences.get(p, 0), ep)
                else:
                    top = max(top, ep)
    except OSError:
        return 0, {}
    return top, fences


def _read_membership_ledger(path: str) -> list:
    """All membership records in the ledger, oldest first. A torn
    final line (crash mid-append) skips — a begin that never fsync'd
    never promised anyone a new view, so a restarted coordinator
    simply sees the previous membership. Same torn-line contract as
    _read_epoch_ledger."""
    out: list = []
    try:
        with open(path, "r", encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and rec.get("mepoch"):
                    out.append(rec)
    except OSError:
        return []
    return out


def _fsync_dir(path: str) -> None:
    """fsync a directory so a just-os.replace'd entry survives power
    loss (the rename itself is atomic but not durable without this)."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass   # some filesystems refuse directory fsync; best effort
    finally:
        os.close(fd)


def _trim_torn_tail(path: str) -> None:
    """Truncate a torn final line (crash mid-append). The torn event was
    never acked — the durability barrier runs before any ack — so
    dropping it is safe; leaving it would glue the next append onto it
    and corrupt the log for every future recovery."""
    try:
        size = os.path.getsize(path)
    except OSError:
        return
    if size == 0:
        return
    with open(path, "rb+") as f:
        f.seek(size - 1)
        if f.read(1) == b"\n":
            return
        pos, block = size, 65536
        while pos > 0:
            step = min(block, pos)
            f.seek(pos - step)
            buf = f.read(step)
            nl = buf.rfind(b"\n")
            if nl != -1:
                f.truncate(pos - step + nl + 1)
                return
            pos -= step
        f.truncate(0)


def _make_log_writer(path: str, trim: bool = True):
    """Prefer the native C++ group-commit writer (native/eventlog.cpp);
    fall back to the pure-Python writer if the toolchain is missing.
    trim=False skips torn-tail repair (callers that share the log with
    a possibly-live writer must never truncate it)."""
    if trim and os.path.exists(path):
        _trim_torn_tail(path)
    try:
        from cook_tpu.native.eventlog import NativeLogWriter
        return NativeLogWriter(path)
    except Exception:
        return _PyLogWriter(path)


class _FailedLogWriter:
    """Installed when a failed rotation cannot reopen ANY log writer:
    a durable store must fail transactions loudly, not degrade into an
    in-memory one (self._log = None would do exactly that). Process
    restart recovers through the normal restore path."""

    def __init__(self, path: str):
        self._path = path

    def _die(self):
        raise OSError(f"event log writer lost after a failed rotation "
                      f"of {self._path}; restart to recover")

    def append(self, line: str) -> None:
        self._die()

    def append_many(self, lines) -> None:
        self._die()

    def append_segments(self, segs, nlines: int) -> None:
        self._die()

    def sync(self) -> None:
        self._die()

    def lines(self) -> int:
        self._die()

    def close(self) -> None:
        pass


class _PyLogWriter:
    """Fallback pure-Python append-only log (the C++ writer in
    cook_tpu/native is preferred; see native/eventlog.cpp).

    sync() gives the same durability guarantee as the native writer's
    group commit: the commit latch exists so a submission is only acked
    after its events are on disk (rest/api.clj:659 semantics), so the
    fallback must fsync too — it just pays one fsync per transaction
    instead of amortizing across concurrent committers."""

    def __init__(self, path: str):
        self._n = 0
        self._dirty = False
        if os.path.exists(path):
            with open(path) as f:
                self._n = sum(1 for _ in f)
        self._f = open(path, "a", buffering=1)
        self._lock = witness_lock("_PyLogWriter._lock")

    def append(self, line: str) -> None:
        with self._lock:
            self._f.write(line + "\n")
            self._n += 1
            self._dirty = True

    def append_many(self, lines) -> None:
        """One lock acquisition + one write() for a whole batch; sync()
        still decides when the bytes reach disk."""
        if not lines:
            return
        buf = "\n".join(lines) + "\n"
        with self._lock:
            self._f.write(buf)
            self._n += len(lines)
            self._dirty = True

    def append_segments(self, segs, nlines: int) -> None:
        """Zero-copy batch entry point: segs are byte fragments that
        concatenate to exactly nlines newline-terminated records (the
        contract _append_segments documents). The fallback joins once
        and writes once — byte-identical on disk to the native path."""
        if not segs or not nlines:
            return
        buf = b"".join(segs).decode("utf-8")
        with self._lock:
            self._f.write(buf)
            self._n += nlines
            self._dirty = True

    def sync(self) -> None:
        with self._lock:
            if not self._dirty:
                return
            self._f.flush()
            os.fsync(self._f.fileno())
            self._dirty = False

    def lines(self) -> int:
        with self._lock:
            return self._n

    def close(self) -> None:
        self._f.close()
