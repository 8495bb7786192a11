"""Device time per named scope, and idle gaps named from the host
events, of one recorded JAX trace (`.xplane.pb`).

Each TPU op's HLO metadata carries `tf_op`, its op name with the
`jax.named_scope`s it ran under (`jit(_device_cycle)/.../rank/sort:`).
`jax.profiler.ProfileData` does not expose metadata stats, so the TPU
plane is read here with a small protobuf wire-format reader (no
TensorFlow import; the schema is tsl/profiler/protobuf/xplane.proto).
An op belongs to the first of `SCOPES` on its path, or, for the
programs that run between cycles, to its executable (`jit(_scatter_*)`).
The TPU trace gives a while loop's own op no metadata: an op without
`tf_op` whose interval encloses ops of one scope takes that scope.
Time is the union of op intervals per scope inside the harness's
marker, and `unscoped` is busy time no scoped op covers.

Idle gaps are labelled from the host-plane events of the same trace,
on the same clock: the program's cycle records are events named after
their kind with `phase` and `cycle` stats (`obs.profiler`), and its
waits, REST and ingest spans are events of their own.
"""
from __future__ import annotations

import bisect
import glob
import os

from benchmark import tracing

SCOPES = ("apply_deltas", "rank", "filter", "head_scan", "epilogue")
EXECUTABLE_PREFIX = "jit(_scatter_"
# host events of the program's own, beside the cycle records
WAITS = ("match.tick_wait", "store.lock_wait", "store.fsync_wait")
CONCURRENT = ("rest.submit", "ingest.commit")
CYCLE_KINDS = ("match", "consume")


# -- protobuf wire format ----------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i, end):
    """(field number, wire type, value) of one message; a
    length-delimited value is its (start, end) in `buf`."""
    while i < end:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            n, i = _varint(buf, i)
            v = (i, i + n)
            i += n
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        else:
            raise tracing.TraceError(f"unsupported wire type {wt}")
        yield num, wt, v


def _str(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _plane_name(buf, span):
    for num, _wt, v in _fields(buf, *span):
        if num == 2:
            return _str(buf, v)
    return ""


def _tpu_plane(buf, span):
    """[(start_ns, end_ns, tf_op, op name)] of the plane's `XLA Ops`
    line."""
    stat_names, meta_stats, meta_names, ops_lines = {}, {}, {}, []
    for num, _wt, v in _fields(buf, *span):
        if num == 3:                                    # XLine
            name, ts, events = "", 0, []
            for n2, _w2, v2 in _fields(buf, *v):
                if n2 == 2:
                    name = _str(buf, v2)
                elif n2 == 3:
                    ts = _signed(v2)
                elif n2 == 4:
                    events.append(v2)
            if name == tracing.OPS_LINE:
                ops_lines.append((ts, events))
        elif num in (4, 5):                             # map entries
            key, val = 0, None
            for n2, _w2, v2 in _fields(buf, *v):
                if n2 == 1:
                    key = _signed(v2)
                elif n2 == 2:
                    val = v2
            if val is None:
                continue
            if num == 5:                                # XStatMetadata
                for n3, _w3, v3 in _fields(buf, *val):
                    if n3 == 2:
                        stat_names[key] = _str(buf, v3)
            else:                                       # XEventMetadata
                stats = meta_stats[key] = []
                for n3, _w3, v3 in _fields(buf, *val):
                    if n3 == 2:
                        # an op's name is its HLO text: keep the part
                        # before " = " (e.g. "%while.27")
                        meta_names[key] = _str(buf, v3).split(" = ", 1)[0]
                    elif n3 == 5:
                        stats.append(v3)
    tf_op_id = next((k for k, n in stat_names.items() if n == "tf_op"),
                    None)
    tf_op = {}
    for mid, stats in meta_stats.items():
        for st in stats:
            sid, text = None, None
            for n4, _w4, v4 in _fields(buf, *st):
                if n4 == 1:
                    sid = v4
                elif n4 == 5:
                    text = _str(buf, v4)
                elif n4 == 7:                           # interned
                    text = stat_names.get(v4)
            if sid == tf_op_id and text is not None:
                # "<op name>:<op type>"; the type is empty for XLA ops
                tf_op[mid] = text.rsplit(":", 1)[0]
                break
    ops = []
    for ts_ns, events in ops_lines:
        for ev in events:
            mid = off = dur = 0
            for n2, _w2, v2 in _fields(buf, *ev):
                if n2 == 1:
                    mid = _signed(v2)
                elif n2 == 2:
                    off = v2
                elif n2 == 3:
                    dur = v2
            a = ts_ns + off / 1e3
            ops.append((a, a + dur / 1e3, tf_op.get(mid, ""),
                        meta_names.get(mid, "")))
    return ops


def device_ops(path: str) -> dict:
    """{TPU plane name: [(start_ns, end_ns, tf_op, op name)]}; tf_op is
    "" for an op that carries none."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, _wt, v in _fields(buf, 0, len(buf)):
        if num == 1:
            name = _plane_name(buf, v)
            if name.startswith("/device:TPU:"):
                out[name] = _tpu_plane(buf, v)
    return out


# -- reductions --------------------------------------------------------

def scope_of(tf_op: str) -> str:
    parts = tf_op.split("/")
    if parts[0].startswith(EXECUTABLE_PREFIX):
        return parts[0]
    return next((p for p in parts if p in SCOPES), "unscoped")


def attributed(ops) -> list:
    """[(start_ns, end_ns, scope)] of one plane's ops (`device_ops`),
    an op without metadata taking the one scope of the scoped ops it
    encloses (a while loop and its body), if they have one."""
    memo: dict = {}

    def scope(t):
        s = memo.get(t)
        if s is None:
            s = memo[t] = scope_of(t)
        return s

    inner = sorted((a, b, scope(t)) for a, b, t, _n in ops if t)
    starts = [a for a, _b, _s in inner]
    out = []
    for a, b, t, _n in ops:
        s = scope(t)
        if not t:
            found = set()
            for j in range(bisect.bisect_left(starts, a),
                           bisect.bisect_left(starts, b)):
                ib, js = inner[j][1], inner[j][2]
                if ib <= b and js != "unscoped":
                    found.add(js)
            if len(found) == 1:
                s = found.pop()
        out.append((a, b, s))
    return out


def _clipped_union(intervals, m0, m1):
    return tracing._union([(max(a, m0), min(b, m1)) for a, b in intervals
                           if b > m0 and a < m1])


def _length(union) -> float:
    return sum(b - a for a, b in union)


def scope_times(path: str) -> dict:
    """{scope or executable: seconds} inside the marker, mean over the
    TPU planes, with `unscoped` (busy time no scoped op covers) and
    `busy`."""
    m0, m1 = tracing.load(path)["marker"]
    planes = device_ops(path)
    total: dict = {}
    for ops in planes.values():
        by: dict = {}
        for a, b, s in attributed(ops):
            by.setdefault(s, []).append((a, b))
        busy = _length(_clipped_union([(a, b) for a, b, _t, _n in ops],
                                      m0, m1))
        scoped = _length(_clipped_union(
            [ab for s, iv in by.items() if s != "unscoped" for ab in iv],
            m0, m1))
        for s, iv in by.items():
            if s != "unscoped":
                total[s] = total.get(s, 0.0) + _length(
                    _clipped_union(iv, m0, m1))
        total["unscoped"] = total.get("unscoped", 0.0) + busy - scoped
        total["busy"] = total.get("busy", 0.0) + busy
    return {s: ns / 1e9 / len(planes) for s, ns in total.items()}


def op_times(path: str) -> list:
    """[["scope/op", seconds]] of every device op inside the marker,
    summed over the TPU planes, longest first; `unscoped/op` for an op
    no scope claims (`attributed`)."""
    m0, m1 = tracing.load(path)["marker"]
    total: dict = {}
    for ops in device_ops(path).values():
        for (a, b, _t, name), (_a, _b, s) in zip(ops, attributed(ops)):
            if b > m0 and a < m1:
                key = f"{s}/{name}"
                total[key] = total.get(key, 0.0) + (min(b, m1)
                                                    - max(a, m0)) / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])]


def host_events(path: str) -> list:
    """(thread, label, start_ns, end_ns) of the program's own host
    events: cycle records as `kind.phase` (`kind` for a segment no
    phase closed), waits and concurrent spans by their names."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in CYCLE_KINDS:
                    phase = dict(ev.stats).get("phase")
                    label = f"{ev.name}.{phase}" if phase else ev.name
                elif ev.name in WAITS or ev.name in CONCURRENT:
                    label = ev.name
                else:
                    continue
                out.append((line.name, label, ev.start_ns, ev.end_ns))
    return out


def label_gaps(path: str, gaps) -> list:
    """[label, seconds, {concurrent span: count}] per idle gap (start_ns,
    end_ns): the cycle phases and waits open at the gap's midpoint on
    any thread (`no cycle phase` when none), and how many REST and
    ingest spans overlap the gap."""
    evs = host_events(path)
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        names = sorted({lab for _t, lab, lo, hi in evs
                        if lab not in CONCURRENT and lo <= mid < hi})
        beside: dict = {}
        for _t, lab, lo, hi in evs:
            if lab in CONCURRENT and lo < b and hi > a:
                beside[lab] = beside.get(lab, 0) + 1
        out.append(["+".join(names) if names else "no cycle phase",
                    (b - a) / 1e9, beside])
    return out


def idle_gaps(path: str, n: int = 10) -> list:
    """The `n` longest idle gaps of any TPU plane inside the marker,
    labelled (`label_gaps`)."""
    return label_gaps(path, tracing.reduce(tracing.load(path), n)["gaps"])


# -- the run in progress -----------------------------------------------

_cache: dict = {}


def run_trace():
    """The trace the harness recorded in this run (its work directory is
    removed after the run, and a run owns the process), else None."""
    from benchmark.harness import WORK
    found = glob.glob(os.path.join(WORK, "*", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def run_scope_ms(ctx, scope: str):
    """Device ms per match cycle under `scope` in this run's traced
    window; None when the program has no such scope (or no trace)."""
    tr = ctx["trace"]
    path = run_trace()
    if tr is None or not tr["match_cycles"] or path is None:
        return None
    if path not in _cache:
        _cache[path] = scope_times(path)
    s = _cache[path].get(scope)
    return s * 1e3 / tr["match_cycles"] if s else None
