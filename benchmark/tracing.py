"""Reduce a JAX profiler trace to device busy time and its breakdown.

Only TPU device planes are read (`/device:TPU:<n>`), and on each the
`XLA Ops` line: busy time is the union of the op intervals, so it does
not depend on any executable's name. A trace without a TPU plane, or a
plane without ops, is an error, never an empty result.

The traced window is the host annotation `MARKER` the harness wraps
around it; its start, taken on the host's wall clock just before the
annotation opened, aligns the trace with the program's cycle records
so that each idle gap can be labelled with what the host was doing.
"""
from __future__ import annotations

import glob
import os

MARKER = "benchmark_traced_window"
OPS_LINE = "XLA Ops"


class TraceError(RuntimeError):
    pass


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise TraceError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def load(path: str) -> dict:
    """Device op intervals per TPU plane, op time by name, and the
    marker's (start, end) on the trace's clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    marker = None
    planes = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    # an op's name is its HLO text: keep the part before
                    # " = " (e.g. "%while.27")
                    planes[plane.name] = [(ev.start_ns, ev.end_ns,
                                           ev.name.split(" = ", 1)[0])
                                          for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARKER:
                        marker = (ev.start_ns, ev.end_ns)
    if not planes:
        raise TraceError("the trace has no TPU device plane with an "
                         f"'{OPS_LINE}' line")
    if not any(planes.values()):
        raise TraceError("no operation ran on the TPU in the trace")
    if marker is None:
        raise TraceError(f"the host annotation {MARKER!r} is missing")
    return {"planes": planes, "marker": marker}


def reduce(trace: dict, marker_wall_ms: float, phases=()) -> dict:
    """busy_s (mean over chips), window_s, the longest ops by total
    time and the longest idle gaps, each gap labelled with the host
    phases (`kind.phase` of the program's cycle records, wall-clock
    ms intervals) that cover its midpoint."""
    m0, m1 = trace["marker"]
    busy, ops, gaps = [], {}, []
    for name, evs in sorted(trace["planes"].items()):
        clipped = [(max(a, m0), min(b, m1)) for a, b, _n in evs
                   if b > m0 and a < m1]
        for a, b, n in evs:
            if b > m0 and a < m1:
                ops[n] = ops.get(n, 0.0) + (min(b, m1) - max(a, m0)) / 1e9
        u = _union(clipped)
        busy.append(sum(b - a for a, b in u) / 1e9)
        edges = [m0] + [x for ab in u for x in ab] + [m1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])

    def wall(ns):
        return marker_wall_ms + (ns - m0) / 1e6

    def label(a, b):
        mid = wall((a + b) / 2)
        names = sorted({n for n, lo, hi in phases if lo <= mid < hi})
        return "+".join(names) if names else "no cycle phase"

    return {"busy_s": sum(busy) / len(busy),
            "window_s": (m1 - m0) / 1e9,
            "device_ops": [[n, s] for n, s in
                           sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[label(a, b), (b - a) / 1e9]
                          for a, b in gaps[:10]]}
