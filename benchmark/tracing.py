"""Reduce a JAX profiler trace to device busy time and its breakdown.

Only TPU device planes are read (`/device:TPU:<n>`), and on each the
`XLA Ops` line: busy time is the union of the op intervals, so it does
not depend on any executable's name. A trace without a TPU plane, or a
plane without ops, is an error, never an empty result.

The traced window is the host annotation `MARKER` the harness wraps
around it. Idle gaps are returned as intervals on the trace's clock;
`scopes.label_gaps` names them from the host events of the same trace.
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


def reduce(trace: dict, n_gaps: int = 10) -> dict:
    """busy_s (mean over chips), busy seconds per chip (`busy_by_chip`,
    by plane name), window_s, and the `n_gaps` longest idle gaps of any
    chip inside the marker, as (start_ns, end_ns)."""
    m0, m1 = trace["marker"]
    busy, gaps = [], []
    names = sorted(trace["planes"])
    for name in names:
        evs = trace["planes"][name]
        u = _union([(max(a, m0), min(b, m1)) for a, b, _n in evs
                    if b > m0 and a < m1])
        busy.append(sum(b - a for a, b in u) / 1e9)
        edges = [m0] + [x for ab in u for x in ab] + [m1]
        gaps += [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"busy_s": sum(busy) / len(busy),
            "busy_by_chip": dict(zip(names, busy)),
            "window_s": (m1 - m0) / 1e9,
            "gaps": gaps[:n_gaps]}
