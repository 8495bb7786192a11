"""Faults planted in the timed path, for test_faults.py.

    python3 benchmark/tests/faults.py <fault> <seed>

Runs the harness on the CPU at the tiny size of data/tiny.json (no look
for a chip) with the named fault installed once the server is up, and
prints the result line. Each fault breaks what the device cycle hands
to the rest of the served path:

- `none`: nothing broken;
- `state_unchanged`: each cycle returns the resident state it was given
  (matched jobs stay pending, hosts keep their capacity);
- `half_the_batch`: every second job of the considered head is dropped
  from the cycle's answer after the device placed it;
- `altered_answer`: the first placement of each cycle is moved to
  another host where it is produced.
"""
from __future__ import annotations

import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _repack(out, cons_idx, cons_host):
    """Give a cycle's output a changed answer, keeping its compacted
    matched prefix consistent with it."""
    import jax.numpy as jnp
    import numpy as np
    C = cons_idx.shape[0]
    m = (cons_idx >= 0) & (cons_host >= 0)
    mat_idx = np.full(C, -1, np.int32)
    mat_host = np.full(C, -1, np.int32)
    mat_idx[:m.sum()] = cons_idx[m]
    mat_host[:m.sum()] = cons_host[m]
    out.cons_host = jnp.asarray(cons_host.astype(np.int32))
    out.mat_idx = jnp.asarray(mat_idx)
    out.mat_host = jnp.asarray(mat_host)
    out.n_matched = jnp.asarray(np.int32(m.sum()))


def state_unchanged(coord, rp):
    import jax
    import jax.numpy as jnp
    orig = rp.dispatch

    def dispatch(*a, **kw):
        before = jax.tree.map(jnp.copy, rp.state)
        out = orig(*a, **kw)
        rp.state = before
        return out
    rp.dispatch = dispatch


def half_the_batch(coord, rp):
    import numpy as np
    orig = rp.dispatch

    def dispatch(*a, **kw):
        out = orig(*a, **kw)
        ch = np.asarray(out.cons_host).copy()
        ch[1::2] = -1
        _repack(out, np.asarray(out.cons_idx), ch)
        return out
    rp.dispatch = dispatch


def altered_answer(coord, rp):
    import numpy as np
    orig = rp.dispatch

    def dispatch(*a, **kw):
        out = orig(*a, **kw)
        ch = np.asarray(out.cons_host).copy()
        hit = np.flatnonzero(ch >= 0)
        if hit.size:
            ch[hit[0]] = (ch[hit[0]] + 1) % len(rp.host_names)
        _repack(out, np.asarray(out.cons_idx), ch)
        return out
    rp.dispatch = dispatch


FAULTS = {"none": None, "state_unchanged": state_unchanged,
          "half_the_batch": half_the_batch,
          "altered_answer": altered_answer}


def main(fault: str, seed: int) -> int:
    sys.path.insert(0, ROOT)
    from benchmark import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tiny.json")) as f:
        tiny = json.load(f)
    out = harness.run_cell(bench, "default-steady", seed, 4.0, False,
                           T_START, require_tpu=False, fault=FAULTS[fault],
                           files=(tiny["config"], tiny["traffic"]))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main(sys.argv[1], int(sys.argv[2]))
    sys.stdout.flush()
    os._exit(code)
