#!/usr/bin/env python3
"""Record the small TPU trace that tests/test_scopes.py reads.

    python3 benchmark/tests/record_scoped_trace.py <out.xplane.pb>

In one profiler session, inside the harness's marker annotation: a
jitted program whose ops run under the named scopes `rank` and
`head_scan`, called three times by a cycle record of kind `consume`
that stamps `readback` after the program and `fold` after a 20 ms
sleep, then commits and sleeps 10 ms more outside any record; a second
thread holds a `rest.submit` annotation across the first cycle. Copies
the resulting .xplane.pb to the given path. Needs a TPU.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileOptions, TraceAnnotation
    from benchmark import tracing
    from cook_tpu import obs
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def f(x):
        with jax.named_scope("rank"):
            y = jnp.sort(x * 2.0 + 1.0)
        with jax.named_scope("head_scan"):
            return jnp.cumsum(y) * 0.5

    x = jnp.arange(1 << 16, dtype=jnp.float32)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    submitted = threading.Event()

    def rest():
        with obs.annotation("rest.submit"):
            submitted.wait(5)

    jax.profiler.start_trace(d, profiler_options=opts)
    with TraceAnnotation(tracing.MARKER):
        th = threading.Thread(target=rest)
        th.start()
        for i in range(3):
            rec = obs.profiler.cycle("consume", "bench", i)
            f(x).block_until_ready()
            rec.stamp("readback")
            time.sleep(0.02)
            rec.stamp("fold")
            obs.profiler.commit(rec, cycle=i)
            submitted.set()
            time.sleep(0.01)
        th.join()
    jax.profiler.stop_trace()
    shutil.copy(tracing.find_xplane(d), out)
    shutil.rmtree(d, ignore_errors=True)
    print(f"record_scoped_trace: wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
