#!/usr/bin/env python3
"""Record the small TPU trace that tests/test_tracing.py reads.

    python3 benchmark/tests/record_trace.py <out.xplane.pb>

Runs a few small jitted programs on the chip inside the harness's
marker annotation, with gaps between them, and copies the resulting
.xplane.pb to the given path. Needs a TPU.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileOptions, TraceAnnotation
    from benchmark import tracing
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.sort(x * 2.0 + 1.0))
    x = jnp.arange(1 << 16, dtype=jnp.float32)
    f(x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    with TraceAnnotation(tracing.MARKER):
        for _ in range(3):
            f(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    shutil.copy(tracing.find_xplane(d), out)
    shutil.rmtree(d, ignore_errors=True)
    print(f"record_trace: wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
