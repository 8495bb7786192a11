#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last stdout line.

    python3 benchmark/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1> [--save-cycles <dir>]

The cell is looked up by name in BENCHMARK.json at the checkout's root;
everything else is found from there (see benchmark/harness.py). With
--trace 0 the result's metrics are the cell's end-to-end metrics, with
--trace 1 its per-layer metrics, read with the profiler on.

Exits non-zero and prints no result when JAX finds no TPU or fewer
chips than the cell asks for. The numbers compared to decide `correct`
are printed beside their limits as the last lines on stderr and, under
"compared", last in the result line. --save-cycles keeps the captured
device cycles (for setting limits and running the control); the
benchmark's own runs never pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-cycles", default=None)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # the compile cache lives at one fixed path inside the checkout, so
    # only a cell's first run there compiles; small programs are kept too
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"     # no eviction
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # the TPU runtime logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    from benchmark import harness
    try:
        out = harness.run_cell(bench, args.workload, args.seed,
                               args.seconds, bool(args.trace), T_START,
                               save_dir=args.save_cycles)
    except harness.NoDevice as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    compared = out.pop("compared")
    for name, c in compared.items():
        print(f"compared {name} = {float(c['value']):.6g} "
              f"(limit {float(c['limit']):.6g})", file=sys.stderr)
    out["compared"] = compared
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:
        import traceback
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the embedded server may still hold the
    # interpreter; every one was stopped or is idle by now
    os._exit(code)
