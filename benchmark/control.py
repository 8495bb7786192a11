#!/usr/bin/env python3
"""Readings of the program and of the control on saved device cycles.

    python3 benchmark/control.py <dir> [<dir> ...]

Each directory holds the cycles one run saved with `run.py
--save-cycles`. For every cycle this prints the compared numbers of the
program's own answer and of the control (the reference computed in
bfloat16 in the program's place), both judged by the float64
reference. The limits in limits.json are set from these readings; see
PERF.md. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark import oracle  # noqa: E402

KEYS = ("dru_gap", "fit_gap", "placement_faults", "input_mismatch",
        "head_already_launched", "head", "placed")


def readings(cap: dict, exp: dict) -> dict:
    """The compared numbers of the program's answer and of the
    control's, each judged as a run judges the program."""
    ctl = oracle.with_answer(cap, *oracle.control_answer(cap, exp))
    return {who: {k: float(v) for k, v in oracle.check_cycle(c, exp).items()
                  if k in KEYS}
            for who, c in (("program", cap), ("control", ctl))}


def main(dirs) -> int:
    for d in dirs:
        for path in sorted(glob.glob(os.path.join(d, "cycle*.npz"))):
            print(json.dumps({"cycle": path,
                              **readings(*oracle.load_cycle(path))}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
