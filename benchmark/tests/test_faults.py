"""The whole run, at a tiny size on the CPU, with the timed path broken
underneath: `correct` must come out false for each fault a one-chip
cell can have, and true with nothing broken. (The exchange between
chips does not exist in a one-chip cell.) Also: a measurement run that
finds no TPU exits non-zero and prints no result."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _env():
    return dict(os.environ, JAX_PLATFORMS="cpu")


def _run(fault: str, seed: int) -> dict:
    p = subprocess.run([sys.executable, os.path.join(HERE, "faults.py"),
                        fault, str(seed)], capture_output=True, text=True,
                       env=_env(), timeout=600, cwd=ROOT)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct():
    out = _run("none", 7)
    assert out["correct"] is True, out["compared"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch",
                                   "altered_answer"])
def test_fault_is_caught(fault):
    out = _run(fault, 7)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["compared"].values())


def test_no_tpu_no_result():
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "default-steady", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=_env(), timeout=600, cwd=ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
