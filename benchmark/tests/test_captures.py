"""Device cycles captured on the chip at the cells' own sizes (`run.py
--save-cycles`), judged by the harness's own `correctness`: the
program's answer must come out correct, and the control (the reference
in bfloat16 in the program's place) and answers broken where they are
produced must come out not correct."""
import glob
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from benchmark import backend, harness, oracle  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CYCLES = sorted(glob.glob(os.path.join(HERE, "data", "captures", "*",
                                       "cycle*.npz")))


def _program(cap, exp):
    return cap


def _control(cap, exp):
    return oracle.with_answer(cap, *oracle.control_answer(cap, exp))


def _half_the_batch(cap, exp):
    # every second placed job of the head dropped from the answer
    inp = oracle.rebuild_inputs(cap)
    hosts = list(inp["dev_hosts"])
    placed = [k for k, h in enumerate(hosts) if h >= 0]
    for k in placed[1::2]:
        hosts[k] = -1
    return oracle.with_answer(cap, inp["dev_rows"], hosts)


def _altered_answer(cap, exp):
    # the first placement moved to the next configured host
    inp = oracle.rebuild_inputs(cap)
    hosts = list(inp["dev_hosts"])
    k = next(k for k, h in enumerate(hosts) if h >= 0)
    known = np.flatnonzero(exp["host"]["known"])
    hosts[k] = int(known[(np.searchsorted(known, hosts[k]) + 1)
                         % len(known)])
    return oracle.with_answer(cap, inp["dev_rows"], hosts)


ANSWERS = {"program": (_program, True), "control": (_control, False),
           "half_the_batch": (_half_the_batch, False),
           "altered_answer": (_altered_answer, False)}


@pytest.mark.parametrize("answer", sorted(ANSWERS))
@pytest.mark.parametrize("path", CYCLES,
                         ids=[os.path.relpath(p, HERE) for p in CYCLES])
def test_answer_through_correctness(path, answer, tmp_path):
    cap, exp = oracle.load_cycle(path)
    change, correct = ANSWERS[answer]
    log = tmp_path / "events.log"
    log.write_text("")
    compared = harness.correctness([(change(cap, exp), exp)], 1,
                                   backend.Ledger({}, set(), {}), str(log),
                                   [])
    assert harness.verdict(compared) is correct, compared
