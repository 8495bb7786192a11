"""The trace reduction, on a small trace recorded on one TPU v5e
(record_trace.py) and on a CPU-only trace, which must be refused:
busy time, idle gaps named from the trace's own host events, device
ops named `scope/op`."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from benchmark import scopes, tracing  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tpu_trace.xplane.pb")


def test_reduce_recorded_tpu_trace():
    t = tracing.load(DATA)
    assert list(t["planes"]) == ["/device:TPU:0"]
    r = tracing.reduce(t)
    assert 0 < r["busy_s"] < r["window_s"]
    # busy is the union of the op intervals: never more than their sum
    ops = scopes.op_times(DATA)
    assert r["busy_s"] <= sum(s for _n, s in ops) + 1e-12
    assert all(" = " not in n and n.count("/") >= 1 for n, _s in ops)
    assert {n.split("/", 1)[0] for n, _s in ops} == {"unscoped"}
    gaps = [(b - a) / 1e9 for a, b in r["gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    # three programs ran with 20 ms sleeps between them
    assert sum(1 for g in gaps if g > 0.015) >= 2


def test_gap_without_a_phase_is_labelled_so():
    # the recorded trace holds no host event of the program's
    r = tracing.reduce(tracing.load(DATA))
    labels = scopes.label_gaps(DATA, r["gaps"])
    assert {label for label, _s, _beside in labels} == {"no cycle phase"}
    assert [s for _l, s, _b in labels] == \
        [(b - a) / 1e9 for a, b in r["gaps"]]


def test_union_merges_overlaps():
    assert tracing._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == \
        [[0, 3], [5, 9]]


def test_trace_without_a_tpu_plane_is_an_error(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation(tracing.MARKER):
        jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(tracing.TraceError):
        tracing.load(tracing.find_xplane(str(tmp_path)))
