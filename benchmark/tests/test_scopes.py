"""Per-scope device time and host-event gap labels (scopes.py), on the
small traces recorded on one TPU v5e (record_trace.py,
record_scoped_trace.py), and the new readers' answers on a program
that has nothing for them to read."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import scopes, tracing  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
PLAIN = os.path.join(DATA, "tpu_trace.xplane.pb")
SCOPED = os.path.join(DATA, "scoped_trace.xplane.pb")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_xplane_reader_gives_each_ops_tf_op():
    (ops,) = scopes.device_ops(PLAIN).values()
    by_name = {name: tf_op for _a, _b, tf_op, name in ops}
    assert by_name["%sort.6"] == "jit(<lambda>)/jit(sort)/sort"
    assert by_name["%iota"] == ""            # an op without metadata
    # the intervals ProfileData reads (which truncates to whole ns)
    pd = tracing.load(PLAIN)["planes"]["/device:TPU:0"]
    assert len(ops) == len(pd)
    for (a, b, _t, _n), (pa, pb, _pn) in zip(ops, pd):
        assert abs(a - pa) < 1 and abs(b - pb) < 2


def test_scope_of():
    assert scopes.scope_of("jit(_device_cycle)/jit(rank_and_match)/rank/"
                           "sort") == "rank"
    assert scopes.scope_of("jit(_device_cycle)/head_scan/while/body/"
                           "rank") == "head_scan"     # the first wins
    assert scopes.scope_of("jit(_scatter_pend)/scatter") == \
        "jit(_scatter_pend)"
    assert scopes.scope_of("jit(<lambda>)/jit(sort)/sort") == "unscoped"
    assert scopes.scope_of("") == "unscoped"


def test_a_loop_without_metadata_takes_the_scope_it_encloses():
    ops = [(0, 100, "", "%while.27"),
           (10, 20, "jit(c)/head_scan/while/body/add", "%fusion.1"),
           (30, 40, "jit(c)/head_scan/while/body/mul", "%fusion.2"),
           (200, 300, "", "%while.3"),                 # two scopes inside
           (210, 220, "jit(c)/rank/sort", "%sort.1"),
           (230, 240, "jit(c)/filter/add", "%fusion.3"),
           (400, 410, "", "%copy.1")]                  # encloses nothing
    assert [s for _a, _b, s in scopes.attributed(ops)] == [
        "head_scan", "head_scan", "head_scan", "unscoped", "rank",
        "filter", "unscoped"]


def test_plain_trace_is_all_unscoped():
    t = scopes.scope_times(PLAIN)
    assert set(t) == {"busy", "unscoped"}
    assert t["unscoped"] == t["busy"] > 0


def test_device_ops_are_named_scope_slash_op():
    ops = scopes.op_times(SCOPED)
    secs = [s for _n, s in ops]
    assert secs == sorted(secs, reverse=True)
    by_scope: dict = {}
    for name, s in ops:
        scope, op = name.split("/", 1)
        assert op and " = " not in op
        by_scope[scope] = by_scope.get(scope, 0.0) + s
    # an op's own time is at least its share of its scope's union
    t = scopes.scope_times(SCOPED)
    for scope in ("rank", "head_scan"):
        assert by_scope[scope] >= t[scope] - 1e-12
    assert {"rank", "head_scan", "unscoped"} <= set(by_scope)


def test_scope_times_match_the_tf_op_grouping():
    t = scopes.scope_times(SCOPED)
    m0, m1 = tracing.load(SCOPED)["marker"]
    (ops,) = scopes.device_ops(SCOPED).values()
    for scope in ("rank", "head_scan"):
        mine = [(a, b) for a, b, tf_op, _n in ops
                if scope in tf_op.split("/")]
        assert mine
        want = sum(b - a for a, b in scopes._clipped_union(mine, m0, m1))
        assert abs(t[scope] - want / 1e9) < 1e-12
    assert t["rank"] > 0 and t["head_scan"] > 0
    assert t["rank"] + t["head_scan"] + t["unscoped"] >= t["busy"] - 1e-12
    assert t["unscoped"] < t["busy"]


def test_gap_labels_come_from_the_host_events_in_the_trace():
    gaps = scopes.idle_gaps(SCOPED, 6)
    labels = [g[0] for g in gaps]
    # three 20 ms sleeps inside the record's `fold` segment, then the
    # 10 ms sleeps outside any record
    assert labels[:3] == ["consume.fold"] * 3
    assert "no cycle phase" in labels[3:]
    assert all(g[1] > 0.015 for g in gaps[:3])
    # the REST span held across the first cycle sits beside its gap
    assert any(g[2].get("rest.submit") for g in gaps)


def _reader(name):
    import importlib.util
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_span_readers_on_records_with_and_without_children():
    old = {"kind": "consume", "cpu_ms": 40.0, "wall_ms": 100.0,
           "attrs": {"matched": 5},
           "phases": [{"name": "readback", "ms": 2.0},
                      {"name": "launch_txn", "ms": 9.0}]}
    new = dict(old, children=[
        {"name": "lock_wait", "phase": "launch_txn", "ms": 1.0},
        {"name": "fsync_wait", "phase": "launch_txn", "ms": 6.0}])
    idle = dict(old, attrs={"matched": 0}, cpu_ms=1.0)
    assert _reader("readback_ms")({"records": [old, idle]}) == 2.0
    assert _reader("consume_cpu_ms")({"records": [old, idle]}) == 40.0
    fsync = _reader("launch_fsync_ms")
    assert fsync({"records": [old, idle]}) is None
    assert fsync({"records": [new, idle]}) == 6.0


def test_trace_readers_find_nothing_without_a_trace():
    for name in ("rank_ms", "head_scan_ms"):
        assert _reader(name)({"trace": None}) is None
