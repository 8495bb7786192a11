"""Mean time in the REST submit handler, entry to response built (the
program's `rest_submit_ms` histogram: its exact sum over its count).
The harness passes no window delta of this histogram, so the mean is
over every submit of the run (warm-up, window and drain, at the cell's
one offered rate). None for a program without the histogram."""
from cook_tpu.obs.metrics import registry


def read(ctx):
    h = registry.snapshot().get("rest_submit_ms") or {}
    return h["sum"] / h["count"] if h.get("count") else None
