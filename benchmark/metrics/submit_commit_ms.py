"""Mean time an ingest worker spent committing one drained batch: its
store transactions, group-commit barrier included (the program's
`ingest_commit_ms` histogram: its exact sum over its count). The
harness passes no window delta of this histogram, so the mean is over
every batch of the run (warm-up, window and drain, at the cell's one
offered rate). None for a program without the histogram."""
from cook_tpu.obs.metrics import registry


def read(ctx):
    h = registry.snapshot().get("ingest_commit_ms") or {}
    return h["sum"] / h["count"] if h.get("count") else None
