"""Time a submission waited in the REST ingest queue before its batch
was committed (the program's `ingest_wait_ms` histogram, counted over
the window only; log2 buckets, so the 95th percentile is interpolated
within one bucket)."""


def read(ctx):
    buckets = ctx["ingest_wait"]
    total = sum(c for _ub, c in buckets)
    if total == 0:
        return None
    target, cum, lo = 0.95 * total, 0, 0.0
    for ub, c in buckets:
        if c and cum + c >= target:
            if ub == float("inf"):
                return lo
            return lo + (target - cum) / c * (ub - lo)
        cum += c
        lo = ub
    return lo
