"""Mean time a launching consume waited between the match loop's
hand-off and the start of its pool's consume worker on it (the consume
record's `queued_ms` attr), from the program's cycle profiler records
in the window; the worst pool's mean where there are several. None for
a program that records no such attr."""


def read(ctx):
    by_pool: dict = {}
    for r in ctx["records"]:
        a = r.get("attrs") or {}
        if r["kind"] == "consume" and a.get("matched", 0) > 0 \
                and "queued_ms" in a:
            by_pool.setdefault(r["pool"], []).append(a["queued_ms"])
    return max((sum(v) / len(v) for v in by_pool.values()), default=None)
