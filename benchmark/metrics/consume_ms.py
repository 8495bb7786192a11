"""Mean wall time of a launching consume (readback, launch loop,
launch transaction, backend hand-off) from the program's cycle profiler
records in the window."""


def read(ctx):
    ms = [r["wall_ms"] for r in ctx["records"] if r["kind"] == "consume"
          and (r.get("attrs") or {}).get("matched", 0) > 0]
    return sum(ms) / len(ms) if ms else None
