"""Mean thread CPU time of a launching consume, from the program's
cycle profiler records in the window: `consume_ms` less this is the
time the consume thread waited (on the GIL, a lock, the disk, the
device)."""


def read(ctx):
    ms = [r["cpu_ms"] for r in ctx["records"] if r["kind"] == "consume"
          and (r.get("attrs") or {}).get("matched", 0) > 0]
    return sum(ms) / len(ms) if ms else None
