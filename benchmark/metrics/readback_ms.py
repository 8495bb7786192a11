"""Mean wall time of the `readback` phase (the matched prefix pulled
from the device) per launching consume, from the program's cycle
profiler records in the window."""


def read(ctx):
    ms = [ph["ms"] for r in ctx["records"] if r["kind"] == "consume"
          and (r.get("attrs") or {}).get("matched", 0) > 0
          for ph in r["phases"] if ph["name"] == "readback"]
    return sum(ms) / len(ms) if ms else None
