"""Mean wall time of the launch transaction (the store's bulk
`create_instances_bulk`, fsync included) per launching consume, from
the program's cycle profiler records in the window."""


def read(ctx):
    ms = [ph["ms"] for r in ctx["records"] if r["kind"] == "consume"
          and (r.get("attrs") or {}).get("matched", 0) > 0
          for ph in r["phases"] if ph["name"] == "launch_txn"]
    return sum(ms) / len(ms) if ms else None
