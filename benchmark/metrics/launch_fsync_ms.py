"""Mean time a launching consume's launch transaction was blocked in
the store's group-commit barrier (the `fsync_wait` child span of its
`launch_txn` phase, leader or follower of the round), from the
program's cycle profiler records in the window. None for a program
that records no such span."""


def read(ctx):
    ms = [sum(c["ms"] for c in r.get("children", ())
              if c["name"] == "fsync_wait" and c["phase"] == "launch_txn")
          for r in ctx["records"] if r["kind"] == "consume"
          and (r.get("attrs") or {}).get("matched", 0) > 0
          and any(c["name"] == "fsync_wait" for c in r.get("children", ()))]
    return sum(ms) / len(ms) if ms else None
