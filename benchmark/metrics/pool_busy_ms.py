"""Device busy time per match cycle of one pool, where each pool has a
chip of its own: the mean over chips of each chip's busy time in the
traced sub-window (the union of its TPU op intervals), divided by the
match cycles begun in it per pool."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    busy = list(tr["busy_by_chip"].values())
    cycles = tr["match_cycles_by_pool"]
    n = sum(cycles.values())
    if not busy or not n or sum(busy) <= 0:
        return None
    return sum(busy) / len(busy) * 1e3 / (n / len(cycles))
