"""Device busy time per match cycle: the union of TPU op intervals in
the traced sub-window, divided by the match cycles that started in
it."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr["match_cycles"] or tr["busy_s"] <= 0:
        return None
    return tr["busy_s"] * 1e3 / tr["match_cycles"]
