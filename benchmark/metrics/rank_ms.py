"""Device time per match cycle under the `rank` named scope (the DRU
rank of running and pending, and the fair-queue order), in the traced
sub-window; None for a program without the scope."""
from benchmark import scopes


def read(ctx):
    return scopes.run_scope_ms(ctx, "rank")
