"""Device time per match cycle under the `head_scan` named scope (the
bin-packing match of the considerable head), in the traced
sub-window; None for a program without the scope."""
from benchmark import scopes


def read(ctx):
    return scopes.run_scope_ms(ctx, "head_scan")
