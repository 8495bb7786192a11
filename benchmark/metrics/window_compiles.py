"""Backend compiles inside the measured window (JAX monitoring
events; a persistent-cache load counts). It should be 0."""


def read(ctx):
    return float(ctx["compiles"])
