"""XLA compilations inside the traced window (``jax.monitoring``)."""


def read(ctx):
    return ctx.counters.get("compiles", 0)
