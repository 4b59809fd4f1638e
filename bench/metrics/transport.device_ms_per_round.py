"""Device time of the transport plane's round program per engine round (ms)."""

TRANSPORT_PROGRAMS = r"jit__device_round\b"


def read(ctx):
    ns = ctx.lib.time_by_name(ctx.modules, TRANSPORT_PROGRAMS, ctx.lo, ctx.hi)
    return ns / 1e6 / ctx.rounds if ns > 0 and ctx.rounds else None
