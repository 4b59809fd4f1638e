"""Device time of the fit plane's programs per engine round (ms)."""

FIT_PROGRAMS = r"jit_(fit_fused|run_chunk|init_state|finalize)\b"


def read(ctx):
    ns = ctx.lib.time_by_name(ctx.modules, FIT_PROGRAMS, ctx.lo, ctx.hi)
    return ns / 1e6 / ctx.rounds if ns > 0 and ctx.rounds else None
