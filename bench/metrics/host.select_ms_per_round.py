"""Host time in cohort selection (``select_cohort``) per engine round (ms),
from the benchmark's span around it."""


def read(ctx):
    s = ctx.counters.get("select_s", 0)
    return s * 1e3 / ctx.rounds if s > 0 and ctx.rounds else None
