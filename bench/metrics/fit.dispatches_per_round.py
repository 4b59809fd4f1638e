"""Fit-plane dispatches per engine round: the growth of the plane
runner's ``dispatch_widths`` over the traced window."""


def read(ctx):
    n = ctx.counters.get("fit_dispatches", 0)
    return n / ctx.rounds if n > 0 and ctx.rounds else None
