"""Bytes of fit examples copied to the device per engine round (MB): the
summed ``bytes`` of the program's ``fl.fit.h2d`` spans."""

from harness import program


def read(ctx):
    copies = program.select(program.attach(ctx) or [], "fl.fit.h2d")
    if not copies or not ctx.rounds:
        return None
    return program.stat_sum(copies, "bytes", ctx.lo, ctx.hi) / 1e6 / ctx.rounds
