"""Device idle time while the host prepares fit blocks, per engine round
(ms): idle inside the program's ``fl.fit.batches``, ``fl.fit.h2d`` and
``fl.fit.anchors`` spans."""

from harness import program

PREP = ("fl.fit.batches", "fl.fit.h2d", "fl.fit.anchors")


def read(ctx):
    prep = program.select(program.attach(ctx) or [], *PREP)
    if not prep or not ctx.rounds:
        return None
    return program.idle_in(ctx.trace, prep, ctx.lo, ctx.hi) / 1e6 / ctx.rounds
