"""Device-to-host reads per engine round: the program's ``fl.sync.*``
spans, one per read."""

from harness import program


def read(ctx):
    syncs = program.select(program.attach(ctx) or [], "fl.sync.")
    if not syncs or not ctx.rounds:
        return None
    return program.count(syncs, ctx.lo, ctx.hi) / ctx.rounds
