"""Host time spent waiting on device-to-host reads per engine round (ms):
the union of the program's ``fl.sync.*`` spans."""

from harness import program


def read(ctx):
    syncs = program.select(program.attach(ctx) or [], "fl.sync.")
    if not syncs or not ctx.rounds:
        return None
    return program.span_ns(syncs, ctx.lo, ctx.hi) / 1e6 / ctx.rounds
