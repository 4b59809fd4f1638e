"""Host time building drawn clients' shards per engine round (ms): the
union of the program's ``fl.shard_build`` spans."""

from harness import program


def read(ctx):
    builds = program.select(program.attach(ctx) or [], "fl.shard_build")
    if not builds or not ctx.rounds:
        return None
    return program.span_ns(builds, ctx.lo, ctx.hi) / 1e6 / ctx.rounds
