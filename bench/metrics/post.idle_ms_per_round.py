"""Device idle time in the post phase per engine round (ms): idle inside
the program's ``fl.gather_rows``, ``fl.divergence``, ``fl.aggregate``,
``fl.finish_round`` and ``fl.evaluate`` spans."""

from harness import program

POST = ("fl.gather_rows", "fl.divergence", "fl.aggregate", "fl.finish_round", "fl.evaluate")


def read(ctx):
    post = program.select(program.attach(ctx) or [], *POST)
    if not post or not ctx.rounds:
        return None
    return program.idle_in(ctx.trace, post, ctx.lo, ctx.hi) / 1e6 / ctx.rounds
