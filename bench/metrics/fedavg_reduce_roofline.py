"""The FedAvg reduce kernel's share of its roofline (%): bytes from the
stacked shapes it was called on, over peak HBM bandwidth, against the
device time of its programs."""

REDUCE_PROGRAMS = r"jit_fedavg_reduce\b"


def read(ctx):
    ns = ctx.lib.time_by_name(ctx.modules, REDUCE_PROGRAMS, ctx.lo, ctx.hi)
    return ctx.flops.roofline_pct(ctx.counters.get("fedavg_reduce_bytes", 0), 0, ns / 1e9, ctx.peak)
