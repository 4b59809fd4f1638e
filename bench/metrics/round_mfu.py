"""Model FLOP utilization of the whole round (%): the FLOPs of the
examples the unique fit rows trained and of the examples evaluated, each
at its count per example from the configuration's reference
(``flops_per_example``), over the traced window times the chip's bf16
peak."""


def read(ctx):
    trained = ctx.counters.get("fit_row_steps", 0) * ctx.config["batch_size"]
    evaluated = ctx.counters.get("eval_examples", 0)
    if trained <= 0 or ctx.window_s <= 0:
        return None
    per = ctx.reference.flops_per_example(ctx.config)
    flops = trained * per["train"] + evaluated * per["eval"]
    return 100.0 * flops / (ctx.window_s * ctx.peak["bf16_flops_per_s"])
