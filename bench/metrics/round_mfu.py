"""Model FLOP utilization of the whole round (%): the CNN's FLOPs for the
unique fit rows trained (3 x forward per example) and the examples
evaluated, over the traced window times the chip's bf16 peak."""


def read(ctx):
    trained = ctx.counters.get("fit_row_steps", 0) * ctx.config["batch_size"]
    evaluated = ctx.counters.get("eval_examples", 0)
    if trained <= 0 or ctx.window_s <= 0:
        return None
    flops = ctx.flops.cnn_flops(trained, evaluated)
    return 100.0 * flops / (ctx.window_s * ctx.peak["bf16_flops_per_s"])
