"""Model FLOP utilisation of the traced window: tokens per second times
the model's forward and backward FLOPs per token (6 N_active plus causal
attention, from ``harness.counts``), over the chips' bf16 peak."""


def read(ctx):
    if ctx.kind != "fl" or ctx.peaks is None or not ctx.window_s:
        return None
    c = ctx.counts
    flops = ctx.tokens * c.train_flops_per_token(ctx.config,
                                                 ctx.traffic["seq_len"])
    return 100.0 * flops / ctx.window_s / (
        ctx.chips * ctx.peaks["bf16_flops_per_s"])
