"""Whole step's share of the chips' bf16 peak: the model's forward and
backward operations per step (the family reference's count; neither
recomputation nor the optimizer counts) times the traced window's steps
per second, over chips x peak."""


def read(ctx):
    flop = ctx.flops_per_token * ctx.tokens_per_step
    per_s = ctx.steps / ctx.window_s()
    return 100.0 * flop * per_s / (ctx.n_chips * ctx.peak["bf16_flops"])
