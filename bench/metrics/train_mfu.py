"""train_mfu (%, model step): the forward and backward operations the
window's tokens require (flops.train_flops_per_token) per second, over the
chips' bf16 peak.  Recomputation does not count."""
from flops import train_flops_per_token


def read(ctx):
    if not ctx.steps or not ctx.window_s:
        return None
    tokens_per_s = ctx.steps * ctx.tokens_per_step / ctx.window_s
    flops = train_flops_per_token(ctx.cell.config, ctx.cell.traffic["seq_len"])
    return 100.0 * flops * tokens_per_s / (ctx.chips * ctx.peaks["flops_bf16"])
