"""step_mfu: model FLOPs of the traced steps over the traced window, as a
share of the chips' bf16 peak (``flops.train_flops_per_token``; remat's
recomputation is not counted)."""
from chipbench import flops


def read(ctx):
    if not ctx.peaks or ctx.window_s <= 0:
        return None
    work = (flops.train_flops_per_token(ctx.cfg, int(ctx.traffic["seq"]))
            * ctx.tokens_per_step * ctx.steps)
    return 100.0 * work / ctx.window_s / (ctx.chips * ctx.peaks["bf16_flops"])
