"""step_mfu: the whole training step's share of the chips' peak.

Required forward + backward operations per token (``flops.py``: 3 x the
terms of the family's ``forward_flops``, from the published equations)
times the tokens trained per second in the traced window, over the chips
times their bf16 peak. Training runs in float32 at the
TPU's default matmul precision, whose products go through the bf16 MXU
passes, so the bf16 peak is the ceiling."""


def read(ctx):
    if ctx.window_s <= 0 or ctx.tokens <= 0:
        return None
    achieved = ctx.flops_per_token * ctx.tokens / ctx.window_s
    return 100.0 * achieved / (ctx.chips * ctx.peak["bf16_flops_per_s"])
