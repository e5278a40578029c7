"""mfu: model FLOPs of the traced window's tokens over its length, as a
share of the chips' bf16 peak (percent).  Recompute and the pipeline's
redundant LM heads are not model FLOPs (``lib/flops.py``)."""


def read(ctx):
    window_s = (ctx.hi - ctx.lo) / 1e9
    return 100.0 * ctx.tokens * ctx.flops_per_token / (
        window_s * ctx.chips * ctx.peak["bf16_flops_per_s"])
