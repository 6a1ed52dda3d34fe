"""Device: the whole step's share of the chip's peak.

Model FLOPs of every prompt and generated token served in the traced
window (`work.model_flops`: 2 x matmul weights per token fed through the
layers, attention over each token's context, the LM head per generated
token) over traced window x peak FLOP/s.  Moves `tokens_per_s`, beside
the kernel's roofline share: a kernel taken off the path leaves its
roofline silent, and this share still bounds a claim.
"""

from chipbench import trace, work


def read(ctx):
    flops = sum(work.model_flops(ctx.cfg, p, t) for p, t in ctx.requests)
    if flops <= 0:
        return None
    return 100.0 * flops / (trace.window_s(ctx.trace)
                            * ctx.peak["flops_per_s"])
