"""Serving loop: share of decode-batch slots that produced a token.

`ServeReport.occupancy`, pooled over the window's slices: tokens produced
by decode steps over decode steps x slots (a request's first token comes
from its prefill and is not counted).  Moves `tokens_per_s`.
"""


def read(ctx):
    c = ctx.counts
    if c["decode_steps"] <= 0:
        return None
    return 100.0 * c["decoded_tokens"] / (c["decode_steps"] * ctx.n_slots)
