"""Device: share of the traced window in which no op ran on the chip.

1 - (union of device-op intervals / traced window).  Moves `tokens_per_s`.
"""

from chipbench import trace


def read(ctx):
    return 100.0 * trace.idle_share(ctx.trace)
