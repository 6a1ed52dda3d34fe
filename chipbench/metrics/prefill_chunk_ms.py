"""Jitted steps: device time per run of the prefill-chunk program.

The chunk program is the one that ran exactly as many times as the
scheduler counted prefill chunks in the traced window.  Moves
`ttft_p90_s`.
"""

from chipbench import trace


def read(ctx):
    hit = trace.program_ran(ctx.trace, ctx.counts["prefill_chunks"])
    return None if hit is None else 1e3 * hit[1]
