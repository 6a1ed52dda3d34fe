"""Jitted steps: device time per run of the decode-step program.

The decode-step program is the one that ran exactly as many times as the
scheduler counted decode steps in the traced window; its time is the mean
of its device spans (the trace's per-program line).  Moves `tpot_p90_s`.
"""

from chipbench import trace


def read(ctx):
    hit = trace.program_ran(ctx.trace, ctx.counts["decode_steps"])
    return None if hit is None else 1e3 * hit[1]
