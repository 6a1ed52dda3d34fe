"""Kernels: the `rosa_fused` kernel's share of the device's busy time.

Summed device time of the kernel's ops over the union of all device-op
intervals in the traced window.  Moves `tokens_per_s`.
"""

from chipbench import trace

KERNEL = r"^%rosa_fused_pallas[.0-9]* = "


def read(ctx):
    n, t = trace.ops_matching(ctx.trace, KERNEL)
    busy = trace.busy_s(ctx.trace)
    if n == 0 or busy <= 0:
        return None
    return 100.0 * t / busy
