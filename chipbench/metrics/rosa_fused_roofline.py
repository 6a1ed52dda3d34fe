"""Kernels: the `rosa_fused` kernel's share of its roofline.

Least time over the kernel's summed device time.  The least time is the
algorithm's (`work.kernel_work`): every optical GEMM of a layer, run once
per layer per decode step at M = n_slots and once per layer per prefill
chunk at M = prefill_chunk, each bounded by the larger of FLOPs / peak and
bytes / bandwidth.  At these shapes every GEMM is bound by bytes (its
float32 weight matrix), so this is a share of the memory roof.  Moves
`tokens_per_s`.

Kernel ops are matched by the name the trace gives the Pallas call: the
op's own HLO name, `%rosa_fused_pallas.<n>`, at the start of its text
(a consumer's text names it too, among its operands).
"""

from chipbench import trace, work

KERNEL = r"^%rosa_fused_pallas[.0-9]* = "


def read(ctx):
    n, t = trace.ops_matching(ctx.trace, KERNEL)
    if n == 0 or t <= 0:
        return None
    c = ctx.counts
    layers = ctx.cfg["n_layers"]
    calls = {ctx.n_slots: c["decode_steps"] * layers}
    calls[ctx.prefill_chunk] = (calls.get(ctx.prefill_chunk, 0)
                                + c["prefill_chunks"] * layers)
    _, _, least, _ = work.kernel_work(ctx.gemms, calls, ctx.peak)
    return 100.0 * least / t
