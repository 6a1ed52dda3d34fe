"""Pallas TPU megakernel: the fused ROSA analog hot path.

One `pallas_call` per (bm, bn) output tile performs what the composed
`rosa.backends` pipeline lowers as separate device ops with HBM
round-trips between them:

    weight quantize -> mrr_transfer realization (noise + static variation)
                    -> per-plane OSA shift-and-add -> f32 accumulate
                    -> dequantize

The fusion is paper-faithful in the same sense the hardware is: on the
photonic chip the voltage->weight transfer, the splitter/ODL shift ladder
and the photodetector accumulate are ONE analog pipeline — intermediate
"tensors" never exist.  Here they never leave VMEM.

The activation side arrives already conditioned and requantized by the
wrapper (ops.py), through the very functions the composed chain uses: an
(M, K) operand is cheap to condition once, whereas conditioning it inside
every output tile would repeat it N/bn times, and any float-order
difference between Mosaic and XLA would move a requantization code across
its rounding boundary.

Operand layout (all f32, padded to block multiples by ops.py):

    xa      (M, K)        activation operand: MIXED, the 8-bit requant
                          codes of the conditioned activations (integers
                          in [-qmax, qmax]); ANALOG, the conditioned
                          activations over their full-scale
    w       (K, N)        weights
    gains   (T,)          OSA slot-gain ladder (ideal: 2^(radix_bits*t))
    s2      (M, 1)        per-row requantization full-scale (a per-tensor
                          scale is broadcast into the column by the wrapper)
    gg      (3,)          [gate, mgate, sw] — the traced analog/digital
                          blend gate, the traced WS/IS mapping selector,
                          and the per-tensor weight full-scale
    w_off   3 x (K, N)    folded noise+variation offsets for the weights
                          (v_off = sigma_dac*eps + dv, t_off = sigma_th*eps
                          + ddt, l_off = dlam) — present iff realize_w

Gates ride as OPERANDS, not static params: sweeping `gate`/`mgate` (the
PR 7 gated evaluators) revisits the same compiled kernel, no retrace.
Static specialization covers only trace-stable structure: mode, whether
the weights realize, and whether each gate exists at all.

Grid is (M/bm, N/bn, K/bk) with K innermost sequential; the f32
accumulator lives in VMEM scratch and the output tile is written once at
the last K step (the photodetector's one-conversion-per-output).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core import mrr


def _realize(wn, v_off, t_off, l_off, p: mrr.MRRParams,
             t_hi: float, t_lo: float):
    """VMEM-resident analog realization: normalized target -> programming
    voltage (closed-form inverse) -> noisy forward chain -> realized weight.

    Offset form of core.mrr.realize_weights: the per-shot Gaussian draws
    and the chip's StaticVariation arrive pre-folded into three additive
    offsets at exactly the insertion points of mrr.weight_of_voltage.
    """
    # ---- inverse: target weight -> programming voltage (Eqs. 3-8 inverted)
    wq = jnp.clip(wn, p.q_min, p.q_max)
    td = t_lo + (wq - p.q_min) / p.q_rng * (t_hi - t_lo)
    tdrop = 0.5 * (td + 1.0)
    det = p.gamma * jnp.sqrt(jnp.maximum(1.0 / tdrop - 1.0, 0.0))
    # The detunings that matter are < 1 nm, next to ~1538 nm resonance
    # constants whose float32 grid is 1.2e-4 nm.  XLA's algebraic
    # simplifier folds (c1 + x) - c2 into x + (c1 - c2), so the composed
    # chain (mrr.realize_weights) never rounds c1 + x; Mosaic keeps the
    # written order.  Spelling the folded form out keeps the compiled
    # kernel on the chain's numerics (the difference is exact in f32).
    ref_minus_0 = float(np.float32(p.lambda_ref) - np.float32(p.lambda_0))
    dl = det + ref_minus_0
    u = dl / p.lambda_0
    dt = p.n_eff * u / (p.beta * (1.0 - u))
    p_mw = dt / p.r_thermal
    v2 = p_mw / (p.kappa * 1e3) * p.r_heater
    v = jnp.clip(jnp.sqrt(jnp.maximum(v2, 0.0)), p.v_min, p.v_max)
    # ---- forward with folded noise/variation offsets
    v = v + v_off
    dtn = (p.kappa * (v * v / p.r_heater) * 1e3) * p.r_thermal + t_off
    bdt = p.beta * dtn
    # small detuning terms accumulate BEFORE the resonance constants (same
    # f32-rounding discipline as mrr.weight_of_voltage)
    detu = (p.lambda_0 * bdt / (p.n_eff + bdt) + l_off) - ref_minus_0
    g2 = p.gamma * p.gamma
    td2 = 2.0 * g2 / (detu * detu + g2) - 1.0
    return p.q_min + p.q_rng * (td2 - t_lo) / (t_hi - t_lo)


def _kernel(*refs, analog: bool, n_planes: int, radix_bits: int, qmax: int,
            realize_w: bool, use_gate: bool, use_mgate: bool, k_steps: int,
            k_real: int, bk: int, p: mrr.MRRParams, t_hi: float,
            t_lo: float):
    """Grid = (M/bm, N/bn, K/bk); K innermost (sequential accumulation)."""
    it = iter(refs)
    x_ref, w_ref, g_ref, s2_ref, gg_ref = (next(it) for _ in range(5))
    w_off = tuple(next(it) for _ in range(3)) if realize_w else None
    o_ref, acc_ref = next(it), next(it)

    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xa = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    s2 = s2_ref[...]                                        # (bm, 1)
    gg = gg_ref[...]
    gate, mgate, sw = gg[0], gg[1], gg[2]
    qf = jnp.float32(qmax)

    # ---- weight side: one normalized grid serves the digital path AND the
    # analog chain input (fake_quant(w/sw) lands on the same codes)
    wn = jnp.clip(jnp.round(w / sw * qf), -qf, qf) * (1.0 / qf)
    if realize_w:
        w_an = _realize(wn, *(r[...] for r in w_off), p=p, t_hi=t_hi,
                        t_lo=t_lo)
        w_ws = wn + gate * (w_an - wn) if use_gate else w_an
    else:
        w_ws = wn
    w_eff = (1.0 - mgate) * w_ws + mgate * wn if use_mgate else w_ws
    if realize_w and k_real % bk:
        # the composed path realizes BEFORE zero-padding; in-tile, the MRR
        # chain maps a padded 0 target to a nonzero realized weight, so
        # padded K lanes are masked out of the contraction explicitly
        k_ids = k_idx * bk + jax.lax.broadcasted_iota(
            jnp.int32, w_eff.shape, 0)
        w_eff = jnp.where(k_ids < k_real, w_eff, 0.0)

    if analog:
        # single-shot analog readout: no digit planes, direct MXU contract
        # of the normalized operands; scales fold back at the flush
        acc_ref[...] += jnp.dot(xa, w_eff,
                                preferred_element_type=jnp.float32)
    else:
        # the requant codes (the DAC feeding the EO modulators): hoist the
        # OSA slot recombination before ONE MXU pass — same algebra as
        # kernels/osa_matmul's fused mode
        sign = jnp.sign(xa)
        mag = jnp.abs(xa).astype(jnp.int32)
        mask = (1 << radix_bits) - 1
        g = g_ref[...]
        x_rec = jnp.zeros_like(xa)
        for t in range(n_planes):
            d = (mag >> (radix_bits * t)) & mask
            x_rec = x_rec + g[t] * (sign * d.astype(xa.dtype))
        acc_ref[...] += jnp.dot(x_rec, w_eff,
                                preferred_element_type=jnp.float32)

    @pl.when(k_idx == k_steps - 1)
    def _flush():
        # electronic post-ADC rescale: per-row requant scale x weight
        # full-scale (MIXED folds the extra 1/qmax of the integer planes)
        if analog:
            o_ref[...] = acc_ref[...] * (s2 * sw)
        else:
            o_ref[...] = acc_ref[...] * (s2 * (sw / qf))


@functools.partial(jax.jit, static_argnames=(
    "analog", "n_planes", "radix_bits", "qmax", "realize_w", "use_gate",
    "use_mgate", "k_real", "p", "bm", "bn", "bk", "interpret"))
def rosa_fused_pallas(xa: jax.Array, w: jax.Array, gains: jax.Array,
                      s2: jax.Array, gg: jax.Array,
                      w_off: "tuple[jax.Array, ...] | None" = None,
                      *, analog: bool = False, n_planes: int = 7,
                      radix_bits: int = 1, qmax: int = 127,
                      realize_w: bool = True,
                      use_gate: bool = False, use_mgate: bool = False,
                      k_real: int = 0,
                      p: mrr.MRRParams = mrr.DEFAULT_PARAMS,
                      bm: int = 128, bn: int = 128, bk: int = 128,
                      interpret: bool = False) -> jax.Array:
    """Fused weight-realize+OSA+accumulate+dequantize GEMM.

    M, K, N must be multiples of (bm, bk, bn) — ops.py pads.  `w_off` must
    be present exactly when `realize_w`.  `k_real` is the unpadded
    reduction length (padded K lanes must not realize — see the masking
    comment in `_kernel`); 0 means K is exact.
    """
    m, k = xa.shape
    k2, n = w.shape
    assert k == k2, (xa.shape, w.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    assert (w_off is not None) == realize_w
    k_steps = k // bk

    t_hi, t_lo = mrr.transmission_endpoints_py(p)
    kernel = functools.partial(
        _kernel, analog=analog, n_planes=n_planes, radix_bits=radix_bits,
        qmax=qmax, realize_w=realize_w, use_gate=use_gate,
        use_mgate=use_mgate, k_steps=k_steps, k_real=k_real, bk=bk, p=p,
        t_hi=t_hi, t_lo=t_lo)

    w_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        w_spec,
        pl.BlockSpec((gains.shape[0],), lambda i, j, kk: (0,)),
        pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
        pl.BlockSpec((3,), lambda i, j, kk: (0,)),
    ]
    operands = [xa, w, gains, s2, gg]
    if realize_w:
        in_specs += [w_spec] * 3
        operands += list(w_off)

    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # XLA may read `w` straight through its producer, such as one
            # layer's slice of a stacked weight inside the layer scan,
            # instead of copying it out first
            allow_input_fusion=[i == 1 for i in range(len(operands))],
        ),
        interpret=interpret,
    )(*operands)
