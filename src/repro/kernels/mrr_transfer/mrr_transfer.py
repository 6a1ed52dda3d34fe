"""Pallas TPU kernel: noisy MRR voltage->weight realization (Eqs. 3-8).

Elementwise physical chain, fused into one VPU pass over VMEM blocks:

    w_target --inverse--> V --(+sigma_dac*eps)--> dT --(+sigma_th*eps)-->
    d_lambda --> Lorentzian T_drop --> T_diff --> realized w

Noise draws arrive as operands (generated with jax.random outside) so the
kernel is deterministic and bit-comparable with ref.py on CPU.  On real TPU
hardware the draws can instead be generated in-kernel with
pltpu.prng_seed/prng_random_bits to save the two HBM streams; that variant
is gated behind `use_tpu_prng` (not available in CPU interpret mode, which
is why correctness validation uses the operand path).

The weight tensor is processed in (block_rows, 128)-aligned VMEM tiles; the
chain is ~20 transcendental-free VPU ops per element (sqrt, divisions), so
the kernel is memory-bound and the tiling exists purely to stream HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core import mrr


def _chain(wt, e_dac, e_th, sigma_dac, sigma_th, p: mrr.MRRParams,
           t_hi: float, t_lo: float):
    """The full forward+inverse chain on VMEM-resident values."""
    # ---- inverse: target weight -> programming voltage ----
    wq = jnp.clip(wt, p.q_min, p.q_max)
    td = t_lo + (wq - p.q_min) / p.q_rng * (t_hi - t_lo)
    tdrop = 0.5 * (td + 1.0)
    det = p.gamma * jnp.sqrt(jnp.maximum(1.0 / tdrop - 1.0, 0.0))
    lam = p.lambda_ref + det
    dl = lam - p.lambda_0
    u = dl / p.lambda_0
    dt = p.n_eff * u / (p.beta * (1.0 - u))
    p_mw = dt / p.r_thermal
    v2 = p_mw / (p.kappa * 1e3) * p.r_heater
    v = jnp.sqrt(jnp.maximum(v2, 0.0))
    v = jnp.clip(v, p.v_min, p.v_max)
    # ---- forward with noise: V' -> dT' -> d_lambda -> T_diff -> w ----
    v = v + sigma_dac * e_dac
    dtn = (p.kappa * (v * v / p.r_heater) * 1e3) * p.r_thermal + sigma_th * e_th
    bdt = p.beta * dtn
    lam2 = p.lambda_0 + p.lambda_0 * bdt / (p.n_eff + bdt)
    detu = lam2 - p.lambda_ref
    g2 = p.gamma * p.gamma
    td2 = 2.0 * g2 / (detu * detu + g2) - 1.0
    return p.q_min + p.q_rng * (td2 - t_lo) / (t_hi - t_lo)


def _kernel(w_ref, edac_ref, eth_ref, o_ref, *, sigma_dac, sigma_th, p,
            t_hi, t_lo):
    o_ref[...] = _chain(w_ref[...], edac_ref[...], eth_ref[...],
                        sigma_dac, sigma_th, p, t_hi, t_lo)


@functools.partial(jax.jit, static_argnames=("sigma_dac", "sigma_th", "p",
                                             "block_rows", "interpret"))
def mrr_transfer_pallas(w_target: jax.Array, eps_dac: jax.Array,
                        eps_th: jax.Array, *, sigma_dac: float = 0.02,
                        sigma_th: float = 0.04,
                        p: mrr.MRRParams = mrr.DEFAULT_PARAMS,
                        block_rows: int = 8,
                        interpret: bool = False) -> jax.Array:
    # block_rows default MUST stay equal to ops.preflight's — the analysis
    # sweep prices the launched configuration, and the wrapper's noise-draw
    # padding (rows_pad) depends on it.  tests/test_kernels.py pins all
    # three defaults (kernel == wrapper == preflight) together.
    """2-D entry: (R, 128*k) tensors, R % block_rows == 0 (ops.py pads)."""
    rows, cols = w_target.shape
    assert rows % block_rows == 0, (rows, block_rows)
    t_hi, t_lo = mrr.transmission_endpoints_py(p)
    kernel = functools.partial(_kernel, sigma_dac=sigma_dac,
                               sigma_th=sigma_th, p=p, t_hi=t_hi, t_lo=t_lo)
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, cols), w_target.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(w_target, eps_dac, eps_th)
