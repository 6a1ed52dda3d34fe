"""Jitted public wrapper for the MRR transfer kernel.

Accepts arbitrary-shape weight tensors; flattens to 2-D, pads to block
alignment, draws the noise operands from a PRNG key, dispatches to the
Pallas kernel (interpreted on the CPU, the test path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import mrr
from repro.kernels import on_tpu
from repro.kernels.mrr_transfer.mrr_transfer import mrr_transfer_pallas

_LANE = 128


def preflight(n_elements: int, *, block_rows: int = 8) -> dict:
    """Static tileability/VMEM report for realizing `n_elements` weights.

    Mirrors `mrr_transfer`'s layout: flatten, pad to a (rows, 128) sheet
    with rows a `block_rows` multiple, stream (block_rows, 128) blocks of
    the target plus two noise operands through the VPU (all three
    double-buffered, elementwise chain — no scratch)."""
    issues: list[str] = []
    if n_elements <= 0 or block_rows <= 0:
        issues.append(f"non-positive size n_elements={n_elements} "
                      f"block_rows={block_rows}")
        return {"kernel": "mrr_transfer", "grid": (0,), "vmem_bytes": 0,
                "pad_waste": 0.0, "issues": issues}
    if block_rows % 8:
        issues.append(f"block_rows={block_rows} not a multiple of 8 "
                      "(f32 sublane tile)")
    rows = -(-n_elements // _LANE)
    rows_pad = -(-rows // block_rows) * block_rows
    block = block_rows * _LANE
    vmem = 4 * 2 * block * 4     # 3 in + 1 out blocks, double-buffered
    return {"kernel": "mrr_transfer", "grid": (rows_pad // block_rows,),
            "vmem_bytes": vmem,
            "pad_waste": (rows_pad * _LANE) / n_elements - 1.0,
            "issues": issues}


@functools.partial(jax.jit, static_argnames=("sigma_dac", "sigma_th", "p",
                                             "block_rows"))
def mrr_transfer(w_target: jax.Array, key: jax.Array,
                 sigma_dac: float = 0.02, sigma_th: float = 0.04,
                 p: mrr.MRRParams = mrr.DEFAULT_PARAMS,
                 block_rows: int = 8) -> jax.Array:
    """Noisy MRR realization of target weights, any shape, any size.

    `block_rows` must match `preflight`'s default (pinned by tests): the
    noise-draw padding below depends on it, so changing the launch tiling
    changes which Gaussian each padded element receives."""
    shape = w_target.shape
    flat = w_target.reshape(-1)
    n = flat.shape[0]
    per_row = _LANE
    rows = -(-n // per_row)
    rows_pad = -(-rows // block_rows) * block_rows
    pad = rows_pad * per_row - n
    flat = jnp.pad(flat, (0, pad)).reshape(rows_pad, per_row)
    k1, k2 = jax.random.split(key)
    e_dac = jax.random.normal(k1, flat.shape, flat.dtype)
    e_th = jax.random.normal(k2, flat.shape, flat.dtype)
    y = mrr_transfer_pallas(flat, e_dac, e_th, sigma_dac=sigma_dac,
                            sigma_th=sigma_th, p=p, block_rows=block_rows,
                            interpret=not on_tpu())
    return y.reshape(-1)[:n].reshape(shape)
