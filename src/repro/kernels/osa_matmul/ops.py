"""Jitted public wrapper around the OSA matmul kernel.

Handles: quantization-scale plumbing, padding to MXU-aligned block multiples,
the interpreter on the CPU (the test path), and default ideal slot gains.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import quant as Q
from repro.kernels import on_tpu
from repro.kernels.osa_matmul.osa_matmul import osa_matmul_pallas


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("quant_bits", "pam_bits", "fused",
                                             "per_vector", "bm", "bn", "bk"))
def osa_matmul(x: jax.Array, w: jax.Array, gains: jax.Array | None = None,
               *, quant_bits: int = 8, pam_bits: int = 1, fused: bool = True,
               per_vector: bool = False,
               bm: int = 128, bn: int = 128, bk: int = 128) -> jax.Array:
    """Float activations -> quantize -> OSA kernel -> dequantized output.

    x: (M, K) float; w: (K, N) float; returns (M, N) f32.
    pam_bits > 1 shrinks the slot count (PAM-2^k digits, paper Sec. 3.1).
    per_vector quantizes each activation row at its own full-scale
    (RosaConfig.act_per_vector — serving's batch-decoupling invariant);
    the (M, 1) scale broadcasts through the final dequant.
    """
    cfg = Q.QuantConfig(bits=quant_bits)
    q, scale = Q.quantize(x, cfg, per_vector=per_vector)
    n_planes = -(-cfg.n_planes // pam_bits)
    if gains is None:
        gains = (Q.plane_weights(cfg) if pam_bits == 1
                 else Q.pam_plane_weights(pam_bits, cfg))
    y = osa_matmul_int(q, w, gains, n_planes=n_planes, fused=fused,
                       bm=bm, bn=bn, bk=bk)
    return y * (scale / cfg.qmax)


def preflight(m: int, k: int, n: int, *, bm: int = 128, bn: int = 128,
              bk: int = 128, quant_bits: int = 8, pam_bits: int = 1) -> dict:
    """Static tileability/VMEM report for an (m, k, n) GEMM — no launch.

    Mirrors exactly what `osa_matmul` would do with the shape: pad every
    dimension up to its block multiple, run a (m/bm, n/bn) grid with a
    k-step inner loop, and hold x/w blocks plus an f32 accumulator scratch
    in VMEM (in/out blocks double-buffered by the pipeline).  The slot
    count is derived from (quant_bits, pam_bits) exactly as `osa_matmul`
    derives it, so the sweep prices what actually launches.  `issues`
    lists hard contract violations (block shapes the MXU tiling cannot
    accept); padding itself is legal but wasteful — `pad_waste` is the
    fraction of extra MACs the padding buys."""
    n_planes = -(-Q.QuantConfig(bits=quant_bits).n_planes // pam_bits)
    issues: list[str] = []
    if min(m, k, n) <= 0 or min(bm, bn, bk) <= 0:
        issues.append(f"non-positive dimension in m,k,n={m},{k},{n} "
                      f"bm,bn,bk={bm},{bn},{bk}")
        return {"kernel": "osa_matmul", "grid": (0, 0, 0), "vmem_bytes": 0,
                "pad_waste": 0.0, "issues": issues}
    # f32 min tile is (8, 128): sublane dims % 8, lane dims % 128
    if bm % 8:
        issues.append(f"bm={bm} not a multiple of 8 (f32 sublane tile)")
    if bk % 128:
        issues.append(f"bk={bk} not a multiple of 128 (x-block lane dim)")
    if bn % 128:
        issues.append(f"bn={bn} not a multiple of 128 (w-block lane dim)")
    mp = -(-m // bm) * bm
    kp = -(-k // bk) * bk
    np_ = -(-n // bn) * bn
    grid = (mp // bm, np_ // bn, kp // bk)
    vmem = 4 * (2 * (bm * bk + bk * bn)      # double-buffered in blocks
                + 2 * bm * bn                # double-buffered out block
                + bm * bn                    # accumulator scratch
                + n_planes)                  # plane gains
    pad_waste = (mp * kp * np_) / (m * k * n) - 1.0
    return {"kernel": "osa_matmul", "grid": grid, "vmem_bytes": vmem,
            "pad_waste": pad_waste, "issues": issues}


def osa_matmul_int(q: jax.Array, w: jax.Array, gains: jax.Array,
                   *, n_planes: int, fused: bool = True,
                   bm: int = 128, bn: int = 128, bk: int = 128) -> jax.Array:
    """Integer-activation entry point (the kernel's native contract)."""
    m, k = q.shape
    _, n = w.shape
    qp = _pad_to(_pad_to(q.astype(jnp.float32), bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w.astype(jnp.float32), bk, 0), bn, 1)
    y = osa_matmul_pallas(qp, wp, gains.astype(jnp.float32),
                          n_planes=n_planes, fused=fused, bm=bm, bn=bn, bk=bk,
                          interpret=not on_tpu())
    return y[:m, :n]
