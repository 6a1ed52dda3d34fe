"""Jitted public wrapper for the SSD scan kernel.

Shapes in model-land are (B, L, H, P) with per-head state (B, H, S, P); this
wrapper folds (B, H) -> BH, pads L to the chunk multiple with identity steps
(log a = 0, b = c = 0 contribute nothing and leave the state untouched).
Off-TPU the kernel runs under the Pallas interpreter (the CPU test path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas


def preflight(bsz: int, l: int, h: int, p: int, s_dim: int, *,
              chunk: int = 128) -> dict:
    """Static tileability/VMEM report for an SSD scan — no launch.

    Mirrors `ssd_scan`'s layout: (B, H) folds to BH rows, L pads to the
    chunk multiple with identity steps, and each grid step holds one
    chunk of x/loga/b/c plus the running (S, P) state scratch in VMEM."""
    issues: list[str] = []
    soft: list[str] = []
    if min(bsz, l, h, p, s_dim, chunk) <= 0:
        issues.append(f"non-positive dimension in B,L,H,P,S,chunk="
                      f"{bsz},{l},{h},{p},{s_dim},{chunk}")
        return {"kernel": "ssd_scan", "grid": (0, 0), "vmem_bytes": 0,
                "pad_waste": 0.0, "issues": issues, "soft_issues": soft}
    # P/S are lane dims the compiler CAN pad to 128 — legal, but any
    # shortfall idles lanes on every matmul, so they are soft issues.
    if p % 128:
        soft.append(f"P={p} not a multiple of 128 (lane dim of x/y): "
                    "lanes idle on every chunk matmul")
    if s_dim % 128:
        soft.append(f"S={s_dim} not a multiple of 128 (lane dim of b/c): "
                    "lanes idle on every chunk matmul")
    if chunk % 8:
        issues.append(f"chunk={chunk} not a multiple of 8 (sublane tile)")
    lp = -(-l // chunk) * chunk
    # the (chunk, 1) cumulative-decay block fills 128-lane rows in VMEM
    vmem = 4 * (2 * (chunk * p + chunk * 128 + 2 * chunk * s_dim)  # in
                + 2 * chunk * p                              # out block
                + s_dim * p)                                 # state scratch
    return {"kernel": "ssd_scan", "grid": (bsz * h, lp // chunk),
            "vmem_bytes": vmem, "pad_waste": lp / l - 1.0,
            "issues": issues, "soft_issues": soft}


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x: jax.Array, loga: jax.Array, b: jax.Array, c: jax.Array,
             chunk: int = 128):
    """x: (B, L, H, P); loga: (B, L, H); b, c: (B, L, G, S) with G head
    groups (G divides H, heads within a group share B/C — Mamba-2's GVA).

    Returns (y: (B, L, H, P), state: (B, H, S, P)).
    """
    bsz, l, h, p = x.shape
    g = b.shape[2]
    s_dim = b.shape[-1]
    rep = h // g

    # broadcast groups to heads, fold (B, H) -> BH
    bh = bsz * h
    xf = x.transpose(0, 2, 1, 3).reshape(bh, l, p)
    lf = loga.transpose(0, 2, 1).reshape(bh, l)
    bf = jnp.repeat(b, rep, axis=2).transpose(0, 2, 1, 3).reshape(bh, l, s_dim)
    cf = jnp.repeat(c, rep, axis=2).transpose(0, 2, 1, 3).reshape(bh, l, s_dim)

    pad = (-l) % chunk
    if pad:
        xf = jnp.pad(xf, ((0, 0), (0, pad), (0, 0)))
        lf = jnp.pad(lf, ((0, 0), (0, pad)))          # log a = 0 -> a = 1
        bf = jnp.pad(bf, ((0, 0), (0, pad), (0, 0)))  # b = 0 -> no state write
        cf = jnp.pad(cf, ((0, 0), (0, pad), (0, 0)))

    y, sf = ssd_scan_pallas(xf, lf, bf, cf, chunk=chunk,
                            interpret=not on_tpu())
    y = y[:, :l].reshape(bsz, h, l, p).transpose(0, 2, 1, 3)
    sf = sf.reshape(bsz, h, s_dim, p)
    return y, sf
