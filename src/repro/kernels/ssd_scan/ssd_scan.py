"""Pallas TPU kernel: Mamba-2 SSD chunked scan.

TPU-native realization of the state-space duality algorithm: the sequence is
split into chunks of Q steps; each chunk is three MXU matmuls

    att  = (C @ B^T) . tril(decay)         (Q, Q)
    Y    = att @ X + exp(lcum) * (C @ S)   (Q, P)
    S'   = exp(ltot) * S + (B * w)^T @ X   (S, P)

with the running state S carried across the chunk grid dimension in a VMEM
scratch accumulator — the classic sequential-innermost-grid-dim pattern.
The (batch*heads) grid dimension is parallel; the chunk dimension is
"arbitrary" (sequential) so the scratch state persists step to step and is
re-zeroed whenever a new (batch, head) row begins.

Chunk length Q and head dim P default to 128 to keep every matmul
MXU-shaped; d_state S is the lane dim of the B/C blocks (Mamba-2 uses
64-256, already aligned).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(x_ref, lcum_ref, b_ref, c_ref, y_ref, sf_ref, state_ref,
            *, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0]                                  # (Q, P)
    lcum = lcum_ref[0]                            # (Q, 1)
    b = b_ref[0]                                  # (Q, S)
    c = c_ref[0]                                  # (Q, S)
    q = x.shape[0]

    # chunk total = last row, picked by a masked reduction (exact: every
    # other term is +0) because Mosaic cannot broadcast a sliced (1, 1)
    last = jax.lax.broadcasted_iota(jnp.int32, lcum.shape, 0) == q - 1
    ltot = jnp.sum(jnp.where(last, lcum, 0.0))
    # intra-chunk: masked decay kernel (rows i, cols j), j <= i
    dmat = jnp.exp(lcum - lcum.T)
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    att = jnp.dot(c, b.T, preferred_element_type=jnp.float32)
    att = att * jnp.where(col <= row, dmat, 0.0)
    y = jnp.dot(att, x, preferred_element_type=jnp.float32)
    # inter-chunk: contribution of the carried state
    s = state_ref[...]                            # (S, P)
    y = y + jnp.exp(lcum) * jnp.dot(c, s, preferred_element_type=jnp.float32)
    # carry the state forward
    w = jnp.exp(ltot - lcum)                      # (Q, 1)
    s_new = jnp.exp(ltot) * s + jnp.dot((b * w).T, x,
                                        preferred_element_type=jnp.float32)
    state_ref[...] = s_new
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _flush():
        sf_ref[0] = s_new.astype(sf_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(x: jax.Array, loga: jax.Array, b: jax.Array,
                    c: jax.Array, *, chunk: int = 128,
                    interpret: bool = False):
    """Batched SSD scan.

    x: (BH, L, P); loga: (BH, L) = log decay; b, c: (BH, L, S).
    Returns (y: (BH, L, P), s_final: (BH, S, P)).  L % chunk == 0.

    The within-chunk cumulative log decay is summed here (Mosaic has no
    cumsum) and enters the kernel as (BH, L, 1): a trailing unit dim keeps
    its (1, chunk, 1) block within the TPU's last-two-dims tiling rule.
    """
    bh, l, p = x.shape
    s_dim = b.shape[-1]
    assert l % chunk == 0, (l, chunk)
    n_chunks = l // chunk
    lcum = jnp.cumsum(loga.reshape(bh, n_chunks, chunk), axis=-1)
    lcum = lcum.reshape(bh, l, 1)

    kernel = functools.partial(_kernel, n_chunks=n_chunks)
    grid = (bh, n_chunks)
    y, sf = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, s_dim), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, s_dim), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, s_dim, p), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, l, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, s_dim, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((s_dim, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, lcum, b, c)
    return y, sf
