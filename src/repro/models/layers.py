"""Shared transformer building blocks (pure functional, scan-friendly).

Every block is a pair of functions:

    <block>_def(cfg)            -> skeleton pytree of ParamDef
    <block>_apply(params, ...)  -> activations

Params are plain pytrees; logical axis names on every ParamDef drive the
distributed sharding rules (distributed/sharding.py).  All blocks support
three execution phases:

    train/prefill : full-sequence forward (B, S, D)
    decode        : single-token forward with a KV cache at position `pos`

Attention flavours: full causal, sliding-window (per-layer window scalar so
gemma-style 5:1 local:global patterns scan), bidirectional (encoders) and
cross-attention (enc-dec).  GQA throughout; qk-norm optional (qwen3).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro import rosa
from repro.models.module import ParamDef

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm_def(dim: int, axis: str = "embed") -> ParamDef:
    return ParamDef((dim,), (axis,), "ones")


def rmsnorm(scale: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with a hand-written backward (EXPERIMENTS.md §Perf A5).

    Forward keeps f32 statistics.  The custom VJP keeps every (B, S, D)
    cotangent in the ACTIVATION dtype — autodiff of the naive f32-stats
    formulation drags f32 copies of the residual stream through the whole
    backward scan (measured: +60% memory-roofline term on the 123B cell);
    only the (B, S, 1) reductions run in f32 here, exactly like production
    fused-norm kernels."""
    return _rmsnorm_core(x, scale, eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_core(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * r * scale


def _rmsnorm_fwd2(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    r32 = jax.lax.rsqrt(var + eps)
    r = r32.astype(x.dtype)
    return x * r * scale, (x, r, scale)


def _rmsnorm_bwd2(eps, res, g):
    x, r, scale = res
    xh = x * r
    d_scale = jnp.sum((g * xh).astype(jnp.float32),
                      axis=tuple(range(g.ndim - 1))).astype(scale.dtype)
    gsc = g * scale
    m = jnp.mean((gsc * xh).astype(jnp.float32), axis=-1,
                 keepdims=True).astype(x.dtype)
    dx = r * (gsc - xh * m)
    return dx, d_scale


_rmsnorm_core.defvjp(_rmsnorm_fwd2, _rmsnorm_bwd2)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
def rope(x: jax.Array, positions: jax.Array, theta) -> jax.Array:
    """x: (..., S, H, D) ; positions: (..., S) ; theta: scalar (traced ok)."""
    d = x.shape[-1]
    half = d // 2
    freq = jnp.exp(
        -jnp.log(jnp.asarray(theta, jnp.float32))
        * (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freq      # (..., S, half)
    # trig tables cast to the activation dtype BEFORE the elementwise mix so
    # neither the forward nor the cotangent ever materializes f32 copies of
    # the (B, S, H, D) tensor (EXPERIMENTS.md §Perf A2)
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


# ---------------------------------------------------------------------------
# Attention (GQA, optional qk-norm / sliding window / cross)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    rope_theta: float = 1e6
    causal: bool = True          # False -> bidirectional (encoder)
    cross: bool = False          # cross-attention (kv from encoder memory)
    uniform_decode: bool = True  # all sequences decode at the same position
    #   -> cache writes lower to dynamic-update-slice, which GSPMD handles
    #   on a sequence-sharded cache without replication (§Perf B1); set
    #   False for continuous batching with ragged positions (scatter path).

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


def attn_def(cfg: AttnConfig) -> dict:
    d = cfg.d_model
    p = {
        "wq": ParamDef((d, cfg.n_heads, cfg.head_dim),
                       ("embed", "heads", "head_dim")),
        "wk": ParamDef((d, cfg.n_kv_heads, cfg.head_dim),
                       ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, cfg.n_kv_heads, cfg.head_dim),
                       ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((cfg.n_heads, cfg.head_dim, d),
                       ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_def(cfg.head_dim, "head_dim")
        p["k_norm"] = rmsnorm_def(cfg.head_dim, "head_dim")
    return p


def cache_write(cache: jax.Array, new: jax.Array, pos: jax.Array,
                uniform: bool) -> jax.Array:
    """Write `new` (B, C, ...) per-sequence tokens into cache (B, S, ...)
    starting at `pos` (B,).  C == 1 is the decode step; C > 1 is a prefill
    chunk (serving)."""
    if uniform:
        # all positions equal: a dynamic-update-slice along S — GSPMD keeps
        # a seq-sharded cache in place (no involuntary replication)
        idx = (jnp.zeros((), jnp.int32), pos[0]) \
            + (jnp.zeros((), jnp.int32),) * (cache.ndim - 2)
        return jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype),
                                            idx)
    b, c = new.shape[:2]
    if c == 1:
        return cache.at[jnp.arange(b), pos].set(new.astype(cache.dtype)[:, 0])
    # ragged chunk write: batched scatter at pos[b] + [0, C); rows whose
    # window crosses S drop the out-of-range tokens (jax scatter semantics)
    rows = jnp.arange(b)[:, None]
    cols = pos[:, None] + jnp.arange(c)[None, :]
    return cache.at[rows, cols].set(new.astype(cache.dtype),
                                    mode="drop")


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, KV, D) -> (B, S, H, D) by repeating each kv head."""
    n_kv = k.shape[-2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=-2)


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
               window, k_len_valid=None) -> jax.Array:
    """Additive mask (..., Sq, Sk). window: scalar; <=0 means unlimited."""
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    ok = jnp.ones(diff.shape, bool)
    if causal:
        ok = ok & (diff >= 0)
    window = jnp.asarray(window)
    ok = ok & ((window <= 0) | (diff < window))
    if k_len_valid is not None:
        # k_len_valid: (B, 1) -> (B, 1, 1) so it broadcasts over (B, Sq, Sk)
        ok = ok & (k_pos[..., None, :] < jnp.asarray(k_len_valid)[..., None])
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attention_core(q: jax.Array, k: jax.Array, v: jax.Array,
                   bias: jax.Array) -> jax.Array:
    """q: (B, Sq, H, D); k, v: (B, Sk, H, D); bias: (B or 1, Sq, Sk).

    The QK einsum stays in the activation dtype (MXU accumulates in f32
    internally); only the softmax itself runs in f32.  The f32->bf16 cast
    sits directly on the einsum output so the backward pass hands bf16
    cotangents to d_q/d_k — keeping the whole residual-stream backward in
    bf16 (EXPERIMENTS.md §Perf A2)."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    scores = scores.astype(jnp.float32) * scale + bias[:, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attn_apply(p: dict, cfg: AttnConfig, x: jax.Array,
               positions: jax.Array, *,
               window=0, theta=None,
               memory: jax.Array | None = None,
               memory_pos: jax.Array | None = None) -> jax.Array:
    """Full-sequence attention. x: (B, S, D)."""
    theta = cfg.rope_theta if theta is None else theta
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    src = memory if cfg.cross else x
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    if not cfg.cross:
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
        k_pos = positions
    else:
        k_pos = memory_pos
    k = _repeat_kv(k, cfg.n_heads)
    v = _repeat_kv(v, cfg.n_heads)
    bias = _mask_bias(positions, k_pos, cfg.causal and not cfg.cross, window)
    o = attention_core(q, k, v, bias)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


def attn_prefill(p: dict, cfg: AttnConfig, x: jax.Array,
                 positions: jax.Array, *, window=0, theta=None):
    """Prefill: like attn_apply but also returns the (k, v) cache."""
    theta = cfg.rope_theta if theta is None else theta
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    q = rope(q, positions, theta)
    k = rope(k, positions, theta)
    kr = _repeat_kv(k, cfg.n_heads)
    vr = _repeat_kv(v, cfg.n_heads)
    bias = _mask_bias(positions, positions, cfg.causal, window)
    o = attention_core(q, kr, vr, bias)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), (k, v)


def flash_decode(q: jax.Array, kc: jax.Array, vc: jax.Array,
                 pos: jax.Array, window, n_heads: int) -> jax.Array:
    """Distributed decode attention over a sequence-sharded KV cache.

    GSPMD's default plan ALL-GATHERS the cache per layer (measured 8.6 GB
    per layer on the 500k cell — §Perf B2).  This shard_map computes the
    flash-decoding split instead: each shard takes partial max / sum-exp /
    value-sum over its local KV slice; the cross-shard combine moves only
    (B, H) statistics and the (B, H, D) partial output.

    q: (B, 1, H, D) replicated; kc/vc: (B, S, KV, D) seq-sharded.
    """
    from repro.distributed.sharding import current_ctx, resolve_spec
    ctx = current_ctx()
    kv_axes = ("cache_batch", "cache_seq", "kv_heads", "head_dim")
    if ctx is None or ctx.mesh is None:
        return None
    spec_kv = resolve_spec(kc.shape, kv_axes, ctx.rules, ctx.mesh)
    seq_part = spec_kv[1] if len(spec_kv) > 1 else None
    if seq_part is None:
        return None                       # cache not seq-sharded: gather-free
    seq_axes = seq_part if isinstance(seq_part, tuple) else (seq_part,)
    s_loc_count = math.prod(ctx.mesh.shape[a] for a in seq_axes)
    mesh = ctx.mesh

    def local(qv, k, v, pv):
        s_loc = k.shape[1]
        # global positions of this shard's KV slice
        idx = jnp.zeros((), jnp.int32)
        for a in seq_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        k_pos = idx * s_loc + jnp.arange(s_loc)
        k_pos = jnp.broadcast_to(k_pos[None], (k.shape[0], s_loc))
        bias = _mask_bias(pv[:, None], k_pos, True, window,
                          k_len_valid=(pv + 1)[:, None])
        kr = _repeat_kv(k, n_heads)
        vr = _repeat_kv(v, n_heads)
        scale = qv.shape[-1] ** -0.5
        s = jnp.einsum("bqhd,bkhd->bhqk", qv, kr).astype(jnp.float32) \
            * scale + bias[:, None]
        m_l = jnp.max(s, axis=-1)                      # (B, H, 1)
        m = jax.lax.pmax(m_l, seq_axes)
        p_ = jnp.exp(s - m[..., None])
        denom = jax.lax.psum(jnp.sum(p_, -1), seq_axes)
        o = jnp.einsum("bhqk,bkhd->bqhd", p_.astype(qv.dtype), vr)
        o = jax.lax.psum(o, seq_axes)
        return o / denom.transpose(0, 2, 1)[..., None].astype(o.dtype)

    batch_part = spec_kv[0] if len(spec_kv) else None
    q_spec = jax.sharding.PartitionSpec(batch_part)     # match kv's batch
    pos_spec = jax.sharding.PartitionSpec(batch_part)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(q_spec, spec_kv, spec_kv, pos_spec),
                         out_specs=q_spec, check_vma=False)(q, kc, vc, pos)


def attn_decode(p: dict, cfg: AttnConfig, x: jax.Array, cache: tuple,
                pos: jax.Array, *, window=0, theta=None,
                memory: jax.Array | None = None,
                memory_pos: jax.Array | None = None):
    """Cached decode. x: (B, C, D); cache: (k, v) each (B, S, KV, D);
    pos: (B,) first position of the chunk.  C == 1 is the classic one-token
    step; C > 1 is a prefill chunk writing C tokens at pos..pos+C (serving).
    Returns (out, new_cache)."""
    theta = cfg.rope_theta if theta is None else theta
    c = x.shape[1]
    q_pos = pos[:, None] + jnp.arange(c)[None, :]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
    if cfg.cross:
        k_full, v_full = cache       # static encoder memory projections
        k_pos = memory_pos[:, :]
        bias = _mask_bias(q_pos, k_pos, False, 0)
        o = attention_core(q, _repeat_kv(k_full, cfg.n_heads),
                           _repeat_kv(v_full, cfg.n_heads), bias)
        return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), cache
    q = rope(q, q_pos, theta)
    k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        k_new = rmsnorm(p["k_norm"], k_new)
    k_new = rope(k_new, q_pos, theta)
    kc, vc = cache
    b = x.shape[0]
    kc = cache_write(kc, k_new, pos, cfg.uniform_decode)
    vc = cache_write(vc, v_new, pos, cfg.uniform_decode)
    o = flash_decode(q, kc, vc, pos, window, cfg.n_heads) if c == 1 else None
    if o is None:                      # unsharded cache: plain attention
        s = kc.shape[1]
        k_pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        bias = _mask_bias(q_pos, k_pos, True, window,
                          k_len_valid=(pos + c)[:, None])
        o = attention_core(q, _repeat_kv(kc, cfg.n_heads),
                           _repeat_kv(vc, cfg.n_heads), bias)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), (kc, vc)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------
def mlp_def(d_model: int, d_ff: int) -> dict:
    return {
        "wi": ParamDef((d_model, 2, d_ff), ("embed", None, "mlp")),  # gate|up
        "wo": ParamDef((d_ff, d_model), ("mlp", "embed")),
    }


def mlp_apply(p: dict, x: jax.Array, engine: "rosa.Engine | None" = None,
              key: jax.Array | None = None, *, name: str = "mlp",
              step: "int | jax.Array" = 0, rosa_cfg=None) -> jax.Array:
    """SwiGLU MLP; with an optical `rosa.Engine` both projections run
    through the paper's optical MAC (OSA bit-serial signed-digit pipeline +
    noisy MRR weight realization — DESIGN.md §3 'execution backends').
    Each projection gets its own deterministic key, folded from the
    engine's base key, its `{name}/wi` / `{name}/wo` layer name, and
    `step`.  Inside a scan-over-layers stack pass the (traced) layer index
    as `step` so layers draw independent noise — the scanned body traces
    once, so the name alone cannot distinguish layers (for the same reason
    an attached EnergyLedger sees the body's two projections once, not L
    times).  `rosa_cfg` is the legacy spelling (uniform config, no plan)."""
    if engine is None and rosa_cfg is not None:
        engine = rosa.Engine.from_config(rosa_cfg)
    if engine is not None and not engine.is_dense:
        if key is not None:
            engine = engine.with_key(key)
        b, s, d = x.shape
        f = p["wi"].shape[-1]
        gu = engine.matmul(x.reshape(-1, d), p["wi"].reshape(d, 2 * f),
                           name=f"{name}/wi", step=step).reshape(b, s, 2, f)
        h = jax.nn.silu(gu[..., 0, :]) * gu[..., 1, :]
        y = engine.matmul(h.reshape(-1, f), p["wo"], name=f"{name}/wo",
                          step=step)
        return y.reshape(b, s, d).astype(x.dtype)
    gu = jnp.einsum("bsd,dcf->bscf", x, p["wi"])
    h = jax.nn.silu(gu[..., 0, :]) * gu[..., 1, :]
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def embed_def(vocab: int, d_model: int) -> ParamDef:
    # 0.02 std keeps tied-unembedding logits in a sane range at init
    return ParamDef((vocab, d_model), ("vocab", "embed"), "normal", 0.02)


def embed_apply(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(table, tokens, axis=0)


def unembed_def(d_model: int, vocab: int) -> ParamDef:
    return ParamDef((d_model, vocab), ("embed", "vocab"))


def unembed_apply(w: jax.Array, x: jax.Array) -> jax.Array:
    return jnp.einsum("bsd,dv->bsv", x, w)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------
def softmax_xent(logits: jax.Array, labels: jax.Array,
                 mask: jax.Array | None = None) -> jax.Array:
    """Mean next-token cross entropy. logits: (B, S, V); labels: (B, S)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


Pytree = Any
