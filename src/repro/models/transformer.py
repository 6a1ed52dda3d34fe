"""Model stacks: decoder-only / MoE / MLA / SSM / hybrid / enc-dec.

All stacks scan over layers (stacked params, one compiled body) with an
optional remat policy — this keeps HLO size and compile time flat in depth,
which matters for the 94-layer dry-run cells.  Heterogeneous-depth patterns
are handled without breaking the scan:

  * gemma3 5:1 local:global — same params every layer; per-layer window and
    rope-theta ride along the scan as (L,) meta arrays.
  * deepseek-v2 layer-0 dense FFN — one unrolled head layer + scanned body.
  * zamba2 — scan over groups of `shared_every` mamba layers, the SHARED
    attention block (one param set, closed over) applied once per group with
    a per-group KV cache; remainder mamba layers unrolled at the tail.

Step functions all take/return plain pytrees so jax.jit can shard them:

    train_loss(params, batch)                -> scalar loss
    prefill(params, batch)                   -> (last_logits, cache)
    decode_step(params, batch-with-cache)    -> (logits, cache)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import rosa
from repro.distributed.sharding import (current_ctx, ep_param_specs, shard_act)
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.module import ParamDef


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | mla_moe | ssm | hybrid | encdec
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    qk_norm: bool = False
    rope_theta: float = 1e6
    # sliding-window pattern: layers with (i % pattern != pattern-1) are
    # local with `window`; pattern == 0 -> all layers full attention.
    window: int = 0
    window_pattern: int = 0
    rope_theta_local: float = 1e4
    moe: MOE.MoEConfig | None = None
    mla: MLA.MLAConfig | None = None
    first_dense_ff: int = 0
    ssm: SSM.SSMConfig | None = None
    shared_every: int = 0        # zamba2: shared attn after every k ssm layers
    n_enc_layers: int = 0        # encdec: encoder depth (n_layers = decoder)
    frontend: str = "none"       # none | vision | audio
    tie_embeddings: bool = False
    remat: str = "full"          # full | dots | none
    parallelism: str = "tp"      # tp | zero3 (train-time layout; §Perf A6)
    moe_ep: bool = False         # expert-parallel shard_map path
    rosa_mlp: bool = False       # route MLP projections through the ROSA
    #   optical MAC (8-bit OSA bit-serial emulation; Pallas kernel on TPU)
    cache_dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-6
    uniform_decode: bool = True  # False -> continuous-batching serving:
    #   per-sequence ragged positions (scatter cache writes; repro.serve)

    @property
    def attn(self) -> L.AttnConfig:
        return L.AttnConfig(self.d_model, self.n_heads, self.n_kv_heads,
                            self.head_dim, self.qk_norm, self.rope_theta,
                            uniform_decode=self.uniform_decode)

    @property
    def is_encdec(self) -> bool:
        return self.n_enc_layers > 0


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def stack_defs(skel, n: int):
    """Prepend a layer dimension of size n to every ParamDef in a skeleton."""
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, ("layers",) + d.axes, d.init,
                           d.scale),
        skel, is_leaf=lambda x: isinstance(x, ParamDef))


def layer_at(tree, i):
    """Leaf-wise `tree[i]`: one layer's params/cache out of a stacked tree."""
    return jax.tree.map(lambda a, idx=i: a[idx], tree)


# ---------------------------------------------------------------------------
# Per-layer meta arrays (window / rope theta patterns)
# ---------------------------------------------------------------------------
def layer_meta(cfg: ModelConfig) -> dict:
    li = jnp.arange(cfg.n_layers)
    if cfg.window_pattern > 0:
        is_global = (li % cfg.window_pattern) == (cfg.window_pattern - 1)
        window = jnp.where(is_global, 0, cfg.window)
        theta = jnp.where(is_global, cfg.rope_theta, cfg.rope_theta_local)
    else:
        window = jnp.zeros_like(li)
        theta = jnp.full((cfg.n_layers,), cfg.rope_theta)
    return {"window": window, "theta": theta.astype(jnp.float32),
            "idx": li}


# ---------------------------------------------------------------------------
# FFN dispatch (dense MLP vs MoE, EP-aware)
# ---------------------------------------------------------------------------
def _ffn_def(cfg: ModelConfig) -> dict:
    if cfg.moe is not None:
        return MOE.moe_def(cfg.moe)
    return L.mlp_def(cfg.d_model, cfg.d_ff)


def _ffn_apply(p: dict, cfg: ModelConfig, x: jax.Array,
               step=0) -> jax.Array:
    if cfg.moe is None:
        if cfg.rosa_mlp:
            # step = (traced) layer index: layers in a scanned stack
            # must fold independent noise keys (see mlp_apply).  An
            # installed engine context (rosa.engine_context — a compiled
            # rosa.Program installs its own) wins: serving pins a
            # fabricated chip + hybrid plan + ledger there.
            engine = rosa.ambient_engine()
            if engine is None:
                engine = rosa.Engine.from_config()
            return L.mlp_apply(p, x, engine=engine, step=step)
        return L.mlp_apply(p, x)
    ctx = current_ctx()
    if cfg.moe_ep and ctx is not None and ctx.mesh is not None:
        import math
        from repro.distributed.sharding import resolve_spec
        mesh = ctx.mesh
        x_spec = resolve_spec(x.shape, ("batch", None, None), ctx.rules, mesh)
        fsdp = tuple(a for a in (ctx.rules.get("embed") or ())
                     if a in mesh.shape)
        if fsdp and p["wi"].shape[1] % math.prod(
                mesh.shape[a] for a in fsdp) != 0:
            fsdp = ()
        # ZeRO-3 layout shards tokens over "model" too -> all-to-all EP
        bp = x_spec[0] if len(x_spec) else None
        batch_axes = set(bp if isinstance(bp, tuple) else (bp,))
        a2a = "model" in batch_axes
        fn = functools.partial(
            MOE.moe_ep_local, cfg=cfg.moe, model_axis="model",
            fsdp_axes=fsdp, a2a=a2a)
        specs = ep_param_specs(p, fsdp)
        return jax.shard_map(
            lambda pl_, xl: fn(pl_, x_local=xl),
            mesh=mesh, in_specs=(specs, x_spec),
            out_specs=x_spec, check_vma=False)(p, x)
    return MOE.moe_ref(p, cfg.moe, x)


# ---------------------------------------------------------------------------
# Decoder block (attn | mla | ssm) + FFN
# ---------------------------------------------------------------------------
def _block_def(cfg: ModelConfig, cross: bool = False) -> dict:
    d = cfg.d_model
    p = {"ln1": L.rmsnorm_def(d), "ln2": L.rmsnorm_def(d)}
    if cfg.family in ("dense", "moe", "encdec"):
        p["attn"] = L.attn_def(cfg.attn)
        p["ffn"] = _ffn_def(cfg)
    elif cfg.family == "mla_moe":
        p["attn"] = MLA.mla_def(cfg.mla)
        p["ffn"] = _ffn_def(cfg)
    elif cfg.family in ("ssm", "hybrid"):
        p = {"ln1": L.rmsnorm_def(d)}
        p["ssm"] = SSM.ssm_def(cfg.ssm)
    else:
        raise ValueError(cfg.family)
    if cross:
        p["ln_cross"] = L.rmsnorm_def(d)
        p["cross"] = L.attn_def(dataclasses.replace(
            cfg.attn, cross=True, causal=False))
    return p


def _block_fwd(p: dict, cfg: ModelConfig, x, positions, meta,
               memory=None, memory_pos=None):
    """Full-sequence block forward (train path, no cache)."""
    if "ssm" in p:
        return x + SSM.ssm_apply(p["ssm"], cfg.ssm,
                                 L.rmsnorm(p["ln1"], x, cfg.norm_eps))
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.family == "mla_moe":
        a = MLA.mla_apply(p["attn"], cfg.mla, h, positions)
    else:
        a = L.attn_apply(p["attn"], cfg.attn, h, positions,
                         window=meta["window"], theta=meta["theta"])
    x = x + shard_act(a, "batch", None, None)
    if "cross" in p:
        h = L.rmsnorm(p["ln_cross"], x, cfg.norm_eps)
        ccfg = dataclasses.replace(cfg.attn, cross=True, causal=False)
        x = x + L.attn_apply(p["cross"], ccfg, h, positions,
                             memory=memory, memory_pos=memory_pos)
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + shard_act(_ffn_apply(p["ffn"], cfg, h, meta.get("idx", 0)),
                         "batch", None, None)


def _block_prefill(p: dict, cfg: ModelConfig, x, positions, meta):
    if "ssm" in p:
        h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        # full-sequence ssm + final state capture for the decode cache
        y, cache = _ssm_prefill(p["ssm"], cfg.ssm, h)
        return x + y, cache
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.family == "mla_moe":
        a, cache = MLA.mla_prefill(p["attn"], cfg.mla, h, positions)
    else:
        a, cache = L.attn_prefill(p["attn"], cfg.attn, h, positions,
                                  window=meta["window"], theta=meta["theta"])
        cache = tuple(c.astype(cfg.cache_dtype) for c in cache)
    x = x + a
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + _ffn_apply(p["ffn"], cfg, h, meta.get("idx", 0)), cache


def _block_decode(p: dict, cfg: ModelConfig, x, pos, meta, cache,
                  memory_pos=None):
    if "ssm" in p:
        h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        y, cache = SSM.ssm_decode(p["ssm"], cfg.ssm, h, cache)
        return x + y, cache
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.family == "mla_moe":
        a, cache = MLA.mla_decode(p["attn"], cfg.mla, h, cache, pos)
    else:
        self_cache = cache["self"] if "cross" in p else cache
        a, self_cache = L.attn_decode(p["attn"], cfg.attn, h, self_cache, pos,
                                      window=meta["window"],
                                      theta=meta["theta"])
        if "cross" in p:
            cache = dict(cache, self=self_cache)
        else:
            cache = self_cache
    x = x + a
    if "cross" in p:
        h = L.rmsnorm(p["ln_cross"], x, cfg.norm_eps)
        ccfg = dataclasses.replace(cfg.attn, cross=True, causal=False)
        a, _ = L.attn_decode(p["cross"], ccfg, h, cache["cross"], pos,
                             memory_pos=memory_pos)
        x = x + a
    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    return x + _ffn_apply(p["ffn"], cfg, h, meta.get("idx", 0)), cache


def _ssm_prefill(p: dict, scfg: SSM.SSMConfig, u: jax.Array):
    """Like ssm_apply but also returns the decode cache (conv + state)."""
    h, g = scfg.n_heads, scfg.n_groups
    x_pre = jnp.einsum("bld,dhp->blhp", u, p["w_x"])
    b_pre = jnp.einsum("bld,dgs->blgs", u, p["w_b"])
    c_pre = jnp.einsum("bld,dgs->blgs", u, p["w_c"])
    x = SSM._causal_conv(x_pre, p["conv_x"])
    b = SSM._causal_conv(b_pre, p["conv_b"])
    c = SSM._causal_conv(c_pre, p["conv_c"])
    z = jnp.einsum("bld,dhp->blhp", u, p["w_z"])
    dt, loga = SSM._decay(p, jnp.einsum("bld,dh->blh", u, p["w_dt"]))
    rep = h // g
    bb = jnp.repeat(b, rep, axis=2).astype(jnp.float32)
    cc = jnp.repeat(c, rep, axis=2).astype(jnp.float32)
    y, state = SSM.ssd_chunked(x.astype(jnp.float32) * dt[..., None], loga,
                               bb, cc, scfg.chunk)
    y = y + p["d_skip"][None, None, :, None] * x.astype(jnp.float32)
    y = y.astype(u.dtype) * jax.nn.silu(z)
    y = L.rmsnorm(p["gate_norm"].reshape(-1),
                  y.reshape(*y.shape[:2], -1)).reshape(y.shape)
    out = jnp.einsum("blhp,hpd->bld", y, p["w_out"])
    k = scfg.d_conv - 1
    cache = {"conv_x": x_pre[:, -k:], "conv_b": b_pre[:, -k:],
             "conv_c": c_pre[:, -k:], "state": state}
    return out, cache


# ---------------------------------------------------------------------------
# Whole-model skeletons
# ---------------------------------------------------------------------------
def model_def(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    skel: dict = {"embed": L.embed_def(cfg.vocab, d),
                  "final_norm": L.rmsnorm_def(d)}
    if not cfg.tie_embeddings:
        skel["unembed"] = L.unembed_def(d, cfg.vocab)

    if cfg.family == "hybrid":
        k = cfg.shared_every
        n_groups, rem = divmod(cfg.n_layers, k)
        skel["groups"] = stack_defs(stack_defs(_block_def(cfg), k), n_groups)
        if rem:
            skel["tail"] = stack_defs(_block_def(cfg), rem)
        acfg = cfg.attn
        skel["shared_attn"] = {"ln": L.rmsnorm_def(d),
                               "attn": L.attn_def(acfg),
                               "ln2": L.rmsnorm_def(d),
                               "ffn": L.mlp_def(d, cfg.d_ff)}
    elif cfg.family == "encdec":
        enc_cfg = dataclasses.replace(cfg, family="dense")
        enc_block = {"ln1": L.rmsnorm_def(d), "ln2": L.rmsnorm_def(d),
                     "attn": L.attn_def(dataclasses.replace(
                         enc_cfg.attn, causal=False)),
                     "ffn": L.mlp_def(d, cfg.d_ff)}
        skel["encoder"] = {"layers": stack_defs(enc_block, cfg.n_enc_layers),
                           "norm": L.rmsnorm_def(d)}
        skel["layers"] = stack_defs(
            _block_def(dataclasses.replace(cfg, family="dense"), cross=True),
            cfg.n_layers)
    else:
        n_scanned = cfg.n_layers - (1 if cfg.first_dense_ff else 0)
        if cfg.first_dense_ff:
            dense0 = dataclasses.replace(cfg, moe=None,
                                         d_ff=cfg.first_dense_ff)
            skel["layer0"] = _block_def(dense0)
        skel["layers"] = stack_defs(_block_def(cfg), n_scanned)
    return skel


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------
def _embed_in(params, cfg: ModelConfig, batch: dict):
    """Token (+ modality-frontend) embedding. Returns (x, positions)."""
    tokens = batch["tokens"]
    x = L.embed_apply(params["embed"], tokens)
    if cfg.frontend == "vision":
        # precomputed patch embeddings prepended to the text tokens
        x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    return shard_act(x, "batch", None, None), positions


def _scan_fwd(params, cfg: ModelConfig, x, positions, meta,
              memory=None, memory_pos=None):
    def body(carry, xs):
        p_l, m_l = xs
        return _block_fwd(p_l, cfg, carry, positions, m_l,
                          memory, memory_pos), None
    x, _ = jax.lax.scan(_remat(cfg, body), x, (params, meta))
    return x


def forward(params, cfg: ModelConfig, batch: dict) -> jax.Array:
    """Full-sequence forward to final hidden states (B, S, D)."""
    x, positions = _embed_in(params, cfg, batch)
    meta = layer_meta(cfg)
    no_meta = {"window": jnp.zeros((), jnp.int32),
               "theta": jnp.float32(cfg.rope_theta)}

    if cfg.family == "hybrid":
        x = _hybrid_fwd(params, cfg, x, positions)
    elif cfg.family == "encdec":
        mem = _encode(params, cfg, batch)
        mem_pos = jnp.broadcast_to(jnp.arange(mem.shape[1])[None, :],
                                   mem.shape[:2])
        x = _scan_fwd(params["layers"], cfg, x, positions,
                      _stub_meta(cfg, cfg.n_layers), memory=mem,
                      memory_pos=mem_pos)
    else:
        if cfg.first_dense_ff:
            dense0 = dataclasses.replace(cfg, moe=None,
                                         d_ff=cfg.first_dense_ff)
            x = _block_fwd(params["layer0"], dense0, x, positions, no_meta)
            meta = jax.tree.map(lambda a: a[1:], meta)
        x = _scan_fwd(params["layers"], cfg, x, positions, meta)
    return L.rmsnorm(params["final_norm"], x, cfg.norm_eps)


def _stub_meta(cfg: ModelConfig, n: int) -> dict:
    return {"window": jnp.zeros((n,), jnp.int32),
            "theta": jnp.full((n,), cfg.rope_theta, jnp.float32)}


def _encode(params, cfg: ModelConfig, batch: dict) -> jax.Array:
    """Audio/text encoder over precomputed source embeddings."""
    # run the encoder in the parameter dtype regardless of the input's
    mem = batch["src_embeds"].astype(params["encoder"]["norm"].dtype)
    b, s = mem.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    enc_cfg = dataclasses.replace(cfg, family="dense")
    acfg = dataclasses.replace(enc_cfg.attn, causal=False)

    def body(carry, p_l):
        h = L.rmsnorm(p_l["ln1"], carry, cfg.norm_eps)
        carry = carry + L.attn_apply(p_l["attn"], acfg, h, pos)
        h = L.rmsnorm(p_l["ln2"], carry, cfg.norm_eps)
        return carry + L.mlp_apply(p_l["ffn"], h), None

    mem, _ = jax.lax.scan(_remat(cfg, body), mem,
                          params["encoder"]["layers"])
    return L.rmsnorm(params["encoder"]["norm"], mem, cfg.norm_eps)


def _hybrid_fwd(params, cfg: ModelConfig, x, positions):
    """zamba2: groups of `shared_every` ssm layers + shared attn block."""
    shared = params["shared_attn"]

    def shared_apply(x):
        h = L.rmsnorm(shared["ln"], x, cfg.norm_eps)
        x = x + L.attn_apply(shared["attn"], cfg.attn, h, positions)
        h = L.rmsnorm(shared["ln2"], x, cfg.norm_eps)
        return x + L.mlp_apply(shared["ffn"], h)

    def group_body(carry, p_g):
        for i in range(cfg.shared_every):
            p_l = layer_at(p_g, i)
            carry = carry + SSM.ssm_apply(
                p_l["ssm"], cfg.ssm, L.rmsnorm(p_l["ln1"], carry,
                                               cfg.norm_eps))
        return shared_apply(carry), None

    x, _ = jax.lax.scan(_remat(cfg, group_body), x, params["groups"])
    if "tail" in params:
        rem = params["tail"]["ln1"].shape[0]
        for i in range(rem):
            p_l = layer_at(params["tail"], i)
            x = x + SSM.ssm_apply(p_l["ssm"], cfg.ssm,
                                  L.rmsnorm(p_l["ln1"], x, cfg.norm_eps))
    return x


def logits_of(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        out = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        out = L.unembed_apply(params["unembed"], x)
    return shard_act(out, "batch", None, "vocab")


def train_loss(params, cfg: ModelConfig, batch: dict) -> jax.Array:
    x = forward(params, cfg, batch)
    if cfg.frontend == "vision":
        x = x[:, batch["patch_embeds"].shape[1]:]     # loss on text positions
    logits = logits_of(params, cfg, x)
    return L.softmax_xent(logits, batch["labels"], batch.get("mask"))


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode with caches
# ---------------------------------------------------------------------------
def prefill(params, cfg: ModelConfig, batch: dict):
    """Run the prompt, return (last-token logits (B, V), cache)."""
    x, positions = _embed_in(params, cfg, batch)
    meta = layer_meta(cfg)
    cache: dict = {}

    if cfg.family == "hybrid":
        x, cache = _hybrid_prefill(params, cfg, x, positions)
    elif cfg.family == "encdec":
        mem = _encode(params, cfg, batch)
        mem_pos = jnp.broadcast_to(jnp.arange(mem.shape[1])[None, :],
                                   mem.shape[:2])

        def body(carry, xs):
            p_l, m_l = xs
            h = L.rmsnorm(p_l["ln1"], carry, cfg.norm_eps)
            a, kv = L.attn_prefill(p_l["attn"], cfg.attn, h, positions)
            carry = carry + a
            h = L.rmsnorm(p_l["ln_cross"], carry, cfg.norm_eps)
            ccfg = dataclasses.replace(cfg.attn, cross=True, causal=False)
            # static cross-attention K/V from the encoder memory
            ck = jnp.einsum("bsd,dhk->bshk", mem, p_l["cross"]["wk"])
            cv = jnp.einsum("bsd,dhk->bshk", mem, p_l["cross"]["wv"])
            carry = carry + L.attn_apply(p_l["cross"], ccfg, h, positions,
                                         memory=mem, memory_pos=mem_pos)
            h = L.rmsnorm(p_l["ln2"], carry, cfg.norm_eps)
            carry = carry + L.mlp_apply(p_l["ffn"], h)
            dt = cfg.cache_dtype
            return carry, {"self": tuple(c.astype(dt) for c in kv),
                           "cross": (ck.astype(dt), cv.astype(dt))}

        x, lcache = jax.lax.scan(_remat(cfg, body), x,
                                 (params["layers"], _stub_meta(cfg, cfg.n_layers)))
        cache = {"layers": lcache, "memory_pos": mem_pos}
    else:
        if cfg.first_dense_ff:
            dense0 = dataclasses.replace(cfg, moe=None,
                                         d_ff=cfg.first_dense_ff)
            no_meta = {"window": jnp.zeros((), jnp.int32),
                       "theta": jnp.float32(cfg.rope_theta)}
            x, cache["layer0"] = _block_prefill(params["layer0"], dense0, x,
                                                positions, no_meta)
            meta = jax.tree.map(lambda a: a[1:], meta)

        def body(carry, xs):
            p_l, m_l = xs
            carry, kv = _block_prefill(p_l, cfg, carry, positions, m_l)
            return carry, kv

        x, cache["layers"] = jax.lax.scan(_remat(cfg, body), x,
                                          (params["layers"], meta))

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_of(params, cfg, x[:, -1:])[:, 0]
    cache["pos"] = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    return logits, cache


def _hybrid_prefill(params, cfg: ModelConfig, x, positions):
    shared = params["shared_attn"]

    def group_body(carry, p_g):
        ssm_caches = []
        for i in range(cfg.shared_every):
            p_l = layer_at(p_g, i)
            y, c = _ssm_prefill(p_l["ssm"], cfg.ssm,
                                L.rmsnorm(p_l["ln1"], carry, cfg.norm_eps))
            carry = carry + y
            ssm_caches.append(c)
        h = L.rmsnorm(shared["ln"], carry, cfg.norm_eps)
        a, kv = L.attn_prefill(shared["attn"], cfg.attn, h, positions)
        carry = carry + a
        h = L.rmsnorm(shared["ln2"], carry, cfg.norm_eps)
        carry = carry + L.mlp_apply(shared["ffn"], h)
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *ssm_caches)
        dt = cfg.cache_dtype
        return carry, {"ssm": stacked,
                       "shared": tuple(c.astype(dt) for c in kv)}

    x, gcache = jax.lax.scan(_remat(cfg, group_body), x, params["groups"])
    cache = {"groups": gcache}
    if "tail" in params:
        tails = []
        rem = params["tail"]["ln1"].shape[0]
        for i in range(rem):
            p_l = layer_at(params["tail"], i)
            y, c = _ssm_prefill(p_l["ssm"], cfg.ssm,
                                L.rmsnorm(p_l["ln1"], x, cfg.norm_eps))
            x = x + y
            tails.append(c)
        cache["tail"] = jax.tree.map(lambda *a: jnp.stack(a), *tails)
    return x, cache


def decode_step(params, cfg: ModelConfig, batch: dict):
    """One token: batch = {token (B,), pos (B,), cache}.

    Returns (logits (B, V), new_cache)."""
    token, pos, cache = batch["token"], batch["pos"], batch["cache"]
    x = L.embed_apply(params["embed"], token[:, None])
    x = shard_act(x, "batch", None, None)
    meta = layer_meta(cfg)

    if cfg.family == "hybrid":
        x, new_cache = _hybrid_decode(params, cfg, x, pos, cache)
    elif cfg.family == "encdec":
        def body(carry, xs):
            p_l, m_l, c_l = xs
            carry, c_l = _block_decode(p_l, cfg, carry, pos, m_l, c_l,
                                       memory_pos=cache["memory_pos"])
            return carry, c_l
        x, lcache = jax.lax.scan(body, x, (params["layers"],
                                           _stub_meta(cfg, cfg.n_layers),
                                           cache["layers"]))
        new_cache = {"layers": lcache, "memory_pos": cache["memory_pos"]}
    else:
        new_cache = {}
        if cfg.first_dense_ff:
            dense0 = dataclasses.replace(cfg, moe=None,
                                         d_ff=cfg.first_dense_ff)
            no_meta = {"window": jnp.zeros((), jnp.int32),
                       "theta": jnp.float32(cfg.rope_theta)}
            x, new_cache["layer0"] = _block_decode(
                params["layer0"], dense0, x, pos, no_meta, cache["layer0"])
            meta = jax.tree.map(lambda a: a[1:], meta)

        def body(carry, xs):
            p_l, m_l, c_l = xs
            carry, c_l = _block_decode(p_l, cfg, carry, pos, m_l, c_l)
            return carry, c_l

        x, new_cache["layers"] = jax.lax.scan(
            body, x, (params["layers"], meta, cache["layers"]))

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_of(params, cfg, x)[:, 0]
    new_cache["pos"] = pos + 1
    if "memory_pos" in cache and "memory_pos" not in new_cache:
        new_cache["memory_pos"] = cache["memory_pos"]
    return logits, new_cache


def chunk_step(params, cfg: ModelConfig, batch: dict):
    """Prefill one chunk of C tokens against a running per-sequence cache.

    batch = {tokens (B, C), pos (B,), n_valid (B,), cache}; positions
    pos..pos+C-1 are written into the cache, `pos` advances by `n_valid`
    (the real token count — the chunk tail may be padding), and the
    returned logits (B, V) are read at local index n_valid-1, i.e. at the
    last REAL token.  Serving uses this to stream long prompts through the
    decode path chunk-by-chunk (repro.serve) so a long prefill never
    stalls running decodes for more than one chunk's latency.

    Supported for attention-cache families (dense/moe/mla_moe/encdec);
    ssm/hybrid prompts must prefill whole (their scan state has no
    positional indexing to chunk against).
    """
    if cfg.family in ("ssm", "hybrid"):
        raise ValueError(f"chunked prefill unsupported for {cfg.family}: "
                         "state-space caches admit no positional chunking")
    tokens, n_valid = batch["tokens"], batch["n_valid"]
    cache = batch["cache"]
    # pos defaults to the cache's own cursor so callers can donate the
    # cache without aliasing its pos buffer into a second operand
    pos = batch.get("pos", cache["pos"])
    x = L.embed_apply(params["embed"], tokens)
    x = shard_act(x, "batch", None, None)
    meta = layer_meta(cfg)

    if cfg.family == "encdec":
        def body(carry, xs):
            p_l, m_l, c_l = xs
            carry, c_l = _block_decode(p_l, cfg, carry, pos, m_l, c_l,
                                       memory_pos=cache["memory_pos"])
            return carry, c_l
        x, lcache = jax.lax.scan(body, x, (params["layers"],
                                           _stub_meta(cfg, cfg.n_layers),
                                           cache["layers"]))
        new_cache = {"layers": lcache, "memory_pos": cache["memory_pos"]}
    else:
        new_cache = {}
        if cfg.first_dense_ff:
            dense0 = dataclasses.replace(cfg, moe=None,
                                         d_ff=cfg.first_dense_ff)
            no_meta = {"window": jnp.zeros((), jnp.int32),
                       "theta": jnp.float32(cfg.rope_theta)}
            x, new_cache["layer0"] = _block_decode(
                params["layer0"], dense0, x, pos, no_meta, cache["layer0"])
            meta = jax.tree.map(lambda a: a[1:], meta)

        def body(carry, xs):
            p_l, m_l, c_l = xs
            carry, c_l = _block_decode(p_l, cfg, carry, pos, m_l, c_l)
            return carry, c_l

        x, new_cache["layers"] = jax.lax.scan(
            body, x, (params["layers"], meta, cache["layers"]))

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    # unembed ONLY the last real token of each row (C-fold cheaper than a
    # full-chunk logits_of, and identical numerics at that position)
    idx = jnp.maximum(n_valid - 1, 0)[:, None, None]
    x_last = jnp.take_along_axis(x, jnp.broadcast_to(
        idx, (x.shape[0], 1, x.shape[2])), axis=1)
    logits = logits_of(params, cfg, x_last)[:, 0]
    new_cache["pos"] = pos + n_valid
    if "memory_pos" in cache and "memory_pos" not in new_cache:
        new_cache["memory_pos"] = cache["memory_pos"]
    return logits, new_cache


def _hybrid_decode(params, cfg: ModelConfig, x, pos, cache):
    shared = params["shared_attn"]

    def group_body(carry, xs):
        p_g, c_g = xs
        ssm_new = []
        for i in range(cfg.shared_every):
            p_l = layer_at(p_g, i)
            c_l = layer_at(c_g["ssm"], i)
            h = L.rmsnorm(p_l["ln1"], carry, cfg.norm_eps)
            y, c_l = SSM.ssm_decode(p_l["ssm"], cfg.ssm, h, c_l)
            carry = carry + y
            ssm_new.append(c_l)
        h = L.rmsnorm(shared["ln"], carry, cfg.norm_eps)
        a, kv = L.attn_decode(shared["attn"], cfg.attn, h, c_g["shared"], pos)
        carry = carry + a
        h = L.rmsnorm(shared["ln2"], carry, cfg.norm_eps)
        carry = carry + L.mlp_apply(shared["ffn"], h)
        return carry, {"ssm": jax.tree.map(lambda *a: jnp.stack(a), *ssm_new),
                       "shared": kv}

    x, gcache = jax.lax.scan(group_body, x, (params["groups"],
                                             cache["groups"]))
    new_cache = {"groups": gcache}
    if "tail" in params:
        rem = params["tail"]["ln1"].shape[0]
        tails = []
        for i in range(rem):
            p_l = layer_at(params["tail"], i)
            c_l = layer_at(cache["tail"], i)
            h = L.rmsnorm(p_l["ln1"], x, cfg.norm_eps)
            y, c_l = SSM.ssm_decode(p_l["ssm"], cfg.ssm, h, c_l)
            x = x + y
            tails.append(c_l)
        new_cache["tail"] = jax.tree.map(lambda *a: jnp.stack(a), *tails)
    return x, new_cache


# ---------------------------------------------------------------------------
# Fresh decode caches (zeros; use jax.eval_shape over this for specs)
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               src_len: int = 0) -> dict:
    dt = cfg.cache_dtype
    kv = lambda n, s: (jnp.zeros((n, batch, s, cfg.n_kv_heads, cfg.head_dim),
                                 dt),
                       jnp.zeros((n, batch, s, cfg.n_kv_heads, cfg.head_dim),
                                 dt))

    def ssm_stack(n):
        c = SSM.ssm_cache_def(cfg.ssm, batch)
        return jax.tree.map(lambda a: jnp.zeros((n,) + a.shape, a.dtype), c)

    if cfg.family == "hybrid":
        k = cfg.shared_every
        n_groups, rem = divmod(cfg.n_layers, k)
        sk, sv = kv(n_groups, max_len)
        cache = {"groups": {
            "ssm": jax.tree.map(
                lambda a: jnp.zeros((n_groups, k) + a.shape[1:], a.dtype),
                ssm_stack(k)),
            "shared": (sk, sv)}}
        if rem:
            cache["tail"] = ssm_stack(rem)
    elif cfg.family == "encdec":
        sk, sv = kv(cfg.n_layers, max_len)
        ck, cv = kv(cfg.n_layers, src_len or max_len)
        cache = {"layers": {"self": (sk, sv), "cross": (ck, cv)},
                 "memory_pos": jnp.broadcast_to(
                     jnp.arange(src_len or max_len)[None, :],
                     (batch, src_len or max_len))}
    elif cfg.family == "ssm":
        cache = {"layers": ssm_stack(cfg.n_layers)}
    elif cfg.family == "mla_moe":
        n = cfg.n_layers - (1 if cfg.first_dense_ff else 0)
        mk = lambda lead: (
            jnp.zeros(lead + (batch, max_len, cfg.mla.kv_lora), dt),
            jnp.zeros(lead + (batch, max_len, cfg.mla.qk_rope), dt))
        cache = {"layers": mk((n,))}
        if cfg.first_dense_ff:
            cache["layer0"] = mk(())
    else:
        n = cfg.n_layers
        cache = {"layers": kv(n, max_len)}
    cache["pos"] = jnp.zeros((batch,), jnp.int32)
    return cache
