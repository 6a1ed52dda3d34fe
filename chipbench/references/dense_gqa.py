"""Plain reference: a dense GQA decoder whose MLP GEMMs run the ROSA optical MAC.

Written from the published descriptions, in straightforward `jax.numpy`,
and independent of the code under test: it imports nothing of the program.

- The decoder (Qwen3 and DeepSeek-LLM are both of this form): token
  embedding; per layer RMSNorm -> GQA attention with rotary embeddings
  (rotate-half form) and, where `qk_norm`, RMSNorm over each head's q and k
  -> residual add -> RMSNorm -> SwiGLU MLP -> residual add; final RMSNorm;
  an untied LM head.  K and V are rounded to the configuration's KV-cache
  dtype before attention reads them, as a served cache holds them.
- The optical MAC (paper Sec. 3.1-3.3), under the input-stationary (IS)
  mapping the configuration states: the weights take the exact digital
  path (8-bit symmetric quantization over the whole matrix); each
  activation row is normalized by its absmax, quantized to 8 bits,
  programmed onto the rings (closed-form inverse of the transfer chain,
  Eqs. 3-7, drive clipped to [V_min, V_max]) and read back through the
  chain with the chip's static per-lane variation (driver offset, thermal
  bias, resonance mismatch); the ideal optical shift-and-add then
  contracts the row's 8-bit codes (requantized at its own absmax) with the
  weights' codes over qmax, and the two full-scales rescale the result.
- The chip: one fabricated chip drawn from `variation_seed`, per GEMM name
  a lane vector of length K for each static field, the field's sigma times
  a standard normal, from the name's CRC folded into the seed's key.

`precision` is one of PRECISIONS: "float32" computes everything in float32
with float32 matmuls (`highest`); "float32_high" and "float32_default"
keep float32 arrays with three-pass and one-pass bfloat16 matmuls;
"bfloat16" holds the weights and activations in bfloat16 with one-pass
matmuls, the transfer chain kept in float32.  `stated` names the one a
configuration states; `LOWER` the next one below it, the control.

The reference runs one jitted layer at a time, so that beside the served
weights it holds one layer's temporaries and not the whole model's.
"""

from __future__ import annotations

import functools
import json
import math
import zlib

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38
# precision -> (array dtype, matmul precision)
PRECISIONS = {"float32": (jnp.float32, "highest"),
              "float32_high": (jnp.float32, "high"),
              "float32_default": (jnp.float32, "default"),
              "bfloat16": (jnp.bfloat16, "default")}
# the nearest precision below each: three passes below float32 at
# `highest`, bfloat16 arrays below any other float32
LOWER = {"float32": "float32_high", "float32_high": "bfloat16",
         "float32_default": "bfloat16"}
MLP_ROWS = 1024
NORMS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")


def stated(cfg: dict) -> str:
    """The PRECISIONS entry a configuration states (`precision.params`
    arrays at `precision.matmul`)."""
    want = (jnp.dtype(cfg["precision"]["params"]), cfg["precision"]["matmul"])
    for name, (dt, matmul) in PRECISIONS.items():
        if (jnp.dtype(dt), matmul) == want:
            return name
    raise ValueError(f"no reference precision for {want}")


def leaf_init(names: tuple[str, ...], shape) -> tuple[str, float]:
    """How the benchmark draws each weight: norms around one, the
    embedding standard normal, every matrix at 1/sqrt(fan_in)."""
    leaf = names[-1]
    if leaf in NORMS:
        return "around_one", 0.1
    if leaf == "embed":
        return "normal", 1.0
    if leaf == "unembed":
        return "normal", shape[0] ** -0.5
    if names[:2] == ("layers", "attn") and leaf in ("wq", "wk", "wv"):
        return "normal", shape[1] ** -0.5
    if names[:2] == ("layers", "attn") and leaf == "wo":
        return "normal", (shape[1] * shape[2]) ** -0.5
    if names[:2] == ("layers", "ffn") and leaf in ("wi", "wo"):
        return "normal", shape[1] ** -0.5
    raise ValueError(f"no init rule for weight {'/'.join(names)}")


# ---------------------------------------------------------------------------
# The microring transfer chain (paper Eqs. 3-7)
# ---------------------------------------------------------------------------
def heater_coupling(m: dict) -> float:
    """Heater coupling that makes the V_min -> V_max sweep shift the
    resonance by exactly `max_shift_nm` (bisection on a monotone map)."""
    def shift(kappa: float) -> float:
        def dl(v: float) -> float:
            dt = kappa * (v * v / m["r_heater"]) * 1e3 * m["r_thermal"]
            return m["lambda_0"] * m["beta"] * dt / (m["n_eff"] + m["beta"] * dt)
        return dl(m["v_max"]) - dl(m["v_min"])

    lo, hi = 0.0, 1.0
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if shift(mid) < m["max_shift_nm"] else (lo, mid)
    return 0.5 * (lo + hi)


def _t_diff(lam, m):
    det = lam - m["lambda_ref"]
    g2 = m["gamma"] * m["gamma"]
    return 2.0 * (g2 / (det * det + g2)) - 1.0


def _weight_of_voltage(v, m, kappa, dv=0.0, ddt=0.0, dlam=0.0):
    v = v + dv
    dt = kappa * (v * v / m["r_heater"]) * 1e3 * m["r_thermal"] + ddt
    bdt = m["beta"] * dt
    lam = m["lambda_0"] + (m["lambda_0"] * bdt / (m["n_eff"] + bdt) + dlam)
    return _t_diff(lam, m)


def _endpoints(m, kappa):
    t_hi = _weight_of_voltage(jnp.float32(m["v_min"]), m, kappa)
    t_lo = _weight_of_voltage(jnp.float32(m["v_max"]), m, kappa)
    return t_hi, t_lo


def realize(q, m, kappa, var):
    """Program normalized values `q` in [-1, 1] onto the rings and read
    them back through a chip with static fields `var` (per last axis)."""
    t_hi, t_lo = _endpoints(m, kappa)
    td = t_lo + (jnp.clip(q, -1.0, 1.0) + 1.0) / 2.0 * (t_hi - t_lo)
    tdrop = 0.5 * (td + 1.0)
    det = m["gamma"] * jnp.sqrt(jnp.maximum(1.0 / tdrop - 1.0, 0.0))
    u = (m["lambda_ref"] + det - m["lambda_0"]) / m["lambda_0"]
    dt = jnp.maximum(m["n_eff"] * u / (m["beta"] * (1.0 - u)), 0.0)
    v2 = dt / m["r_thermal"] / (kappa * 1e3) * m["r_heater"]
    v = jnp.clip(jnp.sqrt(jnp.maximum(v2, 0.0)), m["v_min"], m["v_max"])
    td = _weight_of_voltage(v, m, kappa, var["dv"], var["ddt"], var["dlam"])
    return -1.0 + 2.0 * (td - t_lo) / (t_hi - t_lo)


def sample_chip(optical: dict, lanes: dict) -> dict:
    """The fabricated chip: {gemm name: {"dv", "ddt", "dlam"} (K,)}."""
    key = jax.random.PRNGKey(optical["variation_seed"])
    sig = optical["variation"]
    chip = {}
    for name, k in lanes.items():
        lk = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        k_v, k_t, k_l = jax.random.split(lk, 3)
        chip[name] = {
            "dv": sig["sigma_v_static"] * jax.random.normal(k_v, (k,)),
            "ddt": sig["sigma_dt_static"] * jax.random.normal(k_t, (k,)),
            "dlam": sig["sigma_lambda_fab"] * jax.random.normal(k_l, (k,))}
    return chip


def _quant(x, qmax, axis=None):
    """Symmetric quantization at absmax (per tensor, or per `axis`):
    integer codes and the scale they are in units of."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None),
                    1e-8)
    return jnp.clip(jnp.round(x / s * qmax), -qmax, qmax), s


def _fake_quant(x, qmax, axis=None):
    q, s = _quant(x, qmax, axis)
    return x + (q * (s / qmax) - x)


def optical_is(x, w, var, optical: dict, kappa: float):
    """y = x @ w through the IS-mapped optical MAC.  x (M, K), w (K, N)."""
    qmax = 2 ** (optical["quant_bits"] - 1) - 1
    m = optical["mrr"]
    dt = x.dtype
    qw, sw = _quant(w.astype(jnp.float32), qmax)
    wn = (qw * (1.0 / qmax)).astype(dt)
    x32 = x.astype(jnp.float32)
    sx = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1, keepdims=True), 1e-8)
    q = _fake_quant(x32 / sx, qmax)
    x_eff = (realize(q, m, kappa, var) * sx).astype(dt)
    codes, s2 = _quant(x_eff, qmax, axis=-1)
    return (codes.astype(dt) @ wn) * (s2 * (sw / qmax)).astype(dt)


# ---------------------------------------------------------------------------
# The decoder
# ---------------------------------------------------------------------------
def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps).astype(x.dtype) * scale


def rope(x, pos, theta):
    """x (S, heads, hd); rotate-half form, frequencies theta^(-i/half)."""
    half = x.shape[-1] // 2
    freq = jnp.exp(-jnp.log(jnp.float32(theta))
                   * (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freq
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, q_block: int):
    """Causal GQA over one sequence.  q (S, H, hd); k, v (S, KV, hd)."""
    s, h, hd = q.shape
    kv = k.shape[1]
    q = q.reshape(s, kv, h // kv, hd)
    kpos = jnp.arange(s)
    outs = []
    for lo in range(0, s, q_block):
        qb = q[lo:lo + q_block]
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k).astype(jnp.float32)
        sc = sc * hd ** -0.5
        qpos = lo + jnp.arange(qb.shape[0])
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v))
    return jnp.concatenate(outs, 0).reshape(s, h, hd)


def _layer(x, layers, i, chip, pos, *, cfg: dict, precision: str):
    """Layer `i` of the stacked `layers` over the sequence x (S, D)."""
    dt = PRECISIONS[precision][0]
    cache_dt = jnp.dtype(cfg["precision"]["kv_cache"])
    optical = cfg["optical"]
    kappa = heater_coupling(optical["mrr"])
    lay = jax.tree.map(
        lambda t: jax.lax.dynamic_index_in_dim(t, i, keepdims=False)
        .astype(dt), layers)
    a, f = lay["attn"], lay["ffn"]
    eps = cfg["norm_eps"]
    s = x.shape[0]
    h = rmsnorm(x, lay["ln1"], eps)
    q = jnp.einsum("sd,dhk->shk", h, a["wq"])
    k = jnp.einsum("sd,dhk->shk", h, a["wk"])
    v = jnp.einsum("sd,dhk->shk", h, a["wv"])
    if cfg["qk_norm"]:
        q = rmsnorm(q, a["q_norm"], 1e-6)
        k = rmsnorm(k, a["k_norm"], 1e-6)
    q = rope(q, pos, cfg["rope_theta"])
    k = rope(k, pos, cfg["rope_theta"]).astype(cache_dt).astype(dt)
    v = v.astype(cache_dt).astype(dt)
    o = attention(q, k, v, q_block=256)
    x = x + jnp.einsum("shk,hkd->sd", o, a["wo"])
    d, _, ff = f["wi"].shape
    wi = f["wi"].reshape(d, 2 * ff)

    def mlp(xb):
        # every row of the MLP is its own: blocks of rows bound the
        # temporaries of the long sequences
        h = rmsnorm(xb, lay["ln2"], eps)
        gu = optical_is(h, wi, chip["mlp/wi"], optical, kappa)
        gu = gu.reshape(xb.shape[0], 2, ff)
        g = jax.nn.silu(gu[:, 0]) * gu[:, 1]
        return xb + optical_is(g, f["wo"], chip["mlp/wo"], optical, kappa)

    rows = math.gcd(s, MLP_ROWS)
    return jax.lax.map(mlp, x.reshape(s // rows, rows, d)).reshape(s, d)


def _embed(embed, tokens, *, precision: str):
    return jnp.take(embed, tokens, axis=0).astype(PRECISIONS[precision][0])


def _head(x, at, final_norm, unembed, *, cfg: dict, precision: str):
    dt = PRECISIONS[precision][0]
    x = rmsnorm(jnp.take(x, at, axis=0), final_norm.astype(dt),
                cfg["norm_eps"])
    return (x @ unembed.astype(dt)).astype(jnp.float32)


_JITTED: dict = {}


def _jitted(fn, cfg: dict, precision: str):
    key = (fn.__name__, json.dumps(cfg, sort_keys=True), precision)
    if key not in _JITTED:
        kw = {"precision": precision}
        if fn is not _embed:
            kw["cfg"] = cfg
        _JITTED[key] = jax.jit(functools.partial(fn, **kw))
    return _JITTED[key]


def logits(params, chip, tokens, at, cfg: dict, precision: str):
    """Logits (len(at), V) at positions `at` of the sequence `tokens` (S,),
    one jitted layer at a time, under `precision`'s matmul precision."""
    with jax.default_matmul_precision(PRECISIONS[precision][1]):
        x = _jitted(_embed, cfg, precision)(params["embed"], tokens)
        pos = jnp.arange(tokens.shape[0])
        layer = _jitted(_layer, cfg, precision)
        for i in range(cfg["n_layers"]):
            x = layer(x, params["layers"], jnp.int32(i), chip, pos)
        return _jitted(_head, cfg, precision)(
            x, at, params["final_norm"], params["unembed"])


def gemm_lanes(cfg: dict) -> dict:
    """Reduction width K of each optical GEMM of one layer."""
    return {"mlp/wi": cfg["d_model"], "mlp/wo": cfg["d_ff"]}
