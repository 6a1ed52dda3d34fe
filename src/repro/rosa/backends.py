"""Execution backends for the ROSA optical matmul + the `RosaConfig` knob.

This module is the single home of the paper's MAC semantics (previously
`core/onn_linear.py`).  A *backend* is the contraction primitive that turns
noise-placed operands into outputs:

    dense   exact einsum contraction — the ideal-OSA closed form (Eq. 2),
            also used for non-optical layers routed by `rosa.Engine`.
    ref     pure-jnp OSA pipeline (signed-digit planes + slot gains, Eq. 1)
            — the oracle, honours OSAConfig non-idealities.
    pallas  the Pallas TPU kernel in kernels/osa_matmul (bit-plane
            decomposition + per-plane MXU matmuls).
    fused   the Pallas TPU megakernel in kernels/rosa_fused: quantize,
            MRR realization, shift-and-add and dequantize in one launch.

Backends are registered by name (`register_backend`) and selected by
`RosaConfig.backend`; the default "auto" resolves per platform (fused on
TPU, ref elsewhere).  Off-TPU the Pallas kernels run under the
interpreter, which is the CPU test path only.

Forward semantics (mixed digital-analog mode, Sec. 2-3.1):

  WS mapping: weights are programmed onto TO-tuned analog MRRs through the
    noisy voltage chain (mrr.realize_weights); activations take the exact
    digital EO path (8-bit signed-digit streams) and accumulate via OSA.
  IS mapping: the roles swap — activations are realized on the noisy analog
    MRRs, weights travel the exact digital path.
  ANALOG mode (DEAP baseline): both operands pass the noisy analog chain.

Backward semantics: straight-through — gradients flow as if the matmul were
exact, which makes every model in the zoo noise-aware-trainable (QAT) with
zero graph surgery.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import mrr, osa, quant
from repro.core.constants import ComputeMode, Mapping


@dataclasses.dataclass(frozen=True)
class RosaConfig:
    """Per-layer execution config for the optical backend."""

    mapping: Mapping = Mapping.WS
    mode: ComputeMode = ComputeMode.MIXED
    quant_bits: int = 8
    pam_bits: int = 1
    noise: mrr.NoiseModel = mrr.IDEAL
    osa_cfg: osa.OSAConfig = osa.IDEAL_OSA
    mrr_params: mrr.MRRParams = mrr.DEFAULT_PARAMS
    backend: str = "auto"   # registered backend name, or "auto" (platform)
    act_per_vector: bool = False  # quantize each activation ROW at its own
    #   full-scale.  Default False preserves historic QAT numerics; serving
    #   (repro.serve) turns it on so a request's logits cannot depend on
    #   which other requests share its decode batch (per-tensor scales
    #   couple rows through one absmax — the differential suite caught it)

    @property
    def qcfg(self) -> quant.QuantConfig:
        """Quantization config derived from `quant_bits`."""
        return quant.QuantConfig(bits=self.quant_bits)


DEFAULT = RosaConfig()


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------
# Two backend classes share the registry:
#   * contraction backends (the default) take noise-placed operands:
#     (x_eff (M,K), w_eff (K,N), cfg: RosaConfig | None) -> (M,N);
#   * RAW backends (`raw=True`) replace the whole conditioning+contraction
#     pipeline: (x, w, cfg, *, key, var, gate, mgate) -> (M,N).  The fused
#     megakernel is raw — quantize/realize/OSA/dequant happen inside one
#     pallas_call, so _forward must hand it the UNconditioned operands.
Backend = Callable[..., jax.Array]

_BACKENDS: dict[str, Backend] = {}
_RAW_BACKENDS: set[str] = set()


def register_backend(name: str, raw: bool = False):
    """Decorator: register a backend under `name` (`raw=True` for backends
    that fuse operand conditioning into the contraction)."""
    def deco(fn: Backend) -> Backend:
        """Register `fn` under `name` and return it unchanged."""
        _BACKENDS[name] = fn
        if raw:
            _RAW_BACKENDS.add(name)
        return fn
    return deco


def backend_names() -> list[str]:
    """Registered contraction-backend names."""
    return sorted(_BACKENDS)


def is_raw_backend(name: str) -> bool:
    """Whether `name` registered as a raw (fully-fused) backend."""
    return name in _RAW_BACKENDS


def resolve_backend(name: str) -> tuple[str, Backend]:
    """Resolve a backend name ("auto" -> platform pick) to (name, fn).

    On TPU "auto" picks the fused megakernel (ONE pallas_call for the
    whole analog pipeline — ROADMAP's single biggest raw-speed lever);
    elsewhere the pure-jnp composed reference.  The ideal-QAT shortcut in
    `_forward` still short-circuits before any backend runs.
    """
    if name == "auto":
        name = "fused" if jax.default_backend() == "tpu" else "ref"
    try:
        return name, _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: {backend_names()}"
        ) from None


@register_backend("dense")
def _dense_backend(x: jax.Array, w: jax.Array, cfg=None) -> jax.Array:
    return x @ w


@register_backend("ref")
def _ref_backend(x: jax.Array, w: jax.Array, cfg: RosaConfig) -> jax.Array:
    return osa.osa_matmul_ref(x, w, cfg.osa_cfg, cfg.qcfg,
                              per_vector=cfg.act_per_vector)


@register_backend("pallas")
def _pallas_backend(x: jax.Array, w: jax.Array, cfg: RosaConfig) -> jax.Array:
    # deferred import: pulls in jax.experimental.pallas only when routed here
    from repro.kernels.osa_matmul import ops as osa_ops
    return osa_ops.osa_matmul(x, w, quant_bits=cfg.quant_bits,
                              pam_bits=cfg.pam_bits,
                              per_vector=cfg.act_per_vector)


@register_backend("fused", raw=True)
def _fused_backend(x: jax.Array, w: jax.Array, cfg: RosaConfig, *,
                   key=None, var=None, gate=None, mgate=None) -> jax.Array:
    # deferred import: pulls in jax.experimental.pallas only when routed here
    from repro.kernels.rosa_fused import ops as fused_ops
    # decomposition radix follows osa_cfg (what the composed ref chain
    # uses), NOT RosaConfig.pam_bits (which only the per-op pallas backend
    # reads) — the fused path must price and compute like the chain it fuses
    return fused_ops.rosa_fused_matmul(
        x, w, key, var, gate, mgate, mapping=cfg.mapping, mode=cfg.mode,
        quant_bits=cfg.quant_bits, pam_bits=cfg.osa_cfg.pam_bits,
        act_per_vector=cfg.act_per_vector, noise=cfg.noise,
        osa_cfg=cfg.osa_cfg, p=cfg.mrr_params)


# ---------------------------------------------------------------------------
# Operand conditioning (noise placement)
# ---------------------------------------------------------------------------
def _noisy_realize(t: jax.Array, cfg: RosaConfig, key: jax.Array | None,
                   var: mrr.StaticVariation | None = None,
                   per_vector: bool = False):
    """Quantize a tensor to cfg.quant_bits and realize it on analog MRRs.

    Values are normalized to the MRR weight range [q_min, q_max],
    programmed through the physical chain with DAC/thermal noise and the
    chip's static variation, and de-normalized.  This is where WS puts
    weights and IS puts activations.

    Weights are programmed once and share one per-tensor full-scale;
    activations (`per_vector=True`) are driven vector-at-a-time, each
    (M, K) row at its own DAC full-scale — batch outliers must not
    compress every other sample's analog resolution.
    """
    scale = quant.absmax_scale(t, per_vector)
    q = quant.fake_quant(t / scale, cfg.qcfg)          # 8-bit grid in [-1,1]
    w = mrr.realize_weights(q, key, cfg.mrr_params, cfg.noise, var)
    return w * scale


def _digital_path(t: jax.Array, cfg: RosaConfig,
                  per_vector: bool = False):
    """Exact digital EO encoding: quantization is the only error source.
    `per_vector` applies to the streamed (activation) operand only —
    weights always share one programmed full-scale.
    """
    return quant.fake_quant(t, cfg.qcfg, per_vector=per_vector)


# orientation-aware variation broadcast now lives in core (the fused kernel
# wrapper needs the identical convention); keep the historic private name.
_expand_lanes = mrr.expand_lanes


def realization_rms_error(t: jax.Array, cfg: RosaConfig,
                          var: mrr.StaticVariation | None = None,
                          per_vector: bool = False) -> jax.Array:
    """RMS programming error of realizing `t` on this chip (scalar, no key).

    The deviation between the ideal quantized operand and its *noiseless*
    analog realization under the chip's static variation, in normalized
    weight units.  Per-shot noise is deliberately excluded — it is i.i.d.
    across chips, so only the static part discriminates between them.  This
    is the control-variate surrogate feature of
    `repro.robust.ensemble.estimate_ensemble`: it costs one
    `realize_weights` sweep per (chip, layer) instead of a forward pass
    over the evaluation set, and is vmappable over a chip ensemble.
    """
    scale = quant.absmax_scale(t, per_vector)
    q = quant.fake_quant(t / scale, cfg.qcfg)
    w = mrr.realize_weights(q, None, cfg.mrr_params, mrr.IDEAL,
                            _expand_lanes(var, t))
    return jnp.sqrt(jnp.mean((w - q) ** 2))


def _analog_operand(t: jax.Array, cfg: RosaConfig, key: jax.Array | None,
                    var: mrr.StaticVariation | None,
                    gate: jax.Array | None, per_vector: bool = False):
    """Condition the analog-side operand: noisy realization under per-shot
    noise + static variation, optionally convex-blended against the exact
    digital path by a traced `gate` in [0, 1] (the vectorized
    perturb-one-layer selector of `repro.robust.sensitivity`).
    """
    clean = _digital_path(t, cfg, per_vector and cfg.act_per_vector)
    if cfg.noise.is_ideal and var is None and gate is None:
        return clean
    noisy = _noisy_realize(t, cfg, key, var, per_vector)
    if gate is None:
        return noisy
    return clean + gate * (noisy - clean)


def condition_weight(w: jax.Array, cfg: RosaConfig | None,
                     key: jax.Array | None,
                     var: mrr.StaticVariation | None = None,
                     gate: jax.Array | None = None):
    """Weight conditioning outside the matmul fast path (per-channel
    contractions like depthwise conv): analog realization + gate blend.
    Identity when the layer is dense or fully ideal (matching the historic
    dwconv behaviour: no fake-quant on the ideal path).
    """
    if cfg is None or (cfg.noise.is_ideal and var is None and gate is None):
        return w
    noisy = _noisy_realize(w, cfg, key, _expand_lanes(var, w))
    if gate is None:
        return noisy
    return w + gate * (noisy - w)


def _forward(x: jax.Array, w: jax.Array, cfg: RosaConfig,
             key: jax.Array | None,
             var: mrr.StaticVariation | None = None,
             gate: jax.Array | None = None,
             mgate: jax.Array | None = None) -> jax.Array:
    if cfg.mode is ComputeMode.MIXED:
        if cfg.noise.is_ideal and cfg.osa_cfg.is_ideal \
                and cfg.backend in ("auto", "dense") \
                and var is None and gate is None and mgate is None:
            # exactness-preserving shortcut: ideal OSA over signed-digit
            # planes == fake-quant matmul (tests/test_osa.py asserts this),
            # so QAT training skips the 7-plane decomposition entirely.
            # Guarded on the UNRESOLVED name: "auto" must stay fast for QAT
            # even when it would resolve to pallas on TPU, while an EXPLICIT
            # "ref"/"pallas" request always runs its registered pipeline.
            # ("dense" is algebraically the shortcut itself.)
            return _digital_path(x, cfg, cfg.act_per_vector) \
                @ _digital_path(w, cfg)
        bname, contract = resolve_backend(cfg.backend)
        if bname in _RAW_BACKENDS:
            # fully-fused pipeline: conditioning happens inside the kernel
            return contract(x, w, cfg, key=key, var=var, gate=gate,
                            mgate=mgate)
        if mgate is not None:
            # mapping superposition: realize BOTH orientations and blend the
            # OPERANDS by the traced selector (exact for mgate in {0, 1}) —
            # a whole {layer: IS|WS} plan becomes a float vector, so plan
            # candidates are a vmap axis (repro.robust.sensitivity's
            # MC-verified hybrid search).  One contraction either way.
            k_w, k_x = (jax.random.split(key) if key is not None
                        else (None, None))
            w_ws = _analog_operand(w, cfg, k_w, _expand_lanes(var, w), gate)
            x_is = _analog_operand(x, cfg, k_x, var, gate, per_vector=True)
            w_eff = (1.0 - mgate) * w_ws + mgate * _digital_path(w, cfg)
            x_eff = (1.0 - mgate) * _digital_path(x, cfg,
                                                  cfg.act_per_vector) \
                + mgate * x_is
        elif cfg.mapping in (Mapping.WS, Mapping.GEMM):
            w_eff = _analog_operand(w, cfg, key, _expand_lanes(var, w), gate)
            x_eff = _digital_path(x, cfg, cfg.act_per_vector)
        else:  # IS: inputs on the analog rings, weights exact digital
            w_eff = _digital_path(w, cfg)
            x_eff = _analog_operand(x, cfg, key, var, gate, per_vector=True)
        return contract(x_eff, w_eff, cfg)
    elif cfg.mode is ComputeMode.ANALOG:
        bname, contract = resolve_backend(cfg.backend)
        if bname in _RAW_BACKENDS:
            # single-shot analog readout, fused end to end (mgate is
            # ignored in ANALOG mode, matching the composed branch below)
            return contract(x, w, cfg, key=key, var=var, gate=gate,
                            mgate=None)
        if key is not None:
            k_w, k_x = jax.random.split(key)
        else:
            k_w = k_x = None
        w_eff = _analog_operand(w, cfg, k_w, _expand_lanes(var, w), gate)
        x_eff = _analog_operand(x, cfg, k_x, var, gate)
        return x_eff @ w_eff                      # single-shot analog readout
    elif cfg.mode is ComputeMode.DIGITAL:
        return _digital_path(x, cfg) @ _digital_path(w, cfg)
    raise ValueError(cfg.mode)


# ---------------------------------------------------------------------------
# The drop-in matmul with straight-through gradients
# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(2,))
def rosa_matmul(x: jax.Array, w: jax.Array, cfg: RosaConfig = DEFAULT,
                key: jax.Array | None = None,
                var: mrr.StaticVariation | None = None,
                gate: jax.Array | None = None,
                mgate: jax.Array | None = None) -> jax.Array:
    """Optical matmul  y = x @ w  through the configured ROSA pipeline.

    x: (..., K) activations; w: (K, N) weights; returns (..., N).
    `var` pins one chip's static device variation on the analog operand;
    `gate` (traced scalar in [0, 1]) blends the analog path against the
    exact digital one; `mgate` (traced, {0=WS, 1=IS}) superposes the two
    mapping orientations.  Straight-through gradients w.r.t. both x and w
    (noise, variation and gates are treated as non-differentiable).
    """
    lead = x.shape[:-1]
    y = _forward(x.reshape(-1, x.shape[-1]), w, cfg, key, var, gate, mgate)
    return y.reshape(*lead, w.shape[-1])


def _fwd(x, w, cfg, key, var, gate, mgate):
    return rosa_matmul(x, w, cfg, key, var, gate, mgate), (x, w)


def _bwd(cfg, res, g):
    x, w = res
    g2 = g.reshape(-1, g.shape[-1])
    x2 = x.reshape(-1, x.shape[-1])
    dx = (g2 @ w.T).reshape(x.shape)
    dw = x2.T @ g2
    return dx, dw, None, None, None, None


rosa_matmul.defvjp(_fwd, _bwd)


def make_backend(cfg: RosaConfig):
    """Callable matmul closure (legacy helper, kept for compatibility)."""
    def matmul(x, w, key=None):
        """Closure: `x @ w` through `rosa_matmul` with this config."""
        return rosa_matmul(x, w, cfg, key)
    return matmul
