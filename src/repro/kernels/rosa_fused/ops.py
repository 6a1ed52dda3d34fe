"""Jitted public wrapper around the fused ROSA megakernel.

Handles everything the tiled kernel does not do, in the order the composed
`rosa.backends` chain fixes:

  * the activation operand — conditioned (digital EO path, noisy analog
    realization, gate blend, mapping-gate superposition) by
    `ref.condition_x` / `ref.analog_operand`, the composed chain's own
    functions, and requantized with `core.quant.quantize`, exactly as
    `osa.osa_matmul_ref` requantizes.  The kernel therefore contracts the
    very codes the composed chain contracts: no float-order difference
    between Mosaic and XLA can move a code across its rounding boundary.
    The (M, K) operand is conditioned once here rather than once per
    output tile; the O(T*M*K*N) contraction and the (K, N) weight
    realization stay fused in the kernel.
  * quantization full-scales — global (or per-row) absmax reductions,
    computed here and streamed in as the (M, 1) scale operand.
  * PRNG discipline — the per-layer key splits exactly as `_forward`
    does (mgate/ANALOG: (k_w, k_x); static WS: whole key to the weight
    side; static IS: to the activation side), and each side's Gaussians
    are drawn with `realize_weights`'s internal (DAC, thermal) split, so
    the kernel sees bit-identical noise to the composed path.
  * static variation — `StaticVariation` fields broadcast per orientation
    (core.mrr.expand_lanes) and fold with the noise draws into the three
    additive chain offsets the kernel consumes for the weights.
  * padding to MXU-aligned block multiples + the unpadded-K bookkeeping
    the kernel needs to mask analog-realized pad lanes.

Static specialization (`realize_w`) mirrors `_analog_operand`'s ideal
shortcut: a side with ideal noise, no variation and no gate skips the
chain entirely, so the ideal fused path matches the composed one with
zero realization round-trip error.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import mrr, osa
from repro.core import quant as Q
from repro.core.constants import ComputeMode, Mapping
from repro.kernels import on_tpu
from repro.kernels.rosa_fused import ref
from repro.kernels.rosa_fused.rosa_fused import rosa_fused_pallas
from repro.obs import trace as obs


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _offsets(t: jax.Array, key: jax.Array | None, noise: mrr.NoiseModel,
             var: mrr.StaticVariation | None):
    """Fold per-shot draws + static variation into the three additive
    offsets of the realization chain, broadcast to the operand's shape.

    Draw discipline matches mrr.weight_of_voltage exactly: the side key
    splits into (DAC, thermal) and each perturbation is sigma * N(0, 1).
    """
    if noise.is_ideal:
        e_dac = e_th = jnp.zeros((), t.dtype)
    else:
        if key is None:
            raise ValueError("noisy realization requires a PRNG key")
        k_dac, k_th = jax.random.split(key)
        e_dac = noise.sigma_dac * jax.random.normal(k_dac, t.shape, t.dtype)
        e_th = noise.sigma_th * jax.random.normal(k_th, t.shape, t.dtype)
    z = jnp.zeros((), t.dtype)
    dv, ddt, dlam = ((var.dv, var.ddt, var.dlam) if var is not None
                     else (z, z, z))
    return tuple(jnp.broadcast_to(jnp.asarray(o, t.dtype), t.shape)
                 for o in (e_dac + dv, e_th + ddt, dlam))


@functools.partial(jax.jit, static_argnames=(
    "mapping", "mode", "quant_bits", "pam_bits", "act_per_vector", "noise",
    "osa_cfg", "p", "bm", "bn", "bk"))
def rosa_fused_matmul(x: jax.Array, w: jax.Array,
                      key: jax.Array | None = None,
                      var: mrr.StaticVariation | None = None,
                      gate: jax.Array | None = None,
                      mgate: jax.Array | None = None, *,
                      mapping: Mapping = Mapping.WS,
                      mode: ComputeMode = ComputeMode.MIXED,
                      quant_bits: int = 8, pam_bits: int = 1,
                      act_per_vector: bool = False,
                      noise: mrr.NoiseModel = mrr.IDEAL,
                      osa_cfg: osa.OSAConfig = osa.IDEAL_OSA,
                      p: mrr.MRRParams = mrr.DEFAULT_PARAMS,
                      bm: int = 128, bn: int = 128,
                      bk: int = 128) -> jax.Array:
    """y = x @ w through the fused analog pipeline; x: (M, K), w: (K, N).

    Semantics are those of the composed `rosa.backends._forward` with the
    "ref" contraction backend (the parity tests pin this); `gate`, `mgate`
    and `var` leaves enter as kernel OPERANDS, so gated evaluators sweep
    them without retracing.  Contract caveat: the kernel assumes the
    quantizer's 1e-8 absmax floor never binds on the weights (a weight
    whose global absmax is below 1e-8 is a degenerate all-zero edge case).
    The activations are requantized by the composed chain's own code, so
    the kernel contracts the chain's codes; outputs differ from the chain
    only by float accumulation order and the weight realization's float
    order (tests/test_kernels.py::assert_quantized_parity).
    """
    if mode is ComputeMode.DIGITAL:
        raise ValueError("DIGITAL layers take the exact digital path; the "
                         "fused kernel serves MIXED and ANALOG modes")
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    m, k = x.shape
    _, n = w.shape
    qcfg = Q.QuantConfig(bits=quant_bits)
    analog = mode is ComputeMode.ANALOG
    if analog:
        mgate = None                 # _forward's ANALOG branch ignores it
    use_mgate = mgate is not None
    use_gate = gate is not None

    # -- which sides realize (static; mirrors _analog_operand's shortcut) --
    can_realize = not (noise.is_ideal and var is None and gate is None)
    w_active = use_mgate or analog or mapping in (Mapping.WS, Mapping.GEMM)
    x_active = use_mgate or analog or not w_active
    realize_w = w_active and can_realize
    realize_x = x_active and can_realize

    # -- key split (must match _forward bit-for-bit) --
    if use_mgate or analog:
        k_w, k_x = (jax.random.split(key) if key is not None
                    else (None, None))
    elif w_active:
        k_w, k_x = key, None
    else:
        k_w, k_x = None, key

    # -- the activation operand, conditioned and requantized exactly as
    # the composed chain does it (same functions, same k_x -> same draws)
    if analog:
        x_eff = ref.analog_operand(
            x, k_x, qcfg=qcfg, p=p, noise=noise, var=var, gate=gate,
            clean_per_vector=False, noisy_per_vector=False)
        s2 = Q.absmax_scale(x)
        xa = x_eff * (1.0 / s2)
    else:
        x_eff = ref.condition_x(
            x, k_x, x_active=realize_x, use_mgate=use_mgate, mgate=mgate,
            gate=gate, var=var, qcfg=qcfg, p=p,
            noise=noise if realize_x else mrr.IDEAL,
            act_per_vector=act_per_vector)
        xa, s2 = Q.quantize(x_eff, qcfg, per_vector=act_per_vector)
    sw = Q.absmax_scale(w)

    # -- noise/variation offsets for the weights --
    w_off = (_offsets(w, k_w, noise, mrr.expand_lanes(var, w))
             if realize_w else None)

    # -- OSA slot gains (jitter needs a key the composed ref path never
    # threads either — slot_jitter_sigma != 0 raises, same as _ref_backend)
    if analog:
        n_planes = 1
        gains = jnp.ones((1,), jnp.float32)
    else:
        n_planes = -(-qcfg.n_planes // pam_bits)
        gains = osa.slot_gains(
            dataclasses.replace(osa_cfg, n_slots=n_planes,
                                pam_bits=pam_bits), None, jnp.float32)

    # -- pad + launch --
    xp = _pad_to(_pad_to(xa, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w, bk, 0), bn, 1)
    s2 = jnp.broadcast_to(jnp.asarray(s2, jnp.float32), (m, 1))
    s2 = jnp.pad(s2, ((0, xp.shape[0] - m), (0, 0)), constant_values=1.0)
    z = jnp.float32(0.0)
    gg = jnp.stack([jnp.asarray(gate, jnp.float32) if use_gate else z,
                    jnp.asarray(mgate, jnp.float32) if use_mgate else z,
                    jnp.asarray(sw, jnp.float32)])
    if w_off is not None:
        w_off = tuple(_pad_to(_pad_to(o, bk, 0), bn, 1) for o in w_off)

    if obs.enabled():
        # trace-time only (the Engine.matmul pattern): one instant per
        # traced fused launch, so compile timelines show ONE kernel where
        # the composed path showed four device ops
        obs.instant("kernels.rosa_fused", "compile", m=m, k=k, n=n,
                    mapping=mapping.name, mode=mode.name,
                    realize_x=realize_x, realize_w=realize_w,
                    gated=use_gate, mapping_gated=use_mgate)

    y = rosa_fused_pallas(
        xp, wp, gains, s2, gg, w_off, analog=analog, n_planes=n_planes,
        radix_bits=pam_bits, qmax=qcfg.qmax, realize_w=realize_w,
        use_gate=use_gate, use_mgate=use_mgate, k_real=k, p=p, bm=bm,
        bn=bn, bk=bk, interpret=not on_tpu())
    return y[:m, :n]


def preflight(m: int, k: int, n: int, *, bm: int = 128, bn: int = 128,
              bk: int = 128, quant_bits: int = 8, pam_bits: int = 1,
              realize_w: bool = True) -> dict:
    """Static tileability/VMEM report for a fused (m, k, n) GEMM — no launch.

    Mirrors `rosa_fused_matmul`'s layout: pad every dimension to its block
    multiple, run an (m/bm, n/bn) grid with a k-step inner loop, and hold
    the x/w blocks, the per-row scale and gate operands, the weights'
    three offset streams when they realize, and the f32 accumulator
    scratch in VMEM (in/out blocks double-buffered by the pipeline).
    Defaults price the worst-case launch (realized weights)."""
    n_planes = -(-Q.QuantConfig(bits=quant_bits).n_planes // pam_bits)
    issues: list[str] = []
    if min(m, k, n) <= 0 or min(bm, bn, bk) <= 0:
        issues.append(f"non-positive dimension in m,k,n={m},{k},{n} "
                      f"bm,bn,bk={bm},{bn},{bk}")
        return {"kernel": "rosa_fused", "grid": (0, 0, 0), "vmem_bytes": 0,
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
    w_streams = 1 + 3 * realize_w            # w + its offset operands
    vmem = 4 * (2 * (bm * bk + w_streams * bk * bn)
                + 2 * (bm + 3)               # scale + gate operands (dbuf)
                + 2 * bm * bn                # double-buffered out block
                + bm * bn                    # accumulator scratch
                + n_planes)                  # slot gains
    pad_waste = (mp * kp * np_) / (m * k * n) - 1.0
    return {"kernel": "rosa_fused", "grid": grid, "vmem_bytes": vmem,
            "pad_waste": pad_waste, "issues": issues}
