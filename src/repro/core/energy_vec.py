"""Vectorized (JAX) counterpart of the analytical energy model.

`core.energy.layer_energy` is plain-Python-float by design — it prices one
(layer, OPE config) pair in microseconds and stays trace-free.  The DSE,
however, evaluates a full candidate-grid x workload cross-product, and the
model zoo pushes that product into the hundreds of thousands of cells.
This module ports the *same arithmetic* to `jax.numpy` so the whole grid
evaluates as one vmapped, jitted call:

    cand   = stack_candidates(opes)        # (P,) int arrays: rows/cols/tiles
    layers = stack_layers(shapes)          # (L,) int arrays: g/m/k/n/n_total
    energy, latency = grid_energy(cand, layers, spec)      # (P, L) float64

Compute mode, dataflow mapping, OSA sizing and bit widths are *static*
(they select formulas, not values) and ride in an `EnergySpec`; rows, cols,
tiles and the GEMM dims are traced array data.  Everything runs in float64
(via `jax.enable_x64`) so the vectorized path matches the
scalar reference to ~1e-15 relative — the DSE parity test pins 1e-6.

Scalar-model invariants preserved here (see energy.layer_energy):
  * ceil-divisions are exact integer ceil-divs, not float ceils;
  * event counts (tiles, programming words, streamed values, ADC firings)
    are integers until the final multiply by per-event Joule constants;
  * static power integrates over the same `rounds * (t_prog + t_stream)`
    latency.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import constants as C
from repro.core.constants import ComputeMode, Mapping, OPEConfig
from repro.core.energy import (LayerShape, ODL_STATIC_W, OSAEnergyConfig,
                               PSUM_BITS)


@dataclasses.dataclass(frozen=True)
class EnergySpec:
    """Static (formula-selecting) knobs of one grid evaluation."""

    mapping: Mapping = Mapping.WS
    mode: ComputeMode = ComputeMode.MIXED
    osa_enabled: bool = False
    ode_len: int = 0
    n_bits_in: int = C.N_BITS_INPUT
    n_bits_w: int = C.N_BITS_WEIGHT
    n_bits_out: int = C.N_BITS_OUTPUT
    pam_bits: int = 1
    batch: int = 1

    @classmethod
    def make(cls, mapping: Mapping = Mapping.WS,
             mode: ComputeMode = ComputeMode.MIXED,
             osa: OSAEnergyConfig | None = None,
             batch: int = 1, **kw) -> "EnergySpec":
        osa = osa if osa is not None else OSAEnergyConfig(enabled=False)
        return cls(mapping=mapping, mode=mode, osa_enabled=osa.enabled,
                   ode_len=osa.ode_len, batch=batch, **kw)

    @property
    def osa(self) -> OSAEnergyConfig:
        return OSAEnergyConfig(enabled=self.osa_enabled, ode_len=self.ode_len)

    @property
    def n_slots(self) -> int:
        return max(1, math.ceil((self.n_bits_in - 1) / self.pam_bits))


def stack_candidates(opes: Sequence[OPEConfig]) -> dict[str, np.ndarray]:
    """(P,) int64 arrays of the candidate grid."""
    return {
        "rows": np.array([o.rows for o in opes], dtype=np.int64),
        "cols": np.array([o.cols for o in opes], dtype=np.int64),
        "tiles": np.array([o.tiles for o in opes], dtype=np.int64),
    }


def stack_layers(shapes: Sequence[LayerShape]) -> dict[str, np.ndarray]:
    """(L,) int64 arrays of GEMM-lowered layers (per-group dims pre-split)."""
    cols = {"g": [], "m": [], "k_pg": [], "n_pg": [], "n_total": []}
    for s in shapes:
        g, m, k_pg, n_pg = s.sub_gemm()
        cols["g"].append(g)
        cols["m"].append(m)
        cols["k_pg"].append(k_pg)
        cols["n_pg"].append(n_pg)
        cols["n_total"].append(s.n)
    return {k: np.array(v, dtype=np.int64) for k, v in cols.items()}


def _ceil_div(a, b):
    return -(-a // b)


def _layer_energy_one(cand: dict, layer: dict, spec: EnergySpec):
    """(energy [J], latency [s]) of ONE layer on ONE OPE config.

    Scalar-in/scalar-out port of `energy.layer_energy`; `cand` and `layer`
    hold 0-d integer arrays so the caller can vmap over either side.
    """
    rows, cols, tiles = cand["rows"], cand["cols"], cand["tiles"]
    g, m0, k_pg, n_pg = layer["g"], layer["m"], layer["k_pg"], layer["n_pg"]
    n_total = layer["n_total"]
    m = m0 * spec.batch

    n_slots = spec.n_slots
    mode, osa = spec.mode, spec.osa

    # ---- tile grid of the stationary operand -----------------------------
    if spec.mapping in (Mapping.WS, Mapping.GEMM):
        tiles_r = _ceil_div(n_total, rows)
        tiles_c = _ceil_div(k_pg, cols)
        n_tiles = tiles_r * tiles_c
        stream_len = m
    elif spec.mapping is Mapping.IS:
        tiles_r = _ceil_div(m, rows)
        tiles_c = _ceil_div(k_pg, cols)
        n_tiles = g * tiles_r * tiles_c
        stream_len = n_pg
    else:
        raise ValueError(spec.mapping)
    rounds = _ceil_div(n_tiles, tiles)

    # ---- per-mode timing and event structure -----------------------------
    f64 = lambda x: jnp.asarray(x, jnp.float64)  # noqa: E731 — local alias
    if mode is ComputeMode.MIXED:
        t_program = C.T_TO_TUNING_S
        slots_per_value = n_slots
        t_stream = f64(stream_len) * slots_per_value * C.T_SLOT_S
        conv_per_out = osa.conversions_per_output(n_slots)
    elif mode is ComputeMode.ANALOG:
        t_program = C.T_TO_TUNING_S
        slots_per_value = 1
        t_stream = f64(stream_len) * C.T_TO_TUNING_S
        conv_per_out = 1
    elif mode is ComputeMode.DIGITAL:
        t_program = C.T_EO_TUNING_S
        slots_per_value = spec.n_bits_in * spec.n_bits_w
        t_stream = f64(stream_len) * slots_per_value * C.T_SLOT_S
        conv_per_out = slots_per_value
    else:
        raise ValueError(mode)

    latency = f64(rounds) * (t_program + t_stream)

    # ---- dynamic energy --------------------------------------------------
    prog_events = f64(n_tiles * rows * cols)
    eo_mod = f64(0.0)
    if mode is ComputeMode.DIGITAL:
        dac_prog = f64(0.0)
        eo_mod = prog_events * spec.n_bits_w * C.MRR_EO_DYNAMIC_J_PER_BIT
    else:
        dac_prog = prog_events * spec.n_bits_w * C.DAC_J_PER_BIT

    stream_values = f64(n_tiles) * f64(stream_len) * f64(cols)
    if mode is ComputeMode.ANALOG:
        dac_prog = dac_prog + stream_values * spec.n_bits_in * C.DAC_J_PER_BIT
    else:
        eo_mod = eo_mod + (stream_values * slots_per_value
                           * C.MRR_EO_DYNAMIC_J_PER_BIT)

    useful_outputs = f64(m) * f64(n_total)
    out_events = useful_outputs * f64(tiles_c) * conv_per_out
    pd_tia = out_events * C.PD_TIA_J_PER_BIT
    adc = out_events * C.adc_energy_per_conversion(spec.n_bits_out)

    sram_dyn = out_events * 2 * PSUM_BITS * C.SRAM_J_PER_BIT
    sram_words = (prog_events * spec.n_bits_w
                  + stream_values * spec.n_bits_in
                  + useful_outputs * spec.n_bits_out)
    sram_dyn = sram_dyn + sram_words * C.SRAM_J_PER_BIT

    dram = (f64(m) * f64(k_pg * g) * spec.n_bits_in
            + f64(k_pg * n_pg * g) * spec.n_bits_w
            + useful_outputs * spec.n_bits_out) * C.DRAM_J_PER_BIT

    dynamic = eo_mod + dac_prog + pd_tia + adc + sram_dyn + dram

    # ---- static energy = power * runtime ---------------------------------
    p_laser = f64(tiles * cols) * C.LASER_STATIC_W
    p_mrr = (f64(tiles * rows * cols) * C.MRR_TO_STATIC_W
             if mode is not ComputeMode.DIGITAL else f64(0.0))
    p_odl = (f64(tiles * rows) * osa.stages_per_row(n_slots) * ODL_STATIC_W
             if mode is ComputeMode.MIXED else f64(0.0))
    buf_bits = (f64(tiles * rows * cols) * spec.n_bits_w
                + f64(tiles * cols) * f64(stream_len) * spec.n_bits_in
                + f64(tiles * rows) * PSUM_BITS)
    p_leak = buf_bits * C.SRAM_LEAK_W_PER_BIT

    energy = dynamic + (p_laser + p_mrr + p_odl + p_leak) * latency
    return energy, latency


def grid_energy(cand: dict, layers: dict, spec: EnergySpec):
    """(P, L) energy and latency: every candidate x every layer, one vmap."""
    per_layer = jax.vmap(_layer_energy_one, in_axes=(None, 0, None))
    per_cand = jax.vmap(per_layer, in_axes=(0, None, None))
    return per_cand(
        {k: jnp.asarray(v, jnp.int64) for k, v in cand.items()},
        {k: jnp.asarray(v, jnp.int64) for k, v in layers.items()},
        spec,
    )


# vmap over a dataclass argument needs it registered as a (static) pytree —
# EnergySpec carries no arrays, so it is all aux_data.
jax.tree_util.register_pytree_node(
    EnergySpec,
    lambda s: ((), s),
    lambda aux, _: aux,
)
