"""OPE array-size design-space exploration (paper Sec. 3.5, Fig. 7).

Sweeps (R, C) under the physical constraints C <= MAX_WDM_CHANNELS and
T*R*C <= MAX_TOTAL_MRRS (T auto-filled to the budget), evaluates the EDP of
every workload network, and aggregates with

    G     = (prod_n EDP_n)^(1/N)            # balanced geometric mean
    W_max = max_n EDP_n                      # worst case
    M     = (1-lambda) * G + lambda * W_max  # robust efficiency metric

EDPs are expressed *relative to a reference config per workload* before
aggregation (the paper reports "relative EDP" vs. the compact 4x4 array) so
no single heavy network dominates the geomean.

Two evaluation engines produce identical `DSEPoint`s:

  * ``engine="vmap"`` (default) — candidates and layers are stacked into
    arrays (`core.energy_vec`) and the analytic EDP model is vmapped over
    the full candidate-grid x workload cross-product in ONE jitted float64
    call.  This is what makes model-zoo-scale sweeps (tens of candidates x
    thousands of GEMM rows) interactive.
  * ``engine="scalar"`` — the original nested-loop pure-Python path, kept
    as the parity reference; `tests/test_bench.py` pins the two to 1e-6
    relative on the default grid.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy as E
from repro.core import energy_vec as EV
from repro.core.constants import (COMPACT_4X4, DEAP_HIGH_CHANNEL, ComputeMode,
                                  Mapping, MAX_TOTAL_MRRS, MAX_WDM_CHANNELS,
                                  OPEConfig)


@dataclasses.dataclass
class Workload:
    name: str
    layers: list[E.LayerShape]


@dataclasses.dataclass
class DSEPoint:
    ope: OPEConfig
    edp_per_workload: dict[str, float]
    rel_edp: dict[str, float]
    geomean: float
    worst: float
    metric: float

    @property
    def label(self) -> str:
        return f"R={self.ope.rows},C={self.ope.cols},T={self.ope.tiles}"


def default_candidates(include_baselines: bool = True) -> list[OPEConfig]:
    """The sweep grid: all power-of-two-ish (R, C) within constraints."""
    rs = [1, 2, 4, 8, 16, 32, 64, 128]
    cs = [1, 2, 4, 8]
    cands = []
    for r in rs:
        for c in cs:
            if r * c <= MAX_TOTAL_MRRS and c <= MAX_WDM_CHANNELS:
                cands.append(OPEConfig(rows=r, cols=c))
    if include_baselines:
        cands.append(DEAP_HIGH_CHANNEL)      # violates C<=8; kept for comparison
    return cands


def evaluate(ope: OPEConfig,
             workloads: Sequence[Workload],
             reference: OPEConfig = COMPACT_4X4,
             lam: float = 0.3,
             mapping: Mapping = Mapping.WS,
             mode: ComputeMode = ComputeMode.MIXED,
             osa: E.OSAEnergyConfig = E.NO_OSA,
             batch: int = 1) -> DSEPoint:
    """Scalar reference: EDP of every workload on `ope`, aggregated."""
    edp, rel = {}, {}
    for wl in workloads:
        e = E.network_energy(wl.layers, ope, mapping, mode, osa, batch=batch).edp
        e_ref = E.network_energy(wl.layers, reference, mapping, mode, osa,
                                 batch=batch).edp
        edp[wl.name] = e
        rel[wl.name] = e / e_ref
    g = math.exp(sum(math.log(v) for v in rel.values()) / len(rel))
    w = max(rel.values())
    return DSEPoint(ope=ope, edp_per_workload=edp, rel_edp=rel,
                    geomean=g, worst=w, metric=(1 - lam) * g + lam * w)


# ---------------------------------------------------------------------------
# Vectorized engine
# ---------------------------------------------------------------------------
@jax.jit
def _grid_eval(cand: dict, layers: dict, onehot: jax.Array,
               spec: EV.EnergySpec, lam: jax.Array):
    """One fused evaluation of the whole grid.

    cand holds P+1 configs (the last row is the reference); onehot is the
    (L, W) layer->workload incidence matrix.  Returns per-candidate (P,W)
    absolute and relative EDP plus the (P,) aggregates.
    """
    energy, latency = EV.grid_energy(cand, layers, spec)      # (P+1, L)
    e_net = energy @ onehot                                   # (P+1, W)
    t_net = latency @ onehot
    edp = e_net * t_net
    rel = edp[:-1] / edp[-1:]                                 # vs reference
    geo = jnp.exp(jnp.mean(jnp.log(rel), axis=1))
    worst = jnp.max(rel, axis=1)
    metric = (1.0 - lam) * geo + lam * worst
    return edp[:-1], rel, geo, worst, metric


def evaluate_grid(workloads: Sequence[Workload],
                  candidates: Sequence[OPEConfig],
                  reference: OPEConfig = COMPACT_4X4,
                  lam: float = 0.3,
                  mapping: Mapping = Mapping.WS,
                  mode: ComputeMode = ComputeMode.MIXED,
                  osa: E.OSAEnergyConfig = E.NO_OSA,
                  batch: int = 1) -> list[DSEPoint]:
    """Vectorized DSE: all candidates x all workloads in one jitted call.

    Returns DSEPoints in candidate order (unsorted) so callers can line the
    results up against `candidates`.
    """
    names = [w.name for w in workloads]
    shapes: list[E.LayerShape] = []
    wl_id: list[int] = []
    for wi, wl in enumerate(workloads):
        shapes.extend(wl.layers)
        wl_id.extend([wi] * len(wl.layers))
    if not shapes:
        raise ValueError("no workload layers to evaluate")

    cand_arrays = EV.stack_candidates(list(candidates) + [reference])
    layer_arrays = EV.stack_layers(shapes)
    onehot = np.zeros((len(shapes), len(names)))
    onehot[np.arange(len(shapes)), np.array(wl_id)] = 1.0
    spec = EV.EnergySpec.make(mapping=mapping, mode=mode, osa=osa, batch=batch)

    with jax.enable_x64():
        edp, rel, geo, worst, metric = _grid_eval(
            cand_arrays, layer_arrays, jnp.asarray(onehot, jnp.float64),
            spec, jnp.asarray(lam, jnp.float64))
        edp, rel, geo, worst, metric = map(np.asarray,
                                           (edp, rel, geo, worst, metric))

    return [
        DSEPoint(
            ope=ope,
            edp_per_workload={n: float(edp[i, j]) for j, n in enumerate(names)},
            rel_edp={n: float(rel[i, j]) for j, n in enumerate(names)},
            geomean=float(geo[i]), worst=float(worst[i]),
            metric=float(metric[i]))
        for i, ope in enumerate(candidates)
    ]


def sweep(workloads: Sequence[Workload],
          candidates: Sequence[OPEConfig] | None = None,
          lam: float = 0.3,
          engine: str = "vmap",
          **kw) -> list[DSEPoint]:
    """Full DSE; returns points sorted by the robust metric M (best first).

    ``engine="vmap"`` evaluates the whole grid in one jitted call;
    ``engine="scalar"`` is the pure-Python reference path.
    """
    candidates = candidates or default_candidates()
    if engine == "vmap":
        pts = evaluate_grid(workloads, candidates, lam=lam, **kw)
    elif engine == "scalar":
        pts = [evaluate(ope, workloads, lam=lam, **kw) for ope in candidates]
    else:
        raise ValueError(f"unknown DSE engine {engine!r}")
    pts.sort(key=lambda p: p.metric)
    return pts


def best(workloads: Sequence[Workload], **kw) -> DSEPoint:
    return sweep(workloads, **kw)[0]
