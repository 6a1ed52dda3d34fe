"""Golden paper-fidelity regression tests (PAPER.md headline numbers).

Two layers of pinning, both with EXPLICIT tolerances:

  1. reproduction pins — the value this repo's energy model computes today,
     held to 1e-6 relative: an energy-model refactor that shifts any of
     these numbers must come with a deliberate golden update, never a
     silent drift;
  2. paper windows — where the reproduction tracks the paper closely
     (OSA 29%, compact-array 26%) the value must stay inside a stated
     window around the PAPER's number; where magnitudes are documented to
     differ (DEAP comparisons on synth workloads — see
     benchmarks/table4_hybrid.py) we pin the paper's claim as a bound the
     reproduction must keep exceeding.

Both the scalar model (core.energy, via fig8/table4 paths) and the
vectorized one (core.energy_vec, via the fig7 DSE sweep and
profile_layers_fast) are exercised, plus an element-level parity check
between the two, so neither implementation can drift from the other.
"""

import jax
import numpy as np
import pytest

from repro.configs.paper_cnns import CNN_WORKLOADS
from repro.core import energy as E
from repro.core import mapping as M
from repro.core.constants import (ComputeMode, DEAP_HIGH_CHANNEL, Mapping,
                                  ROSA_OPTIMAL)
from repro.models.cnn import LITE_MODELS

# -- reproduction pins (rel 1e-6) -------------------------------------------
GOLDEN = {
    "fig7_best_label": "R=8,C=8,T=16",      # paper winner: (R=8, C=8)
    "fig7_reduction_vs_deap": 0.33517400209471915,     # paper: 0.64
    "fig7_reduction_vs_compact": 0.22607095668842436,  # paper: 0.26
    "fig8_geomean_reduction_osa": 0.28580986529830166,      # paper: 0.29
    "fig8_geomean_reduction_osa_ode": 0.33332575119641483,  # paper: 0.37
    "table4_avg_hybrid_vs_ws_edp_red": 0.2850777915075481,
    # table4 avg hybrid-vs-DEAP EDP reduction saturates at ~1.0 on the
    # synth workloads (DEAP's high-channel analog arrays price orders of
    # magnitude worse at batch 128) — the paper's 54.7% average is kept as
    # a floor below, not pinned here.
}
REL = 1e-6

# -- paper windows (absolute, explicit) -------------------------------------
PAPER_OSA = 0.29
PAPER_OSA_WINDOW = 0.02          # reproduction tracks closely
PAPER_COMPACT = 0.26
PAPER_COMPACT_WINDOW = 0.05
PAPER_DEAP_FIG7_FLOOR = 0.30     # paper claims 0.64; repo reproduces ~0.335
#   (documented magnitude gap) — must at least stay above this floor
PAPER_TABLE4_DEAP_AVG = 0.547    # repo exceeds; keep exceeding


@pytest.fixture(scope="module")
def fig7():
    from benchmarks import fig7_array_dse
    return fig7_array_dse.run(verbose=False)


@pytest.fixture(scope="module")
def fig8():
    from benchmarks import fig8_osa
    return fig8_osa.run(verbose=False)


def test_fig7_array_dse_golden(fig7):
    assert fig7["best"].label == GOLDEN["fig7_best_label"]
    assert fig7["reduction_vs_deap"] == pytest.approx(
        GOLDEN["fig7_reduction_vs_deap"], rel=REL)
    assert fig7["reduction_vs_compact"] == pytest.approx(
        GOLDEN["fig7_reduction_vs_compact"], rel=REL)


def test_fig7_paper_windows(fig7):
    assert abs(fig7["reduction_vs_compact"] - PAPER_COMPACT) \
        < PAPER_COMPACT_WINDOW
    assert fig7["reduction_vs_deap"] > PAPER_DEAP_FIG7_FLOOR


def test_fig8_osa_golden(fig8):
    assert fig8["geomean_reduction_osa"] == pytest.approx(
        GOLDEN["fig8_geomean_reduction_osa"], rel=REL)
    assert fig8["geomean_reduction_osa_ode"] == pytest.approx(
        GOLDEN["fig8_geomean_reduction_osa_ode"], rel=REL)


def test_fig8_paper_window(fig8):
    """The 29% OSA contribution is the closest-tracked headline number."""
    assert abs(fig8["geomean_reduction_osa"] - PAPER_OSA) < PAPER_OSA_WINDOW
    # ODE sizing must add on top of plain OSA
    assert fig8["geomean_reduction_osa_ode"] > fig8["geomean_reduction_osa"]


def _table4_edp_reductions():
    """EDP-only hybrid-mapping numbers on the table-4 layer subsets
    (profile_layers_fast -> energy_vec; plan_edp -> scalar energy)."""
    ws_red, deap_red = [], []
    for model, layers_full in CNN_WORKLOADS.items():
        lite = {s.name for s in LITE_MODELS[model]}
        mapped = [l for l in layers_full if l.name in lite]
        profs = M.profile_layers_fast(mapped, ROSA_OPTIMAL, batch=128)
        plan = M.hybrid_plan(profs)
        e_h = M.plan_edp(mapped, plan, ROSA_OPTIMAL, batch=128)
        e_ws = M.plan_edp(mapped, {}, ROSA_OPTIMAL, batch=128)
        e_deap = E.network_energy(mapped, DEAP_HIGH_CHANNEL, Mapping.WS,
                                  ComputeMode.ANALOG, E.NO_OSA,
                                  batch=128).edp
        ws_red.append(1 - e_h / e_ws)
        deap_red.append(1 - e_h / e_deap)
    return np.mean(ws_red), np.mean(deap_red)


def test_table4_hybrid_mapping_golden():
    avg_ws, avg_deap = _table4_edp_reductions()
    assert avg_ws == pytest.approx(
        GOLDEN["table4_avg_hybrid_vs_ws_edp_red"], rel=REL)
    # the paper's 54.7%-vs-DEAP average is a floor the reproduction clears
    assert avg_deap > PAPER_TABLE4_DEAP_AVG
    # hybrid never prices worse than pure WS on any network
    assert avg_ws >= 0.0


def test_energy_vec_matches_scalar_on_paper_layers():
    """core.energy_vec and core.energy agree per layer to 1e-9 relative on
    every paper workload row, both mappings — the golden pins above hold
    through EITHER implementation."""

    from repro.core import energy_vec as EV

    for model, layers in CNN_WORKLOADS.items():
        cand = EV.stack_candidates([ROSA_OPTIMAL])
        stacked = EV.stack_layers(layers)
        for mp in (Mapping.IS, Mapping.WS):
            with jax.enable_x64():
                spec = EV.EnergySpec.make(mapping=mp,
                                          mode=ComputeMode.MIXED,
                                          osa=E.OSA_OPTIMAL, batch=128)
                en, lat = EV.grid_energy(cand, stacked, spec)
                vec_edp = np.asarray(en[0] * lat[0])
            for i, layer in enumerate(layers):
                bd = E.layer_energy(layer, ROSA_OPTIMAL, mp,
                                    ComputeMode.MIXED, E.OSA_OPTIMAL,
                                    batch=128)
                assert vec_edp[i] == pytest.approx(bd.edp, rel=1e-9), \
                    (model, layer.name, mp)
