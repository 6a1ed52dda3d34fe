"""The `fused` raw backend end to end: registry contract, rosa_matmul
dispatch parity vs the composed "ref" chain, gates-as-operands (no
retrace across gate/mgate sweeps, vmap over mapping plans), bit-level
EnergyLedger pricing parity, and the optical serving path routed through
the megakernel (`ServeConfig(rosa_backend="fused")`)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import rosa
from repro.configs import get_smoke
from repro.core import mrr, quant
from repro.core.constants import ROSA_OPTIMAL, ComputeMode, Mapping
from repro.serve import (Request, Scheduler, ServeConfig, run_sequential)

NOISY = rosa.RosaConfig(noise=mrr.PAPER_NOISE, backend="fused")


def _operands(seed: int, m=9, k=130, n=40):
    kx, kw, kn = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kx, (m, k)), jax.random.normal(kw, (k, n)),
            kn)


def _var(k_dim: int, seed: int = 3) -> mrr.StaticVariation:
    dv = 0.01 * jax.random.normal(jax.random.PRNGKey(seed), (k_dim,))
    return mrr.StaticVariation(dv=dv, ddt=jnp.float32(0.05),
                               dlam=jnp.float32(1e-4))


# ---------------------------------------------------------------------------
# Registry contract
# ---------------------------------------------------------------------------
def test_fused_backend_registered():
    assert "fused" in rosa.backend_names()
    name, fn = rosa.resolve_backend("fused")
    assert name == "fused" and callable(fn)
    from repro.rosa.backends import is_raw_backend
    assert is_raw_backend("fused")
    assert not is_raw_backend("ref")


def test_auto_resolution_platform_pick():
    """"auto" -> the fused megakernel on TPU, the composed ref elsewhere."""
    name, _ = rosa.resolve_backend("auto")
    expected = "fused" if jax.default_backend() == "tpu" else "ref"
    assert name == expected


# ---------------------------------------------------------------------------
# rosa_matmul dispatch parity: backend="fused" == backend="ref"
# ---------------------------------------------------------------------------
def _assert_quantized_parity(y, y_ref, *, qmax: int = 127,
                             tight: float = 2e-4) -> None:
    """Flip-aware quantized-parity discipline (the contract is documented
    on tests/test_kernels.py::assert_quantized_parity): bulk at float
    tightness, nothing beyond the one-requant-LSB bound, and rows touched
    by a requantization boundary flip stay rare."""
    y = np.asarray(y, np.float64).reshape(-1, y.shape[-1])
    r = np.asarray(y_ref, np.float64).reshape(y.shape)
    scale = max(float(np.max(np.abs(r))), 1.0)
    d = np.abs(y - r) / scale
    assert d.max() <= 2.0 / qmax
    assert int((d.max(axis=-1) > tight).sum()) <= max(2, -(-y.shape[0] // 4))


def _assert_dispatch_parity(cfg: rosa.RosaConfig, seed: int, *,
                            key=True, var=True, gate=None, mgate=None,
                            w_scale=False):
    """With `w_scale`, both backends also get the weight's precomputed
    full-scale: the fused result must not move by a bit, and the composed
    chain ignores it."""
    x, w, kn = _operands(seed)
    var_ = _var(x.shape[1]) if var else None
    kn_ = kn if key else None
    args = (kn_, var_, gate, mgate)
    fused = dataclasses.replace(cfg, backend="fused")
    y_f = rosa.rosa_matmul(x, w, fused, *args)
    y_r = rosa.rosa_matmul(x, w, dataclasses.replace(cfg, backend="ref"),
                           *args)
    _assert_quantized_parity(y_f, y_r)
    if w_scale:
        sw = quant.absmax_scale(w)
        np.testing.assert_array_equal(
            np.asarray(rosa.rosa_matmul(x, w, fused, *args, sw)),
            np.asarray(y_f))
        np.testing.assert_array_equal(
            np.asarray(rosa.rosa_matmul(
                x, w, dataclasses.replace(cfg, backend="ref"), *args, sw)),
            np.asarray(y_r))


@pytest.mark.parametrize("w_scale", [False, True],
                         ids=["scale_computed", "scale_given"])
@pytest.mark.parametrize("seed,cfg_kw,call_kw", [
    (0, {}, {}),                                              # noisy WS
    (1, {"mapping": Mapping.IS, "act_per_vector": True}, {}),
    (2, {}, {"gate": 0.3}),
    (3, {"act_per_vector": True}, {"mgate": 0.5}),
    (4, {"mode": ComputeMode.ANALOG}, {"gate": 0.7}),
    (5, {"noise": mrr.IDEAL}, {"var": False}),                # ideal path
], ids=["ws", "is_apv", "gated", "mgated", "analog", "ideal"])
def test_fused_dispatch_matches_ref(seed, cfg_kw, call_kw, w_scale):
    _assert_dispatch_parity(dataclasses.replace(NOISY, **cfg_kw), seed,
                            w_scale=w_scale, **call_kw)


@pytest.mark.parametrize("mapping", [Mapping.WS, Mapping.IS])
def test_fused_kernel_takes_given_weight_scale(mapping):
    """`rosa_fused_matmul(..., w_scale=absmax_scale(w))` is the call
    without it, bit for bit, on the kernel's own entry point."""
    from repro.kernels.rosa_fused.ops import rosa_fused_matmul
    x, w, kn = _operands(10)
    kw = dict(mapping=mapping, noise=mrr.PAPER_NOISE, act_per_vector=True)
    y = rosa_fused_matmul(x, w, kn, _var(x.shape[1]), **kw)
    y_s = rosa_fused_matmul(x, w, kn, _var(x.shape[1]),
                            w_scale=quant.absmax_scale(w), **kw)
    np.testing.assert_array_equal(np.asarray(y_s), np.asarray(y))


def test_fused_nonideal_osa_dispatch(key):
    from repro.core import osa
    cfg = dataclasses.replace(
        NOISY, mapping=Mapping.IS, act_per_vector=True,
        osa_cfg=osa.OSAConfig(splitter_imbalance=0.01,
                              odl_loss_db_per_stage=0.05))
    _assert_dispatch_parity(cfg, 6)


def test_fused_batched_leading_dims(key):
    """rosa_matmul flattens leading axes before the kernel and restores
    them after — the (B, T, K) decode call shape."""
    k1, k2, kn = jax.random.split(key, 3)
    x = jax.random.normal(k1, (2, 5, 48))
    w = jax.random.normal(k2, (48, 16))
    y_f = rosa.rosa_matmul(x, w, NOISY, kn)
    y_r = rosa.rosa_matmul(x, w, dataclasses.replace(NOISY, backend="ref"),
                           kn)
    assert y_f.shape == (2, 5, 16)
    _assert_quantized_parity(y_f, y_r)


@pytest.mark.parametrize("w_scale", [False, True],
                         ids=["scale_computed", "scale_given"])
def test_fused_straight_through_gradients(key, w_scale):
    """The custom_vjp is backend-agnostic: fused forward, exact dense
    backward (identical cotangents to the ref backend).  A given weight
    full-scale is non-differentiable and leaves the gradients as they
    are, bit for bit."""
    x, w, kn = _operands(7, m=6, k=32, n=8)

    def loss(backend, given):
        cfg = dataclasses.replace(NOISY, backend=backend)

        def f(x_, w_):
            sw = quant.absmax_scale(w_) if given else None
            return jnp.sum(rosa.rosa_matmul(x_, w_, cfg, kn, None, None,
                                            None, sw) ** 2)
        return f

    gx_f, gw_f = jax.grad(loss("fused", w_scale), argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(loss("ref", w_scale), argnums=(0, 1))(x, w)
    _assert_quantized_parity(gx_f, gx_r)
    _assert_quantized_parity(gw_f, gw_r)
    if w_scale:
        for got, want in zip(
                (gx_f, gw_f),
                jax.grad(loss("fused", False), argnums=(0, 1))(x, w)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Gates are kernel operands: one trace across sweeps, vmappable plans
# ---------------------------------------------------------------------------
def test_fused_gate_sweep_single_trace(key):
    """PR 7's gated evaluators sweep gate/mgate VALUES through one compiled
    executable — the fused kernel must take them as operands, not consts."""
    x, w, kn = _operands(8, m=8, k=64, n=16)
    traces = []

    @jax.jit
    def f(x_, w_, k_, gate, mgate):
        traces.append(1)          # trace-time side effect: counts retraces
        return rosa.rosa_matmul(x_, w_, NOISY, k_, None, gate, mgate)

    outs = [f(x, w, kn, jnp.float32(g), jnp.float32(mg))
            for g in (0.0, 0.5, 1.0) for mg in (0.0, 1.0)]
    assert len(traces) == 1
    assert all(o.shape == (8, 16) for o in outs)


def test_fused_vmap_over_mapping_gate(key):
    """A whole {layer: IS|WS} plan as a float vector: candidate plans are
    a vmap axis over the mgate operand (robust.sensitivity's search)."""
    x, w, kn = _operands(9, m=4, k=48, n=12)
    mgates = jnp.array([0.0, 0.5, 1.0])
    ys = jax.vmap(lambda mg: rosa.rosa_matmul(x, w, NOISY, kn, None, None,
                                              mg))(mgates)
    assert ys.shape == (3, 4, 12)
    y_ws = rosa.rosa_matmul(x, w, NOISY, kn, None, None, jnp.float32(0.0))
    np.testing.assert_allclose(np.asarray(ys[0]), np.asarray(y_ws),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# EnergyLedger pricing parity
# ---------------------------------------------------------------------------
def test_ledger_prices_fused_identical_to_composed():
    """Fusion is an execution detail: the analytical energy model prices a
    routed GEMM by (shape, mapping, mode), so the fused trace must export
    BIT-identical totals (energy, delay, EDP, every breakdown term) to the
    composed one for the same plan."""
    exports = {}
    for backend in ("fused", "ref"):
        cfg = dataclasses.replace(NOISY, backend=backend)
        ledger = rosa.EnergyLedger()
        eng = rosa.Engine.from_config(cfg, key=jax.random.PRNGKey(0),
                                      ledger=ledger)
        jax.eval_shape(
            lambda p, x_: eng.matmul(x_, p, name="proj"),
            jax.ShapeDtypeStruct((64, 128), jnp.float32),
            jax.ShapeDtypeStruct((8, 64), jnp.float32))
        exports[backend] = ledger.export(ROSA_OPTIMAL)
    f, r = exports["fused"], exports["ref"]
    assert f["totals"] == r["totals"]          # bit-level: no tolerance
    # events identical modulo provenance (backend name, global seq stamp)
    strip = lambda evs: [{k: v for k, v in e.items()
                          if k not in ("backend", "seq")} for e in evs]
    assert strip(f["events"]) == strip(r["events"])


# ---------------------------------------------------------------------------
# Serving: the decode Program routes through the megakernel
# ---------------------------------------------------------------------------
def test_rosa_serving_fused_backend():
    """Optical serving on the fused backend with a pinned fabricated chip:
    the continuous-batching scheduler must stay differentially equal to
    the per-request sequential oracle (same engine), proving the decode
    Program's matmuls route through the megakernel deterministically."""
    smoke_cfg = get_smoke("qwen3-32b")
    scfg = ServeConfig(n_slots=2, max_len=24, prefill_chunk=4, rosa=True,
                       rosa_backend="fused", variation_seed=7)
    sched = Scheduler(smoke_cfg, scfg)
    rng = np.random.default_rng(11)
    reqs = [Request(i, rng.integers(0, smoke_cfg.vocab,
                                    int(rng.integers(3, 8))),
                    int(rng.integers(2, 6)), arrival=i) for i in range(3)]
    rep = sched.run(reqs, policy="continuous")
    ref = run_sequential(smoke_cfg, scfg, sched.params, reqs,
                         engine=sched.engine)
    for r in reqs:
        assert rep.completions[r.rid].tokens == ref[r.rid]["tokens"]
    assert len(sched.engine.ledger.events) > 0
    assert all(ev.backend == "fused" for ev in sched.engine.ledger.events)
