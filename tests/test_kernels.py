"""Pallas kernels vs pure-jnp oracles (interpret mode, shape/dtype sweeps).

The property sections fuzz the osa_matmul / mrr_transfer kernels against
their ref.py oracles over randomized shapes, dtypes and edge tiles
(hypothesis when installed, fixed-sample parametrization otherwise — the
same guard pattern as tests/test_mrr.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:                      # degrade gracefully: property tests fall back to
    import hypothesis as hp            # fixed-sample parametrization when
    import hypothesis.strategies as st  # hypothesis is not installed
except ModuleNotFoundError:
    hp = st = None

from repro.core import mrr, osa, quant
from repro.core.constants import ComputeMode, Mapping
from repro.kernels.mrr_transfer import mrr_transfer as mt_kernel
from repro.kernels.mrr_transfer import ops as mt_ops
from repro.kernels.mrr_transfer import ref as mt_ref
from repro.kernels.osa_matmul import ops as osa_ops
from repro.kernels.osa_matmul.ref import osa_matmul_ref
from repro.kernels.rosa_fused import ops as fused_ops
from repro.kernels.rosa_fused import ref as fused_ref
from repro.kernels.rosa_fused import rosa_fused as fused_kernel
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan import ref as ssd_ref


# ---------------------------------------------------------------------------
# osa_matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (32, 48, 24), (17, 33, 5),
                                   (128, 128, 128)])
@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.analog_guard
def test_osa_kernel_matches_ref(m, k, n, bits, key):
    k1, k2 = jax.random.split(key)
    cfg = quant.QuantConfig(bits=bits)
    q = jnp.round(jax.random.uniform(k1, (m, k), minval=-cfg.qmax,
                                     maxval=cfg.qmax))
    w = jax.random.normal(k2, (k, n))
    y = osa_ops.osa_matmul_int(q, w, quant.plane_weights(cfg),
                               n_planes=cfg.n_planes, bm=8, bn=8, bk=8)
    y_ref = osa_matmul_ref(q, w, quant_bits=bits)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("fused", [True, False])
def test_osa_kernel_fused_vs_per_plane(fused, key):
    k1, k2 = jax.random.split(key)
    q = jnp.round(jax.random.uniform(k1, (16, 24), minval=-127, maxval=127))
    w = jax.random.normal(k2, (24, 8))
    y = osa_ops.osa_matmul_int(q, w, quant.plane_weights(), n_planes=7,
                               fused=fused, bm=8, bn=8, bk=8)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(osa_matmul_ref(q, w)),
                               rtol=1e-4, atol=1e-3)


def test_osa_kernel_nonideal_gains(key):
    """Calibrated (non power-of-two) slot gains flow through the kernel."""
    k1, k2, k3 = jax.random.split(key, 3)
    q = jnp.round(jax.random.uniform(k1, (8, 16), minval=-127, maxval=127))
    w = jax.random.normal(k2, (16, 4))
    gains = quant.plane_weights() * (1 + 0.01 * jax.random.normal(k3, (7,)))
    y = osa_ops.osa_matmul_int(q, w, gains, n_planes=7, bm=8, bn=8, bk=8)
    y_ref = osa_matmul_ref(q, w, gains=gains)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.analog_guard
def test_osa_float_entrypoint(key):
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (9, 21))
    w = jax.random.normal(k2, (21, 6))
    y = osa_ops.osa_matmul(x, w, bm=8, bn=8, bk=8)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(quant.fake_quant(x) @ w),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# osa_matmul / mrr_transfer property fuzzing vs ref.py
# ---------------------------------------------------------------------------
def _check_osa_parity(m: int, k: int, n: int, bits: int, seed: int,
                      wdtype=jnp.float32) -> None:
    """Kernel == oracle for arbitrary (possibly non-tile-aligned) shapes."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    cfg = quant.QuantConfig(bits=bits)
    q = jnp.round(jax.random.uniform(k1, (m, k), minval=-cfg.qmax,
                                     maxval=cfg.qmax))
    w = jax.random.normal(k2, (k, n)).astype(wdtype)
    y = osa_ops.osa_matmul_int(q, w, quant.plane_weights(cfg),
                               n_planes=cfg.n_planes, bm=8, bn=8, bk=8)
    y_ref = osa_matmul_ref(q, w, quant_bits=bits)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=2e-3)


def _check_mrr_ideal_parity(rows: int, cols: int, seed: int,
                            lo: float, hi: float) -> None:
    """sigma=0: kernel == oracle exactly (up to interpolation tolerance)
    for arbitrary shapes, including non-lane-aligned ones."""
    w = jax.random.uniform(jax.random.PRNGKey(seed), (rows, cols),
                           minval=lo, maxval=hi)
    out_k = mt_ops.mrr_transfer(w, jax.random.PRNGKey(seed + 1),
                                sigma_dac=0.0, sigma_th=0.0)
    z = jnp.zeros_like(w)
    out_r = mt_ref.mrr_transfer_ref(w, z, z, sigma_dac=0.0, sigma_th=0.0)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=5e-4)


def _check_mrr_noisy_parity(n: int, seed: int, sigma_dac: float,
                            sigma_th: float) -> None:
    """Noisy parity: replicate ops.mrr_transfer's internal noise layout
    (flatten -> pad to (rows, 128) -> split key -> two normals) so the
    kernel and the oracle consume IDENTICAL draws."""
    key = jax.random.PRNGKey(seed)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (n,),
                           minval=-1.0, maxval=1.0)
    out_k = mt_ops.mrr_transfer(w, key, sigma_dac=sigma_dac,
                                sigma_th=sigma_th)
    rows = -(-n // 128)
    rows_pad = -(-rows // 8) * 8
    flat = jnp.pad(w, (0, rows_pad * 128 - n)).reshape(rows_pad, 128)
    k1, k2 = jax.random.split(key)
    e_dac = jax.random.normal(k1, flat.shape, flat.dtype)
    e_th = jax.random.normal(k2, flat.shape, flat.dtype)
    out_r = mt_ref.mrr_transfer_ref(flat, e_dac, e_th,
                                    sigma_dac=sigma_dac, sigma_th=sigma_th)
    np.testing.assert_allclose(np.asarray(out_k),
                               np.asarray(out_r.reshape(-1)[:n]),
                               atol=5e-4)


if hp is not None:
    @hp.given(st.integers(1, 40), st.integers(1, 64), st.integers(1, 24),
              st.sampled_from([4, 6, 8]), st.integers(0, 2 ** 16))
    @hp.settings(max_examples=12, deadline=None)
    def test_osa_parity_property(m, k, n, bits, seed):
        _check_osa_parity(m, k, n, bits, seed)

    @hp.given(st.integers(1, 40), st.integers(1, 64),
              st.integers(0, 2 ** 16))
    @hp.settings(max_examples=8, deadline=None)
    def test_osa_parity_bf16_property(m, k, seed):
        _check_osa_parity(m, k, 8, 8, seed, wdtype=jnp.bfloat16)

    @hp.given(st.integers(1, 40), st.integers(1, 40),
              st.integers(0, 2 ** 16),
              st.floats(-1.0, 0.0), st.floats(0.0, 1.0))
    @hp.settings(max_examples=10, deadline=None)
    def test_mrr_ideal_parity_property(rows, cols, seed, lo, hi):
        _check_mrr_ideal_parity(rows, cols, seed, lo, max(hi, lo + 1e-3))

    @hp.given(st.integers(1, 700), st.integers(0, 2 ** 16),
              st.floats(0.0, 0.05), st.floats(0.0, 0.1))
    @hp.settings(max_examples=10, deadline=None)
    def test_mrr_noisy_parity_property(n, seed, sigma_dac, sigma_th):
        _check_mrr_noisy_parity(n, seed, sigma_dac, sigma_th)
else:
    @pytest.mark.parametrize("m,k,n,bits,seed", [
        (1, 1, 1, 8, 0), (7, 9, 3, 4, 1), (8, 8, 8, 6, 2),
        (9, 17, 8, 8, 3), (33, 64, 24, 8, 4), (40, 5, 1, 4, 5),
        (16, 48, 9, 6, 6), (25, 31, 17, 8, 7)])
    def test_osa_parity_property(m, k, n, bits, seed):
        _check_osa_parity(m, k, n, bits, seed)

    @pytest.mark.parametrize("m,k,seed", [(5, 12, 0), (17, 33, 1),
                                          (40, 64, 2)])
    def test_osa_parity_bf16_property(m, k, seed):
        _check_osa_parity(m, k, 8, 8, seed, wdtype=jnp.bfloat16)

    @pytest.mark.parametrize("rows,cols,seed,lo,hi", [
        (1, 1, 0, -1.0, 1.0), (3, 7, 1, -0.5, 0.5), (16, 8, 2, -1.0, 0.0),
        (33, 7, 3, 0.0, 1.0), (40, 40, 4, -0.9, 0.9)])
    def test_mrr_ideal_parity_property(rows, cols, seed, lo, hi):
        _check_mrr_ideal_parity(rows, cols, seed, lo, hi)

    @pytest.mark.parametrize("n,seed,sd,sth", [
        (1, 0, 0.02, 0.04), (127, 1, 0.0, 0.1), (128, 2, 0.05, 0.0),
        (129, 3, 0.02, 0.04), (700, 4, 0.01, 0.02)])
    def test_mrr_noisy_parity_property(n, seed, sd, sth):
        _check_mrr_noisy_parity(n, seed, sd, sth)


# ---------------------------------------------------------------------------
# rosa_fused megakernel vs the composed-chain oracle
# ---------------------------------------------------------------------------
# A pinned non-ideal environment exercising every fused stage at once:
# per-shot DAC/thermal noise, static chip variation (a per-lane dv field),
# and OSA chain non-idealities.  Individual knobs zero out per-case below.
_F_NOISE = mrr.PAPER_NOISE
_F_OSA = osa.OSAConfig(splitter_imbalance=0.01, odl_loss_db_per_stage=0.05)


def _f_var(k_dim: int, seed: int) -> mrr.StaticVariation:
    dv = 0.01 * jax.random.normal(jax.random.PRNGKey(seed ^ 0xA5), (k_dim,))
    return mrr.StaticVariation(dv=dv, ddt=jnp.float32(0.05),
                               dlam=jnp.float32(1e-4))


def assert_quantized_parity(y, y_ref, *, qmax: int = 127,
                            tight: float = 2e-4) -> None:
    """Parity assertion for two implementations of the same quantized
    pipeline computed in different float op orders.

    When a conditioned activation that feeds a requantization differs
    between the two by ~1 ulp and lands within float noise of a rounding
    boundary, its 8-bit code flips by ONE.  A flip moves every output of
    that activation row by at most one requant LSB (~1/qmax of the
    output's full scale).  So: the bulk must match at float-accumulation
    tightness, deviations may never exceed the one-LSB bound, and flipped
    rows must stay rare.  (The fused kernel contracts the codes the
    composed chain's own functions compute, so it leaves only the weight
    realization's float order and the accumulation order.)"""
    y = np.asarray(y, np.float64).reshape(-1, y.shape[-1])
    r = np.asarray(y_ref, np.float64).reshape(y.shape)
    scale = max(float(np.max(np.abs(r))), 1.0)
    d = np.abs(y - r) / scale
    assert d.max() <= 2.0 / qmax, \
        f"deviation {d.max():.2e} exceeds the one-LSB flip bound"
    bad_rows = int((d.max(axis=-1) > tight).sum())
    allowed = max(2, -(-y.shape[0] // 4))
    assert bad_rows <= allowed, \
        (f"{bad_rows} rows (of {y.shape[0]}) beyond the tight tolerance — "
         "more than requant boundary flips can explain")


def _check_fused_parity(m: int, k: int, n: int, seed: int, *,
                        mapping=Mapping.WS, mode=ComputeMode.MIXED,
                        apv: bool = False, noisy: bool = True,
                        with_var: bool = True, gate=None, mgate=None,
                        pam_bits: int = 1, osa_cfg=_F_OSA) -> None:
    """Fused kernel == composed quantize->realize->OSA->dequant oracle.

    Same key in, bit-identical noise draws by contract — tolerances are
    the flip-aware quantized-parity discipline (see
    assert_quantized_parity)."""
    kx, kw, kn = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (m, k))
    w = jax.random.normal(kw, (k, n))
    noise = _F_NOISE if noisy else mrr.IDEAL
    var = _f_var(k, seed) if with_var else None
    kwargs = dict(mapping=mapping, mode=mode, noise=noise,
                  act_per_vector=apv, pam_bits=pam_bits, osa_cfg=osa_cfg)
    y = fused_ops.rosa_fused_matmul(x, w, kn, var, gate, mgate,
                                    bm=8, bn=128, bk=128, **kwargs)
    y_ref = fused_ref.rosa_fused_ref(x, w, kn, var, gate, mgate, **kwargs)
    assert_quantized_parity(y, y_ref)


_FUSED_CASES = [
    # (m, k, n, seed, kwargs) — mappings x per-vector x gates x non-ideal
    (8, 16, 8, 0, {}),
    (12, 70, 33, 1, {"mapping": Mapping.IS}),
    (12, 70, 33, 2, {"mapping": Mapping.IS, "apv": True}),
    (9, 130, 40, 3, {"apv": True}),                 # K pad lanes masked
    (17, 128, 5, 4, {"noisy": False}),              # variation-only realize
    (8, 32, 8, 5, {"noisy": False, "with_var": False}),   # ideal shortcut
    (9, 33, 8, 6, {"gate": 0.3}),
    (16, 48, 24, 7, {"mgate": 0.5, "apv": True}),   # mapping superposition
    (8, 40, 16, 8, {"mode": ComputeMode.ANALOG}),
    (8, 40, 16, 9, {"mode": ComputeMode.ANALOG, "gate": 0.7}),
    (8, 24, 8, 10, {"pam_bits": 2}),                # PAM-4 digits
]


@pytest.mark.parametrize("m,k,n,seed,kwargs", _FUSED_CASES)
def test_fused_matches_composed_chain(m, k, n, seed, kwargs):
    _check_fused_parity(m, k, n, seed, **kwargs)


if hp is not None:
    @hp.given(st.integers(1, 24), st.integers(1, 150), st.integers(1, 16),
              st.sampled_from([Mapping.WS, Mapping.IS]), st.booleans(),
              st.booleans(), st.integers(0, 2 ** 16))
    @hp.settings(max_examples=8, deadline=None)
    def test_fused_parity_property(m, k, n, mapping, apv, with_var, seed):
        _check_fused_parity(m, k, n, seed, mapping=mapping, apv=apv,
                            with_var=with_var)

    @hp.given(st.integers(1, 16), st.integers(1, 140), st.integers(1, 12),
              st.integers(0, 2 ** 16))
    @hp.settings(max_examples=4, deadline=None)
    def test_fused_analog_parity_property(m, k, n, seed):
        _check_fused_parity(m, k, n, seed, mode=ComputeMode.ANALOG)
else:
    @pytest.mark.parametrize("m,k,n,mapping,apv,with_var,seed", [
        (1, 1, 1, Mapping.WS, False, True, 0),
        (7, 129, 3, Mapping.IS, True, True, 1),
        (24, 64, 16, Mapping.WS, True, False, 2),
        (16, 150, 9, Mapping.IS, False, True, 3)])
    def test_fused_parity_property(m, k, n, mapping, apv, with_var, seed):
        _check_fused_parity(m, k, n, seed, mapping=mapping, apv=apv,
                            with_var=with_var)

    @pytest.mark.parametrize("m,k,n,seed", [(1, 1, 1, 0), (9, 140, 7, 1)])
    def test_fused_analog_parity_property(m, k, n, seed):
        _check_fused_parity(m, k, n, seed, mode=ComputeMode.ANALOG)


def test_fused_rejects_digital_mode(key):
    x = jax.random.normal(key, (8, 16))
    with pytest.raises(ValueError, match="DIGITAL"):
        fused_ops.rosa_fused_matmul(x, x.T @ x, mode=ComputeMode.DIGITAL)


# ---------------------------------------------------------------------------
# preflight defaults == launch defaults (all four kernels)
# ---------------------------------------------------------------------------
def _defaults(fn) -> dict:
    import inspect
    return {name: p.default for name, p in
            inspect.signature(fn).parameters.items()
            if p.default is not inspect.Parameter.empty}


@pytest.mark.parametrize("preflight,launchers,shared", [
    (osa_ops.preflight, [osa_ops.osa_matmul],
     ("bm", "bn", "bk", "quant_bits", "pam_bits")),
    (mt_ops.preflight, [mt_ops.mrr_transfer, mt_kernel.mrr_transfer_pallas],
     ("block_rows",)),
    (ssd_ops.preflight, [ssd_ops.ssd_scan], ("chunk",)),
    (fused_ops.preflight, [fused_ops.rosa_fused_matmul],
     ("bm", "bn", "bk", "quant_bits", "pam_bits")),
], ids=["osa_matmul", "mrr_transfer", "ssd_scan", "rosa_fused"])
def test_preflight_defaults_match_kernel_defaults(preflight, launchers,
                                                  shared):
    """The analysis sweep must price the launch configuration that actually
    runs: every default a preflight shares with its wrapper/kernel is
    pinned equal (the mrr_transfer block_rows=8 vs 256 drift hid wrong
    VMEM/grid numbers behind a green check)."""
    pre = _defaults(preflight)
    for launcher in launchers:
        got = _defaults(launcher)
        for name in shared:
            assert name in pre and name in got, \
                f"{launcher.__name__} lost shared default {name!r}"
            assert pre[name] == got[name], \
                (f"preflight default {name}={pre[name]} disagrees with "
                 f"{launcher.__name__}'s {name}={got[name]}")


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("l,chunk", [(16, 8), (24, 8), (17, 8)])
@pytest.mark.parametrize("h,g,p,s", [(4, 2, 8, 4), (2, 1, 16, 8)])
def test_ssd_kernel_matches_sequential(l, chunk, h, g, p, s, key):
    ks = jax.random.split(key, 4)
    b = 2
    x = jax.random.normal(ks[0], (b, l, h, p))
    loga = -jnp.abs(jax.random.normal(ks[1], (b, l, h))) * 0.2
    bb = jax.random.normal(ks[2], (b, l, g, s))
    cc = jax.random.normal(ks[3], (b, l, g, s))
    y, sf = ssd_ops.ssd_scan(x, loga, bb, cc, chunk=chunk)
    rep = h // g
    for bi in range(b):
        for hi in range(h):
            gi = hi // rep
            y_r, s_r = ssd_ref.ssd_scan_ref(
                x[bi, :, hi], jnp.exp(loga[bi, :, hi]), bb[bi, :, gi],
                cc[bi, :, gi])
            np.testing.assert_allclose(np.asarray(y[bi, :, hi]),
                                       np.asarray(y_r), rtol=2e-3, atol=2e-3)
            np.testing.assert_allclose(np.asarray(sf[bi, hi]),
                                       np.asarray(s_r), rtol=2e-3, atol=2e-3)


def test_ssd_chunked_ref_matches_sequential(key):
    ks = jax.random.split(key, 4)
    l, p, s = 32, 8, 4
    x = jax.random.normal(ks[0], (l, p))
    a = jnp.exp(-jnp.abs(jax.random.normal(ks[1], (l,))) * 0.3)
    bb = jax.random.normal(ks[2], (l, s))
    cc = jax.random.normal(ks[3], (l, s))
    y1, s1 = ssd_ref.ssd_scan_ref(x, a, bb, cc)
    y2, s2 = ssd_ref.ssd_scan_chunked_ref(x, a, bb, cc, chunk=8)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# mrr_transfer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(16, 8), (64, 32), (33, 7)])
def test_mrr_transfer_ideal_matches_ref(shape, key):
    w = jax.random.uniform(key, shape, minval=-1, maxval=1)
    out_k = mt_ops.mrr_transfer(w, key, sigma_dac=0.0, sigma_th=0.0)
    z = jnp.zeros_like(w)
    out_r = mt_ref.mrr_transfer_ref(w, z, z, sigma_dac=0.0, sigma_th=0.0)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=5e-4)


def test_mrr_transfer_noise_statistics(key):
    """Kernel noise std matches the behavioural model's Monte-Carlo std."""
    w = jnp.zeros((4096,))
    out = mt_ops.mrr_transfer(w.reshape(64, 64), key)
    std_kernel = float(jnp.std(out))
    std_model = float(mrr.weight_noise_std(jnp.zeros(()), key, 256))
    assert std_kernel == pytest.approx(std_model, rel=0.35)
