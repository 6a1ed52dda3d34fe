"""FLOPs, bytes, least time and peaks against hand counts."""

import pytest

from chipbench import work

V5E = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def test_gemm_counts_by_hand():
    # decode GEMM of qwen3-32b's gate|up projection: 16 x 5120 x 51200
    assert work.gemm_flops(16, 5120, 51200) == 2 * 16 * 5120 * 51200 \
        == 8_388_608_000
    # f32: 16*5120 + 5120*51200 + 16*51200 words of 4 bytes
    assert work.gemm_bytes(16, 5120, 51200) == 4 * (81_920 + 262_144_000
                                                    + 819_200) \
        == 1_052_180_480
    # prefill chunk of the down projection: 256 x 25600 x 5120
    assert work.gemm_flops(256, 25600, 5120) == 67_108_864_000
    assert work.gemm_bytes(256, 25600, 5120) == 4 * (6_553_600
                                                     + 131_072_000
                                                     + 1_310_720)


def test_least_time_picks_the_binding_roof():
    t, bound = work.least_time_s(8_388_608_000, 1_052_180_480, V5E)
    assert bound == "memory" and t == pytest.approx(1_052_180_480 / 819e9)
    t, bound = work.least_time_s(2 * 4096**3, 3 * 4096**2 * 2, V5E)
    assert bound == "compute" and t == pytest.approx(2 * 4096**3 / 197e12)


def test_kernel_work_sums_calls():
    gemms = [("mlp/wi", 5120, 51200), ("mlp/wo", 25600, 5120)]
    flops, nbytes, least, by = work.kernel_work(gemms, {16: 3, 256: 2}, V5E)
    want = 3 * (work.gemm_flops(16, 5120, 51200)
                + work.gemm_flops(16, 25600, 5120)) \
        + 2 * (work.gemm_flops(256, 5120, 51200)
               + work.gemm_flops(256, 25600, 5120))
    assert flops == want
    assert least == pytest.approx(sum(by.values()))
    assert set(by) == {"memory"}


def test_peaks_table():
    assert work.peaks("TPU v5 lite") == {"flops_per_s": 197e12,
                                         "bytes_per_s": 819e9,
                                         "hbm_bytes": 16e9}
    for kind in ("cpu", "TPU v4", "NVIDIA H100"):
        with pytest.raises(KeyError):
            work.peaks(kind)


def test_model_flops_by_hand():
    cfg = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim": 2,
           "d_ff": 8, "n_layers": 3, "vocab": 10}
    # per layer: q 4*4 + k,v 2*(4*2) + o 4*4 = 48; mlp 3*4*8 = 96
    assert work.dense_matmul_params(cfg) == 3 * (48 + 96)
    # prompt 3 + 2 new tokens: 4 tokens fed, contexts 1+2+3+4 = 10
    want = 4 * 2 * 432 + 4 * 3 * 2 * 2 * 10 + 2 * 4 * 10 * 2
    assert work.model_flops(cfg, 3, 2) == want
