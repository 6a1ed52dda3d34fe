"""The work the algorithm needs, computed from shapes, and the chip's peaks.

Every count here is the algorithm's, not an implementation's: a GEMM of an
(M, K) activation by a (K, N) weight does 2*M*K*N operations and moves the
activation, the weight and the output once, at the dtypes the model holds
them.  Tile padding inside a kernel and the noise-offset streams a wrapper
materializes are not counted, so a kernel that pads or re-reads more reads
as further from its roofline, never as doing more work.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip of `device_kind`.  A device that
    is not in the table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def gemm_flops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def gemm_bytes(m: int, k: int, n: int, act_bytes: int = 4,
               weight_bytes: int = 4, out_bytes: int = 4) -> float:
    """Activation (M*K) + weight (K*N) + output (M*N), each once."""
    return float(m * k * act_bytes + k * n * weight_bytes + m * n * out_bytes)


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which roof bounds it."""
    t_c = flops / peak["flops_per_s"]
    t_m = nbytes / peak["bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def kernel_work(gemms, calls: dict, peak: dict, dtype_bytes: int = 4):
    """Summed FLOPs, bytes and least time of a kernel's calls.

    `gemms` are (name, k, n) of the GEMMs one layer routes through the
    kernel; `calls` maps an activation row count M to how many times each
    of those GEMMs ran with it (layers x steps).  Returns
    (flops, bytes, least_s, {bound: least_s}).
    """
    flops = nbytes = least = 0.0
    by_bound: dict = {}
    for m, count in calls.items():
        for _, k, n in gemms:
            f = gemm_flops(m, k, n)
            b = gemm_bytes(m, k, n, dtype_bytes, dtype_bytes, dtype_bytes)
            t, bound = least_time_s(f, b, peak)
            flops += count * f
            nbytes += count * b
            least += count * t
            by_bound[bound] = by_bound.get(bound, 0.0) + count * t
    return flops, nbytes, least, by_bound


def dense_matmul_params(cfg: dict) -> int:
    """Weights that multiply a token's activations in one forward pass of a
    dense GQA decoder, the LM head excluded."""
    d, h, kv, hd, f = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * f
    return cfg["n_layers"] * (attn + mlp)


def model_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """FLOPs the model needs to serve one request: every prompt token and
    every generated token that is fed back passes the layers once
    (2 x matmul weights, plus attention's 4*L*H*d_head*context over the
    positions it attends to), and every generated token takes one LM-head
    product (2 * d_model * vocab)."""
    fed = prompt_len + new_tokens - 1      # the last token is never fed back
    per_tok = 2.0 * dense_matmul_params(cfg)
    attn = 4.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"]
    # token at position p attends to p + 1 positions: sum_{p < fed} (p + 1)
    context = fed * (fed + 1) / 2.0
    head = 2.0 * cfg["d_model"] * cfg["vocab"] * new_tokens
    return fed * per_tok + attn * context + head
