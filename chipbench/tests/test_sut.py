"""The served model is built from its configuration file alone.

Dense files build the same program config as they always have; an
`mla_moe` file (MLA attention, routed and shared experts, one leading
dense layer) is served end to end at smoke sizes on the CPU; and each
file the serving path cannot take is refused with the reason.
"""

import copy
import dataclasses
import json
from pathlib import Path

import pytest

from chipbench import harness, sut, traffic, weights

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
# the dense rule every committed configuration was built by
DENSE_KEYS = ("n_layers", "d_model", "vocab", "n_heads", "n_kv_heads",
              "head_dim", "d_ff", "qk_norm", "rope_theta", "norm_eps",
              "tie_embeddings")
NORMS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm", "kv_norm")


def load(path: Path) -> dict:
    return json.loads(path.read_text())


def mla_moe_file() -> dict:
    return load(DATA / "deepseek-v2-smoke.json")


def moe_file() -> dict:
    """A GQA-attention MoE file at the program's qwen3-moe smoke sizes."""
    cfg = load(ROOT / "chipbench/configs/qwen3-32b.json")
    for k in ("d_ff", "published", "reduced"):
        del cfg[k]
    cfg.update(name="qwen3-moe-smoke", program_config="qwen3-moe-235b-a22b",
               n_layers=2, d_model=64, vocab=256, n_heads=4, n_kv_heads=2,
               head_dim=16, moe={"n_experts": 8, "top_k": 2, "d_model": 64,
                                 "d_ff": 32, "capacity_factor": 2.0})
    return cfg


def tiny_mix() -> dict:
    mix = load(ROOT / "chipbench/mixes/code.json")
    mix["serve"].update(n_slots=4, max_len=128, prefill_chunk=32)
    mix["slice_requests"] = 6
    mix["prompt"].update(median=16, sigma=0.5, min=4, max=40)
    mix["output"].update(median=8, sigma=0.3, min=4, max=16)
    return mix


def leaf_init(names: tuple[str, ...], shape) -> tuple[str, float]:
    if names[-1] in NORMS:
        return "around_one", 0.1
    if names[-1] == "embed":
        return "normal", 1.0
    return "normal", 0.1


def seeded(cfg: dict, seed: int = 2**31 + 3):
    return weights.make(sut.abstract_params(cfg), leaf_init, seed)


@pytest.mark.parametrize("name", ["qwen3-32b", "deepseek-67b"])
def test_dense_file_builds_the_same_config(name):
    from repro.configs import get_config
    cfg = load(ROOT / f"chipbench/configs/{name}.json")
    want = dataclasses.replace(get_config(cfg["program_config"]),
                               **{k: cfg[k] for k in DENSE_KEYS})
    assert sut.model_config(cfg) == want


def test_mla_moe_file_is_served_end_to_end():
    harness.prepare_env()
    cfg, mix = mla_moe_file(), tiny_mix()
    mc = sut.model_config(cfg)
    assert mc.family == "mla_moe" and mc.first_dense_ff == 128
    for block in ("mla", "moe"):
        for k, v in cfg[block].items():
            assert getattr(getattr(mc, block), k) == v, (block, k)
    served = sut.Served(cfg, mix, seeded(cfg))
    assert served.plan() == cfg["optical"]["mapping"]
    assert served.gemms() == [("mlp/wi", 64, 256), ("mlp/wo", 128, 64)]
    served.warm()
    reqs = traffic.make_slice(mix, 2**31 + 7, 0, cfg["vocab"])
    rep = served.run(reqs)
    for r in reqs:
        toks = rep.completions[r["rid"]].tokens
        assert len(toks) == r["max_new_tokens"], r["rid"]
        assert all(0 <= t < cfg["vocab"] for t in toks)


def unknown_block_key(cfg):
    cfg["moe"]["expert_share"] = 4
    return "block 'moe' states \\['expert_share'\\], which MoEConfig"


def disagreeing_d_model(cfg):
    cfg["mla"]["d_model"] = 128
    return "mla.d_model 128 disagrees with d_model 64"


def disagreeing_rope_theta(cfg):
    cfg["rope_theta"] = 1e6
    return "mla.rope_theta 10000.0 disagrees with rope_theta 1000000.0"


def unstated_reduced_key(cfg):
    cfg["reduced"]["moe.n_groups"] = "8 -> 1"
    return "reduced names 'moe.n_groups', which the file does not state"


def unpublished_reduced_key(cfg):
    del cfg["published"]["moe.n_experts"]
    return "reduced names 'moe.n_experts', but published gives no uncut"


def size_the_family_does_not_read(cfg):
    cfg["d_ff"] = 1536
    return "states \\['d_ff'\\], which family 'mla_moe' does not read"


def unstated_size(cfg):
    del cfg["first_dense_ff"]
    return "reads \\['first_dense_ff'\\], which the file does not state"


def unstated_block(cfg):
    del cfg["mla"]
    return "has a 'mla' sub-config, which the file does not state"


def ssm_family(cfg):
    cfg["program_config"] = "mamba2-1.3b"
    return "family 'ssm' is not served: the serving path has no chunked"


def block_on_dense_family(cfg):
    dense = load(ROOT / "chipbench/configs/qwen3-32b.json")
    cfg.clear()
    cfg.update(dense, moe={"n_experts": 8})
    return "block 'moe' on family 'dense', which has no such sub-config"


@pytest.mark.parametrize("fault", [
    unknown_block_key, disagreeing_d_model, disagreeing_rope_theta,
    unstated_reduced_key, unpublished_reduced_key,
    size_the_family_does_not_read, unstated_size, unstated_block, ssm_family,
    block_on_dense_family])
def test_file_is_refused_with_its_reason(fault):
    cfg = copy.deepcopy(mla_moe_file())
    why = fault(cfg)
    with pytest.raises(ValueError, match=why):
        sut.model_config(cfg)


def test_moe_file_with_no_optical_gemm_is_refused():
    harness.prepare_env()
    cfg = moe_file()
    assert sut.model_config(cfg).moe.n_experts == 8
    with pytest.raises(ValueError, match="routes no GEMM through the "
                       "optical engine"):
        sut.Served(cfg, tiny_mix(), seeded(cfg))

