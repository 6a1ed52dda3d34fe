"""Per-architecture smoke tests (deliverable f): reduced configs of all 10
assigned families run a forward/train step on CPU, asserting shapes and
finiteness; decode agrees with prefill."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, get_smoke
from repro.models.model import (ASSIGNED_SHAPES, SMOKE_SHAPES, applicable,
                                build_model, pad_cache)
from repro.models.moe import MoEConfig, moe_def, moe_ep_local, moe_ref
from repro.models.module import init_params


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_finite(arch, key):
    cfg = get_smoke(arch)
    bundle = build_model(cfg)
    params = bundle.init(key)
    batch, _ = bundle.input_specs(SMOKE_SHAPES["train_4k"], concrete=True,
                                  key=key)
    loss, grads = jax.value_and_grad(
        lambda p: bundle.train_loss(p, batch))(params)
    assert jnp.isfinite(loss)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes(arch, key):
    cfg = get_smoke(arch)
    bundle = build_model(cfg)
    params = bundle.init(key)
    batch, _ = bundle.input_specs(SMOKE_SHAPES["train_4k"], concrete=True,
                                  key=key)
    x = bundle.forward(params, batch)
    assert x.shape[0] == batch["tokens"].shape[0]
    assert x.shape[-1] == cfg.d_model
    assert bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_prefill(arch, key):
    cfg = dataclasses.replace(get_smoke(arch), cache_dtype=jnp.float32)
    bundle = build_model(cfg)
    params = bundle.init(key)
    batch, _ = bundle.input_specs(SMOKE_SHAPES["prefill_32k"], concrete=True,
                                  key=key)
    logits_p, cache = bundle.prefill(params, batch)
    cache = pad_cache(cfg, cache, 4)
    nxt = jnp.argmax(logits_p, -1)
    logits_d, _ = bundle.decode_step(
        params, {"token": nxt, "pos": cache["pos"], "cache": cache})
    batch2 = dict(batch)
    batch2["tokens"] = jnp.concatenate([batch["tokens"], nxt[:, None]], 1)
    logits_ref, _ = bundle.prefill(params, batch2)
    np.testing.assert_allclose(np.asarray(logits_d), np.asarray(logits_ref),
                               rtol=0.05, atol=0.05)


def test_full_configs_match_assignment():
    """The full configs carry the exact published dimensions."""
    spec = {
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 151936),
        "deepseek-v2-236b": (60, 5120, 128, 128, 102400),
        "qwen3-32b": (64, 5120, 64, 8, 151936),
        "deepseek-67b": (95, 8192, 64, 8, 102400),
        "mistral-large-123b": (88, 12288, 96, 8, 32768),
        "gemma3-12b": (48, 3840, 16, 8, 262144),
        "mamba2-1.3b": (48, 2048, 0, 0, 50280),
        "seamless-m4t-medium": (12, 1024, 16, 16, 256206),
        "phi-3-vision-4.2b": (32, 3072, 32, 32, 32064),
        "zamba2-1.2b": (38, 2048, 32, 32, 32000),
    }
    for name, (nl, dm, h, kv, v) in spec.items():
        cfg = get_config(name)
        assert cfg.n_layers == nl and cfg.d_model == dm and cfg.vocab == v
        if h:
            assert cfg.n_heads == h and cfg.n_kv_heads == kv
    assert get_config("qwen3-moe-235b-a22b").moe.n_experts == 128
    assert get_config("qwen3-moe-235b-a22b").moe.top_k == 8
    assert get_config("deepseek-v2-236b").moe.n_experts == 160
    assert get_config("deepseek-v2-236b").moe.top_k == 6
    assert get_config("deepseek-v2-236b").mla.kv_lora == 512
    assert get_config("mamba2-1.3b").ssm.d_state == 128
    assert get_config("zamba2-1.2b").ssm.d_state == 64


def test_long_500k_applicability():
    """Skip/run rules for the long-context shape per DESIGN.md."""
    runs = {a: applicable(get_config(a), ASSIGNED_SHAPES["long_500k"])[0]
            for a in ARCHS}
    assert runs["gemma3_12b"] and runs["mamba2_1p3b"] and runs["zamba2_1p2b"]
    assert sum(runs.values()) == 3


def test_moe_ep_equals_ref(key):
    """shard_map EP path == dense reference on a 1x1 mesh (no dropping)."""
    cfg = MoEConfig(n_experts=4, top_k=2, d_model=16, d_ff=8,
                    capacity_factor=8.0, n_shared=1)
    params = init_params(moe_def(cfg), key)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 16))
    y_ref = moe_ref(params, cfg, x)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    from jax.sharding import PartitionSpec as P
    y_ep = jax.shard_map(
        lambda p, xl: moe_ep_local(p, cfg, x_local=xl, fsdp_axes=()),
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), params), P()),
        out_specs=P(), check_vma=False)(params, x)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ep),
                               rtol=2e-3, atol=2e-3)


def test_gemma_window_pattern():
    from repro.models.transformer import layer_meta
    cfg = get_config("gemma3-12b")
    meta = layer_meta(cfg)
    w = np.asarray(meta["window"])
    assert (w == 0).sum() == 8            # 8 global layers
    assert (w == 1024).sum() == 40        # 40 local layers
    assert w[5] == 0 and w[0] == 1024     # every 6th is global
