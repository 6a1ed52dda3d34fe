"""flash_decode (sharded-KV decode attention) vs the gather-free oracle.

Needs >1 device to exercise the shard_map, so it runs a subprocess with 4
forced host devices and a (1, 4) mesh: the KV sequence shards over "model"
(kv_heads=2 is indivisible by 4, mirroring the gemma long_500k cell).
"""

import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed.sharding import SERVE_RULES, use_sharding, resolve_spec
from repro.launch.mesh import make_mesh
from repro.models import layers as L

mesh = make_mesh((1, 4), ("data", "model"))
cfg = L.AttnConfig(d_model=32, n_heads=4, n_kv_heads=2, head_dim=8)
key = jax.random.PRNGKey(0)
B, S = 2, 64
q = jax.random.normal(key, (B, 1, 4, 8))
kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, 2, 8))
vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, 2, 8))
pos = jnp.full((B,), 40, jnp.int32)

# oracle: plain masked attention over the full cache
k_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
bias = L._mask_bias(pos[:, None], k_pos, True, 0, k_len_valid=(pos + 1)[:, None])
o_ref = L.attention_core(q, L._repeat_kv(kc, 4), L._repeat_kv(vc, 4), bias)

with use_sharding(mesh, SERVE_RULES):
    spec = resolve_spec(kc.shape, ("cache_batch", "cache_seq", "kv_heads",
                                   "head_dim"), SERVE_RULES, mesh)
    assert spec[1] is not None, f"seq not sharded: {spec}"
    kc_s = jax.device_put(kc, NamedSharding(mesh, spec))
    vc_s = jax.device_put(vc, NamedSharding(mesh, spec))
    def f(q, kc, vc, pos):
        return L.flash_decode(q, kc, vc, pos, 0, 4)
    o = jax.jit(f)(q, kc_s, vc_s, pos)

err = float(jnp.max(jnp.abs(o - o_ref)))
print("flash_decode max err:", err)
assert err < 2e-5, err

# windowed variant (sliding-window layers)
bias_w = L._mask_bias(pos[:, None], k_pos, True, 8, k_len_valid=(pos + 1)[:, None])
o_ref_w = L.attention_core(q, L._repeat_kv(kc, 4), L._repeat_kv(vc, 4), bias_w)
with use_sharding(mesh, SERVE_RULES):
    o_w = jax.jit(lambda q, k, v, p: L.flash_decode(q, k, v, p, 8, 4))(
        q, kc_s, vc_s, pos)
err_w = float(jnp.max(jnp.abs(o_w - o_ref_w)))
print("flash_decode windowed max err:", err_w)
assert err_w < 2e-5, err_w
print("OK")
"""


def test_flash_decode_matches_oracle():
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    assert "OK" in r.stdout
