"""Checkpointing: atomicity, keep-K, elastic restore."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt


def _tree(key):
    k1, k2 = jax.random.split(key)
    return {"a": jax.random.normal(k1, (8, 4)),
            "nested": {"b": jax.random.normal(k2, (3,)),
                       "step": jnp.asarray(7)}}


def test_save_restore_roundtrip(tmp_path, key):
    t = _tree(key)
    ckpt.save(str(tmp_path), 5, t)
    out = ckpt.restore(str(tmp_path), 5, jax.tree.map(jnp.zeros_like, t))
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_step_ignores_tmp_dirs(tmp_path, key):
    t = _tree(key)
    ckpt.save(str(tmp_path), 1, t)
    ckpt.save(str(tmp_path), 2, t)
    os.makedirs(tmp_path / "step_00000099.tmp-garbage")
    assert ckpt.latest_step(str(tmp_path)) == 2


def test_keep_k_gc(tmp_path, key):
    mgr = ckpt.CheckpointManager(str(tmp_path), every=1, keep=2)
    t = _tree(key)
    for s in range(1, 6):
        mgr.maybe_save(s, t)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert steps == ["step_00000004", "step_00000005"]


def test_restore_shape_mismatch_raises(tmp_path, key):
    t = _tree(key)
    ckpt.save(str(tmp_path), 1, t)
    bad = dict(t, a=jnp.zeros((2, 2)))
    with pytest.raises(ValueError, match="shape mismatch"):
        ckpt.restore(str(tmp_path), 1, bad)


def test_elastic_restore_recreates_sharding(tmp_path, key):
    """Arrays are stored topology-free; restore re-places per sharding."""
    t = _tree(key)
    ckpt.save(str(tmp_path), 3, t)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    out = ckpt.restore(str(tmp_path), 3, jax.tree.map(jnp.zeros_like, t),
                       shardings=sh)
    assert all(o.sharding == NamedSharding(mesh, P())
               for o in jax.tree.leaves(out))


def test_meta_roundtrip(tmp_path, key):
    ckpt.save(str(tmp_path), 9, _tree(key), meta={"arch": "x", "loss": 1.5})
    m = ckpt.read_meta(str(tmp_path), 9)
    assert m["meta"]["arch"] == "x"
    assert m["step"] == 9
