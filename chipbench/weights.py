"""Seeded weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program and the plain
reference are both handed these arrays, so the reference takes nothing the
program made.  The layout (names and shapes) is the one the program serves;
how each leaf is drawn is the reference module's `leaf_init` rule, keyed by
the leaf's path.  Each leaf's key folds the CRC of its path into the seed's
key, so a leaf's values do not depend on which other leaves exist.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 2**63: the low 32 bits seed
    the key, the high bits are folded in."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def path_names(path) -> tuple[str, ...]:
    return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def make(abstract, leaf_init, seed: int, dtype=jnp.float32):
    """Weights shaped like `abstract` (a pytree of ShapeDtypeStructs)."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    rules = [leaf_init(path_names(p), leaf.shape) for p, leaf in paths]
    crcs = [zlib.crc32("/".join(path_names(p)).encode()) & 0x7FFFFFFF
            for p, _ in paths]

    def build(key):
        out = []
        for (_, leaf), (kind, scale), crc in zip(paths, rules, crcs):
            z = jax.random.normal(jax.random.fold_in(key, crc), leaf.shape,
                                  jnp.float32)
            if kind == "normal":
                v = scale * z
            elif kind == "around_one":
                v = 1.0 + scale * z
            else:
                raise ValueError(f"unknown init kind {kind!r}")
            out.append(v.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))
