"""Production mesh factory.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS before the first jax call, and tests must see 1 device.

Single pod:  (16, 16)      axes ("data", "model")    = 256 chips
Multi-pod :  (2, 16, 16)   axes ("pod", "data", "model") = 512 chips;
             the "pod" axis is the DCN-like cross-pod boundary — gradients
             reduce over it, weights FSDP over (pod, data).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """`jax.make_mesh` with every axis Auto, so the model's
    `with_sharding_constraint` annotations (distributed.sharding.shard_act)
    stay hints to the partitioner instead of explicit-sharding types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Tiny mesh over however many (CPU) devices the test process has."""
    return make_mesh((data, model), ("data", "model"))
