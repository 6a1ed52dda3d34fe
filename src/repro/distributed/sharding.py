"""Logical-axis sharding rules (MaxText-style) with divisibility fallback.

Model code annotates tensors with LOGICAL axis names ("batch", "embed",
"heads", ...).  A rules table maps each name to a tuple of mesh axes; this
module resolves names -> PartitionSpec per concrete shape with two safety
rules applied left-to-right over the tensor's dims:

  1. divisibility — a mesh-axis group is only used if the dim size is an
     exact multiple of the group's device count (GSPMD could pad, but
     padded shards waste roofline and break shard_map); progressively
     shorter SUFFIXES of the group are tried (("pod","data") -> ("data",)),
     so e.g. a batch of 8 on a 2x16 (pod,data) sub-mesh falls back cleanly;
  2. no-reuse — a mesh axis claimed by an earlier dim of the same tensor is
     skipped for later dims (a KV cache can shard batch OR sequence over
     "data", never both).

Rules differ between training (FSDP on the weights' embed dim) and serving
(2-D weight sharding, cache sharded over batch/sequence).  The active
(mesh, rules) pair is installed with `use_sharding(...)`; model code calls
`shard_act` which becomes a no-op outside any context — so unit tests on
one CPU device run the identical model code.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.module import ParamDef


# ---------------------------------------------------------------------------
# Rules tables
# ---------------------------------------------------------------------------
TRAIN_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),        # DP over pods x data
    "embed": ("pod", "data"),        # FSDP / ZeRO-3 on weight d_model dims
    "heads": ("model",),             # TP
    "kv_heads": ("model",),
    "mlp": ("model",),
    "experts": ("model",),           # EP
    "vocab": ("model",),
    "cache_batch": ("pod", "data"),
    "cache_seq": ("pod", "data"),
    "act_seq": (),                   # train: sequence unsharded
}

# ZeRO-3 layout (EXPERIMENTS.md §Perf A6): batch data-parallel over the
# WHOLE mesh; weights stay 2-D sharded and are all-gathered layer-by-layer
# inside the scan.  Trades the per-layer TP activation psums (4 x (B,S,D)
# per layer) for bf16 weight gathers — and cuts per-device activation
# residency by the model-axis factor, which is what lets the 123B train
# cell fit HBM at all.
ZERO3_TRAIN_RULES: dict[str, tuple[str, ...]] = dict(
    TRAIN_RULES, batch=("pod", "data", "model"))

SERVE_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "embed": ("pod", "data"),        # 2-D weight sharding for serving
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "experts": ("model",),
    "vocab": ("model",),
    "cache_batch": ("pod", "data"),
    # KV sequence takes whatever axes the batch/kv_heads left unused —
    # batch=1 long-context cells shard 512-way over the whole mesh, while
    # decode_32k cells use "model" for whatever kv_heads couldn't cover.
    "cache_seq": ("pod", "data", "model"),
    "memory_seq": ("pod", "data", "model"),
    "act_seq": ("data",),            # prefill sequence parallelism
}

# Dims are assigned mesh axes in this order (cheap parallelism first: batch
# needs no collectives, kv_heads only an o-proj psum, sequence sharding a
# softmax-stat combine).  Position in the tensor no longer decides who wins
# a mesh axis — priority does.
_PRIORITY = ("cache_batch", "batch", "kv_heads", "heads", "experts",
             "vocab", "mlp", "cache_seq", "memory_seq", "act_seq", "embed",
             "state", "lora", "head_dim")


@dataclasses.dataclass
class ShardingCtx:
    mesh: Mesh | None
    rules: dict[str, tuple[str, ...]]


_STACK: list[ShardingCtx] = []


def current_ctx() -> ShardingCtx | None:
    return _STACK[-1] if _STACK else None


@contextlib.contextmanager
def use_sharding(mesh: Mesh | None, rules: dict[str, tuple[str, ...]]):
    _STACK.append(ShardingCtx(mesh, rules))
    try:
        yield _STACK[-1]
    finally:
        _STACK.pop()


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------
def _axis_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def resolve_spec(shape: tuple[int, ...], axes: tuple[str | None, ...],
                 rules: dict[str, tuple[str, ...]], mesh: Mesh) -> P:
    used: set[str] = set()
    parts: list[Any] = [None] * len(shape)
    order = sorted(
        range(len(shape)),
        key=lambda i: _PRIORITY.index(axes[i])
        if axes[i] in _PRIORITY else len(_PRIORITY))
    for i in order:
        dim, name = shape[i], axes[i]
        group = tuple(a for a in (rules.get(name) or ())
                      if a in mesh.shape) if name else ()
        for start in range(len(group)):
            cand = group[start:]
            size = _axis_size(mesh, cand)
            if size > 1 and dim % size == 0 \
                    and not any(a in used for a in cand):
                parts[i] = cand[0] if len(cand) == 1 else tuple(cand)
                used.update(cand)
                break
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def shard_act(x: jax.Array, *axes: str | None) -> jax.Array:
    """Sharding constraint by logical axis names; no-op without a context."""
    ctx = current_ctx()
    if ctx is None or ctx.mesh is None:
        return x
    spec = resolve_spec(x.shape, axes, ctx.rules, ctx.mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Pytree shardings
# ---------------------------------------------------------------------------
def param_shardings(skel, mesh: Mesh, rules: dict[str, tuple[str, ...]]):
    """Skeleton of ParamDef -> pytree of NamedSharding."""
    return jax.tree.map(
        lambda d: NamedSharding(mesh, resolve_spec(d.shape, d.axes, rules,
                                                   mesh)),
        skel, is_leaf=lambda x: isinstance(x, ParamDef))


def tree_shardings(shapes_tree, axes_tree, mesh: Mesh,
                   rules: dict[str, tuple[str, ...]]):
    """Zip a ShapeDtypeStruct tree with a logical-axes tree -> shardings."""
    flat_s, treedef = jax.tree.flatten(shapes_tree)
    flat_a = treedef.flatten_up_to(axes_tree)
    out = [NamedSharding(mesh, resolve_spec(s.shape, a, rules, mesh))
           for s, a in zip(flat_s, flat_a)]
    return jax.tree.unflatten(treedef, out)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Slot (serving batch) sharding — repro.serve
#
# Continuous-batching decode is embarrassingly parallel over slots: every
# slot's token depends only on its own cache row.  The serve step therefore
# runs under a jax.shard_map whose in/out specs shard every
# state leaf on its slot dimension; these helpers build those specs from
# the cache's logical-axes tree, so the serve subsystem never hand-indexes
# leaf ranks.
# ---------------------------------------------------------------------------
def spec_on_dim(ndim: int, dim: int, axes: str | tuple[str, ...]) -> P:
    """PartitionSpec placing `axes` on dimension `dim` of a rank-`ndim`
    tensor, every other dimension unsharded."""
    parts: list[Any] = [None] * ndim
    if not isinstance(axes, str) and len(axes) == 1:
        axes = axes[0]
    parts[dim] = axes
    return P(*parts)


def slot_dim_specs(axes_tree, template, mesh_axes: tuple[str, ...],
                   name: str = "cache_batch"):
    """Spec pytree sharding every leaf's `name` logical dim over
    `mesh_axes`.  `template` fixes leaf ranks; `axes_tree` is the logical
    axes pytree (models.model.cache_axes for a decode cache)."""
    flat_t, treedef = jax.tree.flatten(template)
    flat_a = treedef.flatten_up_to(axes_tree)
    specs = [spec_on_dim(t.ndim, a.index(name), mesh_axes)
             for t, a in zip(flat_t, flat_a)]
    return jax.tree.unflatten(treedef, specs)


# ---------------------------------------------------------------------------
# Expert-parallel shard_map in_specs (see models/moe.py)
# ---------------------------------------------------------------------------
def ep_param_specs(p: dict, fsdp: tuple[str, ...] | None) -> dict:
    """PartitionSpecs for the MoE param dict entering shard_map.

    Experts over `model`; d_model dims stay FSDP-sharded (gathered inside);
    the router is needed in full on every shard (GSPMD all-gathers it).
    """
    f = tuple(fsdp) if fsdp else None
    fs = (f if f else None)
    specs = {
        "router": P(None, None),
        "wi": P("model", fs, None, None),
        "wo": P("model", None, fs),
    }
    if "shared_wi" in p:
        specs["shared_wi"] = P(fs, None, "model")
        specs["shared_wo"] = P("model", fs)
    return specs
