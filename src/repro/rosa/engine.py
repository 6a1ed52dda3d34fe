"""`Engine` — the single entry point onto the optical path.

The Engine owns the three things every consumer used to re-thread by hand:

  * an `ExecutionPlan` (per-layer RosaConfig resolution, hybrid IS/WS
    mapping included),
  * a base PRNG key plus deterministic per-layer / per-step folding, so
    callers stop plumbing `key=None` through every signature,
  * an optional `EnergyLedger` that records each routed matmul's GEMM shape
    at trace time for trace-based EDP accounting.

Backend selection (dense einsum / pure-jnp OSA ref / Pallas kernel) lives
on each layer's `RosaConfig.backend` and resolves through the registry in
`rosa.backends` — there is no boolean kernel toggle.

Usage:

    key = jax.random.split(caller_key)[0]        # thread, never re-seed:
    engine = Engine.from_hybrid_plan(RosaConfig(noise=mrr.PAPER_NOISE),
                                     {"conv3": Mapping.IS}, key=key)
    y = engine.matmul(x, w, name="conv3")        # folded key, plan config

A constant-baked key (`key=jax.random.PRNGKey(0)` at a call site) makes
every run realize the same device noise — `repro.analysis`'s PRNG check
flags exactly that pattern (PRNG002/PRNG003).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import warnings
import zlib
from typing import Iterable, Mapping as TMapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mrr
from repro.core.constants import Mapping
from repro.obs import trace as obs
from repro.rosa.backends import (DEFAULT, RosaConfig, condition_weight,
                                 rosa_matmul)
from repro.rosa.ledger import EnergyLedger
from repro.rosa.plan import ExecutionPlan


# Context-LOCAL ambient engine: a ContextVar, not a module-global stack, so
# concurrent serving threads (and asyncio tasks) each see only the engine
# they installed — installing an engine in one request handler can never
# leak into another thread's trace.
_ENGINE_VAR: contextvars.ContextVar["Engine | None"] = \
    contextvars.ContextVar("rosa_ambient_engine", default=None)


def ambient_engine() -> "Engine | None":
    """The innermost engine installed by `engine_context`, or None.

    Model code that routes matmuls optically but takes no engine parameter
    (e.g. a scanned transformer stack with `rosa_mlp=True`) resolves its
    engine here at TRACE time — so a serving loop can pin one fabricated
    chip (`Engine.with_variation`), a hybrid mapping plan and an
    `EnergyLedger` without threading the engine through every model
    signature.  Keep the context active around the `jax.jit` call: it is
    consulted while tracing, not at run time.  Prefer `rosa.compile` — a
    `Program` installs its engine around its own traces, so callers never
    manage this context by hand.
    """
    return _ENGINE_VAR.get()


@contextlib.contextmanager
def engine_context(engine: "Engine | None"):
    """Install `engine` as the ambient optical engine for model code.

    Context-local (thread- and task-safe): nested installs restore the
    previous engine on exit, and other threads are unaffected.
    """
    token = _ENGINE_VAR.set(engine)
    try:
        yield engine
    finally:
        _ENGINE_VAR.reset(token)


def current_engine() -> "Engine | None":
    """Deprecated alias of `ambient_engine` (pre-Program API)."""
    warnings.warn(
        "rosa.current_engine is deprecated; use rosa.ambient_engine(), or "
        "better, rosa.compile(...) which threads the engine for you",
        DeprecationWarning, stacklevel=2)
    return ambient_engine()


def use_engine(engine: "Engine"):
    """Deprecated alias of `engine_context` (pre-Program API)."""
    warnings.warn(
        "rosa.use_engine is deprecated; use rosa.engine_context(engine), or "
        "better, rosa.compile(...) which installs the engine around its own "
        "traces", DeprecationWarning, stacklevel=2)
    return engine_context(engine)


def layer_key(base: jax.Array, name: str, step: int | jax.Array = 0
              ) -> jax.Array:
    """Deterministic per-layer/per-step key: fold the layer name's CRC and
    the step counter into the base key.  Same (base, name, step) -> same
    noise draw, independent draws across layers and steps.
    """
    k = jax.random.fold_in(base, zlib.crc32(name.encode("utf-8")) & 0x7FFFFFFF)
    return jax.random.fold_in(k, step)


@dataclasses.dataclass(frozen=True)
class Engine:
    """Routes every named matmul through the resolved execution plan.

    `variation` pins one sampled chip (`{layer: mrr.StaticVariation}`,
    drawn by `repro.robust.variation`) so every forward — including a
    serving decode loop — sees the SAME fabricated device deterministically;
    `gates` carries traced per-layer scalars in [0, 1] blending the analog
    path against the exact digital one (the vectorized perturb-one-layer
    selector of `repro.robust.sensitivity`); `mapping_gates` carries traced
    per-layer WS/IS selectors ({0=WS, 1=IS}) so a whole hybrid plan becomes
    a float vector — a vmap axis for the MC-verified plan search.
    """

    plan: ExecutionPlan = ExecutionPlan()
    key: jax.Array | None = None
    ledger: EnergyLedger | None = None
    variation: TMapping[str, mrr.StaticVariation] | None = None
    gates: TMapping[str, jax.Array] | None = None
    mapping_gates: TMapping[str, jax.Array] | None = None

    # -- constructors -------------------------------------------------------
    @classmethod
    def dense(cls) -> "Engine":
        """All layers exact dense einsum (no optical path)."""
        return cls(ExecutionPlan())

    @classmethod
    def from_config(cls, cfg: RosaConfig = DEFAULT,
                    layers: Iterable[str] | None = None,
                    key: jax.Array | None = None,
                    ledger: EnergyLedger | None = None) -> "Engine":
        """Every layer runs the same RosaConfig."""
        return cls(ExecutionPlan.build(cfg, None, layers), key, ledger)

    @classmethod
    def from_layer_cfgs(cls, cfgs: TMapping[str, RosaConfig | None],
                        layers: Iterable[str] | None = None,
                        key: jax.Array | None = None,
                        ledger: EnergyLedger | None = None) -> "Engine":
        """Explicit per-layer configs; unnamed layers are dense."""
        return cls(ExecutionPlan.build(None, dict(cfgs), layers), key, ledger)

    @classmethod
    def from_hybrid_plan(cls, cfg: RosaConfig,
                         plan: TMapping[str, Mapping] | None,
                         layers: Iterable[str] | None = None,
                         key: jax.Array | None = None,
                         ledger: EnergyLedger | None = None) -> "Engine":
        """`cfg` everywhere, with the mapping field overridden per layer by
        a `{layer: Mapping}` hybrid plan (core.mapping.hybrid_plan).
        """
        return cls(ExecutionPlan.from_mapping_plan(cfg, plan or {}, layers),
                   key, ledger)

    # -- derivations --------------------------------------------------------
    def with_key(self, key: jax.Array | None) -> "Engine":
        """Copy of the engine with the per-shot PRNG key replaced."""
        return dataclasses.replace(self, key=key)

    def with_ledger(self, ledger: EnergyLedger | None) -> "Engine":
        """Copy of the engine with the energy ledger replaced."""
        return dataclasses.replace(self, ledger=ledger)

    def with_plan(self, plan: ExecutionPlan) -> "Engine":
        """Copy of the engine with the execution plan replaced."""
        return dataclasses.replace(self, plan=plan)

    def with_variation(self, variation: TMapping[str, mrr.StaticVariation]
                       | None) -> "Engine":
        """Pin one sampled chip: every subsequent matmul of layer `name`
        applies `variation[name]` (layers absent from the dict run
        variation-free).  Pass None to unpin.
        """
        return dataclasses.replace(
            self, variation=dict(variation) if variation is not None
            else None)

    def with_gates(self, gates: TMapping[str, jax.Array] | None) -> "Engine":
        """Per-layer analog/digital blend gates (traced scalars in [0,1])."""
        return dataclasses.replace(
            self, gates=dict(gates) if gates is not None else None)

    def with_mapping_gates(self, mapping_gates: TMapping[str, jax.Array]
                           | None) -> "Engine":
        """Per-layer WS/IS selectors ({0=WS, 1=IS}, traced): superpose the
        two mapping orientations so plan candidates can be vmapped.
        """
        return dataclasses.replace(
            self, mapping_gates=dict(mapping_gates)
            if mapping_gates is not None else None)

    # -- resolution ---------------------------------------------------------
    @property
    def is_dense(self) -> bool:
        """Whether every layer resolves to the dense digital path."""
        return self.plan.is_dense

    def config(self, name: str) -> RosaConfig | None:
        """Resolved per-layer config (None = dense fallback)."""
        return self.plan.resolve(name)

    def key_for(self, name: str, step: int | jax.Array = 0
                ) -> jax.Array | None:
        """Per-layer, per-step PRNG key, or None when keyless."""
        return None if self.key is None else layer_key(self.key, name, step)

    def variation_for(self, name: str) -> mrr.StaticVariation | None:
        """The pinned chip's variation for one layer, if any."""
        return None if self.variation is None else self.variation.get(name)

    def gate_for(self, name: str) -> jax.Array | None:
        """The analog-blend gate for one layer, if any."""
        return None if self.gates is None else self.gates.get(name)

    def mapping_gate_for(self, name: str) -> jax.Array | None:
        """The WS/IS mapping gate for one layer, if any."""
        return None if self.mapping_gates is None \
            else self.mapping_gates.get(name)

    # -- the routed matmul --------------------------------------------------
    def matmul(self, x: jax.Array, w: jax.Array, *, name: str = "",
               step: int | jax.Array = 0,
               key: jax.Array | None = None,
               w_scale: jax.Array | None = None) -> jax.Array:
        """Compute y = x @ w through this layer's resolved config.

        x: (..., K); w: (K, N).  An explicit `key` overrides the engine's
        folded per-layer key.  `w_scale` is `w`'s precomputed full-scale
        (`quant.absmax_scale(w)`), for weights that stay fixed across
        calls.  Dense layers (resolved config None) contract exactly in
        the caller's dtype.
        """
        cfg = self.plan.resolve(name)
        if obs.enabled():
            # fires at JAX trace time only — one instant per traced matmul,
            # none per executed step — so the compile timeline shows every
            # shape the engine routes (and which fall through to dense)
            obs.instant("rosa.matmul", "compile", layer=name or "unnamed",
                        m=int(np.prod(x.shape[:-1], dtype=np.int64)),
                        k=int(x.shape[-1]), n=int(w.shape[-1]),
                        dense=cfg is None)
        if cfg is None:
            return jnp.einsum("...k,kn->...n", x, w)
        if self.ledger is not None:
            # unnamed matmuls get a shape-stable synthetic name so re-traces
            # and MC loops dedupe to one event instead of inflating EDP;
            # the flip side is that distinct unnamed layers of identical
            # (m, k, n) collapse into one event — pass `name=` for per-layer
            # accounting
            m = int(np.prod(x.shape[:-1], dtype=np.int64))
            k, n = int(x.shape[-1]), int(w.shape[-1])
            self.ledger.record(name or f"unnamed_{m}x{k}x{n}",
                               m=m, k=k, n=n, cfg=cfg)
        if key is None:
            key = self.key_for(name, step)
        return rosa_matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                           cfg, key, self.variation_for(name),
                           self.gate_for(name), self.mapping_gate_for(name),
                           w_scale)

    def effective_weight(self, w: jax.Array, *, name: str = "",
                         step: int | jax.Array = 0,
                         key: jax.Array | None = None) -> jax.Array:
        """Noise-place a weight tensor for contractions the engine does not
        route itself (per-channel depthwise convs): same analog realization,
        variation pinning and gate blending as `matmul`'s WS side; identity
        for dense or fully ideal layers.
        """
        cfg = self.plan.resolve(name)
        if key is None:
            key = self.key_for(name, step)
        return condition_weight(w, cfg, key, self.variation_for(name),
                                self.gate_for(name))
