"""Jitted serving steps: continuous-batch decode + chunked prefill.

The decode state (slot cache + per-slot bookkeeping) lives on device and is
DONATED through every step — XLA updates the paged KV cache in place, so a
tick costs one token of compute, not one cache copy.  Admission (slot
eviction + refill) happens INSIDE the same jitted step: the admit payload
carries a prefilled batch-1 cache, and a traced `valid` flag turns the
whole write into an O(row) no-op, so the step never recompiles between
"plain decode" and "decode + refill" ticks.

Sampling is scheduling-invariant: the key for a request's i-th token folds
(request id, i) from the base key, so continuous batching, one-shot
batching and the per-request sequential oracle draw IDENTICAL samples —
which is what lets tests/test_serve.py assert exact (not just
distributional) equality under seeded sampling.

Prefill streams through `transformer.chunk_step` in `prefill_chunk`-token
chunks against a request-private cache; ssm/hybrid families (whose scan
state cannot be positionally chunked) fall back to whole-prompt prefill +
`pad_cache`.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant as Q
from repro.models import transformer as T
from repro.models.model import (ModelBundle, cache_axes, evict_slot,
                                pad_cache, write_slot)
from repro.obs import trace as obs
from repro.serve.config import ServeConfig


class DecodeState(NamedTuple):
    """Donated per-step serving state.  All vectors are (n_slots,)."""

    cache: Any              # model decode cache, batch = n_slots (pos inside)
    tok: jax.Array          # last sampled token per slot
    rid: jax.Array          # request id per slot (0 when never assigned)
    tidx: jax.Array         # tokens generated so far per slot
    budget: jax.Array       # generation budget per slot
    active: jax.Array       # bool: slot currently serving a request
    key: jax.Array          # base sampling key (constant across steps)


def init_state(cfg: T.ModelConfig, scfg: ServeConfig) -> DecodeState:
    s = scfg.n_slots
    return DecodeState(
        cache=T.init_cache(cfg, s, scfg.max_len),
        tok=jnp.zeros((s,), jnp.int32),
        rid=jnp.zeros((s,), jnp.int32),
        tidx=jnp.zeros((s,), jnp.int32),
        budget=jnp.zeros((s,), jnp.int32),
        active=jnp.zeros((s,), bool),
        key=jax.random.PRNGKey(scfg.seed))


def null_admit(cfg: T.ModelConfig, scfg: ServeConfig) -> dict:
    """An admission payload that admits nothing (valid=False)."""
    return {"valid": jnp.zeros((), bool),
            "slot": jnp.zeros((), jnp.int32),
            "cache": T.init_cache(cfg, 1, scfg.max_len),
            "token": jnp.zeros((1,), jnp.int32),
            "rid": jnp.zeros((1,), jnp.int32),
            "budget": jnp.zeros((1,), jnp.int32)}


def make_admit(req_cache, slot: int, rid: int, token, budget: int) -> dict:
    """Admission payload: request `rid` (first generated token `token`,
    prefilled `req_cache`) takes slot `slot` with `budget` tokens to go."""
    return {"valid": jnp.ones((), bool),
            "slot": jnp.asarray(slot, jnp.int32),
            "cache": req_cache,
            "token": jnp.reshape(jnp.asarray(token, jnp.int32), (1,)),
            "rid": jnp.full((1,), rid, jnp.int32),
            "budget": jnp.full((1,), budget, jnp.int32)}


# ---------------------------------------------------------------------------
# Step params: the served weights in the form the optical steps consume
# ---------------------------------------------------------------------------
@jax.jit
def _prepare_mlp(wi: jax.Array, wo: jax.Array):
    """Stacked (L, d, 2, f) gate|up and (L, f, d) down projections -> the
    (L, d, 2f) contraction layout of `wi` and each layer's full-scale of
    both, by `quant.absmax_scale` (the value a step would compute)."""
    scale = jax.vmap(lambda w: Q.absmax_scale(w.astype(jnp.float32)))
    return wi.reshape(*wi.shape[:2], -1), scale(wi), scale(wo)


def prepare_step_params(cfg: T.ModelConfig, params):
    """The step params: `params` with its routed MLP weights put once into
    the form the optical engine consumes in every step.

    With the optical MLP on (`cfg.rosa_mlp`, dense FFN), the stacked
    `layers/ffn/wi` becomes its (L, d, 2f) contraction layout, and
    `wi_scale`/`wo_scale` (L,) hold each layer's weight full-scale, so no
    step re-lays out gate|up or re-reduces a weight (`layers.mlp_apply`
    takes either form).  Values are moved or reduced exactly, so the steps
    compute the same numbers.  Every other leaf is the same array object;
    `params` itself is left as it is.  Otherwise `params` is returned."""
    ffn = params.get("layers", {}).get("ffn")
    if not (cfg.rosa_mlp and cfg.moe is None and ffn is not None):
        return params
    wi, wo = ffn["wi"], ffn["wo"]
    n_layers = wi.shape[0]
    with obs.span("serve.prepare_weights", "serve", n_weights=2 * n_layers,
                  relaid_bytes=int(np.prod(wi.shape)) * wi.dtype.itemsize):
        if isinstance(wi, jax.ShapeDtypeStruct):    # shapes only: lowering
            new = jax.eval_shape(_prepare_mlp, wi, wo)
        else:
            new = jax.block_until_ready(_prepare_mlp(wi, wo))
    wi2, wi_scale, wo_scale = new
    layers = dict(params["layers"],
                  ffn=dict(ffn, wi=wi2, wi_scale=wi_scale, wo_scale=wo_scale))
    return dict(params, layers=layers)


# ---------------------------------------------------------------------------
# Sampling (shared single-row path => bit-identical across schedulers)
# ---------------------------------------------------------------------------
def sample_token(base_key: jax.Array, rid, tidx, logits: jax.Array,
                 temperature) -> jax.Array:
    """Token for request `rid`'s `tidx`-th generation from logits (V,).

    temperature is a TRACED scalar: one compiled step serves greedy and
    sampled decoding alike (greedy = temperature 0, selected with a traced
    `where`, not a Python branch)."""
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)
    k = jax.random.fold_in(jax.random.fold_in(base_key, rid), tidx)
    t = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    sampled = jax.random.categorical(
        k, logits.astype(jnp.float32) / t, -1).astype(jnp.int32)
    return jnp.where(jnp.asarray(temperature, jnp.float32) > 0.0,
                     sampled, greedy)


_sample_rows = jax.vmap(sample_token, in_axes=(None, 0, 0, 0, None))


# ---------------------------------------------------------------------------
# The serving step
# ---------------------------------------------------------------------------
def _row_write(vec: jax.Array, new: jax.Array, slot, valid) -> jax.Array:
    cur = jax.lax.dynamic_index_in_dim(vec, slot, 0, keepdims=True)
    row = jnp.where(valid, new.astype(vec.dtype), cur)
    return jax.lax.dynamic_update_index_in_dim(vec, row, slot, axis=0)


def _apply_admission(cfg: T.ModelConfig, state: DecodeState, admit: dict,
                     slot_offset) -> DecodeState:
    """Evict + refill one slot, O(row), a no-op when `valid` is False or
    the slot lives on another shard (slot_offset localizes the index)."""
    slot = admit["slot"] - slot_offset
    n_local = state.tok.shape[0]
    valid = admit["valid"] & (slot >= 0) & (slot < n_local)
    slot = jnp.clip(slot, 0, n_local - 1)
    return DecodeState(
        cache=write_slot(cfg, state.cache, admit["cache"], slot, valid),
        tok=_row_write(state.tok, admit["token"], slot, valid),
        rid=_row_write(state.rid, admit["rid"], slot, valid),
        # the prefill already produced generation token #1 (admit["token"])
        tidx=_row_write(state.tidx, jnp.ones((1,), jnp.int32), slot, valid),
        budget=_row_write(state.budget, admit["budget"], slot, valid),
        active=_row_write(state.active, jnp.ones((1,), bool), slot, valid),
        key=state.key)


def _step_body(bundle: ModelBundle, scfg: ServeConfig, params,
               state: DecodeState, admit: dict, temperature,
               slot_offset) -> tuple[DecodeState, dict]:
    state = _apply_admission(bundle.cfg, state, admit, slot_offset)
    cache, tok, rid = state.cache, state.tok, state.rid
    tidx, budget, active = state.tidx, state.budget, state.active

    # -- one decode token for every slot (inactive rows compute masked
    #    garbage; their cache rows never influence active rows) ------------
    logits, cache = bundle.decode_step(
        params, {"token": tok, "pos": cache["pos"], "cache": cache})
    tok_next = _sample_rows(state.key, rid, tidx, logits, temperature)

    tidx_next = jnp.where(active, tidx + 1, tidx)
    done = active & (tidx_next >= budget)
    new_state = DecodeState(cache=cache, tok=tok_next, rid=rid,
                            tidx=tidx_next, budget=budget,
                            active=active & ~done, key=state.key)
    out = {"token": tok_next, "emitted": active, "done": done,
           "pos": cache["pos"]}
    if scfg.collect_logits:
        out["logits"] = logits
    return new_state, out


def _jitter(program):
    """The jit entry for the serving steps: `jax.jit` when no optical
    program is attached, else `program.bind` — which installs the
    program's frozen engine (tuned plan, pinned chip, ledger) as the
    ambient context while the step traces, so the scheduler builds every
    step from ONE `rosa.Program` instead of a global engine stack."""
    return jax.jit if program is None else program.bind


def make_admit_step(bundle: ModelBundle, scfg: ServeConfig, program=None):
    """-> admit(state, admit_payload) -> state (jitted, state donated).

    Admission WITHOUT a decode step — the static-batching baseline forms
    its batch with this, then decodes; the continuous policy never needs
    it (its admissions ride inside `make_serve_step`)."""

    def serve_admit(state: DecodeState, payload: dict) -> DecodeState:
        return _apply_admission(bundle.cfg, state, payload,
                                jnp.zeros((), jnp.int32))

    return _jitter(program)(serve_admit, donate_argnums=(0,))


def slot_state_specs(bundle: ModelBundle, scfg: ServeConfig,
                     mesh) -> DecodeState:
    """PartitionSpecs of a `DecodeState` whose slots are sharded over
    every axis of `mesh` (the sampling key is replicated)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import slot_dim_specs

    if scfg.n_slots % mesh.size:
        raise ValueError(f"n_slots={scfg.n_slots} not divisible by "
                         f"mesh size {mesh.size}")
    axes = tuple(mesh.shape)
    cache_specs = slot_dim_specs(
        cache_axes(bundle.cfg),
        jax.eval_shape(lambda: T.init_cache(bundle.cfg, scfg.n_slots,
                                            scfg.max_len)), axes)
    vec = P(axes if len(axes) > 1 else axes[0])
    return DecodeState(cache=cache_specs, tok=vec, rid=vec, tidx=vec,
                       budget=vec, active=vec, key=P())


def make_serve_step(bundle: ModelBundle, scfg: ServeConfig, mesh=None,
                    program=None):
    """-> step(params, state, admit, temperature) -> (state, out), jitted
    with the state donated.  With `mesh` (carrying a "data" axis that
    divides n_slots) the step runs under a slot-sharded shard_map: each
    device owns n_slots/d slots, params are replicated, and the admit
    payload is broadcast — every shard turns it into a local write (or a
    no-op if the slot lives elsewhere)."""
    if mesh is None:
        body = functools.partial(_step_body, bundle, scfg)

        def serve_decode_step(params, state, admit, temperature):
            return body(params, state, admit, temperature,
                        jnp.zeros((), jnp.int32))

        return _jitter(program)(serve_decode_step, donate_argnums=(1,))

    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.shape)             # shard slots over ALL mesh axes
    n_local = scfg.n_slots // mesh.size
    state_specs = slot_state_specs(bundle, scfg, mesh)
    vec = state_specs.tok
    admit_specs = jax.tree.map(lambda _: P(),
                               null_admit(bundle.cfg, scfg))
    out_specs = {"token": vec, "emitted": vec, "done": vec, "pos": vec}
    if scfg.collect_logits:
        out_specs["logits"] = vec

    def serve_decode_step(params, state, admit, temperature):
        idx = jnp.zeros((), jnp.int32)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return _step_body(bundle, scfg, params, state, admit, temperature,
                          idx * n_local)

    sharded = jax.shard_map(
        serve_decode_step, mesh=mesh,
        in_specs=(P(), state_specs, admit_specs, P()),
        out_specs=(state_specs, out_specs), check_vma=False)
    return _jitter(program)(sharded, donate_argnums=(1,))


def make_evict(bundle: ModelBundle, scfg: ServeConfig, program=None):
    """-> evict(state, slot) -> state with that slot's cache zeroed (jitted,
    donated).  Admission overwrites slots anyway; eviction guarantees a
    completed request's KV rows don't outlive it (scfg.evict_on_done)."""

    def serve_evict(state: DecodeState, slot):
        return state._replace(
            cache=evict_slot(bundle.cfg, state.cache, slot),
            active=_row_write(state.active, jnp.zeros((1,), bool), slot,
                              True))

    return _jitter(program)(serve_evict, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------
class PrefillTask:
    """One request's prefill, advanced one chunk per scheduler tick.

    Attention-cache families stream `prefill_chunk`-token chunks through
    `chunk_step` against a request-private max_len cache (so a long prompt
    never blocks the decode batch for more than one chunk).  ssm/hybrid
    prefill whole (one tick, compiled per prompt length).

    After `advance()` returns True: `.cache` is the admit-ready batch-1
    cache (pos = prompt length) and `.logits` the last-token logits (V,).
    """

    def __init__(self, bundle: ModelBundle, scfg: ServeConfig, prompt,
                 chunk_fn=None, whole_fn=None):
        self.bundle, self.scfg = bundle, scfg
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(self.prompt) == 0:
            raise ValueError("empty prompt")
        if len(self.prompt) >= scfg.max_len:
            raise ValueError(f"prompt length {len(self.prompt)} >= "
                             f"max_len {scfg.max_len}: no decode room")
        self.chunked = bundle.cfg.family not in ("ssm", "hybrid")
        self._chunk_fn = chunk_fn if chunk_fn is not None \
            else make_chunk_fn(bundle)
        self._whole_fn = whole_fn if whole_fn is not None \
            else make_prefill_fn(bundle)
        self._off = 0
        self.cache = (T.init_cache(bundle.cfg, 1, scfg.max_len)
                      if self.chunked else None)
        self.logits = None
        self.done = False

    @property
    def n_chunks(self) -> int:
        if not self.chunked:
            return 1
        c = self.scfg.prefill_chunk
        return -(-len(self.prompt) // c)

    def advance(self, params) -> bool:
        """Run one chunk (or the whole prompt for ssm/hybrid); True when
        the prefill is complete."""
        if self.done:
            return True
        if not self.chunked:
            logits, cache = self._whole_fn(
                params, {"tokens": jnp.asarray(self.prompt)[None]})
            self.cache = pad_cache(self.bundle.cfg, cache,
                                   self.scfg.max_len - len(self.prompt))
            self.logits = logits[0]
            self.done = True
            return True
        c = self.scfg.prefill_chunk
        lo = self._off
        chunk = self.prompt[lo:lo + c]
        n_valid = len(chunk)
        if n_valid < c:                       # pad the tail chunk
            chunk = np.pad(chunk, (0, c - n_valid))
        logits, self.cache = self._chunk_fn(
            params, jnp.asarray(chunk)[None],
            jnp.full((1,), n_valid, jnp.int32), self.cache)
        self._off += n_valid
        if self._off >= len(self.prompt):
            self.logits = logits[0]
            self.done = True
        return self.done


def _replicated(fn, mesh):
    """With a slot `mesh`, run `fn` whole on every device's replica of the
    weights: a request's prefill is batch 1 and cannot be split over
    slots, and a Pallas TPU kernel is never partitioned automatically."""
    if mesh is None:
        return fn
    from jax.sharding import PartitionSpec as P
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def make_chunk_fn(bundle: ModelBundle, program=None, mesh=None):
    """The shared jitted chunk step; ONLY the request cache is donated
    (tokens/n_valid are rebuilt per chunk and too small to matter)."""
    def serve_prefill_chunk(params, tokens, n_valid, cache):
        return bundle.chunk_step(
            params, {"tokens": tokens, "n_valid": n_valid, "cache": cache})

    return _jitter(program)(_replicated(serve_prefill_chunk, mesh),
                            donate_argnums=(3,))


def make_prefill_fn(bundle: ModelBundle, program=None, mesh=None):
    """The jitted whole-prompt prefill (ssm and hybrid families)."""
    def serve_prefill_whole(params, batch):
        return bundle.prefill(params, batch)

    return _jitter(program)(_replicated(serve_prefill_whole, mesh))
