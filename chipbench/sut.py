"""The system under test: `repro.serve.Scheduler` with the optical engine.

This is the one file that calls into the program.  It turns a
configuration file and a mix file into a served model, warms up the
cell's own shapes, and runs request slices through the normal entry
point, `Scheduler.run(policy="continuous")`, with `rosa=True`, backend
"auto" (which has to resolve to the fused kernel on the chip) and the
configuration's pinned chip.

A configuration file names its program config (`program_config`, an
entry of `repro.configs`) and states every top-level size that the
program reads for that entry's family; its `mla` and `moe` blocks are
applied field by field onto the entry's own sub-configs.  So a family the
serving path can chunk-prefill is served from its file alone.
"""

from __future__ import annotations

import dataclasses
from typing import NoReturn

import jax.numpy as jnp
import numpy as np

# sizes copied from a dense configuration file onto the program's config
SIZE_KEYS = ("n_layers", "d_model", "vocab", "n_heads", "n_kv_heads",
             "head_dim", "d_ff", "qk_norm", "rope_theta", "norm_eps",
             "tie_embeddings")
# the top-level sizes the program reads, by family; a file states these
# and no other size
FAMILY_SIZES = {
    "dense": SIZE_KEYS,
    "moe": tuple(k for k in SIZE_KEYS if k != "d_ff"),
    "mla_moe": ("n_layers", "d_model", "vocab", "n_heads", "rope_theta",
                "norm_eps", "tie_embeddings", "first_dense_ff"),
}
ALL_SIZES = frozenset(k for keys in FAMILY_SIZES.values() for k in keys)
# sub-configs a file gives as blocks of the same name
BLOCKS = ("mla", "moe")
# why the serving path cannot run the other families
_NO_CHUNKS = "the serving path has no chunked prefill for state-space layers"
UNSERVED = {"ssm": _NO_CHUNKS, "hybrid": _NO_CHUNKS,
            "encdec": "the serving path has no encoder pass"}


def _refuse(cfg: dict, why: str) -> NoReturn:
    raise ValueError(f"{cfg['name']}: {why}")


def check_reduced(cfg: dict) -> None:
    """Every key in `reduced` is one the file states, top-level or as
    `block.key`, and `published` gives its uncut value."""
    published = cfg.get("published", {})
    for key in cfg.get("reduced", {}):
        block, _, field = key.rpartition(".")
        where = cfg.get(block, {}) if block else cfg
        if not isinstance(where, dict) or field not in where:
            _refuse(cfg, f"reduced names {key!r}, which the file does not "
                    "state")
        if key not in published:
            _refuse(cfg, f"reduced names {key!r}, but published gives no "
                    "uncut value for it")
        if published[key] == where[field]:
            _refuse(cfg, f"reduced names {key!r}, but its value "
                    f"{where[field]!r} is the published one")


def model_config(cfg: dict):
    """The program's config for a configuration file, or ValueError
    naming why the file cannot be served."""
    from repro.configs import get_config
    base = get_config(cfg["program_config"])
    if base.family not in FAMILY_SIZES:
        _refuse(cfg, f"family {base.family!r} is not served: "
                f"{UNSERVED.get(base.family, 'no sizes are known for it')}")
    if base.frontend != "none":
        _refuse(cfg, f"the {base.frontend} frontend is not served: requests "
                "carry token ids only")
    sizes = FAMILY_SIZES[base.family]
    missing = [k for k in sizes if k not in cfg]
    if missing:
        _refuse(cfg, f"family {base.family!r} reads {missing}, which the "
                "file does not state")
    extra = sorted(ALL_SIZES.intersection(cfg).difference(sizes))
    if extra:
        _refuse(cfg, f"the file states {extra}, which family "
                f"{base.family!r} does not read")
    check_reduced(cfg)
    blocks = {}
    for b in BLOCKS:
        sub = getattr(base, b)
        if sub is None:
            if b in cfg:
                _refuse(cfg, f"block {b!r} on family {base.family!r}, which "
                        "has no such sub-config")
            continue
        if b not in cfg:
            _refuse(cfg, f"family {base.family!r} has a {b!r} sub-config, "
                    "which the file does not state")
        fields = {f.name for f in dataclasses.fields(sub)}
        unknown = sorted(set(cfg[b]) - fields)
        if unknown:
            _refuse(cfg, f"block {b!r} states {unknown}, which "
                    f"{type(sub).__name__} does not have")
        blocks[b] = dataclasses.replace(sub, **cfg[b])
    mc = dataclasses.replace(base, **{k: cfg[k] for k in sizes}, **blocks)
    agree = [(f"{b}.d_model", getattr(mc, b).d_model, "d_model", mc.d_model)
             for b in BLOCKS if getattr(mc, b) is not None]
    if mc.mla is not None:
        agree += [("mla.n_heads", mc.mla.n_heads, "n_heads", mc.n_heads),
                  ("mla.rope_theta", mc.mla.rope_theta, "rope_theta",
                   mc.rope_theta)]
    for key, v, top, want in agree:
        if v != want:
            _refuse(cfg, f"{key} {v!r} disagrees with {top} {want!r}")
    if jnp.dtype(mc.cache_dtype) != jnp.dtype(cfg["precision"]["kv_cache"]):
        _refuse(cfg, f"the program's KV cache is {jnp.dtype(mc.cache_dtype)}, "
                f"the configuration states {cfg['precision']['kv_cache']}")
    return mc


def abstract_params(cfg: dict):
    """Shapes of the weights the program serves, in its layout."""
    from repro.models.model import build_model
    from repro.serve import serving_model_config
    bundle = build_model(serving_model_config(model_config(cfg), rosa=True))
    return bundle.abstract(jnp.dtype(cfg["precision"]["params"]))


class Served:
    """One served model: the scheduler, its program and its plan.  A
    configuration whose program routes no GEMM through the optical engine
    is refused: its cell would time digital matmuls only."""

    def __init__(self, cfg: dict, mix: dict, params, backend: str = "auto"):
        from repro.serve import Scheduler, ServeConfig
        s = mix["serve"]
        self.cfg, self.mix = cfg, mix
        self.scfg = ServeConfig(
            n_slots=s["n_slots"], max_len=s["max_len"],
            prefill_chunk=s["prefill_chunk"], temperature=s["temperature"],
            rosa=True, rosa_backend=backend,
            variation_seed=cfg["optical"]["variation_seed"],
            collect_logits=False)
        self.sched = Scheduler(model_config(cfg), self.scfg, params=params)
        if not self.gemms():
            _refuse(cfg, "the program routes no GEMM through the optical "
                    "engine")

    def backend(self) -> str:
        from repro.rosa.backends import resolve_backend
        return resolve_backend(self.scfg.rosa_backend)[0]

    def plan(self) -> dict:
        return {k: v.name for k, v in
                self.sched.program.plan.mapping_plan().items()}

    def gemms(self) -> list[tuple[str, int, int]]:
        """(name, K, N) of every GEMM one layer routes through the engine."""
        return [(e.name, e.k, e.n) for e in self.sched.program.trace.entries]

    def warm(self) -> None:
        """Compile and run every program the window uses: a two-chunk
        prompt (prefill chunk, first-token sampling, admission) and one
        decode step."""
        c = self.scfg.prefill_chunk
        prompt = np.arange(c + 1, dtype=np.int32) % self.cfg["vocab"]
        self.run([{"rid": 2**30, "prompt": prompt, "max_new_tokens": 2,
                   "arrival": 0}])

    def run(self, reqs: list[dict]):
        from repro.serve import Request
        return self.sched.run([Request(**r) for r in reqs],
                              policy="continuous")
