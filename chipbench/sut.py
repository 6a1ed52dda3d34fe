"""The system under test: `repro.serve.Scheduler` with the optical engine.

This is the one file that calls into the program.  It turns a
configuration file and a mix file into a served model, warms up the
cell's own shapes, and runs request slices through the normal entry
point, `Scheduler.run(policy="continuous")`, with `rosa=True`, backend
"auto" (which has to resolve to the fused kernel on the chip) and the
configuration's pinned chip.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

# sizes copied from the configuration file onto the program's own config
SIZE_KEYS = ("n_layers", "d_model", "vocab", "n_heads", "n_kv_heads",
             "head_dim", "d_ff", "qk_norm", "rope_theta", "norm_eps",
             "tie_embeddings")


def model_config(cfg: dict):
    from repro.configs import get_config
    mc = dataclasses.replace(get_config(cfg["program_config"]),
                             **{k: cfg[k] for k in SIZE_KEYS})
    if mc.family != "dense" or mc.moe is not None or mc.mla is not None:
        raise ValueError(f"{cfg['name']}: not a dense GQA decoder")
    if jnp.dtype(mc.cache_dtype) != jnp.dtype(cfg["precision"]["kv_cache"]):
        raise ValueError(f"{cfg['name']}: the program's KV cache is "
                         f"{jnp.dtype(mc.cache_dtype)}, the configuration "
                         f"states {cfg['precision']['kv_cache']}")
    return mc


def abstract_params(cfg: dict):
    """Shapes of the weights the program serves, in its layout."""
    from repro.models.model import build_model
    from repro.serve import serving_model_config
    bundle = build_model(serving_model_config(model_config(cfg), rosa=True))
    return bundle.abstract(jnp.dtype(cfg["precision"]["params"]))


class Served:
    """One served model: the scheduler, its program and its plan."""

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
