"""Serving CLI — a thin driver over the `repro.serve` subsystem.

Three policies:

  continuous  (default) slot-based continuous batching: Poisson request
              stream, chunked prefill interleaved with decode, in-step
              slot eviction/refill on a donated paged KV cache
  oneshot     static batching baseline (the old one-shot script semantics:
              form a full batch, decode until its last request finishes)
  batch       the minimal fixed-batch demo loop (one prompt shape, one
              batch, N tokens) through `steps.make_sampling_decode_step` —
              a single jitted step with traced temperature + carried key

Examples:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --smoke \
      --requests 24 --rate 1.0 --n-slots 4 --temperature 0.7
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-12b --smoke \
      --policy batch --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --smoke \
      --rosa --variation-seed 7 --devices 2
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke


def _run_batch(args) -> None:
    """Fixed-batch demo path (the historic serve.py, minus its bugs)."""
    from repro.launch.steps import make_sampling_decode_step
    from repro.models.model import build_model, pad_cache

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    bundle = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = bundle.init(key)
    print(f"arch={cfg.name} params={bundle.n_params:,}")

    b, s = args.batch, args.prompt_len
    prompt = jax.random.randint(key, (b, s), 0, cfg.vocab, dtype=jnp.int32)
    batch = {"tokens": prompt}
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jnp.zeros((b, 16, cfg.d_model), jnp.bfloat16)
    if cfg.frontend == "audio":
        batch["src_embeds"] = jax.random.normal(
            key, (b, s, cfg.d_model), jnp.float32).astype(jnp.bfloat16)

    t0 = time.time()
    logits, cache = jax.jit(bundle.prefill)(params, batch)
    cache = pad_cache(cfg, cache, args.gen + 1)
    print(f"prefill {b}x{s}: {time.time() - t0:.2f}s")

    step = make_sampling_decode_step(bundle)
    tok = jnp.argmax(logits, -1)
    out = [tok]
    t0 = time.time()
    for _ in range(args.gen - 1):
        tok, cache, key = step(params, tok, cache, args.temperature, key)
        out.append(tok)
    dt = time.time() - t0
    toks = jnp.stack(out, 1)
    print(f"decoded {args.gen} tokens x {b} seqs in {dt:.2f}s "
          f"({b * args.gen / max(dt, 1e-9):.1f} tok/s)")
    print("sample token ids:", toks[0, :12].tolist())


def _run_stream(args) -> None:
    """Continuous-batching / one-shot serving over a synthetic stream."""
    import contextlib

    from repro.launch.mesh import make_mesh
    from repro.serve import (Scheduler, ServeConfig, poisson_requests,
                             report_metrics)

    tracer = None
    ctx = contextlib.nullcontext()
    if args.trace:
        from repro import obs
        obs.install_jax_hooks()
        tracer = obs.Tracer()
        # installed around construction too, so compile/autotune/plan-cache
        # spans land in the same trace as the serving ticks
        ctx = obs.tracing(tracer)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    scfg = ServeConfig(n_slots=args.n_slots, max_len=args.max_len,
                       prefill_chunk=args.prefill_chunk,
                       temperature=args.temperature, seed=args.seed,
                       rosa=args.rosa, variation_seed=args.variation_seed)
    mesh = None
    if args.devices > 1:
        mesh = make_mesh((args.devices,), ("data",))
    with ctx:
        sched = Scheduler(cfg, scfg, init_seed=args.seed, mesh=mesh)
        print(f"arch={cfg.name} params={sched.bundle.n_params:,} "
              f"slots={scfg.n_slots} max_len={scfg.max_len} "
              f"chunk={scfg.prefill_chunk} policy={args.policy}"
              + (f" mesh={args.devices}x data" if mesh else "")
              + (" rosa" if args.rosa else ""))

        reqs = poisson_requests(
            args.requests, args.rate, vocab=cfg.vocab,
            prompt_len=tuple(args.prompt_range),
            gen_len=tuple(args.gen_range), seed=args.seed)
        rep = sched.run(reqs, policy=args.policy)

    if tracer is not None:
        tracer.save(args.trace)
        print(f"trace: {len(tracer)} events -> {args.trace} "
              f"(load in https://ui.perfetto.dev, or summarize with "
              f"`python -m repro.obs summarize {args.trace}`)")

    for m in report_metrics(rep):
        v = f"{m.value:.4g}" if isinstance(m.value, float) else m.value
        print(f"  {m.name:24s} {v} {m.unit}")
    if args.rosa and sched.engine is not None \
            and sched.engine.ledger is not None:
        from repro.core.constants import ROSA_OPTIMAL
        e = sched.engine.ledger.per_token(ROSA_OPTIMAL, batch=scfg.n_slots)
        print(f"  {'energy_per_token':24s} {e:.4g} J (traced ledger)")
    done = sorted(rep.completions.values(), key=lambda c: c.rid)[:3]
    for c in done:
        print(f"  rid={c.rid} prompt={c.prompt_len} "
              f"tokens={c.tokens[:8]}{'...' if len(c.tokens) > 8 else ''}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="gemma3-12b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--policy", default="continuous",
                    choices=["continuous", "oneshot", "batch"])
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    # stream policies
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=1.0,
                    help="Poisson arrivals per tick (<=0: all at tick 0)")
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=56)
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--prompt-range", type=int, nargs=2, default=(4, 8))
    ap.add_argument("--gen-range", type=int, nargs=2, default=(2, 40))
    ap.add_argument("--devices", type=int, default=1,
                    help="shard slots over this many devices (shard_map)")
    ap.add_argument("--rosa", action="store_true",
                    help="serve through the optical engine (hybrid plan "
                         "searched on the decode trace + energy ledger)")
    ap.add_argument("--variation-seed", type=int, default=None,
                    help="pin one sampled fabricated chip (repro.robust)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Perfetto-loadable Chrome trace of the "
                         "run (compile + scheduler + request lifecycle + "
                         "energy counters) to PATH")
    # batch policy
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.policy == "batch":
        _run_batch(args)
    else:
        _run_stream(args)


if __name__ == "__main__":
    main()
