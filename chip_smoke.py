#!/usr/bin/env python3
"""Chip smoke run: qwen3-32b widths served through the fused optical kernel.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # slot-sharded serving on four chips

The model is qwen3-32b at its published widths (d_model 5120, 64 heads,
8 KV heads of head_dim 128, d_ff 25600, vocab 151936, qk_norm) with only
the layer count cut, to 2, so that its float32 weights fit one v5e chip.
Weights are random, drawn from a fixed seed.  Requests go through the
normal entry point, `repro.serve.Scheduler`, with the optical engine on
(`rosa=True`, backend "auto") and one fabricated chip pinned
(`variation_seed`): with an ideal device the engine takes its exact
digital shortcut and launches no kernel, while a pinned chip realizes
every MLP GEMM's analog operand and contracts it in the `rosa_fused`
Pallas kernel.

One chip: serve a seeded Poisson stream of 16 requests (prompts of 64-256
tokens, 16-64 greedy tokens each) over 8 slots and check that every request
completes with finite logits.  Then compare with the composed jnp chain
(backend "ref"), the oracle.  Each routed MLP GEMM is run through both at
the served widths and must pass the kernel's parity contract
(tests/test_kernels.py::assert_quantized_parity): no output beyond one
requantization LSB (2/127 of full scale), and at most a quarter of the
rows beyond float tightness.  The first requests are decoded again through
both backends; their logits must agree within that same one-LSB bound,
and their greedy tokens wherever the top-2 gap exceeds it.

The oracle comparisons run with float32 matmuls on both sides
(`jax.default_matmul_precision("highest")`).  At the TPU's default
precision each MXU pass rounds its operands to bfloat16, which moves
either backend's GEMM far more than the kernel's own deviation from the
chain; the per-GEMM oracle prints how far each backend at default
precision sits from the float32 `ref` result, and the served stream's
deviation from `ref` at default precision is printed as well.

Four chips: serve the same stream with the slots sharded over a 4-device
("data",) mesh and check that the slots really sit on four devices.  Then
serve it again, sharded and on one device in the same process, with
float32 matmuls: tokens and logits must agree as above.

The script needs a TPU: without one it exits non-zero and prints no result.
Set-up, compile and serve wall times and peak device memory are printed on
earlier lines, each labelled with the device.  They are single runs, not
benchmark numbers.  The last line of standard output is one JSON object,
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Everything runs in this one process, which holds the chips.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

SEED = 0
N_LAYERS = 2
N_SLOTS = 8
MAX_LEN = 512
PREFILL_CHUNK = 256
N_REQUESTS = 16
ARRIVAL_RATE = 0.5          # Poisson arrivals per scheduler tick
PROMPT_LEN = (64, 256)
GEN_LEN = (16, 64)
N_ORACLE = 3                # requests decoded again through the ref chain
# The fused kernel's contract against the composed chain
# (tests/test_kernels.py::assert_quantized_parity): a requantization code
# flip moves an output row by at most one LSB of its full scale; outside
# flipped rows the two agree at float tightness, and flips stay rare.
LSB_BOUND = 2.0 / 127
TIGHT = 2e-4


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def tpu_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); this check runs on the chip only")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: {chips} chips asked for, JAX found "
                 f"{len(devs)}")
    return devs


def model_config():
    from repro.configs import get_config
    return dataclasses.replace(get_config("qwen3-32b"), n_layers=N_LAYERS)


def serve_config(backend: str = "auto"):
    from repro.serve import ServeConfig
    return ServeConfig(n_slots=N_SLOTS, max_len=MAX_LEN,
                       prefill_chunk=PREFILL_CHUNK, rosa=True,
                       rosa_backend=backend, variation_seed=SEED,
                       collect_logits=True)


def request_stream(vocab: int):
    from repro.serve import poisson_requests
    return poisson_requests(N_REQUESTS, ARRIVAL_RATE, vocab=vocab,
                            prompt_len=PROMPT_LEN, gen_len=GEN_LEN,
                            seed=SEED)


def check_fused(sched) -> None:
    """"auto" resolved to the fused kernel, and the decode step launches
    it as a compiled TPU kernel (interpret mode lowers to plain HLO)."""
    import jax.numpy as jnp

    from repro.rosa.backends import resolve_backend
    from repro.serve import init_state

    name, _ = resolve_backend(sched.scfg.rosa_backend)
    check(name == "fused", f"backend {sched.scfg.rosa_backend!r} resolved "
                           f"to {name!r}, not 'fused'")
    state = init_state(sched.cfg, sched.scfg)
    if sched.state_sharding is not None:
        import jax
        state = jax.device_put(state, sched.state_sharding)
    text = sched.step.lower(sched.step_params, state, sched.null,
                            jnp.float32(0.0)).as_text()
    check("tpu_custom_call" in text,
          "the decode step holds no compiled Pallas kernel")


def serve(sched, reqs, label: str, what: str) -> dict:
    """Warm up (compiles every step), then serve `reqs`; returns
    {rid: (tokens, logits)} after checking every request completed."""
    import numpy as np

    from repro.serve import Request

    warm = Request(max(r.rid for r in reqs) + 1, reqs[0].prompt, 2)
    t = time.perf_counter()
    sched.run([warm])
    compile_s = time.perf_counter() - t
    rep = sched.run(reqs)
    print(f"{label} {what}: compile+warmup_s={compile_s:.3f} "
          f"serve_s={rep.wall_s:.3f} tokens={rep.total_tokens} "
          f"tokens_per_s={rep.tokens_per_s:.2f} "
          f"decode_steps={rep.decode_steps} "
          f"prefill_chunks={rep.prefill_chunks} (single run)")
    out = {}
    for r in reqs:
        c = rep.completions[r.rid]
        check(len(c.tokens) == r.max_new_tokens,
              f"{what}: request {r.rid} produced {len(c.tokens)} tokens, "
              f"budget {r.max_new_tokens}")
        check(len(c.logits) == len(c.tokens),
              f"{what}: request {r.rid} logged {len(c.logits)} logits")
        logits = [np.asarray(lg) for lg in c.logits]
        for i, lg in enumerate(logits):
            check(lg.shape == (sched.cfg.vocab,) and np.isfinite(lg).all(),
                  f"{what}: request {r.rid} token {i}: logits not finite "
                  f"or of shape {lg.shape}")
            check(int(np.argmax(lg)) == c.tokens[i],
                  f"{what}: request {r.rid} token {i} is not the argmax "
                  "of its logits under greedy decoding")
        out[r.rid] = (list(c.tokens), logits)
    return out


def float32_matmuls():
    import jax
    return jax.default_matmul_precision("highest")


def compare(got: dict, want: dict, what: str, enforce: bool = True) -> None:
    """Logits within LSB_BOUND of `want`'s largest magnitude; greedy
    tokens equal wherever `want`'s top-2 gap exceeds that bound.  After a
    token divergence the streams condition on different inputs, so that
    request is compared no further.  The whole comparison is summarized
    before any failure is raised; with `enforce` False it is only
    reported."""
    import numpy as np

    worst, n_cmp, n_tok, beyond, divs = 0.0, 0, 0, [], []
    for rid, (w_tok, w_log) in want.items():
        g_tok, g_log = got[rid]
        n_tok += len(w_tok)
        for i, (a, b) in enumerate(zip(g_log, w_log)):
            a = a.astype(np.float64)
            b = b.astype(np.float64)
            scale = max(float(np.max(np.abs(b))), 1.0)
            dev = float(np.max(np.abs(a - b))) / scale
            worst = max(worst, dev)
            n_cmp += 1
            if dev > LSB_BOUND:
                beyond.append(f"request {rid} token {i}: {dev:.3e}")
            if g_tok[i] != w_tok[i]:
                top2 = np.sort(b)[-2:]
                gap = float(top2[1] - top2[0]) / scale
                divs.append((f"request {rid} token {i}: {g_tok[i]} != "
                             f"{w_tok[i]}, top-2 gap {gap:.3e}", gap))
                break
    wide = [d for d, gap in divs if gap > LSB_BOUND]
    verdict = (f"bound {LSB_BOUND:.6e}" if enforce
               else "reported, not bounded")
    print(f"  {what}: {n_cmp} of {n_tok} logit vectors compared, largest "
          f"deviation {worst:.6e} of full scale ({verdict}), "
          f"{len(beyond)} beyond {LSB_BOUND:.3e}; {len(divs)} token "
          f"divergences, {len(wide)} with a top-2 gap above it")
    for line in beyond[:8] + [d for d, _ in divs]:
        print(f"    {line}")
    if enforce:
        check(not beyond and not wide,
              f"{what}: {len(beyond)} logit vectors beyond the one-LSB "
              f"bound {LSB_BOUND:.3e} (largest {worst:.3e}), {len(wide)} "
              f"token divergences with a top-2 gap above it")


def kernel_oracle(sched, label: str) -> None:
    """The kernel's own contract at the served widths: each routed MLP
    GEMM, under the plan's mapping and the pinned chip's variation, at the
    decode M and the prefill-chunk M, against the composed chain, both
    with float32 matmuls.  A single requantization code flip moves its
    row by several TIGHT, so rows beyond TIGHT count the flipped rows.
    Also printed: how far each backend at the default matmul precision
    sits from the float32 `ref` result."""
    import contextlib

    import jax
    import numpy as np

    from repro.rosa.backends import rosa_matmul

    for i, e in enumerate(sched.program.trace.entries):
        cfg = sched.engine.config(e.name)
        var = sched.engine.variation_for(e.name)
        key = sched.engine.key_for(e.name)
        kx, kw = jax.random.split(jax.random.fold_in(
            jax.random.PRNGKey(SEED), i))
        w = jax.random.normal(kw, (e.k, e.n))
        for m in (N_SLOTS, PREFILL_CHUNK):
            x = jax.random.normal(kx, (m, e.k))
            y = {}
            for prec, ctx in (("f32", float32_matmuls),
                              ("default", contextlib.nullcontext)):
                with ctx():
                    for backend in ("fused", "ref"):
                        c = dataclasses.replace(cfg, backend=backend)
                        y[backend, prec] = np.asarray(jax.jit(
                            lambda x, w, c=c: rosa_matmul(x, w, c, key,
                                                          var))(x, w),
                            np.float64)
            exact = y["ref", "f32"]
            scale = max(float(np.max(np.abs(exact))), 1.0)
            d = np.abs(y["fused", "f32"] - exact) / scale
            rows = int((d.max(-1) > TIGHT).sum())
            allowed = max(2, -(-m // 4))
            at_default = {b: float(np.max(np.abs(y[b, "default"] - exact)))
                          / scale for b in ("fused", "ref")}
            print(f"{label} kernel oracle {e.name} {cfg.mapping.name} "
                  f"{m}x{e.k}x{e.n}: fused vs ref at float32, largest "
                  f"deviation {d.max():.6e} of full scale (bound "
                  f"{LSB_BOUND:.6e}), rows beyond {TIGHT:.0e}: {rows}/{m} "
                  f"(allowed {allowed}); at default precision, largest "
                  f"deviation from float32 ref: fused "
                  f"{at_default['fused']:.6e}, ref {at_default['ref']:.6e}")
            check(bool(np.isfinite(y["fused", "f32"]).all()),
                  f"kernel oracle {e.name} at M={m}: non-finite output")
            check(d.max() <= LSB_BOUND,
                  f"kernel oracle {e.name} at M={m}: deviation "
                  f"{d.max():.3e} of full scale exceeds the one-LSB bound "
                  f"{LSB_BOUND:.3e}")
            check(rows <= allowed,
                  f"kernel oracle {e.name} at M={m}: {rows} of {m} rows "
                  f"beyond {TIGHT:.0e}, more than requantization boundary "
                  f"flips can explain (allowed {allowed})")


def peak_memory(devs, label: str) -> None:
    for d in devs:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        if peak is not None:
            print(f"{label} device {d.id}: peak_bytes_in_use={peak} "
                  f"({peak / 2**30:.3f} GiB)")


def one_chip(devs, label: str) -> None:
    import jax

    from repro.serve import Scheduler

    cfg = model_config()
    reqs = request_stream(cfg.vocab)
    t = time.perf_counter()
    sched = Scheduler(cfg, serve_config(), init_seed=SEED, plan_cache=False)
    jax.block_until_ready(sched.params)
    print(f"{label} setup_s={time.perf_counter() - t:.3f} "
          f"params={sched.bundle.n_params} plan="
          f"{ {k: v.name for k, v in sched.program.plan.mapping_plan().items()} }")
    check_fused(sched)
    served = serve(sched, reqs, label, "fused")

    oracle = Scheduler(cfg, serve_config("ref"), params=sched.params,
                       plan_cache=False)
    # the same weights, prepared once: a second prepared copy of the MLP
    # would crowd the chip's memory
    oracle.step_params = sched.step_params
    sub = [dataclasses.replace(r, arrival=0) for r in reqs[:N_ORACLE]]
    ref = serve(oracle, sub, label, "ref oracle")
    compare(served, ref, "fused vs ref, default precision", enforce=False)
    with float32_matmuls():
        fused32 = serve(sched, sub, label, "fused, float32 matmuls")
        ref32 = serve(oracle, sub, label, "ref oracle, float32 matmuls")
    compare(fused32, ref32, "fused vs ref, float32 matmuls")
    kernel_oracle(sched, label)
    peak_memory(devs[:1], label)


def step_diff(sched, single, prompt, label: str) -> None:
    """Where the sharded and one-device programs part: the logits of one
    prefill chunk and of one decode step on identical inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T
    from repro.serve import init_state

    def diff(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.max(np.abs(a - b))) / max(float(np.max(np.abs(b))),
                                                  1.0)

    n = min(len(prompt), PREFILL_CHUNK)
    tokens = jnp.asarray(np.pad(prompt[:n], (0, PREFILL_CHUNK - n)))[None]
    n_valid = jnp.full((1,), n, jnp.int32)
    chunk = [s.chunk_fn(s.step_params, tokens, n_valid, T.init_cache(
        s.cfg, 1, s.scfg.max_len))[0] for s in (sched, single)]
    state = jax.device_put(init_state(sched.cfg, sched.scfg),
                           sched.state_sharding)
    step = [s.step(s.step_params, st, s.null, jnp.float32(0.0))[1]["logits"]
            for s, st in ((sched, state),
                          (single, init_state(single.cfg, single.scfg)))]
    print(f"{label} sharded vs one device, float32 matmuls, identical "
          f"inputs: prefill-chunk logits differ by {diff(*chunk):.6e} of "
          f"full scale, decode-step logits by {diff(*step):.6e}")


def four_chips(devs, label: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh
    from repro.serve import Scheduler, init_state

    cfg = model_config()
    reqs = request_stream(cfg.vocab)
    scfg = serve_config()
    mesh = make_mesh((4,), ("data",))
    t = time.perf_counter()
    sched = Scheduler(cfg, scfg, init_seed=SEED, mesh=mesh,
                      plan_cache=False)
    jax.block_until_ready(sched.params)
    print(f"{label} setup_s={time.perf_counter() - t:.3f} mesh=4x data")
    check_fused(sched)

    # the slots, their caches and the step's outputs sit on four devices
    state = jax.device_put(init_state(sched.cfg, scfg), sched.state_sharding)
    state, out = sched.step(sched.step_params, state, sched.null,
                            jnp.float32(0.0))
    for leaf in [out["token"], *jax.tree.leaves(state)]:
        if leaf.ndim == 0 or leaf is state.key:
            continue
        shards = leaf.addressable_shards
        check(len({s.device.id for s in shards}) == 4
              and all(s.data.size * 4 == leaf.size for s in shards),
              f"a slot leaf of shape {leaf.shape} is not split over four "
              f"devices: {[(s.device.id, s.data.shape) for s in shards]}")
    del state, out
    serve(sched, reqs, label, "sharded x4")

    # the same stream on one device, with the same weights: device 0's
    # replica of the parameters, so no second copy is made there
    params0 = jax.tree.map(
        lambda a: next(s.data for s in a.addressable_shards
                       if s.device == devs[0]), sched.params)
    single = Scheduler(cfg, scfg, params=params0, plan_cache=False)
    with float32_matmuls():
        step_diff(sched, single, reqs[0].prompt, label)
        sharded = serve(sched, reqs, label, "sharded x4, float32 matmuls")
        ref = serve(single, reqs, label, "one device, float32 matmuls")
    compare(sharded, ref, "sharded x4 vs one device, float32 matmuls")
    peak_memory(devs[:4], label)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the slot-sharded path on four chips, and only "
                         "that path")
    args = ap.parse_args()
    devs = tpu_devices(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    d0 = devs[0]
    label = f"[{d0.platform} {d0.device_kind} x{len(devs)}]"
    print(f"{label} compile cache: {cache}")
    if args.chips == 4:
        four_chips(devs, label)
    else:
        one_chip(devs, label)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
