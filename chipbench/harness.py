"""One run of one cell: set-up, the measured window, the check, the result.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in `BENCHMARK.json`; its configuration file, its
mix file (`mixes/<traffic>.json`), its limits (`limits/<cell>.json`), the
configuration's plain reference (`references/<reference>.py`) and every
per-layer metric (`metrics/<name>.py`) are found by name too, so a new cell,
mix, configuration or metric is new files and new entries, never an edit.

The window is tick-paced: whole `Scheduler.run` calls over consecutive
slices of the seeded request stream.  A slice that would end past
`--seconds`, judged by the longest slice so far, is not started, so the
window ends within `--seconds` unless its first slice alone is longer.
Every end-to-end metric covers every request and all the time of every
slice.  With `--trace 1` the same window runs under the JAX profiler and
the line carries the per-layer metrics instead of the end-to-end ones.
Readers get the trace, the window's `ServeReport` counts and the program's
own counters over the window (`ctx.program_counters`), so a counter the
program adds reaches its reader with no edit here.

Standard output ends with one JSON line; the numbers compared for
`correct`, each beside its limit, are the last lines of standard error and
the last key of that line.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import time
import types
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
STATE = ROOT / ".chipbench"


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_cell(name: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    applies = (lambda m: "workloads" not in m or name in m["workloads"])
    return {
        "cell": cell,
        "cfg": json.loads((ROOT / entry["file"]).read_text()),
        "mix": json.loads((BENCH / "mixes" / f"{cell['traffic']}.json")
                          .read_text()),
        "limits": json.loads((BENCH / "limits" / f"{name}.json")
                             .read_text())["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def prepare_env() -> None:
    """Caches at fixed paths inside the checkout, set before JAX loads."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["ROSA_PLAN_CACHE"] = str(STATE / "plans")
    for p in (ROOT, ROOT / "src"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def set_matmul_precision(cfg: dict) -> None:
    """Matmuls at the precision the configuration states.  At "default"
    nothing is set: the program runs as it does by itself."""
    matmul = cfg["precision"]["matmul"]
    if matmul != "default":
        import jax
        jax.config.update("jax_default_matmul_precision", matmul)


def find_chip(chips: int):
    """The chips this cell asks for, and one chip's peaks; exits non-zero
    with no result where JAX finds no TPU, too few, or an unknown kind."""
    import jax

    from chipbench import work
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); the benchmark runs on the "
                         "chip only")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: {chips} chips asked for, JAX found "
                         f"{len(devs)}")
    try:
        return devs[:chips], work.peaks(devs[0].device_kind)
    except KeyError as e:
        raise SystemExit(f"chipbench: {e}") from None


class CompileCount:
    """Programs compiled, or loaded from the persistent cache, since start:
    a window that needs either is not warm."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **kw) -> None:
        if event in self.EVENTS:
            self.n += 1


def percentile(vals, q: float) -> float:
    return float(np.percentile(np.asarray(vals, np.float64), q))


class Window:
    """The measured window: slices until the time is up."""

    def __init__(self, served, mix: dict, seed: int, vocab: int):
        self.served, self.mix, self.seed, self.vocab = served, mix, seed, vocab
        self.reqs: list[dict] = []
        self.reports: list = []
        self.seconds = 0.0

    def run(self, seconds: float) -> None:
        from chipbench import traffic
        t0 = time.perf_counter()
        longest = 0.0
        while True:
            reqs = traffic.make_slice(self.mix, self.seed, len(self.reports),
                                      self.vocab)
            t = time.perf_counter()
            self.reports.append(self.served.run(reqs))
            self.reqs += reqs
            longest = max(longest, time.perf_counter() - t)
            if time.perf_counter() - t0 + longest > seconds:
                break
        self.seconds = time.perf_counter() - t0

    def drain_share(self) -> float:
        """Share of the window after each slice's last arrival, when the
        slice only drains."""
        tail = 0.0
        for rep in self.reports:
            comps = rep.completions.values()
            tail += (max(c.done_wall for c in comps)
                     - max(c.enqueue_wall for c in comps))
        return tail / self.seconds

    def done(self) -> list[dict]:
        comps = {}
        for rep in self.reports:
            comps.update(rep.completions)
        return [{"prompt": r["prompt"], "max_new_tokens": r["max_new_tokens"],
                 "tokens": list(comps[r["rid"]].tokens),
                 "comp": comps[r["rid"]]} for r in self.reqs]

    def counts(self) -> dict:
        reps = self.reports
        tokens = sum(r.total_tokens for r in reps)
        firsts = sum(1 for r in reps for c in r.completions.values()
                     if c.tokens)
        return {"decode_steps": sum(r.decode_steps for r in reps),
                "prefill_chunks": sum(r.prefill_chunks for r in reps),
                "ticks": sum(r.ticks for r in reps),
                "tokens": tokens, "decoded_tokens": tokens - firsts,
                "slices": len(reps), "requests": len(self.reqs)}


def read_counters(reg) -> dict:
    """The additive readings of a `repro.obs` metrics registry: each
    Counter's value, and each Histogram's count and sum as `<name>.count`
    and `<name>.sum`.  Gauges are not additive and are left out."""
    from repro.obs.metrics import Counter, Histogram
    out = {}
    for name, item in reg.items().items():
        if isinstance(item, Counter):
            out[name] = item.value
        elif isinstance(item, Histogram):
            snap = item.snapshot()
            out[f"{name}.count"] = snap["count"]
            out[f"{name}.sum"] = snap["sum"]
    return out


def counters_over(before: dict, after: dict) -> dict:
    """What was counted between two `read_counters` readings; a counter
    first created in between counts from 0."""
    return {k: v - before.get(k, 0) for k, v in after.items()}


def run_window(win: Window, seconds: float, logdir: Path | None) -> dict:
    """Runs the window, under the JAX profiler where `logdir` is given, and
    returns the program's counters over it.  The process-global registry
    is read outside the profiler session and outside the window's clock,
    so the warm-up and anything run earlier in the process fall outside
    the difference, and neither the window nor its trace sees the reading."""
    import jax

    from chipbench import trace
    from repro.obs.metrics import registry
    before = read_counters(registry())
    if logdir is not None:
        shutil.rmtree(logdir, ignore_errors=True)
        # host TraceMe events (dispatches, transfers) label the idle gaps;
        # the Python function tracer would slow the host loop it measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(logdir), profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            win.run(seconds)
        jax.profiler.stop_trace()
    else:
        win.run(seconds)
    return counters_over(before, read_counters(registry()))


def end_to_end(win: Window, setup_s: float) -> dict:
    done = win.done()
    c = win.counts()
    ttft = [d["comp"].ttft_s for d in done]
    tpot = [(d["comp"].done_wall - d["comp"].first_token_wall)
            / (len(d["tokens"]) - 1) for d in done if len(d["tokens"]) >= 2]
    return {"tokens_per_s": c["tokens"] / win.seconds,
            "ttft_p90_s": percentile(ttft, 90),
            "tpot_p90_s": percentile(tpot, 90),
            "setup_s": setup_s}


def per_layer(metrics: list, ctx) -> dict:
    out = {}
    for m in metrics:
        mod = importlib.import_module(f"chipbench.metrics.{m['name']}")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def check(served_params, ref, win: Window, c: dict,
          plan: dict) -> tuple[bool, dict]:
    """The comparison that decides `correct` (see compare.py)."""
    from chipbench import compare
    cfg, mix = c["cfg"], c["mix"]
    done = win.done()
    short = sum(1 for d in done if len(d["tokens"]) != d["max_new_tokens"])
    picked = [d for d in done if d["tokens"]]
    t = time.perf_counter()
    chip = ref.sample_chip(cfg["optical"], ref.gemm_lanes(cfg))
    g = compare.reference_gaps(
        ref, served_params, chip, picked, cfg, mix,
        {"program": compare.served_tokens(picked, mix["output"]["max"])}
    )["program"]
    nums = compare.numbers(g, short)
    nums["plan_departs"] = float(plan != cfg["optical"]["mapping"])
    ok, checks = compare.verdict(nums, {"plan_departs": 0.0, **c["limits"]})
    log(f"reference: {len(picked)} requests, {g.size} served tokens "
        f"compared in {time.perf_counter() - t:.3f} s; numbers {nums}")
    return ok, checks


def setup(c: dict, seed: int, t0: float):
    """Weights from the seed, the served model and its warm-up: the
    seconds from `t0` to a window ready to start, and what they built."""
    import jax

    from chipbench import sut, weights
    from repro.launch.compile_cache import enable_compile_cache
    cfg = c["cfg"]
    enable_compile_cache()
    stamps = {"start": time.perf_counter() - t0}
    params = jax.block_until_ready(weights.make(
        sut.abstract_params(cfg), ref_module(cfg).leaf_init, seed,
        jax.numpy.dtype(cfg["precision"]["params"])))
    stamps["weights"] = time.perf_counter() - t0
    served = sut.Served(cfg, c["mix"], params)
    plan = served.plan()
    stamps["program"] = time.perf_counter() - t0
    served.warm()
    setup_s = time.perf_counter() - t0
    log(f"setup_s={setup_s:.3f} (seconds from start: {stamps}) plan={plan} "
        f"gemms={served.gemms()}")
    return setup_s, served, plan


def ref_module(cfg: dict):
    return importlib.import_module(f"chipbench.references.{cfg['reference']}")


def finish(c: dict, win: Window, served, plan: dict, metrics: dict,
           device: dict, extra: dict) -> dict:
    """Frees the program, runs the check and prints the result line."""
    served_params = served.sched.params
    served.sched = None
    gc.collect()
    ok, checks = check(served_params, ref_module(c["cfg"]), win, c, plan)
    for k, v in checks.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    done = win.done()
    out = {"correct": bool(ok), "attempted": len(done),
           "failed": sum(1 for d in done
                         if len(d["tokens"]) != d["max_new_tokens"]),
           "metrics": metrics, "device": device, **extra, "checks": checks}
    print(json.dumps(out), flush=True)
    return out


def main(argv=None, t0: float | None = None) -> dict:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    prepare_env()
    c = load_cell(args.workload)
    cfg, mix, cell = c["cfg"], c["mix"], c["cell"]
    set_matmul_precision(cfg)
    devs, peak = find_chip(cell["chips"])

    from chipbench import trace
    setup_s, served, plan = setup(c, args.seed, t0)
    if served.backend() != "fused":
        raise SystemExit(f"chipbench: backend 'auto' resolved to "
                         f"{served.backend()!r}, not the fused kernel")

    win = Window(served, mix, args.seed, cfg["vocab"])
    compiles = CompileCount()
    logdir = STATE / "trace" / cell["name"] if args.trace else None
    program_counters = run_window(win, args.seconds, logdir)
    counts = win.counts()
    log(f"window: {win.seconds:.3f} s, drain share {win.drain_share():.4f}, "
        f"{counts}, programs compiled or loaded inside it: {compiles.n}")
    log(f"program counters over the window: {program_counters}")
    stats = devs[0].memory_stats() or {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    extra: dict = {}
    if args.trace:
        t = time.perf_counter()
        path = trace.find_xplane(str(logdir))
        red = trace.load_xplane(path)
        ctx = types.SimpleNamespace(
            trace=red, counts=counts, program_counters=program_counters,
            cfg=cfg, mix=mix, peak=peak,
            gemms=served.gemms(), n_slots=mix["serve"]["n_slots"],
            prefill_chunk=mix["serve"]["prefill_chunk"],
            requests=[(len(r["prompt"]), len(r["tokens"]))
                      for r in win.done()])
        metrics = per_layer(c["per_layer"], ctx)
        device["busy_s"] = trace.busy_s(red)
        device["window_s"] = trace.window_s(red)
        extra["breakdown"] = {"device_ops": trace.top_ops(red),
                              "idle_gaps": trace.idle_gaps(red)}
        log(f"trace read in {time.perf_counter() - t:.3f} s from {path}")
    else:
        e2e = end_to_end(win, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in c["end_to_end"]}
    return finish(c, win, served, plan, metrics, device, extra)
