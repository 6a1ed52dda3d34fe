#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, in one process.

    python3 chipbench/control.py --workload qwen3-32b.code --seeds 11,12,13 \
        --control-seeds 3 --seconds 20

For each seed: the seed's weights, a window of the cell's own load
through the timed path, and the comparison a run makes (compare.py),
printed as the program's reading with the verdict the cell's limits give.
For the first `--control-seeds` seeds the control follows: the reference
at the nearest precision below the configuration's (`LOWER` of its
reference module), put in the program's place at the same prompts and
served tokens, judged by the same comparison and limits.  It has to come
out not correct.  One JSON line per seed and reading; the benchmark's own
runs do not run this.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys
import time
from pathlib import Path

sys.path[:] = [p for p in sys.path
               if p not in ("", str(Path(__file__).resolve().parent))]
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def control_choices(ref, chip, picked, cfg, mix, prec, abstract, seed,
                    served) -> list:
    """The tokens the reference at `prec` puts first.  A bfloat16 control
    holds bfloat16 weights alone: the float32 copy is dropped while it
    runs and made again after."""
    import jax

    from chipbench import compare, weights
    dtype = ref.PRECISIONS[prec][0]
    f32 = jax.numpy.dtype(cfg["precision"]["params"])
    if dtype == f32:
        return compare.first_choices(ref, served.sched.params, chip, picked,
                                     cfg, mix, prec)
    served.sched.params = None
    gc.collect()
    low = weights.make(abstract, ref.leaf_init, seed, dtype)
    firsts = compare.first_choices(ref, low, chip, picked, cfg, mix, prec)
    del low
    gc.collect()
    served.sched.params = weights.make(abstract, ref.leaf_init, seed, f32)
    return firsts


def readings(workload: str, seeds: list[int], control_seeds: int,
             seconds: float, controls=None, need_chip: bool = True,
             cell_override: dict | None = None, emit=print) -> list[dict]:
    from chipbench import harness
    harness.prepare_env()
    c = cell_override or harness.load_cell(workload)
    cfg, mix = c["cfg"], c["mix"]
    import jax
    harness.set_matmul_precision(cfg)

    from chipbench import compare, sut, weights
    from repro.launch.compile_cache import enable_compile_cache
    if need_chip:
        harness.find_chip(c["cell"]["chips"])
    enable_compile_cache()
    ref = importlib.import_module(f"chipbench.references.{cfg['reference']}")
    chip = ref.sample_chip(cfg["optical"], ref.gemm_lanes(cfg))
    controls = controls or (ref.LOWER[ref.stated(cfg)],)
    abstract = sut.abstract_params(cfg)
    dtype = jax.numpy.dtype(cfg["precision"]["params"])
    served = None
    out = []
    for i, seed in enumerate(seeds):
        params = weights.make(abstract, ref.leaf_init, seed, dtype)
        if served is None:
            served = sut.Served(cfg, mix, params)
            served.warm()
        served.sched.params = params
        del params      # a control may drop the served copy to fit
        win = harness.Window(served, mix, seed, cfg["vocab"])
        win.run(seconds)
        done = win.done()
        short = sum(1 for d in done
                    if len(d["tokens"]) != d["max_new_tokens"])
        picked = [d for d in done if d["tokens"]]
        choices = {"program": compare.served_tokens(picked,
                                                    mix["output"]["max"])}
        if i < control_seeds:
            for prec in controls:
                choices[f"control_{prec}"] = control_choices(
                    ref, chip, picked, cfg, mix, prec, abstract, seed, served)
        t = time.perf_counter()
        gaps = compare.reference_gaps(ref, served.sched.params, chip, picked,
                                      cfg, mix, choices)
        for who, g in gaps.items():
            nums = compare.numbers(g, short if who == "program" else 0)
            correct, _ = compare.verdict(nums, c["limits"])
            rec = {"seed": seed, "who": who, "tokens": int(g.size),
                   "correct": correct, **nums}
            if who == "program":
                rec.update(requests=len(done), window_s=win.seconds,
                           reference_s=time.perf_counter() - t)
            emit(json.dumps(rec))
            out.append(rec)
        served.sched.params = None
        gc.collect()
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--controls", default=None,
                    help="reference precisions read as controls (default: "
                    "the one below the configuration's)")
    args = ap.parse_args()
    readings(args.workload, [int(s) for s in args.seeds.split(",")],
             args.control_seeds, args.seconds,
             controls=args.controls and args.controls.split(","))


if __name__ == "__main__":
    main()
