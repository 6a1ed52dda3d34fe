#!/usr/bin/env python3
"""Compile a cell's decode step and prefill chunk for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload qwen3-32b.code
    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --config my.json --traffic code

No chip is needed: the TPU compiler runs here for a chip that is described,
not attached, and refuses a program that does not fit the device.  Prints
each program's `memory_analysis()` (arguments, outputs, temporaries) at the
cell's own shapes, with the fused kernel forced on.  It counts one program
at a time, not what else a run keeps on the device.  `--config` and
`--traffic` take a configuration file and a mix that no cell names yet,
to size a configuration before it is added.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path[:] = [p for p in sys.path
               if p not in ("", str(Path(__file__).resolve().parent))]
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    one = ap.add_mutually_exclusive_group(required=True)
    one.add_argument("--workload", help="a cell of BENCHMARK.json")
    one.add_argument("--config", help="a configuration file")
    ap.add_argument("--traffic", default="code",
                    help="the mix for --config (mixes/<traffic>.json)")
    args = ap.parse_args()
    from chipbench import harness
    harness.prepare_env()
    os.environ["ROSA_PLAN_CACHE"] = str(harness.STATE / "plans-rehearsal")
    if args.workload:
        c = harness.load_cell(args.workload)
    else:
        c = {"cfg": json.loads(Path(args.config).read_text()),
             "mix": json.loads((harness.BENCH / "mixes" /
                                f"{args.traffic}.json").read_text())}
    label = args.workload or f"{c['cfg']['name']}.{args.traffic}"
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import sut
    from repro.kernels.rosa_fused import ops
    from repro.models import transformer as T
    from repro.serve import init_state

    jax.config.update("jax_enable_compilation_cache", False)
    ops.on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def put(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    served = sut.Served(c["cfg"], c["mix"],
                        put(sut.abstract_params(c["cfg"])), backend="fused")
    sched, scfg = served.sched, served.scfg
    # the steps take the step params (the served weights prepared once)
    params = put(sched.step_params)
    state = put(jax.eval_shape(lambda: init_state(sched.cfg, scfg)))
    cache = put(jax.eval_shape(lambda: T.init_cache(sched.cfg, 1,
                                                    scfg.max_len)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    programs = {
        "decode step": lambda: sched.step.lower(
            params, state, put(jax.eval_shape(lambda: sched.null)),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=one)),
        "prefill chunk": lambda: sched.chunk_fn.lower(
            params, i32(1, scfg.prefill_chunk), i32(1), cache),
    }
    gib = 2.0 ** 30
    refused = 0
    for what, lower in programs.items():
        try:
            comp = lower().compile()
        except jax.errors.JaxRuntimeError as e:
            refused += 1
            print(f"{label} {what}: refused by the compiler: "
                  f"{str(e).splitlines()[0]}")
            continue
        m = comp.memory_analysis()
        print(f"{label} {what}: arguments "
              f"{m.argument_size_in_bytes / gib:.3f} GiB, outputs "
              f"{m.output_size_in_bytes / gib:.3f} GiB, temporaries "
              f"{m.temp_size_in_bytes / gib:.3f} GiB, aliased "
              f"{m.alias_size_in_bytes / gib:.3f} GiB; kernel in program: "
              f"{'tpu_custom_call' in comp.as_text()}")
    sys.exit(1 if refused else 0)

if __name__ == "__main__":
    main()
