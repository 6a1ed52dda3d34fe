#!/usr/bin/env python3
"""Compile a cell's decode step and prefill chunk for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload qwen3-32b.longctx

No chip is needed: the TPU compiler runs here for a chip that is described,
not attached, and refuses a program that does not fit the device.  Prints
each program's `memory_analysis()` (arguments, outputs, temporaries) at the
cell's own shapes, with the fused kernel forced on.  It counts one program
at a time, not what else a run keeps on the device.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

sys.path[:] = [p for p in sys.path
               if p not in ("", str(Path(__file__).resolve().parent))]
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    from chipbench import harness
    harness.prepare_env()
    os.environ["ROSA_PLAN_CACHE"] = str(harness.STATE / "plans-rehearsal")
    c = harness.load_cell(args.workload)
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

    params = put(sut.abstract_params(c["cfg"]))
    served = sut.Served(c["cfg"], c["mix"], params, backend="fused")
    sched, scfg = served.sched, served.scfg
    state = put(jax.eval_shape(lambda: init_state(sched.cfg, scfg)))
    step = sched.step.lower(params, state, put(jax.eval_shape(
        lambda: sched.null)), jax.ShapeDtypeStruct((), jnp.float32,
                                                   sharding=one)).compile()
    cache = put(jax.eval_shape(lambda: T.init_cache(sched.cfg, 1,
                                                    scfg.max_len)))
    chunk = sched.chunk_fn.lower(
        params, jax.ShapeDtypeStruct((1, scfg.prefill_chunk), jnp.int32,
                                     sharding=one),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one),
        cache).compile()
    gib = 2.0 ** 30
    for what, comp in (("decode step", step), ("prefill chunk", chunk)):
        m = comp.memory_analysis()
        print(f"{args.workload} {what}: arguments "
              f"{m.argument_size_in_bytes / gib:.3f} GiB, outputs "
              f"{m.output_size_in_bytes / gib:.3f} GiB, temporaries "
              f"{m.temp_size_in_bytes / gib:.3f} GiB, aliased "
              f"{m.alias_size_in_bytes / gib:.3f} GiB; kernel in program: "
              f"{'tpu_custom_call' in comp.as_text()}")


if __name__ == "__main__":
    main()
