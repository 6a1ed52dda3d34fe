"""Pre-flight: every Pallas kernel compiles for a TPU v5e at real widths.

Nothing runs here.  The TPU compiler, which is installed alongside jaxlib,
compiles each kernel for a described (not attached) v5e chip, so a block
layout or VMEM budget the chip would refuse fails here, at no chip time,
instead of hiding behind interpret mode.  Shapes are the serving path's:
the qwen3-32b SwiGLU GEMMs (d_model 5120, d_ff 25600, gate and up fused
into one 5120 x 51200 projection) at the decode M (8 slots) and the
prefill-chunk M (256), and the SSD scan at mamba2-1.3b widths.  The
slot-sharded serving steps that launch the fused kernel are compiled for a
described 2x2 v5e at smoke widths.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import mrr
from repro.core.constants import Mapping

D_MODEL, D_FF = 5120, 25600
DECODE_M, CHUNK_M = 8, 256
MLP_GEMMS = {"wi": (D_MODEL, 2 * D_FF), "wo": (D_FF, D_MODEL)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", [DECODE_M, CHUNK_M], ids=["decode", "chunk"])
@pytest.mark.parametrize("gemm", sorted(MLP_GEMMS))
@pytest.mark.parametrize("realize", ["none", "w", "x"])
def test_rosa_fused_compiles(one_chip, monkeypatch, gemm, m, realize):
    """The public wrapper (scales, offsets, padding) plus the kernel, as
    serving launches it: ideal device (no realization), a pinned chip under
    WS (weight-side offset streams) and under IS (activation side)."""
    from repro.kernels.rosa_fused import ops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    k, n = MLP_GEMMS[gemm]
    var = None
    if realize != "none":
        lanes = _sds(one_chip, k)
        var = mrr.StaticVariation(lanes, lanes, lanes)
    mapping = Mapping.IS if realize == "x" else Mapping.WS
    fn = jax.jit(lambda x, w, v: ops.rosa_fused_matmul(
        x, w, None, v, mapping=mapping, act_per_vector=True))
    compiled = fn.lower(_sds(one_chip, m, k), _sds(one_chip, k, n),
                        var).compile()
    _assert_kernel(compiled)


def test_osa_matmul_compiles(one_chip):
    from repro.kernels.osa_matmul.osa_matmul import osa_matmul_pallas
    compiled = osa_matmul_pallas.lower(
        _sds(one_chip, 128, D_MODEL), _sds(one_chip, D_MODEL, D_FF),
        _sds(one_chip, 7), n_planes=7).compile()
    _assert_kernel(compiled)


def test_mrr_transfer_compiles(one_chip):
    """Realization of one 5120 x 25600 weight, flattened to 128 lanes."""
    from repro.kernels.mrr_transfer.mrr_transfer import mrr_transfer_pallas
    rows = D_MODEL * D_FF // 128
    t = _sds(one_chip, rows, 128)
    _assert_kernel(mrr_transfer_pallas.lower(t, t, t).compile())


def test_ssd_scan_compiles(one_chip):
    """mamba2-1.3b: 64 heads of head_dim 64, d_state 128, batch 4 x 2048."""
    from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas
    bh, seq, p, s_dim = 4 * 64, 2048, 64, 128
    compiled = ssd_scan_pallas.lower(
        _sds(one_chip, bh, seq, p), _sds(one_chip, bh, seq),
        _sds(one_chip, bh, seq, s_dim), _sds(one_chip, bh, seq, s_dim),
        chunk=128).compile()
    _assert_kernel(compiled)


@pytest.fixture(scope="module")
def slot_mesh(topo, one_chip):
    import numpy as np
    from jax.sharding import AxisType, Mesh
    return Mesh(np.array(topo.devices[:4]), ("data",),
                axis_types=(AxisType.Auto,))


@pytest.mark.parametrize("arch", ["qwen3-32b", "zamba2-1.2b"])
def test_mesh_prefill_compiles(slot_mesh, monkeypatch, arch):
    """Slot-sharded serving: a request's prefill (chunked for qwen3,
    whole-prompt for the hybrid zamba2) runs on every device's replica of
    the weights.  XLA cannot partition a Mosaic kernel, so a step that
    launches the fused kernel compiles only if it is kept whole per
    device.  The hybrid routes no GEMM through the optical engine (its
    serving trace is empty), so its prefill holds no kernel."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_smoke
    from repro.kernels.rosa_fused import ops
    from repro.models import transformer as T
    from repro.models.model import build_model
    from repro.serve import ServeConfig, Scheduler
    from repro.serve.config import serving_model_config
    from repro.serve.decode import make_chunk_fn, make_prefill_fn

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = get_smoke(arch)
    scfg = ServeConfig(n_slots=4, max_len=64, prefill_chunk=16, rosa=True,
                       rosa_backend="fused", variation_seed=0)
    everywhere = NamedSharding(slot_mesh, P())

    def put(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=everywhere), tree)

    params = build_model(serving_model_config(cfg, rosa=True)).abstract(
        jnp.float32)
    sched = Scheduler(cfg, scfg, params=params, plan_cache=False)
    tokens = jax.ShapeDtypeStruct((1, scfg.prefill_chunk), jnp.int32,
                                  sharding=everywhere)
    if cfg.family in ("ssm", "hybrid"):
        fn = make_prefill_fn(sched.bundle, program=sched.program,
                             mesh=slot_mesh)
        lowered = fn.lower(put(params), {"tokens": tokens})
    else:
        fn = make_chunk_fn(sched.bundle, program=sched.program,
                           mesh=slot_mesh)
        cache = jax.eval_shape(lambda: T.init_cache(sched.cfg, 1,
                                                    scfg.max_len))
        lowered = fn.lower(put(params), tokens,
                           jax.ShapeDtypeStruct((1,), jnp.int32,
                                                sharding=everywhere),
                           put(cache))
    routed = bool(sched.program.trace.entries)
    assert routed == (arch == "qwen3-32b")
    assert ("tpu_custom_call" in lowered.compile().as_text()) == routed
