"""The scheduler's step params: the served MLP weights put once into the
form the optical engine consumes (`repro.serve.prepare_step_params`).

The pass must leave `Scheduler.params` as given, hand the steps a
(L, d, 2f) gate|up layout plus each layer's weight full-scales bit-equal
to what a step would compute, change no bit of any step's output, and
keep the relayout and the weight reductions out of the step programs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import rosa
from repro.analysis.jaxprs import iter_eqns
from repro.configs import get_smoke
from repro.core import mrr
from repro.core import quant as Q
from repro.core.constants import Mapping
from repro.models import transformer as T
from repro.models.model import build_model
from repro.serve import (Scheduler, ServeConfig, prepare_step_params,
                         serving_model_config)

SMOKE = get_smoke("qwen3-32b")
SCFG = ServeConfig(n_slots=2, max_len=24, prefill_chunk=4, rosa=True,
                   rosa_backend="fused", variation_seed=7)


def _bundle():
    return build_model(serving_model_config(SMOKE, rosa=True))


def _params_with_zero_layer(bundle):
    """Seeded weights whose last layer's MLP is all zeros (the 1e-8 floor
    of the full-scale binds there)."""
    params = bundle.init(jax.random.PRNGKey(3))
    ffn = params["layers"]["ffn"]
    ffn = dict(ffn, wi=ffn["wi"].at[-1].set(0.0), wo=ffn["wo"].at[-1].set(0.0))
    return dict(params, layers=dict(params["layers"], ffn=ffn))


def test_step_params_leave_params_untouched():
    bundle = _bundle()
    params = _params_with_zero_layer(bundle)
    raw_ffn = params["layers"]["ffn"]
    n_layers, d, _, f = raw_ffn["wi"].shape
    sched = Scheduler(SMOKE, SCFG, params=params)

    assert sched.params is params
    assert sched.params["layers"]["ffn"] is raw_ffn
    assert raw_ffn["wi"].shape == (n_layers, d, 2, f)
    assert set(raw_ffn) == {"wi", "wo"}

    step = sched.step_params
    sffn = step["layers"]["ffn"]
    assert sffn["wi"].shape == (n_layers, d, 2 * f)
    np.testing.assert_array_equal(
        np.asarray(sffn["wi"]),
        np.asarray(raw_ffn["wi"]).reshape(n_layers, d, 2 * f))
    assert sffn["wo"] is raw_ffn["wo"]
    for name in ("wi", "wo"):
        scale = sffn[f"{name}_scale"]
        assert scale.shape == (n_layers,) and scale.dtype == jnp.float32
        want = [Q.absmax_scale(raw_ffn[name][i]) for i in range(n_layers)]
        np.testing.assert_array_equal(np.asarray(scale),
                                      np.asarray(jnp.stack(want)))
        assert float(scale[-1]) == float(jnp.float32(1e-8))

    # every other leaf is the very array the caller gave
    def others(tree):
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        return {jax.tree_util.keystr(p): a for p, a in flat
                if "'ffn'" not in jax.tree_util.keystr(p)}
    raw, prepared = others(params), others(step)
    assert raw.keys() == prepared.keys()
    assert all(prepared[k] is raw[k] for k in raw)


def test_params_assignment_rebuilds_step_params():
    """Assigning `params` (as a harness does to serve other weights, or
    None to free them) re-derives the step params from the new weights."""
    bundle = _bundle()
    sched = Scheduler(SMOKE, SCFG, params=bundle.init(jax.random.PRNGKey(0)))
    sched.params = None
    assert sched.step_params is None
    fresh = bundle.init(jax.random.PRNGKey(1))
    sched.params = fresh
    np.testing.assert_array_equal(
        np.asarray(sched.step_params["layers"]["ffn"]["wi_scale"]),
        np.asarray(jax.vmap(Q.absmax_scale)(fresh["layers"]["ffn"]["wi"])))


def test_step_params_only_for_the_optical_mlp():
    """Without the optical MLP the steps take the params as they are."""
    plain = build_model(SMOKE)
    params = plain.init(jax.random.PRNGKey(0))
    assert prepare_step_params(plain.cfg, params) is params
    sched = Scheduler(SMOKE, ServeConfig(n_slots=2, max_len=24,
                                         prefill_chunk=4), params=params)
    assert sched.step_params is params


def _engine(mapping: Mapping):
    cfg = rosa.RosaConfig(mapping=mapping, noise=mrr.PAPER_NOISE,
                          backend="fused", act_per_vector=True)
    return rosa.Engine.from_config(cfg, key=jax.random.PRNGKey(5))


def _decode_batch(cfg, n=2):
    cache = T.init_cache(cfg, n, 24)
    return {"token": jnp.array([3, 11][:n], jnp.int32),
            "pos": cache["pos"], "cache": cache}


def _chunk_batch(cfg):
    return {"tokens": jnp.array([[5, 9, 2, 7]], jnp.int32),
            "n_valid": jnp.array([3], jnp.int32),
            "cache": T.init_cache(cfg, 1, 24)}


@pytest.mark.parametrize("mapping", [Mapping.IS, Mapping.WS])
@pytest.mark.parametrize("which", ["decode_step", "chunk_step"])
def test_steps_bitwise_equal_on_step_params(mapping, which):
    bundle = _bundle()
    params = bundle.init(jax.random.PRNGKey(2))
    step = prepare_step_params(bundle.cfg, params)
    batch = (_decode_batch if which == "decode_step"
             else _chunk_batch)(bundle.cfg)
    fn = getattr(bundle, which)
    with rosa.engine_context(_engine(mapping)):
        raw_out = jax.jit(fn)(params, batch)
        step_out = jax.jit(fn)(step, batch)
    logits_raw, logits_step = raw_out[0], step_out[0]
    np.testing.assert_array_equal(np.asarray(logits_step),
                                  np.asarray(logits_raw))
    np.testing.assert_array_equal(np.asarray(jnp.argmax(logits_step, -1)),
                                  np.asarray(jnp.argmax(logits_raw, -1)))
    for a, b in zip(jax.tree.leaves(step_out[1]), jax.tree.leaves(raw_out[1])):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def _weight_work(closed, shapes) -> dict:
    """Counts of `reduce_max` and `reshape` equations whose operand has
    one of `shapes`, anywhere in the jaxpr and its sub-jaxprs."""
    out = {"reduce_max": 0, "reshape": 0}
    for eqn, _path, _depth in iter_eqns(closed):
        prim = eqn.primitive.name
        if prim in out and tuple(eqn.invars[0].aval.shape) in shapes:
            out[prim] += 1
    return out


def test_decode_step_holds_no_weight_prep():
    """The decode step on step params holds no reduction over a weight and
    no relayout of `wi`; on raw params it holds both, per GEMM."""
    bundle = _bundle()
    params = bundle.init(jax.random.PRNGKey(0))
    _, d, _, f = params["layers"]["ffn"]["wi"].shape
    wi_slice, wi_flat, wo_slice = (d, 2, f), (d, 2 * f), (f, d)
    step = prepare_step_params(bundle.cfg, params)
    batch = _decode_batch(bundle.cfg)
    with rosa.engine_context(_engine(Mapping.IS)):
        raw_j = jax.make_jaxpr(bundle.decode_step)(params, batch)
        step_j = jax.make_jaxpr(bundle.decode_step)(step, batch)
    raw = _weight_work(raw_j, {wi_slice, wi_flat, wo_slice})
    assert raw["reduce_max"] >= 2 and raw["reshape"] >= 1
    assert _weight_work(step_j, {wi_slice, wi_flat, wo_slice}) == {
        "reduce_max": 0, "reshape": 0}


def test_every_served_launch_takes_the_prepared_scale():
    """Scheduler setup records one `serve.prepare_weights` span, and every
    fused-kernel launch the serving steps trace reads
    `w_scale_given=True`.  (The plan search traces the model on raw
    params before: those launches are not served.)"""
    from repro.obs import trace as obs
    from repro.serve import Request

    bundle = _bundle()
    params = bundle.init(jax.random.PRNGKey(4))
    n_layers, d, _, f = params["layers"]["ffn"]["wi"].shape
    setup, serving = obs.Tracer(), obs.Tracer()
    with obs.tracing(setup):
        sched = Scheduler(SMOKE, SCFG, params=params)
    with obs.tracing(serving):
        sched.run([Request(0, np.arange(6) % SMOKE.vocab, 3)])
    prep = [e for e in setup.events if e["name"] == "serve.prepare_weights"]
    assert len(prep) == 1
    assert prep[0]["args"] == {"n_weights": 2 * n_layers,
                               "relaid_bytes": n_layers * d * 2 * f * 4}
    launches = [e["args"] for e in serving.events
                if e["name"] == "kernels.rosa_fused"]
    assert len(launches) >= 4         # both GEMMs of decode and prefill
    assert all(a["w_scale_given"] for a in launches)
