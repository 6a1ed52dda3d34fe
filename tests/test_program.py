"""rosa.Program compile-once API: trace capture, JSON round-trips, the
content-addressed on-disk plan cache, autotune determinism, bit-exactness
against the eager Engine.matmul path (CNN + transformer families), and the
ContextVar ambient-engine semantics (thread isolation, deprecation)."""

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_cross_program_close

from repro import rosa
from repro.core import mapping as M
from repro.core import mrr, osa
from repro.core.constants import Mapping, ROSA_OPTIMAL

NOISY = rosa.RosaConfig(noise=mrr.PAPER_NOISE)
TUNE = rosa.AutotuneConfig(batch=4)


def _net(eng, x, w1, w2):
    h = eng.matmul(x, w1, name="a")
    return eng.matmul(h, w2, name="b")


def _args(key):
    k1, k2, k3 = jax.random.split(key, 3)
    return (jax.random.normal(k1, (4, 16)),
            jax.random.normal(k2, (16, 8)),
            jax.random.normal(k3, (8, 4)))


# ---------------------------------------------------------------------------
# Trace capture
# ---------------------------------------------------------------------------
def test_capture_trace_names_shapes_counts(key):
    eng = rosa.Engine.from_config(NOISY)
    w = jnp.ones((16, 16))

    def f(eng_, x):
        h = eng_.matmul(x, w, name="a")
        h = eng_.matmul(h, w, name="a")     # same layer routed twice
        return eng_.matmul(h, w, name="b")

    trace = rosa.capture_trace(f, eng, (jnp.ones((4, 16)),))
    assert trace.names == ("a", "b")
    by_name = {e.name: e for e in trace.entries}
    assert (by_name["a"].m, by_name["a"].k, by_name["a"].n) == (4, 16, 16)
    assert by_name["a"].count == 2
    assert by_name["b"].count == 1


def test_capture_trace_skips_dense_layers(key):
    eng = rosa.Engine.from_layer_cfgs({"opt": NOISY},
                                      layers=("opt", "plain"))
    w = jnp.ones((8, 8))

    def f(eng_, x):
        return eng_.matmul(eng_.matmul(x, w, name="opt"), w, name="plain")

    trace = rosa.capture_trace(f, eng, (jnp.ones((2, 8)),))
    assert trace.names == ("opt",)      # dense layers are not plan candidates


# ---------------------------------------------------------------------------
# JSON round-trips
# ---------------------------------------------------------------------------
def test_execution_plan_json_roundtrip():
    weird = dataclasses.replace(
        NOISY, mapping=Mapping.IS, quant_bits=6, backend="ref",
        act_per_vector=True,
        osa_cfg=osa.OSAConfig(splitter_imbalance=0.01))
    plan = rosa.ExecutionPlan.build(
        NOISY, {"a": weird, "b": None}, layers=("a", "b", "c"))
    doc = plan.to_json()
    back = rosa.ExecutionPlan.from_json(doc)
    assert back == plan
    assert back.resolve("a").osa_cfg.splitter_imbalance == 0.01
    assert back.resolve("b") is None
    # JSON-native all the way down (what the disk cache persists)
    import json
    assert rosa.ExecutionPlan.from_json(
        json.loads(json.dumps(doc))) == plan


def test_program_trace_json_roundtrip():
    trace = rosa.ProgramTrace((rosa.TraceEntry("a", 4, 16, 8, 2),
                               rosa.TraceEntry("b", 4, 8, 4, 1)))
    back = rosa.ProgramTrace.from_json(trace.to_json())
    assert back == trace
    assert back.fingerprint == trace.fingerprint
    assert back.layer_shapes()[0].k == 16


# ---------------------------------------------------------------------------
# Plan cache: cold searches, warm hits, key sensitivity
# ---------------------------------------------------------------------------
def test_plan_cache_cold_then_warm(key, tmp_path):
    eng = rosa.Engine.from_config(NOISY)
    args = _args(key)
    cold = rosa.compile(_net, eng, args, autotune=TUNE, cache=tmp_path)
    assert cold.searched and not cold.cache_hit
    assert (tmp_path / f"{cold.cache_key}.json").exists()
    warm = rosa.compile(_net, eng, args, autotune=TUNE, cache=tmp_path)
    assert warm.cache_hit and not warm.searched   # search skipped entirely
    assert warm.cache_key == cold.cache_key
    assert warm.plan == cold.plan


def test_plan_cache_key_tracks_inputs(key, tmp_path):
    eng = rosa.Engine.from_config(NOISY)
    args = _args(key)
    base = rosa.compile(_net, eng, args, autotune=TUNE, cache=tmp_path)
    # a different RosaConfig must miss the cache and re-search
    eng6 = rosa.Engine.from_config(dataclasses.replace(NOISY, quant_bits=6))
    other = rosa.compile(_net, eng6, args, autotune=TUNE, cache=tmp_path)
    assert other.cache_key != base.cache_key
    assert other.searched and not other.cache_hit
    # different search settings miss too
    tuned = rosa.compile(_net, eng, args, cache=tmp_path,
                         autotune=rosa.AutotuneConfig(batch=64))
    assert tuned.cache_key != base.cache_key
    # different traced workload (new shapes) misses as well
    wide = (jnp.ones((4, 32)), jnp.ones((32, 8)), jnp.ones((8, 4)))
    other_tr = rosa.compile(_net, eng, wide, autotune=TUNE, cache=tmp_path)
    assert other_tr.cache_key != base.cache_key


def test_autotune_matches_manual_search(key):
    eng = rosa.Engine.from_config(NOISY)
    prog = rosa.compile(_net, eng, _args(key), autotune=TUNE, cache=False)
    profs = M.profile_layers_fast(prog.trace.layer_shapes(), TUNE.ope,
                                  batch=TUNE.batch)
    assert prog.plan.mapping_plan() == M.hybrid_plan(profs)
    assert prog.plan.default == NOISY           # base config preserved


def test_autotune_accuracy_guard(key):
    """A degradation matrix + guard_pp vetoes EDP-favoured mappings that
    cost accuracy (repro.robust-style accuracy-aware search)."""
    eng = rosa.Engine.from_config(NOISY)
    free = rosa.compile(_net, eng, _args(key), autotune=TUNE, cache=False)
    deg = {n: {Mapping.IS.value: 50.0, Mapping.WS.value: 0.0}
           for n in free.trace.names}
    guarded = rosa.compile(
        _net, eng, _args(key), cache=False, degradation=deg,
        autotune=dataclasses.replace(TUNE, guard_pp=0.5))
    assert all(m is Mapping.WS
               for m in guarded.plan.mapping_plan().values())


def test_autotune_requires_base_config(key):
    with pytest.raises(ValueError, match="autotune"):
        rosa.compile(_net, rosa.Engine.dense(), _args(key),
                     autotune=TUNE, cache=False)


# ---------------------------------------------------------------------------
# Program execution: bit-exact vs the eager Engine.matmul path
# ---------------------------------------------------------------------------
def test_program_matches_eager_toy(key):
    eng = rosa.Engine.from_config(NOISY, key=jax.random.PRNGKey(0))
    args = _args(key)
    prog = rosa.compile(_net, eng, args, autotune=TUNE, cache=False)
    eager = _net(eng.with_plan(prog.plan), *args)
    assert_cross_program_close(prog(*args), eager)
    # explicit key threading == eager engine with that base key
    k2 = jax.random.PRNGKey(9)
    assert_cross_program_close(
        prog(*args, key=k2),
        np.asarray(_net(eng.with_plan(prog.plan).with_key(k2), *args)))
    assert float(jnp.max(jnp.abs(prog(*args, key=k2) - prog(*args)))) > 0


def test_program_variation_threading(key):
    eng = rosa.Engine.from_config(NOISY, key=jax.random.PRNGKey(0))
    args = _args(key)
    prog = rosa.compile(_net, eng, args, autotune=None, cache=False)
    var = {"a": mrr.StaticVariation(jnp.asarray(0.05), jnp.asarray(0.0),
                                    jnp.asarray(0.0))}
    y = prog(*args, variation=var)
    eager = _net(eng.with_variation(var), *args)
    assert_cross_program_close(y, eager)
    assert float(jnp.max(jnp.abs(y - prog(*args)))) > 0


def test_program_matches_eager_cnn(key):
    """Acceptance pin: Program output bit-exact with the eager
    Engine.matmul path for a CNN family."""
    from repro.models.cnn import LITE_MODELS, LITE_SKIPS, cnn_apply, cnn_def
    from repro.models.module import init_params
    from repro.training.cnn_train import cnn_program

    model = "alexnet"
    specs = LITE_MODELS[model]
    params = init_params(cnn_def(specs), jax.random.PRNGKey(1))
    eng = rosa.Engine.from_config(NOISY, layers=[s.name for s in specs],
                                  key=jax.random.PRNGKey(0))
    prog = cnn_program(model, eng)
    x = jax.random.normal(key, (4, 32, 32, 3))
    eager = cnn_apply(params, specs, x, eng,
                      residual_from=LITE_SKIPS.get(model))
    assert_cross_program_close(prog(params, x), eager)


def test_program_matches_eager_transformer(key):
    """Acceptance pin: Program output bit-exact with the eager
    ambient-engine path for a transformer family (rosa_mlp prefill)."""
    import dataclasses as dc

    from repro.configs import get_smoke
    from repro.models.model import build_model

    cfg = dc.replace(get_smoke("qwen3-32b"), rosa_mlp=True)
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0), jnp.float32)
    eng = rosa.Engine.from_config(NOISY, key=jax.random.PRNGKey(3))
    batch = {"tokens": jax.random.randint(key, (2, 8), 0, cfg.vocab,
                                          dtype=jnp.int32)}
    prog = rosa.compile(lambda e, p, b: bundle.prefill(p, b), eng,
                        (params, batch), autotune=None, cache=False)
    logits, _ = prog(params, batch)
    with rosa.engine_context(eng):
        logits_eager, _ = bundle.prefill(params, batch)
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(logits_eager))


def test_program_ledger_prices_tuned_plan(key):
    ledger = rosa.EnergyLedger()
    eng = rosa.Engine.from_config(NOISY, ledger=ledger)
    prog = rosa.compile(_net, eng, _args(key), autotune=TUNE, cache=False)
    assert prog.ledger is not None
    traced_plan = prog.ledger.mapping_plan()
    assert traced_plan == prog.plan.mapping_plan()
    assert prog.ledger.edp(ROSA_OPTIMAL) == pytest.approx(
        M.plan_edp(prog.trace.layer_shapes(), traced_plan, ROSA_OPTIMAL,
                   batch=1), rel=1e-12)


def test_compile_leaves_populated_ledger_untouched(key):
    """Compiling against an engine whose ledger already carries (scoped)
    runtime events must not append untagged compile-time duplicates —
    tag=None pricing would double-count them (the serving ledger case)."""
    ledger = rosa.EnergyLedger()
    eng = rosa.Engine.from_config(NOISY, key=jax.random.PRNGKey(0),
                                  ledger=ledger)
    args = _args(key)
    with ledger.scope("decode"):
        eng.matmul(args[0], args[1], name="a")
    before = list(ledger.events)
    rosa.compile(_net, eng, args, autotune=TUNE, cache=False)
    assert ledger.events == before


def test_program_bind_installs_engine(key):
    eng = rosa.Engine.from_config(NOISY, key=jax.random.PRNGKey(0))
    args = _args(key)
    prog = rosa.compile(_net, eng, args, autotune=None, cache=False)

    def ambient_fn(x, w):
        return rosa.ambient_engine().matmul(x, w, name="a")

    bound = prog.bind(ambient_fn)
    assert_cross_program_close(bound(args[0], args[1]),
                               eng.matmul(args[0], args[1], name="a"))


def test_dense_program_is_plain_matmul(key):
    args = _args(key)
    prog = rosa.compile(_net, rosa.Engine.dense(), args, cache=False)
    assert len(prog.trace) == 0
    np.testing.assert_allclose(
        np.asarray(prog(*args)), np.asarray(args[0] @ args[1] @ args[2]),
        rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Ambient engine: ContextVar semantics
# ---------------------------------------------------------------------------
def test_engine_context_thread_isolation():
    e1 = rosa.Engine.from_config(NOISY)
    e2 = rosa.Engine.from_config(rosa.DEFAULT)
    barrier = threading.Barrier(2)
    seen = {}

    def worker(name, engine):
        with rosa.engine_context(engine):
            barrier.wait(timeout=10)       # both contexts active at once
            seen[name] = rosa.ambient_engine()

    threads = [threading.Thread(target=worker, args=("t1", e1)),
               threading.Thread(target=worker, args=("t2", e2))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert seen["t1"] is e1
    assert seen["t2"] is e2
    assert rosa.ambient_engine() is None   # nothing leaked to the main thread


def test_engine_context_nests_and_restores():
    e1 = rosa.Engine.from_config(NOISY)
    e2 = rosa.Engine.dense()
    assert rosa.ambient_engine() is None
    with rosa.engine_context(e1):
        assert rosa.ambient_engine() is e1
        with rosa.engine_context(e2):
            assert rosa.ambient_engine() is e2
        assert rosa.ambient_engine() is e1
    assert rosa.ambient_engine() is None


def test_deprecated_wrappers_warn_and_delegate():
    eng = rosa.Engine.from_config(NOISY)
    with pytest.warns(DeprecationWarning, match="use_engine"):
        ctx = rosa.use_engine(eng)
    with ctx:
        with pytest.warns(DeprecationWarning, match="current_engine"):
            assert rosa.current_engine() is eng
    assert rosa.ambient_engine() is None


# ---------------------------------------------------------------------------
# Donation canaries: declared donations survive into compiled HLO
# ---------------------------------------------------------------------------
def test_program_donation_canary(key):
    """Pin: a Program compiled with donate_argnums aliases the donated
    buffer in its optimized HLO (checked against the real alias map, not
    the declaration)."""
    from repro.analysis import program_target, run_checks
    from repro.analysis.hlo import (entry_parameter_shapes,
                                    parse_input_output_aliases)

    eng = rosa.Engine.from_config(NOISY)

    def f(e, x, w, state):
        return state + e.matmul(x, w, name="a")

    sds = jax.ShapeDtypeStruct
    ex = (sds((4, 16), jnp.float32), sds((16, 16), jnp.float32),
          sds((4, 16), jnp.float32))
    prog = rosa.compile(f, eng, ex, donate_argnums=(2,), cache=False)

    t = program_target(prog, ex, name="canary:program")
    assert list(run_checks([t], checks=["donation"])) == []

    txt = prog._call.lower(sds((2,), jnp.uint32), None,
                           *ex).compile().as_text()
    aliases = parse_input_output_aliases(txt)
    params = entry_parameter_shapes(txt)
    aliased = [params.get(p, "").split("{")[0] for p, _ in aliases]
    assert "f32[4,16]" in aliased, (aliases, params)


def test_program_verify_catches_dropped_donation(key):
    """Negative control: donating an arg the program never touches must
    surface as DON001 through verify="error"."""
    from repro import analysis as A

    eng = rosa.Engine.from_config(NOISY)

    def f(e, x, w, scratch):
        return e.matmul(x, w, name="a")

    sds = jax.ShapeDtypeStruct
    ex = (sds((4, 16), jnp.float32), sds((16, 16), jnp.float32),
          sds((4, 16), jnp.float32))
    with pytest.raises(A.VerificationError) as ei:
        rosa.compile(f, eng, ex, donate_argnums=(2,), cache=False,
                     verify="error")
    assert any(fd.code == "DON001" for fd in ei.value.report.findings)


# ---------------------------------------------------------------------------
# accuracy-aware default + cached degradation matrices
# ---------------------------------------------------------------------------
def _counting_source(calls):
    """A DegradationSource whose measure() logs which layers it was asked
    to score (IS mildly worse so the guard keeps WS deterministically)."""
    def measure(names):
        calls.append(tuple(names))
        return {n: {Mapping.IS.value: 2.0, Mapping.WS.value: 0.0}
                for n in names}
    return rosa.DegradationSource(measure=measure, spec={"kind": "test",
                                                         "v": 1})


def test_autotune_accuracy_aware_default():
    assert rosa.AutotuneConfig().accuracy_aware is True
    assert rosa.EDP_ONLY.accuracy_aware is False
    # old cached/serialized configs (no key) stay accuracy-aware
    doc = rosa.AutotuneConfig().to_json()
    doc.pop("accuracy_aware", None)
    assert rosa.AutotuneConfig.from_json(doc).accuracy_aware is True


def test_compile_measures_once_then_warm_skips_mc(key, tmp_path):
    """Tentpole acceptance: a warm accuracy-aware compile takes its
    degradation matrix from the PlanCache and never re-runs MC."""
    eng = rosa.Engine.from_config(NOISY)
    args = _args(key)
    calls = []
    src = _counting_source(calls)
    cold = rosa.compile(_net, eng, args, autotune=TUNE, cache=tmp_path,
                        degradation=src)
    assert cold.searched and calls == [("a", "b")]
    warm = rosa.compile(_net, eng, args, autotune=TUNE, cache=tmp_path,
                        degradation=src)
    assert warm.cache_hit and not warm.searched
    assert calls == [("a", "b")]                  # MC stage skipped entirely
    assert warm.plan == cold.plan
    # plan evicted but matrix kept: re-search, still no re-measure
    (tmp_path / f"{cold.cache_key}.json").unlink()
    rewarm = rosa.compile(_net, eng, args, autotune=TUNE, cache=tmp_path,
                          degradation=src)
    assert rewarm.searched and calls == [("a", "b")]
    assert rewarm.plan == cold.plan


def test_matrix_cache_measures_only_missing_layers(key, tmp_path):
    """Incremental re-score: rows already in the cache are reused and only
    absent layers are measured."""
    eng = rosa.Engine.from_config(NOISY)
    args = _args(key)
    calls = []
    src = _counting_source(calls)
    cache = rosa.PlanCache(tmp_path)
    mkey = cache.matrix_key(NOISY, src.spec)
    cache.store_matrix(mkey, {"a": {Mapping.IS.value: 2.0,
                                    Mapping.WS.value: 0.0}})
    prog = rosa.compile(_net, eng, args, autotune=TUNE, cache=tmp_path,
                        degradation=src)
    assert calls == [("b",)]                       # only the missing row
    assert prog.searched
    # the merged matrix is persisted: a fresh compile measures nothing
    rosa.compile(_net, eng, args, cache=tmp_path, degradation=src,
                 autotune=dataclasses.replace(TUNE, batch=8))
    assert calls == [("b",)]


def test_matrix_cache_invalidation(key, tmp_path):
    """A changed variation spec or base RosaConfig must re-measure."""
    eng = rosa.Engine.from_config(NOISY)
    args = _args(key)
    calls = []
    src = _counting_source(calls)
    rosa.compile(_net, eng, args, autotune=TUNE, cache=tmp_path,
                 degradation=src)
    assert len(calls) == 1
    # same config, different spec -> different matrix key -> re-measure
    src2 = rosa.DegradationSource(measure=src.measure,
                                  spec={"kind": "test", "v": 2})
    rosa.compile(_net, eng, args, autotune=TUNE, cache=tmp_path,
                 degradation=src2)
    assert len(calls) == 2
    # same spec, different RosaConfig -> re-measure too
    eng6 = rosa.Engine.from_config(dataclasses.replace(NOISY, quant_bits=6))
    rosa.compile(_net, eng6, args, autotune=TUNE, cache=tmp_path,
                 degradation=src)
    assert len(calls) == 3


def test_edp_only_ignores_degradation_source(key, tmp_path):
    calls = []
    src = _counting_source(calls)
    eng = rosa.Engine.from_config(NOISY)
    prog = rosa.compile(_net, eng, _args(key), cache=tmp_path,
                        degradation=src,
                        autotune=dataclasses.replace(
                            rosa.EDP_ONLY, batch=TUNE.batch))
    assert calls == []                             # MC never invoked
    assert prog.searched
    # and the EDP-only plan matches the historic accuracy-blind search
    profs = M.profile_layers_fast(prog.trace.layer_shapes(), TUNE.ope,
                                  batch=TUNE.batch)
    assert prog.plan.mapping_plan() == M.hybrid_plan(profs)


def test_matrix_cache_roundtrip_and_corruption(tmp_path):
    cache = rosa.PlanCache(tmp_path)
    mkey = cache.matrix_key(NOISY, {"kind": "test"})
    layers = {"a": {Mapping.IS.value: 1.5, Mapping.WS.value: 0.25}}
    cache.store_matrix(mkey, layers)
    assert cache.load_matrix(mkey) == layers
    assert cache.load_matrix("no-such-key") is None
    (tmp_path / f"{mkey}.deg.json").write_text("{corrupt")
    assert cache.load_matrix(mkey) is None         # never raises
