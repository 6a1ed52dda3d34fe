"""Differential serving-equivalence suite (repro.serve).

The semantic spec of serving: whatever the continuous-batching scheduler
interleaves — staggered arrivals, mid-stream slot eviction + refill, ragged
prompt lengths and budgets — every request's token stream must equal the
per-request sequential oracle's (`run_sequential`) BIT-exactly under greedy
decoding, and exactly under seeded sampling (keys fold (rid, token index),
so the draw is scheduling-invariant by construction).

Also pinned here: the slot cache API invariants (write/evict touch exactly
one row), chunked-prefill == whole-prefill numerics, the slot-sharded
shard_map step, the optical (rosa) serving path with a pinned fabricated
chip, and per-request energy attribution through ledger scopes.
"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_cross_program_close

from repro.configs import get_smoke
from repro.models import transformer as T
from repro.models.model import (build_model, evict_slot, pad_cache,
                                read_slot, write_slot)
from repro.serve import (Request, Scheduler, ServeConfig, energy_metrics,
                         poisson_requests, run_sequential,
                         serving_model_config)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _requests(cfg, seed=1, n=6, prompt=(3, 10), gen=(2, 8), stagger=True):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab, int(rng.integers(*prompt))),
                    int(rng.integers(*gen)),
                    arrival=(i if stagger else 0))
            for i in range(n)]


@pytest.fixture(scope="module")
def smoke_cfg():
    return get_smoke("qwen3-32b")


@pytest.fixture(scope="module")
def sched(smoke_cfg):
    """Shared scheduler: 2 slots so 6 requests force eviction + refill."""
    scfg = ServeConfig(n_slots=2, max_len=24, prefill_chunk=4,
                       collect_logits=True)
    return Scheduler(smoke_cfg, scfg)


# ---------------------------------------------------------------------------
# The differential core
# ---------------------------------------------------------------------------
def _assert_streams_equal(rep, ref, logits=True):
    for rid, r in ref.items():
        comp = rep.completions[rid]
        assert comp.tokens == r["tokens"], (
            f"rid {rid}: {comp.tokens} != {r['tokens']}")
        if logits:
            assert len(comp.logits) == len(r["logits"])
            for a, b in zip(comp.logits, r["logits"]):
                assert_cross_program_close(a, b)


def test_greedy_differential_staggered(smoke_cfg, sched):
    """Continuous batching == sequential, bit-exact logits, with staggered
    arrivals and mid-stream eviction/refill (6 requests through 2 slots)."""
    reqs = _requests(smoke_cfg)
    rep = sched.run(reqs, policy="continuous")
    ref = run_sequential(smoke_cfg, sched.scfg, sched.params, reqs)
    _assert_streams_equal(rep, ref)
    # eviction/refill actually happened: more admissions than slots
    slots = [c.slot for c in rep.completions.values() if c.slot >= 0]
    assert len(slots) > sched.scfg.n_slots
    assert len(set(slots)) <= sched.scfg.n_slots


def test_sampled_differential_seeded(smoke_cfg, sched):
    """Seeded sampling: keys fold (rid, token index), so the continuous
    stream equals the sequential one EXACTLY, not just in distribution."""
    reqs = _requests(smoke_cfg, seed=2)
    rep = sched.run(reqs, policy="continuous", temperature=0.8)
    ref = run_sequential(smoke_cfg, sched.scfg, sched.params, reqs,
                         temperature=0.8)
    _assert_streams_equal(rep, ref, logits=False)
    # sampling actually deviates from greedy somewhere
    greedy = run_sequential(smoke_cfg, sched.scfg, sched.params, reqs)
    assert any(greedy[r.rid]["tokens"] != ref[r.rid]["tokens"]
               for r in reqs)


def test_scheduling_invariance(smoke_cfg, sched):
    """A request's stream must not depend on arrival pattern or batch
    composition: all-at-once vs staggered give identical tokens."""
    reqs_a = _requests(smoke_cfg, seed=3, stagger=True)
    reqs_b = [dataclasses.replace(r, arrival=0) for r in reqs_a]
    rep_a = sched.run(reqs_a, policy="continuous")
    rep_b = sched.run(reqs_b, policy="continuous")
    for r in reqs_a:
        assert rep_a.completions[r.rid].tokens == \
            rep_b.completions[r.rid].tokens


def test_oneshot_matches_sequential_and_loses_throughput(smoke_cfg, sched):
    """The static-batching baseline is CORRECT (same streams) but pays for
    stragglers: ragged budgets waste its slots."""
    reqs = _requests(smoke_cfg, seed=4, n=8, gen=(2, 12))
    ones = sched.run(reqs, policy="oneshot")
    ref = run_sequential(smoke_cfg, sched.scfg, sched.params, reqs)
    _assert_streams_equal(ones, ref)
    cont = sched.run(reqs, policy="continuous")
    assert cont.tokens_per_unit > ones.tokens_per_unit


def test_evict_on_done_policy(smoke_cfg):
    """Paranoid eviction (zero freed slots) must not change any stream."""
    scfg = ServeConfig(n_slots=2, max_len=24, prefill_chunk=4,
                       evict_on_done=True)
    sched = Scheduler(smoke_cfg, scfg)
    reqs = _requests(smoke_cfg, seed=5)
    rep = sched.run(reqs, policy="continuous")
    ref = run_sequential(smoke_cfg, scfg, sched.params, reqs)
    for r in reqs:
        assert rep.completions[r.rid].tokens == ref[r.rid]["tokens"]


def test_ssm_family_differential():
    """ssm caches (conv + SSD state) admit no positional chunking: the
    whole-prompt prefill path must still serve bit-exactly."""
    cfg = get_smoke("mamba2-1.3b")
    scfg = ServeConfig(n_slots=2, max_len=24, prefill_chunk=4)
    sched = Scheduler(cfg, scfg)
    reqs = _requests(cfg, seed=6, n=4)
    rep = sched.run(reqs, policy="continuous")
    ref = run_sequential(cfg, scfg, sched.params, reqs)
    for r in reqs:
        assert rep.completions[r.rid].tokens == ref[r.rid]["tokens"]


def test_windowed_family_differential():
    """gemma-style sliding-window layers under ragged slot positions."""
    cfg = get_smoke("gemma3-12b")
    scfg = ServeConfig(n_slots=2, max_len=24, prefill_chunk=4)
    sched = Scheduler(cfg, scfg)
    reqs = _requests(cfg, seed=7, n=4)
    rep = sched.run(reqs, policy="continuous")
    ref = run_sequential(cfg, scfg, sched.params, reqs)
    for r in reqs:
        assert rep.completions[r.rid].tokens == ref[r.rid]["tokens"]


def test_rosa_differential_with_pinned_chip(smoke_cfg):
    """Optical serving: hybrid plan + pinned StaticVariation chip.  Needs
    act_per_vector quantization — a request's numerics must not depend on
    its batch neighbours (per-tensor scales would couple rows)."""
    scfg = ServeConfig(n_slots=2, max_len=24, prefill_chunk=4, rosa=True,
                       variation_seed=7)
    sched = Scheduler(smoke_cfg, scfg)
    reqs = _requests(smoke_cfg, seed=8, n=4)
    rep = sched.run(reqs, policy="continuous")
    ref = run_sequential(smoke_cfg, scfg, sched.params, reqs,
                         engine=sched.engine)
    for r in reqs:
        assert rep.completions[r.rid].tokens == ref[r.rid]["tokens"]
    assert sched.engine.variation is not None
    assert len(sched.engine.ledger.events) > 0


# ---------------------------------------------------------------------------
# Chunked prefill
# ---------------------------------------------------------------------------
def test_chunked_prefill_matches_whole(smoke_cfg):
    """chunk_step streaming == one-shot prefill, bit-exact with an f32
    cache (bf16 caches differ only by the cast of cross-chunk K/V reads)."""
    cfg = dataclasses.replace(serving_model_config(smoke_cfg),
                              cache_dtype=jnp.float32)
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    max_len, C, L = 24, 4, 11
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, L), 0,
                                cfg.vocab, dtype=jnp.int32)
    logits_w, cache_w = jax.jit(bundle.prefill)(
        params, {"tokens": prompt})
    cache_w = pad_cache(cfg, cache_w, max_len - L)

    cache = T.init_cache(cfg, 1, max_len)
    step = jax.jit(bundle.chunk_step)
    off = 0
    while off < L:
        n = min(C, L - off)
        chunk = jnp.pad(prompt[:, off:off + n], ((0, 0), (0, C - n)))
        logits_c, cache = step(params, {"tokens": chunk,
                                        "n_valid": jnp.full((1,), n,
                                                            jnp.int32),
                                        "cache": cache})
        off += n
    assert int(cache["pos"][0]) == L
    assert_cross_program_close(logits_w, logits_c)
    k_w = np.asarray(cache_w["layers"][0][:, :, :L])
    k_c = np.asarray(cache["layers"][0][:, :, :L])
    assert_cross_program_close(k_w, k_c)


def test_chunk_step_rejects_ssm():
    cfg = get_smoke("mamba2-1.3b")
    bundle = build_model(cfg)
    with pytest.raises(ValueError, match="chunked prefill"):
        bundle.chunk_step(None, {})


# ---------------------------------------------------------------------------
# Slot cache API
# ---------------------------------------------------------------------------
def test_slot_write_evict_roundtrip(smoke_cfg):
    cfg = serving_model_config(smoke_cfg)
    rng = jax.random.PRNGKey(0)
    big = T.init_cache(cfg, 3, 16)
    big = jax.tree.map(
        lambda a: jax.random.normal(rng, a.shape).astype(a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, big)
    req = T.init_cache(cfg, 1, 16)
    req = jax.tree.map(
        lambda a: (jax.random.normal(jax.random.PRNGKey(1),
                                     a.shape) + 1).astype(a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a + 7, req)

    out = jax.jit(lambda b, r, s: write_slot(cfg, b, r, s))(big, req, 1)
    back = read_slot(cfg, out, 1)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(req)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # untouched rows are byte-identical
    for s in (0, 2):
        for a, b in zip(jax.tree.leaves(read_slot(cfg, out, s)),
                        jax.tree.leaves(read_slot(cfg, big, s))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # invalid write is a no-op
    noop = jax.jit(lambda b, r, s: write_slot(cfg, b, r, s, False))(
        big, req, 1)
    for a, b in zip(jax.tree.leaves(noop), jax.tree.leaves(big)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # eviction zeroes exactly one row
    ev = jax.jit(lambda b, s: evict_slot(cfg, b, s))(out, 1)
    assert all(float(jnp.abs(a).sum()) == 0.0
               for a in jax.tree.leaves(read_slot(cfg, ev, 1)))
    for a, b in zip(jax.tree.leaves(read_slot(cfg, ev, 0)),
                    jax.tree.leaves(read_slot(cfg, out, 0))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_request_validation(smoke_cfg, sched):
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(0, np.zeros(4, np.int32), 0)
    too_long = Request(0, np.zeros(20, np.int32), 10)
    with pytest.raises(ValueError, match="max_len"):
        sched.run([too_long])
    # prompt == max_len must be rejected UPFRONT (same bound as
    # PrefillTask), not crash mid-stream at the prefill stage
    edge = Request(0, np.zeros(sched.scfg.max_len, np.int32), 1)
    with pytest.raises(ValueError, match="no decode room"):
        sched.run([edge])


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_act_per_vector_decouples_rows(backend):
    """EVERY optical backend must honor act_per_vector: a row's result is
    identical whether it shares the batch with an outlier or not (the
    pallas kernel runs in interpret mode on CPU)."""
    from repro import rosa

    cfg = rosa.RosaConfig(backend=backend, act_per_vector=True)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (3, 16))
    w = jax.random.normal(k2, (16, 8))
    outlier = jnp.concatenate([x, 100.0 * jnp.ones((1, 16))], 0)
    y_alone = rosa.rosa_matmul(x, w, cfg)
    y_shared = rosa.rosa_matmul(outlier, w, cfg)[:3]
    np.testing.assert_array_equal(np.asarray(y_alone),
                                  np.asarray(y_shared))


def test_loadgen_deterministic(smoke_cfg):
    a = poisson_requests(8, 0.7, vocab=smoke_cfg.vocab, seed=3)
    b = poisson_requests(8, 0.7, vocab=smoke_cfg.vocab, seed=3)
    for x, y in zip(a, b):
        assert x.arrival == y.arrival and x.max_new_tokens == y.max_new_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)
    assert all(a[i].arrival <= a[i + 1].arrival for i in range(7))
    c = poisson_requests(8, 0.7, vocab=smoke_cfg.vocab, seed=4)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c))


def test_empty_report_percentiles_are_typed_sentinels():
    """Percentiles over zero completions return the falsy `EmptyStat`
    sentinel (NaN via float()) instead of a silent bare NaN — short drift
    scenarios legitimately slice reports down to empty sets."""
    import math

    from repro.serve import EmptyStat, ServeReport

    rep = ServeReport(policy="continuous", completions={}, n_slots=2)
    for stat in (rep.percentile(99), rep.wall_percentile_ms(50, "ttft")):
        assert isinstance(stat, EmptyStat)
        assert not stat                          # falsy: `or default` works
        assert math.isnan(float(stat))           # legacy float() sites
    assert rep.percentile(99).q == 99
    assert rep.wall_percentile_ms(50, "ttft").kind == "ttft"


def test_report_metrics_surface(smoke_cfg, sched):
    """The bench-schema metric view of a run: gated metrics are the
    deterministic (step-unit / tick) ones; wall-clock never gates."""
    from repro.serve import report_metrics

    reqs = _requests(smoke_cfg, seed=9, n=3)
    rep = sched.run(reqs, policy="continuous")
    ms = {m.name: m for m in report_metrics(rep)}
    assert ms["total_tokens"].value == sum(r.max_new_tokens for r in reqs)
    assert ms["tokens_per_unit"].gate and ms["latency_p99_ticks"].gate
    assert not ms["tokens_per_s"].gate and not ms["wall_s"].gate
    assert 0 < ms["occupancy"].value <= 1.0
    assert rep.percentile(50) <= rep.percentile(99)


# ---------------------------------------------------------------------------
# Energy attribution
# ---------------------------------------------------------------------------
def test_energy_attribution(smoke_cfg):
    scfg = ServeConfig(n_slots=4, max_len=24, prefill_chunk=4)
    ms = {m.name: m for m in energy_metrics(smoke_cfg, scfg)}
    assert ms["energy_per_token_j"].value > 0
    assert ms["energy_per_token_j"].gate
    # the hybrid plan can only improve on pure WS
    assert 0 < ms["decode_edp_hybrid_vs_ws"].value <= 1.0 + 1e-12
    assert ms["energy_per_prefill_chunk_j"].value > 0


def test_ledger_scopes(smoke_cfg):
    """Prefill and decode traces attribute to distinct scopes on ONE
    ledger, so per-request energy = prompt chunks + tokens x decode."""
    from repro.serve.metrics import build_serving_engine, \
        trace_serving_shapes

    scfg = ServeConfig(n_slots=2, max_len=24, prefill_chunk=4)
    bundle = build_model(serving_model_config(smoke_cfg, rosa=True))
    engine = build_serving_engine(bundle, scfg)
    ledger = trace_serving_shapes(bundle, scfg, engine)
    tags = {ev.tag for ev in ledger.events}
    assert tags == {"decode", "prefill"}
    from repro.core.constants import ROSA_OPTIMAL
    e_dec = ledger.breakdown(ROSA_OPTIMAL, batch=1, tag="decode").energy
    e_pre = ledger.breakdown(ROSA_OPTIMAL, batch=1, tag="prefill").energy
    e_all = ledger.breakdown(ROSA_OPTIMAL, batch=1).energy
    assert e_dec > 0 and e_pre > 0 and e_all > 0
    # the trace already carries the slot batch in m: per_token prices it
    # as-is and only DIVIDES by the slot count (no double-batching)
    assert ledger.per_token(ROSA_OPTIMAL, batch=2) == \
        pytest.approx(e_dec / 2)


def test_runtime_ledger_is_tagged(smoke_cfg):
    """The scheduler's own run-time ledger must attribute events to
    prefill/decode scopes — otherwise per_token (tag='decode') prices an
    empty set and reports 0."""
    from repro.core.constants import ROSA_OPTIMAL

    scfg = ServeConfig(n_slots=2, max_len=24, prefill_chunk=4, rosa=True)
    sched = Scheduler(smoke_cfg, scfg)
    reqs = _requests(smoke_cfg, seed=11, n=2)
    sched.run(reqs, policy="continuous")
    tags = {ev.tag for ev in sched.engine.ledger.events}
    assert "decode" in tags and "prefill" in tags
    assert sched.engine.ledger.per_token(ROSA_OPTIMAL,
                                         batch=scfg.n_slots) > 0


def test_encdec_serving_rejected():
    cfg = get_smoke("seamless-m4t-medium")
    with pytest.raises(NotImplementedError, match="encoder-decoder"):
        Scheduler(cfg, ServeConfig(n_slots=2, max_len=24))


# ---------------------------------------------------------------------------
# Slot-axis sharding (shard_map) — needs >1 device, so subprocess
# ---------------------------------------------------------------------------
_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import numpy as np, jax
from repro.configs import get_smoke
from repro.launch.mesh import make_mesh
from repro.serve import ServeConfig, Scheduler, Request, run_sequential

cfg = get_smoke("qwen3-32b")
scfg = ServeConfig(n_slots=4, max_len=24, prefill_chunk=4)
mesh = make_mesh((2,), ("data",))
rng = np.random.default_rng(3)
rs = [Request(i, rng.integers(0, cfg.vocab, int(rng.integers(3, 10))),
              int(rng.integers(2, 8)), arrival=i) for i in range(6)]
sched = Scheduler(cfg, scfg, mesh=mesh)
rep = sched.run(rs, policy="continuous")
ref = run_sequential(cfg, scfg, sched.params, rs)
assert all(rep.completions[r.rid].tokens == ref[r.rid]["tokens"]
           for r in rs), "sharded streams diverged"
print("OK")
"""


def test_sharded_serve_step_matches_oracle():
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# Donation canaries: the compiled HLO must actually alias the state
# ---------------------------------------------------------------------------
def _abstract_serve_parts(cfg, scfg):
    from repro.serve import decode as D
    bundle = build_model(cfg)
    params = bundle.abstract(jnp.float32)
    state = jax.eval_shape(lambda: D.init_state(cfg, scfg))
    admit = jax.eval_shape(lambda: D.null_admit(cfg, scfg))
    return bundle, params, state, admit


def test_serve_step_donation_canary(smoke_cfg):
    """Pin: every buffer of the donated DecodeState comes back as a real
    `input_output_alias` in the compiled serve step — a regression here
    means the hot loop silently double-buffers the KV cache."""
    from repro.analysis import AnalysisTarget, run_checks
    from repro.serve import decode as D

    scfg = ServeConfig(n_slots=2, max_len=24, prefill_chunk=4)
    bundle, params, state, admit = _abstract_serve_parts(smoke_cfg, scfg)
    temp = jax.ShapeDtypeStruct((), jnp.float32)
    step = D.make_serve_step(bundle, scfg)
    t = AnalysisTarget("canary:serve_step", step,
                       (params, state, admit, temp),
                       donate_argnums=(1,), hot_path=True)
    assert list(run_checks([t], checks=["donation"])) == []


def test_admit_and_evict_donation_canary(smoke_cfg):
    from repro.analysis import AnalysisTarget, run_checks
    from repro.serve import decode as D

    scfg = ServeConfig(n_slots=2, max_len=24, prefill_chunk=4)
    bundle, _, state, admit = _abstract_serve_parts(smoke_cfg, scfg)
    slot = jax.ShapeDtypeStruct((), jnp.int32)
    ts = [AnalysisTarget("canary:admit", D.make_admit_step(bundle, scfg),
                         (state, admit), donate_argnums=(0,),
                         hot_path=True),
          AnalysisTarget("canary:evict", D.make_evict(bundle, scfg),
                         (state, slot), donate_argnums=(0,),
                         hot_path=True)]
    assert list(run_checks(ts, checks=["donation"])) == []


def test_serve_step_alias_map_nonempty(smoke_cfg):
    """Raw-HLO pin (independent of the analysis machinery): the serve
    step's module text carries one alias per DecodeState array leaf."""
    from repro.analysis.hlo import parse_input_output_aliases
    from repro.serve import decode as D

    scfg = ServeConfig(n_slots=2, max_len=24, prefill_chunk=4)
    bundle, params, state, admit = _abstract_serve_parts(smoke_cfg, scfg)
    temp = jax.ShapeDtypeStruct((), jnp.float32)
    step = D.make_serve_step(bundle, scfg)
    txt = step.lower(params, state, admit, temp).compile().as_text()
    n_state_leaves = len(jax.tree.leaves(state))
    assert len(parse_input_output_aliases(txt)) >= n_state_leaves
