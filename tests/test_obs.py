"""repro.obs: tracer, metrics registry, energy bridge, CLI, integrations."""

import io
import itertools
import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.obs import cli as obs_cli


def _fake_clock():
    t = itertools.count()
    return lambda: next(t) * 1e-3       # 1 ms per call


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------
def test_chrome_trace_valid_json_and_nesting_on_raise(tmp_path):
    tr = obs.Tracer()
    with obs.tracing(tr):
        with obs.span("outer", cat="stage"):
            with obs.span("inner"):
                pass
            with pytest.raises(ValueError):
                with obs.span("boom"):
                    raise ValueError("body failed")
    path = tmp_path / "t.json"
    tr.save(path)
    doc = json.loads(path.read_text())            # valid JSON end to end
    evs = doc["traceEvents"]
    by_name = {e["name"]: e for e in evs if e.get("ph") == "X"}
    assert set(by_name) == {"outer", "inner", "boom"}
    # the raising span is bounded and annotated
    assert by_name["boom"]["dur"] >= 0
    assert by_name["boom"]["args"]["error"] == "ValueError"
    # nesting by time containment: both children inside outer's window
    o = by_name["outer"]
    for child in ("inner", "boom"):
        c = by_name[child]
        assert o["ts"] <= c["ts"]
        assert c["ts"] + c["dur"] <= o["ts"] + o["dur"] + 1e-6
    assert doc["displayTimeUnit"] == "ms"


def test_disabled_path_adds_zero_events():
    tr = obs.Tracer()
    n0 = len(tr)
    assert not obs.enabled()
    with obs.span("nope", cat="x"):
        obs.instant("nothing")
        obs.counter("c", 1)
        obs.async_begin("r", 1)
        obs.async_end("r", 1)
    assert len(tr) == n0 == 0
    # disabled, a span is one profiler annotation, fresh per call (a
    # TraceMe is never reused)
    from jax.profiler import TraceAnnotation
    a, b = obs.span("a"), obs.span("b")
    assert isinstance(a, TraceAnnotation) and isinstance(b, TraceAnnotation)
    assert a is not b


def test_tracing_none_disables_under_outer_tracer():
    outer = obs.Tracer()
    with obs.tracing(outer):
        with obs.span("kept"):
            pass
        with obs.tracing(None):
            assert not obs.enabled()
            with obs.span("dropped"):
                pass
        with obs.span("kept2"):
            pass
    names = {e["name"] for e in outer.events}
    assert "kept" in names and "kept2" in names
    assert "dropped" not in names


def test_traced_decorator_and_exception():
    tr = obs.Tracer()

    @obs.traced(cat="fn")
    def work(x):
        if x < 0:
            raise RuntimeError("neg")
        return x + 1

    with obs.tracing(tr):
        assert work(1) == 2
        with pytest.raises(RuntimeError):
            work(-1)
    spans = [e for e in tr.events if e.get("ph") == "X"]
    assert len(spans) == 2
    assert all(s["name"].endswith("work") for s in spans)
    assert spans[1]["args"]["error"] == "RuntimeError"


def test_tracer_thread_safety():
    tr = obs.Tracer()

    def worker(i):
        with obs.tracing(tr):           # ContextVar: per-thread install
            for j in range(50):
                with obs.span(f"w{i}"):
                    obs.counter("c", j)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events
    assert sum(1 for e in evs if e.get("ph") == "X") == 200
    assert sum(1 for e in evs if e.get("ph") == "C") == 200
    # one thread_name metadata record per distinct tid (the OS may reuse
    # idents for non-overlapping threads, so <= 4 but never duplicated)
    metas = [e for e in evs if e.get("ph") == "M"]
    tids = {e["tid"] for e in evs if e.get("ph") == "X"}
    assert len(metas) == len(tids) <= 4
    json.dumps(tr.to_chrome())          # still serializable


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------
def test_registry_thread_safety():
    reg = obs.MetricsRegistry()

    def worker():
        for _ in range(500):
            reg.counter("hits").inc()
            reg.gauge("depth").add(1)
            reg.histogram("lat").observe(0.01)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("hits").value == 4000
    assert reg.gauge("depth").value == 4000
    assert reg.histogram("lat").count == 4000


def test_registry_exports():
    reg = obs.MetricsRegistry()
    reg.counter("rosa.plancache_hits", help="plan IO").inc(3)
    reg.gauge("serve.queue_depth").set(2)
    h = reg.histogram("tick_s", bounds=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    # bench-schema rows: ungated runtime observations
    rows = {m.name: m for m in reg.to_metrics(prefix="p_")}
    assert rows["p_rosa.plancache_hits"].value == 3
    assert not rows["p_rosa.plancache_hits"].gate
    assert rows["p_tick_s_count"].value == 3
    text = reg.to_prometheus()
    assert "# TYPE rosa_plancache_hits counter" in text
    assert "rosa_plancache_hits 3" in text
    assert 'tick_s_bucket{le="+Inf"} 3' in text
    assert "tick_s_count 3" in text
    # histogram stats
    assert h.min == 0.05 and h.max == 5.0
    assert h.percentile(50) == 1.0      # upper edge of the median bucket
    # type mismatch on an existing name is an error, not silent
    with pytest.raises(TypeError):
        reg.gauge("rosa.plancache_hits")


def test_histogram_bounded_memory():
    h = obs.Histogram("h", bounds=(1.0, 2.0))
    for i in range(10_000):
        h.observe(i % 7)
    assert len(h.snapshot()["buckets"]) == 3    # 2 bounds + overflow
    assert h.count == 10_000


# ---------------------------------------------------------------------------
# CLI golden
# ---------------------------------------------------------------------------
def test_cli_summary_golden(tmp_path):
    tr = obs.Tracer(clock=_fake_clock())
    tr._pid = 1          # pin pid for byte-stable output paths
    with tr.span("compile", cat="stage"):
        with tr.span("search"):
            pass
    tr.async_begin("request", 7, cat="request", prompt_len=3)
    tr.async_instant("first_token", 7, cat="request")
    tr.async_end("request", 7, cat="request", tokens=5)
    tr.counter("energy.decode", {"J": 0.25}, cat="energy")
    path = tmp_path / "golden.json"
    tr.save(path)

    buf = io.StringIO()
    obs_cli.summarize(str(path), top=5, out=buf)
    assert buf.getvalue() == (
        "trace: 7 events (2 spans)\n"
        "\n"
        "top 2 spans by self-time (ms):\n"
        "        self      total  count  name\n"
        "       2.000      3.000      1  compile\n"
        "       1.000      1.000      1  search\n"
        "\n"
        "requests:\n"
        "        id    ttft_ms     e2e_ms  args\n"
        "         7      1.000      2.000  tokens=5\n"
        "\n"
        "counters (final values):\n"
        "  energy.decode: J=0.25\n"
    )


def test_cli_main_runs(tmp_path, capsys):
    tr = obs.Tracer()
    with tr.span("a"):
        pass
    p = tmp_path / "t.json"
    tr.save(p)
    assert obs_cli.main(["summarize", str(p)]) == 0
    out = capsys.readouterr().out
    assert "top 1 spans" in out and "  a" in out


# ---------------------------------------------------------------------------
# Energy bridge
# ---------------------------------------------------------------------------
def test_energy_track_cumulative_counters():
    import jax
    import jax.numpy as jnp

    from repro import rosa

    ledger = rosa.EnergyLedger()
    engine = rosa.Engine.from_config(
        rosa.RosaConfig(), layers=["l0"], key=jax.random.PRNGKey(0),
        ledger=ledger)
    with ledger.scope("decode"):
        jax.eval_shape(
            lambda x: engine.matmul(x, jnp.zeros((8, 4)), name="l0"),
            jnp.zeros((2, 8)))
    tr = obs.Tracer()
    with obs.tracing(tr):
        et = obs.EnergyTrack(ledger)
        et.tick("decode")
        et.tick("decode", n=2)
        et.tick("prefill")              # never traced: no event, no crash
    evs = [e for e in tr.events if e.get("ph") == "C"]
    assert [e["name"] for e in evs] == ["energy.decode", "energy.decode"]
    j1, j3 = evs[0]["args"]["J"], evs[1]["args"]["J"]
    assert j1 > 0 and np.isclose(j3, 3 * j1)    # cumulative, linear in n
    assert np.isclose(et.total_j(), j3)
    # disabled -> no accumulation, no emission
    et2 = obs.EnergyTrack(ledger)
    et2.tick("decode")
    assert et2.total_j() == 0.0


# ---------------------------------------------------------------------------
# Ledger seq satellite
# ---------------------------------------------------------------------------
def test_ledger_seq_monotonic_and_exported():
    import jax
    import jax.numpy as jnp

    from repro import rosa
    from repro.core.constants import ROSA_OPTIMAL

    ledger = rosa.EnergyLedger()
    engine = rosa.Engine.from_config(
        rosa.RosaConfig(), layers=["a", "b"], key=jax.random.PRNGKey(0),
        ledger=ledger)

    def fwd(x):
        y = engine.matmul(x, jnp.zeros((8, 8)), name="a")
        return engine.matmul(y, jnp.zeros((8, 4)), name="b")

    jax.eval_shape(fwd, jnp.zeros((2, 8)))
    seqs = [ev.seq for ev in ledger.events]
    assert len(seqs) == 2
    assert seqs[1] > seqs[0] >= 0       # stamped, strictly increasing
    export = ledger.export(ROSA_OPTIMAL)
    assert [e["seq"] for e in export["events"]] == seqs
    # dedup ignores seq: re-tracing the same layer keeps one event
    jax.eval_shape(fwd, jnp.zeros((2, 8)))
    assert len(ledger.unique_events()) == 2


# ---------------------------------------------------------------------------
# rosa.compile + scheduler integrations
# ---------------------------------------------------------------------------
def test_compile_spans_and_plancache_counters(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro import rosa
    from repro.models.cnn import LITE_MODELS, LITE_SKIPS, cnn_apply, cnn_def
    from repro.models.module import abstract_params
    from repro.training.cnn_train import QAT_CFG

    specs = LITE_MODELS["alexnet"]
    engine = rosa.Engine.from_config(QAT_CFG)

    def apply_fn(eng, params, x):
        return cnn_apply(params, specs, x, eng,
                         residual_from=LITE_SKIPS.get("alexnet"))

    skel = abstract_params(cnn_def(specs), dtype=jnp.float32)
    x = jax.ShapeDtypeStruct((4, 32, 32, 3), jnp.float32)
    tune = rosa.AutotuneConfig(batch=4)

    reg = obs.MetricsRegistry()
    tr = obs.Tracer()
    with obs.swap_registry(reg), obs.tracing(tr):
        cold = rosa.compile(apply_fn, engine, (skel, x), autotune=tune,
                            cache=tmp_path)
        warm = rosa.compile(apply_fn, engine, (skel, x), autotune=tune,
                            cache=tmp_path)
    assert cold.searched and warm.cache_hit
    names = [e["name"] for e in tr.events if e.get("ph") == "X"]
    # cold: capture -> search -> store -> freeze; warm: capture -> load
    assert names.count("rosa.compile") == 2
    assert names.count("rosa.capture_trace") == 2
    assert names.count("rosa.plan_search") == 1
    assert names.count("plancache.store") == 1
    assert names.count("plancache.load") == 2
    assert names.count("rosa.freeze") == 2
    assert reg.counter("rosa.plancache_misses").value == 1
    assert reg.counter("rosa.plancache_hits").value == 1


def test_scheduler_trace_and_wall_metrics():
    from repro.configs import get_smoke
    from repro.serve import (Scheduler, ServeConfig, poisson_requests,
                             report_metrics)

    cfg = get_smoke("qwen3-32b")
    scfg = ServeConfig(n_slots=2, max_len=32, prefill_chunk=8, seed=0)
    sched = Scheduler(cfg, scfg, init_seed=0)
    reqs = poisson_requests(4, 1.0, vocab=cfg.vocab, prompt_len=(4, 8),
                            gen_len=(2, 6), seed=0)

    reg = obs.MetricsRegistry()
    tr = obs.Tracer()
    with obs.swap_registry(reg), obs.tracing(tr):
        rep = sched.run(reqs)

    # spans from the tick loop: one dispatch per chunk and per decode step,
    # one pull per decode step, one first-token wait per request
    spans = [e for e in tr.events if e.get("ph") == "X"]
    count = {n: sum(1 for e in spans if e["name"] == n)
             for n in ("serve.tick", "serve.prefill.dispatch",
                       "serve.prefill.first_token", "serve.decode.dispatch",
                       "serve.decode.pull")}
    # a loop pass runs at most one chunk and one decode step
    assert count["serve.tick"] >= max(rep.prefill_chunks,
                                      rep.decode_steps) > 0
    assert count["serve.prefill.dispatch"] == rep.prefill_chunks > 0
    assert count["serve.decode.dispatch"] == rep.decode_steps > 0
    assert count["serve.decode.pull"] == rep.decode_steps
    assert count["serve.prefill.first_token"] == len(reqs)
    # request lifecycle: one b/e pair per request + instants
    begins = [e for e in tr.events if e.get("ph") == "b"]
    ends = [e for e in tr.events if e.get("ph") == "e"]
    assert len(begins) == len(ends) == len(reqs)
    firsts = [e for e in tr.events
              if e.get("ph") == "n" and e["name"] == "first_token"]
    assert len(firsts) == len(reqs)
    # counter tracks sampled every tick
    track_names = {e["name"] for e in tr.events if e.get("ph") == "C"}
    assert {"serve.queue_depth", "serve.slots_active"} <= track_names
    assert reg.counter("serve.requests_completed").value == len(reqs)

    # wall-clock stamps: ordered per request, surfaced as metrics
    for c in rep.completions.values():
        assert (c.enqueue_wall <= c.first_token_wall <= c.done_wall)
        assert c.ttft_s >= 0 and c.latency_s >= c.ttft_s
    names = {m.name: m for m in report_metrics(rep)}
    assert names["ttft_p50_ms"].value >= 0
    assert names["latency_p99_ms"].value > 0
    assert not names["ttft_p50_ms"].gate        # wall clock never gates
    assert not names["latency_p99_ms"].gate
    # tick percentiles unchanged by instrumentation
    assert names["latency_p50_ticks"].gate


def test_scheduler_untraced_report_identical():
    """Tracing must not change scheduling, tokens, or gated metrics."""
    from repro.configs import get_smoke
    from repro.serve import (Scheduler, ServeConfig, poisson_requests,
                             report_metrics)

    cfg = get_smoke("qwen3-32b")
    scfg = ServeConfig(n_slots=2, max_len=32, prefill_chunk=8, seed=0)
    sched = Scheduler(cfg, scfg, init_seed=0)
    reqs = poisson_requests(4, 1.0, vocab=cfg.vocab, prompt_len=(4, 8),
                            gen_len=(2, 6), seed=0)
    with obs.tracing(None):
        rep_off = sched.run(reqs)
    with obs.tracing(obs.Tracer()):
        rep_on = sched.run(reqs)
    for rid in rep_off.completions:
        assert rep_off.completions[rid].tokens \
            == rep_on.completions[rid].tokens
    gated_off = {m.name: m.value for m in report_metrics(rep_off) if m.gate}
    gated_on = {m.name: m.value for m in report_metrics(rep_on) if m.gate}
    assert gated_off == gated_on


# ---------------------------------------------------------------------------
# Spans on the profiler's clock
# ---------------------------------------------------------------------------
def _profiled(fn, logdir):
    """`fn()` under the JAX profiler inside the benchmark's window
    annotation -> (its result, [(name, start_ns, dur_ns, stats)] of the
    host events)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))[-1]
    events = [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    return out, events


def _smoke_scheduler():
    from repro.configs import get_smoke
    from repro.serve import Scheduler, ServeConfig

    cfg = get_smoke("qwen3-32b")
    scfg = ServeConfig(n_slots=2, max_len=32, prefill_chunk=8, seed=0)
    return cfg, Scheduler(cfg, scfg, init_seed=0)


@pytest.fixture(scope="module")
def profiled_run(tmp_path_factory):
    """The smoke scheduler once with no profiler and once under it, with
    no obs tracer installed (the benchmark's set-up)."""
    from repro.serve import poisson_requests

    cfg, sched = _smoke_scheduler()
    reqs = poisson_requests(6, 1.0, vocab=cfg.vocab, prompt_len=(4, 20),
                            gen_len=(2, 6), seed=0)
    assert not obs.enabled()
    rep_plain = sched.run(reqs)
    rep, events = _profiled(lambda: sched.run(reqs),
                            tmp_path_factory.mktemp("xplane"))
    return reqs, rep, rep_plain, events


def _named(events, name):
    return [e for e in events if e[0] == name]


@pytest.mark.parametrize("span,count", [
    ("serve.decode.pull", "decode_steps"),
    ("serve.decode.dispatch", "decode_steps"),
    ("serve.prefill.dispatch", "prefill_chunks"),
])
def test_profiler_step_spans_one_per_step(profiled_run, span, count):
    _, rep, _, events = profiled_run
    assert len(_named(events, span)) == getattr(rep, count) > 0


def test_profiler_request_queued_one_per_request(profiled_run):
    reqs, _, _, events = profiled_run
    queued = _named(events, "serve.request.queued")
    assert sorted(e[3]["rid"] for e in queued) == sorted(r.rid for r in reqs)
    assert all(e[2] > 0 for e in queued)        # none of zero length


def test_profiler_first_token_one_per_request(profiled_run):
    reqs, _, _, events = profiled_run
    firsts = _named(events, "serve.prefill.first_token")
    assert sorted(e[3]["rid"] for e in firsts) == sorted(r.rid for r in reqs)


def test_profiler_tick_spans_hold_the_steps(profiled_run):
    _, rep, _, events = profiled_run
    ticks = sorted(_named(events, "serve.tick"), key=lambda e: e[1])
    assert len(ticks) >= max(rep.prefill_chunks, rep.decode_steps) > 0
    nums = [e[3]["tick"] for e in ticks]
    assert nums == sorted(set(nums))            # one span per loop pass
    window = _named(events, "chipbench.window")[0]
    for name in ("serve.decode.pull", "serve.decode.dispatch",
                 "serve.prefill.dispatch", "serve.prefill.first_token"):
        for _, s, d, _ in _named(events, name):
            assert any(ts <= s and s + d <= ts + td
                       for _, ts, td, _ in ticks), name
    assert all(window[1] <= s and s + d <= window[1] + window[2]
               for _, s, d, _ in ticks)


def test_profiler_tokens_identical(profiled_run):
    _, rep, rep_plain, _ = profiled_run
    assert rep.decode_steps == rep_plain.decode_steps
    assert rep.prefill_chunks == rep_plain.prefill_chunks
    for rid, c in rep_plain.completions.items():
        assert rep.completions[rid].tokens == c.tokens


def test_profiler_queued_spans_closed_when_run_raises(tmp_path):
    """A request still queued when `run` raises has its span closed."""
    from repro.serve import Request, TickHook

    cfg, sched = _smoke_scheduler()
    reqs = [Request(rid=i, prompt=np.arange(5 + i) % cfg.vocab,
                    max_new_tokens=3, arrival=0) for i in range(3)]

    class Boom(TickHook):
        def on_tick_end(self, sched, tick, state, idle_slots):
            raise RuntimeError("boom")

    def run():
        with pytest.raises(RuntimeError, match="boom"):
            sched.run(reqs, hook=Boom())

    _, events = _profiled(run, tmp_path)
    queued = _named(events, "serve.request.queued")
    assert sorted(e[3]["rid"] for e in queued) == [0, 1, 2]


def test_span_helpers_reach_profiler_in_both_modes(tmp_path):
    """Disabled, `obs.span` is a profiler annotation with its args; with a
    tracer installed both sinks record the same name."""
    tr = obs.Tracer()

    def run():
        with obs.span("obs.off", "t", rid=7):
            pass
        with obs.tracing(tr):
            with obs.span("obs.on", "t"):
                pass
        ctx = obs.span_on(None, "obs.reused", "t")
        for _ in range(3):
            with ctx:
                pass

    _, events = _profiled(run, tmp_path)
    assert [e[3] for e in _named(events, "obs.off")] == [{"rid": 7}]
    assert len(_named(events, "obs.on")) == 1
    assert len(_named(events, "obs.reused")) == 3
    assert [e["name"] for e in tr.events if e.get("ph") == "X"] == ["obs.on"]


# ---------------------------------------------------------------------------
# jax.monitoring hooks
# ---------------------------------------------------------------------------
def test_jax_hooks_count_retraces():
    import jax
    import jax.numpy as jnp

    assert obs.install_jax_hooks()
    assert obs.install_jax_hooks()      # idempotent
    reg = obs.MetricsRegistry()
    with obs.swap_registry(reg):
        @jax.jit
        def f(x):
            return x * 2

        f(jnp.ones(3)).block_until_ready()
    assert reg.counter("xla.retraces").value >= 1
    assert reg.histogram("xla.trace_s").count >= 1
