"""Continuous-batching scheduler: slot admission, prefill/decode interleave.

One scheduler drives two admission policies over the SAME jitted step:

  continuous  a completed request's slot is refilled on the very next tick
              (eviction + refill ride inside the decode step), so the
              decode batch stays full whenever work is queued;
  oneshot     the static-batching baseline `launch/serve.py` used to be:
              wait until a full batch of prefilled requests is ready,
              admit them together, decode until the LAST one finishes,
              only then form the next batch.

Each tick runs at most one prefill chunk and one decode step, so cost is
countable in deterministic step units — `ServeReport` exposes those
(decode_steps, prefill_chunks, ticks) next to wall-clock times, and the
`serve_smoke` bench gates on the unit-based throughput ratio, which is
reproducible across machines.

The per-request oracle `run_sequential` (same prefill path, batch-1 decode,
same sampling keys) is what the differential suite pins the scheduler
against: greedy tokens AND logits must match bit-exactly, seeded sampling
must draw identical tokens.
"""

from __future__ import annotations

import contextlib
import dataclasses
import heapq
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.model import build_model
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs
from repro.serve.config import ServeConfig, serving_model_config
from repro.serve.decode import (PrefillTask, init_state, make_admit,
                                make_admit_step, make_chunk_fn, make_evict,
                                make_prefill_fn, make_serve_step, null_admit,
                                prepare_step_params, sample_token,
                                slot_state_specs)


@dataclasses.dataclass
class Request:
    """One serving request; `arrival` is in scheduler ticks."""

    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival: int = 0

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")


@dataclasses.dataclass
class Completion:
    rid: int
    prompt_len: int
    arrival: int
    tokens: list = dataclasses.field(default_factory=list)
    logits: list = dataclasses.field(default_factory=list)
    first_token_tick: int = -1
    admit_tick: int = -1
    done_tick: int = -1
    slot: int = -1
    # wall-clock lifecycle stamps (perf_counter seconds relative to the
    # run's t0) — recorded unconditionally; tick counters above remain the
    # deterministic, machine-independent latency unit
    enqueue_wall: float = 0.0
    admit_wall: float = 0.0
    first_token_wall: float = 0.0
    done_wall: float = 0.0

    @property
    def ttft_ticks(self) -> int:
        return self.first_token_tick - self.arrival

    @property
    def latency_ticks(self) -> int:
        return self.done_tick - self.arrival

    @property
    def ttft_s(self) -> float:
        """Wall-clock time to first token (enqueue → prefill finished)."""
        return self.first_token_wall - self.enqueue_wall

    @property
    def latency_s(self) -> float:
        """Wall-clock end-to-end latency (enqueue → last token)."""
        return self.done_wall - self.enqueue_wall


@dataclasses.dataclass(frozen=True)
class EmptyStat:
    """Typed sentinel for a percentile over an EMPTY completion set.

    Short drift scenarios can slice a report down to zero completions
    (e.g. "requests finished before the first probe window"), where
    `np.percentile` would silently return NaN and poison downstream
    arithmetic.  The sentinel is falsy and still floats to NaN, so legacy
    `float(rep.percentile(...))` call sites keep working while callers
    that care can `isinstance`-check instead of testing `math.isnan`.
    """

    q: float
    kind: str

    def __float__(self) -> float:
        return float("nan")

    def __bool__(self) -> bool:
        return False


@dataclasses.dataclass
class ServeReport:
    policy: str
    completions: dict
    ticks: int = 0
    decode_steps: int = 0
    prefill_chunks: int = 0
    wall_s: float = 0.0
    n_slots: int = 1

    @property
    def total_tokens(self) -> int:
        return sum(len(c.tokens) for c in self.completions.values())

    @property
    def step_units(self) -> int:
        """Deterministic cost: every decode step and prefill chunk is one
        unit of accelerator work."""
        return self.decode_steps + self.prefill_chunks

    @property
    def tokens_per_unit(self) -> float:
        """Useful generated tokens per unit of work — the gated,
        machine-independent throughput metric."""
        return self.total_tokens / max(self.step_units, 1)

    @property
    def occupancy(self) -> float:
        """Mean fraction of decode-batch slots doing useful work (each
        request's FIRST token comes from its prefill, not a decode step,
        so it is excluded)."""
        decoded = self.total_tokens - sum(
            1 for c in self.completions.values() if c.tokens)
        return decoded / max(self.decode_steps * self.n_slots, 1)

    @property
    def tokens_per_s(self) -> float:
        return self.total_tokens / max(self.wall_s, 1e-9)

    def latencies(self, kind: str = "latency") -> np.ndarray:
        vals = [getattr(c, f"{kind}_ticks")
                for c in self.completions.values()]
        return np.asarray(sorted(vals), np.float64)

    def percentile(self, q: float, kind: str = "latency"):
        vals = self.latencies(kind)
        if vals.size == 0:
            return EmptyStat(q, kind)
        return float(np.percentile(vals, q))

    def wall_latencies(self, kind: str = "latency") -> np.ndarray:
        """Per-request wall-clock latencies [s]; kind is latency|ttft."""
        vals = [getattr(c, f"{kind}_s") for c in self.completions.values()]
        return np.asarray(sorted(vals), np.float64)

    def wall_percentile_ms(self, q: float, kind: str = "latency"):
        """q-th percentile of the wall-clock latencies, in ms."""
        vals = self.wall_latencies(kind)
        if vals.size == 0:
            return EmptyStat(q, kind)
        return float(np.percentile(vals, q) * 1e3)


class TickHook:
    """Protocol for per-tick scheduler extensions (drift injection and the
    adaptive controller live in `repro.serve.adaptive`).

    `step_args(tick)` returns extra TRACED positional args appended to the
    decode-step call — the installed `Scheduler.step` must accept them
    (the adaptive package installs a drift-aware step that takes the
    residual thermal offset as a traced scalar, so per-tick drift never
    retraces).  `on_tick_end` runs on the host between ticks, after the
    tick's decode completed — the one place a controller may swap the
    serving program/steps without perturbing an in-flight step.  Ticks
    that make no progress (idle-jump to the next arrival) skip both.
    """

    def step_args(self, tick: int) -> tuple:
        return ()

    def on_tick_end(self, sched: "Scheduler", tick: int, state,
                    idle_slots: int) -> None:
        pass


class Scheduler:
    """Builds the jitted serving machinery once; `run` replays a request
    list under a policy.  With `scfg.rosa` the decode step is compiled
    into ONE `rosa.Program` (hybrid plan autotuned on the decode trace,
    disk plan cache, pinned chip, energy ledger) and every jitted step —
    decode, admit, prefill chunk, whole prefill, evict — is built from it,
    so the frozen engine reaches each trace without a global stack.

    `params` keeps the weights as given, in the model's layout; the steps
    take `step_params`, made from them once whenever `params` is set
    (`prepare_step_params`: the routed MLP weights in the form the
    optical engine consumes)."""

    def __init__(self, model_cfg, scfg: ServeConfig, params=None,
                 init_seed: int = 0, mesh=None, engine=None,
                 plan_cache=None):
        self.cfg = serving_model_config(model_cfg, rosa=scfg.rosa)
        self.scfg = scfg
        self.bundle = build_model(self.cfg)
        self.engine = engine
        self.program = None
        if scfg.rosa and engine is None:
            from repro import rosa
            from repro.serve.metrics import build_serving_program
            prog = build_serving_program(self.bundle, scfg,
                                         cache=plan_cache)
            self.program = prog.with_ledger(rosa.EnergyLedger())
            self.engine = self.program.engine
        elif engine is not None:
            self.program = serving_program(self.bundle, scfg, engine)
        # with a mesh, params (and so the step params) are replicated on
        # every device and the slot state is sharded over it up front, so
        # no step reshards them
        self.state_sharding = self._everywhere = None
        with self._engine_ctx():
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                self._everywhere = NamedSharding(mesh, P())
                self.params = (
                    jax.device_put(params, self._everywhere)
                    if params is not None
                    else jax.jit(self.bundle.init,
                                 out_shardings=self._everywhere)(
                        jax.random.PRNGKey(init_seed)))
                self.state_sharding = jax.tree.map(
                    lambda s: NamedSharding(mesh, s),
                    slot_state_specs(self.bundle, scfg, mesh))
            else:
                self.params = (params if params is not None
                               else self.bundle.init(
                                   jax.random.PRNGKey(init_seed)))
        self.step = make_serve_step(self.bundle, scfg, mesh=mesh,
                                    program=self.program)
        self.admit_step = make_admit_step(self.bundle, scfg,
                                          program=self.program)
        self.chunk_fn = make_chunk_fn(self.bundle, program=self.program,
                                      mesh=mesh)
        self.whole_fn = make_prefill_fn(self.bundle, program=self.program,
                                        mesh=mesh)
        self.evict = make_evict(self.bundle, scfg, program=self.program) \
            if scfg.evict_on_done else None
        self.null = null_admit(self.cfg, scfg)
        self.sample1 = jax.jit(sample_token)
        self.base_key = jax.random.PRNGKey(scfg.seed)

    @property
    def params(self):
        """The served weights, in the model's own layout."""
        return self._params

    @params.setter
    def params(self, params) -> None:
        self._params = step = params
        if params is not None and self.engine is not None \
                and not self.engine.is_dense:
            step = prepare_step_params(self.cfg, params)
            if step is not params and self._everywhere is not None:
                step = jax.device_put(step, self._everywhere)
        self.step_params = step

    def _engine_ctx(self):
        """Ambient context for the few non-jitted call sites (param init);
        every jitted step already carries the engine via `Program.bind`."""
        if self.engine is None:
            return contextlib.nullcontext()
        from repro import rosa
        return rosa.engine_context(self.engine)

    def _scope(self, tag: str):
        """Ledger attribution scope around a jitted call site: only the
        first (tracing) call records, so scoping every tick is free."""
        return _ledger_scope(self.engine, tag)

    def _check(self, req: Request) -> None:
        """Fail FAST, before any request is served: these bounds mirror
        PrefillTask's (prompt < max_len) exactly, so a bad request can
        never abort the loop mid-stream after others completed."""
        if len(req.prompt) >= self.scfg.max_len:
            raise ValueError(
                f"request {req.rid}: prompt length {len(req.prompt)} >= "
                f"max_len {self.scfg.max_len}: no decode room")
        need = len(req.prompt) + req.max_new_tokens - 1
        if need > self.scfg.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + "
                f"{req.max_new_tokens} new tokens needs cache {need} > "
                f"max_len {self.scfg.max_len}")

    # -- the serving loop ---------------------------------------------------
    def run(self, requests: list[Request], policy: str = "continuous",
            temperature: float | None = None,
            hook: TickHook | None = None) -> ServeReport:
        """`temperature` overrides scfg.temperature — it is a TRACED scalar,
        so greedy and sampled runs share one compiled step.  `hook` is a
        `TickHook`: extra traced decode-step args + an end-of-tick host
        callback (see the protocol docstring)."""
        if policy not in ("continuous", "oneshot"):
            raise ValueError(policy)
        for r in requests:
            self._check(r)
        scfg = self.scfg
        n_slots = scfg.n_slots
        temp = jnp.float32(scfg.temperature if temperature is None
                           else temperature)

        completions = {r.rid: Completion(r.rid, len(r.prompt), r.arrival)
                       for r in requests}
        pending = deque(sorted(requests, key=lambda r: (r.arrival, r.rid)))
        prefill_q: deque[Request] = deque()
        ready: deque[tuple] = deque()        # (req, cache, first_token)
        inflight: tuple | None = None        # (req, PrefillTask)
        free = list(range(n_slots))
        heapq.heapify(free)
        slot_rid: list[int | None] = [None] * n_slots
        n_done = 0
        state = init_state(self.cfg, scfg)
        if self.state_sharding is not None:
            state = jax.device_put(state, self.state_sharding)
        rep = ServeReport(policy=policy, completions=completions,
                          n_slots=n_slots)
        tick = 0
        # tracing is ambient and fixed for the run: resolve it once, keep
        # the disabled path at one None check per emission site, and hoist
        # every registry lookup out of the tick loop
        tr = obs.current_tracer()
        reg = obs_metrics.registry()
        c_completed = reg.counter("serve.requests_completed")
        c_evicted = reg.counter("serve.evictions")
        g_depth = reg.gauge("serve.queue_depth")
        g_active = reg.gauge("serve.slots_active")
        last_depth = last_active = -1
        # spans reach the profiler's host line whether or not `tr` is set
        # (then the Chrome sink too); the argument-free ones are built once
        # and re-entered, each use making one fresh TraceMe
        prefill_ctx = obs.span_on(tr, "serve.prefill.dispatch", "serve")
        decode_ctx = obs.span_on(tr, "serve.decode.dispatch", "serve")
        pull_ctx = obs.span_on(tr, "serve.decode.pull", "serve")
        # rid -> open `serve.request.queued` annotation (profiler only):
        # enqueue until the request leaves `prefill_q`, across ticks
        queued: dict[int, TraceAnnotation] = {}
        etrack = None
        if tr is not None and self.engine is not None \
                and self.engine.ledger is not None:
            from repro.obs.energy import EnergyTrack
            etrack = EnergyTrack(self.engine.ledger)
        t0 = time.perf_counter()

        def finish(comp: Completion) -> None:
            comp.done_tick = tick
            comp.done_wall = time.perf_counter() - t0
            c_completed.inc()
            if tr is not None:
                tr.async_end("request", comp.rid, cat="request",
                             tokens=len(comp.tokens))

        with self._engine_ctx(), _closing(queued):
            while n_done < len(requests):
                with obs.span_on(tr, "serve.tick", "serve", tick=tick):
                    progressed = False
                    while pending and pending[0].arrival <= tick:
                        r = pending.popleft()
                        completions[r.rid].enqueue_wall = \
                            time.perf_counter() - t0
                        q = queued[r.rid] = TraceAnnotation(
                            "serve.request.queued", rid=r.rid)
                        q.__enter__()
                        if tr is not None:
                            tr.async_begin("request", r.rid, cat="request",
                                           prompt_len=len(r.prompt))
                        prefill_q.append(r)

                    # -- one prefill chunk per tick -----------------------
                    if inflight is None and prefill_q:
                        req = prefill_q.popleft()
                        inflight = (req, PrefillTask(self.bundle, scfg,
                                                     req.prompt,
                                                     self.chunk_fn,
                                                     self.whole_fn))
                        queued.pop(req.rid).__exit__(None, None, None)
                    if inflight is not None:
                        req, task = inflight
                        with prefill_ctx, self._scope("prefill"):
                            task.advance(self.step_params)
                        if etrack is not None:
                            etrack.tick("prefill")
                        rep.prefill_chunks += 1
                        progressed = True
                        if task.done:
                            comp = completions[req.rid]
                            # the host waits here for the prompt's last chunk
                            with obs.span_on(tr, "serve.prefill.first_token",
                                             "serve", rid=req.rid):
                                tok0 = self.sample1(self.base_key, req.rid,
                                                    0, task.logits, temp)
                                comp.tokens.append(int(tok0))
                            comp.first_token_tick = tick
                            comp.first_token_wall = \
                                time.perf_counter() - t0
                            if tr is not None:
                                tr.async_instant("first_token", req.rid,
                                                 cat="request")
                            if scfg.collect_logits:
                                comp.logits.append(np.asarray(task.logits))
                            if req.max_new_tokens == 1:  # done at prefill
                                finish(comp)
                                n_done += 1
                            else:
                                ready.append((req, task.cache, tok0))
                            inflight = None

                    # -- admission ---------------------------------------
                    admit = self.null
                    if policy == "continuous":
                        # refill rides inside the decode step: one per tick
                        if ready and free:
                            slot = heapq.heappop(free)
                            req, cache0, tok0 = ready.popleft()
                            admit = make_admit(cache0, slot, req.rid, tok0,
                                               req.max_new_tokens)
                            slot_rid[slot] = req.rid
                            self._mark_admit(completions[req.rid], slot,
                                             tick, t0, tr)
                    else:
                        # oneshot: once the batch is idle and a full batch
                        # (or everything that's left) is prefilled, admit
                        # it in one burst, then decode until it drains
                        outstanding = (len(pending) + len(prefill_q)
                                       + len(ready)
                                       + (1 if inflight is not None else 0))
                        if (len(free) == n_slots and ready
                                and (len(ready) >= min(n_slots, outstanding)
                                     or (not pending and not prefill_q
                                         and inflight is None))):
                            while ready and free:
                                slot = heapq.heappop(free)
                                req, cache0, tok0 = ready.popleft()
                                state = self.admit_step(
                                    state,
                                    make_admit(cache0, slot, req.rid, tok0,
                                               req.max_new_tokens))
                                slot_rid[slot] = req.rid
                                self._mark_admit(completions[req.rid],
                                                 slot, tick, t0, tr)
                            progressed = True

                    # -- one decode step for the whole batch -------------
                    if any(r is not None for r in slot_rid):
                        extra = hook.step_args(tick) if hook is not None \
                            else ()
                        with decode_ctx, self._scope("decode"):
                            state, out = self.step(self.step_params, state,
                                                   admit, temp, *extra)
                        if etrack is not None:
                            etrack.tick("decode")
                        rep.decode_steps += 1
                        progressed = True
                        with pull_ctx:      # the host waits for the step
                            tok = np.asarray(out["token"])
                            emitted = np.asarray(out["emitted"])
                            done = np.asarray(out["done"])
                            logits = (np.asarray(out["logits"])
                                      if scfg.collect_logits else None)
                        for s in range(n_slots):
                            if not emitted[s]:
                                continue
                            comp = completions[slot_rid[s]]
                            comp.tokens.append(int(tok[s]))
                            if logits is not None:
                                comp.logits.append(logits[s])
                            if done[s]:
                                finish(comp)
                                n_done += 1
                                slot_rid[s] = None
                                heapq.heappush(free, s)
                                if self.evict is not None:
                                    c_evicted.inc()
                                    state = self.evict(state, jnp.int32(s))

                    if tr is not None:
                        # counters sample on change only: Perfetto renders
                        # steps, and a flat line is pure per-tick overhead
                        depth = (len(pending) + len(prefill_q) + len(ready)
                                 + (1 if inflight is not None else 0))
                        active = sum(1 for r in slot_rid if r is not None)
                        if depth != last_depth:
                            last_depth = depth
                            tr.counter("serve.queue_depth", depth)
                            g_depth.set(depth)
                        if active != last_active:
                            last_active = active
                            tr.counter("serve.slots_active", active)
                            g_active.set(active)

                    if not progressed:
                        if pending:                 # idle: jump to arrival
                            tick = pending[0].arrival
                            continue
                        raise RuntimeError(
                            "scheduler deadlock")   # pragma: no cover
                    if hook is not None:
                        hook.on_tick_end(self, tick, state, len(free))
                    tick += 1

        rep.ticks = tick
        rep.wall_s = time.perf_counter() - t0
        return rep

    @staticmethod
    def _mark_admit(comp: Completion, slot: int, tick: int, t0: float,
                    tr) -> None:
        """Stamp one request's admission (tick, wall, slot, trace)."""
        comp.admit_tick = tick
        comp.slot = slot
        comp.admit_wall = time.perf_counter() - t0
        if tr is not None:
            tr.async_instant("admit", comp.rid, cat="request", slot=slot)


@contextlib.contextmanager
def _closing(annotations: dict):
    """Close the annotations still open in `annotations` when the block
    exits, also by an exception."""
    try:
        yield
    finally:
        for ann in annotations.values():
            ann.__exit__(None, None, None)
        annotations.clear()


def serving_program(bundle, scfg: ServeConfig, engine):
    """Freeze an explicitly-supplied engine into a `rosa.Program` (no plan
    autotune — the caller's plan is taken as-is) so the serving machinery
    can build its jitted steps from it."""
    import jax.numpy as jnp

    from repro import rosa
    from repro.serve.metrics import _abstract_decode_batch

    params = bundle.abstract(jnp.float32)
    batch = _abstract_decode_batch(bundle.cfg, scfg)
    # compile with the ledger detached: the runtime serving ledger must
    # carry ONLY the scoped prefill/decode events the scheduler's step
    # traces record, never untagged compile-time duplicates
    prog = rosa.compile(lambda eng, p, b: bundle.decode_step(p, b),
                        engine.with_ledger(None), (params, batch),
                        autotune=None)
    return prog.with_engine(engine)


def _ledger_scope(engine, tag: str):
    if engine is not None and engine.ledger is not None:
        return engine.ledger.scope(tag)
    return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# Per-request sequential oracle (the differential-test reference)
# ---------------------------------------------------------------------------
def run_sequential(model_cfg, scfg: ServeConfig, params,
                   requests: list[Request], engine=None,
                   temperature: float | None = None) -> dict:
    """Decode every request ALONE (batch 1), same prefill path, same
    sampling keys.  Returns {rid: {"tokens": [...], "logits": [...]}}.

    This is the semantic spec of serving: whatever the continuous scheduler
    interleaves, each request's stream must equal this oracle's exactly."""
    cfg = serving_model_config(model_cfg, rosa=scfg.rosa)
    bundle = build_model(cfg)
    ctx = contextlib.nullcontext()
    program = None
    if scfg.rosa and engine is None:
        from repro import rosa
        from repro.serve.metrics import build_serving_program
        # reuse the ONE autotuned Program instead of compiling twice
        program = build_serving_program(bundle, scfg) \
            .with_ledger(rosa.EnergyLedger())
        engine = program.engine
    elif engine is not None:
        program = serving_program(bundle, scfg, engine)
    if engine is not None:
        from repro import rosa
        ctx = rosa.engine_context(engine)
    chunk_fn = make_chunk_fn(bundle, program=program)
    whole_fn = make_prefill_fn(bundle, program=program)
    decode1_fn = lambda p, t, c: bundle.decode_step(
        p, {"token": t, "pos": c["pos"], "cache": c})
    decode1 = (program.bind(decode1_fn) if program is not None
               else jax.jit(decode1_fn))
    sample1 = jax.jit(sample_token)
    base = jax.random.PRNGKey(scfg.seed)
    temp = jnp.float32(scfg.temperature if temperature is None
                       else temperature)

    out = {}
    with ctx:
        for req in requests:
            task = PrefillTask(bundle, scfg, req.prompt, chunk_fn, whole_fn)
            with _ledger_scope(engine, "prefill"):
                while not task.advance(params):
                    pass
            tok = sample1(base, req.rid, 0, task.logits, temp)
            toks, logs = [int(tok)], [np.asarray(task.logits)]
            cache = task.cache
            for i in range(1, req.max_new_tokens):
                with _ledger_scope(engine, "decode"):
                    logits, cache = decode1(params, tok.reshape(1), cache)
                tok = sample1(base, req.rid, i, logits[0], temp)
                toks.append(int(tok))
                logs.append(np.asarray(logits[0]))
            out[req.rid] = {"tokens": toks, "logits": logs}
    return out
