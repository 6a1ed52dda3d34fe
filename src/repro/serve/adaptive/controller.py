"""The closed loop: drift step, HEALTHY→DEGRADED→RETRIM→REPLAN controller.

`make_drift_step` is the plant model: the scheduler's decode step with the
thermal residual as ONE extra traced scalar — per-tick drift re-dispatches
the same executable (the chip's `StaticVariation` is a pytree, so the
shifted leaves flow straight through the engine).

`AdaptiveController` is a `serve.TickHook`.  Per tick it feeds the
residual into the decode step (`step_args`) and, between ticks
(`on_tick_end`), folds a temperature-sensor reading into the detector,
probes on idle slots, and acts:

  HEALTHY   probes agree with the golden reference; no action
  DEGRADED  CUSUM fired: apply `trim_voltages` at the predicted
            temperature (an actuator write — the programmed voltages
            absorb the estimated offset, leaving only tracking error as
            residual) and ENGAGE the thermal servo: from here on the trim
            follows the alpha-beta prediction every tick (within a
            deadband), because a drift that fired once keeps moving and a
            probe-cadence trim goes stale between windows
  RETRIM    servo engaged, validating: back to HEALTHY once probes
            re-enter the slack band (servo stays engaged — hysteresis is
            for the state machine, not the actuator); REPLAN if agreement
            stays below the guard floor even with a fresh trim
  REPLAN    re-measure the degradation matrix at the live residual, store
            it in the `PlanCache`, re-run the accuracy-aware plan search,
            and swap the serving `Program` double-buffered: the new decode
            step is compiled and warmed BEFORE the pointer swap, which
            happens between ticks — in-flight KV slots carry over
            untouched and no request is ever dropped or perturbed.

`DriftMonitor` is the uncontrolled arm of the A/B: same drift injection,
same probe cadence, no actions — the bench baseline.
"""

from __future__ import annotations

import dataclasses
import enum
import time

import jax
import jax.numpy as jnp

from repro.core.constants import ROSA_OPTIMAL
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs
from repro.robust import variation as V
from repro.rosa.engine import engine_context
from repro.serve.adaptive.probes import ProbeConfig, ProbeSet
from repro.serve.adaptive.detector import DetectorConfig, DriftDetector
from repro.serve.decode import (_step_body, make_admit_step, make_chunk_fn,
                                make_evict, make_prefill_fn)
from repro.serve.scheduler import TickHook, _ledger_scope


def make_drift_step(bundle, scfg, program):
    """The serving decode step with a traced thermal residual [K].

    Signature: `step(params, state, admit, temperature, resid_k)` — drop-in
    for `Scheduler.step` when a `TickHook.step_args` supplies the trailing
    scalar.  The engine context is installed inside the traced body (same
    trick as `Program.bind`), so the shifted chip is re-derived from the
    traced residual and nothing retraces tick-to-tick."""
    engine = program.engine
    chip = dict(engine.variation or {})

    def step(params, state, admit, temperature, resid_k):
        eng = engine
        if chip:
            eng = engine.with_variation(V.shift_thermal(chip, resid_k))
        with engine_context(eng):
            return _step_body(bundle, scfg, params, state, admit,
                              temperature, jnp.zeros((), jnp.int32))

    return jax.jit(step, donate_argnums=(1,))


class ControllerState(enum.IntEnum):
    """Gauge-friendly controller states (`serve.adaptive.state`)."""

    HEALTHY = 0
    DEGRADED = 1
    RETRIM = 2
    REPLAN = 3


@dataclasses.dataclass(frozen=True)
class ControllerConfig:
    """Closed-loop policy knobs."""

    probe_every: int = 4        # ticks between probe attempts
    starve_factor: int = 4      # probe anyway after this many skipped
    #                             windows with no idle slot (never go blind)
    warmup_ticks: int = 4       # no probes before this tick: lets the
    #                             temperature filter settle and keeps an
    #                             epoch of bit-exact pre-action traffic
    guard_agreement: float = 0.60   # post-retrim floor: below this a
    #                                 FRESH trim did not save us -> REPLAN
    trim_slack_k: float = 0.08      # REPLAN only once the applied trim
    #                                 already matches the temperature
    #                                 estimate this closely — a stale trim
    #                                 means re-trim, not re-plan
    trim_deadband_k: float = 0.005   # servo writes the trim only when the
    #                                 prediction moved this far (skip
    #                                 actuator churn inside sensor noise)
    allow_replan: bool = True
    force_replan_at: int | None = None   # deterministic swap trigger
    #                                      (bench pins swap metrics on it)


class DriftMonitor(TickHook):
    """Uncontrolled arm: inject drift, probe, record — never act.

    Owns everything the A/B must share with the controller: the drift
    step installation, the probe cadence and the telemetry series, so the
    two arms differ ONLY in the corrective actions."""

    def __init__(self, sched, env, probes: ProbeSet | None = None,
                 cfg: ControllerConfig = ControllerConfig()):
        if sched.program is None:
            raise ValueError("adaptive serving needs scfg.rosa=True "
                             "(the scheduler must carry a rosa.Program)")
        self.env = env
        self.cfg = cfg
        self.probes = probes if probes is not None \
            else ProbeSet(sched.bundle, sched.program)
        # idempotent install: the A/B harness runs two hooks over ONE
        # scheduler, and both arms must share the same compiled step
        if getattr(sched, "_drift_program", None) is not sched.program:
            sched.step = make_drift_step(sched.bundle, sched.scfg,
                                         sched.program)
            sched._drift_program = sched.program
        self.trim_k = 0.0
        self.first_action_tick = 10 ** 9    # no action yet
        # drift-free reference: the health bar every probe is scored
        # against (also compiles the shared evaluator, before traffic)
        self.ref_agreement = self.probes.agreement(sched.params, 0.0)
        self.series: list[dict] = []        # one row per executed probe
        self.tick_wall_s: list[float] = []
        self.retrims = 0
        self.replans = 0
        self.swaps: list[dict] = []
        self._last_probe = -10 ** 9
        self._last_wall: float | None = None

    # -- TickHook protocol --------------------------------------------------
    def step_args(self, tick: int) -> tuple:
        """The plant: physical residual = true drift minus applied trim."""
        return (jnp.float32(self.env.residual(tick, self.trim_k)),)

    def on_tick_end(self, sched, tick, state, idle_slots) -> None:
        now = time.perf_counter()
        if self._last_wall is not None:
            self.tick_wall_s.append(now - self._last_wall)
        self._last_wall = now
        if self._probe_due(tick, idle_slots):
            self._last_probe = tick
            resid = self.env.residual(tick, self.trim_k)
            with obs.span("adaptive.probe", "adaptive", tick=tick):
                agree = self.probes.agreement(sched.params, resid,
                                              tick=tick)
            self.series.append({"tick": tick, "resid_k": resid,
                                "agreement": agree,
                                "trim_k": self.trim_k,
                                "energy_per_token_j": _energy(sched)})
            self._after_probe(sched, tick, state, agree)

    # -- shared helpers -----------------------------------------------------
    def _probe_due(self, tick: int, idle_slots: int) -> bool:
        """Piggyback rule: probe on cadence when a decode slot idles;
        starvation override keeps a saturated fleet from going blind."""
        if tick < self.cfg.warmup_ticks:
            return False
        since = tick - self._last_probe
        if since < self.cfg.probe_every:
            return False
        return idle_slots > 0 \
            or since >= self.cfg.probe_every * self.cfg.starve_factor

    def _after_probe(self, sched, tick, state, agreement: float) -> None:
        """Monitor: record only."""

    @property
    def mean_agreement(self) -> float:
        if not self.series:
            return float("nan")
        return sum(r["agreement"] for r in self.series) / len(self.series)


class AdaptiveController(DriftMonitor):
    """The acting arm: detector + state machine + program swap."""

    def __init__(self, sched, env, probes: ProbeSet | None = None,
                 cfg: ControllerConfig = ControllerConfig(),
                 det_cfg: DetectorConfig = DetectorConfig(),
                 plan_cache=None):
        super().__init__(sched, env, probes, cfg)
        self.detector = DriftDetector(det_cfg, self.ref_agreement)
        self.state = ControllerState.HEALTHY
        self.tracking = False     # thermal servo engaged (sticky)
        self.trim_updates = 0     # actuator writes, incl. servo follow-ups
        self.plan_cache = plan_cache
        reg = obs_metrics.registry()
        self._g_state = reg.gauge("serve.adaptive.state")
        self._g_drift = reg.gauge("serve.adaptive.drift_est_k")
        self._c_retrim = reg.counter("serve.adaptive.retrims")
        self._c_replan = reg.counter("serve.adaptive.replans")
        self._g_state.set(int(self.state))

    def on_tick_end(self, sched, tick, state, idle_slots) -> None:
        # sensor readings are cheap: fold one in EVERY tick so the
        # tracking estimate is fresh whenever a probe decides to act on it
        self._g_drift.set(self.detector.observe_temp(self.env.sense(tick)))
        # probe FIRST (scores the trim that actually served this tick),
        # THEN let the servo re-aim the trim at the next tick's predicted
        # temperature — writing first would skew every probe by one tick
        # of drift slope
        super().on_tick_end(sched, tick, state, idle_slots)
        if self.tracking:
            target = self.detector.predict()
            if abs(target - self.trim_k) > self.cfg.trim_deadband_k:
                self._write_trim(target, tick)
        if self.cfg.force_replan_at is not None \
                and tick == self.cfg.force_replan_at and not self.replans:
            self._replan(sched, tick, state)

    def _after_probe(self, sched, tick, state, agreement: float) -> None:
        det = self.detector
        fired = det.update(agreement)
        in_band = (det.ref - agreement) <= det.cfg.cusum_k
        if self.state in (ControllerState.HEALTHY, ControllerState.REPLAN):
            if fired:
                self._transition(ControllerState.DEGRADED, tick)
                self._retrim(tick)
            elif self.state is ControllerState.REPLAN and in_band:
                self._transition(ControllerState.HEALTHY, tick)
        elif self.state is ControllerState.RETRIM:
            trim_fresh = abs(det.predict() - self.trim_k) \
                <= self.cfg.trim_slack_k
            if in_band:
                det.reset()
                self._transition(ControllerState.HEALTHY, tick)
            elif agreement < self.cfg.guard_agreement and trim_fresh \
                    and self.cfg.allow_replan:
                # trimmed at the best available estimate and STILL below
                # guard: thermal compensation is out of ammunition
                self._replan(sched, tick, state)
            # else: the servo is already following the prediction every
            # tick — nothing for the state machine to add

    # -- actions ------------------------------------------------------------
    def _transition(self, to: ControllerState, tick: int) -> None:
        self.state = to
        self._g_state.set(int(to))
        obs.instant(f"adaptive.{to.name.lower()}", cat="adaptive",
                    tick=tick)

    def _write_trim(self, target_k: float, tick: int) -> None:
        """One actuator write: program trim voltages for `target_k`.  By
        the trim identity (`voltage_of_weight(dt_trim=d)` under offset d
        == untrimmed under offset 0; pinned in tests/test_adaptive.py)
        this is exactly `trim_k = target` on the injected residual."""
        self.trim_k = float(target_k)
        self.first_action_tick = min(self.first_action_tick, tick)
        self.trim_updates += 1

    def _retrim(self, tick: int) -> None:
        """Corrective action: trim at the predicted temperature and keep
        the servo engaged — drift that fired once keeps moving, and a
        probe-cadence trim would go stale between windows."""
        self._write_trim(self.detector.predict(), tick)
        self.tracking = True
        self.retrims += 1
        self._c_retrim.inc()
        self.detector.reset()
        self._transition(ControllerState.RETRIM, tick)

    def _replan(self, sched, tick, state) -> None:
        """Measure → search → compile → warm → swap, all between ticks."""
        from repro import rosa

        t0 = time.perf_counter()
        self.first_action_tick = min(self.first_action_tick, tick)
        self._transition(ControllerState.REPLAN, tick)
        resid = self.env.residual(tick, self.trim_k)
        with obs.span("adaptive.replan", "adaptive", tick=tick):
            rows = self.probes.degradation_rows(sched.params, resid,
                                                tick=tick)
            base_cfg = sched.program.engine.plan.default
            store = rosa.PlanCache() if self.plan_cache is None \
                else self.plan_cache
            spec = {"kind": "serve-adaptive",
                    "model": sched.bundle.cfg.name,
                    "n_probes": self.probes.cfg.n_probes,
                    "prompt_len": self.probes.cfg.prompt_len,
                    "seed": self.probes.cfg.seed,
                    "resid_mk": round(resid * 1e3)}
            store.store_matrix(rosa.PlanCache.matrix_key(base_cfg, spec),
                               rows)
            from repro.serve.metrics import _abstract_decode_batch
            bundle, scfg = sched.bundle, sched.scfg
            new_prog = rosa.compile(
                lambda eng, p, b: bundle.decode_step(p, b),
                rosa.Engine.from_config(base_cfg),
                (bundle.abstract(jnp.float32),
                 _abstract_decode_batch(bundle.cfg, scfg)),
                autotune=rosa.AutotuneConfig(ope=ROSA_OPTIMAL, batch=1),
                degradation=rows, cache=store)
            new_prog = new_prog.with_variation(self.probes.chip) \
                .with_ledger(rosa.EnergyLedger())
            # double buffer: build + warm EVERY step against the live
            # state's shapes BEFORE any pointer moves, so the swapped-in
            # program never compiles (or drops a tick) on the serving path
            new_step = make_drift_step(bundle, scfg, new_prog)
            dummy = jax.tree.map(jnp.zeros_like, state)
            with _ledger_scope(new_prog.engine, "decode"):
                warm_out = new_step(sched.params, dummy, sched.null,
                                    jnp.float32(scfg.temperature),
                                    jnp.float32(resid))
            jax.block_until_ready(warm_out[0].tok)
            new_admit = make_admit_step(bundle, scfg, program=new_prog)
            new_chunk = make_chunk_fn(bundle, program=new_prog)
            new_whole = make_prefill_fn(bundle, program=new_prog)
            new_evict = make_evict(bundle, scfg, program=new_prog) \
                if scfg.evict_on_done else None
            # the swap: host-side pointer writes between ticks — in-flight
            # slots (DecodeState) carry over untouched
            sched.program, sched.engine = new_prog, new_prog.engine
            sched.step = new_step
            sched.admit_step = new_admit
            sched.chunk_fn = new_chunk
            sched.whole_fn = new_whole
            sched.evict = new_evict
            sched._drift_program = new_prog
            self.probes.rebind(new_prog)
        self.replans += 1
        self._c_replan.inc()
        self.detector.reset()
        self.swaps.append({"tick": tick, "wall_s": time.perf_counter() - t0,
                           "downtime_ticks": 0,
                           "plan": {n: m.value for n, m in
                                    new_prog.engine.plan.mapping_plan()
                                    .items()}})


def _energy(sched) -> float:
    """Energy per generated token [J] of the CURRENT program's decode
    trace (0.0 until the first decode step traced)."""
    ledger = sched.engine.ledger if sched.engine is not None else None
    if ledger is None:
        return 0.0
    try:
        return float(ledger.per_token(ROSA_OPTIMAL,
                                      batch=sched.scfg.n_slots))
    except (ValueError, ZeroDivisionError):
        return 0.0
