"""`rosa.Program` — compile-once programs with autotuned, disk-cached plans.

The paper's wins come from co-optimizing the array config and the per-layer
IS/WS dataflow against a *whole workload*, so plan decisions belong at
program granularity, not per-matmul.  `rosa.compile` is the one entry
point:

    program = rosa.compile(apply_fn, engine, (params, x))
    y = program(params, x, key=key)

Compilation is three deterministic steps:

  1. **Trace** — `apply_fn` is abstractly evaluated once (`jax.eval_shape`,
     no FLOPs) with a trace-capturing engine installed; every named matmul
     the engine routes is recorded into a `ProgramTrace` (layer name, GEMM
     shape, call count).
  2. **Autotune** — with an `AutotuneConfig`, the layer-wise hybrid IS/WS
     plan is searched over the traced workload: EDP-only through
     `core.mapping.profile_layers_fast`, or accuracy-aware when a
     Monte-Carlo `degradation` matrix (`repro.robust.sensitivity`) is
     supplied.  The searched plan is persisted in a content-addressed
     on-disk `PlanCache` keyed by hash(trace, RosaConfig, search settings),
     so a warm compile loads the plan and skips the search entirely.
  3. **Freeze** — the resolved `ExecutionPlan` is installed on the engine,
     the trace is re-priced onto the engine's `EnergyLedger` (when one is
     attached), and the returned `Program` is a jitted executable with
     explicit `key=` / `variation=` threading and optional donation — no
     global engine stack is involved.

`Program.plan` / `Program.lower()` expose the resolved plan for inspection
and JSON round-trip; `Program.bind(fn)` jit-compiles auxiliary step
functions (a serving scheduler's decode/prefill steps) under the same
frozen engine.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import os
import pathlib
import tempfile
from typing import Any, Callable, Sequence

import jax

from repro.core import energy as E
from repro.core import mapping as M
from repro.core.constants import ComputeMode, OPEConfig, ROSA_OPTIMAL
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs
from repro.rosa.engine import Engine, engine_context
from repro.rosa.ledger import EnergyLedger
from repro.rosa.plan import ExecutionPlan
from repro.rosa.serialize import (canonical_json, config_to_json,
                                  content_hash, ope_from_json,
                                  osa_energy_from_json, to_jsonable)

# apply_fn(engine, *args) -> outputs.  The engine is handed in explicitly
# AND installed as the ambient context around the call, so both explicit-
# engine models (cnn_apply) and ambient-engine models (the transformer
# stacks) compile through the same entry point.
ApplyFn = Callable[..., Any]


# ---------------------------------------------------------------------------
# ProgramTrace — the captured named-matmul workload
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TraceEntry:
    """One distinct routed GEMM: layer name, shape, trace-time call count."""

    name: str
    m: int
    k: int
    n: int
    count: int = 1

    def layer_shape(self) -> E.LayerShape:
        """This entry as an energy-model LayerShape."""
        return E.LayerShape(self.name, m=self.m, k=self.k, n=self.n,
                            kind="gemm")


@dataclasses.dataclass(frozen=True)
class ProgramTrace:
    """The full named-matmul trace of one abstract program evaluation."""

    entries: tuple[TraceEntry, ...] = ()

    @property
    def names(self) -> tuple[str, ...]:
        """Layer names in trace order."""
        return tuple(e.name for e in self.entries)

    def layer_shapes(self) -> list[E.LayerShape]:
        """LayerShapes of every traced entry."""
        return [e.layer_shape() for e in self.entries]

    def __len__(self) -> int:
        return len(self.entries)

    @property
    def fingerprint(self) -> str:
        """Content hash of the trace (one input to the plan-cache key)."""
        return content_hash(self.to_json())

    # -- JSON round-trip -----------------------------------------------------
    def to_json(self) -> dict:
        """JSON-able dict of the trace."""
        return {"entries": [to_jsonable(e) for e in self.entries]}

    @classmethod
    def from_json(cls, doc: dict) -> "ProgramTrace":
        """Inverse of `to_json`."""
        return cls(tuple(TraceEntry(name=e["name"], m=int(e["m"]),
                                    k=int(e["k"]), n=int(e["n"]),
                                    count=int(e["count"]))
                         for e in doc["entries"]))

    @classmethod
    def from_ledger(cls, ledger: EnergyLedger) -> "ProgramTrace":
        """Collapse the raw (non-deduped) event list into counted entries,
        first-seen order preserved.
        """
        counts: dict[tuple, int] = {}
        for ev in ledger.events:
            k = (ev.name, ev.m, ev.k, ev.n)
            counts[k] = counts.get(k, 0) + 1
        return cls(tuple(TraceEntry(name, m, k, n, c)
                         for (name, m, k, n), c in counts.items()))


def capture_trace(apply_fn: ApplyFn, engine: Engine,
                  example_args: Sequence[Any]) -> ProgramTrace:
    """Abstractly trace `apply_fn` once and capture its routed matmuls.

    The capture engine is `engine` with a private recording ledger swapped
    in, installed both as the explicit first argument and as the ambient
    context; `jax.eval_shape` runs no math, so capture cost is one Python
    trace.  Only matmuls the engine actually routes optically (resolved
    config not None) appear — plain dense layers are not plan candidates.
    """
    recorder = EnergyLedger()
    probe = engine.with_ledger(recorder)
    if probe.key is None:
        # shapes are key-independent, but the noisy realization path
        # refuses to trace without one — any key does for an abstract pass
        probe = probe.with_key(jax.random.PRNGKey(0))
    with obs.span("rosa.capture_trace", cat="compile"):
        with engine_context(probe):
            jax.eval_shape(functools.partial(apply_fn, probe),
                           *example_args)
    return ProgramTrace.from_ledger(recorder)


# ---------------------------------------------------------------------------
# Autotune settings
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AutotuneConfig:
    """Workload-aware hybrid-mapping search settings.

    EDP profiling runs on the traced GEMMs through the vectorized energy
    model (`mapping.profile_layers_fast`).  Without a degradation matrix
    the accuracy term is muted and the plan is the per-layer EDP argmin;
    with one (see `repro.robust.sensitivity.degradation_matrix`) the
    balanced metric runs accuracy-aware, and `guard_pp` additionally vetoes
    any per-layer choice that costs more than `guard_pp` percentage points
    over that layer's most robust mapping
    (`sensitivity.accuracy_guarded_plan`).

    ``accuracy_aware`` (the default) lets a supplied degradation matrix or
    `DegradationSource` steer the search; ``accuracy_aware=False`` (the
    `EDP_ONLY` preset) mutes the accuracy term even when one is supplied —
    the search is then the pure per-layer EDP argmin and degradation inputs
    do not enter the cache key.
    """

    ope: OPEConfig = ROSA_OPTIMAL
    batch: int = 1
    mode: ComputeMode = ComputeMode.MIXED
    osa: E.OSAEnergyConfig = E.OSA_OPTIMAL
    guard_pp: float | None = None
    accuracy_aware: bool = True

    def to_json(self) -> dict:
        """Lower to a JSON-native dict (cache-key input)."""
        return to_jsonable(self)

    @classmethod
    def from_json(cls, doc: dict) -> "AutotuneConfig":
        """Invert `to_json` (tolerates pre-schema-2 docs without the flag)."""
        return cls(ope=ope_from_json(doc["ope"]), batch=int(doc["batch"]),
                   mode=ComputeMode(doc["mode"]),
                   osa=osa_energy_from_json(doc["osa"]),
                   guard_pp=doc["guard_pp"],
                   accuracy_aware=bool(doc.get("accuracy_aware", True)))


EDP_ONLY = AutotuneConfig(accuracy_aware=False)


@dataclasses.dataclass(frozen=True)
class DegradationSource:
    """A measure-on-miss provider of Monte-Carlo degradation matrices.

    ``measure(layer_names)`` returns ``{layer: {mapping: pp}}`` for exactly
    the requested layers (the expensive MC stage); ``spec`` is a JSON-able
    identity of everything those numbers depend on — ensemble size/seed,
    noise and variation models, eval-set size, trained-params digest.
    `compile` content-addresses cached matrices in the `PlanCache` by
    (spec, base RosaConfig) and invokes ``measure`` only for layers the
    cache does not already hold, so warm compiles skip the MC stage
    entirely and trace growth re-scores only the new layers.  See
    `repro.robust.sensitivity.cnn_degradation_source` for the canonical
    constructor.
    """

    measure: Callable[[Sequence[str]], dict]
    spec: Any


# ---------------------------------------------------------------------------
# Content-addressed on-disk plan cache
# ---------------------------------------------------------------------------
_CACHE_ENV = "ROSA_PLAN_CACHE"
# Part of every cache key AND checked on load: bump it whenever the plan
# SEARCH itself changes meaning (profile_layers_fast semantics, the energy
# model, the balanced metric, this file's search wiring) so stale plans
# searched by older code can never be silently reused.
# 2: AutotuneConfig gained accuracy_aware; degradation matrices joined the
#    cache (ISSUE 7 — shared-forward measurement changed their PRNG draws).
_CACHE_SCHEMA = 2


def default_cache_dir() -> pathlib.Path:
    """Cache root: `$ROSA_PLAN_CACHE` or `~/.cache/rosa-repro/plans`."""
    return pathlib.Path(os.environ.get(
        _CACHE_ENV, "~/.cache/rosa-repro/plans")).expanduser()


class PlanCache:
    """Content-addressed plan store: one JSON file per cache key.

    Keys are sha256 hashes over the canonical JSON of (trace, base
    RosaConfig, autotune settings, degradation matrix), so any change to
    the workload or the search inputs misses the cache and re-searches;
    identical inputs hit and load the identical plan.  Writes are
    atomic-rename so concurrent compiles never observe torn files.

    `max_entries` bounds the store: after every write the oldest-mtime
    entries beyond the bound are unlinked (plan and degradation files
    count alike).  Loads touch their entry's mtime, so eviction is LRU,
    not FIFO — months-long adaptive serving keeps its hot plans while the
    cache stays bounded.  `python -m repro.rosa stats|gc` inspects and
    prunes a store offline.
    """

    def __init__(self, root: str | os.PathLike | None = None,
                 max_entries: int | None = None):
        if max_entries is not None and max_entries < 1:
            raise ValueError("max_entries must be >= 1")
        self.root = pathlib.Path(root) if root is not None \
            else default_cache_dir()
        self.max_entries = max_entries

    def _path(self, key: str) -> pathlib.Path:
        return self.root / f"{key}.json"

    @staticmethod
    def key(trace: ProgramTrace, base_cfg, autotune: AutotuneConfig,
            degradation: dict | None = None) -> str:
        """Content key of a (trace, config, autotune, degradation) plan."""
        return content_hash({
            "schema": _CACHE_SCHEMA,
            "trace": trace.to_json(),
            "config": config_to_json(base_cfg),
            "autotune": autotune.to_json(),
            "degradation": degradation or {},
        })

    def load(self, key: str) -> ExecutionPlan | None:
        """The cached plan under `key`, or None on miss/corruption."""
        path = self._path(key)
        with obs.span("plancache.load", cat="cache", key=key[:12]):
            try:
                doc = json.loads(path.read_text())
                if doc.get("schema") != _CACHE_SCHEMA \
                        or doc.get("key") != key:
                    plan = None
                else:
                    plan = ExecutionPlan.from_json(doc["plan"])
            except (OSError, json.JSONDecodeError, KeyError, TypeError,
                    ValueError):
                # any unreadable/stale/torn entry is a miss, never a crash
                # — the cold path re-searches and overwrites it
                plan = None
        if plan is not None:
            self._touch(path)
        reg = obs_metrics.registry()
        reg.counter("rosa.plancache_hits" if plan is not None
                    else "rosa.plancache_misses").inc()
        return plan

    def store(self, key: str, plan: ExecutionPlan,
              trace: ProgramTrace) -> pathlib.Path:
        """Atomically persist a searched plan under its content key."""
        doc = {"schema": _CACHE_SCHEMA, "key": key, "plan": plan.to_json(),
               "trace_fingerprint": trace.fingerprint}
        with obs.span("plancache.store", cat="cache", key=key[:12]):
            path = self._write(self._path(key), doc)
        self.gc()
        return path

    @staticmethod
    def _touch(path: pathlib.Path) -> None:
        """Bump an entry's mtime on a hit: mtime IS the LRU clock."""
        with contextlib.suppress(OSError):
            os.utime(path)

    def _entries(self) -> list[pathlib.Path]:
        """Every persisted entry (plans AND degradation stores), LRU
        first: eviction order for `gc`, listing order for `stats`."""
        try:
            files = [p for p in self.root.iterdir()
                     if p.suffix == ".json" and p.is_file()]
        except OSError:
            return []
        def mtime(p: pathlib.Path) -> float:
            try:
                return p.stat().st_mtime
            except OSError:       # racing eviction/cleanup: sort last
                return float("inf")
        return sorted(files, key=lambda p: (mtime(p), p.name))

    def gc(self, max_entries: int | None = None) -> int:
        """Evict least-recently-used entries beyond the bound; returns the
        eviction count.  `max_entries=None` uses the instance bound (and
        is a no-op when the instance is unbounded)."""
        bound = self.max_entries if max_entries is None else max_entries
        if bound is None:
            return 0
        if bound < 1:
            raise ValueError("max_entries must be >= 1")
        entries = self._entries()
        evicted = 0
        for path in entries[:max(len(entries) - bound, 0)]:
            with contextlib.suppress(OSError):
                path.unlink()
                evicted += 1
        if evicted:
            obs_metrics.registry().counter(
                "rosa.plancache_evictions").inc(evicted)
        return evicted

    def stats(self) -> dict:
        """JSON-able store summary (the `python -m repro.rosa stats` view)."""
        entries = self._entries()
        plans = [p for p in entries if not p.name.endswith(".deg.json")]
        sizes = []
        for p in entries:
            with contextlib.suppress(OSError):
                sizes.append(p.stat().st_size)
        return {"root": str(self.root),
                "entries": len(entries),
                "plans": len(plans),
                "matrices": len(entries) - len(plans),
                "bytes": sum(sizes),
                "max_entries": self.max_entries,
                "lru": [p.name for p in entries[:3]],
                "mru": [p.name for p in entries[-3:]]}

    def _write(self, path: pathlib.Path, doc: dict) -> pathlib.Path:
        self.root.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except BaseException:
            with contextlib.suppress(FileNotFoundError):
                os.unlink(tmp)
            raise
        return path

    # -- degradation matrices -------------------------------------------------
    # One `<key>.deg.json` per (base RosaConfig, measurement spec): a
    # per-layer accumulator, NOT a single frozen blob.  Entries are keyed
    # by layer name inside, so a grown trace re-measures only its new
    # layers (`DegradationSource`) and every earlier row is reused —
    # the effective key of each row is (layer, RosaConfig, spec).
    @staticmethod
    def matrix_key(base_cfg, spec) -> str:
        """Content key of a degradation-matrix store file."""
        return content_hash({"schema": _CACHE_SCHEMA, "kind": "degradation",
                             "config": config_to_json(base_cfg),
                             "spec": to_jsonable(spec)})

    def _matrix_path(self, key: str) -> pathlib.Path:
        return self.root / f"{key}.deg.json"

    def load_matrix(self, key: str) -> dict | None:
        """The cached `{layer: {mapping: pp}}` rows, or None on any miss."""
        path = self._matrix_path(key)
        try:
            doc = json.loads(path.read_text())
            if doc.get("schema") != _CACHE_SCHEMA or doc.get("key") != key:
                return None
            layers = doc["layers"]
            rows = {str(n): {str(m): float(v) for m, v in row.items()}
                    for n, row in layers.items()}
        except (OSError, json.JSONDecodeError, KeyError, TypeError,
                ValueError, AttributeError):
            return None
        self._touch(path)
        return rows

    def store_matrix(self, key: str, layers: dict) -> pathlib.Path:
        """Atomically persist (or extend) a degradation-matrix store."""
        doc = {"schema": _CACHE_SCHEMA, "key": key, "layers": layers}
        path = self._write(self._matrix_path(key), doc)
        self.gc()
        return path


def _resolve_cache(cache) -> PlanCache | None:
    if cache is False:
        return None
    if cache is None or cache is True:
        return PlanCache()
    if isinstance(cache, PlanCache):
        return cache
    return PlanCache(cache)


def _measured_matrix(src: DegradationSource, trace: ProgramTrace,
                     base_cfg, store: PlanCache | None) -> dict:
    """Degradation rows for the traced layers: cache first, measure the rest.

    Loads whatever rows the PlanCache already holds under
    `PlanCache.matrix_key(base_cfg, src.spec)`, measures ONLY the missing
    layers (the incremental path — a warm cache measures nothing), marks
    layers the source cannot score with an empty row so they are never
    re-attempted, and persists the extended store.
    """
    mkey = PlanCache.matrix_key(base_cfg, src.spec)
    with obs.span("degstore.load", cat="cache", key=mkey[:12]):
        have = (store.load_matrix(mkey) if store is not None else None) \
            or {}
    missing = [n for n in trace.names if n not in have]
    reg = obs_metrics.registry()
    reg.counter("rosa.degstore_layer_hits").inc(
        len(trace.names) - len(missing))
    reg.counter("rosa.degstore_layer_misses").inc(len(missing))
    if missing:
        with obs.span("rosa.degradation_measure", cat="compile",
                      layers=len(missing)):
            have = {**have, **src.measure(missing)}
        for n in missing:
            have.setdefault(n, {})
        if store is not None:
            with obs.span("degstore.store", cat="cache", key=mkey[:12]):
                store.store_matrix(mkey, have)
    return {n: have[n] for n in trace.names if have.get(n)}


# ---------------------------------------------------------------------------
# Program — the frozen executable handle
# ---------------------------------------------------------------------------
class Program:
    """A compiled optical program: frozen engine + jitted apply.

    Call it like the traced function minus the engine argument —
    ``program(*args, key=..., variation=...)`` — with an optional base PRNG
    key (per-layer keys fold inside the engine) and an optional pinned-chip
    `variation` pytree, both threaded explicitly through the jit boundary.
    `donate_argnums` indices refer to ``apply_fn``'s positional args (the
    engine excluded).
    """

    def __init__(self, apply_fn: ApplyFn, engine: Engine,
                 trace: ProgramTrace, *,
                 donate_argnums: Sequence[int] = (),
                 searched: bool = False, cache_hit: bool = False,
                 cache_key: str | None = None):
        self.apply_fn = apply_fn
        self.engine = engine
        self.trace = trace
        self.searched = searched
        self.cache_hit = cache_hit
        self.cache_key = cache_key
        self._donate = tuple(donate_argnums)

        def run(key, variation, *args):
            """Jitted entry: rebind key/variation, then run the forward."""
            eng = engine
            if key is not None:
                eng = eng.with_key(key)
            if variation is not None:
                eng = eng.with_variation(variation)
            with engine_context(eng):
                return apply_fn(eng, *args)

        # key/variation prepend two positions in front of apply_fn's args
        self._call = jax.jit(
            run, donate_argnums=tuple(i + 2 for i in self._donate))

    def __call__(self, *args, key: jax.Array | None = None,
                 variation=None):
        return self._call(key, variation, *args)

    # -- inspection ----------------------------------------------------------
    @property
    def plan(self) -> ExecutionPlan:
        """The resolved per-layer execution plan this program runs."""
        return self.engine.plan

    @property
    def ledger(self) -> EnergyLedger | None:
        """The frozen engine's ledger (None when unattached)."""
        return self.engine.ledger

    def lower(self) -> dict:
        """JSON-serializable artifact: the captured trace, the resolved
        plan, and the cache provenance — `ExecutionPlan.from_json` /
        `ProgramTrace.from_json` invert the nested documents.
        """
        return {
            "trace": self.trace.to_json(),
            "plan": self.plan.to_json(),
            "cache_key": self.cache_key,
            "searched": self.searched,
            "cache_hit": self.cache_hit,
        }

    def lower_json(self) -> str:
        """Canonical-JSON string of `lower()`."""
        return canonical_json(self.lower())

    # -- derivation ----------------------------------------------------------
    def with_engine(self, engine: Engine) -> "Program":
        """Same trace/provenance, different frozen engine (e.g. a pinned
        chip or an attached ledger added after autotuning).
        """
        return Program(self.apply_fn, engine, self.trace,
                       donate_argnums=self._donate, searched=self.searched,
                       cache_hit=self.cache_hit, cache_key=self.cache_key)

    def with_variation(self, variation) -> "Program":
        """Program with one sampled chip pinned on its engine."""
        return self.with_engine(self.engine.with_variation(variation))

    def with_ledger(self, ledger: EnergyLedger | None) -> "Program":
        """Program with `ledger` attached to its engine."""
        return self.with_engine(self.engine.with_ledger(ledger))

    def bind(self, fn: Callable, *, donate_argnums=(),
             static_argnums=()) -> Callable:
        """Jit-compile an auxiliary function under this program's engine.

        The engine is installed as the ambient context while `fn` traces,
        so model code that resolves `rosa.ambient_engine()` sees the
        program's frozen (plan, chip, ledger) — this is how the serving
        scheduler builds its decode/prefill/admit steps from one Program
        without any global engine stack.  The jitted function keeps
        `fn`'s name, so the profiler shows its program as ``jit_<name>``.
        """
        engine = self.engine

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            """Run `fn` with this program's engine ambient."""
            with engine_context(engine):
                return fn(*args, **kwargs)

        return jax.jit(wrapped, donate_argnums=donate_argnums,
                       static_argnums=static_argnums)


# ---------------------------------------------------------------------------
# compile — trace once, autotune, freeze
# ---------------------------------------------------------------------------
@obs.traced("rosa.compile", cat="compile")
def compile(apply_fn: ApplyFn, engine: Engine,
            example_args: Sequence[Any] = (), *,
            autotune: AutotuneConfig | None = None,
            degradation: "dict | DegradationSource | None" = None,
            cache: "PlanCache | str | os.PathLike | None | bool" = None,
            donate_argnums: Sequence[int] = (),
            verify: str = "off") -> Program:
    """Compile `apply_fn` against `engine` into a frozen `Program`.

    `example_args` are arrays or `jax.ShapeDtypeStruct`s matching
    ``apply_fn(engine, *example_args)``; they are only evaluated
    abstractly.  With ``autotune`` the traced workload drives a layer-wise
    hybrid IS/WS plan search seeded from ``engine.plan.default`` (existing
    overrides are replaced by the searched plan); without it the engine's
    plan is taken as-is and compilation is trace + freeze.  ``degradation``
    makes the search accuracy-aware (the default — mute it with
    ``AutotuneConfig(accuracy_aware=False)`` / the `EDP_ONLY` preset):
    either a ready `{layer: {mapping: pp}}` Monte-Carlo matrix
    (`repro.robust.sensitivity`) or a `DegradationSource`, whose measured
    rows are themselves cached in the `PlanCache` per (layer, RosaConfig,
    measurement spec) — a warm compile loads them instead of re-running
    the MC stage, and a grown trace measures only its new layers.

    Searched plans persist in the content-addressed `PlanCache` (``cache``:
    default directory when None, a directory path, a `PlanCache`, or
    ``False`` to disable) — a warm compile with identical trace + config +
    settings loads the plan from disk and skips the search.

    ``verify`` runs the `repro.analysis` static checks (PRNG discipline,
    donation aliasing, recompile hazards, hot-loop purity) over the
    compiled program: ``"error"`` raises `analysis.VerificationError` on
    ERROR-severity findings, ``"warn"`` emits a warning per finding,
    ``"off"`` (default) skips the pass.  Verification re-traces the
    program with an abstract key and — when donations are declared —
    pays one real XLA compile to read the alias map.
    """
    if verify not in ("off", "warn", "error"):
        raise ValueError(
            f"verify must be 'off'|'warn'|'error', got {verify!r}")
    example_args = tuple(example_args)
    trace = capture_trace(apply_fn, engine, example_args)

    searched = False
    cache_hit = False
    cache_key = None
    if autotune is not None:
        base_cfg = engine.plan.default
        if base_cfg is None:
            raise ValueError(
                "autotune needs engine.plan.default (the base RosaConfig "
                "the search specializes per layer); got a dense default — "
                "pass autotune=None to freeze the plan as-is")
        store = _resolve_cache(cache)
        src = degradation if isinstance(degradation, DegradationSource) \
            else None
        deg = degradation if isinstance(degradation, dict) else None
        if not autotune.accuracy_aware:
            # EDP_ONLY: the accuracy term is muted and degradation inputs
            # are excluded from the cache key (they cannot affect the plan)
            src = deg = None
        key_deg = deg if deg is not None else \
            ({"source": to_jsonable(src.spec)} if src is not None else None)
        cache_key = PlanCache.key(trace, base_cfg, autotune, key_deg)
        plan = store.load(cache_key) if store is not None else None
        if plan is not None:
            # warm compile: the plan (and with it, any MC measurement the
            # search consumed) is loaded whole — the MC stage never runs
            cache_hit = True
        elif len(trace) == 0:
            plan = engine.plan     # nothing routed optically: nothing to tune
        else:
            if src is not None:
                deg = _measured_matrix(src, trace, base_cfg, store)
            d_fn = None
            if deg is not None:
                # default-0 lookup: layers the source could not score run
                # EDP-only instead of crashing the whole search
                matrix = deg
                d_fn = lambda name, m: float(     # noqa: E731
                    matrix.get(name, {}).get(m.value, 0.0))
            with obs.span("rosa.plan_search", cat="compile",
                          layers=len(trace)):
                profiles = M.profile_layers_fast(
                    trace.layer_shapes(), autotune.ope, d_fn,
                    mode=autotune.mode, osa=autotune.osa,
                    batch=autotune.batch)
                if autotune.guard_pp is not None and deg is not None:
                    from repro.robust.sensitivity import \
                        accuracy_guarded_plan
                    mapping_plan = accuracy_guarded_plan(
                        profiles, max_extra_pp=autotune.guard_pp)
                else:
                    mapping_plan = M.hybrid_plan(profiles)
            # open layer set: non-GEMM contractions (depthwise convs) and
            # names outside the trace still resolve to the base config
            plan = ExecutionPlan.from_mapping_plan(base_cfg, mapping_plan)
            searched = True
            if store is not None:
                store.store(cache_key, plan, trace)
        engine = engine.with_plan(plan)

    # Final abstract pass under the frozen plan: validates every traced
    # layer resolves against the tuned plan, and re-prices the trace onto
    # the engine's ledger — but only onto a FRESH (empty) ledger, so a
    # live ledger already carrying scoped runtime events (a serving
    # engine) is never polluted with untagged compile-time duplicates.
    # Skipped entirely when the plan is unchanged and there is nothing to
    # price: capture_trace already resolved every layer under it.
    if autotune is not None or engine.ledger is not None:
        final = engine
        if final.ledger is not None and len(final.ledger.events):
            final = final.with_ledger(None)
        if final.key is None:
            final = final.with_key(jax.random.PRNGKey(0))  # same ledger obj
        with obs.span("rosa.freeze", cat="compile"):
            with engine_context(final):
                jax.eval_shape(functools.partial(apply_fn, final),
                               *example_args)

    program = Program(apply_fn, engine, trace,
                      donate_argnums=donate_argnums, searched=searched,
                      cache_hit=cache_hit, cache_key=cache_key)

    if verify != "off":
        # lazy import: rosa must stay importable without the analysis
        # package, and analysis imports rosa types for its CLI targets
        from repro import analysis as A
        report = A.verify_program(program, example_args)
        if verify == "error" and report.errors:
            raise A.VerificationError(report)
        if report.findings:
            import warnings
            for f in report.findings:
                warnings.warn(f"rosa.compile verification: {f}",
                              stacklevel=2)
    return program
