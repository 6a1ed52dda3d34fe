"""repro.serve — continuous-batching serving over the optical Engine.

The subsystem promotes `launch/serve.py` from a one-shot script to a
scheduler-driven serving stack:

  `ServeConfig`      slots / cache capacity / prefill chunking / sampling /
                     optical-engine knobs (frozen, jit-closure safe)
  `Scheduler`        slot-based continuous batching: per-tick prefill
                     chunks, in-step slot eviction + refill on a DONATED
                     paged KV cache, deterministic tick accounting; also
                     runs the static-batching "oneshot" baseline policy
  `run_sequential`   the per-request oracle the differential test suite
                     (tests/test_serve.py) pins the scheduler against —
                     greedy streams must match BIT-exactly
  `poisson_requests` reproducible synthetic load (Poisson arrivals)
  `smoke_report`     the gated `serve_smoke` bench: throughput (step
                     units), latency percentiles (ticks), continuous vs
                     one-shot ratio, per-token energy from the ledger

Sampling keys fold (request id, token index) from one base seed, so a
request's stream is invariant to scheduling — the property that makes
serving testable at all.

`repro.serve.adaptive` closes the drift loop on top of this stack: a
`TickHook` injects per-tick thermal residuals into the decode step, and a
probe/detector/controller pipeline re-trims or re-plans the serving
`rosa.Program` mid-traffic without dropping requests (see
docs/adaptive-serving.md).
"""

from repro.serve.config import ServeConfig, serving_model_config
from repro.serve.decode import (DecodeState, PrefillTask, init_state,
                                make_admit, make_admit_step, make_chunk_fn,
                                make_evict, make_prefill_fn, make_serve_step,
                                null_admit, prepare_step_params,
                                sample_token)
from repro.serve.loadgen import poisson_requests
from repro.serve.metrics import (build_serving_engine, energy_metrics,
                                 report_metrics, smoke_report)
from repro.serve.scheduler import (Completion, EmptyStat, Request,
                                   Scheduler, ServeReport, TickHook,
                                   run_sequential)

__all__ = [
    "Completion", "DecodeState", "EmptyStat", "PrefillTask", "Request",
    "Scheduler", "ServeConfig", "ServeReport", "TickHook",
    "build_serving_engine", "energy_metrics", "init_state", "make_admit",
    "make_admit_step", "make_chunk_fn", "make_evict", "make_prefill_fn",
    "make_serve_step", "null_admit", "poisson_requests",
    "prepare_step_params", "report_metrics",
    "run_sequential", "sample_token", "serving_model_config", "smoke_report",
]
