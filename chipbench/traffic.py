"""The one traffic generator: a mix file of parameters in, request slices out.

Arrivals are in scheduler ticks, because `Scheduler.run` takes its whole
request list up front with a due tick on each request: a request is
enqueued at exactly its due tick, so the generator is never late.

Every seed gets the same work.  A slice holds the same multiset of prompt
lengths, output lengths and inter-arrival gaps in every slice of every
run: the lengths are the mix's lognormal distribution read at evenly
spaced quantiles (heavy tail included), the gaps are exponential quantiles
at the mix's rate.  Their order is drawn from the slice's index alone, so
slice `i` has the same schedule on every seed; the seed draws the token
ids.  A tail over a few tens of requests then measures the program, not
where a seed happened to put the longest prompts.

The offered load is a share (`load`) of the tick capacity that the mix's
own sizes give: a tick runs at most one prefill chunk and one decode step
over `n_slots` slots, so the scheduler sustains at most
min(1 / E[chunks per prompt], n_slots / E[output tokens], 1) requests per
tick.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile_lengths(spec: dict, n: int) -> list[int]:
    """`n` lengths of a clipped lognormal at quantiles (i + 1/2) / n."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        v = spec["median"] * math.exp(spec["sigma"] * z)
        out.append(int(min(max(round(v), spec["min"]), spec["max"])))
    return out


def slice_sizes(mix: dict) -> tuple[list[int], list[int]]:
    n = mix["slice_requests"]
    return quantile_lengths(mix["prompt"], n), quantile_lengths(mix["output"], n)


def rate_per_tick(mix: dict) -> float:
    """Requests per tick: `load` x the tick capacity of the mix's sizes."""
    prompts, outputs = slice_sizes(mix)
    serve = mix["serve"]
    chunks = float(np.mean([-(-p // serve["prefill_chunk"]) for p in prompts]))
    cap = min(1.0 / chunks, serve["n_slots"] / float(np.mean(outputs)), 1.0)
    return mix["arrivals"]["load"] * cap


def _rng(seed: int, index: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**63, index])


def make_slice(mix: dict, seed: int, index: int, vocab: int) -> list[dict]:
    """Slice `index` of the stream for `seed`: a list of requests, each
    {"rid", "prompt" (int32 array), "max_new_tokens", "arrival" (tick)}."""
    if mix["arrivals"]["process"] != "poisson_ticks":
        raise ValueError(f"unknown arrival process "
                         f"{mix['arrivals']['process']!r}")
    n = mix["slice_requests"]
    prompts, outputs = slice_sizes(mix)
    rate = rate_per_tick(mix)
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    order = _rng(0, index)
    prompts = [prompts[i] for i in order.permutation(n)]
    outputs = [outputs[i] for i in order.permutation(n)]
    gaps = [gaps[i] for i in order.permutation(n)]
    arrivals = np.floor(np.cumsum(gaps) - gaps[0]).astype(np.int64)
    rng = _rng(seed, index)
    reqs = []
    for i in range(n):
        reqs.append({
            "rid": index * n + i,
            "prompt": rng.integers(0, vocab, size=prompts[i]).astype(np.int32),
            "max_new_tokens": outputs[i],
            "arrival": int(arrivals[i]),
        })
    return reqs
