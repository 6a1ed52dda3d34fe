"""Serving loop: p90 of the time a request waits in the prefill queue.

The scheduler opens a `serve.request.queued` span on the profiler's host
line when it stamps a request's enqueue time, and closes it when the
request leaves the FIFO prefill queue and its prefill starts.  The p90 of
the durations of those that start inside the traced window; None unless
there is one for every request the window served.  Moves `ttft_p90_s`.
"""

import numpy as np

SPAN = "serve.request.queued"


def read(ctx):
    lo, hi = ctx.trace["window"]
    waits = [d for name, s, d in ctx.trace["host"]
             if name == SPAN and lo <= s < hi]
    if not waits or len(waits) != ctx.counts["requests"]:
        return None
    return 1e-9 * float(np.percentile(np.asarray(waits, np.float64), 90))
