"""Serving loop: share of the traced window in which the device sat idle
inside a scheduler tick.

Each `serve.tick` host span, clipped to the window, less the device-busy
union (`trace.busy_intervals`) inside it, summed over the ticks, over the
window.  It is a part of `device_idle_share`; the rest is idle time outside
the loop (slice set-up, the harness).  None if there is no tick span.
Moves `tokens_per_s`.
"""

import bisect

from chipbench import trace

SPAN = "serve.tick"


def _union(spans):
    merged: list = []
    for a, b in sorted(spans):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def read(ctx):
    red = ctx.trace
    lo, hi = red["window"]
    ticks = _union((max(s, lo), min(s + d, hi)) for name, s, d in red["host"]
                   if name == SPAN and min(s + d, hi) > max(s, lo))
    if not ticks:
        return None
    busy = trace.busy_intervals(red)
    starts = [a for a, _ in busy]
    done = [0.0]                        # busy time before each interval
    for a, b in busy:
        done.append(done[-1] + b - a)

    def busy_before(t):
        i = bisect.bisect_right(starts, t)
        return 0.0 if i == 0 else \
            done[i - 1] + min(busy[i - 1][1], t) - starts[i - 1]

    idle = sum((b - a) - (busy_before(b) - busy_before(a)) for a, b in ticks)
    return 100.0 * idle * 1e-9 / trace.window_s(red)
