"""The program's own counters over the window reach every reader.

The harness reads the process-global `repro.obs` registry right before and
right after the window and hands readers the difference, so the warm-up's
work and anything run earlier in the process fall outside it.
"""

import time

from chipbench import harness
from chipbench.tests.test_faults import tiny_cell


def test_counters_over_take_the_difference_and_leave_gauges_out():
    from repro.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    reg.counter("before").inc(3)
    reg.histogram("lat").observe(0.5)
    reg.gauge("depth").set(7)
    first = harness.read_counters(reg)
    reg.counter("before").inc(2)
    reg.counter("inside").inc(4)
    reg.histogram("lat").observe(1.5)
    reg.histogram("lat").observe(0.25)
    reg.gauge("depth").set(1)
    got = harness.counters_over(first, harness.read_counters(reg))
    assert got == {"before": 2.0, "inside": 4.0, "lat.count": 2,
                   "lat.sum": 1.75}


def test_window_counts_its_own_requests_only():
    from repro.obs.metrics import registry
    c, seed = tiny_cell(), 2**31 + 13
    harness.prepare_env()
    harness.set_matmul_precision(c["cfg"])
    _, served, _ = harness.setup(c, seed, time.perf_counter())
    # the warm-up's request is in the process's count already
    assert registry().counter("serve.requests_completed").value >= 1
    win = harness.Window(served, c["mix"], seed, c["cfg"]["vocab"])
    got = harness.run_window(win, 0.5, None)
    assert win.counts()["requests"] > 0
    assert got["serve.requests_completed"] == win.counts()["requests"]
