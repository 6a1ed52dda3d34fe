"""The readers of the scheduler's own spans, on a hand-made trace."""

import json
import types
from pathlib import Path

import pytest

from chipbench import trace
from chipbench.metrics import prefill_queue_p90_s, tick_idle_share

# window 0-1000 ns; device busy [100, 250) + [400, 650) + [950, 1000)
HAND = {
    "window": [0.0, 1000.0],
    "ops": [["fusion.1", 100.0, 150.0], ["fusion.2", 400.0, 250.0],
            ["copy", 950.0, 200.0]],
    "modules": [],
    "host": [["serve.tick", 0.0, 300.0], ["serve.tick", 300.0, 400.0],
             # the last tick spills past the window: only 900-1000 counts
             ["serve.tick", 900.0, 300.0],
             ["serve.decode.pull", 200.0, 90.0],
             ["serve.request.queued", 0.0, 100.0],
             ["serve.request.queued", 10.0, 300.0],
             ["serve.request.queued", 20.0, 500.0],
             ["serve.request.queued", 30.0, 900.0],
             # starts before the window: not counted
             ["serve.request.queued", -50.0, 40.0]],
}


def ctx(red, requests=4):
    return types.SimpleNamespace(trace=red, counts={"requests": requests})


def test_tick_idle_share_by_hand():
    # tick 1 [0, 300): busy 150, idle 150; tick 2 [300, 700): busy 250,
    # idle 150; tick 3 clipped to [900, 1000): busy 50, idle 50
    assert tick_idle_share.read(ctx(HAND)) == pytest.approx(35.0)
    # a part of the device's idle share (50% here)
    assert tick_idle_share.read(ctx(HAND)) \
        <= 100.0 * trace.idle_share(HAND)


def test_tick_idle_share_none_without_ticks():
    red = {**HAND, "host": [e for e in HAND["host"]
                            if e[0] != "serve.tick"]}
    assert tick_idle_share.read(ctx(red)) is None
    # a tick wholly outside the window is not a tick of it
    red["host"] = [["serve.tick", 1000.0, 50.0]]
    assert tick_idle_share.read(ctx(red)) is None


def test_prefill_queue_p90_by_hand():
    # waits 100, 300, 500, 900 ns: p90 (linear) = 500 + 0.7 * 400 = 780 ns
    assert prefill_queue_p90_s.read(ctx(HAND)) == pytest.approx(780e-9)


@pytest.mark.parametrize("requests", [3, 5])
def test_prefill_queue_p90_none_when_count_differs(requests):
    assert prefill_queue_p90_s.read(ctx(HAND, requests)) is None


def test_readers_find_nothing_in_a_trace_without_spans():
    red = {**HAND, "host": [["PjitFunction(wrapped)", 250.0, 100.0]]}
    assert tick_idle_share.read(ctx(red)) is None
    assert prefill_queue_p90_s.read(ctx(red)) is None


def test_readers_find_nothing_in_a_recorded_trace_without_spans():
    # recorded on the chip before the scheduler's spans reached the profiler
    data = Path(__file__).resolve().parent / "data"
    red = json.loads((data / "qwen3-32b.chat.trace.json").read_text())
    assert tick_idle_share.read(ctx(red)) is None
    assert prefill_queue_p90_s.read(ctx(red)) is None
