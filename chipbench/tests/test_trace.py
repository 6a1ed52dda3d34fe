"""The trace reduction: busy union, idle share, programs, kernel time."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace
from chipbench.metrics import rosa_fused_busy_share

DATA = Path(__file__).resolve().parent / "data"

# a hand-made trace (ns): window 0-1000; ops overlap and spill past the end
HAND = {
    "window": [0.0, 1000.0],
    "ops": [["fusion.1", 100.0, 100.0], ["_kernel", 150.0, 100.0],
            ["_kernel", 400.0, 200.0], ["fusion.2", 550.0, 100.0],
            ["copy", 950.0, 200.0], ["early", -50.0, 100.0]],
    "modules": [["jit_wrapped(1)", 100.0, 160.0],
                ["jit_wrapped(2)", 400.0, 260.0],
                ["jit_wrapped(1)", 950.0, 60.0]],
    "host": [["PjitFunction(wrapped)", 250.0, 100.0],
             ["TransferFromDevice", 280.0, 120.0],
             ["PjitFunction(wrapped)", 650.0, 300.0]],
}


def test_busy_union_and_idle_share_by_hand():
    # busy: [0, 50) + [100, 250) + [400, 650) + [950, 1000) = 500 ns
    assert trace.busy_intervals(HAND) == [(0.0, 50.0), (100.0, 250.0),
                                          (400.0, 650.0), (950.0, 1000.0)]
    assert trace.busy_s(HAND) == pytest.approx(500e-9)
    assert trace.window_s(HAND) == pytest.approx(1000e-9)
    assert trace.idle_share(HAND) == pytest.approx(0.5)


def test_programs_and_kernel_time_by_hand():
    # the module clipped at the window's end counts its part inside
    assert trace.program_groups(HAND) == {
        "jit_wrapped(1)": (2, pytest.approx(210e-9)),
        "jit_wrapped(2)": (1, pytest.approx(260e-9))}
    name, per_run = trace.program_ran(HAND, 2)
    assert name == "jit_wrapped(1)" and per_run == pytest.approx(105e-9)
    assert trace.program_ran(HAND, 3) is None
    assert trace.ops_matching(HAND, r"_kernel") == (2, pytest.approx(300e-9))
    assert trace.top_ops(HAND)[0] == ["_kernel", pytest.approx(300e-9)]
    assert trace.op_label(
        "%rosa_fused_pallas.22 = f32[256,51200]{1,0:T(8,128)S(1)} "
        "custom-call(f32[256,5120]{1,0} %copy-done.7)") == \
        "%rosa_fused_pallas.22 f32[256,51200]"


def test_top_ops_count_leaf_ops_only():
    # a loop around two body ops, and a sibling after it
    red = {"window": [0.0, 100.0],
           "ops": [["%while.1 = s32[] while()", 10.0, 50.0],
                   ["%body.1 = f32[8] fusion()", 10.0, 20.0],
                   ["%body.2 = f32[8] fusion()", 35.0, 25.0],
                   ["%after.1 = f32[8] fusion()", 60.0, 10.0]],
           "modules": [], "host": []}
    assert [e[0][:7] for e in trace.leaf_ops(red)] == ["%body.1", "%body.2",
                                                        "%after."]
    assert dict((k, v) for k, v in trace.top_ops(red)) == {
        "%body.2 f32[8]": pytest.approx(25e-9),
        "%body.1 f32[8]": pytest.approx(20e-9),
        "%after.1 f32[8]": pytest.approx(10e-9)}
    assert trace.busy_s(red) == pytest.approx(60e-9)


def test_idle_gaps_are_labelled_by_the_host():
    # gaps: [50, 100) none, [250, 400) the transfer overlaps most (120 ns)
    # and [650, 950) the dispatch
    gaps = dict((k, v) for k, v in trace.idle_gaps(HAND))
    assert gaps == {"PjitFunction(wrapped)": pytest.approx(300e-9),
                    "TransferFromDevice": pytest.approx(150e-9),
                    "no host event": pytest.approx(50e-9)}


def test_reduction_of_a_recorded_trace():
    # 0.4 s of a qwen3-32b.chat window traced on one v5e (reduced by
    # trace.load_xplane); checked against a plain 100 ns timeline
    red = json.loads((DATA / "qwen3-32b.chat.trace.json").read_text())
    lo, hi = red["window"]
    bins = np.zeros(int((hi - lo) // 100) + 1, bool)
    for _, s, d in red["ops"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            bins[int((a - lo) // 100):int(-(-(b - lo) // 100))] = True
    spans = len(trace.busy_intervals(red))
    assert abs(trace.busy_s(red) - bins.sum() * 100e-9) <= spans * 200e-9
    assert trace.window_s(red) == pytest.approx(0.4)
    assert trace.idle_share(red) == pytest.approx(
        1 - trace.busy_s(red) / 0.4)

    def runs(name):
        return [min(s + d, hi) - max(s, lo) for n, s, d in red["modules"]
                if n == name and s < hi and s + d > lo]

    decode, chunk = "jit_wrapped(8114896416173718779)", \
        "jit_wrapped(1868313486040081554)"
    assert trace.program_ran(red, 4) == (
        decode, pytest.approx(sum(runs(decode)) * 1e-9 / 4))
    # several programs ran twice: the chunk is the longest of them
    assert trace.program_ran(red, 2)[0] == chunk

    # clipped to the window, as every op is
    kernel = [min(s + d, hi) - max(s, lo) for n, s, d in red["ops"]
              if n.startswith("%rosa_fused_pallas") and s < hi and s + d > lo]
    assert len(kernel) == 22
    assert trace.ops_matching(red, rosa_fused_busy_share.KERNEL) == (
        22, pytest.approx(sum(kernel) * 1e-9))
    # consumers name the kernel among their operands and do not count
    assert sum("rosa_fused_pallas" in n for n, _, _ in red["ops"]) > 22
    assert not any(k.startswith("%while")
                   for k, _ in trace.top_ops(red, n=50))
