"""From a profiler trace to the numbers the per-layer metrics read.

`load_xplane` reduces the `.xplane.pb` the JAX profiler writes to a small
JSON-able dict; everything else works on that dict, so the reduction is
tested on a recorded trace kept in `tests/`:

    {"window": [start_ns, end_ns],          # the benchmark's own annotation
     "ops":     [[name, start_ns, dur_ns]], # device 0, one event per op
     "modules": [[name, start_ns, dur_ns]], # device 0, one per program run
     "host":    [[name, start_ns, dur_ns]]} # host-side profiler events

Busy time is the union of the device's op intervals inside the window;
the idle share is 1 minus busy over the window.  Programs are told apart by
how often they ran: the decode-step program runs once per decode step and
the chunk program once per prefill chunk, which the scheduler counts.
"""

from __future__ import annotations

import bisect
import glob
import re
from pathlib import Path

WINDOW = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:0$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _events(line) -> list:
    return [[e.name, float(e.start_ns), float(e.duration_ns)]
            for e in line.events]


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(str(Path(logdir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    red = {"window": None, "ops": [], "modules": [], "host": []}
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    red["ops"] = _events(line)
                elif line.name == MODULES_LINE:
                    red["modules"] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, d in _events(line):
                    if name == WINDOW:
                        red["window"] = [s, s + d]
                    elif d > 0:
                        red["host"].append([name, s, d])
    if red["window"] is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    return red


def _clip(events, window):
    lo, hi = window
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def busy_intervals(red: dict) -> list[tuple[float, float]]:
    """The union of the device's op intervals inside the window."""
    spans = sorted((a, b) for _, a, b in _clip(red["ops"], red["window"]))
    merged: list = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def window_s(red: dict) -> float:
    return (red["window"][1] - red["window"][0]) * 1e-9


def busy_s(red: dict) -> float:
    return sum(b - a for a, b in busy_intervals(red)) * 1e-9


def idle_share(red: dict) -> float:
    return 1.0 - busy_s(red) / window_s(red)


def program_groups(red: dict) -> dict:
    """{program name: (runs, device seconds)} inside the window."""
    out: dict = {}
    for name, a, b in _clip(red["modules"], red["window"]):
        n, t = out.get(name, (0, 0.0))
        out[name] = (n + 1, t + (b - a) * 1e-9)
    return out


def program_ran(red: dict, runs: int) -> tuple[str, float] | None:
    """The program that ran exactly `runs` times in the window, and its
    mean device seconds per run; the longest-running one if several did."""
    hits = [(t, name) for name, (n, t) in program_groups(red).items()
            if n == runs]
    if runs <= 0 or not hits:
        return None
    t, name = max(hits)
    return name, t / runs


def ops_matching(red: dict, pattern: str) -> tuple[int, float]:
    """(count, device seconds) of the ops whose name matches `pattern`."""
    rx = re.compile(pattern)
    hits = [(b - a) for name, a, b in _clip(red["ops"], red["window"])
            if rx.search(name)]
    return len(hits), sum(hits) * 1e-9


def op_label(text: str) -> str:
    """An op's HLO name and result type, from the op's full HLO text:
    '%rosa_fused_pallas.22 = f32[256,51200]{...} custom-call(...)' ->
    '%rosa_fused_pallas.22 f32[256,51200]'."""
    name, eq, rest = text.partition(" = ")
    if not eq:
        return text[:80]
    return f"{name} {rest.split('{')[0].split(' ')[0].lstrip('(')}"


def leaf_ops(red: dict) -> list:
    """The window's device ops that enclose no other op: a loop (`%while`)
    is an event of its own around its body's ops, which would count twice."""
    ops = sorted(_clip(red["ops"], red["window"]), key=lambda e: (e[1], -e[2]))
    parent = [False] * len(ops)
    stack: list = []
    for i, (_, a, b) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= ops[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(ops, parent) if not p]


def top_ops(red: dict, n: int = 10) -> list:
    """The device ops that took most time, summed by label over the leaf
    ops: [[label, s]]."""
    tot: dict = {}
    for name, a, b in leaf_ops(red):
        key = op_label(name)
        tot[key] = tot.get(key, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(red: dict, n: int = 10) -> list:
    """The longest idle gaps of the device inside the window, each labelled
    by the host event that overlaps it most (the shortest such event on a
    tie), summed by label: [[label, s]]."""
    busy = busy_intervals(red)
    lo, hi = red["window"]
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = sorted(_clip(red["host"], red["window"]), key=lambda e: e[1])
    starts = [e[1] for e in host]
    longest = max((b - a for _, a, b in host), default=0.0)
    tot: dict = {}
    for a, b in gaps[:200]:
        best, label = (0.0, 0.0), "no host event"
        i = bisect.bisect_left(starts, a - longest)
        while i < len(host) and host[i][1] < b:
            name, s, e = host[i]
            ov = min(b, e) - max(a, s)
            if ov > 0 and (ov, -(e - s)) > best:
                best, label = (ov, -(e - s)), name
            i += 1
        tot[label] = tot.get(label, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
