"""A whole run at a tiny size, the chip's look skipped: sound, then broken.

Each fault is planted in the timed path underneath the harness (the
serving step as the program builds it) and has to turn `correct` false.
Faults that need several chips do not apply: every cell takes one.
"""

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness

ROOT = Path(__file__).resolve().parents[2]


def tiny_cell() -> dict:
    cfg = json.loads((ROOT / "chipbench/configs/qwen3-32b.json").read_text())
    cfg.update(name="tiny", n_layers=2, d_model=128, n_heads=4,
               n_kv_heads=2, head_dim=32, d_ff=256, vocab=512)
    mix = json.loads((ROOT / "chipbench/mixes/code.json").read_text())
    mix["serve"].update(n_slots=4, max_len=128, prefill_chunk=32)
    mix["slice_requests"] = 16
    mix["prompt"].update(median=16, sigma=0.5, min=4, max=40)
    mix["output"].update(median=24, sigma=0.3, min=8, max=48)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    limits = json.loads((ROOT / "chipbench/limits/qwen3-32b.code.json")
                        .read_text())["limits"]
    return {"cell": {"name": "tiny.code", "chips": 1}, "cfg": cfg,
            "mix": mix, "limits": limits,
            "end_to_end": bench["end_to_end"], "per_layer": []}


def run(seed: int = 2**31 + 11) -> dict:
    """The benchmark's run from set-up to result line, on whatever device
    JAX has: `harness.main` itself looks for the chip first."""
    c = tiny_cell()
    harness.prepare_env()
    harness.set_matmul_precision(c["cfg"])
    setup_s, served, plan = harness.setup(c, seed, time.perf_counter())
    win = harness.Window(served, c["mix"], seed, c["cfg"]["vocab"])
    harness.run_window(win, 0.5, None)
    e2e = harness.end_to_end(win, setup_s)
    metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
               for m in c["end_to_end"]}
    return harness.finish(c, win, served, plan, metrics, {"platform": "cpu"},
                          {})


def altered_token(monkeypatch, decode):
    orig = decode._sample_rows
    monkeypatch.setattr(decode, "_sample_rows", lambda *a: (orig(*a) + 1)
                        % a[3].shape[-1])


def half_batch(monkeypatch, decode):
    orig = decode._sample_rows

    def half(key, rid, tidx, logits, temp):
        n = logits.shape[0]
        keep = (jnp.arange(n) < n // 2)[:, None]
        return orig(key, rid, tidx, jnp.where(keep, logits, 0.0), temp)

    monkeypatch.setattr(decode, "_sample_rows", half)


def state_unchanged(monkeypatch, decode):
    orig = decode._step_body

    def stale(bundle, scfg, params, state, admit, temperature, slot_offset):
        new, out = orig(bundle, scfg, params, state, admit, temperature,
                        slot_offset)
        before = decode._apply_admission(bundle.cfg, state, admit,
                                         slot_offset)
        return new._replace(cache=before.cache), out

    monkeypatch.setattr(decode, "_step_body", stale)


def test_sound_run_is_correct():
    out = run()
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"tokens_per_s", "ttft_p90_s",
                                   "tpot_p90_s", "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [altered_token, half_batch,
                                   state_unchanged])
def test_fault_is_not_correct(monkeypatch, fault):
    from repro.serve import decode
    fault(monkeypatch, decode)
    jax.clear_caches()
    out = run()
    assert out["correct"] is False
