"""The generator: same seed, same slice; any seed, the same schedule."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import traffic

MIXES = Path(__file__).resolve().parents[1] / "mixes"


@pytest.mark.parametrize("name", ["code"])
def test_every_seed_gets_the_same_work(name):
    mix = json.loads((MIXES / f"{name}.json").read_text())
    a = traffic.make_slice(mix, 2**31 + 99, 0, 1000)
    b = traffic.make_slice(mix, 2**31 + 99, 0, 1000)
    c = traffic.make_slice(mix, 5, 1, 1000)
    assert [r["max_new_tokens"] for r in a] == [r["max_new_tokens"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    for key in ("max_new_tokens", "arrival"):
        assert [r[key] for r in a] == [r[key] for r in b]
    d = traffic.make_slice(mix, 5, 0, 1000)
    for key in ("max_new_tokens", "arrival"):
        assert [r[key] for r in a] == [r[key] for r in d]
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in d]
    assert any((x["prompt"] != y["prompt"]).any() for x, y in zip(a, d))
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in c)
    assert sorted(r["max_new_tokens"] for r in a) == \
        sorted(r["max_new_tokens"] for r in c)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in c]
    s = mix["serve"]
    for r in a + c:
        assert 0 <= r["prompt"].min() and r["prompt"].max() < 1000
        assert len(r["prompt"]) + r["max_new_tokens"] - 1 <= s["max_len"]
    assert len({r["rid"] for r in a + c}) == 2 * mix["slice_requests"]


def test_rate_is_a_share_of_tick_capacity():
    mix = json.loads((MIXES / "code.json").read_text())
    prompts, outputs = traffic.slice_sizes(mix)
    chunks = np.mean([-(-p // 256) for p in prompts])
    cap = min(1 / chunks, 4 / np.mean(outputs), 1.0)
    assert traffic.rate_per_tick(mix) == pytest.approx(0.8 * cap)
