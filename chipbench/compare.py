"""How `correct` is decided: served tokens against the plain reference.

Once the window has closed, every finished request is run through the
reference once: the prompt followed by the served tokens, teacher-forced, at
the precision the configuration states.  At every served position the gap is how far the served token's
reference logit lies below the reference's best, as a share of the
reference logits' full scale (largest magnitude) there.  A served greedy
token that the reference also puts first has gap 0.

Each run logs `widest_gap` (largest gap over all compared tokens),
`mean_gap`, `mean_sq_gap` (mean of the squared gaps), `flip_share` (share
of compared tokens that are not the reference's first choice) and
`short_requests` (requests that ended with fewer tokens than asked for).
Those that the cell's limits file names are compared, each against its
limit: `mean_sq_gap` and `short_requests` (limit 0).  The control
(`control.py`) puts a lower-precision reference in the program's place:
at the same positions it reads the gap of the token the lower precision
puts first (`first_choices`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def teacher_forced(req: dict, max_len: int, max_out: int):
    """(tokens (max_len,), positions (max_out,), n): the sequence the
    reference reads and the positions whose logits predict each served
    token."""
    seq = np.concatenate([req["prompt"], np.asarray(req["tokens"][:-1],
                                                    np.int32)])
    tokens = np.zeros(max_len, np.int32)
    tokens[:len(seq)] = seq
    n = len(req["tokens"])
    at = np.minimum(len(req["prompt"]) - 1 + np.arange(max_out), max_len - 1)
    return jnp.asarray(tokens), jnp.asarray(at.astype(np.int32)), n


@jax.jit
def _gaps(logits, chosen):
    best = jnp.max(logits, -1)
    scale = jnp.maximum(jnp.max(jnp.abs(logits), -1), 1e-30)
    got = jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0]
    return (best - got) / scale


def gaps(logits, chosen, n: int) -> np.ndarray:
    return np.asarray(_gaps(logits, jnp.asarray(chosen, jnp.int32)))[:n]


def served_tokens(picked, max_out: int) -> list[np.ndarray]:
    """The served tokens of each picked request, padded to `max_out`."""
    out = []
    for r in picked:
        chosen = np.zeros(max_out, np.int32)
        chosen[:len(r["tokens"])] = r["tokens"]
        out.append(chosen)
    return out


def first_choices(ref, params, chip, picked, cfg, mix,
                  precision: str) -> list:
    """The token the reference at `precision` puts first at each served
    position of each picked request."""
    max_len, max_out = mix["serve"]["max_len"], mix["output"]["max"]
    out = []
    for r in picked:
        tokens, at, _ = teacher_forced(r, max_len, max_out)
        out.append(jnp.argmax(ref.logits(params, chip, tokens, at, cfg,
                                         precision), -1))
    return out


def reference_gaps(ref, params, chip, picked, cfg, mix,
                   choices: dict) -> dict:
    """{name: gaps} of each named choice of tokens (a list with one array
    per picked request), read in one reference pass at the stated
    precision."""
    max_len, max_out = mix["serve"]["max_len"], mix["output"]["max"]
    out: dict = {k: [] for k in choices}
    for j, r in enumerate(picked):
        tokens, at, n = teacher_forced(r, max_len, max_out)
        lg = ref.logits(params, chip, tokens, at, cfg, ref.stated(cfg))
        for k, chosen in choices.items():
            out[k].append(gaps(lg, chosen[j], n))
    return {k: np.concatenate(v) for k, v in out.items()}


def numbers(g: np.ndarray, short: int) -> dict:
    if not g.size:
        return {"widest_gap": float("inf"), "mean_gap": float("inf"),
                "flip_share": float("inf"), "mean_sq_gap": float("inf"),
                "short_requests": float(short)}
    return {"widest_gap": float(np.max(g)), "mean_gap": float(np.mean(g)),
            "flip_share": float(np.mean(g > 0)),
            "mean_sq_gap": float(np.mean(np.square(g))),
            "short_requests": float(short)}


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """Every number that has a limit, beside it; correct when none is
    above its limit."""
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
