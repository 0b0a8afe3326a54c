"""Whether what the timed path served is correct: served tokens against
the plain float32 reference.

Once the window has closed and the program's state is freed, a sample of
each model's finished requests, drawn from the seed and always holding
the longest, goes through the reference: each prompt followed by the
tokens served for it.  At every position that produced a served token the
reading is the gap by which that token's reference logit lies below the
reference's best logit there; greedy decoding of an exact program gives
0 up to rounding.  The numbers compared, per model, are named in its
configuration file with their limits: ``gap``, the widest gap over every
served token of the sample, or ``gap_mean``, the mean over them (for a
model whose bf16 rounding alone spreads its widest gap too far to tell it
from the float8 control; ``PERF.md`` gives the readings).

The low-precision control puts the reference computed in float8 in the
program's place: at each of the same positions it reads the gap of the
token the float8 forward puts first.
"""
from __future__ import annotations

import numpy as np

from bench import reference

SAMPLE = 12
#: The numbers every run prints for each model, compared or not.
NUMBERS = ("gap", "gap_mean")


def sample(requests: list, seed: int, k: int = SAMPLE) -> list:
    """Up to ``k`` of ``requests``, the longest (prompt plus served
    tokens) always among them, the rest drawn from ``seed``."""
    if len(requests) <= k:
        return list(requests)
    longest = max(range(len(requests)),
                  key=lambda j: len(requests[j].prompt) + len(requests[j].output))
    rest = [j for j in range(len(requests)) if j != longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    picked = rng.choice(rest, size=k - 1, replace=False)
    return [requests[longest]] + [requests[j] for j in sorted(picked)]


def _inputs(reqs: list):
    """Sequences (prompt + served tokens but the last), the positions that
    produced each served token, and a mask of the real ones."""
    g = max(len(r.output) for r in reqs)
    s = max(len(r.prompt) + len(r.output) - 1 for r in reqs)
    seqs = np.zeros((len(reqs), s), np.int32)
    pos = np.zeros((len(reqs), g), np.int32)
    mask = np.zeros((len(reqs), g), bool)
    tokens = np.zeros((len(reqs), g), np.int64)
    for i, r in enumerate(reqs):
        p, out = len(r.prompt), np.asarray(r.output)
        seqs[i, :p] = r.prompt
        seqs[i, p:p + len(out) - 1] = out[:-1]
        pos[i] = p - 1 + np.minimum(np.arange(g), len(out) - 1)
        mask[i, :len(out)] = True
        tokens[i, :len(out)] = out
    return seqs, pos, mask, tokens


def _gaps(ref: np.ndarray, tokens: np.ndarray, mask: np.ndarray) -> np.ndarray:
    """(N, G) gap of each chosen token below the best, NaN past a request's
    last served token."""
    best = ref.max(axis=-1)
    chosen = np.take_along_axis(ref, tokens[..., None], axis=-1)[..., 0]
    return np.where(mask, best - chosen, np.nan)


def number(per_token: np.ndarray, name: str) -> float:
    """``gap`` (the widest) or ``gap_mean`` of an (N, G) array of gaps."""
    if name == "gap":
        return float(np.nanmax(per_token))
    if name == "gap_mean":
        return float(np.nanmean(per_token))
    raise ValueError(f"unknown correctness number {name!r}")


def gaps(sizes: dict, root, reqs: list, control: bool = False,
         device=None) -> dict:
    """Gaps (N, G) of the served tokens (and, with ``control``, of the
    float8 reference's own first choices) below the reference's best, by
    request and served-token index."""
    seqs, pos, mask, tokens = _inputs(reqs)
    ref = reference.logits(sizes, root, seqs, pos, "float32", device)
    out = {"served": _gaps(ref, tokens, mask), "positions": int(mask.sum()),
           "requests": len(reqs)}
    if control:
        low = reference.logits(sizes, root, seqs, pos, "float8_e4m3", device)
        out["control"] = _gaps(ref, low.argmax(axis=-1), mask)
    return out
