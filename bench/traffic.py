"""The one traffic generator: a cell's traffic file in, timed requests out.

A traffic file (``bench/workloads/<traffic>.json``) gives the arrival kind,
the rates or the backlog, and the prompt and generation lengths; nothing
else decides the requests.  Two arrival kinds:

  * ``poisson``: per model, an open loop at ``rates_req_s`` over the
    window.  The gaps are exponential, drawn by stratification: the ``n``
    quantiles ``-ln(1 - (k + 1/2) / n) / rate`` with ``n = rate * seconds``,
    in a random order.  (The gaps of ``simulator.events.PoissonArrivals``
    are independent exponential draws; here the count per window is fixed.)
  * ``backlog``: ``backlog`` requests, all due at t = 0, in a random order.

Prompt lengths are spread evenly over ``prompt_lens``; generation lengths
are the stratified quantiles of the uniform distribution over
``gen_range`` (inclusive).

The schedule (due times, and each request's model, prompt length and
generation length) is drawn from the traffic file's ``schedule_seed``:
it is part of the cell, as a recorded trace would be.  The run's seed
draws the prompt tokens (and, in ``run.py``, the weights).  In a queue the
order of arrivals is the work: drawn from the run's seed, it moved the
mix's p90 latency by 22-32% between seeds, where two runs of one seed
agreed within 0.5% in five pairs of six (one TPU v5e chip).
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *stream])


def _even(values, n: int, rng) -> np.ndarray:
    return rng.permutation(np.resize(np.asarray(values), n))


def _uniform_ints(lo: int, hi: int, n: int, rng) -> np.ndarray:
    k = (np.arange(n) + 0.5) / n
    return rng.permutation(lo + np.floor(k * (hi - lo + 1)).astype(int))


def _gaps_ms(rate_req_s: float, n: int, rng) -> np.ndarray:
    k = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-k) / rate_req_s * 1e3)


def requests(traffic: dict, models: list[dict], seed: int, seconds: float):
    """The cell's requests as ``(model, due_ms, prompt, max_new, slo_ms)``
    tuples, sorted by due time.

    ``models`` are the configuration's model entries (``arch``, ``slo_ms``,
    ``sizes``), in the configuration's order.
    """
    kind, plan = traffic["arrivals"], traffic["schedule_seed"]
    out = []
    for i, m in enumerate(models):
        name = m["arch"]
        if kind == "poisson":
            rate = traffic["rates_req_s"][name]
            n = int(round(rate * seconds))
            due = np.cumsum(_gaps_ms(rate, n, _rng(plan, i, 0)))
        elif kind == "backlog":
            n = int(traffic["backlog"][name])
            due = np.zeros(n)
        else:
            raise ValueError(f"unknown arrival kind {kind!r}")
        lens = _even(traffic["prompt_lens"], n, _rng(plan, i, 1))
        gens = _uniform_ints(*traffic["gen_range"], n, _rng(plan, i, 2))
        tok_rng = _rng(seed, i, 3)
        vocab = m["sizes"]["vocab_size"]
        for t, s, g in zip(due, lens, gens):
            out.append((name, float(t),
                        tok_rng.integers(0, vocab, int(s), dtype=np.int32),
                        int(g), float(m["slo_ms"])))
    order = np.argsort([r[1] for r in out], kind="stable")
    if kind == "backlog":
        order = _rng(plan, 99).permutation(len(out))
    return [out[j] for j in order]
