#!/usr/bin/env python3
"""Find a steady cell's knee once: serve equal per-model rates over one set-up.

    python3 bench/sweep.py --workload mix-steady --seed <n> --seconds 40 \
        --rates 0.6,0.8,1.0,1.2,1.4

For each rate the scheduler is planned at that rate (caps from its
placement), the cell's traffic is offered at that rate for ``--seconds``
and drained, and one JSON line gives the p90 latency, the SLO attainment
and the backlog's growth: the p90 of the window's second half against its
first, and how long after the window the last request finished.  The knee
is the highest rate at which at least 99% of requests meet the SLO with no
growing backlog; the cell's rate is a fixed number written into its
traffic file, never derived by a run.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402
from bench.metrics._common import nearest_rank  # noqa: E402


def summarize(rows: list[dict], seconds: float) -> dict:
    def p90(rs):
        lat = [math.inf if r["done_ms"] is None else r["done_ms"] - r["due_ms"]
               for r in rs]
        return nearest_rank(lat, 0.9) if lat else None

    half = seconds * 500.0
    met = sum(r["done_ms"] is not None and r["done_ms"] - r["due_ms"] <= r["slo_ms"]
              for r in rows)
    done = [r["done_ms"] for r in rows if r["done_ms"] is not None]
    return {"requests": len(rows), "p90_ms": p90(rows),
            "attainment_pct": 100.0 * met / len(rows) if rows else None,
            "p90_first_half_ms": p90([r for r in rows if r["due_ms"] < half]),
            "p90_second_half_ms": p90([r for r in rows if r["due_ms"] >= half]),
            "drain_s": (max(done) / 1e3 - seconds) if done else None,
            "unanswered": sum(r["done_ms"] is None for r in rows)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rates", required=True, help="comma list of req/s per model")
    args = ap.parse_args(argv)
    bench = run.Benchmark(run.ROOT)
    run.use_compile_cache(run.ROOT)
    setup = run.build(bench, args.workload, args.seed)
    for r in setup.runners:
        print(json.dumps({"model": r.name, "L_ms": r.latency_ms,
                          "prefill_ms": r.prefill_ms, "decode_ms": r.decode_ms}),
              flush=True)
    models = [m["arch"] for m in setup.config["models"]]
    slo = {m["arch"]: m["slo_ms"] for m in setup.config["models"]}
    for k, rate in enumerate(float(x) for x in args.rates.split(",")):
        rates = {m: rate for m in models}
        spec = dict(setup.traffic, rates_req_s=rates, plan_rates_req_s=rates)
        caps, _, _, placement = run.plan(setup.runners, slo, rates, setup.devices[0])
        reqs, window, error, _ = run.serve_window(setup, args.seed + k, args.seconds,
                                                  None, spec, caps)
        rows = run.request_rows(reqs, window, spec["stop"])
        print(json.dumps({"rate_req_s": rate, "caps": caps,
                          "schedulable": placement.schedulable, "error": error,
                          **summarize(rows, args.seconds)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
