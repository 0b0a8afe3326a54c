#!/usr/bin/env python3
"""Readings for a cell's correctness limits: the program's and the control's.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 20

For each seed, in one process: set-up and a window at the cell's own load
(long enough to finish its longest requests), exactly as ``run.py`` does;
then, per model, over the same sample of finished requests, the widest gap
of the served tokens below the float32 reference's best logit, and the
same reading for the low-precision control: the reference computed in
float8 e4m3 put in the program's place, reading at each position the token
the float8 forward puts first.  One JSON line per seed and model.  A limit
lies above the program's readings and below the control's
(``PERF.md`` gives the readings each limit was set from).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import check, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma list")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    bench = run.Benchmark(run.ROOT)
    run.use_compile_cache(run.ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        setup = run.build(bench, args.workload, seed)
        reqs, _, error, _ = run.serve_window(setup, seed, args.seconds, None)
        run.free(setup)
        readings = run.correctness(setup, reqs, seed, control=True)
        for model, g in readings.items():
            row = {"seed": seed, "model": model, "error": error,
                   "positions": g["positions"], "requests": g["requests"]}
            for side in ("served", "control"):
                row[side] = {n: check.number(g[side], n) for n in check.NUMBERS}
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
