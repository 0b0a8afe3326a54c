"""CPU rehearsal of the multi-model server, at smoke size.

Three reduced architectures (dense GQA, MoE, SSM) go through the same path
as ``python -m repro.launch.serve`` on the chip: parameters initialised
under jit, prefill and decode compiled per batch bucket, L(b) measured,
Elastic Partitioning over the local device, and a seeded Poisson trace
answered with greedy prefill plus decode.  Timings here are CPU wall time.

Run:  PYTHONPATH=src python examples/serve_multimodel.py [--requests 12]
"""
import argparse

from repro.configs import get_smoke_config
from repro.launch import serve

ARCHS = ("yi-9b", "deepseek-moe-16b", "mamba2-780m")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    result = serve.run([get_smoke_config(a) for a in ARCHS],
                       shapes=serve.SMOKE_SHAPES, n_requests=args.requests,
                       seed=args.seed)
    serve.print_run(result)
    assert all(r.output is not None for r in result.requests), "requests lost"


if __name__ == "__main__":
    main()
