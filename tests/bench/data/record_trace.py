#!/usr/bin/env python3
"""Record the small profiler trace that ``tests/bench`` reads, on the chip.

    python3 tests/bench/data/record_trace.py --chips <1|4> --out <dir>

A two-layer transformer of chatglm3-6b's family at a small width, on a
(1, chips) serving mesh with weights from ``bench.weights``, serves two
batches inside the harness's spans (``window``, ``generate/<model>``),
under ``jax.profiler``; the ``.xplane.pb`` lands under ``--out``.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import run, weights  # noqa: E402

SIZES = {"family": "transformer", "n_layers": 2, "d_model": 512,
         "vocab_size": 1024, "padded_vocab_size": 1024, "n_heads": 8,
         "n_kv_heads": 4, "d_head": 64, "d_ff": 1024, "rope_theta": 10000.0,
         "norm_eps": 1e-06}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from repro.launch import serve
    from repro.launch import sharding as shr
    from repro.launch.mesh import make_serving_mesh
    from repro.models.model import Model

    devices = run.accelerator_devices(args.chips)
    mesh = make_serving_mesh(devices)
    cfg = run.program_config({"arch": "chatglm3-6b", "sizes": SIZES})
    sh = shr.param_shardings(cfg, Model(cfg).param_shapes(), mesh, fsdp=False)
    params = weights.make(SIZES, weights.root_key(0, 0), sh)
    shapes = serve.ServeShapes(prompt_lens=(64,), gen_range=(4, 4),
                               batch_buckets=(1, 2))
    runner = serve.ModelRunner(cfg, mesh, shapes, 0, params=params)
    runner.compile()
    prompts = np.random.default_rng(0).integers(0, 1024, (2, 64), dtype=np.int32)
    jax.profiler.start_trace(args.out)
    with jax.profiler.TraceAnnotation("window"):
        for b in (1, 2):
            with jax.profiler.TraceAnnotation(f"generate/{cfg.name}"):
                runner.generate(prompts[:b], 4)
    jax.profiler.stop_trace()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
