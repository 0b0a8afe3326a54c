#!/usr/bin/env python3
"""Record the small profiler trace that ``tests/bench`` reads, on the chip.

    python3 tests/bench/data/record_trace.py --chips 1 --out <dir>

Two-layer models of chatglm3-6b's and mamba2-780m's families at a small
width, on a (1, chips) serving mesh with weights from ``bench.weights``,
serve six requests through ``serve.replay`` (64-token prompts, 4 tokens
out, batches of up to 2, arrivals spread over 300 ms so that the loop
sleeps between them).  The harness's hook (``run.Watch.on_launch``)
starts the profiler at the first batch 1 ms or more into the window, as
in a run: the trace holds the harness's ``window`` span and the
program's ``serve.sleep``, ``serve.form`` and ``serve.batch/<model>``.
The ``.xplane.pb`` lands under ``--out``, beside ``window.json``: the
program's window log (its clock anchor, sleeps, forms and batches).
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import run, weights  # noqa: E402

SIZES = {
    "chatglm3-6b": {"family": "transformer", "n_layers": 2, "d_model": 512,
                    "vocab_size": 1024, "padded_vocab_size": 1024, "n_heads": 8,
                    "n_kv_heads": 4, "d_head": 64, "d_ff": 1024,
                    "rope_theta": 10000.0, "norm_eps": 1e-06},
    "mamba2-780m": {"family": "mamba2", "n_layers": 2, "d_model": 512,
                    "vocab_size": 1024, "padded_vocab_size": 1024,
                    "ssm_d_state": 32, "ssm_headdim": 64, "ssm_expand": 2,
                    "conv_kernel": 4, "norm_eps": 1e-06},
}
#: (model, due ms) of each request.
ARRIVALS = [("chatglm3-6b", 0.0), ("mamba2-780m", 0.0), ("chatglm3-6b", 40.0),
            ("chatglm3-6b", 45.0), ("mamba2-780m", 150.0), ("chatglm3-6b", 300.0)]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from repro.launch import serve
    from repro.launch import sharding as shr
    from repro.launch.mesh import make_serving_mesh
    from repro.models.model import Model
    from repro.obs.served import RECORDER

    mesh = make_serving_mesh(run.accelerator_devices(args.chips))
    shapes = serve.ServeShapes(prompt_lens=(64,), gen_range=(4, 4),
                               batch_buckets=(1, 2))
    runners = []
    for i, (arch, sizes) in enumerate(SIZES.items()):
        cfg = run.program_config({"arch": arch, "sizes": sizes})
        sh = shr.param_shardings(cfg, Model(cfg).param_shapes(), mesh, fsdp=False)
        params = weights.make(sizes, weights.root_key(0, i), sh)
        runners.append(serve.ModelRunner(cfg, mesh, shapes, 0, params=params))
        runners[-1].compile()
    rng = np.random.default_rng(0)
    reqs = [serve.ServeRequest(m, t, 1e4, rng.integers(0, 1024, 64, np.int32), 4)
            for m, t in ARRIVALS]
    watch = run.Watch(RECORDER, float("inf"), args.out, trace_from_ms=1.0)
    try:
        with watch.on_launch():
            serve.replay(runners, reqs, {r.name: 2 for r in runners})
    finally:
        watch.stop_trace()
    w = RECORDER.window
    log = {"wall_ns": w.wall_ns, "traced_from": watch.traced_from,
           "sleeps": [list(s[1:]) for s in w.sleeps],
           "forms": [list(f[1:]) for f in w.forms],
           "batches": [[b.span.model, b.span.launch_ms, b.span.done_ms]
                       for b in w.batches]}
    with open(os.path.join(args.out, "window.json"), "w") as f:
        json.dump(log, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
