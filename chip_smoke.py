#!/usr/bin/env python3
"""Serve a model mix on the local TPU and check what comes out.

Default (one chip): chatglm3-6b and mamba2-780m, at full published width and
depth with random weights from a seed, are resident together on the chip
and answer 16 requests through ``repro.launch.serve`` (prefill plus greedy
decode, measured L(b), Elastic Partitioning, seeded Poisson replay).  It
prints, per model, parameter bytes, compile seconds, the measured L(b),
requests served and tokens generated; the device's peak bytes in use; and
these checks with their largest differences:

  * every logit the server computed is finite;
  * greedy tokens from prefill plus decode match a teacher-forced
    ``forward`` over the same sequence, and the logits at those positions
    agree within ``serve.logit_tol`` of the largest reference |logit|
    (0.1, or 0.5 for a model with recurrent state);
  * chatglm3-6b with ``kernel_impl="pallas"`` (the Pallas TPU kernels)
    agrees with ``"jnp"`` within the same bound.

``--chips 4`` runs only the four-chip phase, in this one process: yi-9b at
full depth, tensor parallel on a (1, 4) ("data", "model") mesh, serves a
few requests; an 8-layer cut of yi-9b (same widths) on one device agrees
with the same cut on the four-chip mesh.  It prints each device's parameter
bytes and peak bytes in use.

It exits non-zero when JAX finds no TPU, when a check fails and when any
phase raises.  The last line of its output is one JSON object naming the
device.

Run:  python3 chip_smoke.py [--chips 4]
"""
import argparse
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.mesh import make_serving_mesh  # noqa: E402

SEED = 0
MODELS = ("chatglm3-6b", "mamba2-780m")
PALLAS_MODEL = "chatglm3-6b"
KERNEL_IMPL = "pallas"
N_REQUESTS = 16
SHARDED_MODEL = "yi-9b"
SHARDED_SHAPES = serve.ServeShapes(prompt_lens=(128, 256), batch_buckets=(1, 2))
SHARDED_REQUESTS = 4
CUT_LAYERS = 8


def check_prompt(runner: serve.ModelRunner, shapes: serve.ServeShapes):
    rng = np.random.default_rng(SEED)
    return rng.integers(0, runner.cfg.vocab_size, shapes.prompt_lens[0],
                        dtype=np.int32)


def report(name: str, check: dict) -> bool:
    print(f"check {name}: " + " ".join(f"{k}={v}" for k, v in check.items()))
    return check["ok"] and check["finite"]


def peak_bytes(devices) -> None:
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"device {d.id} {d.device_kind}: peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use', 'not reported')}")


def one_chip(cfgs, shapes: serve.ServeShapes, n_requests: int) -> bool:
    """Serve the mix on one device, then check it against references."""
    result = serve.run(cfgs, shapes=shapes, n_requests=n_requests, seed=SEED)
    serve.print_run(result)
    ok = all(r.output is not None and len(r.output) == r.max_new
             for r in result.requests)
    print(f"check every request answered: {ok}")
    n_new = shapes.gen_range[0]
    for runner in result.runners:
        prompt = check_prompt(runner, shapes)
        gen = runner.generate(prompt[None], n_new)
        ok &= report(f"{runner.name} prefill+decode vs forward",
                     serve.check_against_forward(runner, prompt, gen))
        if runner.cfg.name.startswith(PALLAS_MODEL):
            pallas = serve.ModelRunner(
                dataclasses.replace(runner.cfg, kernel_impl=KERNEL_IMPL),
                runner.mesh, shapes, SEED, params=runner.params)
            pallas.compile(batches=(1,), prompt_lens=(len(prompt),))
            print(f"model {runner.name} {KERNEL_IMPL}: "
                  f"compile_s={pallas.compile_s}")
            ok &= report(f"{runner.name} {KERNEL_IMPL} vs jnp",
                         serve.prefix_agreement(
                             pallas.generate(prompt[None], n_new), gen,
                             serve.logit_tol(runner.cfg)))
    peak_bytes(jax.devices()[:1])
    return ok


def four_chips(cfg, shapes: serve.ServeShapes, n_requests: int) -> bool:
    """Serve ``cfg`` tensor parallel on four devices, then check a depth
    cut of it on the mesh against the same cut on one device."""
    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, JAX found {len(devices)}")
    mesh = make_serving_mesh(devices)
    result = serve.run([cfg], shapes=shapes, n_requests=n_requests,
                       seed=SEED, mesh=mesh)
    serve.print_run(result)
    ok = all(r.output is not None and len(r.output) == r.max_new
             for r in result.requests)
    print(f"check every request answered: {ok}")
    runner = result.runners[0]
    held = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(runner.params):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    for d, n in held.items():
        print(f"device {d.id}: param_bytes={n} "
              f"share={n / runner.param_bytes}")
    spread = max(held.values()) < 0.3 * runner.param_bytes
    print(f"check parameters spread over the chips: {spread}")
    ok &= spread
    del result, runner

    cut = dataclasses.replace(cfg, n_layers=CUT_LAYERS)
    gens = {}
    for name, m in (("1 device", make_serving_mesh(devices[:1])),
                    ("4 devices", mesh)):
        r = serve.ModelRunner(cut, m, shapes, SEED)
        prompt = check_prompt(r, shapes)
        r.compile(batches=(1,), prompt_lens=(len(prompt),))
        gens[name] = r.generate(prompt[None], shapes.gen_range[0])
        print(f"model {cut.name} {CUT_LAYERS} layers on {name}: "
              f"compile_s={r.compile_s} tokens={gens[name].tokens[0].tolist()}")
        del r
    ok &= report(f"{cut.name} {CUT_LAYERS}-layer cut, 4 devices vs 1",
                 serve.prefix_agreement(gens["4 devices"], gens["1 device"],
                                        serve.logit_tol(cut)))
    peak_bytes(devices)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    serve.use_compile_cache(HERE)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 1
    if args.chips == 4:
        ok = four_chips(get_config(SHARDED_MODEL), SHARDED_SHAPES,
                        SHARDED_REQUESTS)
    else:
        ok = one_chip([get_config(m) for m in MODELS], serve.ServeShapes(),
                      N_REQUESTS)
    if not ok:
        print("chip smoke FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
