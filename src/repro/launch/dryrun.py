import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

The two lines above MUST stay the first statements in this file: jax locks
the device count at first init, and the production meshes need 512 host
placeholder devices.  Nothing here allocates a real array — inputs are
ShapeDtypeStructs and the compile is pure analysis.

Per combo this records:
  * memory_analysis (bytes per device: args/outputs/temps) — proves it fits;
  * cost_analysis FLOPs / bytes — the compute & memory roofline terms;
  * collective bytes parsed from the compiled SPMD HLO — the collective
    roofline term (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute result sizes, i.e. bytes landing per device);
  * roofline seconds per term on TPU v5e constants, the dominant term, and
    MODEL_FLOPS / HLO_FLOPs.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  python -m repro.launch.dryrun --all --out results/dryrun.jsonl
  python -m repro.launch.dryrun --all --multi-pod --out results/dryrun_mp.jsonl
"""
import argparse
import json
import re
import time
import traceback

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.core.hardware import TPU_V5E
from repro.launch import sharding as shr
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import INPUT_SHAPES, applicable, input_specs
from repro.models.model import Model
from repro.training.optim import OptimConfig, adamw_init
from repro.training.train import make_train_step

COLLECTIVE_RE = re.compile(
    r"=\s*((?:\([^)]*\)|[a-z0-9_]+\[[0-9,]*\][^ ]*))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|f8\w*|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([0-9,]*)\]")
DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
               "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1}


CONVERT_RE = re.compile(r"=\s*(f32\[[0-9,]*\])[^\n]*? convert\(")
COMPUTATION_RE = re.compile(r"^(%?[\w\.\-]+)[^\n]*\{", re.M)


def bf16_convert_bytes(hlo_text: str) -> float:
    """f32 result bytes of top-level convert ops (CPU bf16->f32 upcasts).

    The CPU backend materializes an f32 copy of every bf16 tensor before a
    dot; a TPU reads bf16 natively into f32 accumulators.  Each such convert
    inflates 'bytes accessed' by ~2x its result size (write + re-read).
    Only converts in non-fused computations are counted — fusion-internal
    ones never touch memory.
    """
    total = 0.0
    in_fusion = False
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and "(" in stripped:
            name = stripped.split()[0]
            in_fusion = "fused" in name or "region" in name
            continue
        if in_fusion:
            continue
        m = CONVERT_RE.search(line)
        if m:
            dims = m.group(1)[4:-1]
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            total += n * 4
    return total


def collective_stats(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in the per-device HLO."""
    by_kind: dict[str, float] = {}
    counts: dict[str, int] = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        size = 0
        for sm in SHAPE_RE.finditer(type_str):
            dt, dims = sm.group(1), sm.group(2)
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            size += n * DTYPE_BYTES.get(dt.split("[")[0][:4].rstrip("["), 4)
        by_kind[kind] = by_kind.get(kind, 0) + size
        counts[kind] = counts.get(kind, 0) + 1
    return {"bytes_by_kind": by_kind, "counts": counts,
            "total_bytes": sum(by_kind.values())}


def model_flops(cfg, shape_name: str) -> float:
    """Useful ("model") FLOPs per step: 6*N*D train, 2*N*D forward."""
    info = INPUT_SHAPES[shape_name]
    n_active = cfg.active_param_count()
    tokens = info["global_batch"] * (
        info["seq_len"] if info["kind"] in ("train", "prefill") else 1)
    mult = 6.0 if info["kind"] == "train" else 2.0
    return mult * n_active * tokens


def _build_step(cfg, shape_name: str, mesh, *, fsdp_override=None):
    """Builds (jitted_fn, args, kind) for one config on one mesh."""
    import dataclasses as _dc

    from repro.models.shard_ctx import set_mesh_context
    model = Model(cfg)
    kind, specs = input_specs(cfg, shape_name)
    # batched decode prefers GSPMD's own activation layout (§Perf C3 vs A3);
    # train/prefill/long-decode need the pins (remat batch replication).
    shape_kind = INPUT_SHAPES[shape_name]["kind"]
    set_mesh_context(mesh, shr.dp_axes(mesh),
                     pin_activations=(shape_kind != "decode"))
    params_shapes = model.param_shapes()
    fsdp = (kind == "train") or cfg.fsdp_serving
    if fsdp_override is not None:
        fsdp = fsdp_override
    p_sh = shr.param_shardings(cfg, params_shapes, mesh, fsdp=fsdp)
    dp = shr.dp_axes(mesh)
    rep = NamedSharding(mesh, P())

    def logits_sharding(batch_dim: int):
        spec = [None, None, "model"]
        if dp and batch_dim % mesh.shape[dp[0]] == 0:
            spec[0] = dp
        return NamedSharding(mesh, P(*spec))

    if kind == "train":
        (batch,) = specs
        opt_shapes = jax.eval_shape(adamw_init, params_shapes)
        opt_sh = shr.opt_shardings(p_sh, mesh)
        b_sh = shr.batch_shardings(cfg, batch, mesh)
        step = make_train_step(model, OptimConfig())
        metrics_sh = {"loss": rep, "grad_norm": rep, "lr": rep}
        fn = jax.jit(step, in_shardings=(p_sh, opt_sh, b_sh),
                     out_shardings=(p_sh, opt_sh, metrics_sh))
        args = (params_shapes, opt_shapes, batch)
    elif kind == "encode":
        (batch,) = specs
        b_sh = shr.batch_shardings(cfg, batch, mesh)
        bdim = next(iter(batch.values())).shape[0]

        def encode(params, b):
            logits, _ = model.forward(params, b)
            return logits

        fn = jax.jit(encode, in_shardings=(p_sh, b_sh),
                     out_shardings=logits_sharding(bdim))
        args = (params_shapes, batch)
    elif kind == "prefill":
        batch, cache = specs
        b_sh = shr.batch_shardings(cfg, batch, mesh)
        c_sh = shr.cache_shardings(cfg, cache, mesh)
        bdim = next(iter(batch.values())).shape[0]
        fn = jax.jit(model.prefill, in_shardings=(p_sh, b_sh, c_sh),
                     out_shardings=(logits_sharding(bdim), c_sh))
        args = (params_shapes, batch, cache)
    else:  # decode
        cache, tokens = specs
        c_sh = shr.cache_shardings(cfg, cache, mesh)
        t_sh = shr.batch_shardings(cfg, {"tokens": tokens}, mesh)["tokens"]
        bdim = tokens.shape[0]
        fn = jax.jit(model.decode_step, in_shardings=(p_sh, c_sh, t_sh),
                     out_shardings=(logits_sharding(bdim), c_sh))
        args = (params_shapes, cache, tokens)
    return fn, args, kind, fsdp


def _cost_record(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    coll = collective_stats(txt)
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    conv = bf16_convert_bytes(txt)
    # TPU-corrected bytes: strip the CPU backend's bf16->f32 upcast copies
    # (write + re-read per convert); floor guards against parser drift.
    bytes_tpu = max(raw_bytes - 2.0 * conv, raw_bytes / 4.0)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": bytes_tpu,
        "bytes_raw": raw_bytes,
        "convert_bytes": conv,
        "coll_bytes": float(coll["total_bytes"]),
        "coll_by_kind": coll["bytes_by_kind"],
        "coll_counts": coll["counts"],
    }


def _combine(records_and_weights) -> dict:
    """Weighted sum of cost records."""
    out = {"flops": 0.0, "bytes": 0.0, "bytes_raw": 0.0, "convert_bytes": 0.0,
           "coll_bytes": 0.0, "coll_by_kind": {}, "coll_counts": {}}
    for rec, w in records_and_weights:
        out["flops"] += w * rec["flops"]
        out["bytes"] += w * rec["bytes"]
        out["bytes_raw"] += w * rec.get("bytes_raw", rec["bytes"])
        out["convert_bytes"] += w * rec.get("convert_bytes", 0.0)
        out["coll_bytes"] += w * rec["coll_bytes"]
        for k, v in rec["coll_by_kind"].items():
            out["coll_by_kind"][k] = out["coll_by_kind"].get(k, 0) + w * v
        for k, v in rec["coll_counts"].items():
            out["coll_counts"][k] = out["coll_counts"].get(k, 0) + w * v
    return out


def analysis_costs(cfg, shape_name: str, mesh, *, fsdp_override=None) -> dict:
    """Exact per-device cost via reduced-depth *unrolled* compiles.

    XLA's HloCostAnalysis counts a while-loop body once, so the production
    (scanned) executable under-reports per-layer work by the trip count.  We
    compile fully-unrolled reduced-depth variants and extrapolate linearly in
    depth — exact, because layers are identical:

      homogeneous:  C(L) = base + L*layer      (2-point: L=2, 4)
      hybrid 1:2:   C(L) = base + n_rec*rec + n_attn*attn   (3-point: 2,3,6)
    """
    import dataclasses as _dc

    def compile_cost(n_layers: int) -> dict:
        c = _dc.replace(cfg, n_layers=n_layers, analysis_unroll=True)
        fn, args, _, _ = _build_step(c, shape_name, mesh,
                                     fsdp_override=fsdp_override)
        return _cost_record(fn.lower(*args).compile())

    total = cfg.n_layers
    if cfg.arch_type == "hybrid":
        c2, c3, c6 = compile_cost(2), compile_cost(3), compile_cost(6)
        attn = {}
        kinds = cfg.layer_types()
        n_attn = sum(1 for k in kinds if k == "attn")
        n_rec = total - n_attn
        attn_cost = _combine([(c3, 1.0), (c2, -1.0)])
        rec_cost = _combine([(c6, 0.5), (c3, -1.0), (c2, 0.5)])
        base = _combine([(c2, 1.0), (rec_cost, -2.0)])
        return _combine([(base, 1.0), (rec_cost, n_rec), (attn_cost, n_attn)])
    if total <= 4:
        return compile_cost(total)
    ca, cb = compile_cost(2), compile_cost(4)
    layer = _combine([(cb, 0.5), (ca, -0.5)])
    base = _combine([(ca, 1.0), (layer, -2.0)])
    return _combine([(base, 1.0), (layer, total)])


def optimal_model_axis(cfg, shape_name: str) -> int:
    """Best (data, model) factorization of the pod for this combo (§Perf).

    Heads (train/prefill) or KV heads (decode) must divide the model axis or
    GSPMD replicates attention work / falls back to contracting-dim cache
    shards with per-layer full-logits psums.  Pure-SSM archs keep 16.
    """
    kind = INPUT_SHAPES[shape_name]["kind"]
    if cfg.arch_type == "ssm":
        return 16
    if kind == "decode_long":
        # batch-1 windowed decode: the tiny ring cache makes the GQA psum
        # negligible while weight sharding dominates — keep the full 16.
        return 16
    if cfg.arch_type == "moe" and kind.startswith("decode"):
        # expert-parallel decode: narrowing the model axis multiplies the
        # per-device expert weight reads/gathers — keep 16 (measured: 32x8
        # was 2x worse for arctic decode).
        return 16
    if cfg.arch_type == "hybrid":
        # LRU width wants wide TP; only training's batch (256) tolerates the
        # dp=128 that heads=10 -> model=2 implies.  Measured: train 31x
        # better at 128x2, prefill 5x worse (batch 32 < dp floor).
        return 2 if kind == "train" else 16
    key_dim = cfg.n_kv_heads if kind.startswith("decode") else cfg.n_heads
    for m in (16, 8, 4, 2):
        if key_dim % m == 0:
            return m
    return 16  # replicate attention; everything else still shards


def optimal_fsdp(cfg, shape_name: str):
    """§Perf C3: dense/VLM decode wants 2D weight sharding (d_model over
    data) — weight reads /dp at the cost of tiny per-layer psums."""
    if (INPUT_SHAPES[shape_name]["kind"] == "decode"
            and cfg.arch_type in ("dense", "vlm")):
        return True
    return None


def lower_combo(arch_id: str, shape_name: str, *, multi_pod: bool,
                fsdp_override: bool | None = None,
                model_axis: int | None = None):
    """Build + lower + compile one combination.  Returns a result record.

    ``model_axis`` re-factorizes the same chips into (chips/model_axis,
    model_axis) — a perf knob (e.g. GQA decode wants model_axis = n_kv_heads
    so kv heads shard without the contracting-dim fallback).
    """
    cfg = get_config(arch_id)
    ok, why = applicable(cfg, shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if model_axis is not None:
        n = 512 if multi_pod else 256
        mesh_name = f"{n // model_axis}x{model_axis}"
        if multi_pod:
            mesh_name = "2x" + f"{256 // model_axis}x{model_axis}"
    rec = dict(arch=arch_id, shape=shape_name, mesh=mesh_name)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    if model_axis is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    else:
        n = 512 if multi_pod else 256
        if multi_pod:
            mesh = jax.make_mesh((2, 256 // model_axis, model_axis),
                                 ("pod", "data", "model"),
                                 devices=jax.devices()[:n],
                                 axis_types=(AxisType.Auto,) * 3)
        else:
            mesh = jax.make_mesh((n // model_axis, model_axis),
                                 ("data", "model"),
                                 devices=jax.devices()[:n],
                                 axis_types=(AxisType.Auto,) * 2)

    # 1) production compile (scan-over-layers): THE lowering proof + memory.
    fn, args, kind, fsdp = _build_step(cfg, shape_name, mesh,
                                       fsdp_override=fsdp_override)
    t0 = time.time()
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            if hasattr(ma, attr):
                mem[attr] = int(getattr(ma, attr))
    except Exception as e:  # pragma: no cover - backend dependent
        mem["error"] = str(e)
    raw = _cost_record(compiled)
    del compiled, lowered

    # 2) analysis compiles (reduced depth, unrolled): exact roofline counts.
    cost = analysis_costs(cfg, shape_name, mesh, fsdp_override=fsdp_override)

    n_chips = 512 if multi_pod else 256
    # cost_analysis of the SPMD executable reports the per-device module.
    acc = TPU_V5E
    flops, bytes_acc = cost["flops"], cost["bytes"]
    compute_s = flops / (acc.peak_tflops * 1e12) if flops > 0 else -1
    memory_s = bytes_acc / (acc.hbm_gbs * 1e9) if bytes_acc > 0 else -1
    collective_s = cost["coll_bytes"] / (acc.ici_gbs * 1e9)
    mf = model_flops(cfg, shape_name)
    terms = dict(compute_s=compute_s, memory_s=memory_s,
                 collective_s=collective_s)
    dominant = max((v, k) for k, v in terms.items())[1]
    rec.update(
        status="ok", step_kind=kind, fsdp=fsdp,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        flops_per_device=flops, bytes_per_device=bytes_acc,
        collective={"total_bytes": cost["coll_bytes"],
                    "bytes_by_kind": cost["coll_by_kind"],
                    "counts": {k: round(v, 1) for k, v in
                               cost["coll_counts"].items()}},
        scanned_raw=raw, memory=mem,
        roofline=dict(
            **{k: (round(v, 6) if v >= 0 else v) for k, v in terms.items()},
            dominant=dominant,
            model_flops_global=mf,
            model_flops_per_chip=mf / n_chips,
            useful_flop_ratio=(mf / n_chips / flops) if flops > 0 else -1,
        ),
    )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--model-axis", type=int, default=None,
                    help="re-factorize the chips as (chips/N, N) data x model")
    ap.add_argument("--optimized", action="store_true",
                    help="per-combo optimal model axis (see §Perf)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--force", action="store_true",
                    help="recompute combos already present in --out")
    args = ap.parse_args()

    done = set()
    if args.out and os.path.exists(args.out) and not args.force:
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    combos = []
    if args.all:
        for a in ARCH_IDS:
            for s in INPUT_SHAPES:
                combos.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    out_f = open(args.out, "a") if args.out else None
    n_ok = n_skip = n_fail = 0
    for arch_id, shape_name in combos:
        try:
            ma = args.model_axis
            fo = None
            if args.optimized:
                cfg_ = get_config(arch_id)
                if ma is None:
                    ma = optimal_model_axis(cfg_, shape_name)
                fo = optimal_fsdp(cfg_, shape_name)
            n = 512 if args.multi_pod else 256
            mesh_name = ("2x16x16" if args.multi_pod else "16x16") if ma is None \
                else f"{n // ma}x{ma}"
            if (arch_id, shape_name, mesh_name) in done:
                print(f"[cached] {arch_id} x {shape_name} x {mesh_name}")
                continue
            print(f"[dryrun] {arch_id} x {shape_name} x {mesh_name} ...",
                  flush=True)
            rec = lower_combo(arch_id, shape_name, multi_pod=args.multi_pod,
                              model_axis=ma, fsdp_override=fo)
        except Exception as e:
            rec = dict(arch=arch_id, shape=shape_name, mesh=mesh_name,
                       status="error", error=str(e)[-2000:],
                       traceback=traceback.format_exc()[-4000:])
        if rec["status"] == "ok":
            n_ok += 1
            r = rec["roofline"]
            print(f"  ok: compile={rec['compile_s']}s "
                  f"flops/dev={rec['flops_per_device']:.3g} "
                  f"dominant={r['dominant']} "
                  f"terms=({r['compute_s']:.4g}, {r['memory_s']:.4g}, "
                  f"{r['collective_s']:.4g})s", flush=True)
        elif rec["status"] == "skipped":
            n_skip += 1
            print(f"  skipped: {rec['reason']}")
        else:
            n_fail += 1
            print(f"  ERROR: {rec['error'][:500]}")
        if out_f:
            out_f.write(json.dumps(rec) + "\n")
            out_f.flush()
        else:
            print(json.dumps(rec, indent=2))
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    if out_f:
        out_f.close()
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
