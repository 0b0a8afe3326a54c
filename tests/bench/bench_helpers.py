"""What the benchmark's CPU tests share: the repo root on ``sys.path``
(``import bench``) and a smoke-size copy of the benchmark."""
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

MIX = "chatglm3-6b_mamba2-780m"
SMOKE_SHAPES = {"prompt_lens": [16, 32], "gen_range": [4, 8], "batch_buckets": [1, 2, 4]}
SMOKE_SIZES = {
    "chatglm3-6b": dict(n_layers=2, d_model=256, vocab_size=1024,
                        padded_vocab_size=1024, n_heads=4, n_kv_heads=2,
                        d_head=64, d_ff=512),
    "mamba2-780m": dict(n_layers=2, d_model=256, vocab_size=1024,
                        padded_vocab_size=1024, ssm_d_state=32, ssm_headdim=32),
}
#: Widest served-token gap the smoke cells allow: the bf16 program reads
#: about 0.01 here, the float8 control 0.2-0.4.
SMOKE_GAP_LIMIT = 0.05


def smoke_config() -> dict:
    with open(os.path.join(ROOT, "bench", "configs", MIX + ".json")) as f:
        cfg = json.load(f)
    cfg["name"] = "smoke"
    cfg["shapes"] = dict(SMOKE_SHAPES)
    for m in cfg["models"]:
        m["sizes"].update(SMOKE_SIZES[m["arch"]])
        m["checks"] = {k: SMOKE_GAP_LIMIT for k in m["checks"]}
    return cfg


def write_smoke_bench(root: str) -> None:
    """A copy of the benchmark under ``root`` with two more cells at smoke
    size: ``smoke-steady`` (Poisson, drained) and ``smoke-backlog``."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "bench", "configs", "smoke.json"), "w") as f:
        json.dump(smoke_config(), f)
    lens = {"prompt_lens": [16, 32], "gen_range": [4, 8]}
    rates = {"chatglm3-6b": 4.0, "mamba2-780m": 4.0}
    cells = {
        "smoke-steady": dict(schedule_seed=1, arrivals="poisson", rates_req_s=rates,
                             plan_rates_req_s=rates, stop="drain", **lens),
        "smoke-backlog": dict(schedule_seed=1, arrivals="backlog",
                              backlog={"chatglm3-6b": 400, "mamba2-780m": 400},
                              plan_rates_req_s=rates, stop="window", **lens),
    }
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, traffic in cells.items():
        with open(os.path.join(root, "bench", "workloads", name + ".json"), "w") as f:
            json.dump(traffic, f)
        bench["workloads"].append({"name": name, "config": "smoke",
                                   "traffic": name, "chips": 1, "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        wl = m.get("workloads")
        if wl and "mix-steady" in wl:
            wl.append("smoke-steady")
        if wl and "yi9b-tp4-saturate" in wl:
            wl.append("smoke-backlog")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def steer_to_cpu(monkeypatch) -> None:
    """Let the harness serve on the CPU: its look for a chip and its table
    of peaks are steered here, in the test."""
    import jax

    from bench import peaks, run
    monkeypatch.setattr(run, "accelerator_devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"bf16_flops": 1e12, "hbm_bytes_s": 1e11,
                                             "hbm_bytes": 1e10})


#: The stub loop's virtual costs: forming a batch, and each of its tokens.
FORM_MS, TOKEN_MS = 2.0, 10.0


def stub_replay(runners, reqs, caps):
    """A replay loop that never calls ``ModelRunner.generate``, on a virtual
    clock: each request alone, in order of due time, formed in ``FORM_MS``
    from the later of its due time and the previous batch's end, then
    ``TOKEN_MS`` a token; its tokens are zeros.  It logs in the program's
    recorder as ``serve.replay`` does, a batch's form right before the
    batch (where the harness's hook runs)."""
    import numpy as np

    from repro.obs.served import RECORDER
    from repro.obs.spans import BatchSpan, HostSpan, ServedBatch

    log = RECORDER.open_window(reqs)
    try:
        t = 0.0
        for i, r in enumerate(reqs):
            t = log.admitted_ms[i] = max(t, r.arrival_ms)
            launch = t + FORM_MS
            log.forms.append(HostSpan("serve.form", t, launch))
            t = launch + TOKEN_MS * r.max_new
            bid = len(log.batches)
            log.batches.append(ServedBatch(
                BatchSpan("batch", 0, 0, launch, t, r.model, 1), bid,
                len(r.prompt), r.max_new, (i,), launch, launch + TOKEN_MS, t))
            r.output, r.completion_ms = np.zeros(r.max_new, np.int32), t
            log.batch[i], log.done_ms[i] = bid, t
    finally:
        log.open = False


def stub_launches(reqs) -> list[float]:
    """Each batch's launch under ``stub_replay``, served in full."""
    out, t = [], 0.0
    for r in reqs:
        out.append(max(t, r.arrival_ms) + FORM_MS)
        t = out[-1] + TOKEN_MS * r.max_new
    return out
