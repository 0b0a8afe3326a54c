"""Whole runs of the harness at smoke size on the CPU, with its look for a
chip steered in the test."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_helpers import ROOT

from bench import run

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_a_smoke_run_prints_the_contracts_keys(smoke_root, on_cpu, capsys):
    result = run.run(smoke_root, "smoke-steady", 2**33 + 1, 3.0, trace=False)
    run.emit(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == CONTRACT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 24            # 4 req/s for 3 s, each model
    assert set(line["metrics"]) == {"setup_s", "latency_p90_ms", "slo_attainment_pct"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert list(line["checks"]) == ["failed", "gap.chatglm3-6b",
                                   "gap_mean.mamba2-780m"]
    assert err.strip().splitlines()[-1].startswith("check gap_mean.mamba2-780m ")


def test_the_backlog_cell_stops_at_the_end_of_its_window(smoke_root, on_cpu):
    setup = run.build(run.Benchmark(smoke_root), "smoke-backlog", 3)
    reqs, window, error, _ = run.serve_window(setup, 3, 2.0, None)
    rows = run.request_rows(reqs, window, "window")
    batches = run.batch_rows(reqs, window)
    assert error is None and batches
    assert all(b["launch_ms"] < 2e3 for b in batches)
    assert max(b["done_ms"] for b in batches) < 2e3 + 1e3
    assert 0 < len(rows) < len(reqs) == 800
    assert len(rows) == sum(b["size"] for b in batches)
    assert all(r["served"] == r["max_new"] for r in rows)
    record = run.record_of(setup, rows, batches, 2.0, 1.0, None)
    bench = setup.bench
    assert bench.read("throughput_tok_s", record) > 0
    assert bench.read("batch_size_mean", record) == pytest.approx(
        len(rows) / len(batches))
    assert 0 <= bench.read("decode_waste_pct", record) < 100
    assert 0 < bench.read("mfu", record) < 100


def test_run_fails_with_no_tpu(smoke_root):
    """The harness's own look for a chip, unsteered, finds the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "mix-steady", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_fails_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mix-steady", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
