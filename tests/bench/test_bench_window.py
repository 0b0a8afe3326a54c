"""The harness reads a served window from the program's recorder alone.

A stub loop (``bench_helpers.stub_replay``) stands in for ``serve.replay``:
it logs in the recorder on a virtual clock and never calls
``ModelRunner.generate``.  The harness's hook then refuses the batch due
after the window's deadline and starts the trace, and its rows and batch
readers give what the stub's log says.
"""
import statistics
import types

import jax
import pytest
from jax.profiler import ProfileData

from bench_helpers import TOKEN_MS, stub_launches, stub_replay

from bench import run, trace
from bench.metrics._common import nearest_rank
from bench.metrics._flops import window_flops
from repro.launch import serve
from repro.obs import served

#: The scheduler's L(b) in the stub set-up, for every batch.
PREDICTED_MS = 40.0


class FixedLatency:
    def latency_ms(self, profile, batch, share):
        return PREDICTED_MS


def stub_setup(root: str, cell: str) -> run.Setup:
    """A set-up with no model built: the stub loop serves the window."""
    bench = run.Benchmark(root)
    config = bench.config("smoke")
    runners = [types.SimpleNamespace(name=m["arch"], prefill_ms={1: 5.0},
                                     decode_ms={1: 1.0}, latency_ms={1: PREDICTED_MS})
               for m in config["models"]]
    return run.Setup(bench, config, bench.traffic(bench.cell(cell).traffic),
                     jax.devices()[:1], runners, {r.name: 1 for r in runners},
                     FixedLatency(), {r.name: None for r in runners})


@pytest.fixture
def stub(monkeypatch, on_cpu):
    monkeypatch.setattr(serve, "replay", stub_replay)


def test_a_window_served_without_generate_gives_the_rows_of_its_log(smoke_root, stub):
    setup = stub_setup(smoke_root, "smoke-steady")
    reqs, w, error, traced_from = run.serve_window(setup, 7, 2.0, None)
    assert error is None and traced_from is None and not w.open
    launches = stub_launches(reqs)
    dones = [t + TOKEN_MS * r.max_new for t, r in zip(launches, reqs)]
    rows = run.request_rows(reqs, w, "drain")
    assert len(rows) == len(reqs) == 16                 # 4 req/s, 2 s, 2 models
    assert [(r["batch_start_ms"], r["done_ms"], r["served"]) for r in rows] == [
        (t, d, q.max_new) for t, d, q in zip(launches, dones, reqs)]
    batches = run.batch_rows(reqs, w)
    assert batches == [
        {"id": k, "model": q.model, "launch_ms": t, "done_ms": d, "size": 1,
         "prompt_len": len(q.prompt), "steps": q.max_new, "request_ids": (k,),
         "max_new": [q.max_new]}
        for k, (q, t, d) in enumerate(zip(reqs, launches, dones))]
    record = run.record_of(setup, rows, batches, 2.0, 1.0, None)
    read = setup.bench.read
    assert read("queue_wait_p90_ms", record) == pytest.approx(
        nearest_rank([t - q.arrival_ms for t, q in zip(launches, reqs)], 0.9))
    assert read("sched_latency_err_pct", record) == pytest.approx(100 * statistics.median(
        abs(PREDICTED_MS - (d - t)) / (d - t) for t, d in zip(launches, dones)))
    busy_s = sum(d - t for t, d in zip(launches, dones)) / 1e3
    assert read("mfu_busy", record) == pytest.approx(
        100 * window_flops(record) / (busy_s * 1e12))
    assert read("latency_p90_ms", record) == pytest.approx(
        nearest_rank([d - q.arrival_ms for d, q in zip(dones, reqs)], 0.9))
    assert read("slo_attainment_pct", record) == 100.0


def test_the_hook_refuses_the_batch_due_after_the_deadline(smoke_root, stub):
    """``smoke-backlog`` (800 requests due at 0, ``stop: window``) for
    0.5 s: the batch whose launch falls at or after 500 ms never starts,
    and every request from it on goes unanswered."""
    setup = stub_setup(smoke_root, "smoke-backlog")
    reqs, w, error, _ = run.serve_window(setup, 7, 0.5, None)
    started = sum(t < 500.0 for t in stub_launches(reqs))
    assert error is None and not w.open and 0 < started < len(reqs) == 800
    assert len(w.batches) == started and len(w.forms) == started + 1
    assert [b.request_ids for b in w.batches] == [(k,) for k in range(started)]
    assert all(b.span.launch_ms < 500.0 and b.span.done_ms > b.span.launch_ms
               for b in w.batches)
    assert [i for i, b in enumerate(w.batch) if b >= 0] == list(range(started))
    assert all(r.output is None and r.completion_ms is None for r in reqs[started:])
    assert len(run.request_rows(reqs, w, "window")) == started
    drained = run.request_rows(reqs, w, "drain")
    assert sum(r["batch_start_ms"] is None for r in drained) == len(reqs) - started
    # the hook is cleared once the window has closed
    assert "open_window" not in vars(served.RECORDER) and type(w.forms) is list
    again = [serve.ServeRequest(r.model, 0.0, r.slo_ms, r.prompt, r.max_new)
             for r in reqs[:40]]
    stub_replay(setup.runners, again, setup.caps)
    assert all(r.output is not None for r in again)


@pytest.mark.parametrize("seconds", [2.0, 10.5])
def test_the_trace_starts_at_the_first_batch_at_or_after_trace_from(
        smoke_root, stub, tmp_path, seconds):
    """The window's last ``TRACE_S`` seconds: from the first batch launched
    at or after 500 ms in a 10.5 s window; before the window in a 2 s one."""
    setup = stub_setup(smoke_root, "smoke-backlog")
    reqs, w, error, traced_from = run.serve_window(setup, 7, seconds, str(tmp_path))
    trace_from_ms = (seconds - run.TRACE_S) * 1e3
    launches = [b.span.launch_ms for b in w.batches]
    first = next(k for k, t in enumerate(launches) if t >= trace_from_ms)
    assert error is None and traced_from == first
    assert (first == 0) == (trace_from_ms <= 0)
    data = ProfileData.from_file(trace.find(str(tmp_path)))
    spans = [e.name for p in data.planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events]
    assert spans.count("window") == 1


def test_a_run_served_without_generate_reports_every_end_to_end_metric(
        smoke_root, stub, monkeypatch):
    generate = serve.ModelRunner.generate

    def outside_the_window(self, prompts, n_new):
        w = served.RECORDER.window
        if w is not None and w.open:
            raise AssertionError("ModelRunner.generate called in the window")
        return generate(self, prompts, n_new)

    monkeypatch.setattr(serve.ModelRunner, "generate", outside_the_window)
    result = run.run(smoke_root, "smoke-steady", 2**33 + 5, 3.0, trace=False)
    e2e = {m.name for m in run.Benchmark(smoke_root).cell_metrics("smoke-steady", False)}
    assert set(result["metrics"]) == e2e == {"setup_s", "latency_p90_ms",
                                             "slo_attainment_pct"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["attempted"] == 24 and result["failed"] == 0
