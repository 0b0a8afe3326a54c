"""The served path's recorder (``repro.obs.served``) at smoke size on CPU.

A replay logs every request, sleep, batch formation and batch in order and
linked both ways; set-up logs its phases and nothing into the window; the
compile counter sees a compile inside a window; and under ``jax.profiler``
each span lands in the trace where the log's anchor puts it.
"""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch import serve
from repro.obs.served import RECORDER
from repro.obs.spans import BatchSpan, ServedBatch

MIX = ("chatglm3-6b", "mamba2-780m")
#: Span names of the benchmark harness (``bench/trace.py``'s SPAN_PREFIXES).
HARNESS_PREFIXES = ("window", "generate/")


@pytest.fixture(scope="module")
def served():
    return serve.run([get_smoke_config(a) for a in MIX],
                     shapes=serve.SMOKE_SHAPES, n_requests=8, seed=0)


def assert_consistent(w):
    """Stamps in order for every answered request; batches and requests
    linked both ways; nothing half logged."""
    assert not w.open
    reqs = w.requests()
    for k, b in enumerate(w.batches):
        assert b.id == k and b.span.n == len(b.request_ids)
        assert (b.span.launch_ms <= b.dispatch_ms < b.first_token_ms
                <= b.last_token_ms <= b.span.done_ms)
        for i in b.request_ids:
            assert reqs[i].batch == b.id and reqs[i].model == b.span.model
    for r in reqs:
        if r.batch < 0:
            assert np.isnan(r.done_ms)
            continue
        b = w.batches[r.batch]
        assert r.id in b.request_ids
        assert (r.due_ms <= r.admitted_ms <= b.span.launch_ms
                < b.first_token_ms <= r.done_ms == b.span.done_ms)
    for s in w.sleeps:
        assert s.start_ms <= s.woke_ms and s.due_ms <= s.woke_ms


def test_a_smoke_replay_logs_every_request_in_order(served):
    w = served.window
    assert w.compiles == 0
    assert_consistent(w)
    assert all(r.batch >= 0 for r in w.requests())
    assert sorted(i for b in w.batches for i in b.request_ids) == list(
        range(len(served.requests)))
    assert len(w.forms) == len(w.batches)
    for r, req in zip(w.requests(), served.requests):
        assert r.due_ms == req.arrival_ms and r.done_ms == req.completion_ms
    assert w.first_token_p90_ms() is not None


def test_set_up_spans_and_warm_up_log_nothing_into_the_window(served):
    runner = served.runners[0]
    phases = RECORDER.setup[runner.name]
    assert list(phases) == ["compile", "warmup", "measure"]
    spans = list(phases.values())
    assert [s.kind for s in spans] == [f"serve.{p}/{runner.name}" for p in phases]
    assert all(a.end_ms <= b.start_ms for a, b in zip(spans, spans[1:]))
    w = RECORDER.window
    before = (list(w.batches), list(w.sleeps), list(w.forms), w.requests())
    runner.generate(np.zeros((1, serve.SMOKE_SHAPES.prompt_lens[0]), np.int32), 2)
    runner.measure(reps=1)
    assert RECORDER.window is w
    assert (w.batches, w.sleeps, w.forms, w.requests()) == before
    assert RECORDER.setup[runner.name]["compile"] == spans[0]


class FreshJit:
    """A runner whose every batch compiles a program never seen before."""

    name = "fresh"
    shapes = serve.SMOKE_SHAPES

    def __init__(self):
        self.calls = 0

    def generate(self, prompts, n_new):
        self.calls += 1
        k = self.calls
        jax.jit(lambda x: x * k + k)(jnp.ones(3)).block_until_ready()
        t = time.perf_counter()
        return serve.Generation(np.zeros((len(prompts), n_new), np.int32), [],
                                True, t, t, t)


def test_the_compile_counter_sees_a_compile_inside_a_window():
    reqs = [serve.ServeRequest("fresh", 0.0, 1e4, np.zeros(16, np.int32), 4)]
    serve.replay([FreshJit()], reqs, {"fresh": 1})
    n = RECORDER.window.compiles
    assert n >= 1
    jax.jit(lambda x: x - 7)(jnp.ones(5))     # the window has closed
    assert RECORDER.window.compiles == n


def test_spans_land_on_the_profilers_clock(served, tmp_path):
    from jax.profiler import ProfileData

    runners = served.runners
    rng = np.random.default_rng(3)
    reqs = [serve.ServeRequest(
        runners[k % 2].name, 40.0 * k, 1e4,
        rng.integers(0, 64, serve.SMOKE_SHAPES.prompt_lens[k % 2], np.int32), 4)
        for k in range(6)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        serve.replay(runners, reqs, {r.name: 2 for r in runners})
        runners[0].measure(reps=1)
    finally:
        jax.profiler.stop_trace()
    w = RECORDER.window
    assert_consistent(w)
    assert w.sleeps and all(r.output is not None for r in reqs)
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path)
    start_ns = next(v for p in data.planes for k, v in p.stats
                    if k == "profile_start_time")
    traced: dict[str, list[float]] = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    assert not e.name.startswith(HARNESS_PREFIXES), e.name
                    traced.setdefault(e.name, []).append(e.start_ns)
    logged: dict[str, list[float]] = {
        "serve.sleep": [s.start_ms for s in w.sleeps],
        "serve.form": [f.start_ms for f in w.forms]}
    for b in w.batches:
        m = b.span.model
        logged.setdefault(f"serve.batch/{m}", []).append(b.span.launch_ms)
        logged.setdefault(f"serve.prefill/{m}", []).append(b.dispatch_ms)
        logged.setdefault(f"serve.decode/{m}", []).append(b.first_token_ms)
    assert len(logged) == 8 and f"serve.measure/{runners[0].name}" in traced
    for name, stamps in logged.items():
        got = sorted(traced[name])
        assert len(got) == len(stamps), name
        for t, ms in zip(got, stamps):
            assert abs(t - w.trace_ns(ms, start_ns)) < 1e6, (name, t, ms)


def test_a_served_batch_is_the_engines_batch_span(served):
    b = served.window.batches[0]
    assert isinstance(b, ServedBatch) and isinstance(b.span, BatchSpan)
    assert b.span.kind == "batch" and (b.span.epoch, b.span.let) == (0, 0)


def test_print_run_reports_the_window(served, capsys):
    serve.print_run(served)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last.startswith("window first_token_p90_ms=")
    assert "wake_late_max_ms=" in last and last.endswith("compiles=0")
