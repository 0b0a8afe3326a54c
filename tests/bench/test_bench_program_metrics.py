"""The readers of the program's own spans and counters (``repro.obs.served``):
hand-computed values on a hand-built log, nothing where the program has no
window or no recorder, a consistent log when the harness stops a window
early, and the parent harness's readings on the same window."""
import math
import sys
import time

import pytest

from bench_helpers import ROOT, steer_to_cpu

from bench import run
from bench.spec import Benchmark
from repro.obs import served
from repro.obs.spans import BatchSpan, HostSpan, ServedBatch, SleepSpan

GLM, MAMBA = "chatglm3-6b", "mamba2-780m"
READERS = ["first_token_p90_ms", f"decode_step_served_ms.{GLM}",
           f"decode_step_served_ms.{MAMBA}", "queue_wait_cross_model_pct",
           "replay_wake_late_max_ms", "compiles_in_window", "setup_programs_s"]
PHASES = ("compile", "warmup", "measure")


@pytest.fixture(scope="module")
def bench():
    return Benchmark(ROOT)


def batch(bid, model, launch, done, steps, first, last, ids):
    return ServedBatch(BatchSpan("batch", 0, 0, launch, done, model, len(ids)),
                       bid, 128, steps, ids, launch, first, last)


def hand_built() -> served.Recorder:
    """Two models; four answered requests and one left in the queue:

    ======  =====  =====  =================  ===========  ========================
    req     model  due    batch (launch)     first token  wait beside other model
    ======  =====  =====  =================  ===========  ========================
    0       glm    0      0 (10)             40           0 of 10
    1       glm    5      0 (10)             40           0 of 5
    2       mamba  20     1 (110)            120          90 of 90 (batch 0)
    3       glm    100    2 (150)            180          40 of 50 (batch 1)
    4       mamba  240    none
    ======  =====  =====  =================  ===========  ========================
    """
    rec = served.Recorder()
    nan = math.nan
    w = served.Window(0.0, 0, [GLM, GLM, MAMBA, GLM, MAMBA],
                      [0.0, 5.0, 20.0, 100.0, 240.0],
                      [0.0, 5.0, 20.0, 100.0, 240.0], [0, 0, 1, 2, -1],
                      [110.0, 110.0, 150.0, 250.0, nan])
    w.batches = [batch(0, GLM, 10.0, 110.0, 11, 40.0, 105.0, (0, 1)),
                 batch(1, MAMBA, 110.0, 150.0, 5, 120.0, 148.0, (2,)),
                 batch(2, GLM, 150.0, 250.0, 3, 180.0, 240.0, (3,))]
    w.sleeps = [SleepSpan("serve.sleep", 0.0, 20.0, 21.5),
                SleepSpan("serve.sleep", 250.0, 260.0, 260.25)]
    w.compiles, w.open = 2, False
    rec.window = w
    for model, phases in {GLM: (1000, 3000, 3500, 4000),
                          MAMBA: (4000, 4500, 4600, 4700),
                          "other": (0, 9000, 9000, 9000)}.items():
        rec.setup[model] = {p: HostSpan(f"serve.{p}/{model}", a, b) for p, a, b in
                            zip(PHASES, phases, phases[1:])}
    return rec


RECORD = {"models": {GLM: {}, MAMBA: {}}}
EXPECTED = {
    "first_token_p90_ms": 100.0,                 # of 40, 35, 100, 80
    f"decode_step_served_ms.{GLM}": 18.25,       # median of 65/10, 60/2
    f"decode_step_served_ms.{MAMBA}": 7.0,       # 28/4
    "queue_wait_cross_model_pct": 100.0 * 130.0 / 155.0,
    "replay_wake_late_max_ms": 1.5,
    "compiles_in_window": 2,
    "setup_programs_s": 3.7,                     # 3.0 + 0.7; "other" not run
}


@pytest.mark.parametrize("name", READERS)
def test_each_reader_on_a_hand_built_log(bench, monkeypatch, name):
    monkeypatch.setattr(served, "RECORDER", hand_built())
    assert bench.read(name, RECORD) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_no_window_reads_nothing(bench, monkeypatch, name):
    rec = hand_built()
    rec.window = None
    monkeypatch.setattr(served, "RECORDER", rec)
    assert bench.read(name, RECORD) is None


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_recorder_reads_nothing(bench, monkeypatch, name):
    monkeypatch.setitem(sys.modules, "repro.obs.served", None)
    assert bench.read(name, RECORD) is None


def parent_generate(runner, log: list):
    """The parent harness's wrapper around ``runner.generate``, kept as the
    reference: each call's start and end, on the window's clock."""
    inner = runner.generate

    def generate(prompts, n_new):
        t0_s = served.RECORDER.window.t0_s
        start = (time.perf_counter() - t0_s) * 1e3
        out = inner(prompts, n_new)
        log.append({"model": runner.name, "start_ms": start,
                    "end_ms": (time.perf_counter() - t0_s) * 1e3,
                    "size": int(prompts.shape[0]),
                    "prompt_len": int(prompts.shape[1]), "steps": int(n_new)})
        return out

    return generate


def parent_request_rows(reqs, batches, stop: str) -> list[dict]:
    """The parent's ``request_rows``, kept as the reference: a request's
    batch is the last batch of its model that started before the request's
    completion."""
    starts: dict[str, list] = {}
    for b in batches:
        starts.setdefault(b["model"], []).append(b)
    rows = []
    for r in reqs:
        batch = None
        if r.completion_ms is not None:
            batch = [b for b in starts.get(r.model, [])
                     if b["start_ms"] <= r.completion_ms][-1]
        elif stop == "window":
            continue
        rows.append({"due_ms": r.arrival_ms, "batch": batch,
                     "batch_start_ms": None if batch is None else batch["start_ms"]})
    return rows


@pytest.fixture(scope="module")
def stopped(smoke_root):
    """``smoke-backlog`` served by ``serve.replay`` for 1 s and stopped at
    the window's end by the harness's hook; beside the program's log, the
    batches as the parent's wrapper around ``generate`` stamped them."""
    with pytest.MonkeyPatch.context() as mp:
        steer_to_cpu(mp)
        setup = run.build(run.Benchmark(smoke_root), "smoke-backlog", 5)
        wrapped: list[dict] = []
        for r in setup.runners:
            mp.setattr(r, "generate", parent_generate(r, wrapped))
        reqs, w, error, _ = run.serve_window(setup, 5, 1.0, None)
    return setup, reqs, w, error, wrapped


def test_an_early_stop_leaves_the_log_consistent(stopped, on_cpu, monkeypatch):
    """``smoke-backlog`` stops at its window's end: the harness's hook
    raises ``WindowClosed`` right before the program's ``serve.batch`` span
    would open."""
    setup, reqs, w, error, wrapped = stopped
    monkeypatch.setattr(served.RECORDER, "window", w)
    assert error is None and not w.open and w.compiles == 0
    assert len(w.batches) == len(wrapped) > 0
    assert len(w.forms) == len(w.batches) + 1      # the batch refused
    assert type(w.forms) is list                   # the hook is cleared
    answered = [r for r in w.requests() if r.batch >= 0]
    assert len(answered) == sum(b.span.n for b in w.batches)
    assert all(not math.isnan(r.admitted_ms) for r in w.requests())
    for r in w.requests():
        if r.batch < 0:
            assert math.isnan(r.done_ms) and reqs[r.id].output is None
            continue
        b = w.batches[r.batch]
        assert r.id in b.request_ids and reqs[r.id].completion_ms == r.done_ms
        assert r.due_ms <= r.admitted_ms <= b.span.launch_ms < b.first_token_ms
    rows = run.request_rows(reqs, w, "window")
    record = run.record_of(setup, rows, run.batch_rows(reqs, w), 1.0, 1.0, None)
    got = {n: setup.bench.read(n, record) for n in READERS}
    assert got["compiles_in_window"] == 0 and got["replay_wake_late_max_ms"] is None
    assert 0 < got["setup_programs_s"] and got["first_token_p90_ms"] > 0
    assert got[f"decode_step_served_ms.{GLM}"] > 0
    spans = [s.kind for m in record["models"] for s in served.RECORDER.setup[m].values()]
    assert len(spans) == 6 and "window" not in spans


def test_the_window_reads_as_the_parent_harness_read(stopped, on_cpu, monkeypatch):
    """Each request's batch, by the window's id, is the one the parent's
    rule finds; the batch readers agree with the parent's stamps."""
    setup, reqs, w, _, wrapped = stopped
    monkeypatch.setattr(served.RECORDER, "window", w)
    batches = run.batch_rows(reqs, w)
    rows = run.request_rows(reqs, w, "window")
    by_launch = [dict(b, start_ms=b["launch_ms"]) for b in batches]
    parent = parent_request_rows(reqs, by_launch, "window")
    attempted = [i for i, b in enumerate(w.batch) if b >= 0]
    assert len(rows) == len(parent) == len(attempted) > 0
    assert [old["batch"]["id"] for old in parent] == [w.batch[i] for i in attempted]
    for ours, theirs in zip(batches, wrapped):
        assert (ours["model"], ours["size"], ours["prompt_len"], ours["steps"]) == (
            theirs["model"], theirs["size"], theirs["prompt_len"], theirs["steps"])
        assert 0 <= theirs["start_ms"] - ours["launch_ms"] < 1.0
        assert 0 <= ours["done_ms"] - theirs["end_ms"] < 1.0
    record = run.record_of(setup, rows, batches, 1.0, 1.0, None)
    read = setup.bench.read
    assert read("queue_wait_p90_ms", record) == pytest.approx(
        read("queue_wait_p90_ms", {"requests": parent}), abs=0.01)
    stamped = [dict(ours, launch_ms=theirs["start_ms"], done_ms=theirs["end_ms"])
               for ours, theirs in zip(batches, wrapped)]
    old = run.record_of(setup, parent_request_rows(reqs, [
        dict(b, start_ms=b["launch_ms"]) for b in stamped], "window"),
        stamped, 1.0, 1.0, None)
    for name in ("queue_wait_p90_ms", "mfu_busy"):
        assert read(name, record) == pytest.approx(read(name, old), rel=0.01), name
    # an error share moves by the stamps' gap over the batch's time: up to
    # 0.1 ms over batches of 20-90 ms at smoke size, about 0.3 points
    assert read("sched_latency_err_pct", record) == pytest.approx(
        read("sched_latency_err_pct", old), abs=0.5)
