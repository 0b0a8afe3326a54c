"""The readers of the program's own spans and counters (``repro.obs.served``):
hand-computed values on a hand-built log, nothing where the program has no
window or no recorder, and a consistent log when the harness stops a
window early."""
import math
import sys

import pytest

from bench_helpers import ROOT

from bench import run
from bench.spec import Benchmark
from bench.trace import SPAN_PREFIXES
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


def test_an_early_stop_leaves_the_log_consistent(smoke_root, on_cpu):
    """``smoke-backlog`` stops at its window's end: the harness's wrapper
    raises ``WindowClosed`` inside the program's ``serve.batch`` span."""
    setup = run.build(run.Benchmark(smoke_root), "smoke-backlog", 5)
    reqs, batches, window_s, error, _ = run.serve_window(setup, 5, 1.0, None)
    w = served.RECORDER.window
    assert error is None and not w.open and w.compiles == 0
    assert len(w.batches) == len(batches) > 0
    assert len(w.forms) == len(w.batches) + 1      # the batch refused
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
    for ours, theirs in zip(w.batches, batches):
        assert ours.span.model == theirs["model"]
        assert ours.span.launch_ms <= theirs["start_ms"] + 1.0
    rows = run.request_rows(reqs, batches, "window")
    record = run.record_of(setup, rows, batches, 1.0, window_s, 1.0, None)
    got = {n: setup.bench.read(n, record) for n in READERS}
    assert got["compiles_in_window"] == 0 and got["replay_wake_late_max_ms"] is None
    assert 0 < got["setup_programs_s"] and got["first_token_p90_ms"] > 0
    assert got[f"decode_step_served_ms.{GLM}"] > 0
    spans = [s.kind for m in record["models"] for s in served.RECORDER.setup[m].values()]
    assert len(spans) == 6 and not [n for n in spans if n.startswith(SPAN_PREFIXES)]

