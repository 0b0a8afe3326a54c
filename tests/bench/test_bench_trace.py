"""The trace reduction, on small traces recorded on TPU v5e chips
(``tests/bench/data/record_trace.py``), and on a hand-built one.

``trace_1chip`` and ``trace_4chip``: two batches of a two-layer
transformer of chatglm3-6b's family, 1 and 2 rows, a 64-token prompt and
4 tokens each; on one chip, and tensor parallel on four.  They were
recorded by the script's earlier version, inside the harness's earlier
spans (``generate/<model>``, around each ``ModelRunner.generate``), which
bracket the work the program's ``serve.batch/<model>`` spans bracket: the
one-chip trace is read here with those spans renamed so.
"""
import dataclasses
import math
import os
import types

import pytest
from jax.profiler import ProfileData

from bench import trace
from repro.obs.spans import SleepSpan

DATA = os.path.join(os.path.dirname(__file__), "data")
GLM, MAMBA = "chatglm3-6b", "mamba2-780m"


@pytest.fixture(scope="module")
def one_chip():
    path = os.path.join(DATA, "trace_1chip.xplane.pb")
    t = trace.load(path)
    batches = [(e.start_ns, e.start_ns + e.duration_ns,
                trace.BATCH + e.name.removeprefix("generate/"))
               for p in ProfileData.from_file(path).planes if p.name.startswith("/host:")
               for line in p.lines for e in line.events if e.name.startswith("generate/")]
    return dataclasses.replace(t, spans=sorted(t.spans + batches))


def test_the_trace_holds_the_device_and_the_harness_spans(one_chip):
    assert list(one_chip.devices) == [0] and one_chip.start_ns > 0
    assert [n for _, _, n in one_chip.spans] == ["window"] + [f"serve.batch/{GLM}"] * 2
    assert len(one_chip.devices[0].modules) == 8
    assert len(one_chip.devices[0].ops) > 100


def test_reduce_finds_busy_time_programs_and_a_breakdown(one_chip):
    r = trace.reduce(one_chip)
    assert 0 < r["busy_s"] <= r["busy_in_spans_s"] + 1e-9 <= r["spans_s"] + 1e-9
    assert r["spans_s"] <= r["window_s"]
    assert r["busy_s"] == r["busy_s_dev0"] and r["allreduce_s_dev0"] == 0.0
    # one prefill and three decode steps per batch, in the device's order
    names = [n for n, _ in r["programs"]]
    assert names == (["jit_prefill"] + ["jit_decode"] * 3) * 2
    assert all(t > 0 for _, t in r["programs"])
    ops, gaps = r["breakdown"]["device_ops"], r["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    # device operations keep the model's name as their prefix
    assert all(n.startswith(f"{GLM}:jit_") for n, _ in ops)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert {n for n, _ in gaps} <= {"loop", f"serve.batch/{GLM}"}
    assert sum(t for _, t in gaps) <= r["window_s"] - r["busy_s"] + 1e-9


def hand_built() -> trace.Trace:
    """One device busy in [0, 40], [60, 100], [300, 310], [700, 710],
    [990, 1000] and [1100, 1200] ns of a [0, 1200] window; its idle gaps'
    middles fall in each of the program's states:

    =============  ==========  =======================================
    gap            middle      the program there
    =============  ==========  =======================================
    40-60          50          ``serve.batch/<glm>`` 0-100
    100-300        200         ``serve.form`` 150-250
    310-700        505         ``serve.sleep`` 320-690, due at 600
    710-990        850         ``serve.sleep`` 720-980, due 800, woke 975
    1000-1100      1050        no span
    =============  ==========  =======================================
    """
    busy = [(0, 40), (60, 100), (300, 310), (700, 710), (990, 1000), (1100, 1200)]
    dev = trace.Device(
        modules=[(s, e, f"jit_decode({k})") for k, (s, e) in enumerate(busy)],
        ops=[(s, e, f"%fusion.{k} = bf16[8] fusion(%p)") for k, (s, e) in enumerate(busy)])
    spans = [(0, 1200, "window"), (0, 100, f"serve.batch/{GLM}"),
             (150, 250, "serve.form"), (290, 315, f"serve.batch/{MAMBA}"),
             (320, 690, "serve.sleep"), (720, 980, "serve.sleep")]
    return trace.Trace({0: dev}, spans, 0)


#: Each label of an idle gap, and the length (ns) of the hand-built gap it names.
LABELS = {"serve.sleep": 390, "serve.sleep.late": 280, "serve.form": 200,
          "loop": 100, f"serve.batch/{GLM}": 20}


@pytest.mark.parametrize("label", sorted(LABELS))
def test_each_idle_gap_is_labelled_by_what_the_program_did(label):
    window = types.SimpleNamespace(
        sleeps=[SleepSpan("serve.sleep", 320e-6, 600e-6, 690e-6),
                SleepSpan("serve.sleep", 720e-6, 800e-6, 975e-6)],
        trace_ns=lambda ms, start_ns: ms * 1e6 - start_ns)
    late = trace.late_wakes(window, 0)
    assert late == [pytest.approx((600, 690)), pytest.approx((800, 975))]
    r = trace.reduce(hand_built(), late=late)
    gaps = r["breakdown"]["idle_gaps"]
    assert [n for n, _ in gaps] == sorted(LABELS, key=LABELS.get, reverse=True)
    assert dict(gaps)[label] == pytest.approx(LABELS[label] / 1e9)
    assert r["spans_s"] == pytest.approx(125e-9) and r["busy_in_spans_s"] == pytest.approx(90e-9)


def test_device_ops_are_named_by_the_batch_that_ran_them():
    ops = dict(trace.reduce(hand_built())["breakdown"]["device_ops"])
    assert set(ops) == {f"{GLM}:jit_decode/fusion.0", f"{GLM}:jit_decode/fusion.1",
                        f"{MAMBA}:jit_decode/fusion.2", "loop:jit_decode/fusion.3",
                        "loop:jit_decode/fusion.4", "loop:jit_decode/fusion.5"}
    assert math.isclose(sum(ops.values()), 210e-9)


def test_union_and_names():
    u = trace._union([(0, 5, "a"), (3, 8, "b"), (10, 12, "c")], 1, 11)
    assert u.tolist() == [[1, 8], [10, 11]]
    assert trace._covered(u, 0, 20) == 8
    assert trace.op_name("%all-reduce-start.3 = (bf16[8]) all-reduce-start(%x)") \
        == "all-reduce-start.3"
    assert trace.op_name("%fusion.1 = bf16[8] fusion(bf16[8] %all-reduce.2)") == "fusion.1"


def test_four_chips_read_every_device_and_the_all_reduces():
    t = trace.load(os.path.join(DATA, "trace_4chip.xplane.pb"))
    assert sorted(t.devices) == [0, 1, 2, 3]
    assert all(len(t.devices[i].modules) == 8 for i in range(4))
    assert t.devices[0].ops and not any(t.devices[i].ops for i in (1, 2, 3))
    r = trace.reduce(t)
    assert 0 < r["allreduce_s_dev0"] < r["busy_s_dev0"] <= r["window_s"]
    assert [n for n, _ in r["programs"]] == (["jit_prefill"] + ["jit_decode"] * 3) * 2
    assert any("/all-reduce." in n for n, _ in r["breakdown"]["device_ops"])
