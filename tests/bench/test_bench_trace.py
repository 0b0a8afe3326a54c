"""The trace reduction, on small traces recorded on TPU v5e chips
(``tests/bench/data/record_trace.py``: two batches of a two-layer
transformer, 1 and 2 rows, a 64-token prompt and 4 tokens each; on one
chip, and tensor parallel on four)."""
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def one_chip():
    return trace.load(os.path.join(DATA, "trace_1chip.xplane.pb"))


def test_the_trace_holds_the_device_and_the_harness_spans(one_chip):
    assert list(one_chip.devices) == [0]
    assert [n for _, _, n in one_chip.spans] == ["window"] + ["generate/chatglm3-6b"] * 2
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
    assert all(n.startswith("chatglm3-6b:jit_") for n, _ in ops)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert sum(t for _, t in gaps) <= r["window_s"] - r["busy_s"] + 1e-9


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
