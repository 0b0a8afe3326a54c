"""Counts of operations and bytes, checked against sums worked by hand;
the generator's traffic, checked against its promises."""
import json
import os

import jax
import numpy as np
import pytest

from bench_helpers import ROOT

from bench import counts, traffic, weights


def _sizes(config, arch):
    with open(os.path.join(ROOT, "bench", "configs", config + ".json")) as f:
        cfg = json.load(f)
    return next(m for m in cfg["models"] if m["arch"] == arch)


CHATGLM = _sizes("chatglm3-6b_mamba2-780m", "chatglm3-6b")["sizes"]
MAMBA = _sizes("chatglm3-6b_mamba2-780m", "mamba2-780m")["sizes"]
YI = _sizes("yi-9b-tp4", "yi-9b")["sizes"]


def test_parameter_bytes_by_hand():
    # chatglm3-6b: per layer q, k, v, o = 4096 x (32 + 2 + 2) x 128 + 32 x 128 x 4096
    # = 35,651,584, SwiGLU 3 x 4096 x 13696 = 168,296,448; 28 layers in bf16,
    # two float32 norms a layer, embedding and head 2 x 65024 x 4096 in bf16.
    glm_layer = 35_651_584 + 168_296_448
    assert counts.layer_matmul_params(CHATGLM) == glm_layer
    assert counts.param_bytes(CHATGLM) == (
        28 * (glm_layer * 2 + 2 * 4096 * 4) + 2 * 65024 * 4096 * 2 + 4096 * 4)
    assert counts.param_bytes(CHATGLM) == 12_487_376_896
    # yi-9b: 4096 x (32 + 4 + 4) x 128 + 32 x 128 x 4096 = 37,748,736 and
    # 3 x 4096 x 11008 = 135,266,304 per layer, 48 layers, vocabulary 64000.
    assert counts.layer_matmul_params(YI) == 37_748_736 + 135_266_304
    assert counts.param_bytes(YI) == 17_659_609_088


@pytest.mark.parametrize("sizes", [CHATGLM, MAMBA, YI], ids=["chatglm3-6b", "mamba2-780m", "yi-9b"])
def test_parameter_bytes_match_the_weights_made(sizes):
    made = weights.shapes(sizes)
    assert counts.param_bytes(sizes) == sum(x.size * x.dtype.itemsize
                                            for x in jax.tree.leaves(made))


def test_flops_by_hand():
    # chatglm3-6b decode of one row at cache length 511: 2 x 203,948,032 x 28
    # matmul FLOPs, attention 4 x 32 x 128 x 512 x 28, head 2 x 4096 x 65024.
    want = 2 * 203_948_032 * 28 + 4 * 32 * 128 * 512 * 28 + 2 * 4096 * 65024
    assert counts.decode_flops(CHATGLM, 1, 511) == pytest.approx(want, rel=1e-12)
    assert counts.decode_flops(CHATGLM, 4, 511) == pytest.approx(4 * want, rel=1e-12)
    # yi-9b prefill of 4 x 512: matmuls over every token, causal attention
    # over 512 x 513 / 2 pairs, the head at the last position only.
    per_row = (512 * 48 * 2 * 173_015_040 + 48 * 4 * 32 * 128 * 512 * 513 / 2
               + 2 * 4096 * 64000)
    assert counts.prefill_flops(YI, 4, 512) == pytest.approx(4 * per_row, rel=1e-12)


def test_decode_bytes_by_hand():
    # yi-9b at batch 4, 511 cached tokens, per chip of four: every weight but
    # the embedding table, 4 embedding rows, and the kv cache read (511 rows)
    # plus written (1 row): 48 x 4 x 512 x (2 x 4 x 128 x 2 bytes).
    weights_b = 17_659_609_088 - 64000 * 4096 * 2 + 4 * 4096 * 2
    cache = 48 * 4 * 512 * 2 * 4 * 128 * 2
    assert counts.decode_bytes(YI, 4, 511, chips=4) == pytest.approx(
        (weights_b + cache) / 4, rel=1e-12)
    # mamba2-780m: state 48 heads x 128 x 64 in float32 and a 3 x 3072 bf16
    # conv window, read and written, in each of 48 layers.
    state = 48 * 128 * 64 * 4 + 3 * 3072 * 2
    assert counts.decode_bytes(MAMBA, 1, 100) == pytest.approx(
        counts.param_bytes(MAMBA) - 50432 * 1536 * 2 + 1536 * 2 + 48 * 2 * state,
        rel=1e-12)


MODELS = [{"arch": "a", "slo_ms": 100.0, "sizes": {"vocab_size": 1000}},
          {"arch": "b", "slo_ms": 200.0, "sizes": {"vocab_size": 50}}]
POISSON = {"schedule_seed": 9, "arrivals": "poisson", "rates_req_s": {"a": 2.0, "b": 1.0},
           "prompt_lens": [16, 32], "gen_range": [4, 8]}


def _key(reqs):
    return [(m, t, p.tolist(), g, s) for m, t, p, g, s in reqs]


def _schedule(reqs):
    return [(m, t, len(p), g, s) for m, t, p, g, s in reqs]


def test_the_runs_seed_draws_the_tokens_the_cell_the_schedule():
    seed = 2**33 + 12345          # past 32 bits, as the driver's seeds are
    a = traffic.requests(POISSON, MODELS, seed, 50.0)
    assert _key(a) == _key(traffic.requests(POISSON, MODELS, seed, 50.0))
    b = traffic.requests(POISSON, MODELS, seed + 1, 50.0)
    assert _schedule(a) == _schedule(b) and _key(a) != _key(b)
    c = traffic.requests(dict(POISSON, schedule_seed=10), MODELS, seed, 50.0)
    assert _schedule(a) != _schedule(c)


def test_poisson_offers_the_stated_rates_and_sizes():
    a = traffic.requests(POISSON, MODELS, 1, 50.0)
    for model, rate in (("a", 2.0), ("b", 1.0)):
        ra = [r for r in a if r[0] == model]
        assert len(ra) == rate * 50
        gaps = np.diff([0.0] + [r[1] for r in ra])
        assert np.mean(gaps) == pytest.approx(1e3 / rate, rel=0.1)
        assert max(r[1] for r in ra) < 50e3
        assert sorted(len(r[2]) for r in ra) == sorted([16, 32] * int(rate * 25))
    assert [r[1] for r in a] == sorted(r[1] for r in a)
    assert all(0 <= r[2].max() < m["sizes"]["vocab_size"]
               for r in a for m in MODELS if m["arch"] == r[0])
    assert {r[3] for r in a} == {4, 5, 6, 7, 8}


def test_backlog_is_due_at_once_in_a_drawn_order():
    spec = {"schedule_seed": 4, "arrivals": "backlog", "backlog": {"a": 40, "b": 20},
            "prompt_lens": [16, 32], "gen_range": [4, 8]}
    reqs = traffic.requests(spec, MODELS, 5, 10.0)
    assert len(reqs) == 60 and all(r[1] == 0.0 for r in reqs)
    assert [r[0] for r in reqs] != sorted(r[0] for r in reqs)
    with pytest.raises(ValueError):
        traffic.requests(dict(spec, arrivals="bursty"), MODELS, 5, 10.0)
