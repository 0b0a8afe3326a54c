"""The serving path of launch/serve.py at smoke size on CPU.

Prefill plus decode answers every request of a seeded trace, and greedy
decode agrees with a teacher-forced ``forward``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.elastic import ElasticPartitioning
from repro.core.hardware import AcceleratorSpec, ClusterSpec
from repro.core.profiles import ModelProfile
from repro.kernels import ops
from repro.launch import serve
from repro.launch.mesh import make_serving_mesh
from repro.models import Model
from repro.obs.served import RECORDER

MIX = ("chatglm3-6b", "mamba2-780m")


@pytest.fixture(scope="module")
def served():
    return serve.run([get_smoke_config(a) for a in MIX],
                     shapes=serve.SMOKE_SHAPES, n_requests=8, seed=0)


def test_serve_answers_every_request(served):
    assert {r.model for r in served.requests} == {f"{a}-smoke" for a in MIX}
    for r in served.requests:
        assert r.output is not None and len(r.output) == r.max_new
        assert r.completion_ms >= r.arrival_ms
        assert len(r.prompt) in serve.SMOKE_SHAPES.prompt_lens
    assert served.placement.schedulable
    for runner in served.runners:
        s = served.summary(runner)
        assert s["served"] == s["requests"]
        assert set(s["L_ms"]) == set(serve.SMOKE_SHAPES.batch_buckets)
        assert s["param_bytes"] > 0 and s["compile_s"] > 0


@pytest.mark.parametrize("arch", MIX)
def test_greedy_decode_equals_teacher_forced_forward(served, arch):
    runner = next(r for r in served.runners if r.cfg.name == f"{arch}-smoke")
    prompt = np.random.default_rng(1).integers(
        0, runner.cfg.vocab_size, serve.SMOKE_SHAPES.prompt_lens[0],
        dtype=np.int32)
    gen = runner.generate(prompt[None], serve.SMOKE_SHAPES.gen_range[1])
    check = serve.check_against_forward(runner, prompt, gen)
    assert check["finite"] and check["ok"], check
    assert check["token_mismatches"] == 0, check
    # two layers of bf16: far inside the full-depth bound
    assert check["max_abs_diff"] <= check["tol"] / 8, check


def float32_runner(arch: str) -> serve.ModelRunner:
    """A smoke runner whose weights, cache and programs are float32, with
    the batches of one and two rows compiled."""
    cfg = get_smoke_config(arch)
    model = Model(cfg, dtype=jnp.float32)
    runner = serve.ModelRunner(cfg, make_serving_mesh(jax.devices()[:1]),
                               serve.SMOKE_SHAPES, 0,
                               params=model.init(jax.random.key(5)))
    runner.model = model
    runner.compile(batches=(1, 2))
    return runner


@pytest.mark.parametrize("arch", MIX)
def test_served_tokens_do_not_depend_on_batch_mates(arch):
    """Two requests of different prompt lengths and generation lengths
    served as one batch get exactly the tokens each gets alone."""
    runner = float32_runner(arch)
    rng = np.random.default_rng(2)
    reqs = [serve.ServeRequest(runner.name, 0.0, 1e4, rng.integers(
        0, runner.cfg.vocab_size, s, dtype=np.int32), g)
        for s, g in zip(serve.SMOKE_SHAPES.prompt_lens, (7, 4))]
    serve.replay([runner], reqs, {runner.name: 2})
    (batch,) = RECORDER.window.batches
    assert batch.row_lens == serve.SMOKE_SHAPES.prompt_lens
    for r in reqs:
        alone = runner.generate(r.prompt[None], r.max_new)
        np.testing.assert_array_equal(r.output, alone.tokens[0])


def test_a_bf16_batch_of_two_agrees_with_each_row_alone(served):
    """In the served precision, a request decodes the same tokens alone
    and in a batch of two, within the logit bound."""
    runner = served.runners[0]
    s = serve.SMOKE_SHAPES.prompt_lens[0]
    prompts = np.random.default_rng(2).integers(
        0, runner.cfg.vocab_size, (2, s), dtype=np.int32)
    pair = runner.generate(prompts, 4)
    alone = runner.generate(prompts[:1], 4)
    assert serve.prefix_agreement(alone, pair, serve.LOGIT_TOL)["ok"]


def test_forward_logits_follow_n_last(served):
    runner = served.runners[0]
    seq = np.arange(20, dtype=np.int32)[None]
    two, five = runner.forward_logits(seq, 2), runner.forward_logits(seq, 5)
    assert two.shape[1] == 2 and five.shape[1] == 5
    np.testing.assert_array_equal(two, five[:, -2:])


def test_uncompiled_shape_fails_loudly(served):
    runner = served.runners[0]
    with pytest.raises(KeyError):
        runner.generate(np.zeros((3, 16), np.int32), 2)


def test_measured_latency_interpolates_and_reads_time_shares():
    lat = serve.MeasuredLatency({"m": {1: 10.0, 4: 40.0}})
    prof = ModelProfile(name="m", slo_ms=100.0, flops_per_req=0.0,
                        weight_mb=0.0, act_mb_per_req=0.0, par1=1.0,
                        par_exp=0.0, t0_ms=0.0, l2_util_base=0.0)
    assert lat.batch_sizes == (1, 2, 3, 4) and lat.max_batch == 4
    assert lat.latency_ms(prof, 1, 1.0) == 10.0
    assert lat.latency_ms(prof, 2, 1.0) == pytest.approx(20.0)
    assert lat.latency_ms(prof, 4, 0.5) == pytest.approx(80.0)
    # real methods of the provider, not borrowed ones
    assert lat.max_batch_under_slo(prof, 1.0, 100.0) == 4
    assert lat.max_rate(prof, 1.0) == pytest.approx(4 / 0.040)


def test_noisy_measured_latency_still_schedules_low_rates():
    """A host-timed L(1) above L(2) made low rates unschedulable."""
    measured = {"a": {1: 41.98, 2: 36.01, 4: 39.31},
                "b": {1: 27.46, 2: 42.66, 4: 55.16}}
    lat = serve.MeasuredLatency(measured)
    assert lat.tables["a"] == {1: 41.98, 2: 41.98, 4: 41.98}
    assert lat.tables["b"] == measured["b"]
    slo = 2.0 * (41.98 + 55.16)
    profiles = {m: ModelProfile(name=m, slo_ms=slo, flops_per_req=0.0,
                                weight_mb=0.0, act_mb_per_req=0.0, par1=1.0,
                                par_exp=0.0, t0_ms=0.0, l2_util_base=0.0)
                for m in measured}
    cpu = AcceleratorSpec(name="cpu", peak_tflops=0.0, hbm_gbs=0.0, hbm_gb=0.0)
    sched = ElasticPartitioning(profiles, cluster=ClusterSpec(cpu, n_devices=1),
                                lat=lat)
    for rate in (0.5, 2.0, 8.0):
        assert sched.schedule({"a": rate, "b": rate}).schedulable, rate


def test_agreement_tolerates_only_ties():
    ref = np.array([[0.0, 1.0, 3.0], [0.0, 2.0, 2.05]], np.float32)
    exact = serve.agreement(np.array([2, 2]), ref, ref, serve.LOGIT_TOL)
    assert exact["ok"] and exact["max_abs_diff"] == 0.0
    tie = serve.agreement(np.array([2, 1]), ref, ref, serve.LOGIT_TOL)
    assert tie["ok"] and tie["token_mismatches"] == 1
    wrong = serve.agreement(np.array([1, 2]), ref, ref, serve.LOGIT_TOL)
    assert not wrong["ok"]
    drifted = serve.agreement(np.array([2, 2]), ref + 1.0, ref, serve.LOGIT_TOL)
    assert not drifted["ok"]


@pytest.mark.parametrize("arch,tol", [
    ("chatglm3-6b", serve.LOGIT_TOL), ("yi-9b", serve.LOGIT_TOL),
    ("mamba2-780m", serve.RECURRENT_LOGIT_TOL),
    ("recurrentgemma-2b", serve.RECURRENT_LOGIT_TOL)])
def test_recurrent_models_get_the_looser_bound(arch, tol):
    assert serve.logit_tol(get_smoke_config(arch)) == tol


def test_prefix_agreement_stops_where_tokens_part():
    def gen(tokens, logits):
        return serve.Generation(np.array([tokens]), [np.array([row]) for row in logits], True)

    ref = gen([2, 0, 1], [[0, 0, 5.0], [5.0, 0, 0], [0, 5.0, 0]])
    same = serve.prefix_agreement(ref, ref, serve.LOGIT_TOL)
    assert same["ok"] and same["same_tokens"] and same["positions"] == 3
    # parts at position 1 on a clear margin: compared up to there, fails
    off = gen([2, 1, 0], [[0, 0, 5.0], [0, 5.0, 0], [9.0, 0, 0]])
    res = serve.prefix_agreement(off, ref, serve.LOGIT_TOL)
    assert res["positions"] == 2 and not res["ok"]


def test_compile_cache_goes_to_env_or_repo_root(tmp_path, monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert serve.use_compile_cache(str(tmp_path)) == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = serve.use_compile_cache(str(tmp_path))
        assert path == str(tmp_path / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("impl", [None, "auto", "tpu"])
def test_kernel_ops_refuse_an_unnamed_impl(impl):
    x = np.zeros((1, 2, 128, 64), np.float32)
    with pytest.raises(ValueError):
        ops.flash_attention(x, x, x, impl=impl)


def test_kernel_ops_need_impl():
    x = np.zeros((1, 2, 128, 64), np.float32)
    with pytest.raises(TypeError):
        ops.flash_attention(x, x, x)


def test_serve_rejects_plan_mode_without_rates():
    with pytest.raises(SystemExit):
        serve.main(["--results", "missing.jsonl"])


def test_pallas_runner_shares_weights(served):
    runner = served.runners[0]
    other = serve.ModelRunner(
        dataclasses.replace(runner.cfg, kernel_impl="interpret"), runner.mesh,
        serve.SMOKE_SHAPES, 0, params=runner.params)
    assert other.params is runner.params
    assert other.param_bytes == runner.param_bytes
