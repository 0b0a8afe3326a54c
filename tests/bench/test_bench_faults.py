"""``correct`` comes out false when the timed path is broken underneath,
and the low-precision control fails the limit the program meets.

Each fault is planted in the program (or in the weights it serves) by the
test, and a whole run at smoke size on the CPU must then read ``correct``
false: a decode step that returns its state unchanged; half of a batch
left out, its rows given other requests' answers; the exchange between
tensor-parallel chips left out (each chip's partial sums only); a served
token altered where it is produced.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import SMOKE_GAP_LIMIT

from bench import check, run, weights
from repro.launch import serve

SEED = 2**32 + 77


def _decode_keeps_its_state(monkeypatch):
    compile_ = serve.ModelRunner.compile

    def compile(self, *a, **k):
        compile_(self, *a, **k)
        for b, step in list(self._decode.items()):
            def stale(params, cache, tok, step=step):
                fresh = jax.tree.map(jnp.copy, cache)
                return (*step(params, fresh, tok)[:3], cache)
            self._decode[b] = stale

    monkeypatch.setattr(serve.ModelRunner, "compile", compile)


def _half_the_batch_left_out(monkeypatch):
    generate = serve.ModelRunner.generate

    def half(self, prompts, n_new):
        b = prompts.shape[0]
        if b == 1 or (b, prompts.shape[1]) not in self._prefill or b // 2 not in self._decode:
            return generate(self, prompts, n_new)
        kept = generate(self, prompts[: b // 2], n_new)
        return kept._replace(tokens=np.concatenate([kept.tokens, kept.tokens]))

    monkeypatch.setattr(serve.ModelRunner, "generate", half)


def _no_exchange_between_chips(monkeypatch, chips=4):
    params = weights.params

    def local_only(sizes, root):
        p = params(sizes, root)
        if sizes["family"] != "transformer":
            return p
        mlp, attn = p["layers"]["mlp"], p["layers"]["attn"]
        keep_f = sizes["d_ff"] // chips
        keep_h = sizes["n_heads"] // chips
        mlp["w_down"] = mlp["w_down"].at[:, keep_f:].set(0)
        attn["wo"] = attn["wo"].at[:, keep_h:].set(0)
        return p

    monkeypatch.setattr(weights, "params", local_only)


def _token_altered(monkeypatch):
    generate = serve.ModelRunner.generate

    def altered(self, prompts, n_new):
        out = generate(self, prompts, n_new)
        tokens = out.tokens.copy()
        tokens[:, -1] = (tokens[:, -1] + 1) % self.cfg.vocab_size
        return out._replace(tokens=tokens)

    monkeypatch.setattr(serve.ModelRunner, "generate", altered)


FAULTS = {"decode_keeps_its_state": (_decode_keeps_its_state, "smoke-steady"),
          "half_the_batch_left_out": (_half_the_batch_left_out, "smoke-backlog"),
          "no_exchange_between_chips": (_no_exchange_between_chips, "smoke-steady"),
          "token_altered": (_token_altered, "smoke-steady")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_reads_not_correct(fault, smoke_root, on_cpu, monkeypatch):
    plant, cell = FAULTS[fault]
    plant(monkeypatch)
    result = run.run(smoke_root, cell, SEED, 3.0, trace=False)
    assert result["attempted"] > 0
    assert result["correct"] is False, result["checks"]


def test_the_float8_control_fails_the_limit_the_program_meets(smoke_root, on_cpu):
    setup = run.build(run.Benchmark(smoke_root), "smoke-steady", SEED)
    reqs, _, error, _ = run.serve_window(setup, SEED, 3.0, None)
    run.free(setup)
    readings = run.correctness(setup, reqs, SEED, control=True)
    assert error is None and set(readings) == {"chatglm3-6b", "mamba2-780m"}
    for model, g in readings.items():
        served, control = check.number(g["served"], "gap"), check.number(g["control"], "gap")
        assert served <= SMOKE_GAP_LIMIT < control, (model, served, control)
        assert control >= 3 * served, (model, served, control)
