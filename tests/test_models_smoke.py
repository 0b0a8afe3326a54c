"""REQUIRED per-arch smoke tests: reduced configs, one forward + one train
step on CPU, asserting output shapes and no NaNs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models import Model
from repro.training import OptimConfig, adamw_init, make_train_step


def make_batch(cfg, key, b=2, s=32):
    if cfg.arch_type == "audio":
        return {"frame_embeds": jax.random.normal(key, (b, s, cfg.d_model),
                                                  jnp.bfloat16),
                "labels": jax.random.randint(key, (b, s), 0, cfg.vocab_size)}
    if cfg.arch_type == "vlm":
        return {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size),
                "patch_embeds": jax.random.normal(
                    key, (b, cfg.n_frontend_tokens, cfg.d_model),
                    jnp.bfloat16)}
    return {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab_size)}


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_config_reduced(arch_id):
    cfg = get_smoke_config(arch_id)
    assert cfg.n_layers <= 3
    assert cfg.d_model <= 512
    assert cfg.n_experts <= 4
    full = get_config(arch_id)
    assert cfg.arch_type == full.arch_type  # same family


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_forward_shapes_no_nans(arch_id):
    cfg = get_smoke_config(arch_id)
    model = Model(cfg)
    key = jax.random.key(0)
    params = model.init(key)
    batch = make_batch(cfg, key)
    logits, aux = jax.jit(lambda p, b: model.forward(p, b))(params, batch)
    b = 2
    s_expect = 32 + (cfg.n_frontend_tokens if cfg.arch_type == "vlm" else 0)
    assert logits.shape == (b, s_expect, cfg.padded_vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_train_step_no_nans(arch_id):
    cfg = get_smoke_config(arch_id)
    model = Model(cfg)
    key = jax.random.key(1)
    params = model.init(key)
    opt_state = adamw_init(params)
    step = jax.jit(make_train_step(model, OptimConfig(lr=1e-3)))
    batch = make_batch(cfg, key)
    params2, opt_state2, metrics = step(params, opt_state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    assert np.isfinite(float(metrics["grad_norm"]))
    # parameters actually moved
    moved = any(
        not np.array_equal(np.asarray(a, np.float32),
                           np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert moved


@pytest.mark.parametrize("arch_id", [a for a in ARCH_IDS
                                     if get_config(a).has_decoder])
def test_decode_step_shapes(arch_id):
    cfg = get_smoke_config(arch_id)
    model = Model(cfg)
    key = jax.random.key(2)
    params = model.init(key)
    cache = model.init_cache(2, 64)
    toks = jax.random.randint(key, (2, 1), 0, cfg.vocab_size)
    logits, cache2 = jax.jit(model.decode_step)(params, cache, toks)
    assert logits.shape == (2, 1, cfg.padded_vocab)
    np.testing.assert_array_equal(np.asarray(cache2["len"]), [1, 1])
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))


@pytest.mark.parametrize("arch_id", ["yi-9b", "chatglm3-6b", "mamba2-780m",
                                     "recurrentgemma-2b", "deepseek-moe-16b",
                                     "internvl2-76b"])
def test_prefill_decode_matches_forward(arch_id):
    """prefill(S) + decode(1) logits == forward(S+1) logits at fp32."""
    cfg = get_smoke_config(arch_id)
    if cfg.arch_type == "moe":
        cfg = dataclasses.replace(
            cfg, capacity_factor=float(cfg.n_experts) / cfg.top_k + 1)
    model = Model(cfg, dtype=jnp.float32)
    key = jax.random.key(3)
    params = model.init(key)
    b, s = 2, 17
    toks = jax.random.randint(key, (b, s + 1), 0, cfg.vocab_size)
    batch1 = {"tokens": toks[:, :s]}
    batch2 = {"tokens": toks}
    if cfg.arch_type == "vlm":
        pe = jax.random.normal(key, (b, 8, cfg.d_model), jnp.float32)
        batch1["patch_embeds"] = pe
        batch2["patch_embeds"] = pe
    ref1, _ = model.forward(params, batch1)
    cache = model.init_cache(b, 64)
    pre, cache = model.prefill(params, batch1, cache)
    np.testing.assert_allclose(np.asarray(pre[:, 0]), np.asarray(ref1[:, -1]),
                               rtol=1e-4, atol=1e-4)
    ref2, _ = model.forward(params, batch2)
    dec, _ = model.decode_step(params, cache, toks[:, s:s + 1])
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(ref2[:, -1]),
                               rtol=1e-3, atol=1e-3)


def test_sliding_window_decode_matches_windowed_forward():
    """The long_500k ring-buffer cache equals forward with the same window."""
    cfg = get_smoke_config("yi-9b")
    cfg = dataclasses.replace(cfg, sliding_window=None)
    model = Model(cfg, dtype=jnp.float32)
    key = jax.random.key(4)
    params = model.init(key)
    b, s, w = 1, 24, 8
    toks = jax.random.randint(key, (b, s + 1), 0, cfg.vocab_size)
    # decode with ring buffer of size w
    cache = model.init_cache(b, s + 1, window=w)
    lg = None
    for i in range(s + 1):
        lg, cache = model.decode_step(params, cache, toks[:, i:i + 1])
    # forward with an explicit sliding window
    logits, _ = model.forward(params, {"tokens": toks},
                              window_override=w)
    np.testing.assert_allclose(np.asarray(lg[:, 0]),
                               np.asarray(logits[:, -1]),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("arch_id", ["yi-9b", "mamba2-780m",
                                     "recurrentgemma-2b", "internvl2-76b"])
def test_right_padded_rows_decode_as_they_do_alone(arch_id):
    """Each row of a batch right-padded with PAD gets, at fp32, the prefill
    and decode logits it gets alone, and its own cache length.  The long
    row outgrows recurrentgemma's local-attention ring buffer (64)."""
    from repro.models.model import PAD

    cfg = get_smoke_config(arch_id)
    model = Model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(6))
    rng = np.random.default_rng(6)
    lens, n_steps, max_len = (5, 70), 3, 96
    prompts = [rng.integers(0, cfg.vocab_size, s, dtype=np.int32) for s in lens]
    steps = rng.integers(0, cfg.vocab_size, (len(lens), n_steps), dtype=np.int32)
    padded = np.full((len(lens), max(lens)), PAD, np.int32)
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p
    extra = {}
    if cfg.arch_type == "vlm":
        extra = {"patch_embeds": jax.random.normal(
            key=jax.random.key(7), shape=(1, 8, cfg.d_model))}
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode_step)

    def serve(tokens, next_tokens):
        batch = {"tokens": jnp.asarray(tokens)}
        batch.update({k: jnp.repeat(v, len(tokens), 0) for k, v in extra.items()})
        logits, cache = prefill(params, batch, model.init_cache(len(tokens), max_len))
        out = [logits[:, 0]]
        for j in range(n_steps):
            logits, cache = decode(params, cache, jnp.asarray(next_tokens[:, j:j + 1]))
            out.append(logits[:, 0])
        return np.stack(out, axis=1), np.asarray(cache["len"])

    together, got_lens = serve(padded, steps)
    n_prefix = 8 if extra else 0
    np.testing.assert_array_equal(got_lens, np.array(lens) + n_prefix + n_steps)
    for i, p in enumerate(prompts):
        alone, _ = serve(p[None], steps[i:i + 1])
        np.testing.assert_allclose(together[i], alone[0], rtol=1e-4, atol=1e-4)
