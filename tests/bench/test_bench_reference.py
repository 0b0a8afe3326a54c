"""The plain float32 references against the served path, and at full width
by shapes."""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import ROOT, SMOKE_SIZES

from bench import check, reference, run, weights

CONFIGS = {"chatglm3-6b": "chatglm3-6b_mamba2-780m",
           "mamba2-780m": "chatglm3-6b_mamba2-780m", "yi-9b": "yi-9b-tp4"}


def _model(arch):
    with open(os.path.join(ROOT, "bench", "configs", CONFIGS[arch] + ".json")) as f:
        return next(m for m in json.load(f)["models"] if m["arch"] == arch)


@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_weights_follow_the_programs_layout_at_full_size(arch):
    from repro.models.model import Model

    m = _model(arch)
    run._same_layout(weights.shapes(m["sizes"]),
                     Model(run.program_config(m)).param_shapes(), arch)


@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_reference_evaluates_layer_by_layer_at_full_width(arch):
    """Shapes only: one layer's float32 weights, a sample's activations
    (12 requests of 512 + 31 tokens) through one layer, and the head."""
    sizes = _model(arch)["sizes"]
    root = weights.root_key(0, 0)
    layer = jax.eval_shape(lambda r: reference._f32(weights.layer_params(sizes, r, 3)),
                           root)
    family = reference.FAMILIES[sizes["family"]]
    x = jax.ShapeDtypeStruct((check.SAMPLE, 543, sizes["d_model"]), jnp.float32)
    for q in reference.QUANT.values():
        out = jax.eval_shape(functools.partial(family.block, sizes, q), layer, x)
        assert out.shape == x.shape and out.dtype == jnp.float32
    top = jax.eval_shape(lambda r: reference._f32(weights.top_params(sizes, r)), root)
    pos = jax.ShapeDtypeStruct((check.SAMPLE, 32), jnp.int32)
    logits = jax.eval_shape(functools.partial(reference._head, sizes, reference.exact),
                            top, x, pos)
    assert logits.shape == (check.SAMPLE, 32, sizes["padded_vocab_size"])
    assert sum(v.size for v in jax.tree.leaves(layer)) * 4 < 1e9


@pytest.mark.parametrize("arch", ["chatglm3-6b", "mamba2-780m"])
def test_served_prefill_and_decode_agree_with_the_reference_in_float32(arch):
    """At smoke size and in float32, the program's greedy prefill plus
    decode through its cache gives the reference's logits."""
    from repro.launch import serve
    from repro.launch.mesh import make_serving_mesh
    from repro.models.model import Model

    m = _model(arch)
    sizes = dict(m["sizes"], **SMOKE_SIZES[arch])
    cfg = run.program_config({"arch": arch, "sizes": sizes})
    root = weights.root_key(2**35 + 3, 1)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          jax.jit(lambda r: weights.params(sizes, r))(root))
    shapes = serve.ServeShapes(prompt_lens=(24,), gen_range=(6, 6), batch_buckets=(2,))
    runner = serve.ModelRunner(cfg, make_serving_mesh(jax.devices()[:1]), shapes, 0,
                               params=params)
    runner.model = Model(cfg, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        runner.compile()
        prompts = np.random.default_rng(4).integers(0, sizes["vocab_size"], (2, 24),
                                                    dtype=np.int32)
        gen = runner.generate(prompts, 6)
    seqs = np.concatenate([prompts, gen.tokens[:, :-1]], axis=1)
    pos = np.broadcast_to(np.arange(23, 29), (2, 6))
    ref = reference.logits(sizes, root, seqs, pos)
    got = np.stack([np.asarray(x) for x in gen.logits], axis=1)
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() <= 1e-4 * scale, np.abs(got - ref).max() / scale
    np.testing.assert_array_equal(gen.tokens, ref.argmax(-1))
