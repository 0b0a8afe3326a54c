"""The kernels of the serving path compile for a TPU v5e chip.

Nothing runs: each case compiles for a described ``v5e:2x2`` topology (one
chip of it), at chatglm3-6b widths, and checks that the compiled program
holds the Pallas kernel (``tpu_custom_call``).  The topology is described
inside a fixture, never at import, so that only the worker that runs this
file loads the TPU compiler.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.models import Model


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    # A compile for a described chip cannot be read back without the chip.
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_at_chatglm3_widths(one_chip):
    q = _sds(one_chip, (4, 32, 1024, 128))
    kv = _sds(one_chip, (4, 2, 1024, 128))
    _assert_kernel(jax.jit(flash_attention).lower(q, kv, kv).compile())


def test_decode_attention_compiles_at_chatglm3_widths(one_chip):
    q = _sds(one_chip, (8, 32, 128))
    cache = _sds(one_chip, (8, 2048, 2, 128))
    lens = _sds(one_chip, (8,), jnp.int32)
    _assert_kernel(jax.jit(decode_attention).lower(q, cache, cache,
                                                   lens).compile())


def test_chatglm3_pallas_decode_step_compiles(one_chip):
    cfg = dataclasses.replace(get_config("chatglm3-6b"), kernel_impl="pallas")
    model = Model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype), tree)

    params = on_chip(model.param_shapes())
    cache = on_chip(model.cache_shapes(4, 768))
    tokens = _sds(one_chip, (4, 1), jnp.int32)
    _assert_kernel(jax.jit(model.decode_step).lower(params, cache,
                                                    tokens).compile())
