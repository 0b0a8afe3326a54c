"""Random weights of a served model, made by the benchmark from its seed.

The benchmark, not the program, makes the weights: the program serves
them, and the plain references in ``bench/reference`` make the very same
values again, layer by layer, from the same seed.  Each leaf of layer ``i``
is drawn from ``fold_in(fold_in(root, crc32(leaf path)), i)``, so one
layer's weights can be made without the others.

The tree follows the program's parameter layout (its leaf names and
shapes); ``run.py`` checks it against the program's own ``param_shapes``
before serving, so a change of layout fails loudly.  The sizes come from
the configuration file (``bench/configs``), never from the program.

Distributions follow the usual conventions: projections N(0, 1/fan_in),
embeddings N(0, 0.02^2), norm scales 1; Mamba-2's A = -[1..16] over the
heads, D = 1, and step sizes log-uniform in [1e-3, 1e-1] through an
inverse-softplus bias (the published init).
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

SERVED_DTYPE = jnp.bfloat16
DT_MIN, DT_MAX = 1e-3, 1e-1


def root_key(seed: int, model_index: int):
    """The key of one model of a cell; ``seed`` may exceed 32 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, model_index)


def _leaf_key(root, path: str, layer):
    return jax.random.fold_in(jax.random.fold_in(root, zlib.crc32(path.encode())),
                              layer)


def _normal(root, path, layer, shape, fan_in):
    x = jax.random.normal(_leaf_key(root, path, layer), shape, jnp.float32)
    return (x / math.sqrt(fan_in)).astype(SERVED_DTYPE)


def _ones(d):
    return jnp.ones((d,), jnp.float32)


def layer_params(sizes: dict, root, i) -> dict:
    """Weights of layer ``i`` (``i`` may be traced)."""
    d = sizes["d_model"]
    if sizes["family"] == "transformer":
        h, hkv, dh, f = (sizes["n_heads"], sizes["n_kv_heads"], sizes["d_head"],
                         sizes["d_ff"])
        return {
            "ln1": {"scale": _ones(d)},
            "ln2": {"scale": _ones(d)},
            "attn": {
                "wq": _normal(root, "attn/wq", i, (d, h, dh), d),
                "wk": _normal(root, "attn/wk", i, (d, hkv, dh), d),
                "wv": _normal(root, "attn/wv", i, (d, hkv, dh), d),
                "wo": _normal(root, "attn/wo", i, (h, dh, d), h * dh),
            },
            "mlp": {
                "w_gate": _normal(root, "mlp/w_gate", i, (d, f), d),
                "w_up": _normal(root, "mlp/w_up", i, (d, f), d),
                "w_down": _normal(root, "mlp/w_down", i, (f, d), f),
            },
        }
    if sizes["family"] == "mamba2":
        di = sizes["ssm_expand"] * d
        n, p, k = sizes["ssm_d_state"], sizes["ssm_headdim"], sizes["conv_kernel"]
        nh = di // p
        u = jax.random.uniform(_leaf_key(root, "ssm/dt", i), (nh,), jnp.float32)
        dt = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
        return {
            "ln1": {"scale": _ones(d)},
            "ssm": {
                "w_z": _normal(root, "ssm/w_z", i, (d, di), d),
                "w_x": _normal(root, "ssm/w_x", i, (d, di), d),
                "w_bc": _normal(root, "ssm/w_bc", i, (d, 2 * n), d),
                "w_dt": _normal(root, "ssm/w_dt", i, (d, nh), d),
                "conv": _normal(root, "ssm/conv", i, (k, di), k * k),
                "a_log": jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "d_skip": jnp.ones((nh,), jnp.float32),
                "w_out": _normal(root, "ssm/w_out", i, (di, d), di),
            },
        }
    raise ValueError(f"unknown family {sizes['family']!r}")


def top_params(sizes: dict, root) -> dict:
    """Embedding, output head and final norm."""
    d, v = sizes["d_model"], sizes["padded_vocab_size"]
    return {
        "final_norm": {"scale": _ones(d)},
        "embed": {
            "tok": (jax.random.normal(_leaf_key(root, "embed/tok", 0), (v, d),
                                      jnp.float32) * 0.02).astype(SERVED_DTYPE),
            "head": _normal(root, "embed/head", 0, (d, v), d),
        },
    }


def params(sizes: dict, root) -> dict:
    """The whole tree, layers stacked on a leading axis; ``lax.map`` makes
    one layer at a time, so the float32 transient is one layer's weight."""
    layers = jax.lax.map(lambda i: layer_params(sizes, root, i),
                         jnp.arange(sizes["n_layers"]))
    return {**top_params(sizes, root), "layers": layers}


def make(sizes: dict, root, shardings):
    """The tree on the device, in one jitted call, placed by ``shardings``."""
    return jax.jit(lambda r: params(sizes, r), out_shardings=shardings)(root)


def shapes(sizes: dict):
    """ShapeDtypeStructs of ``params`` without making them."""
    return jax.eval_shape(lambda r: params(sizes, r), root_key(0, 0))
