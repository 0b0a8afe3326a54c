"""Plain float32 references of the served models, evaluated layer by layer.

Independent of the program: nothing here imports ``repro``, and the
weights are made again from the seed by ``bench.weights``, one layer at a
time, so a model whose float32 weights would not fit the chip is still
evaluated whole.  Every matrix product runs at ``highest`` precision.

``quant`` rounds both operands of every matrix product, per tensor
scaled to the format's range, before the product; it serves the low
precision control (float8 e4m3 for a model served in bfloat16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.reference import mamba2, transformer

FAMILIES = {"transformer": transformer, "mamba2": mamba2}


def fp8(x):
    """Round to float8 e4m3, scaled per tensor so its largest |x| is 448."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def exact(x):
    return x


QUANT = {"float32": exact, "float8_e4m3": fp8}


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def logits(sizes: dict, root, seqs: np.ndarray, positions: np.ndarray,
           quant: str = "float32", device=None) -> np.ndarray:
    """Logits ``(N, G, V)`` at ``positions`` ``(N, G)`` of the token
    sequences ``seqs`` ``(N, S)``: a full causal forward pass.

    Positions past a sequence's own end may be padding; causality keeps
    them from touching earlier positions.
    """
    family, q = FAMILIES[sizes["family"]], QUANT[quant]
    device = device or jax.devices()[0]
    put = functools.partial(jax.device_put, device=device)
    with jax.default_matmul_precision("highest"):
        top = jax.jit(lambda r: _f32(weights.top_params(sizes, r)))(put(root))
        layer_w = jax.jit(lambda r, i: _f32(weights.layer_params(sizes, r, i)))
        block = jax.jit(functools.partial(family.block, sizes, q))
        x = jnp.take(top["embed"]["tok"], put(jnp.asarray(seqs)), axis=0)
        for i in range(sizes["n_layers"]):
            x = block(layer_w(put(root), put(jnp.int32(i))), x)
        out = jax.jit(functools.partial(_head, sizes, q))(
            top, x, put(jnp.asarray(positions)))
        return np.asarray(out)


def _head(sizes, q, top, x, positions):
    x = jnp.take_along_axis(x, positions[..., None], axis=1)
    x = rmsnorm(x, top["final_norm"]["scale"], sizes["norm_eps"])
    return q(x) @ q(top["embed"]["head"])


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale
