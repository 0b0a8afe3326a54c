"""A decoder-only GQA transformer layer in plain float32.

Pre-norm (RMSNorm), grouped-query causal attention with rotary position
embedding over the whole head dimension (the two halves of each head
rotated as pairs), a SwiGLU MLP, no biases.  Query head ``h`` reads
key/value head ``h // (n_heads / n_kv_heads)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rope(x, theta: float):
    """x: (N, S, H, Dh), positions 0..S-1."""
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def block(sizes: dict, q, w: dict, x):
    """One layer over x: (N, S, D)."""
    from bench.reference import rmsnorm

    eps = sizes["norm_eps"]
    n, s, _ = x.shape
    hq, hkv, dh = sizes["n_heads"], sizes["n_kv_heads"], sizes["d_head"]
    a = w["attn"]
    h = q(rmsnorm(x, w["ln1"]["scale"], eps))
    qh = rope(jnp.einsum("nsd,dhk->nshk", h, q(a["wq"])), sizes["rope_theta"])
    kh = rope(jnp.einsum("nsd,dhk->nshk", h, q(a["wk"])), sizes["rope_theta"])
    vh = jnp.einsum("nsd,dhk->nshk", h, q(a["wv"]))
    group = jnp.arange(hq) // (hq // hkv)
    kh, vh = kh[:, :, group], vh[:, :, group]
    scores = jnp.einsum("nqhk,nshk->nhqs", q(qh), q(kh)) / jnp.sqrt(float(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("nhqs,nshk->nqhk", q(probs), q(vh))
    x = x + jnp.einsum("nqhk,hkd->nqd", q(att), q(a["wo"]))
    m = w["mlp"]
    h2 = q(rmsnorm(x, w["ln2"]["scale"], eps))
    up = jax.nn.silu(h2 @ q(m["w_gate"])) * (h2 @ q(m["w_up"]))
    return x + q(up) @ q(m["w_down"])
