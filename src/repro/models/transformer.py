"""Block assembly: attention/MoE/SSM/RG-LRU residual blocks + layer stacks.

Homogeneous stacks (dense, moe, ssm, audio, vlm) run under ``lax.scan`` over
stacked layer parameters — essential to keep HLO size and compile time
bounded at 80 layers.  The hybrid 1:2 pattern (recurrentgemma) uses a Python
loop over its 26 heterogeneous layers.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import (apply_rope, attention_blockwise,
                                 attention_decode, attention_full, attn_init,
                                 make_norm, mlp, mlp_init)

BLOCKWISE_THRESHOLD = 8192  # use online-softmax attention at/above this S


# ---------------------------------------------------------------- init ----


def init_layer(key, kind: str, cfg: ModelConfig, dtype=jnp.bfloat16):
    ninit, _ = make_norm(cfg.norm)
    d = cfg.d_model
    keys = jax.random.split(key, 4)
    p: dict[str, Any] = {"ln1": ninit(d)}
    if kind in ("attn_mlp", "moe", "attn"):
        p["attn"] = attn_init(keys[0], d, cfg.n_heads, cfg.n_kv_heads,
                              cfg.head_dim, dtype)
        p["ln2"] = ninit(d)
        if kind == "moe":
            p["moe"] = moe_mod.moe_init(keys[1], cfg, dtype)
        else:
            p["mlp"] = mlp_init(keys[1], d, cfg.d_ff, cfg.activation, dtype)
    elif kind == "ssm":
        p["ssm"] = ssm_mod.ssm_init(keys[0], cfg, dtype)
    elif kind == "rglru":
        p["rglru"] = rglru_mod.rglru_init(keys[0], cfg, dtype)
        p["ln2"] = ninit(d)
        p["mlp"] = mlp_init(keys[1], d, cfg.d_ff, cfg.activation, dtype)
    else:
        raise ValueError(kind)
    return p


def init_layer_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16):
    if kind in ("attn_mlp", "moe", "attn"):
        size = max_len if kind != "attn" or cfg.arch_type != "hybrid" else \
            min(max_len, cfg.local_window)
        return {
            "k": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.head_dim), dtype),
        }
    if kind == "ssm":
        return ssm_mod.init_ssm_state(cfg, batch, dtype)
    if kind == "rglru":
        return rglru_mod.init_rglru_state(cfg, batch, dtype)
    raise ValueError(kind)


# ------------------------------------------------------------- attention --


def _attention_seq(p_attn, x, cfg: ModelConfig, positions, window,
                   cache=None, lens=None):
    """Full-sequence attention (train/prefill).  Returns (out, new_cache).

    ``lens`` (B,): each row's own length, needed with a ``cache`` (rows
    right-padded to ``S``).  A pad lies after a row's last token, so the
    causal mask keeps it out of the row's outputs; its K/V are written
    past the row's length, where decode masks them until it overwrites
    them.
    """
    q = jnp.einsum("bsd,dhk->bshk", x, p_attn["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p_attn["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p_attn["wv"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    s = x.shape[1]
    if cfg.kernel_impl != "jnp":
        # Pallas flash attention ((B,H,S,D) layout)
        from repro.kernels import ops
        out = ops.flash_attention(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
            causal=cfg.causal, window=window,
            impl=cfg.kernel_impl).swapaxes(1, 2)
    elif s >= BLOCKWISE_THRESHOLD:
        out = attention_blockwise(q, k, v, causal=cfg.causal, window=window,
                                  unroll=cfg.analysis_unroll)
    else:
        out = attention_full(q, k, v, causal=cfg.causal, window=window)
    new_cache = None
    if cache is not None:
        size = cache["k"].shape[1]
        if s <= size:
            new_cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, 1),
                "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, 1),
            }
        else:  # ring buffer: slot j keeps the row's latest position = j mod size
            last = (lens - 1)[:, None]
            slot_pos = last - jnp.mod(last - jnp.arange(size), size)
            idx = jnp.maximum(slot_pos, 0)[:, :, None, None]
            new_cache = {"k": jnp.take_along_axis(k, idx, axis=1),
                         "v": jnp.take_along_axis(v, idx, axis=1)}
    return jnp.einsum("bshk,hkd->bsd", out, p_attn["wo"]), new_cache


def _attention_step(p_attn, x, cfg: ModelConfig, cache, cache_len, window):
    """One-token decode.  x: (B, 1, D); cache_len: (B,) tokens so far.

    Each row's token takes its own position and writes its K/V in its own
    slot, in place (a ring buffer where the cache is shorter than the
    sequence).
    """
    q = jnp.einsum("bsd,dhk->bshk", x, p_attn["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p_attn["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p_attn["wv"])
    pos = cache_len[:, None]
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    size = cache["k"].shape[1]
    rows, slot = jnp.arange(x.shape[0]), jnp.mod(cache_len, size)
    kc = cache["k"].at[rows, slot].set(k[:, 0], unique_indices=True,
                                       mode="promise_in_bounds")
    vc = cache["v"].at[rows, slot].set(v[:, 0], unique_indices=True,
                                       mode="promise_in_bounds")
    n_valid = jnp.minimum(cache_len + 1, size)
    if cfg.kernel_impl != "jnp":
        from repro.kernels import ops
        out = ops.decode_attention(q[:, 0], kc, vc, n_valid, window=window,
                                   impl=cfg.kernel_impl)[:, None]
    else:
        out = attention_decode(q, kc, vc, n_valid, window=window)
    return (jnp.einsum("bshk,hkd->bsd", out, p_attn["wo"]),
            {"k": kc, "v": vc})


# ---------------------------------------------------------------- blocks --


def block_apply_seq(p, x, kind: str, cfg: ModelConfig, positions,
                    cache=None, window_override=None, lens=None):
    """Full-sequence residual block.  Returns (x, new_cache, aux_loss).

    ``lens`` (B,): each row's own length where the batch is right-padded
    (None: all ``S``); a row's cache or state is its own at that length.
    """
    _, norm = make_norm(cfg.norm)
    aux = jnp.zeros((), jnp.float32)
    h = norm(p["ln1"], x)
    if kind in ("attn_mlp", "moe", "attn"):
        window = window_override if window_override is not None else (
            cfg.local_window if kind == "attn" and cfg.arch_type == "hybrid"
            else cfg.sliding_window)
        a_out, new_cache = _attention_seq(
            p["attn"], h, cfg, positions, window, cache, lens)
        x = x + a_out
        h2 = norm(p["ln2"], x)
        if kind == "moe":
            m_out, aux = moe_mod.moe_apply(p["moe"], h2, cfg)
        else:
            m_out = mlp(p["mlp"], h2, cfg.activation)
        x = x + m_out
    elif kind == "ssm":
        s_out, new_cache = ssm_mod.ssm_apply(p["ssm"], h, cfg, cache, lens)
        x = x + s_out
    elif kind == "rglru":
        r_out, new_cache = rglru_mod.rglru_apply(p["rglru"], h, cfg, cache,
                                               lens)
        x = x + r_out
        h2 = norm(p["ln2"], x)
        x = x + mlp(p["mlp"], h2, cfg.activation)
    else:
        raise ValueError(kind)
    return x, new_cache, aux


def block_apply_step(p, x, kind: str, cfg: ModelConfig, cache, cache_len,
                     window_override=None):
    """One-token decode block.  Returns (x, new_cache)."""
    _, norm = make_norm(cfg.norm)
    h = norm(p["ln1"], x)
    if kind in ("attn_mlp", "moe", "attn"):
        window = window_override if window_override is not None else (
            cfg.local_window if kind == "attn" and cfg.arch_type == "hybrid"
            else cfg.sliding_window)
        # a ring-buffer cache sized below seq acts as the window itself
        a_out, new_cache = _attention_step(p["attn"], h, cfg, cache,
                                           cache_len, window)
        x = x + a_out
        h2 = norm(p["ln2"], x)
        if kind == "moe":
            m_out, _ = moe_mod.moe_apply(p["moe"], h2, cfg)
        else:
            m_out = mlp(p["mlp"], h2, cfg.activation)
        x = x + m_out
    elif kind == "ssm":
        s_out, new_cache = ssm_mod.ssm_decode_step(p["ssm"], h, cfg, cache)
        x = x + s_out
    elif kind == "rglru":
        r_out, new_cache = rglru_mod.rglru_decode_step(p["rglru"], h, cfg, cache)
        x = x + r_out
        h2 = norm(p["ln2"], x)
        x = x + mlp(p["mlp"], h2, cfg.activation)
    else:
        raise ValueError(kind)
    return x, new_cache


# ----------------------------------------------------------- layer stack --


def is_homogeneous(cfg: ModelConfig) -> bool:
    kinds = cfg.layer_types()
    return all(k == kinds[0] for k in kinds)


def stack_apply_seq(layers_params, x, cfg: ModelConfig, positions,
                    caches=None, remat: bool = False, window_override=None,
                    lens=None):
    """Run all layers over a full sequence.

    layers_params: stacked pytree (homogeneous) or list (hybrid).
    caches: stacked cache pytree / list / None.
    lens: (B,) rows' own lengths in a right-padded batch, or None.
    Returns (x, new_caches, total_aux).
    """
    kinds = cfg.layer_types()
    if is_homogeneous(cfg):
        kind = kinds[0]

        def body(carry, xs):
            xc, aux = carry
            if caches is None:
                lp = xs
                cache = None
            else:
                lp, cache = xs
            xo, ncache, a = block_apply_seq(
                lp, xc, kind, cfg, positions, cache, window_override, lens)
            return (xo, aux + a), ncache

        if remat:
            body = jax.checkpoint(body)
        xs = layers_params if caches is None else (layers_params, caches)
        (x, aux), new_caches = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), xs,
            unroll=True if cfg.analysis_unroll else 1)
        return x, new_caches, aux
    # hybrid: python loop
    aux = jnp.zeros((), jnp.float32)
    new_caches = []
    for i, kind in enumerate(kinds):
        cache = None if caches is None else caches[i]

        def fn(lp, xc, cch, kind=kind):
            return block_apply_seq(lp, xc, kind, cfg, positions, cch,
                                   window_override, lens)

        if remat:
            fn = jax.checkpoint(fn)
        x, nc, a = fn(layers_params[i], x, cache)
        aux = aux + a
        new_caches.append(nc)
    return x, (new_caches if caches is not None else None), aux


def stack_apply_step(layers_params, x, cfg: ModelConfig, caches, cache_len,
                     window_override=None):
    """One decode step through all layers.  Returns (x, new_caches)."""
    kinds = cfg.layer_types()
    if is_homogeneous(cfg):
        kind = kinds[0]

        def body(xc, xs):
            lp, cache = xs
            xo, ncache = block_apply_step(lp, xc, kind, cfg, cache,
                                          cache_len, window_override)
            return xo, ncache

        x, new_caches = jax.lax.scan(body, x, (layers_params, caches),
                                     unroll=True if cfg.analysis_unroll else 1)
        return x, new_caches
    new_caches = []
    for i, kind in enumerate(kinds):
        x, nc = block_apply_step(layers_params[i], x, kind, cfg, caches[i],
                                 cache_len, window_override)
        new_caches.append(nc)
    return x, new_caches
