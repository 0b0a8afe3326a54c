"""RG-LRU recurrent block (RecurrentGemma / Griffin, arXiv:2402.19427).

    r_t = sigmoid(W_r x_t)            recurrence gate
    i_t = sigmoid(W_i x_t)            input gate
    a_t = a ^ (c * r_t)               with a = sigmoid(lambda), c = 8
    h_t = a_t h_{t-1} + sqrt(1 - a_t^2) (i_t * x_t)

The sequence dimension is handled with ``jax.lax.associative_scan`` (log-
depth, TPU-friendly); decode is the O(1) single-step update.  The block
wraps the LRU with the Griffin structure: linear in-proj, short depthwise
conv, RG-LRU, and a gated (GeLU) output branch.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.ssm import conv_history

CONV_K = 4
C_EXP = 8.0


def rglru_init(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    d = cfg.d_model
    w = cfg.lru_width or d
    keys = jax.random.split(key, 6)
    s = 1.0 / math.sqrt(d)
    # lambda init so that a = sigmoid(lambda)^c in ~(0.9, 0.999)
    lam = jnp.log(jnp.expm1(jnp.linspace(0.35, 0.9, w))) * 0 + \
        jnp.linspace(2.2, 6.0, w)
    return {
        "w_x": (jax.random.normal(keys[0], (d, w)) * s).astype(dtype),
        "w_gate": (jax.random.normal(keys[1], (d, w)) * s).astype(dtype),
        "conv": (jax.random.normal(keys[2], (CONV_K, w)) / CONV_K).astype(dtype),
        "w_r": (jax.random.normal(keys[3], (w, w)) / math.sqrt(w)).astype(dtype),
        "w_i": (jax.random.normal(keys[4], (w, w)) / math.sqrt(w)).astype(dtype),
        "lam": lam.astype(jnp.float32),
        "w_out": (jax.random.normal(keys[5], (w, d)) / math.sqrt(w)).astype(dtype),
    }


def _gates(params, xb):
    """log a_t and scaled input.  xb: (..., W) float32."""
    r = jax.nn.sigmoid(xb @ params["w_r"].astype(jnp.float32))
    i = jax.nn.sigmoid(xb @ params["w_i"].astype(jnp.float32))
    log_a_base = jax.nn.log_sigmoid(params["lam"])      # (W,) < 0
    log_a = C_EXP * r * log_a_base                      # (..., W)
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-9))
    return a, beta * (i * xb)


def _conv(params, x, conv_state=None, lens=None):
    k = CONV_K
    if conv_state is None:
        pad = jnp.zeros_like(x[:, : k - 1])
    else:
        pad = conv_state
    xpad = jnp.concatenate([pad, x], axis=1)
    out = sum(xpad[:, i:i + x.shape[1]] * params["conv"][i] for i in range(k))
    return out, conv_history(pad, x, lens)


def rglru_apply(params, x, cfg: ModelConfig, state=None, lens=None):
    """x: (B, S, D) -> (B, S, D); state dict(conv, h) or None.

    lens: (B,) rows' own lengths in a right-padded batch, or None; a padded
    position has a = 1 and no input, so it leaves h as it was.
    """
    xb = x @ params["w_x"]                               # (B,S,W)
    conv_state = None if state is None else state["conv"]
    xb, new_conv = _conv(params, xb, conv_state, lens)
    xf = xb.astype(jnp.float32)
    a, b = _gates(params, xf)
    if lens is not None:
        live = (jnp.arange(x.shape[1]) < lens[:, None])[..., None]
        a, b = jnp.where(live, a, 1.0), jnp.where(live, b, 0.0)
    if state is not None:
        # fold the carried state into the first step: b_0 += a_0 * h_prev
        b = b.at[:, 0].add(a[:, 0] * state["h"])

    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, a2 * b1 + b2

    a_s, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    new_h = h[:, -1]
    gate = jax.nn.gelu((x @ params["w_gate"]).astype(jnp.float32))
    y = (h * gate).astype(x.dtype) @ params["w_out"]
    return y, {"conv": new_conv, "h": new_h}


def rglru_decode_step(params, x, cfg: ModelConfig, state):
    """x: (B, 1, D); O(1) recurrent update."""
    xb = x @ params["w_x"]                               # (B,1,W)
    k = CONV_K
    xcat = jnp.concatenate([state["conv"], xb], axis=1)  # (B,k,W)
    conv_out = sum(xcat[:, i] * params["conv"][i] for i in range(k))
    new_conv = xcat[:, 1:]
    xf = conv_out.astype(jnp.float32)                    # (B,W)
    a, b = _gates(params, xf)
    h = a * state["h"] + b
    gate = jax.nn.gelu((x[:, 0] @ params["w_gate"]).astype(jnp.float32))
    y = ((h * gate).astype(x.dtype) @ params["w_out"])[:, None]
    return y, {"conv": new_conv, "h": h}


def init_rglru_state(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16):
    w = cfg.lru_width or cfg.d_model
    return {
        "conv": jnp.zeros((batch, CONV_K - 1, w), dtype),
        "h": jnp.zeros((batch, w), jnp.float32),
    }
