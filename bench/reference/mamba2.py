"""A Mamba-2 layer in plain float32, as its sequential recurrence.

Pre-norm (RMSNorm), then per head ``h`` with state ``S_h`` (N x P):

    x, z, B, C, dt = h W_x, h W_z, h W_B, h W_C, h W_dt
    x  = silu(causal depthwise conv(x))          (kernel ``conv_kernel``)
    dt = softplus(dt + dt_bias),  A = -exp(a_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T
    y_t = C_t^T S_t + D x_t
    out = (y * silu(z)) W_out

one group (B and C shared by the heads), a token at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def block(sizes: dict, q, w: dict, x):
    """One layer over x: (N, S, D)."""
    from bench.reference import rmsnorm

    p = w["ssm"]
    n, s, d = x.shape
    di = sizes["ssm_expand"] * d
    hp, ns, k = sizes["ssm_headdim"], sizes["ssm_d_state"], sizes["conv_kernel"]
    nh = di // hp
    h = q(rmsnorm(x, w["ln1"]["scale"], sizes["norm_eps"]))
    z, xin = h @ q(p["w_z"]), h @ q(p["w_x"])
    bc, dt = h @ q(p["w_bc"]), h @ q(p["w_dt"])
    xpad = jnp.concatenate([jnp.zeros((n, k - 1, di)), xin], axis=1)
    conv = sum(xpad[:, i:i + s] * p["conv"][i] for i in range(k))
    xs = jax.nn.silu(conv).reshape(n, s, nh, hp)
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # (N, S, H)
    a = -jnp.exp(p["a_log"])

    def step(state, t):
        x_t, b_t, c_t, dt_t = t
        state = (jnp.exp(dt_t * a)[:, :, None, None] * state
                 + jnp.einsum("nh,nk,nhp->nhkp", dt_t, b_t, x_t))
        return state, jnp.einsum("nk,nhkp->nhp", c_t, state)

    ts = (xs.swapaxes(0, 1), bc[..., :ns].swapaxes(0, 1),
          bc[..., ns:].swapaxes(0, 1), dt.swapaxes(0, 1))
    _, y = jax.lax.scan(step, jnp.zeros((n, nh, ns, hp)), ts)
    y = y.swapaxes(0, 1) + xs * p["d_skip"][:, None]
    y = y.reshape(n, s, di) * jax.nn.silu(z)
    return x + q(y) @ q(p["w_out"])
