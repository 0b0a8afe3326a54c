"""Mamba-2 SSD (state-space duality) mixer — arXiv:2405.21060.

The chunked SSD algorithm is TPU-friendly by construction: within a chunk
the recurrence is computed as *dense* (chunk x chunk) matmuls (MXU work),
and only a small (H, N, P) state crosses chunk boundaries through a
``lax.scan``.  This file is the pure-jnp implementation used for lowering
and as the oracle for kernels/ssd_scan.py.

Per head h with headdim P and state size N:
    h_t = exp(dt_t * A) h_{t-1} + dt_t * B_t x_t^T        (N x P state)
    y_t = C_t^T h_t + D * x_t
A is a per-head negative scalar (Mamba-2 simplification); B_t, C_t are
shared across heads (single group).  Simplifications vs. the reference CUDA
implementation, recorded in DESIGN.md: the short depthwise conv is applied
to the x-branch only, and B/C get no conv.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

CONV_K = 4  # depthwise conv kernel width
DT_MIN, DT_MAX = 1e-3, 1e-1  # published Mamba-2 step-size init range


def ssm_init(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    d = cfg.d_model
    di = cfg.ssm_d_inner
    n = cfg.ssm_d_state
    nh = cfg.ssm_n_heads
    keys = jax.random.split(key, 7)
    s = 1.0 / math.sqrt(d)
    # step sizes log-uniform in [DT_MIN, DT_MAX] through an inverse-softplus
    # bias, as the published init does; a zero bias gives dt ~ 0.7, a stack
    # far more sensitive to rounding than the published model.
    dt = jnp.exp(jax.random.uniform(keys[6], (nh,))
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    # Separate (not fused) projections so the head dim shards cleanly on the
    # "model" mesh axis (w_z/w_x/conv/w_dt on heads; w_bc replicated).
    return {
        "w_z": (jax.random.normal(keys[0], (d, di)) * s).astype(dtype),
        "w_x": (jax.random.normal(keys[1], (d, di)) * s).astype(dtype),
        "w_bc": (jax.random.normal(keys[2], (d, 2 * n)) * s).astype(dtype),
        "w_dt": (jax.random.normal(keys[3], (d, nh)) * s).astype(dtype),
        "conv": (jax.random.normal(keys[4], (CONV_K, di)) / CONV_K).astype(dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "w_out": (jax.random.normal(keys[5], (di, d)) /
                  math.sqrt(di)).astype(dtype),
    }


def _split_proj(params, x, cfg: ModelConfig):
    n = cfg.ssm_d_state
    z = x @ params["w_z"]
    xin = x @ params["w_x"]
    bc = x @ params["w_bc"]
    bmat, cmat = bc[..., :n], bc[..., n:]
    dt = x @ params["w_dt"]
    return z, xin, bmat, cmat, dt


def conv_history(pad, x, lens=None):
    """The last ``k - 1`` inputs of each row of ``pad`` (B, k-1, W) followed
    by ``x`` (B, S, W): those before the row's own length ``lens`` (B,), or
    before ``S``.  Rows are read from ``x`` and ``pad`` themselves, so their
    concatenation need not be written out."""
    k1 = pad.shape[1]
    if lens is None:
        return jnp.concatenate([pad, x], axis=1)[:, -k1:]
    idx = (lens[:, None] - k1 + jnp.arange(k1))[:, :, None]
    from_x = jnp.take_along_axis(x, jnp.maximum(idx, 0), axis=1)
    from_pad = jnp.take_along_axis(pad, jnp.clip(idx + k1, 0, k1 - 1), axis=1)
    return jnp.where(idx >= 0, from_x, from_pad)


def _causal_conv(xin, conv_w, conv_state=None, lens=None):
    """Depthwise causal conv along the sequence.  xin: (B, S, Di)."""
    k = conv_w.shape[0]
    if conv_state is None:
        pad = jnp.zeros_like(xin[:, : k - 1])
    else:
        pad = conv_state  # (B, k-1, Di)
    xpad = jnp.concatenate([pad, xin], axis=1)
    out = sum(xpad[:, i:i + xin.shape[1]] * conv_w[i] for i in range(k))
    new_state = conv_history(pad, xin, lens)
    return jax.nn.silu(out.astype(jnp.float32)).astype(xin.dtype), new_state


def ssd_chunked(xh, dt, a, bmat, cmat, h0=None, chunk: int = 256,
                unroll: bool = False):
    """Chunked SSD scan.

    xh:   (B, S, H, P)   per-head inputs (dt already NOT applied)
    dt:   (B, S, H)      positive step sizes
    a:    (H,)           negative decay rates (A)
    bmat: (B, S, N), cmat: (B, S, N)  shared across heads
    h0:   (B, H, N, P) initial state or None
    Returns y: (B, S, H, P), h_final: (B, H, N, P).
    """
    b, s, h, p = xh.shape
    n = bmat.shape[-1]
    s_orig = s
    if s % chunk:
        # pad to a chunk multiple; dt=0 on padding makes it a no-op for the
        # state (decay exp(0)=1, update dt*Bx = 0).
        pad = chunk - s % chunk
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0)))
        s = s + pad
    nc = s // chunk
    xc = xh.reshape(b, nc, chunk, h, p).astype(jnp.float32)
    dtc = dt.reshape(b, nc, chunk, h)
    bc = bmat.reshape(b, nc, chunk, n).astype(jnp.float32)
    cc = cmat.reshape(b, nc, chunk, n).astype(jnp.float32)

    # per-position log decay within chunk: logdec[t] = sum_{u<=t} dt_u * a
    da = dtc * a[None, None, None, :]                 # (B,nc,L,H), negative
    cums = jnp.cumsum(da, axis=2)                     # inclusive cumsum

    def chunk_step(hprev, inputs):
        xck, dtk, bk, ck, cumk, dak = inputs          # one chunk, batch-major
        # hprev: (B,H,N,P)
        # intra-chunk: M[i,j] = (C_i . B_j) * exp(cum_i - cum_j) for i>=j
        # (decay from j+1..i) ; dt applied at source j.
        grams = jnp.einsum("bin,bjn->bij", ck, bk)    # (B,L,L)
        # per-head decay matrix; mask the exponent BEFORE exp — the upper
        # triangle has positive (huge) exponents that overflow to inf and
        # poison reverse-mode AD if exp'd first.
        dec = cumk[:, :, None, :] - cumk[:, None, :, :]  # (B,L,L,H) = cum_i-cum_j
        mask = jnp.tril(jnp.ones((xck.shape[1], xck.shape[1]), bool))
        dec = jnp.where(mask[None, :, :, None], dec, -1e30)
        m = jnp.exp(dec) * grams[..., None]           # (B,L,L,H)
        xdt = xck * dtk[..., None]                    # (B,L,H,P)
        y_intra = jnp.einsum("bijh,bjhp->bihp", m, xdt)
        # inter-chunk: y_state_i = C_i^T (exp(cum_i) . hprev)
        y_state = jnp.einsum("bin,bhnp->bihp", ck, hprev) * \
            jnp.exp(cumk)[..., :, :, None]
        # state update: h_new = exp(cum_L) hprev + sum_j exp(cum_L - cum_j) B_j xdt_j^T
        tot = cums_last = cumk[:, -1, :]              # (B,H)
        hdecay = jnp.exp(tot)[:, :, None, None]       # (B,H,1,1)
        w = jnp.exp(tot[:, None, :] - cumk)           # (B,L,H)
        h_new = hdecay * hprev + jnp.einsum(
            "bjn,bjh,bjhp->bhnp", bk, w, xdt)
        return h_new, y_intra + y_state

    if h0 is None:
        h0 = jnp.zeros((b, h, n, p), jnp.float32)
    inputs = (xc.swapaxes(0, 1), dtc.swapaxes(0, 1), bc.swapaxes(0, 1),
              cc.swapaxes(0, 1), cums.swapaxes(0, 1), da.swapaxes(0, 1))
    h_final, ys = jax.lax.scan(chunk_step, h0, inputs,
                               unroll=True if unroll else 1)
    y = ys.swapaxes(0, 1).reshape(b, s, h, p)[:, :s_orig]
    return y, h_final


def ssm_apply(params, x, cfg: ModelConfig, state=None, lens=None):
    """Full Mamba-2 mixer.  x: (B, S, D).

    state: None (prefill/train from zero) or dict(conv=(B,K-1,Di),
    ssm=(B,H,N,P)) for chunk-wise/streaming use.  lens: (B,) rows' own
    lengths in a right-padded batch, or None; a padded position has dt = 0
    and x = 0, so it leaves the state as it was.  Returns (y, new_state).
    """
    b, s, d = x.shape
    nh, p = cfg.ssm_n_heads, cfg.ssm_headdim
    z, xin, bmat, cmat, dt = _split_proj(params, x, cfg)
    conv_state = None if state is None else state["conv"]
    xin, new_conv = _causal_conv(xin, params["conv"], conv_state, lens)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"][None, None, :])
    if lens is not None:
        live = (jnp.arange(s) < lens[:, None])[..., None]
        dt = jnp.where(live, dt, 0.0)
        xin = jnp.where(live, xin, 0)
    a = -jnp.exp(params["a_log"])
    xh = xin.reshape(b, s, nh, p)
    h0 = None if state is None else state["ssm"]
    y, h_final = ssd_chunked(xh, dt, a, bmat, cmat, h0, cfg.ssm_chunk,
                             unroll=cfg.analysis_unroll)
    y = y + xh.astype(jnp.float32) * params["d_skip"][None, None, :, None]
    y = y.reshape(b, s, cfg.ssm_d_inner).astype(x.dtype)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    out = y @ params["w_out"]
    return out, {"conv": new_conv, "ssm": h_final}


def ssm_decode_step(params, x, cfg: ModelConfig, state):
    """One-token decode.  x: (B, 1, D); state from init_ssm_state/prefill."""
    b = x.shape[0]
    nh, p, n = cfg.ssm_n_heads, cfg.ssm_headdim, cfg.ssm_d_state
    z, xin, bmat, cmat, dt = _split_proj(params, x, cfg)
    # conv with cached inputs
    k = CONV_K
    xcat = jnp.concatenate([state["conv"], xin], axis=1)      # (B, k, Di)
    conv_out = sum(xcat[:, i] * params["conv"][i] for i in range(k))
    xin1 = jax.nn.silu(conv_out.astype(jnp.float32)).astype(x.dtype)  # (B, Di)
    new_conv = xcat[:, 1:]
    dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                          + params["dt_bias"][None, :])        # (B, H)
    a = -jnp.exp(params["a_log"])                              # (H,)
    xh = xin1.reshape(b, nh, p).astype(jnp.float32)
    b1 = bmat[:, 0].astype(jnp.float32)                        # (B, N)
    c1 = cmat[:, 0].astype(jnp.float32)
    decay = jnp.exp(dt1 * a[None, :])                          # (B, H)
    upd = jnp.einsum("bn,bh,bhp->bhnp", b1, dt1, xh)
    h_new = decay[:, :, None, None] * state["ssm"] + upd
    y = jnp.einsum("bn,bhnp->bhp", c1, h_new)
    y = y + xh * params["d_skip"][None, :, None]
    y = y.reshape(b, 1, cfg.ssm_d_inner).astype(x.dtype)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    return y @ params["w_out"], {"conv": new_conv, "ssm": h_new}


def init_ssm_state(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16):
    return {
        "conv": jnp.zeros((batch, CONV_K - 1, cfg.ssm_d_inner), dtype),
        "ssm": jnp.zeros((batch, cfg.ssm_n_heads, cfg.ssm_d_state,
                          cfg.ssm_headdim), jnp.float32),
    }
