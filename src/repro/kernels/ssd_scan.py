"""Pallas TPU chunked SSD scan (Mamba-2 state-space duality).

TPU adaptation of the SSD algorithm: the grid is (batch, head, chunks) with
chunks sequential; the (N x P) state lives in VMEM scratch across chunk
iterations.  Within a chunk everything is dense (L x L) / (L x N) matmul work
for the MXU — exactly the papers' insight that SSD turns a recurrence into
mostly-GEMM compute — and only the small state crosses chunk boundaries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_scr, *,
                chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, :, 0, :].astype(jnp.float32)        # (L, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)         # (L,)
    a = a_ref[0]                                     # scalar
    bmat = b_ref[0].astype(jnp.float32)              # (L, N)
    cmat = c_ref[0].astype(jnp.float32)              # (L, N)

    da = dt * a                                      # (L,), negative
    cums = jnp.cumsum(da)                            # (L,)
    xdt = x * dt[:, None]                            # (L, P)

    # intra-chunk: M[i, j] = (C_i . B_j) exp(cums_i - cums_j) for i >= j
    gram = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (L, L)
    dec = cums[:, None] - cums[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, gram.shape, 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, gram.shape, 1)
    # mask exponents before exp (upper triangle would overflow to inf)
    dec = jnp.where(ii >= jj, dec, -1e30)
    m = jnp.exp(dec) * gram
    y = jax.lax.dot_general(m, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)     # (L, P)

    # inter-chunk: C_i^T (exp(cums_i) * h_prev)
    state = state_scr[...]                           # (N, P)
    y += jnp.exp(cums)[:, None] * jax.lax.dot_general(
        cmat, state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: h = exp(cums_L) h_prev + sum_j exp(cums_L - cums_j) B_j xdt_j^T
    tot = cums[chunk - 1]
    w = jnp.exp(tot - cums)                          # (L,)
    state_scr[...] = jnp.exp(tot) * state + jax.lax.dot_general(
        bmat * w[:, None], xdt, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (N, P)

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)


def ssd_scan(xh, dt, a, bmat, cmat, *, chunk: int = 256,
             interpret: bool = False):
    """xh: (B, S, H, P); dt: (B, S, H); a: (H,); b/cmat: (B, S, N).

    Returns y: (B, S, H, P) float32 outputs (state not returned; decode uses
    the pure-jnp step).  S must be a chunk multiple (pad upstream).
    """
    b, s, h, p = xh.shape
    n = bmat.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda b_, h_, c: (b_, c, h_, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b_, h_, c: (b_, c, h_)),
            pl.BlockSpec((1,), lambda b_, h_, c: (h_,)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c: (b_, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c: (b_, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, p),
                               lambda b_, h_, c: (b_, c, h_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, h, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xh, dt, a, bmat, cmat)
