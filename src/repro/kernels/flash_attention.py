"""Pallas TPU flash attention (prefill) — online softmax over KV blocks.

TPU-native tiling (DESIGN.md §hardware-adaptation): the grid is
(batch, q_head, q_blocks, kv_blocks) with the kv dimension innermost and
*sequential* ("arbitrary" dimension semantics), so the running max /
denominator / accumulator live in VMEM scratch across kv iterations and the
(S x S) score matrix never exists in HBM.  Block shapes are MXU-aligned
(multiples of 128 on the sequence dims; head_dim is the lane dim).  GQA is
handled in the index maps: q head h reads kv head h // group.

Validated on CPU with interpret=True against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_kv: int, causal: bool,
                  window: int | None, n_kv: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip fully-masked kv blocks (upper triangle / out of window)
    q_first = qi * block_q
    q_last = q_first + block_q - 1
    k_first = kj * block_kv
    k_last = k_first + block_kv - 1
    live = jnp.bool_(True)
    if causal:
        live = jnp.logical_and(live, k_first <= q_last)
    if window is not None:
        live = jnp.logical_and(live, k_last > q_first - window)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)            # (bkv, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bkv)
        qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kj == n_kv - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: bool = False):
    """q: (B, H, S, Dh); k/v: (B, Hkv, S, Dh).  Returns (B, H, S, Dh)."""
    b, h, s, dh = q.shape
    hkv = k.shape[1]
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    block_q = min(block_q, s)
    block_kv = min(block_kv, s)
    assert s % block_q == 0 and s % block_kv == 0, (s, block_q, block_kv)
    n_q, n_kv = s // block_q, s // block_kv
    scale = 1.0 / (dh ** 0.5)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
        causal=causal, window=window, n_kv=n_kv)
    grid = (b, h, n_q, n_kv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda b_, h_, i, j, g=group: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, block_kv, dh),
                         lambda b_, h_, i, j, g=group: (b_, h_ // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
