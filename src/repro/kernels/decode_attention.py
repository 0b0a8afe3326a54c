"""Pallas TPU flash-decode: one query token vs. a long KV cache (GQA).

Decode attention is HBM-bandwidth-bound: the valid part of the KV cache
streams through VMEM once per step.  The grid is (batch, kv_head, kv_blocks)
with kv_blocks sequential; each program attends the whole GQA *group* of
query heads (G = H / Hkv) against one kv-head's cache block, so the cache is
read exactly once regardless of the query-head count.  Valid-length masking
supports both dense caches and ring-buffer sliding windows.

TPU layout: the per-row lengths are scalar-prefetched into SMEM (they drive
the index maps, which stop fetching blocks past a row's length).  The cache
(B, S, Hkv, Dh) is viewed as (B, S, Hkv*Dh) so that one kv head's block is
(block_s, Dh) on the last two dimensions: sublane-aligned for block_s and
lane-aligned when Dh is a multiple of 128 (or Hkv == 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, block_s: int, n_s: int,
                   window: int | None):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[pl.program_id(0)]
    base = j * block_s

    @pl.when(base < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)              # (G, dh)
        k = k_ref[0].astype(jnp.float32)                 # (bs, dh)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, bs)
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos < length
        if window is not None:
            valid = jnp.logical_and(valid, kpos >= length - window)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_s - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, *,
                     window: int | None = None, block_s: int = 256,
                     interpret: bool = False):
    """q: (B, H, Dh); caches: (B, S, Hkv, Dh); lengths: (B,) int32.

    Returns (B, H, Dh).
    """
    b, h, dh = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    assert h % hkv == 0
    g = h // hkv
    block_s = min(block_s, s)
    assert s % block_s == 0, (s, block_s)
    n_s = s // block_s
    scale = 1.0 / (dh ** 0.5)
    qg = q.reshape(b, hkv, g, dh)
    kf = k_cache.reshape(b, s, hkv * dh)
    vf = v_cache.reshape(b, s, hkv * dh)

    def kv_map(b_, g_, j, lens):
        # blocks past the row's length repeat the last live block index, so
        # the pipeline issues no DMA for them (their compute is skipped too)
        last = jnp.maximum(lens[b_] - 1, 0) // block_s
        return (b_, jnp.minimum(j, last), g_)

    kernel = functools.partial(_decode_kernel, scale=scale, block_s=block_s,
                               n_s=n_s, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, n_s),
            in_specs=[
                pl.BlockSpec((1, 1, g, dh),
                             lambda b_, g_, j, lens: (b_, g_, 0, 0)),
                pl.BlockSpec((1, block_s, dh), kv_map),
                pl.BlockSpec((1, block_s, dh), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, g, dh),
                                   lambda b_, g_, j, lens: (b_, g_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g, dh), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kf, vf)
    return out.reshape(b, h, dh)
