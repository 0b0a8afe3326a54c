"""Pallas TPU RG-LRU linear scan: h_t = a_t * h_{t-1} + b_t.

Grid (batch, seq_blocks) with blocks sequential; the hidden state (W lanes)
persists in VMEM scratch.  Within a block the recurrence is a short
``fori_loop`` of elementwise VPU ops over full-width lanes — the recurrence
is memory-light (state never leaves VMEM) and the sequential depth per grid
step is the block length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, y_ref, h_scr, *, block_t: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(t, h):
        a_t = a_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)
        h = a_t * h + b_t
        y_ref[0, t, :] = h.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_t, step, h_scr[...])


def rglru_scan(a, b, *, block_t: int = 256, interpret: bool = False):
    """a, b: (B, S, W).  Returns h sequence (B, S, W) float32."""
    bsz, s, w = a.shape
    block_t = min(block_t, s)
    assert s % block_t == 0, (s, block_t)
    nb = s // block_t

    kernel = functools.partial(_rglru_kernel, block_t=block_t)
    return pl.pallas_call(
        kernel,
        grid=(bsz, nb),
        in_specs=[
            pl.BlockSpec((1, block_t, w), lambda b_, t: (b_, t, 0)),
            pl.BlockSpec((1, block_t, w), lambda b_, t: (b_, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_t, w), lambda b_, t: (b_, t, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((w,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
