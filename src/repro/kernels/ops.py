"""Jit'd dispatch wrappers over the Pallas kernels.

``impl`` selects the backend and has no default; callers pass it:
  * "jnp"    — pure-jnp reference path (what the dry-run lowers, so the XLA
               roofline reflects the portable path);
  * "pallas" — the Pallas TPU kernels (TPU target);
  * "interpret" — Pallas kernels in interpret mode (CPU correctness).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref as ref_mod
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.rglru_scan import rglru_scan as _rglru_pallas
from repro.kernels.ssd_scan import ssd_scan as _ssd_pallas

IMPLS = ("jnp", "pallas", "interpret")


def _check(impl: str) -> str:
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    return impl


@functools.partial(jax.jit, static_argnames=("causal", "window", "impl"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, impl: str):
    impl = _check(impl)
    if impl == "jnp":
        return ref_mod.flash_attention_ref(q, k, v, causal=causal,
                                           window=window)
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("window", "impl"))
def decode_attention(q, k_cache, v_cache, lengths, *,
                     window: int | None = None, impl: str):
    impl = _check(impl)
    if impl == "jnp":
        return ref_mod.decode_attention_ref(q, k_cache, v_cache, lengths,
                                            window=window)
    return _decode_pallas(q, k_cache, v_cache, lengths, window=window,
                          interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def ssd_scan(xh, dt, a, bmat, cmat, *, chunk: int = 256,
             impl: str):
    impl = _check(impl)
    if impl == "jnp":
        y, _ = ref_mod.ssd_scan_ref(xh, dt, a, bmat, cmat)
        return y
    return _ssd_pallas(xh, dt, a, bmat, cmat, chunk=chunk,
                       interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=("impl",))
def rglru_scan(a, b, *, impl: str):
    impl = _check(impl)
    if impl == "jnp":
        h, _ = ref_mod.rglru_scan_ref(a, b)
        return h
    return _rglru_pallas(a, b, interpret=(impl == "interpret"))
