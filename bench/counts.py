"""Operations and bytes a served model's steps need, from its sizes alone.

``sizes`` is a model's entry in a configuration file (``bench/configs``).
Counts are what the algorithm needs, not what one implementation happens
to do: a FLOP is a multiply or an add (a multiply-accumulate counts 2);
prefill computes the output head at the last position only, as the
program does; attention over a causal prompt of ``s`` tokens counts
``s (s + 1) / 2`` query-key pairs; decode at cache length ``c`` attends to
``c`` positions and reads only the ``c`` cached keys and values that hold
tokens.  Mamba-2's step counts its projections, its depthwise conv and
two multiply-accumulates per state element (update and read-out).

Weights and caches are stored in bfloat16, norm scales and Mamba-2's
per-head vectors and recurrent state in float32.  Bytes are per chip: a
tensor-parallel layer divides its weights, heads and states over ``chips``.
"""
from __future__ import annotations

BF16, F32 = 2, 4


def _dims(sizes: dict):
    d = sizes["d_model"]
    if sizes["family"] == "mamba2":
        di = sizes["ssm_expand"] * d
        return d, di, di // sizes["ssm_headdim"]
    return d, None, None


def layer_matmul_params(sizes: dict) -> int:
    """Weights of one layer that multiply every token."""
    d, di, nh = _dims(sizes)
    if sizes["family"] == "transformer":
        h, hkv, dh = sizes["n_heads"], sizes["n_kv_heads"], sizes["d_head"]
        return d * (h + 2 * hkv) * dh + h * dh * d + 3 * d * sizes["d_ff"]
    return d * (2 * di + 2 * sizes["ssm_d_state"] + nh) + di * d


def layer_other_params(sizes: dict) -> tuple[int, int]:
    """(bf16, float32) parameters of a layer beside its matmul weights."""
    d, di, nh = _dims(sizes)
    if sizes["family"] == "transformer":
        return 0, 2 * d
    return sizes["conv_kernel"] * di, d + 3 * nh


def param_bytes(sizes: dict) -> int:
    """Bytes of every weight of the model (all chips together)."""
    d, v, n_l = sizes["d_model"], sizes["padded_vocab_size"], sizes["n_layers"]
    other_bf16, other_f32 = layer_other_params(sizes)
    per_layer = (layer_matmul_params(sizes) + other_bf16) * BF16 + other_f32 * F32
    return n_l * per_layer + 2 * v * d * BF16 + d * F32


def _token_flops(sizes: dict, context: float) -> float:
    """FLOPs of one token through every layer, attending to ``context``."""
    d, di, nh = _dims(sizes)
    per_layer = 2.0 * layer_matmul_params(sizes)
    if sizes["family"] == "transformer":
        per_layer += 4.0 * sizes["n_heads"] * sizes["d_head"] * context
    else:
        per_layer += (2.0 * sizes["conv_kernel"] * di
                      + 4.0 * nh * sizes["ssm_d_state"] * sizes["ssm_headdim"])
    return sizes["n_layers"] * per_layer


def prefill_flops(sizes: dict, batch: int, prompt_len: int) -> float:
    """A batch's prefill: every prompt token, and the head at the last."""
    s = prompt_len
    head = 2.0 * sizes["d_model"] * sizes["padded_vocab_size"]
    if sizes["family"] == "transformer":
        d, h, dh = sizes["d_model"], sizes["n_heads"], sizes["d_head"]
        matmul = s * sizes["n_layers"] * 2.0 * layer_matmul_params(sizes)
        attn = sizes["n_layers"] * 4.0 * h * dh * s * (s + 1) / 2
        return batch * (matmul + attn + head)
    return batch * (s * _token_flops(sizes, 0) + head)


def decode_flops(sizes: dict, batch: int, cache_len: int) -> float:
    """One decode step of ``batch`` rows whose caches hold ``cache_len``
    tokens before it (the new token attends to ``cache_len + 1``)."""
    head = 2.0 * sizes["d_model"] * sizes["padded_vocab_size"]
    return batch * (_token_flops(sizes, cache_len + 1) + head)


def decode_bytes(sizes: dict, batch: int, cache_len: int, chips: int = 1) -> float:
    """Bytes one decode step must move on each chip: every weight but the
    embedding table (of which it reads ``batch`` rows), and the cache.

    Attention reads the ``cache_len`` cached keys and values and writes the
    new ones; Mamba-2 reads and writes its conv and recurrent states.
    """
    d, di, nh = _dims(sizes)
    v, n_l = sizes["padded_vocab_size"], sizes["n_layers"]
    weights = param_bytes(sizes) - v * d * BF16 + batch * d * BF16
    if sizes["family"] == "transformer":
        kv_row = 2 * sizes["n_kv_heads"] * sizes["d_head"] * BF16
        cache = n_l * batch * kv_row * (cache_len + 1)
    else:
        state = (nh * sizes["ssm_d_state"] * sizes["ssm_headdim"] * F32
                 + (sizes["conv_kernel"] - 1) * di * BF16)
        cache = n_l * batch * 2 * state
    return (weights + cache) / chips
