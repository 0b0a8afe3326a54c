"""The Model: config -> init/forward/loss/prefill/decode.

Pure-functional: parameters are nested dicts of arrays; every public method
is jit-able.  Batches are dicts:

  dense/moe/ssm/hybrid: {"tokens": (B, S) int32}
  vlm:   {"tokens": (B, S_text), "patch_embeds": (B, N_patch, D)}
  audio: {"frame_embeds": (B, T, D), "labels": (B, T) int32}

Training loss is next-token cross-entropy (audio: per-frame CE against
``labels``); VLM masks the loss to text positions.

Serving keeps one cache length per row (``cache["len"]``, shape (B,)):
prompts of different lengths share a batch, right-padded with ``PAD``.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.models.layers import embed_init, make_norm
from repro.models.shard_ctx import constrain_act

#: Token id that right-pads a prompt to its batch's longest; it embeds as
#: token 0, and ``prefill`` reads each row's length from where it starts.
PAD = -1


class Model:
    def __init__(self, cfg: ModelConfig, dtype=jnp.bfloat16):
        self.cfg = cfg
        self.dtype = dtype

    # ------------------------------------------------------------- init ----

    def init(self, key) -> dict:
        """Random parameters from ``key``.

        Run it under ``jax.jit`` for full-size models: XLA then fuses each
        weight's draw, scale and cast, and ``lax.map`` makes the layers of a
        homogeneous stack one at a time, so the float32 transient is at most
        one layer's weight, never a whole stacked weight.
        """
        cfg = self.cfg
        k_embed, k_layers, k_norm = jax.random.split(key, 3)
        ninit, _ = make_norm(cfg.norm)
        params: dict[str, Any] = {"final_norm": ninit(cfg.d_model)}
        if cfg.arch_type == "audio":
            # encoder-only: classification head, no token embedding
            params["head"] = (jax.random.normal(
                k_embed, (cfg.d_model, cfg.padded_vocab))
                / math.sqrt(cfg.d_model)).astype(self.dtype)
        else:
            params["embed"] = embed_init(k_embed, cfg.padded_vocab,
                                         cfg.d_model, self.dtype)
        kinds = cfg.layer_types()
        keys = jax.random.split(k_layers, cfg.n_layers)
        if tfm.is_homogeneous(cfg):
            params["layers"] = jax.lax.map(
                lambda k: tfm.init_layer(k, kinds[0], cfg, self.dtype), keys)
        else:
            params["layers"] = [
                tfm.init_layer(keys[i], kinds[i], cfg, self.dtype)
                for i in range(cfg.n_layers)]
        return params

    def param_shapes(self) -> dict:
        """Parameter ShapeDtypeStructs without allocating (for dry-runs)."""
        return jax.eval_shape(self.init, jax.random.key(0))

    # ------------------------------------------------------------ embed ----

    def _embed_inputs(self, params, batch) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (x, loss_mask).  x: (B, S, D)."""
        cfg = self.cfg
        if cfg.arch_type == "audio":
            x = batch["frame_embeds"].astype(self.dtype)
            return x, jnp.ones(x.shape[:2], bool)
        tok = jnp.take(params["embed"]["tok"], batch["tokens"], axis=0)
        # pin the residual-stream layout: batch on dp, d_model unsharded.
        # Without this the FSDP-sharded embed table leaks its D-sharding
        # into the activations and GSPMD replicates the batch dim instead
        # (§Perf pair A, iteration 3).
        tok = constrain_act(tok, "dp", None, None)
        if cfg.arch_type == "vlm" and "patch_embeds" in batch:
            patches = batch["patch_embeds"].astype(self.dtype)
            x = jnp.concatenate([patches, tok], axis=1)
            mask = jnp.concatenate(
                [jnp.zeros(patches.shape[:2], bool),
                 jnp.ones(tok.shape[:2], bool)], axis=1)
            return constrain_act(x, "dp", None, None), mask
        return tok, jnp.ones(tok.shape[:2], bool)

    def _head(self, params, x) -> jnp.ndarray:
        x = constrain_act(x, "dp", None, None)
        w = params["head"] if self.cfg.arch_type == "audio" \
            else params["embed"]["head"]
        return constrain_act(x @ w, "dp", None, "model")

    # ---------------------------------------------------------- forward ----

    def forward(self, params, batch, *, remat: bool = False,
                window_override=None):
        """Full-sequence forward.  Returns (logits, aux_loss)."""
        cfg = self.cfg
        x, _ = self._embed_inputs(params, batch)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        _, norm = make_norm(cfg.norm)
        x, _, aux = tfm.stack_apply_seq(params["layers"], x, cfg, positions,
                                        caches=None, remat=remat,
                                        window_override=window_override)
        x = norm(params["final_norm"], x)
        return self._head(params, x), aux

    def loss_fn(self, params, batch, *, remat: bool = True):
        """Mean next-token (audio: per-frame) cross-entropy + MoE aux."""
        cfg = self.cfg
        logits, aux = self.forward(params, batch, remat=remat)
        if cfg.arch_type == "audio":
            labels = batch["labels"]
            lg = logits
        else:
            tokens = batch["tokens"]
            n_prefix = logits.shape[1] - tokens.shape[1]  # vlm patch prefix
            # next-token: text logits at position i predict token i+1
            lg = logits[:, n_prefix:-1] if tokens.shape[1] > 1 else logits
            labels = tokens[:, 1:] if tokens.shape[1] > 1 else tokens
        lg = lg.astype(jnp.float32)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
        loss = (logz - gold).mean() + 0.01 * aux
        return loss

    # ------------------------------------------------------------ cache ----

    def init_cache(self, batch: int, max_len: int, *,
                   window: int | None = None) -> dict:
        """Decode cache.  ``window`` caps attention cache size (ring buffer)."""
        cfg = self.cfg
        kinds = cfg.layer_types()
        size = min(max_len, window) if window else max_len

        def one(kind):
            c = tfm.init_layer_cache(kind, cfg, batch, size, self.dtype)
            return c

        if tfm.is_homogeneous(cfg):
            caches = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[one(kinds[0]) for _ in range(cfg.n_layers)])
        else:
            caches = [one(k) for k in kinds]
        return {"layers": caches, "len": jnp.zeros((batch,), jnp.int32)}

    def cache_shapes(self, batch: int, max_len: int, *,
                     window: int | None = None):
        return jax.eval_shape(
            functools.partial(self.init_cache, batch, max_len, window=window))

    # ---------------------------------------------------------- serving ----

    def prefill(self, params, batch, cache):
        """Process a prompt, filling ``cache``.  Returns (last_logits, cache).

        ``tokens`` rows may be right-padded with ``PAD``: each row's logits
        are those of its own last position, its cache length its own, and
        its recurrent state the state at its own last token.
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        x, _ = self._embed_inputs(
            params, dict(batch, tokens=jnp.maximum(tokens, 0)))
        seq_len = x.shape[1]
        # a vlm's patch prefix is never padded
        lens = (seq_len - tokens.shape[1]
                + jnp.sum(tokens != PAD, axis=1, dtype=jnp.int32))
        positions = jnp.broadcast_to(jnp.arange(seq_len), x.shape[:2])
        _, norm = make_norm(cfg.norm)
        x, new_layer_caches, _ = tfm.stack_apply_seq(
            params["layers"], x, cfg, positions, caches=cache["layers"],
            lens=lens)
        x = jnp.take_along_axis(x, (lens - 1)[:, None, None], axis=1)
        logits = self._head(params, norm(params["final_norm"], x))
        return logits, {"layers": new_layer_caches,
                        "len": cache["len"] + lens}

    def decode_step(self, params, cache, tokens):
        """One decode step.  tokens: (B, 1) int32 (audio: unsupported).

        Each row attends, and writes its K/V, at its own cache length.
        """
        cfg = self.cfg
        assert cfg.has_decoder, f"{cfg.name} is encoder-only"
        x = jnp.take(params["embed"]["tok"], tokens, axis=0)
        _, norm = make_norm(cfg.norm)
        x, new_caches = tfm.stack_apply_step(
            params["layers"], x, cfg, cache["layers"], cache["len"])
        x = norm(params["final_norm"], x)
        logits = self._head(params, x)
        return logits, {"layers": new_caches, "len": cache["len"] + 1}
