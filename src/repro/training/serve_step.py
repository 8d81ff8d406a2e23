"""Serving: prefill + KV-cache decode steps (batched requests)."""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.transformer import (NO_HINTS, ShardingHints, encode,
                                      forward, init_caches)


def prefill(params, cfg: ModelConfig, tokens: jnp.ndarray, *,
            cache_len: int, lengths=None, frames=None, patches=None,
            hints: ShardingHints = NO_HINTS, attn_backend=None):
    """Process the prompt, fill caches. Returns (last_logits, caches, memory).

    lengths: (B,) true prompt lengths for a LEFT-padded mixed batch.  Without
    it, every token (pads included) is attended and positions assume no
    padding — only correct for unpadded batches.  With it, pads are masked
    out of attention and the KV cache, and the returned last-position logits
    are each row's true final-token logits (left-padding puts the final token
    at index -1).  Subsequent decode positions must start at `lengths[b]`.
    attn_backend: registry attention backend override (see models/attention).
    """
    b, s = tokens.shape
    caches = init_caches(cfg, b, cache_len)
    memory = None
    if cfg.is_encoder_decoder:
        memory, _ = encode(params, cfg, frames, hints)
    logits, caches, _ = forward(params, cfg, tokens, caches=caches,
                                patches=patches, memory=memory, hints=hints,
                                last_only=True, lengths=lengths,
                                attn_backend=attn_backend)
    return logits[:, -1], caches, memory


def decode_step(params, cfg: ModelConfig, tokens: jnp.ndarray,
                positions: jnp.ndarray, caches, *, memory=None,
                hints: ShardingHints = NO_HINTS, attn_backend=None):
    """One token for every sequence. tokens/positions (B, 1)."""
    logits, caches, _ = forward(params, cfg, tokens, positions=positions,
                                caches=caches, memory=memory, hints=hints,
                                attn_backend=attn_backend)
    return logits[:, -1], caches


def sample(logits: jnp.ndarray, key, temperature: float = 0.0,
           top_k: int = 0) -> jnp.ndarray:
    """logits (B, V) -> token ids (B,)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lf = logits.astype(jnp.float32) / temperature
    if top_k:
        vals, _ = jax.lax.top_k(lf, top_k)
        lf = jnp.where(lf < vals[..., -1:], -1e30, lf)
    return jax.random.categorical(key, lf, axis=-1).astype(jnp.int32)


def sample_per_slot(logits: jnp.ndarray, keys: jnp.ndarray,
                    temperature: float = 0.0, top_k: int = 0) -> jnp.ndarray:
    """logits (B, V), keys (B, 2): one independent PRNG key per row.

    Continuous-batching slots each belong to a different request, so rows
    must not share a key (and a request's key stream must not restart when
    its slot-mates change).
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.vmap(
        lambda lg, k: sample(lg[None], k, temperature, top_k)[0]
    )(logits, keys)


_prefill_jit = jax.jit(prefill, static_argnums=(1,),
                       static_argnames=("cache_len", "hints", "attn_backend"))
_decode_step_jit = jax.jit(decode_step, static_argnums=(1,),
                           static_argnames=("hints", "attn_backend"))


def generate(params, cfg: ModelConfig, prompt: jnp.ndarray, *,
             max_new_tokens: int, cache_len: int, key=None,
             temperature: float = 0.0, frames=None, patches=None,
             hints: ShardingHints = NO_HINTS, attn_backend=None) -> jnp.ndarray:
    """Greedy/temperature generation loop (host-driven, jit per step)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    b, s = prompt.shape
    last, caches, memory = _prefill_jit(
        params, cfg, prompt, cache_len=cache_len, frames=frames,
        patches=patches, hints=hints, attn_backend=attn_backend)
    out = []
    tok = sample(last, key, temperature)
    out.append(tok)
    for i in range(1, max_new_tokens):
        key, sub = jax.random.split(key)
        pos = jnp.full((b, 1), s + i - 1, jnp.int32)
        logits, caches = _decode_step_jit(params, cfg, tok[:, None], pos,
                                          caches, memory=memory, hints=hints,
                                          attn_backend=attn_backend)
        tok = sample(logits, sub, temperature)
        out.append(tok)
    return jnp.stack(out, axis=1)
