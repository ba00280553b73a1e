"""Token sampling (greedy / temperature), padded-vocab aware."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("greedy", "vocab_logical"))
def sample_tokens(logits, key, *, greedy: bool = True,
                  temperature: float = 1.0, vocab_logical: int = 0):
    """logits: (B, V_phys). Returns (B,) int32 token ids < vocab_logical,
    every row in one program; under a temperature every row draws from
    ``key`` (categorical over the last axis draws the rows apart)."""
    logits = logits.astype(jnp.float32)
    if vocab_logical and vocab_logical < logits.shape[-1]:
        mask = jnp.arange(logits.shape[-1]) >= vocab_logical
        logits = jnp.where(mask, -1e30, logits)
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits / jnp.maximum(temperature, 1e-6),
        axis=-1).astype(jnp.int32)
