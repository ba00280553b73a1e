"""Jit'd public wrapper for paged decode attention.

Natural serving layout in: q (B,H,D), pages (num_pages, page_size, Hkv, D)
(token-major, the layout ``serving.paged_model.init_pages`` stores and
appends into), block tables and context lens. The wrapper transposes the
whole page pool to the kernel's head-major layout on every call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.paged_attention.kernel import paged_attention_fwd


@jax.jit
def paged_attention(q, k_pages, v_pages, block_tables, context_lens):
    """q: (B,H,D); k/v_pages: (NP, page, Hkv, D) -> (B,H,D)."""
    kh = jnp.transpose(k_pages, (2, 0, 1, 3))      # (Hkv, NP, page, D)
    vh = jnp.transpose(v_pages, (2, 0, 1, 3))
    return paged_attention_fwd(q, kh, vh,
                               block_tables.astype(jnp.int32),
                               context_lens.astype(jnp.int32),
                               interpret=interpret_mode())
