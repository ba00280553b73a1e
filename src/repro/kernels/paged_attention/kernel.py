"""Paged decode attention — Pallas TPU kernel.

This is the TPU adaptation of the PagedAttention hot loop that vLLM (and
therefore TokenSim's memory model) is built around.  On GPU the kernel is
a warp-level gather over 16-token pages; the TPU-idiomatic analogue is:

* KV pages live in HBM as ``(Hkv, num_pages, page_size, D)``; the *block
  table* (logical->physical page map, the PagedAttention data structure)
  is a **scalar-prefetch** operand, so Mosaic can compute each grid step's
  page address early and overlap the page DMA with compute — gather
  becomes "DMA whole pages into VMEM", the coalesced-load analogue.
* Grid ``(B, Hkv, max_pages)``; each step attends one page.  All Q heads
  of one GQA group ride together as a ``(group, D)`` tile so the
  score matmul is ``(group × D) @ (D × page)`` on the MXU instead of a
  per-head matvec.  q and out are viewed as ``(B, Hkv, group, D)`` so
  that tile spans the block's last two dimensions whole, which Mosaic
  accepts for any group size (1 for MHA).  A ``(1, group, D)`` block of
  ``(B, Hq, D)`` is refused whenever ``group`` is neither a multiple of
  8 nor ``Hq``.
* Pages past ``ceil(context_len / page_size)`` are skipped with
  ``pl.when`` — requests only pay for the KV they actually hold, which is
  exactly the behavior TokenSim's block-granular memory manager models.

Validated in interpret mode against ``ref.py``; ``tests/test_tpu_compile.py``
compiles it for a TPU v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(bt_ref, cl_ref, q_ref, k_ref, v_ref, o_ref,
                       acc_ref, m_ref, l_ref, *,
                       page_size: int, max_pages: int, scale: float):
    b = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ctx_len = cl_ref[b]
    page_start = pi * page_size

    @pl.when(page_start < ctx_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (group, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)                        # (group, page)
        s = jnp.where(pos < ctx_len, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l_prev * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_new
        l_ref[:, 0] = l_new

    @pl.when(pi == max_pages - 1)
    def _finish():
        l = l_ref[:, 0]
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def paged_attention_fwd(q, k_pages, v_pages, block_tables, context_lens, *,
                        interpret: bool = False):
    """q: (B, Hq, D) one decode token per sequence;
    k_pages/v_pages: (Hkv, num_pages, page_size, D);
    block_tables: (B, max_pages) int32 physical page ids;
    context_lens: (B,) int32.  Returns (B, Hq, D).
    """
    b, hq, d = q.shape
    hkv, _, page_size, _ = k_pages.shape
    group = hq // hkv
    max_pages = block_tables.shape[1]
    grid = (b, hkv, max_pages)

    kernel = functools.partial(_paged_attn_kernel, page_size=page_size,
                               max_pages=max_pages, scale=d ** -0.5)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            # q: all heads of the kv group together
            pl.BlockSpec((1, 1, group, d),
                         lambda b_, h_, pi, bt, cl: (b_, h_, 0, 0)),
            # k/v: the physical page picked by the prefetched block table
            pl.BlockSpec((1, 1, page_size, d),
                         lambda b_, h_, pi, bt, cl: (h_, bt[b_, pi], 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda b_, h_, pi, bt, cl: (h_, bt[b_, pi], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda b_, h_, pi, bt, cl: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
        ],
    )

    # q heads of kv head h are contiguous (h*group..), so the reshape is
    # free and groups the head axis by kv head.
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=interpret,
    )(block_tables, context_lens, q.reshape(b, hkv, group, d), k_pages,
      v_pages)
    return out.reshape(b, hq, d)
