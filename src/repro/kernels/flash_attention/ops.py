"""Jit'd public wrapper for the flash attention kernel.

Accepts the model-layout tensors q:(B,Sq,H,D), k/v:(B,Skv,Hkv,D), pads the
head_dim to a multiple of 128 (MXU lane width) and seq lens to the block
size, transposes to head-major, runs the kernel, and undoes the padding.

On the CPU platform the kernel runs in interpret mode; on TPU it
compiles to Mosaic (``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_fwd


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "softcap"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_kv: int = 512, softcap: float = 0.0):
    """Model layout in/out: q (B,Sq,H,D) -> (B,Sq,H,D)."""
    interpret = interpret_mode()
    b, sq, h, d = q.shape
    skv = k.shape[1]

    qh = jnp.moveaxis(q, 2, 1)                 # (B,H,Sq,D)
    kh = jnp.moveaxis(k, 2, 1)
    vh = jnp.moveaxis(v, 2, 1)

    bq = min(block_q, sq)
    bkv = min(block_kv, skv)
    qh = _pad_to(qh, bq, 2)
    kh = _pad_to(kh, bkv, 2)
    vh = _pad_to(vh, bkv, 2)
    d_pad = (-d) % 128 if not interpret else 0
    if d_pad:
        qh = _pad_to(qh, d + d_pad, 3)
        kh = _pad_to(kh, d + d_pad, 3)
        vh = _pad_to(vh, d + d_pad, 3)
        # padded q columns are zeros => scores unchanged; but the softmax
        # scale must use the padded d inside the kernel, so rescale q.
        qh = qh * ((d + d_pad) / d) ** 0.5

    # KV padding beyond skv must never win the softmax: causal masks it
    # (padded kv positions exceed every real q position when sq == skv);
    # non-causal passes kv_len so the kernel masks the padded tail.
    out = flash_attention_fwd(qh, kh, vh, causal=causal, block_q=bq,
                              block_kv=bkv, softcap=softcap, kv_len=skv,
                              interpret=interpret)
    out = out[:, :, :sq, :d]
    return jnp.moveaxis(out, 1, 2)
