"""Jit'd public wrapper for the SSD scan kernel.

Pads S up to a chunk multiple with identity steps (dA_log = 0, x = 0: the
state passes through unchanged and padded y rows are sliced off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.ssd_scan.kernel import ssd_scan_fwd


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(xbar, dA_log, Bm, Cm, *, chunk: int = 256):
    """xbar (B,S,H,P); dA_log (B,S,H); Bm/Cm (B,S,G,N) ->
    (y (B,S,H,P) f32, final_state (B,H,N,P) f32)."""
    interpret = interpret_mode()
    b, s, h, p = xbar.shape
    chunk = min(chunk, s) if s % chunk == 0 or s < chunk else chunk
    pad = (-s) % chunk
    if pad:
        xbar = jnp.pad(xbar.astype(jnp.float32),
                       ((0, 0), (0, pad), (0, 0), (0, 0)))
        dA_log = jnp.pad(dA_log.astype(jnp.float32),
                         ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm.astype(jnp.float32),
                     ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm.astype(jnp.float32),
                     ((0, 0), (0, pad), (0, 0), (0, 0)))
    y, fs = ssd_scan_fwd(xbar.astype(jnp.float32),
                         dA_log.astype(jnp.float32),
                         Bm.astype(jnp.float32), Cm.astype(jnp.float32),
                         chunk=chunk, interpret=interpret)
    return y[:, :s], fs
