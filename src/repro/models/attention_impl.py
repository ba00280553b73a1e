"""Attention compute implementations.

Four paths, all numerically interchangeable (tests assert allclose):

* ``naive``          — materializes (B,H,S,S) scores; small seqs / oracles.
* ``blocked``        — flash-style two-level ``lax.scan`` over q/kv blocks,
                       O(block^2) memory; computes the full S×S rectangle
                       with masking (the *paper-faithful baseline* — this is
                       what a straightforward port does).
* ``blocked_causal`` — beyond-paper §Perf optimization: iterates only the
                       lower-triangle (qb, kb<=qb) block pairs, halving
                       attention FLOPs at long seq (matches what the Pallas
                       kernel does on TPU).
* ``decode``         — one query token against a (possibly huge) KV cache,
                       grouped by kv head (K/V read in their own dtype,
                       never repeated), fp32 accumulation. GSPMD shards the KV
                       sequence axis for ``long_500k`` (SP) and inserts the
                       partial-softmax collectives.

All paths take q:(B,Sq,H,D), k/v:(B,Skv,Hkv,D) with H a multiple of Hkv
(GQA groups contiguous: q head i uses kv head i // (H//Hkv)).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

NEG_INF = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


def _gqa_expand(k, n_q_heads):
    """(B,S,Hkv,D) -> (B,S,H,D) by repeating each kv head contiguously."""
    b, s, hkv, d = k.shape
    rep = n_q_heads // hkv
    if rep == 1:
        return k
    return jnp.repeat(k, rep, axis=2)


# ---------------------------------------------------------------------------
# Naive
# ---------------------------------------------------------------------------
def naive_attention(q, k, v, *, causal: bool, q_offset=0,
                    logit_softcap: float = 0.0):
    """Reference full-materialization attention (fp32 softmax)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    k = _gqa_expand(k, h)
    v = _gqa_expand(v, h)
    scale = d ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if logit_softcap:
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(skv)[None, :]
        scores = jnp.where(qpos >= kpos, scores, NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Blocked (flash-style) — rectangle baseline and causal-triangle variants
# ---------------------------------------------------------------------------
def _flash_inner(q_blk, k, v, *, block_kv, causal, q_pos0, rep, softcap):
    """Online-softmax over kv blocks for one q block.

    q_blk: (B, Bq, H, D); k/v: (B, Skv, Hkv, D) reshaped into kv blocks.
    Returns (B, Bq, H, D).
    """
    b, bq, h, d = q_blk.shape
    skv = k.shape[1]
    nkv = skv // block_kv
    kb = k.reshape(b, nkv, block_kv, k.shape[2], d)
    vb = v.reshape(b, nkv, block_kv, v.shape[2], d)
    scale = d ** -0.5

    def body(carry, inputs):
        o, m, l = carry
        kblk, vblk, kv_idx = inputs          # (B,Bk,Hkv,D)
        kblk = _gqa_expand(kblk, h)
        vblk = _gqa_expand(vblk, h)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, kblk,
                       preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        if causal:
            qpos = q_pos0 + jnp.arange(bq)[:, None]
            kpos = kv_idx * block_kv + jnp.arange(block_kv)[None, :]
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32))
        return (o_new, m_new, l_new), None

    o0 = jnp.zeros((b, h, bq, d), jnp.float32)
    m0 = jnp.full((b, h, bq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, bq), jnp.float32)
    kv_ids = jnp.arange(nkv)
    (o, m, l), _ = jax.lax.scan(
        body, (o0, m0, l0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), kv_ids))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out, 1, 2).astype(q_blk.dtype)   # (B,Bq? ->B,q,h,d)


def blocked_attention(q, k, v, *, causal: bool, block_q=1024, block_kv=1024,
                      logit_softcap: float = 0.0):
    """Flash attention computing the full rectangle (masked). Baseline."""
    b, sq, h, d = q.shape
    block_q = min(block_q, sq)
    block_kv = min(block_kv, k.shape[1])
    if sq % block_q or k.shape[1] % block_kv:
        # fall back for ragged shapes (tests)
        return naive_attention(q, k, v, causal=causal,
                               logit_softcap=logit_softcap)
    nq = sq // block_q
    qb = jnp.moveaxis(q.reshape(b, nq, block_q, h, d), 1, 0)

    def per_q_block(q_blk, qi):
        return _flash_inner(q_blk, k, v, block_kv=block_kv, causal=causal,
                            q_pos0=qi * block_q, rep=h // k.shape[2],
                            softcap=logit_softcap)

    out = jax.lax.map(lambda args: per_q_block(*args), (qb, jnp.arange(nq)))
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, h, d)


def blocked_causal_attention(q, k, v, *, block_q=1024, block_kv=1024,
                             logit_softcap: float = 0.0):
    """Causal flash that only visits lower-triangle (qb, kb<=qb) pairs.

    The (qb, kb) pair list is static; a single ``lax.scan`` walks it in
    row-major order (so online softmax state per q block is updated in kv
    order), gathering blocks with dynamic slices. HLO FLOPs are ~half of
    ``blocked_attention`` at large S.
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    if sq % block_q or skv % block_kv or sq != skv:
        return naive_attention(q, k, v, causal=True,
                               logit_softcap=logit_softcap)
    nq, nkv = sq // block_q, skv // block_kv
    # pairs (qi, ki) with ki*block_kv <= qi*block_q + block_q - 1
    pairs = [(qi, ki) for qi in range(nq) for ki in range(nkv)
             if ki * block_kv <= qi * block_q + block_q - 1]
    qis = jnp.array([p[0] for p in pairs], jnp.int32)
    kis = jnp.array([p[1] for p in pairs], jnp.int32)
    scale = d ** -0.5

    qr = q.reshape(b, nq, block_q, h, d)
    kr = k.reshape(b, nkv, block_kv, k.shape[2], d)
    vr = v.reshape(b, nkv, block_kv, v.shape[2], d)

    def body(carry, pair):
        o, m, l = carry                     # (B,nq,H,Bq,D) fp32 etc.
        qi, ki = pair
        q_blk = jax.lax.dynamic_index_in_dim(qr, qi, 1, keepdims=False)
        k_blk = jax.lax.dynamic_index_in_dim(kr, ki, 1, keepdims=False)
        v_blk = jax.lax.dynamic_index_in_dim(vr, ki, 1, keepdims=False)
        k_blk = _gqa_expand(k_blk, h)
        v_blk = _gqa_expand(v_blk, h)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk,
                       preferred_element_type=jnp.float32) * scale
        if logit_softcap:
            s = jnp.tanh(s / logit_softcap) * logit_softcap
        qpos = qi * block_q + jnp.arange(block_q)[:, None]
        kpos = ki * block_kv + jnp.arange(block_kv)[None, :]
        s = jnp.where(qpos >= kpos, s, NEG_INF)

        m_row = jax.lax.dynamic_index_in_dim(m, qi, 1, keepdims=False)
        l_row = jax.lax.dynamic_index_in_dim(l, qi, 1, keepdims=False)
        o_row = jax.lax.dynamic_index_in_dim(o, qi, 1, keepdims=False)
        m_new = jnp.maximum(m_row, s.max(axis=-1))
        alpha = jnp.exp(m_row - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_row * alpha + p.sum(axis=-1)
        o_new = o_row * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        o = jax.lax.dynamic_update_index_in_dim(o, o_new, qi, 1)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, qi, 1)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, qi, 1)
        return (o, m, l), None

    o0 = jnp.zeros((b, nq, h, block_q, d), jnp.float32)
    m0 = jnp.full((b, nq, h, block_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nq, h, block_q), jnp.float32)
    (o, m, l), _ = jax.lax.scan(body, (o0, m0, l0), (qis, kis))
    out = o / jnp.maximum(l[..., None], 1e-30)          # (B,nq,H,Bq,D)
    out = jnp.moveaxis(out, 2, 3).reshape(b, sq, h, d)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def decode_attention(q, k_cache, v_cache, cache_len, *,
                     logit_softcap: float = 0.0, scale=None):
    """q: (B,1,H,D); k_cache: (B,S,Hkv,D); v_cache: (B,S,Hkv,Dv), Dv <= D
    (absorbed MLA reads a value narrower than its key); cache_len: (B,)
    valid length (the new token's kv must already be written at
    cache_len-1). ``scale``: of the scores (default D ** -0.5).

    Grouped: the G = H // Hkv query heads of a kv group are contracted
    against their kv head directly, so K/V are read once in their own
    dtype and never repeated over G or widened to f32 in memory; scores,
    softmax and accumulation are f32. ``HIGHEST`` keeps the products f32:
    at the default precision a TPU's MXU takes f32 operands (the softmax
    probabilities) as bf16."""
    b, _, h, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    assert h % hkv == 0, (h, hkv)
    qg = q.reshape(b, hkv, h // hkv, d)           # head j -> kv head j // G
    if scale is None:
        scale = d ** -0.5
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache, precision=HIGHEST,
                        preferred_element_type=jnp.float32) * scale
    if logit_softcap:
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
    valid = jnp.arange(s)[None, None, None, :] < cache_len[:, None, None, None]
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs, v_cache, precision=HIGHEST,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------
def attend(q, k, v, *, causal: bool, impl: str, block_q=1024, block_kv=1024,
           q_offset=0, logit_softcap: float = 0.0):
    if impl == "naive":
        return naive_attention(q, k, v, causal=causal, q_offset=q_offset,
                               logit_softcap=logit_softcap)
    if impl == "blocked":
        return blocked_attention(q, k, v, causal=causal, block_q=block_q,
                                 block_kv=block_kv,
                                 logit_softcap=logit_softcap)
    if impl == "blocked_causal":
        if not causal:
            return blocked_attention(q, k, v, causal=False, block_q=block_q,
                                     block_kv=block_kv,
                                     logit_softcap=logit_softcap)
        return blocked_causal_attention(q, k, v, block_q=block_q,
                                        block_kv=block_kv,
                                        logit_softcap=logit_softcap)
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as flash_ops
        return flash_ops.flash_attention(q, k, v, causal=causal)
    raise ValueError(f"unknown attention impl {impl!r}")
