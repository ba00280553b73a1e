"""Paged-KV model execution for the real serving engine.

The decode step runs against KV stored in fixed-size pages selected by a
block table — the runtime realization of the PagedAttention mechanism the
simulator's BlockManager models.  Two attention paths:

* ``gather``  — jnp: gather the sequence's pages and run masked decode
                attention (fast on CPU, what the tests use),
* ``pallas``  — the ``repro.kernels.paged_attention`` TPU kernel.

Latent attention (MLA) pages its latent instead: one (L, pages, page,
kv_lora_rank + qk_rope_head_dim) store, read by absorbed decode
attention through the gather path (the Pallas kernel takes (Hkv, hd)
pages only).

Supported families: attention-based (dense / moe / vlm).  SSM/hybrid have
O(1) decode state (nothing to page); enc-dec serving uses the contiguous
path.  The engine falls back to ``model_zoo.decode_step`` slot caches for
those (see DESIGN.md §Arch-applicability).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import DENSE, MOE, VLM
from repro.models import model_zoo as zoo
from repro.models.attention_impl import decode_attention
from repro.models.layers import norm_apply

PAGED_FAMILIES = (DENSE, MOE, VLM)


def supports_paged(model: zoo.Model) -> bool:
    return model.cfg.family in PAGED_FAMILIES


def check_attn_path(model: zoo.Model, attn_path: str) -> None:
    """MLA's latent pages are read by the gather path only."""
    if model.cfg.mla and attn_path != "gather":
        raise ValueError(
            f"{model.cfg.name}: latent attention (MLA) pages are read by "
            f"attn_path='gather' only; the Pallas paged-attention kernel "
            f"takes (Hkv, head_dim) pages, not {attn_path!r}")


# ---------------------------------------------------------------------------
# Page store
# ---------------------------------------------------------------------------
def init_pages(model: zoo.Model, num_pages: int, page_size: int,
               max_batch: int, max_pages_per_seq: int) -> Dict:
    cfg, plan = model.cfg, model.plan
    cd = model.compute_dtype
    if cfg.mla:
        stores = {"latent": jnp.zeros((cfg.num_layers, num_pages, page_size,
                                       cfg.latent_dim), cd)}
    else:
        shape = (cfg.num_layers, num_pages, page_size, plan.n_kv,
                 cfg.head_dim)
        stores = {"k": jnp.zeros(shape, cd), "v": jnp.zeros(shape, cd)}
    return {
        **stores,
        # per-slot block table + context len (padded rows are inactive)
        "tables": jnp.zeros((max_batch, max_pages_per_seq), jnp.int32),
        "len": jnp.zeros((max_batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Prefill: run the contiguous forward, then scatter KV into pages
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=0)
def prefill_collect(model: zoo.Model, params, tokens, prompt_len):
    """tokens: (1, S) with S a padded bucket; ``prompt_len`` (dynamic)
    marks the real prompt.  Bucketing keeps the jit cache small — one
    compile per power-of-two bucket, not one per prompt length.

    Returns (logits_at_last_real_token (V,), the cache by page store:
    {k, v: (L,S,Hkv,hd)} or MLA's {latent: (L,S,R+rope)})."""
    cache = zoo.init_cache(model, 1, tokens.shape[1])
    logits, cache = zoo.prefill(model, params, {"tokens": tokens}, cache)
    kv = {name: a[:, 0] for name, a in cache.items() if name != "len"}
    last = jax.lax.dynamic_index_in_dim(logits[0], prompt_len - 1, 0,
                                        keepdims=False)
    return last, kv


@functools.partial(jax.jit, static_argnums=0)
def scatter_prefill(model: zoo.Model, pages, kv, table_row, prompt_len):
    """Write one request's prefill cache (``prefill_collect``'s, (L,S,...)
    a store) into its pages.

    table_row: (MP,) physical page ids covering the prompt. Positions at
    or beyond ``prompt_len`` (bucket padding) land in the trash page
    (last physical page, reserved by the engine)."""
    name = next(iter(kv))
    page_size = pages[name].shape[2]
    trash = pages[name].shape[1] - 1
    s = kv[name].shape[1]
    pos = jnp.arange(s)
    page_idx = jnp.where(pos < prompt_len,
                         table_row[jnp.minimum(pos // page_size,
                                               table_row.shape[0] - 1)],
                         trash)
    offset = pos % page_size
    # Adjacent advanced indices keep the L axis leading: target positions
    # are (L, S, ...).
    return {**pages, **{
        name: pages[name].at[:, page_idx, offset].set(
            a.astype(pages[name].dtype)) for name, a in kv.items()}}


# ---------------------------------------------------------------------------
# Paged decode step
# ---------------------------------------------------------------------------
def _attn_decode_paged(p, x_t, model: zoo.Model, stores, tables, lens, *,
                       attn_path: str):
    """x_t: (B,1,d); stores: one layer's page stores, (k_pages, v_pages)
    of (NP,page,Hkv,hd), or MLA's (latent (NP,page,R+rope),); tables:
    (B,MP); lens: (B,) context length *before* this token.
    Returns (out (B,1,d), stores)."""
    cfg = model.cfg
    bsz = x_t.shape[0]
    page = stores[0].shape[1]
    positions = lens[:, None]
    if cfg.mla:
        (lat,) = stores
        new = zoo._mla_latent(p, x_t, model, positions)[:, 0]
        prow = tables[jnp.arange(bsz), lens // page]
        lat = lat.at[prow, lens % page].set(new.astype(lat.dtype))
        seq = lat[tables].reshape(bsz, -1, lat.shape[-1])
        return zoo.mla_decode_attention(p, x_t, model, seq, lens + 1), (lat,)

    k_pages, v_pages = stores
    q = zoo._q_proj(p, x_t, model, positions)            # (B,1,H,hd)
    k_t, v_t = zoo._kv_proj(p, x_t, model, positions)    # (B,1,Hkv,hd)

    prow = tables[jnp.arange(bsz), lens // page]         # (B,)
    off = lens % page
    k_pages = k_pages.at[prow, off].set(k_t[:, 0].astype(k_pages.dtype))
    v_pages = v_pages.at[prow, off].set(v_t[:, 0].astype(v_pages.dtype))
    valid = lens + 1

    if attn_path == "pallas":
        from repro.kernels.paged_attention import ops as paged_ops
        ctx = paged_ops.paged_attention(q[:, 0], k_pages, v_pages,
                                        tables, valid)[:, None]
    else:
        mp = tables.shape[1]
        k_seq = k_pages[tables].reshape(bsz, mp * page, *k_pages.shape[2:])
        v_seq = v_pages[tables].reshape(bsz, mp * page, *v_pages.shape[2:])
        ctx = decode_attention(q, k_seq, v_seq, valid,
                               logit_softcap=cfg.attn_logit_softcap)
    out = zoo._attn_out(p, ctx, model)
    return out, (k_pages, v_pages)


@functools.partial(jax.jit, static_argnums=(0, 4))
def paged_decode_step(model: zoo.Model, params, pages, tokens,
                      attn_path: str = "gather"):
    """One decode iteration over the whole running batch.

    tokens: (B,) current token per slot (padded slots: anything).
    Returns (logits (B,V), pages with lens advanced)."""
    cfg = model.cfg
    lens = pages["len"]
    tables = pages["tables"]
    x = zoo._embed_tokens(model, params, tokens[:, None])
    if cfg.pos_emb == "learned":
        x = x + params["pos"][lens][:, None].astype(x.dtype)

    def body(x_t, inp):
        lp, *stores = inp
        h = norm_apply(lp["ln1"], x_t, cfg.norm)
        attn, stores = _attn_decode_paged(lp["attn"], h, model, stores,
                                          tables, lens,
                                          attn_path=attn_path)
        x_t = x_t + attn
        h = norm_apply(lp["ln2"], x_t, cfg.norm)
        y, _ = zoo._ffn_apply(lp, h, model)
        return x_t + y, stores

    # each layer's slice of the stores goes through the scan as xs/ys;
    # a MoE model's leading dense layers are a scan of their own
    names = ("latent",) if cfg.mla else ("k", "v")
    runs = zoo._layer_runs(model, params)
    outs = []
    for layers, lo, hi in runs:
        cut = [pages[n] if len(runs) == 1 else pages[n][lo:hi]
               for n in names]
        x, ys = jax.lax.scan(body, x, (layers, *cut))
        outs.append(ys)
    stores = dict(zip(names, zoo._join(outs)))
    logits = zoo._lm_head(model, params, x)[:, 0]
    return logits, {**pages, **stores, "len": lens + 1}
