"""Model zoo: one builder covering every assigned architecture family.

``Model`` is a frozen (hashable) bundle of (ArchConfig, PadPlan,
RunSettings); every entry point below takes it as the static first
argument, so ``jax.jit(fn, static_argnums=0)`` just works.

Entry points
------------
* ``init_params(model, key)``     — parameter pytree (fp32 master).
* ``param_specs(model)``          — ShapeDtypeStructs (dry-run, no alloc).
* ``forward(model, params, batch)``   — full-seq logits (train/prefill math).
* ``loss_fn(model, params, batch)``   — token cross-entropy (+ MoE aux).
* ``init_cache / cache_specs``    — decode-state pytree per family.
* ``prefill(model, params, batch, cache, prompt_lens)``
* ``decode_step(model, params, cache, tokens)``

Cache layouts (leading L axis is scanned):
  dense/moe/vlm: {k,v: (L,B,Smax,Hkv_phys,hd), len: (B,)}
  latent (MLA):  {latent: (L,B,Smax,kv_lora_rank+qk_rope_head_dim), len}
  ssm:           {conv: (L,B,W-1,C), ssd: (L,B,H,N,P) f32, len: (B,)}
  hybrid:        ssm states for all layers + {k,v: (Napp,B,Smax,H,hd)}
  audio(enc-dec):{k,v: (Ld,...), xk,xv: (Ld,B,Senc,H,hd), len}
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ArchConfig, AUDIO, DENSE, ENCDEC, HYBRID,
                                MOE, SSM, VLM)
from repro.distributed.api import shard
from repro.distributed.padding import PadPlan, make_pad_plan
from repro.models import ssm as ssm_mod
from repro.models.attention_impl import attend, decode_attention
from repro.models.common import RunSettings, DEFAULT_SETTINGS
from repro.models.layers import (dense_init, dense_apply, embed_init,
                                 embed_apply, head_norm_apply, mlp_init,
                                 mlp_apply, norm_apply, norm_init,
                                 softmax_xent, unembed_apply, apply_rope,
                                 _normal)
from repro.models.moe import moe_init, moe_apply

ATTN_FAMILIES = (DENSE, MOE, VLM)
LEARNED_POS_CAP = 32_768  # learned position tables are capped (DESIGN.md)


@dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    plan: PadPlan
    settings: RunSettings = DEFAULT_SETTINGS

    @property
    def compute_dtype(self):
        return jnp.dtype(self.cfg.dtype)

    @property
    def param_dtype(self):
        return jnp.dtype(self.cfg.param_dtype)

    def with_settings(self, **kw) -> "Model":
        import dataclasses
        return dataclasses.replace(
            self, settings=dataclasses.replace(self.settings, **kw))


def build(cfg: ArchConfig, tp: int = 1,
          settings: RunSettings = DEFAULT_SETTINGS) -> Model:
    return Model(cfg=cfg, plan=make_pad_plan(cfg, tp), settings=settings)


# ---------------------------------------------------------------------------
# Attention block
# ---------------------------------------------------------------------------
def _attn_init(key, model: Model, *, cross: bool = False):
    cfg, plan = model.cfg, model.plan
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv_log = plan.n_q, plan.n_kv // plan.kv_rep
    dt = model.param_dtype
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, nq * hd, dt, bias=cfg.qkv_bias),
        "wk": dense_init(ks[1], d, nkv_log * hd, dt, bias=cfg.qkv_bias),
        "wv": dense_init(ks[2], d, nkv_log * hd, dt, bias=cfg.qkv_bias),
        "wo": dense_init(ks[3], nq * hd, d, dt, scale=(nq * hd) ** -0.5),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = {"scale": jnp.ones((hd,), dt)}
        p["k_norm"] = {"scale": jnp.ones((hd,), dt)}
    return p


def _q_proj(p, x, model: Model, positions):
    cfg, plan = model.cfg, model.plan
    cd = model.compute_dtype
    b, s, _ = x.shape
    q = dense_apply(p["wq"], x, cd).reshape(b, s, plan.n_q, cfg.head_dim)
    if "q_norm" in p:
        q = head_norm_apply(p["q_norm"], q)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
    return shard(q, "batch", "seq", "heads", None)


def _kv_proj(p, x, model: Model, positions):
    """K/V in *physical* head layout (kv_rep applied)."""
    cfg, plan = model.cfg, model.plan
    cd = model.compute_dtype
    b, s, _ = x.shape
    nkv_log = plan.n_kv // plan.kv_rep
    k = dense_apply(p["wk"], x, cd).reshape(b, s, nkv_log, cfg.head_dim)
    v = dense_apply(p["wv"], x, cd).reshape(b, s, nkv_log, cfg.head_dim)
    if "k_norm" in p:
        k = head_norm_apply(p["k_norm"], k)
    if cfg.pos_emb == "rope" and positions is not None:
        k = apply_rope(k, positions, cfg.rope_theta)
    if plan.kv_rep > 1:
        k = jnp.repeat(k, plan.kv_rep, axis=2)
        v = jnp.repeat(v, plan.kv_rep, axis=2)
    k = shard(k, "batch", "kv_seq", "kv_heads", None)
    v = shard(v, "batch", "kv_seq", "kv_heads", None)
    return k, v


def _attn_out(p, ctx, model: Model):
    """ctx: (B,S,nq,hd) -> (B,S,d); padded q heads masked."""
    plan = model.plan
    b, s = ctx.shape[:2]
    if plan.has_q_padding:
        mask = jnp.asarray(plan.q_head_mask(), ctx.dtype)
        ctx = ctx * mask[None, None, :, None]
    ctx = ctx.reshape(b, s, plan.n_q * model.cfg.head_dim)
    return dense_apply(p["wo"], ctx, model.compute_dtype)


def _attn_full(p, x, model: Model, positions, *, causal: bool, kv_x=None,
               return_kv: bool = False):
    """Full-sequence attention (train / prefill / encoder)."""
    cfg, st = model.cfg, model.settings
    if cfg.mla:
        return _mla_full(p, x, model, positions, causal=causal,
                         return_kv=return_kv)
    q = _q_proj(p, x, model, positions)
    k, v = _kv_proj(p, kv_x if kv_x is not None else x, model, positions)
    impl = st.resolve_attn(q.shape[1])
    ctx = attend(q, k, v, causal=causal, impl=impl,
                 block_q=st.attn_block_q, block_kv=st.attn_block_kv,
                 logit_softcap=cfg.attn_logit_softcap)
    out = _attn_out(p, ctx, model)
    if return_kv:
        return out, (k, v)
    return out


def _attn_decode(p, x_t, model: Model, k_cache, v_cache, cache_len,
                 *, cross: bool = False):
    """One-token attention against a cache.

    x_t: (B,1,d).  For self-attn the new token's K/V is written at
    ``cache_len`` first; for cross-attn the cache is read-only.
    Returns (out (B,1,d), k_cache, v_cache). MLA: ``k_cache`` is the
    latent cache (B,Smax,R+rope) and ``v_cache`` None.
    """
    cfg = model.cfg
    if cfg.mla:
        out, k_cache = _mla_attn_decode(p, x_t, model, k_cache, cache_len)
        return out, k_cache, v_cache
    bsz = x_t.shape[0]
    positions = cache_len[:, None]                      # (B,1)
    q = _q_proj(p, x_t, model, positions)
    if not cross:
        k_t, v_t = _kv_proj(p, x_t, model, positions)   # (B,1,Hkv,hd)
        bidx = jnp.arange(bsz)
        k_cache = k_cache.at[bidx, cache_len].set(k_t[:, 0])
        v_cache = v_cache.at[bidx, cache_len].set(v_t[:, 0])
        valid_len = cache_len + 1
    else:
        valid_len = jnp.full((bsz,), k_cache.shape[1], jnp.int32)
    k_cache = shard(k_cache, "batch", "kv_seq", "kv_heads", None)
    v_cache = shard(v_cache, "batch", "kv_seq", "kv_heads", None)
    ctx = decode_attention(q, k_cache, v_cache, valid_len,
                           logit_softcap=cfg.attn_logit_softcap)
    out = _attn_out(p, ctx, model)
    return out, k_cache, v_cache


# ---------------------------------------------------------------------------
# Latent attention (MLA, DeepSeek-V2/V3) without q-LoRA
# ---------------------------------------------------------------------------
#: eps of the latent's RMSNorm (DeepSeek-V3's ``kv_a_layernorm`` default)
MLA_KV_NORM_EPS = 1e-6


def _mla_init(key, model: Model):
    cfg = model.cfg
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dt = model.param_dtype
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, h * cfg.head_dim, dt),
        "wkv_a": dense_init(ks[1], d, cfg.latent_dim, dt),
        "kv_norm": {"scale": jnp.ones((r,), dt)},
        "wkv_b": dense_init(ks[2], r,
                            h * (cfg.qk_nope_head_dim + cfg.v_head_dim), dt),
        "wo": dense_init(ks[3], h * cfg.v_head_dim, d, dt,
                         scale=(h * cfg.v_head_dim) ** -0.5),
    }


def _mla_q(p, x, model: Model, positions):
    """(q_nope (B,S,H,nope), q_pe (B,S,H,rope) with rope applied)."""
    cfg = model.cfg
    b, s, _ = x.shape
    q = dense_apply(p["wq"], x, model.compute_dtype).reshape(
        b, s, cfg.n_heads, cfg.head_dim)
    nope = cfg.qk_nope_head_dim
    return q[..., :nope], apply_rope(q[..., nope:], positions, cfg.rope_theta)


def _mla_latent(p, x, model: Model, positions):
    """What a token caches, (B,S,kv_lora_rank + qk_rope_head_dim): the
    RMS-normed latent, then the rope key that every head shares."""
    cfg = model.cfg
    kv = dense_apply(p["wkv_a"], x, model.compute_dtype)
    c = norm_apply(p["kv_norm"], kv[..., :cfg.kv_lora_rank], "rmsnorm",
                   eps=MLA_KV_NORM_EPS)
    if positions.ndim == 1:
        positions = positions[None]
    k_pe = apply_rope(kv[..., None, cfg.kv_lora_rank:], positions,
                      cfg.rope_theta)[..., 0, :]
    return jnp.concatenate([c, k_pe], axis=-1)


def _mla_out(p, ctx, model: Model):
    """ctx: (B,S,H,v_head_dim) -> (B,S,d)."""
    b, s = ctx.shape[:2]
    return dense_apply(p["wo"], ctx.reshape(b, s, -1), model.compute_dtype)


def _mla_full(p, x, model: Model, positions, *, causal: bool,
              return_kv: bool = False):
    """Full-sequence MLA with each head's key and value expanded from the
    latent; the latent is what it returns for the cache."""
    cfg, st = model.cfg, model.settings
    b, s, _ = x.shape
    h, nope, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    q_nope, q_pe = _mla_q(p, x, model, positions)
    lat = _mla_latent(p, x, model, positions)
    kv = dense_apply(p["wkv_b"], lat[..., :cfg.kv_lora_rank],
                     model.compute_dtype).reshape(b, s, h, nope + dv)
    k_pe = jnp.broadcast_to(lat[:, :, None, cfg.kv_lora_rank:],
                            (b, s, h, cfg.qk_rope_head_dim))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
    # every impl takes V as wide as Q and K: pad V with zeros, drop them
    v = jnp.pad(kv[..., nope:], ((0, 0),) * 3 + ((0, cfg.head_dim - dv),))
    ctx = attend(q, k, v, causal=causal, impl=st.resolve_attn(s),
                 block_q=st.attn_block_q, block_kv=st.attn_block_kv)
    out = _mla_out(p, ctx[..., :dv], model)
    return (out, lat) if return_kv else out


def mla_decode_attention(p, x_t, model: Model, latent_seq, valid):
    """Absorbed MLA for one token a row against its latent cache.

    x_t: (B,1,d) (normed); latent_seq: (B,S,kv_lora_rank+rope) with the
    new token's latent written; valid: (B,) keys to attend. The query's
    no-rope part is taken through ``wkv_b``'s key half into the latent's
    space and joined to its rope part: one latent kv head read by all H
    query heads (G = H), scaled by head_dim ** -0.5 of the published
    192-wide key. The values are the latent's first kv_lora_rank
    columns, taken through ``wkv_b``'s value half per head after."""
    cfg = model.cfg
    cd = model.compute_dtype
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    with jax.named_scope("mla_attention"):
        positions = (valid - 1)[:, None]
        q_nope, q_pe = _mla_q(p, x_t, model, positions)
        w = p["wkv_b"]["w"].astype(cd).reshape(r, cfg.n_heads, -1)
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w[..., :nope])
        q_abs = jnp.concatenate([q_lat, q_pe.astype(q_lat.dtype)], axis=-1)
        k = latent_seq[:, :, None, :]                   # (B,S,1,R+rope)
        ctx = decode_attention(q_abs, k, k[..., :r], valid,
                               scale=cfg.head_dim ** -0.5)
        ctx = jnp.einsum("bqhr,rhv->bqhv", ctx, w[..., nope:])
    return _mla_out(p, ctx, model)


def _mla_attn_decode(p, x_t, model: Model, lat_cache, cache_len):
    """Contiguous cache: write the token's latent at ``cache_len``, then
    attend. Returns (out (B,1,d), lat_cache)."""
    positions = cache_len[:, None]
    new = _mla_latent(p, x_t, model, positions)[:, 0]
    lat_cache = lat_cache.at[jnp.arange(x_t.shape[0]), cache_len].set(
        new.astype(lat_cache.dtype))
    out = mla_decode_attention(p, x_t, model, lat_cache, cache_len + 1)
    return out, lat_cache


# ---------------------------------------------------------------------------
# Transformer blocks (dense / moe)
# ---------------------------------------------------------------------------
def _block_init(key, model: Model, *, cross: bool = False,
                dense: bool = False):
    """``dense``: a leading dense layer of a MoE model (SwiGLU of d_ff)."""
    cfg = model.cfg
    dt = model.param_dtype
    ks = jax.random.split(key, 4)
    p = {"ln1": norm_init(cfg.d_model, cfg.norm, dt),
         "attn": (_mla_init if cfg.mla else _attn_init)(ks[0], model),
         "ln2": norm_init(cfg.d_model, cfg.norm, dt)}
    if cross:
        p["ln_x"] = norm_init(cfg.d_model, cfg.norm, dt)
        p["xattn"] = _attn_init(ks[1], model, cross=True)
    if cfg.family == MOE and not dense:
        m = cfg.moe
        p["moe"] = moe_init(ks[2], cfg.d_model, model.plan.n_experts,
                            m.d_expert, cfg.act, dt, n_held=m.n_held,
                            n_shared=m.n_shared, scoring=m.scoring)
    elif cfg.d_ff:
        p["mlp"] = mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.act, dt,
                            bias=cfg.mlp_bias or cfg.family in (AUDIO, ENCDEC)
                            or cfg.pos_emb == "learned")
    return p


def _ffn_apply(p, x, model: Model):
    """MLP or MoE second half-block. Returns (y, aux)."""
    cfg, st = model.cfg, model.settings
    if "moe" in p:
        m = cfg.moe
        y, aux = moe_apply(p["moe"], x, top_k=m.top_k,
                           n_experts_logical=model.plan.n_experts_logical,
                           impl=st.moe_impl, compute_dtype=model.compute_dtype,
                           scoring=m.scoring, routed_scale=m.routed_scale,
                           held_start=m.held_start, act=cfg.act)
        return y, aux
    y = mlp_apply(p["mlp"], x, cfg.act, model.compute_dtype)
    return y, None


def _block_apply(p, x, model: Model, positions, *, causal=True, enc_out=None,
                 return_kv=False):
    """Pre-norm transformer block (full-seq). Returns (x, aux, kv?)."""
    cfg = model.cfg
    h = norm_apply(p["ln1"], x, cfg.norm)
    attn = _attn_full(p["attn"], h, model, positions, causal=causal,
                      return_kv=return_kv)
    kv = None
    if return_kv:
        attn, kv = attn
    x = x + attn
    if "xattn" in p:
        h = norm_apply(p["ln_x"], x, cfg.norm)
        x = x + _attn_full(p["xattn"], h, model, None, causal=False,
                           kv_x=enc_out)
    h = norm_apply(p["ln2"], x, cfg.norm)
    y, aux = _ffn_apply(p, h, model)
    x = x + y
    x = shard(x, "batch", "seq", "embed")
    return x, aux, kv


def _block_decode(p, x_t, model: Model, k, v, cache_len, *, xk=None, xv=None):
    """Pre-norm block, one token. Returns (x_t, k, v)."""
    cfg = model.cfg
    h = norm_apply(p["ln1"], x_t, cfg.norm)
    attn, k, v = _attn_decode(p["attn"], h, model, k, v, cache_len)
    x_t = x_t + attn
    if "xattn" in p:
        h = norm_apply(p["ln_x"], x_t, cfg.norm)
        attn, _, _ = _attn_decode(p["xattn"], h, model, xk, xv, cache_len,
                                  cross=True)
        x_t = x_t + attn
    h = norm_apply(p["ln2"], x_t, cfg.norm)
    y, _ = _ffn_apply(p, h, model)
    return x_t + y, k, v


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------
def _stack_init(fn, key, n):
    """vmap an init fn over n split keys -> stacked params (n leading)."""
    return jax.vmap(fn)(jax.random.split(key, n))


def init_params(model: Model, key) -> Dict[str, Any]:
    cfg, plan = model.cfg, model.plan
    dt = model.param_dtype
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {}

    if cfg.family in (DENSE, MOE, VLM):
        params["embed"] = embed_init(ks[0], plan.vocab, cfg.d_model, dt)
        if cfg.first_k_dense:
            params["dense_layers"] = _stack_init(
                lambda k: _block_init(k, model, dense=True), ks[4],
                cfg.first_k_dense)
        params["layers"] = _stack_init(
            lambda k: _block_init(k, model), ks[1],
            cfg.num_layers - cfg.first_k_dense)
        params["final_norm"] = norm_init(cfg.d_model, cfg.norm, dt)
        if not cfg.tie_embeddings:
            params["unembed"] = embed_init(ks[2], plan.vocab, cfg.d_model, dt)
        if cfg.pos_emb == "learned":
            n_pos = min(cfg.max_seq_len, LEARNED_POS_CAP)
            params["pos"] = _normal(ks[3], (n_pos, cfg.d_model), dt, 0.02)

    elif cfg.family == SSM:
        params["embed"] = embed_init(ks[0], plan.vocab, cfg.d_model, dt)
        params["layers"] = _stack_init(
            lambda k: {"ln": norm_init(cfg.d_model, cfg.norm, dt),
                       "mixer": ssm_mod.mamba2_init(
                           k, cfg.d_model, cfg.ssm, dt,
                           n_heads_phys=plan.ssm_heads)},
            ks[1], cfg.num_layers)
        params["final_norm"] = norm_init(cfg.d_model, cfg.norm, dt)
        if not cfg.tie_embeddings:
            params["unembed"] = embed_init(ks[2], plan.vocab, cfg.d_model, dt)

    elif cfg.family == HYBRID:
        params["embed"] = embed_init(ks[0], plan.vocab, cfg.d_model, dt)
        params["layers"] = _stack_init(
            lambda k: {"ln": norm_init(cfg.d_model, cfg.norm, dt),
                       "mixer": ssm_mod.mamba2_init(
                           k, cfg.d_model, cfg.ssm, dt,
                           n_heads_phys=plan.ssm_heads)},
            ks[1], cfg.num_layers)
        params["shared_attn"] = _stack_init(
            lambda k: _block_init(k, model), ks[2], cfg.n_shared_attn)
        params["final_norm"] = norm_init(cfg.d_model, cfg.norm, dt)
        if not cfg.tie_embeddings:
            params["unembed"] = embed_init(ks[3], plan.vocab, cfg.d_model, dt)

    elif cfg.family in (ENCDEC, AUDIO):
        # decoder token embedding; encoder input is a precomputed-embedding
        # stub per the assignment (frontend == "embed").
        params["embed"] = embed_init(ks[0], plan.vocab, cfg.d_model, dt)
        params["enc_pos"] = _normal(ks[1], (cfg.enc_seq_len, cfg.d_model),
                                    dt, 0.02)
        n_pos = min(cfg.max_seq_len, LEARNED_POS_CAP)
        params["dec_pos"] = _normal(ks[2], (n_pos, cfg.d_model), dt, 0.02)
        params["enc_layers"] = _stack_init(
            lambda k: _block_init(k, model), ks[3], cfg.n_enc_layers)
        params["dec_layers"] = _stack_init(
            lambda k: _block_init(k, model, cross=True), ks[4],
            cfg.n_dec_layers)
        params["enc_norm"] = norm_init(cfg.d_model, cfg.norm, dt)
        params["final_norm"] = norm_init(cfg.d_model, cfg.norm, dt)
    else:
        raise ValueError(cfg.family)
    return params


def param_specs(model: Model):
    return jax.eval_shape(
        functools.partial(init_params, model), jax.random.key(0))


def param_count(params) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(params)))


# ---------------------------------------------------------------------------
# Scan-over-layers helpers
# ---------------------------------------------------------------------------
def _maybe_remat(fn, model: Model):
    r = model.settings.remat
    if r == "none":
        return fn
    if r == "full":
        return jax.checkpoint(fn,
                              policy=jax.checkpoint_policies.nothing_saveable)
    if r == "dots_saveable":
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    raise ValueError(r)


def _scan_blocks(layers, x, body, model: Model, init_aux=None):
    """Run ``body(x, layer_params) -> (x, aux)`` over stacked layers."""
    body = _maybe_remat(body, model)
    if model.settings.scan_layers:
        def sbody(carry, lp):
            return body(carry, lp)
        x, auxs = jax.lax.scan(sbody, x, layers)
        return x, auxs
    n = jax.tree.leaves(layers)[0].shape[0]
    auxs = []
    for i in range(n):
        lp = jax.tree.map(lambda a: a[i], layers)
        x, aux = body(x, lp)
        auxs.append(aux)
    if auxs and auxs[0] is not None:
        auxs = jax.tree.map(lambda *a: jnp.stack(a), *auxs)
    else:
        auxs = None
    return x, auxs


def _scan_or_unroll(model: Model, body, carry, xs):
    """lax.scan when settings.scan_layers else an unrolled python loop.

    The unrolled path exists for the roofline "counting mode": XLA's
    cost_analysis counts a scan body once, so FLOPs/collectives inside
    the layer loop are undercounted by L unless unrolled (see
    benchmarks/roofline_report.py)."""
    if model.settings.scan_layers:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and jax.tree.leaves(ys[0]):
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def _layer_runs(model: Model, params):
    """(stacked layer params, first layer, end) of each run of one kind
    of layer: a MoE model's leading dense layers, then the rest."""
    k = model.cfg.first_k_dense
    runs = [(params["layers"], k, model.cfg.num_layers)]
    if k:
        runs.insert(0, (params["dense_layers"], 0, k))
    return runs


def _join(parts):
    """The runs' per-layer outputs as one stack (leading layer axis)."""
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *a: jnp.concatenate(a), *parts)


def _collect_aux(auxs) -> Dict[str, jnp.ndarray]:
    if auxs is None:
        return {}
    leaves = jax.tree.leaves(auxs)
    if not leaves:
        return {}
    return jax.tree.map(jnp.sum, auxs)


# ---------------------------------------------------------------------------
# Full-sequence forward
# ---------------------------------------------------------------------------
def _embed_tokens(model: Model, params, tokens):
    x = embed_apply(params["embed"], tokens, model.compute_dtype)
    return shard(x, "batch", "seq", "embed")


def _lm_head(model: Model, params, x):
    cfg = model.cfg
    x = norm_apply(params["final_norm"], x, cfg.norm)
    table = params.get("unembed", params["embed"])["table"]
    logits = unembed_apply(table, x, vocab_logical=model.plan.vocab_logical,
                           fp32=model.settings.logits_fp32)
    return shard(logits, "batch", "seq", "vocab")


def forward(model: Model, params, batch) -> Tuple[jnp.ndarray, Dict]:
    """Full-sequence logits.  batch: {"tokens": (B,S)} (+"embeds" enc-dec).

    Returns (logits (B,S,V_phys), aux dict with MoE losses if any).
    """
    cfg = model.cfg
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.arange(s)

    if cfg.family in (DENSE, MOE, VLM):
        x = _embed_tokens(model, params, tokens)
        if cfg.pos_emb == "learned":
            x = x + params["pos"][:s][None].astype(x.dtype)

        def body(x, lp):
            x, aux, _ = _block_apply(lp, x, model, positions, causal=True)
            return x, aux

        for layers, _, _ in _layer_runs(model, params):
            x, auxs = _scan_blocks(layers, x, body, model)    # last: MoE
        return _lm_head(model, params, x), _collect_aux(auxs)

    if cfg.family == SSM:
        x = _embed_tokens(model, params, tokens)
        hm = _ssm_head_mask(model)

        def body(x, lp):
            h = norm_apply(lp["ln"], x, cfg.norm)
            y, _ = ssm_mod.mamba2_apply(
                lp["mixer"], h, cfg.ssm, compute_dtype=model.compute_dtype,
                head_mask=hm)
            return x + y, None

        x, _ = _scan_blocks(params["layers"], x, body, model)
        return _lm_head(model, params, x), {}

    if cfg.family == HYBRID:
        return _hybrid_forward(model, params, batch)

    if cfg.family in (ENCDEC, AUDIO):
        enc_out = _encode(model, params, batch["embeds"])
        x = _embed_tokens(model, params, tokens)
        x = x + params["dec_pos"][:s][None].astype(x.dtype)

        def body(x, lp):
            x, aux, _ = _block_apply(lp, x, model, positions, causal=True,
                                     enc_out=enc_out)
            return x, aux

        x, auxs = _scan_blocks(params["dec_layers"], x, body, model)
        return _lm_head(model, params, x), _collect_aux(auxs)

    raise ValueError(cfg.family)


def _ssm_head_mask(model: Model):
    plan = model.plan
    if plan.ssm_heads == plan.ssm_heads_logical:
        return None
    return jnp.asarray(plan.ssm_head_mask(), jnp.float32)


def _encode(model: Model, params, embeds):
    """Encoder over precomputed frame embeddings (frontend stub)."""
    cfg = model.cfg
    s = embeds.shape[1]
    x = embeds.astype(model.compute_dtype)
    x = x + params["enc_pos"][:s][None].astype(x.dtype)
    positions = jnp.arange(s)

    def body(x, lp):
        x, _, _ = _block_apply(lp, x, model, positions, causal=False)
        return x, None

    x, _ = _scan_blocks(params["enc_layers"], x, body, model)
    return norm_apply(params["enc_norm"], x, cfg.norm)


def _hybrid_groups(model: Model) -> Tuple[int, int]:
    cfg = model.cfg
    period = cfg.attn_period or cfg.num_layers
    assert cfg.num_layers % period == 0, (cfg.num_layers, period)
    return cfg.num_layers // period, period


def _hybrid_forward(model: Model, params, batch):
    """Zamba2-style: groups of SSD layers + one *shared* attention block
    applied after each group (weights shared across applications)."""
    cfg = model.cfg
    tokens = batch["tokens"]
    b, s = tokens.shape
    n_groups, period = _hybrid_groups(model)
    positions = jnp.arange(s)
    x = _embed_tokens(model, params, tokens)
    hm = _ssm_head_mask(model)

    # reshape stacked ssm layers (L, ...) -> (n_groups, period, ...)
    grouped = jax.tree.map(
        lambda a: a.reshape((n_groups, period) + a.shape[1:]),
        params["layers"])

    def ssm_body(x, lp):
        h = norm_apply(lp["ln"], x, cfg.norm)
        y, _ = ssm_mod.mamba2_apply(
            lp["mixer"], h, cfg.ssm, compute_dtype=model.compute_dtype,
            head_mask=hm)
        return x + y, None

    def group_body(carry, inp):
        x, app_idx = carry
        group_layers = inp
        x, _ = _scan_blocks(group_layers, x, ssm_body, model)
        # shared attention block (round-robin over n_shared_attn copies)
        blk = jax.tree.map(
            lambda a: a[app_idx % cfg.n_shared_attn], params["shared_attn"])
        x, _, _ = _block_apply(blk, x, model, positions, causal=True)
        return (x, app_idx + 1), None

    if model.settings.scan_layers and cfg.n_shared_attn == 1:
        blk = jax.tree.map(lambda a: a[0], params["shared_attn"])

        def gbody(x, group_layers):
            x, _ = _scan_blocks(group_layers, x, ssm_body, model)
            x, _, _ = _block_apply(blk, x, model, positions, causal=True)
            return x, None

        x, _ = _scan_or_unroll(model, gbody, x, grouped)
    else:
        carry = (x, 0)
        for gi in range(n_groups):
            gl = jax.tree.map(lambda a: a[gi], grouped)
            carry, _ = group_body(carry, gl)
        x = carry[0]
    return _lm_head(model, params, x), {}


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def loss_fn(model: Model, params, batch) -> Tuple[jnp.ndarray, Dict]:
    logits, aux = forward(model, params, batch)
    mask = batch.get("loss_mask")
    loss = softmax_xent(logits, batch["labels"], mask)
    metrics = {"xent": loss}
    if aux:
        m = model.cfg.moe
        loss = loss + m.router_aux_coef * aux.get("aux", 0.0) \
            + m.router_z_coef * aux.get("zloss", 0.0)
        metrics.update(aux)
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------
def _cache_struct(model: Model, batch: int, max_len: int) -> Dict[str, Any]:
    """Shapes/dtypes of the decode cache (dict of (shape, dtype))."""
    cfg, plan = model.cfg, model.plan
    cd = model.compute_dtype
    hd = cfg.head_dim
    out: Dict[str, Tuple[tuple, Any]] = {"len": ((batch,), jnp.int32)}
    if cfg.mla:
        out["latent"] = ((cfg.num_layers, batch, max_len, cfg.latent_dim), cd)
    elif cfg.family in (DENSE, MOE, VLM):
        kv = (cfg.num_layers, batch, max_len, plan.n_kv, hd)
        out["k"] = (kv, cd)
        out["v"] = (kv, cd)
    elif cfg.family in (SSM, HYBRID):
        s = cfg.ssm
        conv_dim = plan.ssm_heads * s.head_dim + 2 * s.n_groups * s.d_state
        out["conv"] = ((cfg.num_layers, batch, s.conv_width - 1, conv_dim), cd)
        out["ssd"] = ((cfg.num_layers, batch, plan.ssm_heads, s.d_state,
                       s.head_dim), jnp.float32)
        if cfg.family == HYBRID:
            napp = _hybrid_groups(model)[0]
            kv = (napp, batch, max_len, plan.n_kv, hd)
            out["k"] = (kv, cd)
            out["v"] = (kv, cd)
    elif cfg.family in (ENCDEC, AUDIO):
        kv = (cfg.n_dec_layers, batch, max_len, plan.n_kv, hd)
        xkv = (cfg.n_dec_layers, batch, cfg.enc_seq_len, plan.n_kv, hd)
        out["k"] = (kv, cd)
        out["v"] = (kv, cd)
        out["xk"] = (xkv, cd)
        out["xv"] = (xkv, cd)
    else:
        raise ValueError(cfg.family)
    return out


def init_cache(model: Model, batch: int, max_len: int):
    return {k: jnp.zeros(shp, dt)
            for k, (shp, dt) in _cache_struct(model, batch, max_len).items()}


def cache_specs(model: Model, batch: int, max_len: int):
    return {k: jax.ShapeDtypeStruct(shp, dt)
            for k, (shp, dt) in _cache_struct(model, batch, max_len).items()}


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------
def prefill(model: Model, params, batch, cache, prompt_lens=None):
    """Full-prompt forward that also fills the decode cache.

    batch: {"tokens": (B,S)} (+"embeds").  ``prompt_lens`` (B,) defaults to
    S for every row.  Returns (logits (B,S,V), cache).
    """
    cfg = model.cfg
    tokens = batch["tokens"]
    b, s = tokens.shape
    if prompt_lens is None:
        prompt_lens = jnp.full((b,), s, jnp.int32)
    positions = jnp.arange(s)
    max_len = None

    if cfg.family in (DENSE, MOE, VLM):
        x = _embed_tokens(model, params, tokens)
        if cfg.pos_emb == "learned":
            x = x + params["pos"][:s][None].astype(x.dtype)

        def body(x, lp):
            x, _, kv = _block_apply(lp, x, model, positions, causal=True,
                                    return_kv=True)
            return x, kv

        kvs = []
        for layers, _, _ in _layer_runs(model, params):
            x, kv = _scan_blocks(layers, x, body, model)
            kvs.append(kv)
        kvs = _join(kvs)
        if cfg.mla:                                 # (L,B,S,R+rope)
            cache["latent"] = jax.lax.dynamic_update_slice(
                cache["latent"], kvs.astype(cache["latent"].dtype),
                (0, 0, 0, 0))
            cache["len"] = prompt_lens
            return _lm_head(model, params, x), cache
        k_new, v_new = kvs                          # (L,B,S,Hkv,hd)
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
        cache["len"] = prompt_lens
        return _lm_head(model, params, x), cache

    if cfg.family == SSM:
        x = _embed_tokens(model, params, tokens)
        hm = _ssm_head_mask(model)

        def body(x, lp):
            h = norm_apply(lp["ln"], x, cfg.norm)
            y, (cs, ss) = ssm_mod.mamba2_apply(
                lp["mixer"], h, cfg.ssm, compute_dtype=model.compute_dtype,
                head_mask=hm)
            return x + y, (cs.astype(cache["conv"].dtype), ss)

        x, states = _scan_blocks(params["layers"], x, body, model)
        cache["conv"], cache["ssd"] = states
        cache["len"] = prompt_lens
        return _lm_head(model, params, x), cache

    if cfg.family == HYBRID:
        return _hybrid_prefill(model, params, batch, cache, prompt_lens)

    if cfg.family in (ENCDEC, AUDIO):
        enc_out = _encode(model, params, batch["embeds"])
        x = _embed_tokens(model, params, tokens)
        x = x + params["dec_pos"][:s][None].astype(x.dtype)

        def body(x, lp):
            # self-attn with kv export
            h = norm_apply(lp["ln1"], x, cfg.norm)
            attn, kv = _attn_full(lp["attn"], h, model, positions,
                                  causal=True, return_kv=True)
            x = x + attn
            h = norm_apply(lp["ln_x"], x, cfg.norm)
            xk, xv = _kv_proj(lp["xattn"], enc_out, model, None)
            q = _q_proj(lp["xattn"], h, model, None)
            impl = model.settings.resolve_attn(q.shape[1])
            ctx = attend(q, xk, xv, causal=False, impl=impl,
                         block_q=model.settings.attn_block_q,
                         block_kv=model.settings.attn_block_kv)
            x = x + _attn_out(lp["xattn"], ctx, model)
            h = norm_apply(lp["ln2"], x, cfg.norm)
            y, _ = _ffn_apply(lp, h, model)
            return x + y, (kv[0], kv[1], xk, xv)

        x, kvs = _scan_blocks(params["dec_layers"], x, body, model)
        k_new, v_new, xk, xv = kvs
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], k_new.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], v_new.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
        cache["xk"] = xk.astype(cache["xk"].dtype)
        cache["xv"] = xv.astype(cache["xv"].dtype)
        cache["len"] = prompt_lens
        return _lm_head(model, params, x), cache

    raise ValueError(cfg.family)


def _hybrid_prefill(model: Model, params, batch, cache, prompt_lens):
    cfg = model.cfg
    tokens = batch["tokens"]
    b, s = tokens.shape
    n_groups, period = _hybrid_groups(model)
    positions = jnp.arange(s)
    x = _embed_tokens(model, params, tokens)
    hm = _ssm_head_mask(model)
    grouped = jax.tree.map(
        lambda a: a.reshape((n_groups, period) + a.shape[1:]),
        params["layers"])
    blk = jax.tree.map(lambda a: a[0], params["shared_attn"])

    def ssm_body(x, lp):
        h = norm_apply(lp["ln"], x, cfg.norm)
        y, (cs, ss) = ssm_mod.mamba2_apply(
            lp["mixer"], h, cfg.ssm, compute_dtype=model.compute_dtype,
            head_mask=hm)
        return x + y, (cs, ss)

    def gbody(x, group_layers):
        x, states = _scan_blocks(group_layers, x, ssm_body, model)
        x, _, kv = _block_apply(blk, x, model, positions, causal=True,
                                return_kv=True)
        return x, (states, kv)

    x, (states, kvs) = _scan_or_unroll(model, gbody, x, grouped)
    conv_s, ssd_s = states                      # (n_groups, period, B, ...)
    cache["conv"] = conv_s.reshape((cfg.num_layers,) + conv_s.shape[2:]) \
        .astype(cache["conv"].dtype)
    cache["ssd"] = ssd_s.reshape((cfg.num_layers,) + ssd_s.shape[2:])
    k_new, v_new = kvs                          # (n_groups, B, S, Hkv, hd)
    cache["k"] = jax.lax.dynamic_update_slice(
        cache["k"], k_new.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
    cache["v"] = jax.lax.dynamic_update_slice(
        cache["v"], v_new.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    cache["len"] = prompt_lens
    return _lm_head(model, params, x), cache


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------
def decode_step(model: Model, params, cache, tokens):
    """One autoregressive step.  tokens: (B,) int32 (the current token).

    Returns (logits (B,V_phys), cache with the new token's state written).
    """
    cfg = model.cfg
    b = tokens.shape[0]
    cache_len = cache["len"]

    if cfg.family in (DENSE, MOE, VLM):
        x = _embed_tokens(model, params, tokens[:, None])
        if cfg.pos_emb == "learned":
            x = x + params["pos"][cache_len][:, None].astype(x.dtype)

        def body(x_t, inp):
            lp, k, v = inp
            x_t, k, v = _block_decode(lp, x_t, model, k, v, cache_len)
            return x_t, (k, v)

        # MLA: the latent cache in K's place, no V
        stacks = (cache["latent"], None) if cfg.mla else \
            (cache["k"], cache["v"])
        runs = _layer_runs(model, params)
        kvs = []
        for layers, lo, hi in runs:
            cut = stacks if len(runs) == 1 else tuple(
                None if a is None else a[lo:hi] for a in stacks)
            x, kv = _scan_or_unroll(model, body, x, (layers, *cut))
            kvs.append(kv)
        kv = _join(kvs)
        if cfg.mla:
            cache["latent"] = kv[0]
        else:
            cache["k"], cache["v"] = kv
        cache["len"] = cache_len + 1
        logits = _lm_head(model, params, x)[:, 0]
        return logits, cache

    if cfg.family == SSM:
        x = _embed_tokens(model, params, tokens[:, None])[:, 0]
        hm = _ssm_head_mask(model)

        def body(x_t, inp):
            lp, cs, ss = inp
            h = norm_apply(lp["ln"], x_t, cfg.norm)
            y, (cs, ss) = ssm_mod.mamba2_decode(
                lp["mixer"], h, cfg.ssm, compute_dtype=model.compute_dtype,
                conv_state=cs, ssd_state=ss, head_mask=hm)
            return x_t + y, (cs, ss)

        x, states = _scan_or_unroll(
            model, body, x, (params["layers"], cache["conv"], cache["ssd"]))
        cache["conv"], cache["ssd"] = states
        cache["len"] = cache_len + 1
        logits = _lm_head(model, params, x[:, None])[:, 0]
        return logits, cache

    if cfg.family == HYBRID:
        n_groups, period = _hybrid_groups(model)
        x = _embed_tokens(model, params, tokens[:, None])[:, 0]
        hm = _ssm_head_mask(model)
        grouped = jax.tree.map(
            lambda a: a.reshape((n_groups, period) + a.shape[1:]),
            params["layers"])
        conv_g = cache["conv"].reshape(
            (n_groups, period) + cache["conv"].shape[1:])
        ssd_g = cache["ssd"].reshape(
            (n_groups, period) + cache["ssd"].shape[1:])
        blk = jax.tree.map(lambda a: a[0], params["shared_attn"])

        def inner(x_t, inp):
            lp, cs, ss = inp
            h = norm_apply(lp["ln"], x_t, cfg.norm)
            y, (cs, ss) = ssm_mod.mamba2_decode(
                lp["mixer"], h, cfg.ssm, compute_dtype=model.compute_dtype,
                conv_state=cs, ssd_state=ss, head_mask=hm)
            return x_t + y, (cs, ss)

        def gbody(x_t, inp):
            gl, cs, ss, k, v = inp
            x_t, states = _scan_or_unroll(model, inner, x_t,
                                          (gl, cs, ss))
            x2, k, v = _block_decode(blk, x_t[:, None], model, k, v,
                                     cache_len)
            return x2[:, 0], (states[0], states[1], k, v)

        x, outs = _scan_or_unroll(model, gbody, x,
                                  (grouped, conv_g, ssd_g,
                                   cache["k"], cache["v"]))
        cs, ss, k, v = outs
        cache["conv"] = cs.reshape(cache["conv"].shape)
        cache["ssd"] = ss.reshape(cache["ssd"].shape)
        cache["k"], cache["v"] = k, v
        cache["len"] = cache_len + 1
        logits = _lm_head(model, params, x[:, None])[:, 0]
        return logits, cache

    if cfg.family in (ENCDEC, AUDIO):
        x = _embed_tokens(model, params, tokens[:, None])
        x = x + params["dec_pos"][cache_len][:, None].astype(x.dtype)

        def body(x_t, inp):
            lp, k, v, xk, xv = inp
            x_t, k, v = _block_decode(lp, x_t, model, k, v, cache_len,
                                      xk=xk, xv=xv)
            return x_t, (k, v)

        x, kv = _scan_or_unroll(
            model, body, x, (params["dec_layers"], cache["k"],
                             cache["v"], cache["xk"], cache["xv"]))
        cache["k"], cache["v"] = kv
        cache["len"] = cache_len + 1
        logits = _lm_head(model, params, x)[:, 0]
        return logits, cache

    raise ValueError(cfg.family)
