"""Token-choice top-k MoE (granite-style) with two interchangeable impls.

* ``dense_onehot`` — every expert computes every token; outputs are combined
  with the (renormalized) top-k router weights. Exact, simple, and the
  *paper-faithful baseline* for the dry-run: its HLO FLOPs are E/k× the
  active-parameter FLOPs, which the §Perf hillclimb then removes.
* ``sort`` — dropless grouped-GEMM: token→expert assignments are sorted by
  expert id and dispatched through ``jax.lax.ragged_dot`` (TPU grouped
  matmul). HLO FLOPs ≈ top_k × active FLOPs. This is the beyond-paper
  optimized path.

Both paths agree to float tolerance (tests assert allclose).

Expert weights are stacked with a leading expert axis so EP sharding is a
single PartitionSpec entry: gate/up: (E, d_model, d_expert), down:
(E, d_expert, d_model). Padded experts (pad plan) receive -inf router
logits and therefore zero routing weight.

Routers: ``softmax`` (granite: softmax over the top-k logits) and
``sigmoid`` (DeepSeek-V3's ``noaux_tc`` with one group: the top-k of the
sigmoid scores plus a per-expert ``correction_bias``, weighted by the
unbiased scores normalised over the k and scaled by ``routed_scale``).

A layer may hold fewer experts than its router routes over, as one
chip of an expert-parallel group does: its stacks hold experts
``held_start .. held_start + E_held - 1`` and it returns their part of
the routed output only (the exchange with the chips that hold the
others is not here). Shared experts (``p["shared"]``, one SwiGLU of
width n_shared x d_expert) see every token and are added in full.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import _normal, mlp_apply, mlp_init


def moe_init(key, d_model: int, n_experts: int, d_expert: int, act: str,
             dtype, n_experts_logical: Optional[int] = None, *,
             n_held: int = 0, n_shared: int = 0, scoring: str = "softmax"):
    """``n_experts``: the router's width; ``n_held`` (0: all) experts
    are stacked here; ``n_shared`` shared experts of width d_expert; the
    sigmoid router's correction bias where ``scoring="sigmoid"``."""
    ks = jax.random.split(key, 5)
    gated = act == "silu"
    held = n_held or n_experts
    p = {
        "router": _normal(ks[0], (d_model, n_experts), dtype, d_model ** -0.5),
        "up": _normal(ks[1], (held, d_model, d_expert), dtype,
                      d_model ** -0.5),
        "down": _normal(ks[2], (held, d_expert, d_model), dtype,
                        d_expert ** -0.5),
    }
    if gated:
        p["gate"] = _normal(ks[3], (held, d_model, d_expert), dtype,
                            d_model ** -0.5)
    if scoring == "sigmoid":
        p["correction_bias"] = jnp.zeros((n_experts,), dtype)
    if n_shared:
        p["shared"] = mlp_init(ks[4], d_model, n_shared * d_expert, act,
                               dtype)
    return p


def _router(p, x, top_k: int, n_experts_logical: int, compute_dtype,
            scoring: str = "softmax", routed_scale: float = 1.0):
    """Top-k routing. x: (T, d). Returns (weights (T,k), ids (T,k), aux).
    ``routed_scale`` scales the sigmoid router's weights."""
    logits = (x.astype(jnp.float32)
              @ p["router"].astype(jnp.float32))            # (T, E)
    e = logits.shape[-1]
    if scoring == "sigmoid":
        return _sigmoid_router(p, logits, top_k, n_experts_logical,
                               routed_scale)
    if scoring != "softmax":
        raise ValueError(f"unknown router scoring {scoring!r}")
    if n_experts_logical < e:                                # padded experts
        pad_mask = jnp.arange(e) >= n_experts_logical
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    top_logits, top_ids = jax.lax.top_k(logits, top_k)       # (T, k)
    probs = jax.nn.softmax(top_logits, axis=-1)              # renormalized
    # Load-balance aux loss (Switch-style) + router z-loss, over real experts.
    full_probs = jax.nn.softmax(logits, axis=-1)
    me = full_probs.mean(axis=0)                             # (E,)
    ce = jnp.zeros((e,), jnp.float32).at[top_ids.reshape(-1)].add(
        1.0 / top_ids.size)
    aux = n_experts_logical * jnp.sum(me * ce)
    zloss = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return probs, top_ids, {"aux": aux, "zloss": zloss}


def _sigmoid_router(p, logits, top_k, n_experts_logical, routed_scale):
    """DeepSeek-V3's ``noaux_tc`` with one group: the experts are chosen
    on the sigmoid scores plus the correction bias, and weighted by the
    scores alone, normalised over the k. There is no auxiliary loss (the
    bias balances load)."""
    scores = jax.nn.sigmoid(logits)                          # (T, E)
    choice = scores
    if "correction_bias" in p:
        choice = scores + p["correction_bias"].astype(jnp.float32)
    e = logits.shape[-1]
    if n_experts_logical < e:                                # padded experts
        pad_mask = jnp.arange(e) >= n_experts_logical
        choice = jnp.where(pad_mask[None, :], -jnp.inf, choice)
    _, top_ids = jax.lax.top_k(choice, top_k)                # (T, k)
    weights = jnp.take_along_axis(scores, top_ids, axis=-1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    zero = jnp.zeros((), jnp.float32)
    return weights * routed_scale, top_ids, {"aux": zero, "zloss": zero}


def _held(p, ids, held_start: int):
    """The held experts' local ids of the routed ``ids`` and whether
    each is held; None where this layer holds every expert."""
    held = p["up"].shape[0]
    if held == p["router"].shape[-1]:
        return None
    local = ids - held_start
    return local, (local >= 0) & (local < held)


def _expert_ffn_dense(p, x, compute_dtype):
    """All experts on all tokens. x: (T, d) -> (E, T, d)."""
    up = jnp.einsum("td,edf->etf", x, p["up"].astype(compute_dtype))
    if "gate" in p:
        g = jnp.einsum("td,edf->etf", x, p["gate"].astype(compute_dtype))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype) * up
    else:
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(up.dtype)
    return jnp.einsum("etf,efd->etd", h, p["down"].astype(compute_dtype))


def moe_apply_dense(p, x2, probs, ids, *, held_start: int, compute_dtype):
    """dense_onehot path: every held expert on every token. x2: (T, d)."""
    e = p["router"].shape[-1]
    outs = _expert_ffn_dense(p, x2, compute_dtype)           # (E, T, d)
    onehot = jax.nn.one_hot(ids, e, dtype=jnp.float32)       # (T, k, E)
    weights = jnp.einsum("tk,tke->te", probs, onehot)        # (T, E)
    held = outs.shape[0]
    if held != e:                                            # held experts
        weights = weights[:, held_start:held_start + held]
    return jnp.einsum("te,etd->td", weights.astype(compute_dtype), outs)


def moe_apply_sort(p, x2, probs, ids, *, held_start: int, compute_dtype):
    """Dropless grouped-GEMM path via ragged_dot, over the tokens routed
    to the held experts. x2: (T, d)."""
    t, d = x2.shape
    top_k = ids.shape[-1]
    e = p["up"].shape[0]

    flat_ids = ids.reshape(-1)                               # (T*k,)
    held = _held(p, flat_ids, held_start)
    if held is not None:        # routes to experts not held: a last group
        local, is_held = held
        flat_ids = jnp.where(is_held, local, e)
        probs = jnp.where(is_held.reshape(probs.shape), probs, 0.0)
    order = jnp.argsort(flat_ids)                            # stable
    inv = jnp.argsort(order)
    token_of = order // top_k                                # source token
    xs = x2[token_of]                                        # (T*k, d) sorted
    group_sizes = jnp.bincount(flat_ids, length=e).astype(jnp.int32)

    up = jax.lax.ragged_dot(xs, p["up"].astype(compute_dtype), group_sizes)
    if "gate" in p:
        g = jax.lax.ragged_dot(xs, p["gate"].astype(compute_dtype),
                               group_sizes)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype) * up
    else:
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(up.dtype)
    ys = jax.lax.ragged_dot(h, p["down"].astype(compute_dtype), group_sizes)

    y_flat = ys[inv]                                         # (T*k, d) token order
    if held is not None:        # rows past the held groups are not computed
        y_flat = jnp.where(held[1][:, None], y_flat, 0)
    w = probs.reshape(-1)[:, None].astype(compute_dtype)
    return jnp.sum((y_flat * w).reshape(t, top_k, d), axis=1)


def moe_apply(p, x, *, top_k: int, n_experts_logical: int, impl: str,
              compute_dtype, scoring: str = "softmax",
              routed_scale: float = 1.0, held_start: int = 0,
              act: str = "silu"):
    """x: (..., d). Returns (the held experts' part of the routed output
    plus the shared experts' (of activation ``act``), aux losses)."""
    if impl not in ("dense_onehot", "sort"):
        raise ValueError(f"unknown moe impl {impl!r}")
    apply = moe_apply_dense if impl == "dense_onehot" else moe_apply_sort
    shp = x.shape
    x2 = x.reshape(-1, shp[-1]).astype(compute_dtype)        # (T, d)
    probs, ids, aux = _router(p, x2, top_k, n_experts_logical, compute_dtype,
                              scoring, routed_scale)
    with jax.named_scope("routed_experts"):
        y = apply(p, x2, probs, ids, held_start=held_start,
                  compute_dtype=compute_dtype)
    if "shared" in p:
        with jax.named_scope("shared_experts"):
            y = y + mlp_apply(p["shared"], x2, act, compute_dtype)
    return y.reshape(shp), aux
