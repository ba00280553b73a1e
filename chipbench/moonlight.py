"""Moonlight-16B-A3B's plain reference, size check and work counts.

DeepSeek-V3's block (arXiv:2412.19437) at Moonlight's sizes
(https://huggingface.co/moonshotai/Moonlight-16B-A3B, arXiv:2502.16982),
in float32 ``jax.numpy`` at "highest" matmul precision, with no kernel,
cache, padding or batching. It imports nothing of the program. Pre-norm
RMSNorm blocks; layer 0 a dense SwiGLU, the rest MoE:

* latent attention without q-LoRA, in its decompressed form: the query
  of each head is (no-rope, rope) parts; ``wkv_a`` gives a latent,
  RMS-normed (eps 1e-6, DeepSeek-V3's ``kv_a_layernorm``), and one rope
  key every head shares; ``wkv_b`` expands the latent to each head's
  no-rope key and value; causal softmax scaled by (no-rope + rope) **
  -0.5.
* routing: sigmoid scores of the router's full width; the top-k chosen
  on the scores plus the correction bias, weighted by the scores alone,
  normalised over the k and scaled by ``routed_scaling_factor``
  (``noaux_tc`` with one group); the experts this chip holds give their
  part of the routed sum, the others give nothing here (the share of
  an expert-parallel deployment, as the program computes it); shared
  experts, one SwiGLU of ``n_shared_experts`` x the expert width, see
  every token.

Departure from the published model: rope rotates the two halves of the
64-wide rope parts as pairs (the program's layout), where DeepSeek
rotates interleaved pairs. It is a fixed permutation of the rope
weights' columns, applied alike to query and key, so it changes no
score given weights permuted to match; the weights here are random.

The output check is ``chipbench/reference.py``'s: the gap by which the
served token's logit lies below the best, and the float8 control.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import (BUCKET, HIGHEST, Q_BLOCK, _mm, _rms, _rope,
                                 _swiglu, fp8)
from chipbench.work import ELEM

__all__ = ["program_sizes", "gaps", "decode_work", "prefill_work"]

#: eps of the latent's RMSNorm: DeepSeek-V3's ``kv_a_layernorm`` takes the
#: norm's default, not ``rms_norm_eps``
KV_NORM_EPS = 1e-6


@dataclass(frozen=True)
class Arch:
    """What the reference reads of the file beyond the weights' shapes."""
    dense: int
    heads: int
    rank: int
    nope: int
    rope: int
    dv: int
    vocab: int
    rope_theta: float
    eps: float
    router: int
    held: int
    first_held: int
    top_k: int
    scale: float
    norm_topk: bool

    @staticmethod
    def of(s: dict) -> "Arch":
        return Arch(dense=s["first_k_dense_replace"],
                    heads=s["num_attention_heads"], rank=s["kv_lora_rank"],
                    nope=s["qk_nope_head_dim"], rope=s["qk_rope_head_dim"],
                    dv=s["v_head_dim"], vocab=s["vocab_size"],
                    rope_theta=float(s["rope_theta"]),
                    eps=float(s["rms_norm_eps"]), router=s["router_width"],
                    held=s["n_routed_experts"],
                    first_held=s["first_held_expert"],
                    top_k=s["num_experts_per_tok"],
                    scale=float(s["routed_scaling_factor"]),
                    norm_topk=bool(s["norm_topk_prob"]))


#: file keys whose value the program fixes in code and does not read from
#: its ``ArchConfig``: (the value, where the program fixes it)
FIXED = {
    "rms_norm_eps": (1e-5, "the default eps of repro.models.layers."
                           "norm_apply; model_zoo passes none"),
    "q_lora_rank": (None, "model_zoo._mla_init: wq is one d x H*(nope + "
                          "rope) matrix, no q-LoRA"),
    "n_group": (1, "moe._sigmoid_router: top-k over all experts, no group "
                   "limit"),
    "topk_group": (1, "moe._sigmoid_router: no group limit"),
    "moe_layer_freq": (1, "model_zoo: every layer after the dense ones is "
                          "MoE"),
    "norm_topk_prob": (True, "moe._sigmoid_router normalises the weights "
                             "over the k"),
    "num_nextn_predict_layers": (0, "the program has no multi-token "
                                    "prediction layers"),
}


def program_sizes(cfg) -> dict:
    """The program's ``ArchConfig`` read out under the file's keys. The
    count of routed experts is of those held here; ``router_width`` and
    ``first_held_expert`` say which of the router's they are."""
    moe = cfg.moe
    sizes = {"num_hidden_layers": cfg.num_layers,
             "first_k_dense_replace": cfg.first_k_dense,
             "hidden_size": cfg.d_model,
             "num_attention_heads": cfg.n_heads,
             "num_key_value_heads": cfg.n_kv_heads,
             "kv_lora_rank": cfg.kv_lora_rank,
             "qk_nope_head_dim": cfg.qk_nope_head_dim,
             "qk_rope_head_dim": cfg.qk_rope_head_dim,
             "v_head_dim": cfg.v_head_dim,
             "intermediate_size": cfg.d_ff,
             "moe_intermediate_size": moe.d_expert,
             "n_routed_experts": moe.held,
             "router_width": moe.num_experts,
             "first_held_expert": moe.held_start,
             "num_experts_per_tok": moe.top_k,
             "n_shared_experts": moe.n_shared,
             "scoring_func": moe.scoring,
             "topk_method": "noaux_tc" if moe.scoring == "sigmoid"
             else "greedy",
             "routed_scaling_factor": moe.routed_scale,
             "vocab_size": cfg.vocab_size, "hidden_act": cfg.act,
             "rope_theta": cfg.rope_theta,
             "max_position_embeddings": cfg.max_seq_len,
             "attention_bias": cfg.qkv_bias,
             "tie_word_embeddings": cfg.tie_embeddings,
             "dtype": cfg.dtype}
    sizes.update({k: v for k, (v, _) in FIXED.items()})
    return sizes


# ---------------------------------------------------------------------------
# The reference
# ---------------------------------------------------------------------------
def _mla(a: Arch, p, x, q8):
    """Decompressed latent attention over x: (S, d), causal."""
    s, h = x.shape[0], a.heads
    q = _mm(x, q8(p["wq"]["w"])).reshape(s, h, a.nope + a.rope)
    q = jnp.concatenate([q[..., :a.nope],
                         _rope(q[..., a.nope:], a.rope_theta)], -1)
    kv_a = _mm(x, q8(p["wkv_a"]["w"]))
    c = _rms(kv_a[:, :a.rank], p["kv_norm"]["scale"], KV_NORM_EPS)
    k_pe = _rope(kv_a[:, None, a.rank:], a.rope_theta)       # (S, 1, rope)
    kv = _mm(c, q8(p["wkv_b"]["w"])).reshape(s, h, a.nope + a.dv)
    k = jnp.concatenate([kv[..., :a.nope],
                         jnp.broadcast_to(k_pe, (s, h, a.rope))], -1)
    v = kv[..., a.nope:]
    keys = jnp.arange(s)
    out = []
    for lo in range(0, s, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) \
            * (a.nope + a.rope) ** -0.5
        mask = keys[None, :] <= (lo + jnp.arange(qb.shape[0]))[:, None]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                              precision=HIGHEST))
    ctx = jnp.concatenate(out, 0).reshape(s, h * a.dv)
    return _mm(ctx, q8(p["wo"]["w"]))


def _mlp(p, x, q8):
    return _swiglu(x, q8(p["gate"]["w"]), q8(p["up"]["w"]),
                   q8(p["down"]["w"]))


def _route(a: Arch, logits, bias):
    """(weights (S, k), expert ids (S, k)) of the sigmoid router."""
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + bias, a.top_k)
    w = jnp.take_along_axis(scores, ids, -1)
    if a.norm_topk:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * a.scale, ids


def _moe(a: Arch, p, x, q8):
    """The held experts' part of the routed output, plus the shared
    experts'."""
    w, ids = _route(a, _mm(x, q8(p["router"])),
                   p["correction_bias"].astype(jnp.float32))
    weight = jnp.sum(jax.nn.one_hot(ids, a.router) * w[..., None], 1)
    weight = weight[:, a.first_held:a.first_held + a.held]    # (S, held)

    def expert(y, e):
        out = _swiglu(x, q8(p["gate"][e]), q8(p["up"][e]), q8(p["down"][e]))
        return y + weight[:, e, None] * out, None

    y, _ = jax.lax.scan(expert, _mlp(p["shared"], x, q8),
                        jnp.arange(a.held))
    return y


def forward(a: Arch, params, tokens, q8=lambda w: w.astype(jnp.float32)):
    """Logits (S, vocab) of ``tokens`` (S,); ``q8`` rounds each weight
    matrix (float32 by default)."""
    x = params["embed"]["table"].astype(jnp.float32)[tokens]

    def layer(x, p):
        h = _rms(x, p["ln1"]["scale"], a.eps)
        x = x + _mla(a, p["attn"], h, q8)
        h = _rms(x, p["ln2"]["scale"], a.eps)
        ffn = _moe(a, p["moe"], h, q8) if "moe" in p else _mlp(p["mlp"], h,
                                                               q8)
        return x + ffn, None

    if a.dense:
        x, _ = jax.lax.scan(layer, x, params["dense_layers"])
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], a.eps)
    return _mm(x, q8(params["unembed"]["table"][:a.vocab]).T)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _read(a: Arch, params, tokens, targets, control: bool):
    """Per position: the best logit, the target's logit, and where
    ``control``, the reference's logit at the control's first choice."""
    ref = forward(a, params, tokens)
    pos = jnp.arange(tokens.shape[0])
    out = {"best": ref.max(-1), "target": ref[pos, targets]}
    if control:
        ctrl = forward(a, params, tokens, fp8)
        out["control"] = ref[pos, jnp.argmax(ctrl, -1)]
    return out


def gaps(sizes: dict, params, prompt, served, *,
         control: bool = False) -> dict:
    """Gaps (best logit minus the compared token's) at the positions
    that produced ``served``: the prompt's last, then each served token
    but the last. ``served`` gap: of the served tokens; ``control`` gap:
    of the float8 pass's first choices."""
    a = Arch.of(sizes)
    prompt, served = np.asarray(prompt), np.asarray(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n = len(seq)
    size = -(-n // BUCKET) * BUCKET
    tokens = np.zeros(size, np.int32)
    tokens[:n] = seq
    targets = np.zeros(size, np.int32)
    at = np.arange(len(prompt) - 1, n)
    targets[at] = served
    out = jax.device_get(_read(a, params, jnp.asarray(tokens),
                               jnp.asarray(targets), control))
    res = {"served": out["best"][at] - out["target"][at]}
    if control:
        res["control"] = out["best"][at] - out["control"][at]
    return res


# ---------------------------------------------------------------------------
# Work counts: what a step needs, from shapes alone
# ---------------------------------------------------------------------------
def latent_bytes(s) -> int:
    """One token's cached latent and rope key in one layer (bf16)."""
    return (s["kv_lora_rank"] + s["qk_rope_head_dim"]) * ELEM


def attn_matrices(s) -> int:
    """Weights of one MLA block that a token multiplies once: q, the
    latent's down projection, its expansion (absorbed into the query and
    the output at decode, the same count), the output projection."""
    d, h = s["hidden_size"], s["num_attention_heads"]
    nope, rope, dv = (s["qk_nope_head_dim"], s["qk_rope_head_dim"],
                      s["v_head_dim"])
    r = s["kv_lora_rank"]
    return d * h * (nope + rope) + d * (r + rope) + r * h * (nope + dv) \
        + h * dv * d


def expert_params(s) -> int:
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def _held_share(s) -> float:
    """Of a token's routes, how many land on the held experts, routed
    evenly: k x held / router width."""
    return s["num_experts_per_tok"] * s["n_routed_experts"] \
        / s["router_width"]


def _held_hit(s, tokens: int) -> float:
    """Held experts that ``tokens`` tokens hit, routed evenly: each is
    missed by a token with chance 1 - k / width."""
    miss = 1.0 - s["num_experts_per_tok"] / s["router_width"]
    return s["n_routed_experts"] * (1.0 - miss ** tokens)


def _layer_weights(s, moe_experts: float, *, flops: bool = False) -> float:
    """Weights of every layer, with ``moe_experts`` routed experts in
    each MoE layer: MLA, the dense layers' SwiGLU, the MoE layers'
    router and shared experts; with the latent norms and the correction
    bias unless ``flops`` (they are read, not multiplied)."""
    d, n_layers = s["hidden_size"], s["num_hidden_layers"]
    dense, width = s["first_k_dense_replace"], s["router_width"]
    moe = d * width + (s["n_shared_experts"] + moe_experts) \
        * expert_params(s)
    vectors = 0 if flops else n_layers * s["kv_lora_rank"] \
        + (n_layers - dense) * width
    return (n_layers * attn_matrices(s) + dense * 3 * d
            * s["intermediate_size"] + (n_layers - dense) * moe + vectors)


def _token_flops(s) -> float:
    """2 x the weights one token multiplies in its layers (routed: its
    expected share of the held experts)."""
    return 2.0 * _layer_weights(s, _held_share(s), flops=True)


def _head(s) -> int:
    return s["vocab_size"] * s["hidden_size"]


def decode_work(s, contexts) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over real rows whose attention
    reads ``contexts[i]`` keys each (the new token's included). Absorbed
    attention: scores over the latent and rope key, values over the
    latent; each row's latent read once a layer and its new one written;
    bf16 weights read once, the held experts that the rows hit, the LM
    head whole and the embedding by rows."""
    rows, keys = len(contexts), float(sum(contexts))
    n_layers, h = s["num_hidden_layers"], s["num_attention_heads"]
    r, lat = s["kv_lora_rank"], latent_bytes(s)
    attn = 2.0 * n_layers * h * ((r + s["qk_rope_head_dim"]) + r) * keys
    flops = rows * (_token_flops(s) + 2.0 * _head(s)) + attn
    nbytes = (ELEM * (_layer_weights(s, _held_hit(s, rows)) + _head(s))
              + rows * s["hidden_size"] * ELEM
              + n_layers * lat * keys)        # (keys - rows) read, rows written
    return flops, nbytes


def prefill_work(s, prompt_len: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one prompt's prefill: causal attention in the
    decompressed form (scores over no-rope + rope, values over v), the
    latent written once, the LM head for the last token only."""
    p = int(prompt_len)
    n_layers, h = s["num_hidden_layers"], s["num_attention_heads"]
    pair = 2.0 * h * (s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
                      + s["v_head_dim"])
    flops = (p * _token_flops(s) + 2.0 * _head(s)
             + n_layers * pair * p * (p + 1) / 2.0)
    nbytes = (ELEM * (_layer_weights(s, _held_hit(s, p)) + _head(s))
              + p * s["hidden_size"] * ELEM + p * n_layers * latent_bytes(s))
    return flops, nbytes


def param_count(s) -> int:
    """Parameters this chip holds, logical vocabulary (untied)."""
    return int(_layer_weights(s, s["n_routed_experts"])
               + 2 * s["num_hidden_layers"] * s["hidden_size"]
               + 2 * _head(s) + s["hidden_size"])


def kv_bytes_per_token(s) -> int:
    return s["num_hidden_layers"] * latent_bytes(s)

