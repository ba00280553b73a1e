"""The plain reference: each configuration's forward pass in float32.

Straightforward ``jax.numpy`` at "highest" matmul precision, with no
kernel, cache, padding or batching, from the sizes in the configuration
file and the weights the benchmark made. It imports nothing of the
program. It follows the published descriptions (InternLM2: arXiv
2403.17297; Granite 3.0 MoE: the ibm-granite model card): pre-norm
RMSNorm blocks, split-half rotary embeddings, grouped-query causal
attention, a SwiGLU MLP or a top-k routed mixture of SwiGLU experts
whose top-k router logits are softmaxed, and an LM head over the
logical vocabulary.

The output check runs it once over each compared prompt with its served
tokens and reads, at each position, the gap by which the served token's
logit lies below the best. The control is the same pass with every
weight matrix rounded to float8 (e4m3, one scale per matrix): the
nearest step below the bfloat16 the configuration serves in.

A configuration file names this module under ``"reference"``, and the
harness reaches it through ``run.ARCH_API`` alone: ``program_sizes``,
``gaps``, and the work counts of ``work.py``, re-exported.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.work import decode_work, prefill_work

__all__ = ["program_sizes", "gaps", "decode_work", "prefill_work"]

HIGHEST = jax.lax.Precision.HIGHEST
#: sequences are padded on the right to a multiple of this (causal
#: attention keeps the padding out of every real position); few shapes,
#: few compiles
BUCKET = 1024
#: queries per attention block, to bound the score matrix
Q_BLOCK = 1024


@dataclass(frozen=True)
class Arch:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    rope_theta: float
    eps: float
    tied: bool
    experts: int
    top_k: int

    @staticmethod
    def of(s: dict) -> "Arch":
        return Arch(layers=s["num_hidden_layers"], d=s["hidden_size"],
                    heads=s["num_attention_heads"],
                    kv_heads=s["num_key_value_heads"],
                    head_dim=s["head_dim"], vocab=s["vocab_size"],
                    rope_theta=float(s["rope_theta"]),
                    eps=float(s["rms_norm_eps"]),
                    tied=bool(s["tie_word_embeddings"]),
                    experts=int(s.get("num_local_experts") or 0),
                    top_k=int(s.get("num_experts_per_tok") or 0))


#: file keys whose value the program fixes in code and does not read from
#: its ``ArchConfig``: (the value, where the program fixes it)
FIXED = {
    "rms_norm_eps": (1e-5, "the default eps of repro.models.layers."
                           "norm_apply; model_zoo passes none"),
}


def program_sizes(cfg) -> dict:
    """The program's ``ArchConfig`` read out under the file's keys; a
    MoE's ``intermediate_size`` is its expert width."""
    moe = cfg.moe
    sizes = {"num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
             "num_attention_heads": cfg.n_heads,
             "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
             "intermediate_size": moe.d_expert if moe else cfg.d_ff,
             "vocab_size": cfg.vocab_size, "hidden_act": cfg.act,
             "rope_theta": cfg.rope_theta,
             "tie_word_embeddings": cfg.tie_embeddings,
             "num_local_experts": moe.num_experts if moe else 0,
             "num_experts_per_tok": moe.top_k if moe else 0,
             "dtype": cfg.dtype}
    sizes.update({k: v for k, (v, _) in FIXED.items()})
    return sizes


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def fp8(w):
    """``w`` rounded to float8 e4m3 with one scale per matrix (the last
    two axes), back in float32."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=(-2, -1), keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (S, H, D), positions 0..S-1, halves rotated as pairs."""
    s, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(a: Arch, p, x, q8):
    s = x.shape[0]
    w = {k: q8(p[k]["w"]) for k in ("wq", "wk", "wv", "wo")}
    q = _rope(_mm(x, w["wq"]).reshape(s, a.heads, a.head_dim), a.rope_theta)
    k = _rope(_mm(x, w["wk"]).reshape(s, a.kv_heads, a.head_dim),
              a.rope_theta)
    v = _mm(x, w["wv"]).reshape(s, a.kv_heads, a.head_dim)
    group = a.heads // a.kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    keys = jnp.arange(s)
    out = []
    for lo in range(0, s, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) \
            * a.head_dim ** -0.5
        mask = keys[None, :] <= (lo + jnp.arange(qb.shape[0]))[:, None]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                              precision=HIGHEST))
    ctx = jnp.concatenate(out, 0).reshape(s, a.heads * a.head_dim)
    return _mm(ctx, w["wo"])


def _swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def _mlp(p, x, q8):
    return _swiglu(x, q8(p["gate"]["w"]), q8(p["up"]["w"]),
                   q8(p["down"]["w"]))


def _moe(a: Arch, p, x, q8):
    logits = _mm(x, q8(p["router"])[:, :a.experts])
    top, ids = jax.lax.top_k(logits, a.top_k)
    gates = jax.nn.softmax(top, -1)                          # (S, k)
    weight = jnp.sum(jax.nn.one_hot(ids, a.experts) * gates[..., None], 1)

    def expert(y, e):
        out = _swiglu(x, q8(p["gate"][e]), q8(p["up"][e]), q8(p["down"][e]))
        return y + weight[:, e, None] * out, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(a.experts))
    return y


def _forward(a: Arch, params, tokens, q8):
    """Logits (S, vocab) of ``tokens`` (S,)."""
    table = params["embed"]["table"].astype(jnp.float32)
    x = table[tokens]

    def layer(x, p):
        h = _rms(x, p["ln1"]["scale"], a.eps)
        x = x + _attention(a, p["attn"], h, q8)
        h = _rms(x, p["ln2"]["scale"], a.eps)
        ffn = _moe(a, p["moe"], h, q8) if a.experts else _mlp(p["mlp"], h, q8)
        return x + ffn, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], a.eps)
    head = params["embed" if a.tied else "unembed"]["table"]
    return _mm(x, q8(head[:a.vocab]).T)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _read(a: Arch, params, tokens, targets, control: bool):
    """Per position: the best logit, the target's logit, and where
    ``control``, the reference's logit at the control's first choice."""
    ref = _forward(a, params, tokens, lambda w: w.astype(jnp.float32))
    pos = jnp.arange(tokens.shape[0])
    out = {"best": ref.max(-1), "target": ref[pos, targets]}
    if control:
        ctrl = _forward(a, params, tokens, fp8)
        out["control"] = ref[pos, jnp.argmax(ctrl, -1)]
    return out


def gaps(sizes: dict, params, prompt, served, *,
         control: bool = False) -> dict:
    """Gaps (best logit minus the compared token's) at the positions
    that produced ``served``: the prompt's last, then each served token
    but the last. ``served`` gap: of the served tokens; ``control`` gap:
    of the float8 pass's first choices. ``sizes``: the configuration
    file."""
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
