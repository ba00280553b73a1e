"""Operations and bytes that a step needs, computed from shapes alone.

These count what the algorithm requires, whatever implements it: real
rows and real context lengths (no padding), the experts each token is
routed to (not every expert), causal attention (half the square), and
weights read once in the served precision (bf16, 2 bytes). A program
that pads, gathers the whole page window or computes unrouted experts
does more; its share of the roofline then reads lower, never above 100%.

``sizes`` is a configuration file of ``chipbench/configs`` as a dict.
"""
from __future__ import annotations

import json
from pathlib import Path

#: bytes of one served weight or cache element (bf16)
ELEM = 2

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a device that
    is not in ``peaks.json`` is an error, not a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; have {sorted(table)}")
    return table[device_kind]


def _n_experts(s) -> int:
    return int(s.get("num_local_experts") or 0)


def attn_params(s) -> int:
    d, hd = s["hidden_size"], s["head_dim"]
    q = s["num_attention_heads"] * hd
    kv = s["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def expert_params(s) -> int:
    """One expert (MoE) or the one MLP (dense): gated, three matrices."""
    return 3 * s["hidden_size"] * s["intermediate_size"]


def router_params(s) -> int:
    return s["hidden_size"] * _n_experts(s)


def head_params(s) -> int:
    return s["vocab_size"] * s["hidden_size"]


def param_count(s) -> int:
    """Parameters of the model with its logical vocabulary."""
    e = max(1, _n_experts(s))
    layer = (attn_params(s) + router_params(s) + e * expert_params(s)
             + 2 * s["hidden_size"])
    tables = head_params(s) * (1 if s["tie_word_embeddings"] else 2)
    return s["num_hidden_layers"] * layer + tables + s["hidden_size"]


def kv_bytes_per_token(s) -> int:
    return (2 * s["num_hidden_layers"] * s["num_key_value_heads"]
            * s["head_dim"] * ELEM)


def _active_experts(s) -> int:
    return int(s.get("num_experts_per_tok") or 0) or 1


def layer_flops_per_token(s, *, routed: bool = True) -> int:
    """2 x the layer weights one token multiplies: attention
    projections, the router, and its experts (all of them where
    ``routed`` is False) or the MLP."""
    e = _active_experts(s) if routed else max(1, _n_experts(s))
    layer = attn_params(s) + router_params(s) + e * expert_params(s)
    return 2 * s["num_hidden_layers"] * layer


def matmul_flops_per_token(s, *, routed: bool = True) -> int:
    """A token that is sampled from: its layers and the LM head. The
    embedding lookup is no multiply."""
    return layer_flops_per_token(s, routed=routed) + 2 * head_params(s)


def attn_flops(s, keys_total: float) -> float:
    """QK^T and PV over ``keys_total`` (query, key) pairs, all layers."""
    return (4.0 * s["num_hidden_layers"] * s["num_attention_heads"]
            * s["head_dim"] * keys_total)


def _weight_bytes(s, rows_tokens: int) -> float:
    """bf16 weights one step must read once: attention, router, the
    experts at least one of ``rows_tokens`` tokens routes to (at most
    min(E, k x tokens) per layer; this can count high at small batches,
    where routes coincide), or the MLP, and the LM head table."""
    n_exp = _n_experts(s)
    touched = min(n_exp, _active_experts(s) * rows_tokens) if n_exp else 1
    layer = attn_params(s) + router_params(s) + touched * expert_params(s)
    return ELEM * (s["num_hidden_layers"] * layer + head_params(s))


def decode_work(s, contexts) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over real rows whose attention
    reads ``contexts[i]`` keys each (the new token's included)."""
    rows = len(contexts)
    keys = float(sum(contexts))
    flops = rows * matmul_flops_per_token(s) + attn_flops(s, keys)
    kvb = kv_bytes_per_token(s)
    nbytes = (_weight_bytes(s, rows) + rows * s["hidden_size"] * ELEM
              + (keys - rows) * kvb + rows * kvb)
    return flops, nbytes


def prefill_work(s, prompt_len: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one prompt's prefill: causal attention over
    its real length, its K/V written once, the LM head for its last
    token only (the one that is sampled from)."""
    p = int(prompt_len)
    flops = (p * layer_flops_per_token(s) + 2 * head_params(s)
             + attn_flops(s, p * (p + 1) / 2.0))
    nbytes = (_weight_bytes(s, p) + p * s["hidden_size"] * ELEM
              + p * kv_bytes_per_token(s))
    return flops, nbytes


def bound_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two."""
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
