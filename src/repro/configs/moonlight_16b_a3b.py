"""Moonlight-16B-A3B — DeepSeek-V3's block: latent attention (MLA, no
q-LoRA), a leading dense layer, then MoE layers of 64 routed experts
(top-6 by sigmoid score with a correction bias) and 2 shared experts
[hf:moonshotai/Moonlight-16B-A3B, arXiv:2502.16982; arXiv:2412.19437].

``CONFIG`` is the published model. ``EP8`` is one chip's share of a
deployment that divides each MoE layer over 8 chips by expert
parallelism (attention, the dense layer and the shared experts data
parallel): this chip holds experts 0-7 of the router's 64, and the
first of three pipeline stages, layer 0 and 8 MoE layers. Every width,
the router's width and its experts per token are as published.
"""
import dataclasses

from repro.configs.base import ArchConfig, MoEConfig, MOE

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family=MOE,
    num_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,               # the leading dense layer's width
    vocab_size=163840,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    moe=MoEConfig(num_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  scoring="sigmoid", routed_scale=2.446),
    first_k_dense=1,
    norm="rmsnorm",
    act="silu",
    rope_theta=50_000.0,
    max_seq_len=8192,
)

#: one chip of eight sharing each MoE layer, first pipeline stage of three
EP8 = dataclasses.replace(
    CONFIG, name="moonlight-16b-a3b-ep8", num_layers=9,
    moe=dataclasses.replace(CONFIG.moe, n_held=8, held_start=0))

SMOKE = ArchConfig(
    name="moonlight-16b-a3b-smoke",
    family=MOE,
    num_layers=3,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=160,
    vocab_size=384,
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    moe=MoEConfig(num_experts=8, top_k=2, d_expert=48, n_shared=1,
                  scoring="sigmoid", routed_scale=2.446, n_held=4),
    first_k_dense=1,
    norm="rmsnorm",
    act="silu",
    rope_theta=50_000.0,
)
