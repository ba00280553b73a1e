"""The Moonlight-16B-A3B configuration's module (``chipbench/moonlight``):
its size check against the program, its counts checked by hand, the
roofline reader over them, and a run at smoke size on the CPU through
``run``'s functions, with the float8 control failing the check."""
import dataclasses
import json
from pathlib import Path

import jax
import pytest

from repro.configs import get_config, get_smoke_config

from chipbench import client, devtrace, moonlight, run, work

CELL = "moonlight-16b-a3b.offline"
FILE = json.loads((Path(__file__).resolve().parents[1] / "configs"
                   / "moonlight-16b-a3b.json").read_text())
SEED = 2 ** 31 + 11
#: the output check's limits at smoke size, from readings on the CPU over
#: seeds 2**31 + 11 .. + 20: the mean gap of sound runs 0.00004-0.00248,
#: of the float8 control 0.00694-0.0235. The widest gap does not separate
#: them here (sound 0.0026-0.222, the control 0.110-0.669): the 8-expert
#: top-2 router flips on bf16 rounding, as granite's smoke router does
LIMITS = {"mean_gap": 0.004}

#: one MLA block at the published widths: q 2048x(16x192), the latent's
#: down projection 2048x576, its expansion 512x(16x256), out (16x128)x2048
ATTN = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
EXPERT = 3 * 2048 * 1408
#: one MoE layer's matrices: the router 2048x64, the two shared experts
MOE = 2048 * 64 + 2 * EXPERT


def smoke_cell():
    bench = run.load_cell(CELL)
    t = bench.traffic
    t = dataclasses.replace(
        t, ramp_s=0.5,
        prompt=dataclasses.replace(t.prompt, min=1, median=24, max=60),
        output=dataclasses.replace(t.output, median=8, max=16))
    cfg = get_smoke_config("moonlight-16b-a3b")
    sizes = dict(moonlight.program_sizes(cfg), arch=cfg.name,
                 reference="chipbench/moonlight.py",
                 limits=LIMITS,
                 engine={"max_batch": 8, "block_size": 16, "num_blocks": 64,
                         "max_batched_tokens": 2048})
    return dataclasses.replace(bench, sizes=sizes, traffic=t,
                               reference=run.load_reference(sizes))


def test_program_sizes_of_the_one_chip_share_equal_the_file():
    cell = run.load_cell(CELL)
    assert cell.sizes == FILE and cell.reference is run.load_module(
        Path(moonlight.__file__).resolve())
    cfg = get_config(FILE["arch"])
    run.check_arch(cfg, cell)
    got = moonlight.program_sizes(cfg)
    assert got["router_width"] == 64 and got["n_routed_experts"] == 8
    assert {k: FILE[k] for k in got} == got


@pytest.mark.parametrize("key,value", [
    ("kv_lora_rank", 256), ("qk_nope_head_dim", 64),
    ("qk_rope_head_dim", 32), ("v_head_dim", 192),
    ("router_width", 32), ("n_routed_experts", 16),
    ("first_held_expert", 8), ("num_experts_per_tok", 8),
    ("n_shared_experts", 1), ("scoring_func", "softmax"),
    ("topk_method", "greedy"), ("routed_scaling_factor", 1.0),
    ("first_k_dense_replace", 0), ("n_group", 8),
])
def test_size_check_stops_on_a_changed_mla_or_router_key(key, value):
    cell = run.load_cell(CELL)
    bad = dataclasses.replace(cell, sizes=dict(cell.sizes, **{key: value}))
    with pytest.raises(SystemExit, match=key):
        run.check_arch(get_config(FILE["arch"]), bad)


def test_latent_bytes_and_parameters_by_hand():
    # a token's latent (512) and rope key (64) in bf16, each of 9 layers
    assert moonlight.latent_bytes(FILE) == (512 + 64) * 2 == 1152
    assert moonlight.kv_bytes_per_token(FILE) == 9 * 1152 == 10368
    # layer 0: MLA with its latent norm, SwiGLU 3x2048x11264, two norms;
    # 8 MoE layers: MLA, router and its bias, 8 held and 2 shared experts
    dense = ATTN + 512 + 3 * 2048 * 11264 + 2 * 2048
    moe = ATTN + 512 + MOE + 64 + 8 * EXPERT + 2 * 2048
    head = 163840 * 2048
    assert moonlight.param_count(FILE) == dense + 8 * moe + 2 * head + 2048
    assert moonlight.param_count(FILE) == pytest.approx(1.557e9, rel=1e-3)


def test_held_experts_hit():
    """At batch 128 every held expert is hit; one token routes 6 of 64,
    so it hits 0.75 held experts in expectation."""
    assert moonlight._held_hit(FILE, 1) == pytest.approx(6 * 8 / 64)
    assert moonlight._held_hit(FILE, 128) == pytest.approx(8, abs=1e-4)


def test_decode_counts_by_hand():
    # one row at a context of 100 keys: 9 layers' matrices, layer 0's
    # SwiGLU, the 8 MoE layers' router, shared experts and 6 x 8 / 64
    # of an expert, the LM head; absorbed attention over 16 heads, 576
    # wide for the scores and 512 for the values
    head = 163840 * 2048
    per_token = 2 * (9 * ATTN + 3 * 2048 * 11264
                     + 8 * (MOE + 0.75 * EXPERT) + head)
    attn = 2 * 9 * 16 * (576 + 512) * 100
    flops, nbytes = moonlight.decode_work(FILE, [100])
    assert flops == pytest.approx(per_token + attn, rel=1e-12)
    # weights once (0.75 held experts a layer for one token), the
    # embedding row, 100 latents read or written in each layer
    weights = (9 * (ATTN + 512) + 3 * 2048 * 11264
               + 8 * (MOE + 64 + 0.75 * EXPERT) + head)
    assert nbytes == pytest.approx(2 * weights + 2 * 2048 + 9 * 1152 * 100,
                                   rel=1e-12)
    # each further key: its latent once a layer, scores and values
    more = moonlight.decode_work(FILE, [101])
    assert more[0] - flops == pytest.approx(2 * 9 * 16 * 1088)
    assert more[1] - nbytes == pytest.approx(9 * 1152)
    # the decode step is bound by its bytes at the cell's batch
    f, b = moonlight.decode_work(FILE, [400] * 128)
    peak = work.peaks("TPU v5 lite")
    assert b / peak["hbm_bytes_per_s"] > f / peak["flops_bf16"]


def test_prefill_counts_by_hand():
    # decompressed causal attention: 16 heads x (192 + 128) a pair
    p = 110
    flops, nbytes = moonlight.prefill_work(FILE, p)
    head = 163840 * 2048
    hit = moonlight._held_hit(FILE, p)
    per_token = 2 * (9 * ATTN + 3 * 2048 * 11264 + 8 * (MOE + 0.75 * EXPERT))
    attn = 9 * 2 * 16 * (192 + 128) * p * (p + 1) / 2
    assert flops == pytest.approx(p * per_token + 2 * head + attn, rel=1e-12)
    weights = (9 * (ATTN + 512) + 3 * 2048 * 11264
               + 8 * (MOE + 64 + hit * EXPERT) + head)
    assert nbytes == pytest.approx(2 * weights + p * 2 * 2048
                                   + p * 9 * 1152, rel=1e-12)


def test_decode_roofline_stays_at_most_100_for_the_counted_work():
    """A decode program that takes just the counted work's bound reads
    100%; one that takes longer reads less."""
    cell = run.load_cell(CELL)
    peak = work.peaks("TPU v5 lite")
    contexts = [(37, 600, 1024, 5) * 32, (38, 601, 1025, 6) * 32]
    steps = [client.Step(start=10.0 + 0.25 * i, end=10.2 + 0.25 * i,
                         wall=0.15, kind="decode", contexts=c, prompts=())
             for i, c in enumerate(contexts)]
    tl = client.Timeline(sent=[], steps=steps, open=10.0, close=11.0)
    bound = [work.bound_seconds(*moonlight.decode_work(FILE, c), peak)
             for c in contexts]
    for scale, want in ((1.0, 100.0), (1.25, 80.0)):
        device = scale * sum(bound)
        trace = devtrace.Reduced(
            window_s=1.0, busy_s=device, devices=1,
            program_s={"jit_paged_decode_step": device},
            program_calls={"jit_paged_decode_step": 2})
        got = run.per_layer(cell, tl, trace, peak)["decode_roofline"]
        assert got["value"] == pytest.approx(want, rel=1e-12)


def _rehearse():
    cell = smoke_cell()
    compiles = run.Compiles(jax)
    _, params, engine = run.build(
        cell, SEED, cfg=get_smoke_config("moonlight-16b-a3b"))
    tl, d, served = run.serve(cell, engine, SEED, 1.0, compiles=compiles)
    return cell, params, tl, d, served


def test_smoke_run_is_correct_and_its_control_is_not():
    cell, params, tl, d, served = _rehearse()
    assert tl.compiles_in_window == 0
    compared = run.check(cell, params, tl, d, served, SEED, control=True)
    assert run.correct(compared["served"]), compared["served"]
    assert not run.correct(compared["control"]), compared["control"]
    assert compared["served"]["compared_tokens"][0] >= 24
    layer = run.per_layer(cell, tl, None, work.peaks("TPU v5 lite"))
    assert {"sched_ms_per_step", "mfu_pct"} <= set(layer)
