"""Operations and bytes from shapes, checked against counts by hand."""
import json
from pathlib import Path

import pytest

from chipbench import client, devtrace, run, work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def sizes(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


INTERNLM = sizes("internlm2-1.8b")
#: granite-3.0-1b-a400m-base's published sizes (not a benchmark
#: configuration yet): the MoE counts are checked on it
GRANITE = dict(num_hidden_layers=24, hidden_size=1024,
               num_attention_heads=16, num_key_value_heads=8, head_dim=64,
               intermediate_size=512, vocab_size=49155,
               tie_word_embeddings=True, num_local_experts=32,
               num_experts_per_tok=8)


def test_param_counts_by_hand():
    # internlm2-1.8b: per layer q,o 2048x2048, k,v 2048x1024, SwiGLU
    # 3x2048x8192, two norms; embedding and LM head 92544x2048; final norm
    layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192 + 2 * 2048
    assert work.param_count(INTERNLM) == 24 * layer + 2 * 92544 * 2048 + 2048
    assert work.param_count(INTERNLM) == pytest.approx(1.89e9, rel=2e-3)
    # granite: q,o 1024x1024, k,v 1024x512, router 1024x32, 32 experts of
    # 3x1024x512, two norms; one tied table 49155x1024
    layer = (2 * 1024 * 1024 + 2 * 1024 * 512 + 1024 * 32
             + 32 * 3 * 1024 * 512 + 2 * 1024)
    assert work.param_count(GRANITE) == 24 * layer + 49155 * 1024 + 1024
    assert work.param_count(GRANITE) == pytest.approx(1.33e9, rel=5e-3)


def test_kv_bytes_per_token():
    assert work.kv_bytes_per_token(INTERNLM) == 2 * 24 * 8 * 128 * 2 == 98304
    assert work.kv_bytes_per_token(GRANITE) == 2 * 24 * 8 * 64 * 2 == 49152


def test_granite_flops_per_token_routed_and_dense():
    attn = 2 * 1024 * 1024 + 2 * 1024 * 512
    expert = 3 * 1024 * 512
    head = 49155 * 1024
    routed = 2 * (24 * (attn + 1024 * 32 + 8 * expert) + head)
    dense = 2 * (24 * (attn + 1024 * 32 + 32 * expert) + head)
    assert work.matmul_flops_per_token(GRANITE) == routed
    assert work.matmul_flops_per_token(GRANITE, routed=False) == dense
    assert routed == pytest.approx(0.857e9, rel=1e-3)
    assert dense == pytest.approx(2.669e9, rel=1e-3)


def test_decode_and_prefill_by_hand():
    # one internlm2 row at a context of 100 keys
    flops, nbytes = work.decode_work(INTERNLM, [100])
    assert flops == (work.matmul_flops_per_token(INTERNLM)
                     + 4 * 24 * 16 * 128 * 100)
    weights = 2 * (work.param_count(INTERNLM) - 92544 * 2048
                   - 24 * 2 * 2048 - 2048)
    assert nbytes == weights + 2 * 2048 + 100 * 98304
    # a 10-token prompt: causal pairs 55, LM head once
    flops, nbytes = work.prefill_work(INTERNLM, 10)
    assert flops == (10 * work.layer_flops_per_token(INTERNLM)
                     + 2 * 92544 * 2048 + 4 * 24 * 16 * 128 * 55)
    assert nbytes == weights + 10 * 2048 * 2 + 10 * 98304


@pytest.mark.parametrize("cfg", [INTERNLM, GRANITE], ids=["internlm2",
                                                           "granite"])
def test_share_never_over_100_when_waste_is_removed(cfg):
    """A program that pads rows, pads contexts to the page window, pads
    prompts to a bucket or computes every expert does at least the work
    counted here, so removing that waste cannot lift a share over 100%."""
    peak = work.peaks("TPU v5 lite")
    real = [37, 600, 1024, 5]
    need = work.bound_seconds(*work.decode_work(cfg, real), peak)
    padded_rows = work.bound_seconds(
        *work.decode_work(cfg, real + [1] * 28), peak)
    padded_ctx = work.bound_seconds(
        *work.decode_work(cfg, [2048] * len(real)), peak)
    assert need <= padded_rows and need <= padded_ctx
    for p in (1, 300, 2048, 4000):
        bucket = 1 << (p - 1).bit_length()
        assert (work.bound_seconds(*work.prefill_work(cfg, p), peak)
                <= work.bound_seconds(*work.prefill_work(cfg, bucket), peak))
    assert (work.matmul_flops_per_token(cfg)
            <= work.matmul_flops_per_token(cfg, routed=False))


def test_moe_decode_bytes_count_touched_experts():
    one = work.decode_work(GRANITE, [10])[1]
    many = work.decode_work(GRANITE, [10] * 4)[1]
    per_expert = 2 * 24 * 3 * 1024 * 512
    # one row touches 8 experts, four rows at most all 32
    assert many - one == pytest.approx(24 * per_expert + 3 * 2 * 1024
                                       + 30 * 49152, rel=1e-12)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v99")
    assert work.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_readers_through_the_cells_module_equal_the_counts():
    """``decode_roofline`` and ``mfu_pct`` reach the counts through the
    cell's module; on a synthetic timeline they read exactly what
    ``work`` gives when called directly."""
    cell = run.load_cell("internlm2-1.8b.offline")
    assert cell.sizes == INTERNLM
    steps = [client.Step(start=10.0 + 0.25 * i, end=10.2 + 0.25 * i,
                         wall=0.15, kind=kind, contexts=ctx, prompts=p)
             for i, (kind, ctx, p) in enumerate([
                 ("prefill", (), (110, 1024)),
                 ("decode", (37, 600, 1024, 5), ()),
                 ("decode", (38, 601, 1025, 6), ()),
                 ("prefill", (), (7,))])]
    tl = client.Timeline(sent=[], steps=steps, open=10.0, close=11.0)
    trace = devtrace.Reduced(window_s=1.0, busy_s=0.6, devices=1,
                             program_s={"jit_paged_decode_step": 0.3},
                             program_calls={"jit_paged_decode_step": 2})
    peak = work.peaks("TPU v5 lite")
    layer = run.per_layer(cell, tl, trace, peak)
    decode = steps[1:3]
    bound = sum(work.bound_seconds(*work.decode_work(INTERNLM, s.contexts),
                                   peak) for s in decode) / len(decode)
    assert layer["decode_roofline"]["value"] == 100.0 * bound / (0.3 / 2)
    flops = sum(work.decode_work(INTERNLM, s.contexts)[0]
                if s.kind == "decode" else
                sum(work.prefill_work(INTERNLM, p)[0] for p in s.prompts)
                for s in steps)
    assert layer["mfu_pct"]["value"] == 100.0 * flops / (1.0 * peak[
        "flops_bf16"])
