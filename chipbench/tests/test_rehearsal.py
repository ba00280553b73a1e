"""A run without the chip: the harness's set-up, client loop, check and
readers at smoke size on the CPU, the float8 control failing the check,
and faults planted under the timed path turning ``correct`` false."""
import dataclasses
import json

import jax
import pytest

from repro.configs import get_smoke_config
from repro.serving import paged_model

from chipbench import client, run, traffic, work

SEED = 2 ** 31 + 7
#: the published configurations' structure at the program's smoke sizes
SMOKE = {
    "internlm2-1.8b": dict(
        reference="chipbench/reference.py",
        num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=192,
        vocab_size=384, hidden_act="silu", rope_theta=10000.0,
        rms_norm_eps=1e-5, tie_word_embeddings=False, num_local_experts=0,
        num_experts_per_tok=0, dtype="bfloat16"),
    "granite-moe-1b-a400m": dict(
        reference="chipbench/reference.py",
        num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=64,
        vocab_size=384, hidden_act="silu", rope_theta=10000.0,
        rms_norm_eps=1e-5, tie_word_embeddings=True, num_local_experts=4,
        num_experts_per_tok=2, dtype="bfloat16"),
}
#: limits for these smoke sizes, between readings on the CPU: widest gap
#: of sound runs 0.004-0.006 (internlm2) and 0.15 (granite, whose
#: 4-expert top-2 router flips on bf16 rounding), the float8 control
#: 0.055 (internlm2)
LIMIT = {"internlm2-1.8b": 0.02, "granite-moe-1b-a400m": 0.5}
CELLS = [("internlm2-1.8b", "offline"), ("granite-moe-1b-a400m", "docqa")]
#: the benchmark cell at smoke size; the MoE one borrows the cell and
#: serves an open-loop document-QA shape
DOCQA = dict(loop="poisson", rate=5.0, drain_s=60.0, reference_tokens=256,
             prompt=traffic.Lengths(2048, 0.5, 256, 4096),
             output=traffic.Lengths(24, 0.8, 1, 128))


def smoke_cell(arch, mix):
    bench = run.load_cell("internlm2-1.8b.offline")
    t = bench.traffic
    if mix == "docqa":
        t = dataclasses.replace(t, name=mix, **DOCQA)
    t = dataclasses.replace(
        t, ramp_s=0.5,
        prompt=dataclasses.replace(t.prompt, min=min(t.prompt.min, 8),
                                   median=min(t.prompt.median, 48),
                                   max=min(t.prompt.max, 120)),
        output=dataclasses.replace(t.output, max=min(t.output.max, 24)))
    sizes = dict(SMOKE[arch], arch=arch, limits={"logit_gap": LIMIT[arch]},
                 engine={"max_batch": 8, "block_size": 16,
                         "num_blocks": 128, "max_batched_tokens": 2048})
    return dataclasses.replace(bench, sizes=sizes, traffic=t,
                               reference=run.load_reference(sizes))


def rehearse(arch, mix, seconds=1.0, patch=None):
    cell = smoke_cell(arch, mix)
    compiles = run.Compiles(jax)
    model, params, engine = run.build(cell, SEED,
                                      cfg=get_smoke_config(arch))
    if patch:
        patch(engine)
    tl, d, served = run.serve(cell, engine, SEED, seconds,
                              compiles=compiles)
    return cell, params, tl, d, served


@pytest.mark.parametrize("arch", list(SMOKE))
def test_size_check_passes_on_the_smoke_structures(arch):
    run.check_arch(get_smoke_config(arch), smoke_cell(arch, "offline"))


@pytest.mark.parametrize("key,value", [
    ("rms_norm_eps", 1e-6),               # the program fixes 1e-5
    ("hidden_act", "gelu"),               # the program's is silu
    ("hidden_act", None),                 # a key the file lacks
], ids=["rms_norm_eps", "hidden_act", "missing"])
def test_size_check_stops_on_a_changed_or_missing_key(key, value):
    cell = smoke_cell("internlm2-1.8b", "offline")
    sizes = dict(cell.sizes, **{key: value})
    if value is None:
        del sizes[key]
    with pytest.raises(SystemExit, match=key):
        run.check_arch(get_smoke_config("internlm2-1.8b"),
                       dataclasses.replace(cell, sizes=sizes))


@pytest.mark.parametrize("arch,mix", CELLS)
def test_smoke_run_is_correct_and_reports(arch, mix):
    cell, params, tl, d, served = rehearse(arch, mix)
    assert tl.compiles_in_window == 0
    compared = run.check(cell, params, tl, d, served, SEED)["served"]
    assert run.correct(compared), compared
    assert compared["compared_tokens"][0] >= min(
        cell.traffic.reference_tokens, 24)
    e2e = client.end_to_end(tl)
    names = {m["name"] for m in cell.end_to_end} - {"setup_s"}
    assert names <= set(e2e) and all(e2e[n] > 0 for n in names)
    layer = run.per_layer(cell, tl, None, work.peaks("TPU v5 lite"))
    host_only = {m["name"] for m in cell.per_layer
                 if m["source"] == "host_clock"}
    assert host_only <= set(layer)
    assert all(v["value"] > 0 for v in layer.values())


def test_float8_control_fails_the_check():
    """The control: the reference with float8 weights in the program's
    place, read at the same prompts and served tokens, judged by the
    same comparison and limits."""
    arch, mix = CELLS[0]
    cell, params, tl, d, served = rehearse(arch, mix)
    compared = run.check(cell, params, tl, d, served, SEED, control=True)
    assert run.correct(compared["served"])
    assert not run.correct(compared["control"]), compared["control"]
    gap = compared["served"]["logit_gap"][0]
    assert compared["control"]["logit_gap"][0] >= 3 * gap


def _alter_tokens(engine, monkeypatch):
    """Every token the engine samples is replaced by the next id."""
    sample = engine._sample
    vocab = engine.model.plan.vocab_logical
    monkeypatch.setattr(engine, "_sample",
                        lambda logits: (sample(logits) + 1) % vocab)


def _drop_decode_kv(engine, monkeypatch):
    """The decode step returns the page store it was given: the state
    (the K/V of each decoded token) is left unchanged."""
    step = paged_model.paged_decode_step

    def unchanged(model, params, pages, toks, path):
        logits, new = step(model, params, pages, toks, path)
        return logits, dict(pages, len=new["len"])

    monkeypatch.setattr(paged_model, "paged_decode_step", unchanged)


@pytest.mark.parametrize("fault", [_alter_tokens, _drop_decode_kv],
                         ids=["token_altered", "decode_state_unchanged"])
def test_a_fault_under_the_timed_path_is_not_correct(fault, monkeypatch):
    arch, mix = CELLS[0]
    cell, params, tl, d, served = rehearse(
        arch, mix, patch=lambda eng: fault(eng, monkeypatch))
    compared = run.check(cell, params, tl, d, served, SEED)["served"]
    assert not run.correct(compared), compared


@pytest.mark.parametrize("control", [0, 1], ids=["served", "control"])
def test_a_cli_run_without_the_chip_look(control, monkeypatch, capsys):
    """``run.main`` past its look for a chip, at smoke size: the served
    tokens come out correct and the control, judged in their place by
    the same limits, does not; each compared number is printed with its
    limit, last on standard error and last in the result's line."""
    arch, mix = CELLS[0]
    cell = smoke_cell(arch, mix)
    build = run.build
    peaks = work.peaks
    monkeypatch.setattr(run, "load_cell", lambda name: cell)
    monkeypatch.setattr(run, "start_jax", lambda chips: jax.devices())
    monkeypatch.setattr(run, "build", lambda c, seed: build(
        c, seed, cfg=get_smoke_config(arch)))
    monkeypatch.setattr(work, "peaks", lambda kind: peaks("TPU v5 lite"))
    assert run.main(["--workload", "smoke", "--seed", str(SEED),
                     "--seconds", "1", "--trace", "0",
                     "--control", str(control)]) == 0
    out, err = capsys.readouterr()
    result = json.loads(out.splitlines()[-1])
    assert result["correct"] is (control == 0), result["compared"]
    assert list(result)[-1] == "compared"
    assert {"setup_s", "output_tok_s"} <= set(result["metrics"])
    assert err.splitlines()[-1].startswith("compared unfinished:")
    assert "compared logit_gap:" in err
