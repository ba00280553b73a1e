"""Compile the serving path's kernel, decode step and sampler for a TPU v5e.

Nothing runs here: the TPU compiler installed with JAX compiles for a
described chip, so whatever Mosaic or XLA would refuse on the device (a
block shape off the tiling, a program larger than HBM) fails in this
file. Shapes are the published widths, parameters come from
``jax.eval_shape``.

The topology is described only inside the fixture below. Describing it
while a module is imported would load the TPU library in every pytest
worker, and only one process may hold it.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention.kernel import paged_attention_fwd
from repro.models import model_zoo as zoo
from repro.serving import paged_model
from repro.serving.sampling import sample_tokens

#: the pool and batch that `repro.launch.serve.make_engine` builds by default
BATCH, PAGE, NUM_PAGES, MAX_PAGES = 8, 16, 512 + 1, 512 // 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of one that may be configured
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "stablelm-3b"])
def test_paged_attention_kernel_compiles(one_chip, arch):
    """GQA group 2 (internlm2-1.8b) and MHA, group 1 (stablelm-3b)."""
    cfg = get_config(arch)
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pages = jax.ShapeDtypeStruct((hkv, NUM_PAGES, PAGE, d), jnp.bfloat16)
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((BATCH, hq, d), jnp.bfloat16), pages, pages,
        jax.ShapeDtypeStruct((BATCH, MAX_PAGES), jnp.int32),
        jax.ShapeDtypeStruct((BATCH,), jnp.int32)))
    fwd = jax.jit(functools.partial(paged_attention_fwd, interpret=False))
    compiled = fwd.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _decode_step_args(one_chip, model, batch=BATCH, num_pages=NUM_PAGES,
                      max_pages=MAX_PAGES):
    params = jax.eval_shape(functools.partial(zoo.init_params, model),
                            jax.random.key(0))
    pages = jax.eval_shape(functools.partial(
        paged_model.init_pages, model, num_pages, PAGE, batch, max_pages))
    tokens = jax.ShapeDtypeStruct((batch,), jnp.int32)
    return _on(one_chip, (params, pages, tokens))


@pytest.mark.parametrize("attn_path", ["gather", "pallas"])
def test_paged_decode_step_compiles_full_width(one_chip, monkeypatch,
                                               attn_path):
    """internlm2-1.8b at its published widths, one decode step of the
    serving engine over a 513-page pool, fits one v5e."""
    from repro.kernels.paged_attention import ops as paged_ops
    # jax.default_backend() is the CPU here; compile the kernel as the
    # chip would instead of in interpret mode
    monkeypatch.setattr(paged_ops, "interpret_mode", lambda: False)
    model = zoo.build(get_config("internlm2-1.8b"))
    params, pages, tokens = _decode_step_args(one_chip, model)
    compiled = paged_model.paged_decode_step.lower(
        model, params, pages, tokens, attn_path).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (
        attn_path == "pallas")
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used


def test_gather_decode_step_keeps_kv_window_bf16(one_chip):
    """The gather path at the offline benchmark cell's sizes (batch 32,
    1,280 pages and the trash page, 128 pages a row): no f32 array as
    large as the gathered K/V window, or that window repeated to every
    query head, appears anywhere in the compiled step."""
    batch, num_pages, max_pages = 32, 1280 + 1, 128
    cfg = get_config("internlm2-1.8b")
    model = zoo.build(cfg)
    args = _decode_step_args(one_chip, model, batch, num_pages, max_pages)
    compiled = paged_model.paged_decode_step.lower(
        model, *args, "gather").compile()
    window = batch * max_pages * PAGE * cfg.n_kv_heads * cfg.head_dim
    sizes = {window, window * cfg.n_heads // cfg.n_kv_heads}
    wide = sorted({dims for dims in re.findall(r"f32\[([\d,]+)\]",
                                               compiled.as_text())
                   if math.prod(map(int, dims.split(","))) in sizes})
    assert not wide, wide
    # 3,944,163,328 bytes with K/V repeated and widened (the same compile
    # of the step before the grouped decode attention, JAX 0.9.0)
    assert compiled.memory_analysis().temp_size_in_bytes < 3_944_163_328


def test_mla_decode_step_fits_the_moonlight_cell(one_chip):
    """Moonlight-16B-A3B's one-chip share at the offline benchmark cell's
    sizes (batch 128, 8,192 latent pages and the trash page, 128 pages a
    row): absorbed decode over the latent pages fits one v5e beside its
    float32 weights and keeps the gathered latent window bf16."""
    batch, num_pages, max_pages = 128, 8192 + 1, 128
    cfg = get_config("moonlight-16b-a3b-ep8")
    model = zoo.build(cfg)
    args = _decode_step_args(one_chip, model, batch, num_pages, max_pages)
    compiled = paged_model.paged_decode_step.lower(
        model, *args, "gather").compile()
    window = batch * max_pages * PAGE
    sizes = {window * cfg.latent_dim, window * cfg.kv_lora_rank}
    wide = sorted({dims for dims in re.findall(r"f32\[([\d,]+)\]",
                                               compiled.as_text())
                   if math.prod(map(int, dims.split(","))) in sizes})
    assert not wide, wide
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    # 13.5e9 bytes with JAX 0.9.0: 6.2e9 of f32 weights, the 1.36e9 pool
    # in, out and in the scratch, a 3.1e9 bf16 copy of the weights
    assert used < 16e9, used


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "temperature"])
@pytest.mark.parametrize("arch,batch", [
    ("internlm2-1.8b", 32), ("moonlight-16b-a3b-ep8", 128)],
    ids=["internlm2", "moonlight"])
def test_batched_sampler_compiles_at_the_cells_logits(one_chip, arch, batch,
                                                      greedy):
    """The sampler over one decode step's whole logits in each offline
    cell: internlm2's 32 rows of 92,672 (92,544 logical), moonlight's 128
    of 163,840, as the decode step returns them."""
    model = zoo.build(get_config(arch))
    params, pages, tokens = _decode_step_args(one_chip, model, batch,
                                              num_pages=65, max_pages=4)
    logits, _ = paged_model.paged_decode_step.eval_shape(
        model, params, pages, tokens, "gather")
    assert logits.shape == (batch, model.plan.vocab)
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = sample_tokens.lower(
        *_on(one_chip, (logits, key)), greedy=greedy, temperature=0.7,
        vocab_logical=model.plan.vocab_logical).compile()
    out = compiled.out_info
    assert (out.shape, out.dtype) == ((batch,), jnp.int32)
