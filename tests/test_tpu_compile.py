"""Compile the serving path's kernel and decode step for a TPU v5e.

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
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention.kernel import paged_attention_fwd
from repro.models import model_zoo as zoo
from repro.serving import paged_model

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


def _decode_step_args(one_chip, model):
    params = jax.eval_shape(functools.partial(zoo.init_params, model),
                            jax.random.key(0))
    pages = jax.eval_shape(functools.partial(
        paged_model.init_pages, model, NUM_PAGES, PAGE, BATCH, MAX_PAGES))
    tokens = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
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
