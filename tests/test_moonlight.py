"""Moonlight-16B-A3B (DeepSeek-V3's block) at smoke size on the CPU:
latent attention (MLA), the sigmoid router with its correction bias,
shared experts, the leading dense layer and the held-expert share,
against the benchmark's plain float32 reference (``chipbench/moonlight``).

The program runs in float32 here, so each comparison is of the same
arithmetic in another order: logits agree to 1e-4 (their scale is about
1), where a lost term (a head, an expert, the rope part) moves them by
0.01 or more."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import moonlight as ref
from repro.configs import get_config, get_smoke_config
from repro.configs import moonlight_16b_a3b as moonlight
from repro.core.costmodel.operators import kv_bytes_per_token
from repro.core.request import Request
from repro.models import model_zoo as zoo
from repro.models.layers import mlp_apply
from repro.models.moe import _router, moe_apply, moe_init
from repro.serving.engine import EngineConfig, ServingEngine

#: logits of the same float32 arithmetic in another order
ATOL = 1e-4
SMOKE = get_smoke_config("moonlight-16b-a3b").with_overrides(dtype="float32")


def _params(cfg, seed=0):
    """Seeded random weights with a nonzero correction bias."""
    model = zoo.build(cfg)
    params = zoo.init_params(model, jax.random.key(seed))
    bias = params["layers"]["moe"]["correction_bias"]
    params["layers"]["moe"]["correction_bias"] = 0.3 * jax.random.normal(
        jax.random.key(seed + 1), bias.shape)
    return model, params


def _ref_logits(cfg, params, tokens):
    sizes = ref.program_sizes(cfg)
    return np.asarray(jax.jit(ref.forward, static_argnums=0)(
        ref.Arch.of(sizes), params, jnp.asarray(tokens)))


def test_the_one_chip_share_keeps_every_width():
    """Only the depth and the experts held differ from the published
    model; the router keeps its 64 outputs and its 6 experts per token."""
    full, share = moonlight.CONFIG, get_config("moonlight-16b-a3b-ep8")
    assert dataclasses.replace(
        share, name=full.name, num_layers=full.num_layers,
        moe=dataclasses.replace(share.moe, n_held=0)) == full
    assert (share.num_layers, share.moe.held, share.moe.num_experts,
            share.moe.top_k) == (9, 8, 64, 6)
    assert full.param_count() == pytest.approx(15.96e9, rel=1e-3)
    assert full.active_param_count() == pytest.approx(2.91e9, rel=1e-2)


def test_the_simulator_sizes_match_the_benchmark_counts():
    """``kv_bytes_per_token`` counts the latent and rope key, 1,152 bytes
    a token and layer, and ``param_count`` MLA, the dense layer, the
    shared experts and the held experts, as the benchmark's module does
    from the configuration file at the one-chip share."""
    cfg = get_config("moonlight-16b-a3b-ep8")
    sizes = ref.program_sizes(cfg)
    assert kv_bytes_per_token(cfg) == ref.kv_bytes_per_token(sizes) \
        == 9 * 1152
    assert cfg.param_count() == ref.param_count(sizes) == 1_557_310_464
    model = zoo.build(SMOKE)
    assert SMOKE.param_count() == zoo.param_count(zoo.param_specs(model)) \
        - (model.plan.vocab - SMOKE.vocab_size) * SMOKE.d_model * 2


def test_forward_equals_the_reference():
    model, params = _params(SMOKE)
    tokens = jax.random.randint(jax.random.key(3), (1, 24), 0,
                                SMOKE.vocab_size)
    logits, _ = zoo.forward(model, params, {"tokens": tokens})
    want = _ref_logits(SMOKE, params, tokens[0])
    got = np.asarray(logits[0, :, :SMOKE.vocab_size])
    assert np.abs(want).max() > 100 * ATOL
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_absorbed_decode_equals_decompressed_attention():
    """One query against the latent cache, through ``wkv_b``'s halves,
    gives what the full sequence's attention gives at its last place."""
    model, params = _params(SMOKE)
    p = jax.tree.map(lambda a: a[0], params["layers"])["attn"]
    x = jax.random.normal(jax.random.key(4), (2, 11, SMOKE.d_model))
    positions = jnp.arange(11)
    full = zoo._mla_full(p, x, model, positions, causal=True)
    latent = zoo._mla_latent(p, x, model, positions)
    got = zoo.mla_decode_attention(p, x[:, -1:], model, latent,
                                   jnp.full((2,), 11))
    np.testing.assert_allclose(np.asarray(got[:, 0]),
                               np.asarray(full[:, -1]), atol=1e-5, rtol=0)


def test_prefill_then_paged_decode_agree_with_the_reference():
    """Requests served by ``ServingEngine`` (prefill, then decode over
    the latent pages; rows join and leave the batch): the logits of every
    emitted token equal the reference's full forward over the prompt
    and the tokens before it."""
    model, params = _params(SMOKE)
    engine = ServingEngine(model, params, EngineConfig(
        num_blocks=48, block_size=4, max_batch=3, max_pages_per_seq=12))
    seen = []                            # each sampling call's rows
    sample = engine._sample

    class Emitted(list):
        """The tokens of one request, each with the logits it came from:
        the rows of a call go, in order, to the tokens appended after
        it (the padded rows of a decode step to none)."""
        logits: list

        def append(self, tok):
            self.logits.append(seen[-1].pop(0))
            super().append(tok)

    def record(logits):
        seen.append(list(np.asarray(logits[:, :SMOKE.vocab_size])))
        return sample(logits)

    engine._sample = record
    rng = np.random.default_rng(0)
    prompts = {}
    for i, (plen, out) in enumerate([(5, 9), (17, 4), (1, 12), (9, 7)]):
        prompts[i] = rng.integers(0, SMOKE.vocab_size, plen).astype(np.int32)
        engine.add_request(Request(id=i, arrival_time=0.0, prompt_len=plen,
                                   output_len=out), prompts[i])
        engine.tokens_by_req[i] = Emitted()
        engine.tokens_by_req[i].logits = []
    engine.run()
    for i, prompt in prompts.items():
        served = engine.tokens_by_req[i]
        seq = np.concatenate([prompt, served[:-1]])
        want = _ref_logits(SMOKE, params, seq)[len(prompt) - 1:]
        np.testing.assert_allclose(np.stack(served.logits), want,
                                   atol=ATOL, rtol=0)


def _moe_params(n_held=0, held_start=0):
    """One layer's experts (8 routed, top-2 over all 8, one shared),
    seeded; a share holds a slice of the same experts."""
    p = moe_init(jax.random.key(7), 16, 8, 12, "silu", jnp.float32,
                 n_shared=1, scoring="sigmoid")
    p["correction_bias"] = 0.05 * jax.random.normal(jax.random.key(8), (8,))
    if n_held:
        p = {k: v[held_start:held_start + n_held]
             if k in ("gate", "up", "down") else v for k, v in p.items()}
    return p


ROUTER = dict(top_k=2, n_experts_logical=8, compute_dtype=jnp.float32,
              scoring="sigmoid", routed_scale=2.446)


@pytest.mark.parametrize("impl", ["dense_onehot", "sort"])
def test_held_expert_shares_add_up_to_the_uncut_layer(impl):
    """Four shares of two experts each: their outputs summed, with the
    shared expert (which every share computes) counted once, give the
    layer that holds all eight."""
    x = jax.random.normal(jax.random.key(9), (40, 16))
    whole, _ = moe_apply(_moe_params(), x, impl=impl, **ROUTER)
    parts = [moe_apply(_moe_params(2, s), x, impl=impl, held_start=s,
                       **ROUTER)[0] for s in range(0, 8, 2)]
    p = _moe_params()
    shared = mlp_apply(p["shared"], x, "silu", jnp.float32)
    total = sum(parts) - (len(parts) - 1) * shared
    assert all(float(jnp.abs(y - shared).max()) > 1e-2 for y in parts)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5, rtol=0)


def test_correction_bias_chooses_but_does_not_weigh():
    p = _moe_params()
    x = jax.random.normal(jax.random.key(10), (64, 16))
    args = (2, 8, jnp.float32, "sigmoid", 2.446)
    w0, ids0, _ = _router(dict(p, correction_bias=jnp.zeros(8)), x, *args)
    w, ids, _ = _router(p, x, *args)
    assert (np.asarray(ids) != np.asarray(ids0)).any()
    scores = jax.nn.sigmoid(x @ p["router"])
    picked = jnp.take_along_axis(scores, ids, -1)
    want = 2.446 * picked / picked.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w), np.asarray(want), rtol=1e-6)
    # a bias that forces expert 3 in: chosen everywhere, weighed by its
    # unbiased score
    w3, ids3, _ = _router(dict(p, correction_bias=jnp.eye(8)[3] * 10.0), x,
                          *args)
    assert (np.asarray(ids3) == 3).any(-1).all()
    picked = jnp.take_along_axis(scores, ids3, -1)
    np.testing.assert_allclose(
        np.asarray(w3), np.asarray(2.446 * picked / picked.sum(
            -1, keepdims=True)), rtol=1e-6)


def test_the_pallas_path_refuses_mla():
    model, params = _params(SMOKE)
    with pytest.raises(ValueError, match="MLA"):
        ServingEngine(model, params, EngineConfig(attn_path="pallas"))
