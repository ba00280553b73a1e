"""All attention implementations agree numerically."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention_impl import (attend, blocked_attention,
                                         blocked_causal_attention,
                                         decode_attention, naive_attention)


def rand_qkv(key, b, sq, skv, h, hkv, d, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, skv, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, skv, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("s", [64, 128, 256])
def test_blocked_matches_naive(h, hkv, s):
    q, k, v = rand_qkv(jax.random.key(0), 2, s, s, h, hkv, 32)
    want = naive_attention(q, k, v, causal=True)
    got = blocked_attention(q, k, v, causal=True, block_q=64, block_kv=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("blocks", [(32, 32), (64, 32), (32, 64)])
def test_blocked_causal_matches_naive(blocks):
    bq, bk = blocks
    q, k, v = rand_qkv(jax.random.key(1), 2, 128, 128, 4, 2, 32)
    want = naive_attention(q, k, v, causal=True)
    got = blocked_causal_attention(q, k, v, block_q=bq, block_kv=bk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_non_causal_cross_attention():
    q, k, v = rand_qkv(jax.random.key(2), 2, 32, 96, 4, 4, 16)
    want = naive_attention(q, k, v, causal=False)
    got = blocked_attention(q, k, v, causal=False, block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_softcap():
    q, k, v = rand_qkv(jax.random.key(3), 1, 64, 64, 2, 2, 16)
    want = naive_attention(q, k, v, causal=True, logit_softcap=30.0)
    got = blocked_attention(q, k, v, causal=True, block_q=32, block_kv=32,
                            logit_softcap=30.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # softcap must change the result (guard against silent no-op)
    plain = naive_attention(q, k, v, causal=True)
    assert not np.allclose(np.asarray(want), np.asarray(plain))


def test_decode_matches_naive_last_row():
    """Decode with a cache == last row of full causal attention."""
    b, s, h, hkv, d = 2, 48, 4, 2, 16
    q, k, v = rand_qkv(jax.random.key(4), b, s, s, h, hkv, d)
    full = naive_attention(q, k, v, causal=True)
    cache_len = jnp.full((b,), s, jnp.int32)
    got = decode_attention(q[:, -1:], k, v, cache_len)
    np.testing.assert_allclose(np.asarray(got[:, 0]),
                               np.asarray(full[:, -1]),
                               rtol=2e-5, atol=2e-5)


def test_decode_ragged_lengths():
    b, s, h, d = 3, 32, 2, 16
    q, k, v = rand_qkv(jax.random.key(5), b, 1, s, h, h, d)
    lens = jnp.array([5, 17, 32], jnp.int32)
    got = decode_attention(q, k, v, lens)
    for i, L in enumerate([5, 17, 32]):
        want = naive_attention(q[i:i+1], k[i:i+1, :L], v[i:i+1, :L],
                               causal=False)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want[0]),
                                   rtol=2e-5, atol=2e-5)


def test_dispatch_paths():
    q, k, v = rand_qkv(jax.random.key(6), 1, 64, 64, 2, 2, 16)
    outs = [attend(q, k, v, causal=True, impl=i, block_q=32, block_kv=32)
            for i in ("naive", "blocked", "blocked_causal", "pallas")]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   rtol=2e-4, atol=2e-4)


def _decode_expanded(q, k_cache, v_cache, cache_len, *, logit_softcap=0.0):
    """The expand-and-widen formula, the grouped path's reference: K/V
    repeated to every query head, V widened to f32."""
    b, _, h, d = q.shape
    s = k_cache.shape[1]
    rep = h // k_cache.shape[2]
    kc = jnp.repeat(k_cache, rep, axis=2)
    vc = jnp.repeat(v_cache, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                        preferred_element_type=jnp.float32) * d ** -0.5
    if logit_softcap:
        scores = jnp.tanh(scores / logit_softcap) * logit_softcap
    valid = jnp.arange(s)[None, None, None, :] < cache_len[:, None, None, None]
    probs = jax.nn.softmax(jnp.where(valid, scores, -1e30), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(jnp.float32),
                     vc.astype(jnp.float32))
    return out.astype(q.dtype)


@pytest.mark.parametrize("q_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("softcap", [0.0, 30.0])
@pytest.mark.parametrize("h,hkv", [(16, 8), (8, 1), (4, 4), (12, 4)])
def test_decode_grouped_matches_expanded(h, hkv, softcap, q_dtype):
    """Grouped decode over bf16 K/V == repeat-and-widen, query head j on
    kv head j // (h // hkv), ragged lengths; q f32 or bf16."""
    b, s, d = 4, 40, 32
    q, k, v = rand_qkv(jax.random.key(7), b, 1, s, h, hkv, d)
    q = (4.0 * q).astype(q_dtype)        # scores wide enough for the cap
    k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    lens = jnp.array([1, 9, 33, 40], jnp.int32)
    got = decode_attention(q, k, v, lens, logit_softcap=softcap)
    want = _decode_expanded(q, k, v, lens, logit_softcap=softcap)
    assert got.dtype == want.dtype == q_dtype
    tol = 1e-5 if q_dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    # each query group reads its own kv head: swapping kv heads shows
    if hkv > 1:
        swapped = decode_attention(q, k[:, :, ::-1], v[:, :, ::-1], lens,
                                   logit_softcap=softcap)
        assert not np.allclose(np.asarray(swapped, np.float32),
                               np.asarray(got, np.float32))


def test_decode_grouped_keeps_kv_narrow():
    """No f32 intermediate as large as K/V repeated to every query head:
    the grouped path widens nothing of the cache's size."""
    b, s, h, hkv, d = 4, 256, 16, 8, 128
    q = jax.ShapeDtypeStruct((b, 1, h, d), jnp.float32)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16)
    lens = jax.ShapeDtypeStruct((b,), jnp.int32)

    def avals(jaxpr):                    # every equation's outputs, nested too
        for e in jaxpr.eqns:
            yield from ((str(e.primitive), v.aval) for v in e.outvars)
            for p in e.params.values():
                for sub in p if isinstance(p, (tuple, list)) else (p,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from avals(sub)

    closed = jax.make_jaxpr(decode_attention)(q, kv, kv, lens)
    seen = list(avals(closed.jaxpr))
    assert any(a.dtype == jnp.float32 and a.size == b * h * s
               for _, a in seen)                 # the scores are there
    big = [(p, a) for p, a in seen
           if a.dtype == jnp.float32 and a.size >= b * s * h * d]
    assert not big, big
