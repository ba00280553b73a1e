"""Batched sampling: one program over a step's (B, V) logits.

The batched greedy sampler against a loop of per-row argmaxes, with a
padded vocabulary and planted ties; temperature sampling's range and
repeatability; the engine's tokens on the paged and the slot paths
against the same loop over the same logits; and one sampling call a
decode step, whatever the batch."""
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.request import Request
from repro.models import model_zoo as zoo
from repro.serving import engine as engine_mod
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.sampling import sample_tokens


def per_row_argmax(logits, vocab_logical):
    """The reference: one row at a time, the logical columns only."""
    logits = np.asarray(logits, np.float32)
    return np.array([int(np.argmax(row[:vocab_logical])) for row in logits],
                    np.int32)


@pytest.mark.parametrize("rows,width,vocab_logical,ties", [
    (1, 512, 512, False),
    (7, 512, 384, False),
    (32, 1024, 1000, True),
    (128, 640, 600, True),
], ids=["one-row", "padded", "padded-ties", "wide-batch-ties"])
def test_greedy_equals_per_row_argmax(rows, width, vocab_logical, ties):
    rng = np.random.default_rng(rows * width)
    logits = rng.standard_normal((rows, width)).astype(np.float32)
    # the padded columns would win if they were not masked
    logits[:, vocab_logical:] = 100.0
    if ties:
        # each row's maximum planted at two or three columns: the first wins
        for i in range(rows):
            cols = np.sort(rng.choice(vocab_logical, 2 + i % 2,
                                      replace=False))
            logits[i, cols] = 50.0
    got = np.asarray(sample_tokens(jax.numpy.asarray(logits),
                                   jax.random.key(0), greedy=True,
                                   vocab_logical=vocab_logical))
    assert got.shape == (rows,) and got.dtype == np.int32
    np.testing.assert_array_equal(got, per_row_argmax(logits, vocab_logical))


@pytest.mark.parametrize("temperature", [0.5, 1.0, 4.0])
def test_temperature_stays_in_vocab_and_repeats_for_a_key(temperature):
    rows, width, vocab_logical = 64, 512, 300
    logits = jax.random.normal(jax.random.key(1), (rows, width))
    # the padded columns are the likeliest unless masked
    logits = logits.at[:, vocab_logical:].set(20.0)

    def draw(seed):
        return np.asarray(sample_tokens(
            logits, jax.random.key(seed), greedy=False,
            temperature=temperature, vocab_logical=vocab_logical))

    a = draw(3)
    assert a.shape == (rows,) and a.dtype == np.int32
    assert ((0 <= a) & (a < vocab_logical)).all()
    np.testing.assert_array_equal(a, draw(3))
    # the rows of one key are drawn apart, not as one repeated token
    assert len(set(a.tolist())) > 1


def _engine(arch, max_batch=4, **ec_kw):
    model = zoo.build(get_smoke_config(arch))
    params = zoo.init_params(model, jax.random.key(0))
    return ServingEngine(model, params, EngineConfig(
        num_blocks=64, block_size=8, max_batch=max_batch,
        max_pages_per_seq=8, **ec_kw))


def _serve(eng, n, first=0):
    for i in range(first, first + n):
        eng.add_request(Request(id=i, arrival_time=0.0,
                                prompt_len=9 + i % 3, output_len=5))
    eng.run()


# internlm2's smoke model runs the paged path, whisper's the slot path;
# both pad the vocabulary (384 logical of 512)
PATHS = [("internlm2-1.8b", True), ("whisper-base", False)]


@pytest.mark.parametrize("arch,paged", PATHS, ids=["paged", "slot"])
def test_engine_greedy_tokens_equal_a_per_row_loop(arch, paged,
                                                   monkeypatch):
    """Two engines on the same model: one samples with the batched
    program, the other with a per-row argmax loop over the logits it is
    given. They emit the same tokens."""
    batched = _engine(arch)
    assert batched.paged == paged
    _serve(batched, 6)

    looped = _engine(arch)
    vocab = looped.model.plan.vocab_logical
    monkeypatch.setattr(looped, "_sample",
                        lambda logits: per_row_argmax(logits, vocab))
    _serve(looped, 6)
    assert batched.tokens_by_req == looped.tokens_by_req
    assert all(len(t) == 5 for t in batched.tokens_by_req.values())


@pytest.mark.parametrize("arch,paged", PATHS, ids=["paged", "slot"])
def test_engine_under_a_temperature_repeats_for_a_seed(arch, paged):
    """Under a temperature every token lies in the logical vocabulary,
    and an engine with the same seed serves the same tokens."""
    def served(seed):
        eng = _engine(arch, greedy=False, temperature=1.5, seed=seed)
        assert eng.paged == paged
        _serve(eng, 5)
        return eng.tokens_by_req, eng.model.plan.vocab_logical

    tokens, vocab = served(11)
    assert all(0 <= t < vocab for ts in tokens.values() for t in ts)
    assert served(11)[0] == tokens


@pytest.mark.parametrize("arch,paged", PATHS, ids=["paged", "slot"])
def test_each_row_token_goes_to_its_request(arch, paged):
    """Requests served together, finishing at different steps so that
    rows and slots are reused out of order, get the tokens each gets
    when served alone: every row of the batch's tokens reaches its own
    request."""
    def requests():
        return [Request(id=i, arrival_time=0.0, prompt_len=9 + i % 3,
                        output_len=3 + 5 * i % 7) for i in range(7)]

    together = _engine(arch)
    for r in requests():
        together.add_request(r)
    together.run()
    alone = _engine(arch)
    for r in requests():
        alone.add_request(r)
        alone.run()
    assert together.paged == alone.paged == paged
    assert together.tokens_by_req == alone.tokens_by_req


@pytest.mark.parametrize("n", [1, 3, 4, 7])
@pytest.mark.parametrize("arch,paged", PATHS, ids=["paged", "slot"])
def test_one_sampling_call_per_decode_step(arch, paged, n, monkeypatch):
    """Every decode step samples its whole batch in one call, and every
    prefill its one row, whatever the number of requests."""
    calls = []

    def counted(logits, key, **kw):
        calls.append(logits.shape)
        return sample_tokens(logits, key, **kw)

    monkeypatch.setattr(engine_mod, "sample_tokens", counted)
    eng = _engine(arch)
    _serve(eng, n)
    width = eng.model.plan.vocab
    decodes = sum(r.kind == "decode" for r in eng.records)
    prefills = sum(len(r.batch_ids) for r in eng.records
                   if r.kind == "prefill")
    assert decodes >= 4 and prefills == n
    assert sorted(calls) == sorted([(1, width)] * prefills
                                   + [(eng.ec.max_batch, width)] * decodes)


def test_sample_spans_carry_the_rows_they_served():
    """In a trace, each ``engine.sample`` span holds one sampling call and
    names how many rows it served: a decode step's batch, a prefill's
    one row."""
    eng = _engine("internlm2-1.8b")
    _serve(eng, 3)                       # compiles outside the trace
    done = len(eng.records)
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            _serve(eng, 3, first=3)
        finally:
            jax.profiler.stop_trace()
        found = sorted(Path(d).rglob("*.xplane.pb"))
        profile = jax.profiler.ProfileData.from_file(str(found[-1]))
        rows = [dict(e.stats).get("rows")
                for p in profile.planes if p.name.startswith("/host:")
                for line in p.lines
                for e in sorted(line.events, key=lambda e: e.start_ns)
                if e.name == engine_mod.SAMPLE]
    want = []
    for rec in eng.records[done:]:
        want += [1] * len(rec.batch_ids) if rec.kind == "prefill" \
            else [len(rec.batch_ids)]
    assert rows == want
