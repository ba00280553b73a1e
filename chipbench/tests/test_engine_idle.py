"""The engine's host spans, and device idle split by the innermost span:
the spans of a smoke-size engine traced on the CPU, a synthetic trace
with known answers, and engine steps of the offline cell recorded on a
TPU v5e."""
import tempfile
from pathlib import Path

import jax
import pytest

from repro.configs import get_smoke_config
from repro.core.request import Request
from repro.models import model_zoo as zoo
from repro.serving import engine as engine_mod
from repro.serving.engine import EngineConfig, ServingEngine

from chipbench import devtrace, engine_idle
from chipbench.tests.test_devtrace import ev, synthetic

PLAN, PREPARE, SAMPLE, EMIT = engine_idle.ENGINE[1:]


def test_the_engine_names_the_spans_the_reduction_knows():
    assert engine_mod.SPANS == engine_idle.ENGINE
    assert not set(engine_idle.ENGINE) & set(devtrace.SPANS)
    spans = {n for names in engine_idle.PER_STEP.values() for n in names}
    assert spans == set(engine_idle.ENGINE) - {engine_idle.ITERATION}


def _traced_smoke_run():
    """A smoke engine, warmed, then 3 prompts served to the end under the
    profiler: one prefill step of 3 requests and 3 decode steps. Returns
    the engine's host events (start, end, name, step_num) and the
    records of the traced steps."""
    model = zoo.build(get_smoke_config("internlm2-1.8b"))
    params = zoo.init_params(model, jax.random.key(0))
    eng = ServingEngine(model, params, EngineConfig(
        num_blocks=64, block_size=8, max_batch=4, max_pages_per_seq=8))

    def serve(first):
        for i in range(first, first + 3):
            eng.add_request(Request(id=i, arrival_time=0.0,
                                    prompt_len=9 + i % 3, output_len=4))
        eng.run()

    serve(0)                             # compiles outside the trace
    done = len(eng.records)
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            serve(3)
        finally:
            jax.profiler.stop_trace()
        found = sorted(Path(d).rglob("*.xplane.pb"))
        profile = jax.profiler.ProfileData.from_file(str(found[-1]))
        events = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                   dict(e.stats).get("step_num"))
                  for p in profile.planes if p.name.startswith("/host:")
                  for line in p.lines for e in line.events
                  if e.name in engine_idle.ENGINE]
    return sorted(events), eng.records[done:], done


def test_each_step_is_one_iteration_with_its_spans_in_order():
    events, records, done = _traced_smoke_run()
    assert [r.kind for r in records] == ["prefill"] + ["decode"] * 3
    iterations = [e for e in events if e[2] == engine_idle.ITERATION]
    assert len(iterations) == len(records)
    # XProf groups a step's work by its number: the index of its record
    assert [e[3] for e in iterations] == list(
        range(done, done + len(records)))
    children = [e for e in events if e[2] != engine_idle.ITERATION]
    for (a, b, _, _), rec in zip(iterations, records):
        inside = [e for e in children if a <= e[0] and e[1] <= b]
        rows = len(rec.batch_ids) if rec.kind == "prefill" else 1
        assert [e[2] for e in inside] == \
            [PLAN] + [PREPARE, SAMPLE] * rows + [EMIT]
        assert all(x[1] <= y[0] for x, y in zip(inside, inside[1:]))
    # every child lies inside an iteration
    assert sum(len([e for e in children if a <= e[0] and e[1] <= b])
               for a, b, _, _ in iterations) == len(children)


def synthetic_engine():
    """``test_devtrace.synthetic`` with the engine's spans inside its
    ``engine.step`` (0-600): busy 0-150, 300-500, 900-1000 of the window
    0-1200; one iteration at 10-590, one that starts before the window
    and one that runs past its end."""
    profile = synthetic()
    host = next(p for p in profile.planes if p.name == "/host:CPU")
    host.lines[0].events += [
        ev("engine.iteration", -100, 105),
        ev("engine.iteration", 10, 580),
        ev(PLAN, 140, 30), ev(PREPARE, 170, 30), ev(SAMPLE, 200, 320),
        ev(EMIT, 540, 40),
        ev("engine.iteration", 1150, 150)]
    return profile


def test_synthetic_idle_by_innermost_span():
    profile = synthetic_engine()
    e = engine_idle.reduce(profile)
    assert e.iterations == 2
    assert e.window_s == pytest.approx(1200e-9)
    assert e.busy_s == pytest.approx(450e-9)
    # plan 150-170; prepare 170-200; sample 200-300 and 500-520; emit
    # 540-580; the iteration's own glue 520-540, 580-590 and 1150-1200;
    # engine.step's own 590-600; the rest as devtrace has it
    want = {PLAN: 20, PREPARE: 30, SAMPLE: 120, EMIT: 40,
            engine_idle.ITERATION: 80, "engine.step": 10,
            "client.wait": 250, "client.admit": 40, devtrace.NO_SPAN: 160}
    assert e.idle_by_engine_span == pytest.approx(
        {k: v * 1e-9 for k, v in want.items()})
    assert sum(e.idle_by_engine_span.values()) == pytest.approx(
        e.window_s - e.busy_s)
    assert e.figures() == pytest.approx({
        "sched_idle_ms_per_step": 30e-6, "prep_idle_ms_per_step": 15e-6,
        "sample_idle_ms_per_step": 60e-6})
    # the harness's split is untouched by the engine's spans
    idle = devtrace.reduce(profile).idle_by_span
    assert idle == pytest.approx({"engine.step": 250e-9,
                                  "client.wait": 250e-9,
                                  "client.admit": 40e-9,
                                  devtrace.NO_SPAN: 210e-9})


def test_a_trace_without_the_engines_spans_reads_none():
    e = engine_idle.reduce(synthetic())
    assert e.iterations == 0
    assert e.figures() == dict.fromkeys(engine_idle.PER_STEP)
    assert e.idle_by_engine_span == pytest.approx(
        devtrace.reduce(synthetic()).idle_by_span)


@pytest.mark.parametrize("spans,want", [
    # nested: the child cuts its parent in two
    ([(0, 10, "a"), (2, 5, "b")], [(0, 2, "a"), (2, 5, "b"), (5, 10, "a")]),
    # started together: the shorter is inside
    ([(0, 10, "a"), (0, 4, "b")], [(0, 4, "b"), (4, 10, "a")]),
    # overlapping, not nested: the later start, while it lasts
    ([(0, 6, "a"), (4, 10, "b")], [(0, 4, "a"), (4, 6, "b"), (6, 10, "b")]),
    # clipped to the window, a gap under no span left out
    ([(-5, 3, "a"), (6, 20, "b")], [(0, 3, "a"), (6, 12, "b")]),
])
def test_innermost(spans, want):
    assert engine_idle.innermost(spans, 0, 12) == want


#: the offline cell's engine at batch 32 (seed 2147480013), traced on one
#: TPU v5e: a prefill step of one request, then a decode step of 32 rows,
#: each under ``engine.step`` and followed by a 20-ms sleep under
#: ``client.wait`` and a 5-ms one under no span
RECORDED = (Path(__file__).parent / "data" /
            "v5e_offline_engine_spans.xplane.pb.gz")


def _sweep_idle(profile, lo, hi):
    """Idle nanoseconds in [lo, hi) by the innermost span over them, by a
    sweep over every interval end with a stack of open spans: a second
    way to take the split."""
    names = set(devtrace.SPANS) | set(engine_idle.ENGINE)
    marks = []
    for p in profile.planes:
        if devtrace.DEVICE.match(p.name):
            ops = {ln.name: ln for ln in p.lines}[devtrace.OPS].events
            for e in ops:
                marks += [(e.start_ns, 1, 0, "busy"),
                          (e.start_ns + e.duration_ns, 0, 0, "busy")]
        elif p.name.startswith("/host:"):
            for ln in p.lines:
                for e in ln.events:
                    if e.name in names:
                        end = e.start_ns + e.duration_ns
                        # at one time: ends first, then the longer
                        # span opens before the shorter
                        marks += [(e.start_ns, 1, -end, e.name),
                                  (end, 0, 0, e.name)]
    out, busy, stack, t = {}, 0, [], lo
    for at, opens, _, name in sorted(marks + [(hi, 0, 0, "end")]):
        a, b = max(t, lo), min(at, hi)
        if busy == 0 and b > a:
            key = stack[-1] if stack else devtrace.NO_SPAN
            out[key] = out.get(key, 0.0) + b - a
        t = at
        if name == "busy":
            busy += 1 if opens else -1
        elif name == "end":
            break
        elif opens:
            stack.append(name)
        else:
            stack.remove(name)
    return out


def test_recorded_v5e_engine_spans():
    profile = engine_idle.load(RECORDED)
    e = engine_idle.reduce(profile)
    r = devtrace.reduce(profile)
    assert e.window_s == pytest.approx(r.window_s)
    assert e.busy_s == pytest.approx(r.busy_s)
    assert e.iterations == 2

    by_sweep = _sweep_idle(profile, 0.0, e.window_s * 1e9)
    assert e.idle_by_engine_span == pytest.approx(
        {k: v / 1e9 for k, v in by_sweep.items()}, rel=1e-9)
    for name, spans in engine_idle.PER_STEP.items():
        want = sum(by_sweep.get(n, 0.0) for n in spans) / 1e6 / 2
        assert e.figures()[name] == pytest.approx(want, rel=1e-9)
    assert sum(e.idle_by_engine_span.values()) == pytest.approx(
        e.window_s - e.busy_s)
    # the engine's spans and the harness's own stretch of engine.step
    # make up the idle the harness puts under engine.step
    inside = sum(e.idle_by_engine_span.get(n, 0.0)
                 for n in engine_idle.ENGINE + ("engine.step",))
    assert inside == pytest.approx(r.idle_by_span["engine.step"])
    # the decode step's 31 rows after the first: most of the step's idle
    assert e.figures()["sample_idle_ms_per_step"] > 10 * max(
        e.figures()["prep_idle_ms_per_step"],
        e.figures()["sched_idle_ms_per_step"])
    assert e.idle_by_engine_span[PREPARE] > 0
