"""Window arithmetic on a synthetic timeline, the CLI's refusal without a
chip, and cells found by name. CPU only; no chip is described."""
import json
import os
import subprocess
import sys
from collections import deque
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import client, devtrace, run, traffic, work

ROOT = Path(__file__).resolve().parents[2]


class Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += max(0.0, dt)


class FakeEngine:
    """A continuous-batching engine on a fake clock: a step admits what
    fits and prefills it (one token each), else decodes every running
    request (one token each). Each step takes ``step_s``; the first step
    to start at or after ``stall_at`` takes ``stall_s`` more."""

    def __init__(self, clock, *, max_batch=8, step_s=0.02, stall_at=None,
                 stall_s=0.0):
        self.ec = SimpleNamespace(max_batch=max_batch)
        self.clock, self.step_s = clock, step_s
        self.stall_at, self.stall_s = stall_at, stall_s
        self.waiting, self.running = deque(), []
        self.tokens_by_req, self.prompt_tokens = {}, {}

    @property
    def has_work(self):
        return bool(self.waiting or self.running)

    def add_request(self, req, prompt):
        self.prompt_tokens[req.id] = prompt
        self.tokens_by_req[req.id] = []
        self.waiting.append(req)

    def step(self):
        dt = self.step_s
        if self.stall_at is not None and self.clock.t >= self.stall_at:
            dt, self.stall_at = dt + self.stall_s, None
        self.clock.t += dt
        admitted = []
        while self.waiting and len(self.running) < self.ec.max_batch:
            admitted.append(self.waiting.popleft())
            self.running.append(admitted[-1])
        batch = admitted or list(self.running)
        for r in batch:
            r.tokens_generated += 1
            self.tokens_by_req[r.id].append(7)
            if r.finished:
                self.running.remove(r)
        return SimpleNamespace(batch_ids=tuple(r.id for r in batch),
                               kind="prefill" if admitted else "decode",
                               wall=0.9 * dt)


def mix(**kw):
    base = dict(name="t", loop="poisson", ramp_s=1.0, drain_s=5.0,
                strata=16, prompt=traffic.Lengths(20, 0.5, 4, 64),
                output=traffic.Lengths(10, 0.5, 2, 32), reference_tokens=64,
                rate=20.0)
    base.update(kw)
    return traffic.Traffic(**base)


def serve(t, seconds=4.0, seed=3, **engine_kw):
    clock = Clock()
    eng = FakeEngine(clock, **engine_kw)
    d = traffic.draw(t, seed, traffic.count_for(t, seconds, 8), 100)
    tl = client.run(eng, t, d, seconds, clock=clock, sleep=clock.sleep)
    return tl


def test_a_stall_moves_every_end_to_end_metric():
    t = mix()
    calm = client.end_to_end(serve(t))
    stalled = client.end_to_end(serve(t, stall_at=1000.0 + 3.0,
                                      stall_s=1.0))
    assert stalled["ttft_p90_ms"] > calm["ttft_p90_ms"] + 100
    assert stalled["tpot_p90_ms"] > calm["tpot_p90_ms"]
    assert stalled["output_tok_s"] < calm["output_tok_s"]
    sat = mix(loop="saturated", rate=0.0, drain_s=0.0)
    calm = client.end_to_end(serve(sat))
    stalled = client.end_to_end(serve(sat, stall_at=1000.0 + 2.0,
                                      stall_s=1.0))
    assert stalled["output_tok_s"] < 0.85 * calm["output_tok_s"]


def test_window_sample_and_rates_by_hand():
    tl = client.Timeline(sent=[], steps=[], open=10.0, close=12.0)

    def sent(i, due, stamps):
        req = SimpleNamespace(id=i, finished=True, preempt_count=0)
        tl.sent.append(client.Sent(req=req, due=due, sent=due,
                                   stamps=stamps))

    sent(0, 9.0, [10.5, 10.6])            # due in the ramp: not sampled
    sent(1, 10.0, [10.1, 10.3, 10.5])     # ttft 100 ms, tpot 200 ms
    sent(2, 11.0, [11.4, 11.5, 12.5])     # ttft 400 ms, tpot 550 ms
    # the steps that emitted those tokens: (start, end, tokens)
    for a, b, n in [(9.9, 10.1, 1), (10.1, 10.3, 1), (10.3, 10.5, 2),
                    (10.5, 10.6, 1), (11.3, 11.4, 1), (11.4, 11.5, 1),
                    (11.9, 12.5, 1)]:
        tl.steps.append(client.Step(start=a, end=b, wall=b - a,
                                    kind="decode", contexts=(), prompts=(),
                                    tokens=n))
    e = client.end_to_end(tl)
    assert [s.req.id for s in tl.sample()] == [1, 2]
    # the first step is half inside the window, the last a sixth
    assert e["output_tok_s"] == pytest.approx((0.5 + 6 + 1 / 6) / 2.0)
    assert e["ttft_p50_ms"] == pytest.approx(250.0)
    assert e["ttft_p90_ms"] == pytest.approx(370.0)
    assert e["tpot_p90_ms"] == pytest.approx(515.0)


def test_requests_unfinished_at_the_drain_limit_fail():
    t = mix(drain_s=0.2, output=traffic.Lengths(30, 0.1, 25, 32))
    tl = serve(t, seconds=2.0, step_s=0.05, max_batch=2)
    c = client.counts(tl)
    unfinished = [s for s in tl.sample() if not s.req.finished]
    assert c["attempted"] == len(tl.sample()) > 0
    assert c["failed"] == len(unfinished) > 0
    # with time to drain, none fails
    ok = client.counts(serve(mix(drain_s=60.0), seconds=2.0))
    assert ok["failed"] == 0 and ok["attempted"] > 0


def test_same_seed_same_work_other_seed_same_lengths():
    t = mix()
    a, b = traffic.draw(t, 5, 64, 100), traffic.draw(t, 5, 64, 100)
    assert (a.prompt_lens == b.prompt_lens).all() and (a.due == b.due).all()
    assert all((x == y).all() for x, y in zip(a.prompts, b.prompts))
    c = traffic.draw(t, 2 ** 31 + 11, 64, 100)
    # stratified: another seed draws the same slices in another order
    assert abs(int(c.prompt_lens.sum()) - int(a.prompt_lens.sum())) \
        < 0.1 * a.prompt_lens.sum()
    assert list(a.prompt_lens) != list(c.prompt_lens)


def test_cli_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "internlm2-1.8b.offline", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "needs 1 TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def copy_benchmark(root):
    """The benchmark's configurations, with the modules they name, in a
    checkout at ``root``; returns ``BENCHMARK.json`` as a dict."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "chipbench" / "traffic").mkdir(parents=True)
    (root / "chipbench" / "configs").mkdir()
    for c in bench["configs"]:
        (root / c["file"]).write_text((ROOT / c["file"]).read_text())
        module = json.loads((ROOT / c["file"]).read_text())["reference"]
        (root / module).write_text((ROOT / module).read_text())
    return bench


def test_a_new_cell_is_found_by_name(tmp_path):
    """A later cell adds files and entries only: a traffic mix, and a
    workload that names it."""
    bench = copy_benchmark(tmp_path)
    new = {"loop": "saturated", "ramp_s": 3, "drain_s": 0, "strata": 32,
           "prompt": {"median": 1500, "sigma": 0.5, "min": 256,
                      "max": 4096},
           "output": {"median": 300, "sigma": 0.5, "min": 1, "max": 1024},
           "reference_tokens": 512}
    (tmp_path / "chipbench" / "traffic" / "long_new.json").write_text(
        json.dumps(new))
    name = "internlm2-1.8b.long_new"
    bench["workloads"].append({"name": name, "config": "internlm2-1.8b",
                               "traffic": "long_new", "chips": 1,
                               "why": "test"})
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "output_tok_s")
    rate["workloads"].append(name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = run.load_cell(name, root=tmp_path)
    assert cell.traffic.loop == "saturated"
    assert cell.traffic.name == "long_new"
    assert cell.traffic.prompt.median == 1500
    assert cell.sizes["arch"] == "internlm2-1.8b"
    assert {m["name"] for m in cell.end_to_end} == {"output_tok_s",
                                                    "setup_s"}
    # per-layer metrics that list their cells do not reach a new one
    assert cell.per_layer == []


#: the module of a configuration of another architecture: its sizes, gaps
#: and counts are fixed numbers, so a reading shows whose they are
STUB_ARCH = '''"""A stand-in architecture."""
import numpy as np


def program_sizes(cfg):
    return {"hidden_size": cfg.width, "stub_kind": "latent"}


def gaps(sizes, params, prompt, served, *, control=False):
    out = {"served": np.full(len(served), sizes["stub_gap"])}
    if control:
        out["control"] = np.full(len(served), 2 * sizes["stub_gap"])
    return out


def decode_work(sizes, contexts):
    return 1e9 * len(contexts), 4e6 * sum(contexts)


def prefill_work(sizes, prompt_len):
    return 3e9 * prompt_len, 1e6
'''
STUB_SIZES = dict(arch="stub-arch", reference="chipbench/stub_arch.py",
                  hidden_size=7, stub_kind="latent", vocab_size=100,
                  stub_gap=0.125, limits={"logit_gap": 0.2})


def add_configuration(root, module=STUB_ARCH, sizes=STUB_SIZES):
    """What a later PR adds for a new architecture: its configuration
    file, the module the file names, and entries in ``BENCHMARK.json``
    (a cell on the offline mix, listed by every metric that lists its
    cells). Returns the cell's name."""
    bench = copy_benchmark(root)
    traffic_file = "chipbench/traffic/offline.json"
    (root / traffic_file).write_text((ROOT / traffic_file).read_text())
    (root / "chipbench" / "stub_arch.py").write_text(module)
    file = "chipbench/configs/stub-arch.json"
    (root / file).write_text(json.dumps(sizes))
    bench["configs"].append({"name": "stub-arch", "source": "test",
                             "file": file, "reduced": [], "why": "test"})
    name = "stub-arch.offline"
    bench["workloads"].append({"name": name, "config": "stub-arch",
                               "traffic": "offline", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


def test_a_new_configuration_brings_its_own_module(tmp_path):
    """A configuration of another architecture adds files only; the size
    check, the output check and both roofline readers reach its module
    through the cell, with no file of ``chipbench/`` edited."""
    name = add_configuration(tmp_path)
    cell = run.load_cell(name, root=tmp_path)
    assert Path(cell.reference.__file__) == \
        (tmp_path / "chipbench" / "stub_arch.py").resolve()
    run.check_arch(SimpleNamespace(name="stub", width=7), cell)
    with pytest.raises(SystemExit, match="hidden_size"):
        run.check_arch(SimpleNamespace(name="stub", width=8), cell)

    clock = Clock()
    eng = FakeEngine(clock)
    t = mix(drain_s=60.0)
    d = traffic.draw(t, 3, traffic.count_for(t, 4.0, 8), 100)
    tl = client.run(eng, t, d, 4.0, clock=clock, sleep=clock.sleep)
    compared = run.check(cell, None, tl, d, eng.tokens_by_req, 3,
                         control=True)
    assert compared["served"]["logit_gap"] == (0.125, 0.2)
    assert run.correct(compared["served"])
    assert not run.correct(compared["control"])

    steps = tl.window_steps()
    decode = [s for s in steps if s.kind == "decode"]
    trace = devtrace.Reduced(
        window_s=tl.window_s, busy_s=1.0, devices=1,
        program_s={"jit_paged_decode_step": 0.5},
        program_calls={"jit_paged_decode_step": len(decode)})
    peak = work.peaks("TPU v5 lite")
    layer = run.per_layer(cell, tl, trace, peak)
    bound = sum(work.bound_seconds(1e9 * len(s.contexts),
                                   4e6 * sum(s.contexts), peak)
                for s in decode) / len(decode)
    assert layer["decode_roofline"]["value"] == pytest.approx(
        100.0 * bound / (0.5 / len(decode)))
    flops = sum(1e9 * len(s.contexts) if s.kind == "decode"
                else 3e9 * sum(s.prompts) for s in steps)
    assert layer["mfu_pct"]["value"] == pytest.approx(
        100.0 * flops / (tl.window_s * peak["flops_bf16"]))


@pytest.mark.parametrize("missing", ["reference", "prefill_work"])
def test_a_configuration_without_its_module_stops(tmp_path, missing):
    """No default: a file that names no module, or a module without one
    of ``run.ARCH_API``, stops the run with a message that names it."""
    if missing == "reference":
        sizes = {k: v for k, v in STUB_SIZES.items() if k != "reference"}
        name = add_configuration(tmp_path, sizes=sizes)
    else:
        module = STUB_ARCH.split("\n\ndef prefill_work")[0]
        name = add_configuration(tmp_path, module=module)
    with pytest.raises(SystemExit, match=missing):
        run.load_cell(name, root=tmp_path)
