#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration
(``chipbench/configs/<config>.json``: the program's arch, its sizes, the
engine's sizes, the output check's limit, and under ``"reference"`` the
module of its architecture) and a traffic mix
(``chipbench/traffic/<mix>.json``). The run makes the weights from the
seed, builds ``ServingEngine`` at the configured sizes, warms every
program shape the mix can reach (set-up), serves the mix for a ramp and
then the measured window on the wall clock, and checks a sample of what
the window served against the configuration's float32 reference.

The harness knows no architecture: the size check, the output check and
the work counts of the roofline readers are the functions ``ARCH_API``
of the module the configuration names.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces the window with the JAX profiler and reports its per-layer
metrics, one reader each in ``chipbench/metrics/<metric>.py``. The last
line of standard output is one JSON object; earlier lines and the last
lines of standard error give what was compared, each with its limit.
It refuses to run where JAX finds no TPU or fewer chips than the cell.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache: a fixed directory in the checkout
CACHE = ROOT / ".jax_cache"
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import client, devtrace, traffic, weights, work  # noqa: E402

#: at most this many requests go to the output check
MAX_COMPARED = 16
#: what a configuration's module exposes: ``program_sizes(cfg)``, the
#: program's ``ArchConfig`` under the file's keys; ``gaps(sizes, params,
#: prompt, served, *, control)``, the output check's logit gaps;
#: ``decode_work(sizes, contexts)`` and ``prefill_work(sizes,
#: prompt_len)``, (FLOPs, bytes) of a step
ARCH_API = ("program_sizes", "gaps", "decode_work", "prefill_work")


@dataclass
class Cell:
    name: str
    chips: int
    sizes: dict
    traffic: traffic.Traffic
    end_to_end: list
    per_layer: list
    #: the configuration's module (``ARCH_API``)
    reference: object


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """The Python file at ``path``, executed once per process (and listed
    in ``sys.modules``, where a dataclass looks up its module)."""
    name = "chipbench_file" + re.sub(r"\W", "_", str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = sys.modules[name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(sizes: dict, root: Path = ROOT, file: str = "the file"):
    """The module that a configuration names under ``"reference"``, a
    path under ``chipbench/``. No default: a file without the key, or a
    module without one of ``ARCH_API``, stops the run."""
    name = sizes.get("reference")
    if not name:
        raise SystemExit(f"{file} names no module under \"reference\" "
                         f"(a path under chipbench/ with {ARCH_API})")
    path = (root / name).resolve()
    if (root / "chipbench").resolve() not in path.parents \
            or not path.is_file():
        raise SystemExit(f"{file}: \"reference\" {name!r} is no file "
                         f"under chipbench/")
    mod = load_module(path)
    missing = [f for f in ARCH_API if not callable(getattr(mod, f, None))]
    if missing:
        raise SystemExit(f"{name}, the reference of {file}, lacks "
                         f"{missing}")
    return mod


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    sizes = json.loads((root / cfg["file"]).read_text())
    module = load_reference(sizes, root, cfg["file"])
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reported]
    return Cell(name=name, chips=int(w["chips"]), sizes=sizes,
                traffic=traffic.load(w["traffic"], root / "chipbench" /
                                     "traffic"),
                end_to_end=e2e, per_layer=per_layer, reference=module)


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------
def check_arch(cfg, cell: Cell) -> None:
    """The program's configuration is the file's, or the run stops: every
    key the cell's ``program_sizes`` reads out of ``cfg`` holds the same
    value in the file."""
    got = cell.reference.program_sizes(cfg)
    missing = sorted(set(got) - set(cell.sizes))
    if missing:
        raise SystemExit(f"the file of {cell.name} lacks {missing}, which "
                         f"its reference compares with the program")
    bad = {k: (v, cell.sizes[k]) for k, v in got.items()
           if v != cell.sizes[k]}
    if bad:
        raise SystemExit(f"the program's {cfg.name} differs from its file "
                         f"(program, file): {bad}")


def engine_config(sizes: dict, t: traffic.Traffic, seed: int):
    from repro.serving.engine import EngineConfig
    e = sizes["engine"]
    return EngineConfig(
        num_blocks=e["num_blocks"], block_size=e["block_size"],
        max_batch=e["max_batch"],
        max_pages_per_seq=math.ceil(t.max_context / e["block_size"]),
        max_batched_tokens=e["max_batched_tokens"], seed=seed)


def warm_shapes(engine, t: traffic.Traffic) -> list:
    """Every prefill length the engine pads to for the mix: the prompts,
    and prompts with their tokens after a preemption."""
    return sorted({min(engine._bucket(n), engine.max_ctx)
                   for n in range(t.prompt.min, t.max_context)})


def warm(engine, t: traffic.Traffic) -> int:
    """Run one prefill of every shape and one decode step through the
    engine itself; returns how many shapes."""
    from repro.core.request import Request
    shapes = warm_shapes(engine, t)
    for i, s in enumerate(shapes):
        plen = max(1, s - 1)             # pads to s (or to the cap)
        req = Request(id=-1 - i, arrival_time=0.0, prompt_len=plen,
                      output_len=2 if i == 0 else 1)
        engine.add_request(req, np.arange(plen, dtype=np.int32) % 256)
    engine.run()
    return len(shapes)


class Compiles:
    """Counts programs compiled or read from the persistent cache."""

    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1

    def __call__(self) -> int:
        return self.n


def start_jax(chips: int):
    """The devices, or None where they are not ``chips`` TPU chips. The
    persistent compile cache goes to ``CACHE``, the fixed directory in
    the checkout, for this process and for the program (which keeps it
    where ``JAX_COMPILATION_CACHE_DIR`` says), and keeps every program.
    The TPU runtime's logs go under ``$TMPDIR`` unless placed already."""
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"run: needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return None
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    return devs


def build(cell: Cell, seed: int, cfg=None):
    """(model, params, engine) for ``cell`` from ``seed``, warmed.
    ``cfg``: the program's ArchConfig (default: the file's ``arch``)."""
    import jax
    from repro.configs import get_config
    from repro.models import model_zoo as zoo
    from repro.serving.engine import ServingEngine
    cfg = cfg or get_config(cell.sizes["arch"])
    check_arch(cfg, cell)
    model = zoo.build(cfg)
    params = weights.make(zoo.param_specs(model), seed)
    jax.block_until_ready(params)
    engine = ServingEngine(model, params,
                           engine_config(cell.sizes, cell.traffic, seed))
    warm(engine, cell.traffic)
    return model, params, engine


# ---------------------------------------------------------------------------
# The window and the check
# ---------------------------------------------------------------------------
def serve(cell: Cell, engine, seed: int, seconds: float, *,
          compiles=lambda: 0, trace_dir: Optional[str] = None):
    """Serve the cell's traffic; returns (timeline, served tokens)."""
    import jax
    t = cell.traffic
    d = traffic.draw(t, seed, traffic.count_for(t, seconds,
                                                engine.ec.max_batch),
                     cell.sizes["vocab_size"])
    span = hooks = None
    if trace_dir is not None:
        span = jax.profiler.TraceAnnotation
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        hooks = (lambda: jax.profiler.start_trace(trace_dir,
                                                  profiler_options=opts),
                 jax.profiler.stop_trace)
    tl = client.run(engine, t, d, seconds, span=span, compiles=compiles,
                    hooks=hooks)
    served = {s.req.id: list(engine.tokens_by_req[s.req.id])
              for s in tl.sent}
    return tl, d, served


def choose(tl: client.Timeline, seed: int, want_tokens: int) -> list:
    """Finished requests to compare: the longest, then others drawn from
    the seed until ``want_tokens`` served tokens (at most MAX_COMPARED)."""
    done = [s for s in tl.sent if s.req.finished]
    if not done:
        return []
    done.sort(key=lambda s: (-(s.req.prompt_len + s.req.output_len),
                             s.req.id))
    rest = done[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    picked, tokens = [done[0]], done[0].req.output_len
    for i in order:
        if tokens >= want_tokens or len(picked) >= MAX_COMPARED:
            break
        picked.append(rest[i])
        tokens += rest[i].req.output_len
    return picked


#: what the output check can compare, from the gaps (best logit less the
#: compared token's) at every compared position
GAP_STATS = {
    "logit_gap": lambda g: float(g.max()),          # the widest gap
    "mean_gap": lambda g: float(g.mean()),
    "mismatch_pct": lambda g: 100.0 * float((g > 0).mean()),
}


def check(cell: Cell, params, tl, d, served, seed: int, *,
          control: bool = False) -> dict:
    """Numbers compared, each ``(value, limit)``, by what they judge:
    ``served``, the tokens the window served, and with ``control`` also
    ``control``, the float8 pass's first choices put in their place at
    the same positions (the control, which has to come out not correct).
    Each holds the gap statistics, with the limits the configuration's
    ``limits`` name (None for the others), and the counts that must
    be 0."""
    limits = cell.sizes["limits"]
    picked = choose(tl, seed, cell.traffic.reference_tokens)
    wrong = sum(1 for s in tl.sent if s.req.finished
                and len(served[s.req.id]) != s.req.output_len)
    gaps = {"served": []}
    if control:
        gaps["control"] = []
    for s in picked:
        g = cell.reference.gaps(cell.sizes, params, d.prompts[s.req.id],
                                served[s.req.id], control=control)
        for k, v in g.items():
            gaps[k].append(v)
    out = {}
    for which, gs in gaps.items():
        g = np.concatenate(gs) if gs else np.zeros(0)
        out[which] = {name: (stat(g), limits.get(name))
                      for name, stat in GAP_STATS.items() if len(g)}
        out[which].update(compared_tokens=(len(g), None),
                          wrong_length=(wrong, 0),
                          unfinished=(client.counts(tl)["failed"], 0))
    return out


def correct(compared: dict) -> bool:
    ok = all(v <= lim for v, lim in compared.values() if lim is not None)
    return ok and compared["compared_tokens"][0] > 0


def per_layer(cell: Cell, tl, reduced, peak: dict) -> dict:
    ctx = Context(timeline=tl, trace=reduced, sizes=cell.sizes, peak=peak,
                  reference=cell.reference)
    out = {}
    for m in cell.per_layer:
        v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


@dataclass
class Context:
    """What a per-layer reader reads."""
    timeline: client.Timeline
    trace: Optional[devtrace.Reduced]
    sizes: dict
    peak: dict
    #: the cell's module: its ``decode_work`` and ``prefill_work``
    reference: object


def read_trace(trace_dir: str) -> devtrace.Reduced:
    import jax
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise RuntimeError(f"no trace under {trace_dir}")
    return devtrace.reduce(jax.profiler.ProfileData.from_file(
        str(found[-1])))


# ---------------------------------------------------------------------------
def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: judge the float8 control in the served "
                         "tokens' place (it has to come out not correct)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    cell = load_cell(args.workload)
    devs = start_jax(cell.chips)
    if devs is None:
        return 1
    import jax
    compiles = Compiles(jax)
    peak = work.peaks(devs[0].device_kind)

    model, params, engine = build(cell, args.seed)
    setup_s = time.perf_counter() - T_START
    compiled_in_setup = compiles()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if args.trace else None
    tl, d, served = serve(cell, engine, args.seed, args.seconds,
                          compiles=compiles, trace_dir=trace_dir)
    mem = devs[0].memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    counts = client.counts(tl)
    e2e = client.end_to_end(tl)
    steps = tl.window_steps()
    print(f"cell {cell.name} seed {args.seed}: setup {setup_s:.3f} s "
          f"({compiled_in_setup} programs compiled or loaded); window "
          f"{tl.window_s:.3f} s, {len(steps)} steps "
          f"({sum(s.kind == 'decode' for s in steps)} decode)")
    print(f"client: {counts['attempted']} requests in the sample, "
          f"{counts['failed']} unfinished; sent late p99 "
          f"{counts['late_p99_ms']:.3f} ms, max {counts['late_max_ms']:.3f}"
          f" ms; waiting queue {tl.queue_at_open} at open, "
          f"{tl.queue_at_close} at close; preemptions "
          f"{counts['preemptions']}")
    half = (tl.open + tl.close) / 2
    halves = [client.tokens_between(tl, a, b) / (tl.window_s / 2)
              for a, b in ((tl.open, half), (half, tl.close))]
    print(f"window halves: {halves[0]:.3f}, {halves[1]:.3f} tokens/s")
    longest = sorted(steps, key=lambda s: s.start - s.end)[:5]
    print("longest steps: " + ", ".join(
        f"{s.kind} {1e3 * (s.end - s.start):.1f} ms (engine "
        f"{1e3 * s.wall:.1f}, {len(s.contexts) or len(s.prompts)} rows, "
        f"at +{s.start - tl.open:.2f} s)" for s in longest))
    gaps = [b.start - a.end for a, b in zip(steps, steps[1:])]
    print(f"between steps: {sum(gaps):.3f} s in all, longest "
          f"{max(gaps, default=0.0):.3f} s")
    print(f"compiles inside the window: {tl.compiles_in_window}")
    print(f"peak HBM: {peak_bytes} bytes of {mem.get('bytes_limit')}")
    print("end-to-end: " + json.dumps(e2e))

    reduced = None
    if trace_dir is not None:
        reduced = read_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"trace: busy {reduced.busy_s:.6f} s of {reduced.window_s:.6f}"
              f" s; programs {json.dumps(reduced.program_s)}")

    engine.pages = None                  # free the pool for the reference
    del engine
    gc.collect()
    t_ref = time.perf_counter()
    with jax.profiler.TraceAnnotation("reference"):
        compared = check(cell, params, tl, d, served, args.seed,
                         control=bool(args.control))
    print(f"reference: {time.perf_counter() - t_ref:.3f} s")
    if args.control:
        for k, (v, lim) in compared["served"].items():
            print(f"served {k}: {v!r} limit {lim}")
    compared = compared["control" if args.control else "served"]

    if args.trace:
        metrics = per_layer(cell, tl, reduced, peak)
    else:
        values = dict(e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct(compared), "attempted": counts["attempted"],
              "failed": counts["failed"], "metrics": metrics,
              "device": device}
    if reduced is not None:
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result["breakdown"] = devtrace.breakdown(reduced)
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    for k, (v, lim) in compared.items():
        print(f"compared {k}: {v!r} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
