"""Device idle split by the serving engine's own host spans.

``devtrace`` splits each stretch in which no operation ran on the device
by the benchmark's spans (``devtrace.SPANS``) that cover it. This splits
it by the *innermost* span over it, taken among those and the engine's
(``ENGINE``, the names ``repro.serving.engine.SPANS`` gives). Idle inside
``engine.iteration`` but under none of its children counts as
``engine.iteration``, its self time. The window is ``devtrace.reduce``'s;
busy is the union of every device's operations, as its idle split takes
it (on one chip, its ``busy_s``). A trace of an engine without the spans
has no iterations, and its per-step figures are None.

``PER_STEP`` names the per-step figures: idle under the listed spans
over the iterations that start in the window, in ms. The first sync in
``engine.sample`` waits while the decode program runs: that time is
busy, not lost, so a span's idle is less than its length.
"""
from __future__ import annotations

import gzip
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Optional

from chipbench import devtrace

ENGINE = ("engine.iteration", "engine.plan", "engine.prepare",
          "engine.sample", "engine.emit")
ITERATION = ENGINE[0]
PER_STEP = {
    "sched_idle_ms_per_step": ("engine.plan", "engine.emit"),
    "prep_idle_ms_per_step": ("engine.prepare",),
    "sample_idle_ms_per_step": ("engine.sample",),
}


@dataclass
class EngineIdle:
    window_s: float
    busy_s: float
    iterations: int
    idle_by_engine_span: Dict[str, float] = field(default_factory=dict)

    def figures(self) -> Dict[str, Optional[float]]:
        """``PER_STEP``'s figures, ms per iteration (None without any)."""
        if not self.iterations:
            return dict.fromkeys(PER_STEP)
        return {k: sum(self.idle_by_engine_span.get(n, 0.0) for n in v)
                / self.iterations * 1e3 for k, v in PER_STEP.items()}


def innermost(spans, lo: float, hi: float):
    """``spans`` ((start, end, name), any order) as disjoint stretches of
    [lo, hi), sorted, each named by the innermost span over it: of the
    spans open there, the one that started last (of two that started
    together, the shorter). Stretches under no span are left out."""
    spans = sorted(spans)
    marks = sorted({lo, hi} | {t for s0, s1, _ in spans
                               for t in (s0, s1) if lo < t < hi})
    out, open_, j = [], [], 0
    for a, b in zip(marks, marks[1:]):
        while j < len(spans) and spans[j][0] <= a:
            open_.append(spans[j])
            j += 1
        open_ = [s for s in open_ if s[1] > a]
        if open_:
            out.append((a, b, max(open_, key=lambda s: (s[0], -s[1]))[2]))
    return out


def reduce(profile) -> EngineIdle:
    """``profile``: a ``jax.profiler.ProfileData`` of a TPU run."""
    planes = list(profile.planes)
    devices = [p for p in planes if devtrace.DEVICE.match(p.name)]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    names = set(devtrace.SPANS) | set(ENGINE)
    spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
             for p in planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events if e.name in names]
    busy = []
    for dev in devices:
        lines = {line.name: line for line in dev.lines}
        ops = lines[devtrace.OPS].events if devtrace.OPS in lines else [
            e for line in dev.lines for e in line.events]
        busy += [(e.start_ns, e.start_ns + e.duration_ns) for e in ops]
    lo, hi = _window(planes, busy, spans)
    busy = devtrace._union([(max(a, lo), min(b, hi)) for a, b in busy
                            if b > lo and a < hi])
    idle = devtrace._idle_by_span(busy, innermost(spans, lo, hi), lo, hi)
    return EngineIdle(
        window_s=(hi - lo) / 1e9, busy_s=sum(b - a for a, b in busy) / 1e9,
        idle_by_engine_span=idle,
        iterations=sum(1 for s0, _, n in spans
                       if n == ITERATION and lo <= s0 < hi))


def _window(planes, busy, spans):
    """[lo, hi) on the events' clock, by ``devtrace.reduce``'s rule."""
    env = next((p for p in planes if p.name == "Task Environment"), None)
    start = devtrace._stat(env, "profile_start_time") if env else None
    stop = devtrace._stat(env, "profile_stop_time") if env else None
    starts = [a for a, _ in busy] + [s[0] for s in spans
                                     if s[2] in devtrace.SPANS]
    if start is not None and stop is not None and stop > start:
        lo = float(start) if starts and min(starts) > 1e15 else 0.0
        return lo, lo + float(stop - start)
    if not starts:
        raise ValueError("the trace holds no events")
    ends = [b for _, b in busy] + [s[1] for s in spans
                                   if s[2] in devtrace.SPANS]
    return min(starts), max(ends)


def load(path: Path):
    """A ``ProfileData`` from an ``.xplane.pb``, gzipped or not."""
    import jax
    raw = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    return jax.profiler.ProfileData.from_serialized_xspace(raw)
