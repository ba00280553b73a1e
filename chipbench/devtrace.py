"""Reduce a profiler trace of the window to device and host figures.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Device planes are named ``/device:TPU:<n>``; on each, the
``XLA Modules`` line holds one event per run of a compiled program
(named after the jitted function, e.g. ``jit_paged_decode_step(12)``)
and the ``XLA Ops`` line one per operation. Host threads are lines of
``/host:CPU``; the benchmark's own spans are ``TraceAnnotation`` events
there (``SPANS``). All times share one clock, in nanoseconds.

* busy: the union of the intervals in which an operation ran on a
  device, averaged over the devices;
* per program: the summed device time of each program, by its name
  with the ``(<id>)`` suffix dropped;
* idle by span: each stretch in which no operation ran on the device,
  split by which of the benchmark's host spans covered it.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

SPANS = ("engine.step", "client.admit", "client.wait", "reference")
DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS, MODULES = "XLA Ops", "XLA Modules"
NO_SPAN = "(no span)"


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    devices: int
    program_s: Dict[str, float] = field(default_factory=dict)
    program_calls: Dict[str, int] = field(default_factory=dict)
    idle_by_span: Dict[str, float] = field(default_factory=dict)

    def device_s(self, *names: str) -> float:
        """Device seconds of the programs whose name holds any of
        ``names`` (0.0 where none ran)."""
        return sum(t for p, t in self.program_s.items()
                   if any(n in p for n in names))

    def calls(self, *names: str) -> int:
        return sum(c for p, c in self.program_calls.items()
                   if any(n in p for n in names))


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _program(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _stat(plane, key):
    return next((v for k, v in plane.stats if k == key), None)


def reduce(profile) -> Reduced:
    """``profile``: a ``jax.profiler.ProfileData``. The window is the
    profiler's session (``Task Environment`` start and stop), on the
    events' clock: offsets from the session's start (as ``ProfileData``
    gives them) or, where the events read as epoch times, the times
    themselves; without those stats, the span of the events."""
    planes = list(profile.planes)
    devices = [p for p in planes if DEVICE.match(p.name)]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    env = next((p for p in planes if p.name == "Task Environment"), None)
    start = _stat(env, "profile_start_time") if env else None
    stop = _stat(env, "profile_stop_time") if env else None

    per_device, program_s, calls = [], {}, {}
    for dev in devices:
        lines = {line.name: line for line in dev.lines}
        ops = lines[OPS].events if OPS in lines else [
            e for line in dev.lines for e in line.events]
        per_device.append(_union([(e.start_ns, e.start_ns + e.duration_ns)
                                  for e in ops]))
        for e in (lines[MODULES].events if MODULES in lines else ()):
            p = _program(e.name)
            program_s[p] = program_s.get(p, 0.0) + e.duration_ns / 1e9
            calls[p] = calls.get(p, 0) + 1

    spans = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for p in planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name in SPANS)
    ends = [iv[-1][1] for iv in per_device if iv] + [s[1] for s in spans]
    starts = [iv[0][0] for iv in per_device if iv] + [s[0] for s in spans]
    if start is not None and stop is not None and stop > start:
        lo = float(start) if starts and min(starts) > 1e15 else 0.0
        hi = lo + float(stop - start)
    elif starts:
        lo, hi = min(starts), max(ends)
    else:
        raise ValueError("the trace holds no events")
    clipped = [[(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]
               for iv in per_device]
    busy = sum(b - a for iv in clipped for a, b in iv) / len(devices)
    idle = _idle_by_span(_union([x for iv in clipped for x in iv]), spans,
                         lo, hi)
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=busy / 1e9,
                   devices=len(devices), program_s=program_s,
                   program_calls=calls, idle_by_span=idle)


def _idle_by_span(busy, spans, lo: float, hi: float) -> Dict[str, float]:
    """Seconds of [lo, hi) outside ``busy``, by the host span over it."""
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, min(a, hi)))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    out: Dict[str, float] = {}
    j = 0
    for a, b in gaps:
        covered = 0.0
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < b:
            s0, s1, name = spans[k]
            ov = min(b, s1) - max(a, s0)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov / 1e9
                covered += ov
            k += 1
        rest = (b - a) - covered
        if rest > 0:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + rest / 1e9
    return out


def breakdown(r: Reduced, n: int = 10) -> dict:
    top = sorted(r.program_s.items(), key=lambda kv: -kv[1])[:n]
    gaps = sorted(r.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]}
