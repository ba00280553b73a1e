"""The client: drives ``ServingEngine`` on the wall clock, and the window
arithmetic that turns its timeline into end-to-end metrics.

The clock is ``time.perf_counter``. Each request is timed from when it
was *due*. After every ``step()`` the client stamps the wall time on each
token that step added to ``engine.tokens_by_req``. The window is the
``seconds`` after the ramp; its sample is every request due inside it.
After the window closes nothing new is sent, and the sample is served to
completion for at most the mix's ``drain_s`` (a request still unfinished
then has failed). A saturated loop has no drain: its metric is the
tokens emitted inside the window, where a step that straddles an edge
of the window counts in proportion to the part of it that lies inside.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

from repro.core.request import Request

from chipbench.traffic import Draw, Traffic


@dataclass
class Sent:
    req: Request
    due: float                       # absolute perf_counter time
    sent: float                      # when add_request was called
    stamps: List[float] = field(default_factory=list)


@dataclass
class Step:
    start: float
    end: float
    wall: float                      # the engine's IterationRecord.wall
    kind: str
    contexts: tuple                  # decode: keys each row's attention read
    prompts: tuple                   # prefill: real length of each prompt
    tokens: int = 0                  # tokens the step emitted


@dataclass
class Timeline:
    sent: List[Sent]
    steps: List[Step]
    open: float
    close: float
    saturated: bool = False
    queue_at_open: int = 0
    queue_at_close: int = 0
    compiles_in_window: int = 0

    @property
    def window_s(self) -> float:
        return self.close - self.open

    def in_window(self, t: float) -> bool:
        return self.open <= t < self.close

    def sample(self) -> List[Sent]:
        """Requests due inside the window; in a saturated loop, which
        sends as the queue drains, those with a token inside it."""
        if self.saturated:
            return [s for s in self.sent
                    if any(self.in_window(t) for t in s.stamps)]
        return [s for s in self.sent if self.in_window(s.due)]

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps
                if s.start >= self.open and s.end <= self.close]


def run(engine, traffic: Traffic, draw: Draw, seconds: float, *,
        clock: Callable[[], float] = time.perf_counter,
        sleep: Callable[[float], None] = time.sleep,
        span: Callable[[str], contextlib.AbstractContextManager] = None,
        compiles: Callable[[], int] = lambda: 0,
        hooks: Optional[tuple] = None) -> Timeline:
    """Serve ``draw`` as ``traffic`` says; returns the timeline. Request
    ``i`` of the draw gets id ``i``. ``hooks``: two callables, run as the
    window opens and as it closes (the profiler's start and stop)."""
    span = span or (lambda name: contextlib.nullcontext())
    saturated = traffic.loop == "saturated"
    t0 = clock()
    opened, closed = t0 + traffic.ramp_s, t0 + traffic.ramp_s + seconds
    drain_end = closed + traffic.drain_s
    tl = Timeline(sent=[], steps=[], open=opened, close=closed,
                  saturated=saturated)
    by_id = {}
    seen = {}
    nxt = 0
    n = len(draw.prompt_lens)
    depth = engine.ec.max_batch
    marks = {}

    def send(i: int, due: float, now: float) -> None:
        req = Request(id=i, arrival_time=due - t0,
                      prompt_len=int(draw.prompt_lens[i]),
                      output_len=int(draw.output_lens[i]))
        engine.add_request(req, draw.prompts[i])
        s = Sent(req=req, due=due, sent=now)
        tl.sent.append(s)
        by_id[i] = s
        seen[i] = 0

    while True:
        now = clock()
        if "open" not in marks and now >= opened:
            if hooks:
                hooks[0]()
            marks["open"] = compiles()
            tl.queue_at_open = len(engine.waiting)
        if "close" not in marks and now >= closed:
            marks["close"] = compiles()
            tl.queue_at_close = len(engine.waiting)
            if hooks:
                hooks[1]()
        if now < closed:
            with span("client.admit"):
                if saturated:
                    while len(engine.waiting) < depth:
                        if nxt >= n:
                            raise RuntimeError("traffic ran dry: draw more "
                                               "requests")
                        send(nxt, now, now)
                        nxt += 1
                else:
                    while nxt < n and t0 + draw.due[nxt] <= now:
                        due = t0 + float(draw.due[nxt])
                        if due >= closed:
                            break
                        send(nxt, due, now)
                        nxt += 1
        elif saturated or now >= drain_end or all(
                s.req.finished for s in tl.sent if s.due >= opened):
            break
        if engine.has_work:
            with span("engine.step"):
                start = clock()
                rec = engine.step()
                end = clock()
            if rec is None:
                raise RuntimeError("the engine has work but planned none")
            emitted = 0
            for rid in rec.batch_ids:
                s = by_id.get(rid)
                got = len(engine.tokens_by_req[rid])
                if s is not None and got > seen[rid]:
                    s.stamps.extend([end] * (got - seen[rid]))
                    emitted += got - seen[rid]
                    seen[rid] = got
            _record(tl, engine, by_id, rec, start, end, emitted)
        elif now < closed:
            wake = closed if saturated or nxt >= n else min(
                closed, t0 + float(draw.due[nxt]))
            with span("client.wait"):
                sleep(max(0.0, wake - clock()))
    if "open" not in marks:
        raise RuntimeError("the window never opened")
    tl.compiles_in_window = marks.get("close", compiles()) - marks["open"]
    return tl


def _record(tl: Timeline, engine, by_id: dict, rec, start: float,
            end: float, emitted: int) -> None:
    contexts, prompts = (), ()
    if rec.kind == "decode":
        # a row's attention read its context after the step, less the
        # token the step emitted
        contexts = tuple(by_id[r].req.context_len - 1 if r in by_id else 0
                         for r in rec.batch_ids)
    else:
        prompts = tuple(_prefilled(engine, r) for r in rec.batch_ids)
    tl.steps.append(Step(start=start, end=end, wall=rec.wall, kind=rec.kind,
                         contexts=contexts, prompts=prompts, tokens=emitted))


def _prefilled(engine, rid: int) -> int:
    """Tokens the prefill of ``rid`` ran over: the prompt, and after a
    preemption the tokens generated before it (all but the newest)."""
    return len(engine.prompt_tokens[rid]) + max(
        0, len(engine.tokens_by_req[rid]) - 1)


# ---------------------------------------------------------------------------
# Window arithmetic
# ---------------------------------------------------------------------------
def percentile(xs, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


def tokens_between(tl: Timeline, a: float, b: float) -> float:
    """Tokens emitted in [a, b): each step's tokens spread evenly over
    the step, so that a step across an edge counts for the part of it
    inside (a whole step's tokens in or out would swing the count by a
    batch)."""
    out = 0.0
    for s in tl.steps:
        inside = min(s.end, b) - max(s.start, a)
        if inside > 0:
            out += s.tokens * inside / (s.end - s.start)
    return out


def end_to_end(tl: Timeline) -> dict:
    """Every end-to-end metric the timeline can give (ms, tokens/s).
    Tails are over all requests of the sample that finished."""
    sample = tl.sample()
    done = [s for s in sample if s.req.finished]
    ttft = [(s.stamps[0] - s.due) * 1e3 for s in done]
    tpot = [(s.stamps[-1] - s.stamps[0]) / (len(s.stamps) - 1) * 1e3
            for s in done if len(s.stamps) >= 2]
    emitted = tokens_between(tl, tl.open, tl.close)
    out = {"output_tok_s": emitted / tl.window_s,
           "ttft_p50_ms": percentile(ttft, 50),
           "ttft_p90_ms": percentile(ttft, 90),
           "tpot_p90_ms": percentile(tpot, 90)}
    return {k: v for k, v in out.items() if v is not None}


def counts(tl: Timeline) -> dict:
    """attempted, failed (unfinished at the drain's end), and how late
    the client sent (ms after due)."""
    sample = tl.sample()
    late = [(s.sent - s.due) * 1e3 for s in tl.sent]
    unfinished = 0 if tl.saturated else sum(
        1 for s in sample if not s.req.finished)
    return {"attempted": len(sample), "failed": unfinished,
            "late_p99_ms": percentile(late, 99) or 0.0,
            "late_max_ms": max(late, default=0.0),
            "preemptions": sum(s.req.preempt_count for s in tl.sent)}
