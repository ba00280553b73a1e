"""The one traffic generator: reads a mix's data file, draws from a seed.

A mix (``chipbench/traffic/<name>.json``) gives:

* ``loop``: ``saturated`` (the waiting queue never empties: offline
  batch work) or ``poisson`` (open loop at ``rate`` requests/s);
* ``ramp_s``: seconds of traffic before the window opens; ``drain_s``:
  how long requests due in the window may take to finish after it
  closes (0: no drain);
* ``prompt`` and ``output``: lognormal lengths, ``median`` and
  ``sigma`` of the underlying normal, clipped to [``min``, ``max``];
* ``strata``: lengths and gaps are drawn stratified, each run of
  ``strata`` requests holding one draw from each of ``strata`` equal
  slices of the distribution in an order drawn from the seed, so that
  every seed offers nearly the same work in another order;
* ``reference_tokens``: served tokens the output check compares.

The shape of the lengths follows the repo's ShareGPT calibration
(``repro.core.workload``, copied here so that the program may change and
the yardstick may not).
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

LOOPS = ("saturated", "poisson")
DIR = Path(__file__).resolve().parent / "traffic"


@dataclass(frozen=True)
class Lengths:
    median: float
    sigma: float
    min: int
    max: int

    def at(self, u: np.ndarray) -> np.ndarray:
        """Lengths at quantiles ``u`` in (0, 1)."""
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        n = np.floor(self.median * np.exp(self.sigma * z)).astype(np.int64)
        return np.clip(n, self.min, self.max)


@dataclass(frozen=True)
class Traffic:
    name: str
    loop: str
    ramp_s: float
    drain_s: float
    strata: int
    prompt: Lengths
    output: Lengths
    reference_tokens: int
    rate: float = 0.0

    @property
    def max_context(self) -> int:
        return self.prompt.max + self.output.max


def load(name: str, directory: Path = DIR) -> Traffic:
    """The mix ``<directory>/<name>.json``."""
    raw = json.loads((directory / f"{name}.json").read_text())
    loop = raw["loop"]
    if loop not in LOOPS:
        raise ValueError(f"traffic {name}: loop {loop!r} not in {LOOPS}")
    rate = float(raw.get("rate", 0.0))
    if loop == "poisson" and rate <= 0:
        raise ValueError(f"traffic {name}: a poisson loop needs rate > 0")
    return Traffic(name=name, loop=loop, ramp_s=float(raw["ramp_s"]),
                   drain_s=float(raw["drain_s"]), strata=int(raw["strata"]),
                   prompt=Lengths(**raw["prompt"]),
                   output=Lengths(**raw["output"]),
                   reference_tokens=int(raw["reference_tokens"]), rate=rate)


def _stratified(rng: np.random.Generator, n: int, strata: int) -> np.ndarray:
    """``n`` quantiles: each block of ``strata`` has one in each slice."""
    out = np.empty(n)
    for start in range(0, n, strata):
        j = rng.permutation(strata)
        u = (j + rng.random(strata)) / strata
        out[start:start + strata] = u[:n - start]
    return np.clip(out, 1e-9, 1 - 1e-9)


@dataclass(frozen=True)
class Draw:
    """``n`` requests: lengths, due times (s after the traffic starts;
    all 0 in a saturated loop, which sends as the queue drains) and
    prompt tokens."""
    prompt_lens: np.ndarray
    output_lens: np.ndarray
    due: np.ndarray
    prompts: list


def draw(t: Traffic, seed: int, n: int, vocab: int) -> Draw:
    rng = np.random.default_rng(seed)
    prompt_lens = t.prompt.at(_stratified(rng, n, t.strata))
    output_lens = t.output.at(_stratified(rng, n, t.strata))
    if t.loop == "poisson":
        gaps = -np.log1p(-_stratified(rng, n, t.strata)) / t.rate
        due = np.cumsum(gaps)
    else:
        due = np.zeros(n)
    prompts = [rng.integers(0, vocab, size=int(p), dtype=np.int32)
               for p in prompt_lens]
    return Draw(prompt_lens, output_lens, due, prompts)


def count_for(t: Traffic, seconds: float, max_batch: int,
              tokens_per_s: float = 5000.0) -> int:
    """Requests to draw so that the loop never runs dry: the Poisson
    arrivals of ramp, window and a margin, or for a saturated loop what
    a server emitting ``tokens_per_s`` could finish, plus the queue."""
    horizon = t.ramp_s + seconds
    if t.loop == "poisson":
        return int(math.ceil(t.rate * horizon * 1.5)) + t.strata
    mean_out = t.output.median * math.exp(t.output.sigma ** 2 / 2)
    return int(horizon * tokens_per_s / mean_out) + 2 * max_batch + t.strata
