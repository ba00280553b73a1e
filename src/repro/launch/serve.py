"""Serving CLI: run the real paged-KV engine on a workload.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
      --requests 50 --qps 0 --max-batch 8

The functions below are the CLI's steps; ``chip_smoke.py`` drives the
engine through them too.
"""
from __future__ import annotations

import argparse
import json
from typing import List

import jax

from repro.configs import get_config, get_smoke_config
from repro.core.metrics import Results
from repro.core.request import Request
from repro.core.workload import WorkloadSpec, generate
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_zoo as zoo
from repro.serving.engine import EngineConfig, ServingEngine


def build_model(arch: str, *, smoke: bool = False, seed: int = 0):
    """(model, params) with random weights from ``seed``."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = zoo.build(cfg)
    return model, zoo.init_params(model, jax.random.key(seed))


def make_requests(n: int, *, qps: float = 0.0, seed: int = 0,
                  prompt_len: int = 0, output_len: int = 0) -> List[Request]:
    """ShareGPT-shaped lengths capped at 96/32 tokens, or fixed lengths
    where ``prompt_len`` > 0."""
    if prompt_len:
        wl = WorkloadSpec(num_requests=n, qps=qps, seed=seed,
                          lengths="fixed", prompt_len=prompt_len,
                          output_len=output_len or 16)
    else:
        wl = WorkloadSpec(num_requests=n, qps=qps, seed=seed,
                          max_prompt_len=96, max_output_len=32)
    return generate(wl)


def make_engine(model, params, *, num_blocks: int = 512,
                block_size: int = 16, max_batch: int = 8,
                attn: str = "gather", seed: int = 0) -> ServingEngine:
    ec = EngineConfig(num_blocks=num_blocks, block_size=block_size,
                      max_batch=max_batch,
                      max_pages_per_seq=num_blocks // max(4, max_batch),
                      attn_path=attn, seed=seed)
    return ServingEngine(model, params, ec)


def submit(eng: ServingEngine, reqs: List[Request]) -> None:
    """Queue every request at time 0 (closed batch)."""
    for r in reqs:
        r.arrival_time = 0.0
        eng.add_request(r)


def summarize(eng: ServingEngine, reqs: List[Request]) -> dict:
    summary = Results(requests=reqs, sim_time=eng.clock).summary()
    summary["iterations"] = len(eng.records)
    return summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=50)
    ap.add_argument("--qps", type=float, default=0.0)
    ap.add_argument("--prompt-len", type=int, default=0,
                    help=">0 fixes the prompt length")
    ap.add_argument("--output-len", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--num-blocks", type=int, default=512)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--attn", default="gather", choices=("gather", "pallas"))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    enable_compile_cache()
    model, params = build_model(args.arch, smoke=args.smoke, seed=args.seed)
    reqs = make_requests(args.requests, qps=args.qps, seed=args.seed,
                         prompt_len=args.prompt_len,
                         output_len=args.output_len)
    eng = make_engine(model, params, num_blocks=args.num_blocks,
                      block_size=args.block_size, max_batch=args.max_batch,
                      attn=args.attn, seed=args.seed)
    submit(eng, reqs)
    eng.run()
    summary = summarize(eng, reqs)
    print(json.dumps(summary, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f)


if __name__ == "__main__":
    main()
