#!/usr/bin/env python3
"""Readings for the output check's limit, several seeds in one process.

    python chipbench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 ...

For each seed it runs the cell as ``run.py`` does (weights from the
seed, warm-up, ramp, window) and prints one JSON line with the numbers
``run.check`` compares and those of the float8 control over the same
prompts and served tokens, with whether the control came out correct. The lower reading of a limit is the largest
program gap over the seeds; its upper reading the smallest control gap.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench import client, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    if run.start_jax(cell.chips) is None:
        return 1
    for seed in args.seeds:
        _, params, engine = run.build(cell, seed)
        tl, d, served = run.serve(cell, engine, seed, args.seconds)
        engine.pages = None
        del engine
        gc.collect()
        compared = run.check(cell, params, tl, d, served, seed, control=True)
        row = {"seed": seed,
               **{k: v for k, (v, _) in compared["served"].items()},
               **{f"control_{k}": v
                  for k, (v, _) in compared["control"].items()},
               "control_correct": run.correct(compared["control"]),
               **client.end_to_end(tl), **client.counts(tl)}
        print(json.dumps(row), flush=True)
        del params
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
