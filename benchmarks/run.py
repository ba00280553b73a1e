"""Benchmark driver: one module per paper table/figure.

Prints one CSV line per benchmark: ``name,us_per_call,derived`` where
``derived`` carries the reproduced finding. Full row data lands in
results/bench/*.csv.  ``--quick`` shrinks request counts (CI).

The registry is self-checking (``--check-registry``, wired into
scripts/ci.sh): every module in benchmarks/ must either appear in
``benches`` below or be listed in ``NON_BENCHMARKS``, and every module
named in ``SMOKE_GATED`` (the ones scripts/ci.sh runs with ``--smoke``)
must actually expose a ``main`` accepting ``--smoke`` — so adding a
benchmark without registering it, or wiring a smoke gate that silently
does not exist, fails CI instead of silently skipping coverage.
"""
from __future__ import annotations

import argparse
import os
import pkgutil
import sys
import traceback

#: modules in benchmarks/ that are infrastructure, not benchmarks —
#: perf_hillclimb is the §Perf iteration driver (subprocess dry-runs
#: feeding EXPERIMENTS.md), not a table/figure reproduction
NON_BENCHMARKS = {"common", "run", "finalize_docs", "roofline_report",
                  "perf_hillclimb"}
#: benchmarks scripts/ci.sh runs as `--smoke` CI gates; each must expose
#: main(argv) handling "--smoke"
SMOKE_GATED = {"sim_speed", "kv_hierarchy", "parallelism",
               "observability", "chaos_sweep", "hetero_fleet",
               "autoscale", "prefix_routing"}


def discover_modules() -> set:
    """Every importable module name under benchmarks/."""
    here = os.path.dirname(os.path.abspath(__file__))
    return {m.name for m in pkgutil.iter_modules([here])}


def check_registry(registered: set) -> list:
    """Registry drift errors (empty list = OK)."""
    import importlib
    import inspect

    errors = []
    discovered = discover_modules()
    for name in sorted(discovered - registered - NON_BENCHMARKS):
        errors.append(
            f"benchmarks/{name}.py is not registered: add it to the "
            f"benches list in benchmarks/run.py (or to NON_BENCHMARKS "
            f"if it is not a benchmark)")
    for name in sorted(registered - discovered):
        errors.append(f"registered benchmark {name!r} has no module "
                      f"benchmarks/{name}.py")
    for name in sorted(SMOKE_GATED):
        if name not in discovered:
            errors.append(f"SMOKE_GATED benchmark {name!r} has no module "
                          f"benchmarks/{name}.py")
            continue
        mod = importlib.import_module(f"benchmarks.{name}")
        main = getattr(mod, "main", None)
        if not callable(main):
            errors.append(f"benchmarks/{name}.py is SMOKE_GATED but has "
                          f"no main(argv)")
        elif "--smoke" not in inspect.getsource(mod):
            errors.append(f"benchmarks/{name}.py is SMOKE_GATED but its "
                          f"main() does not handle --smoke; the "
                          f"scripts/ci.sh gate would silently no-op")
    return errors


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--check-registry", action="store_true",
                    help="verify benchmark-module registry + smoke "
                         "gates, run nothing")
    args = ap.parse_args(argv)
    q = args.quick

    from benchmarks import (autoscale, batching, chaos_sweep,
                            disagg_ratio, disagg_validation,
                            hardware_sub, hetero_fleet, kv_hierarchy,
                            mem_footprint, memcache, memratio,
                            observability, parallelism, platform_sweep,
                            prefix_routing, sim_speed, spec_decode,
                            tenant_qos, validation)

    benches = [
        ("validation", lambda: validation.run(n_req=20 if q else 40)),
        ("sim_speed", lambda: sim_speed.run(
            request_counts=(10, 20) if q else (20, 40, 60, 80, 100))),
        ("disagg_validation", lambda: disagg_validation.run(
            counts=(8, 16) if q else (10, 20, 40, 60))),
        ("batching", lambda: batching.run(n_req=300 if q else 2000)),
        ("memratio", lambda: memratio.run(n_req=400 if q else 2000)),
        ("disagg_ratio", lambda: disagg_ratio.run(n_req=150 if q else 600)),
        ("hardware_sub", lambda: hardware_sub.run(n_req=150 if q else 500)),
        ("mem_footprint", lambda: mem_footprint.run(
            n_req=300 if q else 1500)),
        ("memcache", lambda: memcache.run(n_req=300 if q else 1200)),
        ("platform_sweep", lambda: platform_sweep.run(
            n_req=200 if q else 800)),
        ("tenant_qos", lambda: tenant_qos.run(quick=q)),
        ("spec_decode", lambda: spec_decode.run(quick=q)),
        ("kv_hierarchy", lambda: kv_hierarchy.run(quick=q)),
        ("parallelism", lambda: parallelism.run(quick=q)),
        ("observability", lambda: observability.run(quick=q)),
        ("chaos_sweep", lambda: chaos_sweep.run(quick=q)),
        ("hetero_fleet", lambda: hetero_fleet.run(quick=q)),
        ("autoscale", lambda: autoscale.run(quick=q)),
        ("prefix_routing", lambda: prefix_routing.run(quick=q)),
    ]
    errors = check_registry({name for name, _ in benches})
    for e in errors:
        print(f"registry FAIL: {e}", file=sys.stderr)
    if errors:
        return 2
    if args.check_registry:
        print(f"registry OK: {len(benches)} benchmarks registered, "
              f"{len(SMOKE_GATED)} smoke-gated "
              f"({', '.join(sorted(SMOKE_GATED))})")
        return 0

    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failed = 0
    for name, fn in benches:
        if only and name not in only:
            continue
        try:
            fn()
        except Exception:                               # noqa: BLE001
            failed += 1
            print(f"{name},ERROR,", file=sys.stdout)
            traceback.print_exc()
    # roofline report appends its own line if artifacts exist
    try:
        from benchmarks import roofline_report
        d = os.path.join(roofline_report.RESULTS, "dryrun_probe")
        if os.path.isdir(d) and os.listdir(d):
            cells = roofline_report.build_table(d)
            md = roofline_report.to_markdown(cells)
            out = os.path.join(roofline_report.RESULTS, "roofline.md")
            with open(out, "w") as f:
                f.write(md + "\n")
            print(f"roofline_report,{len(cells)},cells->results/roofline.md")
    except Exception:                                   # noqa: BLE001
        failed += 1
        print("roofline_report,ERROR,", file=sys.stdout)
        traceback.print_exc()
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
