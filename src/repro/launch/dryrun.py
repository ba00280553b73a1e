"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 host placeholder devices, lowers the real
train/prefill/decode step with the full-size model as ShapeDtypeStructs
(no allocation), compiles, and records memory_analysis / cost_analysis /
parsed per-device collective bytes for the roofline report.

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k \
      --mesh single --settings baseline --out results/dryrun
  python -m repro.launch.dryrun --all --mesh both
"""
# These lines MUST run before any other import pulls in jax. The devices
# are virtual host devices: pin the CPU platform so that neither this
# process nor a child that perf_hillclimb starts ever takes a TPU.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"  # noqa: E402
os.environ["JAX_PLATFORMS"] = "cpu"  # noqa: E402

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (ASSIGNED, get_config, get_shape,  # noqa: E402
                           LM_SHAPES, shape_applicable)
from repro.configs.base import TRAIN, PREFILL  # noqa: E402
from repro.core.costmodel.backends import cost_analysis_dict  # noqa: E402
from repro.distributed import shard_plan  # noqa: E402
from repro.distributed.api import use_rules  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import model_zoo as zoo  # noqa: E402
from repro.models.common import RunSettings  # noqa: E402
from repro.training.optimizer import AdamWConfig  # noqa: E402
from repro.training.trainer import TrainConfig, make_train_step  # noqa: E402

P = jax.sharding.PartitionSpec

SETTINGS_PRESETS = {
    # paper-faithful baseline: full-rectangle flash attention, dense
    # (every-expert) MoE, full remat — what a straightforward port does.
    "baseline": RunSettings(attn_impl="blocked", moe_impl="dense_onehot",
                            remat="full", scan_layers=True),
    # beyond-paper optimized (settings the §Perf hillclimb CONFIRMED):
    # causal-triangle attention (half the attention FLOPs) + matmul-
    # output-saving remat. moe_impl stays dense_onehot: the grouped-GEMM
    # "sort" path is numerically validated but GSPMD cannot partition
    # argsort/ragged_dot at 256 chips (§Perf A1, +587% compute) — a
    # shard_map expert-parallel dispatch is the recorded future path.
    "optimized": RunSettings(attn_impl="blocked_causal",
                             moe_impl="dense_onehot",
                             remat="dots_saveable", scan_layers=True),
    # serving variant: weights replicated over "data" (no ZeRO-3
    # all-gathers at inference)
    "optimized_serve": RunSettings(attn_impl="blocked_causal",
                                   remat="none", scan_layers=True,
                                   fsdp_params=False),
}

DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "s32": 4,
               "u64": 8, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "c64": 8, "c128": 16}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs — never allocated)
# ---------------------------------------------------------------------------
def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(model: zoo.Model, shape_name: str):
    """Returns {"batch"/"cache"/"tokens" ShapeDtypeStructs} per shape kind."""
    cfg = model.cfg
    shape = get_shape(shape_name)
    b, s = shape.global_batch, shape.seq_len
    out = {}
    if shape.kind == TRAIN:
        batch = {"tokens": sds((b, s), jnp.int32),
                 "labels": sds((b, s), jnp.int32)}
        if cfg.family in ("audio", "encdec"):
            batch["embeds"] = sds((b, cfg.enc_seq_len, cfg.d_model),
                                  jnp.float32)
        out["batch"] = batch
    elif shape.kind == PREFILL:
        batch = {"tokens": sds((b, s), jnp.int32)}
        if cfg.family in ("audio", "encdec"):
            batch["embeds"] = sds((b, cfg.enc_seq_len, cfg.d_model),
                                  jnp.float32)
        out["batch"] = batch
        out["cache"] = zoo.cache_specs(model, b, s)
    else:  # decode: one new token against a cache of seq_len
        out["tokens"] = sds((b,), jnp.int32)
        out["cache"] = zoo.cache_specs(model, b, s)
    return out


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of all shapes in a type string like
    'f32[8,128]' or '(bf16[2,4], u32[])'."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str):
    """Per-device bytes by collective kind, from post-SPMD HLO."""
    out = {k: {"count": 0, "bytes": 0} for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (.+?) (all-reduce|all-gather|"
                     r"reduce-scatter|all-to-all|collective-permute)"
                     r"(?:-start|-done)?\(", ls)
        if not m:
            continue
        if "-done(" in ls:
            continue                      # counted at -start
        result_type, kind = m.group(1), m.group(2)
        nbytes = _shape_bytes(result_type)
        # reduce-scatter's result is 1/n of the data moved; use operand
        if kind == "reduce-scatter":
            args = ls.split("(", 1)[1]
            nbytes = max(nbytes, _shape_bytes(args.split(")")[0]))
        out[kind]["count"] += 1
        out[kind]["bytes"] += nbytes
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


# ---------------------------------------------------------------------------
# Depth probing (roofline counting mode)
# ---------------------------------------------------------------------------
def probe_depths(cfg):
    """Two reduced depths (a < b) per family for finite-difference layer
    accounting: per-layer cost = (f(b)-f(a))/(b-a), constant part =
    f(b) - b*layer, total = constant + L*layer.  Exact because every
    cost component is affine in depth (identical layers; the optimizer
    update scales with per-layer params)."""
    if cfg.family == "hybrid":
        p = cfg.attn_period or 1
        return p, 2 * p
    if cfg.family in ("audio", "encdec"):
        return 2, 4            # scales n_enc and n_dec together
    return 2, 4


def with_depth(cfg, n: int):
    kw = {"num_layers": n}
    if cfg.family in ("audio", "encdec"):
        frac_e = cfg.n_enc_layers / cfg.num_layers
        kw = {"num_layers": n,
              "n_enc_layers": max(1, round(n * frac_e)),
              "n_dec_layers": n - max(1, round(n * frac_e))}
    return cfg.with_overrides(**kw)


# ---------------------------------------------------------------------------
# Cell runner
# ---------------------------------------------------------------------------
def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             settings: RunSettings, grad_compression: bool = False,
             seq_parallel: bool | None = None,
             save_hlo: str | None = None,
             depth_override: int | None = None) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": why}
    if depth_override is not None:
        cfg = with_depth(cfg, depth_override)

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = zoo.build(cfg, tp=16, settings=settings)
    if seq_parallel is None:
        seq_parallel = (shape_name == "long_500k")
    rules = shard_plan.default_rules(multi_pod=multi_pod,
                                     seq_parallel=seq_parallel)

    pparams = shard_plan.param_pspecs(model)
    specs = input_specs(model, shape_name)
    t0 = time.perf_counter()

    def N(tree):
        return shard_plan.named(mesh, tree)

    with mesh:
        if shape.kind == TRAIN:
            tc = TrainConfig(opt=AdamWConfig(),
                             grad_compression=grad_compression)
            step_fn = make_train_step(model, tc)
            params_s = zoo.param_specs(model)
            opt_s = jax.eval_shape(
                lambda p: {"mu": p, "nu": p,
                           "step": jnp.zeros((), jnp.int32)}, params_s)
            ef_s = jax.eval_shape(
                lambda p: jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), p),
                params_s) if grad_compression else \
                {"_": sds((), jnp.float32)}
            in_shard = (pparams, shard_plan.opt_pspecs(model),
                        shard_plan.ef_pspecs(model, grad_compression),
                        shard_plan.batch_pspecs(specs["batch"], rules))
            out_shard = (pparams, shard_plan.opt_pspecs(model),
                         shard_plan.ef_pspecs(model, grad_compression),
                         None)

            def wrapped(params, opt, ef, batch):
                with use_rules(mesh, rules):
                    return step_fn(params, opt, ef, batch)

            jitted = jax.jit(wrapped, in_shardings=N(in_shard),
                             out_shardings=N(out_shard))
            lowered = jitted.lower(params_s, opt_s, ef_s, specs["batch"])

        elif shape.kind == PREFILL:
            params_s = zoo.param_specs(model)
            cache_sh = shard_plan.cache_pspecs(model, rules)
            in_shard = (pparams,
                        shard_plan.batch_pspecs(specs["batch"], rules),
                        cache_sh)
            out_shard = (rules.spec("batch", "vocab"), cache_sh)

            def prefill_step(params, batch, cache):
                with use_rules(mesh, rules):
                    logits, cache = zoo.prefill(model, params, batch,
                                                cache)
                    return logits[:, -1], cache   # serving: sample last

            jitted = jax.jit(prefill_step, in_shardings=N(in_shard),
                             out_shardings=N(out_shard))
            lowered = jitted.lower(params_s, specs["batch"],
                                   specs["cache"])

        else:  # DECODE
            params_s = zoo.param_specs(model)
            cache_sh = shard_plan.cache_pspecs(model, rules)
            in_shard = (pparams, cache_sh, rules.spec("batch"))
            out_shard = (rules.spec("batch", "vocab"), cache_sh)

            def serve_step(params, cache, tokens):
                with use_rules(mesh, rules):
                    return zoo.decode_step(model, params, cache, tokens)

            jitted = jax.jit(serve_step, in_shardings=N(in_shard),
                             out_shardings=N(out_shard))
            lowered = jitted.lower(params_s, specs["cache"],
                                   specs["tokens"])

        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = cost_analysis_dict(compiled.cost_analysis())
    hlo = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    coll = parse_collectives(hlo)

    n_devices = 512 if multi_pod else 256
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_devices,
        "settings": dataclasses.asdict(settings),
        "seq_parallel": seq_parallel,
        "grad_compression": grad_compression,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            k: getattr(mem, k) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes",
             "alias_size_in_bytes")
            if hasattr(mem, k)},
        "cost": {k: cost.get(k) for k in
                 ("flops", "bytes accessed", "transcendentals")
                 if k in cost} if isinstance(cost, dict) else {},
        "collectives": coll,
    }
    return result


# ---------------------------------------------------------------------------
def all_cells():
    for arch in ASSIGNED:
        cfg = get_config(arch)
        for shape in LM_SHAPES:
            yield arch, shape.name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--settings", default="baseline")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--seq-parallel", type=int, default=-1,
                    help="-1 auto (long_500k only), 0 off, 1 on")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--depth-probe", action="store_true",
                    help="roofline counting mode: lower each cell at two "
                         "reduced unrolled depths for finite-difference "
                         "layer accounting (see DESIGN.md §6)")
    args = ap.parse_args()

    settings = SETTINGS_PRESETS[args.settings] \
        if args.settings in SETTINGS_PRESETS else \
        RunSettings(**json.loads(args.settings))
    os.makedirs(args.out, exist_ok=True)

    cells = list(all_cells()) if args.all else [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            depths = [None]
            if args.depth_probe:
                from repro.configs import get_config as _gc
                a, b = probe_depths(_gc(arch))
                depths = [a, b]
            for depth in depths:
                tag = f"{arch}_{shape}_{'multi' if mp else 'single'}" \
                      f"_{args.settings if args.settings in SETTINGS_PRESETS else 'custom'}"
                if depth is not None:
                    tag += f"_d{depth}"
                try:
                    sp = None if args.seq_parallel < 0 \
                        else bool(args.seq_parallel)
                    res = run_cell(arch, shape, multi_pod=mp,
                                   settings=settings,
                                   grad_compression=args.grad_compression,
                                   seq_parallel=sp, save_hlo=args.save_hlo,
                                   depth_override=depth)
                    if depth is not None:
                        res["depth_override"] = depth
                    status = "SKIP" if "skipped" in res else "OK"
                except Exception as e:                     # noqa: BLE001
                    res = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()}
                    status = "FAIL"
                    failures += 1
                path = os.path.join(args.out, tag + ".json")
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                extra = ""
                if status == "OK":
                    extra = (f" compile={res['compile_s']}s "
                             f"flops={res['cost'].get('flops', 0):.3e} "
                             f"coll={res['collectives']['total_bytes']:.3e}B")
                print(f"[{status}] {tag}{extra}", flush=True)
                if status == "SKIP":
                    break                      # skip both depths
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
