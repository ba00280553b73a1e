#!/usr/bin/env python3
"""Serve internlm2-1.8b at its published widths on one TPU chip, once.

Drives ``ServingEngine`` through the steps of ``repro.launch.serve`` on
both paged-attention paths (``gather`` and the Pallas kernel), with
random weights from a seed, and checks that

* every request finishes with exactly its ``output_len`` tokens;
* on identical pages, one decode step gives the same logits on both
  paths;
* the prefill's last-token logits and the first decode step's logits,
  read through the paged cache, match the model's plain float32 forward
  pass over the same tokens.

It refuses to run where JAX's first device is not a TPU. Every time it
prints is a smoke figure from one cold run, compiles included, and not a
benchmark. Its last line is one JSON object naming the device; it is
printed only when every check passed.

Usage: python chip_smoke.py
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.configs import get_config  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model_zoo as zoo  # noqa: E402
from repro.serving import paged_model  # noqa: E402
from repro.serving.engine import ServingEngine  # noqa: E402

ARCH = "internlm2-1.8b"
SEED = 0
N_REQUESTS = 8          # one full batch: every prompt prefills in step 1
NUM_BLOCKS = 512

#: Tolerances on rel_err below: the largest over rows of
#: ||got - want|| / ||want|| across the logical vocabulary.
#: Readings at the published widths: the full 24 layers on a TPU v5e,
#: and cut to 4 layers on the CPU, with faults planted in a copy.
#:
#: The served model computes in bf16 (``ArchConfig.dtype``) and the
#: reference in float32 at "highest" matmul precision: 0.019-0.020 on
#: the chip, 0.016 on the CPU. The reference with its weights rounded
#: to float8_e4m3fn read 0.196-0.200 on the CPU, about 12x higher.
TOL_REF = 1e-1
#: The two attention paths read the same bf16 pages and differ in
#: summation order and in where attention rounds to bf16, so a rounding
#: flip in one layer carries through the rest: 0.0149 on the chip,
#: 0.0083 on the CPU. A kernel mask that drops the newest token read
#: 0.209 on the CPU. One that admits one unwritten slot read only 0.034,
#: inside this bound: that fault is left to the interpret-mode tests in
#: ``tests/test_kernels.py``, which catch it.
TOL_KERNEL = 5e-2


def rel_err(got, want, vocab: int) -> float:
    got = np.asarray(got, np.float64)[..., :vocab]
    want = np.asarray(want, np.float64)[..., :vocab]
    return float(np.max(np.linalg.norm(got - want, axis=-1)
                        / np.linalg.norm(want, axis=-1)))


def check_logits(eng: ServingEngine, ref_model) -> dict:
    """Errors of the paged paths, after the engine's first step (the
    prefill of every request, each with its first token sampled)."""
    model, params = eng.model, eng.params
    reqs = sorted(eng.running, key=lambda r: r.id)
    if len(reqs) != N_REQUESTS or any(r.tokens_generated != 1
                                       for r in reqs):
        raise RuntimeError("the first step did not prefill every request")

    # The decode token's KV slot, as step() appends it, then given back:
    # the engine's own next step appends it again.
    for r in reqs:
        eng.mem.append_tokens(r, 1)
    pages, toks = eng.decode_batch(reqs)
    for r in reqs:
        eng.mem.rollback_tokens(r, 1)
    decode = {path: paged_model.paged_decode_step(model, params, pages,
                                                  toks, path)[0]
              for path in ("gather", "pallas")}
    del pages

    prefill = []
    for r in reqs:
        plen = r.prompt_len
        padded = np.zeros((1, ServingEngine._bucket(plen)), np.int32)
        padded[0, :plen] = eng.prompt_tokens[r.id]
        prefill.append(paged_model.prefill_collect(
            model, params, jnp.asarray(padded), plen)[0])

    # Reference: prompt + first token, right-padded (causal attention
    # keeps the padding out of every real position).
    seqs = np.zeros((len(reqs), ServingEngine._bucket(
        max(r.prompt_len for r in reqs) + 1)), np.int32)
    for i, r in enumerate(reqs):
        seqs[i, :r.prompt_len] = eng.prompt_tokens[r.id]
        seqs[i, r.prompt_len] = eng.tokens_by_req[r.id][0]
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(zoo.forward, static_argnums=0)(
            ref_model, params, {"tokens": jnp.asarray(seqs)})[0]
    rows = jnp.arange(len(reqs))
    plens = jnp.asarray([r.prompt_len for r in reqs])
    ref_prefill, ref_decode = ref[rows, plens - 1], ref[rows, plens]

    n, vocab = len(reqs), model.plan.vocab_logical
    return {
        "pallas_vs_gather": rel_err(decode["pallas"][:n],
                                    decode["gather"][:n], vocab),
        "prefill_vs_f32_ref": rel_err(jnp.stack(prefill), ref_prefill,
                                      vocab),
        "decode_gather_vs_f32_ref": rel_err(decode["gather"][:n],
                                            ref_decode, vocab),
        "decode_pallas_vs_f32_ref": rel_err(decode["pallas"][:n],
                                            ref_decode, vocab),
    }


def run(model, params, ref_model) -> list:
    """Serve N_REQUESTS on each attention path; returns what failed."""
    failures = []
    for path in ("gather", "pallas"):
        eng = serve.make_engine(model, params, num_blocks=NUM_BLOCKS,
                                max_batch=N_REQUESTS, attn=path, seed=SEED)
        reqs = serve.make_requests(N_REQUESTS, seed=SEED)
        serve.submit(eng, reqs)
        t0 = time.perf_counter()
        eng.step()
        wall = time.perf_counter() - t0
        if path == "gather":
            errs = check_logits(eng, ref_model)
            for name, err in errs.items():
                tol = TOL_KERNEL if name == "pallas_vs_gather" else TOL_REF
                ok = err <= tol
                print(f"logits {name}: rel_err {err!r} tol {tol} "
                      f"{'ok' if ok else 'FAIL'}")
                if not ok:
                    failures.append(f"logits {name}")
        t0 = time.perf_counter()
        eng.run()
        wall += time.perf_counter() - t0
        short = [r.id for r in reqs
                 if not r.finished
                 or len(eng.tokens_by_req[r.id]) != r.output_len]
        if short:
            failures.append(f"{path}: requests {short} did not finish "
                            f"with output_len tokens")
        s = serve.summarize(eng, reqs)
        print(f"serve[{path}]: {s['n_finished']}/{len(reqs)} requests "
              f"finished ({len(reqs) - len(short)} with output_len tokens), "
              f"{sum(len(eng.tokens_by_req[r.id]) for r in reqs)} tokens "
              f"served in {s['iterations']} steps; smoke wall "
              f"{wall:.3f} s (cold, compiles included, not a benchmark)")
        del eng
        gc.collect()
    return failures


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    cache_dir = enable_compile_cache()
    cfg = get_config(ARCH)
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir}")
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype}")
    t0 = time.perf_counter()
    model, params = serve.build_model(ARCH, seed=SEED)
    jax.block_until_ready(params)
    print(f"params: {zoo.param_count(params)} ({cfg.param_dtype}), "
          f"init {time.perf_counter() - t0:.3f} s (smoke figure)")
    ref_model = zoo.build(cfg.with_overrides(dtype="float32"))

    failures = run(model, params, ref_model)

    stats = dev.memory_stats() or {}
    print(f"compile: {len(compile_s)} programs, {sum(compile_s):.3f} s "
          f"backend compile (smoke figure)")
    print(f"peak HBM: {stats.get('peak_bytes_in_use')} bytes of "
          f"{stats.get('bytes_limit')}")
    for f in failures:
        print(f"FAIL: {f}")
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
