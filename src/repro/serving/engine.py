"""Real JAX serving engine: continuous batching + paged KV cache.

This is the ground-truth system the simulator is validated against (the
role vLLM/A100 plays in the paper).  Crucially it reuses the *same*
``BlockManager`` and ``ContinuousBatching`` scheduler classes as the
simulator, so structural validation (identical batch/memory traces) is a
meaningful exact test, and its measured iteration times calibrate the
simulator's ``TabularBackend`` for temporal validation.

Families: attention archs run the paged path (pages + block tables +
gather/pallas attention); SSM/hybrid/enc-dec run slot-based contiguous
caches (their decode state is O(1) or fixed — nothing to page).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel.operators import BatchMix
from repro.core.mem.block_manager import BlockManager, MemoryConfig
from repro.core.mem.memory_pool import MemoryPool
from repro.core.request import Request, State
from repro.core.sched.local import make_local_scheduler
from repro.models import model_zoo as zoo
from repro.serving import paged_model
from repro.serving.sampling import sample_token

#: host spans of one ``step()``, on the profiler's clock: the iteration
#: (a step trace event numbered by the step's index) and, inside it, the
#: plan with its bookkeeping, the inputs with the programs' launch, the
#: sampling with its device-to-host syncs, and the records and emission
SPANS = ITERATION, PLAN, PREPARE, SAMPLE, EMIT = (
    "engine.iteration", "engine.plan", "engine.prepare", "engine.sample",
    "engine.emit")


@dataclass
class EngineConfig:
    num_blocks: int = 256
    block_size: int = 16
    max_batch: int = 8
    max_batched_tokens: int = 2048
    max_pages_per_seq: int = 32
    local_policy: str = "continuous"
    attn_path: str = "gather"            # gather | pallas
    greedy: bool = True
    temperature: float = 1.0
    seed: int = 0
    max_mem_ratio: float = 1.0


@dataclass
class IterationRecord:
    mix: BatchMix
    wall: float
    batch_ids: Tuple[int, ...]
    kind: str                            # prefill | decode


class ServingEngine:
    def __init__(self, model: zoo.Model, params, ec: EngineConfig,
                 pool: Optional[MemoryPool] = None, discipline=None):
        self.model = model
        self.params = params
        self.ec = ec
        #: tenant-aware queue ordering (repro.core.tenancy.qos); None=FIFO
        self.discipline = discipline
        self.paged = paged_model.supports_paged(model)
        if self.paged:
            paged_model.check_attn_path(model, ec.attn_path)

        mc = MemoryConfig(num_blocks=ec.num_blocks,
                          block_size=ec.block_size,
                          kv_bytes_per_token=1.0,
                          watermark=max(0.0, 1.0 - ec.max_mem_ratio))
        # scheduler shim state (same classes as the simulator's Worker)
        self.mem = BlockManager(mc)
        self.pool = pool
        self.waiting: deque = deque()
        self.running: List[Request] = []
        self.sched = make_local_scheduler(
            ec.local_policy, max_batch=ec.max_batch,
            max_batched_tokens=ec.max_batched_tokens)

        self.max_ctx = ec.max_pages_per_seq * ec.block_size
        if self.paged:
            # physical page `num_blocks` is the trash page for padded slots
            self.pages = paged_model.init_pages(
                model, ec.num_blocks + 1, ec.block_size, ec.max_batch,
                ec.max_pages_per_seq)
            self.trash_page = ec.num_blocks
        else:
            self.cache = zoo.init_cache(model, ec.max_batch, self.max_ctx)
            self.slot_of: Dict[int, int] = {}
            self.free_slots = list(range(ec.max_batch))[::-1]

        self.tokens_by_req: Dict[int, List[int]] = {}
        self.prompt_tokens: Dict[int, np.ndarray] = {}
        self.clock = 0.0                 # virtual time (sum of iter walls)
        self.records: List[IterationRecord] = []
        self.finished: List[Request] = []
        self._key = jax.random.key(ec.seed)

    # ------------------------------------------------------------------
    def add_request(self, req: Request, prompt_tokens=None) -> None:
        if prompt_tokens is None:
            rng = np.random.RandomState(req.id % (2 ** 31))
            prompt_tokens = rng.randint(
                0, self.model.plan.vocab_logical,
                size=(req.prompt_len,)).astype(np.int32)
        assert req.prompt_len + req.output_len <= self.max_ctx, \
            (req.prompt_len, req.output_len, self.max_ctx)
        self.prompt_tokens[req.id] = np.asarray(prompt_tokens, np.int32)
        self.tokens_by_req[req.id] = []
        req.state = State.WAITING
        self.waiting.append(req)

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # -- waiting-queue protocol shared with core.worker.Worker ---------
    def next_waiting(self) -> Optional[Request]:
        if not self.waiting:
            return None
        if self.discipline is None:
            return self.waiting[0]
        return self.discipline.select(self.waiting, self.clock)

    def pop_waiting(self, req: Request) -> None:
        self.waiting.remove(req)

    def victim_sort_key(self):
        if self.discipline is None:
            return lambda r: (r.arrival_time, r.id)
        return self.discipline.victim_key(self.clock)

    # ------------------------------------------------------------------
    def step(self) -> Optional[IterationRecord]:
        with jax.profiler.StepTraceAnnotation(ITERATION,
                                              step_num=len(self.records)):
            with jax.profiler.TraceAnnotation(PLAN):
                plan = self.sched.plan(self)
                if plan.empty:
                    return None
                self._start_plan(plan)

            t0 = time.perf_counter()
            if plan.prefill:
                self._run_prefill(plan)
                kind = "prefill"
                batch = tuple(r.id for r, _, _ in plan.prefill)
            else:
                self._run_decode(plan)
                kind = "decode"
                batch = tuple(r.id for r in plan.decode)
            wall = time.perf_counter() - t0

            with jax.profiler.TraceAnnotation(EMIT):
                mix = BatchMix.from_batch(
                    [(c, b) for _, c, b in plan.prefill],
                    [r.context_len for r in plan.decode])
                self.clock += wall
                rec = IterationRecord(mix=mix, wall=wall, batch_ids=batch,
                                      kind=kind)
                self.records.append(rec)

                now = self.clock
                for req, chunk, _ in plan.prefill:
                    req.prefill_done_len = max(req.cached_len,
                                               req.prefill_done_len) + chunk
                    if req.remaining_prefill == 0:
                        self._emit(req, now)
                for req in plan.decode:
                    self._emit(req, now)
            return rec

    def _start_plan(self, plan) -> None:
        """Admission and preemption bookkeeping of ``plan``, and the
        decode rows' new slots in the block manager."""
        for req in plan.admitted:
            req.state = State.PREFILL if req.remaining_prefill else \
                State.DECODE
            if req not in self.running:
                self.running.append(req)
            if self.discipline is not None:
                self.discipline.on_service_start(req, self.clock)
            if self.paged:
                pass                     # block table comes from self.mem
            else:
                self.slot_of[req.id] = self.free_slots.pop()
        for req in plan.preempted:
            req.state = State.PREEMPTED
            if req in self.running:
                self.running.remove(req)
            if not self.paged and req.id in self.slot_of:
                self.free_slots.append(self.slot_of.pop(req.id))
            self.waiting.appendleft(req)

        for req in plan.decode:
            self.mem.append_tokens(req, 1)

    def run(self, max_steps: int = 1_000_000) -> None:
        steps = 0
        while self.has_work and steps < max_steps:
            if self.step() is None:
                break
            steps += 1

    # ------------------------------------------------------------------
    def _emit(self, req: Request, now: float) -> None:
        first = req.tokens_generated == 0
        req.tokens_generated += 1
        req.token_times.append(now)
        if first:
            req.t_first_token = now
        req.state = State.DECODE
        if req.finished:
            req.state = State.FINISHED
            req.t_finish = now
            self.running.remove(req)
            self.mem.free(req)
            if self.pool is not None:
                self.pool.store(req.session_id, req.context_len)
            if not self.paged:
                self.free_slots.append(self.slot_of.pop(req.id))
            self.finished.append(req)

    # -- prefill -----------------------------------------------------------
    def _full_sequence(self, req: Request) -> np.ndarray:
        return np.concatenate([
            self.prompt_tokens[req.id],
            np.asarray(self.tokens_by_req[req.id], np.int32)])

    @staticmethod
    def _bucket(n: int) -> int:
        """Pad prompt lengths to power-of-two buckets so the jit cache
        holds O(log max_ctx) prefill programs, not one per length."""
        return max(8, 1 << (int(n) - 1).bit_length())

    def _run_prefill(self, plan) -> None:
        for req, chunk, ctx in plan.prefill:
            with jax.profiler.TraceAnnotation(PREPARE):
                last_logits, plen = self._prefill_one(req, ctx + chunk)
            with jax.profiler.TraceAnnotation(SAMPLE):
                tok = self._sample(last_logits)
                self.tokens_by_req[req.id].append(tok)
            self._slot_write_len(req, plen)

    def _prefill_one(self, req: Request, upto: int):
        """Prefill ``req``'s first ``upto`` tokens into its pages (or its
        slot); returns the last position's logits and the length."""
        seq = self._full_sequence(req)[:upto]
        plen = int(seq.shape[0])
        spad = min(self._bucket(plen), self.max_ctx)
        padded = np.zeros((1, spad), np.int32)
        padded[0, :plen] = seq
        toks = jnp.asarray(padded)
        if self.paged:
            last_logits, kv = paged_model.prefill_collect(
                self.model, self.params, toks, plen)
            table = np.full((self.ec.max_pages_per_seq,),
                            self.trash_page, np.int32)
            blocks = self.mem.block_table(req)
            table[:len(blocks)] = blocks
            self.pages = paged_model.scatter_prefill(
                self.model, self.pages, kv, jnp.asarray(table), plen)
        else:
            slot = self.slot_of[req.id]
            cache1 = zoo.init_cache(self.model, 1, self.max_ctx)
            batch = {"tokens": toks}
            if self.model.cfg.family in ("audio", "encdec"):
                batch["embeds"] = self._enc_embeds(req)[None]
            logits, cache1 = self._prefill_slot_fn(
                self.model, self.params, batch, cache1)
            last_logits = logits[0, plen - 1]
            self._write_slot(slot, cache1, plen)
        return last_logits, plen

    _prefill_slot_fn = staticmethod(
        jax.jit(zoo.prefill, static_argnums=0))
    _decode_slot_fn = staticmethod(
        jax.jit(zoo.decode_step, static_argnums=0))

    def _enc_embeds(self, req: Request):
        rng = np.random.RandomState((req.id + 7919) % (2 ** 31))
        return jnp.asarray(rng.randn(
            self.model.cfg.enc_seq_len,
            self.model.cfg.d_model).astype(np.float32))

    def _write_slot(self, slot: int, cache1, length: int) -> None:
        """Copy a single-request contiguous cache into batch slot."""
        def upd(dst, src):
            if dst.ndim >= 2 and dst.shape[1] == self.ec.max_batch:
                return dst.at[:, slot].set(src[:, 0])
            return dst
        for key in self.cache:
            if key == "len":
                continue
            self.cache[key] = upd(self.cache[key], cache1[key])

    def _slot_write_len(self, req: Request, length: int) -> None:
        if not self.paged:
            slot = self.slot_of[req.id]
            self.cache["len"] = self.cache["len"].at[slot].set(length)

    # -- decode ------------------------------------------------------------
    def _run_decode(self, plan) -> None:
        reqs = plan.decode
        if self.paged:
            with jax.profiler.TraceAnnotation(PREPARE):
                pages, toks = self.decode_batch(reqs)
                logits, self.pages = paged_model.paged_decode_step(
                    self.model, self.params, pages, toks, self.ec.attn_path)
            rows = range(len(reqs))
        else:
            with jax.profiler.TraceAnnotation(PREPARE):
                toks = np.zeros((self.ec.max_batch,), np.int32)
                lens = np.array(self.cache["len"])
                for r in reqs:
                    slot = self.slot_of[r.id]
                    toks[slot] = self._current_token(r)
                    lens[slot] = r.context_len - 1
                self.cache["len"] = jnp.asarray(lens)
                logits, self.cache = self._decode_slot_fn(
                    self.model, self.params, self.cache, jnp.asarray(toks))
            rows = [self.slot_of[r.id] for r in reqs]
        with jax.profiler.TraceAnnotation(SAMPLE):
            for r, i in zip(reqs, rows):
                self.tokens_by_req[r.id].append(self._sample(logits[i]))

    def decode_batch(self, reqs: List[Request]):
        """Inputs of one paged decode step over ``reqs``: the page store
        with their block tables and lengths, and their current tokens.
        Rows past ``len(reqs)`` are padding that writes the trash page."""
        bsz = self.ec.max_batch
        tables = np.full((bsz, self.ec.max_pages_per_seq),
                         self.trash_page, np.int32)
        lens = np.zeros((bsz,), np.int32)
        toks = np.zeros((bsz,), np.int32)
        for i, r in enumerate(reqs):
            bt = self.mem.block_table(r)
            tables[i, :len(bt)] = bt
            lens[i] = r.context_len - 1      # KV before this token
            toks[i] = self._current_token(r)
        pages = {**self.pages, "tables": jnp.asarray(tables),
                 "len": jnp.asarray(lens)}
        return pages, jnp.asarray(toks)

    def _current_token(self, req: Request) -> int:
        gen = self.tokens_by_req[req.id]
        if gen:
            return int(gen[-1])
        return int(self.prompt_tokens[req.id][-1])

    def _sample(self, logits) -> int:
        self._key, sub = jax.random.split(self._key)
        return int(sample_token(logits, sub, greedy=self.ec.greedy,
                                temperature=self.ec.temperature,
                                vocab_logical=self.model.plan.vocab_logical))
