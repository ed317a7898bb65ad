"""Continuous-batching request scheduler over the paged KV cache.

The serving loop is the vLLM-style iteration-level scheduler: at EVERY
decode-step boundary, finished requests are evicted (their blocks go
back to the free list) and waiting requests are admitted FCFS up to
``max_batch`` — a new arrival never waits for the whole in-flight batch
to drain.  Prefill and decode are split: an admission runs its own
(B=1) prefill call, so long prompts never sit inside the batched decode
step that in-flight requests are latency-bound on.

Parity contract (tested): with greedy sampling, the token stream each
request receives from the scheduler — under any admission/eviction
interleaving — is BITWISE-identical to running ``ServeEngine.generate``
one-shot on that request alone.  The ingredients: per-request block
tables gather to the same dense (L, B, max_len, Hkv, dh) view a static
cache would hold (stale rows from reused blocks are masked to exactly
zero probability), and ``decode_step`` accepts per-slot (B,) positions
so staggered requests each attend at their own offset.

Collectives never appear here: the engine's prefill/decode closures own
the mesh, and any replica-level communication goes through
``plan()``/``as_spec`` (enforced by the ``serve-collectives-via-plan``
repo-lint rule).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from repro.obs import Spans

from .engine import ServeEngine, eos_done_mask
from .kv_cache import (BlockAllocator, OutOfBlocks, PagedKVCache,
                       blocks_per_request, scratch_table)


@dataclass
class Request:
    """One generation request and its scheduler-owned state."""

    rid: int
    tokens: np.ndarray            # (S,) prompt
    max_new_tokens: int
    eos_id: int | None = None
    # scheduler state --------------------------------------------------
    blocks: list[int] = field(default_factory=list)
    pos: int = 0                  # next decode position (prompt_len + emitted - 1)
    last_token: int = 0
    out: list[int] = field(default_factory=list)
    done: bool = False
    submitted: float = 0.0        # time.perf_counter() at submit

    @property
    def prompt_len(self) -> int:
        return int(self.tokens.shape[0])

    def emit(self, token: int) -> None:
        self.out.append(int(token))
        if len(self.out) >= self.max_new_tokens:
            self.done = True
        nxt, done = eos_done_mask(
            jnp.asarray([token], jnp.int32), jnp.asarray([self.done]),
            self.eos_id)
        self.done = bool(done[0])
        self.last_token = int(nxt[0])


@dataclass
class SchedulerStats(Spans):
    """The scheduler's counters and the totals of its spans.

    Span seconds (``seconds``, nested as the code nests): ``sched.step``
    holds ``sched.evict``, ``sched.admit`` (which holds one
    ``sched.prefill`` per admission, through its ``sched.write_prefill``
    and its first token), ``sched.gather`` (block tables and the
    dense view), ``sched.decode``, ``sched.write_token``, ``sched.sample``
    (the argmax and its transfer: where the host waits for the decode)
    and ``sched.emit`` (the per-slot loop)."""

    decode_steps: int = 0
    slot_steps: int = 0           # active slots summed over decode steps
    prefills: int = 0
    prefill_tokens: int = 0
    evictions: int = 0
    queue_wait_s: float = 0.0     # submit -> start of admission, summed
    queue_wait_max_s: float = 0.0

    @property
    def occupancy(self) -> float:
        """Mean active slots per decode step."""
        return self.slot_steps / self.decode_steps if self.decode_steps \
            else 0.0

    def summary(self) -> str:
        """The counters and the milliseconds per decode step by span,
        for an operator's log."""
        n = max(self.decode_steps, 1)
        wait = self.queue_wait_s / self.prefills if self.prefills else 0.0
        per_step = ", ".join(
            f"{k} {v / n * 1e3:.3f}" for k, v in sorted(
                self.seconds.items(), key=lambda kv: -kv[1]))
        return (f"{self.decode_steps} decode steps, mean occupancy "
                f"{self.occupancy:.2f} slots; {self.prefills} prefills "
                f"({self.prefill_tokens} prompt tokens), {self.evictions} "
                f"evictions; queue wait mean {wait * 1e3:.2f} ms, max "
                f"{self.queue_wait_max_s * 1e3:.2f} ms\n"
                f"ms per decode step by span: {per_step}")


class Scheduler:
    """FCFS continuous batching on one :class:`ServeEngine`.

    ``max_batch`` bounds the decode batch; every slot's KV lives in
    paged blocks sized ``kv_block_size`` (``engine.max_len`` must be a
    multiple).  ``num_blocks`` defaults to scratch + full occupancy.
    """

    def __init__(self, engine: ServeEngine, max_batch: int,
                 kv_block_size: int, num_blocks: int | None = None):
        self.engine = engine
        self.max_batch = max_batch
        self.blocks_per_req = blocks_per_request(engine.max_len,
                                                 kv_block_size)
        if num_blocks is None:
            num_blocks = 1 + max_batch * self.blocks_per_req
        self.alloc = BlockAllocator(num_blocks)
        self.kv = PagedKVCache.create(engine.model.cfg, num_blocks,
                                      kv_block_size)
        self.slots: list[Request | None] = [None] * max_batch
        self.waiting: deque[Request] = deque()
        self.finished: dict[int, np.ndarray] = {}
        self._next_rid = 0
        self.stats = SchedulerStats()

    @property
    def n_decode_steps(self) -> int:
        return self.stats.decode_steps

    @property
    def n_prefills(self) -> int:
        return self.stats.prefills

    # -- request intake ----------------------------------------------------

    def submit(self, tokens: np.ndarray, max_new_tokens: int,
               eos_id: int | None = None) -> int:
        """Queue a request; returns its id (results in ``finished``)."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.shape[0] + max_new_tokens > self.engine.max_len:
            raise ValueError(
                f"{tokens.shape[0]}+{max_new_tokens} exceeds cache "
                f"{self.engine.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        self.waiting.append(Request(rid=rid, tokens=tokens,
                                    max_new_tokens=max_new_tokens,
                                    eos_id=eos_id,
                                    submitted=time.perf_counter()))
        return rid

    @property
    def in_flight(self) -> int:
        return sum(r is not None for r in self.slots)

    @property
    def idle(self) -> bool:
        return not self.waiting and self.in_flight == 0

    # -- the decode-boundary state machine ---------------------------------

    def _evict_finished(self) -> None:
        for i, req in enumerate(self.slots):
            if req is not None and req.done:
                self.alloc.free(req.blocks)
                req.blocks = []
                self.finished[req.rid] = np.asarray(req.out, np.int32)
                self.slots[i] = None
                self.stats.evictions += 1

    def _admit(self) -> None:
        """FCFS admissions into free slots; each runs its own (B=1)
        prefill — in-flight decodes never wait inside a prompt pass —
        and samples its first token from the prefill logits, exactly as
        the one-shot generate loop does."""
        st = self.stats
        for i in range(self.max_batch):
            if not self.waiting or self.slots[i] is not None:
                continue
            try:
                blocks = self.alloc.alloc(self.blocks_per_req)
            except OutOfBlocks:
                return  # FCFS: later arrivals wait behind the head
            req = self.waiting.popleft()
            wait = time.perf_counter() - req.submitted
            st.queue_wait_s += wait
            st.queue_wait_max_s = max(st.queue_wait_max_s, wait)
            req.blocks = blocks
            # the pool write is queued before the first-token sync, so
            # the host queues it while the chip runs the prefill
            with st.span("sched.prefill", rid=req.rid,
                         prompt_len=req.prompt_len):
                cache, logits = self.engine.prefill_fn(
                    self.engine.params, jnp.asarray(req.tokens[None]), {})
                if "mamba" in cache:
                    raise NotImplementedError(
                        "paged scheduler covers attention-family caches "
                        "only")
                with st.span("sched.write_prefill"):
                    self.kv = self.kv.write_prefill(
                        blocks,
                        {"k": cache["k"][:, 0], "v": cache["v"][:, 0]})
                first = int(jnp.argmax(logits[0]))
            st.prefills += 1
            st.prefill_tokens += req.prompt_len
            req.pos = req.prompt_len
            req.emit(first)
            self.slots[i] = req
            if req.done:        # 1-token request (or instant eos)
                self._evict_finished()

    def step(self) -> None:
        """One decode-step boundary: evict, admit, then one batched
        decode over the active slots (inactive lanes run against the
        scratch block and are discarded)."""
        with self.stats.span("sched.step"):
            self._step()

    def _step(self) -> None:
        st = self.stats
        with st.span("sched.evict"):
            self._evict_finished()
        with st.span("sched.admit"):
            self._admit()
        active = [r for r in self.slots if r is not None]
        if not active:
            return
        with st.span("sched.gather"):
            token = np.zeros((self.max_batch,), np.int32)
            pos = np.zeros((self.max_batch,), np.int32)
            tables = np.stack([scratch_table(self.blocks_per_req)
                               for _ in range(self.max_batch)])
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                token[i] = req.last_token
                pos[i] = req.pos
                tables[i] = np.asarray(req.blocks, np.int32)
            dense = self.kv.gather(tables)
        with st.span("sched.decode"):
            new_cache, logits = self.engine.decode_fn(
                self.engine.params, dense, jnp.asarray(token),
                jnp.asarray(pos))
        with st.span("sched.write_token"):
            self.kv = self.kv.write_token(tables, new_cache, pos)
        st.decode_steps += 1
        st.slot_steps += len(active)
        with st.span("sched.sample"):
            nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        with st.span("sched.emit"):
            for i, req in enumerate(self.slots):
                if req is None:
                    continue
                req.pos += 1
                req.emit(int(nxt[i]))

    def run(self, max_steps: int | None = None) -> dict[int, np.ndarray]:
        """Drive step() until every submitted request finished (or
        ``max_steps`` boundaries elapsed); returns {rid: (n,) tokens}."""
        steps = 0
        while not self.idle:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        self._evict_finished()
        return self.finished
