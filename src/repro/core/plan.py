"""``plan()`` — compile a :class:`CollectiveSpec` into an executable plan.

This is the execute half of the plan/execute API (see ``core.spec``).  A
``CollectivePlan`` is everything Algorithm 1/2 precomputes before any data
moves, resolved ONCE per ``(spec, p, axis_name)`` and memoized:

* the resolved Corollary-2 skip sequence and per-round
  :class:`~repro.core.schedule.RoundPlan`s for both phases;
* per-round send/recv BLOCK INDEX TABLES — for every round, exactly which
  rotated block indices leave and arrive (Theorem 1's partition of the
  p-1 non-resident blocks, property-tested across all schedules);
* for non-uniform ``counts`` (paper Corollary 3), per-round ROW index
  tables: the per-rank gather/scatter row sets that pack each round's
  ragged send window into one fixed-width wire buffer (SPMD needs static
  shapes, so the wire width is the worst windowed count sum — exactly the
  quantity Corollary 3's bound maximizes over);
* for a p×p per-pair ``counts`` MATRIX (alltoallv, paper §4 ragged), an
  :class:`A2APlan`: seed/round/output row tables over the absolute
  (src, dst) pair layout, walking ``schedule.alltoall_moves`` — same
  one-ppermute-per-round discipline, Bruck hop amplification and all;
* the wire-format layout (int8 codes + packed scale bytes) and a backend
  from a small registry (``jnp``, ``fused``, ``jnp+int8``, ``fused+int8``,
  ``nonuniform``, plus the baseline kinds).

Execution (``plan.reduce_scatter(x)`` etc.) then just replays the tables:
one ``collective-permute`` per round, same HLO structure as the original
kwarg API (asserted by the conformance harness and the CI ``plans`` gate).

Plans are cached with ``functools.lru_cache`` — repeated calls with the
same spec are trace-time dict hits, so spec-driven dispatch adds zero
retraces and zero extra collectives.

Two execution modes share each backend's round steps:

* **one-shot** — ``plan.reduce_scatter(x)`` runs begin → q × (start →
  finish) → end in a single call (the classic API); and
* **multi-call (async)** — ``st = plan.rs_begin(x)`` hands the caller a
  :class:`RoundState`; each ``plan.start_round(st)`` issues EXACTLY ONE
  collective-permute and each ``plan.finish_round(st)`` does the local
  fold + next-send assembly (the seam the fused Pallas round kernel
  already separates — see ``kernels.fused_round``), with
  ``plan.rs_end(st)`` / ``plan.ag_end(st)`` extracting the result once
  all rounds are finished.  States of the SAME plan are independent, so
  a caller can interleave rounds of many payloads:
  ``plan.reduce_scatter_pipelined(xs)`` software-pipelines them so
  payload b's round-k ppermute sits between payload b-1's ppermute and
  fold in program order — independent dataflow chains XLA's scheduler
  can overlap.  The bucketed ZeRO-1 gradient sync
  (``optim.zero1``, ``GradSyncConfig.bucket_bytes``) rides this mode.

Async backend-registry contract (``_ASYNC_IMPLS``): a backend opts in by
registering an ops class per phase with ``begin`` / ``start`` /
``finish`` / ``end`` hooks.  ``start`` must issue exactly one
collective-permute and park the wire payload on ``RoundState.inflight``;
``finish`` must be collective-free (local fold + assembling the next
round's send buffer); the one-shot methods are thin drivers over the
same hooks, so both modes are bitwise-identical by construction.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import compat
from repro.kernels import (fused_round, fused_round_dq, pack_wire, pad2d,
                           permute_rows, quantize_rows, resolve_fused,
                           unpack_wire)
from repro.kernels import ref as _kref
from .schedule import (RoundPlan, allgather_plan, alltoall_moves,
                       reduce_scatter_plan)
from .spec import CollectiveSpec, as_spec

Array = jax.Array
ReduceFn = Callable[[Array, Array], Array]

_REDUCERS: dict[str, ReduceFn] = {
    "add": lambda a, b: a + b,
    "max": jnp.maximum,
    "min": jnp.minimum,
}

#: ops the scatter-fold (non-uniform) and fused/wire backends support.
NAMED_OPS = tuple(_REDUCERS)


def resolve_op(op) -> ReduceFn:
    """Named-or-callable ⊕ resolution (the single kwarg-era helper left;
    every backend goes through it)."""
    if callable(op):
        return op
    try:
        return _REDUCERS[op]
    except KeyError:
        raise ValueError(f"unknown reduce op {op!r}") from None


def _fwd_perm(p: int, s: int) -> list[tuple[int, int]]:
    """Data on rank i goes to rank (i + s) mod p  (paper's to-processor)."""
    return [(i, (i + s) % p) for i in range(p)]


def _bwd_perm(p: int, s: int) -> list[tuple[int, int]]:
    """Data on rank i goes to rank (i - s) mod p  (allgather phase)."""
    return [(i, (i - s) % p) for i in range(p)]


# ---------------------------------------------------------------------------
# Block layout — THE padding path (uniform and non-uniform share it)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BlockLayout:
    """Per-rank block row counts along the leading axis.

    The one place block geometry is derived from: ``pad_to_multiple`` /
    ``_as_blocks`` (uniform), the non-uniform row tables (Corollary 3),
    and the ZeRO-1 leaf padding all consume a layout instead of
    re-deriving ``ceil(n/p)`` locally.
    """

    counts: tuple[int, ...]

    @classmethod
    def uniform(cls, p: int, n: int) -> "BlockLayout":
        """Equal blocks of ``ceil(n/p)`` rows (zero-pad to fit)."""
        if p < 1:
            raise ValueError(f"p must be >= 1, got {p}")
        b = -(-n // p) if n else 0
        return cls(counts=(b,) * p)

    @property
    def p(self) -> int:
        return len(self.counts)

    @property
    def total(self) -> int:
        return sum(self.counts)

    @property
    def bmax(self) -> int:
        return max(self.counts)

    @property
    def offsets(self) -> tuple[int, ...]:
        """Row offset of each block (plus the total as a sentinel)."""
        off, acc = [], 0
        for c in self.counts:
            off.append(acc)
            acc += c
        off.append(acc)
        return tuple(off)

    @property
    def is_uniform(self) -> bool:
        return len(set(self.counts)) <= 1

    def pad(self, x: Array) -> tuple[Array, int]:
        """Zero-pad the leading axis of ``x`` up to ``total`` rows."""
        n = x.shape[0]
        pad = self.total - n
        if pad < 0:
            raise ValueError(
                f"input has {n} rows, layout holds only {self.total}")
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)], axis=0)
        return x, pad

    def as_blocks(self, x: Array) -> Array:
        """Reshape the leading axis into (p, bmax, *rest) — uniform only."""
        if not self.is_uniform:
            raise ValueError(
                f"non-uniform layout {self.counts} cannot reshape to "
                f"equal blocks; use the row tables")
        n, p = x.shape[0], self.p
        if n != self.total:
            raise ValueError(
                f"leading dim {n} not divisible by axis size {p}; pad first "
                f"(see pad_to_multiple)")
        return x.reshape(p, self.bmax, *x.shape[1:])

    def window_rows(self, window: Sequence[int]) -> np.ndarray:
        """Per-rank row index table for a rotated block window.

        Row ``r`` lists, in block order, the absolute row indices of
        blocks ``(r + i) mod p`` for ``i`` in ``window``, padded with the
        sentinel ``total`` (a dummy row) to the worst-case window width —
        the quantity Corollary 3's round bound maximizes over.
        """
        p, off, total = self.p, self.offsets, self.total
        widths = [sum(self.counts[(r + i) % p] for i in window)
                  for r in range(p)]
        W = max(widths) if widths else 0
        tab = np.full((p, max(W, 1)), total, dtype=np.int32)
        for r in range(p):
            j = 0
            for i in window:
                c = (r + i) % p
                tab[r, j:j + self.counts[c]] = np.arange(
                    off[c], off[c] + self.counts[c], dtype=np.int32)
                j += self.counts[c]
        return tab


# ---------------------------------------------------------------------------
# Alltoall(v) geometry — per-pair counts compiled to row tables
# ---------------------------------------------------------------------------

@dataclass(frozen=True, eq=False)
class A2APlan:
    """Trace-time geometry of a ragged alltoallv (per-pair ``counts``).

    The per-rank buffer holds the FULL absolute (src, dst) pair layout
    (``total`` rows + one sentinel row); each rank only ever populates the
    rows of entries it currently holds.  ``round_tables[k]`` is the
    ``(p, W_k)`` absolute-row table of round k: row r lists the buffer
    rows rank r gathers into the wire (its entries hopping this round,
    in ``alltoall_moves`` order), sentinel-padded to the worst windowed
    count sum ``W_k`` over ranks — SPMD needs one static wire shape, and
    that max is exactly the per-round quantity the Corollary 3 style
    bound maximizes over.  Sender and receiver store every entry at the
    same absolute rows, so the receive table of rank r is row
    ``(r - skip) mod p`` of the SAME table.
    """

    counts: tuple[tuple[int, ...], ...]   # [src][dst] rows
    pair_offsets: np.ndarray              # (p, p) absolute row of each pair
    total: int                            # sum of all counts
    send_total: tuple[int, ...]           # per-src row sum
    recv_total: tuple[int, ...]           # per-dst row sum
    in_height: int                        # static input rows: max send_total
    out_height: int                       # static output rows: max recv_total
    seed_src: np.ndarray                  # (p, in_height) input rows gathered
    seed_dst: np.ndarray                  # (p, in_height) buffer rows written
    round_tables: tuple[np.ndarray, ...]  # (p, W_k) wire gather/scatter rows
    out_rows: np.ndarray                  # (p, out_height) output gather rows

    @property
    def round_widths(self) -> tuple[int, ...]:
        """Per-round wire width (rows) — the worst windowed count sum."""
        return tuple(t.shape[1] for t in self.round_tables)


def _build_a2a(counts: tuple[tuple[int, ...], ...], p: int,
               schedule: str, group: int | None) -> A2APlan:
    moves = alltoall_moves(p, schedule, group)
    offs = np.zeros((p, p), np.int64)
    acc = 0
    for s in range(p):
        for dcol in range(p):
            offs[s, dcol] = acc
            acc += counts[s][dcol]
    total = acc
    send_total = tuple(sum(row) for row in counts)
    recv_total = tuple(sum(counts[s][dcol] for s in range(p))
                       for dcol in range(p))
    in_h = max(max(send_total), 1)
    out_h = max(max(recv_total), 1)

    # Seed: rank r's input rows (dst-ordered, rows [0, send_total[r]))
    # scatter into the absolute pair layout; sentinel-padded.
    seed_src = np.full((p, in_h), in_h, dtype=np.int32)   # input sentinel
    seed_dst = np.full((p, in_h), total, dtype=np.int32)  # buffer sentinel
    for r in range(p):
        j = 0
        for dcol in range(p):
            c = counts[r][dcol]
            seed_src[r, j:j + c] = np.arange(j, j + c, dtype=np.int32)
            seed_dst[r, j:j + c] = np.arange(
                offs[r, dcol], offs[r, dcol] + c, dtype=np.int32)
            j += c

    # Table widths come from the cost model's analytic bound (ONE
    # implementation of the worst-windowed-count-sum formula); the row
    # fill below would overrun a too-small width, so the CI width gate
    # stays a real consistency check rather than a copy comparing itself.
    from .cost_model import alltoallv_round_widths
    widths = alltoallv_round_widths(counts, schedule, group)
    tables = []
    for (_, moved), W in zip(moves, widths):
        tab = np.full((p, W), total, dtype=np.int32)
        for r in range(p):
            j = 0
            for d, m in moved:
                src = (r - m) % p
                dst = (src + d) % p
                c = counts[src][dst]
                tab[r, j:j + c] = np.arange(
                    offs[src, dst], offs[src, dst] + c, dtype=np.int32)
                j += c
            assert j <= W, (j, W)
        tables.append(tab)

    out_rows = np.full((p, out_h), total, dtype=np.int32)
    for r in range(p):
        j = 0
        for src in range(p):
            c = counts[src][r]
            out_rows[r, j:j + c] = np.arange(
                offs[src, r], offs[src, r] + c, dtype=np.int32)
            j += c
    return A2APlan(counts=counts, pair_offsets=offs, total=total,
                   send_total=send_total, recv_total=recv_total,
                   in_height=in_h, out_height=out_h,
                   seed_src=seed_src, seed_dst=seed_dst,
                   round_tables=tuple(tables), out_rows=out_rows)


# ---------------------------------------------------------------------------
# Multi-call (async) round protocol state
# ---------------------------------------------------------------------------

@dataclass(eq=False)
class RoundState:
    """In-trace state of one multi-call collective phase.

    Created by :meth:`CollectivePlan.rs_begin` / ``ag_begin`` and
    advanced by ``start_round`` / ``finish_round`` (which MUTATE the
    state in place and return it for chaining).  It holds traced arrays,
    so a state never escapes the trace that created it; the protocol
    order (start → finish per round, end only when ``done``) is enforced
    by the plan methods.

    phase:    ``"rs"`` (Algorithm 1) or ``"ag"`` (reversed skip stack).
    nrounds:  total rounds of the phase (0 for the p == 1 identity).
    k:        rounds fully finished so far.
    started:  a ``start_round`` is in flight, awaiting ``finish_round``.
    inflight: the ppermuted wire payload of the started round.
    data:     backend-private buffers (live/send blocks, packed wire,
              rank index, hooks) — owned by the ``_ASYNC_IMPLS`` ops.
    """

    plan: "CollectivePlan"
    phase: str
    nrounds: int
    k: int = 0
    started: bool = False
    inflight: object = None
    data: dict = field(default_factory=dict)

    @property
    def done(self) -> bool:
        """True once every round is finished (``end`` may be called)."""
        return self.k >= self.nrounds

    @property
    def round(self) -> RoundPlan:
        """The :class:`RoundPlan` of the round being started/finished."""
        rounds = (self.plan.rs_rounds if self.phase == "rs"
                  else self.plan.ag_rounds)
        return rounds[self.k]


# ---------------------------------------------------------------------------
# The compiled plan
# ---------------------------------------------------------------------------

@dataclass(frozen=True, eq=False)
class CollectivePlan:
    """Compiled, cached form of a :class:`CollectiveSpec` at axis size p.

    ``rs_send_blocks[k]`` / ``rs_recv_blocks[k]`` are the rotated block
    indices moved in reduce-scatter round k (``ag_*`` likewise for the
    reversed allgather phase); over all rounds the send sets partition
    ``{1, .., p-1}`` exactly (Theorem 1, property-tested).  For
    non-uniform counts, ``rs_row_tables[k]`` is the per-rank
    ``(p, W_k)`` absolute-row gather/scatter table realizing those block
    sets at row granularity.
    """

    spec: CollectiveSpec
    p: int
    axis_name: str
    backend: str
    skips: tuple[int, ...]
    rs_rounds: tuple[RoundPlan, ...]
    ag_rounds: tuple[RoundPlan, ...]
    rs_send_blocks: tuple[tuple[int, ...], ...]
    rs_recv_blocks: tuple[tuple[int, ...], ...]
    ag_send_blocks: tuple[tuple[int, ...], ...]
    ag_recv_blocks: tuple[tuple[int, ...], ...]
    layout: BlockLayout | None          # non-None iff flat spec.counts given
    rs_row_tables: tuple[np.ndarray, ...] | None
    ag_row_tables: tuple[np.ndarray, ...] | None
    a2a: A2APlan | None = None          # non-None iff matrix spec.counts

    # -- layout funnel -----------------------------------------------------

    def layout_for(self, n: int) -> BlockLayout:
        """The layout governing an ``n``-row payload under this plan."""
        if self.layout is not None:
            return self.layout
        return BlockLayout.uniform(self.p, n)

    # -- execution ---------------------------------------------------------

    def reduce_scatter(self, x: Array, *, compress=None,
                       decompress=None) -> Array:
        """Paper Algorithm 1 under this plan (one ppermute per round)."""
        self._check_hooks(compress, decompress)
        self._check_not_a2a("reduce_scatter")
        if self.backend in _BASELINE_RS:
            return _BASELINE_RS[self.backend](self, x)
        if self.p == 1:
            return x
        if self.backend == "nonuniform":
            return _rs_nonuniform(self, x)
        st = self.rs_begin(x, compress=compress, decompress=decompress)
        while not st.done:
            self.finish_round(self.start_round(st))
        return self.rs_end(st)

    def allgather(self, x: Array) -> Array:
        """Algorithm 2's second phase (reversed skip stack) standalone."""
        self._check_not_a2a("allgather")
        if self.backend in _BASELINE_AG:
            return _BASELINE_AG[self.backend](self, x)
        if self.p == 1:
            return x
        if self.backend == "nonuniform":
            return _ag_nonuniform(self, x)
        st = self.ag_begin(x)
        while not st.done:
            self.finish_round(self.start_round(st))
        return self.ag_end(st)

    def allreduce(self, x: Array, *, compress=None, decompress=None) -> Array:
        """Paper Algorithm 2: reduce-scatter + reversed allgather."""
        if self.backend in _BASELINE_AR:
            return _BASELINE_AR[self.backend](self, x)
        w = self.reduce_scatter(x, compress=compress, decompress=decompress)
        return self.allgather(w)

    def broadcast(self, x: Array) -> Array:
        """Round-optimal all-broadcast (Träff, arXiv:2407.18004).

        Every rank contributes its block ``x`` of shape ``(blk, *rest)``
        and receives ``(p*blk, *rest)`` — row-block j is rank j's
        contribution, bitwise-replicated on all ranks — in
        ``ceil(log2 p)`` rounds with exactly one ppermute per round.
        Structurally this is Algorithm 2's allgather phase run standalone
        (the reversed skip stack, no reduction ⊕), which is precisely the
        broadcast paper's schedule: with the root's message pre-scattered
        into p blocks, all-broadcast completes the root broadcast, and
        the round count meets the ceil(log2 p) lower bound at ANY p
        (a binomial tree double-delivers at non-powers of two).

        Weight fan-out to serving replicas (``serve/replica.py``) is the
        consumer: payloads move uncompressed (bit-exact), so
        ``wire_dtype`` and ``use_fused_kernel`` are rejected at spec
        construction.
        """
        self._check_not_a2a("broadcast")
        impl = _ASYNC_IMPLS.get((self.backend, "ag"))
        if impl is None:
            raise ValueError(
                f"backend {self.backend!r} does not implement broadcast; "
                f"use kind='broadcast' (or any uniform circulant backend)")
        if self.p == 1:
            return x
        # ag_begin's _check_async requires an "rs" impl (the paired-phase
        # protocol); the broadcast backend is AG-only, so open the state
        # directly and drive the shared round protocol.
        st = RoundState(plan=self, phase="ag", nrounds=len(self.ag_rounds))
        impl.begin(self, st, x)
        while not st.done:
            self.finish_round(self.start_round(st))
        return self.ag_end(st)

    def alltoall(self, x: Array) -> Array:
        """All-to-all by concatenation (paper §4): Algorithm 1 with ⊕ =
        concat.

        Uniform form (``counts=None``): ``x`` is ``(p, blk, *rest)``, row
        j is this rank's payload for rank j; returns the same shape with
        row j the payload FROM rank j.  Ragged form (p×p ``counts``
        matrix, MPI_Alltoallv): ``x`` is ``(in_height, *rest)`` — this
        rank's payload rows concatenated in destination order in rows
        ``[0, send_total[r])`` — and the result is ``(out_height, *rest)``
        with the received rows concatenated in source order, zeroed past
        this rank's receive total.  Backends come from the ``_A2A_IMPLS``
        registry (jnp / fused / alltoallv / xla baseline).
        """
        if self.spec.wired:
            raise NotImplementedError(
                "alltoall does not support wire_dtype (blocks hop through "
                "intermediate ranks; requantizing per hop would compound "
                "the error)")
        if self.layout is not None:
            raise NotImplementedError(
                "alltoall does not support flat (Corollary 3) counts; "
                "pass a p×p per-pair counts matrix for alltoallv")
        impl = _A2A_IMPLS.get(self.backend)
        if impl is None:
            raise ValueError(
                f"backend {self.backend!r} does not implement alltoall; "
                f"have {sorted(_A2A_IMPLS)}")
        if self.p == 1:
            return x
        return impl(self, x)

    # -- multi-call (async) round protocol ---------------------------------

    def rs_begin(self, x: Array, *, compress=None,
                 decompress=None) -> RoundState:
        """Open a multi-call reduce-scatter over ``x`` (async mode).

        Rotates ``x`` into block coordinates and assembles round 0's send
        payload without issuing any collective.  Drive the returned
        :class:`RoundState` with ``start_round`` / ``finish_round`` — one
        (ppermute, fold) pair per round — then ``rs_end``.  Supported on
        the uniform circulant backends (``jnp`` / ``fused`` and their
        ``+int8`` wire forms); baselines, non-uniform counts and
        alltoallv have no round seam to expose and raise.
        """
        self._check_hooks(compress, decompress)
        self._check_not_a2a("rs_begin")
        self._check_async("rs_begin")
        if self.p == 1:
            return RoundState(plan=self, phase="rs", nrounds=0,
                              data={"identity": x})
        _check_wire_payload(self, x)
        st = RoundState(plan=self, phase="rs", nrounds=len(self.rs_rounds))
        _ASYNC_IMPLS[(self.backend, "rs")].begin(self, st, x,
                                                 compress, decompress)
        return st

    def ag_begin(self, x: Array) -> RoundState:
        """Open a multi-call allgather of block ``x`` — see
        :meth:`rs_begin` (allgather replays the skips in reverse and has
        no reduction, so ``finish_round`` is a pure buffer write)."""
        self._check_not_a2a("ag_begin")
        self._check_async("ag_begin")
        if self.p == 1:
            return RoundState(plan=self, phase="ag", nrounds=0,
                              data={"identity": x})
        _check_wire_payload(self, x)
        st = RoundState(plan=self, phase="ag", nrounds=len(self.ag_rounds))
        _ASYNC_IMPLS[(self.backend, "ag")].begin(self, st, x)
        return st

    def start_round(self, st: RoundState) -> RoundState:
        """Issue round ``st.k``'s single collective-permute.

        The wire payload (already assembled by ``begin`` or the previous
        ``finish_round``) is permuted onto ``st.inflight``; no local fold
        happens here, so work independent of this payload — another
        bucket's ``finish_round``, the next layer's backward — can sit
        between ``start_round`` and ``finish_round`` in program order.
        Mutates and returns ``st``.
        """
        if st.plan is not self:
            raise ValueError("RoundState belongs to a different plan")
        if st.done:
            raise ValueError(
                f"{st.phase} phase complete: all {st.nrounds} rounds "
                f"finished (call {st.phase}_end)")
        if st.started:
            raise ValueError(
                f"round {st.k} already started; call finish_round() first")
        _ASYNC_IMPLS[(self.backend, st.phase)].start(self, st)
        st.started = True
        return st

    def finish_round(self, st: RoundState) -> RoundState:
        """Fold round ``st.k``'s received payload and assemble the next
        round's send buffer (collective-free — the fused backend runs
        both in one Pallas pass).  Mutates and returns ``st``."""
        if st.plan is not self:
            raise ValueError("RoundState belongs to a different plan")
        if not st.started:
            raise ValueError(
                f"round {st.k} has no ppermute in flight; call "
                f"start_round() first")
        _ASYNC_IMPLS[(self.backend, st.phase)].finish(self, st)
        st.inflight = None
        st.started = False
        st.k += 1
        return st

    def rs_end(self, st: RoundState) -> Array:
        """Extract the reduced block once every RS round is finished."""
        return self._phase_end(st, "rs")

    def ag_end(self, st: RoundState) -> Array:
        """Extract the gathered (rank-ordered) buffer once every AG round
        is finished."""
        return self._phase_end(st, "ag")

    def _phase_end(self, st: RoundState, phase: str) -> Array:
        if st.plan is not self:
            raise ValueError("RoundState belongs to a different plan")
        if st.phase != phase:
            raise ValueError(
                f"state is mid-{st.phase}, not {phase} (use {st.phase}_end)")
        if st.started or not st.done:
            left = st.nrounds - st.k
            raise ValueError(
                f"{phase}_end with {left} round(s) unfinished "
                f"(started={st.started})")
        if "identity" in st.data:
            return st.data["identity"]
        return _ASYNC_IMPLS[(self.backend, phase)].end(self, st)

    def reduce_scatter_pipelined(self, xs: Sequence[Array], *,
                                 compress=None, decompress=None
                                 ) -> list[Array]:
        """Reduce-scatter many independent payloads with round-level
        software pipelining (the bucketed grad-sync driver).

        All payloads share this plan (same p / schedule / backend, so the
        same round count q); total collectives = ``len(xs) * q`` — exactly
        one ppermute per payload per round, same as running each payload
        alone.  The emitted program order is double-buffered: payload
        b's round-k ppermute is issued BEFORE payload b-1's round-k fold,
        so each fold sits between two independent collectives and the
        XLA latency-hiding scheduler can overlap them.
        """
        sts = [self.rs_begin(x, compress=compress, decompress=decompress)
               for x in xs]
        return self._run_pipelined(sts, "rs")

    def allgather_pipelined(self, xs: Sequence[Array]) -> list[Array]:
        """Allgather counterpart of :meth:`reduce_scatter_pipelined`."""
        return self._run_pipelined([self.ag_begin(x) for x in xs], "ag")

    def _run_pipelined(self, sts: list[RoundState], phase: str
                       ) -> list[Array]:
        q = max((st.nrounds for st in sts), default=0)
        for _ in range(q):
            prev = None
            for st in sts:
                self.start_round(st)
                if prev is not None:
                    self.finish_round(prev)
                prev = st
            if prev is not None:
                self.finish_round(prev)
        end = self.rs_end if phase == "rs" else self.ag_end
        return [end(st) for st in sts]

    # -- validation helpers ------------------------------------------------

    def _check_async(self, fn: str) -> None:
        if (self.backend, "rs") not in _ASYNC_IMPLS:
            supported = sorted({b for (b, _) in _ASYNC_IMPLS})
            raise NotImplementedError(
                f"backend {self.backend!r} has no multi-call round "
                f"protocol ({fn}); async-capable backends: {supported}")

    def _check_not_a2a(self, fn: str) -> None:
        if self.a2a is not None:
            raise ValueError(
                f"a p×p per-pair counts matrix is alltoall(v)-only; "
                f"{fn} takes flat per-rank counts (Corollary 3)")

    def _check_hooks(self, compress, decompress) -> None:
        if compress is None and decompress is None:
            return
        if self.spec.wired:
            raise ValueError(
                "wire_dtype and compress/decompress hooks are mutually "
                "exclusive")
        if self.backend == "nonuniform":
            raise ValueError(
                "compress/decompress hooks do not support non-uniform "
                "counts")
        if self.spec.kind != "circulant":
            raise ValueError(
                f"compress/decompress hooks need kind='circulant' "
                f"(per-round payloads), got {self.spec.kind!r}")


def _check_wire_payload(plan: CollectivePlan, x: Array) -> None:
    """int8 wire needs float payloads (quantization grid); checked at
    execution because the spec is payload-agnostic."""
    if plan.spec.wired and not jnp.issubdtype(x.dtype, jnp.floating):
        raise ValueError(
            f"wire_dtype='int8' needs a float payload, got {x.dtype}")


# ---------------------------------------------------------------------------
# plan(): spec -> CollectivePlan, memoized
# ---------------------------------------------------------------------------

_BASELINE_KINDS = ("ring", "recursive_halving", "xla")


def _resolve_backend(spec: CollectiveSpec) -> str:
    """Backend registry key for a spec (the one place the kwarg-era
    ``_resolve_op``/``_check_wire`` decision tables live on)."""
    if spec.kind in _BASELINE_KINDS:
        return spec.kind
    if spec.kind == "broadcast":
        # Spec validation already rejected wire_dtype / use_fused_kernel;
        # counts= requires kind='circulant', so nothing else to check.
        return "broadcast"
    if spec.counts_matrix:
        if spec.wire_dtype is not None:
            raise ValueError(
                "alltoallv (per-pair counts) does not support wire_dtype "
                "(blocks hop through intermediate ranks; requantizing per "
                "hop would compound the error)")
        if spec.use_fused_kernel is True:
            raise ValueError(
                "use_fused_kernel does not support per-pair counts (the "
                "ragged wire is table-gathered, not slot-stacked)")
        return "alltoallv"
    if spec.counts is not None:
        if spec.wire_dtype is not None:
            raise ValueError(
                "non-uniform counts and wire_dtype cannot be combined yet "
                "(quantization groups would straddle ragged blocks)")
        if spec.use_fused_kernel is True:
            raise ValueError(
                "use_fused_kernel does not support non-uniform counts "
                "(the fused round kernel assumes equal blocks)")
        if spec.op not in NAMED_OPS:
            raise ValueError(
                f"non-uniform counts need a named op {NAMED_OPS}, "
                f"got {spec.op!r}")
        return "nonuniform"
    if spec.wire_dtype is not None:
        if not isinstance(spec.op, str):
            raise ValueError(
                f"wire_dtype needs a named op ('add'/'max'/'min'), "
                f"got {spec.op!r}")
        return ("fused+int8" if resolve_fused(spec.use_fused_kernel)
                else "jnp+int8")
    if resolve_fused(spec.use_fused_kernel):
        if not isinstance(spec.op, str):
            if spec.use_fused_kernel:
                # Explicit request only — auto silently keeps the jnp path.
                raise ValueError(
                    "use_fused_kernel needs a named op ('add'/'max'/'min'), "
                    f"got callable {spec.op!r}")
            return "jnp"
        return "fused"
    return "jnp"


class _PlanCache:
    """LRU memo for compiled plans with SELECTIVE invalidation.

    ``functools.lru_cache`` almost suffices, but the elastic runtime
    (ft/elastic.py) resizes the live world and wants to evict every plan
    compiled for a rank set that no longer exists — both as memory
    hygiene across many resize events and as a hard guarantee that no
    consumer keeps executing a plan whose ``p`` predates the re-plan.
    Same observable API as the lru_cache it replaces: ``info()`` returns
    a CacheInfo-shaped tuple (hits/misses/maxsize/currsize) and entries
    are identical objects across hits (``plan(s, ...) is plan(s, ...)``).
    """

    class CacheInfo(tuple):
        """hits / misses / maxsize / currsize, attribute-accessible."""
        __slots__ = ()

        def __new__(cls, hits, misses, maxsize, currsize):
            return tuple.__new__(cls, (hits, misses, maxsize, currsize))

        hits = property(lambda s: s[0])
        misses = property(lambda s: s[1])
        maxsize = property(lambda s: s[2])
        currsize = property(lambda s: s[3])

        def __repr__(self):
            return (f"CacheInfo(hits={s[0]}, misses={s[1]}, "
                    f"maxsize={s[2]}, currsize={s[3]})"
                    if (s := tuple(self)) else "CacheInfo()")

    def __init__(self, maxsize: int = 4096):
        self.maxsize = maxsize
        self._data: dict = {}
        self._hits = 0
        self._misses = 0

    def get(self, key, build):
        try:
            val = self._data.pop(key)
            self._data[key] = val  # re-insert: LRU recency order
            self._hits += 1
            return val
        except KeyError:
            self._misses += 1
            val = build()
            self._data[key] = val
            while len(self._data) > self.maxsize:
                self._data.pop(next(iter(self._data)))
            return val

    def info(self):
        return self.CacheInfo(self._hits, self._misses, self.maxsize,
                              len(self._data))

    def clear(self):
        self._data.clear()
        self._hits = self._misses = 0

    def invalidate(self, p: int | None = None,
                   axis_name: str | None = None) -> int:
        """Evict every cached plan matching the given filters (``None``
        matches everything); returns the number evicted."""
        doomed = [k for k in self._data
                  if (p is None or k[1] == p)
                  and (axis_name is None or k[2] == axis_name)]
        for k in doomed:
            del self._data[k]
        return len(doomed)


_PLAN_CACHE = _PlanCache(maxsize=4096)


def _plan_cached(spec: CollectiveSpec, p: int, axis_name: str
                 ) -> CollectivePlan:
    return _PLAN_CACHE.get((spec, p, axis_name),
                           lambda: _build_plan(spec, p, axis_name))


def _build_plan(spec: CollectiveSpec, p: int, axis_name: str
                ) -> CollectivePlan:
    backend = _resolve_backend(spec)
    if spec.kind in _BASELINE_KINDS:
        return CollectivePlan(
            spec=spec, p=p, axis_name=axis_name, backend=backend,
            skips=(), rs_rounds=(), ag_rounds=(),
            rs_send_blocks=(), rs_recv_blocks=(),
            ag_send_blocks=(), ag_recv_blocks=(),
            layout=None, rs_row_tables=None, ag_row_tables=None)

    rs = reduce_scatter_plan(p, spec.schedule, spec.group)
    ag = allgather_plan(p, spec.schedule, spec.group)
    rs_send = tuple(tuple(range(pl.lo, pl.hi)) for pl in rs)
    rs_recv = tuple(tuple(range(0, pl.nblocks)) for pl in rs)
    ag_send = tuple(tuple(range(0, pl.nblocks)) for pl in ag)
    ag_recv = tuple(tuple(range(pl.lo, pl.hi)) for pl in ag)

    layout = rs_tables = ag_tables = a2a = None
    if spec.counts is not None:
        if len(spec.counts) != p:
            raise ValueError(
                f"counts has {len(spec.counts)} entries for axis size {p}")
        if spec.counts_matrix:
            a2a = _build_a2a(spec.counts, p, spec.schedule, spec.group)
        else:
            layout = BlockLayout(counts=spec.counts)
            rs_tables = tuple(layout.window_rows(w) for w in rs_send)
            ag_tables = tuple(layout.window_rows(w) for w in ag_send)

    return CollectivePlan(
        spec=spec, p=p, axis_name=axis_name, backend=backend,
        skips=tuple(pl.skip for pl in rs), rs_rounds=rs, ag_rounds=ag,
        rs_send_blocks=rs_send, rs_recv_blocks=rs_recv,
        ag_send_blocks=ag_send, ag_recv_blocks=ag_recv,
        layout=layout, rs_row_tables=rs_tables, ag_row_tables=ag_tables,
        a2a=a2a)


def plan(spec: CollectiveSpec | None = None, p: int | None = None,
         axis_name: str | None = None, **kw) -> CollectivePlan:
    """Compile ``spec`` for axis ``axis_name`` of size ``p`` (cached).

    ``p`` may be omitted inside a shard_map region (resolved from the
    axis).  Bare kwargs build the spec in place::

        plan(p=8, axis_name="x", schedule="power2").reduce_scatter(x)
    """
    spec = as_spec(spec, **kw)
    if axis_name is None:
        raise ValueError("plan() needs an axis_name")
    if p is None:
        p = compat.axis_size(axis_name)
    return _plan_cached(spec, int(p), axis_name)


# Cache introspection rides on plan() itself: ``plan.cache_stats()`` /
# ``plan.clear()`` / ``plan.invalidate(p=..., axis_name=...)``.  All
# proxy the one _PlanCache behind _plan_cached, so an identity assertion
# like ``plan(s, ...) is plan(s, ...)`` plus a hits/misses delta from
# cache_stats() observes the same cache the elastic controller evicts
# from after a world resize.
plan.cache_stats = _PLAN_CACHE.info
plan.clear = _PLAN_CACHE.clear
plan.invalidate = _PLAN_CACHE.invalidate


def plan_cache_info():
    """Deprecated alias — use ``plan.cache_stats()``."""
    return plan.cache_stats()


def plan_cache_clear() -> None:
    """Deprecated alias — use ``plan.clear()``."""
    plan.clear()


# ---------------------------------------------------------------------------
# Uniform circulant backends — multi-call round ops (the one-shot round
# loops of the kwarg era, split at the (start = ppermute) / (finish =
# fold + next-send assembly) seam; identical round structure, ppermute
# sequence and arithmetic in both modes)
# ---------------------------------------------------------------------------

def _rotated_blocks(plan: CollectivePlan, x: Array) -> Array:
    """Rotate ``x`` into block coordinates: R[i] = block of rank (r+i)."""
    r = lax.axis_index(plan.axis_name)
    return jnp.roll(plan.layout_for(x.shape[0]).as_blocks(x), -r, axis=0)


#: One TPU vector lane row: the minor width of the rotated-row view.
_LANES = 128


def _roll_rows(x: Array, shift) -> Array:
    """``jnp.roll(x, shift, axis=0)`` for a (p, ...) array, rotated through
    a (p, numel / 128, 128) view when the rows allow it.  The TPU
    compiler relays out a rotated many-dimensional block, and a leaf
    reshaped straight from (rows, d) to (p, rows / p * d), row by row:
    minutes of compile time at an embedding's row count."""
    if int(np.prod(x.shape[1:])) % _LANES:
        return jnp.roll(x, shift, axis=0)
    return jnp.roll(x.reshape(x.shape[0], -1, _LANES), shift,
                    axis=0).reshape(x.shape)


def _rotated_rows(plan: CollectivePlan, x: Array) -> tuple[Array, tuple]:
    """:func:`_rotated_blocks` as a (p, block numel) matrix, and the block
    shape."""
    r = lax.axis_index(plan.axis_name)
    blocks = plan.layout_for(x.shape[0]).as_blocks(x)
    rows = blocks.reshape(plan.p, -1)
    return _roll_rows(rows, -r), blocks.shape[1:]


class _RsJnp:
    """Algorithm 1's rounds, plain jnp ops (always available).

    State: the shrinking rotated block buffer ``R``; round k sends
    ``R[lo:hi]`` and folds the received blocks into ``R[:nblocks]``.
    """

    @staticmethod
    def begin(plan, st, x, compress, decompress):
        st.data.update(R=_rotated_blocks(plan, x),
                       compress=compress, decompress=decompress)

    @staticmethod
    def start(plan, st):
        pl = st.round
        payload = st.data["R"][pl.lo:pl.hi]
        if st.data["compress"] is not None:
            payload = st.data["compress"](payload)
        st.inflight = compat.ppermute(payload, plan.axis_name,
                                      _fwd_perm(plan.p, pl.skip))

    @staticmethod
    def finish(plan, st):
        pl, T = st.round, st.inflight
        if st.data["decompress"] is not None:
            T = st.data["decompress"](T)
        R, nb = st.data["R"], pl.nblocks
        head = resolve_op(plan.spec.op)(R[:nb], T)
        st.data["R"] = head if nb == pl.lo else jnp.concatenate(
            [head, R[nb:pl.lo]], axis=0)

    @staticmethod
    def end(plan, st):
        return st.data["R"][0]


class _RsFused:
    """Algorithm 1's rounds on the fused Pallas kernel.

    The rotated block buffer is viewed as 2-D ``(blocks, block_numel)``;
    after the prologue slice every round is ppermute → fused_round, with
    the kernel emitting both the shrunken live buffer and the next
    round's contiguous send payload — the fold/assembly split that makes
    ``finish`` collective-free.  Identical values and ppermute sequence
    to the jnp path — only the local data movement is fused.
    """

    @staticmethod
    def begin(plan, st, x, compress, decompress):
        R2, blk_shape = _rotated_rows(plan, x)
        first = plan.rs_rounds[0]
        st.data.update(blk_shape=blk_shape,
                       live=R2[: first.lo],
                       send=R2[first.lo: first.hi],
                       compress=compress, decompress=decompress)

    @staticmethod
    def start(plan, st):
        payload = (st.data["send"] if st.data["compress"] is None
                   else st.data["compress"](st.data["send"]))
        st.inflight = compat.ppermute(payload, plan.axis_name,
                                      _fwd_perm(plan.p, st.round.skip))

    @staticmethod
    def finish(plan, st):
        pl, T, live = st.round, st.inflight, st.data["live"]
        if st.data["decompress"] is not None:
            T = st.data["decompress"](T)
        if T.dtype != live.dtype:
            # Match the jnp path, whose concatenate promotes the buffer
            # (e.g. bf16 live vs f32 decompressed payload).
            dt = jnp.result_type(live.dtype, T.dtype)
            live, T = live.astype(dt), T.astype(dt)
        plans = plan.rs_rounds
        next_lo = plans[st.k + 1].lo if st.k + 1 < len(plans) else pl.lo
        live, send = fused_round(live, T, nb=pl.nblocks, next_lo=next_lo,
                                 op=plan.spec.op)
        st.data.update(live=live, send=send)

    @staticmethod
    def end(plan, st):
        return st.data["live"][0].reshape(st.data["blk_shape"])


class _RsWire:
    """Algorithm 1's rounds on the int8 wire format.

    The rotated block buffer is promoted to an f32 (blocks, block_numel)
    accumulation buffer whose columns are padded to a whole number of
    quantization groups.  Every round then ppermutes ONE packed int8
    buffer ([codes | scale bytes], see kernels.quantize) and runs a
    single dequantize + ⊕-fold + requantize-next-send pass — the Pallas
    ``fused_round_dq`` kernel on the fused backend, its jnp oracle
    otherwise (bitwise-identical arithmetic; both jitted).  Round count
    and ppermute sequence match the uncompressed path exactly.
    """

    @staticmethod
    def begin(plan, st, x, compress, decompress):
        fused = plan.backend == "fused+int8"
        R2, blk_shape = _rotated_rows(plan, x)
        R2 = R2.astype(jnp.float32)
        cols = R2.shape[1]
        g = min(plan.spec.wire_group, cols)
        R2 = pad2d(R2, 1, g)
        first_round = plan.rs_rounds[0]
        first = R2[first_round.lo: first_round.hi]
        if fused:
            codes, scales = quantize_rows(first, group=g)
        else:
            codes, scales = _kref.quantize_ref(first, group=g)
        st.data.update(blk_shape=blk_shape, out_dtype=x.dtype,
                       cols=cols, g=g, fused=fused,
                       live=R2[: first_round.lo],
                       wire=pack_wire(codes, scales))

    @staticmethod
    def start(plan, st):
        st.inflight = compat.ppermute(
            st.data["wire"], plan.axis_name,
            _fwd_perm(plan.p, st.round.skip))

    @staticmethod
    def finish(plan, st):
        pl, live, g = st.round, st.data["live"], st.data["g"]
        rc, rs = unpack_wire(st.inflight, live.shape[1], group=g)
        plans = plan.rs_rounds
        next_lo = plans[st.k + 1].lo if st.k + 1 < len(plans) else pl.lo
        kern = fused_round_dq if st.data["fused"] else _kref.fused_round_dq_ref
        live, send = kern(live, rc, rs, nb=pl.nblocks, next_lo=next_lo,
                          op=plan.spec.op, group=g)
        st.data["live"] = live
        if send is not None:
            st.data["wire"] = pack_wire(*send)

    @staticmethod
    def end(plan, st):
        out = st.data["live"][0]
        cols = st.data["cols"]
        if cols != out.shape[0]:
            out = out[:cols]
        return out.reshape(st.data["blk_shape"]).astype(st.data["out_dtype"])


class _AgPlain:
    """Allgather rounds, uncompressed (backends ``jnp`` and ``fused``).

    Allgather has no ⊕, so its fused form needs no Pallas: the growing
    concat chain (which recopies the whole buffer every round — O(p log p)
    block traffic) becomes static in-place updates of one preallocated
    (p, blk) buffer (O(p) traffic; XLA turns the static-index
    dynamic-update-slice into an in-place write under jit).  Send payloads
    are buffer prefixes, already contiguous.
    """

    @staticmethod
    def begin(plan, st, x):
        r = lax.axis_index(plan.axis_name)
        fused = plan.backend == "fused"
        if fused:
            buf = jnp.zeros((plan.p, *x.shape), x.dtype)
            buf = lax.dynamic_update_slice_in_dim(buf, x[None], 0, axis=0)
        else:
            buf = x[None]  # (1, blk, *rest): rotated, R[i] = block of (r+i)
        st.data.update(buf=buf, r=r, fused=fused, blk=x.shape)

    @staticmethod
    def start(plan, st):
        pl, buf = st.round, st.data["buf"]
        payload = (lax.slice_in_dim(buf, 0, pl.nblocks, axis=0)
                   if st.data["fused"] else buf[:pl.nblocks])
        st.inflight = compat.ppermute(payload, plan.axis_name,
                                      _bwd_perm(plan.p, pl.skip))

    @staticmethod
    def finish(plan, st):
        pl, T, buf = st.round, st.inflight, st.data["buf"]
        if st.data["fused"]:
            # Received blocks land at rows [lo, hi) = [skip, prev bound).
            st.data["buf"] = lax.dynamic_update_slice_in_dim(
                buf, T, pl.lo, axis=0)
        else:
            st.data["buf"] = jnp.concatenate([buf, T], axis=0)

    @staticmethod
    def end(plan, st):
        blk = st.data["blk"]
        # Un-rotate: out[j] = block of rank j.
        out = jnp.roll(st.data["buf"], st.data["r"], axis=0)
        return out.reshape(plan.p * blk[0], *blk[1:])


class _AgWire:
    """Allgather rounds on the int8 wire format.

    Allgather has no ⊕, so each rank quantizes its own block ONCE; the
    rounds then move the packed int8 wire rows unmodified (every element
    is quantized exactly once — the error is a single quantization step).
    The fused backend selects the preallocated-buffer round structure
    (static in-place updates) vs the concat chain — both move identical
    bytes and one ppermute per round.  All ranks dequantize the same
    codes, so the gathered result is bitwise-replicated (Theorem 2's
    invariant survives compression).
    """

    @staticmethod
    def begin(plan, st, x):
        fused = plan.backend == "fused+int8"
        r = lax.axis_index(plan.axis_name)
        x2 = x.reshape(1, -1).astype(jnp.float32)
        cols = x2.shape[1]
        g = min(plan.spec.wire_group, cols)
        x2 = pad2d(x2, 1, g)
        if fused:
            codes, scales = quantize_rows(x2, group=g)
        else:
            codes, scales = _kref.quantize_ref(x2, group=g)
        row = pack_wire(codes, scales)                 # (1, wc) int8
        if fused:
            buf = jnp.zeros((plan.p, row.shape[1]), jnp.int8)
            buf = lax.dynamic_update_slice_in_dim(buf, row, 0, axis=0)
        else:
            buf = row
        st.data.update(buf=buf, r=r, fused=fused, g=g, cols=cols,
                       padded_cols=x2.shape[1], blk=x.shape,
                       out_dtype=x.dtype)

    # Rounds move the packed int8 rows exactly like the plain path.
    start = staticmethod(_AgPlain.start)
    finish = staticmethod(_AgPlain.finish)

    @staticmethod
    def end(plan, st):
        g, cols, blk = st.data["g"], st.data["cols"], st.data["blk"]
        codes, scales = unpack_wire(st.data["buf"], st.data["padded_cols"],
                                    group=g)
        vals = _kref.dequant_ref(codes, scales, group=g)  # (p, cols_pad) f32
        if cols != st.data["padded_cols"]:
            vals = vals[:, :cols]
        out = _roll_rows(vals, st.data["r"])  # out[j] = block of j
        return (out.reshape(plan.p * blk[0], *blk[1:])
                .astype(st.data["out_dtype"]))


#: async backend registry — (backend, phase) → round-step ops.  The
#: contract: ``begin`` assembles round 0's send payload (no collective),
#: ``start`` issues exactly one collective-permute onto
#: ``RoundState.inflight``, ``finish`` is collective-free fold +
#: next-send assembly, ``end`` extracts the phase result.  Backends
#: absent here (nonuniform, alltoallv, baselines) only run one-shot.
_ASYNC_IMPLS: dict[tuple[str, str], type] = {
    ("jnp", "rs"): _RsJnp,
    ("fused", "rs"): _RsFused,
    ("jnp+int8", "rs"): _RsWire,
    ("fused+int8", "rs"): _RsWire,
    ("jnp", "ag"): _AgPlain,
    ("fused", "ag"): _AgPlain,
    ("jnp+int8", "ag"): _AgWire,
    ("fused+int8", "ag"): _AgWire,
    # kind="broadcast" (Träff arXiv:2407.18004) is the AG phase run
    # standalone: no ("broadcast", "rs") entry exists on purpose — the
    # plan's only operation is CollectivePlan.broadcast.
    ("broadcast", "ag"): _AgPlain,
}


# ---------------------------------------------------------------------------
# All-to-all by concatenation (paper §4)
# ---------------------------------------------------------------------------

def _a2a_jnp(plan: CollectivePlan, x: Array) -> Array:
    """Bruck-style rounds: trace-time bookkeeping keeps, per live slot,
    the list of (source-offset, array) pairs — the concatenation operator
    materialized as Python lists of same-shaped arrays, so every round is
    still a single fused ppermute over a stacked payload.  Volume is
    (p/2)*ceil(log2 p) blocks per rank (the classic Bruck trade-off:
    round-optimal, not volume-optimal)."""
    p = plan.p
    r = lax.axis_index(plan.axis_name)
    rot = jnp.roll(x, -r, axis=0)  # rot[i] = payload for dest (r+i)
    # slots[i]: list of (offset o, payload) — payload originated at (r+o).
    slots: list[list[tuple[int, Array]]] = [[(0, rot[i])] for i in range(p)]
    for pl in plan.rs_rounds:
        s = pl.skip
        # Stack every array sent this round into ONE ppermute payload.
        send_entries = [e for i in range(pl.lo, pl.hi) for e in slots[i]]
        stacked = jnp.stack([a for (_, a) in send_entries], axis=0)
        T = compat.ppermute(stacked, plan.axis_name, _fwd_perm(p, s))
        # Unstack with shifted source offsets; ⊕ = list concatenation.
        idx = 0
        for j in range(pl.nblocks):
            src_slot = pl.lo + j
            for (o, _) in slots[src_slot]:
                slots[j].append((((o - s) % p), T[idx]))
                idx += 1
        assert idx == len(send_entries)
        del slots[pl.lo:]  # slots [lo, hi) were sent; live = [0, s)
    entries = slots[0]
    assert len(entries) == p, f"expected {p} payloads, got {len(entries)}"
    ordered = [a for (_, a) in sorted(entries, key=lambda e: e[0])]
    stacked = jnp.stack(ordered, axis=0)  # stacked[o] = payload from (r+o)
    return jnp.roll(stacked, r, axis=0)   # row j = payload from rank j


def _a2a_fused(plan: CollectivePlan, x: Array) -> Array:
    """Bruck-style rounds over stacked slot buffers (fused alltoall).

    slots[i] is one (count_i, blk) array; offs[i] is the parallel Python
    list of source offsets.  Entry order inside each slot matches the
    unfused list-of-arrays path exactly, so results are bitwise-equal.
    """
    p = plan.p
    r = lax.axis_index(plan.axis_name)
    blk_shape = x.shape[1:]
    rot = jnp.roll(x, -r, axis=0)
    rot2 = rot.reshape(p, -1)
    slots = [lax.slice_in_dim(rot2, i, i + 1, axis=0) for i in range(p)]
    offs: list[list[int]] = [[0] for _ in range(p)]
    for pl in plan.rs_rounds:
        s = pl.skip
        send = (slots[pl.lo] if pl.nblocks == 1 else
                jnp.concatenate(slots[pl.lo:pl.hi], axis=0))
        T = compat.ppermute(send, plan.axis_name, _fwd_perm(p, s))
        idx = 0
        for j in range(pl.nblocks):
            src_slot = pl.lo + j
            cnt = len(offs[src_slot])
            piece = lax.slice_in_dim(T, idx, idx + cnt, axis=0)
            slots[j] = jnp.concatenate([slots[j], piece], axis=0)
            offs[j] = offs[j] + [(o - s) % p for o in offs[src_slot]]
            idx += cnt
        assert idx == T.shape[0]
        del slots[pl.lo:], offs[pl.lo:]
    assert slots[0].shape[0] == p, \
        f"expected {p} payloads, got {slots[0].shape[0]}"
    order = sorted(range(p), key=lambda i: offs[0][i])
    ordered = permute_rows(slots[0], order)  # ordered[o] = from (r+o)
    out = jnp.roll(ordered, r, axis=0)       # row j = payload from rank j
    return out.reshape(p, *blk_shape)


def _a2a_v(plan: CollectivePlan, x: Array) -> Array:
    """Ragged alltoallv over the per-pair counts matrix.

    Same table discipline as the Corollary 3 reduce-scatter: the buffer
    stays in ABSOLUTE (src, dst) pair order, round k gathers this rank's
    hopping rows through ``a2a.round_tables[k]`` into one fixed-width
    wire buffer (width = the worst windowed count sum over ranks),
    ppermutes it once, and scatter-SETS the received rows through the
    sender's view of the same table (no ⊕ — payloads move verbatim, so
    any dtype works).  Exactly one collective-permute per round —
    ``ceil(log2 p)`` for the optimal schedules, ragged counts included.

    Input ``(in_height, *rest)``: rank r's payload rows, concatenated in
    destination order, in rows ``[0, send_total[r])``.  Output
    ``(out_height, *rest)``: received rows concatenated in source order,
    zeroed past ``recv_total[r]`` (SPMD shapes are rank-invariant;
    callers slice with their static count when they know it).
    """
    a2a, p = plan.a2a, plan.p
    if x.shape[0] != a2a.in_height:
        raise ValueError(
            f"input has {x.shape[0]} rows, counts matrix needs "
            f"in_height={a2a.in_height} (= max per-rank send total)")
    r = lax.axis_index(plan.axis_name)
    blk_shape = x.shape[1:]
    x2 = x.reshape(a2a.in_height, -1)
    cols = x2.shape[1]
    # Input sentinel row (read by seed padding) and buffer sentinel row
    # (written by wire padding, read by gather padding; never data).
    xpad = jnp.concatenate([x2, jnp.zeros((1, cols), x2.dtype)], axis=0)
    buf = jnp.zeros((a2a.total + 1, cols), x2.dtype)
    buf = buf.at[_take_row(a2a.seed_dst, r)].set(
        jnp.take(xpad, _take_row(a2a.seed_src, r), axis=0))
    for k, pl in enumerate(plan.rs_rounds):
        table = a2a.round_tables[k]
        send_rows = _take_row(table, r)
        payload = jnp.take(buf, send_rows, axis=0)
        T = compat.ppermute(payload, plan.axis_name, _fwd_perm(p, pl.skip))
        # Sender (r - skip) gathered exactly the rows this rank must
        # store — both address the same absolute pair layout, so the
        # receive table IS the sender's row of the send table.
        recv_rows = _take_row(table, (r - pl.skip) % p)
        buf = buf.at[recv_rows].set(T)
    out = jnp.take(buf, _take_row(a2a.out_rows, r), axis=0)
    cnt = _take_row(np.asarray(a2a.recv_total, np.int32), r)
    mask = jnp.arange(a2a.out_height) < cnt
    out = jnp.where(mask.reshape(-1, *([1] * (out.ndim - 1))), out, 0)
    return out.reshape(a2a.out_height, *blk_shape)


# ---------------------------------------------------------------------------
# Non-uniform counts (paper Corollary 3) — gather/scatter over row tables
# ---------------------------------------------------------------------------

def _take_row(table: np.ndarray, idx) -> Array:
    """Row ``idx`` (traced rank expression) of a trace-time-constant
    table — one dynamic-slice, no gather fan-out."""
    return lax.dynamic_index_in_dim(jnp.asarray(table), idx, axis=0,
                                    keepdims=False)


def _scatter_fold(buf: Array, rows: Array, T: Array, op: str) -> Array:
    """Fold received wire rows into the buffer at ``rows``.  Real indices
    are unique within a round (each wire row is a distinct (column,
    offset) pair); padding rows all target the dummy sentinel row, which
    is never read back as data."""
    if op == "add":
        return buf.at[rows].add(T)
    if op == "max":
        return buf.at[rows].max(T)
    if op == "min":
        return buf.at[rows].min(T)
    raise ValueError(f"non-uniform counts need a named op, got {op!r}")


def _rs_nonuniform(plan: CollectivePlan, x: Array) -> Array:
    """Corollary 3: reduce-scatter with per-rank block sizes.

    The buffer stays in ABSOLUTE column order (no physical rotation —
    blocks have different sizes, so rotation is encoded in the row
    tables instead).  Round k gathers this rank's rows for the rotated
    send window into a fixed-width wire buffer (width = the worst
    windowed count sum over ranks — SPMD needs one static shape, and
    that max is exactly the per-round quantity Corollary 3 bounds),
    ppermutes it once, and scatter-⊕s the received rows through the
    receiving rank's view of the same table.  Exactly one
    collective-permute per round — Theorem 1's ceil(log2 p) rounds
    survive ragged counts unchanged.

    Input: ``(sum(counts), *rest)`` per rank.  Output:
    ``(max(counts), *rest)`` — this rank's reduced block in rows
    ``[0, counts[r])``, zero rows above (SPMD output shapes must be
    rank-invariant; callers slice with their static count when they
    know it).
    """
    layout, p, op = plan.layout, plan.p, plan.spec.op
    N, bmax = layout.total, layout.bmax
    if x.shape[0] != N:
        raise ValueError(
            f"input has {x.shape[0]} rows, counts {layout.counts} "
            f"need {N}")
    if p == 1:
        return x
    r = lax.axis_index(plan.axis_name)
    blk_shape = x.shape[1:]
    x2 = x.reshape(N, -1)
    cols = x2.shape[1]
    # Row N is the dummy sentinel: padding gathers read it, padding
    # scatters accumulate into it; it is never read back as data.
    buf = jnp.concatenate([x2, jnp.zeros((1, cols), x2.dtype)], axis=0)
    for k, pl in enumerate(plan.rs_rounds):
        table = plan.rs_row_tables[k]
        send_rows = _take_row(table, r)
        payload = jnp.take(buf, send_rows, axis=0)
        T = compat.ppermute(payload, plan.axis_name, _fwd_perm(p, pl.skip))
        # Sender (r - skip) packed exactly the columns this rank must
        # fold — and both store column c at the same absolute rows, so
        # the receive table IS the sender's row of the send table.
        recv_rows = _take_row(table, (r - pl.skip) % p)
        buf = _scatter_fold(buf, recv_rows, T, op)
    # Extract rows [off_r, off_r + counts[r]), padded to bmax and masked.
    ext = jnp.concatenate(
        [buf[:N], jnp.zeros((bmax, cols), x2.dtype)], axis=0)
    start = _take_row(np.asarray(layout.offsets[:p], np.int32), r)
    out = lax.dynamic_slice_in_dim(ext, start, bmax, axis=0)
    cnt = _take_row(np.asarray(layout.counts, np.int32), r)
    mask = jnp.arange(bmax) < cnt
    out = jnp.where(mask.reshape(bmax, *([1] * (out.ndim - 1))), out, 0)
    return out.reshape(bmax, *blk_shape)


def _ag_nonuniform(plan: CollectivePlan, x: Array) -> Array:
    """Allgather(v): inverse layout of :func:`_rs_nonuniform`.

    Input: ``(max(counts), *rest)`` — this rank's block in rows
    ``[0, counts[r])``.  Output: ``(sum(counts), *rest)``, all blocks in
    rank order, identical on every rank (no ⊕ — blocks move verbatim, so
    replication is bitwise).
    """
    layout, p = plan.layout, plan.p
    N, bmax = layout.total, layout.bmax
    if x.shape[0] != bmax:
        raise ValueError(
            f"input has {x.shape[0]} rows, counts {layout.counts} "
            f"need max(counts) = {bmax}")
    if p == 1:
        return x
    r = lax.axis_index(plan.axis_name)
    blk_shape = x.shape[1:]
    x2 = x.reshape(bmax, -1)
    cols = x2.shape[1]
    counts, offs = layout.counts, layout.offsets
    # Seed the (N + sentinel) buffer with this rank's own rows.
    src = np.full((p, bmax), bmax, dtype=np.int32)      # x2 row (or dummy)
    dst = np.full((p, bmax), N, dtype=np.int32)         # buf row (or dummy)
    for rr in range(p):
        src[rr, : counts[rr]] = np.arange(counts[rr], dtype=np.int32)
        dst[rr, : counts[rr]] = np.arange(
            offs[rr], offs[rr] + counts[rr], dtype=np.int32)
    xpad = jnp.concatenate([x2, jnp.zeros((1, cols), x2.dtype)], axis=0)
    buf = jnp.zeros((N + 1, cols), x2.dtype)
    buf = buf.at[_take_row(dst, r)].set(jnp.take(xpad, _take_row(src, r),
                                                 axis=0))
    for k, pl in enumerate(plan.ag_rounds):
        table = plan.ag_row_tables[k]
        send_rows = _take_row(table, r)
        payload = jnp.take(buf, send_rows, axis=0)
        T = compat.ppermute(payload, plan.axis_name, _bwd_perm(p, pl.skip))
        # Received from (r + skip): its send window covers exactly the
        # columns this rank is missing at rotated [skip, prev) — same
        # absolute rows, so the receive table is the sender's row.
        recv_rows = _take_row(table, (r + pl.skip) % p)
        buf = buf.at[recv_rows].set(T)
    return buf[:N].reshape(N, *blk_shape)


# ---------------------------------------------------------------------------
# Baseline backends (ring / recursive_halving / xla) — lazy import of the
# implementations in core.collectives (which imports this module)
# ---------------------------------------------------------------------------

def _baseline(fn_name: str):
    def run(plan: CollectivePlan, x: Array) -> Array:
        from repro.core import collectives as C
        fn = getattr(C, fn_name)
        return fn(x, plan.axis_name, op=plan.spec.op)
    return run


_BASELINE_RS = {
    "ring": _baseline("ring_reduce_scatter"),
    "recursive_halving": _baseline("recursive_halving_reduce_scatter"),
    "xla": _baseline("xla_reduce_scatter"),
}
_BASELINE_AR = {
    "ring": _baseline("ring_allreduce"),
    "xla": _baseline("xla_allreduce"),
}
_BASELINE_AG = {
    "xla": _baseline("xla_allgather"),
}
#: alltoall registry — the uniform circulant loops (lifted from the old
#: special cases in CollectivePlan.alltoall), the ragged table backend,
#: and XLA's native all-to-all as the A/B baseline.
_A2A_IMPLS = {
    "jnp": _a2a_jnp,
    "fused": _a2a_fused,
    "alltoallv": _a2a_v,
    "xla": _baseline("xla_alltoall"),
}

#: backend registry — what plan() can resolve a spec onto, and which
#: collectives each backend implements (introspection for the CI gate
#: and the docs; execution dispatches on the plan's ``backend`` field).
BACKENDS: dict[str, tuple[str, ...]] = {
    "jnp": ("reduce_scatter", "allgather", "allreduce", "alltoall"),
    "fused": ("reduce_scatter", "allgather", "allreduce", "alltoall"),
    "jnp+int8": ("reduce_scatter", "allgather", "allreduce"),
    "fused+int8": ("reduce_scatter", "allgather", "allreduce"),
    "nonuniform": ("reduce_scatter", "allgather", "allreduce"),
    "alltoallv": ("alltoall",),
    "broadcast": ("broadcast",),
    "ring": ("reduce_scatter", "allreduce"),
    "recursive_halving": ("reduce_scatter",),
    "xla": ("reduce_scatter", "allgather", "allreduce", "alltoall"),
}
