"""ZeRO-1 distributed optimizer driven by the paper's collectives.

This is the framework's primary integration of Träff's algorithms: every
(large) gradient leaf is REDUCE-SCATTERED (Algorithm 1) across the data
axes along its leading dimension, AdamW updates only the local 1/(pod*data)
shard (optimizer state is never replicated — the ZeRO-1 memory win), and
updated parameter shards are ALLGATHERED back with the reversed schedule
(Algorithm 2's second phase).  Per step and per rank this moves exactly
2(p-1)/p of the gradient volume in 2*ceil(log2 p) collective-permute
rounds per leaf — Theorem 2's optimum.

PER-LEAF, not flat-raveled: leaves keep their tensor-parallel (model-axis)
sharding on inner dimensions — a ravel would force an all-gather over the
model axis and materialize full fp32 gradients per rank (168 GB for a 42B
model).  The leading dim (the layer-stack axis for scanned blocks, vocab
for embeddings) is zero-padded to a multiple of the DP world and sliced
back after the allgather.  Leaves too small to shard profitably (norms,
biases, scalars) are synchronized with a plain psum and updated
replicated — they are <0.1% of parameters.

Grad-sync implementations are pluggable (--grad-sync):
  circulant[:schedule]  paper Algorithm 1/2 (halving default; power2 /
                        fully_connected / sqrt per Corollary 2)
  ring                  p-1-round bandwidth baseline
  xla                   lax.psum_scatter + lax.all_gather
  allreduce             plain replicated allreduce + full optimizer
                        (no ZeRO; memory baseline)
The config compiles to CollectiveSpecs (``GradSyncConfig.rs_spec()`` /
``.ag_spec()``); each data axis executes one cached CollectivePlan, so
the grad sync rides the same plan/execute seam as every other consumer.
Optional compressed gradient sync via wire_dtype='int8' (the circulant
collectives' packed int8 wire format: per-round quantize-on-send + fused
dequant-⊕ rounds) with an EF-SGD error-feedback residual carried in the
optimizer state so convergence is preserved; ``use_fused_kernel`` routes
the circulant rounds' local fold + send assembly through the fused Pallas
round kernel (kernels.fused_round).

Shard layout per leaf: axis-major blocks over ``axis_names`` order —
rank (r0, r1) holds rows [lin * ld_pad/P, (lin+1) * ld_pad/P) with
lin = r0 * p1 + r1; the matching hierarchical AG reassembles exactly.
"""
from __future__ import annotations

import functools
import math
import warnings
from dataclasses import dataclass
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import compat
from repro.core import collectives as C
from repro.core.spec import CollectiveSpec
from repro.kernels import dequantize_blocks, quantize_blocks
from . import adamw


@dataclass(frozen=True)
class GradSyncConfig:
    """How zero1 synchronizes gradients and re-gathers parameter shards.

    The config is declarative: it compiles to :class:`CollectiveSpec`
    objects (:meth:`rs_spec` / :meth:`ag_spec`) and every knob maps onto
    a spec field or a zero1-side policy.  Fields:

    ``impl``
        Sync algorithm: ``'circulant'`` (paper Algorithm 1/2; the only
        impl that supports wire compression and bucketing), ``'ring'``
        (p-1-round bandwidth baseline), ``'xla'`` (psum_scatter /
        all_gather), or ``'allreduce'`` (replicated allreduce + full
        optimizer — the no-ZeRO memory baseline).
    ``schedule``
        Corollary-2 skip schedule for the circulant impl: ``'halving'``
        (default), ``'power2'``, ``'fully_connected'``, ``'sqrt'``.
    ``wire_dtype``
        ``None`` (exact) or ``'int8'``: compress every circulant round's
        send payload onto the packed int8 wire (codes + f32 group scales
        in one buffer; ~4x fewer β bytes, lossy).
    ``compress``
        DEPRECATED alias for ``wire_dtype`` (kept for the kwarg era;
        emits a DeprecationWarning).
    ``error_feedback``
        EF-SGD residual for compressed sync: each rank keeps its local
        quantization error in ``Zero1State.ef`` and adds it back into
        the next step's gradient before quantizing.  Only meaningful
        when the sync is actually lossy (see :attr:`uses_error_feedback`).
    ``quant_group``
        Elements per int8 quantization scale group on the wire.
    ``min_shard_numel``
        Leaves smaller than this stay replicated and are synced with a
        plain psum (norms, biases, scalars — <0.1% of parameters).
    ``rs_dtype``
        Reduce-scatter payload dtype; ``'bfloat16'`` halves the RS link
        volume (§Perf A).  Allgather always runs exact in param dtype.
    ``use_fused_kernel``
        Route the circulant rounds' fold + send assembly through the
        fused Pallas kernel (``kernels/fused_round.py``); ``None`` =
        auto (TPU only).
    ``bucket_bytes``
        ``None`` (default) syncs each leaf in one shot — the legacy
        path, bitwise-identical to pre-bucketing builds.  An int enables
        BUCKETED, OVERLAPPED sync: the flat gradient vector is
        partitioned into ~``bucket_bytes``-sized buckets (see
        :func:`plan_grad_buckets`), each bucket runs one circulant RS
        (and one AG for the updated shards) on the cached plan, and the
        rounds are software-pipelined across buckets
        (``CollectivePlan.reduce_scatter_pipelined``) so bucket b's
        ppermute overlaps bucket b+1's fold.  Requires
        ``impl='circulant'``.
    """

    impl: str = "circulant"       # circulant | ring | xla | allreduce
    schedule: str = "halving"     # Corollary-2 schedule for circulant
    wire_dtype: str | None = None  # None | 'int8': compressed circulant
    #                               rounds (int8 codes + f32 group scales
    #                               packed on the wire; ~4x fewer β bytes)
    compress: str | None = None   # DEPRECATED alias for wire_dtype
    error_feedback: bool = True   # EF-SGD residual for compressed sync:
    #                               each rank keeps its local quantization
    #                               error and adds it back into the next
    #                               step's gradient before quantizing
    quant_group: int = 512
    min_shard_numel: int = 1024   # leaves smaller than this stay replicated
    rs_dtype: str = "float32"     # reduce-scatter payload dtype; 'bfloat16'
    #                               halves the RS link volume (§Perf A)
    use_fused_kernel: bool | None = None  # fused Pallas round kernel for the
    #                               circulant RS/AG; None = auto (TPU only)
    bucket_bytes: int | None = None  # None = single-shot per leaf (legacy,
    #                               bitwise-identical); int = bucketed,
    #                               software-pipelined sync (circulant only)

    def __post_init__(self):
        if self.compress is not None:
            warnings.warn(
                "GradSyncConfig(compress=...) is deprecated; pass "
                "wire_dtype=... — it feeds the CollectiveSpec the grad "
                "sync plans are built from (see GradSyncConfig.rs_spec)",
                DeprecationWarning, stacklevel=3)
        if self.bucket_bytes is not None:
            if self.bucket_bytes <= 0:
                raise ValueError(
                    f"bucket_bytes must be positive, got {self.bucket_bytes}")
            if self.impl != "circulant":
                raise ValueError(
                    "bucket_bytes requires impl='circulant' — the bucketed "
                    "path pipelines circulant plans "
                    f"(got impl={self.impl!r})")

    @property
    def wire(self) -> str | None:
        """Effective wire dtype (``wire_dtype`` wins over the legacy
        ``compress`` spelling)."""
        return self.wire_dtype or self.compress

    def rs_spec(self) -> CollectiveSpec:
        """The reduce-scatter :class:`CollectiveSpec` this config means.

        ``impl='allreduce'`` (the no-ZeRO baseline) shards nothing, but
        its tiny-leaf fallback still wants an xla spec.
        """
        kind = self.impl if self.impl != "allreduce" else "xla"
        if kind != "circulant":
            return CollectiveSpec(kind=kind)
        return CollectiveSpec(
            kind="circulant", schedule=self.schedule,
            use_fused_kernel=self.use_fused_kernel,
            wire_dtype=self.wire if self.wire == "int8" else None,
            wire_group=self.quant_group)

    def ag_spec(self) -> CollectiveSpec:
        """Allgather spec: parameter shards must reassemble EXACTLY, so
        the wire format never applies; ring has no allgather and falls
        back to the circulant schedule (same reversed-skip structure)."""
        kind = "circulant" if self.impl in ("circulant", "ring") else "xla"
        if kind != "circulant":
            return CollectiveSpec(kind=kind)
        return CollectiveSpec(
            kind="circulant", schedule=self.schedule,
            use_fused_kernel=self.use_fused_kernel)

    @property
    def uses_error_feedback(self) -> bool:
        """EF is meaningful only when the sync is actually lossy: the
        circulant impl is the one that honors ``wire_dtype`` (ring/xla
        transmit exactly; allreduce has no sharded RS to compensate)."""
        return (self.error_feedback and self.wire == "int8"
                and self.impl == "circulant")


class Zero1State(NamedTuple):
    """ZeRO-1 optimizer state: per-leaf AdamW moments holding only this
    rank's 1/world shard for sharded (zero) leaves, plus the optional
    EF-SGD residual tree for the compressed wire."""
    m: object        # pytree: sharded fp32 (zero leaves) / full (tiny)
    v: object
    step: jax.Array
    ef: object = None  # error-feedback residuals: per-rank quantization
    #                    error, (world, *leaf) sharded over the data axes
    #                    (zero leaves) / (1, *leaf) replicated (tiny
    #                    leaves, unused); None when EF is off


def data_parallel_world_static(mesh_shape: dict, axis_names) -> int:
    """Product of the data-parallel axis sizes, from static mesh shape
    (usable outside a mesh context, e.g. for state-spec construction)."""
    p = 1
    for a in axis_names:
        p *= mesh_shape[a]
    return p


def is_zero_leaf(shape, world: int, min_numel: int) -> bool:
    """Shard a leaf iff it is big enough and leading-dim padding waste is
    bounded (< 2x)."""
    numel = int(np.prod(shape)) if shape else 0
    if numel < max(min_numel, world):
        return False
    ld = shape[0]
    pad_ld = ld + (-ld) % world
    return pad_ld <= 2 * ld or numel // max(ld, 1) * pad_ld >= min_numel


def leaf_flags(params, world: int, min_numel: int = 1024):
    """Per-leaf :func:`is_zero_leaf` pytree — True where the optimizer
    state is sharded 1/world."""
    return jax.tree.map(
        lambda l: is_zero_leaf(l.shape, world, min_numel), params)


def _pad_lead(x, world: int):
    ld = x.shape[0]
    pad = (-ld) % world
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)], axis=0)
    return x


def shard_offset(ld_pad: int, axis_names: Sequence[str]):
    """(row offset, rows per shard) of this rank's slice (axis-major)."""
    p_total = 1
    lin = jnp.zeros((), jnp.int32)
    for a in axis_names:
        lin = lin * compat.axis_size(a) + lax.axis_index(a)
        p_total *= compat.axis_size(a)
    rows = ld_pad // p_total
    return lin * rows, rows


def reduce_scatter_leaf(g, axis_names, sync: GradSyncConfig, world: int):
    """Hierarchical RS along dim 0; returns the averaged local shard.
    One cached :class:`CollectivePlan` per axis (sync.rs_spec())."""
    spec = sync.rs_spec()
    out = _pad_lead(g, world)
    for ax in axis_names:
        out = C.reduce_scatter(out, ax, spec=spec)
    return out / world


def allgather_leaf(shard, ld: int, axis_names, sync: GradSyncConfig):
    """Inverse: hierarchical AG along dim 0, then drop padding rows."""
    spec = sync.ag_spec()
    out = shard
    for ax in reversed(list(axis_names)):
        out = C.allgather(out, ax, spec=spec)
    return out[:ld]


def allreduce_leaf(g, axis_names, sync: GradSyncConfig, world: int):
    """Tiny-leaf path: replicated mean.  Scalars/1-elem rows cannot block-
    partition, so this uses psum (XLA all-reduce) — negligible volume."""
    out = g
    for ax in axis_names:
        out = lax.psum(out, ax)
    return out / world


def ef_quantize(g, residual, group: int):
    """EF-SGD compensation step (per rank, per leaf): add the carried
    residual into the raw gradient, round the sum onto the int8 grid the
    wire will use, and keep the new rounding error as the next step's
    residual.  The quantized gradient is what enters the compressed
    reduce-scatter, so round 0 of the wire re-derives (near-)identical
    codes and the dominant compression error is fed back instead of
    lost.  Per-round requantization error of partial sums is NOT
    recoverable per rank (it mixes contributions) and stays uncompensated
    — standard EF-SGD scope."""
    comp = g.astype(jnp.float32) + residual
    q = dequantize_blocks(quantize_blocks(comp, group=group, backend="jnp"),
                          backend="jnp")
    return q, comp - q


# ---------------------------------------------------------------------------
# Bucketed, overlapped grad sync (GradSyncConfig.bucket_bytes)
# ---------------------------------------------------------------------------

def plan_grad_buckets(shapes: Sequence[tuple], world: int,
                      bucket_bytes: int, itemsize: int = 4
                      ) -> list[list[tuple[int, int, int]]]:
    """Partition the flat gradient vector into size-targeted buckets.

    ``shapes`` are the sharded (zero) leaves' shapes in flat traversal
    order.  Each leaf's padded leading dim splits into ``world`` blocks
    of ``R = ld_pad // world`` shard rows; the partitioner walks the
    leaves in order and greedily fills buckets to ~``bucket_bytes`` of
    full-gradient volume (one shard row accounts for ``world`` gradient
    rows — the bytes every rank moves through the wire for it).

    Returns a list of buckets; each bucket is a list of ``(leaf, lo,
    hi)`` segments meaning shard rows ``[lo, hi)`` of ``shapes[leaf]``.
    Invariants (tested): segments of one leaf are disjoint, in
    increasing ``lo`` order across buckets, and cover ``[0, R)``
    exactly; a leaf larger than ``bucket_bytes`` is split across
    buckets; a row larger than ``bucket_bytes`` gets a bucket of its
    own (never an empty bucket).  Static/host-side: the partition
    depends only on shapes, so it is computed once per compile.
    """
    if bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    buckets: list[list[tuple[int, int, int]]] = []
    cur: list[tuple[int, int, int]] = []
    cur_bytes = 0
    for i, shape in enumerate(shapes):
        ld = shape[0]
        rest = int(np.prod(shape[1:])) if len(shape) > 1 else 1
        R = (ld + (-ld) % world) // world
        row_bytes = rest * world * itemsize
        lo = 0
        while lo < R:
            room = bucket_bytes - cur_bytes
            if cur and room < row_bytes:
                buckets.append(cur)
                cur, cur_bytes = [], 0
                room = bucket_bytes
            take = min(R - lo, max(1, room // row_bytes))
            cur.append((i, lo, lo + take))
            cur_bytes += take * row_bytes
            lo += take
            if cur_bytes >= bucket_bytes:
                buckets.append(cur)
                cur, cur_bytes = [], 0
    if cur:
        buckets.append(cur)
    return buckets


def _zero_leaf_meta(flat_g, flat_flags):
    """(zero-leaf indices, per-leaf trailing-row numel) for bucketing."""
    zero_idx = [i for i, f in enumerate(flat_flags) if f]
    rn = {i: max(1, int(np.prod(flat_g[i].shape[1:]))) for i in zero_idx}
    return zero_idx, rn


#: Minor dimension of the bucket block matrices: one TPU vector lane row.
_LANES = 128


def _bucket_lanes(shapes, world: int, bucket_widths) -> int:
    """Minor width ``c`` of every bucket array: the largest divisor of one
    lane row that divides each leaf's shard numel, each multi-dimensional
    leaf's row numel and each bucket width (128 at qwen3-1.7b's shapes).
    Each leaf row is then a whole number of ``c``-wide rows.  Under tensor
    parallelism that keeps the bucketed step bitwise equal to the per-leaf
    one (``tests/_zero1_checks.py``): ``c``-wide rows that cut across leaf
    rows changed the grad norm's partial sums there."""
    widths = [(s[0] + (-s[0]) % world) // world
              * int(np.prod(s[1:], dtype=np.int64)) for s in shapes]
    rows = [int(np.prod(s[1:], dtype=np.int64)) for s in shapes
            if len(s) > 1]
    return math.gcd(_LANES, *widths, *rows, *bucket_widths)


def _bucket_widths(buckets, zero_idx, rn):
    """Per-bucket column width (shard numel) in the global block matrix."""
    return [sum((hi - lo) * rn[zero_idx[li]] for (li, lo, hi) in b)
            for b in buckets]


def _bucket_vectors(blocks, widths, zero_idx):
    """Assemble each bucket as a ``(world, w / c, c)`` block matrix whose
    row ``lin`` is rank ``lin``'s shard data -- the layout the circulant
    RS/AG block partition expects.  ``blocks[i]`` is leaf ``i`` as a
    ``(world, R * rn / c, c)`` block matrix, ``widths`` the bucket widths
    (``c`` from :func:`_bucket_lanes`).

    The partitioner walks leaves and shard rows in order, so every
    bucket is a CONTIGUOUS range of the global ``(world, Wtot / c, c)``
    block matrix: one concatenate builds it, then each bucket is a single
    slice (op count matters -- assembly sits on the training step's
    critical path).  The minor dimension stays ``c`` throughout: the TPU
    compiler relays out a reshape from a leaf's ``(rows, d)`` to a flat
    ``(world, rows / world * d)`` row by row, which costs minutes of
    compile time at an embedding's row count."""
    G = (blocks[zero_idx[0]] if len(zero_idx) == 1 else
         jnp.concatenate([blocks[i] for i in zero_idx], axis=1))
    c = G.shape[2]
    vecs, off = [], 0
    for w in widths:
        vecs.append(G[:, off:off + w // c])
        off += w // c
    return vecs


def _bucketed_reduce(grads, flags, ef, axis_names, sync: GradSyncConfig,
                     world: int, rs_dt):
    """Bucketed, software-pipelined gradient reduce-scatter.

    Per-element arithmetic is IDENTICAL to the per-leaf path (the fold
    sequence of a circulant RS depends only on the block index, which
    the bucket layout preserves), so the uncompressed bucketed sync is
    bitwise-equal to ``reduce_scatter_leaf``; the int8 wire differs only
    through quantization-group boundaries (within wire tolerances).
    EF residual accounting is per leaf, exactly as in the one-shot path
    — each bucket's wire rounds then transport the same compensated
    rows.  Returns ``(g_red tree, new_ef tree | None)``.
    """
    flat_g, tdef = jax.tree.flatten(grads)
    flat_flags = jax.tree.leaves(flags)
    flat_ef = jax.tree.leaves(ef) if ef is not None else [None] * len(flat_g)
    zero_idx, rn = _zero_leaf_meta(flat_g, flat_flags)
    zshapes = [flat_g[i].shape for i in zero_idx]
    buckets = plan_grad_buckets(zshapes, world, sync.bucket_bytes,
                                jnp.dtype(rs_dt).itemsize)
    widths = _bucket_widths(buckets, zero_idx, rn)
    c = _bucket_lanes(zshapes, world, widths)
    zset = set(zero_idx)
    out: list = [None] * len(flat_g)
    new_ef = list(flat_ef)
    for i, g in enumerate(flat_g):
        if i not in zset:
            out[i] = allreduce_leaf(g.astype(jnp.float32), axis_names,
                                    sync, world)
    blocks = {}
    for i in zero_idx:
        g = flat_g[i]
        if ef is not None:
            q, err = ef_quantize(g, flat_ef[i][0], sync.quant_group)
            new_ef[i] = err[None]
            g = q
        gp = _pad_lead(g.astype(rs_dt), world)
        blocks[i] = gp.reshape(world, -1, c)
    vecs = _bucket_vectors(blocks, widths, zero_idx)
    spec = sync.rs_spec()
    for ax in axis_names:
        vecs = C.reduce_scatter_pipelined(vecs, ax, spec=spec)
    # Each bucket's RS result (1, w_b / c, c) is this rank's contiguous
    # range of the global block matrix, so concatenating the bucket
    # results in order gives the rank's full shard; per-leaf slices then
    # fall at the leaf block widths.  Single divide, then L slices.
    own = vecs[0] if len(vecs) == 1 else jnp.concatenate(vecs, axis=1)
    own = (own[0] / world).astype(jnp.float32)
    off = 0
    for i in zero_idx:
        n = blocks[i].shape[1]
        out[i] = own[off:off + n].reshape(-1, *flat_g[i].shape[1:])
        off += n
    g_red = jax.tree.unflatten(tdef, out)
    if ef is None:
        return g_red, None
    return g_red, jax.tree.unflatten(tdef, new_ef)


def _bucketed_allgather(local, params, flags, axis_names,
                        sync: GradSyncConfig, world: int):
    """Bucketed, software-pipelined allgather of updated param shards.

    ``local`` mirrors ``params``: zero leaves hold this rank's updated
    shard ``(R, *rest)``, tiny leaves the full replicated update.  Uses
    the SAME static bucket partition as the grad reduce (same shapes,
    same itemsize) so plans and bucket geometries are shared.  Allgather
    is pure transport, so the result is bitwise-equal to per-leaf
    ``allgather_leaf`` (mixed-dtype buckets promote via ``result_type``
    and cast back — lossless round trips).
    """
    flat_l, tdef = jax.tree.flatten(local)
    flat_p = jax.tree.leaves(params)
    flat_flags = jax.tree.leaves(flags)
    zero_idx, rn = _zero_leaf_meta(flat_p, flat_flags)
    zshapes = [flat_p[i].shape for i in zero_idx]
    buckets = plan_grad_buckets(zshapes, world, sync.bucket_bytes,
                                jnp.dtype(sync.rs_dtype).itemsize)
    widths = _bucket_widths(buckets, zero_idx, rn)
    c = _bucket_lanes(zshapes, world, widths)
    out = list(flat_l)
    # The local shards of all leaves as one (Wtot / c, c) matrix in leaf
    # order (mixed dtypes promote via result_type and cast back after
    # transport — lossless round trips); each bucket is a contiguous row
    # range of it (see _bucket_vectors).
    dt = jnp.result_type(*[flat_l[i].dtype for i in zero_idx])
    lvec = (flat_l[zero_idx[0]].astype(dt).reshape(-1, c)
            if len(zero_idx) == 1 else
            jnp.concatenate([flat_l[i].astype(dt).reshape(-1, c)
                             for i in zero_idx]))
    vecs, off = [], 0
    for w in widths:
        vecs.append(lvec[off:off + w // c])
        off += w // c
    spec = sync.ag_spec()
    for ax in reversed(list(axis_names)):
        vecs = C.allgather_pipelined(vecs, ax, spec=spec)
    # Gathered bucket b is (world * w_b / c, c), rank-major; re-joining
    # the buckets along their second axis rebuilds the global
    # (world, Wtot / c, c) block matrix, from which each leaf is one
    # range of it.
    G = (vecs[0].reshape(world, -1, c) if len(vecs) == 1 else
         jnp.concatenate([v.reshape(world, -1, c) for v in vecs], axis=1))
    off = 0
    for i in zero_idx:
        ld = flat_p[i].shape[0]
        n = (ld + (-ld) % world) // world * rn[i] // c
        out[i] = (G[:, off:off + n].reshape(-1, *flat_p[i].shape[1:])[:ld]
                  .astype(flat_p[i].dtype))
        off += n
    return jax.tree.unflatten(tdef, out)


def zero1_step(loss_and_grad: Callable, params, opt: Zero1State, batch, *,
               axis_names: Sequence[str], opt_cfg: adamw.AdamWConfig,
               sync: GradSyncConfig, constrain: Callable | None = None):
    """One manual-region training step (inside shard_map over the data
    axes; the model axis stays auto/GSPMD).  Returns (params', opt',
    metrics).

    ``constrain`` (tree -> tree) pins the reduced gradient shards to the
    parameters' model-axis sharding.  The grad-norm sums and the AdamW
    update are then partitioned the same way whether the sync ran per
    leaf or bucketed, so the two trajectories stay bitwise equal under
    tensor parallelism."""
    with jax.named_scope("zero1.fwd_bwd"):
        loss, grads = loss_and_grad(params, batch)
    world = 1
    for a in axis_names:
        world *= compat.axis_size(a)
    flags = jax.tree.map(
        lambda l: is_zero_leaf(l.shape, world, sync.min_shard_numel), params)
    use_zero = sync.impl != "allreduce"

    # --- reduce: shard big leaves (Algorithm 1), psum tiny ones ---
    rs_dt = jnp.dtype(sync.rs_dtype)
    use_ef = sync.uses_error_feedback and opt.ef is not None
    bucketed = use_zero and sync.bucket_bytes is not None

    def reduce_one(g, flag):
        if flag and use_zero:
            g = g.astype(rs_dt)
            out = reduce_scatter_leaf(g, axis_names, sync, world)
            return out.astype(jnp.float32)
        return allreduce_leaf(g.astype(jnp.float32), axis_names, sync, world)

    with jax.named_scope("zero1.reduce_scatter"):
        if bucketed:
            # Bucketed, pipelined sync: bucket b's round-k ppermute
            # overlaps bucket b+1's fold (see _bucketed_reduce;
            # bucket_bytes=None keeps the per-leaf one-shot path below,
            # bitwise-identical).
            g_red, ef_out = _bucketed_reduce(
                grads, flags, opt.ef if use_ef else None, axis_names, sync,
                world, rs_dt)
            new_ef = ef_out if use_ef else opt.ef
        elif use_ef:
            # Compressed sync with error feedback: compensate, quantize,
            # and carry the rounding error (see ef_quantize).  ``e``
            # arrives as this rank's (1, *leaf) shard of the (world,
            # *leaf) state.
            def reduce_one_ef(g, flag, e):
                if flag and use_zero:
                    q, err = ef_quantize(g, e[0], sync.quant_group)
                    out = reduce_scatter_leaf(q.astype(rs_dt), axis_names,
                                              sync, world)
                    return out.astype(jnp.float32), err[None]
                return (allreduce_leaf(g.astype(jnp.float32), axis_names,
                                       sync, world), e)

            pairs = jax.tree.map(reduce_one_ef, grads, flags, opt.ef)
            ispair = lambda x: (isinstance(x, tuple) and len(x) == 2
                                and not isinstance(x, jax.Array))
            g_red = jax.tree.map(lambda o: o[0], pairs, is_leaf=ispair)
            new_ef = jax.tree.map(lambda o: o[1], pairs, is_leaf=ispair)
        else:
            g_red = jax.tree.map(reduce_one, grads, flags)
            new_ef = opt.ef
        if constrain is not None:
            g_red = constrain(g_red)
    with jax.named_scope("zero1.adamw"):
        # --- global grad norm: shards partition the reduced grad exactly, so
        # one psum of the summed shard sq-norms + the (replicated) tiny-leaf
        # sq-norms gives the global norm ---
        flat_flags = jax.tree.leaves(flags)
        flat_g = jax.tree.leaves(g_red)
        shard_sq = sum((jnp.sum(jnp.square(g)) for g, f in
                        zip(flat_g, flat_flags) if f and use_zero),
                       start=jnp.zeros((), jnp.float32))
        tiny_sq = sum((jnp.sum(jnp.square(g)) for g, f in
                       zip(flat_g, flat_flags) if not (f and use_zero)),
                      start=jnp.zeros((), jnp.float32))
        for ax in axis_names:
            shard_sq = lax.psum(shard_sq, ax)
        gnorm = jnp.sqrt(shard_sq + tiny_sq)
        scale = adamw.clip_scale_from_norm(opt_cfg, gnorm)

        # --- AdamW on shards ---
        step = opt.step + 1
        t = step.astype(jnp.float32)
        lr = adamw.lr_at(opt_cfg, step)
        bc1 = 1 - opt_cfg.beta1 ** t
        bc2 = 1 - opt_cfg.beta2 ** t

        def update_one(p, g, m, v, flag):
            if flag and use_zero:
                ld = p.shape[0]
                p_pad = _pad_lead(p, world)
                off, rows = shard_offset(p_pad.shape[0], axis_names)
                p_loc = lax.dynamic_slice_in_dim(p_pad, off, rows, axis=0)
            else:
                p_loc = p
            g = g * scale
            m2 = opt_cfg.beta1 * m + (1 - opt_cfg.beta1) * g
            v2 = opt_cfg.beta2 * v + (1 - opt_cfg.beta2) * g * g
            delta = -lr * ((m2 / bc1) / (jnp.sqrt(v2 / bc2) + opt_cfg.eps)
                           + opt_cfg.weight_decay * p_loc.astype(jnp.float32))
            new_loc = (p_loc.astype(jnp.float32) + delta).astype(p.dtype)
            if flag and use_zero and not bucketed:
                # Bucketed mode defers the gather: shards from all leaves are
                # re-bucketed and allgathered pipelined below.
                with jax.named_scope("zero1.allgather"):
                    new_p = allgather_leaf(new_loc, p.shape[0], axis_names,
                                           sync)
            else:
                new_p = new_loc
            return new_p, m2, v2

        out = jax.tree.map(update_one, params, g_red, opt.m, opt.v, flags)
        istup = lambda x: isinstance(x, tuple) and len(x) == 3 \
            and not isinstance(x, jax.Array)
        new_params = jax.tree.map(lambda o: o[0], out, is_leaf=istup)
        new_m = jax.tree.map(lambda o: o[1], out, is_leaf=istup)
        new_v = jax.tree.map(lambda o: o[2], out, is_leaf=istup)
    if bucketed:
        with jax.named_scope("zero1.allgather"):
            new_params = _bucketed_allgather(new_params, params, flags,
                                             axis_names, sync, world)

    mloss = loss
    for ax in axis_names:
        mloss = lax.pmean(mloss, ax)
    metrics = {"loss": mloss, "grad_norm": gnorm,
               "lr": adamw.lr_at(opt_cfg, step)}
    return (new_params,
            Zero1State(m=new_m, v=new_v, step=step, ef=new_ef), metrics)


# ---------------------------------------------------------------------------
# State construction / specs (used by train.steps)
# ---------------------------------------------------------------------------

def init_zero1_state(params, world: int, sync: GradSyncConfig) -> Zero1State:
    """GLOBAL optimizer state arrays: zero leaves get (ld_pad, *rest) fp32
    (to be sharded over the data axes), tiny leaves full fp32 replicas.
    With compressed sync + error feedback, every leaf also gets an EF
    residual: (world, *leaf) for zero leaves — one full-leaf residual PER
    DATA RANK, sharded so each rank keeps exactly its own — and a dummy
    (1, *leaf) replica for tiny leaves (psum'd exactly; never read)."""
    use_zero = sync.impl != "allreduce"

    def mk(l):
        if use_zero and is_zero_leaf(l.shape, world, sync.min_shard_numel):
            ld_pad = l.shape[0] + (-l.shape[0]) % world
            return jnp.zeros((ld_pad, *l.shape[1:]), jnp.float32)
        return jnp.zeros(l.shape, jnp.float32)

    zeros = jax.tree.map(mk, params)
    ef = None
    if sync.uses_error_feedback:
        def mk_ef(l):
            n = world if is_zero_leaf(l.shape, world,
                                      sync.min_shard_numel) else 1
            return jnp.zeros((n, *l.shape), jnp.float32)

        ef = jax.tree.map(mk_ef, params)
    return Zero1State(m=zeros, v=jax.tree.map(jnp.copy, zeros),
                      step=jnp.zeros((), jnp.int32), ef=ef)


def resize_zero1_state(state: Zero1State, params, new_world: int,
                       sync: GradSyncConfig) -> Zero1State:
    """Remap a GLOBAL (gathered) :class:`Zero1State` to a new data-parallel
    world size — the elastic reshard step (ft/elastic.py).

    Inputs are the checkpointed, host-side global views: zero leaves'
    ``m``/``v`` are ``(ld_pad_old, *rest)`` (leading dim padded to the
    OLD world), tiny leaves are full replicas.  Only the leaf's true
    leading dim (from ``params``) and the NEW world matter:

    * ``m``/``v``: drop the old padding rows (``[:ld]`` — padded rows
      are zero by construction: padded gradient rows are zero, so the
      moments never leave zero there) and re-pad to the new world's
      multiple.  A leaf whose :func:`is_zero_leaf` flag flips between
      worlds is handled by the same slice+pad (tiny leaves store exactly
      ``ld`` rows).  The round trip p→p′→p is lossless.
    * ``ef`` (EF-SGD residuals, ``(old_world, *leaf)`` — one full-leaf
      residual per rank): resized by MASS CONSERVATION — row 0 of the
      new ``(new_world, *leaf)`` state is the sum over all old rank
      rows, remaining rows zero.  Semantics: each rank adds its residual
      into its local gradient before quantization and the reduce-scatter
      SUMS ranks, so only the total ``sum_r ef_r`` enters the reduced
      gradient; per-rank attribution carries no information across a
      resize (the rank set itself changed).  Shrink and grow are the
      same operation, and the residual mass survives p→p′→p exactly.
    * ``step``: unchanged.
    """
    if new_world < 1:
        raise ValueError(f"new_world must be >= 1, got {new_world}")
    use_zero = sync.impl != "allreduce"

    def rs_mv(mv, l):
        if not l.shape:
            return jnp.asarray(mv)  # scalar leaf: always replicated
        ld = l.shape[0]
        arr = np.asarray(mv)[:ld]
        if use_zero and is_zero_leaf(l.shape, new_world,
                                     sync.min_shard_numel):
            pad = (-ld) % new_world
            if pad:
                arr = np.concatenate(
                    [arr, np.zeros((pad, *arr.shape[1:]), arr.dtype)])
        return jnp.asarray(arr)

    def rs_ef(e, l):
        rows = new_world if (use_zero and is_zero_leaf(
            l.shape, new_world, sync.min_shard_numel)) else 1
        out = np.zeros((rows, *l.shape), np.float32)
        out[0] = np.asarray(e, np.float32).sum(axis=0)
        return jnp.asarray(out)

    new_m = jax.tree.map(rs_mv, state.m, params)
    new_v = jax.tree.map(rs_mv, state.v, params)
    new_ef = None
    if state.ef is not None:
        if not sync.uses_error_feedback:
            raise ValueError(
                "state carries EF residuals but sync does not use error "
                "feedback — resize would silently drop residual mass")
        new_ef = jax.tree.map(rs_ef, state.ef, params)
    return Zero1State(m=new_m, v=new_v, step=jnp.asarray(state.step),
                      ef=new_ef)


def zero1_state_specs(params, world: int, sync: GradSyncConfig,
                      collective_axes):
    """Manual-axis PartitionSpecs for the optimizer state (dim 0 over the
    data axes for zero leaves; replicated otherwise).  EF residuals are
    sharded on their per-rank leading axis."""
    from jax.sharding import PartitionSpec as P
    use_zero = sync.impl != "allreduce"

    def spec(l):
        if use_zero and is_zero_leaf(l.shape, world, sync.min_shard_numel):
            return P(collective_axes)
        return P()

    m_specs = jax.tree.map(spec, params)
    ef_specs = None
    if sync.uses_error_feedback:
        ef_specs = jax.tree.map(spec, params)
    return Zero1State(m=m_specs, v=jax.tree.map(lambda s: s, m_specs),
                      step=P(), ef=ef_specs)
