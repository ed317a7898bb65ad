"""Pallas TPU kernel: the fused circulant ROUND — Algorithm 1's hot loop.

Each reduce-scatter round k of the circulant collectives does two local
memory operations after the ppermute delivers T:

  (a) fold the received blocks into the live buffer head,
      ``R[:nb] = R[:nb] ⊕ T``            (the paper's γ-term), and
  (b) assemble the NEXT round's send blocks ``R[s_{k+1} : s_k]`` into a
      contiguous send buffer for the next collective-permute.

Done with plain jnp ops that is a reduce + a concatenate + a slice — three
HBM round-trips over the live buffer.  The fused kernel does both in ONE
pass: every input row is read once, every output row written once, and the
round's ppermute payload comes out contiguous.  Rows are the paper's
blocks (the live buffer is viewed as ``(blocks, block_numel)``); the fold
boundary ``nb`` and the keep/send split ``next_lo`` are trace-time
constants from the schedule, so the kernel body is pure static slicing —
no masks, no predicates, bitwise-identical to the jnp path.

Layout of one round (live buffer has ``lo`` rows, ``nb`` received rows,
next round keeps ``next_lo`` rows and sends ``lo - next_lo``)::

      row         0 ......... nb ........ lo
      value       op(live,T)  |  live (copied through)
      routed to   keep[0:next_lo]  |  send[0:lo-next_lo]   (split at next_lo)

``nb`` may straddle ``next_lo`` in either direction (halving schedules
fold past the split; fully_connected folds only row 0) — both boundaries
are static, so each output region is an unrolled pair of row-slices.

Target: TPU (grid over VPU-aligned column tiles).  On CPU the kernel runs
under ``interpret=True`` as a gridless whole-buffer call — the
interpreter's per-grid-step overhead dominates otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import quantize as _qz
from .block_reduce import DEFAULT_COL_TILE, _OPS


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def resolve_fused(use_fused_kernel: bool | None) -> bool:
    """Auto-selection rule for the ``use_fused_kernel`` kwarg.

    ``True``/``False`` are explicit.  ``None`` (auto) enables the fused
    Pallas path only on TPU: on CPU the kernel would run in interpret
    mode, which is for validation rather than speed.
    """
    if use_fused_kernel is None:
        return jax.default_backend() == "tpu"
    return bool(use_fused_kernel)


def _store_rows(ref, lo_idx: int, hi_idx: int, val):
    """Static row-range store; whole-ref stores skip the interpreter's
    sliced-update path (measurably cheaper in interpret mode)."""
    if lo_idx == 0 and hi_idx == ref.shape[0]:
        ref[...] = val
    else:
        ref[lo_idx:hi_idx] = val


def _round_body(x_ref, t_ref, keep_ref, send_ref, *, op: str, nb: int,
                next_lo: int, lo: int):
    """Shared kernel body; ``send_ref`` is None on the final round."""
    reduce_fn = _OPS[op]
    folded = reduce_fn(x_ref[:nb], t_ref[...])
    a = min(nb, next_lo)
    if a:
        _store_rows(keep_ref, 0, a, folded[:a] if a < nb else folded)
    if a < next_lo:
        _store_rows(keep_ref, a, next_lo, x_ref[a:next_lo])
    if send_ref is None:
        return
    if nb > next_lo:
        _store_rows(send_ref, 0, nb - next_lo, folded[next_lo:nb])
    b = max(nb, next_lo)
    if b < lo:
        _store_rows(send_ref, b - next_lo, lo - next_lo, x_ref[b:lo])


def _kernel_keep_send(x_ref, t_ref, keep_ref, send_ref, *, op, nb, next_lo, lo):
    _round_body(x_ref, t_ref, keep_ref, send_ref, op=op, nb=nb,
                next_lo=next_lo, lo=lo)


def _kernel_keep_only(x_ref, t_ref, keep_ref, *, op, nb, next_lo, lo):
    _round_body(x_ref, t_ref, keep_ref, None, op=op, nb=nb,
                next_lo=next_lo, lo=lo)


def fused_round(
    live: jax.Array,
    received: jax.Array,
    *,
    nb: int,
    next_lo: int,
    op: str = "add",
    col_tile: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array | None]:
    """One fused circulant round over 2-D ``(blocks, block_numel)`` buffers.

    ``live``: the ``(lo, cols)`` live buffer; ``received``: the
    ``(nb, cols)`` ppermuted payload.  Returns ``(keep, send)`` where
    ``keep`` is rows ``[0, next_lo)`` of the new live buffer and ``send``
    is rows ``[next_lo, lo)`` (the next round's contiguous payload), or
    ``None`` when ``next_lo == lo`` (final round).  Requires
    ``1 <= nb <= lo`` and ``1 <= next_lo <= lo`` — schedule validity
    (fold-liveness, see ``core.schedule``) guarantees both.
    """
    if live.ndim != 2 or received.ndim != 2:
        raise ValueError(
            f"need 2-D buffers, got {live.shape} and {received.shape}")
    lo, cols = live.shape
    if received.shape != (nb, cols):
        raise ValueError(
            f"received shape {received.shape} != ({nb}, {cols})")
    if not (1 <= nb <= lo and 1 <= next_lo <= lo):
        raise ValueError(
            f"invalid round: nb={nb}, next_lo={next_lo}, lo={lo}")
    if interpret is None:
        interpret = _interpret_default()
    final = next_lo == lo  # last round: no send output
    kernel = functools.partial(
        _kernel_keep_only if final else _kernel_keep_send,
        op=op, nb=nb, next_lo=next_lo, lo=lo)
    out_shape: object = jax.ShapeDtypeStruct((next_lo, cols), live.dtype)
    if not final:
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((lo - next_lo, cols), live.dtype)]
    kw: dict = {"interpret": True}
    if not interpret:
        # Compiled (TPU): grid over VPU-aligned column tiles, whole rows
        # per step.  In interpret mode a gridless whole-buffer call is
        # used instead — the interpreter's per-grid-step slicing/masking
        # machinery costs more than any tiling could win on CPU.
        ct = min(DEFAULT_COL_TILE if col_tile is None else col_tile, cols)
        out_specs: object = pl.BlockSpec((next_lo, ct), lambda j: (0, j))
        if not final:
            out_specs = [out_specs,
                         pl.BlockSpec((lo - next_lo, ct), lambda j: (0, j))]
        kw = {
            "grid": (pl.cdiv(cols, ct),),
            "in_specs": [
                pl.BlockSpec((lo, ct), lambda j: (0, j)),
                pl.BlockSpec((nb, ct), lambda j: (0, j)),
            ],
            "out_specs": out_specs,
        }
    res = pl.pallas_call(kernel, out_shape=out_shape, **kw)(live, received)
    if final:
        return res, None
    return res[0], res[1]


# ---------------------------------------------------------------------------
# Compressed (int8 wire) round: dequant + ⊕-fold + requant-next-send,
# one HBM traversal (the wire_dtype="int8" hot loop)
# ---------------------------------------------------------------------------

def _dq_round_body(x_ref, c_ref, s_ref, keep_ref, send_c_ref, send_s_ref, *,
                   op: str, nb: int, next_lo: int, lo: int, g: int):
    """Compressed-round kernel body; ``send_*`` refs are None on the final
    round.  Same static keep/send routing as ``_round_body``, but the
    received payload arrives as int8 codes + f32 scales (dequantized in
    VMEM, never materialized as f32 in HBM) and the next round's send rows
    leave requantized.  Elementwise expressions mirror ``ref.quantize_ref``
    / ``ref.dequant_ref`` exactly so the kernel is bitwise-equal to the
    jnp reference path.  Every row range is read from a ref slice and
    quantized on its own (quantization is per row): Mosaic rejects the
    layout of a row slice taken from a loaded multi-row value."""
    reduce_fn = _OPS[op]
    cols = c_ref.shape[1]

    def fold(r0, r1):
        q = c_ref[r0:r1].astype(jnp.float32).reshape(r1 - r0, cols // g, g)
        deq = (q * s_ref[r0:r1][..., None]).reshape(r1 - r0, cols)
        return reduce_fn(x_ref[r0:r1], deq)

    def send_rows(r0, r1, val):
        sg, scale = _qz.group_scales(val, g)
        codes = jnp.clip(jnp.round(sg / scale[..., None]), -127, 127)
        _store_rows(send_c_ref, r0, r1,
                    codes.reshape(r1 - r0, cols).astype(jnp.int8))
        _store_rows(send_s_ref, r0, r1, scale)

    a = min(nb, next_lo)
    if a:
        _store_rows(keep_ref, 0, a, fold(0, a))
    if a < next_lo:
        _store_rows(keep_ref, a, next_lo, x_ref[a:next_lo])
    if send_c_ref is None:
        return
    if nb > next_lo:
        send_rows(0, nb - next_lo, fold(next_lo, nb))
    b = max(nb, next_lo)
    if b < lo:
        send_rows(b - next_lo, lo - next_lo, x_ref[b:lo])


def _dq_kernel_keep_send(x_ref, c_ref, s_ref, keep_ref, send_c_ref,
                         send_s_ref, *, op, nb, next_lo, lo, g):
    _dq_round_body(x_ref, c_ref, s_ref, keep_ref, send_c_ref, send_s_ref,
                   op=op, nb=nb, next_lo=next_lo, lo=lo, g=g)


def _dq_kernel_keep_only(x_ref, c_ref, s_ref, keep_ref, *, op, nb, next_lo,
                         lo, g):
    _dq_round_body(x_ref, c_ref, s_ref, keep_ref, None, None, op=op, nb=nb,
                   next_lo=next_lo, lo=lo, g=g)


def fused_round_dq(
    live: jax.Array,
    codes: jax.Array,
    scales: jax.Array,
    *,
    nb: int,
    next_lo: int,
    op: str = "add",
    group: int = _qz.DEFAULT_GROUP,
    interpret: bool | None = None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array] | None]:
    """One fused COMPRESSED circulant round over 2-D buffers.

    ``live``: (lo, cols) f32 accumulation buffer, ``cols`` divisible by
    the quantization group ``g = min(group, cols)``; ``codes``/``scales``:
    the received int8 payload for ``nb`` blocks.  In ONE pass: dequantize,
    ⊕-fold into the buffer head, emit ``keep`` rows [0, next_lo), and
    requantize rows [next_lo, lo) as the next round's ``(codes, scales)``
    send pair (``None`` when ``next_lo == lo``, the final round).
    jnp oracle: ``ref.fused_round_dq_ref`` (bitwise-equal in interpret).
    """
    if live.ndim != 2 or codes.ndim != 2:
        raise ValueError(
            f"need 2-D buffers, got {live.shape} and {codes.shape}")
    lo, cols = live.shape
    g = min(group, cols)
    if cols % g:
        raise ValueError(f"cols {cols} not divisible by group {g}")
    ng = cols // g
    if codes.shape != (nb, cols):
        raise ValueError(f"codes shape {codes.shape} != ({nb}, {cols})")
    if scales.shape != (nb, ng):
        raise ValueError(f"scales shape {scales.shape} != ({nb}, {ng})")
    if not (1 <= nb <= lo and 1 <= next_lo <= lo):
        raise ValueError(
            f"invalid round: nb={nb}, next_lo={next_lo}, lo={lo}")
    if interpret is None:
        interpret = _interpret_default()
    final = next_lo == lo
    ns = lo - next_lo
    kernel = functools.partial(
        _dq_kernel_keep_only if final else _dq_kernel_keep_send,
        op=op, nb=nb, next_lo=next_lo, lo=lo, g=g)
    out_shape: object = jax.ShapeDtypeStruct((next_lo, cols), jnp.float32)
    if not final:
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((ns, cols), jnp.int8),
                     jax.ShapeDtypeStruct((ns, ng), jnp.float32)]
    kw: dict = {"interpret": True}
    if not interpret:
        # Compiled (TPU): column tiles of whole quantization groups, so
        # each grid step owns its scales slice (``quantize.tile_cols``).
        ct, sct = _qz.tile_cols(cols, g)
        out_specs: object = pl.BlockSpec((next_lo, ct), lambda j: (0, j))
        if not final:
            out_specs = [out_specs,
                         pl.BlockSpec((ns, ct), lambda j: (0, j)),
                         pl.BlockSpec((ns, sct), lambda j: (0, j))]
        kw = {
            "grid": (pl.cdiv(cols, ct),),
            "in_specs": [
                pl.BlockSpec((lo, ct), lambda j: (0, j)),
                pl.BlockSpec((nb, ct), lambda j: (0, j)),
                pl.BlockSpec((nb, sct), lambda j: (0, j)),
            ],
            "out_specs": out_specs,
        }
    res = pl.pallas_call(kernel, out_shape=out_shape, **kw)(
        live, codes, scales)
    if final:
        return res, None
    return res[0], (res[1], res[2])


def quantize_rows(x: jax.Array, *, group: int = _qz.DEFAULT_GROUP,
                  interpret: bool | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Pallas group-quantize with the fused-round interpret default — the
    round-0 send quantization of the compressed collectives."""
    if interpret is None:
        interpret = _interpret_default()
    return _qz.quantize(x, group=group, interpret=interpret)


def _permute_kernel(x_ref, o_ref, *, perm: tuple[int, ...]):
    for dst, src in enumerate(perm):
        o_ref[dst : dst + 1] = x_ref[src : src + 1]


def permute_rows(
    x: jax.Array,
    perm,
    *,
    col_tile: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Static row permutation ``out[i] = x[perm[i]]`` in one HBM pass.

    Used by the fused alltoall to lay the final slot into source-rank
    order (the permutation is trace-time metadata, so it unrolls into
    static row copies — no gather indices materialized).
    """
    perm = tuple(int(i) for i in perm)
    rows, cols = x.shape
    if sorted(perm) != list(range(rows)):
        raise ValueError(f"perm {perm} is not a permutation of 0..{rows - 1}")
    if interpret is None:
        interpret = _interpret_default()
    kw: dict = {"interpret": True}
    if not interpret:
        ct = min(DEFAULT_COL_TILE if col_tile is None else col_tile, cols)
        kw = {
            "grid": (pl.cdiv(cols, ct),),
            "in_specs": [pl.BlockSpec((rows, ct), lambda j: (0, j))],
            "out_specs": pl.BlockSpec((rows, ct), lambda j: (0, j)),
        }
    return pl.pallas_call(
        functools.partial(_permute_kernel, perm=perm),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        **kw,
    )(x)
