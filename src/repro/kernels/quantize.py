"""Pallas TPU kernels: int8 symmetric group quantization for compressed
communication rounds (β-term reducer, DESIGN §3).

``quantize``    : f32/bf16 (rows, cols) → int8 codes + f32 scales, one
                  scale per (row, group of G columns).
``dequant_add`` : fused decompress-and-reduce — acc + codes * scale in one
                  VMEM pass (the receive side of a compressed round; fuses
                  the paper's ⊕ with decompression so the int8 payload is
                  never materialized as f32 in HBM).

Group layout: scales[i, g] covers codes[i, g*G:(g+1)*G].  Each grid step
owns whole groups and their scales (``tile_cols``).
Ragged shapes (rows not divisible by ``row_tile``, cols not divisible by
``group``) are zero-padded internally and sliced back — the last group of
a row may cover fewer than G real elements; its scale is the amax of the
real elements (zero padding never raises an amax).

The int8 WIRE FORMAT for compressed collective rounds is also defined
here: one contiguous int8 buffer per round, ``[codes | scale bytes]``
along the column axis, so a compressed round still ppermutes exactly ONE
array — the lowered HLO keeps one collective-permute per round and the
bytes on the wire are exactly ``cols + 4*ceil(cols/G)`` per row.
``pack_wire`` / ``unpack_wire`` convert between (codes, scales) and the
wire buffer via same-width bitcasts (f32 ↔ u32 ↔ 4×u8), which every
supported JAX lowers on every backend.

Target: TPU; validated on CPU via interpret=True.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

DEFAULT_GROUP = 512  # elements per quantization group (one scale each)
_EPS = 1e-30
# Explicit reciprocal: a literal ``amax / 127.0`` is rewritten to a
# reciprocal-multiply by XLA in some contexts but not others (jit vs pallas
# interpret), producing 1-ulp scale drift between the kernel and the jnp
# reference.  A constant multiply is the same single IEEE op everywhere.
_INV127 = 1.0 / 127.0


def wire_ngroups(cols: int, group: int = DEFAULT_GROUP) -> int:
    """Number of (per-row) quantization groups covering ``cols`` columns."""
    g = min(group, cols)
    return -(-cols // g)


def wire_width(cols: int, group: int = DEFAULT_GROUP) -> int:
    """int8 wire-buffer columns for ``cols`` payload columns: codes plus
    four scale bytes per group (the compressed round's β-term bytes/row)."""
    return cols + 4 * wire_ngroups(cols, group)


def pad2d(x: jax.Array, row_mult: int, col_mult: int) -> jax.Array:
    """Zero-pad a 2-D array so rows/cols are multiples of the tile grid.

    THE shared ragged-shape padding helper: the quantize/dequant kernels,
    the jitted kernel wrappers (``kernels.ops``) and the collective
    plan's wire backends all pad through here instead of re-deriving the
    ``(-n) % m`` arithmetic locally (leading-axis *block* padding is the
    plan's ``BlockLayout.pad`` — driven by the counts table)."""
    rows, cols = x.shape
    pr, pc = (-rows) % row_mult, (-cols) % col_mult
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


_pad2 = pad2d  # internal alias used by the kernels below


_SCALE_LANES = 128


def tile_cols(cols: int, g: int) -> tuple[int, int]:
    """Column tile of the int8 kernels for a ``cols``-wide buffer whose
    ``cols`` is a whole number of ``g``-wide groups: ``(ct, sct)``, the
    payload columns and scale columns each grid step owns.

    The TPU tiling takes a block's last dimension only when it is a
    multiple of 128 or the array's whole width.  A scale block therefore
    spans either every group of the row (up to 128 groups: one tile) or
    exactly 128 groups (``ct = 128 * g`` payload columns per step; the
    last step may be partial, and its out-of-range groups are dropped on
    write)."""
    ng = cols // g
    if ng <= _SCALE_LANES:
        return cols, ng
    return _SCALE_LANES * g, _SCALE_LANES


def group_scales(x: jax.Array, g: int) -> tuple[jax.Array, jax.Array]:
    """(rows, k*g) f32 -> (grouped (rows, k, g), scales (rows, k)): the
    per-group amax scale, one expression shared by the quantizing
    kernels; ``ref.quantize_ref`` writes the same expression out on its
    own, so a fault here shows as a bitwise difference."""
    rows, cols = x.shape
    xg = x.reshape(rows, cols // g, g)
    amax = jnp.max(jnp.abs(xg), axis=2)
    return xg, amax * _INV127 + _EPS


def _quantize_kernel(x_ref, codes_ref, scale_ref, *, g: int):
    rows, ct = x_ref.shape
    xg, scale = group_scales(x_ref[...].astype(jnp.float32), g)
    q = jnp.clip(jnp.round(xg / scale[..., None]), -127, 127)
    codes_ref[...] = q.reshape(rows, ct).astype(jnp.int8)
    scale_ref[...] = scale


def quantize(
    x: jax.Array,
    *,
    group: int = DEFAULT_GROUP,
    row_tile: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization with per-(row, group) scales.

    Any 2-D shape: ragged rows/cols are zero-padded to the (row_tile,
    group) grid internally and sliced back.  Returns ``codes`` of
    ``x.shape`` and ``scales`` of ``(rows, ceil(cols / min(group, cols)))``.
    """
    if x.ndim != 2:
        raise ValueError(f"need 2-D input, got {x.shape}")
    rows, cols = x.shape
    g = min(group, cols)
    rt = min(row_tile, rows)
    xp = _pad2(x, rt, g)
    rp, cp = xp.shape
    ct, sct = tile_cols(cp, g)
    codes, scales = pl.pallas_call(
        functools.partial(_quantize_kernel, g=g),
        grid=(rp // rt, pl.cdiv(cp, ct)),
        in_specs=[pl.BlockSpec((rt, ct), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((rt, ct), lambda i, j: (i, j)),
            pl.BlockSpec((rt, sct), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, cp), jnp.int8),
            jax.ShapeDtypeStruct((rp, cp // g), jnp.float32),
        ],
        interpret=interpret,
    )(xp)
    if (rp, cp) != (rows, cols):
        codes = codes[:rows, :cols]
        scales = scales[:rows]
    return codes, scales


def _dequant_add_kernel(acc_ref, codes_ref, scale_ref, o_ref, *, g: int):
    rows, ct = codes_ref.shape
    acc = acc_ref[...].astype(jnp.float32)
    q = codes_ref[...].astype(jnp.float32).reshape(rows, ct // g, g)
    deq = (q * scale_ref[...][..., None]).reshape(rows, ct)
    o_ref[...] = (acc + deq).astype(o_ref.dtype)


def dequant_add(
    acc: jax.Array,
    codes: jax.Array,
    scales: jax.Array,
    *,
    group: int = DEFAULT_GROUP,
    row_tile: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Fused ``acc + dequant(codes, scales)`` (the compressed-round ⊕).

    Ragged shapes are zero-padded internally (zero codes dequantize to 0,
    so padding never perturbs the accumulator) and sliced back.
    """
    rows, cols = codes.shape
    g = min(group, cols)
    rt = min(row_tile, rows)
    ng = wire_ngroups(cols, g)
    if acc.shape != codes.shape:
        raise ValueError(f"acc {acc.shape} vs codes {codes.shape}")
    if scales.shape != (rows, ng):
        raise ValueError(f"scales {scales.shape}, want {(rows, ng)}")
    accp = _pad2(acc, rt, g)
    codesp = _pad2(codes, rt, g)
    rp, cp = codesp.shape
    scalesp = scales if rp == rows else jnp.pad(scales, ((0, rp - rows),
                                                         (0, 0)))
    ct, sct = tile_cols(cp, g)
    tile = pl.BlockSpec((rt, ct), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(_dequant_add_kernel, g=g),
        grid=(rp // rt, pl.cdiv(cp, ct)),
        in_specs=[tile, tile,
                  pl.BlockSpec((rt, sct), lambda i, j: (i, j))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rp, cp), acc.dtype),
        interpret=interpret,
    )(accp, codesp, scalesp)
    if (rp, cp) != (rows, cols):
        out = out[:rows, :cols]
    return out


# ---------------------------------------------------------------------------
# int8 wire format: [codes | scale bytes] in ONE int8 buffer per round
# ---------------------------------------------------------------------------

def pack_wire(codes: jax.Array, scales: jax.Array) -> jax.Array:
    """Pack int8 codes (rows, cols) + f32 scales (rows, ng) into one
    contiguous int8 buffer (rows, cols + 4*ng) — the compressed round's
    single ppermute payload.  The scale bytes follow the codes one byte
    plane at a time (byte k of every scale, k = 0..3): no array gets a
    minor dimension of 4, which the TPU compiler would relay out row by
    row."""
    u = lax.bitcast_convert_type(scales, jnp.uint32)          # (rows, ng)
    planes = [lax.bitcast_convert_type(
        ((u >> (8 * k)) & 0xFF).astype(jnp.uint8), jnp.int8)
        for k in range(4)]
    return jnp.concatenate([codes, *planes], axis=1)


def unpack_wire(wire: jax.Array, cols: int, *,
                group: int = DEFAULT_GROUP) -> tuple[jax.Array, jax.Array]:
    """Inverse of ``pack_wire``: split a (rows, wire_width(cols, group))
    int8 buffer back into codes (rows, cols) and f32 scales (rows, ng)."""
    ng = wire_ngroups(cols, group)
    if wire.shape[1] != cols + 4 * ng:
        raise ValueError(
            f"wire has {wire.shape[1]} cols, want {cols + 4 * ng} "
            f"(cols={cols}, group={group})")
    codes = wire[:, :cols]
    sb = lax.bitcast_convert_type(wire[:, cols:], jnp.uint8)
    u = sum(sb[:, k * ng:(k + 1) * ng].astype(jnp.uint32) << (8 * k)
            for k in range(4)).astype(jnp.uint32)
    return codes, lax.bitcast_convert_type(u, jnp.float32)
