"""Pallas TPU kernel: fused block reduction — the ⊕ hot loop of Algorithm 1.

Each communication round folds the received blocks T into the live buffer
head: ``R[:nb] = R[:nb] ⊕ T``.  On TPU this is the paper's γ-term; done
naively it is three HBM round-trips per element.  The kernel streams both
operands HBM→VMEM in (row_tile, col_tile) blocks aligned to the VPU lanes
(8×128), reduces in VMEM, and writes back one result tile — exactly one
read of each operand and one write of the result.

Target: TPU (MXU/VPU); validated on CPU via ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# VPU-aligned default tiles: 8 sublanes x 128 lanes for fp32; rows are
# multiplied up for bf16-friendly (16, 128) packing by ops.py.
DEFAULT_ROW_TILE = 256
DEFAULT_COL_TILE = 512

# Shared named-⊕ table (fused_round.py imports it; ref.py/collectives
# mirror the same names for their jnp paths).
_OPS = {
    "add": lambda a, b: a + b,
    "max": jnp.maximum,
    "min": jnp.minimum,
}


def _block_reduce_kernel(a_ref, b_ref, o_ref, *, op: str):
    a = a_ref[...]
    b = b_ref[...]
    o_ref[...] = _OPS[op](a, b)


def block_reduce(
    a: jax.Array,
    b: jax.Array,
    *,
    op: str = "add",
    row_tile: int = DEFAULT_ROW_TILE,
    col_tile: int = DEFAULT_COL_TILE,
    interpret: bool = False,
) -> jax.Array:
    """Elementwise ``a ⊕ b`` for 2-D (rows, cols) operands with explicit
    VMEM tiling.  Shapes must be tile-divisible (ops.py pads)."""
    if a.shape != b.shape or a.ndim != 2:
        raise ValueError(f"need equal 2-D shapes, got {a.shape} vs {b.shape}")
    rows, cols = a.shape
    rt, ct = min(row_tile, rows), min(col_tile, cols)
    if rows % rt or cols % ct:
        raise ValueError(f"shape {a.shape} not divisible by tile ({rt},{ct})")
    grid = (rows // rt, cols // ct)
    spec = pl.BlockSpec((rt, ct), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_block_reduce_kernel, op=op),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        name="block_reduce",
        interpret=interpret,
    )(a, b)
