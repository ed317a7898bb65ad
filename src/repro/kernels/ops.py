"""Jitted public wrappers around the Pallas kernels.

Handle: arbitrary (non-tile-aligned) shapes via padding, >2-D payloads via
flattening, CPU fallback via interpret mode, and a pure-jnp escape hatch
(``backend='jnp'``) so the framework runs everywhere.  The collective layer
calls these; kernels never leak pallas details upward.
"""
from __future__ import annotations

import functools

import jax

from . import block_reduce as _br
from . import quantize as _qz
from . import ref as _ref


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad2d(x, rt, ct):
    r, c = x.shape
    return _qz.pad2d(x, rt, ct), (r, c)


def _to2d(x):
    """Flatten anything to 2-D (leading, rest)."""
    if x.ndim == 2:
        return x, x.shape
    if x.ndim < 2:
        return x.reshape(1, -1), x.shape
    return x.reshape(x.shape[0], -1), x.shape


@functools.partial(jax.jit, static_argnames=("op", "backend"))
def fused_block_reduce(a: jax.Array, b: jax.Array, *, op: str = "add",
                       backend: str = "pallas") -> jax.Array:
    """``a ⊕ b`` with VMEM tiling (any shape, any rank)."""
    if backend == "jnp":
        return _ref.block_reduce_ref(a, b, op=op)
    a2, orig_shape = _to2d(a)
    b2, _ = _to2d(b)
    rt, ct = _br.DEFAULT_ROW_TILE, _br.DEFAULT_COL_TILE
    rt, ct = min(rt, a2.shape[0]), min(ct, a2.shape[1])
    ap, (r, c) = _pad2d(a2, rt, ct)
    bp, _ = _pad2d(b2, rt, ct)
    out = _br.block_reduce(ap, bp, op=op, row_tile=rt, col_tile=ct,
                           interpret=_interpret_default())
    return out[:r, :c].reshape(orig_shape)


def quantize_blocks(x: jax.Array, *, group: int = _qz.DEFAULT_GROUP,
                    backend: str = "pallas"):
    """int8-quantize a payload; returns {'codes', 'scales'} pytree whose
    leaves ppermute independently (the compressed-round payload).  Ragged
    shapes are handled inside the kernel (pad-and-slice), so ``codes``
    has exactly the flattened input shape."""
    x2, orig_shape = _to2d(x)
    cols = x2.shape[1]
    g = min(group, cols)
    if backend == "jnp":
        codes, scales = _ref.quantize_ref(x2, group=g)
    else:
        codes, scales = _qz.quantize(x2, group=g,
                                     interpret=_interpret_default())
    return {"codes": codes, "scales": scales,
            "meta": (orig_shape, cols, g)}


def dequantize_blocks(payload, *, backend: str = "pallas") -> jax.Array:
    """Inverse of quantize_blocks (unfused; for tests/serving)."""
    orig_shape, cols, g = payload["meta"]
    x = _ref.dequant_ref(payload["codes"], payload["scales"], group=g)
    return x.reshape(orig_shape)


def dequant_accumulate(acc: jax.Array, payload, *,
                       backend: str = "pallas") -> jax.Array:
    """Fused ``acc + dequant(payload)`` — the compressed-round ⊕."""
    orig_shape, cols, g = payload["meta"]
    acc2, _ = _to2d(acc)
    if backend == "jnp":
        out = _ref.dequant_add_ref(acc2, payload["codes"], payload["scales"],
                                   group=g)
    else:
        out = _qz.dequant_add(acc2, payload["codes"], payload["scales"],
                              group=g, interpret=_interpret_default())
    return out.reshape(orig_shape)


def make_compressors(group: int = _qz.DEFAULT_GROUP, backend: str = "pallas"):
    """(compress, decompress) pair for circulant_reduce_scatter's per-round
    hooks.  The collective ppermutes every array leaf of the compressed
    payload; static shape metadata must NOT ride along (it would be traced
    and/or ppermuted), so it is carried through a trace-time closure —
    compress and decompress are always called back-to-back within one
    round's trace, so a single-slot cell is sound."""
    meta_cell: dict[str, tuple] = {}

    def compress(x):
        payload = quantize_blocks(x, group=group, backend=backend)
        meta_cell["meta"] = payload.pop("meta")
        return payload

    def decompress(payload):
        payload = dict(payload)
        payload["meta"] = meta_cell["meta"]
        return dequantize_blocks(payload, backend=backend)

    return compress, decompress
