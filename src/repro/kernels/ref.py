"""Pure-jnp oracles for every Pallas kernel (the allclose references).

The quantized-round oracles (``quantize_ref`` / ``dequant_ref`` /
``fused_round_dq_ref``) use the exact same elementwise expressions and
f32 accumulation as the kernels, so on the interpret path the kernel and
the reference are BITWISE equal — the conformance harness relies on this
to hold the fused compressed path to the jnp compressed path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Shared with the kernels — the bitwise kernel-vs-oracle contract depends
# on both sides using the exact same constants and op shapes.
from .quantize import _EPS, _INV127


def block_reduce_ref(a: jax.Array, b: jax.Array, *, op: str = "add") -> jax.Array:
    return {"add": jnp.add, "max": jnp.maximum, "min": jnp.minimum}[op](a, b)


def fused_round_ref(live: jax.Array, received: jax.Array, *, nb: int,
                    next_lo: int, op: str = "add"
                    ) -> tuple[jax.Array, jax.Array | None]:
    """jnp oracle for kernels.fused_round: fold + keep/send split."""
    lo = live.shape[0]
    head = block_reduce_ref(live[:nb], received, op=op)
    new = jnp.concatenate([head, live[nb:lo]], axis=0)
    if next_lo == lo:
        return new, None
    return new[:next_lo], new[next_lo:lo]


def permute_rows_ref(x: jax.Array, perm) -> jax.Array:
    # Static row slices: a gather with a constant index vector compiles
    # for minutes on a TPU at an embedding leaf's width.
    return jnp.concatenate([x[int(i):int(i) + 1] for i in perm], axis=0)


def _pad_cols(x: jax.Array, g: int) -> jax.Array:
    pc = (-x.shape[1]) % g
    return jnp.pad(x, ((0, 0), (0, pc))) if pc else x


def quantize_ref(x: jax.Array, *, group: int = 512
                 ) -> tuple[jax.Array, jax.Array]:
    rows, cols = x.shape
    g = min(group, cols)
    xp = _pad_cols(x.astype(jnp.float32), g)
    xg = xp.reshape(rows, -1, g)
    amax = jnp.max(jnp.abs(xg), axis=2)                    # (rows, ng)
    scale = amax * _INV127 + _EPS
    q = jnp.clip(jnp.round(xg / scale[..., None]), -127, 127)
    codes = q.reshape(rows, xp.shape[1]).astype(jnp.int8)
    return codes[:, :cols], scale


def dequant_ref(codes: jax.Array, scales: jax.Array, *, group: int = 512
                ) -> jax.Array:
    rows, cols = codes.shape
    g = min(group, cols)
    qp = _pad_cols(codes.astype(jnp.float32), g)
    qg = qp.reshape(rows, -1, g)
    return (qg * scales[..., None]).reshape(rows, qp.shape[1])[:, :cols]


def dequant_add_ref(acc, codes, scales, *, group: int = 512):
    return (acc.astype(jnp.float32)
            + dequant_ref(codes, scales, group=group)).astype(acc.dtype)


def fused_round_dq_ref(
    live: jax.Array,
    codes: jax.Array,
    scales: jax.Array,
    *,
    nb: int,
    next_lo: int,
    op: str = "add",
    group: int = 512,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array] | None]:
    """jnp oracle for the compressed circulant round
    (kernels.fused_round.fused_round_dq): dequantize the received int8
    payload, ⊕-fold it into the f32 live-buffer head, split keep/send,
    and REQUANTIZE the next round's send rows.

    Returns ``(keep, (send_codes, send_scales))``, with the send pair
    ``None`` on the final round (``next_lo == lo``).
    """
    lo = live.shape[0]
    deq = dequant_ref(codes, scales, group=group)
    head = block_reduce_ref(live[:nb].astype(jnp.float32), deq, op=op)
    new = jnp.concatenate([head, live[nb:lo].astype(jnp.float32)], axis=0)
    if next_lo == lo:
        return new, None
    send_codes, send_scales = quantize_ref(new[next_lo:lo], group=group)
    return new[:next_lo], (send_codes, send_scales)
