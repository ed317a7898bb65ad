"""The work a step, or a kernel call, requires: FLOPs and HBM bytes from
shapes alone.

These count what the algorithm needs, not what an implementation does:
no recomputation, no padding, no copies the mathematics does not ask
for, causal attention over the keys a query may see.  A share of a peak
computed from them is then honest whatever later implements the step.
Multiply-accumulates count as 2 FLOPs.
"""
from __future__ import annotations


def _isz(dtype) -> int:
    import numpy as np
    return np.dtype(dtype).itemsize


def matmul_params_per_layer(s) -> int:
    """Weights one layer multiplies by for each token."""
    attn = s.d * s.h * s.dh + 2 * s.d * s.kv * s.dh + s.h * s.dh * s.d
    return attn + 3 * s.d * s.ff


def layer_weight_elems(s) -> int:
    """Every weight of one layer (matrices, norm gains)."""
    return matmul_params_per_layer(s) + 2 * s.d + (2 * s.dh if s.qk_norm
                                                   else 0)


def forward_flops(s, tokens: int, keys: int) -> float:
    """Forward FLOPs for ``tokens`` tokens that attend ``keys`` keys in
    all (summed over the tokens), through every layer and the head."""
    per_token = 2 * (s.layers * matmul_params_per_layer(s) + s.d * s.vocab)
    attn = s.layers * 4 * s.h * s.dh * keys      # QK^T and PV
    return float(per_token * tokens + attn)


def causal_keys(seq: int) -> int:
    """Keys attended by a causal sequence of ``seq`` tokens, summed."""
    return seq * (seq + 1) // 2


def train_step_flops(s, batch: int, seq: int) -> float:
    """Forward and backward (twice the forward) of one training step."""
    return 3 * forward_flops(s, batch * seq, batch * causal_keys(seq))


def decode_step(s, active: int, keys: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one batched decode step: ``active`` sequences
    that attend ``keys`` cached keys in all (the new one included).

    Bytes: every weight read once (the embedding table only for the
    ``active`` rows looked up), the cached keys and values attended, the
    new key and value written, and the logits written."""
    isz = _isz(s.dtype)
    flops = forward_flops(s, active, keys)
    weights = (s.layers * layer_weight_elems(s) + s.d            # final norm
               + s.d * s.vocab + active * s.d)                   # head, rows
    kv = s.layers * 2 * s.kv * s.dh * (keys + active)            # read+write
    return flops, float((weights + kv) * isz + active * s.vocab * 4)


def prefill(s, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one prompt of ``seq`` tokens: weights read once,
    keys and values written, last-position logits."""
    isz = _isz(s.dtype)
    flops = forward_flops(s, seq, causal_keys(seq))
    weights = s.layers * layer_weight_elems(s) + s.d + s.d * s.vocab \
        + seq * s.d
    kv = s.layers * 2 * s.kv * s.dh * seq
    return flops, float((weights + kv) * isz + s.vocab * 4)


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip needs: the larger of the compute bound and the
    memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def reduce_scatter_fold_bytes(n: int, p: int, itemsize: int) -> float:
    """HBM bytes the folds of a reduce-scatter of ``n`` elements over
    ``p`` ranks require on each rank, whatever the schedule: it has to
    add the ``p - 1`` blocks of ``n / p`` elements it receives into its
    own, reading both operands and writing the sum."""
    return 3.0 * (p - 1) / p * n * itemsize


def zero1_sync_elems(param_shapes, world: int, min_numel: int = 1024
                     ) -> int:
    """Elements the ZeRO-1 gradient reduce-scatter carries per step: every
    leaf big enough to shard, its leading axis padded to the world (the
    rows are the gradient's; the padding adds nothing to the sum, so it
    is not counted)."""
    n = 0
    for shape in param_shapes:
        numel = 1
        for d in shape:
            numel *= d
        if shape and numel >= max(min_numel, world):
            n += numel
    return n
