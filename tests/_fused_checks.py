"""Subprocess worker: fused-vs-unfused equivalence for every circulant
collective on N fake CPU devices (N non-power-of-two included — the
paper's general case).

For each collective (RS / AG / AR / alltoall) the fused Pallas round path
(``use_fused_kernel=True``, interpret mode on CPU) must be BITWISE equal
to the jnp path: the kernel reorders no arithmetic, it only fuses the
local data movement.  Sweeps non-tile-divisible block sizes (odd cols
exercise the kernel's edge handling), bf16 / int32 payloads, rank-3
payloads, and non-default schedules.

Run:  python tests/_fused_checks.py <ndev>
"""
import os
import sys

NDEV = int(sys.argv[1]) if len(sys.argv) > 1 else 6
import re  # noqa: E402 — strip inherited count: XLA keeps the LAST flag
_inherited = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                    os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={NDEV} " + _inherited)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import compat  # noqa: E402
from repro.core import collectives as C  # noqa: E402

mesh = compat.make_mesh((NDEV,), ("x",))
rng = np.random.default_rng(123)
p = NDEV


def run1(fn, x_global):
    """check_vma=False: the replication checker is off for the
    pallas_call rows; numerics are asserted below instead."""
    f = jax.jit(compat.shard_map(lambda v: fn(v[0])[None], mesh=mesh,
                                 in_specs=(P("x"),), out_specs=P("x"),
                                 check_vma=False))
    return np.asarray(f(x_global))


def check(name, cond=True):
    if not cond:
        raise AssertionError(f"FAILED: {name}")
    print(f"ok: {name}")


def both(fn_of_fused, x):
    a = run1(lambda v: fn_of_fused(v, True), x)
    b = run1(lambda v: fn_of_fused(v, False), x)
    return a, b


def make(shape, dtype):
    if dtype == jnp.int32:
        return jnp.asarray(rng.integers(-99, 99, shape), jnp.int32)
    return jnp.asarray(rng.standard_normal(shape), dtype)


# --- reduce-scatter: dtypes × odd (non-tile-divisible) block sizes ---
for dtype in (jnp.float32, jnp.bfloat16, jnp.int32):
    for blk in (4, 515):  # 515 floats/block: no tile boundary divides it
        x = make((p, p * blk), dtype)
        a, b = both(lambda v, f: C.circulant_reduce_scatter(
            v, "x", use_fused_kernel=f), x)
        check(f"RS fused==unfused bitwise [p={p} blk={blk} "
              f"{jnp.dtype(dtype).name}]", np.array_equal(a, b))

# --- schedules (non-default round structures) ---
x = make((p, p * 12), jnp.float32)
for sched in ("power2", "fully_connected", "sqrt"):
    a, b = both(lambda v, f, s=sched: C.circulant_reduce_scatter(
        v, "x", schedule=s, use_fused_kernel=f), x)
    check(f"RS[{sched}] fused==unfused bitwise", np.array_equal(a, b))

# --- rank-3 payload + max op ---
x3 = make((p, p * 5, 3), jnp.float32)
a, b = both(lambda v, f: C.circulant_reduce_scatter(
    v, "x", op="max", use_fused_kernel=f), x3)
check("RS rank-3 op=max fused==unfused bitwise", np.array_equal(a, b))

# --- allgather ---
blocks = make((p, 515), jnp.float32)
a, b = both(lambda v, f: C.circulant_allgather(
    v, "x", use_fused_kernel=f), blocks)
check("AG fused==unfused bitwise", np.array_equal(a, b))
check("AG gathers all blocks",
      np.array_equal(a.reshape(p, p, 515)[0], np.asarray(blocks)))

# --- allreduce (RS + AG composed) ---
for dtype in (jnp.float32, jnp.int32):
    x = make((p, p * 7), dtype)
    a, b = both(lambda v, f: C.circulant_allreduce(
        v, "x", use_fused_kernel=f), x)
    check(f"AR fused==unfused bitwise [{jnp.dtype(dtype).name}]",
          np.array_equal(a, b))

# --- int8 wire format: fused vs unfused compressed rounds must agree
# BITWISE (identical arithmetic, both jitted — the Pallas dq-round kernel
# and its jnp oracle trace to the same XLA graph shapes), and the
# compressed result must sit within the quantization error of the exact
# jnp reduce-scatter ---
for blk in (4, 515):  # 515: ragged quantization group (515 % 512 != 0)
    x = make((p, p * blk), jnp.float32)
    a, b = both(lambda v, f: C.circulant_reduce_scatter(
        v, "x", wire_dtype="int8", use_fused_kernel=f), x)
    check(f"RS int8-wire fused==unfused bitwise [blk={blk}]",
          np.array_equal(a, b))
    exact = run1(lambda v: C.circulant_reduce_scatter(v, "x"), x)
    err = np.abs(a.astype(np.float64) - exact.astype(np.float64)).max()
    check(f"RS int8-wire within quantization error of exact "
          f"[blk={blk}] (max err {err:.3f})", err < 0.05 * p + 0.1)

x = make((p, p * 7), jnp.float32)
a, b = both(lambda v, f: C.circulant_allreduce(
    v, "x", wire_dtype="int8", use_fused_kernel=f), x)
check("AR int8-wire fused==unfused bitwise", np.array_equal(a, b))
for r in range(p):
    np.testing.assert_array_equal(a[r], a[0])
check("AR int8-wire output bitwise-replicated across ranks")

blocks = make((p, 515), jnp.float32)
a, b = both(lambda v, f: C.circulant_allgather(
    v, "x", wire_dtype="int8", use_fused_kernel=f), blocks)
check("AG int8-wire fused==unfused bitwise", np.array_equal(a, b))
err = np.abs(a.reshape(p, p, 515).astype(np.float64)
             - np.asarray(blocks, np.float64)[None]).max()
check(f"AG int8-wire one-quantization error bound (max err {err:.4f})",
      err < 0.05)

# --- alltoall (⊕ = concatenation; fused uses stacked slots + Pallas
# row-permutation for the final source ordering) ---
a2a = make((p, p, 7), jnp.float32)
a, b = both(lambda v, f: C.circulant_alltoall(
    v, "x", use_fused_kernel=f), a2a)
check("A2A fused==unfused bitwise", np.array_equal(a, b))
ref = np.asarray(a2a)
for r in range(p):
    for j in range(p):
        np.testing.assert_array_equal(a[r, j], ref[j, r])
check("A2A fused transposes payloads correctly")

print(f"ALL FUSED CHECKS PASSED (ndev={NDEV})")
