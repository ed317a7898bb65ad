"""Subprocess worker for bench_collectives: wall-clock of the shard_map
collectives on 8 simulated CPU devices.  Emits CSV rows on stdout."""
import os
import sys
import time

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import compat  # noqa: E402
from repro.core import CollectiveSpec  # noqa: E402
from repro.core import collectives as C  # noqa: E402

NDEV = 8
mesh = compat.make_mesh((NDEV,), ("x",))
rng = np.random.default_rng(0)


def timed(fn, x, iters=10):
    # check_vma=False: the fused rows run pallas_call; harmless for the
    # jnp rows.
    f = jax.jit(compat.shard_map(lambda v: fn(v[0])[None], mesh=mesh,
                                 in_specs=(P("x"),), out_specs=P("x"),
                                 check_vma=False))
    f(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(x)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


for n_elem in [1 << 12, 1 << 18, 1 << 22]:
    x = rng.standard_normal((NDEV, n_elem)).astype(np.float32)
    rows = {
        "circulant_rs": lambda v: C.circulant_reduce_scatter(v, "x"),
        "circulant_rs_pow2": lambda v: C.circulant_reduce_scatter(
            v, "x", schedule="power2"),
        "circulant_rs_fused": lambda v: C.circulant_reduce_scatter(
            v, "x", use_fused_kernel=True),
        "ring_rs": lambda v: C.ring_reduce_scatter(v, "x"),
        "xla_rs": lambda v: C.xla_reduce_scatter(v, "x"),
        "circulant_rs_int8": lambda v: C.circulant_reduce_scatter(
            v, "x", wire_dtype="int8"),
        "circulant_ar": lambda v: C.circulant_allreduce(v, "x"),
        "circulant_ar_fused": lambda v: C.circulant_allreduce(
            v, "x", use_fused_kernel=True),
        "circulant_ar_int8": lambda v: C.circulant_allreduce(
            v, "x", wire_dtype="int8"),
        "ring_ar": lambda v: C.ring_allreduce(v, "x"),
        "xla_psum": lambda v: C.xla_allreduce(v, "x"),
        # plan/execute API rows: same collectives through CollectiveSpec
        # dispatch (overhead must be invisible — plans are cached).
        "spec_rs": lambda v: C.reduce_scatter(
            v, "x", spec=CollectiveSpec()),
        "spec_ar_int8": lambda v: C.allreduce(
            v, "x", spec=CollectiveSpec(wire_dtype="int8")),
    }
    for name, fn in rows.items():
        us = timed(fn, x)
        print(f"collectives/{name}_n{n_elem},{us:.3f},ndev={NDEV}")

# Non-uniform (Corollary 3) reduce-scatter: worst case, one column holds
# the whole vector — every round ships ~n_elem rows from one rank.
for n_elem in [1 << 12, 1 << 18]:
    counts = [0] * NDEV
    counts[NDEV // 2] = n_elem
    spec = CollectiveSpec(counts=tuple(counts))
    x = rng.standard_normal((NDEV, n_elem)).astype(np.float32)
    us = timed(lambda v: C.reduce_scatter(v, "x", spec=spec), x)
    print(f"collectives/spec_rs_onecol_n{n_elem},{us:.3f},ndev={NDEV}")
