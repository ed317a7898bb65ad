"""Subprocess worker for the ``kernels`` benchmark group: the Pallas
kernels against their jnp oracles (allclose / bitwise) with host timings
of the current backend (interpret mode on CPU: structure, not speed).

Run: python benchmarks/_kernel_worker.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402


def emit(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.3f},{derived}")


def bench_kernels():
    import jax
    import jax.numpy as jnp
    from repro.kernels import (fused_block_reduce, fused_round,
                               quantize_blocks)
    from repro.kernels import ref as R

    rng = np.random.default_rng(0)
    for shape in [(256, 512), (1024, 2048)]:
        a = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        b = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        fused_block_reduce(a, b).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(5):
            out = fused_block_reduce(a, b)
        out.block_until_ready()
        us = (time.perf_counter() - t0) / 5 * 1e6
        ref = R.block_reduce_ref(a, b)
        ok = bool(jnp.allclose(out, ref))
        emit(f"kernels/block_reduce_{shape[0]}x{shape[1]}", us,
             f"allclose={ok};interpret=True")

    # Fused circulant round (fold + next-send layout, one pass) vs the
    # unfused jnp chain (reduce + concat + 2 slices) on one mid-game round
    # shape: live 8 blocks, 4 received, keep/send split at 4.
    def one_round(f):
        @jax.jit
        def run(live, T):
            return f(live, T, nb=4, next_lo=4, op="add")
        return run

    fused_fn = one_round(fused_round)
    unfused_fn = one_round(R.fused_round_ref)

    def timed(f, live, T, iters=20):
        t0 = time.perf_counter()
        for _ in range(iters):
            k, s = f(live, T)
        k.block_until_ready()
        s.block_until_ready()
        return (time.perf_counter() - t0) / iters * 1e6

    for cols in [16384, 65536]:
        live = jnp.asarray(rng.standard_normal((8, cols)), jnp.float32)
        T = jnp.asarray(rng.standard_normal((4, cols)), jnp.float32)
        for f in (fused_fn, unfused_fn):  # warm up both before timing
            k, s = f(live, T)
            k.block_until_ready()
        # Paired back-to-back reps: per-rep ratios cancel common-mode
        # machine-load drift (shared CI runners swing several-x); the
        # reported ratio is the median of the paired ratios.
        t_fused, t_unfused, ratios = 1e30, 1e30, []
        for _ in range(9):
            tf = timed(fused_fn, live, T)
            tu = timed(unfused_fn, live, T)
            ratios.append(tf / tu)
            t_fused, t_unfused = min(t_fused, tf), min(t_unfused, tu)
        ratio = sorted(ratios)[len(ratios) // 2]
        kf, sf = fused_fn(live, T)
        ku, su = unfused_fn(live, T)
        ok = bool(jnp.array_equal(kf, ku) and jnp.array_equal(sf, su))
        emit(f"kernels/fused_round_8x{cols}", t_fused,
             f"bitwise={ok};unfused_us={t_unfused:.3f};"
             f"ratio={ratio:.3f};interpret=True")

    x = jnp.asarray(rng.standard_normal((16, 4096)), jnp.float32)
    t0 = time.perf_counter()
    payload = quantize_blocks(x, group=512)
    comp = payload["codes"].size + payload["scales"].size * 4
    us = (time.perf_counter() - t0) * 1e6
    emit("kernels/quantize_16x4096", us,
         f"compression={x.size * 4 / comp:.2f}x")

    # Compressed round (dequant + fold + requant-next-send, one pass) vs
    # its jnp oracle on the same mid-game round geometry; both jitted —
    # under jit the two are bitwise-equal (identical arithmetic; XLA
    # makes the same contraction choices for both graphs).
    from repro.kernels import fused_round_dq
    from repro.kernels.ref import fused_round_dq_ref, quantize_ref

    def one_dq_round(f):
        @jax.jit
        def run(live, c, s):
            return f(live, c, s, nb=4, next_lo=4, op="add", group=512)
        return run

    dq_fused = one_dq_round(fused_round_dq)
    dq_ref = one_dq_round(fused_round_dq_ref)
    for cols in [16384, 65536]:
        live = jnp.asarray(rng.standard_normal((8, cols)), jnp.float32)
        c, s = quantize_ref(
            jnp.asarray(rng.standard_normal((4, cols)), jnp.float32),
            group=512)
        c, s = jax.device_put(c), jax.device_put(s)

        def timed_dq(f, iters=20):
            t0 = time.perf_counter()
            for _ in range(iters):
                k, sd = f(live, c, s)
            k.block_until_ready()
            return (time.perf_counter() - t0) / iters * 1e6

        for f in (dq_fused, dq_ref):
            k, _ = f(live, c, s)
            k.block_until_ready()
        t_fused, t_ref, ratios = 1e30, 1e30, []
        for _ in range(9):
            tf, tu = timed_dq(dq_fused), timed_dq(dq_ref)
            ratios.append(tf / tu)
            t_fused, t_ref = min(t_fused, tf), min(t_ref, tu)
        ratio = sorted(ratios)[len(ratios) // 2]
        kf, sf = dq_fused(live, c, s)
        ku, su = dq_ref(live, c, s)
        ok = bool(jnp.array_equal(kf, ku)
                  and jnp.array_equal(sf[0], su[0])
                  and jnp.array_equal(sf[1], su[1]))
        emit(f"kernels/fused_round_dq_8x{cols}", t_fused,
             f"bitwise={ok};unfused_us={t_ref:.3f};"
             f"ratio={ratio:.3f};interpret=True")


if __name__ == "__main__":
    bench_kernels()
