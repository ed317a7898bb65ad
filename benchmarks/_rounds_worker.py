"""Subprocess worker for the ``rounds`` and ``cost_model`` benchmark
groups: exact round/block/⊕ counts of the numpy simulator against
Theorem 1/2, and the α-β-γ cost model's predicted times (Corollary 1/3).

Run: python benchmarks/_rounds_worker.py rounds|cost_model
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402


def emit(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.3f},{derived}")


def bench_rounds():
    from repro.core import simulator as sim
    from repro.core.schedule import ceil_log2

    for p in [2, 3, 7, 8, 22, 31, 64, 100, 255, 256, 257, 1000]:
        inputs = [[np.ones(1, np.float64) for _ in range(p)]
                  for _ in range(p)]
        t0 = time.perf_counter()
        _, st = sim.simulate_reduce_scatter(inputs)
        us = (time.perf_counter() - t0) * 1e6
        st.assert_theorem1(p)
        emit(f"rounds/reduce_scatter_p{p}", us,
             f"rounds={st.rounds};blocks={st.blocks_sent[0]};"
             f"theory_rounds={ceil_log2(p)};theory_blocks={p - 1}")
    for p in [8, 22, 64, 257]:
        inputs = [[np.ones(1, np.float64) for _ in range(p)]
                  for _ in range(p)]
        t0 = time.perf_counter()
        _, st = sim.simulate_allreduce(inputs)
        us = (time.perf_counter() - t0) * 1e6
        st.assert_theorem2(p)
        emit(f"rounds/allreduce_p{p}", us,
             f"rounds={st.rounds};blocks={st.blocks_sent[0]};"
             f"theory_rounds={2 * ceil_log2(p)};theory_blocks={2 * (p - 1)}")



def bench_cost_model():
    from repro.core import cost_model as cm

    model = cm.CommModel.tpu_v5e()
    for p in [16, 64, 256, 1024]:
        for m in [4096, 1 << 20, 1 << 28]:
            rows = {
                "circulant": cm.t_allreduce(m, p, model),
                "circulant_torus": cm.t_allreduce(m, p, model, torus=True),
                "ring": cm.t_ring_allreduce(m, p, model),
                "reduce_bcast": cm.t_bcast_reduce_allreduce(m, p, model),
            }
            best = min(rows, key=rows.get)
            for name, t in rows.items():
                emit(f"cost_model/allreduce_p{p}_m{m}/{name}", t * 1e6,
                     f"best={best}")
        x = cm.crossover_m(p, model)
        emit(f"cost_model/torus_crossover_p{p}", 0.0,
             f"ring_beats_circulant_above_m={x:.3g}")
    # Alltoall: hop-through-intermediate-ranks β volume (Bruck trade-off).
    for p in [16, 64, 256]:
        m = 1 << 20
        entries = cm.a2a_round_entries(p)
        emit(f"cost_model/alltoall_p{p}_m{m}", cm.t_alltoall(m, p, model) * 1e6,
             f"rounds={len(entries)};blocks_sent={sum(entries)};"
             f"volume_amplification={sum(entries) / (p - 1):.2f}x")


if __name__ == "__main__":
    {"rounds": bench_rounds, "cost_model": bench_cost_model}[sys.argv[1]]()
