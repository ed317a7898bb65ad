"""Benchmark harness — one benchmark per paper table/claim.

The paper (Träff 2024) is an algorithms paper: its quantitative content is
Theorem 1/2 (round/volume optimality), Corollaries 1-3 (α-β-γ cost model)
and the Corollary-2 schedule family.  Benchmarks:

  rounds       exact round/block/⊕ counts vs theory (Theorem 1/2)
  cost_model   predicted T(m,p) per algorithm/schedule (Corollary 1/3),
               including the beyond-paper torus hop refinement
  collectives  wall-clock of the shard_map collectives on 8 simulated
               devices (subprocess; structure demo, not TPU perf)
  kernels      Pallas interpret-mode vs jnp-ref timing + allclose
  wire         measured bytes-on-wire per (collective × wire format) from
               compiled HLO vs the analytic codes+scales budget — the
               int8 wire format's ~3.9x β-term reduction, machine-checked
  plans        plan/execute API overhead: spec-driven dispatch retraces
               (want 0; frozen spec + cached plan) and collective-permute
               delta vs the schedule round count (want 0), incl. the
               non-uniform Corollary-3 specs
  a2a          alltoall(v): HLO collective-permutes == ceil(log2 p) for
               uniform, fused AND ragged per-pair counts; alltoallv wire
               widths == the analytic worst-windowed-count-sum bound;
               fused/jnp ratio; MoE ep-vs-global dispatch parity
  overlap      bucketed, software-pipelined grad sync: per-bucket HLO
               collective-permutes == B*ceil(log2 p) per RS (2x for AR),
               pipelined drivers bitwise == one-shot, bucketed ZeRO-1
               step within 1.05x of unbucketed, trajectory within wire
               tolerances
  elastic      rank-failure drills: mid-run shrink (4->3, injected rank
               loss + transient ckpt-IO faults) and grow (2->4) resume
               within one step boundary; re-plan+verify latency per spec;
               post-resize trajectory vs uninterrupted p' reference
  serve        continuous-batching serving: steady-state tokens/s and
               p50/p99 per-boundary latency over a staggered request
               mix, bitwise scheduler-vs-one-shot parity, and the
               broadcast plan's HLO collective-permutes == ceil(log2 p)
               weight fan-out gate
  roofline     re-emit the dry-run roofline table (reads reports/dryrun)

Output: ``name,us_per_call,derived`` CSV rows.
Usage:  PYTHONPATH=src python -m benchmarks.run [--only rounds,kernels]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def emit(name: str, us: float, derived: str = ""):
    print(f"{name},{us:.3f},{derived}")


# ---------------------------------------------------------------------------
# Every group that touches JAX runs in a worker process of its own: this
# process never imports JAX, so on a TPU host exactly one process at a
# time holds the device.
WORKERS = {
    "rounds": ("_rounds_worker.py", ["rounds"], 900),
    "cost_model": ("_rounds_worker.py", ["cost_model"], 900),
    "collectives": ("_collective_worker.py", [], 900),
    "kernels": ("_kernel_worker.py", [], 900),
    "wire": ("_wire_worker.py", [], 900),
    "plans": ("_plan_worker.py", [], 900),
    "a2a": ("_a2a_worker.py", [], 900),
    "overlap": ("_overlap_worker.py", [], 1200),
    "elastic": ("_elastic_worker.py", [], 1800),
    "serve": ("_serve_worker.py", [], 1800),
}


def run_worker(group: str) -> None:
    """Run ``group``'s worker and pass its CSV rows through; a failed
    worker becomes a ``<group>/ERROR`` row."""
    script, args, timeout = WORKERS[group]
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, os.path.join(here, script), *args],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    if proc.returncode != 0:
        emit(f"{group}/ERROR", 0.0, proc.stderr[-200:].replace("\n", " "))
        return
    print(proc.stdout, end="")


# ---------------------------------------------------------------------------
def bench_analysis():
    """Static-analysis gate: ``python -m repro.analysis --all`` must exit
    clean (plan verifier sweep, jaxpr lint, HLO audit, repo lint).
    Subprocess — the CLI forces its own fake-device XLA_FLAGS."""
    import tempfile
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tf:
        report_path = tf.name
    try:
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "--all",
             "--json", report_path],
            capture_output=True, text=True, timeout=900, env=env, cwd=root)
        us = (time.perf_counter() - t0) * 1e6
        try:
            rep = json.load(open(report_path))
        except (OSError, ValueError):
            rep = None
        if proc.returncode != 0 or rep is None:
            n = rep["n_findings"] if rep else -1
            emit("analysis/ERROR", us,
                 f"findings={n};rc={proc.returncode};"
                 + proc.stdout[-160:].replace("\n", " ").replace(",", " "))
            return
        by_pass = rep["findings_by_pass"]
        for pass_name in rep["passes_run"]:
            emit(f"analysis/{pass_name}", us / len(rep["passes_run"]),
                 f"findings={by_pass.get(pass_name, 0)};"
                 f"waived={len(rep.get('waived', [])) if pass_name == 'repo' else 0};"
                 f"ok={rep['ok']}")
    finally:
        try:
            os.unlink(report_path)
        except OSError:
            pass


# ---------------------------------------------------------------------------
def bench_roofline():
    d = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "reports", "dryrun")
    if not os.path.isdir(d):
        emit("roofline/NO_REPORTS", 0.0, "run repro.launch.dryrun first")
        return
    for fn in sorted(os.listdir(d)):
        if not fn.endswith(".json"):
            continue
        r = json.load(open(os.path.join(d, fn)))
        if r.get("status") != "OK":
            emit(f"roofline/{fn[:-5]}", 0.0, r.get("status", "?")[:60])
            continue
        rl = r["roofline"]
        t_star = max(rl["t_compute_s"], rl["t_memory_s"],
                     rl["t_collective_s"])
        # 2pod records are compiled with --no-correction (mesh-pass only):
        # their collective term misses loop-resident collectives.
        note = (";collective_uncorrected"
                if not r.get("corr_multiplier") and "_2pod" in fn else "")
        emit(f"roofline/{fn[:-5]}", t_star * 1e6,
             f"bottleneck={rl['bottleneck']};"
             f"frac={rl['roofline_fraction']:.4f};"
             f"c={rl['t_compute_s']:.4f};m={rl['t_memory_s']:.4f};"
             f"x={rl['t_collective_s']:.4f}{note}")


BENCHES = {
    **{g: (lambda g=g: run_worker(g)) for g in WORKERS},
    "analysis": bench_analysis,
    "roofline": bench_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(BENCHES))
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(BENCHES)
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()


if __name__ == "__main__":
    main()
