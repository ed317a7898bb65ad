"""Run one cell of ``BENCHMARK.json`` once and print one JSON line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the line's metrics are the cell's end-to-end metrics;
with ``--trace 1`` its per-layer metrics, read from a traced window, the
benchmark's host spans and the program's compile counter.  Either way
the run checks what its timed path produced against the plain reference
and prints each compared number beside its limit, last on standard error
and under ``checks`` in the line.  It exits non-zero, printing no line,
when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()     # set-up counts from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    __package__ = "bench"

from bench import harness  # noqa: E402


def checks_of(cell: dict, cmp: dict) -> dict:
    """Each compared number with its limit (``cell["limits"]``)."""
    return {name: {"value": cmp[name], "limit": lim,
                   "rule": "value <= limit"}
            for name, lim in cell["limits"].items()}


def result(spec: dict, out: dict, do_trace: bool, checks: dict) -> dict:
    devs = out["devs"]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": out["peak"]}
    if do_trace:
        from bench import trace
        metrics = harness.read_per_layer(spec["per_layer"], out["run"])
        tr = out["run"]["trace"]
        if tr is None or not tr["devices"]:
            raise RuntimeError("the traced window holds no device operation")
        device["busy_s"] = trace.busy_s(tr)
        device["window_s"] = tr["window_s"]
        extra = {"breakdown": trace.breakdown(tr)}
    else:
        metrics = {m["name"]: {"value": float(out["e2e"][m["name"]]),
                               "unit": m["unit"]}
                   for m in spec["end_to_end"]}
        extra = {}
    ok = all(c["value"] <= c["limit"] for c in checks.values()) \
        and out["failed"] == 0 and out["cmp"].get("ok", True)
    return {"correct": bool(ok), "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device,
            **extra, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.cell_spec(args.workload)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    import jax
    harness.mark("import")
    try:
        devs = harness.devices(spec["chips"])
    except harness.NoDevice as e:
        harness.log(f"bench: {e}; nothing was run")
        return 3
    from repro.launch.compile import setup_compile_cache
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    harness.mark("devices")
    driver = importlib.import_module("bench." + spec["cell"]["driver"])
    out = driver.run(spec, args.seed, args.seconds, bool(args.trace),
                     T_START, devs)
    out["devs"] = devs
    harness.log("set-up phases (s from the first statement): "
                + harness.phases(T_START))
    checks = checks_of(spec["cell"], out["cmp"])
    harness.emit(result(spec, out, bool(args.trace), checks))
    return 0


if __name__ == "__main__":
    sys.exit(main())
