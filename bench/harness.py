"""What every cell shares: the benchmark's files, the device guard, the
process clock, per-layer metric discovery and the result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json``  -- model sizes as run, source, cuts;
* ``bench/cells/<cell>.json``      -- driver and deployment parameters;
* ``bench/traffic/<traffic>.json`` -- the mix the general generator reads;
* ``bench/metrics/<metric>.py``    -- one reader per per-layer metric.

A new cell, configuration, mix or metric is new files plus new entries in
``BENCHMARK.json``; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoDevice(RuntimeError):
    """No accelerator, too few chips, or a chip the peaks table lacks."""


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def cell_spec(name: str, root: str = ROOT) -> dict:
    """Everything about one cell, merged from its files.  Raises KeyError
    for a cell that ``BENCHMARK.json`` does not list."""
    spec = benchmark(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = os.path.join(root, "bench")
    cfg_entry = configs[w["config"]]
    return {
        "name": name,
        "chips": w["chips"],
        "config": _load(os.path.join(root, cfg_entry["file"])),
        "cell": _load(os.path.join(bench, "cells", name + ".json")),
        "traffic": _load(os.path.join(bench, "traffic",
                                      w["traffic"] + ".json")),
        "end_to_end": [m for m in spec["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in spec["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def peaks(kind: str) -> dict:
    table = _load(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise NoDevice(f"device kind {kind!r} is not in bench/peaks.json "
                       f"({sorted(table)})")
    return table[kind]


def devices(chips: int) -> list:
    """The first ``chips`` TPU chips, or NoDevice: never the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX found no TPU (platform "
                       f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"cell needs {chips} chips, JAX sees {len(devs)}")
    peaks(devs[0].device_kind)
    return devs[:chips]


_MARKS: list = []


def mark(name: str) -> None:
    """Note the end of one phase of set-up (logged by ``phases``)."""
    _MARKS.append((name, time.perf_counter()))


def phases(t_start: float) -> str:
    """Each phase of set-up marked so far, in seconds from ``t_start``
    (``time.perf_counter``), for the log."""
    return ", ".join(f"{n} {t - t_start:.2f}" for n, t in _MARKS)


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def metric_reader(name: str, root: str = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(metrics: list[dict], run: dict, root: str = ROOT) -> dict:
    """Each per-layer metric's reader over one run's record; a reader that
    finds nothing returns None and the metric is left out."""
    out = {}
    for m in metrics:
        value = metric_reader(m["name"], root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(result: dict) -> None:
    """The run's last lines: each compared number beside its limit on
    standard error, then the result line (its ``checks`` last) on
    standard output."""
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r}, "
            f"{c['rule']})")
    print(json.dumps(result), flush=True)
