"""Fixtures of the benchmark's own tests (``python -m pytest bench/tests``).

``tiny_root`` is a copy of the benchmark's files with cells at a size the
CPU can run: the program's scaled-down configurations, which the
benchmark's configuration files select with ``program_scale_down``.
"""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"program_scale_down": True, "hidden_size": 64,
        "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 128, "torch_dtype": "float32"}


def copy_bench(dst: str) -> str:
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return dst


def add_tiny(root: str, base_cell: str, name: str, cell_over: dict,
             traffic_over: dict | None = None, sizes: dict = TINY) -> None:
    """A small twin of ``base_cell``: its configuration at ``sizes`` (the
    scaled-down ones by default), its cell file with ``cell_over``, and a
    workload entry."""
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    w = dict(next(x for x in spec["workloads"] if x["name"] == base_cell))
    cfg_entry = dict(next(c for c in spec["configs"]
                          if c["name"] == w["config"]))
    cfg = json.load(open(os.path.join(root, cfg_entry["file"])))
    cfg.update(sizes, name=w["config"] + "-" + name)
    cfg_entry.update(name=cfg["name"],
                     file=f"bench/configs/{cfg['name']}.json")
    json.dump(cfg, open(os.path.join(root, cfg_entry["file"]), "w"))
    cell = json.load(open(os.path.join(root, "bench", "cells",
                                       base_cell + ".json")))
    cell.update(cell_over)
    json.dump(cell, open(os.path.join(root, "bench", "cells",
                                      name + ".json"), "w"))
    if traffic_over:
        t = json.load(open(os.path.join(root, "bench", "traffic",
                                        w["traffic"] + ".json")))
        t.update(traffic_over)
        w["traffic"] = w["traffic"] + "-tiny"
        json.dump(t, open(os.path.join(root, "bench", "traffic",
                                       w["traffic"] + ".json"), "w"))
    w.update(name=name, config=cfg_entry["name"])
    spec["configs"].append(cfg_entry)
    spec["workloads"].append(w)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if base_cell in m.get("workloads", []):
            m["workloads"].append(name)
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))


@pytest.fixture
def tiny_root(tmp_path):
    root = copy_bench(str(tmp_path))
    add_tiny(root, "internlm2-1.8b.chat-decode", "tiny.chat",
             {"max_len": 64, "max_batch": 3, "kv_block": 16,
              "rate_per_s": 20.0, "sample_tokens": 400},
             {"prompt_len": {"choice": [5, 9], "weights": [0.5, 0.5]},
              "output_len": {"uniform": [4, 12]}})
    add_tiny(root, "qwen3-1.7b.zero1-dp4", "tiny.train",
             {"global_batch": 8},
             {"seq_len": 16})
    return root


@pytest.fixture
def mid_root(tmp_path):
    """The chat cell at its published widths and bfloat16, four layers,
    short requests: a size at which bfloat16 and the fp8
    control part as they do at the cell's own size."""
    root = copy_bench(str(tmp_path))
    add_tiny(root, "internlm2-1.8b.chat-decode", "mid.chat",
             {"max_len": 128, "max_batch": 4, "rate_per_s": 2.0,
              "sample_tokens": 400},
             {"prompt_len": {"choice": [16, 40], "weights": [0.5, 0.5]},
              "output_len": {"uniform": [20, 60]}},
             sizes={"num_hidden_layers": 4})
    return root


@pytest.fixture
def cpu_peaks(monkeypatch):
    """Let the drivers read a peaks row for the CPU (tests only)."""
    from bench import harness
    row = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
           "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    monkeypatch.setattr(harness, "peaks", lambda kind: row)
    return row
