"""A cell, a configuration, a mix and a per-layer metric placed as new
files, with new entries in BENCHMARK.json, are found by name; no file of
the benchmark changes."""
import hashlib
import json
import os

from bench import harness

from conftest import copy_bench


def digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_every_listed_cell_resolves():
    spec = harness.benchmark()
    for w in spec["workloads"]:
        c = harness.cell_spec(w["name"])
        assert c["cell"]["driver"] in ("serve", "train")
        assert any(m["name"] == "setup_s" for m in c["end_to_end"])
        assert len(c["end_to_end"]) >= 2 and c["per_layer"]
    for m in spec["per_layer"]:
        # what a reader states of itself is what BENCHMARK.json lists
        g = harness.metric_reader(m["name"]).__globals__
        assert (g["LAYER"], g["UNIT"], g["SOURCE"], g["MOVES"]) == (
            m["layer"], m["unit"], m["source"], m["moves"]), m["name"]


def test_new_files_found_by_name(tmp_path):
    root = copy_bench(str(tmp_path))
    before = digest(root)
    bench = os.path.join(root, "bench")
    cfg = json.load(open(os.path.join(bench, "configs",
                                      "internlm2-1.8b-serve.json")))
    cfg["name"] = "new-config"
    json.dump(cfg, open(os.path.join(bench, "configs", "new-config.json"),
                        "w"))
    json.dump({"prompt_len": {"choice": [64]},
               "output_len": {"uniform": [8, 16]}},
              open(os.path.join(bench, "traffic", "new-mix.json"), "w"))
    json.dump({"driver": "serve", "max_len": 128, "kv_block": 16,
               "max_batch": 2, "rate_per_s": 1.0, "sample_tokens": 100,
               "limits": {"max_gap": 0.1}},
              open(os.path.join(bench, "cells", "new.cell.json"), "w"))
    with open(os.path.join(bench, "metrics", "new.metric.py"), "w") as f:
        f.write("def read(run):\n    return run.get('answer')\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "new-config", "source": "x",
                            "file": "bench/configs/new-config.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "new.cell", "config": "new-config",
                              "traffic": "new-mix", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "new.metric", "unit": "count",
                              "better": "lower", "source": "host_clock",
                              "layer": "x", "moves": "setup_s",
                              "workloads": ["new.cell"]})
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))

    c = harness.cell_spec("new.cell", root)
    assert c["config"]["name"] == "new-config"
    assert c["traffic"]["prompt_len"] == {"choice": [64]}
    assert c["cell"]["max_len"] == 128
    assert [m["name"] for m in c["per_layer"]] == ["new.metric"]
    # setup_s has no workloads key: every cell reports it
    assert [m["name"] for m in c["end_to_end"]] == ["setup_s"]
    got = harness.read_per_layer(c["per_layer"], {"answer": 42}, root)
    assert got == {"new.metric": {"value": 42.0, "unit": "count"}}
    # a reader that finds nothing leaves its metric out
    assert harness.read_per_layer(c["per_layer"], {}, root) == {}
    after = digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
