"""Readings that set a cell's limits: the program's numbers over many
seeds (the lower readings) and the control's and the faults' (the upper
readings).  The benchmark's own runs never call this.

    python3 -m bench.control --workload <cell> --seeds 1,2,3 [--seconds 30]

Serve cells: per seed, the cell's window at its own load, then the widest
reference gap of the served tokens (``max_gap``) and, at the same
positions of the same prompts and tokens, of the token that the
reference computed on fp8 (e4m3) operands puts first (``control_gap``:
weights scaled per output column; activations, q, k, v and attention
probabilities per row).

Train cells: per seed, the program's first three steps against the
reference (no window), and the reference's own variants in the program's
place: fp8 (e4m3) operands in every contraction, as the serve control,
straight through in the backward (``control``), and on the first
``--fault-seeds`` seeds half of the rows (``half_batch``) and the first
chip's rows alone, no exchange (``no_exchange``).  A step that returns
its state unchanged reads 1 on ``grad_gap`` and ``change_gap`` by their
definition and needs no run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    __package__ = "bench"

from bench import harness, model  # noqa: E402


def serve_readings(spec, seed, seconds, devs) -> dict:
    from bench import serve
    out = serve.run(spec, seed, seconds, False, time.perf_counter(), devs,
                    control=True)
    return {"max_gap": out["cmp"]["max_gap"],
            "control_gap": out["cmp"]["control_gap"],
            "tokens": out["cmp"]["tokens"]}


def train_readings(spec, seed, devs, sess, faults: bool = True) -> dict:
    """``sess``: the program's session, built once for every seed; its
    state is made from the seed here and freed before the reference."""
    from bench import train
    _, make = train.place(sess, spec, seed)
    with sess.use_mesh():
        first = train.first_steps(sess, train.opt_of(spec["config"]), make,
                                  seed)
    sess.params = sess.opt = None
    gc.collect()
    ref = train.reference(spec, seed, devs)
    out = {"program": train.compare(first, ref)}
    out["control"] = train.compare(
        train.reference(spec, seed, devs, low=model.FP8), ref)
    for v in ("half_batch", "no_exchange") if faults else ():
        out[v] = train.compare(train.reference(spec, seed, devs, variant=v),
                               ref)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--fault-seeds", type=int, default=3,
                    help="train cells: read the faults on the first N seeds")
    args = ap.parse_args(argv)
    spec = harness.cell_spec(args.workload)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    import jax
    from repro.launch.compile import setup_compile_cache
    try:
        devs = harness.devices(spec["chips"])
    except harness.NoDevice as e:
        harness.log(f"control: {e}")
        return 3
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    sess = None
    if spec["cell"]["driver"] == "train":
        from bench import train
        sess = train.session(spec, devs)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.time()
        if sess is None:
            r = serve_readings(spec, seed, args.seconds, devs)
        else:
            r = train_readings(spec, seed, devs, sess, i < args.fault_seeds)
        print(json.dumps({"seed": seed, "seconds": time.time() - t, **r}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
