"""Find a serve cell's knee: the highest Poisson rate at which the queue
of waiting requests does not grow over a window.

    python3 -m bench.sweep --workload <cell> --rates 0.5,0.7,0.9 --seconds 30

One process, one set-up: for each rate, a fresh scheduler on the same
engine runs the cell's window at that rate; the line per rate gives the
queue length at each quarter of the window, the requests that arrived
and the tokens served.  The benchmark's own runs never call this; its
output goes into the cell file (``knee_per_s``, ``rate_per_s``) and
``PERF.md``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    __package__ = "bench"

from bench import harness, serve, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    spec = harness.cell_spec(args.workload)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    import jax
    from repro.launch.compile import setup_compile_cache
    try:
        devs = harness.devices(spec["chips"])
    except harness.NoDevice as e:
        harness.log(f"sweep: {e}")
        return 3
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell, mix = spec["cell"], spec["traffic"]
    sess = serve.Session(spec, args.seed, devs)
    serve.warm_up(sess, mix)
    for rate in (float(r) for r in args.rates.split(",")):
        sess.new_scheduler(cell)
        load = traffic.open_loop(mix, rate, args.seconds, args.seed,
                                 sess.sizes.vocab, warm=cell["max_batch"])
        queue = []
        rec = serve.run_window(sess, load, args.seconds, drain_s=0.0,
                               on_step=lambda now: queue.append(
                                   (now, len(sess.sched.waiting))))
        queue = [(t - rec["t0"], q) for t, q in queue]
        lat = serve.latency(rec)
        quarters = [max([q for t, q in queue if t <= args.seconds * f / 4],
                        default=0) for f in (1, 2, 3, 4)]
        tokens = sum(len([t for t in r["times"] if rec["t0"] <= t
                          <= rec["t_end"]]) for r in rec["info"].values())
        print(json.dumps({
            "rate_per_s": rate, "queue_max_by_quarter": quarters,
            "waiting_at_close": rec["waiting_at_close"],
            "arrived": rec["submitted"], "tokens_per_s": tokens / args.seconds,
            "itl_p99_ms": serve._pct(lat["gaps"], 99) * 1e3
            if lat["gaps"] else None,
            "ttft_p95_ms": serve._pct(lat["ttft"], 95) * 1e3
            if lat["ttft"] else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
