"""The program's own phases on the device trace's clock, for one cell.

    python3 -m bench.phases --workload <cell> --seed <n> --seconds <s> \
        [--dump <file>.json]

Runs the cell's window as its driver does (serve: ``bench/serve.py``'s
session, warm-up, open-loop window and traced last seconds; train:
``bench/train.py``'s session, then ``trace_steps`` steps of
``train.drive`` under a trace, as its traced run takes them) and prints
one JSON line with what the program's spans (``repro.obs``,
``sched.*``) and the ZeRO-1 step's named scopes (``zero1.*``) show
there (``bench/spans.py``): the brackets on the offset between each
device's clock and the host's, the traced idle time by the host span it
falls in, and the readings of the planned per-layer metrics
``serve.step_idle_ms`` and ``serve.emit_ms`` (serve) or ``train.rs_ms``
and ``train.ag_ms`` (train).  ``--dump`` writes the reduced trace, with
its spans and scopes, to a file.  It checks nothing against a reference
and is no part of ``bench.run``; once ``bench/trace.py`` and the drivers
reduce the program's spans themselves, it goes, with ``spans.idle_gaps``.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    __package__ = "bench"

from bench import harness, spans, trace  # noqa: E402


def _xplane(path: str) -> str:
    import glob
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"no trace written under {path}")
    return files[0]


def _stat_delta(a, b) -> dict:
    """The window's part of a scheduler's stats: ``b`` less ``a``."""
    n = b.decode_steps - a.decode_steps
    adm = b.prefills - a.prefills
    sec = {k: v - a.seconds.get(k, 0.0) for k, v in b.seconds.items()}
    return {"decode_steps": n, "prefills": adm,
            "occupancy": (b.slot_steps - a.slot_steps) / n if n else None,
            "queue_wait_mean_ms": 1e3 * (b.queue_wait_s - a.queue_wait_s)
            / adm if adm else None,
            "queue_wait_max_ms": 1e3 * b.queue_wait_max_s,
            "ms_per_decode_step": {k: 1e3 * v / n for k, v in sec.items()}
            if n else {}}


def serve_phases(spec, seed, seconds, devs, dump):
    from bench import serve, traffic
    cell, mix = spec["cell"], spec["traffic"]
    sess = serve.Session(spec, seed, devs)
    serve.warm_up(sess, mix)
    load = traffic.open_loop(mix, cell["rate_per_s"], seconds, seed,
                             sess.sizes.vocab, warm=cell["max_batch"])
    snaps = []

    def on_step(now):
        if not snaps:
            snaps.append(sess.sched.stats.copy())
        snaps[1:] = [sess.sched.stats.copy()]
    with trace.maybe_dir(True) as tdir:
        rec = serve.run_window(sess, load, seconds, trace_dir=tdir,
                               on_step=on_step)
        cap = rec["trace"]
        tr = spans.load(_xplane(tdir))
    tr["window_s"] = cap.window_s
    # the window's decode steps (host clock), inside the trace and before
    t_cap = cap.t_start
    d = {True: [], False: []}
    for t0, t1, adm, active, *_ in rec["steps"]:
        if active:
            d[t0 >= t_cap].append(1e3 * (t1 - t0 - adm))
    out, tr_h = _on_host_clock(tr, ("sched.sample",))
    out.update(
        stats=_stat_delta(*snaps),
        decode_step_ms={"traced": _mean(d[True]), "untraced":
                        _mean(d[False]), "n": [len(d[True]),
                                               len(d[False])]})
    out["serve.step_idle_ms"] = spans.step_idle_ms(tr_h)
    out["serve.emit_ms"] = out["stats"]["ms_per_decode_step"].get(
        "sched.emit")
    sess.free()
    _dump(dump, tr)
    return out


def train_phases(spec, seed, seconds, devs, dump):
    from repro.launch import bootstrap
    from bench import train
    cell = spec["cell"]
    sess, sizes, make = train.build(spec, seed, devs)
    n = cell["trace_steps"]
    with sess.use_mesh():
        step, _ = train.drive(sess, 0, lambda k: k >= train.FIRST)
        batch = bootstrap.place_batch(sess, sess.pipe.batch_at(step))
        text = sess.built.step_fn.lower(sess.params, sess.opt,
                                        batch).compile().as_text()
        with trace.maybe_dir(True) as tdir:     # as bench/train.py traces
            cap = trace.Capture(tdir)
            train.drive(sess, step, lambda k: k >= n)
            cap.stop()
            tr = spans.load(_xplane(tdir),
                            {"step_fn": spans.hlo_scopes(text)})
    tr["window_s"] = cap.window_s
    # the runtime's completion callbacks give the upper bounds
    out, tr_h = _on_host_clock(tr, ())
    for name, scope in (("train.rs_ms", "zero1.reduce_scatter"),
                        ("train.ag_ms", "zero1.allgather"),
                        ("fwd_bwd_ms", "zero1.fwd_bwd"),
                        ("adamw_ms", "zero1.adamw")):
        out[name] = spans.scope_ms(tr_h, scope, n)
    out["train.sync_exposed_ms"] = harness.metric_reader(
        "train.sync_exposed_ms")({"trace": tr, "trace_steps": n})
    out["step_ms"] = 1e3 * cap.window_s / n
    out["hlo_scoped_ops"] = dict(collections.Counter(
        spans.hlo_scopes(text).values()))
    _dump(dump, tr)
    return out


def _on_host_clock(tr, syncs):
    """What every cell prints of the shared clock and the idle time, and
    ``tr`` moved onto the host's clock."""
    pieces = spans.clock_pieces(tr, syncs)
    moved = spans.on_host_clock(tr, pieces)
    ms = lambda x: None if x is None else x / 1e6
    # per device, each piece: [from (s into the window), lo, hi (ms)]
    t0 = tr["window"][0] or 0
    return {"clock_offset_ms": {
                d: [[None if a is None else (a - t0) / 1e9, ms(lo), ms(hi)]
                    for a, lo, hi in ps]
                for d, ps in pieces.items()} or None,
            "idle_share": trace.idle_share(tr),
            "idle_gaps": spans.idle_gaps(moved),
            "idle_gaps_before": trace.idle_gaps(tr)}, moved


def _mean(xs):
    return sum(xs) / len(xs) if xs else None


def _dump(path, tr):
    if path:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(tr, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)
    spec = harness.cell_spec(args.workload)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    import jax
    from repro.launch.compile import setup_compile_cache
    try:
        devs = harness.devices(spec["chips"])
    except harness.NoDevice as e:
        harness.log(f"phases: {e}")
        return 3
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run = {"serve": serve_phases, "train": train_phases}[
        spec["cell"]["driver"]]
    out = run(spec, args.seed, args.seconds, devs, args.dump)
    out["workload"] = args.workload
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
