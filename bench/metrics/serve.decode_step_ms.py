"""Mean time of the decode half of ``Scheduler.step`` over the window:
the benchmark's host span around each step less its span around the
step's admissions (KV gather, batched decode, KV write, sampling)."""
LAYER = "serving (serve/scheduler.py, serve/kv_cache.py)"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "itl_p95_ms"


def read(run):
    d = [t1 - t0 - adm for t0, t1, adm, active, *_ in run["steps"] if active]
    return 1e3 * sum(d) / len(d) if d else None
