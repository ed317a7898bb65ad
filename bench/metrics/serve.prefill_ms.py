"""Mean time of one admission: the benchmark's host span around
``Scheduler._admit`` (B=1 prefill, ``write_prefill``, first token) over
the window, divided by the prefills it ran.  Every slot's next token
waits for it, so it sets the slow steps that ``itl_p99_ms`` lands on."""
LAYER = "serving (serve/scheduler.py, serve/kv_cache.py)"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "itl_p99_ms"


def read(run):
    n = sum(a[2] for a in run["admits"])
    return 1e3 * sum(a[1] - a[0] for a in run["admits"]) / n if n else None
