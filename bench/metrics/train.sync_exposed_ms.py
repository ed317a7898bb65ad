"""Per training step, the device time in which the gradient sync's
collective-permutes (the circulant reduce-scatter and allgather rounds)
run and no other operation runs on that chip; worst chip, from the trace
of whole steps."""
from bench import trace

LAYER = "collectives (core/plan.py, core/collectives.py)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(run):
    tr = run.get("trace")
    if not tr or not tr["devices"]:
        return None
    worst = max(trace.exposed_collective_ns(ev)
                for ev in tr["devices"].values())
    return worst / run["trace_steps"] / 1e6
