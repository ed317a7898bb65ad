"""Share of the traced serving window (the last seconds of the window)
in which no operation ran on the chip: 1 - (union of its XLA
operations) / window."""
from bench import trace

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"


def read(run):
    tr = run.get("trace")
    idle = trace.idle_share(tr) if tr else None
    return None if idle is None else 100.0 * idle
