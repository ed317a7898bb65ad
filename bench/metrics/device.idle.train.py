"""Share of the traced training window (whole steps, driven as the
window drives them) in which no operation ran on a chip: 1 - (union of
its XLA operations) / window, averaged over the chips."""
from bench import trace

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"


def read(run):
    tr = run.get("trace")
    idle = trace.idle_share(tr) if tr else None
    return None if idle is None else 100.0 * idle
