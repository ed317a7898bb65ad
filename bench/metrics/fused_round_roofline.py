"""Share of its roofline the fused round kernel reaches: the HBM bytes
the reduce-scatter's folds require per step (``bench/work.py``: read
both operands and write the sum of the (p-1)/p of the gradient each rank
folds, float32) over 819 GB/s, against the summed device time of the
kernel's events in the trace of whole steps, over all chips."""
from bench import trace

LAYER = "round kernels (kernels/fused_round.py)"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tokens_per_s"
#: The kernel's events: the exact-wire ZeRO-1 step runs no Mosaic kernel
#: but the fused round, and the program names none of its kernels yet,
#: so every ``tpu_custom_call`` of the step is one of its calls.
KERNEL = "tpu_custom_call"


def read(run):
    tr = run.get("trace")
    if not tr or not tr["devices"]:
        return None
    spent = hits = 0
    for ev in tr["devices"].values():
        ns, n = trace.kernel_ns(ev, KERNEL)
        spent, hits = spent + ns, hits + n
    if not hits:
        return None
    work = run["work"]
    p = run["chips"]
    need = work.reduce_scatter_fold_bytes(
        work.zero1_sync_elems(run["param_shapes"], p), p, 4) \
        * run["trace_steps"] * len(tr["devices"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / (spent / 1e9)
