"""Model FLOP/s utilization of the training window: the FLOPs a step's
forward and backward require (``bench/work.py``; no recomputation
counted) times steps per second, over the chips' summed bf16 peak."""
LAYER = "trainer step (train/steps.py, optim/zero1.py)"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s"


def read(run):
    work, s, c = run["work"], run["sizes"], run["cell"]
    flops = work.train_step_flops(s, c["global_batch"], run["seq_len"])
    tokens = c["global_batch"] * run["seq_len"]
    return 100.0 * run["tokens_per_s"] / tokens * flops / (
        run["chips"] * run["peaks"]["bf16_flops_per_s"])
