"""Run the tiny training cell on four CPU devices with one fault planted
(or none, or the control) and print the harness's verdict as JSON.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python bench/tests/_tiny_train.py <root> <none|unchanged|half_batch|no_exchange|control>

Called by ``test_faults.py`` and ``test_control.py`` in a process of its
own, because the device count is fixed when JAX starts.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.dirname(os.path.dirname(HERE)),
                os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")]

import jax  # noqa: E402

from bench import harness, model, run, train  # noqa: E402


def plant(sess, fault):
    """Break the timed path underneath the harness."""
    step_fn = sess.built.step_fn
    if fault == "unchanged":            # the step returns its state
        def broken(params, opt, batch):
            return (params, opt) + (step_fn(params, opt, batch)[2],)
    elif fault == "half_batch":         # mean over the first half only
        def broken(params, opt, batch):
            half = {k: v.at[v.shape[0] // 2:].set(v[: v.shape[0] // 2])
                    for k, v in batch.items()}
            return step_fn(params, opt, half)
    elif fault == "no_exchange":        # each rank keeps its own gradient
        from jax import lax

        from repro.optim import zero1

        def local(g, axis_names, sync, world):
            gp = zero1._pad_lead(g, world)
            off, rows = zero1.shard_offset(gp.shape[0], axis_names)
            return lax.dynamic_slice_in_dim(gp, off, rows, axis=0)
        zero1.reduce_scatter_leaf = local
        zero1.allreduce_leaf = lambda g, axis_names, sync, world: g
        return
    else:
        raise ValueError(fault)
    sess.built.step_fn = broken


def main():
    root, fault = sys.argv[1], sys.argv[2]
    harness.peaks = lambda kind: {"bf16_flops_per_s": 1e12,
                                  "hbm_bytes_per_s": 1e11}
    spec = harness.cell_spec("tiny.train", root)
    devs = jax.devices()[:4]
    if fault not in ("none", "control"):
        build = train.build

        def broken_build(*a, **k):
            out = build(*a, **k)
            plant(out[0], fault)
            return out
        train.build = broken_build
    if fault == "control":
        # The reference on fp8 operands stands in the program's place.
        def control_steps(sess, opt, make, seed):
            return train.reference(spec, seed, devs, low=model.FP8)
        train.first_steps = control_steps
    out = train.run(spec, 1 << 33, 1.0, False, time.perf_counter(), devs)
    checks = run.checks_of(spec["cell"], out["cmp"])
    out["devs"] = devs
    print(json.dumps(run.result(spec, out, False, checks)))


if __name__ == "__main__":
    main()
