"""Train driver: the program's ZeRO-1 step (``launch.bootstrap``
``build_session`` + ``run_step``) in a closed loop on four chips.

Set-up builds one session, places the benchmark's weights in it, feeds it
the benchmark's token rows (``traffic.train_batch``) and drives it from
the seed through its first three steps; those steps compile and warm it,
and their readings are kept for the check.  The same session then runs
the window.  After the window the program's state is freed and the plain
float32 reference (``bench/model.py``) trains three steps from the same
weights on the same rows; the check compares each step's loss, the
per-leaf norms of the first clipped gradient (read from the optimizer's
first moment, which after one step is (1 - beta1) times it) and the
per-leaf norms of the parameters' change after three steps.
"""
from __future__ import annotations

import functools
import gc
import time

import numpy as np

from . import harness, model, trace, traffic, work

FIRST = 3        # steps of set-up the check follows


class Feed:
    """What ``run_step`` reads: ``batch_at(step)``, from the seed."""

    def __init__(self, seed, batch, seq, vocab):
        self.args = (seed, batch, seq, vocab)

    def batch_at(self, step):
        seed, batch, seq, vocab = self.args
        return traffic.train_batch(seed, step, batch, seq, vocab)


def _leaf_norms(tree) -> list:
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                           for x in jax.tree.leaves(t)])
    return [float(x) for x in f(tree)]


def _change_norms(a, b) -> list:
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x, y: [
        jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32)
                                    - q.astype(jnp.float32))))
        for p, q in zip(jax.tree.leaves(x), jax.tree.leaves(y))])
    return [float(x) for x in f(a, b)]


def opt_of(cfg_file: dict) -> dict:
    return dict(cfg_file["optimizer"])


def build(spec: dict, seed: int, devs):
    """The program's session with the benchmark's weights and feed."""
    sess = session(spec, devs)
    sizes, make = place(sess, spec, seed)
    return sess, sizes, make


def session(spec: dict, devs):
    """The program's session, its state not yet made."""
    from repro.launch import bootstrap
    from .serve import check_program_config

    cfg_file, cell, mix = spec["config"], spec["cell"], spec["traffic"]
    sizes = model.Sizes(cfg_file)
    opt = opt_of(cfg_file)
    sess = bootstrap.build_session(
        arch=cfg_file["program_arch"], n_layers=sizes.layers,
        scale_down=cfg_file.get("program_scale_down", False),
        seq_len=mix["seq_len"], global_batch=cell["global_batch"],
        dp=cell["dp"], mp=1, grad_sync=cell["grad_sync"],
        steps=opt["total_steps"], lr=opt["lr"], warmup=opt["warmup_steps"],
        init_state=False, devices=list(devs))
    check_program_config(sess.cfg, cfg_file)
    oc = sess.opt_cfg
    prog_opt = {k: getattr(oc, k) for k in ("lr", "beta1", "beta2", "eps",
                                            "weight_decay", "warmup_steps",
                                            "total_steps", "min_lr_ratio",
                                            "clip_norm")}
    if prog_opt != opt:
        raise ValueError(f"program optimizer {prog_opt} != file {opt}")
    return sess


def place(sess, spec: dict, seed: int):
    """The seed's weights, fresh optimizer state and the seed's rows in
    ``sess``; returns ``(sizes, make)``, ``make(key)`` remaking the
    weights as placed."""
    import jax
    from .serve import same_tree

    cfg_file, cell, mix = spec["config"], spec["cell"], spec["traffic"]
    sizes = model.Sizes(cfg_file)
    shapes = jax.eval_shape(sess.model.init, jax.random.PRNGKey(0))
    make = jax.jit(functools.partial(model.init, sizes),
                   out_shardings=sess.built.param_sharding(shapes))
    sess.params = make(model.key_of(seed))
    same_tree(shapes, sess.params)
    sess.opt = jax.jit(sess.built.init_opt,
                       out_shardings=sess.built.opt_spec(shapes))(sess.params)
    sess.pipe = Feed(seed, cell["global_batch"], mix["seq_len"], sizes.vocab)
    return sizes, make


def first_steps(sess, opt: dict, make, seed: int) -> dict:
    """Steps 0..FIRST-1 through ``run_step``: each loss, the first clipped
    gradient's leaf norms (from the first moment) and the leaf norms of
    the parameters' change over the FIRST steps (the weights remade from
    the seed)."""
    from repro.launch import bootstrap
    losses, grad = [], None
    for step in range(FIRST):
        met = bootstrap.run_step(sess, step)
        losses.append(float(met["loss"]))
        if step == 0:
            grad = [n / (1 - opt["beta1"]) for n in _leaf_norms(sess.opt.m)]
    change = _change_norms(sess.params, make(model.key_of(seed)))
    return {"loss": losses, "grad": grad, "change": change}


def reference_program(spec: dict, devs, low=None):
    """The reference's training step, jitted over ``devs``: parameters
    replicated, first and second moments split along their first axis
    that the chips divide, the batch split over the chips.  ``low`` (a
    ``model.Low``) rounds the operands of every contraction before use,
    straight through in the backward.
    Returns ``(step, shardings)``; ``step(params, m, v, batch, t)``."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    sizes = model.Sizes(spec["config"])
    opt = opt_of(spec["config"])
    mesh = Mesh(np.asarray(devs), ("x",))
    rep = NamedSharding(mesh, P())

    def split(x):
        for ax, n in enumerate(x.shape):
            if n % len(devs) == 0:
                return NamedSharding(mesh, P(*([None] * ax), "x"))
        return rep

    shapes = jax.eval_shape(functools.partial(model.init, sizes),
                            model.key_of(0))
    mv = jax.tree.map(split, shapes)
    if low is not None:              # straight through in the backward
        def st(f):
            return lambda x: x + jax.lax.stop_gradient(f(x) - x)
        low = model.Low(st(low.weight), low.act and st(low.act))

    @functools.partial(jax.jit, out_shardings=(rep, mv, mv, mv, rep))
    def step(params, m, v, batch, t):
        loss, g = jax.value_and_grad(functools.partial(
            model.loss, sizes, low=low))(params, batch)
        p, m, v, g = model.adamw(opt, params, g, m, v, t, sizes.dtype)
        return p, m, v, g, loss

    return step, {"params": rep, "moments": mv, "shapes": shapes,
                  "batch": NamedSharding(mesh, P("x"))}


def reference(spec: dict, seed: int, devs, low=None, variant=None) -> dict:
    """Three steps of the plain float32 reference from the seed's weights
    on the same rows, on the same chips.  ``variant`` reads a fault in the
    reference's place: ``half_batch`` (the second half of the rows left
    out, the mean taken over the first) or ``no_exchange`` (the rows of
    the first chip alone), each by repeating the rows kept."""
    import jax
    import jax.numpy as jnp

    cell, mix = spec["cell"], spec["traffic"]
    sizes = model.Sizes(spec["config"])
    step, sh = reference_program(spec, devs, low)
    make = jax.jit(functools.partial(model.init, sizes),
                   out_shardings=sh["params"])
    params = make(model.key_of(seed))
    zeros = jax.jit(lambda: jax.tree.map(
        lambda s: jnp.zeros(s.shape, jnp.float32), sh["shapes"]),
        out_shardings=sh["moments"])
    m, v = zeros(), zeros()
    keep = {"half_batch": cell["global_batch"] // 2,
            "no_exchange": cell["global_batch"] // len(devs)}.get(
                variant, cell["global_batch"])
    losses, grad = [], None
    for t in range(FIRST):
        batch = traffic.train_batch(seed, t, cell["global_batch"],
                                    mix["seq_len"], sizes.vocab)
        batch = {k: jax.device_put(np.resize(x[:keep], x.shape), sh["batch"])
                 for k, x in batch.items()}
        params, m, v, g, loss = step(params, m, v, batch, t + 1)
        losses.append(float(loss))
        if t == 0:
            grad = _leaf_norms(g)
        del g
    del m, v
    return {"loss": losses, "grad": grad,
            "change": _change_norms(params, make(model.key_of(seed)))}


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers the check holds to their limits.  A leaf's gap
    is the gap between the program's norm and the reference's, over the
    larger of the reference leaf's norm and the median leaf's; leaves
    whose reference gradient is under a thousandth of the median leaf's
    move under Adam by round-off alone and are left out of the change."""
    def worst(a, b, keep):
        a, b = np.asarray(a), np.asarray(b)
        gap = np.abs(a - b) / np.maximum(b, np.median(b))
        return float(gap[keep].max()) if keep.any() else 0.0

    g_ref = np.asarray(ref["grad"])
    moved = g_ref >= 1e-3 * np.median(g_ref)
    return {"loss_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(prog["loss"], ref["loss"])),
            "grad_gap": worst(prog["grad"], g_ref, np.ones(len(g_ref), bool)),
            "change_gap": worst(prog["change"], ref["change"], moved),
            "left_out": int((~moved).sum())}


def drive(sess, step: int, stop) -> tuple[int, int]:
    """``run_step`` from ``step`` until ``stop(steps done)``, with at most
    two steps in flight (the host prepares the next batch while the chips
    run the last); returns (next step, steps done) once all have ended."""
    from repro.launch import bootstrap
    prev, n = None, 0
    while True:
        with trace.span("bench.step"):
            met = bootstrap.run_step(sess, step)
        step, n = step + 1, n + 1
        if prev is not None:
            float(prev["loss"])
        prev = met
        if stop(n):
            float(prev["loss"])
            return step, n


def run(spec: dict, seed: int, seconds: float, do_trace: bool,
        t_start: float, devs) -> dict:
    import jax
    from repro.launch.compile import CompileCounter

    kind = devs[0].device_kind
    cfg_file, cell, mix = spec["config"], spec["cell"], spec["traffic"]
    opt = opt_of(cfg_file)
    sess, sizes, make = build(spec, seed, devs)
    harness.mark("session")
    tokens = cell["global_batch"] * mix["seq_len"]
    with sess.use_mesh():
        first = first_steps(sess, opt, make, seed)
        jax.block_until_ready(sess.params)
        harness.mark("first_steps")
        t0 = time.perf_counter()
        with CompileCounter() as cc:
            step, n_steps = drive(sess, FIRST, lambda n: time.perf_counter()
                                  >= t0 + seconds)
        t1 = time.perf_counter()
        reduced = None
        if do_trace:                 # whole steps, driven as the window's
            with trace.maybe_dir(True) as tdir:
                cap = trace.Capture(tdir)
                drive(sess, step, lambda n: n >= cell["trace_steps"])
                cap.stop()
                reduced = cap.reduce()
    peak = harness.memory_peak(devs)
    shapes = [x.shape for x in jax.tree.leaves(sess.params)]
    sess.params = sess.opt = None
    del sess
    gc.collect()
    ref = reference(spec, seed, devs)
    cmp = compare(first, ref)
    window = t1 - t0
    e2e = {"setup_s": t0 - t_start,
           "train_tokens_per_s": n_steps * tokens / window}
    harness.log(f"train: window {window:.3f}s steps {n_steps} "
                f"tokens/s {e2e['train_tokens_per_s']:.1f} "
                f"compiles in window {cc.count} peak {peak} "
                f"losses {first['loss']} reference {ref['loss']} {cmp}")
    run_rec = {"window_s": window, "compiles": cc.count, "steps": n_steps,
               "tokens_per_s": e2e["train_tokens_per_s"], "trace": reduced,
               "trace_steps": cell["trace_steps"], "sizes": sizes,
               "cell": cell, "seq_len": mix["seq_len"],
               "peaks": harness.peaks(kind), "param_shapes": shapes,
               "chips": len(devs), "work": work}
    return {"e2e": e2e, "run": run_rec, "peak": peak, "cmp": cmp,
            "attempted": n_steps, "failed": 0}
