"""Bring-up check of the main path on a TPU.

    python chip_smoke.py             # one chip: kernels, trainer, server
    python chip_smoke.py --chips 4   # four chips: collectives, ZeRO-1 dp=4

Each phase drives the entry points a user calls -- the Pallas round
kernels, ``launch.bootstrap.build_session``/``run_step``,
``build_serve_session`` + ``serve.Scheduler``, the ``core.collectives``
dispatchers -- and checks what comes out against the repository's
references.  A failed check raises, so the script exits non-zero and
never prints its last line, which is otherwise exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
It refuses to run unless JAX's first device is a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# -- sizes ---------------------------------------------------------------
TRAIN_ARCH = "qwen3-1.7b"
# Depth that fits one v5e chip (16 GB) with f32 Adam state: 2 of 28
# layers (0.72e9 params) peak at 14.5 GB, because the step keeps an
# undonated second copy of params and optimizer state.
TRAIN_LAYERS = 2
TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 256, 4, 6
# A short run's schedule: full rate from step 1, cosine decay over the run.
TRAIN_LR, TRAIN_WARMUP = 1e-3, 1
DP4_BATCH, DP4_STEPS = 8, 3
BUCKET_BYTES = 25_000_000          # grad-sync bucket (launch.train example)
# The ZeRO-1 dp=4 losses of each circulant form against grad_sync="xla".
# Exact forms sum the same gradients in another order, and the bf16
# parameter update can turn a last-bit difference into one bf16 ulp of a
# weight: 1e-3 relative.  The int8 wire rounds each gradient element to
# 1/254 of its 512-element group's largest; the CPU check of the same
# step without error feedback (tests/_zero1_checks.py) stays within 0.35%
# of the exact losses, so 2e-2 relative leaves six times that while a
# wrong scale or a lost round, which moves the loss by whole units, fails.
EXACT_LOSS_RTOL, INT8_LOSS_RTOL = 1e-3, 2e-2

SERVE_ARCH = "internlm2-1.8b"
# Mixed lengths, three of them distinct: the Scheduler compiles its B=1
# prefill once per prompt length.
PROMPT_LENS = (37, 400, 120, 37, 400)
MAX_NEW = (12, 6, 16, 10, 8)
SERVE_MAX_LEN, KV_BLOCK, MAX_BATCH = 512, 16, 4

# -- tolerances of the serving check -------------------------------------
# Both are in units of the reference's logit scale at that position (the
# RMS of its float32 logits over the vocabulary).
# LOGIT_TOL bounds max |served - reference| over the vocabulary.  The
# model computes in bf16 (8 significant bits); on the CPU at internlm2's
# published widths, 2 to 6 layers gave 0.019-0.024, and 24 layers at
# d_model 512 gave 0.038.  The same model with its weights rounded to
# float8 (e4m3) gave 0.27-0.38, and 0.44 at 24 layers, so a precision
# below bf16 fails this bound.
LOGIT_TOL = 0.12
# GAP_TOL bounds how far the emitted token's reference logit may sit below
# the reference maximum: a token can beat the reference's argmax only
# where the two logits are within twice the per-logit error (a near-tie,
# where a B=4 decode may round otherwise than B=1).
GAP_TOL = 2 * LOGIT_TOL

# -- collectives ---------------------------------------------------------
COLL_BLK = 65536                    # f32 elements per block
F32_TOL = {"rtol": 2e-5, "atol": 2e-5}   # float summation order only


def int8_tol(p: int) -> dict:
    """Quantization-bounded error of the int8 wire (the conformance
    harness's bound: every round requantizes partial sums)."""
    return {"rtol": 0.1, "atol": 0.05 * p + 0.1}


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Serving comparison
# ---------------------------------------------------------------------------

def compare_served_logits(served, ref, tokens, *, logit_tol=LOGIT_TOL,
                          gap_tol=GAP_TOL) -> dict:
    """Check one request's served logits against the float32 reference.

    ``served``/``ref``: (n, vocab) logits at the n positions that produced
    the n emitted ``tokens`` (row 0 from prefill, rows 1.. from decode
    steps, the reference teacher-forced on the emitted tokens).  Raises
    AssertionError when a served logit row is off by more than
    ``logit_tol``, when an emitted token is not the argmax of its served
    row (greedy sampling), or when an emitted token's reference logit is
    more than ``gap_tol`` below the reference maximum; returns the worst
    error and gap otherwise.
    """
    import numpy as np
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    tokens = np.asarray(tokens)
    n = ref.shape[0]
    if served.shape != ref.shape or tokens.shape != (n,):
        raise ValueError(f"served {served.shape}, ref {ref.shape}, "
                         f"tokens {tokens.shape}")
    scale = np.sqrt(np.mean(ref * ref, axis=-1))
    err = np.abs(served - ref).max(axis=-1) / scale
    gap = (ref.max(axis=-1) - ref[np.arange(n), tokens]) / scale
    bad = [f"position {i}: logit error {err[i]:.4f} > {logit_tol}"
           for i in np.flatnonzero(err > logit_tol)]
    bad += [f"position {i}: token {tokens[i]} is not the served argmax "
            f"{int(np.argmax(served[i]))}"
            for i in range(n) if tokens[i] != np.argmax(served[i])]
    bad += [f"position {i}: reference gap {gap[i]:.4f} > {gap_tol}"
            for i in np.flatnonzero(gap > gap_tol)]
    if bad:
        raise AssertionError("; ".join(bad[:6]))
    return {"max_err": float(err.max()), "max_gap": float(gap.max())}


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def _round_geometries(p: int) -> list[tuple[int, int, int]]:
    """(lo, nblocks, next_lo) of every reduce-scatter round at p ranks."""
    from repro.core.plan import plan
    from repro.core.spec import CollectiveSpec
    out = set()
    for sched in ("halving", "fully_connected"):
        rounds = plan(CollectiveSpec(schedule=sched), p=p,
                      axis_name="x").rs_rounds
        for k, r in enumerate(rounds):
            nxt = rounds[k + 1].lo if k + 1 < len(rounds) else r.lo
            out.add((r.lo, r.nblocks, nxt))
    return sorted(out)


def phase_kernels() -> None:
    """Compile the five Pallas round kernels at the gradient sync's real
    widths for every round geometry at p ranks; each must appear as a
    ``tpu_custom_call`` and match its ``kernels/ref.py`` oracle bitwise.

    Block widths (columns) of qwen3-1.7b's gradient sync at p ranks: one
    25 MB f32 bucket, and the embedding leaf (vocab x d_model / p, how
    ZeRO-1 lays it out); plus 2048 columns, which the int8 kernels take
    as a single column tile (``quantize.tile_cols``).

    The cases of one (p, width, geometry) -- and the quantize/dequant and
    the permute cases of one (p, width) -- run as one program that runs
    each kernel and its oracle on the same inputs and returns one bitwise
    verdict per case: compiles, not arithmetic, dominate a cold run."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import (DEFAULT_GROUP, fused_round, fused_round_dq,
                               permute_rows, quantize_rows, wire_ngroups)
    from repro.kernels import ref
    from repro.kernels.quantize import dequant_add
    from repro.launch.bootstrap import resolve_cfg

    cfg = resolve_cfg(TRAIN_ARCH)
    on_tpu = jax.default_backend() == "tpu"
    rng = np.random.default_rng(0)

    def make(shape, kind):
        # Made on the host: a device-side generator at these widths costs
        # more compile time than every kernel together.
        if kind == "codes":
            return rng.integers(-127, 128, shape, dtype=np.int8)
        if kind == "scales":
            return rng.uniform(1e-3, 1.0, shape).astype(np.float32)
        x = rng.standard_normal(shape, dtype=np.float32)
        return jnp.asarray(x, jnp.bfloat16) if kind == "bf16" else x

    def bits(x):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        u = {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
        return jax.lax.bitcast_convert_type(x, u)

    def run(label, cases):
        """cases: [(name, kernel, oracle, ((shape, kind), ...))]."""
        inputs = [[jax.device_put(make(shape, kind)) for shape, kind in specs]
                  for _, _, _, specs in cases]

        def program(inputs):
            verdicts = []
            for (_, kern, oracle, _), args in zip(cases, inputs):
                # The barrier keeps both results materialized in their
                # dtypes: XLA may otherwise skip a bf16 rounding on the
                # oracle's side of a fusion (excess precision).
                got, want = jax.lax.optimization_barrier(tuple(
                    jax.tree.leaves(f(*args)) for f in (kern, oracle)))
                verdicts.append(jnp.all(jnp.stack([
                    jnp.array_equal(bits(g), bits(w))
                    for g, w in zip(got, want, strict=True)])))
            return jnp.stack(verdicts)

        t0 = time.time()
        compiled = jax.jit(program).lower(inputs).compile()
        secs = time.time() - t0
        n_custom = compiled.as_text().count(
            'custom_call_target="tpu_custom_call"')
        verdicts = [bool(v) for v in compiled(inputs)]
        log(f" {label}: {len(cases)} kernels compile={secs:.1f}s "
            f"tpu_custom_calls={n_custom} "
            f"run+check={time.time() - t0 - secs:.1f}s")
        for (name, _, _, specs), same in zip(cases, verdicts):
            log(f"  {name} in={list(specs)} bitwise={same}")
        if on_tpu and n_custom < len(cases):
            raise AssertionError(f"{label}: {n_custom} tpu_custom_calls for "
                                 f"{len(cases)} kernels")
        bad = [name for (name, *_), same in zip(cases, verdicts) if not same]
        if bad:
            raise AssertionError(f"differ from their oracles: {bad}")

    for p in (2, 3, 4):
        geoms = _round_geometries(p)
        log(f" p={p} round geometries (lo, nb, next_lo) = {geoms}")
        widths = {"bucket": -(-BUCKET_BYTES // 4 // p),
                  "embedding_leaf": cfg.vocab_size * cfg.d_model // p,
                  "single_tile": 2048}
        for wname, cols in widths.items():
            cp = cols + (-cols) % DEFAULT_GROUP
            for lo, nb, nl in geoms:
                rnd = dict(nb=nb, next_lo=nl)
                run(f"p={p} {wname} round lo={lo} nb={nb} next_lo={nl}", [
                    *[(f"fused_round[{dt}]",
                       functools.partial(fused_round, **rnd),
                       functools.partial(ref.fused_round_ref, **rnd),
                       (((lo, cols), dt), ((nb, cols), dt)))
                      for dt in ("f32", "bf16")],
                    ("fused_round_dq",
                     functools.partial(fused_round_dq, **rnd),
                     functools.partial(ref.fused_round_dq_ref, **rnd),
                     (((lo, cp), "f32"), ((nb, cp), "codes"),
                      ((nb, cp // DEFAULT_GROUP), "scales")))])
            cases = []
            for rows in sorted({nb for _, nb, _ in geoms}):
                cases += [(f"quantize_rows[{dt},rows={rows}]", quantize_rows,
                           ref.quantize_ref, (((rows, cols), dt),))
                          for dt in ("f32", "bf16")]
                cases.append((f"dequant_add[rows={rows}]",
                              functools.partial(dequant_add,
                                                interpret=not on_tpu),
                              ref.dequant_add_ref,
                              (((rows, cols), "f32"), ((rows, cols), "codes"),
                               ((rows, wire_ngroups(cols)), "scales"))))
            run(f"p={p} {wname} quantize/dequant", cases)
            perm = tuple(reversed(range(p)))
            run(f"p={p} {wname} permute", [
                (f"permute_rows[{dt},perm={perm}]",
                 functools.partial(permute_rows, perm=perm),
                 functools.partial(ref.permute_rows_ref, perm=perm),
                 (((p, cols), dt),)) for dt in ("f32", "bf16")])


def _run_training(label: str, *, steps: int, **session_kw) -> list[float]:
    """build_session + run_step for ``steps`` steps; asserts finite losses
    and no compile after step 0.  Returns the losses."""
    import math

    import jax

    from repro.launch import bootstrap
    from repro.launch.compile import CompileCounter

    t0 = time.time()
    sess = bootstrap.build_session(steps=steps, lr=TRAIN_LR,
                                   warmup=TRAIN_WARMUP, **session_kw)
    cfg = sess.cfg
    n_params = sum(int(x.size) for x in jax.tree.leaves(sess.params))
    full = bootstrap.resolve_cfg(session_kw["arch"]).n_layers
    widths = {k: getattr(cfg, k) for k in ("d_model", "n_heads", "n_kv_heads",
                                           "head_dim", "d_ff", "vocab_size")}
    log(f"  {label}: {cfg.name} depth {cfg.n_layers}/{full} widths {widths} "
        f"params={n_params} dp={sess.world} "
        f"batch={session_kw['global_batch']}x{session_kw['seq_len']} "
        f"build={time.time() - t0:.1f}s")
    losses = []
    with sess.use_mesh():
        with CompileCounter() as first:
            t0 = time.time()
            losses.append(float(bootstrap.run_step(sess, 0)["loss"]))
            log(f"  {label}: step 0 loss={losses[0]!r} "
                f"host_s={time.time() - t0:.3f} compiles={first.count} "
                f"compile_s={first.seconds:.1f}")
        with CompileCounter() as later:
            for step in range(1, steps):
                t0 = time.time()
                losses.append(float(bootstrap.run_step(sess, step)["loss"]))
                log(f"  {label}: step {step} loss={losses[-1]!r} "
                    f"host_s={time.time() - t0:.3f}")
    log(f"  {label}: compiles after step 0: {later.count} {_memory()}")
    # Free the session's device buffers now, so the next session starts
    # on an empty chip.
    for x in jax.tree.leaves((sess.params, sess.opt)):
        x.delete()
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"{label}: non-finite loss {losses}")
    if later.count:
        raise AssertionError(f"{label}: {later.count} compiles after step 0")
    return losses


def phase_trainer() -> None:
    """qwen3-1.7b at its published widths on one chip (mode single)."""
    losses = _run_training("trainer", steps=TRAIN_STEPS, arch=TRAIN_ARCH,
                           n_layers=TRAIN_LAYERS, seq_len=TRAIN_SEQ,
                           global_batch=TRAIN_BATCH, dp=1)
    if not losses[-1] < losses[0]:
        raise AssertionError(f"trainer: loss did not fall {losses}")


def phase_trainer_dp4() -> None:
    """The ZeRO-1 dp=4 step with circulant grad sync in three forms --
    single-shot, bucketed (25 MB buckets) and the int8 wire -- against
    ``grad_sync="xla"`` (tolerances above).

    The int8 form runs without error feedback: the EF residual is a
    float32 copy of every gradient leaf per rank, and with the step's
    state not donated the int8+EF step needs 5.99 GB of arguments, as
    much again for its outputs and 7.30 GB of temporaries per chip
    (compiled for a described v5e:2x2), more than a v5e's 16 GB."""
    import gc

    common = dict(arch=TRAIN_ARCH, n_layers=TRAIN_LAYERS, seq_len=TRAIN_SEQ,
                  global_batch=DP4_BATCH, dp=4, mp=1)
    forms = {
        "xla": (dict(grad_sync="xla"), None),
        "circulant": (dict(grad_sync="circulant"), EXACT_LOSS_RTOL),
        "circulant+bucketed": (dict(grad_sync="circulant",
                                    bucket_bytes=BUCKET_BYTES),
                               EXACT_LOSS_RTOL),
        "circulant+int8": (dict(grad_sync="circulant", wire_dtype="int8",
                                error_feedback=False), INT8_LOSS_RTOL),
    }
    base = None
    for name, (kw, rtol) in forms.items():
        losses = _run_training(name, steps=DP4_STEPS, **common, **kw)
        gc.collect()
        if base is None:
            base = losses
            continue
        for step, (g, b) in enumerate(zip(losses, base)):
            if not abs(g - b) <= rtol * abs(b):
                raise AssertionError(
                    f"{name} step {step}: loss {g!r} vs xla {b!r} "
                    f"(rtol {rtol})")
        log(f"  {name} vs xla: max |dloss| = "
            f"{max(abs(g - b) for g, b in zip(losses, base))!r} "
            f"(rtol {rtol})")


def phase_server() -> None:
    """internlm2-1.8b through the continuous-batching Scheduler, its
    logits checked against the float32 reference of the same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.bootstrap import build_serve_session
    from repro.launch.compile import CompileCounter
    from repro.models.reference import reference_logits
    from repro.serve import Scheduler

    t0 = time.time()
    prompt_lens, max_new, max_len = PROMPT_LENS, MAX_NEW, SERVE_MAX_LEN
    sess = build_serve_session(arch=SERVE_ARCH, max_len=max_len)
    cfg, eng = sess.cfg, sess.engine
    n_params = sum(int(x.size) for x in jax.tree.leaves(sess.params))
    log(f"  {cfg.name} depth {cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} dtype={cfg.dtype} params={n_params} "
        f"build={time.time() - t0:.1f}s")
    sched = Scheduler(eng, max_batch=MAX_BATCH, kv_block_size=KV_BLOCK)

    # Record what the scheduler's own prefill/decode calls return.
    prefill_fn, decode_fn = eng.prefill_fn, eng.decode_fn
    prefill_rows, decode_rows = [], {}

    def recording_prefill(params, tokens, extras):
        cache, logits = prefill_fn(params, tokens, extras)
        prefill_rows.append(np.asarray(logits[0], np.float32))
        return cache, logits

    def recording_decode(params, cache, token, pos):
        cache, logits = decode_fn(params, cache, token, pos)
        rows = np.asarray(logits, np.float32)
        for i, req in enumerate(sched.slots):
            if req is not None:
                decode_rows[(req.rid, req.pos)] = rows[i]
        return cache, logits

    eng.prefill_fn, eng.decode_fn = recording_prefill, recording_decode
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in prompt_lens]
    rids = [sched.submit(pr, n) for pr, n in zip(prompts, max_new)]
    t0 = time.time()
    with CompileCounter() as cc:
        done = sched.run()
    log(f"  scheduler: {len(rids)} requests prompts={list(prompt_lens)} "
        f"max_new={list(max_new)} slots={MAX_BATCH} max_len={max_len} "
        f"decode_steps={sched.n_decode_steps} prefills={sched.n_prefills} "
        f"host_s={time.time() - t0:.1f} compiles={cc.count} "
        f"compile_s={cc.seconds:.1f}")

    ref0 = None
    for rid, prompt, n in zip(rids, prompts, max_new):
        toks = done[rid]
        if len(toks) != n:
            raise AssertionError(f"request {rid}: {len(toks)} tokens, "
                                 f"want {n}")
        s = len(prompt)
        # Zero-padded to max_len so every request shares one compiled
        # reference; causal attention keeps the padding out of the rows
        # read back.
        seq = np.zeros((max_len,), np.int32)
        seq[:s + n - 1] = np.concatenate([prompt, toks[:-1]])
        ref = np.asarray(reference_logits(sess.params, cfg,
                                          jnp.asarray(seq)))[s - 1:s - 1 + n]
        served = [prefill_rows[rid]] + [decode_rows[(rid, s - 1 + i)]
                                        for i in range(1, n)]
        stats = compare_served_logits(np.stack(served), ref, toks)
        log(f"  request {rid}: prompt={s} tokens={toks.tolist()} "
            f"max_logit_err={stats['max_err']:.4f} (tol {LOGIT_TOL}) "
            f"max_ref_gap={stats['max_gap']:.4f} (tol {GAP_TOL})")
        if ref0 is None:
            ref0 = ref[:1]

    # Control: the same prefill with weights rounded to float8 must fail
    # the logit bound, or the bound could not tell precisions apart.
    # (The barrier keeps XLA from folding the float8 round trip away.)
    lowp = jax.jit(lambda params: jax.tree.map(
        lambda a: jax.lax.optimization_barrier(
            a.astype(jnp.float8_e4m3fn)).astype(a.dtype), params))(
                sess.params)
    _, logits = prefill_fn(lowp, jnp.asarray(prompts[0][None]), {})
    row = np.asarray(logits[0], np.float32)[None]
    del lowp
    try:
        compare_served_logits(row, ref0, [int(np.argmax(row[0]))],
                              gap_tol=np.inf)
    except AssertionError as e:
        log(f"  control (float8 weights) fails as it must: {e}")
    else:
        raise AssertionError("control: float8-rounded weights passed the "
                             "logit bound")


def phase_collectives() -> None:
    """RS, AG, AR through the dispatchers (circulant jnp, fused, fused +
    int8 wire) against the xla backend and a host reference, with the
    collective-permute count of each compiled program; then the
    conformance harness's alltoall(v) and broadcast sweeps."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro import compat
    from repro.analysis.hlo_budget import count_collective_permutes
    from repro.core import collectives as C
    from repro.core import conformance as conf
    from repro.core.schedule import ceil_log2
    from repro.core.spec import CollectiveSpec

    forms = {"xla": CollectiveSpec(kind="xla"),
             "circulant": CollectiveSpec(use_fused_kernel=False),
             "fused": CollectiveSpec(use_fused_kernel=True),
             "fused+int8": CollectiveSpec(use_fused_kernel=True,
                                          wire_dtype="int8")}
    blk = COLL_BLK
    for p in (2, 3, 4):
        devices = jax.devices()[:p]
        mesh = compat.make_mesh((p,), (conf.AXIS,), devices=devices)
        log(f"  p={p} mesh devices={[d.id for d in devices]}")
        rounds = ceil_log2(p)
        x = np.random.default_rng(p).standard_normal(
            (p, p * blk)).astype(np.float32)
        total = x.astype(np.float64).sum(axis=0)
        cases = {
            "reduce_scatter": (x, lambda r: total.reshape(p, blk)[r], rounds),
            "allgather": (x[:, :blk], lambda r: x[:, :blk].reshape(-1),
                          rounds),
            "allreduce": (x, lambda r: total, 2 * rounds),
        }
        for kind, (xin, host, want_cp) in cases.items():
            fn = getattr(C, kind)
            base = None
            for name, spec in forms.items():
                f = jax.jit(compat.shard_map(
                    lambda v, spec=spec: fn(v[0], conf.AXIS, spec=spec)[None],
                    mesh=mesh, in_specs=(P(conf.AXIS),),
                    out_specs=P(conf.AXIS), check_vma=False))
                t0 = time.time()
                compiled = f.lower(xin).compile()
                secs = time.time() - t0
                text = compiled.as_text()
                n_cp = count_collective_permutes(text)
                out = np.asarray(compiled(xin)).astype(np.float64)
                tol = int8_tol(p) if "int8" in name else F32_TOL
                for r in range(p):
                    np.testing.assert_allclose(
                        out[r], host(r), **tol,
                        err_msg=f"{kind}[{name}] p={p} rank {r} vs host")
                if base is None:
                    base = out
                else:
                    np.testing.assert_allclose(
                        out, base, **tol,
                        err_msg=f"{kind}[{name}] p={p} vs xla")
                err = float(np.abs(out - base).max())
                log(f"  p={p} {kind}[{name}] blk={blk} "
                    f"collective_permutes={n_cp} "
                    f"tpu_custom_call={'tpu_custom_call' in text} "
                    f"max_diff_vs_xla={err!r} compile={secs:.2f}s")
                if name != "xla" and n_cp != want_cp:
                    raise AssertionError(
                        f"{kind}[{name}] p={p}: {n_cp} collective-permutes, "
                        f"want {want_cp}")
        t0 = time.time()
        a2a = conf.run_alltoall(p, mesh)
        log(f"  p={p} alltoall(v): {a2a['n_cases']} cases, "
            f"collective-permutes {a2a['rounds']} {time.time() - t0:.1f}s")
        t0 = time.time()
        bc = conf.run_broadcast(p, mesh)
        log(f"  p={p} broadcast: {bc} {time.time() - t0:.1f}s")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def _memory() -> str:
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    now = max(s.get("bytes_in_use", 0) for s in stats)
    return f"peak_bytes_in_use={peak} bytes_in_use={now}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the multi-chip phases only (collectives, "
                         "ZeRO-1 dp=4)")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401
    except ImportError:
        print(f"chip_smoke: the repository's src/ is not beside {__file__}",
              file=sys.stderr)
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    from repro.launch.compile import CompileCounter, setup_compile_cache
    cache = setup_compile_cache()
    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)} jax {jax.__version__} compile_cache={cache}")

    if args.chips == 4:
        phases = [("collectives", phase_collectives),
                  ("trainer_dp4", phase_trainer_dp4)]
    else:
        phases = [("kernels", phase_kernels), ("trainer", phase_trainer),
                  ("server", phase_server)]
    for name, phase in phases:
        log(f"== phase {name}")
        t0 = time.time()
        with CompileCounter() as cc:
            phase()
        log(f"== phase {name}: ok {time.time() - t0:.1f}s "
            f"compiles={cc.count} compile_s={cc.seconds:.1f} {_memory()}")

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
