"""Training driver: config-driven launcher with checkpointing, watchdog and
restart-safe data cursors.

Runs anywhere: on this CPU container use a small mesh + reduced config
(examples/quickstart.py does exactly that); on a real pod, point it at the
production mesh.  All distribution knobs are CLI flags so the launcher is
the single entry point a cluster scheduler invokes on every host.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --scale-down --steps 50 --mesh 1x1 --mode single
"""
from __future__ import annotations

import argparse
import time

from repro.checkpoint import CheckpointManager, config_fingerprint
from repro.configs import ALIASES
from repro.ft import FailureInjector, Watchdog
from repro.launch import bootstrap
from repro.launch.compile import CompileCounter, setup_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ALIASES), required=True)
    ap.add_argument("--scale-down", action="store_true",
                    help="reduced same-family config (CPU runs)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--mesh", default="1x1",
                    help="DxM (data x model), e.g. 4x2; 1x1 = single")
    ap.add_argument("--mode", default=None,
                    choices=[None, "single", "zero1", "fsdp_auto"])
    ap.add_argument("--grad-sync", default="circulant",
                    choices=["circulant", "ring", "xla", "allreduce"])
    ap.add_argument("--schedule", default="halving")
    ap.add_argument("--wire-dtype", default=None, choices=[None, "int8"],
                    help="compressed int8 wire format for the circulant "
                         "gradient sync (quantize-on-send, fused "
                         "dequant-reduce rounds, error feedback)")
    ap.add_argument("--no-error-feedback", action="store_true",
                    help="disable the EF-SGD residual for compressed sync")
    ap.add_argument("--compress", default=None, choices=[None, "int8"],
                    help="DEPRECATED alias for --wire-dtype (emits a "
                         "DeprecationWarning; the wire format is part of "
                         "the grad-sync CollectiveSpec now)")
    ap.add_argument("--bucket-bytes", type=int, default=None,
                    help="bucketed, overlapped grad sync: target bytes per "
                         "gradient bucket (e.g. 25000000); each bucket runs "
                         "one circulant RS/AG on the cached plan with rounds "
                         "software-pipelined across buckets. Default: off "
                         "(single-shot per leaf, bitwise-identical legacy "
                         "path). Requires --grad-sync circulant")
    ap.add_argument("--fused-kernel", default="auto",
                    choices=["auto", "on", "off"],
                    help="fused Pallas round kernel for the circulant "
                         "collectives (auto = Pallas on TPU, jnp on CPU)")
    ap.add_argument("--moe-dispatch", default=None,
                    choices=[None, "global", "rowwise", "ep"],
                    help="MoE dispatch layout (MoE archs only); 'ep' "
                         "shards experts over the model axis and "
                         "exchanges the dispatch buffer via the circulant "
                         "alltoall plan + routed counts via alltoallv")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="failure injection (restart drill)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    d, m = (int(x) for x in args.mesh.split("x"))
    setup_compile_cache()
    try:
        sess = bootstrap.build_session(
            arch=args.arch, scale_down=args.scale_down, steps=args.steps,
            seq_len=args.seq_len, global_batch=args.global_batch,
            dp=d, mp=m, mode=args.mode, grad_sync=args.grad_sync,
            schedule=args.schedule, wire_dtype=args.wire_dtype,
            error_feedback=not args.no_error_feedback,
            use_fused_kernel={"auto": None, "on": True,
                              "off": False}[args.fused_kernel],
            bucket_bytes=args.bucket_bytes,
            moe_dispatch=args.moe_dispatch,
            lr=args.lr, warmup=args.warmup,
            compress=args.compress)  # deprecated alias; warns
    except (RuntimeError, ValueError) as e:
        raise SystemExit(str(e)) from e

    start = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        if mgr.latest_step() is not None:
            start, man = bootstrap.restore_session(sess, mgr)
            print(f"resumed from step {start} "
                  f"(manifest cursor {man.get('data_cursor')})")

    injector = FailureInjector(fail_at_step=args.fail_at_step)
    wd = Watchdog()
    losses = []
    first, later = CompileCounter(), CompileCounter()
    with sess.use_mesh():
        for step in range(start, args.steps):
            injector.check(step)
            t0 = time.time()
            with first if step == start else later:
                metrics = bootstrap.run_step(sess, step)
            dt = time.time() - t0
            status = wd.observe(step, dt)
            losses.append(float(metrics["loss"]))
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d}  loss {losses[-1]:.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"lr {float(metrics['lr']):.2e}  {dt*1e3:.0f}ms "
                      f"[{status}]")
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.save_async(
                    step + 1, sess.params, bootstrap.opt_flat(sess),
                    {"data_cursor": step + 1,
                     "config": config_fingerprint(sess.cfg),
                     "mesh": args.mesh, "arch": args.arch,
                     "world": sess.world})
    if mgr:
        mgr.wait()
    print(f"final loss: {losses[-1]:.4f} (start {losses[0]:.4f})")
    print(f"compiles: first step {first.count}, later steps {later.count}")
    return losses


if __name__ == "__main__":
    main()
