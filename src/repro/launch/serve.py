"""Serving driver: one-shot batched generation, continuous batching, and
multi-replica weight fan-out — all on the shared launch bootstrap.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
        --scale-down --batch 4 --prompt-len 16 --max-new 16

``--max-batch`` switches to the continuous-batching scheduler (paged KV
cache sized by ``--kv-block-size``); ``--replicas N`` serves data-
parallel over N replicas whose weights were fanned out through the
``kind="broadcast"`` plan (needs N fake/real devices).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ALIASES
from repro.launch.bootstrap import build_serve_session
from repro.launch.compile import setup_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ALIASES), required=True)
    ap.add_argument("--scale-down", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--moe-dispatch", default=None,
                    choices=[None, "global", "rowwise", "ep"],
                    help="MoE dispatch layout (MoE archs only); 'ep' "
                         "serves with experts sharded over --ep-devices "
                         "ranks, exchanging dispatch buffers via the "
                         "circulant alltoall plan")
    ap.add_argument("--ep-devices", type=int, default=2,
                    help="mesh size for --moe-dispatch ep")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel serving replicas; weights are "
                         "fanned out via the broadcast plan")
    ap.add_argument("--max-batch", type=int, default=0,
                    help="> 0: continuous-batching scheduler with this "
                         "many decode slots (instead of one-shot "
                         "generate)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="paged KV cache block size (--max-batch mode; "
                         "must divide prompt-len + max-new)")
    args = ap.parse_args(argv)

    setup_compile_cache()
    try:
        sess = build_serve_session(
            arch=args.arch, max_len=args.prompt_len + args.max_new,
            scale_down=args.scale_down, temperature=args.temperature,
            moe_dispatch=args.moe_dispatch, ep_devices=args.ep_devices,
            replicas=args.replicas)
    except (ValueError, RuntimeError) as e:
        raise SystemExit(str(e))
    cfg = sess.cfg
    if args.replicas > 1:
        st = sess.push_stats
        print(f"broadcast weight fan-out: {st['n_leaves']} leaves, "
              f"{st['bytes']} bytes, {st['rounds']} rounds x "
              f"{args.replicas} replicas")

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    extras = {}
    if cfg.family == "encdec":
        extras["frames"] = jax.numpy.asarray(rng.standard_normal(
            (args.batch, args.prompt_len, cfg.d_model)).astype(np.float32))
    if cfg.family == "vlm":
        extras["image_embeds"] = jax.numpy.asarray(rng.standard_normal(
            (args.batch, cfg.n_image_tokens, cfg.d_model)
        ).astype(np.float32))

    if args.max_batch > 0:
        from repro.serve import Scheduler
        if extras:
            raise SystemExit("--max-batch covers decoder-only archs "
                             "(no prefill extras)")
        sched = Scheduler(sess.engine, max_batch=args.max_batch,
                          kv_block_size=args.kv_block_size)
        t0 = time.time()
        rids = [sched.submit(prompts[b], args.max_new)
                for b in range(args.batch)]
        done = sched.run()
        dt = time.time() - t0
        total = sum(len(done[r]) for r in rids)
        print(f"scheduler: {args.batch} requests, {total} tokens in "
              f"{dt:.2f}s ({total / dt:.1f} tok/s incl. compile)")
        print(sched.stats.summary())
        for b, r in enumerate(rids[:2]):
            print(f"  req{r}: {done[r][:12].tolist()}")
        return done

    if args.replicas > 1:
        if extras:
            raise SystemExit("--replicas covers decoder-only archs "
                             "(batched prefill extras don't split)")
        gen = sess.replica_set.generate
    else:
        gen = sess.engine.generate
    kw = {"extras": extras} if args.replicas == 1 else {}
    t0 = time.time()
    out = gen(prompts, args.max_new, **kw)
    dt = time.time() - t0
    tps = args.batch * args.max_new / dt
    print(f"generated {out.shape} in {dt:.2f}s ({tps:.1f} tok/s incl. "
          f"compile)")
    for b in range(min(2, args.batch)):
        print(f"  seq{b}: {out[b][:12].tolist()}")
    # steady-state decode timing (compiled)
    t0 = time.time()
    gen(prompts, args.max_new, **kw)
    dt2 = time.time() - t0
    print(f"steady-state: {args.batch * args.max_new / dt2:.1f} tok/s")
    return out


if __name__ == "__main__":
    main()
