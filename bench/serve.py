"""Serve driver: open-loop traffic through the program's continuous-
batching ``Scheduler`` over a ``ServeEngine``, one chip.

Set-up builds the engine on the benchmark's weights, warms every shape
the mix uses (one request per prompt length, through the scheduler
itself) and admits a batch of requests already in flight, so the window
opens on a full batch.  The window then submits each arrival when it is
due and steps the scheduler; a request's time to first token counts from
when it was due.  After the window the requests that arrived in it are
stepped to their first token, the device's peak memory is read, the
program's state is freed, and a sample of finished requests is checked
against the plain float32 reference (``bench/model.py``).
"""
from __future__ import annotations

import functools
import gc
import time

import numpy as np

from . import harness, model, trace, traffic, work


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


class Session:
    """The program's server, built on the benchmark's weights."""

    def __init__(self, spec: dict, seed: int, devs):
        import jax
        from repro.launch.bootstrap import resolve_cfg
        from repro.models import build
        from repro.serve import ServeEngine

        cfg_file, cell = spec["config"], spec["cell"]
        self.sizes = model.Sizes(cfg_file)
        self.cfg = resolve_cfg(cfg_file["program_arch"],
                               scale_down=cfg_file.get("program_scale_down",
                                                       False),
                               n_layers=self.sizes.layers)
        check_program_config(self.cfg, cfg_file)
        prog = build(self.cfg, recipe=None, remat=False)
        self.params = jax.jit(functools.partial(model.init, self.sizes))(
            model.key_of(seed))
        same_tree(jax.eval_shape(prog.init, jax.random.PRNGKey(0)),
                  self.params)
        self.engine = ServeEngine(prog, self.params, cell["max_len"])
        self.new_scheduler(cell)

    def new_scheduler(self, cell: dict) -> None:
        """A fresh scheduler (empty pool) on the engine, its admissions
        wrapped in the benchmark's host span."""
        from repro.serve import Scheduler
        self.sched = None
        gc.collect()
        self.sched = Scheduler(self.engine, max_batch=cell["max_batch"],
                               kv_block_size=cell["kv_block"])
        self.spans = {"admit": [], "step": []}
        admit = self.sched._admit

        def timed_admit():
            t = time.perf_counter()
            n = self.sched.n_prefills
            with trace.span("bench.admit"):
                admit()
            if self.sched.n_prefills > n:
                self.spans["admit"].append(
                    (t, time.perf_counter(), self.sched.n_prefills - n))
        self.sched._admit = timed_admit

    def step(self):
        t = time.perf_counter()
        a = len(self.spans["admit"])
        done = len(self.sched.finished)
        self.sched.step()
        admit = self.spans["admit"][a][1] - self.spans["admit"][a][0] \
            if len(self.spans["admit"]) > a else 0.0
        active = [r for r in self.sched.slots if r is not None]
        self.spans["step"].append((t, time.perf_counter(), admit,
                                   len(active), sum(r.pos for r in active),
                                   len(self.sched.finished) - done))

    def free(self):
        """Drop the program's state (pool, engine); keep the weights,
        which are the benchmark's own."""
        self.sched = self.engine = None
        gc.collect()


def check_program_config(pcfg, c: dict) -> None:
    """The program's registry config has to run what the file states."""
    want = {"d_model": c["hidden_size"], "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "head_dim": c.get("head_dim") or c["hidden_size"]
            // c["num_attention_heads"],
            "d_ff": c["intermediate_size"], "vocab_size": c["vocab_size"],
            "n_layers": c["num_hidden_layers"], "norm_eps": c["rms_norm_eps"],
            "rope_theta": float(c["rope_theta"]),
            "qk_norm": bool(c.get("qk_norm", False)),
            "tie_embeddings": bool(c["tie_word_embeddings"]),
            "dtype": c["torch_dtype"], "family": "dense",
            "qkv_bias": False, "sliding_window": 0}
    bad = {k: (getattr(pcfg, k), v) for k, v in want.items()
           if getattr(pcfg, k) != v}
    if bad:
        raise ValueError(f"program config differs from the file: {bad}")


def same_tree(program_shapes, params) -> None:
    import jax
    a = jax.tree.map(lambda x: (x.shape, str(x.dtype)), program_shapes)
    b = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    if a != b:
        raise ValueError(f"weight tree differs from the program's: {a} "
                         f"vs {b}")


def warm_up(sess: Session, mix: dict) -> None:
    """Every shape the window uses: one request per prompt length (each
    compiles its B=1 prefill), two tokens each, through the scheduler."""
    lengths = sorted(set(mix["prompt_len"]["choice"]))
    r = traffic.rng(0, 9)
    for n in lengths:
        sess.sched.submit(r.integers(0, sess.sizes.vocab, n, np.int32), 2)
    sess.sched.run()
    sess.sched.finished.clear()


def run_window(sess: Session, load: dict, seconds: float,
               trace_dir: str | None = None, trace_s: float = 3.0,
               drain_s: float = 60.0, on_step=None) -> dict:
    """Open loop over ``load`` (from ``traffic.open_loop``).  Returns the
    per-request record and the window's bounds."""
    sched = sess.sched
    info = {}                                   # rid -> record
    for i in range(len(load["warm"]["out"])):
        rid = sched.submit(load["warm"]["prompt"][i],
                           int(load["warm"]["out"][i]))
        info[rid] = {"prompt": load["warm"]["prompt"][i], "due": None,
                     "times": [], "seen": 0}
    while sched.waiting:                        # the in-flight batch
        sess.step()
    t_last = time.perf_counter()
    for req in sched.slots:
        if req is not None:
            info[req.rid].update(seen=len(req.out), times=[t_last])
    sess.spans = {"admit": [], "step": []}
    harness.mark("in_flight")
    t0 = time.perf_counter()
    t_end = t0 + seconds
    due = t0 + load["due"]
    nxt, n = 0, len(due)
    tracing = None
    if trace_dir is not None:                   # the window's last seconds
        t_trace = t0 + max(0.0, seconds - trace_s)

    def record(now):
        for req in sched.slots:
            if req is None:
                continue
            rec = info[req.rid]
            while rec["seen"] < len(req.out):
                rec["times"].append(now)
                rec["seen"] += 1

    while True:
        now = time.perf_counter()
        if trace_dir is not None and tracing is None and now >= t_trace:
            tracing = trace.Capture(trace_dir)
        if now >= t_end:
            waiting = len(sched.waiting)
            break
        while nxt < n and due[nxt] <= now:
            rid = sched.submit(load["prompt"][nxt], int(load["out"][nxt]))
            info[rid] = {"prompt": load["prompt"][nxt], "due": due[nxt],
                         "submitted": now, "times": [], "seen": 0}
            nxt += 1
        if sched.idle:
            time.sleep(max(0.0, min(t_end, due[nxt] if nxt < n else t_end)
                           - time.perf_counter()))
            continue
        with trace.span("bench.step"):
            sess.step()
        now = time.perf_counter()
        record(now)
        if on_step is not None:
            on_step(now)
    if tracing is not None:
        tracing.stop()
    window_steps = list(sess.spans["step"])
    window_admits = list(sess.spans["admit"])
    # Arrivals of the window still without a first token: step on (no new
    # arrivals) until they have one.
    stop = time.perf_counter() + drain_s
    pending = lambda: [r for r in info.values()
                       if r["due"] is not None and not r["times"]]
    while pending() and time.perf_counter() < stop and not sched.idle:
        sess.step()
        record(time.perf_counter())
    return {"info": info, "t0": t0, "t_end": t_end,
            "submitted": nxt, "waiting_at_close": waiting,
            "steps": window_steps, "admits": window_admits,
            "trace": tracing}


def latency(rec: dict) -> dict:
    """TTFT of the window's arrivals and every inter-token gap that ends
    inside the window."""
    t0, t_end = rec["t0"], rec["t_end"]
    ttft, gaps = [], []
    for r in rec["info"].values():
        ts = r["times"]
        if r["due"] is not None and ts:
            ttft.append(ts[0] - r["due"])
        gaps += [b - a for a, b in zip(ts, ts[1:]) if t0 <= b <= t_end]
    return {"ttft": ttft, "gaps": gaps}


def sample_requests(sess: Session, rec: dict, seed: int, tokens: int) -> list:
    """Finished requests to check: the longest, then others in an order
    drawn from the seed, until they hold ``tokens`` served tokens (or
    every finished request is in)."""
    done = [(rid, np.asarray(toks)) for rid, toks in
            sess.sched.finished.items() if rid in rec["info"]]
    if not done:
        return []
    done.sort(key=lambda x: -len(x[1]))
    order = [0] + [1 + i for i in
                   traffic.rng(seed, 5).permutation(len(done) - 1)]
    chosen, n = [], 0
    for i in order:
        if n >= tokens:
            break
        chosen.append(done[i])
        n += len(done[i][1])
    return [(rec["info"][rid]["prompt"], toks) for rid, toks in chosen]


def _gap_program(sizes, max_len, low):
    """Reference logits over a padded sequence and, at each of its
    positions, how far below the reference's best lies (a) the served
    token and (b) the token the ``low`` control puts first; both in
    units of the reference's logit RMS at that position."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gaps(params, seq, nxt):
        ref = model.logits(sizes, params, seq)
        scale = jnp.sqrt(jnp.mean(ref * ref, axis=-1))
        best = ref.max(axis=-1)
        served = (best - jnp.take_along_axis(ref, nxt[:, None], 1)[:, 0]) \
            / scale
        if low is None:
            return served, served
        pick = jnp.argmax(model.logits(sizes, params, seq, low), axis=-1)
        ctrl = (best - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]) \
            / scale
        return served, ctrl
    return gaps


def compare(sizes, params, requests, max_len: int, control: bool = False
            ) -> dict:
    """Widest gap, over every served token of the sampled requests, by
    which the token's reference logit lies below the reference's best
    (greedy decoding serves the argmax).  With ``control``, the same for
    the token the reference computed on fp8 operands puts first at each
    position."""
    import jax.numpy as jnp
    fn = _gap_program(sizes, max_len, model.FP8 if control else None)
    worst, worst_ctrl, n_tok = 0.0, 0.0, 0
    for prompt, toks in requests:
        s, n = len(prompt), len(toks)
        seq = np.zeros((max_len,), np.int32)
        seq[:s + n - 1] = np.concatenate([prompt, toks[:-1]])
        nxt = np.zeros((max_len,), np.int32)
        nxt[s - 1:s - 1 + n] = toks
        g, c = (np.asarray(x) for x in fn(params, jnp.asarray(seq),
                                          jnp.asarray(nxt)))
        worst = max(worst, float(g[s - 1:s - 1 + n].max()))
        worst_ctrl = max(worst_ctrl, float(c[s - 1:s - 1 + n].max()))
        n_tok += n
    return {"max_gap": worst, "control_gap": worst_ctrl, "tokens": n_tok,
            "requests": len(requests)}


def run(spec: dict, seed: int, seconds: float, do_trace: bool,
        t_start: float, devs, control: bool = False) -> dict:
    from repro.launch.compile import CompileCounter
    cell, mix = spec["cell"], spec["traffic"]
    sess = Session(spec, seed, devs)
    harness.mark("weights")
    warm_up(sess, mix)
    harness.mark("warm_up")
    load = traffic.open_loop(mix, cell["rate_per_s"], seconds, seed,
                             sess.sizes.vocab, warm=cell["max_batch"])
    with trace.maybe_dir(do_trace) as tdir:
        with CompileCounter() as cc:
            rec = run_window(sess, load, seconds, trace_dir=tdir)
        reduced = rec["trace"].reduce() if rec["trace"] is not None else None
    lat = latency(rec)
    peak = harness.memory_peak(devs)
    requests = sample_requests(sess, rec, seed, cell["sample_tokens"])
    arrived = [r for r in rec["info"].values() if r["due"] is not None]
    failed = sum(1 for r in arrived if not r["times"])
    sess.free()
    cmp = compare(sess.sizes, sess.params, requests, cell["max_len"],
                  control=control)
    cmp["ok"] = bool(requests)
    window = rec["t_end"] - rec["t0"]
    run_rec = {"window_s": window, "compiles": cc.count,
               "steps": rec["steps"], "admits": rec["admits"],
               "trace": reduced, "sizes": sess.sizes, "cell": cell,
               "peaks": harness.peaks(devs[0].device_kind), "work": work}
    e2e = {"setup_s": rec["t0"] - t_start}
    for q in (95, 99):
        e2e[f"itl_p{q}_ms"] = _pct(lat["gaps"], q) * 1e3 if lat["gaps"] \
            else None
    late = max((r["submitted"] - r["due"] for r in arrived), default=0.0)
    slow = sorted(rec["steps"], key=lambda x: x[0] - x[1])[:6]
    harness.log(
        "serve: slowest steps (ms, admit ms, active, evicted): " + ", ".join(
            f"{(b - a) * 1e3:.1f}/{adm * 1e3:.1f}/{n}/{ev}"
            for a, b, adm, n, _, ev in slow)
        + "; admissions (ms, n): " + ", ".join(
            f"{(b - a) * 1e3:.1f}/{n}" for a, b, n in rec["admits"])
        + "; gap percentiles 50/90/95/99 (ms): " + "/".join(
            f"{_pct(lat['gaps'], q) * 1e3:.2f}" for q in (50, 90, 95, 99))
        if lat["gaps"] else "serve: no gaps")
    harness.log(
        f"serve: window {window:.3f}s arrivals {len(arrived)} generator "
        f"lateness max {late * 1e3:.2f} ms, waiting at close "
        f"{rec['waiting_at_close']}, failed {failed}; steps "
        f"{len(rec['steps'])} prefills {sum(a[2] for a in rec['admits'])} "
        f"gaps {len(lat['gaps'])} ttft {len(lat['ttft'])}; itl p50 "
        f"{_pct(lat['gaps'], 50)} ttft p50/p95 {_pct(lat['ttft'], 50)}/"
        f"{_pct(lat['ttft'], 95)}; "
        f"compiles in window {cc.count}; peak bytes {peak}; compared "
        f"{cmp['requests']} requests, {cmp['tokens']} tokens")
    return {"e2e": e2e, "run": run_rec, "peak": peak, "cmp": cmp,
            "attempted": len(arrived), "failed": failed}
