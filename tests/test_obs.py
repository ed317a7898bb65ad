"""Program spans (``repro.obs``) and the scheduler's spans and counters
(``SchedulerStats``), read back from a JAX profiler trace on the CPU."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build
from repro.obs import Spans
from repro.serve import Scheduler, ServeEngine

STEP_CHILDREN = {"sched.evict", "sched.admit", "sched.gather",
                 "sched.decode", "sched.write_token", "sched.sample",
                 "sched.emit"}
ADMIT_CHILDREN = {"sched.prefill", "sched.write_prefill"}


def _host_spans(trace_dir: str, prefix: str) -> list:
    """``[start_ns, end_ns, name, {stat: value}]`` of every host event whose
    name starts with ``prefix``, in start order."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append([e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats)])
    return sorted(out, key=lambda x: x[:2])


def _inside(child, parents) -> bool:
    return any(p[0] <= child[0] and child[1] <= p[1] for p in parents)


def test_span_adds_seconds():
    s = Spans()
    with s.span("a"):
        with s.span("b", rid=1):
            pass
    with s.span("a"):
        pass
    assert set(s.seconds) == {"a", "b"}
    assert s.seconds["a"] >= s.seconds["b"] > 0
    snap = s.copy()
    with s.span("a"):
        pass
    assert snap.seconds["a"] < s.seconds["a"]
    assert snap.seconds["b"] == s.seconds["b"]


def test_span_lands_in_the_profiler_trace_with_its_ids(tmp_path):
    s = Spans()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with s.span("obs.outer"):
            with s.span("obs.inner", rid=7, prompt_len=12):
                pass
    finally:
        jax.profiler.stop_trace()
    got = _host_spans(str(tmp_path), "obs.")
    assert [g[2] for g in got] == ["obs.outer", "obs.inner"]
    assert got[1][3] == {"rid": 7, "prompt_len": 12}
    assert _inside(got[1], [got[0]])


def _engine(max_len=24):
    cfg = get_config("internlm2-1.8b").scaled_down(n_layers=2, vocab_size=64)
    model = build(cfg, recipe=None, remat=False)
    return ServeEngine(model=model, params=model.init(jax.random.PRNGKey(0)),
                       max_len=max_len)


PROMPTS = (8, 5, 11, 7)
MAXNEW = (4, 6, 3, 5)


def _serve(engine, trace_dir=None):
    """Four requests through two slots (so two of them wait), traced
    into ``trace_dir`` when one is given."""
    rng = np.random.default_rng(0)
    sched = Scheduler(engine, max_batch=2, kv_block_size=4)
    rids = [sched.submit(rng.integers(0, 64, (n,)).astype(np.int32), m)
            for n, m in zip(PROMPTS, MAXNEW)]
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    try:
        got = sched.run()
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return sched, rids, got


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    engine = _engine()
    _serve(engine)                       # compile outside the trace
    d = str(tmp_path_factory.mktemp("sched_trace"))
    sched, rids, got = _serve(engine, d)
    _, _, plain = _serve(engine)
    return {"sched": sched, "rids": rids, "got": got, "plain": plain,
            "spans": _host_spans(d, "sched.")}


def test_scheduler_spans_nest_as_the_code_does(traced):
    spans = traced["spans"]
    by = {}
    for s in spans:
        by.setdefault(s[2], []).append(s)
    assert set(by) == {"sched.step"} | STEP_CHILDREN | ADMIT_CHILDREN
    for name in STEP_CHILDREN:
        assert all(_inside(s, by["sched.step"]) for s in by[name]), name
    for name in ADMIT_CHILDREN:
        assert all(_inside(s, by["sched.admit"]) for s in by[name]), name
    # the pool write is queued inside the prefill, before its first token
    assert all(_inside(s, by["sched.prefill"])
               for s in by["sched.write_prefill"])
    # one prefill span per request, carrying its id and prompt length
    prefills = {s[3]["rid"]: s[3]["prompt_len"] for s in by["sched.prefill"]}
    assert prefills == dict(zip(traced["rids"], PROMPTS))


def test_scheduler_stats_agree_with_the_spans(traced):
    st, spans = traced["sched"].stats, traced["spans"]
    for name in {"sched.step"} | STEP_CHILDREN | ADMIT_CHILDREN:
        mine = [s for s in spans if s[2] == name]
        traced_s = sum(e - s for s, e, *_ in mine) / 1e9
        # the totals time the same block on another clock, around it
        assert traced_s - 1e-3 <= st.seconds[name] \
            <= traced_s + 1e-3 * len(mine) + 1e-3, name
    sched = traced["sched"]
    count = {name: sum(s[2] == name for s in spans)
             for name in ("sched.decode", "sched.prefill")}
    assert count["sched.decode"] == st.decode_steps == sched.n_decode_steps
    assert count["sched.prefill"] == st.prefills == sched.n_prefills \
        == len(PROMPTS)
    assert st.prefill_tokens == sum(PROMPTS)
    assert st.evictions == len(PROMPTS)
    # every token after a request's first comes from one decode slot-step
    assert st.slot_steps == sum(MAXNEW) - len(MAXNEW)
    assert 1 < st.occupancy <= 2


def test_queue_wait_counts_the_requests_that_waited(traced):
    st = traced["sched"].stats
    steps = [e - s for s, e, n, _ in traced["spans"] if n == "sched.step"]
    # the third request waits for a slot through at least one whole step
    assert st.queue_wait_max_s >= min(steps) / 1e9 > 0
    assert st.queue_wait_s >= st.queue_wait_max_s


def test_traced_streams_equal_untraced(traced):
    got, plain = traced["got"], traced["plain"]
    assert set(got) == set(plain)
    for rid in got:
        np.testing.assert_array_equal(got[rid], plain[rid])


def test_stats_summary_names_every_counter(traced):
    text = traced["sched"].stats.summary()
    for word in ("decode steps", "occupancy", "prefills", "prompt tokens",
                 "evictions", "queue wait", "sched.emit", "sched.sample"):
        assert word in text
