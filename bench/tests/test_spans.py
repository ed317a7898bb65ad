"""The program's spans and scopes on the device trace's clock
(``bench/spans.py``), on small traces whose answers are counted by hand,
and on one recorded on a v5e."""
import json
import os

import pytest

from bench import harness, spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))


def serving():
    # One device busy 0-10 and 40-50 of a 0-60 window, under one
    # benchmark step; two scheduler steps, the first sampling 5-20 and
    # emitting 20-30, the second gathering 36-45.
    return {"devices": {0: [[0, 10, "fusion.1", "fusion"],
                            [40, 50, "while.2", "while"]]},
            "host": [[0, 60, "bench.step"]],
            "spans": [[0, 35, "sched.step"], [5, 20, "sched.sample"],
                      [20, 30, "sched.emit"], [35, 60, "sched.step"],
                      [36, 45, "sched.gather"]],
            "window": [0, 60], "window_s": 60e-9}


def test_gap_straddling_spans_is_split_by_overlap():
    tr = serving()
    # gap 10-40: sample 10-20, emit 20-30, the first step 30-35, the
    # second 35-36, gather 36-40; gap 50-60: the second step
    assert dict(spans.idle_gaps(tr)) == pytest.approx({
        "sched.sample": 10e-9, "sched.emit": 10e-9, "sched.step": 16e-9,
        "sched.gather": 4e-9})
    # where no program span covers a part, the benchmark's does, else
    # nothing does
    tr["spans"] = [[5, 20, "sched.sample"]]
    tr["host"] = [[0, 35, "bench.step"]]
    assert dict(spans.idle_gaps(tr)) == pytest.approx({
        "sched.sample": 10e-9, "bench.step": 15e-9, "host": 15e-9})
    # the old reduction, on the same trace, is unchanged
    assert dict(trace.idle_gaps(serving())) == pytest.approx(
        {"bench.step": 40e-9})


def test_an_offset_moves_a_gap_to_the_neighbouring_span():
    tr = {"devices": {0: [[0, 10, "a", "fusion"], [20, 30, "b", "fusion"]]},
          "host": [], "spans": [[0, 15, "sched.sample"],
                                [15, 30, "sched.emit"]],
          "window": [10, 25], "window_s": 15e-9}   # on the host's clock
    assert dict(spans.idle_gaps(tr)) == pytest.approx(
        {"sched.sample": 5e-9, "sched.emit": 5e-9})
    moved = spans.on_host_clock(tr, {0: [[None, 5, 5]]})
    assert moved["devices"][0][0][:2] == [5, 15]
    assert tr["devices"][0][0][:2] == [0, 10]          # a copy
    assert dict(spans.idle_gaps(moved)) == pytest.approx(
        {"sched.emit": 10e-9})


def test_step_idle_leaves_out_admission_and_steps_without_decode():
    tr = {"devices": {0: [[10, 20, "p", "fusion"], [35, 60, "d", "while"],
                          [70, 80, "s", "fusion"], [120, 130, "x", "fusion"]]},
          "host": [],
          "spans": [[0, 100, "sched.step"], [0, 30, "sched.admit"],
                    [40, 50, "sched.decode"],
                    [110, 140, "sched.step"]],   # no decode: left out
          "window": [0, 140], "window_s": 140e-9}
    # inside 30-100, idle 30-35, 60-70 and 80-100
    assert spans.step_idle_ms(tr) == pytest.approx(35 / 1e6)
    tr["spans"] = tr["spans"][-1:]
    assert spans.step_idle_ms(tr) is None


def test_scope_time_is_a_union_on_the_worst_chip():
    rs, ag = "zero1.reduce_scatter", "zero1.allgather"
    tr = {"scopes": {0: [[0, 10, rs, "fusion"], [5, 15, rs, "tpu_custom_call"],
                         [20, 21, ag, "collective-permute-start"],
                         [24, 25, ag, "collective-permute-done"]],
                     1: [[0, 12, rs, "fusion"], [30, 40, ag, "fusion"]]}}
    assert spans.scope_ms(tr, rs, 1) == pytest.approx(15e-6)
    # an asynchronous permute counts from its start to its done
    assert spans.scope_ms(tr, ag, 2) == pytest.approx(5e-6)
    assert spans.scope_ms({"scopes": {0: []}}, ag, 1) is None


def test_scope_of_reads_the_innermost_zero1_scope():
    assert spans.scope_of("jit(step_fn)/shard_map/zero1.adamw/"
                          "zero1.allgather/ppermute") == "zero1.allgather"
    assert spans.scope_of("jit(step_fn)/shard_map/zero1.reduce_scatter/"
                          "fused_round/pallas_call") == "zero1.reduce_scatter"
    assert spans.scope_of("jit(step_fn)/pmean") is None
    text = ('  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, '
            'metadata={op_name="jit(step_fn)/shard_map/zero1.adamw/mul"}\n'
            '  %collective-permute-start.1 = (f32[2,4], f32[2,4]) '
            'collective-permute-start(%s), metadata={op_name='
            '"jit(step_fn)/shard_map/zero1.reduce_scatter/ppermute"}\n'
            '  %add.1 = f32[] add(%x, %y), metadata={op_name="jit(f)/add"}\n')
    assert spans.hlo_scopes(text) == {
        "fusion.3": "zero1.adamw",
        "collective-permute-start.1": "zero1.reduce_scatter"}


def runs():
    """Three steps on a device whose clock runs 100 ns behind the host's.
    Each enqueues ``decode`` (run 2k, which starts 5 ns after the host
    began to enqueue it) and then ``_argmax`` (run 2k+1, queued behind
    the decode) inside ``sched.sample``, which ends 4 ns after the argmax
    does; the host's completion callback for each run starts 3 ns after
    the run ends."""
    mods, enq, done, sp = [], [], [], []
    for k, t in enumerate((1000, 2000, 3000)):
        for r, (s, e, fn) in ((2 * k, (t - 95, t + 300, "decode")),
                              (2 * k + 1, (t + 360, t + 408, "_argmax"))):
            mods.append([s, e, fn, r])
            done.append([e + 103, e + 110, 0, r])
        enq += [[t, t + 2, 0, 2 * k], [t + 450, t + 452, 0, 2 * k + 1]]
        sp.append([t + 440, t + 512, "sched.sample"])
    return {"devices": {0: []}, "host": [], "spans": sp,
            "modules": {0: mods}, "enqueues": enq, "completions": done}


def test_clock_bracket_from_enqueues_and_completions():
    tr = runs()
    # lower: a decode starts 95 after the host began to enqueue it (the
    # argmax, queued behind it, bounds less); upper: each run ends 103
    # before its completion callback, 104 before its sample ends
    assert spans.clock_pieces(tr) == {0: [[None, 95, 103]]}
    tr["completions"] = []
    assert spans.clock_pieces(tr, ("sched.sample",)) == {0: [[None, 95, 104]]}
    assert spans.clock_pieces(tr) == {0: [[None, 95, None]]}
    # a second device, whose clock runs 1 ns further behind, has its own
    tr["modules"][1] = [[s - 1, e - 1, p, r] for s, e, p, r in
                        tr["modules"][0]]
    tr["enqueues"] += [[s, e, 1, r] for s, e, _, r in tr["enqueues"]]
    assert spans.clock_pieces(tr, ("sched.sample",))[1] == [[None, 96, 105]]


def test_a_step_of_the_device_clock_starts_a_new_piece():
    tr = runs()
    # from the second step on, the device's clock reads 50 ns later
    late = {r for _, _, _, r in tr["modules"][0][2:]}
    tr["modules"][0] = [[s + 50 * (r in late), e + 50 * (r in late), p, r]
                        for s, e, p, r in tr["modules"][0]]
    pieces = spans.clock_pieces(tr, ("sched.sample",))[0]
    # the second step's decode (lower bound 45) still fits the first
    # piece; its end (upper bound 53) does not
    assert pieces == [[None, 95, 103], [2300 + 50, 45, 53]]
    moved = spans.on_host_clock(tr, {0: pieces})
    starts = [s for s, *_ in moved["modules"][0]]
    # the decode enqueued before the step is read on the old piece
    assert starts == [905 + 99, 1360 + 99, 1955 + 99,
                      2410 + 49, 2955 + 49, 3410 + 49]
    assert spans.offset_of([None, 95, None]) == 95


def test_a_lone_contradicting_bound_starts_no_piece():
    tr = runs()
    # a stray sync that ends before the second decode does (upper bound
    # 2): the argmax's start (lower bound 90) contradicts it, not the piece
    tr["spans"].append([2290, 2302, "sched.sample"])
    one = {0: [[None, 95, 103]]}
    assert spans.clock_pieces(tr, ("sched.sample",)) == one
    # one still held at the end (2 ns after the last argmax) is dropped too
    tr["spans"][-1] = [3405, 3410, "sched.sample"]
    assert spans.clock_pieces(tr, ("sched.sample",)) == one


def test_serve_phases_run_on_cpu(tiny_root, cpu_peaks, tmp_path):
    """The serve cell's phases at a size the CPU holds: the scheduler's
    counters over the window, and its spans in the reduced trace (the
    CPU trace has no device plane, so nothing on the device clock)."""
    import jax

    from bench import phases
    spec = harness.cell_spec("tiny.chat", tiny_root)
    dump = str(tmp_path / "tr.json")
    out = phases.serve_phases(spec, (1 << 31) + 7, 3.0, jax.devices()[:1],
                              dump)
    st = out["stats"]
    assert st["decode_steps"] > 0 and 1 <= st["occupancy"] <= 3
    assert out["serve.emit_ms"] == st["ms_per_decode_step"]["sched.emit"] > 0
    assert out["serve.step_idle_ms"] is None
    assert out["clock_offset_ms"] is None
    names = {s[2] for s in json.load(open(dump))["spans"]}
    assert {"sched.step", "sched.decode", "sched.sample",
            "sched.emit"} <= names


TRAIN_PHASES = """
import json, sys
import jax
from bench import harness, phases
spec = harness.cell_spec("tiny.train", sys.argv[1])
out = phases.train_phases(spec, (1 << 33) + 5, 1.0, jax.devices()[:4],
                          sys.argv[2])
print(json.dumps(out))
"""


def test_train_phases_run_on_four_cpu_devices(tiny_root, tmp_path):
    """The train cell's phases on four CPU devices: ``train.drive`` runs
    under the trace, and the step's compiled text names operations under
    every ``zero1.*`` scope (the CPU trace has no device plane, so no
    scope has a time)."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(HERE))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([repo, os.path.join(repo, "src")]))
    dump = str(tmp_path / "tr.json")
    r = subprocess.run([sys.executable, "-c", TRAIN_PHASES, tiny_root, dump],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert set(out["hlo_scoped_ops"]) == {
        "zero1.fwd_bwd", "zero1.reduce_scatter", "zero1.adamw",
        "zero1.allgather"}
    assert out["train.rs_ms"] is None and out["train.ag_ms"] is None
    assert out["step_ms"] > 0
    host = {name for *_, name in json.load(open(dump))["host"]}
    assert host == {"bench.step"}


def _recorded(part):
    rec = json.load(open(os.path.join(HERE, "data", "v5e_trace_spans.json")))
    tr = rec[part]
    for k in ("devices", "modules", "scopes"):
        tr[k] = {int(d): v for d, v in tr.get(k, {}).items()}
    return tr, tr.pop("expected")


def test_recorded_chip_trace_serving():
    """Four decode steps of chat-decode recorded on a v5e by
    ``bench.phases`` (the device's busy time kept as its union), across a
    step of the profiler's clock alignment: each
    piece's bracket holds, and on the corrected clock the program's
    spans take all but a sliver of the idle time."""
    tr, exp = _recorded("serve")
    pieces = spans.clock_pieces(tr, ("sched.sample",))
    assert len(pieces[0]) == 2
    assert all(lo <= hi for _, lo, hi in pieces[0])
    assert pieces == {int(d): p for d, p in exp["clock_pieces"].items()}
    moved = spans.on_host_clock(tr, pieces)
    gaps = dict(spans.idle_gaps(moved))
    assert gaps == pytest.approx(dict(exp["idle_gaps"]), rel=1e-9)
    assert gaps.get("host", 0.0) <= 0.1 * sum(gaps.values())
    assert spans.step_idle_ms(moved) == pytest.approx(exp["step_idle_ms"],
                                                      rel=1e-9)
