"""The reduction from a trace to metrics, on small traces whose answers
are counted by hand, and on one recorded on a v5e."""
import json
import os

import pytest

from bench import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def small():
    # Two devices over a 100 ns window.  Device 0: compute 0-20, a
    # collective-permute from 15 (start) to 50 (done), compute 40-45, the
    # kernel 60-70.  Device 1: compute 0-30, a synchronous all-reduce
    # 30-40.
    return {"devices": {
        0: [[0, 20, "fusion.1", "fusion"],
            [15, 16, "collective-permute-start.3", "collective-permute-start"],
            [40, 45, "fusion.2", "fusion"],
            [49, 50, "collective-permute-done.3", "collective-permute-done"],
            [60, 70, "step_fn.9", "tpu_custom_call"]],
        1: [[0, 30, "convolution.4", "convolution"],
            [30, 40, "all-reduce.7", "all-reduce"]]},
        "host": [[0, 100, "bench.step"], [55, 58, "bench.admit"]],
        "window": [0, 100], "window_s": 100e-9}


def test_op_of_reads_the_hlo_text():
    assert trace.op_of(
        "%copy-start = (bf16[8,128]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) "
        "copy-start(bf16[8,128]{1,0} %x.1)") == ("copy-start", "copy-start")
    assert trace.op_of(
        "%step.1 = (f32[2,64]{1,0}, f32[2,64]{1,0}) custom-call(f32[4,64] "
        "%a), custom_call_target=\"tpu_custom_call\"") == \
        ("step.1", "tpu_custom_call")
    assert trace.op_of(
        "%collective-permute-done.2 = f32[1,4]{1,0} collective-permute-done("
        "(f32[1,4], f32[1,4]) %collective-permute-start.2)") == \
        ("collective-permute-done.2", "collective-permute-done")


def test_union_and_minus():
    assert trace.union([[5, 9], [0, 3], [2, 4], [9, 10]]) == [[0, 4], [5, 10]]
    assert trace.length([[0, 3], [2, 4]]) == 4
    assert trace.minus([[0, 10]], [[2, 3], [5, 7], [9, 12]]) == \
        [[0, 2], [3, 5], [7, 9]]
    assert trace.minus([[0, 10]], []) == [[0, 10]]


def test_busy_and_idle():
    tr = small()
    # device 0: [0,20] u [15,16] u [40,45] u [49,50] u [60,70] = 20+5+1+10
    # device 1: [0,40] = 40
    assert trace.busy_s(tr) == pytest.approx((36 + 40) / 2 * 1e-9)
    assert trace.idle_share(tr) == pytest.approx(1 - 38 / 100)


def test_busy_inside_spans():
    ev = small()["devices"][0]
    # busy [0,20] [40,45] [49,50] [60,70]; inside [10,65] less [42,62]:
    # [10,20] + [40,42] + [62,65] = 15
    assert trace.busy_in(ev, [[10, 65]], [[42, 62]]) == 15
    assert trace.busy_in(ev, [[0, 100]]) == trace.length(ev)
    assert trace.busy_in(ev, [[20, 40]]) == 0


def test_exposed_collective():
    tr = small()
    # device 0: the exchange runs 15-50; compute covers 15-20 and 40-45
    # (the start/done markers are the exchange's own).
    assert trace.exposed_collective_ns(tr["devices"][0]) == 25
    # device 1: the all-reduce 30-40 overlaps nothing.
    assert trace.exposed_collective_ns(tr["devices"][1]) == 10


def test_kernel_time_and_breakdown():
    tr = small()
    assert trace.kernel_ns(tr["devices"][0], "tpu_custom_call") == (10.0, 1)
    ops = dict(trace.top_ops(tr))
    assert ops["fusion"] == pytest.approx((20 + 5) / 2 * 1e-9)
    gaps = dict(trace.idle_gaps(tr))
    # device 0 idle: 20-40, 45-49, 50-60, 70-100 -> 64 ns, all in
    # bench.step except none inside bench.admit (55-58 lies in 50-60
    # but does not cover it).
    assert gaps == {"bench.step": pytest.approx(64e-9)}


def test_recorded_chip_trace():
    """A few steps recorded on a TPU v5e (reduced form)."""
    path = os.path.join(HERE, "data", "v5e_trace.json")
    tr = json.load(open(path))
    tr["devices"] = {int(k): v for k, v in tr["devices"].items()}
    exp = tr.pop("expected")
    assert trace.busy_s(tr) == pytest.approx(exp["busy_s"], rel=1e-9)
    assert trace.idle_share(tr) == pytest.approx(exp["idle_share"],
                                                 rel=1e-9)
    assert 0 < trace.busy_s(tr) <= tr["window_s"]
    for d, ev in tr["devices"].items():
        assert trace.exposed_collective_ns(ev) <= trace.length(
            trace.collective_intervals(ev)) + 1e-9


def test_decode_mfu_reads_device_time_of_decode_steps():
    """The decode share's time is the chip's busy time inside the traced
    step spans less their admissions; its work is that of the last
    steps of the window, one per traced span."""
    from bench import harness, model, work
    sizes = model.Sizes({"hidden_size": 64, "num_attention_heads": 4,
                         "num_key_value_heads": 2, "intermediate_size": 128,
                         "vocab_size": 128, "num_hidden_layers": 2,
                         "rms_norm_eps": 1e-5, "rope_theta": 1e4,
                         "tie_word_embeddings": False,
                         "torch_dtype": "bfloat16"})
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    tr = small()
    tr["host"] = [[0, 50, "bench.step"], [10, 20, "bench.admit"],
                  [55, 100, "bench.step"]]
    # (t0, t1, admit s, active, keys, evicted); the first precedes the trace
    steps = [(0, 1, 0, 3, 30, 0), (0, 1, 0, 2, 20, 0), (0, 1, 0, 3, 40, 0)]
    run = {"trace": tr, "steps": steps, "work": work, "sizes": sizes,
           "peaks": peak}
    need = sum(work.roofline_s(*work.decode_step(sizes, a, k), peak)
               for a, k in ((2, 20), (3, 40)))
    # device 0 busy inside [0,50] u [55,100] less [10,20]:
    # [0,10] + [40,45] + [49,50] + [60,70] = 26 ns
    got = harness.metric_reader("serve.decode_mfu")(run)
    assert got == pytest.approx(100.0 * need / 26e-9)
    run["trace"] = dict(tr, host=[])
    assert harness.metric_reader("serve.decode_mfu")(run) is None
