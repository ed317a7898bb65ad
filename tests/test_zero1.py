"""ZeRO-1 integration: the paper's collectives driving gradient sync must
reproduce single-device AdamW training exactly (subprocess, 8 fake devices).

Checks (in tests/_zero1_checks.py): per-impl loss-trajectory equality,
int8-compressed training, optimizer-state sharding 1/world, the
train-step HLO containing the 2*ceil(log2 p) collective-permutes of
Theorem 2, and bucketed (bucket_bytes) sync: f32 bitwise-equal to
unbucketed, int8+EF within the wire tolerance.

Device-free here: the bucket partitioner's edge cases and the
GradSyncConfig validation of ``bucket_bytes``."""
import os
import subprocess
import sys

import pytest

from repro.optim.zero1 import GradSyncConfig, plan_grad_buckets

HERE = os.path.dirname(os.path.abspath(__file__))


def _coverage(buckets):
    """leaf -> ordered [lo, hi) segments, in bucket order."""
    cov = {}
    for b in buckets:
        for (li, lo, hi) in b:
            cov.setdefault(li, []).append((lo, hi))
    return cov


def _assert_exact_cover(buckets, shapes, world):
    cov = _coverage(buckets)
    for li, shape in enumerate(shapes):
        rows = (shape[0] + (-shape[0]) % world) // world
        segs = cov.get(li, [])
        assert segs, f"leaf {li} not covered"
        assert segs[0][0] == 0 and segs[-1][1] == rows
        for (_, hi), (lo2, _) in zip(segs, segs[1:]):
            assert hi == lo2, f"gap/overlap in leaf {li}: {segs}"
        assert all(lo < hi for lo, hi in segs)


def test_partitioner_tiny_param_smaller_than_one_block():
    # ld=3 < world=8: pads to one shard row per rank — a single segment.
    buckets = plan_grad_buckets([(3, 16)], 8, 1 << 20, 4)
    assert buckets == [[(0, 0, 1)]]


def test_partitioner_boundary_splits_a_param():
    # One 64-row leaf, bucket target = half its bytes: the leaf must be
    # split across >= 2 buckets with contiguous, disjoint segments.
    shapes = [(64, 32)]
    world = 4
    total = 64 * 32 * 4
    buckets = plan_grad_buckets(shapes, world, total // 2, 4)
    assert len(buckets) >= 2
    assert all(li == 0 for b in buckets for (li, _, _) in b)
    _assert_exact_cover(buckets, shapes, world)


def test_partitioner_multi_leaf_exact_cover():
    shapes = [(10, 4), (3, 8), (64, 2), (7,), (128, 3)]
    for world in (4, 6, 8):  # incl. non-power-of-two
        for bb in (64, 600, 1 << 12, 1 << 30):
            buckets = plan_grad_buckets(shapes, world, bb, 4)
            _assert_exact_cover(buckets, shapes, world)
            assert all(b for b in buckets), "empty bucket"


def test_partitioner_row_larger_than_bucket_gets_own_bucket():
    # One shard row = 1024*4*4 bytes >> bucket_bytes: every bucket is a
    # single one-row segment; never an empty bucket, never starvation.
    buckets = plan_grad_buckets([(8, 1024)], 4, 64, 4)
    assert all(len(b) == 1 and b[0][2] - b[0][1] == 1 for b in buckets)
    _assert_exact_cover(buckets, [(8, 1024)], 4)


def test_partitioner_single_bucket_when_target_huge():
    shapes = [(16, 8), (32, 4)]
    buckets = plan_grad_buckets(shapes, 4, 1 << 40, 4)
    assert len(buckets) == 1
    _assert_exact_cover(buckets, shapes, 4)


def test_partitioner_rejects_nonpositive_target():
    with pytest.raises(ValueError, match="positive"):
        plan_grad_buckets([(8, 8)], 4, 0, 4)


def test_config_validates_bucket_bytes():
    GradSyncConfig(bucket_bytes=None)          # default: off
    GradSyncConfig(bucket_bytes=1 << 20)       # circulant: ok
    with pytest.raises(ValueError, match="positive"):
        GradSyncConfig(bucket_bytes=-1)
    with pytest.raises(ValueError, match="circulant"):
        GradSyncConfig(impl="ring", bucket_bytes=1 << 20)


def test_zero1_end_to_end():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_zero1_checks.py")],
        capture_output=True, text=True, timeout=1200, env=env)
    if proc.returncode != 0:
        raise AssertionError(
            f"zero1 checks failed:\n--- stdout ---\n{proc.stdout}\n"
            f"--- stderr ---\n{proc.stderr}")
    assert "ALL ZERO1 CHECKS PASSED" in proc.stdout


@pytest.mark.parametrize("fused,auto_axes,platform,want", [
    (None, True, "tpu", False),    # Auto model axis on a TPU: jnp rounds
    (False, True, "tpu", False),
    (None, False, "tpu", None),    # fully manual: auto selection stands
    (True, False, "tpu", True),
    (None, True, "cpu", None),     # interpret mode partitions anywhere
    (True, True, "cpu", True),
])
def test_zero1_sync_fused_selection(fused, auto_axes, platform, want):
    from repro.train.steps import resolve_zero1_sync
    sync = resolve_zero1_sync(GradSyncConfig(use_fused_kernel=fused),
                              auto_axes=auto_axes, platform=platform)
    assert sync.use_fused_kernel is want


def test_zero1_sync_refuses_fused_kernel_over_auto_axis_on_tpu():
    from repro.train.steps import resolve_zero1_sync
    with pytest.raises(ValueError, match="Mosaic kernels cannot be"):
        resolve_zero1_sync(GradSyncConfig(use_fused_kernel=True),
                           auto_axes=True, platform="tpu")
