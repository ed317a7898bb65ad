"""The numbers the check compares, on readings counted by hand."""
import pytest

from bench import train


def test_train_compare_by_worst_leaf():
    prog = {"loss": [1.0, 2.0, 3.0], "grad": [1.0, 2.0, 3.0, 0.0],
            "change": [1.0, 1.0, 1.0, 1.0]}
    ref = {"loss": [1.0, 2.0, 3.1], "grad": [1.0, 2.0, 3.3, 0.0],
           "change": [1.0, 1.1, 1.0, 1.0]}
    got = train.compare(prog, ref)
    assert got["loss_gap"] == pytest.approx(0.1 / 3.1)
    # leaf 2: |3 - 3.3| / max(3.3, median 1.5)
    assert got["grad_gap"] == pytest.approx(0.3 / 3.3)
    # leaf 1: |1 - 1.1| / max(1.1, median 1.0); leaf 3 (zero gradient in
    # the reference) is left out of the change
    assert got["change_gap"] == pytest.approx(0.1 / 1.1)
    assert got["left_out"] == 1


def test_a_state_left_unchanged_reads_one():
    ref = {"loss": [5.0] * 3, "grad": [2.0, 3.0], "change": [0.5, 0.7]}
    prog = {"loss": [5.0] * 3, "grad": [0.0, 0.0], "change": [0.0, 0.0]}
    got = train.compare(prog, ref)
    assert got["grad_gap"] == pytest.approx(1.0)
    assert got["change_gap"] == pytest.approx(1.0)
