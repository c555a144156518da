"""``correct`` on the CPU at a tiny size: a sound run of every cell passes."""

import numpy as np
import pytest

from bench import correct
from bench.tests.runs import run
from bench.tests.tiny import cell_names, tiny_cell


@pytest.mark.parametrize("name", cell_names())
def test_sound_run_is_correct(name):
    r = run(tiny_cell(name))
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r["checks"])[:4] == list(correct.NUMBERS)


def _delta(*rows):
    return [np.asarray(r, np.float32) for r in rows]


def test_gaps_treat_non_finite_readings_as_failing():
    ref = {"loss": [2.0, 2.0], "grad": [1.0, 2.0, 0.0], "grad_raw": [1.0, 2.0, 1e-9], "change": [1.0, 1.0, 0.0],
           "delta": _delta([1, 0], [0, 1], [0, 0])}
    nan = float("nan")
    prog = {"loss": [2.0, nan], "grad": [1.0, 2.0, 5.0], "change": [1.0, nan, 7.0],
            "delta": _delta([1, 0], [0, nan], [7, 0])}
    nums = correct.numbers(prog, ref)
    assert nums["loss_gap"] == float("inf") and nums["change_gap"] == float("inf")
    assert nums["direction_gap"] == float("inf")
    assert nums["grad_gap"] == 0.0  # the third leaf's reference gradient is zero to rounding: left out
    ok, checks = correct.verdict(nums, dict.fromkeys(correct.NUMBERS, 1.0))
    assert not ok and checks["grad_gap"]["value"] == 0.0


def test_direction_gap_sees_a_turned_step_of_the_same_norm():
    ref = {"loss": [2.0], "grad": [1.0, 1.0], "grad_raw": [1.0, 1.0], "change": [1.0, 1.0],
           "delta": _delta([1, 0, 0], [0, 1, 0])}
    prog = dict(ref, delta=_delta([1, 0, 0], [0, 0.6, 0.8]))
    nums = correct.numbers(prog, ref)
    assert nums["loss_gap"] == nums["grad_gap"] == nums["change_gap"] == 0.0
    assert nums["direction_gap"] == pytest.approx(0.4)
