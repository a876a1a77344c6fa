"""CPU tests of the cell ``train.fluid-f32.dynamic`` at a small fleet: a sound
run is correct, the bfloat16 control is refused, and each planted fault
is refused."""
import pytest

from bench import run
from bench.tests import cells

CELL = "train.fluid-f32.dynamic"


def test_sound_run_is_correct(jax_config_restored):
    res = cells.run_small(CELL, 2**31 + 12345)
    cells.assert_result_shape(res)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "intervals_per_s.fluid",
                                   "peak_hbm_mb"}


def test_control_is_refused(jax_config_restored):
    numbers, limits = cells.control_numbers(CELL, 77)
    _, ok = run.judge(numbers, limits)
    assert not ok, numbers


@pytest.mark.parametrize(
    "fault", ["unchanged_state", "half_batch", "no_merge"])
def test_fault_is_refused(fault, jax_config_restored):
    res = cells.faulted_run(CELL, fault, 31)
    assert res["correct"] is False, res["checks"]
