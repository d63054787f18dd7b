"""The control, the nearest precision below the configuration's float32,
has to come out as not correct (at the rehearsal size, on the CPU; the
chip readings at the cells' own sizes are in PERF.md)."""
import pytest

from _runs import REFRESH_CELL, SERVE_CELL, failed_checks, rehearse


@pytest.mark.parametrize("cell,caught_by", [
    (REFRESH_CELL, "stat_gap"),        # the program's bfloat16 data path
    (SERVE_CELL, "predictive_gap"),    # bfloat16 functionals in the evaluator's place
])
def test_control_is_not_correct(cell, caught_by):
    result = rehearse(cell, "control")
    assert result["correct"] is False
    assert caught_by in failed_checks(result)
