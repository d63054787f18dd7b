"""A run with its timed path broken underneath comes out as not correct:
once for each fault the cell can have (one chip, so no exchange between
chips), and a sound run comes out correct."""
import pytest

from _runs import REFRESH_CELL, SERVE_CELL, failed_checks, rehearse

CASES = [
    (REFRESH_CELL, "frozen", "move_gap"),         # a step that returns its state
    (REFRESH_CELL, "half", "stat_gap"),           # half the round's sections
    (REFRESH_CELL, "altered_draw", "move_gap"),   # a draw altered where produced
    (SERVE_CELL, "frozen", "chain_decision_flips"),
    (SERVE_CELL, "half", "predictive_gap"),       # the mean over half the draws
    (SERVE_CELL, "altered_draw", "chain_move_gap"),
    (SERVE_CELL, "altered_answer", "predictive_gap"),
]


@pytest.mark.parametrize("cell,fault,caught_by", CASES)
def test_broken_timed_path_is_not_correct(cell, fault, caught_by):
    result = rehearse(cell, fault)
    assert result["correct"] is False
    assert caught_by in failed_checks(result)


@pytest.mark.parametrize("cell", [REFRESH_CELL, SERVE_CELL])
def test_sound_run_is_correct(cell):
    result = rehearse(cell)
    assert result["correct"] is True, result["checks"]
    assert result["metrics"] == {}  # rehearsal numbers are never metrics
    assert result["device"]["platform"] == "cpu"
