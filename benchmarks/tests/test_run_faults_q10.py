"""The Q10 cell's whole run (``--rehearse`` on the CPU at a tiny scale)
with the timed path broken underneath: an answer altered, fact rows left
out, a recovered attribute read from a neighbouring row
(``faults_q10.py``, which plants the same three on the chip). Each comes
out not agreeing; unbroken the run agrees through the DAG's ``gagg``
final, and the control (float32 sums in the program's place) does not."""

import pytest

import faults_q10
from test_run_faults import drive


def test_unbroken_run_agrees_and_control_does_not(capsys):
    line = drive(faults_q10.CELL, capsys, control=True)
    assert line["rehearsal"] and line["correct"] is False  # never true here
    assert line["rehearsal_agrees"], line["compared"]
    assert line["metrics"] == {}
    counts = line["rehearsal_counts"]
    assert set(counts["by_kind"]) == {"q10"} and counts["by_kind"]["q10"] >= 1
    assert counts["paths"]["last_mode"] == "gagg"
    assert "fold" in counts["paths"]["last_join_modes"].split(",")
    assert line["control"]["correct"] is False
    c = line["control"]["compared"]
    assert (c["sum_gap"]["value"] > c["sum_gap"]["limit"]
            or c["wrong_statements"]["value"] > 0)


@pytest.mark.parametrize("fault", list(faults_q10.FAULTS))
def test_a_planted_fault_fails(fault, capsys):
    with faults_q10.FAULTS[fault]():
        line = drive(faults_q10.CELL, capsys)
    assert line["rehearsal_agrees"] is False
    c = line["compared"]
    if fault == "altered":
        assert (c["sum_gap"]["value"] > 1e-7
                or c["wrong_statements"]["value"] > 0)
    elif fault == "neighbour_row":
        # the groups and their sums are all there: a text cell is not
        assert c["wrong_statements"]["value"] >= 1
        assert c["sum_gap"]["value"] <= c["sum_gap"]["limit"]
        assert any("text cell" in r for r in line["reasons"]), line["reasons"]
    else:
        assert (c["sum_gap"]["value"] > 1e-3
                or c["wrong_statements"]["value"] > 0)
