"""The Q5 cell's whole run (``--rehearse`` on the CPU at a tiny scale)
with the timed path broken underneath: an answer altered, fact rows left
out, the second key pair of the customer join dropped (``faults_q5.py``,
which plants the same three on the chip). Each comes out not agreeing;
unbroken the run agrees through the DAG's ``grouped`` final with the
customer join folded, and the control (float32 sums in the program's
place) does not."""

import pytest

import faults_q5
from test_run_faults import drive


def test_unbroken_run_agrees_and_control_does_not(capsys):
    line = drive(faults_q5.CELL, capsys, control=True)
    assert line["rehearsal"] and line["correct"] is False  # never true here
    assert line["rehearsal_agrees"], line["compared"]
    assert line["metrics"] == {}
    counts = line["rehearsal_counts"]
    assert set(counts["by_kind"]) == {"q5"} and counts["by_kind"]["q5"] >= 1
    assert counts["paths"]["last_mode"] == "grouped"
    assert "fold" in counts["paths"]["last_join_modes"].split(",")
    assert line["control"]["correct"] is False
    c = line["control"]["compared"]
    assert c["wrong_statements"]["value"] == 0
    assert c["sum_gap"]["value"] > c["sum_gap"]["limit"]


@pytest.mark.parametrize("fault", list(faults_q5.FAULTS))
def test_a_planted_fault_fails(fault, capsys):
    with faults_q5.FAULTS[fault]():
        line = drive(faults_q5.CELL, capsys)
    assert line["rehearsal_agrees"] is False
    c = line["compared"]
    if fault == "altered":
        assert c["sum_gap"]["value"] > 1e-7
    elif fault == "second_pair_dropped":
        # every nation of the region is there, with several times its sum
        assert (c["sum_gap"]["value"] > 1.0
                or c["wrong_statements"]["value"] > 0)
    else:
        assert (c["sum_gap"]["value"] > 1e-3
                or c["wrong_statements"]["value"] > 0)
