"""Drive the whole of a run (the look for a chip skipped: ``--rehearse``
on the CPU at a tiny scale) with the timed path broken underneath, and
see the comparison come out false; unbroken, it agrees. The faults a
one-chip query cell can have: an answer altered where it is produced,
and part of the data left out of what the statements read
(``faults.py``, which plants the same two on the chip).

The control (the reference with float32 sums and bfloat16 averages put
in the program's place) must come out not correct too."""

import argparse
import json

import pytest

import faults
import run as bench_run


def drive(workload: str, capsys, control: bool = False) -> dict:
    import jax

    args = argparse.Namespace(
        workload=workload, seed=2_147_483_777, seconds=1.0, trace=0,
        rehearse=30_000, control=control,
    )
    bench = bench_run.read_benchmark()
    cell = bench_run.find(bench["workloads"], workload, "workload")
    line = bench_run.run(args, jax, jax.devices(), cell, bench)
    out = capsys.readouterr().out
    controls = [json.loads(ln)["control"] for ln in out.splitlines()
                if ln.startswith('{"control"')]
    line["control"] = controls[0] if controls else None
    return line


CELLS = ["tpch_sf10_1chip.scan", "tpch_sf10_1chip.join",
         "ssb_sf10_1chip.flight1"]


@pytest.mark.parametrize("workload", CELLS)
def test_unbroken_run_agrees_and_control_does_not(workload, capsys):
    line = drive(workload, capsys, control=True)
    assert line["rehearsal"] and line["correct"] is False  # never true here
    assert line["rehearsal_agrees"], line["compared"]
    assert line["metrics"] == {}
    assert line["control"]["correct"] is False
    c = line["control"]["compared"]
    assert c["sum_gap"]["value"] > c["sum_gap"]["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_an_answer_altered_where_it_is_produced_fails(workload, capsys):
    with faults.altered(faults.FACT[workload]):
        line = drive(workload, capsys)
    assert line["rehearsal_agrees"] is False
    worst = max(line["compared"]["sum_gap"]["value"],
                line["compared"]["avg_gap"]["value"])
    assert worst > 1e-7


@pytest.mark.parametrize("workload", CELLS)
def test_rows_left_out_fail(workload, capsys):
    with faults.left_out(faults.FACT[workload]):
        line = drive(workload, capsys)
    assert line["rehearsal_agrees"] is False
    c = line["compared"]
    assert c["sum_gap"]["value"] > 1e-3 or c["wrong_statements"]["value"] > 0
