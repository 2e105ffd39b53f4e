"""trace_reduce.py on a small recorded trace (``trace_small.json``: the
first 120 device events of each line of a traced scan run on one v5e,
PR 26, with all of the benchmark's own spans), and on a hand-made one."""

import json
import os

import pytest

from harness import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_and_clip():
    assert trace_reduce.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [
        [0, 3], [5, 8]
    ]
    assert trace_reduce.clip([[0, 3], [5, 8]], 2, 6) == [[2, 3], [5, 6]]


def hand_trace():
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_program(1)", 110.0, 40.0], ["jit_program(2)", 300.0, 100.0],
            ["jit_other", 900.0, 50.0],  # outside the window
        ]},
        {"name": "XLA Ops", "events": [
            ["%a", 110.0, 20.0], ["%b", 125.0, 25.0],  # overlap: 110..150
            ["%a", 300.0, 100.0], ["%c", 900.0, 50.0],
        ]},
    ]}
    core2 = {"name": "/device:TPU:0 SparseCore 0", "lines": [
        {"name": "XLA Ops", "events": [["%ignored", 0.0, 1000.0]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ["bench:window", 100.0, 400.0],
        ["bench:stmt:q6", 100.0, 100.0], ["bench:stmt:q1", 250.0, 200.0],
    ]}]}
    return {"planes": [dev, core2, host]}


def test_hand_made_trace():
    r = trace_reduce.reduce(hand_trace())
    assert r["chips"] == 1 and r["statements_traced"] == 2
    assert r["window_s"] == pytest.approx(400e-9)
    assert r["busy_s"] == pytest.approx(140e-9)  # 110..150 and 300..400
    assert r["programs"] == {
        "jit_program(1)": pytest.approx(40e-9),
        "jit_program(2)": pytest.approx(100e-9),
    }
    assert dict(map(tuple, r["device_ops"])) == {
        "%a": pytest.approx(120e-9), "%b": pytest.approx(25e-9),
    }
    # idle: 100..110 (inside q6), 150..300 (its middle, 225, lies between
    # the statements), 400..500 (inside q1... no: q1 ends at 450)
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["inside stmt:q6"] == pytest.approx(10e-9)
    assert gaps["between statements"] == pytest.approx(150e-9)
    assert gaps["inside stmt:q1"] == pytest.approx(100e-9)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_recorded_trace():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        r = trace_reduce.reduce(json.load(f))
    assert r["chips"] == 1 and r["statements_traced"] == 10
    assert r["window_s"] == pytest.approx(9.115945111)
    # the ten programs of the recorded window, whole (the Modules line is
    # short); the Q6 Pallas programs took 35.1 ms each on the device
    assert len(r["programs"]) == 8
    assert min(r["programs"].values()) == pytest.approx(0.0351, rel=0.01)
    assert 0 < r["busy_s"] < sum(r["programs"].values())
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_no_window_span_is_an_error():
    t = hand_trace()
    t["planes"][2]["lines"][0]["events"].pop(0)
    with pytest.raises(ValueError):
        trace_reduce.reduce(t)
