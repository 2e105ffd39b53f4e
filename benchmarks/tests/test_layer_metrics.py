"""Each per-layer metric's reader against a hand sum over a made-up
window; a reader with nothing to read returns None, never 0."""

import json
import os

import pytest

from harness import layer_metrics

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..",
                     "BENCHMARK.json")

CTX = {
    # 10 statements: 1000 ms on the client, 900 in the session, of which
    # 700 round the device call, 50 merging; 600 ms of device busy time
    "delta": {"calls": 10, "total_ms": 900.0, "parse_ms": 5.0, "plan_ms": 10.0,
              "queue_ms": 1.0, "device_ms": 700.0, "host_ms": 50.0,
              "h2d_bytes": 4096, "compile_ms": 0.0},
    "client_ms": [100.0] * 10,
    "trace": {"busy_s": 0.6, "window_s": 1.0, "statements_traced": 10,
              "programs": {"jit_program(ab12)": 0.5, "jit_other": 0.1}},
    "peaks": {"hbm_bytes_per_s": 1e9},
    "work_bytes": 1e7,  # 0.01 s at the peak
    "setup": {"warm_s_per_further_set": 1.75},
}

WANT = {
    "wire_ms_per_stmt": 100.0 - 90.0,
    "plan_ms_per_stmt": 1.6,
    "dispatch_ms_per_stmt": 70.0 - 60.0,
    "h2d_bytes_per_stmt": 409.6,
    "device_ms_per_stmt": 60.0,
    "scan_roofline": 100.0 * 0.01 / 0.5,
    "join_roofline": 100.0 * 0.01 / 0.5,
    "host_ms_per_stmt": 5.0,
    "device_idle_pct": 40.0,
    "warm_s_per_param_set": 1.75,
}


def names():
    with open(BENCH) as f:
        return [m["name"] for m in json.load(f)["per_layer"]]


def test_every_metric_of_the_benchmark_has_a_hand_sum():
    assert sorted(names()) == sorted(WANT)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_against_the_hand_sum(name):
    spec = layer_metrics.read_metric(name)
    assert layer_metrics.evaluate(spec, CTX) == pytest.approx(WANT[name])
    with open(BENCH) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == name)
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key]


@pytest.mark.parametrize("name", sorted(WANT))
def test_nothing_to_read_is_none(name):
    empty = {"delta": {}, "client_ms": [], "trace": None, "peaks": CTX["peaks"],
             "work_bytes": 0, "setup": {}}
    assert layer_metrics.evaluate(layer_metrics.read_metric(name), empty) is None
