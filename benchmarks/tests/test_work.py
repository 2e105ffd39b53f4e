"""work.py's byte counts against a hand sum."""

import json
import os

import pytest

from harness import loader, traffic, work


def test_type_widths():
    assert work.type_bytes("bigint") == 8
    assert work.type_bytes("int") == 4
    assert work.type_bytes("date") == 4
    assert work.type_bytes("decimal(15,2)") == 8
    assert work.type_bytes("char(1)") == 1
    assert work.type_bytes("char(10)") == 10
    with pytest.raises(ValueError):
        work.type_bytes("jsonb")


def test_scan_bytes_by_hand():
    cfg = loader.read_config("tpch_sf10_1chip")
    mix = traffic.read_mix("scan_q1_q6")
    rows = {"lineitem": 60_000_000}
    # Q6: l_shipdate 4 + l_discount 8 + l_quantity 8 + l_extendedprice 8
    assert work.statement_bytes(
        cfg, rows, mix["statements"]["q6"]["reads"]
    ) == 60_000_000 * 28
    # Q1: two char(1) + four decimal(15,2) + date
    assert work.statement_bytes(
        cfg, rows, mix["statements"]["q1"]["reads"]
    ) == 60_000_000 * (1 + 1 + 8 * 4 + 4)


def test_join_and_flight1_bytes_by_hand():
    cfg = loader.read_config("tpch_sf10_1chip")
    q3 = traffic.read_mix("join_q3")["statements"]["q3"]["reads"]
    rows = {"lineitem": 60_000_000, "orders": 15_000_000,
            "customer": 1_500_000}
    assert work.statement_bytes(cfg, rows, q3) == (
        60_000_000 * (8 + 8 + 8 + 4) + 15_000_000 * (8 + 8 + 4 + 4)
        + 1_500_000 * (8 + 10)
    )
    cfg = loader.read_config("ssb_sf10_1chip")
    q13 = traffic.read_mix("flight1_q11_q12_q13")["statements"]["q13"]["reads"]
    rows = {"lineorder": 60_000_000, "dates": 2556}
    assert work.statement_bytes(cfg, rows, q13) == (
        60_000_000 * 16 + 2556 * 12
    )


def test_peaks_known_and_unknown():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_every_read_column_is_declared_and_loaded():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in bench["workloads"]:
        cfg = loader.read_config(cell["config"])
        mix = traffic.read_mix(cell["traffic"])
        for stmt in mix["statements"].values():
            for table, cols in stmt["reads"].items():
                loaded = {n for n, _t, ld in cfg["tables"][table]["columns"]
                          if ld}
                assert set(cols) <= loaded
