"""The four-chip cell's whole run on four virtual CPU devices
(``--rehearse`` at a tiny scale, SF30's estimates put in after the load
so the planner cuts the plan SF30 gets): unbroken it agrees with the
reference through two redistributes; with one destination's buckets of
the first exchange left out (``faults_mesh.py``, which plants the same
on the chips) the comparison comes out false.

Needs four devices: ``XLA_FLAGS=--xla_force_host_platform_device_count=4
JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_run_faults_mesh.py``
(with fewer it skips)."""

import argparse

import pytest

import faults_mesh
import run as bench_run
from harness import loader

CELL = "tpch_sf30_4chip.join"
SF30 = {"customer": 4_500_000, "orders": 45_000_000,
        "lineitem": 180_000_000}


@pytest.fixture
def four_devices(monkeypatch):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    real = loader.Deployment.load

    def load_with_sf30_estimates(self, data):
        real(self, data)
        for table, rows in SF30.items():
            self.cluster.catalog.get(table).stats["rows"] = rows

    monkeypatch.setattr(loader.Deployment, "load", load_with_sf30_estimates)
    return jax.devices()[:4]


def drive(devices) -> dict:
    import jax

    args = argparse.Namespace(
        workload=CELL, seed=2_147_483_777, seconds=1.0, trace=0,
        rehearse=60_000, control=False,
    )
    bench = bench_run.read_benchmark()
    cell = bench_run.find(bench["workloads"], CELL, "workload")
    assert cell["chips"] == 4
    return bench_run.run(args, jax, devices, cell, bench)


def test_unbroken_mesh_run_agrees(four_devices):
    line = drive(four_devices)
    assert line["rehearsal"] and line["correct"] is False  # never true here
    assert line["rehearsal_agrees"], line["compared"]
    paths = line["rehearsal_counts"]["paths"]
    assert paths["last_mode"] == "gsort"


def test_a_bucket_left_out_of_an_exchange_fails(four_devices):
    with faults_mesh.bucket_left_out():
        line = drive(four_devices)
    assert line["rehearsal_agrees"] is False
    c = line["compared"]
    assert c["wrong_statements"]["value"] > 0 or c["sum_gap"]["value"] > 1e-3
