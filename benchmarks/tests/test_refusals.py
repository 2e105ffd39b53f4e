"""What makes a run fail rather than report: no TPU, a timed statement
answered by the host executor, a compilation inside the window."""

import argparse

import pytest

import run as bench_run
from harness import client_loop, loader, views

CELL = "tpch_sf10_1chip.scan"


def rehearse(**over):
    import jax

    args = argparse.Namespace(
        workload=CELL, seed=2_147_483_901, seconds=0.5, trace=0,
        rehearse=20_000, control=False,
    )
    vars(args).update(over)
    bench = bench_run.read_benchmark()
    cell = bench_run.find(bench["workloads"], CELL, "workload")
    return bench_run.run(args, jax, jax.devices(), cell, bench)


def test_no_tpu_no_result(capsys):
    rc = bench_run.main(
        ["--workload", CELL, "--seed", "7", "--seconds", "1", "--trace", "0"]
    )
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "TPU" in out.err


def test_an_unknown_cell_is_refused():
    with pytest.raises(SystemExit):
        bench_run.main(
            ["--workload", "no_such.cell", "--seed", "7", "--seconds", "1"]
        )


def test_host_executor_answering_fails_the_run(monkeypatch):
    real = loader.Deployment.load

    def load_then_host_only(self, data):
        real(self, data)
        self.sql("set enable_fused_execution = off")

    monkeypatch.setattr(loader.Deployment, "load", load_then_host_only)
    with pytest.raises(views.HostAnswered):
        rehearse()


def test_a_compilation_inside_the_window_fails_the_run(monkeypatch):
    real = client_loop.closed_loop

    def compiles_first(*a, **kw):
        import jax
        import jax.numpy as jnp

        jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
        return real(*a, **kw)

    monkeypatch.setattr(client_loop, "closed_loop", compiles_first)
    with pytest.raises(bench_run.RunFailure, match="compiled inside"):
        rehearse()


def fused(n, **more):
    f = {"fused_statements": [str(n)], "platform_demotions": ["0"],
         "last_run_platform": ["tpu"]}
    f.update(more)
    return {"fused": f, "pallas": [("p", "compiled")],
            "health": [("cn0", "coordinator", "tpu")]}


def test_check_window_by_the_views():
    views.check_window(fused(3), fused(13), 10, "tpu")
    with pytest.raises(views.HostAnswered, match="fused_statements"):
        views.check_window(fused(3), fused(12), 10, "tpu")
    with pytest.raises(views.HostAnswered, match="demotion"):
        views.check_window(fused(3), fused(13, demoted=["q"]), 10, "tpu")
    with pytest.raises(views.HostAnswered, match="platform_demotions"):
        views.check_window(
            fused(3), fused(13, platform_demotions=["1"]), 10, "tpu"
        )
    with pytest.raises(views.HostAnswered, match="unsupported"):
        views.check_window(
            fused(3), fused(13, unsupported=["trivial scan", "x"]), 10, "tpu"
        )
    with pytest.raises(views.HostAnswered, match="last_run_platform"):
        views.check_window(fused(3), fused(13), 10, "cpu")
    bad = fused(13)
    bad["pallas"] = [("p", "demoted")]
    with pytest.raises(views.HostAnswered, match="pallas"):
        views.check_window(fused(3), bad, 10, "tpu")
