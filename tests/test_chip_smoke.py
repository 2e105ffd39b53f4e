"""chip_smoke.py's refusal to run without a chip, and where the compile
cache lives. All CPU-only; nothing here compiles a device program except
the one ``slow``-marked dry run.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, env_extra=None, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True,
        env=env, cwd=ROOT, timeout=timeout,
    )


def test_chip_smoke_refuses_without_tpu():
    """No automatic CPU path: exit non-zero, no result on stdout, and
    nothing built or loaded (the refusal precedes the engine import)."""
    r = _run(["chip_smoke.py"])
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr
    assert "coordinator up" not in r.stderr and "loaded" not in r.stderr


def test_chip_smoke_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo the script fails even past its device check."""
    with open(os.path.join(ROOT, "chip_smoke.py")) as f:
        (tmp_path / "chip_smoke.py").write_text(f.read())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run(
        [sys.executable, "chip_smoke.py", "--dry-run-cpu", "--rows", "1000"],
        capture_output=True, text=True, env=env, cwd=str(tmp_path),
        timeout=120,
    )
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "root of a checkout" in r.stderr


@pytest.mark.slow
def test_chip_smoke_dry_run_cpu():
    r = _run(["chip_smoke.py", "--dry-run-cpu", "--rows", "200000"],
             timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    final = json.loads(lines[-1])
    assert final["ok"] is True and final["dry_run"] is True
    assert final["device"]["platform"] == "cpu"
    record = json.loads(lines[-2])
    assert record["dry_run"] is True and record["platform"] == "cpu"
    assert all(s["correct"] for s in record["statements"])
    assert record["reduced"], "a cut of scale must be listed"


_CACHE_PROBE = (
    "import jax\n"
    "from opentenbase_tpu.engine import Cluster\n"
    "c = Cluster(num_datanodes=2, shard_groups=16)\n"
    "assert c.fused_executor() is not None\n"
    "print('CACHE_DIR=' + str(jax.config.jax_compilation_cache_dir))\n"
)


def _cache_dir(env_extra):
    r = _run(["-c", _CACHE_PROBE], env_extra)
    assert r.returncode == 0, r.stderr[-2000:]
    return [
        ln.split("=", 1)[1] for ln in r.stdout.splitlines()
        if ln.startswith("CACHE_DIR=")
    ][0]


def test_compile_cache_placed_from_outside(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the cache lives there and the code
    sets no other."""
    want = str(tmp_path / "cache")
    assert _cache_dir({"JAX_COMPILATION_CACHE_DIR": want}) == want


def test_compile_cache_default_is_in_checkout():
    """Unset: a fixed, git-ignored directory derived from the package
    path — never a temp name, pid or time."""
    assert _cache_dir({}) == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("knob", [
    "OTB_" + "COMPILE_CACHE_DIR",
    "OTB_" + "EXCHANGE_HBM_BUDGET",
    "OTB_" + "SCAN_HBM_BUDGET",
    "OTB_" + "RADIX_HBM_BUDGET",
    "OTB_" + "DAG_WINDOW_BUDGET",
    "OTB_" + "DIMFOLD_MAX",
    "enable_" + "pallas_join",
])
def test_private_cache_knob_is_gone(knob):
    """A deleted knob is read nowhere: the compile cache's private
    directory, the five budgets and limits the process environment
    used to carry, and the GUC nobody set."""
    hits = []
    for top in ("opentenbase_tpu", "tools", "chip_smoke.py",
                "__graft_entry__.py"):
        path = os.path.join(ROOT, top)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs
            if f.endswith((".py", ".sh"))
        ]
        for fp in files:
            with open(fp, errors="replace") as f:
                if knob in f.read():
                    hits.append(fp)
    assert hits == []
