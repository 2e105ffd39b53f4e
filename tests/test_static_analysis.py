"""otb_lint + lockwatch: every checker must catch the historical bug
that motivated it, seeded back into a copy of the real tree.

The five seeds mirror the incidents in ISSUE 8 / the analysis package
docstring: an unread GUC (log_min_messages, PR 5), a removed jax API
(``enable_x64``, PR 3), close-without-shutdown (PR 3), a socket-I/O function with no
FAULT site (PR 4's thesis), and an int32 cumsum offset (PR 6). Each
test copies the package, applies one seed, and asserts ``otb_lint
--check`` against the COMMITTED baseline goes red.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

import opentenbase_tpu
from opentenbase_tpu.cli.otb_lint import main as lint_main

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.abspath(opentenbase_tpu.__file__))
)
BASELINE = os.path.join(REPO_ROOT, "tools", "lint_baseline.json")


def _copy_tree(tmp_path) -> str:
    """Copy the real package + committed baseline into tmp_path so a
    seed never touches the working tree."""
    root = str(tmp_path / "repo")
    shutil.copytree(
        os.path.join(REPO_ROOT, "opentenbase_tpu"),
        os.path.join(root, "opentenbase_tpu"),
        ignore=shutil.ignore_patterns("__pycache__", "*.pyc"),
    )
    os.makedirs(os.path.join(root, "tools"))
    shutil.copy(BASELINE, os.path.join(root, "tools", "lint_baseline.json"))
    return root


def _check(root: str) -> int:
    return lint_main([
        "--root", root,
        "--baseline", os.path.join(root, "tools", "lint_baseline.json"),
        "--check",
    ])


def _append(root: str, rel: str, code: str) -> None:
    with open(os.path.join(root, rel), "a", encoding="utf-8") as f:
        f.write("\n" + code + "\n")


# ---------------------------------------------------------------------------
# the committed tree is green
# ---------------------------------------------------------------------------


def test_shipped_tree_is_green(tmp_path, capsys):
    root = _copy_tree(tmp_path)
    assert _check(root) == 0
    out = capsys.readouterr().out
    verdict = json.loads(out.strip().splitlines()[-1])
    assert verdict["lint_gate"] == "ok"
    assert verdict["new"] == 0


# ---------------------------------------------------------------------------
# the five historical bug classes, seeded back
# ---------------------------------------------------------------------------


def test_seed_unread_guc_fails(tmp_path, capsys):
    """The log_min_messages class: registered, validated, never read."""
    root = _copy_tree(tmp_path)
    cfg = os.path.join(root, "opentenbase_tpu", "config.py")
    with open(cfg) as f:
        src = f.read()
    src = src.replace(
        '    "enable_fused_execution": (_bool, True),',
        '    "enable_fused_execution": (_bool, True),\n'
        '    "lint_seed_knob": (_bool, False),',
    )
    with open(cfg, "w") as f:
        f.write(src)
    assert _check(root) != 0
    assert "guc-unread" in capsys.readouterr().out


def test_seed_jax_enable_x64_fails(tmp_path, capsys):
    """The silent-Pallas-demotion class: a removed jax API, unguarded."""
    root = _copy_tree(tmp_path)
    _append(root, "opentenbase_tpu/ops/sort.py",
            "_lint_seed_x64 = jax.experimental.enable_x64")
    assert _check(root) != 0
    assert "deprecated-api" in capsys.readouterr().out


def test_seed_close_without_shutdown_fails(tmp_path, capsys):
    """The 155 s-teardown class: close() with no shutdown() in stop."""
    root = _copy_tree(tmp_path)
    _append(root, "opentenbase_tpu/net/pool.py", (
        "class _LintSeedServer:\n"
        "    def stop(self):\n"
        "        self._lsock.close()\n"
    ))
    assert _check(root) != 0
    assert "socket-shutdown" in capsys.readouterr().out


def test_seed_faultless_io_function_fails(tmp_path, capsys):
    """PR 4's thesis: a new distributed boundary with no FAULT site
    cannot be chaos-tested."""
    root = _copy_tree(tmp_path)
    _append(root, "opentenbase_tpu/net/server.py", (
        "def _lint_seed_push(sock, data):\n"
        "    sock.sendall(data)\n"
    ))
    assert _check(root) != 0
    assert "fault-missing" in capsys.readouterr().out


def test_seed_int32_cumsum_fails(tmp_path, capsys):
    """The emit_pairs overflow: int32 prefix sum feeding offsets."""
    root = _copy_tree(tmp_path)
    _append(root, "opentenbase_tpu/ops/join.py", (
        "def _lint_seed_offsets(counts):\n"
        "    offsets = jnp.cumsum(counts.astype(jnp.int32))\n"
        "    return offsets\n"
    ))
    assert _check(root) != 0
    assert "int32-width" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# baseline ratchet round-trip
# ---------------------------------------------------------------------------


def test_baseline_round_trip(tmp_path, capsys):
    """clean -> violation added -> stage fails -> --update-baseline ->
    passes. The deliberate-regeneration escape hatch works, and ONLY
    deliberately."""
    root = _copy_tree(tmp_path)
    baseline = os.path.join(root, "tools", "lint_baseline.json")
    assert _check(root) == 0
    _append(root, "opentenbase_tpu/net/server.py", (
        "def _lint_seed_rt(sock, data):\n"
        "    sock.sendall(data)\n"
    ))
    assert _check(root) == 1  # new finding: red
    capsys.readouterr()
    assert lint_main(["--root", root, "--baseline", baseline,
                      "--update-baseline"]) == 0
    assert _check(root) == 0  # blessed: green again
    # and burning the seed back OUT leaves a 'fixed' hint, still green
    with open(os.path.join(root, "opentenbase_tpu/net/server.py")) as f:
        src = f.read()
    with open(os.path.join(root, "opentenbase_tpu/net/server.py"),
              "w") as f:
        f.write(src.replace("def _lint_seed_rt(sock, data):\n"
                            "    sock.sendall(data)\n", ""))
    capsys.readouterr()
    assert _check(root) == 0
    assert "fixed" in capsys.readouterr().out


def test_baseline_key_survives_line_drift(tmp_path):
    """Keys carry no line numbers: prepending code to a module must
    not turn baselined findings into 'new' ones."""
    root = _copy_tree(tmp_path)
    path = os.path.join(root, "opentenbase_tpu", "net", "server.py")
    with open(path) as f:
        src = f.read()
    # shift every line down by ten
    with open(path, "w") as f:
        f.write('"""doc"""\n' + "\n" * 9 + src)
    assert _check(root) == 0


# ---------------------------------------------------------------------------
# pragma handling
# ---------------------------------------------------------------------------


def test_pragma_with_reason_suppresses(tmp_path):
    root = _copy_tree(tmp_path)
    _append(root, "opentenbase_tpu/ops/sort.py", (
        "_lint_seed_x64 = jax.experimental.enable_x64"
        "  # otb_lint: ignore[deprecated-api] -- seeded for the test\n"
    ))
    assert _check(root) == 0


def test_pragma_without_reason_rejected(tmp_path, capsys):
    """A bare mute is itself a violation — and one that can never be
    baselined away."""
    root = _copy_tree(tmp_path)
    baseline = os.path.join(root, "tools", "lint_baseline.json")
    _append(root, "opentenbase_tpu/ops/sort.py", (
        "_lint_seed_x64 = jax.experimental.enable_x64"
        "  # otb_lint: ignore[deprecated-api]\n"
    ))
    assert _check(root) != 0
    assert "pragma-missing-reason" in capsys.readouterr().out
    # --update-baseline refuses to bless it
    lint_main(["--root", root, "--baseline", baseline,
               "--update-baseline"])
    with open(baseline) as f:
        doc = json.load(f)
    assert not any("pragma-missing-reason" in k for k in doc["findings"])
    assert _check(root) != 0  # still red after regeneration


def test_pragma_unused_flagged(tmp_path):
    """A pragma whose finding no longer fires is rot — flagged so a
    fixed violation takes its mute with it."""
    root = _copy_tree(tmp_path)
    _append(root, "opentenbase_tpu/ops/sort.py", (
        "_fine = 1  # otb_lint: ignore[deprecated-api] -- nothing here\n"
    ))
    assert _check(root) != 0


def test_pragma_previous_line_covers(tmp_path):
    root = _copy_tree(tmp_path)
    _append(root, "opentenbase_tpu/ops/sort.py", (
        "# otb_lint: ignore[deprecated-api] -- seeded; pragma sits on "
        "the line above\n"
        "_lint_seed_x64 = jax.experimental.enable_x64\n"
    ))
    assert _check(root) == 0


# ---------------------------------------------------------------------------
# individual checker units (synthetic mini-trees)
# ---------------------------------------------------------------------------


def _mini_project(tmp_path, files: dict):
    """Build opentenbase_tpu/<rel> -> source mini-tree; returns a
    Project over it."""
    from opentenbase_tpu.analysis.core import Project

    root = tmp_path / "mini"
    for rel, src in files.items():
        p = root / "opentenbase_tpu" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return Project(str(root))


def _run_rules(project, rule_prefix):
    from opentenbase_tpu.analysis import all_checkers
    from opentenbase_tpu.analysis.core import run_checkers

    active, suppressed = run_checkers(project, all_checkers())
    return [f for f in active if f.rule.startswith(rule_prefix)]


def test_guc_unregistered_read(tmp_path):
    p = _mini_project(tmp_path, {
        "config.py": 'GUCS = {"real_knob": (int, 1)}\n',
        "engine.py": (
            "class S:\n"
            "    def f(self):\n"
            '        a = self.gucs.get("real_knob", 1)\n'
            '        b = self.gucs.get("typo_knob", 1)\n'
            '        c = self.gucs.get("ext.custom", 1)\n'
        ),
    })
    found = _run_rules(p, "guc-unregistered")
    assert [f.ident for f in found] == ["typo_knob"]


def test_except_swallow_honest_paths_pass(tmp_path):
    p = _mini_project(tmp_path, {
        "net/x.py": (
            "def risky(ch):\n"
            "    try:\n"
            "        ch.send(1)\n"
            "    except Exception:\n"
            "        pass\n"
            "def honest_mark(ch):\n"
            "    try:\n"
            "        ch.send(1)\n"
            "    except Exception:\n"
            "        ch.broken = True\n"
            "def honest_raise(ch):\n"
            "    try:\n"
            "        ch.send(1)\n"
            "    except Exception:\n"
            "        raise\n"
            "def narrow(ch):\n"
            "    try:\n"
            "        ch.send(1)\n"
            "    except OSError:\n"
            "        pass\n"
        ),
    })
    found = _run_rules(p, "except-swallow")
    assert [f.ident for f in found] == ["risky:1"]


def test_wire_op_unhandled(tmp_path):
    p = _mini_project(tmp_path, {
        "engine.py": (
            "def go(ch):\n"
            '    ch.rpc({"op": "ping"})\n'
            '    ch.rpc({"op": "warp_core_breach"})\n'
        ),
        "dn/server.py": (
            "def dispatch(msg):\n"
            '    op = msg.get("op")\n'
            '    if op == "ping":\n'
            '        return {"ok": True}\n'
        ),
    })
    found = _run_rules(p, "wire-op-unhandled")
    assert [f.ident for f in found] == [
        "warp_core_breach->opentenbase_tpu/dn/server.py"
    ]


def test_sqlstate_registry(tmp_path):
    p = _mini_project(tmp_path, {
        "engine.py": (
            "def f():\n"
            '    raise SQLError("x", "40001")\n'
            "def g():\n"
            '    raise SQLError("y", "40O01")\n'  # letter O typo
        ),
    })
    found = _run_rules(p, "sqlstate-unknown")
    assert [f.ident for f in found] == ["40O01"]


def test_sqlstate_registry_is_the_analyzed_trees(tmp_path):
    """--root must judge against the ANALYZED tree's errcodes.py, not
    the running checkout's: a code registered only in the analyzed
    tree is valid there; a code absent from it is flagged even though
    the host registry knows it."""
    p = _mini_project(tmp_path, {
        "errcodes.py": 'ERRCODES = {"0A000": "feature_not_supported"}\n',
        "engine.py": (
            "def f():\n"
            '    raise SQLError("x", "0A000")\n'  # valid HERE only
            "def g():\n"
            '    raise SQLError("y", "40001")\n'  # valid only on host
        ),
    })
    found = _run_rules(p, "sqlstate-unknown")
    assert [f.ident for f in found] == ["40001"]


def test_sqlstate_bare_state_machine_not_flagged(tmp_path):
    """`state = "READY"` is someone's state machine — five uppercase
    letters with no digit must not read as a SQLSTATE."""
    p = _mini_project(tmp_path, {
        "net/x.py": (
            "def f(self):\n"
            '    state = "READY"\n'
            '    self.state = "CLOSE"\n'
        ),
    })
    assert _run_rules(p, "sqlstate-unknown") == []


def test_fault_site_uniqueness(tmp_path):
    p = _mini_project(tmp_path, {
        "net/a.py": (
            "def f(sock):\n"
            '    FAULT("net/one")\n'
            "    sock.sendall(b'')\n"
        ),
        "net/b.py": (
            "def g(sock):\n"
            '    FAULT("net/one")\n'
            "    sock.sendall(b'')\n"
        ),
    })
    found = _run_rules(p, "fault-duplicate-site")
    assert len(found) == 2  # both ends of the collision named
    assert all("net/one" in f.message for f in found)


# ---------------------------------------------------------------------------
# lockwatch
# ---------------------------------------------------------------------------


@pytest.fixture
def watched():
    from opentenbase_tpu.analysis import lockwatch

    lockwatch.reset()
    lockwatch.enable()
    try:
        yield lockwatch
    finally:
        lockwatch.disable()
        lockwatch.reset()


def test_lockwatch_detects_inverted_order(watched):
    """Two threads, inverted lock order — run SEQUENTIALLY so the test
    can never actually deadlock; the watchdog flags the inversion from
    the orders alone, which is its whole value."""
    import threading

    a = threading.Lock()
    b = threading.Lock()

    def ab():
        with a:
            with b:
                pass

    def ba():
        with b:
            with a:
                pass

    for fn in (ab, ba):
        t = threading.Thread(target=fn)
        t.start()
        t.join()
    cycles = watched.find_cycles()
    assert len(cycles) == 1, cycles
    assert watched.report(stream=_DevNull()) == 1


def test_lockwatch_consistent_order_clean(watched):
    import threading

    a = threading.Lock()
    b = threading.Lock()
    for _ in range(3):
        def ab():
            with a:
                with b:
                    pass
        t = threading.Thread(target=ab)
        t.start()
        t.join()
    assert watched.find_cycles() == []
    assert watched.report(stream=_DevNull()) == 0


def test_lockwatch_rlock_reentry_not_an_edge(watched):
    import threading

    r = threading.RLock()
    with r:
        with r:  # reentrant re-acquire must not self-edge
            pass
    assert watched.find_cycles() == []


def test_lockwatch_allowlist_names_pair(watched):
    """Every allowlist entry names a lock pair; matching cycles are
    filtered from the default report but visible on demand."""
    for pa, pb in watched.ALLOWLIST:
        assert pa and pb  # the pair is NAMED
    # the documented rwlock table-mutex pattern: same allocation site,
    # both orders — allowlisted as a sorted-total-order hierarchy
    edge_site = "opentenbase_tpu/utils/rwlock.py:172"
    with watched._graph_mu:
        watched._edges[(edge_site, edge_site)] = "t"
    assert watched.find_cycles() == []  # filtered
    assert watched.find_cycles(include_allowed=True) == [[edge_site]]


def test_lockwatch_allowlist_same_file_inversion_still_caught(watched):
    """An identical-pattern allowlist entry blesses SELF-edges only: a
    real inversion between two DIFFERENT locks born in the allowlisted
    file must still trip the gate."""
    w = "opentenbase_tpu/utils/rwlock.py:38"
    t = "opentenbase_tpu/utils/rwlock.py:172"
    with watched._graph_mu:
        watched._edges[(w, t)] = "t1"
        watched._edges[(t, w)] = "t2"
    assert len(watched.find_cycles()) == 1  # NOT filtered


def test_lockwatch_condition_locks_tracked(watched):
    """Condition(lock) must keep working when the lock is wrapped, and
    wait()'s release/reacquire must keep the held-set accurate."""
    import threading

    mu = threading.Lock()
    cv = threading.Condition(mu)
    done = []

    def waiter():
        with cv:
            cv.wait(timeout=5)
            done.append(True)

    t = threading.Thread(target=waiter)
    t.start()
    import time

    time.sleep(0.05)
    with cv:
        cv.notify()
    t.join(timeout=5)
    assert done == [True]
    assert watched.find_cycles() == []


def test_lockwatch_condition_rlock_recursive_wait(watched):
    """Condition(RLock) waited at hold depth 2: _release_save must
    fully release (the default one-level fallback deadlocks in wait),
    and the held-set must be depth-accurate after restore."""
    import threading
    import time

    r = threading.RLock()
    cv = threading.Condition(r)
    woke = []

    def waiter():
        with cv:
            with cv:  # depth 2 — the case the delegation exists for
                cv.wait(timeout=5)
                woke.append(True)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with cv:  # acquirable ONLY if the waiter fully released
        cv.notify()
    t.join(timeout=5)
    assert woke == [True]
    held = getattr(watched._state, "held", [])
    assert held == []  # bookkeeping drained with the scopes
    assert watched.find_cycles() == []


def test_lockwatch_engine_drive_has_no_cycle():
    """The statement lock through every class it has (shared reads,
    table-granular writers on one and on two tables, exclusive DDL)
    over the WIRE, where the net server's backend threads take them,
    with the watchdog recording every acquisition: no non-allowlisted
    cycle in the acquisition graph. A subprocess, because the locks of
    interest must be created after the watchdog is on."""
    env = dict(os.environ, OTB_LOCKWATCH="1", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
        import json, threading
        from opentenbase_tpu.analysis import lockwatch
        from opentenbase_tpu.engine import Cluster
        from opentenbase_tpu.net.client import connect_tcp
        from opentenbase_tpu.net.server import ClusterServer

        c = Cluster(num_datanodes=2, shard_groups=16)
        srv = ClusterServer(c).start()
        boot = connect_tcp(srv.host, srv.port)
        boot.execute("set enable_fused_execution = off")
        for t in ("lwa", "lwb"):
            boot.execute(f"create table {t} (k bigint, v bigint) "
                         "distribute by shard(k)")
        boot.execute("insert into lwa values " + ",".join(
            f"({i},{i})" for i in range(50)))

        def reader():
            with connect_tcp(srv.host, srv.port) as x:
                for _ in range(8):
                    x.query("select count(*), sum(v) from lwa")

        def writer(tbl, base):
            with connect_tcp(srv.host, srv.port) as x:
                for j in range(8):
                    x.execute(f"insert into {tbl} values ({base+j}, 1)")

        def multi_table():
            # a two-table write set: the sorted table-mutex hierarchy
            with connect_tcp(srv.host, srv.port) as x:
                for j in range(4):
                    x.execute(f"insert into lwb select k+{1000+j*100}, v "
                              "from lwa where k < 5")

        def ddl():
            with connect_tcp(srv.host, srv.port) as x:
                x.execute("create table lwc (k bigint) "
                          "distribute by roundrobin")
                x.execute("drop table lwc")

        errs = []
        def run(fn, *a):
            # a dead driver thread watches nothing: it fails the drive
            def wrapped():
                try:
                    fn(*a)
                except BaseException as e:
                    errs.append(f"{fn.__name__}: {e!r}")
            return threading.Thread(target=wrapped)

        ths = [run(reader) for _ in range(3)]
        ths += [run(writer, "lwa", 100), run(writer, "lwb", 200),
                run(multi_table), run(ddl)]
        for t in ths: t.start()
        for t in ths: t.join()
        boot.close(); srv.stop(); c.close()
        print(json.dumps({"cycles": lockwatch.find_cycles(),
                          "ordered_pairs": len(lockwatch.edges()),
                          "driver_errors": errs}, default=str))
        """)],
        capture_output=True, text=True, timeout=180, cwd=REPO_ROOT, env=env,
    )
    assert out.returncode == 0, (out.stdout, out.stderr)
    v = json.loads(out.stdout.strip().splitlines()[-1])
    assert v["driver_errors"] == [] and v["cycles"] == [], v
    # the drive orders 30-odd lock pairs; far fewer means it did not run
    assert v["ordered_pairs"] >= 15, v


class _DevNull:
    def write(self, *_a):
        pass

    def flush(self):
        pass
