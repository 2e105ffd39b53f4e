"""otb_race (static lockset inference) + racewatch (TSan-lite runtime):
both halves must catch their bug class, and the shared baseline must
ratchet exactly like otb_lint's.

Static seeds go into a COPY of the real tree and must turn
``otb_race --check`` red against the COMMITTED baseline.  Dynamic
tests run the real classes in a SUBPROCESS with ``OTB_RACEWATCH=1``
(instrumentation is applied at class-definition time, mirroring
lockwatch's create-after-enable rule), or script a fresh class after
an in-process ``enable()``.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import textwrap
import threading

import pytest

import opentenbase_tpu
from opentenbase_tpu.cli.otb_race import main as race_main

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.abspath(opentenbase_tpu.__file__))
)
RACE_BASELINE = os.path.join(REPO_ROOT, "tools", "race_baseline.json")


def _copy_tree(tmp_path) -> str:
    root = str(tmp_path / "repo")
    shutil.copytree(
        os.path.join(REPO_ROOT, "opentenbase_tpu"),
        os.path.join(root, "opentenbase_tpu"),
        ignore=shutil.ignore_patterns("__pycache__", "*.pyc"),
    )
    os.makedirs(os.path.join(root, "tools"))
    shutil.copy(
        RACE_BASELINE, os.path.join(root, "tools", "race_baseline.json")
    )
    return root


def _check(root: str) -> int:
    return race_main([
        "--root", root,
        "--baseline", os.path.join(root, "tools", "race_baseline.json"),
        "--check",
    ])


def _append(root: str, rel: str, code: str) -> None:
    with open(os.path.join(root, rel), "a", encoding="utf-8") as f:
        f.write("\n" + code + "\n")


# a guarded/unguarded mix reachable from a thread entry point — the
# exact shape the tentpole exists to catch
_GUARD_MIX_SEED = """
class _RaceSeedBox:
    def __init__(self):
        self._seed_mu = threading.Lock()
        self.seed_state = 0

    def _seed_loop(self):
        with self._seed_mu:
            self.seed_state += 1

    def seed_poke(self):
        self.seed_state += 1


def _race_seed_start(box):
    threading.Thread(target=box._seed_loop, daemon=True).start()
"""


# ---------------------------------------------------------------------------
# the committed tree is green
# ---------------------------------------------------------------------------


def test_shipped_tree_is_green(tmp_path, capsys):
    root = _copy_tree(tmp_path)
    assert _check(root) == 0
    verdict = json.loads(
        capsys.readouterr().out.strip().splitlines()[-1]
    )
    assert verdict["race_gate"] == "ok"
    assert verdict["new"] == 0


# ---------------------------------------------------------------------------
# static half: the seeded bug classes
# ---------------------------------------------------------------------------


def test_seed_guarded_unguarded_mix_fails(tmp_path, capsys):
    """A guarded write establishes the lock; an unguarded write from a
    thread-reachable method must go red against the committed
    baseline."""
    root = _copy_tree(tmp_path)
    _append(root, "opentenbase_tpu/ha.py", _GUARD_MIX_SEED)
    assert _check(root) != 0
    assert "race-guard-mismatch" in capsys.readouterr().out


def test_seed_check_then_act_fails(tmp_path, capsys):
    root = _copy_tree(tmp_path)
    _append(root, "opentenbase_tpu/ha.py", textwrap.dedent("""
    class _CtaSeedBox:
        def __init__(self):
            self._seed_mu = threading.Lock()
            self.seed_slot = None

        def _seed_loop(self):
            with self._seed_mu:
                self.seed_slot = object()

        def seed_get(self):
            if self.seed_slot is None:
                with self._seed_mu:
                    self.seed_slot = object()
            return True


    def _cta_seed_start(box):
        threading.Thread(target=box._seed_loop, daemon=True).start()
    """))
    assert _check(root) != 0
    assert "race-check-then-act" in capsys.readouterr().out


def test_seed_release_without_finally_fails(tmp_path, capsys):
    root = _copy_tree(tmp_path)
    _append(root, "opentenbase_tpu/ha.py", textwrap.dedent("""
    def _release_seed(mu, work):
        mu.acquire()
        work()
        mu.release()
    """))
    assert _check(root) != 0
    assert "lock-release-path" in capsys.readouterr().out


def test_consistent_lockset_and_init_only_stay_green(tmp_path):
    """Every access under the one guard, plus ``__init__``-only writes
    read elsewhere: nothing to report."""
    root = _copy_tree(tmp_path)
    _append(root, "opentenbase_tpu/ha.py", textwrap.dedent("""
    class _CleanSeedBox:
        def __init__(self):
            self._seed_mu = threading.Lock()
            self.seed_state = 0
            self.seed_config = "set-once"

        def _seed_loop(self):
            with self._seed_mu:
                self.seed_state += 1

        def seed_bump(self):
            with self._seed_mu:
                self.seed_state += 1

        def seed_label(self):
            return self.seed_config


    def _clean_seed_start(box):
        threading.Thread(target=box._seed_loop, daemon=True).start()
    """))
    assert _check(root) == 0


def test_release_in_finally_stays_green(tmp_path):
    root = _copy_tree(tmp_path)
    _append(root, "opentenbase_tpu/ha.py", textwrap.dedent("""
    def _finally_seed(mu, work):
        mu.acquire()
        try:
            work()
        finally:
            mu.release()
    """))
    assert _check(root) == 0


def test_seed_device_host_leak_fails(tmp_path, capsys):
    """Satellite: the otb_lint device-host-leak family — np.* on a
    jnp-derived value inside ops/ is the r04/r05 silent-CPU class."""
    from opentenbase_tpu.cli.otb_lint import main as lint_main

    root = _copy_tree(tmp_path)
    shutil.copy(
        os.path.join(REPO_ROOT, "tools", "lint_baseline.json"),
        os.path.join(root, "tools", "lint_baseline.json"),
    )
    _append(root, "opentenbase_tpu/ops/join.py", textwrap.dedent("""
    def _leak_seed(col):
        dev = jnp.cumsum(col)
        return float(np.asarray(dev)[0])
    """))
    assert lint_main([
        "--root", root,
        "--baseline", os.path.join(root, "tools", "lint_baseline.json"),
        "--check",
    ]) != 0
    assert "device-host-leak" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# static half: unit behaviors (mini trees)
# ---------------------------------------------------------------------------


def _mini_project(tmp_path, files: dict):
    from opentenbase_tpu.analysis.core import Project

    root = tmp_path / "mini"
    for rel, src in files.items():
        p = root / "opentenbase_tpu" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return Project(str(root))


def _run_race_rules(project, rule_prefix=""):
    from opentenbase_tpu.analysis import race_checkers
    from opentenbase_tpu.analysis.core import run_checkers

    active, suppressed = run_checkers(
        project, race_checkers(), tool="race",
    )
    return [f for f in active if f.rule.startswith(rule_prefix)]


_THREADED_CLASS = """
import threading

class Box:
    def __init__(self):
        self._mu = threading.Lock()
        self.stats = {{}}

    def _loop(self):
        {loop_body}

    def touch(self):
        {touch_body}

def start(b):
    threading.Thread(target=b._loop).start()
"""


def test_container_mutation_counts_as_write(tmp_path):
    """``self.stats["x"] += 1`` without the lock is a write to stats —
    the exact ChannelPool bug this PR fixed."""
    p = _mini_project(tmp_path, {"m.py": _THREADED_CLASS.format(
        loop_body='with self._mu:\n            self.stats["a"] = 1',
        touch_body='self.stats["b"] = 2',
    )})
    found = _run_race_rules(p, "race-guard-mismatch")
    assert [f.ident for f in found] == ["Box.stats:touch"]


def test_condition_aliases_its_lock(tmp_path):
    """Condition(self._lock) and self._lock are ONE guard — acquiring
    either spelling is consistent, never a mismatch."""
    p = _mini_project(tmp_path, {"m.py": (
        "import threading\n"
        "class Box:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._cv = threading.Condition(self._lock)\n"
        "        self.items = []\n"
        "    def _loop(self):\n"
        "        with self._cv:\n"
        "            self.items.append(1)\n"
        "    def drain(self):\n"
        "        with self._lock:\n"
        "            self.items.clear()\n"
        "def start(b):\n"
        "    threading.Thread(target=b._loop).start()\n"
    )})
    assert _run_race_rules(p, "race-") == []


def test_lock_held_helper_exempt(tmp_path):
    """A ``_locked`` suffix or a 'caller holds' docstring moves the
    obligation to the caller — the helper's unguarded accesses pass."""
    p = _mini_project(tmp_path, {"m.py": (
        "import threading\n"
        "class Box:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n"
        "        self.n = 0\n"
        "    def _loop(self):\n"
        "        with self._mu:\n"
        "            self.n += 1\n"
        "            self._bump_locked()\n"
        "            self._sync()\n"
        "    def _bump_locked(self):\n"
        "        self.n += 1\n"
        "    def _sync(self):\n"
        '        """Caller holds ``_mu``."""\n'
        "        self.n += 1\n"
        "def start(b):\n"
        "    threading.Thread(target=b._loop).start()\n"
    )})
    assert _run_race_rules(p, "race-") == []


def test_exempt_primitives_not_shared_data(tmp_path):
    """Events/queues are internally synchronized; touching them with no
    lock is not a finding."""
    p = _mini_project(tmp_path, {"m.py": (
        "import threading, queue\n"
        "class Box:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n"
        "        self._stop = threading.Event()\n"
        "        self._q = queue.Queue()\n"
        "        self.n = 0\n"
        "    def _loop(self):\n"
        "        with self._mu:\n"
        "            self.n += 1\n"
        "    def stop(self):\n"
        "        self._stop.set()\n"
        "        self._q.put(None)\n"
        "def start(b):\n"
        "    threading.Thread(target=b._loop).start()\n"
    )})
    assert _run_race_rules(p, "race-") == []


def test_unreachable_private_method_not_flagged(tmp_path):
    """An unguarded access in a private method no thread entry reaches
    is dead-to-concurrency: not flagged."""
    p = _mini_project(tmp_path, {"m.py": (
        "import threading\n"
        "class Box:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n"
        "        self.n = 0\n"
        "    def _loop(self):\n"
        "        with self._mu:\n"
        "            self.n += 1\n"
        "    def _orphan_helper(self):\n"
        "        self.n += 1\n"
        "def start(b):\n"
        "    threading.Thread(target=b._loop).start()\n"
    )})
    assert _run_race_rules(p, "race-") == []


def test_pragma_tools_do_not_cross(tmp_path):
    """An otb_race pragma must neither suppress an otb_lint finding nor
    show up as otb_lint pragma rot — and vice versa."""
    from opentenbase_tpu.analysis import all_checkers
    from opentenbase_tpu.analysis.core import run_checkers

    p = _mini_project(tmp_path, {"ops/m.py": (
        "_x = jax.experimental.enable_x64"
        "  # otb_race: ignore[deprecated-api] -- wrong tool\n"
    )})
    lint_active, _ = run_checkers(p, all_checkers(), tool="lint")
    # the deprecated-api finding survives (race pragma can't mute it),
    # and the race pragma is NOT reported as lint pragma rot
    assert any(f.rule == "deprecated-api" for f in lint_active)
    assert not any(f.rule == "pragma-unused" for f in lint_active)
    race_active, _ = _run_race_rules(p), None
    # ...but the race run DOES see its own pragma as unused rot
    from opentenbase_tpu.analysis import race_checkers

    ra, _ = run_checkers(p, race_checkers(), tool="race")
    assert any(f.rule == "pragma-unused" for f in ra)


# ---------------------------------------------------------------------------
# baseline ratchet round-trip + reasoned-pragma refusal
# ---------------------------------------------------------------------------


def test_baseline_round_trip(tmp_path, capsys):
    root = _copy_tree(tmp_path)
    baseline = os.path.join(root, "tools", "race_baseline.json")
    assert _check(root) == 0
    _append(root, "opentenbase_tpu/ha.py", _GUARD_MIX_SEED)
    assert _check(root) == 1  # new finding: red
    capsys.readouterr()
    assert race_main(["--root", root, "--baseline", baseline,
                      "--update-baseline"]) == 0
    assert _check(root) == 0  # blessed: green again
    # removing the seed leaves a 'fixed' hint, still green
    path = os.path.join(root, "opentenbase_tpu", "ha.py")
    with open(path) as f:
        src = f.read()
    with open(path, "w") as f:
        f.write(src.replace(_GUARD_MIX_SEED, ""))
    capsys.readouterr()
    assert _check(root) == 0
    assert "fixed" in capsys.readouterr().out


def test_update_baseline_preserves_dynamic_keys(tmp_path):
    """The static regeneration must never drop the racewatch gate's
    blessed race-dynamic entries — one file, two writers."""
    root = _copy_tree(tmp_path)
    baseline = os.path.join(root, "tools", "race_baseline.json")
    assert race_main([
        "--root", root, "--baseline", baseline,
        "--bless-dynamic",
        "race-dynamic::opentenbase_tpu/x.py::Fake.field",
        "--reason", "seeded for the preservation test",
    ]) == 0
    assert race_main(["--root", root, "--baseline", baseline,
                      "--update-baseline"]) == 0
    with open(baseline) as f:
        doc = json.load(f)
    key = "race-dynamic::opentenbase_tpu/x.py::Fake.field"
    assert key in doc["findings"]
    assert "preservation test" in doc["findings"][key]["message"]
    assert _check(root) == 0  # dynamic keys are not static 'fixed' noise


def test_bless_dynamic_requires_reason(tmp_path, capsys):
    root = _copy_tree(tmp_path)
    baseline = os.path.join(root, "tools", "race_baseline.json")
    assert race_main([
        "--root", root, "--baseline", baseline,
        "--bless-dynamic", "race-dynamic::opentenbase_tpu/x.py::F.f",
    ]) == 2
    assert "REQUIRES --reason" in capsys.readouterr().err
    with open(baseline) as f:
        doc = json.load(f)
    assert "race-dynamic::opentenbase_tpu/x.py::F.f" not in doc["findings"]


def test_reasonless_pragma_refused(tmp_path, capsys):
    """A bare ``# otb_race: ignore[...]`` is itself a violation that
    can never be baselined away."""
    root = _copy_tree(tmp_path)
    baseline = os.path.join(root, "tools", "race_baseline.json")
    _append(root, "opentenbase_tpu/ha.py", _GUARD_MIX_SEED.replace(
        "self.seed_state += 1\n\n",
        "self.seed_state += 1  # otb_race: ignore[race-guard-mismatch]\n\n",
        1,
    ).replace(
        "        with self._seed_mu:\n"
        "            self.seed_state += 1  # otb_race: ignore[race-guard-mismatch]",
        "        with self._seed_mu:\n"
        "            self.seed_state += 1",
    ))
    # put the reasonless pragma on the UNGUARDED write instead
    path = os.path.join(root, "opentenbase_tpu", "ha.py")
    with open(path) as f:
        src = f.read()
    src = src.replace(
        "    def seed_poke(self):\n        self.seed_state += 1",
        "    def seed_poke(self):\n"
        "        self.seed_state += 1"
        "  # otb_race: ignore[race-guard-mismatch]",
    )
    with open(path, "w") as f:
        f.write(src)
    assert _check(root) != 0
    assert "pragma-missing-reason" in capsys.readouterr().out
    capsys.readouterr()
    race_main(["--root", root, "--baseline", baseline,
               "--update-baseline"])
    with open(baseline) as f:
        doc = json.load(f)
    assert not any(
        "pragma-missing-reason" in k for k in doc["findings"]
    )
    assert _check(root) != 0  # still red after regeneration


def test_reasoned_pragma_suppresses(tmp_path):
    root = _copy_tree(tmp_path)
    _append(root, "opentenbase_tpu/ha.py", _GUARD_MIX_SEED.replace(
        "    def seed_poke(self):\n        self.seed_state += 1",
        "    def seed_poke(self):\n"
        "        self.seed_state += 1"
        "  # otb_race: ignore[race-guard-mismatch] -- seeded for the test",
    ))
    assert _check(root) == 0


# ---------------------------------------------------------------------------
# dynamic half: scripted racewatch semantics (in-process)
# ---------------------------------------------------------------------------


@pytest.fixture
def rw():
    from opentenbase_tpu.analysis import lockwatch, racewatch

    racewatch.reset()
    racewatch.enable()
    try:
        yield racewatch
    finally:
        racewatch.disable()
        racewatch.reset()
        lockwatch.disable()
        lockwatch.reset()


def _box_class(rw):
    @rw.shared_state("_mu")
    class Box:
        def __init__(self):
            self._mu = threading.Lock()
            self.n = 0
            self.stats = {"x": 0}

        def bump_guarded(self):
            with self._mu:
                self.n += 1
                self.stats["x"] += 1

        def bump_unguarded(self):
            self.n += 1
            self.stats["x"] += 1

    return Box


def _run_threads(*fns):
    for fn in fns:
        t = threading.Thread(target=fn)
        t.start()
        t.join()


def test_racewatch_disjoint_lockset_write_reports_once(rw):
    """Two threads, same field, disjoint locksets, one write → exactly
    one reported race per field, carrying BOTH stacks."""
    b = _box_class(rw)()
    _run_threads(b.bump_guarded, b.bump_unguarded)
    races = rw.races()
    by_field = {r["field"] for r in races}
    assert by_field == {"n", "stats"}
    for r in races:
        assert r["a"].stack and r["b"].stack
        assert r["a"].thread_id != r["b"].thread_id
        assert r["a"].write or r["b"].write
        assert not (r["a"].lockset & r["b"].lockset)
    # exactly one race per field, however many times it keeps racing
    _run_threads(b.bump_unguarded)
    assert len(rw.races()) == len(races)
    keys = [f.key for f in rw.findings()]
    assert len(keys) == len(set(keys)) == len(races)
    assert all(k.startswith("race-dynamic::") for k in keys)


def test_racewatch_consistent_lockset_green(rw):
    b = _box_class(rw)()
    _run_threads(b.bump_guarded, b.bump_guarded, b.bump_guarded)
    assert rw.races() == []
    assert rw.report(stream=_DevNull()) == 0


def test_racewatch_init_only_writes_green(rw):
    b = _box_class(rw)()

    def reader():
        _ = b.n
        _ = b.stats

    _run_threads(reader, reader)
    assert rw.races() == []


def test_racewatch_reader_reader_green(rw):
    """Two unguarded READERS never race (no write in the pair)."""
    Box = _box_class(rw)
    b = Box()
    _run_threads(b.bump_guarded)  # publish a guarded write first

    def reader():
        with b._mu:
            _ = b.n

    _run_threads(reader, reader)
    assert rw.races() == []


def test_racewatch_check_baseline_gate(rw):
    from opentenbase_tpu.analysis import baseline as bl

    b = _box_class(rw)()
    _run_threads(b.bump_guarded, b.bump_unguarded)
    doc = {"version": 1, "findings": {}}
    new, seen = rw.check_baseline(doc)
    assert len(new) == 2 and seen == []
    doc["findings"] = {f.key: {"line": 1, "message": "blessed"}
                      for f in new}
    new2, seen2 = rw.check_baseline(doc)
    assert new2 == [] and len(seen2) == 2


# ---------------------------------------------------------------------------
# dynamic half: the fixed races, re-provoked against the REAL classes
# (subprocess: instrumentation applies at class definition, so the env
# var must be set before the engine imports)
# ---------------------------------------------------------------------------


def _run_racewatch_subprocess(script: str) -> str:
    env = dict(os.environ)
    env["OTB_RACEWATCH"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=180,
        cwd=REPO_ROOT, env=env,
    )
    assert out.returncode == 0, (out.stdout, out.stderr)
    return out.stdout


def test_pool_stats_race_fixed():
    """PR fix #1 (ChannelPool.acquire): stats updates moved under the
    pool lock.  Two threads hammer acquire/release with an armed FAULT
    delay widening the old race window; the counters must be EXACT and
    racewatch must see no disjoint-lockset pair on ChannelPool.stats."""
    out = _run_racewatch_subprocess("""
        import socket, threading
        from opentenbase_tpu import fault
        from opentenbase_tpu.analysis import racewatch
        from opentenbase_tpu.net.pool import ChannelPool

        # a listener that accepts and holds sockets open (never replies)
        lsock = socket.socket()
        lsock.bind(("127.0.0.1", 0)); lsock.listen(64)
        conns = []
        def accept_loop():
            while True:
                try:
                    c, _ = lsock.accept(); conns.append(c)
                except OSError:
                    return
        threading.Thread(target=accept_loop, daemon=True).start()

        # the existing FAULT delay site on the rpc path holds threads
        # inside the pool plumbing so acquires genuinely overlap
        fault.inject("net/pool/rpc_send", "delay(5)", "every(1)")
        pool = ChannelPool("127.0.0.1", lsock.getsockname()[1], size=8,
                           rpc_timeout=5)
        N = 20
        barrier = threading.Barrier(2)
        def worker():
            barrier.wait()
            for _ in range(N):
                ch = pool.acquire()
                pool.release(ch)
        ts = [threading.Thread(target=worker) for _ in range(2)]
        for t in ts: t.start()
        for t in ts: t.join()
        fault.clear()
        # verify under the pool lock: an unguarded verification read
        # would itself be a (reported!) race — the sanitizer has no
        # happens-before notion for join()
        with pool._lock:
            acquired = pool.stats["acquired"]
        assert acquired == 2 * N, acquired
        races = [r for r in racewatch.races()
                 if r["class"] == "ChannelPool"]
        assert races == [], racewatch.findings()
        pool.close(); lsock.close()
        print("POOL_OK")
    """)
    assert "POOL_OK" in out


def test_logring_dropped_race_fixed():
    """PR fix #3 (LogRing): the below-threshold ``dropped`` counter is
    guarded and ``set_min_level`` publishes atomically — exact counts,
    no disjoint-lockset write on LogRing.dropped."""
    out = _run_racewatch_subprocess("""
        import threading
        from opentenbase_tpu.analysis import racewatch
        from opentenbase_tpu.obs.log import LogRing

        ring = LogRing(node="t", min_level="warning")
        N = 300
        barrier = threading.Barrier(3)
        def dropper():
            barrier.wait()
            for _ in range(N):
                ring.emit("debug", "test", "below threshold")
        ts = [threading.Thread(target=dropper) for _ in range(3)]
        for t in ts: t.start()
        for t in ts: t.join()
        with ring._mu:  # guarded verification read (no join() HB here)
            dropped = ring.dropped
        assert dropped == 3 * N, dropped
        bad = [r for r in racewatch.races()
               if r["class"] == "LogRing" and r["field"] == "dropped"]
        assert bad == [], racewatch.findings()
        print("LOGRING_OK")
    """)
    assert "LOGRING_OK" in out


def test_spanring_allocations_race_fixed():
    """PR fix #4 (SpanRing): the class-level ``allocations`` counter is
    a guarded read-modify-write — exact across concurrent recorders."""
    out = _run_racewatch_subprocess("""
        import threading
        from opentenbase_tpu.obs.tracectx import SpanRing, TraceContext

        ring = SpanRing()
        ctx = TraceContext.new()
        base = SpanRing.allocations
        N = 400
        barrier = threading.Barrier(3)
        def recorder():
            barrier.wait()
            for i in range(N):
                ring.record(ctx, "s", "c", 0.0, 0.001)
        ts = [threading.Thread(target=recorder) for _ in range(3)]
        for t in ts: t.start()
        for t in ts: t.join()
        assert SpanRing.allocations == base + 3 * N, SpanRing.allocations
        print("SPANRING_OK")
    """)
    assert "SPANRING_OK" in out


@pytest.mark.slow
def test_chaos_schedule_under_racewatch_finds_no_new_race(tmp_path):
    """The dynamic gate: one fixed-seed chaos schedule (promotion,
    fencing, resync under live traffic) with every @shared_state class
    instrumented. Two threads on one instance field with disjoint
    locksets and a write is a race; one whose ``race-dynamic::`` key is
    not in tools/race_baseline.json fails. Slow: ~10 s of schedule
    under the sanitizer's overhead. Replay: ``OTB_RACEWATCH=1 python -m
    opentenbase_tpu.cli.otb_chaos --seed 1107 --schedules 1``."""
    out = _run_racewatch_subprocess(f"""
        import json
        from opentenbase_tpu.analysis import baseline as bl
        from opentenbase_tpu.analysis import racewatch
        from opentenbase_tpu.fault.schedule import (
            ChaosSchedule, run_schedule,
        )

        sched = ChaosSchedule.generate(1107, duration_s=4.0,
                                       num_datanodes=2)
        v = run_schedule(sched, {str(tmp_path / "chaos")!r},
                         detect_ms=1100, beats=3)
        new, _old = racewatch.check_baseline(bl.load({RACE_BASELINE!r}))
        print(json.dumps({{"chaos_gate": v["chaos_gate"],
                           "violations": v["violations"],
                           "races_new": [f.key for f in new]}}))
    """)
    v = json.loads(out.strip().splitlines()[-1])
    # a sanitizer run that breaks the invariants it watches under
    # proves nothing
    assert v["chaos_gate"] == "ok", v
    assert v["races_new"] == [], v


class _DevNull:
    def write(self, *_a):
        pass

    def flush(self):
        pass
