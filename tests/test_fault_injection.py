"""Fault-injection framework + the self-healing it provokes (fault/).

Covers the robustness contract end to end: zero-overhead-when-off,
deterministic prob(p, seed) replay, read-fragment retry + failover to
the coordinator's caught-up copy under an injected DN crash, DN-side
cancel of abandoned fragments, write-path retryable SQLSTATEs on both
wire protocols, in-doubt 2PC resolution for all three decision
outcomes, torn-WAL-frame reassembly, pool slot exception safety, and
GTM client failover to a promoted standby."""

import io
import random
import time

import pytest

from opentenbase_tpu import fault
from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.fault import FAULT, FaultError


@pytest.fixture(autouse=True)
def _clean_faults():
    """Every test starts and ends with nothing armed and counters
    zeroed — the registry is process-global on purpose."""
    fault.clear()
    fault.reset_stats()
    yield
    fault.clear()
    fault.reset_stats()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_fault_off_is_noop_and_allocation_free():
    """With nothing armed, a FAULT site is one dict lookup: no firing,
    no allocations (the trace_queries=off contract, applied here)."""
    import gc
    import sys

    assert FAULT("any/site") is None
    assert FAULT("any/site", node=3) is None
    # warm every cache (code objects, small ints, kwnames constants)
    for _ in range(1000):
        FAULT("exec/fragment", node=1)
    r = range(20000)
    gc.collect()
    before = sys.getallocatedblocks()
    for _ in r:
        FAULT("exec/fragment", node=1)
    after = sys.getallocatedblocks()
    assert after - before <= 8, (
        f"FAULT-off allocated {after - before} blocks over 20k calls"
    )


def test_trigger_once_every_after():
    fault.inject("s/once", "error", "once")
    with pytest.raises(FaultError):
        FAULT("s/once")
    assert FAULT("s/once") is None  # disarmed after the one shot
    assert "s/once" not in fault.armed()

    fault.inject("s/every", "error", "every(3)")
    pattern = []
    for _ in range(9):
        try:
            FAULT("s/every")
            pattern.append(0)
        except FaultError:
            pattern.append(1)
    assert pattern == [0, 0, 1, 0, 0, 1, 0, 0, 1]

    fault.inject("s/after", "error", "after(2)")
    pattern = []
    for _ in range(5):
        try:
            FAULT("s/after")
            pattern.append(0)
        except FaultError:
            pattern.append(1)
    assert pattern == [0, 0, 1, 1, 1]


def test_prob_seed_is_deterministically_replayable():
    def run(seed):
        fault.inject("s/prob", "error", f"prob(0.4; {seed})")
        out = []
        for _ in range(200):
            try:
                FAULT("s/prob")
                out.append(0)
            except FaultError:
                out.append(1)
        fault.clear("s/prob")
        return out

    a, b = run(42), run(42)
    assert a == b, "same seed must replay the same fire pattern"
    assert 0 < sum(a) < 200  # actually probabilistic, not constant
    assert run(43) != a  # seed changes the pattern


def test_context_filters_gate_firing():
    fault.inject("s/filt", "error", "every(1), node=1")
    assert FAULT("s/filt", node=0) is None  # filtered: not even a hit
    with pytest.raises(FaultError):
        FAULT("s/filt", node=1)
    rows = {r[0]: r for r in fault.stats()}
    assert rows["s/filt"][4] == 1  # hits count post-filter only
    assert rows["s/filt"][5] == 1


def test_context_filters_never_match_a_context_free_site():
    # a fault WITH filters armed against a site that passes no keyword
    # context must never fire: the filter key is absent, which is the
    # same as a mismatching value — NOT a wildcard (regression: an empty
    # ctx used to skip filter matching entirely, so 'node=1' fired on
    # every context-free hit)
    fault.inject("s/ctxfree", "error", "every(1), node=1")
    assert FAULT("s/ctxfree") is None
    assert FAULT("s/ctxfree", other="x") is None
    rows = {r[0]: r for r in fault.stats()}
    assert rows["s/ctxfree"][4] == 0  # not even a post-filter hit
    fault.clear()


def test_drop_conn_at_connect_exercises_the_retry_ladder():
    # FaultDropConnection must be a ConnectionResetError so
    # connect_with_retry treats it like a real peer reset and RETRIES
    # (regression: as plain ConnectionError it broke out of the ladder
    # after one attempt)
    import socket as _socket

    from opentenbase_tpu.fault import FaultDropConnection
    from opentenbase_tpu.net.client import connect_with_retry

    assert issubclass(FaultDropConnection, ConnectionResetError)
    lsock = _socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    _, port = lsock.getsockname()
    try:
        fault.inject("net/client/connect", "drop_conn", "once")
        sock = connect_with_retry(
            "127.0.0.1", port, timeout=5, retries=2, backoff_s=0.01
        )
        sock.close()  # attempt 1 injected a reset; attempt 2 connected
        rows = {r[0]: r for r in fault.stats()}
        assert rows["net/client/connect"][5] >= 1  # it really fired
    finally:
        fault.clear()
        lsock.close()


def test_bad_action_and_spec_are_rejected():
    with pytest.raises(ValueError):
        fault.inject("s", "explode")
    with pytest.raises(ValueError):
        fault.inject("s", "delay")  # requires (ms)
    with pytest.raises(ValueError):
        fault.inject("s", "error", "sometimes")
    with pytest.raises(ValueError):
        fault.inject("s", "error", "every(0)")


def test_guc_gates_sql_arming_but_not_clearing():
    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    with pytest.raises(Exception, match="fault_injection"):
        s.execute("select pg_fault_inject('x/y', 'error')")
    s.execute("set fault_injection = on")
    s.execute("select pg_fault_inject('x/y', 'error', 'once')")
    assert "x/y" in fault.armed()
    s2 = c.session()  # a session WITHOUT the GUC can still disarm
    assert s2.query("select pg_fault_clear()")[0][0] == 1
    assert fault.armed() == {}


# ---------------------------------------------------------------------------
# in-process DN topology harness (shared fault registry by design)
# ---------------------------------------------------------------------------


def _start_topology(tmp_path, rows=200):
    """1 coordinator + 2 in-process DNServer instances following its
    WAL — same thread-level shape as the subprocess harness, but the
    fault registry is shared so tests can arm dn/* sites directly."""
    from opentenbase_tpu.dn.server import DNServer
    from opentenbase_tpu.storage.replication import WalSender

    c = Cluster(num_datanodes=2, shard_groups=32,
                data_dir=str(tmp_path / "cn"))
    s = c.session()
    # the fused device path would execute eligible plans in-process and
    # never touch the DN channels these tests are aimed at
    s.execute("set enable_fused_execution = off")
    s.execute(
        "create table t (k bigint, v bigint) distribute by shard(k)"
    )
    vals = ",".join(f"({i}, {i * 10})" for i in range(rows))
    s.execute(f"insert into t values {vals}")
    sender = WalSender(c.persistence)
    dns = []
    for node in (0, 1):
        dn = DNServer(
            str(tmp_path / f"dn{node}"), sender.host, sender.port,
            num_datanodes=2, shard_groups=32,
        ).start()
        dns.append(dn)
        c.attach_datanode(
            node, "127.0.0.1", dn.port, pool_size=2, rpc_timeout=60,
        )
    return c, s, dns, sender


def _stop_topology(c, dns, sender):
    for node in (0, 1):
        try:
            c.detach_datanode(node)
        except Exception:
            pass
    for dn in dns:
        try:
            dn.stop()
        except Exception:
            pass
    try:
        sender.stop()
    except Exception:
        pass
    c.close()


def _remote_count(instr):
    return sum(1 for i in instr if i.get("remote"))


def test_read_fragment_retry_and_failover_under_crash_node(tmp_path):
    """Acceptance: with a crash_node fault armed on one DN, a read-only
    distributed query completes via retry + failover, EXPLAIN ANALYZE
    shows the retry, and pg_stat_faults / activity counters move."""
    c, s, dns, sender = _start_topology(tmp_path)
    try:
        want = s.query("select count(*), sum(v) from t")  # pre-crash
        s.execute("set fault_injection = on")
        s.execute("set fragment_retries = 1")
        s.execute("set fragment_retry_backoff_ms = 5")
        s.execute(
            "select pg_fault_inject('dn/exec_fragment', 'crash_node',"
            " 'node=1, once')"
        )
        # the crash fires mid-query on dn1; the coordinator retries the
        # fragment, finds the node dead, and fails over to its own copy
        got = s.query("select count(*), sum(v) from t")
        assert got == want
        assert dns[1]._crashed
        act = {
            r[0]: r for r in s.query(
                "select session_id, frag_retries, frag_failovers "
                "from pg_stat_cluster_activity"
            )
        }[s.session_id]
        assert act[1] >= 1 and act[2] >= 1
        faults = {
            (r[0], r[1]): r for r in s.query(
                "select node, site, fired from pg_stat_faults"
            )
        }
        assert faults[("cn", "dn/exec_fragment")][2] >= 1
        # dn1 stays dead: EXPLAIN ANALYZE on the same query must show
        # the failover in its per-fragment record
        lines = [r[0] for r in s.query(
            "explain analyze select count(*), sum(v) from t"
        )]
        text = "\n".join(lines)
        assert "failover=local" in text, text
        assert "retries=" in text, text
        # clear + revive: the node serves remotely again
        s.execute("select pg_fault_clear()")
        dns[1]._revive()
        assert s.query("select count(*), sum(v) from t") == want
    finally:
        _stop_topology(c, dns, sender)


def test_cancel_fragment_stops_abandoned_dn_work(tmp_path):
    """Satellite: the coordinator sends cancel_fragment when the socket
    deadline cuts an RPC; the DN stops at its next operator boundary
    instead of running to completion (the old known simplification)."""
    c, s, dns, sender = _start_topology(tmp_path, rows=50)
    try:
        fault.inject("dn/exec_fragment", "delay(1500)", "node=0, once")
        s.execute("set statement_timeout = '300ms'")
        t0 = time.monotonic()
        with pytest.raises(Exception, match="statement timeout"):
            s.query("select sum(v) from t")
        assert time.monotonic() - t0 < 1.4  # cut, not run-to-completion
        # the DN saw the cancel and aborted the delayed fragment
        deadline = time.time() + 5
        while time.time() < deadline:
            if dns[0].stats.get("fragments_cancelled", 0) >= 1:
                break
            time.sleep(0.05)
        assert dns[0].stats.get("fragments_cancelled", 0) >= 1
        assert dns[0].stats.get("cancel_requests", 0) >= 1
        # the session recovers cleanly once the timeout budget is back
        s.execute("set statement_timeout = 0")
        assert s.query("select count(*) from t")[0][0] == 50
    finally:
        _stop_topology(c, dns, sender)


def test_write_path_surfaces_retryable_sqlstate_both_wires(tmp_path):
    """Write fragments never blind-retry: a DN failure during the 2PC
    prepare aborts the statement with SQLSTATE 08006 on BOTH wire
    protocols, so the client layer knows a re-run is safe."""
    from opentenbase_tpu.net.client import WireError, connect_tcp
    from opentenbase_tpu.net.pgwire import PgWireServer
    from opentenbase_tpu.net.server import ClusterServer
    from test_pgwire import V3Client

    c, s, dns, sender = _start_topology(tmp_path, rows=8)
    srv = ClusterServer(c).start()
    pg = PgWireServer(c).start()
    try:
        # 8 consecutive keys so both datanodes are 2PC participants
        vals1 = ",".join(f"({k}, 1)" for k in range(1001, 1009))
        vals2 = ",".join(f"({k}, 2)" for k in range(2001, 2009))
        # JSON wire protocol
        fault.inject("dn/2pc_prepare", "error", "once")
        cl = connect_tcp(srv.host, srv.port)
        with pytest.raises(WireError) as ei:
            cl.execute(f"insert into t values {vals1}")
        assert ei.value.sqlstate == "08006"
        # the statement aborted whole — a re-run inserts exactly once
        cl.execute(f"insert into t values {vals1}")
        assert cl.query(
            "select count(*) from t where k >= 1001 and k <= 1008"
        ) == [(8,)]
        cl.close()
        # pgwire protocol: the E message carries the C field
        fault.inject("dn/2pc_prepare", "error", "once")
        v3 = V3Client(pg.host, pg.port)
        with pytest.raises(RuntimeError) as ei:
            v3.query(f"insert into t values {vals2}")
        assert "C08006" in str(ei.value)  # the E message's C field
        _, rows, _ = v3.query(
            "select count(*) from t where k >= 2001 and k <= 2008"
        )
        assert rows == [("0",)]
        v3.close()
    finally:
        try:
            pg.stop()
        except Exception:
            pass
        try:
            srv.stop()
        except Exception:
            pass
        _stop_topology(c, dns, sender)


# ---------------------------------------------------------------------------
# in-doubt 2PC resolution (coordinator killed between prepare and commit)
# ---------------------------------------------------------------------------


def _dn_gids(dn):
    return [e["gid"] for e in dn._twophase_list()]


def test_indoubt_resolution_all_three_outcomes(tmp_path):
    """A coordinator 'killed' between 2pc_prepare and 2pc_commit leaves
    no in-doubt gid after pg_resolve_indoubt(), for every decision
    shape: (a) no commit record -> presumed abort; (b) durable commit
    record, phase 2 never ran -> commit; (c) phase 2 partially
    delivered -> the straggler vote resolves to commit. Verified
    against every DN's 2pc_list through both wire protocols."""
    from opentenbase_tpu.net.client import connect_tcp
    from opentenbase_tpu.net.pgwire import PgWireServer
    from opentenbase_tpu.net.server import ClusterServer
    from test_pgwire import V3Client

    c, s, dns, sender = _start_topology(tmp_path, rows=8)
    srv = ClusterServer(c).start()
    pg = PgWireServer(c).start()
    try:
        base = s.query("select count(*) from t")[0][0]
        # sever WAL streaming (after the DNs caught up): the stream
        # would otherwise deliver the commit record within milliseconds
        # and retire the vote journals itself — real self-healing, but
        # this test must observe the in-doubt window deterministically
        deadline = time.time() + 20
        while time.time() < deadline and any(
            dn.standby.applied < c.persistence.wal.position
            for dn in dns
        ):
            time.sleep(0.02)
        sender.stop()
        # verification sessions keep the default fused path so reads
        # run in-process (the severed stream would stall remote reads)
        time.sleep(0.1)
        # 8 consecutive keys per batch: both DNs vote in the 2PC
        batch = {
            n: ",".join(f"({k}, {n})" for k in range(n, n + 8))
            for n in (3001, 3101, 3201)
        }

        # (a) killed BEFORE the commit record: presumed abort
        fault.inject("coord/2pc_after_prepare", "error", "once")
        sa = c.session()
        with pytest.raises(FaultError):
            sa.execute(f"insert into t values {batch[3001]}")
        assert any(_dn_gids(dn) for dn in dns)  # votes journaled
        cl = connect_tcp(srv.host, srv.port)
        resolved = cl.query("select pg_resolve_indoubt()")
        assert resolved and all(o == "aborted" for _g, o in resolved)
        assert all(_dn_gids(dn) == [] for dn in dns)
        assert not [p for p in c.gts.prepared_txns() if p.gid]
        s2 = c.session()
        assert s2.query("select count(*) from t")[0][0] == base

        # (b) killed AFTER the commit record, before phase 2: commit
        fault.inject("coord/2pc_before_phase2", "error", "once")
        s_b = c.session()
        with pytest.raises(FaultError):
            s_b.execute(f"insert into t values {batch[3101]}")
        assert any(_dn_gids(dn) for dn in dns)
        v3 = V3Client(pg.host, pg.port)
        _, rows, _ = v3.query("select pg_resolve_indoubt()")
        assert rows and all(o == "committed" for _g, o in rows)
        v3.close()
        assert all(_dn_gids(dn) == [] for dn in dns)
        assert s2.query("select count(*) from t")[0][0] == base + 8

        # (c) phase 2 partially delivered: one DN's commit verb fails,
        # its vote journal survives, and the resolver replays commit
        fault.inject("dn/2pc_commit", "error", "once")
        sc = c.session()
        sc.execute(f"insert into t values {batch[3201]}")
        assert any(_dn_gids(dn) for dn in dns)  # the straggler's vote
        resolved = cl.query("select pg_resolve_indoubt()")
        assert resolved and all(o == "committed" for _g, o in resolved)
        assert all(_dn_gids(dn) == [] for dn in dns)
        assert s2.query("select count(*) from t")[0][0] == base + 16
        cl.close()
        # counters moved
        st = dict(s2.query("select stat, value from pg_stat_2pc"))
        assert st["resolver_runs"] >= 3
        assert st["resolved_abort"] >= 1
        assert st["resolved_commit"] >= 2
    finally:
        try:
            pg.stop()
        except Exception:
            pass
        try:
            srv.stop()
        except Exception:
            pass
        _stop_topology(c, dns, sender)


def test_background_resolver_age_gates_live_commits(tmp_path):
    """The background loop must never presume-abort a vote younger than
    min_age_s (it could be a commit in flight); an old orphan goes."""
    c, s, dns, sender = _start_topology(tmp_path, rows=8)
    try:
        # plant an orphan vote directly on dn0 (a decision message that
        # never arrived for a coordinator that never decided)
        dns[0]._twophase_prepare({"gid": "orphan_x", "gxid": 999})
        out = c.resolve_indoubt(min_age_s=3600)  # too young: skipped
        assert ("orphan_x", "aborted") not in out
        assert _dn_gids(dns[0]) == ["orphan_x"]
        out = c.resolve_indoubt(min_age_s=0.0)
        assert ("orphan_x", "aborted") in out
        assert _dn_gids(dns[0]) == []
        # the background wrapper runs the same path
        stop = c.start_indoubt_resolver(interval_s=0.1, min_age_s=0.0)
        stop()
    finally:
        _stop_topology(c, dns, sender)


# ---------------------------------------------------------------------------
# torn WAL frames (wal_torn) + pool slot exception safety
# ---------------------------------------------------------------------------


def test_torn_frame_reassembly_fuzz_unit(tmp_path):
    """Byte-arbitrary reassembly proof for the standby's _drain logic:
    any split of the record stream — header boundaries, mid-length-
    word, mid-body — must yield every record exactly once, in order."""
    from opentenbase_tpu.storage.persist import WAL

    path = str(tmp_path / "w.log")
    wal = WAL(path)
    rng = random.Random(11)
    for i in range(40):
        wal.append(b"D", {"op": "noop", "i": i,
                          "pad": "x" * rng.randint(0, 200)})
    wal.close()
    with open(path, "rb") as f:
        data = f.read()
    for trial in range(25):
        trng = random.Random(trial)
        buf, got, pos = b"", [], 0
        while pos < len(data):
            cut = min(pos + trng.randint(1, 97), len(data))
            buf += data[pos:cut]
            pos = cut
            consumed = 0  # mirror StandbyCluster._drain exactly
            for _tag, header, _arr, off in WAL.read_stream(
                io.BytesIO(buf)
            ):
                got.append(header["i"])
                consumed = off
            buf = buf[consumed:]
        assert got == list(range(40)), f"trial {trial}: {got[:5]}..."
        assert buf == b""


def test_wal_torn_failpoint_streams_correctly(tmp_path):
    """Integration: with wal_torn armed on every outgoing chunk, a live
    standby still replicates bit-exact state (driven by the failpoint,
    per the satellite)."""
    from opentenbase_tpu.storage.replication import (
        StandbyCluster,
        WalSender,
    )

    c = Cluster(num_datanodes=2, shard_groups=16,
                data_dir=str(tmp_path / "p"))
    s = c.session()
    s.execute(
        "create table w (k bigint, txt text) distribute by shard(k)"
    )
    fault.inject("repl/wal_stream", "wal_torn", "prob(1; 7)")
    sender = None
    sb = None
    try:
        sender = WalSender(c.persistence, poll_s=0.02)
        sb = StandbyCluster(str(tmp_path / "sb"), 2, 16)
        sb.start_replication(sender.host, sender.port)
        for i in range(5):
            vals = ",".join(
                f"({i * 50 + j}, 'val_{i}_{j}')" for j in range(50)
            )
            s.execute(f"insert into w values {vals}")
        assert sb.wait_caught_up(c.persistence, timeout_s=30)
        want = sorted(s.query("select k, txt from w"))
        got = sorted(sb.session().query("select k, txt from w"))
        assert got == want
        hits = {r[0]: r for r in fault.stats()}
        assert hits["repl/wal_stream"][5] >= 1  # actually tore chunks
    finally:
        fault.clear()
        if sb is not None:
            sb.stop()
        if sender is not None:
            sender.stop()
        c.close()


def test_pool_slot_survives_poisoned_message(tmp_path):
    """Satellite regression: a request that fails to SERIALIZE must not
    leak the pool slot nor poison the channel; a failure AFTER the send
    starts must discard the channel (desynced stream), never hand the
    next caller a stale response."""
    from opentenbase_tpu.dn.server import DNServer
    from opentenbase_tpu.net.pool import ChannelPool
    from opentenbase_tpu.storage.replication import WalSender

    c = Cluster(num_datanodes=2, shard_groups=16,
                data_dir=str(tmp_path / "cn"))
    sender = WalSender(c.persistence)
    dn = DNServer(str(tmp_path / "dn"), sender.host, sender.port,
                  2, 16).start()
    pool = ChannelPool("127.0.0.1", dn.port, size=1)
    try:
        assert pool.rpc({"op": "ping"})["ok"]
        # poison: an unserializable payload raises BEFORE any byte is
        # sent — the slot returns, the channel stays clean and REUSED
        with pytest.raises(TypeError):
            pool.rpc({"op": "ping", "bad": object()})
        assert pool._total == 1
        assert pool.rpc({"op": "ping"})["ok"]
        assert pool.stats["opened"] == 1  # same channel both times
        # desync: a fault between send and recv leaves a reply in
        # flight; the channel must be discarded, and the next rpc (on a
        # fresh channel) must see ITS response, not the stale one
        fault.inject("net/pool/rpc_recv", "error", "once")
        with pytest.raises(FaultError):
            pool.rpc({"op": "ping"})
        assert pool._total == 0  # slot freed, channel discarded
        resp = pool.rpc({
            "op": "2pc_list",
        })
        assert "gids" in resp and resp["gids"] == []  # not a ping reply
        assert pool.stats["discarded"] == 1
        assert pool._total == 1
    finally:
        pool.close()
        dn.stop()
        sender.stop()
        c.close()


# ---------------------------------------------------------------------------
# GTM failover
# ---------------------------------------------------------------------------


def test_gtm_client_fails_over_to_promoted_standby_mid_txn():
    """Tentpole (3): NativeGTS falls back to the standby feed address on
    primary loss instead of erroring the session — a transaction begun
    on the old primary commits through the promoted standby."""
    from opentenbase_tpu.gtm.client import NativeGTS
    from opentenbase_tpu.gtm.gts import GTSServer
    from opentenbase_tpu.gtm.server import GTSFrontend
    from opentenbase_tpu.gtm.standby import ReplicationLink

    prim = GTSServer()
    fe1 = GTSFrontend(prim).start()
    link = ReplicationLink(prim)
    sb = link.add_standby()
    cli = NativeGTS(fe1.host, fe1.port)
    try:
        info = cli.begin()
        ts1 = cli.get_gts()
        fe1.stop()  # primary crash: listener and live conns severed
        promoted = sb.promote()
        fe2 = GTSFrontend(promoted).start()
        try:
            cli.set_standby(fe2.host, fe2.port)
            ts2 = cli.get_gts()  # transparently fails over
            assert cli.failovers == 1
            assert ts2 > ts1  # promoted clock jumped the reserve
            cts = cli.commit(info.gxid)  # mid-txn commit, new primary
            assert cts > ts2
            assert cli.ping()
        finally:
            fe2.stop()
    finally:
        cli.close()


def test_gtm_grant_failpoint_drops_backend_and_client_survives():
    """gtm/grant drop_conn severs one exchange; the client's failover
    path reconnects to the SAME (still-alive) primary and retries."""
    from opentenbase_tpu.gtm.client import NativeGTS
    from opentenbase_tpu.gtm.gts import GTSServer
    from opentenbase_tpu.gtm.server import GTSFrontend

    gts = GTSServer()
    fe = GTSFrontend(gts).start()
    cli = NativeGTS(fe.host, fe.port)
    try:
        t1 = cli.get_gts()
        fault.inject("gtm/grant", "drop_conn", "once")
        t2 = cli.get_gts()  # dropped once, retried on a fresh conn
        assert t2 > t1
        assert cli.failovers == 0  # same address, no standby switch
    finally:
        cli.close()
        fe.stop()


def test_fault_arm_forwards_to_dn_processes_and_stats_aggregate(
    tmp_path,
):
    """pg_fault_inject forwards over the wire (fault_arm op) and
    pg_stat_faults aggregates per-node rows — exercised through a REAL
    subprocess DN so the forwarding actually matters."""
    import os
    import subprocess
    import sys

    from opentenbase_tpu.storage.replication import WalSender

    c = Cluster(num_datanodes=2, shard_groups=16,
                data_dir=str(tmp_path / "cn"))
    s = c.session()
    s.execute("set enable_fused_execution = off")  # force DN dispatch
    s.execute("create table t (k bigint, v bigint) distribute by shard(k)")
    s.execute("insert into t values (1,1),(2,2),(3,3),(4,4)")
    sender = WalSender(c.persistence)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    procs = []
    try:
        for node in (0, 1):
            p = subprocess.Popen(
                [sys.executable, "-m", "opentenbase_tpu.dn.server",
                 "--data-dir", str(tmp_path / f"dn{node}"),
                 "--wal-host", sender.host,
                 "--wal-port", str(sender.port),
                 "--num-datanodes", "2", "--shard-groups", "16"],
                stdout=subprocess.PIPE, text=True, env=env,
            )
            procs.append(p)
            line = p.stdout.readline().strip()
            assert line.startswith("READY "), line
            c.attach_datanode(
                node, "127.0.0.1", int(line.split()[1]),
                pool_size=2, rpc_timeout=60,
            )
        s.execute("set fault_injection = on")
        site, armed = s.query(
            "select pg_fault_inject('dn/exec_fragment', 'delay(1)',"
            " 'every(1)')"
        )[0]
        assert site == "dn/exec_fragment" and armed == 2
        assert s.query("select sum(v) from t")[0][0] == 10
        rows = s.query(
            "select node, site, fired from pg_stat_faults "
            "where site = 'dn/exec_fragment' order by node"
        )
        by_node = {r[0]: r[2] for r in rows}
        # the delay fired inside the DN subprocesses, not the CN
        assert by_node.get("dn0", 0) + by_node.get("dn1", 0) >= 2
        cleared = s.query("select pg_fault_clear()")[0][0]
        assert cleared >= 2  # local + both DNs
    finally:
        for node in (0, 1):
            try:
                c.detach_datanode(node)
            except Exception:
                pass
        for p in procs:
            try:
                if p.poll() is None:
                    p.terminate()
                    p.wait(timeout=5)
            except Exception:
                try:
                    p.kill()
                except Exception:
                    pass
        try:
            sender.stop()
        except Exception:
            pass
        c.close()
