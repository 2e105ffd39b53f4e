"""Cluster telemetry plane (obs/log.py, obs/exporter.py, obs/progress.py).

Covers the operator-facing contract end to end: severity ordering and
log_min_messages actually filtering, one merged time-ordered log across
CN + DN processes + GTM with a fault fired inside a DN, OpenMetrics
exposition-format conformance with monotone counters across scrapes,
auto_explain's threshold semantics, pg_stat_progress_* observed from a
second session mid-command, pg_cluster_health watching a crash_node'd
DN die and revive, pg_stat_reset, and exporter-off = zero listener
sockets."""

import re
import tempfile
import threading
import time

import pytest

from opentenbase_tpu import fault
from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.obs import log as olog
from opentenbase_tpu.obs.log import LEVELS, LogRing


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Faults cleared and the process-default ring's threshold restored
    — both registries are process-global on purpose."""
    fault.clear()
    fault.reset_stats()
    prev = olog.default_ring().min_level
    yield
    fault.clear()
    fault.reset_stats()
    olog.default_ring().set_min_level(prev)
    olog.set_thread_ring(None)


# ---------------------------------------------------------------------------
# severity model + ring semantics
# ---------------------------------------------------------------------------


def test_severity_ordering_debug_log_notice_warning_error():
    order = ["debug", "log", "notice", "warning", "error"]
    ranks = [LEVELS[name] for name in order]
    assert ranks == sorted(ranks) and len(set(ranks)) == len(ranks)


def test_ring_filters_below_threshold_and_is_bounded():
    ring = LogRing(node="x", capacity=8, min_level="notice")
    assert ring.emit("debug", "c", "dropped") is None
    assert ring.emit("log", "c", "dropped") is None
    assert ring.emit("notice", "c", "kept") is not None
    assert ring.emit("error", "c", "kept") is not None
    assert [r[4] for r in ring.rows()] == ["kept", "kept"]
    assert ring.dropped == 2
    ring.set_min_level("debug")
    for i in range(20):
        ring.emit("log", "c", f"m{i}")
    assert len(ring) == 8  # bounded: oldest evicted
    assert ring.rows()[-1][4] == "m19"
    # consumer-side min_level filter + context travels as one line
    ring.emit("error", "c", "boom", gid="g1", node=3)
    (rec,) = ring.rows("error")
    assert '"gid": "g1"' in rec[5] and '"node": 3' in rec[5]
    assert rec[2] == "x"  # the ring's node label, never a ctx override


def test_log_min_messages_honored_via_set(tmp_path):
    c = Cluster(num_datanodes=1, shard_groups=4)
    s = c.session()
    s.execute("set log_min_messages = error")
    n0 = len(s.query("select pg_cluster_logs('debug')"))
    c.log.emit("warning", "test", "suppressed")
    assert len(s.query("select pg_cluster_logs('debug')")) == n0
    s.execute("set log_min_messages = debug")
    c.log.emit("debug", "test", "kept-now")
    rows = s.query("select pg_cluster_logs('debug')")
    assert any(r[4] == "kept-now" for r in rows)
    # bad level names are rejected, not silently accepted
    with pytest.raises(Exception):
        s.execute("set log_min_messages = chatty")
    c.close()


def test_log_destination_file_sink(tmp_path):
    d = str(tmp_path / "cn")
    import os

    os.makedirs(d)
    with open(os.path.join(d, "opentenbase.conf"), "w") as f:
        f.write("log_destination = file\nlog_directory = serverlog\n")
    c = Cluster(num_datanodes=1, shard_groups=4, data_dir=d)
    c.log.emit("error", "test", "to-disk", marker="file-sink-proof")
    path = os.path.join(d, "serverlog", "otb.log")
    with open(path) as f:
        text = f.read()
    assert "to-disk" in text and "file-sink-proof" in text
    assert "[ERROR]" in text
    c.close()


def test_statement_errors_reach_the_server_log():
    c = Cluster(num_datanodes=1, shard_groups=4)
    s = c.session()
    with pytest.raises(Exception):
        s.execute("select * from no_such_table_xyz")
    rows = s.query("select pg_cluster_logs('error')")
    assert any(
        r[3] == "statement" and "no_such_table_xyz" in r[5] for r in rows
    ), rows
    c.close()


# ---------------------------------------------------------------------------
# merged cluster log: CN + DN processes + GTM, fault fired in a DN
# ---------------------------------------------------------------------------


def _dn_topology(tmp, n_rows=120):
    from opentenbase_tpu.dn.server import DNServer
    from opentenbase_tpu.storage.replication import WalSender

    c = Cluster(num_datanodes=2, shard_groups=16, data_dir=f"{tmp}/cn")
    s = c.session()
    s.execute("set enable_fused_execution = off")
    s.execute("create table t (k bigint, v bigint) distribute by shard(k)")
    s.execute(
        "insert into t values "
        + ",".join(f"({i},{i * 3})" for i in range(n_rows))
    )
    sender = WalSender(c.persistence)
    dns = [
        DNServer(f"{tmp}/dn{n}", sender.host, sender.port, 2, 16).start()
        for n in (0, 1)
    ]
    for n, dn in enumerate(dns):
        c.attach_datanode(
            n, "127.0.0.1", dn.port, pool_size=2, rpc_timeout=60
        )
    return c, s, sender, dns


def _teardown(c, sender, dns):
    for n in range(len(dns)):
        c.detach_datanode(n)
    for dn in dns:
        dn.stop()
    sender.stop()
    c.close()


def test_merged_logs_health_and_waits_reconstruct_a_chaos_run():
    """THE acceptance scenario: arm crash_node on a DN, watch the query
    heal, then reconstruct the whole incident from telemetry alone —
    the fault firing (in the DN's ring), the retries and failover (in
    the CN's), the DN down-then-revived in pg_cluster_health, and the
    backoff visible in the wait model."""
    tmp = tempfile.mkdtemp(prefix="otbtel_")
    c, s, sender, dns = _dn_topology(tmp)
    try:
        want = s.query("select count(*), sum(v) from t")
        s.execute("set fault_injection = on")
        s.execute("set fragment_retries = 1")
        s.execute("set fragment_retry_backoff_ms = 5")
        s.execute(
            "select pg_fault_inject('dn/exec_fragment', 'crash_node',"
            " 'node=1, once')"
        )
        assert s.query("select count(*), sum(v) from t") == want

        # health mid-incident: dn1 down, dn0 untouched
        health = {
            r[0]: r for r in s.query("select * from pg_cluster_health")
        }
        assert health["dn1"][2] is False
        assert health["dn0"][2] is True and health["cn0"][2] is True
        # a dead node ships no logs (its failure shows in health)
        nodes_now = {
            r[2] for r in s.query("select pg_cluster_logs()")
        }
        assert "dn1" not in nodes_now

        # disarm + revive (the chaos harness's respawn), then the full
        # story must be in the one merged view
        s.execute("select pg_fault_clear()")
        dns[1]._revive()
        assert s.query("select count(*), sum(v) from t") == want
        health = {
            r[0]: r for r in s.query("select * from pg_cluster_health")
        }
        assert health["dn1"][2] is True

        logs = s.query("select pg_cluster_logs()")
        by = {}
        for ts, level, node, comp, msg, ctx in logs:
            by.setdefault((node, comp), []).append(msg)
        dn1_fault = by.get(("dn1", "fault"), [])
        assert any("fault fired" in m for m in dn1_fault), by
        assert any("crash_node" in m for m in dn1_fault), by
        assert any("revived" in m for m in dn1_fault), by
        cn_exec = by.get(("cn0", "executor"), [])
        assert any("retrying" in m for m in cn_exec), by
        assert any("failed over" in m for m in cn_exec), by
        # log node labels match pg_cluster_health's node names, so the
        # two views cross-reference (cn0 / dnN / gtm0)
        assert any(node == "gtm0" for node, _ in by), by
        # merged view is time-ordered across all three node kinds
        ts_list = [r[0] for r in logs]
        assert ts_list == sorted(ts_list)
        # node filter narrows to one ring
        only_dn1 = s.query("select pg_cluster_logs('debug', 'dn1')")
        assert only_dn1 and {r[2] for r in only_dn1} == {"dn1"}
        # min_level filter drops the 'log'-level fault records
        errors_only = s.query("select pg_cluster_logs('error')")
        assert all(r[1] == "error" for r in errors_only)

        # the wait model shows where the healing time went
        waits = s.query(
            "select wait_event_type, wait_event, count "
            "from pg_stat_wait_events"
        )
        assert any(w[1] == "RetryBackoff" for w in waits), waits

        # injected delay windows surface as FaultInjection waits
        s.execute(
            "select pg_fault_inject('dn/exec_fragment', 'delay(30)',"
            " 'node=0, once')"
        )
        s.query("select count(*) from t")
        waits = s.query(
            "select wait_event_type, wait_event, total_ms "
            "from pg_stat_wait_events"
        )
        fi = [w for w in waits if w[0] == "FaultInjection"]
        assert fi and fi[0][2] >= 20, waits

        # and a scrape carries the incident's series: fault hits, the
        # DNs' liveness, the standbys' replication lag
        from opentenbase_tpu.obs.exporter import render_cluster_metrics

        body = render_cluster_metrics(c)
        for series in ("otb_fault_hits_total", "otb_dn_up",
                       "otb_replication_lag_bytes"):
            assert series in body, series
    finally:
        _teardown(c, sender, dns)


# ---------------------------------------------------------------------------
# OpenMetrics exporter
# ---------------------------------------------------------------------------

# exposition text format: comment/HELP/TYPE lines or  name{labels} value
_EXPO_LINE = re.compile(
    r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{([a-zA-Z_][a-zA-Z0-9_]*="
    r'"(\\.|[^"\\])*",?)*\})? -?([0-9.eE+\-]+|\+Inf|NaN))$'
)


def _counter_samples(body: str) -> dict:
    out = {}
    for ln in body.splitlines():
        if ln.startswith("#"):
            continue
        name = ln.split("{", 1)[0].split(" ", 1)[0]
        if not (name.endswith("_total") or name.endswith("_count")):
            continue
        key, _, val = ln.rpartition(" ")
        out[key] = float(val)
    return out


def test_openmetrics_exposition_conformance_and_monotone_counters():
    from opentenbase_tpu.obs.exporter import scrape

    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    s.execute("create table m (k bigint, v bigint) distribute by shard(k)")
    s.execute("insert into m values (1,1),(2,2),(3,3)")
    s.execute("select sum(v) from m")
    # make sure the wait-event section renders (regression: a tuple
    # shape change there once degraded scrapes to '# render error')
    c.waits.end(c.waits.begin(s.session_id, "IPC", "test_wait"))
    exp = c.start_metrics_exporter(0)
    try:
        b1 = scrape("127.0.0.1", exp.port)
        assert b1.splitlines(), "empty exposition"
        assert "render error" not in b1, b1
        for ln in b1.splitlines():
            assert _EXPO_LINE.match(ln), f"bad exposition line: {ln!r}"
        # histogram contract: cumulative buckets ending in +Inf == count
        inf = [ln for ln in b1.splitlines() if 'le="+Inf"' in ln]
        assert inf, "no +Inf buckets"
        s.execute("select count(*) from m")
        s.execute("select sum(v) from m group by k")
        b2 = scrape("127.0.0.1", exp.port)
        for ln in b2.splitlines():
            assert _EXPO_LINE.match(ln), f"bad exposition line: {ln!r}"
        c1, c2 = _counter_samples(b1), _counter_samples(b2)
        regressed = [
            k for k, v in c1.items() if k in c2 and c2[k] < v
        ]
        assert not regressed, f"counters went backwards: {regressed}"
        moved = [k for k, v in c2.items() if v > c1.get(k, 0.0)]
        assert moved, "no counter moved between scrapes"
        # a 404 path answers without killing the listener
        import socket as _socket

        with _socket.create_connection(("127.0.0.1", exp.port)) as sk:
            sk.sendall(b"GET /nope HTTP/1.1\r\nHost: x\r\n\r\n")
            assert b"404" in sk.recv(4096)
        assert scrape("127.0.0.1", exp.port)
    finally:
        c.close()


def test_exporter_off_means_no_listener_socket(tmp_path):
    c = Cluster(num_datanodes=1, shard_groups=4)
    assert c._metrics_exporter is None  # default: metrics_port unset
    c.close()
    # and on via the GUC: the conf file opens a real listener
    import os
    import socket as _socket

    probe = _socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    d = str(tmp_path / "cn")
    os.makedirs(d)
    with open(os.path.join(d, "opentenbase.conf"), "w") as f:
        f.write(f"metrics_port = {port}\n")
    c = Cluster(num_datanodes=1, shard_groups=4, data_dir=d)
    try:
        assert c._metrics_exporter is not None
        from opentenbase_tpu.obs.exporter import scrape

        assert "otb_sessions" in scrape("127.0.0.1", port)
    finally:
        c.close()
    # stopped with the cluster
    with pytest.raises(OSError):
        _socket.create_connection(("127.0.0.1", port), timeout=0.5)


# ---------------------------------------------------------------------------
# auto_explain
# ---------------------------------------------------------------------------


def test_auto_explain_threshold_on_off():
    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    s.execute("create table ae (k bigint, v bigint) distribute by shard(k)")
    s.execute("insert into ae values (1,10),(2,20),(3,30)")

    def ae_records():
        return [
            r for r in s.query("select pg_cluster_logs()")
            if r[3] == "auto_explain"
        ]

    # off by default
    s.execute("select sum(v) from ae")
    assert ae_records() == []
    # threshold 0: every statement logs, with the instrumented tree
    s.execute("set auto_explain_min_duration_ms = 0")
    s.execute("select sum(v) from ae")
    recs = ae_records()
    assert recs, "auto_explain produced nothing at threshold 0"
    last = recs[-1]
    assert last[1] == "log" and "duration:" in last[4]
    assert "select sum(v) from ae" in last[4]
    assert "Fragment" in last[5] or "Fused" in last[5], last[5]
    # an unreachable threshold logs nothing new
    s.execute("set auto_explain_min_duration_ms = 60000")
    n = len(ae_records())
    s.execute("select count(*) from ae")
    assert len(ae_records()) == n
    # -1 switches it off again (PG's off spelling)
    s.execute("set auto_explain_min_duration_ms = -1")
    s.execute("select count(*) from ae")
    assert len(ae_records()) == n
    c.close()


# ---------------------------------------------------------------------------
# progress views
# ---------------------------------------------------------------------------


def test_progress_refresh_observed_mid_flight_from_second_session(tmp_path):
    c = Cluster(num_datanodes=2, shard_groups=16, data_dir=str(tmp_path))
    s = c.session()
    s.execute(
        "create table f (k bigint, g text, v bigint) "
        "distribute by shard(k)"
    )
    s.execute("insert into f values (1,'a',10),(2,'b',20),(3,'a',30)")
    s.execute(
        "create materialized view mv as select g, count(*) as n, "
        "sum(v) as sv from f group by g"
    )
    s.execute("insert into f values (4,'b',40),(5,'c',50)")
    s.execute("set fault_injection = on")
    s.execute("select pg_fault_inject('matview/refresh', 'delay(600)', 'once')")
    s2 = c.session()
    err: list = []

    def run():
        try:
            s.execute("refresh materialized view mv")
        except Exception as e:  # surfaces in the main thread's assert
            err.append(e)

    th = threading.Thread(target=run)
    th.start()
    seen = None
    for _ in range(200):
        rows = s2.query(
            "select matviewname, phase, state "
            "from pg_stat_progress_refresh"
        )
        running = [r for r in rows if r[2] == "running"]
        if running:
            seen = running
            break
        time.sleep(0.01)
    th.join()
    assert not err, err
    assert seen and seen[0][0] == "mv", seen
    done = s2.query(
        "select matviewname, state, deltas_applied, phase "
        "from pg_stat_progress_refresh"
    )
    assert any(
        r[1] == "finished" and r[3] == "done" for r in done
    ), done
    # a FAILED refresh must not read as a success in the view
    s.execute("select pg_fault_inject('matview/refresh', 'error', 'once')")
    with pytest.raises(Exception):
        s.execute("refresh materialized view mv")
    failed = s2.query(
        "select state, phase from pg_stat_progress_refresh"
    )
    assert failed == [("finished", "failed")], failed
    c.close()


def test_progress_checkpoint_and_recovery(tmp_path):
    d = str(tmp_path)
    c = Cluster(num_datanodes=2, shard_groups=16, data_dir=d)
    s = c.session()
    s.execute("create table p (k bigint, v bigint) distribute by shard(k)")
    s.execute("insert into p values (1,1),(2,2)")
    c.persistence.checkpoint()
    rows = s.query(
        "select phase, tables_total, tables_done, state "
        "from pg_stat_progress_checkpoint"
    )
    assert rows == [("done", rows[0][1], rows[0][1], "finished")], rows
    s.execute("insert into p values (3,3)")  # a WAL tail to replay
    c.close()
    c2 = Cluster.recover(d, num_datanodes=2, shard_groups=16)
    s2 = c2.session()
    rows = s2.query(
        "select phase, wal_replay_lsn, wal_end_lsn, records_applied, "
        "state from pg_stat_progress_recovery"
    )
    assert rows and rows[0][0] == "done" and rows[0][4] == "finished"
    assert rows[0][3] >= 1  # the post-checkpoint insert replayed
    logs = s2.query("select pg_cluster_logs('log')")
    assert any(
        r[3] == "recovery" and "complete" in r[4] for r in logs
    )
    assert s2.query("select count(*) from p") == [(3,)]
    c2.close()


# ---------------------------------------------------------------------------
# pg_stat_reset
# ---------------------------------------------------------------------------


def test_pg_stat_reset_zeroes_counters_but_not_fault_stats():
    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    s.execute("create table r (k bigint, v bigint) distribute by shard(k)")
    s.execute("insert into r values (1,1),(2,2)")
    s.execute("select sum(v) from r")
    assert s.query("select count(*) from pg_stat_statements")[0][0] > 0
    assert s.query("select count(*) from pg_stat_query_phases")[0][0] > 0
    # a fault hit that must survive the reset
    s.execute("set fault_injection = on")
    s.execute("select pg_fault_inject('dn/dispatch', 'delay(1)', 'once')")
    before = s.query("select site, arms from pg_stat_faults")
    assert before

    # enough accumulation that post-reset counts are clearly smaller
    for _ in range(6):
        s.query("select sum(v) from r")
    pre = dict(s.query(
        "select phase, statements from pg_stat_query_phases"
    ))
    assert pre.get("execute", 0) >= 6, pre

    t0 = time.time()
    s.execute("select pg_stat_reset()")
    # only the reset statement itself may have re-accumulated
    assert s.query("select count(*) from pg_stat_statements")[0][0] <= 1
    post = dict(s.query(
        "select phase, statements from pg_stat_query_phases"
    ))
    assert post.get("execute", 0) <= 2 < pre["execute"], (pre, post)
    dml = s.query("select stat, value from pg_stat_dml")
    assert all(v == 0 for _stat, v in dml if _stat.startswith("cn."))
    # stats_reset stamped on the counters views
    resets = {
        r[0] for r in s.query("select stats_reset from pg_stat_dml")
    }
    assert all(ts >= t0 for ts in resets), resets
    # fault stats excluded (pg_fault_clear owns those)
    assert s.query("select site, arms from pg_stat_faults") == before
    c.close()


# ---------------------------------------------------------------------------
# otb_monitor --health / --logs over the coordinator wire
# ---------------------------------------------------------------------------


def test_otb_monitor_health_and_logs_subcommands(capsys):
    from opentenbase_tpu.cli import otb_monitor
    from opentenbase_tpu.net.server import ClusterServer

    c = Cluster(num_datanodes=2, shard_groups=16)
    c.log.emit("warning", "test", "monitor-sees-this", probe=7)
    srv = ClusterServer(c).start()
    try:
        rc = otb_monitor.main([
            "--health", f"127.0.0.1:{srv.port}",
            "--logs", f"127.0.0.1:{srv.port}",
            "--min-level", "warning",
        ])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "cn0 (coordinator): up" in out
        assert "gtm0 (gtm): up" in out
        assert "monitor-sees-this" in out
        assert "[WARNING]" in out
    finally:
        srv.stop()
        c.close()
