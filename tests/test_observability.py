"""Observability surface: system views, enriched pg_stat_statements,
per-operator distributed EXPLAIN ANALYZE, wait events, query phases,
and Chrome-trace export (SURVEY §5 — pg_stat_cluster_activity,
stormstats, explain_dist.c equivalents; obs/ package)."""

import json
import threading
import time

import pytest

from opentenbase_tpu.engine import Cluster


@pytest.fixture()
def sess():
    s = Cluster(num_datanodes=2, shard_groups=16).session()
    s.execute("create table t (k bigint, v text) distribute by shard(k)")
    s.execute("insert into t values (1,'a'),(2,'b'),(3,'c'),(4,'d')")
    return s


@pytest.fixture()
def join_sess(sess):
    sess.execute(
        "create table u (k bigint, w bigint) distribute by shard(k)"
    )
    sess.execute("insert into u values (1,10),(2,20),(3,30),(4,40)")
    return sess


def test_pgxc_node_view(sess):
    rows = sess.query(
        "select node_name, node_type from pgxc_node order by node_name"
    )
    names = [r[0] for r in rows]
    assert "cn0" in names and "dn0" in names and "gtm0" in names
    dn = [r for r in rows if r[1] == "datanode"]
    assert len(dn) == 2


def test_prepared_xacts_view(sess):
    sess.execute("begin")
    sess.execute("insert into t values (9,'z')")
    sess.execute("prepare transaction 'viewgid'")
    rows = sess.query("select gid from pg_prepared_xacts")
    assert rows == [("viewgid",)]
    sess.execute("commit prepared 'viewgid'")
    assert sess.query("select count(*) from pg_prepared_xacts")[0][0] == 0


def test_cluster_activity(sess):
    rows = sess.query(
        "select session_id, state from pg_stat_cluster_activity"
    )
    assert any(r[1] == "active" for r in rows)  # this very session


def test_stat_statements(sess):
    sess.query("select count(*) from t")
    sess.query("select count(*) from t")
    rows = sess.query(
        "select query, calls from pg_stat_statements where calls >= 2"
    )
    assert any("count(*) from t" in r[0] for r in rows)


def test_stat_statements_enriched(sess):
    sess.query("select v, count(*) from t group by v")
    sess.query("select v, count(*) from t group by v")
    rows = sess.query(
        "select calls, total_ms, plan_ms, exec_ms, min_ms, max_ms, "
        "mean_ms, stddev_ms from pg_stat_statements "
        "where query like '%group by v%'"
    )
    assert rows, "statement missing from pg_stat_statements"
    calls, total, plan, exc, mn, mx, mean, stddev = rows[0]
    assert calls >= 2
    assert total > 0 and plan > 0 and exc > 0
    assert 0 < mn <= mx <= total
    assert mn <= mean <= mx and stddev >= 0
    # plan + exec never exceed the whole
    assert plan + exc <= total + 1e-6


def test_shard_map_view(sess):
    rows = sess.query(
        "select node_index, count(*) from pgxc_shard_map group by node_index "
        "order by node_index"
    )
    assert [r[0] for r in rows] == [0, 1]
    assert sum(r[1] for r in rows) == 16


def test_stat_user_tables(sess):
    rows = sess.query(
        "select relname, sum(n_live_tup) from pg_stat_user_tables "
        "where relname = 't' group by relname"
    )
    assert rows == [("t", 4)]
    sess.execute("delete from t where k = 1")
    rows = sess.query(
        "select sum(n_live_tup), sum(n_total_tup) from pg_stat_user_tables "
        "where relname = 't'"
    )
    assert rows[0] == (3, 4)  # dead tuple retained until vacuum


def test_explain_analyze(sess):
    sess.execute("set enable_fused_execution = off")
    res = sess.execute(
        "explain analyze select v, count(*) from t group by v"
    )
    text = "\n".join(r[0] for r in res.rows)
    assert "Fragment 0 on dn0" in text and "Fragment 0 on dn1" in text
    assert "Total: rows=4" in text and "ms" in text


def test_explain_analyze_operator_tree(join_sess):
    """Host path: EXPLAIN (ANALYZE, VERBOSE) of a 2-DN sharded join
    prints a per-operator tree with rows/time aggregated across
    datanodes (min/max/avg like explain_dist.c) plus per-motion
    rows+bytes; VERBOSE adds the per-datanode breakdown."""
    s = join_sess
    s.execute("set enable_fused_execution = off")
    res = s.execute(
        "explain (analyze, verbose) select t.v, sum(u.w) from t "
        "join u on t.k = u.k group by t.v"
    )
    lines = [r[0] for r in res.rows]
    text = "\n".join(lines)
    # plan-node tree with per-node aggregation over both datanodes
    join_lines = [ln for ln in lines if "Join inner" in ln and "avg=" in ln]
    assert join_lines and "loops=2" in join_lines[0]
    scan_lines = [ln for ln in lines if "Scan t" in ln and "rows=" in ln]
    assert scan_lines and "min=" in scan_lines[0] and "max=" in scan_lines[0]
    # per-motion rows + bytes on the fragment header
    assert any("motion rows=" in ln and "bytes=" in ln for ln in lines)
    # VERBOSE: per-datanode rows under each operator
    assert "on dn0:" in text and "on dn1:" in text
    # the coordinator's merge side of the tree is reported too
    assert "Coordinator:" in text
    assert any("Total: rows=" in ln for ln in lines)


def test_explain_analyze_fused_phases(sess):
    """Fused path: EXPLAIN ANALYZE reports compile vs device-execute
    vs host-merge ms, and pg_stat_statements carries the same attribution."""
    res = sess.execute("explain analyze select count(*) from t")
    text = "\n".join(r[0] for r in res.rows)
    assert "Fused device execution:" in text, text
    assert "compile=" in text and "device=" in text
    assert "Total: rows=1" in text
    # the same attribution, per statement class, in pg_stat_statements
    # (the ledger columns took the place of pg_stat_fused's last_*_ms /
    # total_*_ms rows): device time and its split by span
    sess.query("select count(*) from t")
    row = sess.query(
        "select calls, device_ms, compile_ms, bind_ms, launch_ms, "
        "device_wait_ms, device_launches, device_syncs "
        "from pg_stat_statements where query = 'select count(*) from t'"
    )[0]
    assert row[0] >= 1 and row[1] > 0 and row[2] >= 0
    assert row[3] > 0 and row[4] > 0 and row[5] > 0
    assert row[6] >= 1 and row[7] >= 1
    events = {
        r[0] for r in sess.query("select event, detail from pg_stat_fused")
    }
    assert "fused_statements" in events
    # the MXU group reduce's launches by lane plan (ISSUE 28)
    assert {"mxu_plans_bounded", "mxu_plans_full"} <= events
    # joins the shape rule sent from a radix table to sort-merge (ISSUE 32)
    assert "radix_sized_out" in events
    # no timing row is left in the view: the spans and columns carry them
    assert not any(e.endswith(("_ms", "]")) for e in events)


def test_explain_analyze_fused_join(join_sess):
    """The fused DAG path (2-DN sharded join collapsed onto the device
    mesh) reports its compile/device/host split in EXPLAIN output."""
    s = join_sess
    res = s.execute(
        "explain (analyze, verbose) select t.v, sum(u.w) from t "
        "join u on t.k = u.k group by t.v"
    )
    text = "\n".join(r[0] for r in res.rows)
    if "Fused device execution:" not in text:
        pytest.skip("join plan not fused on this backend")
    assert "compile=" in text and "device=" in text
    assert "Total: rows=4" in text


def test_wait_event_lock(sess):
    """A session blocked on a row lock is visible to ANOTHER session
    through pg_stat_cluster_activity's wait columns, and the wait lands
    in pg_stat_wait_events afterwards."""
    c = sess.cluster
    holder = c.session()
    holder.execute("begin")
    holder.execute("update t set v = 'x' where k = 2")
    waiter = c.session()
    errs = []

    def blocked():
        try:
            waiter.execute("update t set v = 'y' where k = 2")
        except Exception as e:  # released by rollback below
            errs.append(e)

    th = threading.Thread(target=blocked)
    th.start()
    try:
        deadline = time.monotonic() + 10
        seen = None
        while time.monotonic() < deadline:
            rows = sess.query(
                "select session_id, wait_event_type, wait_event "
                "from pg_stat_cluster_activity "
                "where wait_event_type = 'Lock'"
            )
            if rows:
                seen = rows
                break
            time.sleep(0.02)
        assert seen, "blocked session never surfaced a Lock wait"
        assert seen[0][0] == waiter.session_id
        assert seen[0][2] == "tuple"
    finally:
        holder.execute("rollback")
        th.join(timeout=10)
    ev = sess.query(
        "select count, total_ms from pg_stat_wait_events "
        "where wait_event_type = 'Lock' and wait_event = 'tuple'"
    )
    assert ev and ev[0][0] >= 1 and ev[0][1] > 0


def test_wait_event_wlm_queue(sess):
    """A statement parked in a full WLM admission queue surfaces as a
    ResourceGroup wait (visible from a second session) and accumulates
    into pg_stat_wait_events + pg_stat_wlm.queue_wait_ms."""
    c = sess.cluster
    sess.execute("create resource group obsg with (concurrency=1, queue_depth=4)")
    a, b = c.session(), c.session()
    for x in (a, b):
        x.execute("set resource_group = obsg")
    started = threading.Event()
    errs = []

    def hold():
        try:
            started.set()
            a.execute("select pg_sleep(1.2)")
        except Exception as e:
            errs.append(e)

    def queued():
        try:
            started.wait(5)
            time.sleep(0.15)  # let the holder take the one slot
            b.execute("select count(*) from t")
        except Exception as e:
            errs.append(e)

    th_a = threading.Thread(target=hold)
    th_b = threading.Thread(target=queued)
    th_a.start()
    th_b.start()
    try:
        deadline = time.monotonic() + 10
        seen = None
        while time.monotonic() < deadline:
            rows = sess.query(
                "select session_id, state, wait_event from "
                "pg_stat_cluster_activity "
                "where wait_event_type = 'ResourceGroup'"
            )
            if rows:
                seen = rows
                break
            time.sleep(0.02)
        assert seen, "queued session never surfaced a ResourceGroup wait"
        assert seen[0][0] == b.session_id
        assert seen[0][1] == "queued"
        assert seen[0][2] == "obsg"
    finally:
        th_a.join(timeout=15)
        th_b.join(timeout=15)
    assert not errs, errs
    ev = sess.query(
        "select count from pg_stat_wait_events "
        "where wait_event_type = 'ResourceGroup' and wait_event = 'obsg'"
    )
    assert ev and ev[0][0] >= 1
    qw = sess.query(
        "select queue_wait_ms from pg_stat_wlm where group_name = 'obsg'"
    )
    assert qw and qw[0][0] > 0


def test_query_phases_view(sess):
    sess.query("select v, count(*) from t group by v")
    rows = sess.query(
        "select phase, statements, total_ms, p50_ms, p99_ms "
        "from pg_stat_query_phases"
    )
    phases = {r[0]: r for r in rows}
    for must in ("parse", "plan", "execute"):
        assert must in phases, (must, rows)
        assert phases[must][1] > 0
        assert phases[must][2] >= 0
    # percentiles come from the same histogram: p50 <= p99
    for r in rows:
        assert r[3] <= r[4] + 1e-9


def test_chrome_trace_export(join_sess, tmp_path):
    """trace_queries=on traces a query end to end; the export round-
    trips through json.load with well-nested span timestamps grouped by
    trace_id (per-node pids mean one pid now carries many statements);
    the pg_export_traces() admin function serves the same document over
    SQL (what the otb_trace CLI fetches)."""
    from opentenbase_tpu.obs.export import export_chrome_trace

    s = join_sess
    # host path: fragment + motion spans are the interesting content
    s.execute("set enable_fused_execution = off")
    s.execute("set trace_queries = on")
    s.query(
        "select t.v, sum(u.w) from t join u on t.k = u.k group by t.v"
    )
    s.execute("set trace_queries = off")
    path = tmp_path / "trace.json"
    export_chrome_trace(s.cluster, str(path))
    with open(path) as f:
        doc = json.load(f)
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert events, "no spans exported"
    # per-node pids: every coordinator span sits on the cn0 track
    # (the in-process GTM's grants render as a gtm0 track beside it),
    # and process_name metadata events name the tracks
    meta_names = {
        e["args"]["name"]: e["pid"]
        for e in doc["traceEvents"] if e.get("ph") == "M"
    }
    assert "cn0" in meta_names
    assert all(
        e["pid"] == meta_names["cn0"] for e in events
        if e["name"] == "query"
    )
    by_trace: dict = {}
    for e in events:
        tid = (e.get("args") or {}).get("trace_id")
        assert tid, e  # every exported span carries its trace identity
        by_trace.setdefault(tid, []).append(e)
    # each traced statement carries a root 'query' span enclosing the
    # rest of ITS trace
    traced = [
        evs for evs in by_trace.values()
        if any(e["name"] == "query" for e in evs)
    ]
    assert traced
    for evs in traced:
        root = next(e for e in evs if e["name"] == "query")
        lo, hi = root["ts"], root["ts"] + root["dur"]
        for e in evs:
            assert e["ts"] >= lo - 1000  # 1ms slack for clock rounding
            assert e["ts"] + e["dur"] <= hi + 1000
    # the join query's trace recorded real executor work under its root
    # (the trailing SET's trace is legitimately parse-only)
    join_names = [
        {e["name"] for e in evs} for evs in traced
        if any(
            e["name"] == "query" and "join" in (
                (e.get("args") or {}).get("query") or ""
            )
            for e in evs
        )
    ]
    assert join_names, "join query was not traced"
    names = join_names[0]
    assert any(n.startswith("fragment") for n in names), names
    assert any(n.startswith("motion") for n in names), names
    assert "plan" in names and "execute" in names
    # same document over the SQL surface
    via_sql = json.loads(
        s.query("select pg_export_traces(10)")[0][0]
    )
    assert via_sql["traceEvents"]


def test_trace_off_zero_span_allocations(sess):
    """With trace_queries=off and no EXPLAIN ANALYZE, a query allocates
    ZERO spans — the tracer must be free when disabled."""
    from opentenbase_tpu.obs.trace import Span

    sess.query("select count(*) from t")  # warm everything up
    before = Span.allocations
    sess.query("select v, count(*) from t group by v")
    sess.query("select count(*) from t where k > 1")
    assert Span.allocations == before


def test_explain_analyze_traces_without_guc(sess):
    """EXPLAIN ANALYZE always lands a trace in the ring, GUC off."""
    tracer = sess.cluster.tracer
    before = len(tracer)
    sess.execute("set enable_fused_execution = off")
    sess.execute("explain analyze select count(*) from t")
    assert len(tracer) == before + 1
    spans = tracer.last(1)[0].spans
    assert any(sp.cat == "fragment" for sp in spans)


def test_join_system_view_with_user_table(sess):
    # arbitrary SQL over system views: join against shard ownership
    rows = sess.query(
        "select n.node_name, t3.n_live_tup from pg_stat_user_tables t3 "
        "join pgxc_node n on t3.node_index = n.mesh_index "
        "where t3.relname = 't' order by n.node_name"
    )
    assert len(rows) == 2 and sum(r[1] for r in rows) == 4


def test_pg_stat_pallas_view():
    from opentenbase_tpu.engine import Cluster

    s = Cluster(num_datanodes=2, shard_groups=16).session()
    s.execute("create table pv (a bigint) distribute by shard(a)")
    s.execute("insert into pv values (1), (2), (3)")
    s.execute("set enable_pallas_scan = on")
    s.cluster._fused = None
    assert s.query("select count(*) from pv")[0][0] == 3
    rows = s.query("select program, state from pg_stat_pallas")
    assert any(st == "compiled" for _p, st in rows)
    assert not any(st == "demoted" for _p, st in rows)


# ---------------------------------------------------------------------------
# Cross-node distributed tracing (obs/tracectx.py): wire-propagated
# context, per-node span rings, trace_fetch merge, and the
# device-platform watchdog.
# ---------------------------------------------------------------------------


@pytest.fixture()
def dn_topology(tmp_path):
    """1 CN + 2 in-process DN servers over real sockets (the chaos-smoke
    topology): fragments ship over channels, so traces must stitch
    across a genuine wire."""
    from opentenbase_tpu.dn.server import DNServer
    from opentenbase_tpu.storage.replication import WalSender

    c = Cluster(num_datanodes=2, shard_groups=16,
                data_dir=str(tmp_path / "cn"))
    s = c.session()
    s.execute("set enable_fused_execution = off")
    s.execute("create table tt (k bigint, v bigint) distribute by shard(k)")
    s.execute("insert into tt values "
              + ",".join(f"({i},{i * 3})" for i in range(120)))
    sender = WalSender(c.persistence)
    dns = [
        DNServer(str(tmp_path / f"dn{n}"), sender.host, sender.port,
                 2, 16).start()
        for n in (0, 1)
    ]
    for n, dn in enumerate(dns):
        c.attach_datanode(n, "127.0.0.1", dn.port, pool_size=2,
                          rpc_timeout=60)
    try:
        yield c, s, dns
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


def _export(s, last=5):
    return json.loads(s.query(f"select pg_export_traces({last})")[0][0])


def _spans_by_trace(doc):
    by_trace: dict = {}
    for e in doc["traceEvents"]:
        if e.get("ph") != "X":
            continue
        tid = (e.get("args") or {}).get("trace_id")
        if tid:
            by_trace.setdefault(tid, []).append(e)
    return by_trace


def test_cross_node_trace_stitch(dn_topology):
    """One traced statement produces ONE merged Chrome trace holding
    spans from the CN, both DN server processes, and the GTM — all
    under one trace_id with parent/child edges intact across the
    wire (the acceptance shape)."""
    c, s, _dns = dn_topology
    s.execute("set trace_queries = on")
    s.query("select count(*), sum(v) from tt")
    s.execute("set trace_queries = off")
    doc = _export(s)
    names = {
        e["args"]["name"]: e["pid"]
        for e in doc["traceEvents"] if e.get("ph") == "M"
    }
    by_trace = _spans_by_trace(doc)
    stitched = [
        evs for evs in by_trace.values()
        if any(e["name"] == "query" and "count" in (
            (e.get("args") or {}).get("query") or "")
            for e in evs)
    ]
    assert stitched, "traced statement missing from the export"
    evs = stitched[0]
    pid_of = {v: k for k, v in names.items()}
    nodes = {pid_of[e["pid"]] for e in evs}
    assert {"cn0", "dn0", "dn1", "gtm0"} <= nodes, nodes
    # DN-side span content: fragment execution attributed per node
    dn_spans = [e for e in evs if pid_of[e["pid"]].startswith("dn")]
    assert any(e["name"] == "exec_fragment" for e in dn_spans)
    # GTM-side: the statement's snapshot grant
    gtm_spans = [e for e in evs if pid_of[e["pid"]] == "gtm0"]
    assert any(e["cat"] == "gts" for e in gtm_spans)
    # parent/child edges: every parent_span_id resolves to a span_id
    # present in the SAME trace (the root has none)
    span_ids = {
        e["args"].get("span_id") for e in evs
    } - {None}
    for e in evs:
        parent = e["args"].get("parent_span_id")
        if parent is not None:
            assert parent in span_ids, (e["name"], parent)


def test_trace_chaos_retry_failover(dn_topology):
    """crash_node -> retry -> failover under tracing: the merged trace
    carries the CN root, the failed attempt span (attempt=1), the
    retry child span (attempt=2), and the failover-tagged fragment
    span — the satellite's chaos shape."""
    from opentenbase_tpu import fault

    c, s, dns = dn_topology
    want = s.query("select count(*), sum(v) from tt")
    s.execute("set fault_injection = on")
    s.execute("set fragment_retries = 1")
    s.execute("set fragment_retry_backoff_ms = 5")
    s.execute("select pg_fault_inject('dn/exec_fragment', 'crash_node',"
              " 'node=1, once')")
    s.execute("set trace_queries = on")
    assert s.query("select count(*), sum(v) from tt") == want
    s.execute("set trace_queries = off")
    s.execute("select pg_fault_clear()")
    dns[1]._revive()
    fault.reset_stats()
    doc = _export(s)
    by_trace = _spans_by_trace(doc)
    chaos = [
        evs for evs in by_trace.values()
        if any(e["name"].startswith("fragment") and
               e["cat"] == "attempt" for e in evs)
    ]
    assert chaos, "no attempt spans in any trace"
    evs = chaos[0]
    assert any(e["name"] == "query" for e in evs)  # CN root
    attempts = {
        e["args"]["attempt"] for e in evs if e["cat"] == "attempt"
    }
    assert 1 in attempts and 2 in attempts, attempts  # fail + retry
    finals = [
        e for e in evs
        if e["cat"] == "fragment" and e["args"].get("failover")
    ]
    assert finals and finals[0]["args"]["failover"] == "local"
    assert finals[0]["args"]["attempt"] >= 2


def test_trace_off_zero_allocations_cross_process(dn_topology):
    """trace_queries=off allocates ZERO spans on EVERY node: the CN's
    Span counter stays flat, no ``_trace`` header crosses the wire,
    and the DN/GTM span rings stay empty (SpanRing.allocations is the
    remote half of the zero-overhead contract)."""
    from opentenbase_tpu.obs.trace import Span
    from opentenbase_tpu.obs.tracectx import SpanRing

    c, s, dns = dn_topology
    s.query("select count(*) from tt")  # warm everything up
    span_before = Span.allocations
    ring_before = SpanRing.allocations
    dn_rings = [len(dn.span_ring) for dn in dns]
    s.query("select count(*), sum(v) from tt")
    s.query("select count(*) from tt where k > 5")
    assert Span.allocations == span_before
    assert SpanRing.allocations == ring_before
    assert [len(dn.span_ring) for dn in dns] == dn_rings
    gtm_ring = c.gts.span_ring
    assert gtm_ring.rows() == gtm_ring.rows()  # ring readable, and...
    assert SpanRing.allocations == ring_before  # ...reads allocate 0


def test_device_platform_watchdog(tmp_path):
    """A cluster told to expect TPU that answers a fused run from CPU
    is observable within ONE statement: the demotion counter moves,
    pg_cluster_logs carries the elog(warning, device, ...), and
    pg_cluster_health's cn0 row shows the actually-used platform."""
    s = Cluster(num_datanodes=2, shard_groups=16).session()
    s.execute("create table wd (k bigint, v bigint) distribute by shard(k)")
    s.execute("insert into wd values (1,10),(2,20),(3,30)")
    s.execute("set expected_device_platform = tpu")
    assert s.query("select count(*) from wd")[0][0] == 3  # fused on CPU
    fx = s.cluster._fused
    assert fx is not None and fx.platform_demotions >= 1
    st = dict(s.query("select event, detail from pg_stat_fused"))
    assert st.get("last_run_platform") == "cpu"
    assert int(st.get("platform_demotions", 0)) >= 1
    h = {r[0]: r for r in s.query("select * from pg_cluster_health")}
    assert h["cn0"][7] == "cpu"          # device_platform column
    logs = s.query("select pg_cluster_logs('warning')")
    assert any(
        r[3] == "device" and "demoted" in r[4] for r in logs
    ), logs
    # the exporter renders the monotone counter
    from opentenbase_tpu.obs.exporter import render_cluster_metrics

    text = render_cluster_metrics(s.cluster)
    assert "otb_platform_demotions_total" in text
    line = [
        ln for ln in text.splitlines()
        if ln.startswith("otb_platform_demotions_total")
    ][0]
    assert float(line.rpartition(" ")[2]) >= 1
    # RESET must switch the watchdog off (restore the env-inferred
    # expectation) without recycling the executor
    s.execute("reset expected_device_platform")
    before = fx.platform_demotions
    s.query("select count(*) from wd where k > 1")
    assert fx.platform_demotions == before
