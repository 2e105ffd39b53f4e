"""Elastic cluster (rebalance/): online ADD/REMOVE NODE with crash-safe
background shard rebalancing, plus cold/hot node groups.

The contract under test is the reference's PgxcMoveData_* + pgxc_group
pair, rebuilt as a journaled background service: ADD NODE under live
traffic fails zero statements and lands within 10% of byte-even;
REMOVE NODE drains the victim to zero owned shard groups; a coordinator
crash at ANY phase of a move (mid-COPYING, mid-FLIP, mid-journal-write)
recovers to the exact journaled routing and finishes the plan in the
background; and a table placed TO GROUP on a cold group never stores or
scans a row on the hot serving set."""

import threading
import time

import numpy as np
import pytest

from opentenbase_tpu import fault
from opentenbase_tpu.engine import Cluster, SQLError
from opentenbase_tpu.rebalance import planner


@pytest.fixture(autouse=True)
def _clean_faults():
    fault.clear()
    yield
    fault.clear()


def _seed(c, n=2000, table="t"):
    s = c.session()
    s.execute(
        f"create table {table} (k bigint, v bigint) "
        "distribute by shard(k)"
    )
    for lo in range(0, n, 1000):
        vals = ",".join(
            f"({i}, {i * 7})" for i in range(lo, min(lo + 1000, n))
        )
        s.execute(f"insert into {table} values {vals}")
    return s


def _owners(c):
    return set(int(x) for x in np.unique(c.shardmap.map))


# ---------------------------------------------------------------------------
# planner: minimal motion, byte-even targets
# ---------------------------------------------------------------------------

def test_planner_add_node_moves_minimum_to_even(tmp_path):
    c = Cluster(num_datanodes=2, shard_groups=32)
    _seed(c, 2000)
    plan = planner.plan_add_node(c.shardmap, 16.0, 2, [0, 1])
    assert plan.moves, "a loaded 2-node map must shed onto the newcomer"
    assert all(dst == 2 for _s, dst in plan.moves.values())
    assert all(src in (0, 1) for src, _d in plan.moves.values())
    # minimal motion: never more than the byte-even share of the groups
    assert len(plan.moves) <= c.shardmap.num_shards // 3 + 1
    after = plan.node_bytes_after()
    mean = sum(after.values()) / len(after)
    assert max(abs(b - mean) for b in after.values()) <= mean * 0.35


def test_planner_remove_node_drains_everything(tmp_path):
    c = Cluster(num_datanodes=3, shard_groups=32)
    _seed(c, 1500)
    victim_shards = c.shardmap.shards_on_node(2)
    plan = planner.plan_remove_node(c.shardmap, 16.0, 2, [0, 1])
    assert set(plan.moves) == set(int(s) for s in victim_shards)
    assert all(src == 2 and dst in (0, 1)
               for src, dst in plan.moves.values())


# ---------------------------------------------------------------------------
# ADD NODE online: live traffic, zero failed statements, byte-even
# ---------------------------------------------------------------------------

def test_add_node_under_traffic_zero_failures(tmp_path):
    c = Cluster(num_datanodes=2, shard_groups=32,
                data_dir=str(tmp_path))
    _seed(c, 2000)
    stop = threading.Event()
    acked, failures = [], []

    def writer():
        ws = c.session()
        i = 0
        while not stop.is_set():
            i += 1
            try:
                ws.execute(
                    f"insert into t values ({10_000 + i}, {i})"
                )
                acked.append(i)
            except Exception as e:  # the acceptance gate: must be none
                failures.append(repr(e))
            time.sleep(0.002)

    th = threading.Thread(target=writer, daemon=True)
    th.start()
    time.sleep(0.1)
    s = c.session()
    s.execute("alter cluster add node dn2 wait")
    stop.set()
    th.join(timeout=30)
    assert failures == []
    assert _owners(c) == {0, 1, 2}
    verdict, spread = c.rebalance.balance_verdict()
    assert verdict == "balanced" and spread <= 10.0, (verdict, spread)
    # zero lost acked writes, zero duplicates
    assert s.query("select count(*) from t") == [(2000 + len(acked),)]
    assert s.query(
        "select count(*) from (select k from t group by k "
        "having count(*) > 1) d"
    ) == [(0,)]
    # the move is observable: every wave reached done with rows copied
    hist = c.rebalance.status_rows()
    assert hist and all(m.phase == "done" for m in hist)
    assert sum(m.rows_copied for m in hist) > 0


def test_remove_node_drains_to_zero_owned_shards(tmp_path):
    c = Cluster(num_datanodes=3, shard_groups=32,
                data_dir=str(tmp_path))
    s = _seed(c, 1500)
    # a locator-placed table rides along: its rows must re-route too
    s.execute(
        "create table rr (a bigint) distribute by roundrobin"
    )
    s.execute("insert into rr values " + ",".join(
        f"({i})" for i in range(300)
    ))
    s.execute("alter cluster remove node dn2 wait")
    assert not bool((c.shardmap.map == 2).any())
    assert not c.nodes.has("dn2")
    assert 2 not in c.stores
    assert s.query("select count(*) from t") == [(1500,)]
    assert s.query("select count(*) from rr") == [(300,)]
    assert all(2 not in c.catalog.get(n).node_indices
               for n in c.catalog.table_names())


# ---------------------------------------------------------------------------
# satellite 1 — shard-map durability: move, crash, recover, routing
# unchanged
# ---------------------------------------------------------------------------

def test_move_then_crash_recovers_identical_routing(tmp_path):
    c = Cluster(num_datanodes=2, shard_groups=32,
                data_dir=str(tmp_path))
    s = _seed(c, 1200)
    s.execute("alter cluster add node dn2 wait")
    want_map = c.shardmap.map.copy()
    epoch = c.catalog_epoch
    pre = s.query("select k, v from t order by k")
    # abandon without checkpoint: the D-records alone must carry the map
    r = Cluster.recover(str(tmp_path), num_datanodes=2, shard_groups=32)
    rs = r.session()
    assert rs.query("select pg_rebalance_wait()")[0][0] == "idle"
    assert np.array_equal(r.shardmap.map, want_map)
    assert r.catalog_epoch >= epoch  # the flip bumped it durably
    assert rs.query("select k, v from t order by k") == pre
    # point lookups route through the recovered map (not a full scan)
    assert rs.query("select v from t where k = 17") == [(17 * 7,)]


# ---------------------------------------------------------------------------
# crash-safety: coordinator death at every failpoint resumes the plan
# ---------------------------------------------------------------------------

def _crash_resume(tmp_path, site, spec="once"):
    c = Cluster(num_datanodes=2, shard_groups=32,
                data_dir=str(tmp_path))
    s = _seed(c, 1500)
    fault.inject(site, "error", spec)
    # background (no WAIT): the mover thread dies like a crashed
    # coordinator — no cleanup, no abort records
    s.execute("alter cluster add node dn2")
    assert c.rebalance.wait(60)
    fault.clear(site)
    assert any(m.phase == "crashed" for m in c.rebalance.status_rows())
    journaled = {
        rbid: dict(rec) for rbid, rec in c.rebalance._journaled.items()
    }
    assert journaled, "the begin record must precede any copying"
    r = Cluster.recover(str(tmp_path), num_datanodes=2, shard_groups=32)
    rs = r.session()
    state = rs.query("select pg_rebalance_wait(60)")[0][0]
    assert state == "idle"
    # the resumed plan completed exactly: every journaled move satisfied
    for rec in journaled.values():
        for sid, (_src, dst) in rec["moves"].items():
            assert int(r.shardmap.map[int(sid)]) == int(dst)
    assert _owners(r) == {0, 1, 2}
    assert rs.query("select count(*) from t") == [(1500,)]
    assert rs.query(
        "select count(*) from (select k from t group by k "
        "having count(*) > 1) d"
    ) == [(0,)]
    return r, rs


def test_crash_mid_copying_resumes(tmp_path):
    _crash_resume(tmp_path, "rebalance/copy")


def test_crash_mid_flip_resumes(tmp_path):
    _crash_resume(tmp_path, "rebalance/flip")


def test_crash_mid_journal_write_resumes(tmp_path):
    _crash_resume(tmp_path, "rebalance/journal")


def test_checkpoint_mid_copy_then_restore(tmp_path):
    """A checkpoint taken while copy chunks are live (invisible pending
    rows on the destination) must restore to a state the resume can
    finish: the pendings are journaled as prepared writes, aborted on
    recovery, and the plan re-runs."""
    c = Cluster(num_datanodes=2, shard_groups=32,
                data_dir=str(tmp_path))
    s = _seed(c, 3000)
    # shrink chunks so one wave spans several: the crash then happens
    # BETWEEN chunks of the same wave, with earlier chunks still live
    c.rebalance.CHUNK_ROWS = 128
    fault.inject("rebalance/copy", "error", "after(2)")
    s.execute("alter cluster add node dn2")
    assert c.rebalance.wait(60)
    fault.clear()
    assert any(m.phase == "crashed" for m in c.rebalance.status_rows())
    assert c.rebalance._live, "crash between chunks leaves live pendings"
    c.persistence.checkpoint()  # snapshots the pendings via copy_gate
    r = Cluster.recover(str(tmp_path), num_datanodes=2, shard_groups=32)
    rs = r.session()
    assert rs.query("select pg_rebalance_wait(60)")[0][0] == "idle"
    assert _owners(r) == {0, 1, 2}
    assert rs.query("select count(*) from t") == [(3000,)]
    assert rs.query(
        "select count(*) from (select k from t group by k "
        "having count(*) > 1) d"
    ) == [(0,)]


# ---------------------------------------------------------------------------
# seeded chaos schedules (satellite 3): coordinator killed mid-COPYING
# and mid-FLIP under live traffic
# ---------------------------------------------------------------------------

def test_chaos_schedule_kill_mid_copying(tmp_path):
    from opentenbase_tpu.fault.schedule import run_rebalance_schedule

    v = run_rebalance_schedule(1101, str(tmp_path / "w"), "copying")
    assert v["crashed_mid_move"], v
    assert v["violations"] == [], v
    assert v["chaos_gate"] == "ok"


def test_chaos_schedule_kill_mid_flip(tmp_path):
    from opentenbase_tpu.fault.schedule import run_rebalance_schedule

    v = run_rebalance_schedule(1102, str(tmp_path / "w"), "flip")
    assert v["crashed_mid_move"], v
    assert v["violations"] == [], v
    assert v["chaos_gate"] == "ok"


# ---------------------------------------------------------------------------
# cold/hot node groups: placement, routing isolation, durability
# ---------------------------------------------------------------------------

def _cold_cluster(tmp_path):
    c = Cluster(num_datanodes=4, shard_groups=32,
                data_dir=str(tmp_path))
    s = c.session()
    s.execute("create node group cold_g with (dn2, dn3) cold")
    s.execute(
        "create table coldt (k bigint, v bigint) "
        "distribute by hash(k) to group cold_g"
    )
    s.execute("insert into coldt values " + ",".join(
        f"({i}, {i})" for i in range(400)
    ))
    s.execute(
        "create table hott (k bigint, v bigint) "
        "distribute by shard(k)"
    )
    s.execute("insert into hott values " + ",".join(
        f"({i}, {i})" for i in range(400)
    ))
    return c, s


def test_cold_group_tables_never_touch_hot_nodes(tmp_path):
    c, s = _cold_cluster(tmp_path)
    meta = c.catalog.get("coldt")
    assert sorted(meta.node_indices) == [2, 3]
    assert sorted(meta.locator.node_indices) == [2, 3]
    # physical isolation: not one cold row on a hot node
    for hot in (0, 1):
        assert "coldt" not in c.stores.get(hot, {})
    n2 = c.stores[2]["coldt"].nrows
    n3 = c.stores[3]["coldt"].nrows
    assert n2 + n3 == 400 and n2 > 0 and n3 > 0
    assert s.query("select count(*) from coldt") == [(400,)]
    # planner isolation: the scan's fragments name only cold nodes, and
    # EXPLAIN surfaces the group the scan resolved to
    lines = [r[0] for r in s.query(
        "explain select sum(v) from coldt where k < 100"
    )]
    frag = [ln for ln in lines if "node group:" in ln]
    assert frag and all("cold_g (cold)" in ln for ln in frag), lines
    # SHARD distribution is global-map routed: TO GROUP must be refused
    with pytest.raises(SQLError, match="SHARD.*GROUP"):
        s.execute(
            "create table bad (k bigint) "
            "distribute by shard(k) to group cold_g"
        )


def test_cold_group_placement_survives_recovery(tmp_path):
    c, s = _cold_cluster(tmp_path)
    c.persistence.checkpoint()  # exercise the checkpointed-path too
    s.execute("insert into coldt values (9001, 1)")
    r = Cluster.recover(str(tmp_path), num_datanodes=4, shard_groups=32)
    rs = r.session()
    meta = r.catalog.get("coldt")
    assert sorted(meta.node_indices) == [2, 3]
    # the LOCATOR's copy restored too — hash routing must not silently
    # fall back to the fresh-create full node set
    assert sorted(meta.locator.node_indices) == [2, 3]
    g = r.nodes.group_of_index(2)
    assert g is not None and g.name == "cold_g" and g.kind == "cold"
    assert rs.query("select count(*) from coldt") == [(401,)]
    for hot in (0, 1):
        assert "coldt" not in r.stores.get(hot, {})
    # post-recovery inserts keep routing inside the group
    rs.execute("insert into coldt values (9002, 2)")
    assert (r.stores[2]["coldt"].nrows
            + r.stores[3]["coldt"].nrows) == 402


# ---------------------------------------------------------------------------
# satellite 2 — observability: view, exporter series, EXPLAIN groups
# ---------------------------------------------------------------------------

def test_pg_stat_rebalance_and_exporter_series(tmp_path):
    from opentenbase_tpu.obs.exporter import render_cluster_metrics

    c = Cluster(num_datanodes=2, shard_groups=32,
                data_dir=str(tmp_path))
    s = _seed(c, 1200)
    s.execute("alter cluster add node dn2 wait")
    rows = s.query(
        "select rbid, kind, src, dst, shards, phase, rows_copied "
        "from pg_stat_rebalance"
    )
    assert rows and all(r[1] == "add_node" and r[5] == "done"
                        for r in rows)
    assert all(r[3] == 2 for r in rows)  # every wave lands on dn2
    assert sum(r[6] for r in rows) > 0
    assert sum(r[4] for r in rows) == len(
        [x for x in c.shardmap.map if x == 2]
    )
    text = render_cluster_metrics(c)
    assert "otb_rebalance_moves_total" in text
    assert "otb_rebalance_rows_copied_total" in text
    assert "otb_rebalance_active 0" in text


def test_pgxc_group_view(tmp_path):
    c, s = _cold_cluster(tmp_path)
    rows = s.query(
        "select group_name, kind, members from pgxc_group"
    )
    assert rows == [("cold_g", "cold", "dn2,dn3")]


# ---------------------------------------------------------------------------
# removed-node fencing: a stale plan must fail retryably, not read zero
# rows
# ---------------------------------------------------------------------------

def test_stale_topology_is_retryable_not_empty(tmp_path):
    from opentenbase_tpu.executor.dist import DistExecutor, StaleTopology

    c = Cluster(num_datanodes=2, shard_groups=32)
    ex = DistExecutor(c.catalog, c.stores, c.gts.snapshot_ts())
    with pytest.raises(StaleTopology) as ei:
        ex._stores(7)
    assert ei.value.sqlstate == "72001"
    assert "retry" in str(ei.value)


# ---------------------------------------------------------------------------
# DN-process participant: the same copy/flip halves over the channel
# ---------------------------------------------------------------------------

def test_dn_process_rebalance_apply_finalize(tmp_path):
    """A DN server process lands a copy chunk invisible
    (rebalance_apply: xmin = PENDING_TS) and stamps it visible at the
    flip timestamp (rebalance_finalize) — the PgxcMoveData bulk-load /
    flip halves on the real-topology path."""
    import os
    import subprocess
    import sys

    from opentenbase_tpu.plan import serde
    from opentenbase_tpu.storage.replication import WalSender

    c = Cluster(num_datanodes=2, shard_groups=32,
                data_dir=str(tmp_path / "cn"))
    s = _seed(c, 100)
    sender = WalSender(c.persistence)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
    )
    p = subprocess.Popen(
        [
            sys.executable, "-m", "opentenbase_tpu.dn.server",
            "--data-dir", str(tmp_path / "dn0"),
            "--wal-host", sender.host,
            "--wal-port", str(sender.port),
            "--num-datanodes", "2",
            "--shard-groups", "32",
        ],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=env,
    )
    try:
        line = p.stdout.readline().strip()
        assert line.startswith("READY "), line
        c.attach_datanode(
            0, "127.0.0.1", int(line.split()[1]),
            pool_size=2, rpc_timeout=300,
        )
        # the fused path aggregates over coordinator-local stores; the
        # distributed path is the one that dispatches to the DN process
        s.execute("set enable_fused_execution = off")
        assert s.query("select count(*) from t") == [(100,)]
        from opentenbase_tpu.plan import logical as L

        meta = c.catalog.get("t")
        src = c.stores[0]["t"]
        batch = src.take_batch(np.arange(3, dtype=np.int64))
        wire = serde.batch_to_wire(batch, [
            L.OutCol(k, ty, None) for k, ty in meta.schema.items()
        ])
        resp = c.dn_channels[0].rpc({
            "op": "rebalance_apply", "node": 0, "table": "t",
            "batch": wire,
        })
        assert resp.get("ok"), resp
        # landed invisible: remote scans must not see the pending rows
        assert s.query("select count(*) from t") == [(100,)]
        resp2 = c.dn_channels[0].rpc({
            "op": "rebalance_finalize", "node": 0, "table": "t",
            "start": resp["start"], "end": resp["end"],
            "commit_ts": int(c.gts.get_gts()),
        })
        assert resp2.get("ok"), resp2
        # the real flip bumps table versions after stamping; do the
        # same so the versioned result cache can't serve the pre-flip
        # count
        c.bump_table_versions({"t"})
        assert s.query("select count(*) from t") == [(103,)]
    finally:
        try:
            c.detach_datanode(0)
        except Exception:
            pass
        try:
            if p.poll() is None:
                p.terminate()
                try:
                    p.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(timeout=5)
        except Exception:
            pass
        try:
            sender.stop()
        except Exception:
            pass
        c.close()


def test_rebalance_rate_limit_guc(tmp_path):
    from opentenbase_tpu import config

    assert "rebalance_rate_limit" in config.GUCS
    c = Cluster(num_datanodes=2, shard_groups=32)
    assert c.rebalance._rate_limit() == config.GUCS[
        "rebalance_rate_limit"
    ][1]
    c.conf_gucs["rebalance_rate_limit"] = 1234
    assert c.rebalance._rate_limit() == 1234
