"""Interval/range partitioning tests — the reference's
PARTITION BY RANGE ... BEGIN/STEP/PARTITIONS grammar (gram.y:4172) plus
routing, pruning, DML fanout, and durability."""

import pytest

from opentenbase_tpu.engine import Cluster, SQLError


@pytest.fixture()
def c():
    return Cluster(num_datanodes=2, shard_groups=32)


def mk(c, sess=None):
    s = sess or c.session()
    s.execute(
        "create table m (id bigint, ts bigint, v text)"
        " partition by range (ts) begin (0) step (100) partitions (4)"
        " distribute by shard(id)"
    )
    s.execute(
        "insert into m values (1, 10,'a'),(2, 110,'b'),(3, 250,'c'),(4, 399,'d')"
    )
    return s


def test_insert_routes_and_select_unions(c):
    s = mk(c)
    rows = s.query("select id, ts, v from m order by id")
    assert rows == [(1, 10, "a"), (2, 110, "b"), (3, 250, "c"), (4, 399, "d")]
    # physically split: children hold the right slices
    assert s.query("select count(*) from m$p0") == [(1,)]
    assert s.query("select count(*) from m$p2") == [(1,)]


def test_out_of_range_and_null_keys_rejected(c):
    s = mk(c)
    with pytest.raises(SQLError, match="out of range"):
        s.execute("insert into m values (9, 400, 'x')")
    with pytest.raises(SQLError, match="null partition key"):
        s.execute("insert into m values (9, null, 'x')")


def test_where_pruning_correctness(c):
    s = mk(c)
    # equality and ranges still return exact answers through the pruning
    assert s.query("select v from m where ts = 250") == [("c",)]
    assert [r[0] for r in s.query(
        "select v from m where ts >= 100 and ts < 300 order by ts"
    )] == ["b", "c"]
    assert s.query("select v from m where ts > 1000") == []


def test_pruning_skips_partitions(c):
    """The rewritten plan only touches surviving children."""
    s = mk(c)
    rows = s.query("explain select v from m where ts = 250")
    text = "\n".join(r[0] for r in rows)
    assert "m$p2" in text
    assert "m$p0" not in text and "m$p3" not in text


def test_aggregate_and_join_over_partitions(c):
    s = mk(c)
    s.execute("create table ref (id bigint, tag text) distribute by shard(id)")
    s.execute("insert into ref values (1,'one'),(3,'three')")
    assert s.query("select count(*), max(ts) from m") == [(4, 399)]
    rows = s.query(
        "select m.v, ref.tag from m join ref on m.id = ref.id order by m.id"
    )
    assert rows == [("a", "one"), ("c", "three")]


def test_update_delete_fanout_atomic(c):
    s = mk(c)
    assert s.execute("update m set v = 'upd' where ts < 200").rowcount == 2
    assert s.query("select v from m where ts = 10") == [("upd",)]
    assert s.execute("delete from m where ts >= 300").rowcount == 1
    assert s.query("select count(*) from m") == [(3,)]
    # explicit txn spanning partitions rolls back atomically
    s.execute("begin")
    s.execute("delete from m")
    assert s.query("select count(*) from m") == [(0,)]
    s.execute("rollback")
    assert s.query("select count(*) from m") == [(3,)]


def test_truncate_and_drop_parent(c):
    s = mk(c)
    s.execute("truncate table m")
    assert s.query("select count(*) from m") == [(0,)]
    s.execute("insert into m values (1, 50, 'z')")
    s.execute("drop table m")
    with pytest.raises(Exception):
        s.query("select * from m")
    assert "m" not in c.partitions


def test_calendar_month_partitions(c):
    s = c.session()
    s.execute(
        "create table ev (id bigint, at timestamp)"
        " partition by range (at) begin ('2024-01-01') step (1 month)"
        " partitions (3) distribute by shard(id)"
    )
    s.execute(
        "insert into ev values (1,'2024-01-15 12:00:00'),"
        "(2,'2024-02-29 23:59:59'),(3,'2024-03-31 00:00:00')"
    )
    assert s.query("select count(*) from ev$p0") == [(1,)]
    assert s.query("select count(*) from ev$p1") == [(1,)]
    assert s.query("select count(*) from ev$p2") == [(1,)]
    with pytest.raises(SQLError, match="out of range"):
        s.execute("insert into ev values (4,'2024-04-01 00:00:00')")


def test_pg_partitions_view(c):
    s = mk(c)
    rows = s.query(
        "select partition, range_lo, range_hi, n_live_tup from pg_partitions"
        " where parent = 'm' order by index"
    )
    assert rows == [
        ("m$p0", 0, 100, 1), ("m$p1", 100, 200, 1),
        ("m$p2", 200, 300, 1), ("m$p3", 300, 400, 1),
    ]


def test_partitioned_recovery(tmp_path):
    c = Cluster(num_datanodes=2, shard_groups=32, data_dir=str(tmp_path))
    s = mk(c, c.session())
    s.execute("delete from m where ts = 110")

    r = Cluster.recover(str(tmp_path), num_datanodes=2, shard_groups=32)
    rs = r.session()
    assert "m" in r.partitions
    assert [x[0] for x in rs.query("select id from m order by id")] == [1, 3, 4]
    rs.execute("insert into m values (5, 120, 'e')")  # routing still works
    assert rs.query("select count(*) from m$p1") == [(1,)]


def test_partitioned_recovery_from_checkpoint(tmp_path):
    c = Cluster(num_datanodes=2, shard_groups=32, data_dir=str(tmp_path))
    mk(c, c.session())
    c.persistence.checkpoint()
    r = Cluster.recover(str(tmp_path), num_datanodes=2, shard_groups=32)
    assert "m" in r.partitions
    assert r.session().query("select count(*) from m") == [(4,)]


def test_subquery_over_partitioned_table(c):
    s = mk(c)
    rows = s.query(
        "select id from m where ts = (select max(ts) from m)"
    )
    assert rows == [(4,)]


def test_timezone_independent_timestamp_boundaries():
    """Boundary/routing math must treat naive literals as UTC (storage
    is naive-UTC µs), regardless of the host timezone."""
    import os
    import subprocess
    import sys

    code = (
        "import sys; sys.path.insert(0, '/root/repo')\n"
        "from opentenbase_tpu.engine import Cluster\n"
        "c = Cluster(num_datanodes=1, shard_groups=8)\n"
        "s = c.session()\n"
        "s.execute(\"create table ev (id bigint, at timestamp)"
        " partition by range (at) begin ('2024-01-01') step (1 month)"
        " partitions (2) distribute by shard(id)\")\n"
        "s.execute(\"insert into ev values (1,'2024-01-01 02:00:00')\")\n"
        "assert s.query(\"select id from ev where at = '2024-01-01 02:00:00'\") == [(1,)]\n"
        "print('TZ-OK')\n"
    )
    env = dict(os.environ, TZ="America/New_York", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env
    )
    assert "TZ-OK" in out.stdout, out.stderr[-800:]


def test_update_partition_key_rejected(c):
    s = mk(c)
    with pytest.raises(SQLError, match="partition key"):
        s.execute("update m set ts = 250 where id = 1")
    # non-key updates still fine
    s.execute("update m set v = 'ok' where id = 1")


def test_dml_where_subquery_over_parent(c):
    s = mk(c)
    assert s.execute(
        "delete from m where ts = (select max(ts) from m)"
    ).rowcount == 1
    assert s.query("select count(*) from m") == [(3,)]


def test_drop_child_directly_rejected(c):
    s = mk(c)
    with pytest.raises(SQLError, match="partition of"):
        s.execute("drop table m$p0")
    assert s.query("select count(*) from m") == [(4,)]


def test_dollar_name_not_treated_as_child(tmp_path):
    c = Cluster(num_datanodes=2, shard_groups=32, data_dir=str(tmp_path))
    s = c.session()
    s.execute(
        "create table a (id bigint, ts bigint) partition by range (ts)"
        " begin (0) step (10) partitions (2) distribute by shard(id)"
    )
    s.execute("create table a$pxy (id bigint, v text) distribute by shard(id)")
    s.execute("insert into a$pxy values (1,'own-dict')")

    r = Cluster.recover(str(tmp_path), num_datanodes=2, shard_groups=32)
    assert r.session().query("select v from a$pxy") == [("own-dict",)]
