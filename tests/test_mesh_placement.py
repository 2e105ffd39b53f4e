"""A redistribute targets a table's own placement (plan/distribute.py,
catalog/locator.route_by_table, executor/dist.py, dn/server.py,
executor/fused_dag.py): the planner alone, the one routing formula on
the host and in the device program against ``Locator.route_insert``,
TPC-H Q3 over the wire on four datanodes over a four-device mesh against
the benchmark's plain reference, the three executors on one plan, a
moved shard group, the one-device exchange budget, and the
``fused.exchange`` span with its ledger columns."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if os.path.join(ROOT, "benchmarks") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from opentenbase_tpu.catalog.distribution import DistStrategy  # noqa: E402
from opentenbase_tpu.engine import Cluster  # noqa: E402
from opentenbase_tpu.plan import logical as L  # noqa: E402
from opentenbase_tpu.plan.analyze import analyze_statement  # noqa: E402
from opentenbase_tpu.plan.distribute import (  # noqa: E402
    RemoteSource, distribute_statement, motion_route,
)
from opentenbase_tpu.plan.optimize import optimize_statement  # noqa: E402
from opentenbase_tpu.sql.parser import parse  # noqa: E402

SF30 = {"customer": 4_500_000, "orders": 45_000_000,
        "lineitem": 180_000_000}


def plan_of(cluster, sql):
    sp = optimize_statement(
        analyze_statement(parse(sql)[0], cluster.catalog), cluster.catalog
    )
    return distribute_statement(sp, cluster.catalog)


def scans(node):
    """Tables scanned inside one fragment (RemoteSources not followed)."""
    if isinstance(node, L.Scan):
        return {node.table}
    if isinstance(node, RemoteSource):
        return set()
    return set().union(*(scans(c) for c in node.children()), set())


def shipped_table(root):
    """The table a motion fragment ships as it is (a scan under filters
    and projections), or None when it ships a join's output."""
    while isinstance(root, (L.Filter, L.Project)):
        root = root.child
    return root.table if isinstance(root, L.Scan) else None


def has_aggregate(node) -> bool:
    if isinstance(node, L.Aggregate):
        return True
    if isinstance(node, RemoteSource):
        return False
    return any(has_aggregate(c) for c in node.children())


def mesh4(cluster, devices: int = 4):
    """The cluster's fused executor on the first four virtual devices:
    one datanode a device, as the four-chip deployment."""
    import jax

    from opentenbase_tpu.executor.fused import FusedExecutor, build_mesh

    assert cluster._fused is None
    cluster._fused = FusedExecutor(
        cluster.catalog, cluster.stores,
        mesh=build_mesh(jax.devices()[:devices]),
    )
    return cluster._fused


# ---------------------------------------------------------------------------
# the planner alone
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def planner():
    c = Cluster(num_datanodes=4, shard_groups=64)
    s = c.session()
    s.execute("create table a (ak bigint, ax bigint, av bigint) "
              "distribute by shard(ak)")
    s.execute("create table b (bk bigint, bx bigint, bv bigint) "
              "distribute by shard(bk)")
    s.execute("create table h (hk bigint, hx bigint) "
              "distribute by hash(hk)")
    s.execute("create table tx (tk text, tv bigint) distribute by shard(tk)")
    # estimates only: every table far beyond the broadcast limit
    for name, rows in (("a", 40_000_000), ("b", 9_000_000),
                       ("h", 20_000_000), ("tx", 5_000_000)):
        c.catalog.get(name).stats = {"rows": rows, "ndv": {}}
    return c


PLANNER_CASES = {
    # SHARD x SHARD on different keys: a sits on ak, b moves onto it
    "shard_shard_other_key": (
        "select count(*) from a, b where ak = bx",
        {"b": "shard:a"}, {"a"},
    ),
    # both placed on their join keys but by different strategies: the
    # larger (a) stays, h moves onto a's shard placement
    "shard_hash_both_on_keys": (
        "select count(*) from a, h where ak = hk",
        {"h": "shard:a"}, {"a"},
    ),
    # only the HASH table is placed on its key: a moves onto it
    "shard_onto_hash": (
        "select count(*) from a, h where ax = hk",
        {"a": "hash:h"}, {"h"},
    ),
    # a FULL join null-extends both sides: both still move, by hash
    "full_join_moves_both": (
        "select count(*) from a full join b on ak = bx",
        {"a": "hash", "b": "hash"}, set(),
    ),
    # neither side placed on the keys: both move, as before
    "non_key_moves_both": (
        "select count(*) from a, b where ax = bx",
        {"a": "hash", "b": "hash"}, set(),
    ),
    # a text key hashes per dictionary: both move, as before
    "text_key_moves_both": (
        "select count(*) from tx, tx t2 where tx.tk = t2.tk and "
        "tx.tv < t2.tv and tx.tv = 3",
        None, None,
    ),
    # a left join whose preserved side moves: it still lands on a
    "left_join_moved_side_preserved": (
        "select count(*) from b left join a on bx = ak",
        {"b": "shard:a"}, {"a"},
    ),
}


@pytest.mark.parametrize("case", list(PLANNER_CASES))
def test_planner_keeps_the_placed_side(planner, case):
    sql, moved, kept = PLANNER_CASES[case]
    dp = plan_of(planner, sql)
    motions = {}
    for f in dp.fragments[:-1]:
        assert f.motion == "redistribute", dp.explain()
        (table,) = scans(f.root)
        motions[table] = f.target.label() if f.target else "hash"
        if f.target is not None:
            meta = planner.catalog.get(f.target.table)
            assert f.target.strategy == meta.dist.strategy
            assert f.target.nodes == tuple(meta.node_indices)
            assert f.dest_nodes == f.target.nodes
            assert f"to {f.target.label()}" in dp.explain()
    if moved is None:  # self-join on a colocated text key: no motion
        assert motions == {} or set(motions.values()) == {"hash"}
        return
    assert motions == moved, dp.explain()
    assert kept <= scans(dp.fragments[-1].root), dp.explain()


def test_join_keeps_the_kept_sides_keys_for_grouping(planner):
    """Groups on the kept side's distribution key are whole per node: no
    coordinator re-aggregate. Grouping on the moved side's other column
    still merges at the coordinator."""
    whole = plan_of(planner, "select ak, sum(bv) from a, b where ak = bx "
                             "group by ak")
    assert not has_aggregate(whole.root), whole.explain()
    split = plan_of(planner, "select bk, sum(av) from a, b where ak = bx "
                             "group by bk")
    assert has_aggregate(split.root), split.explain()


@pytest.mark.parametrize("group_key,whole", [("bx", True), ("ak", False)])
def test_outer_join_placement_rides_the_preserved_keys(
    planner, group_key, whole
):
    """b LEFT JOIN a with b moved onto a: unmatched b rows null-extend
    a's columns, so only the moved key bx still says where a row lives —
    groups on bx are whole per node, groups on ak (NULL on every node)
    are not."""
    dp = plan_of(planner, f"select {group_key}, count(*) from b left join "
                          f"a on bx = ak group by {group_key}")
    assert [f.motion_label() for f in dp.fragments[:-1]] == [
        "redistribute(0) to shard:a"], dp.explain()
    assert has_aggregate(dp.root) is not whole, dp.explain()


def test_pruned_side_is_no_placement(planner):
    """A scan pruned to one node by a key equality no longer covers the
    table's placement: the join moves both sides as before."""
    dp = plan_of(planner, "select count(*) from a, b where ak = bx "
                          "and ak = 7")
    assert all(f.target is None for f in dp.fragments), dp.explain()


def test_target_rides_serde_and_explain(planner):
    from opentenbase_tpu.plan import serde
    from opentenbase_tpu.plan.distribute import Placement

    dp = plan_of(planner, "select count(*) from a, b where ak = bx")
    target = dp.fragments[0].target
    assert target == Placement("a", DistStrategy.SHARD, (0, 1, 2, 3))
    assert serde.loads_plan(serde.dumps_plan(target)) == target
    s = planner.session()
    text = "\n".join(r[0] for r in s.execute(
        "explain select count(*) from a, b where ak = bx").rows)
    assert "->redistribute(0) to shard:a" in text, text


# ---------------------------------------------------------------------------
# placement: the exchange's destination is the locator's
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def placed():
    """kept(k) by SHARD or HASH, moved(x, k2) sharded on x: the join on
    k = k2 moves ``moved`` onto ``kept``'s placement."""
    c = Cluster(num_datanodes=4, shard_groups=64)
    s = c.session()
    rng = np.random.default_rng(29)
    keys = np.unique(np.concatenate([
        rng.integers(-2**40, 2**40, 700), np.arange(-50, 50),
    ]))
    for strat in ("shard", "hash"):
        s.execute(f"create table kept_{strat} (k bigint, w bigint) "
                  f"distribute by {strat}(k)")
        s.execute(f"insert into kept_{strat} values " + ",".join(
            f"({k},{i})" for i, k in enumerate(keys)))
    s.execute("create table moved (x bigint, k2 bigint) "
              "distribute by shard(x)")
    k2 = rng.choice(keys, 3000)
    s.execute("insert into moved values " + ",".join(
        f"({i},{k})" for i, k in enumerate(k2)))
    s.execute("analyze")
    for name in ("kept_shard", "kept_hash", "moved"):
        c.catalog.get(name).stats["rows"] = 30_000_000
    mesh4(c)
    return c, s, k2


def _insert_nodes(cluster, table, keys):
    from opentenbase_tpu import types as t
    from opentenbase_tpu.storage.column import Column

    meta = cluster.catalog.get(table)
    col = Column(t.INT8, np.asarray(keys, dtype=np.int64), None, None)
    return meta.locator.route_insert({"k": col}, len(keys))


@pytest.mark.parametrize("strat", ["shard", "hash"])
def test_host_exchange_lands_where_an_insert_would(placed, strat):
    from opentenbase_tpu.executor.dist import partition_batch
    from opentenbase_tpu.storage.column import Column
    from opentenbase_tpu.storage.table import ColumnBatch
    from opentenbase_tpu import types as t

    c, _s, k2 = placed
    dp = plan_of(c, f"select sum(w) from kept_{strat}, moved where k = k2")
    (frag,) = dp.fragments[:-1]
    assert frag.target.label() == f"{strat}:kept_{strat}", dp.explain()
    values = {"x": np.arange(len(k2), dtype=np.int64),
              "k2": np.asarray(k2, dtype=np.int64)}
    batch = ColumnBatch({  # the fragment's own output columns
        oc.name: Column(t.INT8, values[oc.name], None, None)
        for oc in frag.root.schema
    }, len(k2))
    parts = partition_batch(
        batch, frag.hash_positions, len(frag.dest_nodes),
        motion_route(frag, c.catalog),
    )
    want = _insert_nodes(c, f"kept_{strat}", k2)
    got = np.empty(len(k2), dtype=np.int64)
    for slot, idx in enumerate(parts):
        got[idx] = frag.dest_nodes[slot]
    assert (got == want).all()
    assert len(set(want.tolist())) == 4  # every node is a destination


@pytest.mark.parametrize("strat", ["shard", "hash"])
def test_device_exchange_lands_where_an_insert_would(placed, strat):
    """Run the join on the four-device mesh and read the exchange
    program's output: every live row on device d carries a key the kept
    table's locator routes to the datanode of device d."""
    from opentenbase_tpu.executor import fused_dag

    c, s, k2 = placed
    seen = []
    real = fused_dag.DagRunner._run_exchange

    def capture(self, frag, *a, **kw):
        out = real(self, frag, *a, **kw)
        seen.append((frag, out))
        return out

    sql = f"select sum(w), count(*) from kept_{strat}, moved where k = k2"
    s.execute("set enable_fused_execution = off")
    host = s.query(sql)
    s.execute("set enable_fused_execution = on")
    fused_dag.DagRunner._run_exchange = capture
    try:
        dev = s.query(sql)
    finally:
        fused_dag.DagRunner._run_exchange = real
    assert dev == host and dev[0][1] == len(k2)
    (frag, out), = seen
    D = 4
    keycol = np.asarray(out["cols"][frag.hash_positions[0]]).reshape(
        D, D, out["cap"])
    counts = np.asarray(out["counts"]).reshape(D, D)  # [dest, src]
    nodes = c.catalog.get(f"kept_{strat}").node_indices
    arrived = 0
    for d in range(D):
        for src in range(D):
            keys = keycol[d, src, : counts[d, src]]
            arrived += len(keys)
            assert (
                _insert_nodes(c, f"kept_{strat}", keys) == nodes[d]
            ).all(), (strat, d, src)
    assert arrived == len(k2)


def test_route_table_is_a_program_argument(placed):
    """A moved shard group re-keys nothing: the exchange programs in the
    cache before and after MOVE DATA are the same objects, and the
    answer is still the host executor's."""
    c, s, _k2 = placed
    sql = "select sum(w), count(*) from kept_shard, moved where k = k2"
    before = s.query(sql)
    dag = c._fused._dag
    progs = {k: v[0] for k, v in dag._programs.items()
             if k[0] in ("xcnt", "xchg")}
    assert progs
    sid = int(np.nonzero(c.shardmap.map == 0)[0][0])
    s.execute(f"move data from dn0 to dn1 shards ({sid})")
    assert int(c.shardmap.map[sid]) == 1
    done = dag.completed
    assert s.query(sql) == before
    assert dag.completed == done + 1, dag.unsupported
    after = {k: v[0] for k, v in dag._programs.items()
             if k[0] in ("xcnt", "xchg")}
    for k, prog in progs.items():
        assert after.get(k) is prog, k
    s.execute("set enable_fused_execution = off")
    assert s.query(sql) == before
    s.execute("set enable_fused_execution = on")


# ---------------------------------------------------------------------------
# TPC-H Q3 on 4 datanodes over a four-device mesh, against the reference
# ---------------------------------------------------------------------------

PARAM_SETS = [
    {"segment": "BUILDING", "day": 15},
    {"segment": "MACHINERY", "day": 4},
]


class Tpch4:
    """The configuration's tables on 4 datanodes behind the wire server,
    a four-device mesh underneath, data and reference from
    benchmarks/datasets/tpch.py."""

    def __init__(self, sf30_estimates: bool):
        from harness import compare, loader, traffic

        self.compare = compare
        cfg = loader.read_config("tpch_sf30_4chip")
        assert cfg["datanodes"] == 4 and cfg["chips"] == 4
        self.mix = traffic.read_mix("join_q3")
        self.data = loader.generate(cfg, 2_147_483_777, 0.004)
        self.dep = loader.Deployment(cfg)
        self.fx = mesh4(self.dep.cluster)
        self.dep.create_tables()
        self.dep.load(self.data)
        self.patch = pytest.MonkeyPatch()
        if sf30_estimates:
            # the motion choice reads estimates only: SF30's row counts
            for table, rows in SF30.items():
                self.dep.cluster.catalog.get(table).stats["rows"] = rows
            # the join's formulation reads static widths only: SF30's
            # 2^21 padded customers a device are past the radix table's
            # bound (P <= 4096). The toy's 256 (P = 16) are held to a
            # bound scaled down with them, so fragment 1 takes the
            # four-chip cell's formulation
            from opentenbase_tpu.ops import pallas_join

            self.patch.setattr(pallas_join, "MAX_PARTITIONS", 8)

    def text(self, params: dict) -> str:
        stmt = self.mix["statements"]["q3"]
        return stmt["text"].format(
            segment=params["segment"], date=f"1995-03-{params['day']:02d}"
        )

    def reference(self, params: dict):
        return self.data.module.reference(
            "q3", params, self.data.blocks, self.data.glob, exact=True,
        )

    def fused_rows(self) -> dict:
        rows: dict = {}
        for ev, detail in self.dep.sql(
            "select event, detail from pg_stat_fused"
        ).rows:
            rows.setdefault(ev, []).append(detail)
        return rows

    def close(self):
        self.patch.undo()
        self.dep.close()


@pytest.fixture(scope="module", params=["broadcast", "sf30_plan"])
def tpch4(request):
    t = Tpch4(sf30_estimates=request.param == "sf30_plan")
    yield request.param, t
    t.close()


@pytest.mark.parametrize("pi", range(len(PARAM_SETS)))
def test_q3_over_the_wire_equals_the_reference(tpch4, pi):
    variant, t = tpch4
    params = PARAM_SETS[pi]
    sql = t.text(params)
    cluster = t.dep.cluster
    dp = plan_of(cluster, sql)
    redistributed = set()
    for f in dp.fragments[:-1]:
        if f.motion == "redistribute":
            redistributed.add(shipped_table(f.root))
            assert f.target is not None, dp.explain()
    assert not redistributed & {"lineitem", "customer"}, dp.explain()
    assert not has_aggregate(dp.root), dp.explain()
    motions = [f.motion for f in dp.fragments[:-1]]
    if variant == "broadcast":
        assert motions == ["broadcast"], dp.explain()
    else:
        assert motions == ["redistribute", "redistribute"], dp.explain()
        assert [f.target.label() for f in dp.fragments[:-1]] == [
            "shard:customer", "shard:lineitem"]
    before = t.fused_rows()
    res = t.dep.sql(sql)
    after = t.fused_rows()
    got = t.compare.compare_statement(res.rows, t.reference(params))
    verdict = t.compare.judge([got], t.compare.read_limits())
    assert verdict["correct"], (verdict, res.rows[:3])
    assert len(res.rows) == 10
    assert (int(after["fused_statements"][-1])
            == int(before["fused_statements"][-1]) + 1)
    assert [u for u in after.get("unsupported", [])
            if u != "trivial scan"] == []
    assert not after.get("demoted")
    programs = after["last_programs"][-1].split(",")
    if variant == "sf30_plan":
        assert "program_dag_exchange" in programs, programs
        assert after["last_mode"][-1] == "gsort"  # whole groups a device
        # fragment 1's customer join: admitted by the estimates, sent to
        # sort-merge by its width (the final's co-sort is ``merge`` too);
        # counted once a compiled program that holds it — the count pass
        # and the exchange of this set of literals — and never on a
        # cached re-bind (the span test may have run this set before)
        assert after["last_join_modes"][-1] == "merge"
        sized_out = (int(after["radix_sized_out"][-1])
                     - int(before["radix_sized_out"][-1]))
        assert sized_out == (2 if "program_dag_count" in programs else 0)
        assert int(after["radix_sized_out"][-1]) >= 2
    else:
        assert "program_dag_broadcast" in programs, programs
        assert int(after["radix_sized_out"][-1]) == 0


@pytest.mark.parametrize("tpch4", ["sf30_plan"], indirect=True)
def test_exchange_span_and_ledger_columns(tpch4):
    """One ``fused.exchange`` span a motion fragment, parent of that
    fragment's bind/launch/wait; ``rows`` is what the reference's filter
    lets through and the locators send elsewhere; the ledger's
    milliseconds still partition ``device_ms``."""
    _variant, t = tpch4
    cluster = t.dep.cluster
    params = PARAM_SETS[0]
    sql = t.text(params)
    t.dep.sql(sql)  # warm: the traced run is a cached one
    cols = ("calls, device_ms, gate_ms, cache_ms, bind_ms, launch_ms, "
            "device_wait_ms, collect_ms, exchange_ms, exchange_rows, "
            "exchange_bytes, exchange_slots, exchange_fragments")

    def ledger():
        (row,) = [
            r for r in t.dep.sql(
                f"select query, {cols} from pg_stat_statements").rows
            if "l_orderkey" in r[0] and "pg_stat" not in r[0]
        ]
        return [float(x) for x in row[1:]]

    b = ledger()
    t.dep.sql("set trace_queries = on")
    try:
        t.dep.sql(sql)
    finally:
        t.dep.sql("set trace_queries = off")
    d = [x - y for x, y in zip(ledger(), b)]
    tr = next(x for x in reversed(cluster.tracer.last(4)) if x.query == sql)
    xs = [sp for sp in tr.spans if sp.name == "fused.exchange"]
    assert [sp.args["frag"] for sp in xs] == [0, 1]
    assert [sp.args["target"] for sp in xs] == [
        "shard:customer", "shard:lineitem"]
    by_id = {sp.span_id: sp for sp in tr.spans}
    for x in xs:
        assert x.cat == "fused" and x.args["motion"] == "redistribute"
        assert x.args["devices"] == 4 and x.args["count_pass"] == "cached"
        assert x.args["slots"] == 4 * 3 * x.args["cap"] >= x.args["rows"]
        assert x.args["bytes"] > 0
        kids = [sp.name for sp in tr.spans if sp.parent_id == x.span_id]
        # (the count pass is cached: its bind, no launch of its own)
        assert sorted(k for k in kids if k != "fused.cache") == [
            "fused.bind", "fused.bind", "fused.launch", "fused.wait"], kids
        assert by_id[x.parent_id].name == "fused"
    # fragment 0's rows by the reference's own filter, placed by the
    # two tables' locators: orders before the date whose customer lives
    # on another datanode than the order
    day = np.datetime64(f"1995-03-{params['day']:02d}", "D")
    day = int((day - np.datetime64("1970-01-01", "D")).astype(np.int64))
    moved = 0
    from opentenbase_tpu import types as ty
    from opentenbase_tpu.storage.column import Column

    for blk in t.data.blocks:
        o = blk["orders"]
        keep = o["o_orderdate"] < day
        here = cluster.catalog.get("orders").locator.route_insert(
            {"o_orderkey": Column(ty.INT8, o["o_orderkey"][keep], None, None)},
            int(keep.sum()))
        there = cluster.catalog.get("customer").locator.route_insert(
            {"c_custkey": Column(ty.INT8, o["o_custkey"][keep], None, None)},
            int(keep.sum()))
        moved += int((here != there).sum())
    assert xs[0].args["rows"] == moved
    # the ledger: five exchange columns moved by this one statement,
    # and the seven parts never count a millisecond twice
    assert d[0] == 1
    device_ms, parts = d[1], d[2:9]
    assert all(p >= 0 for p in parts) and sum(parts) <= device_ms + 1e-6
    assert d[9] == sum(x.args["rows"] for x in xs)
    assert d[10] == sum(x.args["bytes"] for x in xs)
    assert d[11] == sum(x.args["slots"] for x in xs)
    assert d[12] == 2
    assert d[8] > 0  # exchange_ms: the spans' own time
    # how the buckets are filled moves none of it: the same rows go to
    # the same places in the same padded slabs as before the bucketing
    # sort carried the payload (the parent commit's reading, this data)
    assert [(x.args["rows"], x.args["cap"], x.args["slots"],
             x.args["bytes"]) for x in xs] == [
        (2211, 256, 3072, 86016), (527, 128, 1536, 64512)]
    assert d[9:12] == [2738.0, 150528.0, 4608.0]
    totals = t.fused_rows()
    assert int(totals["exchange_fragments"][-1]) >= 2 and "exchange_ms" not in totals
    assert int(totals["exchange_rows"][-1]) >= d[9]


# ---------------------------------------------------------------------------
# one plan, three executors; a moved shard group
# ---------------------------------------------------------------------------


def _tpch_like(mesh_devices: int = 4):
    """TPC-H's three tables at the configuration's distribution, a few
    thousand rows, SF30's estimates."""
    c = Cluster(num_datanodes=4, shard_groups=64)
    s = c.session()
    s.execute("create table customer (c_custkey bigint, c_mktsegment "
              "char(10)) distribute by shard(c_custkey)")
    s.execute("create table orders (o_orderkey bigint, o_custkey bigint, "
              "o_orderdate date, o_shippriority int) "
              "distribute by shard(o_orderkey)")
    s.execute("create table lineitem (l_orderkey bigint, l_extendedprice "
              "decimal(15,2), l_discount decimal(15,2), l_shipdate date) "
              "distribute by shard(l_orderkey)")
    rng = np.random.default_rng(31)
    nc, no, nl = 300, 1500, 6000
    s.execute("insert into customer values " + ",".join(
        f"({k},'{seg}')" for k, seg in zip(
            range(1, nc + 1),
            rng.choice(["BUILDING", "AUTOMOBILE", "MACHINERY"], nc))))
    s.execute("insert into orders values " + ",".join(
        f"({ok},{ck},'{d}',0)" for ok, ck, d in zip(
            range(1, no + 1), rng.integers(1, nc + 1, no),
            np.datetime64("1994-06-01") + rng.integers(0, 600, no))))
    s.execute("insert into lineitem values " + ",".join(
        f"({ok},{p:.2f},0.0{dd},'{d}')" for ok, p, dd, d in zip(
            rng.integers(1, no + 1, nl),
            rng.uniform(900, 90000, nl).round(2), rng.integers(0, 9, nl),
            np.datetime64("1994-06-01") + rng.integers(0, 700, nl))))
    s.execute("analyze")
    for table, rows in SF30.items():
        c.catalog.get(table).stats["rows"] = rows
    mesh4(c, mesh_devices)
    return c, s


Q3 = (
    "select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue, "
    "o_orderdate, o_shippriority from customer, orders, lineitem "
    "where c_mktsegment = 'BUILDING' and c_custkey = o_custkey "
    "and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15' "
    "and l_shipdate > date '1995-03-15' "
    "group by l_orderkey, o_orderdate, o_shippriority "
    "order by revenue desc, o_orderdate limit 10"
)


@pytest.fixture(scope="module")
def three_ways():
    """(host rows, mesh rows, one-device rows) of the same plan."""
    c4, s4 = _tpch_like()
    s4.execute("set enable_fused_execution = off")
    host = s4.query(Q3)
    s4.execute("set enable_fused_execution = on")
    return c4, s4, host


@pytest.mark.parametrize("executor", ["mesh4", "mesh1"])
def test_mesh_inlined_dag_and_host_agree(three_ways, executor):
    c4, s4, host = three_ways
    if executor == "mesh4":
        c, s = c4, s4
    else:
        c, s = _tpch_like(mesh_devices=1)
    dp = plan_of(c, Q3)
    assert [f.motion_label() for f in dp.fragments] == [
        "redistribute(1) to shard:customer",
        "redistribute(2) to shard:lineitem", "gather"]
    done = c._fused._dag.completed if c._fused._dag else 0
    assert s.query(Q3) == host and len(host) == 10
    dag = c._fused._dag
    assert dag.completed == done + 1, dag.unsupported
    if executor == "mesh4":
        assert dag.last_programs.count("program_dag_exchange") == 2
        assert dag.exchange_totals["exchange_fragments"] >= 2
    else:
        # one device: every exchange an identity, the DAG one program,
        # and the exchange columns stay at nought
        assert len(dag.last_programs) == 1
        assert not any(dag.exchange_totals.values())
        (row,) = [r for r in s.query(
            "select query, exchange_ms, exchange_rows, exchange_bytes, "
            "exchange_slots, exchange_fragments from pg_stat_statements")
            if "l_orderkey" in r[0] and "pg_stat" not in r[0]]
        assert [float(x) for x in row[1:]] == [0.0] * 5


def test_q3_follows_a_moved_shard_group(three_ways):
    """MOVE DATA repoints a shard group and moves its rows of all three
    tables: the redistributes follow the map, the answer stays."""
    c, s, host = three_ways
    s.query(Q3)
    sid = int(np.nonzero(c.shardmap.map == 2)[0][0])
    s.execute(f"move data from dn2 to dn3 shards ({sid})")
    assert int(c.shardmap.map[sid]) == 3
    dag = c._fused._dag
    done = dag.completed
    assert s.query(Q3) == host
    assert dag.completed == done + 1, dag.unsupported
    s.execute("set enable_fused_execution = off")
    assert s.query(Q3) == host
    s.execute("set enable_fused_execution = on")


# ---------------------------------------------------------------------------
# the budget judges one device's share
# ---------------------------------------------------------------------------


def test_exchange_budget_is_one_devices_share():
    from opentenbase_tpu.executor import fused_dag
    from opentenbase_tpu.plan import batchplan

    c, s = _tpch_like()
    s.execute("set enable_fused_execution = off")
    host = s.query(Q3)
    s.execute("set enable_fused_execution = on")
    assert s.query(Q3) == host
    dag = c._fused._dag
    # every exchange as a run holds it against the budget: its bucket
    # size, its schema and the padded rows a device its sort carries
    held = []
    check = dag._check_hbm_budget
    dag._check_hbm_budget = lambda *a: (held.append(a), check(*a))[1]
    try:
        assert s.query(Q3) == host
    finally:
        del dag._check_hbm_budget
    assert len(held) == 2 and all(a[3] > 0 for a in held), held
    ests = [
        batchplan.exchange_bytes(
            cap, batchplan.exchange_row_bytes(schema), D, rows)
        for cap, schema, D, rows in held
    ]
    one = max(ests)
    # the buffers that exist: the sort's operands in and out over the
    # rows and the pad, the slab sent and the collective's result
    assert ests == [
        (2 * (rows + cap) + 2 * 4 * cap)
        * batchplan.exchange_row_bytes(schema)
        for cap, schema, _D, rows in held
    ]
    schema = held[0][1]
    # every device's buffers together pass this budget; one device's
    # share fits: the statement stays on the device
    s.execute(f"set device_memory_limit = {one + 1}")
    assert 4 * one > one + 1
    done = dag.completed
    assert s.query(Q3) == host
    assert dag.completed == done + 1 and not dag.unsupported
    # one device's share passes it: declined with the MiB in the message
    with pytest.raises(fused_dag.DagUnsupported, match=r"~\d+ MiB a device"):
        c._fused.device_memory_limit = 1 << 20
        dag._check_hbm_budget(1 << 20, schema, 4)
    s.execute(f"set device_memory_limit = {one - 1}")
    assert s.query(Q3) == host  # the host executor answers
    assert dag.completed == done + 1
    assert any("MiB a device" in u for u in dag.unsupported), dag.unsupported


# ---------------------------------------------------------------------------
# otb_trace --xplane on a mesh: fragments, exposed against hidden
# ---------------------------------------------------------------------------


def test_xplane_reduction_lists_fragments_and_exposed_all_to_all():
    """Two chips, one statement of two fragments. Chip 0 enters the
    exchange's collective at 4 ms and leaves at 9; chip 1 computes until
    7 and is in the collective from 7 to 9: of chip 0's 5 ms, 3 were
    spent waiting on chip 1's work (hidden) and 2 with both chips inside
    (exposed); all of chip 1's 2 ms are exposed."""
    from opentenbase_tpu.obs import profile

    ms = 1e6
    a2a = {"scope": "exchange/bucket/all_to_all"}

    def chip(n, work_end):
        return {"name": f"/device:TPU:{n}", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_program_dag_exchange(1)", 1 * ms, 8 * ms, {}],
                ["jit_program_dag_gsort(2)", 10 * ms, 4 * ms, {}],
            ]},
            {"name": "XLA Ops", "events": [
                ["%fusion.1", 1 * ms, (work_end - 2) * ms,
                 {"scope": "exchange/route"}],
                ["%sort.1", (work_end - 1) * ms, 0.75 * ms,
                 {"scope": "exchange/bucket/sort"}],
                ["%dynamic-slice.1", (work_end - 0.25) * ms, 0.25 * ms,
                 {"scope": "exchange/bucket/slab"}],
                ["%all-to-all.1", work_end * ms, (9 - work_end) * ms, a2a],
                ["%sort.2", 10 * ms, 4 * ms,
                 {"scope": "join0/merge/sort"}],
            ]},
        ]}

    host = {"name": "/host:CPU", "lines": [{"name": "otb-server/1", "events": [
        ["otb:query", 0.0, 15 * ms, {"queryid": "9"}],
        ["otb:fused", 0.5 * ms, 14 * ms, {"path": "dag"}],
        ["otb:fused.exchange", 0.6 * ms, 9 * ms, {
            "frag": 0, "motion": "redistribute", "target": "shard:t",
            "devices": 2, "rows": 70, "cap": 64, "slots": 128,
            "bytes": 2176, "count_pass": "cached"}],
        ["otb:fused.launch", 0.7 * ms, 0.2 * ms, {
            "program": "program_dag_exchange", "frag": 0}],
        ["otb:fused.wait", 1 * ms, 8.5 * ms, {"frag": 0}],
        ["otb:fused.launch", 9.7 * ms, 0.2 * ms, {
            "program": "program_dag_gsort", "frag": "final"}],
        ["otb:fused.wait", 10 * ms, 4.2 * ms, {"frag": "final"}],
    ]}]}
    report = profile.reduce({"planes": [chip(0, 4), chip(1, 7), host]})
    assert report["chips"] == 2 and report["statements"] == 1
    (c,) = report["classes"].values()
    assert c["fragments"]["0"] == {
        "programs": {"program_dag_exchange": 1}, "motion": "redistribute",
        "target": "shard:t", "exchange_ms": pytest.approx(9.0),
        "rows": 70, "slots": 128, "bytes": 2176,
    }
    assert c["fragments"]["final"]["programs"] == {"program_dag_gsort": 1}
    assert c["all_to_all"]["/device:TPU:0"] == pytest.approx(
        {"total_ms": 5.0, "hidden_ms": 3.0})
    assert c["all_to_all"]["/device:TPU:1"] == pytest.approx(
        {"total_ms": 2.0, "hidden_ms": 0.0})
    assert c["device_busy_ms"] == pytest.approx(2 * (8 + 4))  # chip-ms
    # the bucketing is attributed whole: its sort, its slices and the
    # collective each under a scope of their own, nothing unscoped
    assert c["scopes_ms"] == pytest.approx({
        "exchange/route": 2 + 5, "exchange/bucket/sort": 2 * 0.75,
        "exchange/bucket/slab": 2 * 0.25,
        "exchange/bucket/all_to_all": 5 + 2, "join0/merge/sort": 2 * 4,
    })
    assert c["unscoped_ops_ms"] == {}
    text = profile.render(report)
    assert "on 2 chips" in text and "fragments (per statement)" in text
    assert "redistribute to shard:t" in text
    assert "/device:TPU:0" in text and "5.000 = 2.000 + 3.000" in text
