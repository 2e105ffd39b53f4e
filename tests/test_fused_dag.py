"""Fused DAG executor (executor/fused_dag.py): distributed joins on the
device mesh must match the host fragment executor exactly, including
NULL-key semantics, duplicate-build fallbacks, and data changes between
queries. Also covers the predicate-pushdown/join-key-extraction pass
(plan/optimize.py) that feeds it."""

import numpy as np
import pytest

from opentenbase_tpu.engine import Cluster


@pytest.fixture(scope="module")
def sess():
    s = Cluster(num_datanodes=4, shard_groups=64).session()
    s.execute(
        "create table customer (c_custkey bigint, c_mktsegment text) "
        "distribute by shard(c_custkey)"
    )
    s.execute(
        "create table orders (o_orderkey bigint, o_custkey bigint, "
        "o_orderdate date, o_shippriority int) distribute by shard(o_orderkey)"
    )
    s.execute(
        "create table lineitem (l_orderkey bigint, l_extendedprice "
        "numeric(12,2), l_discount numeric(4,2), l_shipdate date) "
        "distribute by shard(l_orderkey)"
    )
    rng = np.random.default_rng(9)
    nc, no, nl = 200, 800, 3000
    s.execute("insert into customer values " + ",".join(
        f"({k},'{seg}')" for k, seg in zip(
            range(1, nc + 1),
            rng.choice(["BUILDING", "AUTOMOBILE", "MACHINERY"], nc),
        )
    ))
    s.execute("insert into orders values " + ",".join(
        f"({ok},{ck},'{d}',{pr})" for ok, ck, d, pr in zip(
            range(1, no + 1), rng.integers(1, nc + 1, no),
            np.datetime64("1994-06-01") + rng.integers(0, 600, no),
            rng.integers(0, 3, no),
        )
    ))
    s.execute("insert into lineitem values " + ",".join(
        f"({ok},{p:.2f},0.0{dd},'{d}')" for ok, p, dd, d in zip(
            rng.integers(1, no + 1, nl),
            rng.uniform(900, 90000, nl).round(2),
            rng.integers(0, 9, nl),
            np.datetime64("1994-06-01") + rng.integers(0, 700, nl),
        )
    ))
    return s


Q3 = (
    "select l_orderkey, sum(l_extendedprice * (1 - l_discount)), "
    "o_orderdate, o_shippriority "
    "from customer, orders, lineitem "
    "where c_mktsegment = 'BUILDING' and c_custkey = o_custkey "
    "and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15' "
    "and l_shipdate > date '1995-03-15' "
    "group by l_orderkey, o_orderdate, o_shippriority "
    "order by 2 desc, o_orderdate limit 10"
)


def _both(s, q, expect_dag=None):
    """Run host-path then fused-path; with expect_dag=True assert the
    fused result was actually PRODUCED by the DAG runner (round-1 lesson:
    a silent fallback makes dev==host trivially true)."""
    s.execute("set enable_fused_execution = off")
    host = s.query(q)
    s.execute("set enable_fused_execution = on")
    fx = s.cluster.fused_executor()
    before = fx._dag.completed if fx._dag is not None else 0
    dev = s.query(q)
    if expect_dag is True:
        assert fx._dag is not None and fx._dag.completed > before, (
            "query did not complete through the fused DAG"
        )
    elif expect_dag is False:
        after = fx._dag.completed if fx._dag is not None else 0
        assert after == before, "query unexpectedly ran through the DAG"
    return host, dev


def test_q3_on_device_matches_host(sess):
    host, dev = _both(sess, Q3, expect_dag=True)
    assert dev == host
    assert len(dev) == 10


def test_two_table_join_agg(sess):
    q = (
        "select o_shippriority, count(*), sum(l_extendedprice) "
        "from orders, lineitem where o_orderkey = l_orderkey "
        "group by o_shippriority order by o_shippriority"
    )
    host, dev = _both(sess, q, expect_dag=True)
    assert dev == host and len(dev) == 3


def test_join_rows_without_aggregate(sess):
    q = (
        "select o_orderkey, l_extendedprice from orders, lineitem "
        "where o_orderkey = l_orderkey and l_extendedprice < 2000 "
        "order by o_orderkey, l_extendedprice"
    )
    host, dev = _both(sess, q, expect_dag=True)
    assert dev == host and len(dev) > 0


def test_semi_and_anti_joins(sess):
    semi = (
        "select count(*) from orders where o_orderkey in "
        "(select l_orderkey from lineitem where l_extendedprice > 50000)"
    )
    anti = (
        "select count(*) from orders where not exists "
        "(select 1 from lineitem where l_orderkey = o_orderkey)"
    )
    for q in (semi, anti):
        host, dev = _both(sess, q, expect_dag=True)
        assert dev == host, q


def test_null_join_keys_never_match(sess):
    s = sess
    s.execute("create table nl (k bigint, v bigint) distribute by shard(v)")
    s.execute("create table nr (k bigint, w bigint) distribute by shard(w)")
    s.execute("insert into nl values (null, 1), (1, 2), (2, 3)")
    s.execute("insert into nr values (null, 10), (1, 20), (3, 30)")
    q = "select sum(v + w) from nl, nr where nl.k = nr.k"
    host, dev = _both(s, q, expect_dag=True)
    assert dev == host == [(22,)]
    # anti-join probes with NULL keys must SURVIVE
    qa = (
        "select count(*) from nl where not exists "
        "(select 1 from nr where nr.k = nl.k)"
    )
    host, dev = _both(s, qa, expect_dag=True)
    assert dev == host == [(2,)]  # NULL-key row + k=2


def test_duplicate_both_sides_falls_back(sess):
    s = sess
    s.execute("create table d1 (k bigint, v bigint) distribute by shard(k)")
    s.execute("create table d2 (k bigint, w bigint) distribute by shard(k)")
    s.execute("insert into d1 values (1,10),(1,11),(2,20)")
    s.execute("insert into d2 values (1,100),(1,101),(3,300)")
    q = "select sum(v + w) from d1, d2 where d1.k = d2.k"
    host, dev = _both(s, q, expect_dag=False)
    assert dev == host == [(444,)]


def test_dag_sees_new_writes(sess):
    s = sess
    q = (
        "select count(*) from orders, lineitem "
        "where o_orderkey = l_orderkey"
    )
    s.execute("set enable_fused_execution = on")
    before = s.query(q)[0][0]
    s.execute(
        "insert into lineitem values (1, 5.00, 0.01, '1994-01-01')"
    )
    assert s.query(q)[0][0] == before + 1
    s.execute("delete from lineitem where l_extendedprice = 5.00")
    assert s.query(q)[0][0] == before


def test_pushdown_extracts_keys_and_sinks_filters():
    from opentenbase_tpu.plan import logical as L
    from opentenbase_tpu.plan.analyze import analyze_statement
    from opentenbase_tpu.plan.optimize import pushdown_predicates
    from opentenbase_tpu.sql.parser import parse

    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    s.execute("create table a (x bigint, p bigint) distribute by shard(x)")
    s.execute("create table b (y bigint, q bigint) distribute by shard(y)")
    stmt = parse(
        "select sum(p + q) from a, b where x = y and p > 0 and q < 5"
    )[0]
    sp = pushdown_predicates(analyze_statement(stmt, c.catalog))
    # find the join: keys extracted, one filter sunk per side
    node = sp.root
    while not isinstance(node, L.Join):
        node = node.child
    assert node.left_keys and node.right_keys
    assert isinstance(node.left, L.Filter)
    assert isinstance(node.right, L.Filter)
    assert node.residual is None


def test_outer_join_unchanged_semantics(sess):
    # left joins are not in the DAG subset: must still answer correctly
    q = (
        "select count(*) from orders left join lineitem "
        "on o_orderkey = l_orderkey where o_shippriority = 1"
    )
    host, dev = _both(sess, q)
    assert dev == host


def test_on_clause_residual_sinks_under_where():
    from opentenbase_tpu.plan import logical as L
    from opentenbase_tpu.plan.analyze import analyze_statement
    from opentenbase_tpu.plan.optimize import pushdown_predicates
    from opentenbase_tpu.sql.parser import parse

    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    s.execute("create table a (x bigint, p bigint) distribute by shard(x)")
    s.execute("create table b (y bigint, q bigint) distribute by shard(y)")
    stmt = parse(
        "select sum(p + q) from a join b on x = y and q < 5 where p > 0"
    )[0]
    sp = pushdown_predicates(analyze_statement(stmt, c.catalog))
    node = sp.root
    while not isinstance(node, L.Join):
        node = node.child
    # the ON-clause extra (q < 5) must sink into the right side even
    # with a WHERE above (review regression)
    assert isinstance(node.right, L.Filter)
    assert node.residual is None


def test_exists_rollback_no_orphan_subplans():
    from opentenbase_tpu.plan.analyze import analyze_statement
    from opentenbase_tpu.sql.parser import parse

    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    s.execute("create table o2 (ok bigint) distribute by shard(ok)")
    s.execute("create table l2 (lk bigint, p bigint) distribute by shard(lk)")
    s.execute("insert into o2 values (1),(2)")
    s.execute("insert into l2 values (1, 5),(9, 1)")
    # uncorrelated EXISTS whose inner WHERE registers a scalar subplan:
    # the abandoned pull-up trial must roll its registration back, so
    # exactly one subplan (from the count rewrite) survives
    sql = (
        "select count(*) from o2 where exists "
        "(select 1 from l2 where p > (select min(p) from l2))"
    )
    sp = analyze_statement(parse(sql)[0], c.catalog)
    assert len(sp.subplans) == 2  # count-rewrite subplan + its inner min
    assert s.query(sql) == [(2,)]
    # correlated EXISTS with an inner scalar-subquery conjunct: pull-up
    # succeeds, inner subplan registered exactly once
    sql2 = (
        "select count(*) from o2 where exists "
        "(select 1 from l2 where lk = ok and p > (select min(p) from l2))"
    )
    sp2 = analyze_statement(parse(sql2)[0], c.catalog)
    assert len(sp2.subplans) == 1
    assert s.query(sql2) == [(1,)]


def test_join_reorder_bad_from_order():
    """VERDICT item 6 done-criterion: a bad FROM order (big x big first,
    tiny dim last) still produces a plan starting from the tiny table,
    and answers correctly."""
    from opentenbase_tpu.plan import logical as L
    from opentenbase_tpu.plan.analyze import analyze_statement
    from opentenbase_tpu.plan.optimize import optimize_statement
    from opentenbase_tpu.sql.parser import parse

    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    s.execute("create table big1 (k1 bigint, v1 bigint) distribute by shard(k1)")
    s.execute("create table big2 (k2 bigint, v2 bigint) distribute by shard(k2)")
    s.execute("create table tiny (tk bigint, tag bigint) distribute by shard(tk)")
    s.execute("insert into big1 values " + ",".join(
        f"({i}, {i * 2})" for i in range(400)))
    s.execute("insert into big2 values " + ",".join(
        f"({i}, {i * 3})" for i in range(400)))
    s.execute("insert into tiny values (5, 50), (7, 70)")
    s.execute("analyze")
    meta = c.catalog.get("big1")
    assert meta.stats["rows"] == 400 and meta.stats["ndv"]["k1"] >= 300

    # bad order: two big tables first, tiny last
    sql = (
        "select sum(v1 + v2 + tag) from big1, big2, tiny "
        "where k1 = k2 and k2 = tk"
    )
    sp = optimize_statement(
        analyze_statement(parse(sql)[0], c.catalog), c.catalog
    )
    # walk to the bottom-left leaf of the join tree: must be tiny
    node = sp.root
    while not isinstance(node, L.Join):
        node = node.child
    bottom = node
    while isinstance(bottom, L.Join):
        bottom = bottom.left
    while not isinstance(bottom, L.Scan):
        bottom = bottom.child
    assert bottom.table == "tiny", "reorder did not start from the tiny table"
    want = (50 + 5 * 2 + 5 * 3) + (70 + 7 * 2 + 7 * 3)
    assert s.query(sql) == [(want,)]


def test_broadcast_motion_chosen_and_correct():
    """Motion costing: a tiny dimension table broadcasts to the fact
    table's nodes instead of reshuffling the fact table; results match
    and the DAG executes the broadcast on device."""
    from opentenbase_tpu.plan.analyze import analyze_statement
    from opentenbase_tpu.plan.distribute import distribute_statement
    from opentenbase_tpu.plan.optimize import optimize_statement
    from opentenbase_tpu.sql.parser import parse

    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    s.execute("create table fact (fk bigint, dk bigint, v bigint) "
              "distribute by shard(fk)")
    s.execute("create table dim (dk bigint, tag bigint) "
              "distribute by shard(dk)")
    s.execute("insert into fact values " + ",".join(
        f"({i}, {i % 7}, {i})" for i in range(500)))
    s.execute("insert into dim values " + ",".join(
        f"({d}, {d * 10})" for d in range(7)))
    s.execute("analyze")

    sql = ("select sum(v + tag) from fact, dim "
           "where fact.dk = dim.dk and tag >= 0")
    sp = optimize_statement(
        analyze_statement(parse(sql)[0], c.catalog), c.catalog
    )
    dp = distribute_statement(sp, c.catalog)
    motions = [f.motion for f in dp.fragments]
    assert "broadcast" in motions, motions
    assert "redistribute" not in motions, (
        "the fact table must stay in place"
    )

    s.execute("set enable_fused_execution = off")
    host = s.query(sql)
    s.execute("set enable_fused_execution = on")
    fx = s.cluster.fused_executor()
    before = fx._dag.completed if fx._dag is not None else 0
    dev = s.query(sql)
    assert dev == host
    assert fx._dag is not None and fx._dag.completed > before


def test_join_reorder_four_tables():
    """4-table cluster: the tiny table must be considered for the whole
    cluster (review regression: nested-first recursion hid it)."""
    from opentenbase_tpu.plan import logical as L
    from opentenbase_tpu.plan.analyze import analyze_statement
    from opentenbase_tpu.plan.optimize import optimize_statement
    from opentenbase_tpu.sql.parser import parse

    c = Cluster(num_datanodes=2, shard_groups=16)
    s = c.session()
    for tname, k in (("a4", "ka"), ("b4", "kb"), ("c4", "kc")):
        s.execute(
            f"create table {tname} ({k} bigint, v{tname} bigint) "
            f"distribute by shard({k})"
        )
        s.execute(f"insert into {tname} values " + ",".join(
            f"({i}, {i})" for i in range(300)))
    s.execute("create table t4 (kt bigint, vt bigint) distribute by shard(kt)")
    s.execute("insert into t4 values (3, 30), (4, 40)")
    s.execute("analyze")

    sql = (
        "select sum(va4 + vb4 + vc4 + vt) from a4, b4, c4, t4 "
        "where ka = kb and kb = kc and kc = kt"
    )
    sp = optimize_statement(
        analyze_statement(parse(sql)[0], c.catalog), c.catalog
    )
    node = sp.root
    while not isinstance(node, L.Join):
        node = node.child
    bottom = node
    while isinstance(bottom, L.Join):
        bottom = bottom.left
    while not isinstance(bottom, L.Scan):
        bottom = bottom.child
    assert bottom.table == "t4", "4-table cluster must start from t4"
    assert s.query(sql) == [((3 * 3 + 30) + (4 * 3 + 40),)]


def test_single_device_mesh_inlines_whole_dag(sess):
    """On a 1-device mesh every exchange is an identity: the DAG must
    collapse to one inlined program and still match the host answer."""
    import jax
    import numpy as _np

    from opentenbase_tpu.executor.fused import FusedExecutor
    from opentenbase_tpu.executor.fused_dag import DagRunner
    from opentenbase_tpu.plan.analyze import analyze_statement
    from opentenbase_tpu.plan.distribute import distribute_statement
    from opentenbase_tpu.plan.optimize import optimize_statement
    from opentenbase_tpu.sql.parser import parse

    c = sess.cluster
    mesh1 = jax.sharding.Mesh(
        _np.asarray(jax.devices("cpu")[:1]), ("dn",)
    )
    fx1 = FusedExecutor(c.catalog, c.stores, mesh=mesh1)
    runner = DagRunner(fx1)
    try:
        sess.execute("set enable_fused_execution = off")
        want = sess.query(Q3)
        sp = optimize_statement(
            analyze_statement(parse(Q3)[0], c.catalog), c.catalog
        )
        dp = distribute_statement(sp, c.catalog)
        assert len(dp.fragments) > 1  # a real multi-fragment join plan
        res = runner.run(dp, c.gts.snapshot_ts(), sess._dicts_view(), [])
        assert res is not None, "1-device DAG fell back"
        final_idx, batch = res
        from opentenbase_tpu.executor.local import LocalExecutor

        ex = LocalExecutor(
            c.catalog, {}, c.gts.snapshot_ts(),
            remote_inputs={final_idx: batch}, subquery_values=[],
        )
        got = ex.run_plan(dp.root).to_rows()
        assert got == want
        # exactly one final program, ZERO exchange programs were built
        kinds = {k[0] for k in runner._programs}
        assert "final" in kinds
        assert not any(
            k in kinds for k in ("xcnt", "xchg", "bcnt", "bcast")
        ), kinds
    finally:
        sess.execute("set enable_fused_execution = on")  # module fixture


def test_packed_group_overflow_falls_back(sess):
    """Group keys whose combined range exceeds int64 must trip the
    pack-overflow flag and still answer correctly via per-key sorting."""
    s = sess
    s.execute(
        "create table wide2 (a bigint, b bigint, v bigint) "
        "distribute by shard(v)"
    )
    big = 2**40
    s.execute(
        "insert into wide2 values "
        f"(0, 0, 1), ({big}, {big}, 2), (0, {big}, 3), ({big}, 0, 4)"
    )
    q = (
        "select wide2.a, wide2.b, sum(wide2.v) from wide2, wide2 w2 "
        "where wide2.v = w2.v group by wide2.a, wide2.b "
        "order by wide2.a, wide2.b"
    )
    s.execute("set enable_fused_execution = off")
    want = s.query(q)
    s.execute("set enable_fused_execution = on")
    fx = s.cluster.fused_executor()
    before = fx._dag.completed if fx._dag is not None else 0
    got = s.query(q)
    assert got == want and len(got) == 4
    assert fx._dag is not None and fx._dag.completed > before


def test_packed_range_wrap_detected():
    """A single key whose value spread itself overflows int64 must trip
    the pack guard (review repro: the guard must not wrap)."""
    import jax.numpy as jnp
    import numpy as np

    from opentenbase_tpu.executor.fused_dag import _pack_group_keys

    a = jnp.asarray(np.array([0, 0, 1, 1], dtype=np.int64))
    b = jnp.asarray(
        np.array([-(2**62), 2**62 - 1, 0, 1], dtype=np.int64)
    )
    mask = jnp.ones(4, dtype=bool)
    _packed, ok, _layout = _pack_group_keys([(a, None), (b, None)], mask)
    assert not bool(np.asarray(ok)), "wrapping range must clear ok"


def _mesh1_runner(sess):
    """Fresh 1-device DagRunner over the module cluster's stores."""
    import jax
    import numpy as _np

    from opentenbase_tpu.executor.fused import FusedExecutor
    from opentenbase_tpu.executor.fused_dag import DagRunner

    c = sess.cluster
    mesh1 = jax.sharding.Mesh(
        _np.asarray(jax.devices("cpu")[:1]), ("dn",)
    )
    return DagRunner(FusedExecutor(c.catalog, c.stores, mesh=mesh1))


def _run_mesh1(sess, runner, q):
    from opentenbase_tpu.executor.local import LocalExecutor
    from opentenbase_tpu.plan.analyze import analyze_statement
    from opentenbase_tpu.plan.distribute import distribute_statement
    from opentenbase_tpu.plan.optimize import optimize_statement
    from opentenbase_tpu.sql.parser import parse

    c = sess.cluster
    sp = optimize_statement(
        analyze_statement(parse(q)[0], c.catalog), c.catalog
    )
    dp = distribute_statement(sp, c.catalog)
    res = runner.run(dp, c.gts.snapshot_ts(), sess._dicts_view(), [])
    if res is None:
        return None
    final_idx, batch = res
    ex = LocalExecutor(
        c.catalog, {}, c.gts.snapshot_ts(),
        remote_inputs={final_idx: batch}, subquery_values=[],
    )
    return ex.run_plan(dp.root).to_rows()


def test_gsort_mode_engaged_for_q3_shape(sess):
    """The Q3 shape (group-by-unique-build + ORDER BY/LIMIT) at mesh
    size 1 takes the co-sort path when folds are pinned off — the
    round-3 fast join stays covered — and matches the host answer.
    (With folds on this shape chain-folds into gagg, tested below.)"""
    import opentenbase_tpu.executor.fused_dag as fd

    sess.execute("set enable_fused_execution = off")
    want = sess.query(Q3)
    sess.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    saved = fd.DIMFOLD_MAX_BUILD
    fd.DIMFOLD_MAX_BUILD = 0
    try:
        got = _run_mesh1(sess, runner, Q3)
    finally:
        fd.DIMFOLD_MAX_BUILD = saved
    assert got == want
    assert runner.last_mode == "gsort", runner.last_mode


def test_q3_chain_folds_into_gagg(sess):
    """With folds on, the 3-table Q3 peels customer INTO orders and
    orders INTO lineitem (chain folds), FD-reduces the grouping to
    l_orderkey, and runs ONE probe-width gagg sort — matching the
    host exactly."""
    sess.execute("set enable_fused_execution = off")
    want = sess.query(Q3)
    sess.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    got = _run_mesh1(sess, runner, Q3)
    assert got == want
    assert runner.last_mode == "gagg", runner.last_mode
    assert len(runner.last_folded) == 2, runner.last_folded


def test_topk_ships_only_limit_rows(sess):
    """With ORDER BY + LIMIT the device must ship k rows, not every
    group (the round-2 Q3 killer was a full-group-capacity gather)."""
    runner = _mesh1_runner(sess)
    got = _run_mesh1(sess, runner, Q3)
    assert got is not None
    assert runner.last_mode in ("gsort", "gseg", "grouped_topk", "gagg")


def test_grouped_topk_mode_when_group_not_on_build(sess):
    """Grouping by a PROBE-side non-key column can't use the build-row
    segment trick; with an agg-only ORDER BY it rides the no-join
    sorted-runs path (gagg) at mesh size 1."""
    q = (
        "select l_shipdate, sum(l_extendedprice) from orders, lineitem "
        "where o_orderkey = l_orderkey group by l_shipdate "
        "order by 2 desc limit 5"
    )
    sess.execute("set enable_fused_execution = off")
    want = sess.query(q)
    sess.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    got = _run_mesh1(sess, runner, q)
    assert got == want
    assert runner.last_mode == "gagg", runner.last_mode


def test_rows_topk_mode(sess):
    """ORDER BY ... LIMIT over plain join rows ranks on device and ships
    k rows per device at any mesh size."""
    q = (
        "select o_orderkey, l_extendedprice from orders, lineitem "
        "where o_orderkey = l_orderkey "
        "order by l_extendedprice desc limit 7"
    )
    sess.execute("set enable_fused_execution = off")
    want = sess.query(q)
    sess.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    got = _run_mesh1(sess, runner, q)
    assert got == want and len(got) == 7
    assert runner.last_mode == "rows_topk", runner.last_mode


def test_gsort_negative_sums_fall_back_correctly(sess):
    """Negative aggregate values break the monotone-prefix fast path;
    the runtime flag must reject it and the query still answers right."""
    s = sess
    s.execute(
        "create table negd (g bigint, v bigint) distribute by shard(g)"
    )
    s.execute(
        "insert into negd values (1, -5), (1, 10), (2, -7), (3, 4)"
    )
    s.execute(
        "create table negk (k bigint, tag int) distribute by shard(k)"
    )
    s.execute("insert into negk values (1, 0), (2, 1), (3, 0)")
    q = (
        "select negd.g, sum(negd.v) from negk, negd "
        "where negk.k = negd.g group by negd.g "
        "order by 2 desc limit 2"
    )
    s.execute("set enable_fused_execution = off")
    want = s.query(q)
    s.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    got = _run_mesh1(sess, runner, q)
    assert got == want, (got, want)


def test_count_star_via_gsort(sess):
    """count(*) and count(col) ride the run-length scans. Folds are
    pinned off so the gsort co-sort path itself stays covered (with
    folds on, this foldable shape prefers gagg — tested separately)."""
    import opentenbase_tpu.executor.fused_dag as fd

    q = (
        "select o_orderkey, count(*), sum(l_extendedprice), "
        "o_orderdate from orders, lineitem "
        "where o_orderkey = l_orderkey "
        "group by o_orderkey, o_orderdate "
        "order by 2 desc, o_orderkey limit 6"
    )
    sess.execute("set enable_fused_execution = off")
    want = sess.query(q)
    sess.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    saved = fd.DIMFOLD_MAX_BUILD
    fd.DIMFOLD_MAX_BUILD = 0
    try:
        got = _run_mesh1(sess, runner, q)
    finally:
        fd.DIMFOLD_MAX_BUILD = saved
    assert got == want
    assert runner.last_mode == "gsort", runner.last_mode


def test_gsort_min_max(sess):
    """min()/max() in the join-bearing co-sort path (VERDICT r4 ask
    #6): one reverse segmented scan lands the run reduction at the
    build position — a min() in a Q3-like select list must no longer
    demote off the device."""
    import opentenbase_tpu.executor.fused_dag as fd

    q = (
        "select o_orderkey, min(l_extendedprice), "
        "max(l_extendedprice), sum(l_extendedprice), o_orderdate "
        "from orders, lineitem where o_orderkey = l_orderkey "
        "group by o_orderkey, o_orderdate "
        "order by 4 desc, o_orderkey limit 8"
    )
    sess.execute("set enable_fused_execution = off")
    want = sess.query(q)
    sess.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    saved = fd.DIMFOLD_MAX_BUILD
    fd.DIMFOLD_MAX_BUILD = 0
    try:
        got = _run_mesh1(sess, runner, q)
    finally:
        fd.DIMFOLD_MAX_BUILD = saved
    assert got == want, (got[:3], want[:3])
    assert runner.last_mode == "gsort", runner.last_mode


def test_gsort_min_max_order_by_min(sess):
    """Ranking BY the min() itself: the per-group reduction feeds the
    device top-k packing, still without leaving the co-sort path."""
    import opentenbase_tpu.executor.fused_dag as fd

    q = (
        "select o_orderkey, min(l_shipdate) from orders, lineitem "
        "where o_orderkey = l_orderkey group by o_orderkey "
        "order by 2, o_orderkey limit 6"
    )
    sess.execute("set enable_fused_execution = off")
    want = sess.query(q)
    sess.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    saved = fd.DIMFOLD_MAX_BUILD
    fd.DIMFOLD_MAX_BUILD = 0
    try:
        got = _run_mesh1(sess, runner, q)
    finally:
        fd.DIMFOLD_MAX_BUILD = saved
    assert got == want, (got[:3], want[:3])
    assert runner.last_mode == "gsort", runner.last_mode


def test_gsort_min_max_negative_values(sess):
    """Negative values stress the sentinel fill (the non-negativity
    guard protects SUM's monotone prefix only — min/max must keep the
    device mode and the answer with negatives present)."""
    import opentenbase_tpu.executor.fused_dag as fd

    s = sess
    s.execute(
        "create table negm (g bigint, v bigint) distribute by shard(g)"
    )
    s.execute(
        "insert into negm values (1, -5), (1, 10), (2, -7), (2, -9), "
        "(3, 4), (3, 0), (3, -1)"
    )
    s.execute(
        "create table negmk (k bigint, tag int) distribute by shard(k)"
    )
    s.execute("insert into negmk values (1, 0), (2, 1), (3, 0)")
    q = (
        "select negmk.k, min(negm.v), max(negm.v) from negmk, negm "
        "where negmk.k = negm.g group by negmk.k "
        "order by negmk.k limit 3"
    )
    s.execute("set enable_fused_execution = off")
    want = s.query(q)
    s.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    saved = fd.DIMFOLD_MAX_BUILD
    fd.DIMFOLD_MAX_BUILD = 0
    try:
        got = _run_mesh1(sess, runner, q)
    finally:
        fd.DIMFOLD_MAX_BUILD = saved
    assert got == want, (got, want)
    assert runner.last_mode == "gsort", runner.last_mode


def test_gsort_residual_qual(sess):
    """A join RESIDUAL (non-equi ON condition over both sides) rides
    the co-sort path: build inputs forward-propagate from the run's
    leading build row, failing probe rows leave every reduction, and
    groups whose rows ALL fail disappear (VERDICT r4 ask #6)."""
    import opentenbase_tpu.executor.fused_dag as fd

    q = (
        "select o_orderkey, count(*), sum(l_extendedprice), "
        "min(l_extendedprice), o_orderdate "
        "from orders join lineitem on o_orderkey = l_orderkey "
        "and l_shipdate > o_orderdate "
        "group by o_orderkey, o_orderdate "
        "order by 3 desc, o_orderkey limit 8"
    )
    sess.execute("set enable_fused_execution = off")
    want = sess.query(q)
    sess.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    saved = fd.DIMFOLD_MAX_BUILD
    fd.DIMFOLD_MAX_BUILD = 0
    try:
        got = _run_mesh1(sess, runner, q)
    finally:
        fd.DIMFOLD_MAX_BUILD = saved
    assert got == want, (got[:3], want[:3])
    assert runner.last_mode == "gsort", runner.last_mode


def test_gsort_residual_all_fail_group_vanishes(sess):
    """A group whose every probe row fails the residual must not emit
    at all (its run exists but holds zero passing rows)."""
    import opentenbase_tpu.executor.fused_dag as fd

    s = sess
    s.execute(
        "create table rk (k bigint, cutoff bigint) "
        "distribute by shard(k)"
    )
    s.execute("insert into rk values (1, 100), (2, 0), (3, 50)")
    s.execute(
        "create table rv (g bigint, v bigint) distribute by shard(g)"
    )
    s.execute(
        "insert into rv values (1, 10), (1, 20), (2, 1), (2, 2), "
        "(3, 60), (3, 40)"
    )
    q = (
        "select rk.k, count(*), sum(rv.v) from rk "
        "join rv on rk.k = rv.g and rv.v > rk.cutoff "
        "group by rk.k order by rk.k limit 5"
    )
    s.execute("set enable_fused_execution = off")
    want = s.query(q)
    # k=1: no v > 100 -> group absent; k=2: both pass; k=3: 60 passes
    assert want == [(2, 2, 3), (3, 1, 60)], want
    s.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    saved = fd.DIMFOLD_MAX_BUILD
    fd.DIMFOLD_MAX_BUILD = 0
    try:
        got = _run_mesh1(sess, runner, q)
    finally:
        fd.DIMFOLD_MAX_BUILD = saved
    assert got == want, (got, want)
    assert runner.last_mode == "gsort", runner.last_mode


def test_count_star_via_gagg_fold(sess):
    """The same foldable shape with folds ON rides gagg: the dim join
    becomes a dense gather, grouping FD-reduces to the probe key, and
    the carried ORDER BY column restores output order."""
    q = (
        "select o_orderkey, count(*), sum(l_extendedprice), "
        "o_orderdate from orders, lineitem "
        "where o_orderkey = l_orderkey "
        "group by o_orderkey, o_orderdate "
        "order by 2 desc, o_orderkey limit 6"
    )
    sess.execute("set enable_fused_execution = off")
    want = sess.query(q)
    sess.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    got = _run_mesh1(sess, runner, q)
    assert got == want
    assert runner.last_mode == "gagg", runner.last_mode
    assert runner.last_folded, "top join did not fold"


def test_demotion_is_loud_not_silent(sess):
    """An unexpected exception inside the fused path must (a) not break
    the query — the host path answers — and (b) land in pg_stat_fused
    (VERDICT r2: the blanket except may never demote invisibly)."""
    s = sess
    fx = s.cluster.fused_executor()
    q = (
        "select o_shippriority, sum(l_extendedprice) from orders, "
        "lineitem where o_orderkey = l_orderkey group by o_shippriority "
        "order by o_shippriority"
    )
    s.execute("set enable_fused_execution = off")
    want = s.query(q)
    s.execute("set enable_fused_execution = on")
    orig = fx.dag_output
    fx.dag_output = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("injected fused failure")
    )
    try:
        before = len(fx.dag_demotions)
        got = s.query(q)
        assert got == want  # host path answered
        assert len(fx.dag_demotions) == before + 1
        assert "injected fused failure" in fx.dag_demotions[-1]
        stat = s.query(
            "select count(*) from pg_stat_fused where event = 'demoted'"
        )
        assert stat[0][0] >= 1
    finally:
        fx.dag_output = orig


def test_unsupported_fallback_reason_recorded(sess):
    """Plans outside the DAG subset must leave a reason in
    pg_stat_fused rather than vanishing to the host path."""
    s = sess
    fx = s.cluster.fused_executor()
    # a left join with an ORDER BY/LIMIT shape routes to the DAG runner
    # first and is outside its subset -> the reason must be recorded
    q = (
        "select o_orderkey from orders left join lineitem "
        "on o_orderkey = l_orderkey order by o_orderkey limit 3"
    )
    s.execute("set enable_fused_execution = on")
    s.query(q)
    assert fx._dag is not None and fx._dag.unsupported, (
        "DAG fallback left no reason"
    )
    reasons = s.query(
        "select count(*) from pg_stat_fused "
        "where event = 'unsupported'"
    )
    assert reasons[0][0] >= 1


def test_gagg_mode_clickbench_shape(sess):
    """High-cardinality GROUP BY + ORDER BY agg LIMIT (the ClickBench
    hot pattern) rides the no-join sort formulation."""
    q = (
        "select l_orderkey, count(*), sum(l_extendedprice) "
        "from lineitem group by l_orderkey "
        "order by 2 desc, 3 desc limit 8"
    )
    sess.execute("set enable_fused_execution = off")
    want = sess.query(q)
    sess.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    got = _run_mesh1(sess, runner, q)
    assert got == want
    assert runner.last_mode == "gagg", runner.last_mode


def test_gagg_group_col_order_decodes_key(sess):
    """ORDER BY on a group column rides gagg: the monotone packing is
    invertible, so the ranking reads key values decoded from the sorted
    packed key (no extra operand, no fallback)."""
    q = (
        "select l_orderkey, sum(l_extendedprice) from lineitem "
        "group by l_orderkey order by l_orderkey limit 8"
    )
    sess.execute("set enable_fused_execution = off")
    want = sess.query(q)
    sess.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    got = _run_mesh1(sess, runner, q)
    assert got == want
    assert runner.last_mode == "gagg", runner.last_mode


def test_gsort_narrow_overflow_retries_wide(sess):
    """Keys past the i32 narrow range must trip the runtime flag and
    re-run the wide (i64) program with identical results."""
    s = sess
    big = 2**40
    s.execute(
        "create table wk (k bigint, pr int) distribute by shard(k)"
    )
    s.execute("insert into wk values " + ",".join(
        f"({big + i}, {i % 3})" for i in range(50)
    ))
    s.execute(
        "create table wl (lk bigint, amt bigint) distribute by shard(lk)"
    )
    s.execute("insert into wl values " + ",".join(
        f"({big + (i % 50)}, {i})" for i in range(400)
    ))
    q = (
        "select wl.lk, sum(wl.amt), wk.pr from wk, wl "
        "where wk.k = wl.lk group by wl.lk, wk.pr "
        "order by 2 desc limit 5"
    )
    s.execute("set enable_fused_execution = off")
    want = s.query(q)
    s.execute("set enable_fused_execution = on")
    runner = _mesh1_runner(sess)
    import opentenbase_tpu.executor.fused_dag as fd

    saved = fd.DIMFOLD_MAX_BUILD
    fd.DIMFOLD_MAX_BUILD = 0
    try:
        got = _run_mesh1(sess, runner, q)
    finally:
        fd.DIMFOLD_MAX_BUILD = saved
    assert got == want
    assert runner.last_mode == "gsort"
    assert runner._narrow_off, "narrow overflow was never flagged"
