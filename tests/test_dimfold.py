"""Dimension-fold joins (executor/fused_dag.py _lookup_dense): an inner
join against a small dense-keyed build side must run as a direct-index
gather, produce results identical to the host path, and fall back
through the runtime density flag on gaps, duplicates, and updates —
the TPU-native analog of the reference's replicated-table join
shippability (src/backend/optimizer/util/pgxcship.c:139)."""

import numpy as np
import pytest

from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.plan import batchplan


def _both(s, q, expect_dag=True):
    s.execute("set enable_fused_execution = off")
    host = s.query(q)
    s.execute("set enable_fused_execution = on")
    fx = s.cluster.fused_executor()
    before = fx._dag.completed if fx._dag is not None else 0
    dev = s.query(q)
    if expect_dag is True:
        assert fx._dag is not None and fx._dag.completed > before
    return host, dev


def _runner(s):
    return s.cluster.fused_executor()._dag


@pytest.fixture()
def sess():
    """1-datanode cluster: every join is fold-eligible regardless of
    motion planning, isolating the dense-lookup machinery."""
    s = Cluster(num_datanodes=1, shard_groups=16).session()
    rng = np.random.default_rng(3)
    s.execute(
        "create table dim (d_key bigint, d_cat int, d_name int) "
        "distribute by replication"
    )
    s.execute(
        "create table fact (f_key bigint, f_val bigint) "
        "distribute by roundrobin"
    )
    nd, nf = 100, 1200
    s.execute("insert into dim values " + ",".join(
        f"({k},{c},{n})" for k, c, n in zip(
            range(10, 10 + nd),
            rng.integers(0, 4, nd),
            rng.integers(0, 1000, nd),
        )
    ))
    s.execute("insert into fact values " + ",".join(
        f"({k},{v})" for k, v in zip(
            rng.integers(0, 10 + nd + 20, nf),  # some keys miss the dim
            rng.integers(1, 100, nf),
        )
    ))
    return s


Q_AGG = (
    "select d_cat, count(*), sum(f_val) from fact, dim "
    "where f_key = d_key group by d_cat order by d_cat"
)


def test_dense_dim_fold_matches_host(sess):
    host, dev = _both(sess, Q_AGG)
    assert dev == host and len(dev) == 4
    assert _runner(sess).last_folded, "dense dim join did not fold"


def test_fold_with_dim_filter(sess):
    q = (
        "select count(*), sum(f_val) from fact, dim "
        "where f_key = d_key and d_cat = 2"
    )
    host, dev = _both(sess, q)
    assert dev == host
    assert _runner(sess).last_folded


def test_gap_dim_falls_back(sess):
    # punch holes in the key range: dense check must fail, the flag
    # must disable the fold, and sort-merge must answer correctly
    sess.execute("delete from dim where d_cat = 1")
    host, dev = _both(sess, Q_AGG)
    assert dev == host
    r = _runner(sess)
    assert r._fold_off, "gap dim did not trip the density flag"
    assert not r.last_folded


def test_duplicate_dim_keys_fall_back(sess):
    # a duplicated build key breaks the position identity; with random
    # fact keys duplicated too, no side can build — the DAG correctly
    # hands the whole join to the host path, results unchanged
    sess.execute("insert into dim values (50, 9, 9)")
    host, dev = _both(sess, Q_AGG, expect_dag=None)
    assert dev == host


def test_update_creates_fallback_then_recovers_semantics(sess):
    # an UPDATE leaves a dead version with the same key in the store;
    # results must stay correct either way
    sess.execute("update dim set d_cat = 0 where d_key = 11")
    host, dev = _both(sess, Q_AGG)
    assert dev == host


def test_null_probe_keys_never_match(sess):
    sess.execute("insert into fact values (null, 7)")
    host, dev = _both(sess, Q_AGG)
    assert dev == host


def test_fold_multidn_broadcast_dim():
    """On a multi-device mesh the fold requires a broadcast-motion
    (replicated) build subtree — exercise it end to end."""
    s = Cluster(num_datanodes=4, shard_groups=32).session()
    rng = np.random.default_rng(5)
    s.execute(
        "create table dim (d_key bigint, d_cat int) "
        "distribute by replication"
    )
    s.execute(
        "create table fact (f_key bigint, f_val bigint) "
        "distribute by shard(f_key)"
    )
    nd, nf = 64, 1500
    s.execute("insert into dim values " + ",".join(
        f"({k},{c})" for k, c in zip(
            range(nd), rng.integers(0, 3, nd)
        )
    ))
    s.execute("insert into fact values " + ",".join(
        f"({k},{v})" for k, v in zip(
            rng.integers(0, nd, nf), rng.integers(1, 50, nf)
        )
    ))
    host, dev = _both(
        s,
        "select d_cat, sum(f_val) from fact, dim where f_key = d_key "
        "group by d_cat order by d_cat",
    )
    assert dev == host and len(dev) == 3


def test_gagg_min_max_aggs(sess):
    """min/max ride the segmented scan in gagg (VERDICT r3 weak-6:
    near-benchmark shapes with min()/max() must not demote). Grouping
    by the shard key keeps groups per-device complete, the gagg
    precondition."""
    sess.execute(
        "create table mm (m_key bigint, m_val bigint) "
        "distribute by shard(m_key)"
    )
    rng = np.random.default_rng(11)
    sess.execute("insert into mm values " + ",".join(
        f"({k},{v})" for k, v in zip(
            rng.integers(0, 50, 600), rng.integers(-500, 500, 600)
        )
    ))
    q = (
        "select m_key, min(m_val), max(m_val), count(*) from mm "
        "group by m_key order by 2, m_key limit 5"
    )
    host, dev = _both(sess, q)
    assert dev == host
    assert _runner(sess).last_mode == "gagg"


def test_gagg_min_max_with_nulls(sess):
    sess.execute("insert into fact values (12, null), (12, null)")
    q = (
        "select d_cat, max(f_val), min(f_val) from fact, dim "
        "where f_key = d_key group by d_cat order by 2 desc limit 4"
    )
    host, dev = _both(sess, q)
    assert dev == host


def test_gagg_narrow_overflow_retries_wide(sess):
    """Group keys past the i32 packing range trip the runtime flag and
    re-run wide with identical results."""
    sess.execute(
        "create table wide (w_key bigint, w_val bigint) "
        "distribute by shard(w_key)"
    )
    # keys SPREAD over more than 2^31 so the i32 narrow packing
    # (which rebases at the running min) genuinely overflows
    sess.execute("insert into wide values " + ",".join(
        f"({(i % 40) * 2**26},{i})" for i in range(400)
    ))
    q = (
        "select w_key, sum(w_val) from wide group by w_key "
        "order by 2 desc limit 5"
    )
    host, dev = _both(sess, q)
    assert dev == host
    r = _runner(sess)
    assert r.last_mode == "gagg"
    assert r._narrow_off, "narrow overflow was never flagged"


def test_windowed_gagg_matches_host(monkeypatch):
    """Bigger-than-budget probes stream in windows (wgagg): per-window
    compacted partials merge in one final program. Forced here with a
    tiny batchplan.DEFAULT_WINDOW_BUDGET on a 1-device mesh; results must match
    the host path exactly, including FD-dropped group keys and
    cross-window groups (the reference analog: multi-batch hash join,
    nodeHash.c ExecHashIncreaseNumBatches)."""
    import jax

    monkeypatch.setattr(batchplan, "DEFAULT_WINDOW_BUDGET", 200_000)
    s = Cluster(num_datanodes=1, shard_groups=16).session()
    rng = np.random.default_rng(7)
    s.execute(
        "create table dim (k bigint, cat bigint) "
        "distribute by replication"
    )
    s.execute(
        "create table f (fk bigint, v bigint) distribute by roundrobin"
    )
    nd, nf = 64, 6000
    s.execute("insert into dim values " + ",".join(
        f"({i},{i % 7})" for i in range(nd)
    ))
    s.execute("insert into f values " + ",".join(
        f"({int(k)},{int(v)})" for k, v in zip(
            rng.integers(0, nd, nf), rng.integers(1, 50, nf)
        )
    ))
    q = (
        "select fk, cat, sum(v), count(*) from f, dim where fk = k "
        "group by fk, cat order by 3 desc, fk limit 9"
    )
    s.execute("set enable_fused_execution = off")
    want = s.query(q)

    from opentenbase_tpu.executor.fused import FusedExecutor
    from opentenbase_tpu.executor.fused_dag import DagRunner
    from opentenbase_tpu.executor.local import LocalExecutor
    from opentenbase_tpu.plan.analyze import analyze_statement
    from opentenbase_tpu.plan.distribute import distribute_statement
    from opentenbase_tpu.plan.optimize import optimize_statement
    from opentenbase_tpu.sql.parser import parse

    c = s.cluster
    mesh1 = jax.sharding.Mesh(
        np.asarray(jax.devices("cpu")[:1]), ("dn",)
    )
    runner = DagRunner(FusedExecutor(c.catalog, c.stores, mesh=mesh1))
    sp = optimize_statement(
        analyze_statement(parse(q)[0], c.catalog), c.catalog
    )
    dp = distribute_statement(sp, c.catalog)
    res = runner.run(dp, c.gts.snapshot_ts(), s._dicts_view(), [])
    assert res is not None, runner.unsupported[-3:]
    assert runner.last_mode == "wgagg", runner.last_mode
    final_idx, batch = res
    ex = LocalExecutor(
        c.catalog, {}, c.gts.snapshot_ts(),
        remote_inputs={final_idx: batch}, subquery_values=[],
    )
    got = ex.run_plan(dp.root).to_rows()
    assert got == want, (got, want)


def test_windowed_gagg_minmax_and_carried_order(monkeypatch):
    """min/max partials merge across windows; ORDER BY an FD-dropped
    key rides the carried columns."""
    import jax

    monkeypatch.setattr(batchplan, "DEFAULT_WINDOW_BUDGET", 200_000)
    s = Cluster(num_datanodes=1, shard_groups=16).session()
    rng = np.random.default_rng(9)
    s.execute(
        "create table dim (k bigint, cat bigint) "
        "distribute by replication"
    )
    s.execute(
        "create table f (fk bigint, v bigint) distribute by roundrobin"
    )
    s.execute("insert into dim values " + ",".join(
        f"({i},{(i * 3) % 11})" for i in range(48)
    ))
    vals = [
        f"({int(kk)},{int(v)})" for kk, v in zip(
            rng.integers(0, 48, 5000),
            rng.integers(-900, 900, 5000),
        )
    ]
    vals.append("(3, null)")
    s.execute("insert into f values " + ",".join(vals))
    q = (
        "select fk, cat, min(v), max(v), sum(v) from f, dim "
        "where fk = k group by fk, cat "
        "order by 5 desc, cat, fk limit 11"
    )
    s.execute("set enable_fused_execution = off")
    want = s.query(q)

    import jax as _j
    from opentenbase_tpu.executor.fused import FusedExecutor
    from opentenbase_tpu.executor.fused_dag import DagRunner
    from opentenbase_tpu.executor.local import LocalExecutor
    from opentenbase_tpu.plan.analyze import analyze_statement
    from opentenbase_tpu.plan.distribute import distribute_statement
    from opentenbase_tpu.plan.optimize import optimize_statement
    from opentenbase_tpu.sql.parser import parse

    c = s.cluster
    mesh1 = _j.sharding.Mesh(
        np.asarray(_j.devices("cpu")[:1]), ("dn",)
    )
    runner = DagRunner(FusedExecutor(c.catalog, c.stores, mesh=mesh1))
    sp = optimize_statement(
        analyze_statement(parse(q)[0], c.catalog), c.catalog
    )
    dp = distribute_statement(sp, c.catalog)
    res = runner.run(dp, c.gts.snapshot_ts(), s._dicts_view(), [])
    assert res is not None, runner.unsupported[-3:]
    assert runner.last_mode == "wgagg", runner.last_mode
    final_idx, batch = res
    ex = LocalExecutor(
        c.catalog, {}, c.gts.snapshot_ts(),
        remote_inputs={final_idx: batch}, subquery_values=[],
    )
    got = ex.run_plan(dp.root).to_rows()
    assert got == want, (got, want)


def test_windowed_gagg_hoisted_build_prep(monkeypatch):
    """A big window-invariant build side hoists into ONE prep program
    (evaluate + key-sort once) and every window consumes it presorted —
    results identical, top join still folds."""
    import jax

    monkeypatch.setattr(batchplan, "DEFAULT_WINDOW_BUDGET", 200_000)
    s = Cluster(num_datanodes=1, shard_groups=16).session()
    rng = np.random.default_rng(13)
    s.execute(
        "create table seg (g bigint, cat bigint) "
        "distribute by replication"
    )
    s.execute(
        "create table ord (ok bigint, gk bigint, od bigint) "
        "distribute by replication"
    )
    s.execute(
        "create table f (fk bigint, v bigint) distribute by roundrobin"
    )
    ng, no, nf = 32, 600, 7000
    s.execute("insert into seg values " + ",".join(
        f"({i},{i % 5})" for i in range(ng)
    ))
    s.execute("insert into ord values " + ",".join(
        f"({i},{int(g)},{int(d)})" for i, g, d in zip(
            range(no), rng.integers(0, ng, no),
            rng.integers(0, 99, no),
        )
    ))
    s.execute("insert into f values " + ",".join(
        f"({int(k)},{int(v)})" for k, v in zip(
            rng.integers(0, no + 40, nf), rng.integers(1, 60, nf)
        )
    ))
    q = (
        "select fk, od, cat, sum(v), count(*) from f, ord, seg "
        "where fk = ok and gk = g and cat < 4 "
        "group by fk, od, cat order by 4 desc, fk limit 10"
    )
    s.execute("set enable_fused_execution = off")
    want = s.query(q)

    from opentenbase_tpu.executor.fused import FusedExecutor
    from opentenbase_tpu.executor.fused_dag import DagRunner
    from opentenbase_tpu.executor.local import LocalExecutor
    from opentenbase_tpu.plan.analyze import analyze_statement
    from opentenbase_tpu.plan.distribute import distribute_statement
    from opentenbase_tpu.plan.optimize import optimize_statement
    from opentenbase_tpu.sql.parser import parse

    c = s.cluster
    mesh1 = jax.sharding.Mesh(
        np.asarray(jax.devices("cpu")[:1]), ("dn",)
    )
    runner = DagRunner(FusedExecutor(c.catalog, c.stores, mesh=mesh1))
    monkeypatch.setattr(runner, "HOIST_MIN_ROWS", 100)
    sp = optimize_statement(
        analyze_statement(parse(q)[0], c.catalog), c.catalog
    )
    dp = distribute_statement(sp, c.catalog)
    res = runner.run(dp, c.gts.snapshot_ts(), s._dicts_view(), [])
    assert res is not None, runner.unsupported[-3:]
    assert runner.last_mode == "wgagg", runner.last_mode
    assert ("prep",) == tuple(
        k[0] for k in runner._programs if k[0] == "prep"
    ), "prep program was not compiled (hoist did not engage)"
    # the presorted build carries the top fold's match bit in a column
    # the final reads (marked on rows already in key order: no gather)
    records = [
        rec for entry in runner._programs.values() for prog in entry
        for rec in getattr(prog, "joins", {}).values()
    ]
    assert "fold:1024x1024 bit=cat" in records, records
    final_idx, batch = res
    ex = LocalExecutor(
        c.catalog, {}, c.gts.snapshot_ts(),
        remote_inputs={final_idx: batch}, subquery_values=[],
    )
    got = ex.run_plan(dp.root).to_rows()
    assert got == want, (got, want)


def test_dag_literal_change_binds_current_values(sess):
    """The DAG runner's structural program cache must bind the CURRENT
    query's literals (round-4 regression: the first query's lifted
    constants were baked into the cached param specs)."""
    q7 = (
        "select d_cat, count(*) from fact, dim "
        "where f_key = d_key and d_cat = 2 group by d_cat"
    )
    q1 = (
        "select d_cat, count(*) from fact, dim "
        "where f_key = d_key and d_cat = 3 group by d_cat"
    )
    h7, g7 = _both(sess, q7)
    assert g7 == h7
    h1, g1 = _both(sess, q1)
    assert g1 == h1
    assert g1 != g7  # different literal, different answer
    h7b, g7b = _both(sess, q7)
    assert g7b == h7
