"""Fused mesh executor: results must match the general fragment executor
exactly, and the multichip dry-run must validate on a virtual mesh."""

import threading

import numpy as np
import pytest

from opentenbase_tpu.engine import Cluster


@pytest.fixture(scope="module")
def sess():
    s = Cluster(num_datanodes=2, shard_groups=32).session()
    s.execute(
        "create table li (flag text, status text, qty numeric(10,2), "
        "price numeric(12,2), disc numeric(4,2), ship date) "
        "distribute by roundrobin"
    )
    rng = np.random.default_rng(3)
    n = 4000
    flags = rng.choice(["A", "N", "R"], n)
    statuses = rng.choice(["F", "O"], n)
    rows = ",".join(
        f"('{f}','{st}',{q:.2f},{p:.2f},{d:.2f},'{dt}')"
        for f, st, q, p, d, dt in zip(
            flags,
            statuses,
            rng.uniform(1, 50, n).round(2),
            rng.uniform(9, 1000, n).round(2),
            rng.uniform(0, 0.1, n).round(2),
            np.datetime64("1994-01-01") + rng.integers(0, 1000, n),
        )
    )
    s.execute("insert into li values " + rows)
    return s


QUERIES = [
    # Q6 shape: filter + scalar agg
    "select sum(price * disc), count(*) from li "
    "where ship >= date '1994-06-01' and ship < date '1995-06-01' "
    "and disc between 0.02 and 0.08 and qty < 30",
    # Q1 shape: grouped aggregation with several aggs
    "select flag, status, count(*), sum(qty), avg(price), min(disc), max(disc) "
    "from li where ship <= date '1996-09-01' group by flag, status "
    "order by flag, status",
    # text-filtered grouped agg
    "select status, count(*) from li where flag = 'A' group by status order by status",
    # empty result
    "select sum(qty) from li where qty < 0",
]


@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_fused_matches_general(sess, qi):
    q = QUERIES[qi]
    sess.execute("set enable_fused_execution to false")
    expected = sess.query(q)
    sess.execute("set enable_fused_execution to true")
    got = sess.query(q)
    assert len(got) == len(expected)
    for g, e in zip(got, expected):
        for gv, ev in zip(g, e):
            if isinstance(ev, float):
                assert gv == pytest.approx(ev), (q, got, expected)
            else:
                assert gv == ev, (q, got, expected)


def test_fused_actually_engaged(sess):
    fx = sess.cluster.fused_executor()
    assert fx is not None
    sess.execute("set enable_fused_execution to true")
    sess.query("select count(*) from li")
    assert len(fx._programs) > 0


def test_fused_sees_new_writes(sess):
    sess.execute("set enable_fused_execution to true")
    before = sess.query("select count(*) from li")[0][0]
    sess.execute(
        "insert into li values ('Z','F',1.00,2.00,0.01,'1994-01-01')"
    )
    after = sess.query("select count(*) from li")[0][0]
    assert after == before + 1
    sess.execute("delete from li where flag = 'Z'")
    assert sess.query("select count(*) from li")[0][0] == before


def test_dryrun_multichip_virtual():
    import __graft_entry__ as g

    g.dryrun_multichip(4)


def test_entry_compiles():
    import __graft_entry__ as g
    import jax

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    rev, cnt = [np.asarray(o) for o in out]
    assert cnt > 0 and rev > 0


def test_literal_change_reuses_program_not_parameters(jax8):
    """Structural program caching lifts literals to params — but the
    params must bind THIS query's literals, not the compile-time ones.
    Round-4 regression: 'who = 1' silently returned the count for
    'who = 7' on every fused path."""
    from opentenbase_tpu.engine import Cluster

    c = Cluster(num_datanodes=2, shard_groups=32)
    s = c.session()
    s.execute(
        "create table lit (k bigint, who bigint) distribute by shard(k)"
    )
    s.execute("insert into lit values " + ",".join(
        f"({j},7)" for j in range(40)
    ))
    s.execute("insert into lit values " + ",".join(
        f"({100 + j},1)" for j in range(12)
    ))
    assert s.query("select count(*) from lit where who = 7") == [(40,)]
    assert s.query("select count(*) from lit where who = 1") == [(12,)]
    assert s.query("select count(*) from lit where who = 7") == [(40,)]
    assert s.query(
        "select sum(k) from lit where who = 1"
    ) == [(sum(range(100, 112)),)]
    # grouped shape too
    assert s.query(
        "select who, count(*) from lit where k < 100 group by who "
        "order by who"
    ) == [(7, 40)]
    assert s.query(
        "select who, count(*) from lit where k < 1000 group by who "
        "order by who"
    ) == [(1, 12), (7, 40)]


def _xla_lane_plans(fx):
    """The MXU lane bounds in the keys of the XLA grouped programs."""
    return {k[-1] for k in fx._programs if k[0] != "pallas" and k[-1]}


def _fused_row(s, event):
    return int(dict(s.query("select event, detail from pg_stat_fused"))[event])


def test_lane_plan_follows_the_statistics(jax8):
    """The MXU group reduce cuts as many limbs as the column statistics
    say the values need. A value that crosses a limb boundary binds a
    wider program (never the narrower one again); an argument the
    interval arithmetic cannot bound keeps its dtype's width."""
    s = Cluster(num_datanodes=2, shard_groups=32).session()
    s.execute(
        "create table lp (k bigint, g bigint, v bigint) "
        "distribute by shard(k)"
    )
    s.execute("insert into lp values " + ",".join(
        f"({i},{i % 20},{i % 101})" for i in range(600)
    ))
    fx = s.cluster.fused_executor()
    # twenty groups: beyond the Pallas kernel's joint key domain, so the
    # XLA grouped program answers
    q = "select g, sum(v), count(v) from lp group by g order by g"

    def want(rows):
        out: dict = {}
        for g, v in rows:
            t = out.setdefault(g, [0, 0])
            t[0] += v
            t[1] += 1
        return [(g, t[0], t[1]) for g, t in sorted(out.items())]

    rows = [(i % 20, i % 101) for i in range(600)]
    assert s.query(q) == want(rows)
    narrow = _xla_lane_plans(fx)
    assert len(narrow) == 1
    (b,) = narrow
    assert b.key_limbs == ((1, False),) and b.arg_limbs == ((1, False),)
    assert b.arg_ids == (0, 0)  # sum(v) and count(v): one argument
    bounded = _fused_row(s, "mxu_plans_bounded")
    assert bounded >= 1 and _fused_row(s, "mxu_plans_full") == 0

    # 70000 needs three limbs, -3 a sign: the old program must not run
    s.execute("insert into lp values (1000, 3, 70000), (1001, 4, -3)")
    rows += [(3, 70000), (4, -3)]
    assert s.query(q) == want(rows)
    (wide,) = _xla_lane_plans(fx) - narrow
    assert wide.arg_limbs == ((3, True),)
    assert _fused_row(s, "mxu_plans_bounded") > bounded

    # integer division and modulo are outside pallas_scan.bound: every
    # limb of the dtype, and still the exact answer
    got = s.query(
        "select g % 7, sum(v / 2), count(*) from lp group by g % 7 "
        "order by 1"
    )
    ref: dict = {}
    for g, v in rows:
        t = ref.setdefault(g % 7, [0, 0])
        t[0] += int(v / 2)  # PG integer division truncates toward zero
        t[1] += 1
    assert got == [(g, t[0], t[1]) for g, t in sorted(ref.items())]
    assert _fused_row(s, "mxu_plans_full") >= 1
    (full,) = _xla_lane_plans(fx) - narrow - {wide}
    assert full.key_limbs == (None,) and full.arg_limbs == (None,)


class _WatchedGate:
    """The cluster's fused gate, saying when a statement has reached it."""

    def __init__(self, lock):
        self.lock = lock
        self.reached = threading.Event()

    def acquire(self):
        self.reached.set()
        return self.lock.acquire()

    def release(self):
        self.lock.release()

    def __enter__(self):
        return self.lock.acquire()

    def __exit__(self, *exc):
        self.lock.release()


def test_guc_shadows_are_written_under_the_fused_gate():
    """The executor is the cluster's, one for every session: a statement
    writes its join_mode / device_memory_limit / watchdog shadows onto
    it only once it HOLDS the fused gate, so a session waiting for the
    gate cannot change what the holder's program is built under."""
    c = Cluster(num_datanodes=1, shard_groups=16)
    try:
        s = c.session()
        s.execute("create table gg (k bigint, v bigint) "
                  "distribute by shard(k)")
        s.execute("insert into gg values (1, 2), (3, 4)")
        fx = c.fused_executor()
        fx.device_memory_limit = 111  # the holder's
        fx.join_mode = "radix"
        waiter = c.session()
        waiter.execute("set device_memory_limit = 222")
        waiter.execute("set join_mode = sortmerge")
        gate = c._fused_lock = _WatchedGate(c._fused_lock)
        got = []
        t = threading.Thread(
            target=lambda: got.append(waiter.query("select sum(v) from gg"))
        )
        with gate:
            t.start()
            assert gate.reached.wait(60), "statement never reached the gate"
            assert (fx.device_memory_limit, fx.join_mode) == (111, "radix")
        t.join(60)
        assert got == [[(6,)]]
        assert (fx.device_memory_limit, fx.join_mode) == (222, "sortmerge")
    finally:
        c.close()
