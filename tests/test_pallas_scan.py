"""Pallas fused scan kernel (ops/pallas_scan.py), interpreter mode.

Validates the certifier (what may run in f32), the limb-accumulation
exactness story, and the engine integration: with enable_pallas_scan on,
eligible ungrouped filter+SUM/COUNT queries produce bit-identical
results to the XLA path they replace."""

import numpy as np
import pytest

import jax.numpy as jnp

from opentenbase_tpu import types as t
from opentenbase_tpu.engine import Cluster
from opentenbase_tpu.ops import pallas_scan as ps
from opentenbase_tpu.plan import texpr as E


def C(i, ty=t.INT8):
    return E.Col(i, ty)


def K(v, ty=t.INT8):
    return E.Const(v, ty)


def test_certifier_bounds():
    cb = [1e7, 10.0, None]
    assert ps.bound(C(0), cb) == 1e7
    assert ps.bound(E.BinE("*", C(0), C(1), t.INT8), cb) == 1e8
    assert ps.bound(C(2), cb) is None
    assert ps.certify_predicate(
        E.BinE("<", C(1), K(5), t.BOOL), cb
    )
    # operand beyond 2^24 is rejected
    assert not ps.certify_predicate(
        E.BinE("<", C(0), K(1 << 25), t.BOOL), [float(1 << 25), 1.0]
    )


@pytest.mark.parametrize("src, dst, want", [
    (t.INT4, t.INT8, 9.0),  # the physical value is kept
    (t.INT8, t.SqlType(t.TypeId.DECIMAL, 15, 0), 9.0),
    (t.INT8, t.SqlType(t.TypeId.DECIMAL, 15, 2), None),  # times 100
    (t.SqlType(t.TypeId.DECIMAL, 15, 2),
     t.SqlType(t.TypeId.DECIMAL, 15, 4), None),
    (t.SqlType(t.TypeId.DECIMAL, 15, 2), t.INT8, None),  # divided
    (t.INT8, t.FLOAT8, None),
])
def test_bound_refuses_a_cast_that_rescales(src, dst, want):
    """A bound sizes the MXU group reduce's limbs and certifies the
    Pallas kernel: a cast that multiplies or divides the physical value
    (ops/expr._cast_data) must not pass its operand's bound through."""
    assert ps.bound(E.CastE(C(0, src), dst), [9.0]) == want


def test_inline_projects_reaches_inside_case():
    case = E.CaseE(
        ((E.BinE(">", C(0), K(0), t.BOOL), C(1)),), C(0), t.INT8
    )
    proj = (E.BinE("+", C(2), K(1), t.INT8), C(0))
    got = ps.inline_projects(E.BinE("*", case, K(2), t.INT8), [proj])
    cols = sorted(
        n.index for n in E.walk(got) if isinstance(n, E.Col)
    )
    assert cols == [0, 2, 2]  # #1 -> scan #0, #0 -> scan #2 + 1, twice
    assert ps.inline_projects(K(3), [proj]) == K(3)


def test_decompose_value_wide_product():
    cb = [1e7, 10.0]
    dec = ps.decompose_value(E.BinE("*", C(0), C(1), t.INT8), cb)
    assert dec is not None and len(dec) == 2  # limb-split product
    dec1 = ps.decompose_value(C(1), cb)
    assert dec1 is not None and len(dec1) == 1
    # both operands wide: not certifiable
    assert ps.decompose_value(
        E.BinE("*", C(0), C(0), t.INT8), cb
    ) is None


def test_kernel_exactness_interpret():
    """Limb accumulation reproduces the exact int64 sum of a wide-product
    aggregate over 100k rows."""
    rng = np.random.default_rng(7)
    n = 100_000
    price = rng.integers(90000, 10_000_000, n)  # scaled decimal ~1e7
    disc = rng.integers(0, 11, n)
    ship = rng.integers(8000, 9500, n).astype(np.int64)

    mask_np = (ship >= 8766) & (ship < 9131) & (disc >= 5) & (disc <= 7)
    expect_sum = int(np.sum(np.where(mask_np, price * disc, 0)))
    expect_cnt = int(mask_np.sum())

    def mask_fn(blk):
        return (
            (blk[2] >= 8766.0) & (blk[2] < 9131.0)
            & (blk[0] >= 5.0) & (blk[0] <= 7.0)
        )

    def hi_term(blk):
        return jnp.floor(blk[1] / ps.LIMB) * blk[0]

    def lo_term(blk):
        x = blk[1]
        return (x - jnp.floor(x / ps.LIMB) * ps.LIMB) * blk[0]

    run = ps.build_partials(
        4, mask_fn, [hi_term, lo_term], interpret=True
    )
    live = np.ones(n, dtype=np.float32)
    out = run([
        jnp.asarray(disc, jnp.float32),
        jnp.asarray(price, jnp.float32),
        jnp.asarray(ship, jnp.float32),
        jnp.asarray(live),
    ])
    sums, counts = ps.combine_partials(
        np.asarray(out)[None], [(0, ps.LIMB), (0, 1.0)], 1
    )
    assert int(sums[0, 0]) == expect_sum
    assert int(counts[0]) == expect_cnt


@pytest.fixture()
def q6(

):
    c = Cluster(num_datanodes=2, shard_groups=32)
    s = c.session()
    s.execute(
        "create table lineitem (l_quantity numeric(10,2), "
        "l_extendedprice numeric(12,2), l_discount numeric(4,2), "
        "l_shipdate date) distribute by roundrobin"
    )
    rng = np.random.default_rng(3)
    rows = []
    for _ in range(4000):
        rows.append(
            f"({rng.uniform(1, 50):.2f}, {rng.uniform(900, 99000):.2f}, "
            f"0.0{rng.integers(0, 9)}, "
            f"'199{rng.integers(3, 6)}-0{rng.integers(1, 9)}-1{rng.integers(0, 9)}')"
        )
    s.execute("insert into lineitem values " + ",".join(rows))
    return s


Q6 = (
    "select sum(l_extendedprice * l_discount), count(*) from lineitem "
    "where l_shipdate >= date '1994-01-01' "
    "and l_shipdate < date '1995-01-01' "
    "and l_discount between 0.05 and 0.07 and l_quantity < 24"
)


def test_engine_pallas_matches_xla(q6):
    xla = q6.query(Q6)
    q6.execute("set enable_pallas_scan = on")
    # clear the plan cache so the pallas route is (re)attempted
    q6.cluster._fused = None
    pal = q6.query(Q6)
    assert pal == xla
    fx = q6.cluster.fused_executor()
    assert any(
        isinstance(k, tuple) and k and k[0] == "pallas"
        and v is not False
        for k, v in fx._programs.items()
    ), "pallas program was not used"


def test_engine_pallas_rejects_unbounded(q6):
    """Queries outside the certified subset still answer correctly (XLA
    path) — e.g. min/max aggregates."""
    q6.execute("set enable_pallas_scan = on")
    q6.cluster._fused = None
    r = q6.query(
        "select min(l_shipdate), max(l_quantity) from lineitem"
    )
    assert r[0][0] is not None


def test_stale_stats_recertify(q6):
    """Data growth past the f32 bound must evict/bypass the cached
    pallas program (review regression): results stay exact."""
    q6.execute("set enable_pallas_scan = on")
    q6.cluster._fused = None
    first = q6.query(Q6)
    fx = q6.cluster.fused_executor()
    assert any(
        isinstance(k, tuple) and k and k[0] == "pallas" and v is not False
        for k, v in fx._programs.items()
    )
    # a price far beyond 2^24: the product bound certification now fails
    q6.execute(
        "insert into lineitem values (1.00, 99999999.99, 0.06, "
        "'1994-06-15')"
    )
    got = q6.query(Q6)
    q6.execute("set enable_pallas_scan = off")
    q6.cluster._fused = None
    want = q6.query(Q6)
    assert got == want
    assert got != first  # the new row is inside the filter


def test_hash_collision_falls_back_to_device_sort():
    """A group-by with enough distinct keys to guarantee hash slot
    collisions still aggregates correctly (on-device sort fallback,
    review regression)."""
    from opentenbase_tpu.engine import Cluster

    s = Cluster(num_datanodes=2, shard_groups=32).session()
    s.execute("create table t (g bigint, v bigint) distribute by shard(g)")
    n_groups = 500  # ~1024 slots: collision probability ~ 1
    values = ",".join(
        f"({g}, {g * 3 + r})" for g in range(n_groups) for r in range(2)
    )
    s.execute(f"insert into t values {values}")
    rows = s.query("select g, sum(v), count(*) from t group by g")
    assert len(rows) == n_groups
    got = {g: (sv, c) for g, sv, c in rows}
    for g in range(n_groups):
        assert got[g] == (6 * g + 1, 2)


@pytest.fixture()
def q1():
    c = Cluster(num_datanodes=2, shard_groups=32)
    s = c.session()
    s.execute(
        "create table li (l_returnflag text, l_linestatus text, "
        "l_quantity numeric(10,2), l_extendedprice numeric(12,2), "
        "l_discount numeric(4,2), l_shipdate date) "
        "distribute by roundrobin"
    )
    rng = np.random.default_rng(11)
    n = 5000
    rows = ",".join(
        f"('{f}','{st}',{q:.2f},{p:.2f},0.0{d},'{dt}')"
        for f, st, q, p, d, dt in zip(
            rng.choice(["A", "N", "R"], n),
            rng.choice(["F", "O"], n),
            rng.uniform(1, 50, n).round(2),
            rng.uniform(900, 9000, n).round(2),
            rng.integers(0, 9, n),
            np.datetime64("1994-01-01") + rng.integers(0, 1500, n),
        )
    )
    s.execute("insert into li values " + rows)
    return s


Q1 = (
    "select l_returnflag, l_linestatus, sum(l_quantity), "
    "sum(l_extendedprice), sum(l_extendedprice * l_discount), count(*) "
    "from li where l_shipdate <= date '1997-09-02' "
    "group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus"
)


def test_engine_grouped_pallas_matches_xla(q1):
    """TPC-H Q1 shape: small-domain GROUP BY runs in the grouped pallas
    kernel and matches the XLA path bit-for-bit."""
    xla = q1.query(Q1)
    q1.execute("set enable_pallas_scan = on")
    q1.cluster._fused = None
    pal = q1.query(Q1)
    assert pal == xla
    assert len(pal) == 6
    fx = q1.cluster.fused_executor()
    assert any(
        isinstance(k, tuple) and k and k[0] == "pallas" and v is not False
        for k, v in fx._programs.items()
    ), "grouped pallas program was not used"


def test_grouped_pallas_int_keys(q1):
    """Integer group keys with negative values decode correctly."""
    s = q1
    s.execute("create table gt (k int, v numeric(10,2)) distribute by roundrobin")
    s.execute(
        "insert into gt values (-2, 1.00), (-2, 2.50), (0, 4.00), "
        "(3, 1.25), (3, 0.25), (3, 1.00)"
    )
    q = "select k, sum(v), count(*) from gt group by k order by k"
    want = s.query(q)
    s.execute("set enable_pallas_scan = on")
    s.cluster._fused = None
    got = s.query(q)
    assert got == want == [(-2, 3.5, 2), (0, 4.0, 1), (3, 2.5, 3)]


def test_grouped_pallas_large_domain_falls_back(q1):
    """Keys with a domain beyond the kernel cap answer via XLA."""
    s = q1
    s.execute("create table wide (k bigint, v bigint) distribute by roundrobin")
    s.execute(
        "insert into wide values " + ",".join(
            f"({k * 1000}, {k})" for k in range(40)
        )
    )
    q = "select k, sum(v) from wide group by k order by k"
    s.execute("set enable_pallas_scan = on")
    s.cluster._fused = None
    got = s.query(q)
    assert len(got) == 40 and got[0] == (0, 0)


def test_grouped_pallas_key_beyond_f32_bound_falls_back(q1):
    """Keys past 2^24 are not f32-exact: grouped kernel must refuse and
    the XLA path must answer correctly (adjacent keys stay distinct)."""
    s = q1
    s.execute("create table bigk (k bigint, v bigint) distribute by roundrobin")
    s.execute("insert into bigk values (16777216, 1), (16777217, 2)")
    s.execute("set enable_pallas_scan = on")
    s.cluster._fused = None
    got = s.query("select k, sum(v) from bigk group by k order by k")
    assert got == [(16777216, 1), (16777217, 2)]


def test_grouped_pallas_offset_domain(q1):
    """Small domain far from zero (e.g. years) must still use the grouped
    kernel: range stats come from real rows, not padding zeros."""
    s = q1
    s.execute("create table yr (y int, v numeric(10,2)) distribute by roundrobin")
    s.execute(
        "insert into yr values " + ",".join(
            f"({1992 + (i % 7)}, {i}.25)" for i in range(50)
        )
    )
    q = "select y, sum(v), count(*) from yr group by y order by y"
    want = s.query(q)
    s.execute("set enable_pallas_scan = on")
    s.cluster._fused = None
    before = {
        k for k in s.cluster.fused_executor()._programs if k[0] == "pallas"
    } if s.cluster._fused else set()
    got = s.query(q)
    assert got == want and len(got) == 7
    fx = s.cluster.fused_executor()
    assert any(
        isinstance(k, tuple) and k[0] == "pallas" and v is not False
        for k, v in fx._programs.items() if k not in before
    ), "offset-domain keys did not reach the grouped pallas kernel"


def test_count_nullif_not_miscounted_by_pallas(q1):
    """count(expr) where expr can be dynamically NULL must not be folded
    into count(*) by the pallas path (review regression)."""
    s = q1
    s.execute("create table cn (a bigint) distribute by roundrobin")
    s.execute("insert into cn values (0), (1), (2), (0)")
    s.execute("set enable_pallas_scan = on")
    s.cluster._fused = None
    assert s.query("select count(nullif(a, 0)) from cn")[0][0] == 2
    assert s.query("select count(a), count(*) from cn")[0] == (4, 4)
