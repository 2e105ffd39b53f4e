"""Each reference against a second, row-at-a-time evaluation written by
hand, on 1,000 fact rows."""

from fractions import Fraction

import pytest

from datasets import ssb, tpch

SCALE = 1000 / 6_000_000


def rows_of(table: dict) -> list:
    names = list(table)
    return [dict(zip(names, vals)) for vals in zip(
        *(table[n].tolist() for n in names)
    )]


@pytest.fixture(scope="module")
def tp():
    block = tpch.make_block(21, SCALE, 0)
    return block, tpch.make_global(21, SCALE)


@pytest.fixture(scope="module")
def ss():
    block = ssb.make_block(22, SCALE, 0)
    return block, ssb.make_global(22, SCALE)


def test_q6(tp):
    block, glob = tp
    p = {"year": 1994, "discount": 6, "quantity": 24}
    total = 0
    for r in rows_of(block["lineitem"]):
        if (tpch.days("1994-01-01") <= r["l_shipdate"] < tpch.days("1995-01-01")
                and 5 <= r["l_discount"] <= 7 and r["l_quantity"] < 2400):
            total += r["l_extendedprice"] * r["l_discount"]
    ref = tpch.reference("q6", p, [block], glob)
    assert ref["rows"] == [(Fraction(total, 10_000),)]
    assert total > 0


def test_q1(tp):
    block, glob = tp
    p = {"delta": 90}
    groups: dict = {}
    for r in rows_of(block["lineitem"]):
        if r["l_shipdate"] > tpch.days("1998-09-02"):
            continue
        g = groups.setdefault((r["l_returnflag"], r["l_linestatus"]), [0] * 6)
        dp = r["l_extendedprice"] * (100 - r["l_discount"])
        g[0] += r["l_quantity"]
        g[1] += r["l_extendedprice"]
        g[2] += dp
        g[3] += dp * (100 + r["l_tax"])
        g[4] += r["l_discount"]
        g[5] += 1
    want = []
    d = tpch.DICTIONARIES["lineitem"]
    for (f, s), g in sorted(groups.items()):
        n = g[5]
        want.append((
            d["l_returnflag"][f], d["l_linestatus"][s], Fraction(g[0], 100),
            Fraction(g[1], 100), Fraction(g[2], 10**4), Fraction(g[3], 10**6),
            Fraction(g[0], 100 * n), Fraction(g[1], 100 * n),
            Fraction(g[4], 100 * n), n,
        ))
    assert tpch.reference("q1", p, [block], glob)["rows"] == want
    assert len(want) >= 3


def test_q3(tp):
    block, glob = tp
    p = {"segment": "BUILDING", "day": 15}
    date = tpch.days("1995-03-15")
    seg = {c["c_custkey"] for c in rows_of(glob["customer"])
           if c["c_mktsegment"] == 1}
    orders = {o["o_orderkey"]: o for o in rows_of(block["orders"])
              if o["o_custkey"] in seg and o["o_orderdate"] < date}
    rev: dict = {}
    for r in rows_of(block["lineitem"]):
        if r["l_orderkey"] in orders and r["l_shipdate"] > date:
            rev[r["l_orderkey"]] = rev.get(r["l_orderkey"], 0) + (
                r["l_extendedprice"] * (100 - r["l_discount"])
            )
    want = sorted(
        ((k, Fraction(v, 10**4), orders[k]["o_orderdate"], 0)
         for k, v in rev.items()), key=lambda r: (-r[1], r[2]),
    )[:10]
    assert tpch.reference("q3", p, [block], glob)["rows"] == want
    assert want


def star_rows(ss):
    block, glob = ss
    year = {d["d_datekey"]: d["d_year"] for d in rows_of(glob["dates"])}
    for r in rows_of(block["lineorder"]):
        yield r, year[r["lo_orderdate"]]


def test_q11(ss):
    total = sum(
        r["lo_extendedprice"] * r["lo_discount"]
        for r, y in star_rows(ss)
        if y == 1993 and 1 <= r["lo_discount"] <= 3 and r["lo_quantity"] < 25
    )
    assert ssb.reference("q11", {}, [ss[0]], ss[1])["rows"] == [
        (Fraction(total),)
    ]
    assert total > 0


def test_q12_q13(ss):
    import datetime

    block, glob = ss
    jan, week6 = 0, 0
    for r in rows_of(block["lineorder"]):
        k = r["lo_orderdate"]
        day = datetime.date(k // 10000, k // 100 % 100, k % 100)
        if not 26 <= r["lo_quantity"] <= 35:
            continue
        w = r["lo_extendedprice"] * r["lo_discount"]
        if (day.year, day.month) == (1994, 1) and 4 <= r["lo_discount"] <= 6:
            jan += w
        # week 6 of 1994 by the configuration's rule: days 36..42 of it
        if (day.year == 1994 and 35 <= day.timetuple().tm_yday - 1 < 42
                and 5 <= r["lo_discount"] <= 7):
            week6 += w
    assert ssb.reference("q12", {}, [block], glob)["rows"] == [(Fraction(jan),)]
    assert ssb.reference("q13", {}, [block], glob)["rows"] == [
        (Fraction(week6),)
    ]


def test_control_reads_far_above_the_reference(tp):
    """The control (float32 sums, bfloat16 averages) on a block of the
    cell's own size per block: its gap is what the limits must catch."""
    block = tpch.make_block(23, 1.0, 0)
    glob = tpch.make_global(23, 1.0)
    p = {"delta": 90}
    exact = tpch.reference("q1", p, [block], glob)["rows"]
    control = tpch.reference("q1", p, [block], glob, exact=False)["rows"]
    sum_gap = max(abs(c[5] - e[5]) / e[5] for c, e in zip(control, exact))
    avg_gap = max(abs(c[7] - e[7]) / e[7] for c, e in zip(control, exact))
    assert sum_gap > 1e-9 and avg_gap > 1e-4
