"""The Q5 reference against a second, row-at-a-time evaluation written
by hand, at a toy size; and the Q5 data set's rules: ``tpch.py``'s rows
for every column both configurations load, ``supplier`` 10,000 * SF on
dense keys, ``l_suppkey`` by cl.4.2.3's formula from the line's part
key, the 25 nations in their 5 regions, the same rows for the same seed,
60M ``lineitem`` rows at SF10 by the counts alone."""

from fractions import Fraction

import numpy as np
import pytest

from datasets import tpch, tpch_q5

SCALE = 30_000 / 6_000_000
REGIONS = tpch_q5.REGIONS


def rows_of(table: dict) -> list:
    names = list(table)
    return [dict(zip(names, vals))
            for vals in zip(*(table[n].tolist() for n in names))]


@pytest.fixture(scope="module")
def toy():
    return tpch_q5.make_block(24, SCALE, 0), tpch_q5.make_global(24, SCALE)


@pytest.mark.parametrize("region,year", [
    ("ASIA", 1994), ("AMERICA", 1997), ("MIDDLE EAST", 1993),
])
def test_q5(toy, region, year):
    """Q5 as its text reads: six nested lookups and a dictionary of
    sums, nothing shared with the reference's arrays."""
    block, glob = toy
    nations = {r["n_nationkey"]: r for r in rows_of(glob["nation"])}
    regions = {r["r_regionkey"]: r for r in rows_of(glob["region"])}
    cust = {r["c_custkey"]: r for r in rows_of(glob["customer"])}
    supp = {r["s_suppkey"]: r for r in rows_of(glob["supplier"])}
    orders = {r["o_orderkey"]: r for r in rows_of(block["orders"])}
    lo, hi = tpch.days(f"{year}-01-01"), tpch.days(f"{year + 1}-01-01")
    want: dict = {}
    for li in rows_of(block["lineitem"]):
        o = orders[li["l_orderkey"]]
        c, s = cust[o["o_custkey"]], supp[li["l_suppkey"]]
        n = nations[s["s_nationkey"]]
        r = regions[n["n_regionkey"]]
        if (c["c_nationkey"] == s["s_nationkey"]
                and REGIONS[r["r_name"]] == region
                and lo <= o["o_orderdate"] < hi):
            name = tpch_q5.NATION_NAMES[n["n_name"]]
            want[name] = want.get(name, 0) + (
                li["l_extendedprice"] * (100 - li["l_discount"]))
    ref = tpch_q5.reference(
        "q5", {"region": region, "year": year}, [block], glob)
    assert ref["kinds"] == ["text", "sum"]
    assert ref["order"] == [(1, "desc")]
    assert ref["rows"] == sorted(
        ((n, Fraction(v, 10_000)) for n, v in want.items()),
        key=lambda r: -r[1],
    )
    assert len(want) >= 2  # something was compared


def test_blocks_add_up(toy):
    a = tpch_q5.make_block(24, SCALE, 0)
    b = tpch_q5.make_block(25, SCALE, 0)
    # (orders of two blocks must ascend for the reference's search)
    b["orders"]["o_orderkey"] = b["orders"]["o_orderkey"] + 10**9
    b["lineitem"]["l_orderkey"] = b["lineitem"]["l_orderkey"] + 10**9
    both = {t: {k: np.concatenate([a[t][k], b[t][k]]) for k in a[t]}
            for t in ("orders", "lineitem")}
    p = {"region": "EUROPE", "year": 1995}
    assert (tpch_q5.reference("q5", p, [a, b], toy[1])
            == tpch_q5.reference("q5", p, [both], toy[1]))


def test_control_reads_far_above_the_reference():
    """The control (float32 sums) on one block of the cell's own size:
    its gap is what the limit must catch."""
    block = tpch_q5.make_block(23, 1.0, 0)
    glob = tpch_q5.make_global(23, 1.0)
    p = {"region": "ASIA", "year": 1995}
    exact = tpch_q5.reference("q5", p, [block], glob)["rows"]
    control = tpch_q5.reference("q5", p, [block], glob, exact=False)["rows"]
    assert len(exact) == 5
    by_name = dict(control)
    gap = max(abs(by_name[n] - v) / v for n, v in exact)
    assert gap > 1e-9, gap


@pytest.mark.parametrize("seed", [11, 2**31 + 12345])
def test_generators(seed):
    sf = 0.01
    cnt = tpch_q5.counts(sf)
    assert tpch_q5.counts(10)["supplier"] == 100_000  # 10,000 * SF
    assert tpch_q5.counts(10)["customer"] == 1_500_000
    assert cnt["supplier"] == 100 and cnt["nation"] == 25
    assert tpch_q5.fact_rows(10) == 60_000_000  # by the counts alone
    assert tpch_q5.n_blocks(10) == tpch.n_blocks(10)
    g = tpch_q5.make_global(seed, sf)
    (b,) = [tpch_q5.make_block(seed, sf, i)
            for i in range(tpch_q5.n_blocks(sf))]
    o, li = b["orders"], b["lineitem"]
    # tpch.py's rows for every column both configurations load
    base = tpch.make_block(seed, sf, 0)
    for col in ("o_orderkey", "o_custkey", "o_orderdate"):
        assert np.array_equal(o[col], base["orders"][col])
    for col in ("l_orderkey", "l_extendedprice", "l_discount"):
        assert np.array_equal(li[col], base["lineitem"][col])
    assert np.array_equal(
        g["customer"]["c_custkey"],
        tpch.make_global(seed, sf)["customer"]["c_custkey"])
    assert len(li["l_orderkey"]) == 59_999 == tpch_q5.fact_rows(sf)
    # the part key is the one tpch.py priced the line with
    qty = base["lineitem"]["l_quantity"] // 100
    assert np.array_equal(
        li["l_extendedprice"],
        qty * tpch.retail_price_cents(li["l_partkey"].astype(np.int64)))
    # supplier: dense keys 1..S; a line's supplier by cl.4.2.3 from its
    # part key, one of that part's four, within [1, S]
    s = cnt["supplier"]
    assert np.array_equal(g["supplier"]["s_suppkey"], np.arange(1, s + 1))
    pk = li["l_partkey"].astype(np.int64)
    four = np.stack([(pk + i * (s // 4 + (pk - 1) // s)) % s + 1
                     for i in range(4)])
    assert (four == li["l_suppkey"]).any(axis=0).all()
    which = (four == li["l_suppkey"]).argmax(axis=0)
    assert abs(np.bincount(which, minlength=4) / len(pk) - 0.25).max() < 0.02
    assert li["l_suppkey"].min() >= 1 and li["l_suppkey"].max() <= s
    assert len(np.unique(li["l_suppkey"])) == s  # drawn over all of it
    assert tpch_q5.suppkey(np.asarray([1, 2, 7]), np.asarray([0, 3, 1]), 4
                           ).tolist() == [2, 2, 2]  # by hand, S = 4
    # nations: uniform in [0, 24]; the 25 in their 5 regions (cl.4.3)
    for t, col in (("customer", "c_nationkey"), ("supplier", "s_nationkey")):
        nk = g[t][col]
        assert nk.min() >= 0 and nk.max() <= 24
        assert len(nk) == cnt[t]
    assert len(np.unique(g["customer"]["c_nationkey"])) == 25
    n, r = g["nation"], g["region"]
    assert np.array_equal(n["n_nationkey"], np.arange(25))
    assert np.array_equal(r["r_regionkey"], np.arange(5))
    names = tpch_q5.DICTIONARIES["nation"]["n_name"]
    assert len(names) == len(set(names)) == 25
    assert tpch_q5.DICTIONARIES["region"]["r_name"] == [
        "AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
    assert np.bincount(n["n_regionkey"]).tolist() == [5] * 5
    region_of = {names[c]: REGIONS[k] for c, k in
                 zip(n["n_name"].tolist(), n["n_regionkey"].tolist())}
    assert region_of["FRANCE"] == "EUROPE" and region_of["CHINA"] == "ASIA"
    assert region_of["EGYPT"] == "MIDDLE EAST"
    assert region_of["KENYA"] == "AFRICA" and region_of["PERU"] == "AMERICA"
    assert [names[i] for i in (0, 24)] == ["ALGERIA", "UNITED STATES"]
    # the same rows for the same seed, other rows for another
    again = tpch_q5.make_block(seed, sf, 0)["lineitem"]
    assert all(np.array_equal(again[k], li[k]) for k in li)
    other = tpch_q5.make_block(seed + 1, sf, 0)["lineitem"]
    assert len(other["l_orderkey"]) == 59_999
    assert not np.array_equal(other["l_suppkey"], li["l_suppkey"])
    g2 = tpch_q5.make_global(seed, sf)
    assert all(np.array_equal(g2[t][k], g[t][k]) for t in g for k in g[t])
