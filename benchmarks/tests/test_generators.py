"""The generators against the sources' row counts and distributions, at
SF0.01."""

import numpy as np

from datasets import ssb, tpch

SF = 0.01


def blocks(mod, seed=11):
    return [mod.make_block(seed, SF, b) for b in range(mod.n_blocks(SF))]


def test_tpch_counts_and_keys():
    (b,) = blocks(tpch)
    o, li = b["orders"], b["lineitem"]
    assert len(o["o_orderkey"]) == 15_000
    # 15,000 = 7 * 2142 + 6: the six left over take 4, 1, 7, 2, 6, 3
    assert len(li["l_orderkey"]) == 59_999 == tpch.fact_rows(SF)
    assert tpch.fact_rows(10) == 60_000_000  # mean 4 lines an order, exactly
    # dbgen's sparse keys: 8 of every 32 values, ascending, unique
    assert np.all(o["o_orderkey"] % 32 < 8)
    assert np.all(np.diff(o["o_orderkey"]) > 0)
    assert o["o_orderkey"][:9].tolist() == [0, 1, 2, 3, 4, 5, 6, 7, 32]
    per = np.bincount(np.searchsorted(o["o_orderkey"], li["l_orderkey"]))
    assert per.min() == 1 and per.max() == 7
    assert abs(np.bincount(per)[1:] - 15_000 / 7).max() <= 1
    assert np.all(o["o_custkey"] % 3 != 0)
    assert o["o_custkey"].min() >= 1 and o["o_custkey"].max() <= 1500
    assert o["o_orderdate"].min() >= tpch.days("1992-01-01")
    assert o["o_orderdate"].max() <= tpch.days("1998-08-02")


def test_tpch_line_rules():
    (b,) = blocks(tpch)
    o, li = b["orders"], b["lineitem"]
    odate = o["o_orderdate"][np.searchsorted(o["o_orderkey"], li["l_orderkey"])]
    lag = li["l_shipdate"] - odate
    assert lag.min() >= 1 and lag.max() <= 121
    cur = tpch.CURRENTDATE
    # l_linestatus by ship date; l_returnflag by a receipt date 1..30 on
    assert np.array_equal(li["l_linestatus"] == tpch.STATUS_O,
                          li["l_shipdate"] > cur)
    n = li["l_returnflag"] == tpch.FLAG_N
    assert np.all(li["l_shipdate"][n] > cur - 30)
    assert np.all(li["l_shipdate"][~n] < cur)
    ra = li["l_returnflag"][~n]
    assert 0.45 < (ra == tpch.FLAG_R).mean() < 0.55
    qty = li["l_quantity"] // 100
    assert qty.min() == 1 and qty.max() == 50
    price = li["l_extendedprice"] // qty
    assert np.all(li["l_extendedprice"] % qty == 0)
    assert price.min() >= 90_000 and price.max() <= 90_000 + 20_000 + 99_900
    assert li["l_discount"].min() == 0 and li["l_discount"].max() == 10
    assert li["l_tax"].min() == 0 and li["l_tax"].max() == 8


def test_tpch_retail_price_formula():
    # cl.4.2.3: 90000 + ((partkey/10) mod 20001) + 100 * (partkey mod 1000)
    assert tpch.retail_price_cents(np.asarray([1]))[0] == 90_000 + 0 + 100
    assert tpch.retail_price_cents(np.asarray([199_999]))[0] == (
        90_000 + 19_999 + 99_900
    )
    assert np.array_equal(
        tpch.price_table(5000)[1:], tpch.retail_price_cents(np.arange(1, 5001))
    )


def test_same_seed_same_data_other_seed_other_data():
    a, b, c = (tpch.make_block(s, SF, 0)["lineitem"]["l_extendedprice"]
               for s in (5, 5, 6))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    assert len(tpch.make_block(big, SF, 0)["orders"]["o_orderkey"]) == 15_000


def test_ssb_counts():
    full = ssb.counts(10)
    assert full == {"orders": 15_000_000, "part": 800_000, "dates": 2556}
    assert ssb.counts(1)["part"] == 200_000
    assert ssb.counts(100)["part"] == 200_000 * 7
    (b,) = blocks(ssb)
    assert len(b["lineorder"]["lo_orderkey"]) == 59_999


def test_ssb_shapes():
    g = ssb.make_global(3, SF)
    d = g["dates"]
    assert len(d["d_datekey"]) == 2556
    assert d["d_datekey"][0] == 19920101 and d["d_datekey"][-1] == 19981230
    assert d["d_datekey"][59] == 19920229  # 1992 is a leap year
    assert np.all(np.diff(d["d_datekey"]) > 0)  # unique, and far from dense
    assert np.array_equal(d["d_year"], d["d_datekey"] // 10000)
    assert np.array_equal(d["d_yearmonthnum"], d["d_datekey"] // 100)
    # 1992-01-01 is in week 1, 1992-01-08 in week 2, 1992-12-31 (day 366) in 53
    assert d["d_weeknuminyear"][[0, 6, 7, 365, 366]].tolist() == [1, 1, 2, 53, 1]
    (b,) = blocks(ssb)
    lo = b["lineorder"]
    assert np.isin(lo["lo_orderdate"], d["d_datekey"]).all()
    assert set(lo) == {"lo_orderkey", "lo_orderdate", "lo_quantity",
                       "lo_extendedprice", "lo_discount"}
    assert lo["lo_quantity"].min() == 1 and lo["lo_quantity"].max() == 50
    assert lo["lo_discount"].min() == 0 and lo["lo_discount"].max() == 10
    # lo_extendedprice = lo_quantity * p_price, p_price in 900.00..2099.00
    price = lo["lo_extendedprice"] / lo["lo_quantity"]
    assert price.min() >= 90_000 and price.max() <= 209_900
