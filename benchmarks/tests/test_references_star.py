"""Each star reference against a second, row-at-a-time evaluation
written by hand, on 1,000 fact rows; and the star generators' row
counts, key ranges and dictionary sizes on two seeds."""

from fractions import Fraction

import numpy as np
import pytest

from datasets import ssb, ssb_star

SCALE = 1000 / 6_000_000


def rows_of(table: dict, dicts: dict | None = None) -> list:
    """The table's rows as dictionaries, text columns decoded."""
    names = list(table)
    cols = [
        [dicts[n][c] for c in table[n].tolist()] if dicts and n in dicts
        else table[n].tolist()
        for n in names
    ]
    return [dict(zip(names, vals)) for vals in zip(*cols)]


@pytest.fixture(scope="module")
def star():
    return ssb_star.make_block(24, SCALE, 0), ssb_star.make_global(24, SCALE)


def joined(star):
    """Every fact row with the dimension rows its keys name, looked up
    in dictionaries keyed by the dimension's own key column."""
    block, glob = star
    d = ssb_star.DICTIONARIES
    cust = {r["c_custkey"]: r for r in rows_of(glob["customer"], d["customer"])}
    supp = {r["s_suppkey"]: r for r in rows_of(glob["supplier"], d["supplier"])}
    part = {r["p_partkey"]: r for r in rows_of(glob["part"], d["part"])}
    date = {r["d_datekey"]: r for r in rows_of(glob["dates"])}
    for lo in rows_of(block["lineorder"]):
        yield (lo, cust[lo["lo_custkey"]], supp[lo["lo_suppkey"]],
               part[lo["lo_partkey"]], date[lo["lo_orderdate"]])


def grouped(pairs) -> dict:
    out: dict = {}
    for key, v in pairs:
        out[key] = out.get(key, 0) + v
    return out


def test_q21(star):
    want = grouped(
        ((d["d_year"], p["p_brand1"]), lo["lo_revenue"])
        for lo, _c, s, p, d in joined(star)
        if p["p_category"] == "MFGR#12" and s["s_region"] == "AMERICA"
    )
    ref = ssb_star.reference("q21", {}, [star[0]], star[1])
    assert ref["kinds"] == ["sum", "int", "text"]
    assert ref["order"] == [(1, "asc"), (2, "asc")]
    assert ref["rows"] == [
        (Fraction(v), y, b) for (y, b), v in sorted(want.items())
    ]
    assert len(want) >= 3


def test_q31(star):
    want = grouped(
        ((c["c_nation"], s["s_nation"], d["d_year"]), lo["lo_revenue"])
        for lo, c, s, _p, d in joined(star)
        if c["c_region"] == "ASIA" and s["s_region"] == "ASIA"
        and 1992 <= d["d_year"] <= 1997
    )
    ref = ssb_star.reference("q31", {}, [star[0]], star[1])
    assert ref["kinds"] == ["text", "text", "int", "sum"]
    assert ref["order"] == [(2, "asc"), (3, "desc")]
    rows = [(cn, sn, y, Fraction(v)) for (cn, sn, y), v in want.items()]
    assert ref["rows"] == sorted(rows, key=lambda r: (r[2], -r[3]))
    assert len(want) >= 3 and all(r[2] <= 1997 for r in ref["rows"])


def test_q41(star):
    want = grouped(
        ((d["d_year"], c["c_nation"]),
         lo["lo_revenue"] - lo["lo_supplycost"])
        for lo, c, s, p, d in joined(star)
        if c["c_region"] == "AMERICA" and s["s_region"] == "AMERICA"
        and p["p_mfgr"] in ("MFGR#1", "MFGR#2")
    )
    ref = ssb_star.reference("q41", {}, [star[0]], star[1])
    assert ref["kinds"] == ["int", "text", "sum"]
    assert ref["order"] == [(0, "asc"), (1, "asc")]
    assert ref["rows"] == [
        (y, n, Fraction(v)) for (y, n), v in sorted(want.items())
    ]
    assert len(want) >= 3


def test_blocks_add_up(star):
    """Two blocks referenced together equal one evaluation of both."""
    a = ssb_star.make_block(24, SCALE, 0)
    b = ssb_star.make_block(25, SCALE, 0)
    both = {"lineorder": {
        k: np.concatenate([a["lineorder"][k], b["lineorder"][k]])
        for k in a["lineorder"]
    }}
    for q in ssb_star.QUERIES:
        assert (ssb_star.reference(q, {}, [a, b], star[1])
                == ssb_star.reference(q, {}, [both], star[1]))


def test_control_reads_far_above_the_reference():
    """The control (float32 sums) on a block of the cell's own size per
    block: its gap is what the limit must catch."""
    block = ssb_star.make_block(23, 1.0, 0)
    glob = ssb_star.make_global(23, 1.0)
    for q, col in (("q21", 0), ("q31", 3), ("q41", 2)):
        exact = ssb_star.reference(q, {}, [block], glob)["rows"]
        control = ssb_star.reference(q, {}, [block], glob, exact=False)["rows"]
        assert len(exact) == len(control) > 30
        gap = max(abs(c[col] - e[col]) / abs(e[col])
                  for c, e in zip(control, exact))
        assert gap > 1e-9, (q, gap)


@pytest.mark.parametrize("seed", [11, 2**31 + 12345])
def test_generators(seed):
    sf = 0.01
    assert ssb_star.counts(10) == {
        "orders": 15_000_000, "part": 800_000, "dates": 2556,
        "customer": 300_000, "supplier": 20_000,
    }
    cnt = ssb_star.counts(sf)
    assert (cnt["customer"], cnt["supplier"], cnt["part"]) == (300, 25, 2000)
    g = ssb_star.make_global(seed, sf)
    (b,) = [ssb_star.make_block(seed, sf, i)
            for i in range(ssb_star.n_blocks(sf))]
    lo = b["lineorder"]
    assert len(lo["lo_orderkey"]) == 59_999  # every seed, as ssb.py's
    assert set(lo) == {"lo_orderkey", "lo_custkey", "lo_partkey",
                       "lo_suppkey", "lo_orderdate", "lo_revenue",
                       "lo_supplycost"}
    # dense dimension keys 1..n, every foreign key inside its dimension
    for table, key, fk in (("customer", "c_custkey", "lo_custkey"),
                           ("supplier", "s_suppkey", "lo_suppkey"),
                           ("part", "p_partkey", "lo_partkey")):
        n = cnt[table]
        assert np.array_equal(g[table][key], np.arange(1, n + 1))
        assert lo[fk].min() >= 1 and lo[fk].max() <= n
        assert len(np.unique(lo[fk])) > n // 2  # drawn over all of it
    assert np.isin(lo["lo_orderdate"], g["dates"]["d_datekey"]).all()
    assert np.array_equal(g["dates"]["d_datekey"],
                          ssb.date_table()["d_datekey"])
    # one customer an order: the lines of an order share it
    first = np.r_[True, lo["lo_orderkey"][1:] != lo["lo_orderkey"][:-1]]
    order = np.cumsum(first) - 1
    assert np.array_equal(lo["lo_custkey"], lo["lo_custkey"][first][order])
    assert not np.array_equal(lo["lo_suppkey"], lo["lo_suppkey"][first][order])
    # dictionaries: 25 nations in 5 regions, 5 x 5 x 40 brands
    d = ssb_star.DICTIONARIES
    assert [len(d["customer"][c]) for c in ("c_nation", "c_region")] == [25, 5]
    assert d["supplier"]["s_nation"] == d["customer"]["c_nation"]
    assert [len(d["part"][c]) for c in ("p_mfgr", "p_category", "p_brand1")
            ] == [5, 25, 1000]
    assert len(set(d["part"]["p_brand1"])) == 1000
    assert d["part"]["p_brand1"][40 * 1 + 20] == "MFGR#1221"
    for who in ("customer", "supplier"):
        p = who[0]
        nation, region = g[who][f"{p}_nation"], g[who][f"{p}_region"]
        assert nation.min() >= 0 and nation.max() <= 24
        assert np.array_equal(region, ssb_star.REGION_OF[nation])
    assert np.bincount(ssb_star.REGION_OF).tolist() == [5] * 5
    brand = g["part"]["p_brand1"]
    assert brand.min() >= 0 and brand.max() <= 999
    for code in brand[:50].tolist():
        b1 = d["part"]["p_brand1"][code]
        assert b1.startswith(d["part"]["p_category"][code // 40])
        assert d["part"]["p_category"][code // 40].startswith(
            d["part"]["p_mfgr"][code // 200])
    assert np.array_equal(g["part"]["p_category"], brand // 40)
    assert np.array_equal(g["part"]["p_mfgr"], brand // 200)
    # money: revenue <= extended price, supply cost 60 % of a part's
    # price (900.00..2099.00), both integer hundredths
    assert lo["lo_supplycost"].min() >= 54_000
    assert lo["lo_supplycost"].max() <= 125_940
    assert lo["lo_revenue"].min() >= 90_000 * 90 // 100
    assert lo["lo_revenue"].max() <= 50 * 209_900
    assert lo["lo_revenue"].dtype == lo["lo_supplycost"].dtype == np.int32
    other = ssb_star.make_block(seed + 1, sf, 0)["lineorder"]
    assert len(other["lo_orderkey"]) == 59_999
    assert not np.array_equal(other["lo_partkey"], lo["lo_partkey"])
