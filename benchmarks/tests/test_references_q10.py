"""The Q10 reference against a second, row-at-a-time evaluation written
by hand, at a toy size (the join, the sums, the cut to twenty and a tie
at rank twenty); and the Q10 data set's rules: ``tpch.py``'s and
``tpch_q5.py``'s rows for every column they share, the customer's five
attributes as cl.4.2.3 shapes them, one dictionary value a row, the same
rows for the same seed."""

import re
from fractions import Fraction

import numpy as np
import pytest

from datasets import tpch, tpch_q5, tpch_q10

SCALE = 30_000 / 6_000_000


def rows_of(table: dict) -> list:
    names = list(table)
    return [dict(zip(names, vals))
            for vals in zip(*(table[n].tolist() for n in names))]


@pytest.fixture
def toy():
    # (make_global last: the reference reads ITS text lists)
    return tpch_q10.make_block(24, SCALE, 0), tpch_q10.make_global(24, SCALE)


def by_hand(block, glob, date: str, shift: int = 0) -> list:
    """Q10 as its text reads: nested lookups and a dictionary of sums,
    nothing shared with the reference's arrays. Rows as the reference
    returns them, every group, best first (ties by the key)."""
    text = tpch_q10.DICTIONARIES["customer"]
    nations = {r["n_nationkey"]: r for r in rows_of(glob["nation"])}
    cust = {r["c_custkey"]: r for r in rows_of(glob["customer"])}
    orders = {r["o_orderkey"]: r for r in rows_of(block["orders"])}
    lo = np.datetime64(date, "D")
    y, m = int(date[:4]), int(date[5:7]) + 3
    hi = np.datetime64(f"{y + (m > 12)}-{(m - 1) % 12 + 1:02d}-01", "D")
    day0 = np.datetime64("1970-01-01", "D")
    lo, hi = int((lo - day0).astype(int)), int((hi - day0).astype(int))
    want: dict = {}
    for li in rows_of(block["lineitem"]):
        o = orders[li["l_orderkey"]]
        if li["l_returnflag"] == 2 and lo <= o["o_orderdate"] < hi:  # 'R'
            want[o["o_custkey"]] = want.get(o["o_custkey"], 0) + (
                li["l_extendedprice"] * (100 - li["l_discount"]))
    assert tpch_q10.DICTIONARIES["lineitem"]["l_returnflag"][2] == "R"
    rows = []
    for k, v in want.items():
        c = cust[k]
        rows.append((
            k, text["c_name"][c["c_name"]], Fraction(v, 10_000),
            Fraction(c["c_acctbal"], 100),
            tpch_q10.NATION_NAMES[nations[c["c_nationkey"]]["n_name"]],
            text["c_address"][c["c_address"]],
            text["c_phone"][c["c_phone"]],
            text["c_comment"][c["c_comment"]],
        ))
    return sorted(rows, key=lambda r: (-r[2], r[0]))


@pytest.mark.parametrize("date", ["1993-10-01", "1994-11-01", "1993-02-01"])
def test_q10(toy, date):
    block, glob = toy
    want = by_hand(block, glob, date)
    ref = tpch_q10.reference("q10", {"date": date}, [block], glob)
    assert ref["kinds"] == ["int", "text", "sum", "sum", "text", "text",
                            "text", "text"]
    assert ref["order"] == [(2, "desc")]
    assert len(want) > 40  # the cut to twenty cut something
    assert ref["rows"] == want[:20]


def test_quarter_ends_three_months_on():
    assert tpch_q10.quarter("1993-11-01") == (
        tpch.days("1993-11-01"), tpch.days("1994-02-01"))
    assert tpch_q10.quarter("1995-01-01")[1] == tpch.days("1995-04-01")


def test_a_tie_at_rank_twenty_goes_to_the_smaller_key(toy):
    """Thirty customers with one returned line each, the 20th and 21st
    best with the same revenue: the reference keeps the smaller key,
    which is how ``compare.py`` pairs tied rows (by their exact cells,
    the key first)."""
    _block, glob = toy
    date = "1993-10-01"
    lo, _hi = tpch_q10.quarter(date)
    n = 30
    custkey = np.arange(n, 0, -1, dtype=np.int64) * 4  # best first: 120..4
    price = (np.arange(n, 0, -1, dtype=np.int64) + 50) * 1_000
    price[20] = price[19]  # ranks 20 and 21 tie; keys 44 and 40
    okey = np.arange(1, n + 1, dtype=np.int64)
    block = {
        "orders": {"o_orderkey": okey, "o_custkey": custkey,
                   "o_orderdate": np.full(n, lo + 5, dtype=np.int32)},
        "lineitem": {"l_orderkey": okey, "l_extendedprice": price,
                     "l_discount": np.zeros(n, dtype=np.int64),
                     "l_returnflag": np.full(n, 2, dtype=np.int32)},
    }
    tied = by_hand(block, glob, date)
    assert tied[19][2] == tied[20][2] and (tied[19][0], tied[20][0]) == (
        40, 44)
    ref = tpch_q10.reference("q10", {"date": date}, [block], glob)
    assert [r[0] for r in ref["rows"][17:]] == [52, 48, 40]
    assert ref["rows"] == tied[:20]


def test_blocks_add_up(toy):
    a = tpch_q10.make_block(24, SCALE, 0)
    b = tpch_q10.make_block(25, SCALE, 0)
    # (orders of two blocks must ascend for the reference's search)
    b["orders"]["o_orderkey"] = b["orders"]["o_orderkey"] + 10**9
    b["lineitem"]["l_orderkey"] = b["lineitem"]["l_orderkey"] + 10**9
    both = {t: {k: np.concatenate([a[t][k], b[t][k]]) for k in a[t]}
            for t in ("orders", "lineitem")}
    p = {"date": "1994-05-01"}
    assert (tpch_q10.reference("q10", p, [a, b], toy[1])
            == tpch_q10.reference("q10", p, [both], toy[1]))


def test_the_reference_refuses_another_data_sets_text(toy):
    block, glob = toy
    tpch_q10.make_global(24, SCALE / 2)  # another size's lists
    with pytest.raises(ValueError, match="another data set"):
        tpch_q10.reference("q10", {"date": "1993-10-01"}, [block], glob)


def test_control_reads_far_above_the_reference():
    """The control (float32 sums) on one block of the cell's own size:
    its gap is what the limit must catch."""
    block = tpch_q10.make_block(23, 0.35, 0)
    glob = tpch_q10.make_global(23, 0.35)
    p = {"date": "1994-01-01"}
    exact = tpch_q10.reference("q10", p, [block], glob)["rows"]
    control = tpch_q10.reference("q10", p, [block], glob, exact=False)["rows"]
    assert len(exact) == 20
    by_key = {r[0]: r[2] for r in control}
    gaps = [abs(by_key[r[0]] - r[2]) / r[2] for r in exact if r[0] in by_key]
    assert len(gaps) < 20 or max(gaps) > 1e-9, gaps


@pytest.mark.parametrize("seed", [11, 2**31 + 12345])
def test_generators(seed):
    sf = 0.01
    cnt = tpch_q10.counts(sf)
    assert tpch_q10.counts(10)["customer"] == 1_500_000
    assert cnt["customer"] == 1_500 and cnt["nation"] == 25
    assert tpch_q10.fact_rows(10) == 60_000_000  # by the counts alone
    assert tpch_q10.n_blocks(10) == tpch.n_blocks(10)
    g = tpch_q10.make_global(seed, sf)
    (b,) = [tpch_q10.make_block(seed, sf, i)
            for i in range(tpch_q10.n_blocks(sf))]
    # tpch.py's rows for every column both configurations load
    base = tpch.make_block(seed, sf, 0)
    for t in ("orders", "lineitem"):
        for col, v in b[t].items():
            assert np.array_equal(v, base[t][col]), col
    assert set(b["lineitem"]) == {
        "l_orderkey", "l_extendedprice", "l_discount", "l_returnflag"}
    assert set(np.unique(b["lineitem"]["l_returnflag"]).tolist()) == {0, 1, 2}
    q5 = tpch_q5.make_global(seed, sf)
    c = g["customer"]
    assert np.array_equal(c["c_custkey"], q5["customer"]["c_custkey"])
    assert np.array_equal(c["c_nationkey"], q5["customer"]["c_nationkey"])
    assert np.array_equal(g["nation"]["n_nationkey"], np.arange(25))
    assert np.array_equal(g["nation"]["n_name"], q5["nation"]["n_name"])
    assert list(c) == ["c_custkey", "c_name", "c_address", "c_nationkey",
                       "c_phone", "c_acctbal", "c_comment"]
    # the text columns: one value a row, the row's code its position
    text = tpch_q10.DICTIONARIES["customer"]
    n = cnt["customer"]
    for col in tpch_q10.TEXT_COLUMNS:
        assert np.array_equal(c[col], np.arange(n)) and len(text[col]) == n
        assert all(type(v) is str and v == v.strip() for v in text[col])
    assert text["c_name"][0] == "Customer#000000001"
    assert text["c_name"][-1] == f"Customer#{n:09d}"
    for phone, nk in zip(text["c_phone"], c["c_nationkey"].tolist()):
        m = re.fullmatch(r"(\d\d)-(\d{3})-(\d{3})-(\d{4})", phone)
        assert m and int(m.group(1)) == nk + 10 and len(phone) == 15
        assert 100 <= int(m.group(2)) and 1000 <= int(m.group(4))
    alen = [len(v) for v in text["c_address"]]
    assert min(alen) >= 10 and max(alen) <= 40 and len(set(alen)) > 20
    assert set("".join(text["c_address"])) <= set(tpch_q10.ADDRESS_ALPHABET)
    clen = [len(v) for v in text["c_comment"]]
    assert min(clen) >= 29 and max(clen) <= 116 and len(set(clen)) > 60
    for col in ("c_address", "c_phone", "c_comment"):
        assert len(set(text[col])) > 0.99 * n, col  # near-unique
    assert len(set(text["c_name"])) == n
    bal = c["c_acctbal"]
    assert bal.min() >= -99_999 and bal.max() <= 999_999
    assert bal.min() < -90_000 and bal.max() > 990_000 and (bal < 0).any()
    # a third of the customers has no order (cl.4.2.3)
    assert not (b["orders"]["o_custkey"] % 3 == 0).any()
    # the same rows for the same seed, other rows for another
    g2 = tpch_q10.make_global(seed, sf)
    assert all(np.array_equal(g2[t][k], g[t][k]) for t in g for k in g[t])
    assert tpch_q10.DICTIONARIES["customer"] == text
    other = tpch_q10.make_global(seed + 1, sf)
    assert not np.array_equal(other["customer"]["c_acctbal"], bal)
    assert tpch_q10.DICTIONARIES["customer"]["c_phone"] != text["c_phone"]
