"""Star Schema Benchmark, the star flights: ``lineorder`` with its four
dimensions from a seed, and the plain references of Q2.1, Q3.1 and Q4.1
(the first query of each of flights 2, 3 and 4).

Nothing of the engine is imported here. Shapes follow O'Neil, O'Neil and
Chen, "Star Schema Benchmark" rev 3 (2009): ``lineorder`` 6M*SF rows in
orders of 1..7 lines, ``customer`` 30k*SF, ``supplier`` 2k*SF, ``part``
200k*floor(1+log2 SF), ``dates`` 2556 days (``ssb.py``'s). The source's
dbgen is not at hand, so the rules are stated (``assumed`` in the
configuration): dimension keys dense 1..n; a customer's and a supplier's
nation uniform over TPC-H's 25 nations, each in its region; a part's
brand uniform over the 1,000 ``MFGR#<m><c><b>`` (m, c in 1..5, b in
1..40), its category and manufacturer the brand's prefixes; an order's
customer and a line's part and supplier uniform. Money is the source's
integer hundredths: ``lo_revenue = lo_extendedprice * (100 -
lo_discount) / 100`` and ``lo_supplycost = 6 * p_price / 10``.

Text columns are int32 codes into the value lists of ``DICTIONARIES``.
Blockwise like ``ssb.py``: block ``b`` of ``lineorder`` comes from
``SeedSequence([seed, 1, b])`` alone, the dimensions are made whole from
``SeedSequence([seed, 2, k])``. Every seed has the same row counts.

The references find a line's dimension row by direct index (``key - 1``;
the date by position in the ascending ``d_datekey``) and add each kept
line into its group's slot with ``np.add.at``.
"""

from __future__ import annotations

import numpy as np

from . import ssb, tpch

CUSTOMERS_PER_SF = 30_000
SUPPLIERS_PER_SF = 2_000
YEAR0, YEARS = 1992, 7

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
# TPC-H's nations (cl.4.3) with the region each lies in
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2),
    ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0), ("MOZAMBIQUE", 0),
    ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3), ("SAUDI ARABIA", 4),
    ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1),
]
NATION_NAMES = [n for n, _r in NATIONS]
REGION_OF = np.asarray([r for _n, r in NATIONS], dtype=np.int32)
MFGRS = [f"MFGR#{m}" for m in range(1, 6)]
CATEGORIES = [f"{m}{c}" for m in MFGRS for c in range(1, 6)]
BRANDS = [f"{c}{b}" for c in CATEGORIES for b in range(1, 41)]

DICTIONARIES = {
    "customer": {"c_nation": NATION_NAMES, "c_region": REGIONS},
    "supplier": {"s_nation": NATION_NAMES, "s_region": REGIONS},
    "part": {"p_mfgr": MFGRS, "p_category": CATEGORIES, "p_brand1": BRANDS},
}

n_blocks = ssb.n_blocks
BLOCK_ORDERS = ssb.BLOCK_ORDERS


def counts(scale: float) -> dict:
    return {
        **ssb.counts(scale),
        "customer": max(int(round(CUSTOMERS_PER_SF * scale)), 50),
        "supplier": max(int(round(SUPPLIERS_PER_SF * scale)), 25),
    }


def _who(rng, n: int, key: str, prefix: str) -> dict:
    nation = rng.integers(0, len(NATIONS), n, dtype=np.int32)
    return {
        key: np.arange(1, n + 1, dtype=np.int32),
        f"{prefix}_nation": nation,
        f"{prefix}_region": REGION_OF[nation],
    }


def make_global(seed: int, scale: float) -> dict:
    """The four dimensions, whole."""
    cnt = counts(scale)

    def rng(k):
        return np.random.default_rng(np.random.SeedSequence([seed, 2, k]))

    brand = rng(2).integers(0, len(BRANDS), cnt["part"], dtype=np.int32)
    dates = ssb.date_table()
    return {
        "customer": _who(rng(0), cnt["customer"], "c_custkey", "c"),
        "supplier": _who(rng(1), cnt["supplier"], "s_suppkey", "s"),
        "part": {
            "p_partkey": np.arange(1, cnt["part"] + 1, dtype=np.int32),
            "p_mfgr": brand // 200,
            "p_category": brand // 40,
            "p_brand1": brand,
        },
        "dates": {k: dates[k] for k in ("d_datekey", "d_year")},
    }


def make_block(seed: int, scale: float, b: int) -> dict:
    cnt = counts(scale)
    lo = b * BLOCK_ORDERS
    hi = min(lo + BLOCK_ORDERS, cnt["orders"])
    n = hi - lo
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1, b]))
    okey = tpch.sparse_orderkey(np.arange(lo, hi, dtype=np.int64))
    day = rng.integers(
        0, tpch.ENDDATE - 151 - tpch.STARTDATE + 1, n, dtype=np.int32
    )
    custkey = rng.integers(1, cnt["customer"] + 1, n, dtype=np.int32)
    per = tpch.lines_per_order(rng, n)
    idx = np.repeat(np.arange(n, dtype=np.int32), per)
    m = len(idx)
    qty = rng.integers(1, 51, m, dtype=np.int32)
    partkey = rng.integers(1, cnt["part"] + 1, m, dtype=np.int32)
    suppkey = rng.integers(1, cnt["supplier"] + 1, m, dtype=np.int32)
    disc = rng.integers(0, 11, m, dtype=np.int32)
    price = tpch.price_table(cnt["part"])[partkey]
    ext = qty * price  # int64 hundredths
    return {"lineorder": {
        "lo_orderkey": okey[idx],
        "lo_custkey": custkey[idx],
        "lo_partkey": partkey,
        "lo_suppkey": suppkey,
        "lo_orderdate": ssb._datekeys()[day][idx],
        "lo_revenue": (ext * (100 - disc) // 100).astype(np.int32),
        "lo_supplycost": (6 * price // 10).astype(np.int32),
    }}


# ---------------------------------------------------------------------------
# references (``exact=False``: the control, float32 sums). A query is its
# group slots (the value list and code of each key, year as an offset
# from 1992), which lines it keeps and what it sums.
# ---------------------------------------------------------------------------


def _year(lo, glob) -> np.ndarray:
    d = glob["dates"]
    return d["d_year"][np.searchsorted(d["d_datekey"], lo["lo_orderdate"])]


def _grouped(keep, codes, sizes, value, exact):
    """(sums, counts) over the mixed-radix slot of ``codes``."""
    slot = np.zeros(int(keep.sum()), dtype=np.int64)
    for c, size in zip(codes, sizes):
        slot = slot * size + c[keep]
    n = int(np.prod(sizes))
    v = value[keep]
    if exact:
        sums = np.zeros(n, dtype=np.int64)
        np.add.at(sums, slot, v.astype(np.int64))
    else:
        sums = np.zeros(n, dtype=np.float32)
        np.add.at(sums, slot, v.astype(np.float32))
    return sums, np.bincount(slot, minlength=n)


def q21_block(block, p, exact, glob):
    lo, part, supp = block["lineorder"], glob["part"], glob["supplier"]
    pi, si = lo["lo_partkey"] - 1, lo["lo_suppkey"] - 1
    keep = (
        (part["p_category"][pi] == CATEGORIES.index("MFGR#12"))
        & (supp["s_region"][si] == REGIONS.index("AMERICA"))
    )
    return _grouped(
        keep, (_year(lo, glob) - YEAR0, part["p_brand1"][pi]),
        (YEARS, len(BRANDS)), lo["lo_revenue"], exact,
    )


def q31_block(block, p, exact, glob):
    lo, cust, supp = block["lineorder"], glob["customer"], glob["supplier"]
    ci, si = lo["lo_custkey"] - 1, lo["lo_suppkey"] - 1
    year = _year(lo, glob)
    asia = REGIONS.index("ASIA")
    keep = (
        (cust["c_region"][ci] == asia) & (supp["s_region"][si] == asia)
        & (year >= 1992) & (year <= 1997)
    )
    return _grouped(
        keep, (cust["c_nation"][ci], supp["s_nation"][si], year - YEAR0),
        (len(NATIONS), len(NATIONS), YEARS), lo["lo_revenue"], exact,
    )


def q41_block(block, p, exact, glob):
    lo = block["lineorder"]
    cust, supp, part = glob["customer"], glob["supplier"], glob["part"]
    ci, si, pi = (
        lo["lo_custkey"] - 1, lo["lo_suppkey"] - 1, lo["lo_partkey"] - 1
    )
    america = REGIONS.index("AMERICA")
    mfgr = part["p_mfgr"][pi]
    keep = (
        (cust["c_region"][ci] == america) & (supp["s_region"][si] == america)
        & ((mfgr == MFGRS.index("MFGR#1")) | (mfgr == MFGRS.index("MFGR#2")))
    )
    return _grouped(
        keep, (_year(lo, glob) - YEAR0, cust["c_nation"][ci]),
        (YEARS, len(NATIONS)), lo["lo_revenue"] - lo["lo_supplycost"], exact,
    )


def _merged(parts: list, sizes: tuple):
    """Per live slot: (its codes, its sum), blocks added in order."""
    counts = sum(c for _s, c in parts)
    if parts[0][0].dtype == np.float32:
        sums = np.zeros(len(counts), dtype=np.float32)
        for s, _c in parts:
            sums += s
    else:
        sums = sum(s for s, _c in parts)
    for slot in np.nonzero(counts)[0]:
        codes = np.unravel_index(slot, sizes)
        total = sums[slot]
        yield [int(c) for c in codes], tpch._dec(
            float(total) if sums.dtype == np.float32 else int(total), 0
        )


def q21_finish(parts, glob, p, exact=True):
    rows = [
        (total, YEAR0 + y, BRANDS[b])
        for (y, b), total in _merged(parts, (YEARS, len(BRANDS)))
    ]
    return {"kinds": ["sum", "int", "text"],
            "order": [(1, "asc"), (2, "asc")],
            "rows": sorted(rows, key=lambda r: (r[1], r[2]))}


def q31_finish(parts, glob, p, exact=True):
    n = len(NATIONS)
    rows = [
        (NATION_NAMES[c], NATION_NAMES[s], YEAR0 + y, total)
        for (c, s, y), total in _merged(parts, (n, n, YEARS))
    ]
    return {"kinds": ["text", "text", "int", "sum"],
            "order": [(2, "asc"), (3, "desc")],
            "rows": sorted(rows, key=lambda r: (r[2], -r[3]))}


def q41_finish(parts, glob, p, exact=True):
    rows = [
        (YEAR0 + y, NATION_NAMES[c], total)
        for (y, c), total in _merged(parts, (YEARS, len(NATIONS)))
    ]
    return {"kinds": ["int", "text", "sum"],
            "order": [(0, "asc"), (1, "asc")],
            "rows": sorted(rows, key=lambda r: (r[0], r[1]))}


QUERIES = {
    "q21": (q21_block, q21_finish),
    "q31": (q31_block, q31_finish),
    "q41": (q41_block, q41_finish),
}


def reference(query: str, params: dict, blocks: list, glob: dict,
              exact: bool = True, pool=None) -> dict:
    return tpch.run_query(QUERIES, query, params, blocks, glob, exact, pool)
