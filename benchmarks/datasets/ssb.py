"""Star Schema Benchmark data set: generator from a seed, and the plain
references of flight 1 (Q1.1, Q1.2, Q1.3).

Nothing of the engine is imported here. Shapes follow O'Neil, O'Neil and
Chen, "Star Schema Benchmark" rev 3 (2009): ``lineorder`` 6M*SF rows in
orders of 1..7 lines, priced from ``part``'s 200k*floor(1+log2 SF) keys,
and ``dates`` 2556 days from 1992-01-01 with ``d_datekey`` as yyyymmdd.
Money is the source's integer hundredths. Flight 1 reads no other
dimension, so none is made.

Blockwise like ``tpch.py``: block ``b`` of ``lineorder`` comes from
``SeedSequence([seed, 1, b])`` alone; ``dates`` is made whole. Every
seed has the same row counts.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from . import tpch

ORDERS_PER_SF = 1_500_000
BLOCK_ORDERS = tpch.BLOCK_ORDERS

DICTIONARIES: dict = {}  # no text column is loaded


def counts(scale: float) -> dict:
    return {
        "orders": max(int(round(ORDERS_PER_SF * scale)), 8),
        "part": max(
            int(200_000 * math.floor(1 + math.log2(scale))), 1000
        ) if scale >= 1 else max(int(round(200_000 * scale)), 1000),
        "dates": 2556,
    }


def n_blocks(scale: float) -> int:
    return -(-counts(scale)["orders"] // BLOCK_ORDERS)


def date_table() -> dict:
    d = tpch.DAY0 + np.arange(tpch.STARTDATE, tpch.STARTDATE + 2556)
    ymd = d.astype("datetime64[D]").astype(str)
    key = np.asarray([int(s.replace("-", "")) for s in ymd], dtype=np.int32)
    year = (key // 10000).astype(np.int32)
    jan1 = (year.astype(str).astype("datetime64[Y]")).astype("datetime64[D]")
    day_in_year = (d.astype("datetime64[D]") - jan1).astype(np.int32)
    return {
        "d_datekey": key, "d_year": year,
        "d_yearmonthnum": (key // 100).astype(np.int32),
        "d_weeknuminyear": (day_in_year // 7 + 1).astype(np.int32),
    }


@functools.lru_cache(maxsize=1)
def _datekeys() -> np.ndarray:
    return date_table()["d_datekey"]


def make_global(seed: int, scale: float) -> dict:
    return {"dates": date_table()}


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
    datekey = _datekeys()[day]
    per = tpch.lines_per_order(rng, n)
    idx = np.repeat(np.arange(n, dtype=np.int32), per)
    m = len(idx)
    qty = rng.integers(1, 51, m, dtype=np.int32)
    partkey = rng.integers(1, cnt["part"] + 1, m, dtype=np.int32)
    price = tpch.price_table(cnt["part"])[partkey]
    ext = qty * price
    disc = rng.integers(0, 11, m, dtype=np.int32)
    return {"lineorder": {
        "lo_orderkey": okey[idx],
        "lo_orderdate": datekey[idx],
        "lo_quantity": qty,
        "lo_extendedprice": ext.astype(np.int32),
        "lo_discount": disc,
    }}


# ---------------------------------------------------------------------------
# references (``exact=False``: the control, float32 sums)
# ---------------------------------------------------------------------------


def _flight1(block, glob, exact, day_keep, disc, qty):
    """sum(lo_extendedprice * lo_discount) over the lines whose date row
    passes ``day_keep`` (a mask over the date table), with lo_discount
    and lo_quantity inside the closed ranges ``disc`` and ``qty``."""
    lo = block["lineorder"]
    d = glob["dates"]
    keep = (
        day_keep[np.searchsorted(d["d_datekey"], lo["lo_orderdate"])]
        & (lo["lo_discount"] >= disc[0]) & (lo["lo_discount"] <= disc[1])
        & (lo["lo_quantity"] >= qty[0]) & (lo["lo_quantity"] <= qty[1])
    )
    w = lo["lo_extendedprice"][keep].astype(np.int64) * lo["lo_discount"][keep]
    return tpch._sum(w, exact)


def q11_block(block, p, exact, glob):
    return _flight1(
        block, glob, exact, glob["dates"]["d_year"] == 1993, (1, 3), (1, 24)
    )


def q12_block(block, p, exact, glob):
    return _flight1(
        block, glob, exact, glob["dates"]["d_yearmonthnum"] == 199401,
        (4, 6), (26, 35),
    )


def q13_block(block, p, exact, glob):
    d = glob["dates"]
    return _flight1(
        block, glob, exact,
        (d["d_weeknuminyear"] == 6) & (d["d_year"] == 1994), (5, 7), (26, 35),
    )


def q11_finish(parts, glob, p, exact=True):
    return {"kinds": ["sum"], "order": [],
            "rows": [(tpch._dec(tpch._merge(parts), 0),)]}


QUERIES = {
    "q11": (q11_block, q11_finish),
    "q12": (q12_block, q11_finish),
    "q13": (q13_block, q11_finish),
}


def reference(query: str, params: dict, blocks: list, glob: dict,
              exact: bool = True, pool=None) -> dict:
    return tpch.run_query(QUERIES, query, params, blocks, glob, exact, pool)
