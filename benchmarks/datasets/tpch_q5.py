"""TPC-H as Q5 (cl.2.4.5, "local supplier volume") reads it: the six
tables of its text from a seed, and its plain reference.

Nothing of the engine is imported here. ``orders``, ``lineitem`` and
``customer`` are ``tpch.py``'s rows, value for value, for every column
both configurations load; this module adds what Q5 joins on and
``tpch.py`` never made: ``l_suppkey`` by cl.4.2.3's formula from the
line's ``l_partkey`` (``tpch.py`` draws the part key to price the line
and drops it; the draws up to it are repeated here from the same stream,
and a test holds the two together through ``l_extendedprice``),
``c_nationkey`` and ``s_nationkey`` uniform over the 25 nations,
``supplier`` on dense keys 1..10,000 * SF, and ``nation`` and ``region``
as cl.4.3 lists them (names and the nation-to-region map are
``ssb_star.py``'s lists, which are TPC-H's).

Blockwise like ``tpch.py``: block ``b`` of ``orders`` + ``lineitem``
comes from ``SeedSequence([seed, 1, b])`` (the shared columns) and
``SeedSequence([seed, 3, b])`` (the supplier of a line) alone; the small
tables are made whole. Every seed has the same row counts.

Physical values as in ``tpch.py``: ``decimal(15,2)`` as int64
hundredths, ``date`` as int32 days since 1970-01-01, text as int32 codes
into ``DICTIONARIES``.
"""

from __future__ import annotations

import numpy as np

from . import tpch
from .ssb_star import NATION_NAMES, REGION_OF, REGIONS

SUPPLIERS_PER_SF = 10_000

DICTIONARIES = {
    "nation": {"n_name": NATION_NAMES},
    "region": {"r_name": REGIONS},
}

n_blocks = tpch.n_blocks
fact_rows = tpch.fact_rows


def counts(scale: float) -> dict:
    return {
        **tpch.counts(scale),
        "supplier": max(int(round(SUPPLIERS_PER_SF * scale)), 10),
        "nation": len(NATION_NAMES),
        "region": len(REGIONS),
    }


def suppkey(partkey: np.ndarray, i: np.ndarray, s: int) -> np.ndarray:
    """cl.4.2.3: the ``i``-th (0..3) of a part's four suppliers,
    ``(p + i * (S/4 + (p - 1)/S)) mod S + 1`` in integer arithmetic."""
    p = partkey.astype(np.int64)
    return (p + i * (s // 4 + (p - 1) // s)) % s + 1


def part_keys(seed: int, scale: float, b: int) -> np.ndarray:
    """The ``l_partkey`` of every line of block ``b``, as
    ``tpch.make_block`` drew it: its stream and its draws up to that
    one, in its order."""
    cnt = tpch.counts(scale)
    lo = b * tpch.BLOCK_ORDERS
    n = min(lo + tpch.BLOCK_ORDERS, cnt["orders"]) - lo
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1, b]))
    rng.integers(0, 2 * (cnt["customer"] // 3), n, dtype=np.int64)
    rng.integers(
        tpch.STARTDATE, tpch.ENDDATE - 151 + 1, n, dtype=np.int32
    )
    m = int(tpch.lines_per_order(rng, n).sum())
    rng.integers(1, 51, m, dtype=np.int64)
    return rng.integers(1, cnt["part"] + 1, m, dtype=np.int32)


def make_block(seed: int, scale: float, b: int) -> dict:
    """Block ``b``: ``tpch.py``'s orders and lines, cut to the columns
    this configuration loads, each line with its supplier."""
    base = tpch.make_block(seed, scale, b)
    o, li = base["orders"], base["lineitem"]
    partkey = part_keys(seed, scale, b)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3, b]))
    i = rng.integers(0, 4, len(partkey), dtype=np.int64)
    return {
        "orders": {
            k: o[k] for k in ("o_orderkey", "o_custkey", "o_orderdate")
        },
        "lineitem": {
            "l_orderkey": li["l_orderkey"],
            "l_partkey": partkey,
            "l_suppkey": suppkey(partkey, i, counts(scale)["supplier"]),
            "l_extendedprice": li["l_extendedprice"],
            "l_discount": li["l_discount"],
        },
    }


def make_global(seed: int, scale: float) -> dict:
    """customer (``tpch.py``'s keys, with a nation), supplier, nation,
    region, whole."""
    cnt = counts(scale)

    def nations(k: int, n: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([seed, 2, k]))
        return rng.integers(0, cnt["nation"], n, dtype=np.int64)

    return {
        "customer": {
            "c_custkey": tpch.make_global(seed, scale)["customer"][
                "c_custkey"],
            "c_nationkey": nations(1, cnt["customer"]),
        },
        "supplier": {
            "s_suppkey": np.arange(1, cnt["supplier"] + 1, dtype=np.int64),
            "s_nationkey": nations(2, cnt["supplier"]),
        },
        "nation": {
            "n_nationkey": np.arange(cnt["nation"], dtype=np.int64),
            "n_name": np.arange(cnt["nation"], dtype=np.int32),
            "n_regionkey": REGION_OF.astype(np.int64),
        },
        "region": {
            "r_regionkey": np.arange(cnt["region"], dtype=np.int64),
            "r_name": np.arange(cnt["region"], dtype=np.int32),
        },
    }


# ---------------------------------------------------------------------------
# reference (``exact=False``: the control, float32 sums)
# ---------------------------------------------------------------------------


def q5_block(block, p, exact, glob):
    """(revenue, lines) by supplier nation over the block's lines whose
    order falls in the year, whose customer and supplier share a nation,
    and whose nation lies in the region. Keys are dense (1..n), so a
    row is found by ``key - 1``; an order by position, as ``q3_block``."""
    cust, supp = glob["customer"], glob["supplier"]
    nat, reg = glob["nation"], glob["region"]
    rkey = reg["r_regionkey"][
        reg["r_name"] == REGIONS.index(p["region"])
    ]
    in_region = np.zeros(len(nat["n_nationkey"]), dtype=bool)
    in_region[nat["n_nationkey"][np.isin(nat["n_regionkey"], rkey)]] = True
    lo = tpch.days(f"{p['year']}-01-01")
    hi = tpch.days(f"{p['year'] + 1}-01-01")
    o, li = block["orders"], block["lineitem"]
    okeep = (o["o_orderdate"] >= lo) & (o["o_orderdate"] < hi)
    onation = cust["c_nationkey"][o["o_custkey"] - 1]
    pos = np.searchsorted(o["o_orderkey"], li["l_orderkey"])
    snation = supp["s_nationkey"][li["l_suppkey"] - 1]
    keep = okeep[pos] & (onation[pos] == snation) & in_region[snation]
    slot = snation[keep]
    w = li["l_extendedprice"][keep] * (100 - li["l_discount"][keep])
    if exact:
        sums = np.zeros(len(in_region), dtype=np.int64)
        np.add.at(sums, slot, w)
    else:
        sums = np.zeros(len(in_region), dtype=np.float32)
        np.add.at(sums, slot, w.astype(np.float32))
    return sums, np.bincount(slot, minlength=len(in_region))


def q5_finish(parts, glob, p, exact=True):
    lines = sum(c for _s, c in parts)
    name_of = dict(zip(
        glob["nation"]["n_nationkey"].tolist(),
        glob["nation"]["n_name"].tolist(),
    ))
    rows = [
        (NATION_NAMES[name_of[k]],
         tpch._dec(tpch._merge([
             float(s[k]) if s.dtype == np.float32 else int(s[k])
             for s, _c in parts
         ]), 4))
        for k in np.nonzero(lines)[0].tolist()
    ]
    return {
        "kinds": ["text", "sum"],
        "order": [(1, "desc")],
        "rows": sorted(rows, key=lambda r: -r[1]),
    }


QUERIES = {"q5": (q5_block, q5_finish)}


def reference(query: str, params: dict, blocks: list, glob: dict,
              exact: bool = True, pool=None) -> dict:
    """The answer of ``query`` under ``params`` over the generated data.
    ``exact=False`` is the control (float32 sums)."""
    return tpch.run_query(QUERIES, query, params, blocks, glob, exact, pool)
