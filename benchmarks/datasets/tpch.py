"""TPC-H data set: generator from a seed, and the plain references.

Nothing of the engine is imported here. The generator follows dbgen's
rules (TPC-H spec rev 3, cl.4.2) for the columns the configuration
loads; the references are straightforward numpy over the same arrays.

Blockwise: block ``b`` of ``orders`` and the ``lineitem`` rows of those
orders are drawn from ``SeedSequence([seed, 1, b])`` alone, so blocks
can be made (and referenced) by a few threads in any order and the data
of a seed is always the same. Every seed has the same row counts: the
lines-per-order counts of a block are a permutation of a fixed balanced
multiset of 1..7 (see ``assumed`` in the configuration).

Physical values: ``decimal(15,2)`` as int64 hundredths, ``date`` as
int32 days since 1970-01-01, text as int32 codes into the value lists
of ``DICTIONARIES``.
"""

from __future__ import annotations

import functools
from fractions import Fraction

import numpy as np

DAY0 = np.datetime64("1970-01-01", "D")


def days(iso: str) -> int:
    return int((np.datetime64(iso, "D") - DAY0).astype(np.int64))


STARTDATE = days("1992-01-01")
ENDDATE = days("1998-12-31")
CURRENTDATE = days("1995-06-17")
ORDERS_PER_SF = 1_500_000
CUSTOMERS_PER_SF = 150_000
PARTS_PER_SF = 200_000
BLOCK_ORDERS = 7 * 75_000  # 525,000 orders = 2.1M lineitem rows a block

DICTIONARIES = {
    "lineitem": {
        "l_returnflag": ["A", "N", "R"],
        "l_linestatus": ["F", "O"],
    },
    "customer": {
        "c_mktsegment": [
            "AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD",
        ],
    },
}
FLAG_A, FLAG_N, FLAG_R = 0, 1, 2
STATUS_F, STATUS_O = 0, 1


def counts(scale: float) -> dict:
    n_orders = max(int(round(ORDERS_PER_SF * scale)), 8)
    n_cust = max(int(round(CUSTOMERS_PER_SF * scale)), 3)
    n_part = max(int(round(PARTS_PER_SF * scale)), 10)
    return {"orders": n_orders, "customer": n_cust, "part": n_part}


def sparse_orderkey(i: np.ndarray) -> np.ndarray:
    """dbgen's MK_SPARSE: of every 32 key values the first 8 are used."""
    return ((i >> 3) << 5) | (i & 7)


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """p_retailprice (cl.4.2.3), in hundredths."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


@functools.lru_cache(maxsize=2)
def price_table(n_part: int) -> np.ndarray:
    """retail_price_cents of every part key 0..n_part (a gather is much
    cheaper than 64-bit division over every line)."""
    return retail_price_cents(np.arange(n_part + 1, dtype=np.int64))


def n_blocks(scale: float) -> int:
    return -(-counts(scale)["orders"] // BLOCK_ORDERS)


def fact_rows(scale: float) -> int:
    """lineitem rows at ``scale``, the same for every seed."""
    n = counts(scale)["orders"]
    full, rest = divmod(n, BLOCK_ORDERS)
    per = lambda k: 28 * (k // 7) + sum([4, 1, 7, 2, 6, 3][: k % 7])  # noqa: E731
    return full * per(BLOCK_ORDERS) + per(rest)


def lines_per_order(rng, n: int) -> np.ndarray:
    """A permutation of the balanced multiset of 1..7 (mean exactly 4 on
    a multiple of 7; the few left over take 4, 1, 7, 2, 6, 3)."""
    c = np.concatenate([
        np.tile(np.arange(1, 8, dtype=np.int32), n // 7),
        np.asarray([4, 1, 7, 2, 6, 3], dtype=np.int32)[: n % 7],
    ])
    rng.shuffle(c)
    return c


def make_block(seed: int, scale: float, b: int) -> dict:
    """Block ``b``: {'orders': {...}, 'lineitem': {...}}."""
    cnt = counts(scale)
    lo = b * BLOCK_ORDERS
    hi = min(lo + BLOCK_ORDERS, cnt["orders"])
    n = hi - lo
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1, b]))
    okey = sparse_orderkey(np.arange(lo, hi, dtype=np.int64))
    # o_custkey: never a multiple of 3 (a third of customers has no order)
    k = rng.integers(0, 2 * (cnt["customer"] // 3), n, dtype=np.int64)
    custkey = 3 * (k // 2) + 1 + (k % 2)
    odate = rng.integers(
        STARTDATE, ENDDATE - 151 + 1, n, dtype=np.int32
    )
    orders = {
        "o_orderkey": okey,
        "o_custkey": custkey,
        "o_orderdate": odate,
        "o_shippriority": np.zeros(n, dtype=np.int32),
    }
    per = lines_per_order(rng, n)
    idx = np.repeat(np.arange(n, dtype=np.int32), per)
    m = len(idx)
    qty = rng.integers(1, 51, m, dtype=np.int64)
    partkey = rng.integers(1, cnt["part"] + 1, m, dtype=np.int32)
    ship = odate[idx] + rng.integers(1, 122, m, dtype=np.int32)
    receipt = ship + rng.integers(1, 31, m, dtype=np.int32)
    ra = rng.integers(0, 2, m, dtype=np.int32) * FLAG_R  # A (0) or R (2)
    lineitem = {
        "l_orderkey": okey[idx],
        "l_quantity": qty * 100,
        "l_extendedprice": qty * price_table(cnt["part"])[partkey],
        "l_discount": rng.integers(0, 11, m, dtype=np.int64),
        "l_tax": rng.integers(0, 9, m, dtype=np.int64),
        "l_returnflag": np.where(
            receipt <= CURRENTDATE, ra, FLAG_N
        ).astype(np.int32),
        "l_linestatus": (ship > CURRENTDATE).astype(np.int32),
        "l_shipdate": ship.astype(np.int32),
    }
    return {"orders": orders, "lineitem": lineitem}


def make_global(seed: int, scale: float) -> dict:
    """Tables small enough to make whole: customer."""
    n = counts(scale)["customer"]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    return {
        "customer": {
            "c_custkey": np.arange(1, n + 1, dtype=np.int64),
            "c_mktsegment": rng.integers(0, 5, n, dtype=np.int32),
        }
    }


# ---------------------------------------------------------------------------
# references. Each takes the raw substitution parameters of the traffic
# mix and returns (column kinds, ORDER BY spec, rows) for compare.py.
# ``exact`` is False for the control: the same queries with float32 sums.
# ---------------------------------------------------------------------------


def _sum(a: np.ndarray, exact: bool):
    """Exact int64 sum, or the control's float32 accumulation."""
    if exact:
        return int(a.sum(dtype=np.int64))
    return float(a.astype(np.float32).sum(dtype=np.float32))


def _dec(total, scale: int):
    if isinstance(total, float):
        return Fraction(total) / 10 ** scale
    return Fraction(int(total), 10 ** scale)


def bf16(x: Fraction) -> Fraction:
    """The control's average: the quotient rounded to bfloat16 (8 bits
    of mantissa), the precision below the float32 the engine states."""
    bits = np.asarray([float(x)], dtype=np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return Fraction(float(bits.view(np.float32)[0]))


def _merge(parts: list):
    """Add per-block partial sums (ints, or the control's floats kept
    in float32)."""
    if isinstance(parts[0], float):
        acc = np.float32(0.0)
        for p in parts:
            acc = np.float32(acc + np.float32(p))
        return float(acc)
    return sum(parts)


def q6_block(block, p, exact, glob):
    li = block["lineitem"]
    lo = days(f"{p['year']}-01-01")
    hi = days(f"{p['year'] + 1}-01-01")
    keep = (
        (li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
        & (li["l_discount"] >= p["discount"] - 1)
        & (li["l_discount"] <= p["discount"] + 1)
        & (li["l_quantity"] < p["quantity"] * 100)
    )
    return _sum(li["l_extendedprice"][keep] * li["l_discount"][keep], exact)


def q6_finish(parts, glob, p, exact=True):
    return {
        "kinds": ["sum"], "order": [],
        "rows": [(_dec(_merge(parts), 4),)],
    }


def q1_block(block, p, exact, glob):
    li = block["lineitem"]
    cutoff = days("1998-12-01") - p["delta"]
    keep = li["l_shipdate"] <= cutoff
    key = (li["l_returnflag"] * 2 + li["l_linestatus"])[keep]
    qty = li["l_quantity"][keep]
    price = li["l_extendedprice"][keep]
    disc = li["l_discount"][keep]
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + li["l_tax"][keep])
    out = {}
    for g in range(6):
        m = key == g
        n = int(m.sum())
        if n:
            out[g] = [
                _sum(qty[m], exact), _sum(price[m], exact),
                _sum(disc_price[m], exact), _sum(charge[m], exact),
                _sum(disc[m], exact), n,
            ]
    return out


def q1_finish(parts, glob, p, exact=True):
    avg = (lambda x: x) if exact else bf16
    flags = DICTIONARIES["lineitem"]["l_returnflag"]
    status = DICTIONARIES["lineitem"]["l_linestatus"]
    rows = []
    for g in range(6):
        have = [part[g] for part in parts if g in part]
        if not have:
            continue
        sq, sp, sd, sc, sdisc = (
            _merge([h[i] for h in have]) for i in range(5)
        )
        n = sum(h[5] for h in have)
        rows.append((
            flags[g // 2], status[g % 2], _dec(sq, 2), _dec(sp, 2),
            _dec(sd, 4), _dec(sc, 6), avg(_dec(sq, 2) / n),
            avg(_dec(sp, 2) / n), avg(_dec(sdisc, 2) / n), n,
        ))
    return {
        "kinds": ["text", "text", "sum", "sum", "sum", "sum", "avg", "avg",
                  "avg", "int"],
        "order": [(0, "asc"), (1, "asc")],
        "rows": rows,
    }


def q3_block(block, p, exact, glob):
    """Per-order revenue of the block's qualifying lines, cut to the
    block's own ten best (the global ten are among them)."""
    cust = glob["customer"]
    seg = DICTIONARIES["customer"]["c_mktsegment"].index(p["segment"])
    date = days(f"1995-03-{p['day']:02d}")
    in_seg = np.zeros(len(cust["c_custkey"]) + 1, dtype=bool)
    in_seg[cust["c_custkey"][cust["c_mktsegment"] == seg]] = True
    o, li = block["orders"], block["lineitem"]
    okeep = (o["o_orderdate"] < date) & in_seg[o["o_custkey"]]
    # rows of one order are adjacent and orders ascend: position by search
    pos = np.searchsorted(o["o_orderkey"], li["l_orderkey"])
    keep = (li["l_shipdate"] > date) & okeep[pos]
    pos = pos[keep]
    w = li["l_extendedprice"][keep] * (100 - li["l_discount"][keep])
    if exact:
        rev = np.zeros(len(okeep), dtype=np.int64)
        np.add.at(rev, pos, w)
    else:
        rev = np.zeros(len(okeep), dtype=np.float32)
        np.add.at(rev, pos, w.astype(np.float32))
    hit = np.zeros(len(okeep), dtype=bool)
    hit[pos] = True
    live = np.nonzero(hit)[0]
    order = np.lexsort((o["o_orderdate"][live], -rev[live]))[:10]
    best = live[order]
    return [
        (int(o["o_orderkey"][i]),
         _dec(int(rev[i]) if exact else float(rev[i]), 4),
         int(o["o_orderdate"][i]), int(o["o_shippriority"][i]))
        for i in best
    ]


def q3_finish(parts, glob, p, exact=True):
    rows = sorted(
        (r for part in parts for r in part), key=lambda r: (-r[1], r[2])
    )[:10]
    return {
        "kinds": ["int", "sum", "date", "int"],
        "order": [(1, "desc"), (2, "asc")],
        "rows": rows,
    }


QUERIES = {
    "q1": (q1_block, q1_finish),
    "q6": (q6_block, q6_finish),
    "q3": (q3_block, q3_finish),
}


def run_query(queries: dict, query: str, params: dict, blocks: list,
              glob: dict, exact: bool, pool) -> dict:
    """``queries[query]`` = (per block, finish): the per-block parts, by
    ``pool``'s threads where one is given, folded by ``finish``."""
    per_block, finish = queries[query]

    def one(block):
        return per_block(block, params, exact, glob)

    parts = list(pool.map(one, blocks)) if pool else [one(b) for b in blocks]
    return finish(parts, glob, params, exact)


def reference(query: str, params: dict, blocks: list, glob: dict,
              exact: bool = True, pool=None) -> dict:
    """The answer of ``query`` under ``params`` over the generated data.
    ``exact=False`` is the control (float32 sums)."""
    return run_query(QUERIES, query, params, blocks, glob, exact, pool)
