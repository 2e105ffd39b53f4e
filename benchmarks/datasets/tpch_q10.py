"""TPC-H as Q10 (cl.2.4.10, "returned item reporting") reads it: the
four tables of its text from a seed, and its plain reference.

Nothing of the engine is imported here. ``orders`` and ``lineitem`` are
``tpch.py``'s rows, value for value, for every column both
configurations load (``l_returnflag`` among them); ``c_custkey`` is
``tpch.py``'s, ``c_nationkey`` and ``nation`` are ``tpch_q5.py``'s. This
module adds the customer's attributes the query returns (cl.4.2.3):
``c_name`` (``Customer#`` and the key as nine digits), ``c_phone``
(country code ``c_nationkey + 10`` and three groups
``[100, 999]-[100, 999]-[1000, 9999]``), ``c_acctbal`` uniform in
[-999.99, 9999.99], and, dbgen's text grammar not being at hand (see
``assumed`` in the configuration), ``c_address`` as 10..40 seeded
characters of an alphabet of 64 and ``c_comment`` as 29..116 characters
cut from a seeded pool of words at a seeded place (which is how dbgen
cuts its own pool).

The four text columns hold nearly one distinct value a row, so their
value lists are made with the rows: ``make_global`` puts them under
``DICTIONARIES["customer"]`` (the loader reads ``DICTIONARIES`` after it
generated), the row's code is its position, and the reference decodes
through the same lists. They are the lists of the LAST ``make_global``
of the process; ``reference`` refuses another size.

Physical values as in ``tpch.py``: ``decimal(15,2)`` as int64
hundredths, ``date`` as int32 days since 1970-01-01, text as int32 codes
into ``DICTIONARIES``.
"""

from __future__ import annotations

import numpy as np

from . import tpch, tpch_q5
from .ssb_star import NATION_NAMES

TEXT_COLUMNS = ("c_name", "c_address", "c_phone", "c_comment")
ADDRESS_ALPHABET = (
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789,."
)
# a small vocabulary in the manner of cl.4.2.2.10's (nouns, verbs,
# adjectives, adverbs, prepositions, terminators are dbgen's classes)
WORDS = (
    "packages requests accounts deposits foxes ideas theodolites pinto "
    "beans instructions dependencies excuses platelets asymptotes courts "
    "dolphins multipliers sauternes warthogs frets dinos attainments "
    "somas braids hockey players frays warhorses dugouts notornis "
    "epitaphs pearls tithes waters orbits gifts sheaves depths sentiments "
    "decoys realms pains grouches escapades sleep wake are cajole haggle "
    "nag use boost affix detect integrate maintain nod was lose sublate "
    "solve thrash promise engage hinder print doze run furious sly "
    "careful blithe quick fluffy slow quiet ruthless thin close dogged "
    "daring brave stealthy permanent enticing idle busy regular final "
    "ironic even bold silent sometimes always never furiously slyly "
    "carefully blithely quickly fluffily slowly quietly ruthlessly "
    "thinly closely doggedly daringly bravely stealthily permanently "
    "about above according across after against along alongside among "
    "around at atop before behind beneath beside besides between beyond "
    "by despite during except for from in inside instead into near of on "
    "outside over past since through throughout to toward under until up "
    "upon without with within the special pending unusual express"
).split()
COMMENT_POOL_WORDS = 1 << 19  # ~3.7 MB of text: 1.5M cuts of it are
# distinct but for a few in a thousand

DICTIONARIES = {
    "lineitem": {"l_returnflag": tpch.DICTIONARIES["lineitem"][
        "l_returnflag"]},
    "nation": {"n_name": NATION_NAMES},
    "customer": {},  # make_global's, one value a row
}

n_blocks = tpch.n_blocks
fact_rows = tpch.fact_rows


def counts(scale: float) -> dict:
    return {**tpch.counts(scale), "nation": len(NATION_NAMES)}


def make_block(seed: int, scale: float, b: int) -> dict:
    """Block ``b``: ``tpch.py``'s orders and lines, cut to the columns
    this configuration loads."""
    base = tpch.make_block(seed, scale, b)
    o, li = base["orders"], base["lineitem"]
    return {
        "orders": {
            k: o[k] for k in ("o_orderkey", "o_custkey", "o_orderdate")
        },
        "lineitem": {
            k: li[k] for k in (
                "l_orderkey", "l_extendedprice", "l_discount",
                "l_returnflag",
            )
        },
    }


def _stream(seed: int, k: int):
    return np.random.default_rng(np.random.SeedSequence([seed, 4, k]))


def names(custkey: np.ndarray) -> list:
    return [f"Customer#{k:09d}" for k in custkey.tolist()]


def phones(seed: int, nationkey: np.ndarray) -> list:
    rng = _stream(seed, 1)
    n = len(nationkey)
    a = rng.integers(100, 1000, n).tolist()
    b = rng.integers(100, 1000, n).tolist()
    c = rng.integers(1000, 10000, n).tolist()
    return [
        f"{cc}-{x}-{y}-{z}"
        for cc, x, y, z in zip((nationkey + 10).tolist(), a, b, c)
    ]


def addresses(seed: int, n: int) -> list:
    rng = _stream(seed, 2)
    alphabet = np.frombuffer(ADDRESS_ALPHABET.encode(), dtype=np.uint8)
    chars = alphabet[rng.integers(0, 64, (n, 40), dtype=np.uint8)]
    length = rng.integers(10, 41, n)
    chars[np.arange(40)[None, :] >= length[:, None]] = 0
    # (a bytes value drops the zeros behind its text)
    return [s.decode() for s in chars.view("S40").ravel().tolist()]


def comments(seed: int, n: int) -> list:
    rng = _stream(seed, 3)
    pool = " ".join(
        np.asarray(WORDS, dtype=object)[
            rng.integers(0, len(WORDS), COMMENT_POOL_WORDS)
        ].tolist()
    )
    length = rng.integers(29, 117, n)
    start = rng.integers(0, len(pool) - 116, n)
    out = []
    for lo, ln in zip(start.tolist(), length.tolist()):
        s = pool[lo:lo + ln]
        # a cut may fall on a blank: a value neither starts nor ends
        # with one (a char(n) comparison would not see it)
        if s[0] == " ":
            s = "a" + s[1:]
        if s[-1] == " ":
            s = s[:-1] + "s"
        out.append(s)
    return out


def make_global(seed: int, scale: float) -> dict:
    """customer (``tpch.py``'s keys, ``tpch_q5.py``'s nation, and what
    Q10 returns of it) and nation, whole; the customer's four value
    lists go to ``DICTIONARIES``."""
    cnt = counts(scale)
    n = cnt["customer"]
    q5 = tpch_q5.make_global(seed, scale)
    custkey = q5["customer"]["c_custkey"]
    nationkey = q5["customer"]["c_nationkey"]
    DICTIONARIES["customer"] = {
        "c_name": names(custkey),
        "c_address": addresses(seed, n),
        "c_phone": phones(seed, nationkey),
        "c_comment": comments(seed, n),
    }
    code = np.arange(n, dtype=np.int32)
    return {
        "customer": {
            "c_custkey": custkey,
            "c_name": code,
            "c_address": code,
            "c_nationkey": nationkey,
            "c_phone": code,
            "c_acctbal": _stream(seed, 0).integers(
                -99_999, 1_000_000, n, dtype=np.int64),
            "c_comment": code,
        },
        "nation": {
            k: q5["nation"][k] for k in ("n_nationkey", "n_name")
        },
    }


# ---------------------------------------------------------------------------
# reference (``exact=False``: the control, float32 sums)
# ---------------------------------------------------------------------------

LIMIT = 20


def quarter(date: str) -> tuple:
    """[DATE, DATE + 3 months) in days."""
    lo = np.datetime64(date, "M")
    return tpch.days(date), int(
        ((lo + np.timedelta64(3, "M")).astype("datetime64[D]") - tpch.DAY0)
        .astype(np.int64)
    )


def q10_block(block, p, exact, glob):
    """(revenue, lines) by ``o_custkey`` (dense from 1: slot = key) over
    the block's returned lines whose order falls in the quarter. Rows
    of one order are adjacent and orders ascend: position by search, as
    ``tpch.q3_block``; the money as there, exact integers of 1e-4."""
    lo, hi = quarter(p["date"])
    o, li = block["orders"], block["lineitem"]
    okeep = (o["o_orderdate"] >= lo) & (o["o_orderdate"] < hi)
    pos = np.searchsorted(o["o_orderkey"], li["l_orderkey"])
    keep = (li["l_returnflag"] == tpch.FLAG_R) & okeep[pos]
    slot = o["o_custkey"][pos[keep]]
    w = li["l_extendedprice"][keep] * (100 - li["l_discount"][keep])
    slots = len(glob["customer"]["c_custkey"]) + 1
    if exact:
        sums = np.zeros(slots, dtype=np.int64)
        np.add.at(sums, slot, w)
    else:
        sums = np.zeros(slots, dtype=np.float32)
        np.add.at(sums, slot, w.astype(np.float32))
    return sums, np.bincount(slot, minlength=slots)


def q10_finish(parts, glob, p, exact=True):
    cust, nat = glob["customer"], glob["nation"]
    text = DICTIONARIES["customer"]
    if len(text.get("c_name", ())) != len(cust["c_custkey"]):
        raise ValueError(
            "DICTIONARIES['customer'] is another data set's: the "
            "reference runs after its own make_global"
        )
    total = parts[0][0].copy()
    for s, _c in parts[1:]:
        total += s  # (the control's stays float32)
    lines = sum(c for _s, c in parts)
    keys = np.nonzero(lines)[0]
    # revenue descending; a tie by the customer key, which is how
    # compare.py pairs tied rows (by their exact cells, the key first)
    best = keys[np.lexsort((keys, -total[keys]))][:LIMIT]
    name_of = dict(zip(
        nat["n_nationkey"].tolist(), nat["n_name"].tolist()
    ))
    rows = []
    for k in best.tolist():
        r = int(np.searchsorted(cust["c_custkey"], k))  # keys ascend
        rows.append((
            k,
            text["c_name"][cust["c_name"][r]],
            tpch._dec(
                float(total[k]) if total.dtype == np.float32
                else int(total[k]), 4),
            tpch._dec(int(cust["c_acctbal"][r]), 2),
            NATION_NAMES[name_of[int(cust["c_nationkey"][r])]],
            text["c_address"][cust["c_address"][r]],
            text["c_phone"][cust["c_phone"][r]],
            text["c_comment"][cust["c_comment"][r]],
        ))
    return {
        "kinds": ["int", "text", "sum", "sum", "text", "text", "text",
                  "text"],
        "order": [(2, "desc")],
        "rows": rows,
    }


QUERIES = {"q10": (q10_block, q10_finish)}


def reference(query: str, params: dict, blocks: list, glob: dict,
              exact: bool = True, pool=None) -> dict:
    """The answer of ``query`` under ``params`` over the generated data.
    ``exact=False`` is the control (float32 sums)."""
    return tpch.run_query(QUERIES, query, params, blocks, glob, exact, pool)
