"""The comparison that decides ``correct``: the rows a client received
for a timed statement against the data set's plain reference.

Three numbers come out of a run, each with a limit of its own
(``limits.json``):

  wrong_statements  statements whose shape, order, text, integer or date
                    cells differ from the reference, or that failed.
                    Exact: limit 0.
  sum_gap           the widest relative gap of a decimal/integer SUM cell
                    (the configuration states exact fixed-point sums; the
                    wire delivers them as doubles, so 2**-53 is the floor).
  avg_gap           the widest relative gap of an AVG cell (the engine
                    states float32 averages).
"""

from __future__ import annotations

import json
import os
from fractions import Fraction

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
EXACT_KINDS = ("text", "int", "date")


def read_limits() -> dict:
    with open(os.path.join(HERE, "limits.json")) as f:
        return json.load(f)["limits"]


def _cell(kind: str, v):
    """A received cell in the reference's terms."""
    if v is None:
        return None
    if kind == "text":
        return str(v).rstrip()  # char(n) may arrive blank-padded
    if kind == "int":
        if isinstance(v, float) and not v.is_integer():
            return v
        return int(v)
    if kind == "date":
        if isinstance(v, str):
            return int(
                (np.datetime64(v, "D") - np.datetime64("1970-01-01", "D"))
                .astype(np.int64)
            )
        return int(v)
    return Fraction(v)


def _gap(got: Fraction, want: Fraction) -> float:
    if want == 0:
        return float(abs(got))
    return float(abs(got - want) / abs(want))


def _ordered(rows: list, order: list) -> bool:
    """The rows obey the statement's ORDER BY on their own values."""
    def key(r):
        return tuple(r[i] if d == "asc" else _neg(r[i]) for i, d in order)
    keys = [key(r) for r in rows]
    return all(a <= b for a, b in zip(keys, keys[1:]))


def _neg(v):
    if isinstance(v, str):
        raise TypeError("descending text order is not built")
    return -v


def compare_statement(rows, ref: dict) -> dict:
    """{'wrong': reason or None, 'sum_gap': f, 'avg_gap': f} for one
    statement's received rows against its reference."""
    kinds, want = ref["kinds"], ref["rows"]
    out = {"wrong": None, "sum_gap": 0.0, "avg_gap": 0.0}
    if rows is None or len(rows) != len(want):
        out["wrong"] = (
            f"{0 if rows is None else len(rows)} rows, reference {len(want)}"
        )
        return out
    try:
        got = [
            tuple(_cell(k, v) for k, v in zip(kinds, r, strict=True))
            for r in rows
        ]
    except (ValueError, TypeError) as e:
        out["wrong"] = f"undecodable row: {e}"
        return out
    if any(v is None for r in got for v in r):
        out["wrong"] = "NULL in a result the reference has none in"
        return out
    if not _ordered(got, ref["order"]):
        out["wrong"] = "rows not in the statement's order"
        return out
    exact = [i for i, k in enumerate(kinds) if k in EXACT_KINDS]

    def by_exact(r):
        return tuple(r[i] for i in exact)

    # rows tied under ORDER BY may come in either order: pair them by
    # their exact cells (the group keys), then by value
    got_s = sorted(got, key=lambda r: (by_exact(r), r))
    want_s = sorted(want, key=lambda r: (by_exact(r), r))
    for g, w in zip(got_s, want_s):
        for k, gv, wv in zip(kinds, g, w):
            if k in EXACT_KINDS:
                if gv != wv:
                    out["wrong"] = f"{k} cell {gv!r}, reference {wv!r}"
                    return out
            else:
                key = "sum_gap" if k == "sum" else "avg_gap"
                out[key] = max(out[key], _gap(gv, wv))
    return out


def judge(results: list, limits: dict) -> dict:
    """Fold per-statement comparisons into the run's numbers, each beside
    its limit, and the verdict."""
    numbers = {
        "wrong_statements": sum(1 for r in results if r["wrong"]),
        "sum_gap": max((r["sum_gap"] for r in results), default=0.0),
        "avg_gap": max((r["avg_gap"] for r in results), default=0.0),
    }
    compared = {
        name: {"value": numbers[name], "limit": limits[name]}
        for name in numbers
    }
    ok = bool(results) and all(
        c["value"] <= c["limit"] for c in compared.values()
    )
    reasons = [r["wrong"] for r in results if r["wrong"]][:3]
    return {"correct": ok, "compared": compared, "reasons": reasons}
