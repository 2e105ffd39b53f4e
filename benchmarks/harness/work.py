"""The bytes a statement must read, from the configuration's own column
types and row counts — never from what the program uploads or keeps.

A statement's ``reads`` (in its traffic mix) names each table it touches
and the columns of it that the query text references; each such column
is counted once, whole, at the width its declared SQL type needs."""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json"
        )
    return table[device_kind]


def type_bytes(sqltype: str) -> int:
    """Bytes one value of a declared SQL type needs."""
    t = sqltype.lower().strip()
    if t in ("bigint", "int8"):
        return 8
    if t in ("int", "integer", "int4", "date"):
        return 4
    m = re.fullmatch(r"(?:decimal|numeric)\((\d+),\s*(\d+)\)", t)
    if m:
        return 8 if int(m.group(1)) > 9 else 4
    m = re.fullmatch(r"(?:char|varchar)\((\d+)\)", t)
    if m:
        return int(m.group(1))
    raise ValueError(f"no width known for SQL type {sqltype!r}")


def column_types(cfg: dict, table: str) -> dict:
    return {name: ty for name, ty, _loaded in cfg["tables"][table]["columns"]}


def statement_bytes(cfg: dict, rows: dict, reads: dict) -> int:
    """Bytes of every referenced column of every table read once."""
    total = 0
    for table, columns in reads.items():
        types = column_types(cfg, table)
        total += rows[table] * sum(type_bytes(types[c]) for c in columns)
    return total
