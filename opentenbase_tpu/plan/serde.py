"""Portable plan serialization — the outfuncs/readfuncs analog.

The reference ships plan fragments CN -> DN as text node trees
(set_portable_output, src/backend/nodes/outfuncs.c:75; read back via
src/backend/nodes/readfuncs.c:78, received as the 'p' protocol message
src/backend/tcop/postgres.c:5580). Here every logical-plan and typed-
expression node is a frozen dataclass, so one generic reflective codec
covers the whole IR: a JSON tree tagged with node class names, tuples,
enums, and SqlType instances. Decoding validates against the registry of
known node classes — nothing outside the plan IR can be instantiated.

Also provides ColumnBatch (de)serialization for motioned intermediate
results (DataRow messages), as npz bytes so numeric columns round-trip
bit-exactly.
"""

from __future__ import annotations

import base64
import dataclasses
import io
import json

import numpy as np

from opentenbase_tpu import types as t
from opentenbase_tpu.plan import logical as L
from opentenbase_tpu.plan import texpr as E
from opentenbase_tpu.catalog.distribution import DistStrategy
from opentenbase_tpu.plan.distribute import Placement, RemoteSource
from opentenbase_tpu.storage.column import Column
from opentenbase_tpu.storage.table import ColumnBatch


def _registry() -> dict:
    out = {}
    for mod in (L, E):
        for name in dir(mod):
            cls = getattr(mod, name)
            if isinstance(cls, type) and dataclasses.is_dataclass(cls):
                out[name] = cls
    out["RemoteSource"] = RemoteSource
    out["Placement"] = Placement  # a redistribute's target
    return out


_REGISTRY = _registry()


def plan_to_jsonable(x):
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        cls = type(x).__name__
        fields = {
            f.name: plan_to_jsonable(getattr(x, f.name))
            for f in dataclasses.fields(x)
        }
        if isinstance(x, t.SqlType):
            return {"$ty": [x.id.value, x.precision, x.scale]}
        return {"$n": cls, "f": fields}
    if isinstance(x, tuple):
        return {"$tu": [plan_to_jsonable(v) for v in x]}
    if isinstance(x, list):
        return [plan_to_jsonable(v) for v in x]
    if isinstance(x, t.TypeId):
        return {"$id": x.value}
    if isinstance(x, DistStrategy):
        return {"$dist": x.value}
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        return float(x)
    if x is None or isinstance(x, (bool, int, float, str)):
        return x
    raise TypeError(f"unserializable plan value: {type(x).__name__}")


def plan_from_jsonable(x):
    if isinstance(x, dict):
        if "$ty" in x:
            tid, prec, scale = x["$ty"]
            return t.SqlType(t.TypeId(tid), prec, scale)
        if "$id" in x:
            return t.TypeId(x["$id"])
        if "$dist" in x:
            return DistStrategy(x["$dist"])
        if "$tu" in x:
            return tuple(plan_from_jsonable(v) for v in x["$tu"])
        if "$n" in x:
            cls = _REGISTRY.get(x["$n"])
            if cls is None:
                raise ValueError(f"unknown plan node {x['$n']}")
            kwargs = {
                k: plan_from_jsonable(v) for k, v in x["f"].items()
            }
            return cls(**kwargs)
        raise ValueError(f"malformed plan json: {sorted(x)}")
    if isinstance(x, list):
        return [plan_from_jsonable(v) for v in x]
    return x


def dumps_plan(plan) -> str:
    return json.dumps(plan_to_jsonable(plan))


def loads_plan(s: str):
    return plan_from_jsonable(json.loads(s))


# ---------------------------------------------------------------------------
# Batch serde (motioned intermediate results / fragment outputs)
# ---------------------------------------------------------------------------


def batch_to_wire(batch: ColumnBatch, schema) -> dict:
    """ColumnBatch -> {"npz": b64, "cols": [...meta...]}; dictionaries
    travel by dict_id (resolved against the receiving catalog, which the
    WAL keeps in sync) rather than by value."""
    arrays = {}
    meta = []
    for (name, col), oc in zip(batch.columns.items(), schema):
        arrays[f"d{len(meta)}"] = np.asarray(col.data)
        has_v = col.validity is not None
        if has_v:
            arrays[f"v{len(meta)}"] = np.asarray(col.validity)
        meta.append({
            "name": name,
            "ty": [col.type.id.value, col.type.precision, col.type.scale],
            "valid": has_v,
            "dict_id": oc.dict_id,
        })
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return {
        "npz": base64.b64encode(buf.getvalue()).decode(),
        "cols": meta,
        "nrows": batch.nrows,
    }


def batch_from_wire(w: dict, catalog) -> ColumnBatch:
    data = base64.b64decode(w["npz"])
    cols: dict[str, Column] = {}
    with np.load(io.BytesIO(data), allow_pickle=False) as z:
        for i, m in enumerate(w["cols"]):
            ty = t.SqlType(t.TypeId(m["ty"][0]), m["ty"][1], m["ty"][2])
            d = z[f"d{i}"]
            v = z[f"v{i}"] if m["valid"] else None
            dic = (
                catalog.dictionary(m["dict_id"]) if m["dict_id"] else None
            )
            cols[m["name"]] = Column(ty, d, v, dic)
    return ColumnBatch(cols, int(w["nrows"]))


def frame_to_wire(sub: list, arrays: dict) -> dict:
    """Commit-group frame (storage/persist.py encode_commit_group) ->
    JSON-safe wire dict — the DN-shipped DML payload."""
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return {
        "sub": sub,
        "npz": base64.b64encode(buf.getvalue()).decode(),
    }


def frame_from_wire(w: dict) -> tuple[list, dict]:
    data = base64.b64decode(w["npz"])
    with np.load(io.BytesIO(data), allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    return list(w["sub"]), arrays
