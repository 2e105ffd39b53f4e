"""Per-layer metrics, each read by the declarative rule in its own file
``layer_metrics/<name>.json``. A reader that finds nothing to read
returns None and the metric is left out of the line.

Reader kinds (``reader.kind``):
  statements_delta   sum of pg_stat_statements column deltas over the
                     window / delta of calls
  client_minus_statements
                     client mean latency - delta total_ms / delta calls
  statements_minus_trace
                     delta of ``numerator`` / delta calls - traced device
                     busy ms per traced statement
  trace              ``value``: busy_ms_per_stmt | idle_pct
  roofline           bytes ``work.statement_bytes`` says the traced
                     statements must read / peak ``peak`` / traced time of
                     the programs whose names match ``programs``
  setup              ``value``: a number of the run's ``setup_split`` line
"""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read_metric(name: str) -> dict:
    with open(os.path.join(HERE, "layer_metrics", f"{name}.json")) as f:
        return json.load(f)


def _per_call(ctx: dict, columns: list):
    d = ctx["delta"]
    if not d or d.get("calls", 0) <= 0:
        return None
    return sum(d[c] for c in columns) / d["calls"]


def _busy_ms_per_stmt(ctx: dict):
    tr = ctx.get("trace")
    if not tr or not tr["statements_traced"]:
        return None
    return tr["busy_s"] * 1000.0 / tr["statements_traced"]


def evaluate(spec: dict, ctx: dict):
    r = spec["reader"]
    kind = r["kind"]
    if kind == "statements_delta":
        return _per_call(ctx, r["numerator"])
    if kind == "client_minus_statements":
        server = _per_call(ctx, ["total_ms"])
        if server is None or not ctx["client_ms"]:
            return None
        return sum(ctx["client_ms"]) / len(ctx["client_ms"]) - server
    if kind == "statements_minus_trace":
        host = _per_call(ctx, r["numerator"])
        busy = _busy_ms_per_stmt(ctx)
        if host is None or busy is None:
            return None
        return host - busy
    if kind == "trace":
        tr = ctx.get("trace")
        if not tr:
            return None
        if r["value"] == "busy_ms_per_stmt":
            return _busy_ms_per_stmt(ctx)
        if r["value"] == "idle_pct":
            return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
        raise ValueError(f"unknown trace value {r['value']!r}")
    if kind == "setup":
        return ctx["setup"].get(r["value"])
    if kind == "roofline":
        tr = ctx.get("trace")
        if not tr or not ctx["work_bytes"]:
            return None
        least_s = ctx["work_bytes"] / ctx["peaks"][r["peak"]]
        pat = re.compile(r["programs"])
        took = sum(s for n, s in tr["programs"].items() if pat.search(n))
        if took <= 0:
            return None
        return 100.0 * least_s / took
    raise ValueError(f"unknown reader kind {kind!r}")
