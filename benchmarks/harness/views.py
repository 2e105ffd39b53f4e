"""What the engine's own views say about who answered. The checks are
``chip_smoke.py``'s ``check_views`` (copied, not imported), applied once
to the whole window instead of after every statement: reading views
inside the window would be traffic of its own."""

from __future__ import annotations

STATEMENT_COLUMNS = (
    "calls", "total_ms", "parse_ms", "plan_ms", "queue_ms", "device_ms",
    "host_ms", "compile_ms", "h2d_bytes",
)


class HostAnswered(Exception):
    """The device path did not produce the window's rows."""


def read(sql) -> dict:
    fused: dict = {}
    for ev, detail in sql("select event, detail from pg_stat_fused").rows:
        fused.setdefault(ev, []).append(detail)
    pallas = [
        tuple(r) for r in
        sql("select program, state from pg_stat_pallas").rows
    ]
    health = sql(
        "select node_name, role, device_platform from pg_cluster_health"
    ).rows
    cols = ", ".join(STATEMENT_COLUMNS)
    stmts = {
        r[0]: dict(zip(STATEMENT_COLUMNS, (float(x) for x in r[1:])))
        for r in sql(f"select query, {cols} from pg_stat_statements").rows
    }
    return {"fused": fused, "pallas": pallas, "health": health,
            "statements": stmts}


def last(fused: dict, name: str, default=None):
    return fused.get(name, [default])[-1]


def statement_delta(before: dict, after: dict) -> dict:
    """Summed pg_stat_statements columns of the statement classes that
    ran between two reads, the views' own reads left out."""
    total = dict.fromkeys(STATEMENT_COLUMNS, 0.0)
    for query, row in after["statements"].items():
        if " pg_stat_" in query or " pg_cluster_" in query:
            continue
        base = before["statements"].get(query, {})
        for c in STATEMENT_COLUMNS:
            total[c] += row[c] - base.get(c, 0.0)
    return total


def check_window(before: dict, after: dict, timed: int, platform: str) -> None:
    """Raise HostAnswered unless every timed statement was answered by a
    device program on ``platform``."""
    f = after["fused"]
    if f.get("demoted"):
        raise HostAnswered(f"fused->host demotion(s): {f['demoted']}")
    if int(last(f, "platform_demotions", "0")) != 0:
        raise HostAnswered(
            f"platform_demotions = {last(f, 'platform_demotions')}"
        )
    bad = [r for r in after["pallas"] if r[1] != "compiled"]
    if bad:
        raise HostAnswered(f"pallas fallback(s): {bad}")
    # the view reads are bare scans the DAG runner declines by design
    odd = [u for u in f.get("unsupported", []) if u != "trivial scan"]
    if odd:
        raise HostAnswered(f"unsupported on device: {odd}")
    n0 = int(last(before["fused"], "fused_statements", "0"))
    n1 = int(last(f, "fused_statements", "0"))
    if n1 - n0 != timed:
        raise HostAnswered(
            f"fused_statements moved {n0} -> {n1} over {timed} timed "
            "statements: the host executor answered some"
        )
    if last(f, "last_run_platform") != platform:
        raise HostAnswered(
            f"last_run_platform = {last(f, 'last_run_platform')!r}, "
            f"want {platform!r}"
        )
    cn = [h for h in after["health"] if h[1].startswith("coordinator")]
    if not cn or cn[0][2] != platform:
        raise HostAnswered(
            f"pg_cluster_health device_platform = "
            f"{cn[0][2] if cn else None!r}, want {platform!r}"
        )
