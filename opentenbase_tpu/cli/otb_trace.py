"""Query-trace exporter — fetch the coordinator's recent query traces
as Chrome-trace-format JSON (load in chrome://tracing or
https://ui.perfetto.dev).

    python -m opentenbase_tpu.cli.otb_trace --cn HOST:PORT \
        [--last N] [--out trace.json] [--user U] [--password P]

The coordinator keeps a bounded in-memory ring of finished query traces
(``trace_queries = on`` traces every statement; EXPLAIN ANALYZE always
traces its own) and merges every reachable node's span ring into the
export: pid = node (cn0/dnN/gtm0), spans joined by trace_id, so one
statement's true cross-node critical path renders as separate process
tracks. This tool calls the ``pg_export_traces(N)`` admin function over
the wire and writes the document to ``--out``.

Exit code 0 on success (even when the ring is empty — an empty trace is
a valid trace), 1 when the coordinator is unreachable.

    python -m opentenbase_tpu.cli.otb_trace --xplane DIR [--json]

reads a JAX profiler trace instead (the directory given to
``jax.profiler.start_trace``, or one ``.xplane.pb``) and prints where
each statement class spent its time: the ``otb:`` spans every timed
site writes into the profiler's trace, device time by program and by
``otb/`` scope (direct + what the ops the compiler made inherit from
the op that reads them), the costliest ops with their ns an element,
GB/s and each operand's memory space (``@S(1)`` on chip, ``@hbm``),
idle gaps by cause (obs/profile.py). No coordinator is contacted.
"""

from __future__ import annotations

import argparse
import json
import sys


def fetch_traces(
    host: str, port: int, last: int, user=None, password=None
) -> dict:
    from opentenbase_tpu.net.client import ClientSession

    cs = ClientSession(
        host, port, timeout=30, user=user, password=password,
        connect_retries=0,
    )
    try:
        rows = cs.query(f"select pg_export_traces({int(last)})")
    finally:
        cs.close()
    return json.loads(rows[0][0])


def reduce_xplane(where: str, as_json: bool) -> int:
    import os

    from opentenbase_tpu.obs import profile

    try:
        path = where if os.path.isfile(where) else profile.find_xplane(where)
        report = profile.reduce(profile.load(path))
    except (OSError, ValueError) as e:
        print(f"otb_trace: {where}: {e}", file=sys.stderr)
        return 1
    print(json.dumps(report) if as_json else profile.render(report))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="otb_trace",
        description="Export recent query traces as Chrome trace JSON",
    )
    ap.add_argument(
        "--cn", metavar="HOST:PORT", help="coordinator wire endpoint",
    )
    ap.add_argument(
        "--xplane", metavar="DIR",
        help="reduce a JAX profiler trace (its directory or .xplane.pb) "
        "instead of contacting a coordinator",
    )
    ap.add_argument(
        "--json", action="store_true",
        help="with --xplane: print the reduction as JSON",
    )
    ap.add_argument(
        "--last", type=int, default=20,
        help="number of most-recent traces to export (default 20)",
    )
    ap.add_argument(
        "--out", default="trace.json",
        help="output file (default trace.json)",
    )
    ap.add_argument("--user", default=None)
    ap.add_argument("--password", default=None)
    args = ap.parse_args(argv)
    if args.xplane:
        return reduce_xplane(args.xplane, args.json)
    if not args.cn:
        ap.error("one of --cn or --xplane is required")

    host, _, port = args.cn.rpartition(":")
    try:
        doc = fetch_traces(
            host or "127.0.0.1", int(port), args.last,
            user=args.user, password=args.password,
        )
    except Exception as e:
        print(f"otb_trace: {args.cn}: {e}", file=sys.stderr)
        return 1
    with open(args.out, "w") as f:
        json.dump(doc, f)
    events = doc.get("traceEvents", [])
    spans = [e for e in events if e.get("ph") == "X"]
    nodes = {e["pid"] for e in spans}
    traces = {
        (e.get("args") or {}).get("trace_id") for e in spans
    } - {None}
    print(
        f"wrote {args.out}: {len(spans)} spans from {len(traces)} "
        f"traced statements across {len(nodes)} nodes"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
