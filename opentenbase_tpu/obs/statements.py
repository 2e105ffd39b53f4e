"""Per-statement resource ledger + fingerprint-keyed statement stats.

The pg_stat_statements analog, v2.  Two halves:

**ResourceLedger** — a per-statement accumulator installed on the
session thread for the duration of one top-level statement.  Layers
that already *count* resources but never *attribute* them (the GTS
client, the WAL, the wait registry, the device table cache, the
distributed executor) call :func:`current` and, when a ledger is
active, add their cost to it.  The producer never knows which
statement it is serving — attribution is positional: whatever ledger
the session thread pushed.  Nested statements (EXPLAIN ANALYZE's
inner run, matview refresh bodies) may push a child ledger and merge
it up, so the hooks always see exactly one attribution target.

**StatementStats** — the cluster-wide fingerprint-keyed table behind
the ``pg_stat_statements`` view.  Keys are *queryids*: a stable hash
of the statement's generic shape, computed by lifting literals to
``$n`` params (the serving plane's :func:`_lift_constants`) and
deparsing canonically — ``select v from t where k = 1`` and
``... k = 2`` land in one entry, the way the reference's queryid
jumbling collapses literals.  Raw-text keys (the v1 scheme) explode
one entry per literal and churn eviction under serving load.
Accumulation is fully lock-guarded (``@shared_state("_mu")``) — the
v1 dict was mutated with bare ``+=`` RMWs from concurrent sessions —
and eviction is amortized least-calls with hysteresis, never a
whole-dict sort on the execute hot path.

Per-entry latency distribution comes from an ``obs.metrics.Histogram``
(p50/p95/p99 in the view); totals, min/max and sum-of-squares are
exact.
"""

from __future__ import annotations

import hashlib
import heapq
import threading
import time
from typing import Optional

from opentenbase_tpu.analysis.racewatch import shared_state
from opentenbase_tpu.obs.metrics import Histogram

# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

#: numeric ledger fields merged 1:1 into a statement entry. Order is
#: the view's column order for the resource block.
LEDGER_FIELDS = (
    "parse_ms",
    "plan_ms",
    "queue_ms",
    "exec_ms",
    "device_ms",
    "host_ms",
    "compile_ms",
    "rows_read",
    "dn_rpc_ms",
    "frag_retries",
    "frag_failovers",
    "h2d_bytes",
    "d2h_bytes",
    "delta_tail_rows",
    "wal_bytes",
    "wal_flushes",
    "gts_rpcs",
    "gts_ms",
    # the fused path's decomposition of device_ms (obs/trace.span
    # sites in engine._try_fused_inner, executor/fused.py and
    # fused_dag.py): waiting for the fused gate, the device cache,
    # binding literals to a program, enqueueing it (compile time
    # excluded — that is compile_ms), host waits on the device, and
    # decoding the fetched arrays; merge_ms is the coordinator merge
    # (part of host_ms). The three counts are per statement.
    "gate_ms",
    "cache_ms",
    "bind_ms",
    "launch_ms",
    "device_wait_ms",
    "collect_ms",
    "merge_ms",
    "device_launches",
    "device_syncs",
    "fused_retries",
    # the mesh's motion fragments (fused_dag's ``fused.exchange`` span,
    # one a redistribute or broadcast; none on a one-device mesh, where
    # the DAG is one program): the span's own ms — a seventh part of
    # device_ms, its binds, launches and waits bill their own columns —
    # then rows that left their device, the bucket slots shipped for
    # them (slots / rows is the padding waste), those slots' bytes, and
    # the fragments
    "exchange_ms",
    "exchange_rows",
    "exchange_bytes",
    "exchange_slots",
    "exchange_fragments",
)

#: the six parts of device_ms (what is left is the fused span's own)
DEVICE_SPLIT_FIELDS = (
    "gate_ms", "cache_ms", "bind_ms", "launch_ms", "device_wait_ms",
    "collect_ms",
)
FUSED_COUNT_FIELDS = ("device_launches", "device_syncs", "fused_retries")
#: the mesh's motions: the span's own ms, then four counts
EXCHANGE_COUNT_FIELDS = (
    "exchange_rows", "exchange_bytes", "exchange_slots",
    "exchange_fragments",
)
EXCHANGE_FIELDS = ("exchange_ms",) + EXCHANGE_COUNT_FIELDS


class ResourceLedger:
    """One statement's resource bill.  Not thread-safe by design: a
    ledger belongs to the session thread that pushed it.  Producers on
    other threads (DN fragment workers) are attributed post-hoc from
    executor instrumentation instead."""

    __slots__ = LEDGER_FIELDS + (
        "wait_ms",
        "rows_returned",
        "plan_cache",
        "result_cache",
        "run_platform",
    )

    def __init__(self):
        for f in LEDGER_FIELDS:
            setattr(self, f, 0)
        # wait class -> ms (e.g. {"LWLock": 0.4, "IO": 1.2})
        self.wait_ms: dict[str, float] = {}
        self.rows_returned = 0
        self.plan_cache = ""  # "hit" | "miss" | ""
        self.result_cache = ""  # "hit" | "miss" | ""
        self.run_platform = ""  # "tpu" | "cpu" | ... | "" (host-only)

    # -- producer hooks ---------------------------------------------------
    def add_wait(self, wtype: str, ms: float) -> None:
        self.wait_ms[wtype] = self.wait_ms.get(wtype, 0.0) + ms

    def wait_total(self) -> float:
        return sum(self.wait_ms.values())

    # -- lifecycle --------------------------------------------------------
    def finalize(self, total_ms: float, phases: dict,
                 parse_share: float = 0.0) -> None:
        """Fold the session's phase accumulator into the ledger once
        the statement finishes.  ``device_ms``/``compile_ms`` are NOT
        taken from phases — the fused path adds them directly — so
        host_ms can be derived as the execute remainder: a platform
        demotion shows up as device_ms -> host_ms within one
        statement, which is the whole point."""
        self.parse_ms += parse_share + phases.get("parse", 0.0)
        self.plan_ms += phases.get("plan", 0.0)
        self.queue_ms += phases.get("queue", 0.0)
        exec_ms = phases.get("execute")
        if exec_ms is None:
            exec_ms = max(total_ms - self.plan_ms - self.queue_ms, 0.0)
        self.exec_ms += exec_ms
        self.host_ms += max(exec_ms - self.device_ms - self.compile_ms, 0.0)

    def merge(self, child: "ResourceLedger") -> None:
        """Fold a child ledger (e.g. EXPLAIN ANALYZE's instrumented
        run) into this one so nested costs aren't lost."""
        for f in LEDGER_FIELDS:
            setattr(self, f, getattr(self, f) + getattr(child, f))
        for k, v in child.wait_ms.items():
            self.add_wait(k, v)
        if child.run_platform:
            self.run_platform = child.run_platform

    def to_ctx(self) -> dict:
        """Flat JSON-able dict for the slow-query log line."""
        d = {}
        for f in LEDGER_FIELDS:
            v = getattr(self, f)
            d[f] = round(v, 3) if isinstance(v, float) else v
        d["wait_ms"] = {k: round(v, 3) for k, v in sorted(self.wait_ms.items())}
        d["rows_returned"] = self.rows_returned
        if self.plan_cache:
            d["plan_cache"] = self.plan_cache
        if self.result_cache:
            d["result_cache"] = self.result_cache
        if self.run_platform:
            d["platform"] = self.run_platform
        return d


# thread-local ledger stack: producers attribute to the innermost.
_tls = threading.local()


def current() -> Optional[ResourceLedger]:
    """The attribution target for the calling thread, or None when no
    statement is being billed here (background threads, replay)."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


class active:
    """Context manager binding ``ledger`` as the calling thread's
    attribution target for the dynamic extent of a statement."""

    __slots__ = ("ledger",)

    def __init__(self, ledger: ResourceLedger):
        self.ledger = ledger

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self.ledger)
        return self.ledger

    def __exit__(self, *exc):
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is self.ledger:
            stack.pop()
        elif stack is not None:
            try:
                stack.remove(self.ledger)
            except ValueError:
                pass
        return False


def batch_nbytes(batch) -> int:
    """Host-side byte estimate of a ColumnBatch (the d2h result-fetch
    cost of a fused run)."""
    total = 0
    for col in getattr(batch, "columns", {}).values():
        data = getattr(col, "data", None)
        total += int(getattr(data, "nbytes", 0) or 0)
        validity = getattr(col, "validity", None)
        total += int(getattr(validity, "nbytes", 0) or 0)
    return total


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------


def generic_text(stmt, raw_text: str) -> tuple[str, bool]:
    """Canonical generic form of a statement: literals lifted to
    ``$n`` and deparsed the way the serving plane's plan cache keys
    plans.  Returns (text, is_generic).  Statements the deparser
    doesn't speak (DDL won't reach here; exotic shapes might) fall
    back to the raw text, tagged with the node kind so distinct
    statement classes never alias."""
    from opentenbase_tpu.sql import ast as A

    if isinstance(stmt, A.ExecuteStmt):
        # prepared execution: the prepared name IS the shape; args are
        # the literals.
        args = ", ".join(f"${i + 1}" for i in range(len(stmt.args or ())))
        return (f"execute {stmt.name}({args})", True)
    try:
        from opentenbase_tpu.serving.plancache import _lift_constants
        from opentenbase_tpu.sql.deparse import deparse

        lifted, _consts = _lift_constants(stmt)
        return (deparse(lifted), True)
    except Exception:
        return (type(stmt).__name__ + ":" + raw_text[:200], False)


def queryid_of(text: str) -> int:
    """Stable positive int64 queryid from the generic text (the
    reference's uint64 jumble hash, minus the sign headaches)."""
    h = hashlib.blake2b(text.encode("utf-8", "replace"), digest_size=8)
    return int.from_bytes(h.digest(), "big") >> 1


# ---------------------------------------------------------------------------
# the stats table
# ---------------------------------------------------------------------------


class _StmtEntry:
    """One fingerprint's accumulated bill."""

    __slots__ = LEDGER_FIELDS + (
        "queryid",
        "query",
        "calls",
        "total_ms",
        "rows",
        "min_ms",
        "max_ms",
        "sumsq_ms",
        "wait_ms_total",
        "plan_cache_hits",
        "result_cache_hits",
        "platform",
        "hist",
    )

    def __init__(self, queryid: int, query: str):
        self.queryid = queryid
        self.query = query
        self.calls = 0
        self.total_ms = 0.0
        self.rows = 0
        self.min_ms: Optional[float] = None
        self.max_ms = 0.0
        self.sumsq_ms = 0.0
        self.wait_ms_total = 0.0
        self.plan_cache_hits = 0
        self.result_cache_hits = 0
        self.platform = ""
        self.hist = Histogram()
        for f in LEDGER_FIELDS:
            setattr(self, f, 0)


@shared_state("_mu")
class StatementStats:
    """Cluster-wide fingerprint-keyed statement table.  Every mutation
    of shared entries happens under ``_mu`` — the v1 scheme's bare
    ``setdefault`` + ``+=`` lost updates under the concentrator's
    thread pool (see tests/test_statements.py's racewatch repro)."""

    # eviction hysteresis: when the table trips the bound we evict
    # down to max - slack in one amortized pass, so a steady stream of
    # new fingerprints doesn't pay an eviction per insert.
    SLACK_FRACTION = 8

    def __init__(self, max_entries: int = 1000):
        self._mu = threading.Lock()
        self.max_entries = max(int(max_entries), 1)
        self._entries: dict[int, _StmtEntry] = {}
        # raw text -> (queryid, generic text): parsing + deparse are
        # deterministic per raw text, so repeat literals (the serving
        # plane's steady state) skip the fingerprint walk entirely.
        self._fp_cache: dict[tuple, tuple] = {}
        self.reset_at = 0.0
        self.stats = {
            "recorded": 0,
            "evictions": 0,
            "fallback_keys": 0,
            "fp_cache_hits": 0,
        }

    # -- fingerprinting ---------------------------------------------------
    def fingerprint(self, stmt, raw_text: str,
                    pos: Optional[int] = None) -> tuple[int, str]:
        """(queryid, generic text) for one statement.  ``pos`` is the
        statement's index inside a multi-statement string — kept in
        the fingerprint so per-position entries survive (a batch's
        second ``select 1`` is a different planning context than its
        first, and v1 kept them distinct too)."""
        ck = (type(stmt).__name__, raw_text, pos)
        with self._mu:
            hit = self._fp_cache.get(ck)
            if hit is not None:
                self.stats["fp_cache_hits"] += 1
                return hit
        text, generic = generic_text(stmt, raw_text)
        if pos is not None:
            text = f"{text} /* stmt #{pos} */"
        qid = queryid_of(type(stmt).__name__ + "\x00" + text)
        with self._mu:
            if not generic:
                self.stats["fallback_keys"] += 1
            if len(self._fp_cache) >= 4096:
                self._fp_cache.clear()
            self._fp_cache[ck] = (qid, text)
        return qid, text

    # -- accumulation -----------------------------------------------------
    def record(self, stmt, raw_text: str, pos: Optional[int],
               ms: float, rows: int, ledger: ResourceLedger) -> int:
        qid, text = self.fingerprint(stmt, raw_text, pos)
        with self._mu:
            e = self._entries.get(qid)
            if e is None:
                e = self._entries[qid] = _StmtEntry(qid, text)
                if len(self._entries) > self.max_entries:
                    self._evict_locked(keep=qid)
            e.calls += 1
            e.total_ms += ms
            e.rows += int(rows)
            e.min_ms = ms if e.min_ms is None else min(e.min_ms, ms)
            e.max_ms = max(e.max_ms, ms)
            e.sumsq_ms += ms * ms
            e.hist.record(ms)
            for f in LEDGER_FIELDS:
                setattr(e, f, getattr(e, f) + getattr(ledger, f))
            e.wait_ms_total += ledger.wait_total()
            if ledger.plan_cache == "hit":
                e.plan_cache_hits += 1
            if ledger.result_cache == "hit":
                e.result_cache_hits += 1
            if ledger.run_platform:
                e.platform = ledger.run_platform
            elif not e.platform and ledger.host_ms > 0:
                e.platform = "host"
            self.stats["recorded"] += 1
        return qid

    def _evict_locked(self, keep: Optional[int] = None) -> None:
        """Amortized least-calls eviction: trip only past the bound,
        then shed ``slack`` extra entries so the next trip is O(n)
        inserts away, not one.  heapq.nsmallest is O(n log k) over a
        snapshot — never the v1 full sort per overflow."""
        slack = max(self.max_entries // self.SLACK_FRACTION, 1)
        n_evict = len(self._entries) - self.max_entries + slack
        if n_evict <= 0:
            return
        victims = heapq.nsmallest(
            n_evict + (1 if keep is not None else 0),
            self._entries.items(),
            key=lambda kv: (kv[1].calls, kv[1].total_ms),
        )
        evicted = 0
        for k, _e in victims:
            if evicted >= n_evict or len(self._entries) <= 1:
                break
            if k == keep:
                continue
            del self._entries[k]
            evicted += 1
        self.stats["evictions"] += evicted

    def set_max_entries(self, n: int) -> None:
        with self._mu:
            self.max_entries = max(int(n), 1)
            if len(self._entries) > self.max_entries:
                self._evict_locked()

    def reset(self) -> None:
        with self._mu:
            self._entries.clear()
            self.reset_at = time.time()

    # -- read side --------------------------------------------------------
    def entry_count(self) -> int:
        with self._mu:
            return len(self._entries)

    def snapshot(self) -> list[_StmtEntry]:
        with self._mu:
            return list(self._entries.values())

    def top(self, n: int = 10, key: str = "total_ms") -> list[_StmtEntry]:
        """Top-n entries by an accumulated field (exporter + otb_top)."""
        snap = self.snapshot()
        snap.sort(key=lambda e: getattr(e, key, 0.0), reverse=True)
        return snap[:n]


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE footer
# ---------------------------------------------------------------------------


def _fmt_bytes(n: int) -> str:
    n = int(n)
    if n >= 1 << 20:
        return f"{n / (1 << 20):.1f} MiB"
    if n >= 1 << 10:
        return f"{n / (1 << 10):.1f} KiB"
    return f"{n} B"


def resource_footer(ledger: ResourceLedger, total_ms: float) -> list[str]:
    """The EXPLAIN ANALYZE ``Resources:`` footer — the same bill the
    statement's pg_stat_statements row accrues, itemized for one run."""
    device = float(ledger.device_ms)
    compile_ms = float(ledger.compile_ms)
    host = max(total_ms - device - compile_ms, 0.0)
    lines = [
        "Resources:",
        (f"  time: total={total_ms:.3f} ms device={device:.3f} ms"
         f" host={host:.3f} ms compile={compile_ms:.3f} ms"),
        (f"  transfer: h2d={_fmt_bytes(ledger.h2d_bytes)}"
         f" d2h={_fmt_bytes(ledger.d2h_bytes)}"
         f" delta_tail_rows={int(ledger.delta_tail_rows)}"),
        (f"  io: rows_read={int(ledger.rows_read)}"
         f" wal={_fmt_bytes(ledger.wal_bytes)}"
         f" wal_flushes={int(ledger.wal_flushes)}"),
        (f"  dist: dn_rpc={float(ledger.dn_rpc_ms):.3f} ms"
         f" retries={int(ledger.frag_retries)}"
         f" failovers={int(ledger.frag_failovers)}"
         f" gts_rpcs={int(ledger.gts_rpcs)}"
         f" gts={float(ledger.gts_ms):.3f} ms"),
    ]
    if ledger.device_launches:
        lines.append(
            "  fused: " + " ".join(
                f"{f[:-3]}={float(getattr(ledger, f)):.3f} ms"
                for f in DEVICE_SPLIT_FIELDS + ("merge_ms",)
            ) + "".join(
                f" {f}={int(getattr(ledger, f))}"
                for f in FUSED_COUNT_FIELDS
            )
        )
    if ledger.exchange_fragments:
        lines.append(
            f"  exchange: own={float(ledger.exchange_ms):.3f} ms"
            + "".join(
                f" {f.removeprefix('exchange_')}={int(getattr(ledger, f))}"
                for f in EXCHANGE_COUNT_FIELDS
            )
        )
    if ledger.wait_ms:
        waits = " ".join(
            f"{k}={v:.3f} ms" for k, v in sorted(ledger.wait_ms.items())
        )
        lines.append(f"  waits: {waits}")
    verdicts = []
    if ledger.plan_cache:
        verdicts.append(f"plan_cache={ledger.plan_cache}")
    if ledger.result_cache:
        verdicts.append(f"result_cache={ledger.result_cache}")
    if ledger.run_platform:
        verdicts.append(f"platform={ledger.run_platform}")
    if verdicts:
        lines.append("  cache: " + " ".join(verdicts))
    return lines
