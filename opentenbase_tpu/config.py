"""GUC registry + configuration file — the guc.c machinery.

The reference defines every setting with a type, default, and validator
in src/backend/utils/misc/guc.c (14k LoC of tables) and reads
postgresql.conf at startup. Here the registry is a declarative dict;
``SET`` validates against it (unknown names error unless namespaced with
a dot, PG's custom-variable rule), and a cluster reads
``<data_dir>/opentenbase.conf`` (``key = value`` lines, ``#`` comments)
into its session defaults.
"""

from __future__ import annotations

import os
from typing import Optional


class GucError(ValueError):
    pass


def _bool(v):
    if isinstance(v, bool):
        return v
    s = str(v).lower()
    if s in ("true", "on", "yes", "1"):
        return True
    if s in ("false", "off", "no", "0"):
        return False
    raise GucError(f"invalid boolean: {v!r}")


def _int(v):
    if isinstance(v, bool):
        raise GucError(f"invalid integer: {v!r}")
    try:
        return int(v)
    except (TypeError, ValueError):
        raise GucError(f"invalid integer: {v!r}") from None


def _str(v):
    return str(v)


_DURATION_UNITS = {"us": 0.001, "ms": 1, "s": 1000, "min": 60000, "h": 3600000}


def _duration(v):
    """int milliseconds, or a PG duration string ('150ms', '2s')."""
    if isinstance(v, bool):
        raise GucError(f"invalid duration: {v!r}")
    if isinstance(v, (int, float)):
        return int(v)
    s = str(v).strip()
    for unit, mult in sorted(
        _DURATION_UNITS.items(), key=lambda kv: -len(kv[0])
    ):
        if s.endswith(unit):
            num = s[: -len(unit)].strip()
            try:
                return int(float(num) * mult)
            except ValueError:
                break
    try:
        return int(s)
    except ValueError:
        raise GucError(f"invalid duration: {v!r}") from None


def _enum(*allowed):
    def f(v):
        # SET x = on/off/true/false arrives as a python bool (the SQL
        # boolean keywords); PG's enum GUCs accept those spellings when
        # the enum has on/off rungs (guc.c config_enum_lookup_by_name)
        if isinstance(v, bool):
            v = "on" if v else "off"
        s = str(v).lower()
        if s not in allowed:
            raise GucError(f"must be one of {allowed}, got {v!r}")
        return s

    return f


# name -> (validator, default). Defaults mirror the engine's historical
# behavior; None means "engine decides" (e.g. backend-dependent).
GUCS: dict = {
    "enable_fused_execution": (_bool, True),
    # wire encryption (be-secure.c): the coordinator front end wraps
    # every accepted socket in TLS when ssl=on; plaintext clients are
    # rejected at the handshake
    "ssl": (_bool, False),
    "ssl_cert_file": (_str, ""),
    "ssl_key_file": (_str, ""),
    "enable_pallas_scan": (_bool, None),
    # device join formulation (executor/fused_dag.py + the host
    # executor via OTB_JOIN_MODE): 'auto' picks fold > radix >
    # sort-merge by planner cardinality estimates; forcing a mode is
    # for tests, EXPLAIN smoke checks, and perf triage
    "join_mode": (_enum("auto", "radix", "sortmerge"), "auto"),
    # spill-aware batch planner (plan/batchplan.py): HBM budget in
    # bytes every data-dependent device allocation (radix tables,
    # exchange buffers, probe windows) is sized against; 0 = use the
    # per-op constants of plan/batchplan.py
    "device_memory_limit": (_int, 0),
    "enable_fast_query_shipping": (_bool, True),  # otb_lint: ignore[guc-unread] -- reserved: the FQS fast-path (pgxc_FQS_planner) is not built yet; accepted so conf files written for the reference load unchanged
    # within-fragment scan workers on DN processes (execParallel.c's
    # max_parallel_workers_per_gather analog)
    "dn_parallel_workers": (_int, 4),
    "lock_timeout": (_duration, 0),
    "deadlock_timeout": (_duration, 1000),
    "statement_timeout": (_duration, 0),
    "work_mem": (_int, 65536),
    # workload management (wlm/): session override of the role->group
    # binding; '' = use ALTER ROLE ... RESOURCE GROUP / default_group
    "resource_group": (_str, ""),
    # cap on the admission-queue wait when statement_timeout is 0
    # (otherwise a parked statement waits unbounded); 0 = no cap
    "wlm_queue_timeout": (_duration, 0),
    "search_path": (_str, "public"),  # otb_lint: ignore[guc-unread] -- the engine has one flat namespace (no CREATE SCHEMA); accepted because every PG client driver SETs it at connect
    "session_authorization": (_str, None),
    "role": (_str, None),
    "application_name": (_str, ""),
    "client_min_messages": (  # otb_lint: ignore[guc-unread] -- no NOTICE/WARNING wire channel exists yet (frames carry rows or one error); becomes real when the pgwire front end grows NoticeResponse
        _enum("debug", "log", "notice", "warning", "error"), "notice",
    ),
    # server logging (obs/log.py, the elog.c pipeline). Severity order is
    # debug < log < notice < warning < error (obs.log.LEVELS); records
    # below log_min_messages never enter the ring or the file sink.
    "log_min_messages": (
        _enum("debug", "log", "notice", "warning", "error"), "log",
    ),
    # 'ring' keeps the bounded in-memory ring only; 'file' additionally
    # appends formatted lines under <data_dir>/<log_directory>/otb.log
    "log_destination": (_enum("ring", "file"), "ring"),
    "log_directory": (_str, "log"),
    # per-node OpenMetrics exporter (obs/exporter.py): 0 = no listener
    # socket at all (off, the default); >0 = serve GET /metrics there
    "metrics_port": (_int, 0),
    # auto_explain (the contrib module): statements running at least
    # this many ms get their instrumented plan logged at level 'log';
    # -1 = off (PG's auto_explain.log_min_duration contract), 0 = all
    "auto_explain_min_duration_ms": (_duration, -1),
    # pg_stat_statements v2 (obs/statements.py): fingerprint-keyed
    # per-statement resource ledger. enable_stat_statements=off skips
    # accumulation entirely (results are byte-identical either way);
    # stat_statements_max bounds the entry table (CLUSTER-scoped,
    # amortized least-calls eviction — pg_stat_statements.max analog)
    "enable_stat_statements": (_bool, True),
    "stat_statements_max": (_int, 1000),
    # one structured JSON slow-query log line (full resource ledger +
    # trace_id) for statements running at least this many ms; -1 = off,
    # 0 = every statement (PG's log_min_duration_statement contract)
    "log_min_duration_statement": (_duration, -1),
    # serving plane (serving/plancache.py) — these four are CLUSTER-
    # scoped: SET in any live session applies to every session
    # immediately and flushes the affected cache (engine._x_setstmt
    # routes them through ServingPlane.set_guc). enable_plan_cache
    # keys the full planned artifact on the canonical deparse
    # fingerprint with constants parameterized out; a hit skips
    # parse->analyze->distribute->cost entirely.
    "enable_plan_cache": (_bool, True),
    "plan_cache_size": (_int, 512),       # entries (constant variants)
    # result cache: whole result sets keyed by (fingerprint, per-table
    # committed-write versions) — off by default: it is snapshot-
    # correct but makes repeated-query benchmarks measure the cache,
    # so turning the serving plane on is an explicit act
    "enable_result_cache": (_bool, False),
    "result_cache_size": (_int, 64 << 20),  # bytes, LRU-evicted
    # matview serving path (matview/rewrite.py): a SELECT whose
    # canonical text exactly matches a FRESH materialized view's
    # defining query is answered from the matview instead of the fact
    # tables; staleness is checked against per-table write versions
    "enable_matview_rewrite": (_bool, True),
    # span tracing (obs/trace.py + obs/tracectx.py): off = zero-cost
    # (no span allocation anywhere on the statement path, on any node —
    # the wire carries no ``_trace`` header and remote span rings stay
    # untouched); EXPLAIN ANALYZE always traces its one statement
    # regardless
    "trace_queries": (_bool, False),
    # device-platform watchdog (executor/fused.py note_run_platform):
    # the platform every fused run is EXPECTED to execute on — an
    # explicit statement of intent, never inferred ('' = no
    # expectation). A run on any other platform bumps
    # otb_platform_demotions_total, elogs a warning the first time,
    # and stamps pg_cluster_health.device_platform — the r04/r05
    # silent-CPU class made continuously observable.
    "expected_device_platform": (_enum("", "tpu", "cpu", "gpu"), ""),
    # fault injection (fault/): pg_fault_inject() refuses unless the
    # session turned this on — an accidental arm in production SQL must
    # be a two-step mistake. Off adds nothing to any hot path: every
    # FAULT site is a single empty-dict lookup.
    "fault_injection": (_bool, False),
    # self-healing reads (executor/dist.py): extra attempts for a
    # failed/timed-out remote READ fragment before failing over to the
    # coordinator's own caught-up copy; writes never blind-retry — they
    # abort with a retryable SQLSTATE (40001/08006) instead
    "fragment_retries": (_int, 2),
    "fragment_retry_backoff_ms": (_duration, 25),
    # GTM client failover (gtm/client.py NativeGTS): 'host:port' of the
    # standby's wire frontend; on primary loss the client reconnects
    # there instead of erroring the session
    "gtm_standby_addr": (_str, ""),
    # self-healing HA (ha.py HAMonitor): total detection budget for
    # declaring the primary dead — the monitor probes every
    # failover_detect_ms / failover_beats and promotes after
    # failover_beats CONSECUTIVE missed beats, so a single dropped
    # probe never triggers a failover
    "failover_detect_ms": (_duration, 3000),
    "failover_beats": (_int, 3),
    # serving lease (ha.ServingLease): the CN must prove DN-quorum
    # contact within this window before serving ANY statement —
    # including plan/result-cache hits, which issue no DN RPC and so
    # never trip the fencing epochs on their own. 0 (default) = leases
    # off, the pre-lease behavior. When on, load_conf refuses configs
    # whose detection budget does not exceed TTL + skew: the
    # no-dual-primary construction (failover waits out the lease) only
    # holds when a partitioned primary's lease must lapse BEFORE the
    # monitor can promote a successor.
    "lease_ttl_ms": (_duration, 0),
    "lease_skew_ms": (_duration, 100),
    # failed-failover retry ladder (ha.HAMonitor): exponential backoff
    # cap for re-driving failover() when no candidate promoted
    "failover_retry_max_ms": (_duration, 10000),
    # flap hysteresis (ha.HATopology.note_heal): a primary that healed
    # after being declared dead cannot be deposed again inside this
    # window — bounds promotions under a flapping link
    "failover_cooldown_ms": (_duration, 2000),
    # commit durability ladder (the full PG synchronous_commit shape,
    # ROADMAP item 4b): 'off' = ack once the commit record is written +
    # OS-flushed, no fsync wait (an OS crash may lose the acked tail —
    # never duplicates or reorders it; a process crash loses nothing);
    # 'local' = ack after the group fsync (one leader fsync covers
    # every concurrent committer); 'remote_write' = additionally wait
    # until a QUORUM of attached standbys acked receipt of the commit's
    # WAL position over the pipelined replication ack channel (no
    # per-commit RPC — the walsender's in-memory ack table answers);
    # 'on' = remote_apply: every reachable attached DN standby has
    # APPLIED the position (the HA failover zero-lost-writes guarantee)
    # default 'local', NOT 'off': before the ladder existed every commit
    # record fsynced, so the conf-file default must keep that durability
    # (an unconfigured deployment silently losing acked commits on an OS
    # crash would be a downgrade, not a default)
    "synchronous_commit": (
        _enum("off", "local", "remote_write", "on"), "local",
    ),
    # group commit (ROADMAP item 4a): concurrent committers share one
    # WAL fsync (leader election in storage/persist.WAL.flush_to) and
    # one batched GTS grant (engine.GtsCommitBatcher). Off = the seed's
    # fsync-per-commit + RPC-per-commit path (what tests/test_write_path.py
    # compares against, and an operator escape hatch).
    "enable_group_commit": (_bool, True),
    # PG's commit_delay/commit_siblings: the flush leader naps
    # commit_delay_us before its fsync — only when at least
    # commit_siblings OTHER sessions are mid-commit — so their records
    # join the batch. 0 (default) = never nap.
    "commit_delay_us": (_int, 0),
    "commit_siblings": (_int, 5),
    # vectorized ingest (ROADMAP item 4c): multi-row INSERT ... VALUES
    # of plain literals (and PREPAREd-insert EXECUTEs) bypass the
    # general parse->analyze->plan pipeline and build per-shard
    # columnar delta batches directly — the reference's multi-row
    # INSERT -> COPY rewrite ("dozens of times" faster, v2.5.0 note).
    # Off = the seed row-at-a-time path (differential baseline).
    "enable_bulk_insert_rewrite": (_bool, True),
    # background delta compaction (storage/compaction.py): fold pending
    # ingest delta batches into base arrays every this-many ms. Scans
    # never fold (see enable_delta_scan) — 0 leaves folding to VACUUM,
    # the MAX_DELTAS write-side backpressure, and explicit compaction.
    "delta_compaction_naptime_ms": (_duration, 0),
    # scannable delta plane (ISSUE-15): scans iterate base + pending
    # delta batches without absorbing, on both executors — reads never
    # mutate storage, compaction is a background amortizer. Off
    # restores the legacy fold-on-read read path (host scans fold
    # first; the device cache compacts before refresh and keeps the
    # flat >8-entry MVCC full-plane cutoff) — what
    # tests/test_delta_scan.py compares against on the same binary, and
    # an operator escape hatch.
    "enable_delta_scan": (_bool, True),
    # Elastic rebalance copy throttle (bytes/s of shard-move traffic a
    # background ADD/REMOVE NODE may stream; <= 0 = unthrottled). Read
    # by rebalance/service.py between copy chunks so a rebalance never
    # starves foreground traffic of ingest bandwidth.
    "rebalance_rate_limit": (_int, 64 << 20),
    # Multi-coordinator serving plane (coord/): read routing for
    # read-only statements outside a transaction. 'primary' = the
    # classic path (every read runs on the CN that parsed it);
    # 'replica' = eligible SELECTs are served from hot standbys whose
    # staleness — proved by the walsender's per-peer applied-ack table,
    # not by an RPC — is within max_staleness AND whose applied
    # position covers the session's own last commit (read-your-writes)
    "read_routing": (_enum("primary", "replica"), "primary"),
    # staleness budget for replica-routed reads: a standby qualifies
    # only if it was provably caught up with the primary's WAL within
    # this window (hot_standby's max_standby_streaming_delay lineage,
    # inverted into an eligibility bound the ROUTER enforces)
    "max_staleness": (_duration, 500),
    # what a replica-routed read does when NO standby is in bound:
    # 'primary' serves it locally (counting stale_read_refused);
    # 'wait' parks until a standby proves freshness, up to
    # replica_read_wait_ms, then falls back to the primary
    "replica_read_fallback": (_enum("primary", "wait"), "primary"),
    "replica_read_wait_ms": (_duration, 2000),
    "autovacuum": (_bool, False),
    "autovacuum_naptime_s": (_int, 60),
    "autovacuum_scale_factor_pct": (_int, 20),
}


def validate(name: str, value):
    """Validated value for SET; unknown names must be namespaced
    ('ext.knob'), PG's custom-variable-class rule."""
    entry = GUCS.get(name)
    if entry is None:
        if "." not in name:
            raise GucError(f'unrecognized configuration parameter "{name}"')
        return value
    fn, _default = entry
    return fn(value)


def defaults() -> dict:
    return {
        name: default
        for name, (_fn, default) in GUCS.items()
        if default is not None
    }


def load_conf(data_dir: Optional[str]) -> dict:
    """Read <data_dir>/opentenbase.conf (the postgresql.conf analog):
    ``name = value`` per line, '#' comments, validated on load."""
    out: dict = {}
    if not data_dir:
        return out
    path = os.path.join(data_dir, "opentenbase.conf")
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for lineno, raw in enumerate(f, 1):
            line = raw.split("#", 1)[0].strip()
            if not line:
                continue
            if "=" not in line:
                raise GucError(
                    f"{path}:{lineno}: expected name = value, got {raw!r}"
                )
            name, _, value = line.partition("=")
            name = name.strip()
            value = value.strip().strip("'\"")
            out[name] = validate(name, value)
    _check_lease_budget(out, path)
    return out


def _check_lease_budget(conf: dict, path: str) -> None:
    """Cross-GUC invariant (checked only when leases are on): the
    failure-detection budget must EXCEED lease TTL + skew. Failover
    waits out the old lease before flipping routing; if detection could
    finish while a partitioned primary's lease is still valid, a window
    opens where both generations serve — the dual-primary the lease
    exists to make impossible. Misconfiguration is refused at load, not
    discovered during a partition."""
    ttl = int(conf.get("lease_ttl_ms", GUCS["lease_ttl_ms"][1]) or 0)
    if ttl <= 0:
        return
    detect = int(
        conf.get("failover_detect_ms", GUCS["failover_detect_ms"][1])
    )
    beats = int(conf.get("failover_beats", GUCS["failover_beats"][1]))
    skew = int(conf.get("lease_skew_ms", GUCS["lease_skew_ms"][1]))
    if detect * beats <= ttl + skew:
        raise GucError(
            f"{path}: failover_detect_ms ({detect}) x failover_beats "
            f"({beats}) must exceed lease_ttl_ms ({ttl}) + "
            f"lease_skew_ms ({skew}) — a primary's lease must lapse "
            f"before a successor can be promoted"
        )
