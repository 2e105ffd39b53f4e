"""Per-node OpenMetrics/Prometheus exporter — scrape without a SQL session.

The reference fleet is scraped through postgres_exporter; here every node
process can open its own tiny HTTP listener (``metrics_port`` GUC, off by
default) serving ``GET /metrics`` in the Prometheus text exposition
format, no dependencies: the existing registries render as

- phase histograms  -> ``otb_phase_duration_ms`` histogram (cumulative
  ``_bucket{le=...}`` counts + ``_sum``/``_count``), one series per phase;
- wait events       -> ``otb_wait_events_total`` / ``otb_wait_event_ms_total``;
- WLM / fault / 2PC / DML / matview counters -> labeled ``_total`` counters;
- gauges            -> replication lag per connected standby (LSN delta),
  DN channel-pool occupancy, DN heartbeat age/liveness, live sessions,
  current WAL position.

A conformance test (tests/test_telemetry.py) asserts every emitted line
parses under the exposition grammar and that counters are monotone
across scrapes — the contract a real Prometheus relies on.
"""

from __future__ import annotations

import socket
import threading
import time
from typing import Callable, Optional

from opentenbase_tpu.net.protocol import shutdown_and_close
from opentenbase_tpu.obs import statements as _stmtobs


def _esc(v) -> str:
    return (
        str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")
    )


def _line(name: str, labels: dict, value) -> str:
    if labels:
        lbl = ",".join(
            f'{k}="{_esc(v)}"' for k, v in sorted(labels.items())
        )
        return f"{name}{{{lbl}}} {value}"
    return f"{name} {value}"


def _head(out: list, name: str, kind: str, help_: str) -> None:
    out.append(f"# HELP {name} {help_}")
    out.append(f"# TYPE {name} {kind}")


def render_cluster_metrics(cluster) -> str:
    """The coordinator-side exposition document. Reads the same
    registries the pg_stat_* views read — one source of truth."""
    out: list[str] = []

    # phase histograms (obs/metrics.py) as native prometheus histograms
    with cluster.metrics._mu:
        hists = sorted(
            (k, v) for k, v in cluster.metrics.histograms.items()
            if k.startswith("phase.")
        )
    if hists:
        _head(out, "otb_phase_duration_ms", "histogram",
              "Per-phase statement latency (parse/plan/queue/execute/...)")
        for name, h in hists:
            phase = name[len("phase."):]
            with h._mu:
                counts = list(h.counts)
                total = h.total
                count = h.count
            cum = 0
            for bound, n in zip(h.bounds, counts):
                cum += n
                out.append(_line(
                    "otb_phase_duration_ms_bucket",
                    {"phase": phase, "le": repr(float(bound))}, cum,
                ))
            out.append(_line(
                "otb_phase_duration_ms_bucket",
                {"phase": phase, "le": "+Inf"}, count,
            ))
            out.append(_line(
                "otb_phase_duration_ms_sum", {"phase": phase},
                round(total, 6),
            ))
            out.append(_line(
                "otb_phase_duration_ms_count", {"phase": phase}, count,
            ))

    # wait events (obs/waits.py + fault-injection windows)
    from opentenbase_tpu.engine import _sv_wait_events

    rows = _sv_wait_events(cluster)  # (type, event, count, ms, reset)
    if rows:
        _head(out, "otb_wait_events_total", "counter",
              "Completed waits by (type, event)")
        for wtype, event, count, _ms, _reset in rows:
            out.append(_line(
                "otb_wait_events_total",
                {"type": wtype, "event": event}, count,
            ))
        _head(out, "otb_wait_event_ms_total", "counter",
              "Milliseconds spent waiting by (type, event)")
        for wtype, event, _count, ms, _reset in rows:
            out.append(_line(
                "otb_wait_event_ms_total",
                {"type": wtype, "event": event}, ms,
            ))

    # WLM per-group counters + live gauges
    groups = cluster.wlm.stat_rows()
    if groups:
        _head(out, "otb_wlm_statements_total", "counter",
              "WLM admission outcomes per resource group")
        for g in groups:
            name = g[0]
            for stat, val in zip(
                ("admitted", "queued", "shed", "timed_out"), g[7:11]
            ):
                out.append(_line(
                    "otb_wlm_statements_total",
                    {"group": name, "outcome": stat}, val,
                ))
        _head(out, "otb_wlm_running", "gauge",
              "Statements currently admitted per resource group")
        for g in groups:
            out.append(_line("otb_wlm_running", {"group": g[0]}, g[5]))

    # fault-injection counters (chaos evidence; process-local half)
    from opentenbase_tpu import fault as _fault

    frows = _fault.stats()
    if frows:
        _head(out, "otb_fault_hits_total", "counter",
              "Armed-failpoint evaluations per site")
        for site, _a, _t, _arms, hits, _fired, _armed in frows:
            out.append(_line("otb_fault_hits_total", {"site": site}, hits))
        _head(out, "otb_fault_fired_total", "counter",
              "Failpoint firings per site")
        for site, _a, _t, _arms, _hits, fired, _armed in frows:
            out.append(_line(
                "otb_fault_fired_total", {"site": site}, fired,
            ))

    # 2PC resolver + shipped-DML counters
    with cluster._2pc_stats_mu:
        tp = sorted(cluster.twophase_stats.items())
    _head(out, "otb_twophase_total", "counter",
          "In-doubt 2PC resolver counters")
    for k, v in tp:
        out.append(_line("otb_twophase_total", {"stat": k}, int(v)))
    with cluster._dml_stats_mu:
        dml = sorted(cluster.dml_stats.items())
    _head(out, "otb_dml_commits_total", "counter",
          "Multi-node commits by write-set delivery mode")
    for k, v in dml:
        out.append(_line("otb_dml_commits_total", {"mode": k}, int(v)))

    # elastic-cluster rebalancer (rebalance/): move/row counters plus a
    # liveness gauge — an operator watches ADD NODE progress from a
    # scrape, not a SQL session
    rb = getattr(cluster, "rebalance", None)
    if rb is not None:
        _head(out, "otb_rebalance_moves_total", "counter",
              "Shard-group move waves completed by the rebalancer")
        out.append(_line(
            "otb_rebalance_moves_total", {},
            int(rb.counters.get("moves_total", 0)),
        ))
        _head(out, "otb_rebalance_rows_copied_total", "counter",
              "Rows copied between nodes by the rebalancer")
        out.append(_line(
            "otb_rebalance_rows_copied_total", {},
            int(rb.counters.get("rows_copied_total", 0)),
        ))
        _head(out, "otb_rebalance_active", "gauge",
              "1 while a rebalance operation is in flight")
        out.append(_line(
            "otb_rebalance_active", {}, 1 if rb.active else 0,
        ))

    # fragment self-healing counters (cluster-lifetime accumulators:
    # per-session counts die with the session, and a counter that drops
    # on disconnect would read as a reset to Prometheus)
    with cluster._dml_stats_mu:
        heal = dict(cluster.frag_heal_stats)
    _head(out, "otb_fragment_retries_total", "counter",
          "Remote fragment retry attempts")
    out.append(_line(
        "otb_fragment_retries_total", {}, int(heal.get("retries", 0)),
    ))
    _head(out, "otb_fragment_failovers_total", "counter",
          "Remote fragments failed over to coordinator stores")
    out.append(_line(
        "otb_fragment_failovers_total", {},
        int(heal.get("failovers", 0)),
    ))

    # self-healing HA: the fencing epoch + failover counters — a
    # promotion is visible on the very next scrape (the generation
    # gauge steps, the promotions counter bumps on the promoted node)
    _head(out, "otb_node_generation", "gauge",
          "Fencing generation of this node's timeline")
    out.append(_line(
        "otb_node_generation", {},
        int(getattr(cluster, "node_generation", 0)),
    ))
    ha = dict(getattr(cluster, "ha_stats", None) or {})
    _head(out, "otb_promotions_total", "counter",
          "Standby promotions performed by this node")
    out.append(_line(
        "otb_promotions_total", {}, int(ha.get("promotions", 0)),
    ))
    _head(out, "otb_fenced_refusals_total", "counter",
          "Statements refused after this node was fenced out")
    out.append(_line(
        "otb_fenced_refusals_total", {},
        int(ha.get("fenced_refusals", 0)),
    ))
    # partition tolerance (ISSUE-19): serving-lease + partition-chaos
    # counters — a gray-failure run is reconstructable from a scrape
    _head(out, "otb_lease_expirations_total", "counter",
          "Serving-lease valid->expired transitions on this node")
    out.append(_line(
        "otb_lease_expirations_total", {},
        int(ha.get("lease_expirations", 0)),
    ))
    _head(out, "otb_self_demotions_total", "counter",
          "Times this node self-demoted (lease lapse or fenced grant) "
          "before serving a statement")
    out.append(_line(
        "otb_self_demotions_total", {},
        int(ha.get("self_demotions", 0)),
    ))
    _head(out, "otb_failover_retries_total", "counter",
          "Failed failover attempts re-driven by the HA monitor's "
          "backoff ladder")
    out.append(_line(
        "otb_failover_retries_total", {},
        int(ha.get("failover_retries", 0)),
    ))
    _head(out, "otb_partition_heals_total", "counter",
          "Partition heal events observed (matrix heals + re-detected "
          "primaries)")
    out.append(_line(
        "otb_partition_heals_total", {},
        int(ha.get("partition_heals", 0)),
    ))

    # multi-coordinator serving plane (coord/): CN liveness, catalog
    # stream health, and the replica-read outcome counters — the
    # ISSUE-18 coherence evidence, scrapeable per node
    cs = getattr(cluster, "catalog_service", None)
    if cs is not None:
        _head(out, "otb_cn_active", "gauge",
              "Coordinators currently serving (this node plus every "
              "registered peer that answers its ping)")
        try:
            active = int(cs.active_coordinators())
        except Exception:
            active = -1
        out.append(_line("otb_cn_active", {}, active))
        _head(out, "otb_catalog_stream_lag_bytes", "gauge",
              "Primary-CN WAL bytes not yet applied by this peer's "
              "catalog stream (0 on the primary, -1 unknown)")
        out.append(_line(
            "otb_catalog_stream_lag_bytes", {}, int(cs.stream_lag()),
        ))
    rstats = getattr(cluster, "replica_stats", None)
    if rstats is not None:
        with cluster._replica_stats_mu:
            rstats = dict(rstats)
        _head(out, "otb_replica_read_total", "counter",
              "Reads served from bounded-staleness standbys")
        out.append(_line(
            "otb_replica_read_total", {},
            int(rstats.get("replica_reads", 0)),
        ))
        _head(out, "otb_stale_read_refused_total", "counter",
              "Replica-routed reads refused back to the primary "
              "because no standby proved max_staleness")
        out.append(_line(
            "otb_stale_read_refused_total", {},
            int(rstats.get("stale_read_refused", 0)),
        ))
        _head(out, "otb_forwarded_statements_total", "counter",
              "Statements this peer CN forwarded to the primary")
        out.append(_line(
            "otb_forwarded_statements_total", {},
            int(rstats.get("forwarded", 0)),
        ))

    # matview counters
    if cluster.matviews:
        _head(out, "otb_matview_refreshes_total", "counter",
              "Matview refreshes by mode")
        for name, d in cluster.matviews.items():
            for mode, key in (
                ("incremental", "incremental_refreshes"),
                ("full", "full_refreshes"),
            ):
                out.append(_line(
                    "otb_matview_refreshes_total",
                    {"matview": name, "mode": mode},
                    int(d.stats.get(key, 0)),
                ))
        _head(out, "otb_matview_rewrites_total", "counter",
              "Queries served from a matview by the rewrite path")
        for name, d in cluster.matviews.items():
            out.append(_line(
                "otb_matview_rewrites_total", {"matview": name},
                int(d.stats.get("rewrites", 0)),
            ))

    # device health: platform gauge + demotion counters. The r04/r05
    # bench rounds silently executed on platform=cpu and
    # nobody noticed until the JSON was read — a scrape must show it.
    fx = getattr(cluster, "_fused", None)
    if fx is not None:
        _head(out, "otb_device_platform", "gauge",
              "Fused-executor device platform (1 = active)")
        try:
            plat = fx.platform()
        except Exception:
            plat = "unknown"
        out.append(_line(
            "otb_device_platform", {"platform": plat}, 1,
        ))
        _head(out, "otb_pallas_demotions_total", "counter",
              "Pallas kernels demoted to the XLA path")
        out.append(_line(
            "otb_pallas_demotions_total", {},
            int(getattr(fx, "pallas_demotions", 0)),
        ))
        _head(out, "otb_dag_demotions_total", "counter",
              "Fused/DAG queries demoted to the host executor "
              "by unexpected exceptions")
        out.append(_line(
            "otb_dag_demotions_total", {},
            int(getattr(fx, "dag_demotion_count", 0)),
        ))
        if getattr(fx, "last_run_platform", None):
            _head(out, "otb_device_last_run_platform", "gauge",
                  "Platform the last fused run actually executed on "
                  "(1 = active)")
            out.append(_line(
                "otb_device_last_run_platform",
                {"platform": fx.last_run_platform}, 1,
            ))

    # device-platform watchdog counter: runs that executed on a platform
    # other than the configured expectation (the r04/r05 silent-CPU
    # class). Rendered from the process-lifetime total so the series
    # stays monotone across executor recycles — and rendered whenever
    # the fused module is loaded, even after cluster._fused was torn
    # down, so the counter never vanishes from a scrape.
    import sys as _sys

    _fused_mod = _sys.modules.get("opentenbase_tpu.executor.fused")
    if _fused_mod is not None:
        _head(out, "otb_platform_demotions_total", "counter",
              "Fused runs that executed on a platform other than the "
              "configured one (expected_device_platform watchdog)")
        out.append(_line(
            "otb_platform_demotions_total", {},
            int(_fused_mod.PLATFORM_DEMOTIONS_TOTAL[0]),
        ))

    # serving plane (serving/ + net/concentrator.py): cache counters
    # as counters, occupancy as gauges, concentrator live gauges
    serving = getattr(cluster, "serving", None)
    if serving is not None:
        for prefix, cache in (
            ("otb_plan_cache", serving.plan_cache),
            ("otb_result_cache", serving.result_cache),
        ):
            rows = dict(cache.stat_rows())
            _head(out, f"{prefix}_total", "counter",
                  "Serving-plane cache outcomes")
            for stat in ("hits", "misses", "inserts", "evictions",
                         "invalidations", "forced_misses"):
                out.append(_line(
                    f"{prefix}_total", {"outcome": stat},
                    int(rows.get(stat, 0)),
                ))
            _head(out, f"{prefix}_entries", "gauge",
                  "Live serving-plane cache entries")
            out.append(_line(
                f"{prefix}_entries", {}, int(rows.get("entries", 0)),
            ))
            if prefix == "otb_result_cache":
                _head(out, "otb_result_cache_bytes", "gauge",
                      "Resident result-cache bytes")
                out.append(_line(
                    "otb_result_cache_bytes", {},
                    int(rows.get("bytes", 0)),
                ))
    conc = getattr(cluster, "_concentrator", None)
    if conc is not None:
        crows = dict(conc.stat_rows())
        _head(out, "otb_concentrator_clients", "gauge",
              "Client connections multiplexed by the concentrator")
        out.append(_line(
            "otb_concentrator_clients", {}, int(crows.get("clients", 0)),
        ))
        _head(out, "otb_concentrator_backends", "gauge",
              "Concentrator backend sessions by state")
        for state in ("backends", "backends_free", "pinned"):
            out.append(_line(
                "otb_concentrator_backends", {"state": state},
                int(crows.get(state, 0)),
            ))
        _head(out, "otb_concentrator_queued", "gauge",
              "Statements waiting for a concentrator backend")
        out.append(_line(
            "otb_concentrator_queued", {}, int(crows.get("queued", 0)),
        ))
        _head(out, "otb_concentrator_statements_total", "counter",
              "Statements executed through the concentrator")
        out.append(_line(
            "otb_concentrator_statements_total", {},
            int(crows.get("statements", 0)),
        ))
        _head(out, "otb_concentrator_sheds_total", "counter",
              "Statements shed by the concentrator (SQLSTATE 53300)")
        out.append(_line(
            "otb_concentrator_sheds_total", {},
            int(crows.get("sheds", 0)),
        ))

    # gauges: WAL position, sessions, replication lag, pool occupancy,
    # DN heartbeat age (from the health prober's bookkeeping)
    _head(out, "otb_sessions", "gauge", "Registered sessions")
    out.append(_line("otb_sessions", {}, len(cluster.sessions)))
    p = cluster.persistence
    if p is not None:
        _head(out, "otb_wal_position_bytes", "gauge",
              "Current WAL end position")
        out.append(_line("otb_wal_position_bytes", {}, int(p.wal.position)))
        wal = p.wal.stat_snapshot()
        wal_pos = int(wal["position"])
        peers = []
        for sender in list(getattr(p, "wal_senders", ())):
            peers.extend(sender.peer_positions())
        if peers:
            _head(out, "otb_replication_lag_bytes", "gauge",
                  "WAL bytes not yet sent to each connected standby")
            for addr, sent in peers:
                out.append(_line(
                    "otb_replication_lag_bytes", {"peer": addr},
                    max(wal_pos - int(sent), 0),
                ))
        acks = []
        for sender in list(getattr(p, "wal_senders", ())):
            acks.extend(sender.peer_acks())
        if acks:
            _head(out, "otb_wal_ack_lag_bytes", "gauge",
                  "WAL bytes each standby has not yet acknowledged "
                  "applying (the synchronous_commit=remote_write "
                  "evidence)")
            for addr, acked in acks:
                out.append(_line(
                    "otb_wal_ack_lag_bytes", {"peer": addr},
                    max(wal_pos - int(acked), 0),
                ))
        # group commit (ROADMAP item 4a): fsyncs paid vs commits that
        # asked for durability, and the per-flush batch-size histogram
        _head(out, "otb_wal_fsyncs_total", "counter",
              "WAL fsync syscalls (group flush pays one per batch)")
        out.append(_line("otb_wal_fsyncs_total", {}, int(wal["fsyncs"])))
        _head(out, "otb_group_commit_saved_total", "counter",
              "Commit fsyncs amortized away by group commit "
              "(commit flushes minus leader fsyncs)")
        out.append(_line(
            "otb_group_commit_saved_total", {},
            max(int(wal["commit_flushes"]) - int(wal["group_fsyncs"]), 0),
        ))
        hist = wal["batch_hist"]
        if hist:
            _head(out, "otb_group_commit_batch_size", "counter",
                  "Group-flush batches by size bucket (le = commits "
                  "covered by that one fsync, power-of-two buckets)")
            for b in sorted(hist):
                out.append(_line(
                    "otb_group_commit_batch_size", {"le": str(b)},
                    int(hist[b]),
                ))
    ist = getattr(cluster, "ingest_stats", None)
    if ist is not None:
        with cluster._ingest_stats_mu:
            ist = dict(ist)
        _head(out, "otb_ingest_batches_total", "counter",
              "Columnar delta batches appended by the vectorized "
              "ingest plane (multi-row INSERT -> COPY rewrite)")
        out.append(_line(
            "otb_ingest_batches_total", {}, int(ist["batches"]),
        ))
        _head(out, "otb_ingest_rows_total", "counter",
              "Rows ingested through columnar delta batches")
        out.append(_line("otb_ingest_rows_total", {}, int(ist["rows"])))
        _head(out, "otb_ingest_compactions_total", "counter",
              "Background/lazy delta-compaction passes that folded "
              "batches into base tables")
        out.append(_line(
            "otb_ingest_compactions_total", {}, int(ist["compactions"]),
        ))
    stores = getattr(cluster, "stores", None)
    if stores:
        # scannable delta plane (ISSUE-15): scans serving pending delta
        # rows without a fold, and device tail-uploads of delta rows —
        # summed by the ONE helper pg_stat_wal/pg_stat_fused also use
        # (local import: engine imports this module's server half)
        from opentenbase_tpu.engine import _delta_plane_totals

        folds_avoided, rows_read, _absorbed = _delta_plane_totals(
            cluster
        )
        _head(out, "otb_delta_fold_avoided_total", "counter",
              "Scans that served pending delta rows without forcing "
              "a fold (the scannable delta plane)")
        out.append(_line(
            "otb_delta_fold_avoided_total", {}, folds_avoided,
        ))
        _head(out, "otb_delta_rows_read_total", "counter",
              "Delta-resident rows served to scans without a fold")
        out.append(_line("otb_delta_rows_read_total", {}, rows_read))
        fx = getattr(cluster, "_fused", None)
        if fx is not None:
            _head(out, "otb_delta_tail_uploads_total", "counter",
                  "Device-cache refreshes whose appended tail "
                  "uploaded straight from delta batches (no fold, "
                  "no full re-upload)")
            out.append(_line(
                "otb_delta_tail_uploads_total", {},
                int(fx.cache.stats.get("delta_tail_uploads", 0)),
            ))
    pools = getattr(cluster, "dn_channels", None) or {}
    if pools:
        _head(out, "otb_dn_pool_channels", "gauge",
              "Channel-pool occupancy per datanode")
        for n, pool in sorted(pools.items()):
            occ = pool.occupancy()
            for state in ("in_use", "idle"):
                out.append(_line(
                    "otb_dn_pool_channels",
                    {"node": f"dn{n}", "state": state}, occ[state],
                ))
    health = getattr(cluster, "_dn_health", None) or {}
    if health:
        now = time.time()
        _head(out, "otb_dn_up", "gauge",
              "Last datanode heartbeat outcome (1 = answered)")
        for n, h in sorted(health.items()):
            out.append(_line(
                "otb_dn_up", {"node": f"dn{n}"}, 1 if h.get("ok") else 0,
            ))
        _head(out, "otb_dn_heartbeat_age_seconds", "gauge",
              "Seconds since the last successful datanode heartbeat")
        for n, h in sorted(health.items()):
            ok_ts = h.get("ok_ts")
            age = round(now - ok_ts, 3) if ok_ts else -1
            out.append(_line(
                "otb_dn_heartbeat_age_seconds", {"node": f"dn{n}"}, age,
            ))
    # workload observatory (obs/statements.py): top statements by
    # accumulated wall time, labeled by queryid. Counters are monotone
    # per queryid; an evicted fingerprint's series simply disappears
    # (absent keys are legal in the exposition format).
    ss = getattr(cluster, "stmt_stats", None)
    if ss is not None:
        top = ss.top(10, "total_ms")
        if top:
            _head(out, "otb_stmt_calls", "counter",
                  "Statement executions per query fingerprint")
            for e in top:
                out.append(_line(
                    "otb_stmt_calls", {"queryid": str(e.queryid)},
                    int(e.calls),
                ))
            _head(out, "otb_stmt_total_ms", "counter",
                  "Total statement wall ms per query fingerprint")
            for e in top:
                out.append(_line(
                    "otb_stmt_total_ms", {"queryid": str(e.queryid)},
                    round(e.total_ms, 3),
                ))
            _head(out, "otb_stmt_device_ms", "counter",
                  "Device execute ms per query fingerprint")
            for e in top:
                out.append(_line(
                    "otb_stmt_device_ms", {"queryid": str(e.queryid)},
                    round(float(e.device_ms), 3),
                ))
            # the fused path's split of device_ms and its counts, one
            # series a ledger field (obs/statements.py)
            for f in (_stmtobs.DEVICE_SPLIT_FIELDS + ("merge_ms",)
                      + _stmtobs.FUSED_COUNT_FIELDS
                      + _stmtobs.EXCHANGE_FIELDS):
                _head(out, f"otb_stmt_{f}", "counter",
                      f"Fused-path {f} per query fingerprint")
                for e in top:
                    out.append(_line(
                        f"otb_stmt_{f}", {"queryid": str(e.queryid)},
                        round(float(getattr(e, f)), 3),
                    ))
            _head(out, "otb_stmt_transfer_bytes", "counter",
                  "h2d+d2h transfer bytes per query fingerprint")
            for e in top:
                out.append(_line(
                    "otb_stmt_transfer_bytes",
                    {"queryid": str(e.queryid)},
                    int(e.h2d_bytes) + int(e.d2h_bytes),
                ))
    return "\n".join(out) + "\n"


class MetricsExporter:
    """Minimal HTTP/1.1 listener serving ``GET /metrics`` from a render
    callable. One thread per connection, connection: close — a scrape
    every few seconds, not a web server."""

    def __init__(
        self, render: Callable[[], str],
        host: str = "127.0.0.1", port: int = 0,
    ):
        self.render = render
        self._lsock = socket.socket()
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind((host, port))
        self._lsock.listen(16)
        self.host, self.port = self._lsock.getsockname()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._accept_loop, daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        shutdown_and_close(self._lsock)

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._lsock.accept()
            except OSError:
                return
            threading.Thread(
                target=self._serve, args=(conn,), daemon=True
            ).start()

    def _serve(self, conn: socket.socket) -> None:
        try:
            conn.settimeout(5.0)
            req = b""
            while b"\r\n\r\n" not in req and len(req) < 8192:
                chunk = conn.recv(4096)
                if not chunk:
                    return
                req += chunk
            line = req.split(b"\r\n", 1)[0].decode("latin-1")
            parts = line.split()
            path = parts[1] if len(parts) >= 2 else "/"
            if path.split("?", 1)[0] not in ("/metrics", "/"):
                body = b"not found\n"
                conn.sendall(
                    b"HTTP/1.1 404 Not Found\r\n"
                    b"Content-Type: text/plain\r\n"
                    + f"Content-Length: {len(body)}\r\n".encode()
                    + b"Connection: close\r\n\r\n" + body
                )
                return
            try:
                body = self.render().encode()
            except Exception as e:  # a broken renderer must not kill scrapes
                body = f"# render error: {e}\n".encode()
            conn.sendall(
                b"HTTP/1.1 200 OK\r\n"
                b"Content-Type: text/plain; version=0.0.4; charset=utf-8\r\n"
                + f"Content-Length: {len(body)}\r\n".encode()
                + b"Connection: close\r\n\r\n" + body
            )
        except OSError:
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass


def scrape(host: str, port: int, timeout: float = 5.0) -> str:
    """Fetch one exposition document (the test/CLI-side scraper)."""
    with socket.create_connection((host, port), timeout=timeout) as s:
        s.sendall(
            f"GET /metrics HTTP/1.1\r\nHost: {host}\r\n"
            "Connection: close\r\n\r\n".encode()
        )
        buf = b""
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
    head, _, body = buf.partition(b"\r\n\r\n")
    if b" 200 " not in head.split(b"\r\n", 1)[0]:
        raise RuntimeError(f"scrape failed: {head.splitlines()[0]!r}")
    return body.decode()
