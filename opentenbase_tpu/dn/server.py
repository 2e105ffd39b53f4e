"""Datanode executor server — a real process boundary for fragments.

The reference's datanodes are separate postgres processes that receive
serialized plan fragments over the wire ('p' message,
src/backend/tcop/postgres.c:5580 -> exec_plan_message :2050) and stream
rows back. Here a DN process is:

- a ``StandbyCluster`` following the coordinator's WAL over streaming
  replication (storage/replication.py) — the DN's copy of the data plane,
  kept in sync by the same redo machinery as a hot standby;
- a framed-RPC server executing portable plan fragments
  (plan/serde.py) against its local shard stores with a coordinator-
  provided snapshot timestamp, after waiting for its replay position to
  reach the coordinator's WAL position (read-your-writes, the
  remote_apply consistency mode).

Run as a module:
  python -m opentenbase_tpu.dn.server --data-dir D --wal-host H
      --wal-port P [--listen-port N]
prints "READY <port>" on stdout once serving.
"""

from __future__ import annotations

import argparse
import socket
import sys
import threading
import time
from typing import Optional

from opentenbase_tpu import fault as _fault
from opentenbase_tpu import host_side_role
from opentenbase_tpu.fault import FAULT, FaultDropConnection
from opentenbase_tpu.net.protocol import (
    recv_frame,
    send_frame,
    shutdown_and_close,
)
from opentenbase_tpu.obs import log as _olog
from opentenbase_tpu.obs import tracectx as _tctx


class FragmentCancelled(RuntimeError):
    """The coordinator sent cancel_fragment for this token (it abandoned
    the fragment at its socket deadline); execution stops at the next
    operator boundary instead of running to completion."""


class DNServer:
    def __init__(
        self,
        data_dir: str,
        wal_host: str,
        wal_port: int,
        num_datanodes: int = 2,
        shard_groups: int = 256,
        host: str = "127.0.0.1",
        port: int = 0,
        metrics_port: int = 0,
    ):
        from opentenbase_tpu.storage.replication import StandbyCluster

        # this process's server log (obs/log.py): its own ring, NOT the
        # process default — in-process test topologies host the
        # coordinator and several DN servers in one interpreter, and
        # each node's records must attribute to that node. Service
        # threads bind it thread-locally so module-level emitters
        # (fault firings, channel errors) land here too; the standby
        # cluster's own logging (WAL recovery, replication) is pointed
        # at it below. pg_cluster_logs() fetches it over ``log_fetch``.
        self.log_ring = _olog.LogRing(node="dn")
        # this process's span ring (obs/tracectx.py): fragment
        # executions, 2PC verbs, and WAL waits record here when the
        # request carried a ``_trace`` header; the coordinator fetches
        # it over the ``trace_fetch`` op and merges by trace_id —
        # mirroring the log ring's log_fetch path. Node attribution
        # happens at fetch time (this process does not know its mesh
        # index, same as the log ring).
        self.span_ring = _tctx.SpanRing(capacity=4096)
        # kept for the repoint-rewind path: a diverged survivor
        # rebuilds its standby over the same data_dir
        self._data_dir = data_dir
        self._num_datanodes = num_datanodes
        self._shard_groups = shard_groups
        self.standby = StandbyCluster(data_dir, num_datanodes, shard_groups)
        self.standby.cluster.log = self.log_ring
        # gids resolved by the replication stream (their 'G' frame was
        # applied here): a late/repeat 2PC decision for one of these
        # must NOT re-apply its journal payload
        # insertion-ordered gid set (dict keys): bounded eviction must
        # drop the OLDEST gids, not arbitrary ones — set.pop() could
        # evict the gid just added while keeping stale ones (ADVICE r4)
        self._stream_resolved: dict = {}
        # observability: shipped-DML direct applies vs gap-deferred
        # fallbacks (surfaced through ping -> coordinator pg_stat_dml);
        # bumped from concurrent connection threads, hence the lock
        self.stats: dict = {}
        self._stats_mu = threading.Lock()
        # peer exchange (squeue.c's consumer-keyed tuple queues): other
        # DNs push motioned partitions here; consumer fragments wait on
        # the condition until every producer's part arrived
        self._exch: dict = {}        # (xid, dest) -> {from: wire batch}
        self._exch_born: dict = {}   # (xid, dest) -> arrival time (GC)
        self._exch_cv = threading.Condition()
        self._peer_pools: dict = {}  # (host, port) -> ChannelPool
        self._peer_mu = threading.Lock()
        # startup sweep: 'G' frames already in the local WAL copy were
        # applied during StandbyCluster replay — retire their journals
        # before any repeat 2pc_commit could double-apply them
        from opentenbase_tpu.storage.persist import WAL as _WAL

        try:
            for tag, header, _arr, _off in _WAL.read_records(
                self.standby.cluster.persistence.wal.path,
                decode_arrays=False,
            ):
                if tag == "G" and header.get("gid"):
                    self._on_stream_txn(header["gid"])
        except OSError:
            pass
        self.standby.stream_txn_hook = self._on_stream_txn
        self.standby.start_replication(wal_host, wal_port)
        self._promoted_srv = None
        self._promoted_walsender = None
        self._promote_mu = threading.Lock()
        # fencing epoch learned from wire ops (monotone max). The
        # stream-learned half lives on the standby cluster
        # (node_generation, set by replayed ha_generation records);
        # effective_generation() is the max of both.
        self._hgen = 0
        # serving-lease grant table (ha.ServingLease): holder name ->
        # (generation, monotonic deadline). Consulted by promote/ping
        # replies so a failover can wait out every grant the OLD
        # generation might still be serving under.
        self._leases: dict = {}
        self._lease_mu = threading.Lock()
        # DN-side fragment cancel (the reference's real cancel message):
        # tokens the coordinator abandoned; running fragments poll the
        # set at operator boundaries. Insertion-ordered for bounded
        # eviction of the oldest, like _stream_resolved.
        self._cancelled: dict = {}
        self._cancel_mu = threading.Lock()
        # crash_node fault: True once an injected crash took this node
        # down — the listener is closed and every live connection drops
        # its request without a reply (indistinguishable from a killed
        # process to the coordinator, while tests keep the object)
        self._crashed = False
        # live fragment executions (pg_cluster_health's in-flight gauge)
        self._inflight = 0
        self._lsock = socket.socket()
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind((host, port))
        self._lsock.listen(32)
        self.host, self.port = self._lsock.getsockname()
        self._stop = threading.Event()
        self._accept: Optional[threading.Thread] = None
        # per-node OpenMetrics exporter (metrics_port GUC semantics:
        # 0 = no listener socket at all)
        self._metrics_exporter = None
        if metrics_port > 0:
            from opentenbase_tpu.obs.exporter import (
                MetricsExporter,
                render_cluster_metrics,
            )

            self._metrics_exporter = MetricsExporter(
                lambda: render_cluster_metrics(self.standby.cluster),
                port=metrics_port,
            )

    def start(self) -> "DNServer":
        self._accept = threading.Thread(target=self._accept_loop, daemon=True)
        self._accept.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._metrics_exporter is not None:
            self._metrics_exporter.stop()
        shutdown_and_close(self._lsock)
        with self._peer_mu:
            for pool in self._peer_pools.values():
                try:
                    pool.close()
                except Exception:
                    pass
            self._peer_pools.clear()
        # snapshot under the promote lock: stop() racing a concurrent
        # promotion RPC could read a half-published (_promoted_srv,
        # _promoted_walsender) pair and leak the one it missed
        with self._promote_mu:
            promoted_srv = self._promoted_srv
            promoted_walsender = self._promoted_walsender
        if promoted_srv is not None:
            try:
                promoted_srv.stop()
            except Exception:
                pass
        if promoted_walsender is not None:
            try:
                promoted_walsender.stop()
            except Exception:
                pass
        self.standby.stop()

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._lsock.accept()
            except OSError:
                return
            try:
                # failpoint: the DN refusing/dropping a just-accepted
                # coordinator connection. Its OWN try block: drop_conn
                # raises a ConnectionResetError (an OSError), and the
                # accept handler above would read that as a closed
                # listener and kill the loop — the loop must survive
                # any injected action.
                FAULT("dn/accept")
            except Exception as e:
                self.log_ring.emit(
                    "warning", "dn",
                    f"connection refused at accept: {e!r:.120}",
                )
                try:
                    conn.close()
                except OSError:
                    pass
                continue
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(
                target=self._serve, args=(conn,), daemon=True
            ).start()

    # -- RPC loop ---------------------------------------------------------
    def _serve(self, conn: socket.socket) -> None:
        # everything this service thread emits — including module-level
        # fault-firing records — belongs to THIS node's server log
        _olog.set_thread_ring(self.log_ring)
        try:
            while not self._stop.is_set():
                # failpoint at the DN's own frame boundary: a request
                # torn between recv and dispatch (distinct from the
                # shared net/protocol sites, which fire for every peer)
                FAULT("dn/serve")
                msg = recv_frame(conn)
                if msg is None:
                    break
                if self._crashed and msg.get("op") not in (
                    "fault_arm", "fault_clear", "fault_stats"
                ):
                    break  # injected crash: no replies (fault-control
                    # ops on a surviving channel stay answerable so a
                    # chaos harness can always disarm + revive)
                try:
                    send_frame(conn, self._dispatch(msg))
                except FaultDropConnection:
                    break  # drop without a reply, like a dying process
                except Exception as e:
                    # the error DOES travel — as a reply frame to the
                    # caller — but the server log must carry it too: a
                    # dispatch crash diagnosed only from the client side
                    # is invisible to pg_cluster_logs' merged view
                    self.log_ring.emit(
                        "warning", "dn",
                        f"dispatch error for op "
                        f"{msg.get('op')!r}: {type(e).__name__}: "
                        f"{e!s:.200}",
                    )
                    send_frame(
                        conn, {"error": f"{type(e).__name__}: {e}"}
                    )
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _simulate_crash(self) -> None:
        """crash_node fault: stop accepting, stop answering. The python
        object survives (tests can inspect/recover it) but from every
        peer's perspective the node is gone mid-request."""
        self._crashed = True
        shutdown_and_close(self._lsock)
        self._bump("injected_crashes")
        self.log_ring.emit(
            "warning", "fault",
            "injected crash_node: datanode down "
            "(listener closed, connections dropping)",
        )

    def _failpoint(self, site: str, **ctx):
        """Evaluate one FAULT site with the DN's crash_node semantics
        (take the node down, sever THIS request without a reply) handled
        in one place; returns the action for any other site-handled
        reaction."""
        act = FAULT(site, **ctx)
        if act == "crash_node":
            self._simulate_crash()
            raise FaultDropConnection("injected datanode crash")
        return act

    def _dispatch(self, msg: dict) -> dict:
        # cross-node tracing: an optional ``_trace`` header binds the
        # statement's trace context to THIS service thread for the
        # request — the same per-thread binding the log ring uses — so
        # fragment/2PC/WAL-wait spans land in our span ring already
        # stitched to the coordinator's trace. No header = no binding =
        # zero tracing cost (the trace_queries=off contract, enforced
        # cross-process by the SpanRing.allocations test).
        hdr = msg.get("_trace")
        if hdr is None:
            return self._dispatch_inner(msg)
        prev = _tctx.bind(_tctx.from_header(hdr))
        try:
            return self._dispatch_inner(msg)
        finally:
            _tctx.bind(prev)

    def _dispatch_inner(self, msg: dict) -> dict:
        op = msg.get("op")
        # fault-control ops answer even on a 'crashed' node: the chaos
        # harness must always be able to clear its own faults (the
        # control plane a real kill would provide via process respawn)
        if op == "fault_arm":
            _fault.inject(
                str(msg["site"]), str(msg["action"]),
                str(msg.get("spec") or ""),
            )
            return {"ok": True}
        if op == "fault_clear":
            n = _fault.clear(msg.get("site"))
            if self._crashed:
                # disarm + revive in one control message: the chaos
                # harness's equivalent of respawning the process
                self._revive()
            return {"ok": True, "cleared": n}
        if op == "fault_stats":
            return {"ok": True, "rows": [list(r) for r in _fault.stats()]}
        if op == "log_fetch":
            # ship this node's server-log ring to the coordinator
            # (pg_cluster_logs' merge). Answers even on a 'crashed'
            # node only for surviving channels — like fault ops, the
            # control plane a respawned process would provide — but
            # this op sits BELOW the crashed gate on purpose: a dead
            # node ships nothing until it is revived.
            rows = self.log_ring.rows(
                msg.get("min_level"),
                float(msg.get("since_ts") or 0.0),
            )
            return {"ok": True, "rows": [list(r) for r in rows]}
        if op == "trace_fetch":
            # ship this node's span ring to the coordinator (the
            # pg_export_traces merge) — log_fetch's sibling, same
            # below-the-crashed-gate placement on purpose: a dead node
            # ships nothing until it is revived
            return {
                "ok": True,
                "rows": self.span_ring.rows(
                    trace_ids=msg.get("trace_ids"),
                    since_ts=float(msg.get("since_ts") or 0.0),
                ),
            }
        # fencing-epoch gate (self-healing HA): data-plane ops carry the
        # caller's node_generation. A caller BEHIND this node's known
        # generation is a stale ex-primary partitioned through a
        # promotion — refuse with the fenced error (SQLSTATE 72000) and
        # tell it to demote; split-brain becomes a refused RPC instead
        # of silent divergence. A caller AHEAD advances our known
        # generation (the coordinator is the authority).
        hg = msg.get("hgen")
        if hg is not None:
            hg = int(hg)
            cur = self.effective_generation()
            if hg < cur:
                self._bump("fenced_refusals")
                self.log_ring.emit(
                    "warning", "ha",
                    f"fenced stale-generation op {op!r} "
                    f"(caller {hg} < node {cur})",
                    op=op, caller_generation=hg, generation=cur,
                )
                return {
                    "error": (
                        f"stale generation: {op} carries generation "
                        f"{hg} but this node follows generation {cur};"
                        " caller must demote and resync"
                    ),
                    "fenced": True,
                    "gen": cur,
                    "sqlstate": "72000",
                }
            # advance the learned generation under the promote lock:
            # two dispatch threads doing an unguarded read-max-write
            # could finish in the wrong order and REGRESS _hgen,
            # quietly re-opening the fence for a stale ex-primary
            with self._promote_mu:
                if hg > self._hgen:
                    self._hgen = hg
        self._failpoint("dn/dispatch", op=op)
        if op == "lease_grant":
            # serving lease (ha.ServingLease): record the grant. Sits
            # BELOW the hgen gate on purpose — a renewal from a stale
            # generation is refused fenced above, which is exactly how
            # a partitioned ex-primary learns it must demote forever.
            holder = str(msg.get("holder") or "cn0")
            ttl_ms = int(msg.get("ttl_ms") or 0)
            with self._lease_mu:
                self._leases[holder] = (
                    int(msg.get("hgen") or 0),
                    time.monotonic() + ttl_ms / 1000.0,
                )
            self._bump("lease_grants")
            return {"ok": True}
        if op == "cancel_fragment":
            tok = str(msg.get("token") or "")
            with self._cancel_mu:
                self._cancelled[tok] = time.time()
                while len(self._cancelled) > 1024:
                    self._cancelled.pop(next(iter(self._cancelled)))
            self._bump("cancel_requests")
            return {"ok": True}
        if op == "ping":
            self._exch_gc()  # periodic sweep rides the health checks
            with self._stats_mu:
                st = dict(self.stats)
                inflight = self._inflight
            out = {
                "ok": True, "applied": self.standby.applied,
                "dml_stats": st,
                # pg_cluster_health's per-node gauges ride the heartbeat
                "inflight": inflight,
                "armed_faults": len(_fault.armed()),
                # replica-read plane: the walreceiver's local socket
                # address keys this node into the primary walsender's
                # per-peer ack table (coord/replica.py staleness proof),
                # and the replayed DDL clock rides the heartbeat so
                # pg_cluster_health can show catalog coherence per node
                "repl_addr": getattr(self.standby, "repl_addr", ""),
                "catalog_epoch": int(
                    getattr(self.standby.cluster, "catalog_epoch", 0)
                ),
                # self-healing HA: fencing generation + live role so a
                # failover is visible on the next heartbeat
                "generation": self.effective_generation(),
                # serving lease: worst outstanding stale-generation
                # grant, for observability and failover planning
                "lease_remaining_ms": self._stale_lease_remaining_ms(
                    self.effective_generation()
                ),
                "role": (
                    # otb_race: ignore[race-guard-mismatch] -- heartbeat snapshot; a ping racing the promotion RPC reports the pre-promote role for one beat, the next beat corrects it
                    "coordinator" if self._promoted_srv is not None
                    else "datanode"
                ),
            }
            if self._promoted_srv is not None:
                out["promoted"] = True
                out["coordinator_port"] = self._promoted_srv.port
            return out
        if op == "query":
            # replica read (coord/replica.py ChannelTarget): read-only
            # SQL against this node's hot standby. Sits ABOVE the
            # promoted fence on purpose — after this node takes over as
            # coordinator its data is still the freshest copy there is,
            # so routed reads keep working across the failover.
            return self._query(msg)
        if op == "promote":
            return self._promote(msg)
        if op == "repl_repoint":
            return self._repoint(msg)
        if self._promoted_srv is not None:
            # a promoted node owns its data read-write; replication-
            # role ops from a partitioned old coordinator must be
            # refused, or its 2PC decisions would write behind the new
            # primary's back (the split-brain fence a promoted PG
            # standby applies by rejecting the WAL stream)
            return {
                "error": "stale generation: datanode has been promoted "
                "to coordinator; replication-role ops refused — caller "
                "must demote and resync",
                "fenced": True,
                "gen": self.effective_generation(),
                "sqlstate": "72000",
            }
        if op == "exec_fragment":
            return self._exec_fragment(msg)
        if op == "rebalance_apply":
            return self._rebalance_apply(msg)
        if op == "rebalance_finalize":
            return self._rebalance_finalize(msg)
        if op == "2pc_prepare":
            return self._twophase_prepare(msg)
        if op == "2pc_commit":
            return self._twophase_finish(msg, committed=True)
        if op == "2pc_abort":
            return self._twophase_finish(msg, committed=False)
        if op == "exch_put":
            return self._exch_put(msg)
        if op == "exch_take":
            return self._exch_take(msg)
        if op == "2pc_list":
            entries = self._twophase_list()
            return {
                "ok": True,
                "gids": [e["gid"] for e in entries],
                "entries": entries,
            }
        return {"error": f"unknown op {op}"}

    # -- shard-rebalance participant (rebalance/ real-topology path) ------
    # The coordinator-local rebalancer copies between in-process stores;
    # with attached DNs the same two steps ship over the channel instead:
    # rebalance_apply lands a copy chunk's rows with xmin = PENDING_TS
    # (invisible — the PgxcMoveData bulk-load half), rebalance_finalize
    # stamps a landed range visible at the flip timestamp. Both are
    # idempotent against the WAL stream: the stream's 'T'/flip records
    # re-derive the same state, and direct-applied ranges are reported
    # back so the coordinator journals exactly what landed here.

    def _rebalance_apply(self, msg: dict) -> dict:
        from opentenbase_tpu.plan import serde
        from opentenbase_tpu.storage.table import PENDING_TS, ShardStore

        c = self.standby.cluster
        with c._exec_lock:
            node = int(msg["node"])
            tname = str(msg["table"])
            try:
                meta = c.catalog.get(tname)
            except ValueError as e:
                return {"error": str(e)}
            batch = serde.batch_from_wire(msg["batch"], c.catalog)
            store = c.stores.setdefault(node, {}).setdefault(
                tname, ShardStore(meta.schema, meta.dictionaries)
            )
            s, e = store.append_delta(batch, PENDING_TS)
            self._bump("rebalance_chunks")
        return {"ok": True, "start": int(s), "end": int(e)}

    def _rebalance_finalize(self, msg: dict) -> dict:
        c = self.standby.cluster
        with c._exec_lock:
            node = int(msg["node"])
            tname = str(msg["table"])
            store = c.stores.get(node, {}).get(tname)
            if store is None:
                return {"error": f"no store for dn{node}.{tname}"}
            store.stamp_xmin(
                int(msg["start"]), int(msg["end"]),
                int(msg["commit_ts"]),
            )
        return {"ok": True}

    # -- two-phase commit participant -------------------------------------
    # The reference's datanodes vote in the coordinator's implicit 2PC
    # (pgxc_node_remote_prepare, execRemote.c:3936; the 2PC control
    # messages, pgxcnode.c:2843-3081). The DN's durable vote is a
    # fsynced journal entry under <data_dir>/prepared_2pc that CARRIES
    # THE TRANSACTION'S WRITE SET (twophase.c's state files hold the
    # prepared WAL records the same way): PREPARE persists gid + data
    # before the coordinator's irrevocable commit stamp; COMMIT applies
    # the journaled writes to this DN's stores immediately through the
    # stream-replay code path (read-your-writes without waiting for the
    # WAL stream), with gid-tagged 'G' frames deduplicating the two
    # delivery paths exactly-once; ABORT discards; 2pc_list lets the
    # coordinator's resolve_indoubt sweep orphans after a crash. The
    # prepared data also survives a coordinator crash on the DN's disk.

    def _twophase_dir(self) -> str:
        import os

        d = os.path.join(self.standby.data_dir, "prepared_2pc")
        os.makedirs(d, exist_ok=True)
        return d

    def _on_stream_txn(self, gid: str) -> None:
        """The replication stream applied (or is about to apply) the
        'G' frame for ``gid``: its journal is resolved."""
        import os

        self._stream_resolved[gid] = None
        while len(self._stream_resolved) > 4096:
            self._stream_resolved.pop(
                next(iter(self._stream_resolved))
            )
        try:
            os.unlink(os.path.join(self._twophase_dir(), gid))
        except OSError:
            pass

    def _twophase_prepare(self, msg: dict) -> dict:
        # 2PC verbs are trace-visible: the durable-vote fsync and the
        # decision apply are exactly the commit-path costs an operator
        # needs attributed when a distributed commit stalls
        ctx = _tctx.current()
        if ctx is None:
            return self._twophase_prepare_inner(msg)
        t0 = time.time()
        try:
            return self._twophase_prepare_inner(msg)
        finally:
            self.span_ring.record(
                ctx, "2pc_prepare", "2pc", t0, time.time(),
                gid=str(msg.get("gid")),
            )

    def _twophase_prepare_inner(self, msg: dict) -> dict:
        import json
        import os

        gid = str(msg["gid"])
        if not gid or "/" in gid or gid.startswith("."):
            return {"error": f"bad gid {gid!r}"}
        # failpoint BEFORE the vote journal hits disk: an error here is
        # a DN that never voted (the coordinator must abort the txn)
        self._failpoint("dn/2pc_prepare", gid=gid)
        d = self._twophase_dir()
        tmp = os.path.join(d, f".{gid}.tmp")
        path = os.path.join(d, gid)
        entry = {
            "gid": gid,
            "gxid": msg.get("gxid"),
            "participants": msg.get("participants") or [],
            "prepared_at": time.time(),
        }
        # shipped DML (execRemote.c:3936): the write set itself rides
        # the prepare and fsyncs WITH the vote — the twophase.c state
        # file contract. COMMIT applies it locally without waiting for
        # the WAL stream; the gid-tagged 'G' frame dedups later.
        if msg.get("writes") is not None:
            entry["writes"] = msg["writes"]
        with open(tmp, "w") as f:
            json.dump(entry, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        dfd = os.open(d, os.O_RDONLY)
        try:
            os.fsync(dfd)  # the rename itself must be durable
        finally:
            os.close(dfd)
        # failpoint AFTER the journal is durable: the vote exists but
        # the ack is lost — the in-doubt shape pg_resolve_indoubt()
        # exists to drive to a decision
        self._failpoint("dn/2pc_prepare:after_journal", gid=gid)
        return {"ok": True}

    def _twophase_finish(self, msg: dict, committed: bool) -> dict:
        ctx = _tctx.current()
        if ctx is None:
            return self._twophase_finish_inner(msg, committed)
        t0 = time.time()
        try:
            return self._twophase_finish_inner(msg, committed)
        finally:
            self.span_ring.record(
                ctx, "2pc_commit" if committed else "2pc_abort", "2pc",
                t0, time.time(), gid=str(msg.get("gid")),
            )

    def _twophase_finish_inner(self, msg: dict, committed: bool) -> dict:
        import json
        import os

        gid = str(msg["gid"])
        verb = "2pc_commit" if committed else "2pc_abort"
        # before-journal failpoint: the decision message arrived but
        # nothing was applied/retired yet — a lost phase-2 delivery
        self._failpoint(f"dn/{verb}", gid=gid)
        path = os.path.join(self._twophase_dir(), gid)
        try:
            with open(path) as f:
                entry = json.load(f)
        except FileNotFoundError:
            # presumed-abort protocol: finishing an unknown gid is a
            # no-op (the prepare may never have arrived, or the stream
            # already resolved it)
            return {"ok": True, "known": False}
        except ValueError:
            entry = {}
        applied = False
        if committed and entry.get("writes") is not None:
            applied = self._apply_journal(gid, entry, msg)
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass
        # after-journal failpoint: applied + journal retired, ack lost
        self._failpoint(f"dn/{verb}:after_journal", gid=gid)
        return {"ok": True, "known": True, "applied": applied}

    def _apply_journal(self, gid: str, entry: dict, msg: dict) -> bool:
        """Apply a journaled write set to OUR stores through the same
        code path stream replay uses — exactly once across the two
        delivery paths (direct_applied tells the stream to skip the
        matching 'G' frame; _stream_resolved tells us the stream won)."""
        from opentenbase_tpu.plan import serde

        c = self.standby.cluster
        with c._exec_lock:
            # re-check the fence UNDER the lock: the dispatch gate ran
            # before we queued on it, and promote() drains+bumps
            # atomically under this same lock — a phase-2 from the
            # deposed generation that lost the race must not write a
            # row the promoted WAL will never carry
            hg = msg.get("hgen")
            if hg is not None and int(hg) < self.effective_generation():
                self._bump("fenced_refusals")
                return False
            if (
                gid in self._stream_resolved
                or gid in self.standby.direct_applied
            ):
                return False
            commit_ts = msg.get("commit_ts")
            if commit_ts is None:
                return False
            sub, arrays = serde.frame_from_wire(entry["writes"])
            # failpoint: the batch-apply boundary (error = the DN dying
            # between the decision and the store apply — direct_applied
            # stays unset, so the stream's gid-tagged 'G' frame applies
            # it exactly once on the ordinary path; delay = a DN whose
            # ingest apply lags the coordinator's ack wait)
            self._failpoint("dn/batch_apply", gid=gid, frames=len(sub))
            if c.persistence.frame_apply_gap(sub):
                # our replica is BEHIND this frame: a touched table's
                # DDL hasn't streamed yet, or our dictionaries are
                # missing values below the frame's delta — a direct
                # apply would lose rows or assign wrong codes. Defer —
                # the gid-tagged 'G' frame arrives in stream order
                # with everything it needs, and direct_applied stays
                # unset so the stream applies it.
                self._bump("dml_deferred_gap")
                return False
            c.persistence._apply(
                "G",
                {"commit_ts": int(commit_ts), "writes": sub, "gid": gid},
                arrays,
            )
            if self.standby.relog_closed:
                # this node IS the promoted primary (the in-doubt
                # resolver lands here after promote() drained
                # pending_relog): no stream will ever carry this
                # frame, so WAL-log it NOW — otherwise the row lives
                # in a read-write primary's stores with no WAL record
                # any standby or rejoiner could ever replay
                c.persistence.wal.append(
                    b"G",
                    {"commit_ts": int(commit_ts), "writes": sub,
                     "gid": gid},
                    arrays or None,
                )
                c.persistence._record_decision(
                    gid, "commit", int(commit_ts)
                )
            else:
                self.standby.direct_applied.add(gid)
                # promotion safety: until the stream's 'G' frame
                # lands, this txn exists in our stores but in no WAL
                # we could be promoted on — keep the payload so
                # promote() can re-log it
                self.standby.note_direct_apply(
                    gid, int(commit_ts), entry["writes"]
                )
            self._bump("dml_direct_applied")
        return True

    def _twophase_list(self) -> list:
        import json
        import os

        out = []
        d = self._twophase_dir()
        try:
            names = sorted(
                g for g in os.listdir(d) if not g.startswith(".")
            )
        except OSError:
            return []
        now = time.time()
        for g in names:
            age = None
            try:
                with open(os.path.join(d, g)) as f:
                    age = now - float(
                        json.load(f).get("prepared_at") or 0.0
                    )
            except (OSError, ValueError):
                pass
            out.append({"gid": g, "age_s": age})
        return out

    # -- peer DN<->DN exchange --------------------------------------------
    # The reference's redistribution data plane is producer datanodes
    # writing tuples into consumer-keyed shared queues / DataPump
    # sockets (/root/reference/src/backend/pgxc/squeue/squeue.c:403-660)
    # with the coordinator only coordinating. Same shape here: the
    # producer fragment partitions its output locally and pushes each
    # partition to the consumer DN's exchange store over a peer
    # channel; the coordinator ships the address book and sees row
    # counts only.

    def _exch_gc(self, max_age_s: float = 600.0) -> None:
        now = time.time()
        with self._exch_cv:
            for k in [
                k for k, born in self._exch_born.items()
                if now - born > max_age_s
            ]:
                self._exch.pop(k, None)
                self._exch_born.pop(k, None)

    def _bump(self, key: str, by: int = 1) -> None:
        with self._stats_mu:
            self.stats[key] = self.stats.get(key, 0) + by

    def _exch_put(self, msg: dict) -> dict:
        key = (str(msg["xid"]), int(msg["dest"]))
        with self._exch_cv:
            self._exch.setdefault(key, {})[int(msg["from"])] = (
                msg["batch"]
            )
            self._exch_born.setdefault(key, time.time())
            self._exch_cv.notify_all()
        self._bump("exch_parts_in")
        self._exch_gc()
        return {"ok": True}

    # The wait budget must sit BELOW the coordinator channel's rpc
    # timeout (120s default): producers completed their RPCs before any
    # consumer dispatches, so a missing part means a dead producer —
    # surface the DN's clean "exchange timed out" error rather than
    # letting the client socket time out first and discard the channel.
    EXCH_WAIT_S = 60.0

    def _exch_wait(self, xid: str, dest: int, producers,
                   timeout_s: float = EXCH_WAIT_S, cancelled=None):
        """Wire parts from every producer, in producer order — or None
        on timeout/cancel. Pops the entry (one consumption per
        exchange). ``cancelled`` is polled between waits so an
        abandoned consumer stops parking on dead producers."""
        key = (str(xid), int(dest))
        deadline = time.time() + timeout_s
        with self._exch_cv:
            while True:
                parts = self._exch.get(key, {})
                if all(int(p) in parts for p in producers):
                    self._exch.pop(key, None)
                    self._exch_born.pop(key, None)
                    return [parts[int(p)] for p in producers]
                if cancelled is not None and cancelled():
                    return None
                left = deadline - time.time()
                if left <= 0:
                    return None
                self._exch_cv.wait(min(left, 0.25 if cancelled else 1.0))

    def _exch_take(self, msg: dict) -> dict:
        self._exch_gc()
        parts = self._exch_wait(
            msg["xid"], int(msg["dest"]), msg.get("producers") or [],
        )
        if parts is None:
            return {"error": "exchange timeout"}
        return {"ok": True, "parts": parts}

    def _peer(self, host: str, port: int):
        from opentenbase_tpu.net.pool import ChannelPool

        key = (host, int(port))
        with self._peer_mu:
            pool = self._peer_pools.get(key)
            if pool is None:
                pool = ChannelPool(host, int(port), size=2)
                self._peer_pools[key] = pool
            return pool

    def _motion_push(self, out, mo: dict, node: int, plan) -> None:
        """Partition ``out`` per the motion spec and push each part to
        its consumer DN — remote pushes in parallel (the serial wall
        time would grow linearly with cluster size otherwise);
        self-parts deposit locally without a socket."""
        from opentenbase_tpu.executor.dist import partition_batch
        from opentenbase_tpu.plan import serde

        dest = mo["dest"]  # [[node, host, port], ...]
        kind = mo["kind"]
        parts: dict[int, object] = {}
        if kind == "broadcast":
            wire = serde.batch_to_wire(out, plan.schema)
            for dn, _h, _p in dest:
                parts[int(dn)] = wire
        else:  # redistribute — the ONE shared routing formula
            idx_by = partition_batch(
                out, mo["hash_positions"], len(dest), mo.get("route")
            )
            for di in range(len(dest)):
                parts[int(dest[di][0])] = serde.batch_to_wire(
                    out.take(idx_by[di]), plan.schema
                )
        errors: list = []
        pushers = []
        for dn, host_, port_ in dest:
            dn = int(dn)
            payload = {
                "op": "exch_put", "xid": mo["xid"], "dest": dn,
                "from": int(mo["from"]), "batch": parts[dn],
            }
            if (host_, int(port_)) == (self.host, self.port):
                self._exch_put(payload)  # self-part: no socket
                continue

            def push(h=host_, p=port_, pl=payload):
                try:
                    self._peer(h, p).rpc(pl)
                    self._bump("exch_parts_out")
                except Exception as e:
                    # collected and re-raised on the pushing thread
                    # below, but ALSO logged here with the destination:
                    # the re-raise loses which peer failed, and a
                    # motion stall is diagnosed per-edge
                    self.log_ring.emit(
                        "warning", "dn",
                        f"motion push to {h}:{p} failed: {e!r:.160}",
                    )
                    errors.append(e)

            th = threading.Thread(target=push, daemon=True)
            th.start()
            pushers.append(th)
        for th in pushers:
            th.join()
        if errors:
            raise errors[0]

    def _stale_lease_remaining_ms(self, new_gen: int) -> int:
        """Worst-case milliseconds a holder on a generation BELOW
        ``new_gen`` could still believe it holds a serving lease this
        node granted — what failover() must wait out before flipping
        client routing."""
        now = time.monotonic()
        worst = 0.0
        with self._lease_mu:
            for _holder, (gen, deadline) in self._leases.items():
                if gen < new_gen and deadline > now:
                    worst = max(worst, deadline - now)
        return int(worst * 1000.0)

    # -- coordinator failover ---------------------------------------------
    def effective_generation(self) -> int:
        """The highest fencing generation this node knows: learned from
        wire ops (_hgen), from replayed ha_generation WAL records (the
        standby cluster's node_generation), or from its own promotion."""
        return max(
            # otb_race: ignore[race-guard-mismatch] -- lock-free monotonic read on the per-op fencing hot path; a stale int defers the refusal to the caller's next op, it never unfences
            self._hgen,
            int(getattr(self.standby.cluster, "node_generation", 0)),
        )

    def _promote(self, msg: dict) -> dict:
        """Promote this datanode process to a full COORDINATOR: its
        StandbyCluster holds the complete replicated state (WAL copy,
        catalog, 2PC journals), so any DN can take over when the
        coordinator dies — pg_ctl promote pointed at a datanode.
        Stops WAL replication, finishes recovery (re-parks in-doubt
        2PC, truncates the torn stream tail, re-logs unstreamed
        direct-applied 2PC commits, WAL-logs the bumped fencing
        generation), opens a read-write SQL front end AND a walsender
        so the surviving standbys / rejoining ex-primary can follow
        the new timeline. Idempotent."""
        from opentenbase_tpu.net.server import ClusterServer
        from opentenbase_tpu.storage.replication import WalSender

        with self._promote_mu:  # idempotent under concurrent RPCs
            if self._promoted_srv is None:
                # failpoint INSIDE the promotion window: a chaos
                # schedule killing the candidate mid-promote
                # (crash_node) forces the HA monitor onto its
                # next-best candidate
                self._failpoint("dn/promote")
                gen = msg.get("generation")
                c = self.standby.promote(
                    generation=int(gen) if gen is not None else None,
                )
                self._hgen = max(self._hgen, c.node_generation)
                self._promoted_srv = ClusterServer(c).start()
                if msg.get("walsender", True):
                    self._promoted_walsender = WalSender(c.persistence)
                self._bump("promoted")
            c = self.standby.cluster
            out = {
                "ok": True,
                "port": self._promoted_srv.port,
                "generation": int(c.node_generation),
                "promote_lsn": int(getattr(c, "ha_promote_lsn", 0)),
                # serving lease: the worst grant an OLD generation could
                # still be serving under — failover sits this out (plus
                # skew) before flipping client routing
                "lease_remaining_ms": self._stale_lease_remaining_ms(
                    int(c.node_generation)
                ),
            }
            if self._promoted_walsender is not None:
                out["wal_port"] = self._promoted_walsender.port
            return out

    def _repoint(self, msg: dict) -> dict:
        """Post-failover resync: re-point this standby's walreceiver at
        the promoted node's walsender and re-stream from our own
        offset (truncating any torn tail first — the restart/resync
        walreceiver contract). The ha_generation record arrives over
        the new stream and advances our WAL-learned generation."""
        self._failpoint("dn/repoint")
        with self._promote_mu:
            # guarded: a repoint racing this node's own promotion RPC
            # must see the published role, not a half-built one
            promoted = self._promoted_srv
        if promoted is not None:
            return {"error": "node is a promoted coordinator; "
                             "it does not follow anyone"}
        host = str(msg.get("wal_host") or "127.0.0.1")
        port = int(msg["wal_port"])
        try:
            from opentenbase_tpu.storage.replication import (
                probe_timeline,
            )

            _gen, promote_lsn = probe_timeline(host, port)
            if 0 <= promote_lsn < int(self.standby.applied):
                # diverged survivor: a still-live deposed primary
                # streamed frames here AFTER the promotion point, so
                # our WAL holds bytes the new timeline does not —
                # offset-based streaming would silently fork (and the
                # ha_generation record would never arrive). Rewind:
                # truncate to the promotion point, rebuild the stores
                # from the truncated log, re-stream (pg_rewind for a
                # surviving standby, not just the ex-primary).
                return self._repoint_rewind(host, port, promote_lsn)
            self.standby.restart_replication(host, port)
        except Exception as e:
            self.log_ring.emit(
                "error", "ha",
                f"repoint to {host}:{port} failed: {e}",
            )
            return {"error": f"repoint failed: {type(e).__name__}: {e}"}
        self._bump("repoints")
        self.log_ring.emit(
            "warning", "ha",
            f"walreceiver re-pointed at {host}:{port} "
            f"(resumed from {self.standby.applied})",
        )
        return {"ok": True, "applied": self.standby.applied}

    def _repoint_rewind(self, host: str, port: int,
                        promote_lsn: int) -> dict:
        """Rewind a diverged survivor onto the promoted timeline:
        stop the old stream, release the old cluster's file handles,
        and rebuild through rejoin_standby — which truncates the WAL
        at the promotion point, drops any checkpoint taken past it,
        replays the truncated log into fresh stores (discarding the
        dead timeline's applied rows), and re-streams."""
        from opentenbase_tpu.storage.replication import rejoin_standby

        old = self.standby
        rewound = int(old.applied) - int(promote_lsn)
        try:
            old.stop()
            if old._thread is not None:
                old._thread.join(timeout=5)
        except Exception as e:
            # best-effort: a receiver thread that will not die cleanly
            # must not block the rewind — the rebuild below replaces it
            self.log_ring.emit(
                "warning", "ha",
                f"rewind: old walreceiver stop failed: {e}",
            )
        try:
            old.cluster.close()
        except Exception as e:
            # best-effort: the truncate reopens the WAL file anyway
            self.log_ring.emit(
                "warning", "ha",
                f"rewind: old cluster close failed: {e}",
            )
        try:
            sb = rejoin_standby(
                self._data_dir, host, port,
                self._num_datanodes, self._shard_groups,
            )
        except Exception as e:
            self.log_ring.emit(
                "error", "ha",
                f"repoint rewind to {host}:{port} failed: {e}",
            )
            return {
                "error": f"repoint rewind failed: "
                         f"{type(e).__name__}: {e}",
            }
        sb.cluster.log = self.log_ring
        sb.stream_txn_hook = self._on_stream_txn
        self.standby = sb
        self._bump("repoints")
        self._bump("repoint_rewinds")
        self.log_ring.emit(
            "warning", "ha",
            f"diverged survivor rewound {rewound} bytes to promotion "
            f"point {promote_lsn} and re-pointed at {host}:{port}",
        )
        return {"ok": True, "applied": sb.applied, "rewound": rewound}

    def _revive(self) -> None:
        """Undo an injected crash: reopen the listener on the same port
        and accept again (the chaos harness's process respawn)."""
        if not self._crashed:
            return
        self._lsock = socket.socket()
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind((self.host, self.port))
        self._lsock.listen(32)
        self._crashed = False
        self._accept = threading.Thread(
            target=self._accept_loop, daemon=True
        )
        self._accept.start()
        self._bump("revives")
        self.log_ring.emit(
            "log", "fault",
            f"datanode revived: listening again on {self.port}",
        )

    def _wait_applied(
        self, lsn: int, timeout_s: float = 90.0, cancelled=None
    ) -> bool:
        t0 = time.time()
        while time.time() - t0 < timeout_s:
            if self.standby.applied >= lsn:
                return True
            if cancelled is not None and cancelled():
                return False
            time.sleep(0.002)
        return False

    def _query(self, msg: dict) -> dict:
        """Serve one read-only statement from this node's hot standby
        (the replica-read plane's wire shape). ``min_lsn`` is the
        caller's read-your-writes floor: replay must reach it before
        the snapshot is taken — the same wait exec_fragment applies for
        remote_apply, re-checked here against the LIVE replay position
        rather than the router's possibly stale ack table."""
        from opentenbase_tpu.engine import SQLError

        min_lsn = int(msg.get("min_lsn", 0))
        if min_lsn and not self._wait_applied(min_lsn, timeout_s=10.0):
            return {
                "error": (
                    f"replication lag: replica read floor {min_lsn} not "
                    f"reached (applied {self.standby.applied})"
                ),
                "sqlstate": "72001",
            }
        self._failpoint("dn/query")
        try:
            res = self.standby.session().execute(str(msg.get("sql", "")))
        except SQLError as e:
            return {"error": str(e), "sqlstate": e.sqlstate}
        self._bump("replica_reads")
        return {
            "ok": True,
            "tag": res.command,
            "columns": list(res.columns),
            "rows": [list(r) for r in res.rows],
            "rowcount": res.rowcount,
            "applied": self.standby.applied,
        }

    def _exec_fragment(self, msg: dict) -> dict:
        node = int(msg["node"])
        with self._stats_mu:
            self._inflight += 1
        ctx = _tctx.current()
        t0 = time.time() if ctx is not None else 0.0
        rows = None
        try:
            out = self._exec_fragment_inner(msg, node)
            rows = out.get("rows") if isinstance(out, dict) else None
            return out
        finally:
            if ctx is not None:
                self.span_ring.record(
                    ctx, "exec_fragment", "fragment", t0, time.time(),
                    node=node, rows=rows,
                )
            with self._stats_mu:
                self._inflight -= 1

    def _exec_fragment_inner(self, msg: dict, node: int) -> dict:
        from opentenbase_tpu.executor.local import LocalExecutor
        from opentenbase_tpu.plan import serde

        self._failpoint("dn/exec_fragment", node=node)
        # the coordinator's abandon message (cancel_fragment) is keyed
        # by this token; cancelled() is polled at every batch/operator
        # boundary below and inside LocalExecutor
        token = msg.get("cancel_token")

        def cancelled() -> bool:
            # otb_race: ignore[race-guard-mismatch] -- lock-free poll at every operator boundary; dict membership is GIL-atomic and a missed-by-one-poll cancel lands at the next boundary
            return token is not None and token in self._cancelled

        def cancel_check() -> None:
            if cancelled():
                raise FragmentCancelled(
                    "fragment canceled by coordinator"
                )

        min_lsn = int(msg.get("min_lsn", 0))
        if min_lsn:
            # a real WAL wait (replay behind the coordinator's write
            # position) is trace-visible: the remote_apply stall shows
            # on the query's cross-node critical path, not just as
            # mystery latency. Recorded only when we actually parked —
            # the caught-up fast path records nothing.
            ctx = _tctx.current()
            waited_from = (
                time.time()
                if ctx is not None and self.standby.applied < min_lsn
                else None
            )
            ok = self._wait_applied(min_lsn, cancelled=cancelled)
            if waited_from is not None:
                self.span_ring.record(
                    ctx, "wal_wait", "wal", waited_from, time.time(),
                    min_lsn=min_lsn, applied=self.standby.applied,
                )
            if not ok:
                if cancelled():
                    self._bump("fragments_cancelled")
                    return {"error": "fragment canceled by coordinator"}
                return {
                    "error": "replication lag: wal position not reached"
                }
        from opentenbase_tpu import types as t

        plan = serde.loads_plan(msg["plan"])
        snapshot_ts = msg.get("snapshot_ts")
        c = self.standby.cluster
        inputs = {
            int(k): serde.batch_from_wire(v, c.catalog)
            for k, v in (msg.get("inputs") or {}).items()
        }
        # peer-exchanged inputs: wait for every producer DN's pushed
        # partition (the consumer side of the squeue data plane) —
        # OUTSIDE the exec lock so redo apply keeps flowing while we
        # wait on peers
        try:
            for k, spec in (msg.get("exchanges") or {}).items():
                cancel_check()  # between batch waits
                parts = self._exch_wait(
                    spec["xid"], node, spec.get("producers") or [],
                    cancelled=cancelled,
                )
                if parts is None:
                    cancel_check()
                    return {"error": f"exchange {spec['xid']} timed out"}
                from opentenbase_tpu.executor.dist import concat_batches

                inputs[int(k)] = concat_batches([
                    serde.batch_from_wire(p, c.catalog) for p in parts
                ])
            subquery_values = [
                (v, t.SqlType(t.TypeId(ty[0]), ty[1], ty[2]))
                for v, ty in (msg.get("subquery_values") or [])
            ]
            # execute under the standby's statement lock so redo apply
            # never interleaves with a fragment read (recovery-conflict
            # interlock)
            with c._exec_lock:
                cancel_check()
                out = None
                ex = None
                K = int(msg.get("parallel", 1))
                if K > 1:
                    # within-fragment parallel scan+partial-agg over row
                    # blocks (execParallel.c:565); None = shape/size does
                    # not qualify, fall through to the serial path
                    from opentenbase_tpu.executor.local import (
                        run_fragment_parallel,
                    )

                    out = run_fragment_parallel(
                        c.catalog, c.stores.get(node, {}), snapshot_ts,
                        plan, inputs, subquery_values, K,
                        cancel_check=(
                            cancel_check if token is not None else None
                        ),
                        fold_on_read=not msg.get("delta_scan", True),
                    )
                    if out is not None:
                        self._bump("parallel_fragments")
                if out is None:
                    ex = LocalExecutor(
                        c.catalog,
                        c.stores.get(node, {}),
                        snapshot_ts,
                        remote_inputs=inputs,
                        subquery_values=subquery_values,
                        cancel_check=(
                            cancel_check if token is not None else None
                        ),
                        fold_on_read=not msg.get("delta_scan", True),
                    )
                    out = ex.run_plan(plan)
            mo = msg.get("motion")
            if mo is not None:
                # producer side: partition + push peer-to-peer; the
                # coordinator gets control-plane info only (row count)
                cancel_check()
                self._motion_push(out, mo, node, plan)
                return {
                    "ok": True, "rows": out.nrows,
                    "pruned_blocks": getattr(ex, "zone_pruned_blocks", 0),
                    "total_blocks": getattr(ex, "zone_total_blocks", 0),
                }
            cancel_check()
            return {
                "batch": serde.batch_to_wire(out, plan.schema),
                "pruned_blocks": getattr(ex, "zone_pruned_blocks", 0),
                "total_blocks": getattr(ex, "zone_total_blocks", 0),
            }
        except FragmentCancelled:
            self._bump("fragments_cancelled")
            return {"error": "fragment canceled by coordinator"}
        finally:
            if token is not None:
                with self._cancel_mu:
                    self._cancelled.pop(token, None)

def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--wal-host", required=True)
    ap.add_argument("--wal-port", type=int, required=True)
    ap.add_argument("--listen-port", type=int, default=0)
    ap.add_argument("--num-datanodes", type=int, default=2)
    ap.add_argument("--shard-groups", type=int, default=256)
    ap.add_argument(
        "--metrics-port", type=int, default=0,
        help="OpenMetrics exporter port (0 = no listener)",
    )
    args = ap.parse_args(argv)
    host_side_role()
    srv = DNServer(
        args.data_dir, args.wal_host, args.wal_port,
        args.num_datanodes, args.shard_groups, port=args.listen_port,
        metrics_port=args.metrics_port,
    ).start()
    print(f"READY {srv.port}", flush=True)
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        srv.stop()


if __name__ == "__main__":
    sys.exit(main())
