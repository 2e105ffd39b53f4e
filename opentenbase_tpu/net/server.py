"""Coordinator TCP front end — the tcop/postmaster analog.

The reference's postmaster forks a backend per connection, each running
the tcop message loop (src/backend/tcop/postgres.c:4792 PostgresMain).
Here the coordinator runs one thread per connection, each owning a
``Session`` against the shared in-process cluster — same session
semantics (GUCs, open transaction) per connection, same single shared
data plane underneath.

Statement execution from concurrent connections is serialized through the
cluster's executor lock: the engine's store mutation paths assume one
writer at a time (the reference gets this from per-tuple locking +
MVCC; a columnar batch engine takes the coarser lock and relies on
snapshot isolation for readers).
"""

from __future__ import annotations

import socket
import threading
from typing import Optional

from opentenbase_tpu.fault import FAULT, FaultDropConnection
from opentenbase_tpu.net.protocol import (
    encode_frame,
    recv_frame,
    recv_frame_body,
    recv_frame_header,
    send_encoded,
    send_frame,
    shutdown_and_close,
)
from opentenbase_tpu.obs import tracectx as _tctx
from opentenbase_tpu.obs.trace import span as _span


def _walk_ast(node):
    """Generic AST walk over dataclass fields (expressions only)."""
    import dataclasses

    yield node
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            if isinstance(v, (list, tuple)):
                for x in v:
                    if dataclasses.is_dataclass(x):
                        yield from _walk_ast(x)
            elif dataclasses.is_dataclass(v):
                yield from _walk_ast(v)


class ClusterServer:
    def __init__(
        self,
        cluster,
        host: str = "127.0.0.1",
        port: int = 0,
        ssl_cert: Optional[str] = None,
        ssl_key: Optional[str] = None,
    ):
        self.cluster = cluster
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind((host, port))
        self._lsock.listen(64)
        self.host, self.port = self._lsock.getsockname()
        self._stop = threading.Event()
        self._accept_thread: Optional[threading.Thread] = None
        self._conn_threads: list[threading.Thread] = []
        # raw accepted sockets of live backends, force-closed on stop()
        self._conns: set = set()
        # engine-wide statement lock (owned by the Cluster; see docstring)
        self._exec_lock = cluster._exec_lock
        # TLS (be-secure.c): explicit ctor args win, else the ssl* GUCs
        # from <data_dir>/opentenbase.conf. With a context set, EVERY
        # accepted socket must complete the handshake — a plaintext
        # client is dropped at accept, so credentials and data never
        # cross the wire unencrypted.
        self._ssl_ctx = None
        conf = getattr(cluster, "conf_gucs", {}) or {}
        if ssl_cert is None and conf.get("ssl"):
            ssl_cert = conf.get("ssl_cert_file") or None
            ssl_key = conf.get("ssl_key_file") or None
            if not ssl_cert:
                # ssl=on without a certificate must REFUSE to start —
                # silently serving plaintext while the operator believes
                # TLS is enforced is the one unacceptable outcome
                # (postmaster.c refuses the same misconfiguration)
                raise ValueError(
                    "ssl = on requires ssl_cert_file in opentenbase.conf"
                )
        if ssl_cert:
            import ssl as _ssl

            ctx = _ssl.SSLContext(_ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(ssl_cert, ssl_key or None)
            self._ssl_ctx = ctx

    # -- lifecycle -------------------------------------------------------
    def start(self) -> "ClusterServer":
        t = threading.Thread(target=self._accept_loop, daemon=True)
        t.start()
        self._accept_thread = t
        return self

    def stop(self) -> None:
        self._stop.set()
        shutdown_and_close(self._lsock)
        # join the accept loop first so _conn_threads cannot grow while
        # we iterate a snapshot of it
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5)
        # force-disconnect live backends: a client that never sends its
        # close frame must not hold shutdown hostage (the postmaster
        # SIGTERMs its backends on smart shutdown for the same reason)
        for c in list(self._conns):
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        for t in list(self._conn_threads):
            t.join(timeout=5)

    def __enter__(self) -> "ClusterServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- loops -----------------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _addr = self._lsock.accept()
            except OSError:
                return  # listener closed
            try:
                # failpoint: a coordinator refusing/dropping new backends
                # (drop_conn closes the just-accepted socket; the accept
                # loop itself must survive any injected action)
                FAULT("net/server/accept")
            except Exception:
                try:
                    conn.close()
                except OSError:
                    pass
                continue
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._conns.add(conn)
            t = threading.Thread(
                target=self._serve_conn, args=(conn,), daemon=True
            )
            t.start()
            # prune finished backends so a long-lived coordinator doesn't
            # accumulate one dead Thread per connection ever served
            self._conn_threads = [
                x for x in self._conn_threads if x.is_alive()
            ]
            self._conn_threads.append(t)

    def _serve_conn(self, conn: socket.socket) -> None:
        from opentenbase_tpu.fault import set_thread_actor

        # every wire op this backend performs on the client's behalf
        # (fragment ships, sync-commit pings, lease-era DN RPCs) must
        # travel under the COORDINATOR'S name in the partition matrix —
        # a cut of cn0's egress has to sever work done FOR a client,
        # not just the CN's own background threads
        set_thread_actor(
            getattr(self.cluster, "coordinator_name", "cn0") or "cn0"
        )
        raw = conn  # the accepted socket registered in _conns
        if self._ssl_ctx is not None:
            # the handshake runs HERE, in the per-connection thread,
            # with a timeout — a silent client must never stall the
            # accept loop (be-secure.c does its handshake in the forked
            # backend for the same reason)
            try:
                conn.settimeout(10.0)
                conn = self._ssl_ctx.wrap_socket(conn, server_side=True)
                conn.settimeout(None)
            except Exception:
                # plaintext (or bad, or stalled) client against a
                # TLS-required server: reject at the handshake
                try:
                    conn.close()
                except OSError:
                    pass
                self._conns.discard(raw)
                return
            # wrap_socket() detached the raw fd — re-register the live
            # SSLSocket or stop()'s force-disconnect would shut down a
            # dead fd and never wake this backend
            self._conns.discard(raw)
            self._conns.add(conn)
            raw = conn
        session = self.cluster.session()
        # trust mode only while no users exist (pg_hba 'trust' vs
        # 'scram-sha-256'); once any role is created, the handshake is
        # mandatory before the first statement
        authed = not self.cluster.users
        try:
            while not self._stop.is_set():
                # the wait for the next request ends with its length
                # header: ``wire.request`` runs from here to the
                # reply's send returning (obs/trace.span)
                length = recv_frame_header(conn)
                if length is None:
                    break
                more, authed = self._serve_request(
                    conn, session, length, authed
                )
                if not more:
                    break
        except OSError:
            # the socket died under us — client vanished mid-frame, or
            # stop() force-disconnected this backend while a statement
            # was in flight; either way exit quietly, cleanup below
            pass
        finally:
            # abort any transaction left open by a dropped connection
            # (the backend-exit cleanup of the reference's tcop loop)
            self._conns.discard(raw)
            self._conn_cleanup(session, conn)

    def _ping_reply(self) -> dict:
        """Liveness probe (ha.py failure detector): answered before
        auth — a heartbeat must not need credentials — and carries the
        fencing generation + live role so a probe doubles as a health
        row."""
        c = self.cluster
        if getattr(c, "ha_demoted", False):
            role = "fenced"
        elif c.read_only:
            # a streaming peer coordinator (coord/peer.py)
            # is read_only like a hot standby but serves a
            # different contract (local reads + forwarded
            # writes) — the probe must say which it is
            role = (
                getattr(c, "coordinator_role", "")
                or "standby"
            )
            if role == "coordinator":
                role = "standby"
        else:
            role = "coordinator"
        rec = getattr(c, "catalog_receiver", None)
        # serving lease (ha.ServingLease): validity rides
        # the probe so pg_cluster_health peer rows show a
        # self-demoted CN without extra protocol
        lease = getattr(c, "serving_lease", None)
        lease_ms = (
            lease.remaining_ms() if lease is not None else -1
        )
        return {
            "ok": True,
            "role": role,
            "generation": int(
                getattr(c, "node_generation", 0)
            ),
            "lease_valid": (
                lease is None or lease_ms > 0
            ),
            "lease_remaining_ms": lease_ms,
            # multi-CN health surface: the probed node's
            # catalog epoch + stream-applied offset let the
            # primary render per-coordinator rows (and lag)
            # from one probe, no extra protocol
            "catalog_epoch": int(c.catalog_epoch),
            "applied": int(
                rec.applied if rec is not None
                else (
                    c.persistence.wal.position
                    if c.persistence else 0
                )
            ),
        }

    def _serve_request(self, conn, session, length: int, authed: bool):
        """One request, from its length header (already read) to the
        reply's send returning: the ``wire.request`` span. Returns
        (keep serving, authed). With ``trace_queries`` on the server
        owns the statement's QueryTrace — ``wire.request`` is its root
        and ``Session.execute`` adopts it (``query`` nests inside)."""
        trace = None
        if session.gucs.get("trace_queries") and session._trace is None:
            trace = self.cluster.tracer.start("", session.session_id)
            session._trace = trace
        req = _span(
            session, "wire.request", cat="wire", record=False,
            span_id=None if trace is None else trace.ctx.span_id,
            bytes_in=length + 4,
        )
        exec_ms = None  # set by a statement: the others publish nothing
        req.__enter__()
        try:
            # failpoint: a request torn between its header and its body
            FAULT("net/server/request")
            with _span(session, "wire.decode", cat="wire"):
                msg = recv_frame_body(conn, length)
            if msg is None:
                return False, authed
            op = msg.get("op")
            if op == "close":
                send_frame(conn, {"ok": True})
                return False, authed
            if op == "ping":
                send_frame(conn, self._ping_reply())
            elif op == "auth":
                authed = self._scram_exchange(conn, msg)
                if authed:
                    # the proven identity drives role-based WLM
                    # bindings and audit attribution
                    session.user = str(msg.get("user", session.user))
            elif not authed:
                send_frame(
                    conn,
                    {"error": "AuthError: authentication required"},
                )
            elif msg.get("q") is None:
                send_frame(conn, {"error": "malformed request"})
            else:
                if trace is not None:
                    trace.query = msg["q"].strip()
                exec_ms = self._serve_statement(conn, session, msg, req)
            return True, authed
        finally:
            req.__exit__(None, None, None)
            if trace is not None:
                session._trace = None
            if exec_ms is not None:
                # what the request took beyond Session.execute: the
                # statement's ledger closed before the reply was sent,
                # so the wire's share lives in pg_stat_query_phases
                self.cluster.metrics.histogram("phase.wire").record(
                    max(req.ms - exec_ms, 0.0)
                )
                if trace is not None:
                    self.cluster.tracer.finish(
                        trace, root="wire.request", **req.args()
                    )

    def _serve_statement(self, conn, session, msg: dict, req) -> float:
        """Execute ``msg["q"]`` and send its reply, each leg a span:
        ``wire.lock_wait`` (classify + acquire the statement lock),
        the session's own ``query``, ``wire.encode``, ``wire.send``.
        Returns the milliseconds ``Session.execute`` took."""
        import contextlib
        import time

        sql = msg["q"]
        # cross-node tracing: a ``_trace`` header from the client binds
        # for the statement (obs/tracectx.py), so work this server fans
        # out parents to the caller's span; a trace this server owns
        # binds over it, as Session.execute does for its own
        _hdr = msg.get("_trace")
        own = req._trace
        bound = own is not None or bool(_hdr)
        _prev_ctx = None
        if bound:
            _prev_ctx = _tctx.bind(
                own.ctx if own is not None else _tctx.from_header(_hdr)
            )
        exec_ms = 0.0
        try:
            # failpoint: statement dispatch. drop_conn tears the
            # backend down mid-protocol (client sees a vanished
            # server); error surfaces as an 'E' frame like any
            # engine error
            FAULT("net/server/dispatch")
            with contextlib.ExitStack() as held:
                # read-only statements share the data plane (MVCC
                # snapshots isolate them from each other); writes,
                # DDL, and anything uncertain take it exclusively —
                # the statement-level analog of the reference's
                # lock-free MVCC readers
                with _span(session, "wire.lock_wait", cat="wire") as lsp:
                    kind, wt = self._classify(sql, session)
                    lsp.set(kind=kind)
                    if kind == "read":
                        guard = self._exec_lock.read()
                    elif kind == "write":
                        # plain autocommit DML: writers on DISJOINT
                        # tables share the data plane (per-table
                        # mutexes serialize same-table writers); DDL
                        # and explicit transactions stay exclusive
                        guard = self._exec_lock.write_tables(wt)
                    else:
                        guard = self._exec_lock
                    held.enter_context(guard)
                t0 = time.perf_counter()
                try:
                    res = session.execute(sql)
                finally:
                    exec_ms = (time.perf_counter() - t0) * 1000.0
            reply = {
                "tag": res.command,
                "columns": res.columns,
                "rows": [list(r) for r in res.rows],
                "rowcount": res.rowcount,
                # WAL end after the statement: the causal
                # token a forwarding peer CN waits on so a
                # read after its own (forwarded) write is
                # never stale (read-your-writes across CNs)
                "wal_pos": int(
                    self.cluster.persistence.wal.position
                ) if self.cluster.persistence else 0,
            }
            req.set(rows=len(res.rows))
        except FaultDropConnection:
            raise  # sever this backend like a real peer reset
        except Exception as e:  # otb_lint: ignore[except-swallow] -- not a swallow: the error is delivered to the client as an error frame below, and Session.execute already elog'd it at level error
            reply = {"error": f"{type(e).__name__}: {e}"}
            sqlstate = getattr(e, "sqlstate", None)
            if sqlstate:  # 53xxx sheds, 57014 timeouts, ...
                reply["sqlstate"] = sqlstate
        finally:
            if bound:
                _tctx.bind(_prev_ctx)
        with _span(session, "wire.encode", cat="wire"):
            data = encode_frame(reply)
        req.set(bytes_out=len(data))
        with _span(session, "wire.send", cat="wire"):
            send_encoded(conn, data)
        return exec_ms

    def _classify(self, sql: str, session, stmts=None):
        """ONE parse classifying the statement's lock class (callers
        that already parsed — the concentrator's pin detection — pass
        ``stmts`` to skip re-parsing):

        - ("read", None): a single plain SELECT (no FOR UPDATE) outside
          a transaction, referencing no system view (their refresh
          materializes tables), no view (whose expansion could), and
          calling no state-mutating function — shares the data plane
          with other readers (MVCC snapshots isolate them).
        - ("write", tables): plain autocommit DML on known, plain,
          non-partitioned tables with no subqueries — shares the data
          plane with writers on DISJOINT tables.
        - ("excl", None): everything else — DDL, explicit transactions,
          anything uncertain, parse errors (which then surface from the
          normal execution path)."""
        if session.txn is not None:
            return "excl", None
        try:
            from opentenbase_tpu.engine import _SYSTEM_VIEWS
            from opentenbase_tpu.sql import ast as A
            from opentenbase_tpu.sql.parser import parse

            if stmts is None:
                stmts = parse(sql)
            if len(stmts) != 1:
                return "excl", None
            st = stmts[0]
            if isinstance(st, A.Select):
                if st.for_update is not None:
                    return "excl", None
                refs: set = set()
                session._referenced_tables(st, refs)
                if refs & set(_SYSTEM_VIEWS):
                    return "excl", None
                if refs & set(self.cluster.views):
                    return "excl", None
                # FROM-less admin/sequence calls mutate state
                # (clean_2pc, deadlock victims, FGA policies, nextval)
                mutating = set(session._ADMIN_FUNCS) | set(
                    session._SEQ_FUNCS
                )
                for item in st.items:
                    for node in _walk_ast(item.expr):
                        if isinstance(node, A.FuncCall) and (
                            node.name in mutating
                        ):
                            return "excl", None
                return "read", None
            if isinstance(st, (A.Insert, A.Update, A.Delete)):
                refs = {st.table}
                if isinstance(st, A.Insert) and st.query is not None:
                    session._referenced_tables(st.query, refs)
                # a subquery anywhere else (WHERE/SET/VALUES) reads
                # tables this walk can't see: classify exclusive
                for node in _walk_ast(st):
                    if isinstance(
                        node,
                        (
                            A.InSubquery,
                            A.ExistsSubquery,
                            A.ScalarSubquery,
                        ),
                    ):
                        return "excl", None
                cat = self.cluster.catalog
                for tb in refs:
                    if not cat.has(tb):
                        return "excl", None
                    if tb in self.cluster.partitions:
                        return "excl", None
                    if tb in self.cluster.views:
                        return "excl", None
                    meta = cat.get(tb)
                    if getattr(meta, "foreign", None) is not None:
                        return "excl", None
                return "write", refs
            if isinstance(st, A.MoveData):
                # MOVE DATA holds its own per-shard barrier and takes a
                # brief exclusive acquire only for the ownership flip —
                # readers of non-moving shards overlap the copy phase
                # (shardbarrier.c semantics; VERDICT r4 ask #7). The
                # writer-class slot serializes it against same-table
                # writers through the engine's barrier gate instead of
                # fencing out every reader.
                return "write", set()
            return "excl", None
        except Exception:  # otb_lint: ignore[except-swallow] -- by design: any statement the classifier cannot parse/prove classes as exclusive, and the parse error (if real) surfaces from the normal execution path a moment later
            return "excl", None

    def _is_readonly(self, sql: str, session) -> bool:
        """Back-compat shim over _classify (tests use it)."""
        return self._classify(sql, session)[0] == "read"

    def _scram_exchange(self, conn: socket.socket, msg: dict) -> bool:
        """Server half of the SCRAM flow (net/auth.py). Returns True
        when the client proved knowledge of the password. A fake salt
        is served for unknown users so the flow does not leak which
        roles exist (auth.c's mock authentication)."""
        import hashlib
        import secrets

        from opentenbase_tpu.net import auth as sa

        # failpoint: the server half of the SCRAM exchange (a client
        # vanishing mid-handshake must leave no half-authed backend)
        FAULT("net/server/scram")
        user = str(msg.get("user", ""))
        client_nonce = str(msg.get("client_nonce", ""))
        verifier = self.cluster.users.get(user)
        if verifier is None:
            # fake salt must be stable per user but NOT publicly
            # computable, or comparing it against sha256(user) would
            # reveal which roles exist — key it with a per-cluster secret
            import hmac as _hmac
            import os as _os

            secret = getattr(self.cluster, "_mock_salt_secret", None)
            if secret is None:
                secret = _os.urandom(16)
                self.cluster._mock_salt_secret = secret
            fake_salt = _hmac.new(
                secret, user.encode(), hashlib.sha256
            ).hexdigest()[:32]
            verifier = {
                "salt": fake_salt,
                "iterations": sa.ITERATIONS,
                "stored_key": "00" * 32,
                "server_key": "00" * 32,
            }
        nonce = client_nonce + secrets.token_hex(16)
        send_frame(conn, {
            "salt": verifier["salt"],
            "iterations": verifier["iterations"],
            "nonce": nonce,
        })
        reply = recv_frame(conn)
        if reply is None or reply.get("op") != "proof":
            send_frame(conn, {"error": "AuthError: handshake aborted"})
            return False
        authmsg = sa.auth_message(
            user, client_nonce, nonce, verifier["salt"]
        )
        # the all-zero fake verifier can never validate, so the check is
        # uniform for real and unknown users (no early-exit timing tell)
        if sa.verify_proof(
            verifier, str(reply.get("proof", "")), authmsg
        ):
            send_frame(conn, {
                "ok": True,
                "server_sig": sa.server_signature(verifier, authmsg),
            })
            return True
        send_frame(
            conn,
            {"error": f'AuthError: authentication failed for "{user}"'},
        )
        return False

    def _conn_cleanup(self, session, conn) -> None:
        if session.txn is not None:
            try:
                with self._exec_lock:
                    session.execute("rollback")
            except Exception as e:
                # never silent: the orphaned txn is now the in-doubt
                # machinery's problem, and the log says why
                self.cluster.log.emit(
                    "warning", "session",
                    f"rollback on disconnect failed: {e!r:.200}",
                    session=session.session_id,
                )
        # release any WLM slot and leave pg_stat_cluster_activity NOW —
        # a dropped connection must not linger as a phantom session
        session.close()
        try:
            conn.close()
        except OSError:
            pass
