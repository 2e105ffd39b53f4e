"""pgwire session concentrator — the poolmgr.c / pgbouncer analog.

The reference dedicates an entire pooler process to this problem
(``poolmgr.c``, SURVEY §2.1): "millions of users" means tens of
thousands of client connections, and a backend per connection
(net/pgwire.py's thread-per-connection front end) does not survive
that. The concentrator accepts any number of client connections on ONE
event-driven acceptor (a ``selectors`` loop owning every client
socket) and multiplexes their statements over a BOUNDED pool of
backend ``Session``s driven by a small worker-thread pool — so 10 000
idle connections cost 10 000 sockets and ~nothing else.

Pooling mode is pgbouncer's *transaction pooling* with session
pinning, strict about the cases transaction pooling classically
breaks:

- ``BEGIN`` pins the client to one backend session until COMMIT/
  ROLLBACK returns it to the pool;
- ``SET``/``RESET``, ``PREPARE``/``DEALLOCATE`` pin for the rest of
  the connection (session state must not leak to — or from — other
  clients); a state-pinned session is RETIRED when its client leaves,
  never returned to the pool carrying foreign GUCs;
- everything else runs on any free backend.

Statements execute through ``Session.execute`` and therefore pass WLM
admission exactly like every other front end — shed/queue semantics
(SQLSTATE 53xxx / 57014) are preserved and ride the wire as 'E'
messages. When every backend is pinned-or-busy and the statement
queue is full, the concentrator itself sheds with SQLSTATE 53300
(too_many_connections), pgbouncer's "no more connections allowed".

Protocol surface: startup / SSLRequest refusal / SCRAM-SHA-256 (the
shared RFC 5802 core in net/pgwire.py, driven here as a non-blocking
state machine) / simple query 'Q' / Sync / Terminate. The extended
query protocol is answered with SQLSTATE 0A000 — like pgbouncer's
statement mode, drivers must use simple queries through the
concentrator (the per-connection pgwire front end keeps full
extended-protocol support).
"""

from __future__ import annotations

import selectors
import socket
import struct
import threading
import queue as _queue
from typing import Optional

from opentenbase_tpu.analysis.racewatch import shared_state
from opentenbase_tpu.fault import FAULT, FaultDropConnection, FaultError
from opentenbase_tpu.net.pgwire import (
    _Conn,
    emit_result,
    scram_server_first,
    scram_verify_final,
)
from opentenbase_tpu.net.protocol import shutdown_and_close

_PROTO_V3 = 196608
_SSL_REQUEST = 80877103
_CANCEL_REQUEST = 80877102
_GSSENC_REQUEST = 80877104

_CLOSE_JOB = "__close__"


class _Client:
    """One multiplexed client connection (no backend of its own)."""

    __slots__ = (
        "sock", "conn", "buf", "buf_lock", "state", "user", "sasl",
        "pinned", "state_pinned", "busy", "lock", "closed",
    )

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self.conn = _Conn(sock)
        self.buf = bytearray()
        # buffer appends take THIS lock only — never cl.lock, which a
        # worker may hold across a sendall to a slow reader; the
        # selector thread must never block behind a network write
        self.buf_lock = threading.Lock()
        self.state = "startup"
        self.user = ""
        self.sasl: Optional[dict] = None
        self.pinned = None          # Session while pinned
        self.state_pinned = False   # SET/PREPARE happened: pin for life
        self.busy = False           # a statement is in flight
        self.lock = threading.RLock()
        self.closed = False


@shared_state("_mu")
class PgConcentrator:
    """Event-driven pgwire front end over a bounded Session pool."""

    def __init__(
        self,
        cluster,
        host: str = "127.0.0.1",
        port: int = 0,
        backends: int = 8,
        queue_depth: int = 256,
        queue_timeout_s: float = 10.0,
    ):
        self.cluster = cluster
        self.backends = max(int(backends), 1)
        self.queue_depth = max(int(queue_depth), 0)
        self.queue_timeout_s = float(queue_timeout_s)
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind((host, port))
        self._lsock.listen(1024)
        self._lsock.setblocking(False)
        self.host, self.port = self._lsock.getsockname()
        self._sel = selectors.DefaultSelector()
        self._sel.register(self._lsock, selectors.EVENT_READ, None)
        self._stop = threading.Event()
        self._exec_lock = cluster._exec_lock
        # the bounded backend pool: K Sessions shared by every client
        self._free: "_queue.Queue" = _queue.Queue()
        for _ in range(self.backends):
            self._free.put(cluster.session())
        # unbounded job queue; the STATEMENT backlog is bounded by
        # _queued against queue_depth (close jobs must never shed)
        self._jobs: "_queue.Queue" = _queue.Queue()
        self._mu = threading.Lock()
        self._queued = 0
        self._clients: set = set()
        self.stats = {
            "clients_total": 0, "statements": 0, "sheds": 0,
            "errors": 0, "pinned": 0,
        }
        self._threads: list[threading.Thread] = []

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "PgConcentrator":
        t = threading.Thread(target=self._loop, daemon=True)
        t.start()
        self._threads.append(t)
        for _ in range(self.backends):
            w = threading.Thread(target=self._worker, daemon=True)
            w.start()
            self._threads.append(w)
        self.cluster._concentrator = self
        return self

    def stop(self) -> None:
        self._stop.set()
        shutdown_and_close(self._lsock)
        for _ in range(self.backends):
            self._jobs.put(None)  # worker sentinels
        for t in self._threads:
            t.join(timeout=5)
        # snapshot-and-clear under the lock: a timed-out join above
        # means the selector/worker threads may still be mid-_teardown,
        # and iterating the live set while they discard from it races
        # (set-changed-during-iteration, or a client severed twice)
        with self._mu:
            clients = list(self._clients)
            self._clients.clear()
        for cl in clients:
            cl.closed = True
            shutdown_and_close(cl.sock)
            sess = cl.pinned
            cl.pinned = None
            if sess is not None:
                self._recycle(sess, retire=True)
        try:
            self._sel.close()
        except OSError:
            pass
        while True:
            try:
                sess = self._free.get_nowait()
            except _queue.Empty:
                break
            sess.close()
        if self.cluster._concentrator is self:
            self.cluster._concentrator = None

    def __enter__(self) -> "PgConcentrator":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- observability ----------------------------------------------------
    def stat_rows(self) -> list[tuple]:
        with self._mu:
            rows = [
                ("clients", len(self._clients)),
                ("clients_total", self.stats["clients_total"]),
                ("backends", self.backends),
                ("backends_free", self._free.qsize()),
                ("pinned", self.stats["pinned"]),
                ("queued", self._queued),
                ("queue_depth_limit", self.queue_depth),
                ("statements", self.stats["statements"]),
                ("sheds", self.stats["sheds"]),
                ("errors", self.stats["errors"]),
            ]
        return rows

    # -- event loop (the small acceptor) ----------------------------------
    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                events = self._sel.select(timeout=0.2)
            except OSError:
                return  # selector closed under us at stop()
            for key, _mask in events:
                if key.data is None:
                    self._accept_burst()
                else:
                    self._on_readable(key.data)

    def _accept_burst(self) -> None:
        while True:
            try:
                sock, _addr = self._lsock.accept()
            except BlockingIOError:
                return
            except OSError:
                return  # listener closed
            try:
                # failpoint: refusing/dropping clients at the acceptor
                FAULT("net/concentrator/accept")
            except (FaultError, ConnectionError):
                shutdown_and_close(sock)
                continue
            # blocking with a SEND bound: a client that stops reading
            # its responses blocks whichever thread is mid-sendall to
            # it — the timeout converts that from a permanent wedge
            # into a bounded stall that evicts the offender (recv only
            # happens when the selector reports readable, so the
            # timeout never fires on the read side)
            sock.settimeout(30.0)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            cl = _Client(sock)
            with self._mu:
                self._clients.add(cl)
                self.stats["clients_total"] += 1
            try:
                self._sel.register(sock, selectors.EVENT_READ, cl)
            except (OSError, ValueError):
                self._teardown(cl)

    def _on_readable(self, cl: _Client) -> None:
        try:
            # failpoint: a client socket dying / stalling mid-message
            FAULT("net/concentrator/recv")
            data = cl.sock.recv(1 << 16)
        except (OSError, FaultDropConnection):
            self._teardown(cl)
            return
        if not data:
            self._teardown(cl)
            return
        with cl.buf_lock:
            cl.buf += data
        # never BLOCK the selector thread on cl.lock: a worker holding
        # it is mid-response, and its _exec_job finally is guaranteed
        # to re-pump this client once the statement finishes
        if cl.lock.acquire(blocking=False):
            try:
                self._pump(cl)
            finally:
                cl.lock.release()

    # -- per-client protocol state machine --------------------------------
    def _pump(self, cl: _Client) -> None:
        """Consume complete messages from the client's buffer. Runs in
        the selector thread AND in workers (after a statement finishes,
        to drain pipelined queries) — serialized per client. Every
        send issued from here is a small control message (auth, shed,
        Sync, protocol errors), so the socket's send bound is dropped
        for the duration: a client that stops reading can stall this
        thread ~2s at most before it is evicted (result sets are sent
        by workers under the normal 30s bound)."""
        with cl.lock:
            try:
                cl.sock.settimeout(2.0)
            except OSError:
                pass
            try:
                self._pump_inner(cl)
            finally:
                try:
                    cl.sock.settimeout(30.0)
                except OSError:
                    pass

    def _pump_inner(self, cl: _Client) -> None:
        while not cl.closed and not cl.busy:
            if cl.state == "startup":
                if not self._pump_startup(cl):
                    return
                continue
            msg = self._take_message(cl)
            if msg is None:
                return
            tag, body = msg
            try:
                if cl.state in ("sasl_init", "sasl_final"):
                    self._pump_sasl(cl, tag, body)
                else:
                    self._pump_ready(cl, tag, body)
            except (OSError, FaultDropConnection):
                self._teardown(cl)
                return
            except Exception as e:
                # malformed protocol bytes (bad UTF-8, short SASL
                # fields, ...) sever THIS client — they must never
                # reach the selector loop and kill the one thread
                # every connection depends on
                self.cluster.log.emit(
                    "warning", "concentrator",
                    f"protocol error, dropping client: {e!r:.200}",
                )
                self._teardown(cl)
                return

    def _take_message(self, cl: _Client):
        with cl.buf_lock:
            if len(cl.buf) < 5:
                return None
            tag = bytes(cl.buf[:1])
            (ln,) = struct.unpack("!I", bytes(cl.buf[1:5]))
            if ln < 4 or ln > (1 << 26):
                # a length the protocol cannot produce would desync the
                # stream parser (ln=0 re-reads the length bytes as the
                # next tag): sever, never spray garbage errors
                take = None
            elif len(cl.buf) < 1 + ln:
                return None
            else:
                body = bytes(cl.buf[5:1 + ln])
                del cl.buf[:1 + ln]
                take = (tag, body)
        if take is None:
            self._teardown(cl)
            return None
        return take

    def _pump_startup(self, cl: _Client) -> bool:
        """One untagged startup packet; True = made progress."""
        with cl.buf_lock:
            if len(cl.buf) < 4:
                return False
            (ln,) = struct.unpack("!I", bytes(cl.buf[:4]))
            if ln < 8 or ln > (1 << 20):
                bad = True
                body = b""
            elif len(cl.buf) < ln:
                return False
            else:
                bad = False
                body = bytes(cl.buf[4:ln])
                del cl.buf[:ln]
        if bad:
            self._teardown(cl)
            return False
        (code,) = struct.unpack("!I", body[:4])
        try:
            if code in (_SSL_REQUEST, _GSSENC_REQUEST):
                cl.conn.send_raw(b"N")  # no TLS on this listener
                return True
            if code == _CANCEL_REQUEST:
                self._teardown(cl)
                return False
            if code != _PROTO_V3:
                cl.conn.error(
                    f"unsupported frontend protocol {code}", "08P01"
                )
                cl.conn.flush()
                self._teardown(cl)
                return False
            params = {}
            parts = body[4:].split(b"\0")
            for k, v in zip(parts[::2], parts[1::2]):
                if k:
                    params[k.decode()] = v.decode()
            cl.user = params.get("user", "")
            if self.cluster.users:
                cl.conn.auth(10, b"SCRAM-SHA-256\0\0")
                cl.conn.flush()
                cl.state = "sasl_init"
                return True
            self._auth_ok(cl)
            return True
        except (OSError, FaultDropConnection):
            self._teardown(cl)
            return False
        except Exception as e:
            # malformed startup packet: drop the client, never the loop
            self.cluster.log.emit(
                "warning", "concentrator",
                f"startup error, dropping client: {e!r:.200}",
            )
            self._teardown(cl)
            return False

    def _auth_ok(self, cl: _Client) -> None:
        conn = cl.conn
        conn.auth(0)
        conn.parameter_status(
            "server_version", "10.0 (opentenbase_tpu concentrator)"
        )
        conn.parameter_status("client_encoding", "UTF8")
        conn.parameter_status("DateStyle", "ISO, MDY")
        conn.parameter_status("integer_datetimes", "on")
        conn.put(b"K", struct.pack("!II", 0, 0))
        conn.ready(b"I")
        cl.state = "ready"

    def _pump_sasl(self, cl: _Client, tag: bytes, body: bytes) -> None:
        if tag != b"p":
            cl.conn.error("expected SASLResponse", "28000")
            cl.conn.flush()
            self._teardown(cl)
            return
        if cl.state == "sasl_init":
            mech, rest = body.split(b"\0", 1)
            if mech != b"SCRAM-SHA-256":
                cl.conn.error("unsupported SASL mechanism", "28000")
                cl.conn.flush()
                self._teardown(cl)
                return
            (ln,) = struct.unpack("!i", rest[:4])
            client_first = rest[4:4 + ln].decode()
            cl.sasl, server_first = scram_server_first(
                self.cluster, cl.user, client_first
            )
            cl.conn.auth(11, server_first.encode())
            cl.conn.flush()
            cl.state = "sasl_final"
            return
        ok, server_sig = scram_verify_final(cl.sasl or {}, body.decode())
        cl.sasl = None
        if not ok:
            cl.conn.error(
                f'password authentication failed for user "{cl.user}"',
                "28P01",
            )
            cl.conn.flush()
            self._teardown(cl)
            return
        cl.conn.auth(12, server_sig)
        self._auth_ok(cl)

    def _pump_ready(self, cl: _Client, tag: bytes, body: bytes) -> None:
        if tag == b"X":
            self._teardown(cl)
            return
        if tag == b"Q":
            sql = body.rstrip(b"\0").decode()
            if not sql.strip():
                cl.conn.put(b"I")
                cl.conn.ready(self._txn_status(cl))
                return
            self._dispatch(cl, sql)
            return
        if tag == b"S":  # Sync outside the extended protocol
            cl.conn.ready(self._txn_status(cl))
            return
        if tag == b"H":  # Flush
            cl.conn.flush()
            return
        # extended protocol (Parse/Bind/Describe/Execute/Close): the
        # concentrator is simple-query only, like pgbouncer's statement
        # mode — the per-connection pgwire front end keeps full support
        cl.conn.error(
            "extended query protocol is not supported through the "
            "session concentrator; use simple queries (or connect to "
            "the per-connection pgwire front end)",
            "0A000",
        )
        cl.conn.flush()

    @staticmethod
    def _in_txn(sess) -> bool:
        """An open transaction on this backend — local, or FORWARDED to
        the primary CN (peer-coordinator serving: a forwarded BEGIN
        leaves sess.txn None while the primary-side transaction is
        open; the pin must hold for either kind or another client's
        statements would ride a foreign transaction)."""
        return sess.txn is not None or getattr(sess, "_fwd_in_txn", False)

    def _txn_status(self, cl: _Client) -> bytes:
        sess = cl.pinned
        return b"T" if (
            sess is not None and self._in_txn(sess)
        ) else b"I"

    # -- dispatch + shed ---------------------------------------------------
    def _dispatch(self, cl: _Client, sql: str) -> None:
        import time as _time

        with self._mu:
            if self._queued >= self.queue_depth:
                self.stats["sheds"] += 1
                shed = True
            else:
                self._queued += 1
                shed = False
        if shed:
            self._shed(cl, "statement queue is full")
            return
        cl.busy = True
        self._jobs.put(
            (cl, sql, _time.monotonic() + self.queue_timeout_s, None)
        )

    def _shed(self, cl: _Client, why: str) -> None:
        try:
            cl.conn.error(
                f"concentrator backends exhausted: {why} "
                f"({self.backends} backends)",
                "53300",
            )
            cl.conn.ready(self._txn_status(cl))
        except (OSError, FaultDropConnection):
            self._teardown(cl)

    # -- workers (the bounded execution plane) -----------------------------
    def _worker(self) -> None:
        import time as _time

        while True:
            job = self._jobs.get()
            if job is None:
                return
            cl, sql, deadline, pin_info = job
            try:
                if sql == _CLOSE_JOB:
                    self._finish_close(cl)
                    continue
                if cl.closed:
                    # the client vanished while this statement queued;
                    # its pinned backend still needs recycling
                    with self._mu:
                        self._queued -= 1
                    self._finish_close(cl)
                    continue
                # acquire a backend WITHOUT parking the worker: a
                # worker blocked in _free.get() would starve queued
                # jobs that need no free backend at all (a pinned
                # client's COMMIT, a close job) — exactly the jobs
                # that would free backends up. The pin-detection parse
                # rides the job tuple so requeue retries skip it.
                if pin_info is None:
                    pin_info = self._pin_info(cl, sql)
                sess, needs_pin, sticky, stmts = self._session_for(
                    cl, pin_info
                )
                if sess is None:
                    if _time.monotonic() < deadline:
                        self._jobs.put((cl, sql, deadline, pin_info))
                        _time.sleep(0.005)  # all pinned: brief backoff
                        continue
                    with self._mu:
                        self._queued -= 1
                        self.stats["sheds"] += 1
                    with cl.lock:
                        self._shed(
                            cl, "every backend is pinned or busy"
                        )
                    with cl.lock:
                        cl.busy = False
                    if not cl.closed:
                        self._pump(cl)
                    continue
                with self._mu:
                    self._queued -= 1
                if needs_pin:
                    cl.pinned = sess
                    cl.state_pinned = cl.state_pinned or sticky
                    with self._mu:
                        self.stats["pinned"] += 1
                self._exec_job(cl, sql, sess, stmts)
            except Exception as e:
                # a worker must survive anything a statement throws
                self.cluster.log.emit(
                    "error", "concentrator",
                    f"worker error: {e!r:.200}",
                )
                with self._mu:
                    self.stats["errors"] += 1
                self._teardown(cl)

    def _pin_info(self, cl: _Client, sql: str):
        """(stmts, needs_pin, sticky) — ONE parse for pin detection,
        handed onward so lock classing never re-parses and requeue
        retries never parse at all."""
        if cl.pinned is not None:
            return None, False, False
        needs_pin = sticky = False
        stmts = None
        try:
            from opentenbase_tpu.sql import ast as A
            from opentenbase_tpu.sql.parser import parse

            stmts = parse(sql)
            for st in stmts:
                if isinstance(st, (A.SetStmt, A.PrepareStmt,
                                   A.DeallocateStmt)):
                    needs_pin = sticky = True
                elif isinstance(st, A.BeginStmt):
                    needs_pin = True
        except Exception:  # otb_lint: ignore[except-swallow] -- by design: an unparseable statement needs no pin; the engine re-parses on whichever backend runs it and reports the real syntax error to the client
            stmts = None
        return stmts, needs_pin, sticky

    def _session_for(self, cl: _Client, pin_info):
        """(session, needs_pin, sticky, parsed stmts) — the pinned
        backend when one exists, else a pool backend if one is free
        RIGHT NOW (the worker loop requeues and retries until the
        job's deadline), else (None, ..)."""
        stmts, needs_pin, sticky = pin_info
        if cl.pinned is not None:
            return cl.pinned, False, False, stmts
        try:
            sess = self._free.get_nowait()
        except _queue.Empty:
            return None, needs_pin, sticky, stmts
        return sess, needs_pin, sticky, stmts

    def _exec_job(self, cl: _Client, sql: str, sess, stmts=None) -> None:
        from opentenbase_tpu.net.server import ClusterServer

        try:
            err = None
            res = None
            try:
                kind, wt = ClusterServer._classify(
                    self, sql, sess, stmts=stmts
                )
                if kind == "read":
                    with self._exec_lock.read():
                        res = sess.execute(sql)
                elif kind == "write":
                    with self._exec_lock.write_tables(wt):
                        res = sess.execute(sql)
                else:
                    with self._exec_lock:
                        res = sess.execute(sql)
            except FaultDropConnection:
                raise
            except Exception as e:  # otb_lint: ignore[except-swallow] -- not a swallow: delivered to the client as an 'E' message with its SQLSTATE below, and Session.execute elog'd it
                err = e
            with self._mu:
                self.stats["statements"] += 1
            # a statement may have opened a transaction the classifier
            # did not see (multi-statement strings): a backend with an
            # open txn — local or forwarded — can never return to the
            # pool
            if cl.pinned is None and self._in_txn(sess):
                cl.pinned = sess
                with self._mu:
                    self.stats["pinned"] += 1
            with cl.lock:
                if cl.closed:
                    return
                try:
                    if err is None:
                        emit_result(cl.conn, res)
                    else:
                        from opentenbase_tpu.net.pgwire import (
                            PgWireServer,
                        )

                        cl.conn.error(
                            f"{type(err).__name__}: {err}",
                            PgWireServer._sqlstate_of(err),
                        )
                    cl.conn.ready(
                        b"T" if self._in_txn(sess) else b"I"
                    )
                except (OSError, FaultDropConnection):
                    self._teardown(cl)
                    return
        finally:
            self._release(cl, sess)
            with cl.lock:
                cl.busy = False
            if cl.closed:
                # teardown may have landed between _release and the
                # busy flip (it saw busy=True and skipped the close
                # job): re-check here; _finish_close pops the pin
                # atomically so a racing close job recycles only once
                self._finish_close(cl)
            else:
                self._pump(cl)  # drain pipelined statements

    def _release(self, cl: _Client, sess) -> None:
        """Return an unpinned (or just-unpinnable) backend to the
        pool: transaction pins lift when the txn ends; state pins
        (SET/PREPARE) hold for the connection's life. A client that
        closed while its statement ran is cleaned up HERE — the
        teardown saw busy=True and left the backend to us."""
        if sess is None:
            return
        if cl.pinned is sess:
            if cl.closed:
                self._finish_close(cl)
                return
            if self._in_txn(sess) or cl.state_pinned:
                return  # stays pinned
            cl.pinned = None
            with self._mu:
                self.stats["pinned"] -= 1
        self._free.put(sess)

    # -- teardown ----------------------------------------------------------
    def _teardown(self, cl: _Client) -> None:
        """Sever a client (EOF, Terminate, protocol error, stop). Safe
        from any thread; the pinned backend (if any) is recycled by a
        worker so the selector loop never waits on the exec lock."""
        with self._mu:
            first = not cl.closed
            cl.closed = True
            self._clients.discard(cl)
        if not first:
            return
        try:
            self._sel.unregister(cl.sock)
        except (KeyError, ValueError, OSError):
            pass
        shutdown_and_close(cl.sock)
        with cl.lock:
            busy = cl.busy
        if cl.pinned is not None and not busy:
            # no worker owns this client right now: recycle its backend
            # via a worker (never roll back on the selector thread —
            # rollback takes the exec lock). A busy client's cleanup
            # happens in _release when its statement finishes.
            # 4-tuple like every other job: a 3-tuple here crashed the
            # unpacking worker with ValueError and silently shrank the
            # worker pool
            self._jobs.put((cl, _CLOSE_JOB, None, None))

    def _finish_close(self, cl: _Client) -> None:
        """Worker half of teardown: roll back any open transaction and
        recycle the pinned backend. A state-pinned session is RETIRED
        (replaced by a fresh one) — foreign SETs and prepared
        statements must never leak into the shared pool. Idempotent:
        the pin is popped atomically, so a racing close job and
        statement-finish cleanup recycle exactly once."""
        with self._mu:
            sess, cl.pinned = cl.pinned, None
            if sess is not None:
                self.stats["pinned"] -= 1
        if sess is None:
            return
        self._recycle(sess, retire=cl.state_pinned)

    def _recycle(self, sess, retire: bool) -> None:
        try:
            if self._in_txn(sess):
                # a forwarded transaction rolls back on the PRIMARY —
                # Session.execute routes the rollback there itself
                with self._exec_lock:
                    sess.execute("rollback")
        except Exception as e:
            self.cluster.log.emit(
                "warning", "concentrator",
                f"rollback on client close failed: {e!r:.200}",
                session=sess.session_id,
            )
        if retire or self._stop.is_set():
            sess.close()
            if not self._stop.is_set():
                self._free.put(self.cluster.session())
        else:
            self._free.put(sess)
