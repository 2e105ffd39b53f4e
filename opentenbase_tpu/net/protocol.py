"""Framing for the coordinator wire protocol.

Frame := u32 length | payload (UTF-8 JSON object). The JSON layer plays
the role of the reference's tagged protocol messages ('Q'uery, 'D'ataRow,
'E'rror, 'C'ommandComplete — src/backend/tcop/postgres.c message loop):

  request:  {"q": "<sql>"}                      simple query
            {"op": "close"}                     terminate session
  response: {"tag": str, "columns": [..], "rows": [[..]], "rowcount": int}
            {"error": str}
            {"ok": true}                        for op messages

Values are JSON-encoded; Decimal/date/timestamp columns travel as strings
with a "types" sidecar so the client can round-trip them faithfully.
"""

from __future__ import annotations

import datetime
import decimal
import json
import socket
import struct

from opentenbase_tpu.fault import FAULT


def _default(o):
    if isinstance(o, decimal.Decimal):
        return {"$dec": str(o)}
    if isinstance(o, datetime.datetime):
        return {"$ts": o.isoformat()}
    if isinstance(o, datetime.date):
        return {"$d": o.isoformat()}
    raise TypeError(f"unserializable {type(o)}")


def _revive(o):
    if isinstance(o, dict) and len(o) == 1:
        if "$dec" in o:
            return decimal.Decimal(o["$dec"])
        if "$ts" in o:
            return datetime.datetime.fromisoformat(o["$ts"])
        if "$d" in o:
            return datetime.date.fromisoformat(o["$d"])
    return o


def _revive_tree(x):
    if isinstance(x, list):
        return [_revive_tree(v) for v in x]
    if isinstance(x, dict):
        r = _revive(x)
        if r is not x:
            return r
        return {k: _revive_tree(v) for k, v in x.items()}
    return x


def shutdown_and_close(sock: socket.socket) -> None:
    """Teardown that actually unblocks peers: shutdown() wakes a thread
    blocked in accept()/recv() on this socket; close() alone does not
    (the blocked call holds the old fd). Every server stop() path uses
    this so no join(timeout) has to expire waiting for a sleeper."""
    try:
        sock.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    try:
        sock.close()
    except OSError:
        pass


# -- streaming-replication handshake (storage/replication.py) ----------
# The walreceiver opens with 16 bytes (start offset, its cluster's
# node_generation); the walsender answers 16 bytes (ITS generation, its
# timeline base a.k.a. promote_lsn) before any WAL byte flows. A probe
# (offset = REPL_PROBE) gets the header and an immediate close — the
# rejoin path uses it to learn how far to truncate a diverged WAL.
# Shared here so sender and receiver can never drift apart on layout.

REPL_PROBE = -1
_REPL_HELLO = "<qq"
REPL_HELLO_LEN = struct.calcsize(_REPL_HELLO)


def pack_repl_hello(a: int, b: int) -> bytes:
    return struct.pack(_REPL_HELLO, a, b)


def unpack_repl_hello(data: bytes) -> tuple[int, int]:
    return struct.unpack(_REPL_HELLO, data)


def recv_repl_hello(sock: socket.socket) -> tuple[int, int]:
    """Read one complete hello off the wire (short TCP reads handled);
    raises ConnectionError when the peer closes mid-handshake. THE one
    receive path for both hello directions — walsender, walreceiver,
    and the rejoin probe all sit on it."""
    data = _recv_exact(sock, REPL_HELLO_LEN)
    if data is None:
        raise ConnectionError("peer closed during replication handshake")
    return unpack_repl_hello(data)


# Replication ack frame (receiver -> sender, after the hellos): one
# little-endian int64 = the receiver's applied offset, i.e. bytes it
# has durably written to its own wal.log AND replayed. The walsender's
# per-connection ack reader folds these into its peer table — the
# in-memory evidence synchronous_commit=remote_write consults without
# any per-commit RPC (the pipelined-quorum half of ROADMAP item 4b).

_REPL_ACK = "<q"
REPL_ACK_LEN = struct.calcsize(_REPL_ACK)


def pack_repl_ack(offset: int) -> bytes:
    return struct.pack(_REPL_ACK, offset)


def recv_repl_ack(sock: socket.socket) -> int:
    """One complete ack frame; raises ConnectionError on peer close."""
    data = _recv_exact(sock, REPL_ACK_LEN)
    if data is None:
        raise ConnectionError("peer closed the replication ack channel")
    return struct.unpack(_REPL_ACK, data)[0]


def encode_frame(obj: dict) -> bytes:
    """Serialize a frame WITHOUT touching the socket. Callers that must
    stay exception-safe around pooled channels (net/pool.py) encode
    first: a serialization error before any byte is written leaves the
    connection clean, while the same error raised mid-send would desync
    the request/response stream."""
    data = json.dumps(obj, default=_default).encode()
    return struct.pack("<I", len(data)) + data


def send_frame(sock: socket.socket, obj: dict) -> None:
    send_encoded(sock, encode_frame(obj))


def send_encoded(sock: socket.socket, data: bytes) -> None:
    """Send an ``encode_frame`` result (the coordinator front end times
    serialization and the send apart)."""
    # failpoint at the shared frame-send boundary: EVERY JSON-wire
    # peer (sessions, DN channels, GTM, log shipping) crosses it
    FAULT("net/protocol/send")
    sock.sendall(data)


def recv_frame(sock: socket.socket) -> dict | None:
    length = recv_frame_header(sock)
    if length is None:
        return None
    return recv_frame_body(sock, length)


def recv_frame_header(sock: socket.socket) -> int | None:
    """The frame's length header alone: a server blocks HERE between
    requests, so the wait for the next one stays outside its spans."""
    head = _recv_exact(sock, 4)
    if head is None:
        return None
    return struct.unpack("<I", head)[0]


def recv_frame_body(sock: socket.socket, length: int) -> dict | None:
    body = _recv_exact(sock, length)
    if body is None:
        return None
    return _revive_tree(json.loads(body.decode()))


def _recv_exact(sock: socket.socket, n: int) -> bytes | None:
    # failpoint: a peer stalling/vanishing mid-frame (torn reads)
    FAULT("net/protocol/recv")
    out = b""
    while len(out) < n:
        chunk = sock.recv(n - len(out))
        if not chunk:
            return None
        out += chunk
    return out
