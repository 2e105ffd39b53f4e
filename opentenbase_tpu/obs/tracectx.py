"""Cross-node trace context — W3C-traceparent-style propagation.

PR 2's span ring (obs/trace.py) stops at the coordinator: a fragment
retry on dn1 and the GTS round-trip that ordered it could not be
stitched to the statement that caused them.  This module is the wire
identity that makes a query ONE causal story across CN -> DN -> GTM:

- ``TraceContext``: (trace_id, span_id, sampled) minted once per traced
  statement and rendered as a ``00-<trace_id>-<span_id>-<flags>``
  traceparent header.  Wire clients (net/pool.Channel.rpc, net/client,
  gtm/client.NativeGTS) attach it as an optional ``_trace`` field when
  a context is bound; servers (dn/server dispatch, gtm/server grant
  loop, net/server statements) bind it thread-locally for the request —
  the same per-thread binding PR 5 uses for log rings.
- ``bind``/``current``: the thread-local binding.  ``current()`` is one
  getattr — with ``trace_queries = off`` no context ever exists and
  every producer site stays allocation-free (``SpanRing.allocations``
  is the cross-process half of the zero-overhead test).
- ``SpanRing``: the bounded per-node span ring a DN server process or
  the GTM owns (mirroring ``LogRing``).  Records are plain lists so the
  ``trace_fetch`` protocol op ships them verbatim; timestamps are epoch
  microseconds (``time.time()``), the one clock every localhost process
  shares, so the coordinator's merge needs no offset negotiation.

Record shape (JSON-wire friendly):
    [trace_id, span_id, parent_span_id, name, cat, ts_us, dur_us, tid,
     args_or_None]
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Optional

from opentenbase_tpu.analysis.racewatch import shared_state

_tls = threading.local()


def _hex_id(nbytes: int) -> str:
    """Random hex that starts with a letter. The ids ride a
    ``jax.profiler`` TraceMe as metadata, and the profiler stores a
    value that reads as a number as one: an id of decimal digits alone
    (one in ~1,800 of eight bytes) came back as an int, one with a
    single ``e`` among them (``1234e56789012345``) as a float, and the
    span no longer joined its trace. A leading a, b, e or f reads as
    neither."""
    raw = bytearray(os.urandom(nbytes))
    raw[0] |= 0xA0
    return raw.hex()


def new_trace_id() -> str:
    return _hex_id(16)


def new_span_id() -> str:
    return _hex_id(8)


class TraceContext:
    """One hop of trace identity: which trace, which parent span."""

    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: str, span_id: str, sampled: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled

    @classmethod
    def new(cls) -> "TraceContext":
        return cls(new_trace_id(), new_span_id(), True)

    def child(self) -> "TraceContext":
        """Same trace, fresh span id — one per RPC *attempt*, so a
        retried fragment's DN-side spans parent to the attempt that
        actually carried them, not to a merged blur."""
        return TraceContext(self.trace_id, new_span_id(), self.sampled)

    def to_header(self) -> str:
        return (
            f"00-{self.trace_id}-{self.span_id}-"
            f"{'01' if self.sampled else '00'}"
        )


def from_header(header) -> Optional[TraceContext]:
    """Parse a traceparent header; tolerant — a malformed header from a
    peer must degrade to 'untraced', never error the request."""
    try:
        parts = str(header).split("-")
        if len(parts) != 4:
            return None
        _ver, trace_id, span_id, flags = parts
        if len(trace_id) != 32 or len(span_id) != 16:
            return None
        int(trace_id, 16)
        int(span_id, 16)
        return TraceContext(trace_id, span_id, flags != "00")
    except (ValueError, AttributeError):
        return None


def bind(ctx: Optional[TraceContext]) -> Optional[TraceContext]:
    """Bind ``ctx`` as THIS thread's trace context; returns the previous
    binding so callers restore it (``prev = bind(ctx) ... bind(prev)``)."""
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    return prev


def current() -> Optional[TraceContext]:
    return getattr(_tls, "ctx", None)


def inject(msg: dict) -> dict:
    """Copy-on-write ``_trace`` header attach for JSON-wire clients:
    returns ``msg`` untouched when no sampled context is bound (the
    untraced hot path adds one getattr, zero allocations)."""
    ctx = current()
    if ctx is None or not ctx.sampled or "_trace" in msg:
        return msg
    out = dict(msg)
    out["_trace"] = ctx.to_header()
    return out


@shared_state("_mu")
class SpanRing:
    """Bounded per-node ring of finished remote spans (the DN/GTM side
    of a distributed trace).  Thread-safe; ``allocations`` counts every
    record so the cross-process zero-overhead test can assert the
    untraced path never touches it."""

    allocations = 0
    # class-level counter, class-level lock: the += is a read-modify-
    # write shared by every ring in the process, and guarding it with
    # an instance _mu would still lose increments across instances
    _alloc_mu = threading.Lock()

    def __init__(self, capacity: int = 4096):
        self._mu = threading.Lock()
        self._ring: deque[list] = deque(maxlen=capacity)

    def record(
        self, ctx: TraceContext, name: str, cat: str,
        t0_s: float, t1_s: float, parent_id: Optional[str] = None,
        **args,
    ) -> str:
        """Append one finished span timed on the epoch clock; mints the
        span id and parents it to ``ctx.span_id`` (the wire-carried
        parent) unless an explicit ``parent_id`` overrides it.  None-
        valued args are elided (the elog contract)."""
        if args:
            args = {k: v for k, v in args.items() if v is not None}
        with SpanRing._alloc_mu:
            SpanRing.allocations += 1
        span_id = new_span_id()
        rec = [
            ctx.trace_id, span_id, parent_id or ctx.span_id,
            str(name), str(cat),
            t0_s * 1e6, max(t1_s - t0_s, 0.0) * 1e6,
            threading.get_ident(), args or None,
        ]
        with self._mu:
            self._ring.append(rec)
        return span_id

    def rows(
        self, trace_ids=None, since_ts: float = 0.0,
    ) -> list[list]:
        """Records, optionally restricted to ``trace_ids`` and to spans
        starting after ``since_ts`` (epoch seconds) — what the
        ``trace_fetch`` protocol op ships to the coordinator."""
        wanted = set(trace_ids) if trace_ids else None
        floor_us = since_ts * 1e6
        with self._mu:
            recs = list(self._ring)
        return [
            r for r in recs
            if r[5] > floor_us and (wanted is None or r[0] in wanted)
        ]

    def __len__(self) -> int:
        with self._mu:
            return len(self._ring)


def epoch_us() -> float:
    return time.time() * 1e6
