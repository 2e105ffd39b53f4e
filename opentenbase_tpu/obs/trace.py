"""Span-based query tracing.

One ``QueryTrace`` per traced statement holds a flat list of finished
``Span`` records (start/duration in microseconds on the shared
``time.perf_counter`` clock, plus the recording thread id) — exactly the
shape Chrome-trace "X" (complete) events want, so export is a dump, not
a transform.  Nesting is implicit in the timestamps: a child span's
[ts, ts+dur] window sits inside its parent's, which is what the
Perfetto/chrome://tracing renderers use to stack them.

Cost model: when ``trace_queries = off`` no ``QueryTrace`` exists and
every producer site guards on ``trace is not None`` — zero Span
allocations on the untraced hot path (``Span.allocations`` is the test
hook proving it).  EXPLAIN ANALYZE force-starts a trace for its one
statement regardless of the GUC.

``span`` is the ONE producer helper every timed site uses (the wire
server, the session's phases, the fused path): a ``perf_counter`` pair
feeding the statement's resource ledger, a ``jax.profiler``
``TraceAnnotation`` named ``otb:<name>`` (recorded only while a
profiler session runs, so the span sits on the device trace's own
clock in the ``.xplane.pb``), and — only when the session is tracing —
a ``QueryTrace`` record parented to the enclosing span.
``obs/profile.py`` reduces the profiler's side.

``compile_window`` attributes XLA compilation time to the query that
paid it: jax emits ``/jax/core/compile/*_duration`` monitoring events
synchronously on the compiling thread, and the window accumulates them
thread-locally — the fused path's "compile vs execute" split that
VERDICT r5 said we could not prove.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Optional

from opentenbase_tpu.obs import statements as _stmtobs
from opentenbase_tpu.obs.tracectx import TraceContext, new_span_id


class Span:
    """One finished span. ``allocations`` counts every construction —
    the trace-off zero-overhead test asserts it stays flat.

    ``span_id``/``parent_id`` are the cross-node edge identity
    (obs/tracectx.py): only spans that parent remote work carry an
    explicit span_id; leaf phase spans default to parenting the root."""

    __slots__ = (
        "name", "cat", "ts_us", "dur_us", "tid", "args",
        "span_id", "parent_id",
    )

    allocations = 0

    def __init__(
        self, name, cat, ts_us, dur_us, tid, args,
        span_id=None, parent_id=None,
    ):
        Span.allocations += 1
        self.name = name
        self.cat = cat
        self.ts_us = ts_us
        self.dur_us = dur_us
        self.tid = tid
        self.args = args
        self.span_id = span_id
        self.parent_id = parent_id


class QueryTrace:
    """Spans of one traced statement. Thread-safe: fragment executors
    record from worker threads concurrently."""

    __slots__ = (
        "qid", "query", "session_id", "started_s", "finished_s",
        "spans", "_mu", "ctx", "epoch_offset_us",
    )

    def __init__(self, qid: int, query: str, session_id: int = 0):
        self.qid = qid
        self.query = query
        self.session_id = session_id
        self.started_s = time.perf_counter()
        # cross-node identity (obs/tracectx.py): the wire header minted
        # once per traced statement; ctx.span_id is the root span's id
        self.ctx = TraceContext.new()
        # epoch offset: spans record on the perf_counter clock, remote
        # rings on the epoch clock — the export shifts CN spans by this
        # so one merged timeline needs no cross-process negotiation
        self.epoch_offset_us = time.time() * 1e6 - self.started_s * 1e6
        self.finished_s: Optional[float] = None
        self.spans: list[Span] = []
        self._mu = threading.Lock()

    @property
    def trace_id(self) -> str:
        return self.ctx.trace_id

    def record(
        self, name: str, cat: str, t0_s: float, t1_s: float,
        span_id=None, parent_id=None, **args,
    ) -> None:
        """Append a finished span timed on the perf_counter clock.
        Spans default to parenting the statement's root span; callers
        that fan out remote work pass an explicit ``span_id`` so
        wire-propagated children attach to the right attempt.  None-
        valued args are elided (the elog contract) so call sites can
        pass conditionals unconditionally."""
        if args:
            args = {k: v for k, v in args.items() if v is not None}
        span = Span(
            name, cat, t0_s * 1e6, max(t1_s - t0_s, 0.0) * 1e6,
            threading.get_ident(), args or None,
            span_id=span_id,
            parent_id=parent_id or self.ctx.span_id,
        )
        with self._mu:
            self.spans.append(span)


class Tracer:
    """Per-cluster trace ring: the last ``capacity`` finished query
    traces, oldest evicted first (a bounded in-memory ring — the
    pg_stat_statements.max idea applied to traces)."""

    def __init__(self, capacity: int = 64):
        self._mu = threading.Lock()
        self._ring: deque[QueryTrace] = deque(maxlen=capacity)
        self._qids = itertools.count(1)

    def start(self, query: str, session_id: int = 0) -> QueryTrace:
        return QueryTrace(next(self._qids), query, session_id)

    def finish(
        self, trace: QueryTrace, root: str = "query", **args,
    ) -> None:
        """Close the root span and publish the trace into the ring.
        The root is ``query`` for a session-owned trace and
        ``wire.request`` when the wire server owns it (``query`` then
        nests inside as an ordinary span)."""
        trace.finished_s = time.perf_counter()
        args = {k: v for k, v in args.items() if v is not None}
        args["query"] = trace.query[:200]
        root = Span(
            root, root.split(".")[0], trace.started_s * 1e6,
            (trace.finished_s - trace.started_s) * 1e6,
            threading.get_ident(), args,
            span_id=trace.ctx.span_id,
        )
        with trace._mu:
            trace.spans.insert(0, root)
        with self._mu:
            self._ring.append(trace)

    def last(self, n: Optional[int] = None) -> list[QueryTrace]:
        with self._mu:
            traces = list(self._ring)
        if n is not None and n > 0:
            traces = traces[-n:]
        return traces

    def __len__(self) -> int:
        with self._mu:
            return len(self._ring)


# ---------------------------------------------------------------------------
# the span helper: one timed site, three sinks
# ---------------------------------------------------------------------------

_span_tls = threading.local()
_annotation = None  # jax.profiler.TraceAnnotation, bound on first use


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use so that
    importing obs/ never imports JAX (host-side roles, the JAX-free
    client)."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class span:
    """``with span(session, name, ledger_field, count_field, **args)``.

    - always: one ``perf_counter`` pair (``.ms`` after exit); with a
      statement ledger active (obs/statements.current) the span's OWN
      milliseconds — less what enclosed spans billed to fields of
      their own, less what ``exclude`` took out — add to
      ``ledger_field``, and 1 to ``count_field``: the fields partition
      the time, they never count a millisecond twice;
    - whenever a profiler session runs (``TraceMe``'s own test, no
      switch of ours): a ``TraceAnnotation("otb:" + name, **args)``,
      the span on the device trace's clock;
      ``trace_id``/``span_id``/``parent_id`` ride it only when the
      session is tracing;
    - only when the session is tracing (``session._trace``): a
      ``QueryTrace.record`` with its own ``span_id`` under the
      enclosing span of the same trace (else the trace's root).

    ``session`` None inherits the enclosing open span's trace — the
    fused path's sites have no session in reach. ``set(**args)`` adds
    args found out inside the span to both sinks; ``listening`` says
    whether either sink will read them (a site with args that cost
    something to compute asks first). ``record=False`` keeps a span out
    of the QueryTrace (the session-owned ``query`` root, which
    ``Tracer.finish`` builds) while children still parent to its
    ``span_id``. With nothing listening a span is one small object, a
    clock pair and a ledger add; no ``Span`` is built when tracing is
    off."""

    # per-instance state is set only where it differs from these (a
    # span is made fifteen to twenty times a statement: this is the
    # helper's whole cost when nothing is listening)
    cat = "span"
    ms = 0.0
    span_id = None
    listening = False
    _record = True
    _trace = None
    _ann = None
    _late = None
    _parent = None
    _billed = 0.0  # ms inside this span billed elsewhere

    def __init__(
        self, session, name: str, ledger_field: Optional[str] = None,
        count_field: Optional[str] = None, cat: Optional[str] = None,
        span_id: Optional[str] = None, record: bool = True, **args,
    ):
        self.name = name
        self._session = session
        self._field = ledger_field
        self._count = count_field
        self._args = args
        if cat is not None:
            self.cat = cat
        if span_id is not None:
            self.span_id = span_id
        if not record:
            self._record = False

    def set(self, **args) -> None:
        if self._late is None:
            self._late = args
        else:
            self._late.update(args)

    def exclude(self, ms: float) -> None:
        """Keep ``ms`` of this span out of its ledger field (a launch's
        compile time is ``compile_ms``'s, not ``launch_ms``'s)."""
        self._billed += ms

    def args(self) -> dict:
        """Every arg given so far; None-valued ones are elided (the
        elog contract: sites pass conditionals unconditionally)."""
        args = self._args
        if self._late:
            args = {**args, **self._late}
        if None in args.values():
            args = {k: v for k, v in args.items() if v is not None}
        return args

    def __enter__(self) -> "span":
        stack = getattr(_span_tls, "stack", None)
        if stack is None:
            stack = _span_tls.stack = []
        self._stack = stack
        session = self._session
        if session is not None:
            trace = session._trace
        else:
            trace = stack[-1]._trace if stack else None
        annotation = _annotation or _trace_annotation()
        if trace is not None:
            self._trace = trace
            self.listening = True
            if self.span_id is None:
                self.span_id = new_span_id()
            for outer in reversed(stack):
                if outer._trace is trace:
                    self._parent = outer.span_id
                    break
            if annotation.is_enabled():
                self._ann = ann = annotation(
                    "otb:" + self.name, trace_id=trace.trace_id,
                    span_id=self.span_id,
                    parent_id=self._parent or trace.ctx.span_id,
                    **self.args(),
                )
                ann.__enter__()
        elif annotation.is_enabled():
            self.listening = True
            args = self._args
            if None in args.values():
                args = self.args()
            self._ann = ann = annotation("otb:" + self.name, **args)
            ann.__enter__()
        stack.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        stack = self._stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            stack.remove(self)
        self.ms = ms = (t1 - self._t0) * 1000.0
        field = self._field
        if field is not None:
            # the enclosing field-bearing span must not bill this again
            for outer in reversed(stack):
                if outer._field is not None:
                    outer._billed += ms
                    break
        if field is not None or self._count is not None:
            led = _stmtobs.current()
            if led is not None:
                if field is not None:
                    setattr(
                        led, field,
                        getattr(led, field) + max(ms - self._billed, 0.0),
                    )
                if self._count is not None:
                    setattr(
                        led, self._count, getattr(led, self._count) + 1
                    )
        if self.listening:
            ann = self._ann
            if ann is not None:
                late = self._late
                if late:
                    if None in late.values():
                        late = {
                            k: v for k, v in late.items() if v is not None
                        }
                    ann.set_metadata(**late)
                ann.__exit__(*exc)
            trace = self._trace
            if trace is not None and self._record:
                trace.record(
                    self.name, self.cat, self._t0, t1,
                    span_id=self.span_id, parent_id=self._parent,
                    **self.args(),
                )
        return False


def scope(stage: str):
    """``jax.named_scope("otb/<stage>")`` round one plan operator's
    lowering inside a program body: metadata on the HLO ops it emits
    (obs/profile.py sums device time by it), nothing at run time.
    Stage components are lower-case words, so the reduction can tell
    them from JAX's own frames (``jit(..)``, ``while``, ``body``)."""
    import jax

    return jax.named_scope("otb/" + stage)


# ---------------------------------------------------------------------------
# XLA compile-time attribution (jax.monitoring duration events)
# ---------------------------------------------------------------------------

_tls = threading.local()
_listener_wired = False
_wire_mu = threading.Lock()


def _wire_listener() -> None:
    global _listener_wired
    if _listener_wired:
        return
    with _wire_mu:
        if _listener_wired:
            return
        try:
            import jax.monitoring as _monitoring

            def _on_duration(event, duration, **_kw):
                # trace + lower + backend compile all count as "compile"
                if "/jax/core/compile/" not in event:
                    return
                stack = getattr(_tls, "stack", None)
                if stack:
                    stack[-1][0] += duration

            _monitoring.register_event_duration_secs_listener(_on_duration)
        except Exception:
            pass  # no monitoring API: compile_ms stays 0, never breaks
        _listener_wired = True


class compile_window:
    """``with compile_window() as w: ...`` → ``w.ms`` is the XLA compile
    time spent on THIS thread inside the block. Nested windows both see
    inner compiles (the inner total folds into the outer on exit)."""

    __slots__ = ("ms",)

    def __enter__(self) -> "compile_window":
        _wire_listener()
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append([0.0])
        self.ms = 0.0
        return self

    def __exit__(self, *exc):
        stack = _tls.stack
        secs = stack.pop()[0]
        self.ms = secs * 1000.0
        if stack:
            stack[-1][0] += secs
        return False
