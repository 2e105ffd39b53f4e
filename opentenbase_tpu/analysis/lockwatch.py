"""Runtime lock-order watchdog — cycles in the acquisition graph.

Static analysis cannot see lock ORDER; PR 4's review caught a lock-free
eviction race only because a human stared at two functions at once.
This module watches the real thing: with ``OTB_LOCKWATCH=1`` (or an
explicit ``enable()``), every ``threading.Lock`` / ``threading.RLock``
created afterwards is wrapped, each acquisition records edges from
every lock the thread already holds to the one it is taking, and
``report()`` (also run via atexit) finds cycles in that graph — the
classic two-threads-inverted-order deadlock, caught on ANY run where
both orders merely *happen*, not only on the run where they interleave
fatally.

Nodes are allocation sites (``file:line`` of the ``Lock()`` call), so
reports are stable across runs and name code, not addresses. The
rwlock's per-table mutexes are all born on one line and acquired in
``sorted(set(tables))`` order — a same-site edge there is a total
order, not an inversion — which is exactly what the ALLOWLIST is for:
every entry names the lock pair and the reason the order is safe.

Enabling must happen BEFORE the locks of interest are created
(tests/test_static_analysis.py's engine drive sets the env var for a
subprocess that then imports the engine);
locks created pre-enable stay native and invisible, by design — the
watchdog is opt-in instrumentation, never a production tax.
"""

from __future__ import annotations

import atexit
import os
import sys
import threading

_real_lock = threading.Lock
_real_rlock = threading.RLock

# (site_a, site_b) pairs whose ordering edges are known-safe; every
# entry names WHY or it has no business here. Matching is by substring
# of the allocation site so line drift doesn't rot the list. An entry
# whose two patterns are IDENTICAL matches only self-edges (a == b):
# it blesses many-instances-from-one-site hierarchies without also
# blessing every future inversion between DIFFERENT locks born in the
# same file.
ALLOWLIST: tuple = (
    # utils/rwlock.py write_tables: per-table mutexes are all created
    # at one setdefault site and acquired in sorted(set(tables)) order
    # — the total order IS the deadlock avoidance, so the same-site
    # table->table self-edge is a hierarchy, not an inversion.
    ("utils/rwlock.py", "utils/rwlock.py"),
)

_state = threading.local()  # _state.held: list of _WatchedLock
_graph_mu = _real_lock()
# edge (site_a -> site_b) -> first (thread_name, example) that took it
_edges: dict = {}
_enabled = False
_atexit_registered = False


def _alloc_site(depth: int = 2) -> str:
    f = sys._getframe(depth)
    # the factory is called through our shim, so the caller of
    # threading.Lock() is two frames up. Locks born inside threading.py
    # itself (Condition() making its default RLock) attribute to the
    # USER frame that constructed the Condition — otherwise every
    # default condition lock in the process shares one graph node and
    # unrelated nestings read as cycles.
    while f.f_back is not None and f.f_code.co_filename.endswith(
        ("threading.py",)
    ):
        f = f.f_back
    path = f.f_code.co_filename
    for marker in ("/opentenbase_tpu/", "/tests/", "/tools/"):
        i = path.find(marker)
        if i >= 0:
            path = path[i + 1:]
            break
    return f"{path}:{f.f_lineno}"


class _WatchedLock:
    """Wraps one Lock/RLock; quacks enough for Condition to use it
    (acquire/release/locked/_is_owned/_release_save/_acquire_restore
    all delegate or derive)."""

    __slots__ = ("_lk", "site", "_rlock")

    def __init__(self, lk, site: str, rlock: bool):
        self._lk = lk
        self.site = site
        self._rlock = rlock

    # -- bookkeeping -----------------------------------------------------
    def _note_acquired(self) -> None:
        held = getattr(_state, "held", None)
        if held is None:
            held = _state.held = []
        if held:
            me = threading.current_thread().name
            with _graph_mu:
                for h in held:
                    if h is self and self._rlock:
                        continue  # reentrant re-acquire, not an edge
                    _edges.setdefault(
                        (h.site, self.site), me
                    )
        held.append(self)

    def _note_released(self) -> None:
        held = getattr(_state, "held", None)
        if held:
            for i in range(len(held) - 1, -1, -1):
                if held[i] is self:
                    del held[i]
                    break

    # -- lock surface ----------------------------------------------------
    def acquire(self, blocking: bool = True, timeout: float = -1):
        ok = self._lk.acquire(blocking, timeout)
        if ok:
            self._note_acquired()
        return ok

    def release(self) -> None:
        self._lk.release()
        self._note_released()

    def locked(self) -> bool:
        return self._lk.locked()

    def _is_owned(self):
        if hasattr(self._lk, "_is_owned"):
            return self._lk._is_owned()
        # Lock fallback, same trick Condition uses
        # otb_race: ignore[lock-release-path] -- nonblocking ownership probe: acquire(False)/release back-to-back, nothing between them can raise
        if self._lk.acquire(False):
            self._lk.release()
            return False
        return True

    # Condition.wait() protocol: a reentrantly-held RLock must be FULLY
    # released around the wait (the default release()/acquire() fallback
    # drops one level and deadlocks in wait() at depth >= 2)
    def _release_save(self):
        if hasattr(self._lk, "_release_save"):
            inner = self._lk._release_save()
        else:
            self._lk.release()
            inner = None
        held = getattr(_state, "held", None)
        depth = 0
        if held:
            depth = sum(1 for h in held if h is self)
            _state.held = [h for h in held if h is not self]
        return (inner, depth)

    def _acquire_restore(self, saved):
        inner, depth = saved
        if hasattr(self._lk, "_acquire_restore"):
            self._lk._acquire_restore(inner)
        else:
            self._lk.acquire()
        for _ in range(max(depth, 1)):
            self._note_acquired()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def __repr__(self) -> str:
        return f"<WatchedLock {self.site} of {self._lk!r}>"


def _watched_lock():
    return _WatchedLock(_real_lock(), _alloc_site(), rlock=False)


def _watched_rlock():
    return _WatchedLock(_real_rlock(), _alloc_site(), rlock=True)


def enable() -> bool:
    """Patch the Lock/RLock factories; idempotent. Returns True when
    newly enabled."""
    global _enabled, _atexit_registered
    if _enabled:
        return False
    _enabled = True
    threading.Lock = _watched_lock
    threading.RLock = _watched_rlock
    if not _atexit_registered:
        _atexit_registered = True
        atexit.register(_atexit_report)
    return True


def disable() -> None:
    """Restore the native factories (tests); the graph survives so a
    just-finished run can still be reported."""
    global _enabled
    _enabled = False
    threading.Lock = _real_lock
    threading.RLock = _real_rlock


def reset() -> None:
    with _graph_mu:
        _edges.clear()


def edges() -> dict:
    with _graph_mu:
        return dict(_edges)


def _allowed(cycle: list) -> bool:
    """A cycle is allowlisted when EVERY edge in it matches an
    allowlist pair (substring match on both sites; identical-pattern
    entries match self-edges only — see ALLOWLIST)."""
    n = len(cycle)
    for i in range(n):
        a, b = cycle[i], cycle[(i + 1) % n]
        if not any(
            pa in a and pb in b and (pa != pb or a == b)
            for pa, pb in ALLOWLIST
        ):
            return False
    return True


def find_cycles(include_allowed: bool = False) -> list:
    """Cycles in the site graph as site lists, self-loops included
    (same-site edge = two instances from one allocation site ordered
    both ways or nested). Deterministic order."""
    with _graph_mu:
        adj: dict = {}
        for (a, b) in _edges:
            adj.setdefault(a, set()).add(b)
    cycles: list = []
    seen_keys: set = set()
    for start in sorted(adj):
        stack = [(start, [start])]
        while stack:
            node, path = stack.pop()
            for nxt in sorted(adj.get(node, ())):
                if nxt == start and (len(path) > 1 or nxt in adj.get(nxt, ())):
                    # normalize rotation so each cycle reports once
                    i = path.index(min(path))
                    key = tuple(path[i:] + path[:i])
                    if key not in seen_keys:
                        seen_keys.add(key)
                        cyc = list(key)
                        if include_allowed or not _allowed(cyc):
                            cycles.append(cyc)
                elif nxt not in path and nxt > start:
                    # only explore nodes after `start` so every cycle
                    # is found exactly once, from its smallest node
                    stack.append((nxt, path + [nxt]))
    return cycles


def report(stream=None) -> int:
    """Print the verdict; returns the number of NON-allowlisted
    cycles."""
    stream = stream if stream is not None else sys.stderr
    cycles = find_cycles()
    with _graph_mu:
        n_edges = len(_edges)
    if not cycles:
        print(
            f"lockwatch: ok ({n_edges} ordered lock pairs, no "
            f"non-allowlisted cycles)", file=stream,
        )
        return 0
    print(
        f"lockwatch: {len(cycles)} potential deadlock cycle(s) over "
        f"{n_edges} ordered pairs:", file=stream,
    )
    for cyc in cycles:
        print("  cycle: " + " -> ".join(cyc + [cyc[0]]), file=stream)
    return len(cycles)


def _atexit_report() -> None:
    if edges():
        report()


if os.environ.get("OTB_LOCKWATCH") == "1":  # pragma: no cover - env opt-in
    enable()
