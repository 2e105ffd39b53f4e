"""racewatch — a TSan-lite runtime race sanitizer (``OTB_RACEWATCH=1``).

The static half (``checkers/races.py``) sees locksets the code SPELLS;
this module watches the locksets the process actually HOLDS.  It is
the ``lockwatch`` pattern extended from lock *order* to *access*
tracking: the same wrapped ``threading.Lock``/``RLock`` factories give
a per-thread held set, and classes annotated ``@shared_state("_mu")``
get their instance attributes instrumented so every read and write
records a ``(thread, lockset, access)`` tuple.  Two threads touching
the same field with DISJOINT locksets, at least one of them writing,
is a reported race — with both stacks, like TSan.

What counts as a write: attribute assignment, and mutation of a plain
``dict`` / ``list`` / ``set`` stored in an instrumented attribute (the
value is transparently wrapped in a recording subclass at assignment
time — ``self.stats["hits"] += 1`` without the lock is exactly the bug
class this exists for).  Locks, Events, Threads, thread-locals and
other internally-synchronized values are skipped by type; accesses
before ``__init__`` returns are construction-private and exempt.

Zero production tax: with the env var unset, ``@shared_state`` returns
the class untouched and the import does nothing.  Enabling must happen
before the annotated classes are DEFINED (tests/test_race_analysis.py
sets the env var for a subprocess that then imports the engine),
mirroring lockwatch's
create-after-enable rule.

Races surface as ``analysis.core.Finding``s with rule ``race-dynamic``
and stable keys ``race-dynamic::<path>::<Class>.<field>``, diffed
against the same ``tools/race_baseline.json`` the static half
ratchets on.  Baselining a dynamic race requires a reason —
``otb_race --bless-dynamic KEY --reason WHY`` records it in the
baseline entry, the CLI refuses a reasonless bless.
"""

from __future__ import annotations

import functools
import itertools
import os
import sys
import threading
import traceback

from opentenbase_tpu.analysis import lockwatch as _lw

_enabled = False
# the sanitizer's OWN lock is a native lock, never the wrapped factory:
# it must not appear in held sets or the lockwatch order graph
_mu = _lw._real_lock()  # guards _records / _races / _classes

# thread identity that is NEVER reused: threading.get_ident() hands a
# finished thread's ident to the next one, which would make thread A's
# unguarded writes look like thread B's own and mask the race
_tls = threading.local()
_tid_counter = itertools.count(1)
# instance identity that is never reused either (id() recycles after
# GC): two INSTANCES of a class rightly hold two different locks, and
# keying accesses by class alone would read that as disjoint locksets
# on shared data — data that isn't shared at all
_iid_counter = itertools.count(1)


def _thread_uid() -> int:
    uid = getattr(_tls, "rw_uid", None)
    if uid is None:
        uid = _tls.rw_uid = next(_tid_counter)
    return uid
# (cls_qualname, field) -> {signature: _Access} — one representative
# access (with stack) per distinct (thread, lockset, write) shape
_records: dict = {}
# (cls_qualname, field) -> race dict (first pair wins; both stacks)
_races: dict = {}
# cls_qualname -> repo-relative source path (for Finding.path)
_classes: dict = {}

# values of these types are synchronization primitives or otherwise
# internally synchronized — not shared *data*
_EXEMPT_TYPE_NAMES = (
    "lock", "rlock", "_watchedlock", "condition", "event", "thread",
    "local", "queue", "simplequeue", "lifoqueue", "priorityqueue",
    "semaphore", "boundedsemaphore", "barrier", "socket", "module",
    "function", "method", "builtin_function_or_method", "type",
)
_MAX_SHAPES = 24  # distinct access shapes kept per field
_STACK_DEPTH = 14


class _Access:
    __slots__ = ("thread_id", "thread_name", "lockset", "write", "stack")

    def __init__(self, thread_id, thread_name, lockset, write, stack):
        self.thread_id = thread_id
        self.thread_name = thread_name
        self.lockset = lockset
        self.write = write
        self.stack = stack


def enabled() -> bool:
    return _enabled


def enable() -> bool:
    """Switch recording on; idempotent.  Rides lockwatch's factory
    wrapping for the per-thread held set (enabling racewatch enables
    lockwatch — one wrapping layer, two consumers)."""
    global _enabled
    if _enabled:
        return False
    _lw.enable()
    _enabled = True
    return True


def disable() -> None:
    """Stop recording (already-instrumented classes stay instrumented
    but check the flag per access; tests use this)."""
    global _enabled
    _enabled = False


def reset() -> None:
    with _mu:
        _records.clear()
        _races.clear()


def _held_lockset() -> frozenset:
    held = getattr(_lw._state, "held", None)
    if not held:
        return frozenset()
    return frozenset(id(w) for w in held)


def _rel_source(cls) -> str:
    mod = sys.modules.get(cls.__module__)
    path = getattr(mod, "__file__", None) or "<unknown>"
    path = path.replace(os.sep, "/")
    i = path.find("opentenbase_tpu/")
    return path[i:] if i >= 0 else path


def _stack() -> tuple:
    # drop the instrumentation frames themselves; keep the caller tail
    frames = traceback.extract_stack(limit=_STACK_DEPTH + 4)[:-3]
    return tuple(
        f"{fr.filename.rsplit('/', 1)[-1]}:{fr.lineno} in {fr.name}"
        for fr in frames[-_STACK_DEPTH:]
    )


def _note(cls_qual: str, owner_uid: int, field: str, write: bool) -> None:
    if not _enabled:
        return
    me = _thread_uid()
    lockset = _held_lockset()
    sig = (me, lockset, write)
    # accesses compare within ONE instance's field — a second instance
    # has its own locks and its own data, never a disjoint lockset
    key = (cls_qual, owner_uid, field)
    report_key = (cls_qual, field)
    with _mu:
        shapes = _records.get(key)
        if shapes is None:
            shapes = _records[key] = {}
        mine = shapes.get(sig)
        if mine is None and len(shapes) < _MAX_SHAPES:
            mine = shapes[sig] = _Access(
                me, threading.current_thread().name, lockset, write,
                _stack(),
            )
        if report_key in _races:
            return  # first racing pair per (class, field) is the report
        for other in shapes.values():
            if other.thread_id == me:
                continue
            if (other.write or write) and not (other.lockset & lockset):
                new = mine if mine is not None else _Access(
                    me, threading.current_thread().name, lockset,
                    write, _stack(),
                )
                _races[report_key] = {
                    "class": cls_qual,
                    "field": field,
                    "path": _classes.get(cls_qual, "<unknown>"),
                    "a": other,
                    "b": new,
                }
                return


# ---------------------------------------------------------------------------
# recording container proxies — dict/list/set mutation IS a write
# ---------------------------------------------------------------------------


def _proxy_class(base, mutators):
    ns = {"__slots__": ("_rw_cls", "_rw_owner", "_rw_field", "_rw_cell")}

    def make(verb):
        basem = getattr(base, verb)

        def op(self, *a, **kw):
            # the owner's ready cell gates recording: a container
            # populated item-by-item during __init__ is construction-
            # private, same as direct attribute writes
            if self._rw_cell[0]:
                _note(self._rw_cls, self._rw_owner, self._rw_field,
                      write=True)
            return basem(self, *a, **kw)

        op.__name__ = verb
        return op

    for verb in mutators:
        if hasattr(base, verb):
            ns[verb] = make(verb)
    return type(f"_RW{base.__name__.capitalize()}", (base,), ns)


_RWDict = _proxy_class(dict, (
    "__setitem__", "__delitem__", "update", "setdefault", "pop",
    "popitem", "clear",
))
_RWList = _proxy_class(list, (
    "__setitem__", "__delitem__", "append", "extend", "insert",
    "remove", "pop", "clear", "sort", "reverse", "__iadd__",
))
_RWSet = _proxy_class(set, (
    "add", "remove", "discard", "pop", "clear", "update",
    "difference_update", "intersection_update",
    "symmetric_difference_update", "__iand__", "__ior__", "__isub__",
    "__ixor__",
))


def _wrap_value(value, cls_qual: str, owner_uid: int, field: str,
                ready_cell: list):
    """Exact plain containers get a recording subclass; everything
    else passes through.  (Subclasses — OrderedDict, deque — keep
    their own semantics; their attribute READS are still recorded.)"""
    t = type(value)
    if t is dict:
        out = _RWDict(value)
    elif t is list:
        out = _RWList(value)
    elif t is set:
        out = _RWSet(value)
    else:
        return value
    out._rw_cls = cls_qual
    out._rw_owner = owner_uid
    out._rw_field = field
    out._rw_cell = ready_cell
    return out


def _is_exempt_value(value) -> bool:
    return type(value).__name__.lower() in _EXEMPT_TYPE_NAMES


# ---------------------------------------------------------------------------
# the annotation
# ---------------------------------------------------------------------------


def shared_state(*guards: str):
    """Class decorator declaring a multi-threaded class whose shared
    attributes are guarded by the named lock attribute(s) (``"_mu"``).
    A no-op unless racewatch was enabled before the class definition
    ran; enabled, it instruments attribute access so the sanitizer
    sees every (thread, lockset, access) tuple."""

    def apply(cls):
        if not _enabled:
            return cls
        cls_qual = cls.__qualname__
        _classes[cls_qual] = _rel_source(cls)
        guard_names = frozenset(guards)
        # names resolved on the class (methods, descriptors, class
        # attrs) are code, not shared instance data
        skip = set(dir(cls)) | set(guard_names) | {
            "_rw_ready", "_rw_uid", "_rw_cell",
        }

        orig_init = cls.__init__
        orig_set = cls.__setattr__
        orig_del = cls.__delattr__

        @functools.wraps(orig_init)
        def __init__(self, *a, **kw):
            object.__setattr__(self, "_rw_uid", next(_iid_counter))
            # one mutable cell shared with every container proxy this
            # instance owns: flipped once construction finishes
            object.__setattr__(self, "_rw_cell", [False])
            orig_init(self, *a, **kw)
            self.__dict__["_rw_cell"][0] = True
            object.__setattr__(self, "_rw_ready", True)

        def __setattr__(self, name, value):
            if name not in skip and not name.startswith("__"):
                if not _is_exempt_value(value):
                    d = self.__dict__
                    value = _wrap_value(
                        value, cls_qual, d.get("_rw_uid", 0), name,
                        d.get("_rw_cell") or [True],
                    )
                    if d.get("_rw_ready"):
                        _note(cls_qual, d.get("_rw_uid", 0), name,
                              write=True)
            orig_set(self, name, value)

        def __delattr__(self, name):
            d = self.__dict__
            if name not in skip and d.get("_rw_ready"):
                _note(cls_qual, d.get("_rw_uid", 0), name, write=True)
            orig_del(self, name)

        def __getattribute__(self, name):
            value = object.__getattribute__(self, name)
            if (
                name not in skip
                and not name.startswith("__")
            ):
                d = object.__getattribute__(self, "__dict__")
                if (
                    name in d
                    and d.get("_rw_ready")
                    and not _is_exempt_value(value)
                ):
                    _note(cls_qual, d.get("_rw_uid", 0), name,
                          write=False)
            return value

        cls.__init__ = __init__
        cls.__setattr__ = __setattr__
        cls.__delattr__ = __delattr__
        cls.__getattribute__ = __getattribute__
        cls._rw_guards = guard_names
        return cls

    return apply


# ---------------------------------------------------------------------------
# reporting — the shared finding format + baseline gate
# ---------------------------------------------------------------------------


def races() -> list:
    with _mu:
        return list(_races.values())


def findings() -> list:
    """Recorded races as analysis.core Findings: rule ``race-dynamic``,
    stable key ``race-dynamic::<path>::<Class>.<field>``."""
    from opentenbase_tpu.analysis.core import Finding

    out = []
    for r in races():
        a, b = r["a"], r["b"]
        out.append(Finding(
            rule="race-dynamic",
            path=r["path"],
            line=1,
            message=(
                f"{r['class']}.{r['field']}: thread "
                f"{a.thread_name!r} ({'write' if a.write else 'read'}, "
                f"locks={len(a.lockset)}) races thread "
                f"{b.thread_name!r} ({'write' if b.write else 'read'}, "
                f"locks={len(b.lockset)}) with disjoint locksets"
            ),
            ident=f"{r['class']}.{r['field']}",
        ))
    return sorted(out, key=lambda f: f.key)


def check_baseline(doc: dict) -> tuple:
    """(new, baselined) dynamic findings against a loaded baseline doc
    (``analysis.baseline.load``) — the racewatch gate's ratchet."""
    base = doc.get("findings", {})
    new, seen = [], []
    for f in findings():
        (seen if f.key in base else new).append(f)
    return new, seen


def report(stream=None) -> int:
    """Print every recorded race with both stacks; returns the count."""
    stream = stream if stream is not None else sys.stderr
    rs = races()
    if not rs:
        print("racewatch: ok (no disjoint-lockset races)", file=stream)
        return 0
    print(f"racewatch: {len(rs)} data race(s):", file=stream)
    for r in rs:
        print(
            f"  RACE {r['class']}.{r['field']} ({r['path']})",
            file=stream,
        )
        for tag in ("a", "b"):
            acc = r[tag]
            kind = "write" if acc.write else "read"
            print(
                f"    {tag}: thread {acc.thread_name!r} {kind} "
                f"holding {len(acc.lockset)} lock(s)",
                file=stream,
            )
            for line in acc.stack[-6:]:
                print(f"       {line}", file=stream)
    return len(rs)


if os.environ.get("OTB_RACEWATCH") == "1":  # pragma: no cover - env opt-in
    enable()
