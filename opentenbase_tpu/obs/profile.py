"""From a profiler ``.xplane.pb`` to where a statement's time went.

The span helper (obs/trace.span) writes every timed site into the JAX
profiler's own trace as a ``TraceMe`` named ``otb:<span>``, so host
spans and device operations share one clock. This module reads that
file and prints, per statement class (the ledger's ``queryid``, carried
by the ``otb:query`` span):

- each span's count, total and SELF time (less what its children cover);
- device busy time by program (XLA module) and by ``otb/`` scope (the
  ``jax.named_scope`` each plan operator's lowering runs under), with
  the share that fell under no scope and the ops that make it up;
- every idle gap of the device inside a ``wire.request``, put down to
  ``in_program:<module>`` when a running program covers it, else to the
  innermost span open on the serving thread, else ``unattributed``;
- launches, syncs and retries per statement;
- on a mesh: the statement's fragments with their motion
  (``fused.exchange``: target, rows, slots, bytes) and the programs each
  launched, and per chip the time inside ``exchange/all_to_all`` split
  into the part during which another chip was still computing (hidden:
  the collective waited on, or overlapped, a peer's work) and the rest
  (exposed: every other chip was in the collective or idle too).

Two steps, so the arithmetic can be checked on a small recorded trace
kept as JSON: ``load`` turns the file into plain lists, ``reduce`` does
the rest. Nothing here runs on a statement's path, and nothing of JAX
or of the benchmark is imported: the file is decoded by the few lines
of protobuf wire format below (``jax.profiler.ProfileData`` does not
expose the per-operation metadata that holds the scope).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import struct

SPAN_PREFIX = "otb:"
REQUEST = "otb:wire.request"
QUERY = "otb:query"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the six parts of ``device_ms`` (obs/statements.DEVICE_SPLIT_FIELDS)
SPLIT_SPANS = (
    "fused.gate_wait", "fused.cache", "fused.bind", "fused.launch",
    "fused.wait", "fused.collect",
)
ALL_TO_ALL = "all_to_all"  # last word of the exchange's collective scope
# JAX's own name-stack frames: an ``otb/`` scope ends where one starts
_FRAMES = frozenset((
    "while", "body", "cond", "closed_call", "shard_map", "pallas_call",
    "pjit", "remat", "checkpoint", "custom_jvp_call", "custom_vjp_call",
    "core_call", "named_call", "branch",
))
_WORD = re.compile(r"^[a-z0-9_]+$")
# the profiler aligns the device's clock with the host's to about a
# millisecond (a v5e run showed programs starting 0.9 ms before the
# host span that launched them)
SKEW_NS = 2e6


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


# ---------------------------------------------------------------------------
# protobuf wire format, as far as XSpace needs it
# ---------------------------------------------------------------------------


def _fields(buf):
    """(field number, wire type, value) of one message: ints for
    varints and fixed words, a memoryview for length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        key = 0
        shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        no, wt = key >> 3, key & 7
        if wt == 0:
            v = 0
            shift = 0
            while True:
                b = buf[i]
                i += 1
                v |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield no, wt, v
        elif wt == 2:
            ln = 0
            shift = 0
            while True:
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield no, wt, buf[i:i + ln]
            i += ln
        elif wt == 1:
            yield no, wt, bytes(buf[i:i + 8])
            i += 8
        elif wt == 5:
            yield no, wt, bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wt} in an xplane file")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(mv) -> str:
    return bytes(mv).decode("utf-8", "replace")


def _stat(buf, stat_names: dict):
    """One XStat as (name, value); a ref value resolves to the name it
    points at (strings are interned as stat metadata)."""
    name, value = None, None
    for no, wt, v in _fields(buf):
        if no == 1:
            name = stat_names.get(v, str(v))
        elif no == 2:
            value = struct.unpack("<d", v)[0]
        elif no == 3:
            value = v
        elif no == 4:
            value = _signed(v)
        elif no in (5, 6):
            value = _text(v)
        elif no == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _plane(buf) -> dict:
    name = ""
    lines, emeta, smeta = [], [], {}
    for no, wt, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            lines.append(v)
        elif no == 4:
            emeta.append(v)
        elif no == 5:
            key, meta = None, None
            for n2, _w, v2 in _fields(v):
                if n2 == 1:
                    key = v2
                elif n2 == 2:
                    meta = v2
            sname = ""
            for n3, _w, v3 in _fields(meta if meta is not None else b""):
                if n3 == 2:
                    sname = _text(v3)
            smeta[key] = sname
    events: dict = {}  # metadata id -> (name, stats dict)
    for entry in emeta:
        key, meta = None, b""
        for n2, _w, v2 in _fields(entry):
            if n2 == 1:
                key = v2
            elif n2 == 2:
                meta = v2
        ename, stats = "", {}
        for n3, _w, v3 in _fields(meta):
            if n3 == 2:
                ename = _text(v3)
            elif n3 == 5:
                k, val = _stat(v3, smeta)
                stats[k] = val
        events[key] = (ename, stats)
    return {"name": name, "lines": lines, "events": events,
            "stats": smeta}


def _line(buf, plane: dict, keep) -> dict:
    name, t0_ns, raw = "", 0, []
    for no, wt, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            t0_ns = _signed(v)
        elif no == 4:
            raw.append(v)
    out = []
    for ev in raw:
        mid, off_ps, dur_ps, stats = 0, 0, 0, []
        for no, wt, v in _fields(ev):
            if no == 1:
                mid = v
            elif no == 2:
                off_ps = _signed(v)
            elif no == 3:
                dur_ps = _signed(v)
            elif no == 4:
                stats.append(v)
        ename, mstats = plane["events"].get(mid, ("", {}))
        kept = keep(name, ename)
        if kept is None:
            continue
        args = dict(mstats) if kept else {}
        if kept:
            for s in stats:
                k, val = _stat(s, plane["stats"])
                args[k] = val
        out.append([ename, t0_ns + off_ps / 1000.0, dur_ps / 1000.0, args])
    return {"name": name, "events": out}


def scope_of(op_name) -> str:
    """The ``otb/`` scope in an HLO op's ``op_name`` metadata
    (``jit(program_x)/shard_map/otb/join0/merge/sort/sort:``): the
    stage words after ``otb`` up to JAX's next frame or the primitive
    (the last word). '' when the op ran under no scope."""
    parts = str(op_name or "").split(":")[0].split("/")
    if "otb" not in parts:
        return ""
    words = []
    for w in parts[parts.index("otb") + 1:-1]:
        if w == "otb":
            continue
        if w in _FRAMES or not _WORD.match(w) or w.startswith("branch_"):
            break
        words.append(w)
    return "/".join(words)


def load(path: str) -> dict:
    """Device planes' module and op lines (each op with its scope) and,
    of the host planes, the ``otb:`` spans with their args."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    planes = []
    for no, _wt, v in _fields(data):
        if no != 1:
            continue
        plane = _plane(v)
        device = plane["name"].startswith(DEVICE_PREFIX)

        def keep(line: str, event: str):
            if device:
                if line == OPS_LINE:
                    return True
                return False if line == MODULES_LINE else None
            return True if event.startswith(SPAN_PREFIX) else None

        lines = []
        for buf in plane["lines"]:
            line = _line(buf, plane, keep)
            if not line["events"]:
                continue
            if device and line["name"] == OPS_LINE:
                for ev in line["events"]:
                    ev[3] = {"scope": scope_of(ev[3].get("tf_op"))}
            lines.append(line)
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _nest(events: list) -> list:
    """Spans of one thread as nodes with their parent: sorted by start
    (the longer first on a tie), a stack gives the enclosing span."""
    nodes = [
        {"name": n, "start": s, "end": s + d, "args": a, "parent": None,
         "kids": [], "kids_ms": 0.0}
        for n, s, d, a in sorted(events, key=lambda e: (e[1], -e[2]))
    ]
    stack: list = []
    for node in nodes:
        while stack and stack[-1]["end"] < node["end"]:
            stack.pop()
        if stack:
            node["parent"] = stack[-1]
            stack[-1]["kids"].append(node)
            stack[-1]["kids_ms"] += (node["end"] - node["start"]) / 1e6
        stack.append(node)
    return nodes


def _innermost(node: dict, out: list) -> list:
    """The span tree under ``node`` flattened to [start, end, name]
    segments: at every instant the innermost span open."""
    name = node["name"][len(SPAN_PREFIX):]
    edge = node["start"]
    for kid in node["kids"]:
        if kid["start"] > edge:
            out.append([edge, kid["start"], name])
        _innermost(kid, out)
        edge = max(edge, kid["end"])
    if node["end"] > edge:
        out.append([edge, node["end"], name])
    return out


def _self_times(op_events: list) -> list:
    """(name, scope, start, self_ns) of each device op: an op that
    encloses others (a while loop and its body) keeps only what they
    do not cover."""
    evs = sorted(op_events, key=lambda e: (e[1], -e[2]))
    out = []
    stack: list = []
    for name, s, d, a in evs:
        rec = [name, a.get("scope", ""), s, d]
        while stack and stack[-1][0] < s + d:
            stack.pop()
        if stack:
            stack[-1][1][3] -= d
        stack.append((s + d, rec))
        out.append(rec)
    return out


def _module_name(name: str) -> str:
    return name.split("(")[0]


def _add(d: dict, k, v) -> None:
    d[k] = d.get(k, 0.0) + v


def reduce(trace: dict) -> dict:
    """Per statement class: spans (count, total, self), the split of
    the dispatch, device time by program and scope, idle by cause,
    launches/syncs/retries. Times in milliseconds, whole-trace totals;
    ``statements`` is the divisor for a per-statement reading."""
    threads = []
    dev_planes = []
    for p in trace["planes"]:
        if p["name"].startswith(DEVICE_PREFIX):
            if p["name"][len(DEVICE_PREFIX):].isdigit():
                dev_planes.append(p)
            continue
        for line in p["lines"]:
            threads.append(_nest(line["events"]))
    # statements: each wire.request, else (in-process sessions) each
    # outermost query span; the class is the query span's queryid
    stmts = []
    for nodes in threads:
        roots = [n for n in nodes if n["name"] == REQUEST]
        if not roots:
            roots = [
                n for n in nodes
                if n["name"] == QUERY and n["parent"] is None
            ]
        for r in roots:
            r["spans"] = []
            stmts.append(r)
        for n in nodes:
            top = n
            while top is not None and "spans" not in top:
                top = top["parent"]
            if top is not None:
                top["spans"].append(n)
                if n["name"] == QUERY and "queryid" in n["args"]:
                    top["class"] = str(n["args"]["queryid"])
    stmts.sort(key=lambda r: r["start"])
    classes: dict = {}

    def cls(stmt) -> dict:
        key = stmt.get("class", "unclassified")
        c = classes.get(key)
        if c is None:
            c = classes[key] = {
                "statements": 0, "spans": {}, "launches": 0, "syncs": 0,
                "retries": 0, "programs_ms": {}, "scopes_ms": {},
                "unscoped_ops_ms": {}, "device_busy_ms": 0.0,
                "idle_ms": {}, "join_modes": {}, "joins": {},
                "grouping": {}, "groups": {}, "group_keys": {},
                "fragments": {},
                "all_to_all": {},
            }
        return c

    for st in stmts:
        c = cls(st)
        c["statements"] += 1
        for n in st["spans"]:
            name = n["name"][len(SPAN_PREFIX):]
            ms = (n["end"] - n["start"]) / 1e6
            rec = c["spans"].setdefault(
                name, {"count": 0, "total_ms": 0.0, "self_ms": 0.0}
            )
            rec["count"] += 1
            rec["total_ms"] += ms
            rec["self_ms"] += ms - n["kids_ms"]
            frag = n["args"].get("frag")
            if frag is not None and name in ("fused.launch",
                                             "fused.exchange"):
                f = c["fragments"].setdefault(str(frag), {
                    "programs": {}, "motion": None, "target": None,
                    "exchange_ms": 0.0, "rows": 0, "slots": 0, "bytes": 0,
                })
                if name == "fused.launch":
                    prog = str(n["args"].get("program"))
                    f["programs"][prog] = f["programs"].get(prog, 0) + 1
                else:
                    f["motion"] = n["args"].get("motion")
                    f["target"] = n["args"].get("target")
                    f["exchange_ms"] += ms
                    for k in ("rows", "slots", "bytes"):
                        f[k] += int(n["args"].get(k) or 0)
            if name == "fused.launch":
                c["launches"] += 1
                if "retry_of" in n["args"]:
                    c["retries"] += 1
                # each join's formulation with its static widths a
                # device; a grouped final's formulation (``direct/<slots>``
                # or ``sort``), capacity and keys
                for arg in ("join_modes", "joins", "grouping", "groups",
                            "group_keys"):
                    v = n["args"].get(arg)
                    if v:
                        c[arg][str(v)] = c[arg].get(str(v), 0) + 1
            elif name == "fused.wait":
                c["syncs"] += 1

    def stmt_at(t: float):
        """The statement whose span covers device time ``t``, give or
        take the profiler's host/device clock skew."""
        lo, hi = 0, len(stmts)
        while lo < hi:
            mid = (lo + hi) // 2
            if stmts[mid]["start"] <= t:
                lo = mid + 1
            else:
                hi = mid
        if lo and stmts[lo - 1]["end"] + SKEW_NS >= t:
            return stmts[lo - 1]
        if lo < len(stmts) and stmts[lo]["start"] - SKEW_NS <= t:
            return stmts[lo]
        return None

    outside = {"programs_ms": {}, "device_busy_ms": 0.0}
    collective: list = []  # per chip: the all_to_all ops' intervals
    compute: list = []  # per chip: the union of every other op
    for plane in dev_planes:
        modules, ops = [], []
        for line in plane["lines"]:
            if line["name"] == MODULES_LINE:
                modules = line["events"]
            elif line["name"] == OPS_LINE:
                ops = line["events"]
        # a program belongs to the statement its midpoint falls in; an
        # op to its program (same clock, exact)
        mods = sorted((s, s + d, _module_name(n)) for n, s, d, _a in modules)
        owner = [stmt_at((s + e) / 2.0) for s, e, _n in mods]
        for (s, e, name), st in zip(mods, owner):
            tgt = cls(st) if st is not None else outside
            _add(tgt["programs_ms"], name, (e - s) / 1e6)
        starts = [m[0] for m in mods]
        for name, scope, s, self_ns in _self_times(ops):
            i = bisect.bisect_right(starts, s) - 1
            st = (
                owner[i] if i >= 0 and s <= mods[i][1] else stmt_at(s)
            )
            if st is None:
                outside["device_busy_ms"] += self_ns / 1e6
                continue
            c = cls(st)
            c["device_busy_ms"] += self_ns / 1e6
            _add(c["scopes_ms"], scope or "(no scope)", self_ns / 1e6)
            if not scope:
                _add(c["unscoped_ops_ms"], name.split(" = ")[0][:60],
                     self_ns / 1e6)
        busy = _union([[s, s + d] for _n, s, d, _a in ops])
        a2a, work = [], []
        for _n, s, d, a in ops:
            (a2a if a.get("scope", "").endswith(ALL_TO_ALL)
             else work).append([s, s + d])
        collective.append((plane["name"], _union(a2a)))
        compute.append(_union(work))
        for st in stmts:
            c = cls(st)
            segments = _innermost(st, [])
            edge = st["start"]
            for s, e in busy + [[st["end"], st["end"]]]:
                if e <= st["start"]:
                    continue
                s = min(s, st["end"])
                if s > edge:
                    _causes(c["idle_ms"], edge, s, mods, segments)
                edge = max(edge, e)
                if edge >= st["end"]:
                    break
    # exposed against hidden: a chip's time inside the collective while
    # some OTHER chip was still running an op outside it
    for i, (chip, spans_i) in enumerate(collective):
        others = _union([
            iv for j, ivs in enumerate(compute) if j != i for iv in ivs
        ])
        for s, e in spans_i:
            st = stmt_at((s + e) / 2.0)
            if st is None:
                continue
            rec = cls(st)["all_to_all"].setdefault(
                chip, {"total_ms": 0.0, "hidden_ms": 0.0}
            )
            rec["total_ms"] += (e - s) / 1e6
            rec["hidden_ms"] += _overlap(s, e, others) / 1e6
    for c in classes.values():
        c["unscoped_ops_ms"] = dict(sorted(
            c["unscoped_ops_ms"].items(), key=lambda kv: -kv[1]
        )[:8])
        split = {
            s: c["spans"].get(s, {}).get("self_ms", 0.0)
            for s in SPLIT_SPANS
        }
        c["dispatch_split_ms"] = split
        c["dispatch_split_sum_ms"] = sum(split.values())
        c["fused_self_ms"] = c["spans"].get("fused", {}).get("self_ms", 0.0)
    return {
        "statements": len(stmts), "classes": classes, "outside": outside,
        "chips": len(dev_planes),
    }


def _overlap(lo: float, hi: float, intervals: list) -> float:
    """Length of [lo, hi) covered by sorted disjoint ``intervals``."""
    i = bisect.bisect_left(intervals, [lo, lo])
    if i and intervals[i - 1][1] > lo:
        i -= 1
    total = 0.0
    while i < len(intervals) and intervals[i][0] < hi:
        total += max(min(intervals[i][1], hi) - max(intervals[i][0], lo), 0)
        i += 1
    return total


def _causes(idle: dict, lo: float, hi: float, modules: list,
            segments: list) -> None:
    """Put the device's idle gap [lo, hi) down to its causes: the part
    a running program covers is ``in_program:<module>`` (the chip idled
    between two of its ops); the rest goes to the innermost span open
    on the serving thread over it, ``unattributed`` where none is."""
    rest = [[lo, hi]]
    for s, e, name in modules:
        if e <= lo:
            continue
        if s >= hi:
            break
        _add(idle, "in_program:" + name, (min(e, hi) - max(s, lo)) / 1e6)
        rest = [
            piece for a, b in rest
            for piece in ([a, min(b, s)], [max(a, e), b])
            if piece[1] > piece[0]
        ]
    for a, b in rest:
        covered = 0.0
        for s, e, name in segments:
            if e <= a or s >= b:
                continue
            part = min(e, b) - max(s, a)
            covered += part
            _add(idle, name, part / 1e6)
        if b - a - covered > 1e-6:
            _add(idle, "unattributed", (b - a - covered) / 1e6)


def render(report: dict) -> str:
    """The report as text, per-statement means."""
    chips = max(report.get("chips", 1), 1)
    out = [f"{report['statements']} statements traced"
           + (f" on {chips} chips (device times summed over them)"
              if chips > 1 else "")]
    for key, c in sorted(
        report["classes"].items(),
        key=lambda kv: -kv[1]["spans"].get(
            "wire.request", kv[1]["spans"].get("query", {})
        ).get("total_ms", 0.0),
    ):
        n = max(c["statements"], 1)
        out.append("")
        out.append(
            f"class {key}: {c['statements']} statements, per statement "
            f"{c['launches'] / n:.2f} launches, {c['syncs'] / n:.2f} "
            f"syncs, {c['retries'] / n:.2f} retries"
            + (f", join_modes {sorted(c['join_modes'])}"
               if c["join_modes"] else "")
            + (f", joins {sorted(c['joins'])}" if c["joins"] else "")
            + (f", grouping {sorted(c['grouping'])}" if c["grouping"] else "")
            + (f", groups {sorted(c['groups'])} of group_keys "
               f"{sorted(c['group_keys'])}" if c["groups"] else "")
        )
        out.append("  span                  count   total ms    self ms"
                   "  (per statement)")
        for name, r in sorted(
            c["spans"].items(), key=lambda kv: -kv[1]["total_ms"]
        ):
            out.append(
                f"  {name:<20} {r['count'] / n:>6.2f} "
                f"{r['total_ms'] / n:>10.3f} {r['self_ms'] / n:>10.3f}"
            )
        out.append(
            "  dispatch split (self ms): " + " ".join(
                f"{k.split('.')[1]}={v / n:.3f}"
                for k, v in c["dispatch_split_ms"].items()
            ) + f" sum={c['dispatch_split_sum_ms'] / n:.3f}"
            f" fused_self={c['fused_self_ms'] / n:.3f}"
        )
        if c.get("fragments"):
            out.append("  fragments (per statement):")
            for frag, f in sorted(c["fragments"].items()):
                progs = ", ".join(
                    f"{k} x{v / n:.2f}" for k, v in f["programs"].items()
                )
                line = f"    {frag:<6} {progs}"
                if f["motion"]:
                    line += (
                        f" | {f['motion']} to {f['target']}: "
                        f"{f['exchange_ms'] / n:.3f} ms, rows "
                        f"{f['rows'] / n:.0f}, slots {f['slots'] / n:.0f}"
                        f", bytes {f['bytes'] / n:.0f}"
                    )
                out.append(line)
        if c.get("all_to_all"):
            out.append("  exchange/all_to_all per chip (ms a statement): "
                       "total = exposed + hidden behind other chips' work")
            for chip, r in sorted(c["all_to_all"].items()):
                out.append(
                    f"    {chip:<16} {r['total_ms'] / n:>10.3f} = "
                    f"{(r['total_ms'] - r['hidden_ms']) / n:.3f} + "
                    f"{r['hidden_ms'] / n:.3f}"
                )
        busy = c["device_busy_ms"]
        out.append(f"  device busy {busy / n:.3f} ms; by program:")
        for k, v in sorted(c["programs_ms"].items(), key=lambda kv: -kv[1]):
            out.append(f"    {k:<40} {v / n:>10.3f}")
        out.append("  by scope (op self time):")
        for k, v in sorted(c["scopes_ms"].items(), key=lambda kv: -kv[1]):
            share = 100.0 * v / busy if busy else 0.0
            out.append(f"    {k:<40} {v / n:>10.3f}  {share:5.1f} %")
        if c["unscoped_ops_ms"]:
            out.append("  ops under no scope:")
            for k, v in c["unscoped_ops_ms"].items():
                out.append(f"    {k:<60} {v / n:>10.3f}")
        idle = sum(c["idle_ms"].values())
        out.append(f"  device idle inside statements {idle / n:.3f} ms:")
        for k, v in sorted(c["idle_ms"].items(), key=lambda kv: -kv[1]):
            share = 100.0 * v / idle if idle else 0.0
            out.append(f"    {k:<40} {v / n:>10.3f}  {share:5.1f} %")
    o = report["outside"]
    if o["programs_ms"]:
        out.append("")
        out.append("device programs outside any statement (ms):")
        for k, v in sorted(o["programs_ms"].items(), key=lambda kv: -kv[1]):
            out.append(f"    {k:<40} {v:>10.3f}")
    return "\n".join(out)
