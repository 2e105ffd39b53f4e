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

- every device op with a stage, its sizes and where its operands lie,
  read from the program's text (below).

Two steps, so the arithmetic can be checked on a small recorded trace
kept as JSON: ``load`` turns the file into plain lists, ``reduce`` does
the rest. Nothing here runs on a statement's path, and nothing of JAX
or of the benchmark is imported: the file is decoded by the few lines
of protobuf wire format below (``jax.profiler.ProfileData`` does not
expose the per-operation metadata that holds the scope).

**The program text.** An ``XLA Ops`` event's name is its instruction's
whole HLO line: result name, shapes with element type, dimensions and
layout (memory space ``S(<n>)``, none is HBM), opcode, a fusion's kind
or a custom call's target, and each operand as shape and ``%name``.
``reduce`` parses it once an op of a program (text it does not know
leaves the op what it was). The profiler also stores a program's whole
optimized HLO module in the trace (plane ``/host:metadata``, one event
metadata a program named ``<module>(<program id>)`` as its ``XLA
Modules`` events are, stat ``Hlo Proto``), but on a v5e only for a
program the traced process compiled itself: one loaded from the
persistent compile cache, as a benchmark cell's are after its first
run, comes without. Where the module is there ``load`` decodes it
(``programs``) and it is the graph: every instruction, those that never
run as ops too (``get-tuple-element``, ``bitcast``, ``tuple``,
parameters), and what each fusion's callee holds. Where it is not, the
graph is the op lines', and an operand that is no op event (a line
gives its name and shape, not what it reads) is bound to the result it
most likely reads (``_graph``: a tuple's element by exact shape and
layout, a ``bitcast`` by type and element count, the latest such result
before its reader). An op belongs to the program run that encloses it
on its chip: ``%fusion.2`` is another op in each program.

**A stage for every op.** An op whose own ``op_name`` gives an ``otb/``
scope keeps it (``scopes_ms``: the DIRECT time, what every earlier
report held). Any other op takes a stage from the graph, by one rule:

1. from inside, where the program's module is there: a fusion whose
   callee's instructions carry scopes takes the scope most of them
   carry (ties: the first by name);
2. else from the ops that READ it (compiler-made splits, relayouts and
   copies prepare an input for the op they serve), found through
   instructions that are no op events; where its readers' stages
   differ, the reader that starts first in the program run gives its;
3. where no reader has a stage, from the ops it reads, the same way;
4. repeated until nothing moves, readers before producers in every
   round, each round judged on the stages of the round before, so that
   chains (split -> relayout -> scoped op) resolve and the result does
   not depend on the order ops are visited in.

``scopes_inherited_ms`` holds what each scope took in this way,
``inherited_ops`` each such op once with the op it took its stage from,
``unscoped_ops_ms`` / ``unscoped_ms`` / ``unscoped_pct`` what the graph
could not place. Direct + inherited + unscoped is the device op self
time.

**What an op read, from where, at what rate.** ``ops`` lists a
statement class's costliest ops: program, op, stage, self ms, result
elements and ns an element, bytes moved as GB/s and as a share of the
chip's HBM bandwidth (the device plane states it), and each operand as
``dtype[dims]@S(1)`` or ``@hbm``; ``scope_rates`` sums the same by
scope, with ``operands_outside_s1``: the ops that gather from a table
(the first operand of an op whose ``op_name`` ends in ``gather``; with
the module, a ``gather``'s first operand followed to the fusion's
parameter) which lies outside ``S(1)``, and their ms. A gather costs
8.6 ns an element from a table in ``S(1)`` and 14-22 from one outside
(PERF.md, the chip record). Bytes are the compiler's own count for the
op where the event carries one, else result + operands by the shapes.
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
TOP_OPS = 16  # rows of a statement class's costliest-ops table
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
        elif no == 5:
            value = _text(v)
        elif no == 6:
            value = v  # bytes stay bytes: a program's HLO module
        elif no == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _plane(buf) -> dict:
    name = ""
    lines, emeta, smeta, pstats = [], [], {}, []
    for no, wt, v in _fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            lines.append(v)
        elif no == 4:
            emeta.append(v)
        elif no == 6:
            pstats.append(v)
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
            "stats": smeta,
            "plane_stats": dict(_stat(v, smeta) for v in pstats)}


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


# ---------------------------------------------------------------------------
# the program text: an op event's name is its instruction's HLO line
# ---------------------------------------------------------------------------

# ops whose operands and results are whole tuples of buffers they hand
# on, not bytes they move in their own time
_CONTROL = frozenset(("while", "conditional", "call"))
PEAK_HBM = "peak_hbm_bw_gigabytes_per_second"  # a device plane's own stat
_NAME = re.compile(r"(?:ROOT )?%?([\w.\-]+) = ")
_ARRAY = re.compile(r"([a-z][a-z0-9]*)\[([^\]]*)\](\{[^{}]*\})?")
_SPACE = re.compile(r"S\((\d+)\)")
_OPCODE = re.compile(r" ?([a-z][\w\-]*)\(")
_OPERAND = re.compile(r" ?%?([\w.\-]+)")
_KIND = re.compile(r"\bkind=(\w+)|custom_call_target=\"([^\"]*)\"")
_COMMENT = re.compile(r"/\*[^*]*\*/")  # ``/*index=5*/`` in a long tuple


def _text_shape(text: str, i: int, out: list) -> int:
    """Reads one shape of HLO text at ``i`` into ``out`` as its array
    leaves ``[dtype, dims, memory space, layout]`` (a tuple flattened;
    space 0 is HBM, ``S(<n>)`` in the layout n) and returns its end."""
    if text[i] == "(":
        i += 1
        while text[i] != ")":
            i = _text_shape(text, i, out)
            if text[i] == ",":
                i += 1
            while text[i] == " ":
                i += 1
        return i + 1
    m = _ARRAY.match(text, i)
    if m is None:
        raise ValueError(text[i:i + 20])
    dims = [int(d.lstrip("<=")) for d in m.group(2).split(",") if d]
    layout = m.group(3) or ""
    space = _SPACE.search(layout)
    out.append(
        [m.group(1), dims, int(space.group(1)) if space else 0, layout]
    )
    return m.end()


def hlo_line(text: str):
    """An op event's name, where it is the instruction's HLO line
    (``%fusion.6 = pred[2097152]{0:T(1024)(128)(4,1)S(1)} fusion(
    pred[2097152]{...} %x, ...), kind=kCustom, ...``), as ``(name,
    record, {operand: its leaves})``: ``op`` the opcode, ``kind`` a
    fusion's kind or a custom call's target, ``shapes`` the result's
    leaves, ``operands`` their names. None for text that is none."""
    text = _COMMENT.sub("", text)
    try:
        m = _NAME.match(text)
        if m is None:
            return None
        shapes: list = []
        i = _text_shape(text, m.end(), shapes)
        op = _OPCODE.match(text, i)
        if op is None:
            return None
        i = op.end()
        operands, stubs = [], {}
        while text[i] != ")":
            leaves: list = []
            if text[i] != "%":
                i = _text_shape(text, i, leaves)
            o = _OPERAND.match(text, i)
            if o is None:
                return None
            operands.append(o.group(1))
            if leaves:
                stubs[o.group(1)] = leaves
            i = o.end()
            if text[i] == ",":
                i += 1
            while text[i] == " ":
                i += 1
        kind = _KIND.search(text, i)
        return m.group(1), {
            "op": op.group(1), "shapes": shapes, "operands": operands,
            "kind": (kind.group(1) or kind.group(2)) if kind else "",
        }, stubs
    except (ValueError, IndexError):
        return None


# the same from a program's HLO module, where the trace holds one: the
# profiler stores it for a program the traced process compiled itself
# (a v5e run: a program loaded from the persistent compile cache comes
# without, so a benchmark cell's warm runs have none)

METADATA_PLANE = "/host:metadata"
HLO_PROTO = "Hlo Proto"  # the stat of a program's event metadata there
# xla_data.proto PrimitiveType
_DTYPES = {
    1: "pred", 2: "s8", 3: "s16", 4: "s32", 5: "s64", 6: "u8", 7: "u16",
    8: "u32", 9: "u64", 10: "f16", 11: "f32", 12: "f64", 14: "opaque",
    15: "c64", 16: "bf16", 17: "token", 18: "c128", 19: "f8e5m2",
    20: "f8e4m3fn", 21: "s4", 22: "u4", 23: "f8e4m3b11fnuz",
    24: "f8e5m2fnuz", 25: "f8e4m3fnuz", 26: "s2", 27: "u2",
}
_TUPLE = 13
# what of a fusion's callee is worth a label in the costliest-ops table
_HEAVY = frozenset((
    "gather", "scatter", "sort", "reduce", "reduce-window", "dot",
    "transpose", "concatenate", "dynamic-slice", "dynamic-update-slice",
))
# instructions a gather's table passes through unchanged on its way
# from the fusion's parameter
_VIEWS = frozenset(("bitcast", "convert", "copy", "reshape"))


def _ints(v) -> list:
    """A repeated int64 field: one varint, or a packed run of them."""
    if isinstance(v, int):
        return [_signed(v)]
    out, i, n = [], 0, len(v)
    while i < n:
        x = shift = 0
        while True:
            b = v[i]
            i += 1
            x |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        out.append(_signed(x))
    return out


def _leaves(buf, out: list) -> list:
    """A ShapeProto as the array leaves ``_text_shape`` gives (the
    layout's text left empty)."""
    etype, dims, space, subs = 0, [], 0, []
    for no, _wt, v in _fields(buf):
        if no == 2:
            etype = v
        elif no == 3:
            dims.extend(_ints(v))
        elif no == 4:
            subs.append(v)
        elif no == 5:
            for n2, _w, v2 in _fields(v):
                if n2 == 8:
                    space = v2
    if etype == _TUPLE:
        for sub in subs:
            _leaves(sub, out)
    else:
        out.append([_DTYPES.get(etype, f"type{etype}"), dims, space, ""])
    return out


def _instruction(buf) -> dict:
    ins = {"name": "", "op": "", "kind": "", "shapes": [], "scope": "",
           "id": None, "operand_ids": [], "calls": []}
    for no, _wt, v in _fields(buf):
        if no == 1:
            ins["name"] = _text(v)
        elif no == 2:
            ins["op"] = _text(v)
        elif no == 3:
            _leaves(v, ins["shapes"])
        elif no == 7:
            for n2, _w, v2 in _fields(v):
                if n2 == 2:
                    ins["scope"] = scope_of(_text(v2))
        elif no == 9:
            ins["param"] = v
        elif no in (11, 28) and len(v):  # fusion kind, custom-call target
            ins["kind"] = _text(v)
        elif no == 35:
            ins["id"] = v
        elif no == 36:
            ins["operand_ids"].extend(_ints(v))
        elif no == 38:
            ins["calls"].extend(_ints(v))
    return ins


def _tables(members: list) -> list:
    """Which parameters of a fused computation are gathered FROM: each
    ``gather``'s first operand, followed through views to a parameter."""
    by_name = {m["name"]: m for m in members}
    found = set()
    for m in members:
        if m["op"] != "gather" or not m["operands"]:
            continue
        src = by_name.get(m["operands"][0])
        while src is not None and src["op"] in _VIEWS and src["operands"]:
            src = by_name.get(src["operands"][0])
        if src is not None and src["op"] == "parameter":
            found.add(src.get("param", 0))
    return sorted(found)


def hlo_module(buf) -> dict:
    """One ``HloProto`` as ``{instruction name: record}`` over all of its
    computations (names are unique a module): ``op`` the opcode, ``kind``
    a fusion's kind or a custom call's target, ``shapes`` the result's
    array leaves, ``operands`` their names, ``scope`` its own ``otb/``
    scope; a fusion also says what its callee holds: ``inner`` the
    scopes of its instructions with their counts, ``holds`` its heavy
    opcodes, ``tables`` the operands a gather inside reads from."""
    module = b""
    for no, _wt, v in _fields(buf):
        if no == 1:
            module = v
    comps: dict = {}
    for no, _wt, comp in _fields(module):
        if no != 3:
            continue
        cid, by_id = None, {}
        for n2, _w, v2 in _fields(comp):
            if n2 == 5:
                cid = v2
            elif n2 == 2:
                ins = _instruction(v2)
                by_id[ins.pop("id")] = ins
        for ins in by_id.values():
            ins["operands"] = [
                by_id[i]["name"] for i in ins.pop("operand_ids")
                if i in by_id
            ]
        comps[cid] = list(by_id.values())
    instrs: dict = {}
    for members in comps.values():
        for ins in members:
            calls = ins.pop("calls")
            if ins["op"] == "fusion" and calls and calls[0] in comps:
                callee = comps[calls[0]]
                inner: dict = {}
                for m in callee:
                    if m["scope"]:
                        inner[m["scope"]] = inner.get(m["scope"], 0) + 1
                if inner:
                    ins["inner"] = inner
                holds = sorted({m["op"] for m in callee} & _HEAVY)
                if holds:
                    ins["holds"] = holds
                tables = _tables(callee)
                if tables:
                    ins["tables"] = tables
            elif ins["op"] == "gather":
                ins["tables"] = [0]
            instrs[ins["name"]] = ins
    for ins in instrs.values():
        del ins["name"]
    return instrs


def load(path: str) -> dict:
    """Device planes' module and op lines (each op with its scope, the
    primitive its ``op_name`` ends in and the bytes the compiler counts
    it to access) and the chip's HBM bandwidth as the plane states it,
    of the host planes the ``otb:`` spans with their args, and under
    ``programs`` the instructions of each program whose HLO module the
    trace holds."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    planes, programs = [], {}
    for no, _wt, v in _fields(data):
        if no != 1:
            continue
        plane = _plane(v)
        if plane["name"] == METADATA_PLANE:
            # one event metadata a program, ``<module>(<program id>)`` as
            # its ``XLA Modules`` events are named
            for name, stats in plane["events"].values():
                if stats.get(HLO_PROTO) is not None:
                    programs[name] = hlo_module(stats[HLO_PROTO])
            continue
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
                    stats = ev[3]
                    op_name = str(stats.get("tf_op") or "")
                    ev[3] = {"scope": scope_of(op_name)}
                    if "/" in op_name:
                        ev[3]["prim"] = (
                            op_name.split(":")[0].rsplit("/", 1)[-1]
                        )
                    if stats.get("bytes_accessed"):
                        ev[3]["bytes"] = stats["bytes_accessed"]
            lines.append(line)
        if lines:
            kept = {"name": plane["name"], "lines": lines}
            if device and plane["plane_stats"].get(PEAK_HBM):
                kept["hbm_gb_per_s"] = plane["plane_stats"][PEAK_HBM]
            planes.append(kept)
    return {"planes": planes, "programs": programs}


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
    """(name, scope, start, self_ns, args) of each device op: an op
    that encloses others (a while loop and its body) keeps only what
    they do not cover."""
    evs = sorted(op_events, key=lambda e: (e[1], -e[2]))
    out = []
    stack: list = []
    for name, s, d, a in evs:
        rec = [name, a.get("scope", ""), s, d, a]
        while stack and stack[-1][0] < s + d:
            stack.pop()
        if stack:
            stack[-1][1][3] -= d
        stack.append((s + d, rec))
        out.append(rec)
    return out


def _module_name(name: str) -> str:
    return name.split("(")[0]


def _op(name: str) -> str:
    """An op event's instruction (``%fusion.6``): what stands before
    `` = `` in its HLO line."""
    return name.split(" = ")[0]


def _graph(instrs, seen: dict) -> dict:
    """The instructions of one program by name: its HLO module where
    the trace held one; else (and for an op the module does not name)
    what each op event's own HLO line says, with a record for every
    operand that is no op event. Such an operand is a parameter, a
    constant, a ``get-tuple-element`` or a view, and a line says only
    its name and shape, so it is bound to the result it most likely
    reads, going through the ops in the order they start in a program
    run: as a tuple's element to the latest tuple-shaped result so far
    with a leaf of exactly its type, dimensions and layout (async
    starts apart: their ``-done`` names them); else, where its name
    says ``bitcast``, as a view to the latest result of its type and
    element count that nothing has read yet (failing that, the
    latest). Anything else binds to nothing."""
    graph = dict(instrs or {})
    parsed = []
    for op, (text, _scope, start, args) in seen.items():
        if op.lstrip("%") in graph:
            continue
        got = hlo_line(text)
        if got is None:
            continue
        name, rec, stubs = got
        if args.get("prim") == "gather" or rec["op"] == "gather":
            rec["tables"] = [0]  # gather(table, indices)
        graph[name] = rec
        parsed.append((start, name, rec, stubs))
    events = {op.lstrip("%") for op in seen}
    elements: dict = {}  # a leaf -> the last tuple-shaped result with it
    wholes: dict = {}  # (type, element count) -> results, oldest first
    read = set()
    for _start, name, rec, stubs in sorted(parsed, key=lambda p: p[:2]):
        read.update(o for o in rec["operands"] if o in events)
        for operand, leaves in stubs.items():
            if operand in events or operand in graph:
                continue
            source = None
            if len(leaves) == 1:
                source = elements.get(_leaf_key(leaves[0]))
                if source is None and operand.startswith("bitcast"):
                    same = wholes.get((leaves[0][0], _elements(leaves[0])), ())
                    source = next(
                        (r for r in reversed(same) if r not in read),
                        same[-1] if same else None,
                    )
            if source is not None:
                read.add(source)
            graph[operand] = {
                "op": "", "kind": "", "shapes": leaves,
                "operands": [source] if source else [],
            }
        if rec["op"].endswith("-start"):
            continue
        for leaf in rec["shapes"]:
            if len(rec["shapes"]) > 1:
                elements[_leaf_key(leaf)] = name
            wholes.setdefault((leaf[0], _elements(leaf)), []).append(name)
    return graph


def _leaf_key(leaf) -> tuple:
    return leaf[0], tuple(leaf[1]), leaf[3]


def _stages(graph: dict, seen: dict) -> dict:
    """``{op: (scope, the op it came from, how)}`` for every op event of
    a program whose own ``op_name`` gave no scope and the graph could
    place (the module docstring has the rule). ``seen`` is ``{op: (its
    text, its own scope, its first start in a program run, its event's
    args)}``."""
    users: dict = {}
    for name, ins in graph.items():
        for operand in ins["operands"]:
            users.setdefault(operand, []).append(name)
    events = {op.lstrip("%"): op for op in seen}

    def nearest(name: str, edges) -> list:
        """The op events reached from ``name`` along ``edges`` through
        instructions that are no events."""
        found, done, stack = [], {name}, list(edges(name))
        while stack:
            n = stack.pop()
            if n in done:
                continue
            done.add(n)
            if n in events:
                found.append(events[n])
            else:
                stack.extend(edges(n))
        return found

    staged: dict = {}
    todo = []
    for op, (_text, scope, _start, _args) in seen.items():
        if scope:
            continue
        inner = graph.get(op.lstrip("%"), {}).get("inner")
        if inner:
            best = min(inner, key=lambda k: (-inner[k], k))
            staged[op] = (best, op, "inside")
        else:
            todo.append(op)
    readers = {
        op: nearest(op.lstrip("%"), lambda n: users.get(n, ()))
        for op in todo
    }
    producers = {
        op: nearest(
            op.lstrip("%"), lambda n: graph.get(n, {}).get("operands", ())
        )
        for op in todo
    }

    def scope_at(op: str) -> str:
        return seen[op][1] or staged.get(op, ("",))[0]

    while True:
        for edges, how in ((readers, "reader"), (producers, "producer")):
            placed = {}
            for op in todo:
                if op in staged:
                    continue
                scoped = [o for o in edges[op] if scope_at(o)]
                if scoped:
                    first = min(scoped, key=lambda o: (seen[o][2], o))
                    placed[op] = (scope_at(first), first, how)
            if placed:
                staged.update(placed)
                break
        else:
            return staged


def _bits(dtype: str) -> int:
    if dtype == "pred":
        return 8
    m = re.search(r"\d+", dtype)
    return int(m.group()) if m else 0


def _elements(leaf) -> int:
    n = 1
    for d in leaf[1]:
        n *= d
    return n


def _show(leaf) -> str:
    """``dtype[dims]@S(1)`` or ``@hbm``."""
    return "%s[%s]@%s" % (
        leaf[0], ",".join(map(str, leaf[1])),
        f"S({leaf[2]})" if leaf[2] else "hbm",
    )


def _describe(graph: dict, op: str, counted) -> dict:
    """What one op wrote and read: its label, result and operands as
    text, result elements (its widest leaf), bytes moved (``counted``,
    the compiler's own count where the trace carries one: it takes a
    slice for a slice; else result + operands by the shapes, as for a
    custom call), and whether an operand a gather reads from lies
    outside memory space ``S(1)``."""
    ins = graph.get(op.lstrip("%"))
    if ins is None:
        return {"opcode": "", "result": [], "operands": [], "tables": [],
                "elements": 0, "bytes": counted or 0, "hbm_bytes": 0,
                "outside_s1": False}
    label = ins["op"]
    if ins.get("kind"):
        label += ":" + ins["kind"]
    if ins.get("holds"):
        label += "(" + ",".join(ins["holds"]) + ")"
    operands = [
        graph[o]["shapes"] if o in graph else [] for o in ins["operands"]
    ]
    leaves = ins["shapes"] + [x for part in operands for x in part]
    moved = 0
    if ins["op"] in _CONTROL or ins["op"].endswith(("-start", "-done")):
        pass  # its body's ops moved them; an async copy runs beside the ops
    elif counted:
        moved = counted
    else:
        moved = sum(_elements(x) * _bits(x[0]) // 8 for x in leaves)
    tables = [
        operands[i] for i in ins.get("tables", ()) if i < len(operands)
    ]
    return {
        "opcode": label,
        "result": [_show(leaf) for leaf in ins["shapes"]],
        "operands": [
            "(" + ", ".join(map(_show, leaves)) + ")" if len(leaves) != 1
            else _show(leaves[0]) for leaves in operands
        ],
        "tables": list(ins.get("tables", ())),
        "elements": max(
            [_elements(leaf) for leaf in ins["shapes"]], default=0
        ) if moved else 0,
        "bytes": moved,
        # what of it went through HBM: the leaves that lie there, by
        # the shapes (an operand in on-chip memory loads HBM with nothing)
        "hbm_bytes": min(moved, sum(
            _elements(x) * _bits(x[0]) // 8 for x in leaves if x[2] == 0
        )),
        "outside_s1": any(
            leaf[2] != 1 for leaves in tables for leaf in leaves
        ),
    }


def _add(d: dict, k, v) -> None:
    d[k] = d.get(k, 0.0) + v


def reduce(trace: dict) -> dict:
    """Per statement class: spans (count, total, self), the split of
    the dispatch, device time by program and scope, idle by cause,
    launches/syncs/retries. Times in milliseconds, whole-trace totals;
    ``statements`` is the divisor for a per-statement reading."""
    threads = []
    dev_planes = []
    programs = trace.get("programs") or {}
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
                "scopes_inherited_ms": {}, "inherited_ops": {},
                "unscoped_ops_ms": {}, "device_busy_ms": 0.0,
                "ops": {}, "scope_rates": {},
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
        mods = sorted(
            (s, s + d, _module_name(n), n) for n, s, d, _a in modules
        )
        owner = [stmt_at((s + e) / 2.0) for s, e, _n, _full in mods]
        for (s, e, name, _full), st in zip(mods, owner):
            tgt = cls(st) if st is not None else outside
            _add(tgt["programs_ms"], name, (e - s) / 1e6)
        starts = [m[0] for m in mods]
        # an op is its program's: ``%fusion.2`` is another op in each of
        # a cell's programs, and the same op in every run of one
        timed = []
        seen: dict = {}  # program -> {op: (text, scope, start, args)}
        for name, scope, s, self_ns, args in _self_times(ops):
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and s <= mods[i][1]
            prog = mods[i][3] if inside else ""
            op = _op(name)
            seen.setdefault(prog, {}).setdefault(
                op, (name, scope, s - mods[i][0] if inside else s, args)
            )
            timed.append(
                (op, scope, self_ns / 1e6, prog,
                 owner[i] if inside else stmt_at(s))
            )
        placed, told = {}, {}
        for prog, its in seen.items():
            graph = _graph(programs.get(prog), its)
            placed[prog] = _stages(graph, its)
            told[prog] = {
                op: _describe(graph, op, args.get("bytes"))
                for op, (_t, _s, _at, args) in its.items()
            }
        for op, scope, ms, prog, st in timed:
            if st is None:
                outside["device_busy_ms"] += ms
                continue
            c = cls(st)
            c["device_busy_ms"] += ms
            _add(c["scopes_ms"], scope or "(no scope)", ms)
            module = _module_name(prog)
            stage, source = scope, None
            if not scope:
                stage, source, how = placed[prog].get(op, ("", None, None))
                if stage:
                    _add(c["scopes_inherited_ms"], stage, ms)
                    took = c["inherited_ops"].get((module, op))
                    if took is None:
                        took = c["inherited_ops"][module, op] = {
                            "program": module, "op": op, "from": source,
                            "scope": stage, "how": how, "ms": 0.0,
                        }
                    took["ms"] += ms
                else:
                    _add(c["unscoped_ops_ms"], op[:60], ms)
            what = told[prog][op]
            # one row for the same op of one module's programs (a
            # literal-keyed program a parameter set), told by its shapes
            key = (module, op, *what["result"], *what["operands"])
            row = c["ops"].get(key)
            if row is None:
                row = c["ops"][key] = dict(
                    what, program=module, op=op, scope=stage,
                    inherited_from=source, ms=0.0, count=0,
                )
            row["ms"] += ms
            row["count"] += 1
            rate = c["scope_rates"].setdefault(
                stage or "(no scope)",
                {"ms": 0.0, "bytes": 0, "hbm_bytes": 0, "outside": {}},
            )
            rate["ms"] += ms
            rate["bytes"] += what["bytes"]
            rate["hbm_bytes"] += what["hbm_bytes"]
            if what["outside_s1"]:
                _add(rate["outside"], (module, op), ms)
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
    peak = next(
        (p["hbm_gb_per_s"] for p in dev_planes if "hbm_gb_per_s" in p), None
    )
    for c in classes.values():
        busy = c["device_busy_ms"]
        c["unscoped_ms"] = sum(c["unscoped_ops_ms"].values())
        c["unscoped_pct"] = 100.0 * c["unscoped_ms"] / busy if busy else 0.0
        c["unscoped_ops_ms"] = dict(sorted(
            c["unscoped_ops_ms"].items(), key=lambda kv: -kv[1]
        )[:8])
        c["inherited_ops"] = sorted(
            c["inherited_ops"].values(), key=lambda r: -r["ms"]
        )
        rows = sorted(c["ops"].values(), key=lambda r: -r["ms"])[:TOP_OPS]
        for r in rows:
            del r["outside_s1"]
            cells = r["count"] * r["elements"]
            r["ns_per_element"] = r["ms"] * 1e6 / cells if cells else None
            r.update(_rate(r["bytes"] * r["count"],
                           r.pop("hbm_bytes") * r["count"], r["ms"], peak))
        c["ops"] = rows
        c["scope_rates"] = {
            k: dict(
                _rate(v["bytes"], v["hbm_bytes"], v["ms"], peak),
                ms=v["ms"], bytes=v["bytes"], operands_outside_s1={
                    "ops": len(v["outside"]),
                    "ms": sum(v["outside"].values()),
                },
            )
            for k, v in c["scope_rates"].items()
        }
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


def _rate(moved: float, through_hbm: float, ms: float, peak) -> dict:
    """Bytes over milliseconds as GB/s, and where the trace states the
    chip's HBM bandwidth, what went through HBM as a share of it."""
    return {
        "gb_per_s": moved / (ms * 1e6) if ms and moved else None,
        "hbm_peak_pct": (
            100.0 * through_hbm / (ms * 1e6) / peak
            if ms and through_hbm and peak else None
        ),
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
    for s, e, name, _full in modules:
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


def _num(v, spec: str) -> str:
    return "-" if v is None else format(v, spec)


def _render_scopes(c: dict, n: int) -> list:
    """Device time by scope as direct + inherited, the ops the graph
    placed and those it could not, and the costliest ops with what each
    read from where (a report of before these keys existed renders its
    direct times)."""
    busy = c["device_busy_ms"]
    direct = dict(c["scopes_ms"])
    inherited = c.get("scopes_inherited_ms", {})
    rates = c.get("scope_rates", {})
    own = direct.pop("(no scope)", 0.0)
    left = c.get("unscoped_ms", own)
    out = ["  by scope (op self time, ms a statement): direct + inherited"
           " = total, share of busy; GB/s moved (% of HBM peak); gathers"
           " from a table outside S(1)"]
    rows = [
        (direct.get(k, 0.0) + inherited.get(k, 0.0), k)
        for k in set(direct) | set(inherited)
    ]
    if own:
        rows.append((left, "(no scope)"))
    for v, k in sorted(rows, key=lambda r: (-r[0], r[1])):
        r = rates.get(k, {})
        far = r.get("operands_outside_s1", {})
        first = 0.0 if k == "(no scope)" else direct.get(k, 0.0)
        out.append(
            f"    {k:<34} {first / n:>10.3f} + {(v - first) / n:>9.3f}"
            f" = {v / n:>10.3f}  {100.0 * v / busy if busy else 0.0:5.1f} %"
            f"  {_num(r.get('gb_per_s'), '7.1f')} GB/s"
            f" ({_num(r.get('hbm_peak_pct'), '.1f')} %)"
            + (f"  {far['ops']} outside S(1), {far['ms'] / n:.3f} ms"
               if far.get("ops") else "")
        )
    if own:
        out.append(
            f"  ops whose own op_name gives no scope {own / n:.3f} ms: "
            f"{(own - left) / n:.3f} placed by the graph, {left / n:.3f}"
            f" ({100.0 * left / busy if busy else 0.0:.1f} % of busy) still"
            " under none"
        )
    if c.get("inherited_ops"):
        out.append("  ops the graph placed (op <- the op it took its stage "
                   "from, stage, how):")
        for r in c["inherited_ops"][:TOP_OPS]:
            out.append(
                f"    {r['op']:<28} <- {r['from']:<28} {r['scope']:<26}"
                f" {r['how']:<8} {r['ms'] / n:>10.3f}"
            )
        rest = c["inherited_ops"][TOP_OPS:]
        if rest:
            out.append(f"    ... and {len(rest)} more, "
                       f"{sum(r['ms'] for r in rest) / n:.3f}")
    if c["unscoped_ops_ms"]:
        out.append("  ops under no scope:")
        for k, v in c["unscoped_ops_ms"].items():
            out.append(f"    {k:<60} {v / n:>10.3f}")
    if c.get("ops"):
        out.append("  costliest ops: program, op, stage (<- the op it came "
                   "from); self ms a statement, runs, ns an element of the "
                   "result, GB/s (% of HBM peak); opcode -> result <- "
                   "operands, each @S(<n>) or @hbm, * a gather's table")
        for r in c["ops"]:
            operands = ", ".join(
                ("*" if i in r["tables"] else "") + o
                for i, o in enumerate(r["operands"])
            )
            out.append(
                f"    {r['program'][4:]:<24} {r['op']:<24} "
                f"{r['scope'] or '(no scope)'}"
                f"{' <- ' + r['inherited_from'] if r['inherited_from'] else ''}"
            )
            out.append(
                f"      {r['ms'] / n:>10.3f} ms  x{r['count'] / n:.2f}"
                f"  {_num(r['ns_per_element'], '.2f')} ns/el"
                f"  {_num(r['gb_per_s'], '.1f')} GB/s"
                f" ({_num(r['hbm_peak_pct'], '.1f')} %)"
                + (f"  {r['opcode']} -> {', '.join(r['result'])}"
                   f" <- {operands}" if r["opcode"] else "")
            )
    return out


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
        out.extend(_render_scopes(c, n))
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
