"""From the profiler's trace to numbers: device busy time, time per named
program, the device operations that took most time, and the idle gaps
labelled by what the host was doing (the benchmark's own TraceAnnotation
spans, named ``bench:<label>``; ``bench:window`` bounds the window).

Two steps, so that the reduction can be checked on a small recorded
trace kept as JSON: ``load`` turns an ``.xplane.pb`` into plain lists,
``reduce`` does the arithmetic."""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
NAME_CHARS = 120  # an XLA op's name is its whole HLO line


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """Device planes whole; of the host planes only the bench: spans."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        lines = []
        for line in plane.lines:
            events = [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events
                if device or e.name.startswith(SPAN_PREFIX)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union(intervals: list) -> list:
    """Sorted disjoint [start, end] covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [
        [max(s, lo), min(e, hi)] for s, e in intervals
        if min(e, hi) > max(s, lo)
    ]


def _device_planes(trace: dict) -> list:
    # a chip's tensor core is "/device:TPU:<n>"; further planes of the
    # same chip (e.g. "... SparseCore ...") carry a suffix
    return [
        p for p in trace["planes"]
        if p["name"].startswith(DEVICE_PREFIX)
        and p["name"][len(DEVICE_PREFIX):].isdigit()
    ]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def host_spans(trace: dict) -> list:
    return [
        ev for p in trace["planes"] if not p["name"].startswith(DEVICE_PREFIX)
        for line in p["lines"] for ev in line["events"]
        if ev[0].startswith(SPAN_PREFIX)
    ]


def reduce(trace: dict) -> dict:
    spans = host_spans(trace)
    window = [s for s in spans if s[0] == WINDOW_SPAN]
    if not window:
        raise ValueError("trace has no bench:window span")
    lo = window[0][1]
    hi = lo + window[0][2]
    labels = sorted(
        (s for s in spans if s[0] != WINDOW_SPAN), key=lambda s: s[1]
    )
    planes = _device_planes(trace)
    if not planes:
        raise ValueError("trace has no device plane")
    busy_total = 0.0
    programs: dict = {}
    ops: dict = {}
    gaps: dict = {}
    for plane in planes:
        op_events = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
        busy = clip(
            union([[s, s + d] for _n, s, d in op_events]), lo, hi
        )
        busy_total += sum(e - s for s, e in busy)
        for name, s, d in _line(plane, MODULES_LINE):
            c = clip([[s, s + d]], lo, hi)
            if c:
                programs[name] = programs.get(name, 0.0) + c[0][1] - c[0][0]
        for name, s, d in _line(plane, OPS_LINE):
            c = clip([[s, s + d]], lo, hi)
            if c:
                ops[name] = ops.get(name, 0.0) + c[0][1] - c[0][0]
        edge = lo
        for s, e in busy + [[hi, hi]]:
            if s > edge:
                label = _label(labels, (edge + s) / 2.0)
                gaps[label] = gaps.get(label, 0.0) + s - edge
            edge = max(edge, e)
    n = len(planes)

    def top(d: dict) -> list:
        return [
            [k[:NAME_CHARS], v / 1e9 / n] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:10]
        ]

    return {
        "chips": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / 1e9 / n,
        "programs": {k: v / 1e9 / n for k, v in programs.items()},
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
        "statements_traced": sum(
            1 for s in labels if s[0].startswith(SPAN_PREFIX + "stmt:")
        ),
    }


def _label(labels: list, t: float) -> str:
    """What the host was doing at time t, by the benchmark's spans."""
    for name, s, d in labels:
        if s <= t <= s + d:
            return "inside " + name[len(SPAN_PREFIX):]
    return "between statements"
