"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics
read: device busy time, idle gaps, time per operation.

Two steps, so that the arithmetic can be checked against a small trace
kept under ``tests/data``: ``read_device_events`` turns the profiler's
file into plain records, and everything else works on those records.

A record: {"name", "start_ns", "dur_ns", "category", "shape"}; ``name``
is the HLO instruction's name, ``category`` the profiler's HLO category
("custom-call", "fusion", ...) where it gives one, ``shape`` the result
shape as "bf16[32,8,4,128]" where the event carries the instruction's
text."""
from __future__ import annotations

import glob
import os
import re

_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTR = re.compile(r"^%?([^\s=]+)\s*=\s*(.*)$", re.S)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def parse_instruction(text: str) -> tuple:
    """(name, opcode, result shape) of an HLO instruction as the TPU's
    trace names an operation:

        %fusion.199 = (f32[192]{0:T(256)}, bf16[192,4096]{1,0}) fusion(...)
        %closed_call.14 = bf16[192,8,4,128]{3,2,1,0} custom-call(...)

    Layouts are dropped; a tuple keeps its elements.  Text that is not
    an instruction gives (text, "", "")."""
    m = _INSTR.match(text.strip())
    if not m:
        return text.strip().lstrip("%"), "", ""
    name, rest = m.group(1), m.group(2)
    prev = None
    while prev != rest:                      # nested braces in layouts
        prev, rest = rest, _LAYOUT.sub("", rest)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, tail = rest[:i + 1], rest[i + 1:]
    else:
        shape, _, tail = rest.partition(" ")
    op = re.match(r"\s*([A-Za-z][\w\-]*)\(", tail)
    return name, (op.group(1) if op else ""), shape.replace(" ", "")


def read_planes(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def device_plane_names(data) -> list:
    """Planes of chips: "/device:TPU:0"; not the custom planes beside
    them ("/device:CUSTOM:Megascale Trace")."""
    names = [p.name for p in data.planes]
    chips = [n for n in names if re.fullmatch(r"/device:[A-Z]+:\d+", n)]
    return sorted(chips)


def read_device_events(data, plane_name: str,
                       line_names=("XLA Ops",)) -> list:
    """Plain records of the operations that ran on one device."""
    out = []
    for plane in data.planes:
        if plane.name != plane_name:
            continue
        for line in plane.lines:
            if line.name not in line_names:
                continue
            for ev in line.events:
                name, op, shape = parse_instruction(ev.name)
                out.append({"name": name, "start_ns": int(ev.start_ns),
                            "dur_ns": int(ev.duration_ns),
                            "category": op, "shape": shape})
    out.sort(key=lambda e: (e["start_ns"], -e["dur_ns"]))
    return out


def find_host_marker(data, name: str):
    """start_ns of the first host event called ``name`` (a
    TraceAnnotation the harness wrote at a known host time), or None."""
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    return int(ev.start_ns)
    return None


# ---------------------------------------------------------------------------
# arithmetic on plain records
# ---------------------------------------------------------------------------

def clip(events: list, t0: int, t1: int) -> list:
    out = []
    for e in events:
        a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
        a, b = max(a, t0), min(b, t1)
        if b > a:
            out.append(dict(e, start_ns=a, dur_ns=b - a))
    return out


def busy_intervals(events: list) -> list:
    """Union of the intervals in which some operation ran."""
    out = []
    for a, b in sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                       for e in events):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(events: list) -> int:
    return sum(b - a for a, b in busy_intervals(events))


def idle_gaps(events: list, t0: int, t1: int) -> list:
    gaps, cur = [], t0
    for a, b in busy_intervals(events):
        if a > cur:
            gaps.append((cur, min(a, t1)))
        cur = max(cur, b)
    if cur < t1:
        gaps.append((cur, t1))
    return [(a, b) for a, b in gaps if b > a]


def self_times(events: list) -> list:
    """Each event's duration less the events nested inside it (a while
    loop's event spans its body's): so that sums over operations count
    every nanosecond once.  Events are taken from one device line, where
    they nest properly."""
    evs = sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"]))
    selfs = [e["dur_ns"] for e in evs]
    stack = []                                  # indices of open parents
    for i, e in enumerate(evs):
        a, b = e["start_ns"], e["start_ns"] + e["dur_ns"]
        while stack and evs[stack[-1]]["start_ns"] \
                + evs[stack[-1]]["dur_ns"] <= a:
            stack.pop()
        if stack:
            p = evs[stack[-1]]
            if b <= p["start_ns"] + p["dur_ns"]:
                selfs[stack[-1]] -= e["dur_ns"]
        stack.append(i)
    return [dict(e, self_ns=max(0, s)) for e, s in zip(evs, selfs)]


def label(e: dict) -> str:
    """A name that survives renumbering: the instruction's name without
    its index, its opcode and its result shape."""
    base = re.sub(r"[.\-_]?\d+$", "", e["name"].lstrip("%"))
    parts = [base]
    if e.get("category") and e["category"] != base:
        parts.append(e["category"])
    if e.get("shape"):
        parts.append(e["shape"])
    return " ".join(parts)


def by_label(events_with_self: list) -> dict:
    out = {}
    for e in events_with_self:
        out[label(e)] = out.get(label(e), 0) + e["self_ns"]
    return out


def attribute_gaps(gaps: list, host_spans: list, offset_ns: int) -> dict:
    """Idle seconds by what the host was doing: each gap goes to the
    host span (on the device clock after ``offset_ns``) that covers most
    of it; of spans that cover as much the shorter, of spans alike in
    both the first in ``host_spans``; a span of no length covers
    nothing; a gap nothing covers goes to "unattributed".
    host_spans: [{"name", "ts", "dur"}] on the host clock.

    One walk of the gaps and the spans, each in order of start: a span
    is taken up once its start lies before a gap's end and dropped for
    good once its end lies at or before a gap's start, since no later
    gap starts earlier.  So a gap is held against the spans open round
    it (the engine thread's nest two or three deep) and not against
    every span of the run.  The sums are made in the order the gaps
    were given, as if each had been held against all."""
    spans = sorted((s["ts"] + offset_ns, s["ts"] + s["dur"] + offset_ns,
                    s["dur"], i, s["name"])
                   for i, s in enumerate(host_spans) if s["dur"] > 0)
    names = ["unattributed"] * len(gaps)
    live, nxt = [], 0
    for g in sorted(range(len(gaps)), key=lambda g: gaps[g][0]):
        a, b = gaps[g]
        while nxt < len(spans) and spans[nxt][0] < b:
            live.append(spans[nxt])
            nxt += 1
        live = [s for s in live if s[1] > a]
        best = None                   # (-covered, dur, place in host_spans)
        for x, y, d, i, name in live:
            cov = min(b, y) - max(a, x)
            if cov > 0 and (best is None or (-cov, d, i) < best):
                best, names[g] = (-cov, d, i), name
    out = {}
    for (a, b), name in zip(gaps, names):
        out[name] = out.get(name, 0) + (b - a)
    return out
