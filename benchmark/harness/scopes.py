"""Names the program gives its own work, read back.

Device side: the step programs put ``jax.named_scope`` names on their
phases, ``name=`` on the attention kernel and a name on each jitted
program.  On the TPU the trace carries the last two (an operation is
its instruction's text, "%ragged_paged_attention.9 = ..."; the module
line reads "jit_ragged_step_t192(...)") and nothing of the first: an
operation event has no ``op_name``.  The engine has the map
(``LLMEngine.program_scopes``: instruction name to ``op_name``, from the
compiled module's text), and ``scoped_events`` joins the three: the
module execution that contains an operation names its program, the
program's map gives the scope ("jit(ragged_step_t192)/layers/while/body/
qkv/dot_general" is ``qkv``), and the ``engine.launch`` annotation on
the host plane (same clock) says which launch it ran for.  Which scopes
and kernels a program has is its architecture's to say: ``arch`` below
is the module ``spec.load_shapes`` finds by the configuration's
``reference`` (``ctx["arch"]``), and the engine's map is the one the
engine that served gave before it went (``ctx["program_scopes"]``).

Host side: every Tracer span and request instant carries ``step``, the
id of the launch it belongs to; the joins below are by that id and never
by order.

A program without the names (the parent of the PR that added them) gives
every reader here nothing to read: they return None or an empty list and
do not raise."""
from __future__ import annotations

import functools
import os
import re
import statistics

from . import spans as S, spec, xplane as X

POOL_COPY = "kvpool_copy"
UNSCOPED = "unscoped"

_SHAPE = re.compile(r"[a-z]+[0-9]*\[([0-9,]*)\]")


# ---------------------------------------------------------------------------
# device side
# ---------------------------------------------------------------------------

def scope_of(op_name: str, arch):
    """The innermost of the program's scope names (``arch.SCOPES``) on
    an operation's path; ``arch.LOOP`` for what the scan over layers
    runs outside every phase; None for a path that holds none of them."""
    parts = op_name.split("/")
    for p in reversed(parts):
        if p in arch.SCOPES:
            return p
    return arch.LOOP if arch.LOOP in parts else None


def is_kernel_name(name: str, arch) -> bool:
    """An instruction named after one of the architecture's kernels
    (``arch.KERNELS``): "ragged_paged_attention.3",
    "%ragged_paged_attention_q8.1"."""
    base = re.sub(r"[.\-_]?\d+$", "", name.lstrip("%"))
    return base in arch.KERNELS


def kernel_ns(events_with_self: list, arch) -> int:
    """Traced time of the architecture's kernels, found by name."""
    return sum(e["self_ns"] for e in events_with_self
               if is_kernel_name(e["name"], arch))


def is_pool_shaped(e: dict, shapes: set) -> bool:
    """The operation's result (or every element of a tuple result... one
    of them) has the pool's shape."""
    return any(tuple(int(x) for x in m.split(",") if x) in shapes
               for m in _SHAPE.findall(e.get("shape") or ""))


def classify(e: dict, shapes: set, arch) -> str:
    """One of the program's scopes, its loop, ``kvpool_copy`` or
    ``unscoped``: where this operation's self time is counted.  A
    pool-shaped result (``shapes``: ``arch.pool_shapes(cfg)``) outside
    the pool's own writers (``arch.POOL_SCOPES``: the page writes and the
    kernel) is a copy of the pool that the program did not ask for,
    whatever scope XLA left on it."""
    sc = e.get("scope")
    if sc not in arch.POOL_SCOPES and is_pool_shaped(e, shapes):
        return POOL_COPY
    return sc or UNSCOPED


def by_class(events: list, cfg: dict, arch) -> dict:
    """Self nanoseconds by class; the values add up to busy time."""
    shapes = arch.pool_shapes(cfg)
    out: dict = {}
    for e in events:
        k = classify(e, shapes, arch)
        out[k] = out.get(k, 0) + e["self_ns"]
    return out


def is_matmul(e: dict, shapes: set, arch) -> bool:
    """Counted as matrix-product time: an operation that holds a dot,
    whatever its scope (XLA fuses across scopes); everything else under
    a matmul scope (``arch.MATMUL_SCOPES``); and the scan's own
    operations (``arch.LOOP``), which
    are the per-layer slices and layout copies of the stacked weights
    that only the products read: XLA moves the q, k, v and o matrices
    into fast memory with operations of their own, and the product that
    follows then shows a bandwidth no memory has.  Left out, the time
    would hold part of the work and the roofline share pass 100."""
    k = classify(e, shapes, arch)
    if k == POOL_COPY:
        return False
    return bool(e.get("has_dot")) or k in arch.MATMUL_SCOPES \
        or k == arch.LOOP


def matmul_ns(events: list, cfg: dict, arch) -> int:
    """Self nanoseconds of the events ``is_matmul`` takes."""
    shapes = arch.pool_shapes(cfg)
    return sum(e["self_ns"] for e in events if is_matmul(e, shapes, arch))


_MODULE = re.compile(r"^jit_([A-Za-z_]\w*?)\(\d+\)$")


def program_of(module_event_name: str):
    """"jit_ragged_step_t192(1735...)" -> "ragged_step_t192": the name
    the engine gave the jit, as the trace's module line carries it."""
    m = _MODULE.match(module_event_name)
    return m.group(1) if m else None


@functools.lru_cache(maxsize=2)
def _read(trace_dir: str, plane_name: str) -> tuple:
    """(device operations, module executions, engine.launch annotations)
    of one profile, all on the profile's own clock; read once for a
    run's readers.  The TPU's operation events carry their instruction's
    text and no statistics beyond time, so the program an operation
    belongs to is the module execution that contains it."""
    data = X.read_planes(X.find_xplane(trace_dir))
    ops = X.read_device_events(data, plane_name)
    modules, launches = [], []
    for plane in data.planes:
        if plane.name == plane_name:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [{"program": program_of(ev.name),
                                "start_ns": int(ev.start_ns),
                                "dur_ns": int(ev.duration_ns)}
                               for ev in line.events]
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "engine.launch":
                        st = dict(ev.stats)
                        launches.append({"step": int(st.get("step", 0)),
                                         "bucket": int(st.get("bucket", 0)),
                                         "start_ns": int(ev.start_ns)})
    modules.sort(key=lambda m: m["start_ns"])
    launches.sort(key=lambda l: l["start_ns"])
    return ops, modules, launches


def annotate(ops: list, modules: list, launches: list,
             program_map: dict, arch) -> list:
    """Give each operation the program it ran in (the module execution
    that contains it), from that program's map its ``scope`` and
    ``has_dot``, and the ``step`` of the launch it ran for: the last
    ``engine.launch`` annotated before its module began.  The engine
    keeps one launch ahead (PR 34): launch k + 1 is annotated while
    launch k runs, and launch k + 2 only once k's result came back,
    which is after k + 1's module began (the device runs them in turn);
    so the last annotation before a module began is still its own."""
    out, mi, li = [], 0, -1
    for e in sorted(ops, key=lambda e: e["start_ns"]):
        while mi < len(modules) and modules[mi]["start_ns"] \
                + modules[mi]["dur_ns"] <= e["start_ns"]:
            mi += 1
        mod = modules[mi] if mi < len(modules) \
            and modules[mi]["start_ns"] <= e["start_ns"] else None
        t = mod["start_ns"] if mod else e["start_ns"]
        while li + 1 < len(launches) and launches[li + 1]["start_ns"] <= t:
            li += 1
        prog = mod["program"] if mod else None
        info = program_map.get(prog, {}).get(e["name"].lstrip("%"), {})
        out.append(dict(e, program=prog,
                        op_name=info.get("op_name", ""),
                        scope=scope_of(info.get("op_name", ""), arch),
                        has_dot=bool(info.get("dot")),
                        step=launches[li]["step"] if li >= 0 else None))
    return out


def trace_dir() -> str:
    return os.path.join(spec.REPO, ".bench_out", "trace")


def scoped_events(ctx) -> list:
    """The traced window's device operations, clipped to it, each with
    ``self_ns``, ``program``, ``scope``, ``has_dot`` and ``step``.
    Empty where there is no trace, or the engine gave no map of its
    programs (``ctx["program_scopes"]``: a program that names neither
    its jitted programs nor its phases has none to give).  Worked out
    once for a run's readers and kept in ``ctx``."""
    tr, pmap = ctx.get("trace"), ctx.get("program_scopes")
    if tr is None or not pmap:
        return []
    if "_scoped_events" not in ctx:
        ops, modules, launches = _read(trace_dir(), tr["plane"])
        ctx["_scoped_events"] = annotate(
            X.self_times(X.clip(ops, *tr["window"])), modules, launches,
            pmap, ctx["arch"])
    return ctx["_scoped_events"]


def launch_annotations(ctx) -> list:
    """The profile's ``engine.launch`` annotations: {"step", "bucket",
    "start_ns"} on the profile's clock, in order."""
    tr = ctx.get("trace")
    return _read(trace_dir(), tr["plane"])[2] if tr is not None else []


def whole_steps(launches: list, window: tuple) -> set:
    """Ids of the launches annotated inside the window and followed
    inside it by the next annotation.  That next one is made once the
    launch IN FRONT of this one came back (the engine keeps one launch
    ahead since PR 34), so this launch's operations begin inside the
    window, and all lie inside it but, at most, those of the last id
    here: a step that the window's end may cut, among the hundreds the
    readers sum over."""
    w0, w1 = window
    inside = [l for l in launches if w0 <= l["start_ns"] < w1]
    return {a["step"] for a, _b in zip(inside, inside[1:])}


# ---------------------------------------------------------------------------
# host side: joins by step id
# ---------------------------------------------------------------------------

def launch_args(spans: list) -> dict:
    """step id -> the args of that launch's ``engine.device_launch``
    (bucket, tokens, rows, chunks, decode, logit_rows) with its start."""
    out = {}
    for s in S.named(spans, "engine.device_launch", "X"):
        if "step" in s["args"]:
            out[int(s["args"]["step"])] = dict(s["args"], ts=s["ts"],
                                               end=s["ts"] + s["dur"])
    return out


def prefill_waits_ns(spans: list, t0: int, t1: int) -> list:
    """For each request queued in [t0, t1) whose first chunk was
    launched: queued to the start of that launch's device_launch."""
    launches = launch_args(spans)
    first = {}
    for s in S.named(spans, "request.prefill_chunk"):
        a = s["args"]
        if "step" in a and a["rid"] not in first:
            first[a["rid"]] = int(a["step"])
    out = []
    for s in S.named(spans, "request.queued"):
        step = first.get(s["args"].get("rid"))
        if t0 <= s["ts"] < t1 and step in launches:
            out.append(launches[step]["ts"] - s["ts"])
    return out


def launch_periods(spans: list, t0: int, t1: int) -> list:
    """One record per launch whose result came back in [t0, t1) and whose
    predecessor's is known: {"step", "bucket", "chunk", "start", "end",
    "ns"}; the period runs from the end of the previous launch's
    ``engine.block_on_result`` to the end of its own."""
    launches = launch_args(spans)
    ends = {int(s["args"]["step"]): s["ts"] + s["dur"]
            for s in S.named(spans, "engine.block_on_result", "X")
            if "step" in s["args"]}
    out = []
    for step in sorted(ends):
        if step - 1 in ends and step in launches \
                and t0 <= ends[step] < t1:
            a = launches[step]
            out.append({"step": step, "bucket": int(a.get("bucket", 0)),
                        "chunk": int(a.get("chunks", 0)) > 0,
                        "start": ends[step - 1], "end": ends[step],
                        "ns": ends[step] - ends[step - 1]})
    return out


def stalls(periods: list, factor: float = 2.0) -> tuple:
    """(nanoseconds over the line in all, the periods over it): the line
    of a class (bucket, with or without a chunk) is ``factor`` times its
    median."""
    classes: dict = {}
    for p in periods:
        classes.setdefault((p["bucket"], p["chunk"]), []).append(p)
    total, over = 0, []
    for ps in classes.values():
        line = factor * statistics.median(p["ns"] for p in ps)
        for p in ps:
            if p["ns"] > line:
                total += p["ns"] - line
                over.append(dict(p, line_ns=line))
    return total, sorted(over, key=lambda p: p["step"])


def self_ns_inside(spans: list, a: int, b: int) -> dict:
    """Self nanoseconds by name of properly nested spans (one track)
    inside [a, b): each span's part of the interval less its direct
    children's parts.  Nesting is decided on the spans' whole extents,
    the clipping comes after: a wrapper and its first phase may be
    clipped to the same interval and must not both count it."""
    def part(s):
        return max(0, min(b, s["ts"] + s["dur"]) - max(a, s["ts"]))

    out: dict = {}
    stack: list = []
    for s in sorted(spans, key=lambda s: (s["ts"], -s["dur"])):
        while stack and stack[-1]["ts"] + stack[-1]["dur"] <= s["ts"]:
            stack.pop()
        if stack and s["ts"] + s["dur"] <= stack[-1]["ts"] \
                + stack[-1]["dur"]:
            out[stack[-1]["name"]] -= part(s)
        out[s["name"]] = out.get(s["name"], 0) + part(s)
        stack.append(s)
    return out


def explain_period(p: dict, spans: list, trace=None) -> dict:
    """Where one launch's period went, in ms: the self time inside it of
    every engine span (a wrapper's self time is what none of its phases
    covers), then what ran on other tracks as it overlaps the period
    (the collector, the runner's turn between steps, the HTTP tier), and
    the device's busy time where the profile saw the period."""
    a, b = p["start"], p["end"]
    xs = [s for s in spans if s["ph"] == "X" and s["ts"] < b
          and s["ts"] + s["dur"] > a
          and s["name"] != "engine.device_inflight"]
    where = self_ns_inside(
        [s for s in xs if s["name"].startswith("engine.")], a, b)
    for s in xs:
        if not s["name"].startswith("engine."):
            where[s["name"]] = where.get(s["name"], 0) \
                + min(b, s["ts"] + s["dur"]) - max(a, s["ts"])
    out = {"step": p["step"], "bucket": p["bucket"], "chunk": p["chunk"],
           "period_ms": p["ns"] / 1e6, "line_ms": p["line_ns"] / 1e6,
           "self_ms": {k: round(v / 1e6, 3) for k, v in sorted(
               where.items(), key=lambda kv: -kv[1]) if v > 0}}
    if trace is not None:
        off = trace["offset_ns"]
        w0, w1 = trace["window"]
        if w0 <= a + off and b + off <= w1:
            out["device_busy_ms"] = X.busy_ns(
                X.clip(trace["events"], a + off, b + off)) / 1e6
    return out
