"""From the program's Tracer to the benchmark's own span records.

A Tracer event is the tuple (ph, name, ts_ns, dur_ns, tid, args, id) on
``perf_counter_ns``, which on Linux is CLOCK_MONOTONIC: the clock of the
load generator's stamps too."""
from __future__ import annotations


def normalise(events) -> list:
    out = []
    for ph, name, ts, dur, _tid, args, _id in events:
        out.append({"ph": ph, "name": name, "ts": int(ts), "dur": int(dur),
                    "args": dict(args) if args else {}})
    out.sort(key=lambda e: e["ts"])
    return out


def in_window(spans: list, t0: int, t1: int) -> list:
    """Spans that ended (for instants: happened) inside [t0, t1)."""
    return [s for s in spans if t0 <= s["ts"] + s["dur"] < t1]


def named(spans: list, name: str, ph: str | None = None) -> list:
    return [s for s in spans
            if s["name"] == name and (ph is None or s["ph"] == ph)]


def launches(spans: list) -> list:
    """One record per step launch: the ``engine.dispatch`` that launched
    (its args say what rode in it) joined to the ``engine.device_inflight``
    that followed (launch to materialised result).  Each:
    {"chunks", "decode", "ts", "ms"}."""
    out = []
    pending = None
    for s in spans:
        if s["ph"] != "X":
            continue
        if s["name"] == "engine.dispatch" and s["args"].get("launched"):
            pending = s
        elif s["name"] == "engine.device_inflight" and pending is not None:
            out.append({"chunks": int(pending["args"].get("chunks", 0)),
                        "decode": int(pending["args"].get("decode", 0)),
                        "ts": s["ts"], "end": s["ts"] + s["dur"],
                        "ms": s["dur"] / 1e6})
            pending = None
    return out


def attention_rows(spans: list, t0: int, t1: int) -> list:
    """(n_q, kv_len) of every row of every step completed in [t0, t1),
    from the request events: ``req`` begin (prompt length),
    ``request.prefill_chunk`` (tokens of a chunk, and whether it was the
    last: the first chunk's start, the cache hit, follows from the sum)
    and ``runner.deliver`` (the k-th token of a request: k = 1 rode on
    the last chunk, k > 1 was a decode row at K/V length prompt+k-1)."""
    prompt, chunks = {}, {}
    for s in spans:
        a = s["args"]
        if s["ph"] == "b" and s["name"] == "req":
            prompt[a["rid"]] = int(a["prompt_tokens"]) + int(
                a.get("replayed", 0))
        elif s["name"] == "request.prefill_chunk":
            chunks.setdefault(a["rid"], []).append((s["ts"], int(a["tokens"])))
    rows = []
    for rid, cs in chunks.items():
        if rid not in prompt:
            continue
        pos = prompt[rid] - sum(n for _, n in cs)     # the cache hit
        for ts, n in cs:
            pos += n
            if t0 <= ts < t1:
                rows.append((n, pos))
    for s in spans:
        if s["name"] == "runner.deliver" and t0 <= s["ts"] < t1:
            a = s["args"]
            k = int(a["tokens"])
            if k > 1 and a["rid"] in prompt:
                rows.append((1, prompt[a["rid"]] + k - 1))
    return rows


def self_time_ns(spans: list, names) -> int:
    """Self time of the named X spans: each one's duration less what
    other X spans nested inside it cover."""
    xs = [s for s in spans if s["ph"] == "X"]
    total = 0
    for s in xs:
        if s["name"] not in names:
            continue
        a, b = s["ts"], s["ts"] + s["dur"]
        inner = sorted((max(a, c["ts"]), min(b, c["ts"] + c["dur"]))
                       for c in xs if c is not s and c["ts"] >= a
                       and c["ts"] + c["dur"] <= b and c["dur"] < s["dur"])
        covered, cur = 0, a
        for x, y in inner:
            if y > cur:
                covered += y - max(x, cur)
                cur = y
        total += s["dur"] - covered
    return total
