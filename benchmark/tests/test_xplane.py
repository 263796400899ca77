"""The trace reduction on hand-made records (the recorded trace has a
test of its own in test_recorded_trace.py)."""
import json
import os
import random
import time
import types

import pytest

import run
from harness import scopes, spans as S, spec, xplane as X


def ev(name, start, dur, cat="", shape=""):
    return {"name": name, "start_ns": start, "dur_ns": dur,
            "category": cat, "shape": shape}


def test_busy_is_the_union_and_idle_its_complement():
    evs = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 5), ev("d", 31, 2)]
    assert X.busy_intervals(evs) == [[0, 15], [30, 35]]
    assert X.busy_ns(evs) == 20
    assert X.idle_gaps(evs, 0, 50) == [(15, 30), (35, 50)]
    assert X.idle_gaps(evs, 10, 32) == [(15, 30)]


def test_clip_to_the_window():
    evs = X.clip([ev("a", 0, 10), ev("b", 20, 10), ev("c", 40, 5)], 5, 25)
    assert [(e["start_ns"], e["dur_ns"]) for e in evs] == [(5, 5), (20, 5)]


def test_self_time_counts_every_nanosecond_once():
    # a while loop of 100 ns holding two body operations of 30 and 40
    evs = [ev("while.3", 0, 100), ev("fusion.1", 10, 30),
           ev("custom-call.2", 50, 40), ev("copy.9", 120, 10)]
    st = {e["name"]: e["self_ns"] for e in X.self_times(evs)}
    assert st == {"while.3": 30, "fusion.1": 30, "custom-call.2": 40,
                  "copy.9": 10}
    assert sum(st.values()) == X.busy_ns(evs)


def test_labels_survive_renumbering():
    a = X.label(ev("%fusion.13", 0, 1, "fusion", "f32[32,32768]"))
    b = X.label(ev("fusion.977", 0, 1, "fusion", "f32[32,32768]"))
    assert a == b == "fusion f32[32,32768]"
    assert X.label(ev("copy.137", 0, 1)) == "copy"
    assert X.label(ev("closed_call.14", 0, 1, "custom-call",
                      "bf16[32,8,4,128]")) \
        == "closed_call custom-call bf16[32,8,4,128]"


def test_instruction_text_as_the_tpu_trace_names_it():
    t = ("%fusion.199 = (f32[192]{0:T(256)S(1)}, bf16[192,4096]{1,0:T(8,128)"
         "(2,1)S(1)}) fusion(bf16[192,4096]{1,0:T(8,128)(2,1)S(1)} "
         "%custom-call.42, s32[]{:T(128)} %g), kind=kOutput, calls=%fc.44")
    assert X.parse_instruction(t) == ("fusion.199", "fusion",
                                      "(f32[192],bf16[192,4096])")
    k = ("%closed_call.14 = bf16[192,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} "
         "custom-call(bf16[1]{0} %x), custom_call_target=\"tpu_custom_call\"")
    assert X.parse_instruction(k) == ("closed_call.14", "custom-call",
                                      "bf16[192,8,4,128]")
    assert X.parse_instruction("bench.mark") == ("bench.mark", "", "")


def test_attention_kernel_by_the_names_the_architecture_lists():
    arch = spec.load_shapes("llama_dense")
    evs = X.self_times([
        ev("ragged_paged_attention.14", 0, 5, "custom-call",
           "bf16[192,8,4,128]"),
        # whatever its result's shape: the other model's head layout
        ev("ragged_paged_attention_q8.2", 10, 7, "custom-call",
           "bf16[192,4,8,128]"),
        # a kernel without the name is not found, nor a fusion that
        # gives the kernel's shape, nor a name that only starts alike
        ev("closed_call.14", 20, 1, "custom-call", "bf16[192,8,4,128]"),
        ev("fusion.1", 30, 1, "fusion", "bf16[192,8,4,128]"),
        ev("ragged_paged_attention_bwd.1", 40, 1, "custom-call", "")])
    assert [scopes.is_kernel_name(e["name"], arch) for e in evs] \
        == [True, True, False, False, False]
    assert scopes.kernel_ns(evs, arch) == 12


def test_gaps_go_to_what_the_host_was_doing():
    host = [{"name": "engine.schedule", "ts": 100, "dur": 50},
            {"name": "engine.block_on_result", "ts": 150, "dur": 500},
            {"name": "engine.pack", "ts": 110, "dur": 10}]
    # device clock = host clock + 1000
    gaps = [(1100, 1150), (1112, 1118), (5000, 5010)]
    out = X.attribute_gaps(gaps, host, 1000)
    assert out == {"engine.schedule": 50, "engine.pack": 6,
                   "unattributed": 10}


def attribute_gaps_oracle(gaps: list, host_spans: list,
                          offset_ns: int) -> dict:
    """``xplane.attribute_gaps`` as it stood until PR 44: every gap held
    against every span.  Kept here, and nowhere else, as what the walk
    has to give, key for key and nanosecond for nanosecond."""
    out = {}
    spans = [(s["ts"] + offset_ns, s["ts"] + s["dur"] + offset_ns,
              s["dur"], s["name"]) for s in host_spans if s["dur"] > 0]
    for a, b in gaps:
        best, best_cov, best_dur = "unattributed", 0, None
        for x, y, d, name in spans:
            cov = min(b, y) - max(a, x)
            if cov <= 0:
                continue
            if cov > best_cov or (cov == best_cov and d < best_dur):
                best, best_cov, best_dur = name, cov, d
        out[best] = out.get(best, 0) + (b - a)
    return out


def gap_case(seed: int) -> tuple:
    """(gaps, host_spans, offset) from the seed.  Times are drawn from
    a few dozen points, so spans share starts, ends and durations, nest
    and repeat, and gaps end where spans begin (coverage 0); some spans
    have no length; every few cases the spans are an engine thread's
    (turns one after another, two spans inside each, one inside the
    second), a span covers many gaps, the gaps come unsorted, or
    overlap, and the offset is not nought."""
    r = random.Random(seed)
    offset = r.choice((0, 0, 1000, -37, 10**12))
    span_names = ["engine.%s" % c for c in "abcdef"]
    pts = r.randrange(8, 60)
    spans = []
    if seed % 3 == 0:                      # an engine thread's nesting
        t = 0
        for _ in range(r.randrange(1, 40)):
            d = r.randrange(0, 12)
            spans.append({"name": "engine.turn", "ts": t, "dur": d})
            if d > 2:
                cut = r.randrange(1, d)
                spans.append({"name": "engine.a", "ts": t, "dur": cut})
                spans.append({"name": "engine.b", "ts": t + cut,
                              "dur": d - cut})
                spans.append({"name": "engine.c", "ts": t + cut,
                              "dur": r.randrange(0, d - cut + 1)})
            t += d + r.randrange(0, 3)
        pts = max(pts, t)
        if seed % 2:
            r.shuffle(spans)
    else:
        for _ in range(r.randrange(0, 40)):
            spans.append({"name": r.choice(span_names),
                          "ts": r.randrange(pts),
                          "dur": r.choice((0, 1, 2, 2, 3, 5, 8, pts))})
    if seed % 5 == 0:                      # one span over everything
        spans.insert(r.randrange(len(spans) + 1),
                     {"name": "engine.whole", "ts": -5, "dur": pts + 10})
    gaps = []
    if seed % 4 == 1:                      # any list: overlapping, empty
        for _ in range(r.randrange(0, 40)):
            a = r.randrange(-3, pts + 3)
            gaps.append((a, a + r.randrange(0, 9)))
    else:                                  # as idle_gaps gives them
        a = r.randrange(-3, 3)
        while a < pts + 3 and len(gaps) < 60:
            b = a + r.randrange(1, 6)
            gaps.append((a, b))
            a = b + r.randrange(1, 6)
        if seed % 2:
            r.shuffle(gaps)
    gaps = [(a + offset, b + offset) for a, b in gaps]
    return gaps, spans, offset


@pytest.mark.parametrize("seed", range(400))
def test_the_walk_gives_what_the_double_loop_gave(seed):
    gaps, spans, offset = gap_case(seed)
    want = attribute_gaps_oracle(gaps, spans, offset)
    got = X.attribute_gaps(gaps, spans, offset)
    # the same keys in the same order too: run.py sorts them by size,
    # and equal sizes stay in the order they were first met
    assert list(got.items()) == list(want.items())


@pytest.mark.parametrize("seed", range(0, 400, 4))
def test_spans_outside_the_traced_window_cover_no_gap(seed):
    """``run._engine_spans`` leaves out what lies outside the window the
    gaps lie in: the answer is the one all the run's spans give."""
    gaps, spans, offset = gap_case(seed)
    if not gaps:
        gaps = [(offset, offset + 1)]
    spans = [dict(s, ph="X") for s in spans]
    r = random.Random(seed)
    t = max(s["ts"] + s["dur"] for s in spans) if spans else 0
    spans += [{"ph": "X", "name": "engine.step", "ts": -5, "dur": t + 10},
              {"ph": "i", "name": "engine.a", "ts": 3, "dur": 0}]
    # a window that holds every gap, on the host clock, ends on a gap's
    t0 = min(a for a, _b in gaps) - offset - r.randrange(2)
    t1 = max(b for _a, b in gaps) - offset + r.randrange(2)
    inside = run._engine_spans(spans, t0, t1)
    every = run._engine_spans(spans, -10**18, 10**18)
    assert len(inside) <= len(every) == sum(
        s["ph"] == "X" and s["name"] != "engine.step" for s in spans)
    assert list(X.attribute_gaps(gaps, inside, offset).items()) \
        == list(attribute_gaps_oracle(gaps, every, offset).items())


def test_reduce_trace_on_the_recorded_step(monkeypatch):
    """``run._reduce_trace`` with the recorded step's operations where
    the profiler's file would be read: the breakdown the driver copies,
    the spans of the traced window alone walked, and what the reduction
    cost, which stays out of the result's line."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "recorded_step.json")) as f:
        d = json.load(f)
    events = [dict(zip(d["fields"], r)) for r in d["events"]]
    w0, w1 = d["window"]
    monkeypatch.setattr(X, "find_xplane", lambda _dir: "recorded")
    monkeypatch.setattr(X, "read_planes", lambda _path: None)
    monkeypatch.setattr(X, "device_plane_names",
                        lambda _data: ["/device:TPU:0"])
    monkeypatch.setattr(X, "read_device_events",
                        lambda _data, _plane: events)
    monkeypatch.setattr(X, "find_host_marker", lambda _data, _name: 7_000)
    # device clock = host clock + 2,000
    prof = types.SimpleNamespace(dir="", t_mark=5_000, t0=w0 - 2_000,
                                 t1=w1 - 2_000)
    host = [{"ph": "X", "name": "engine.step", "ts": w0, "dur": w1 - w0},
            {"ph": "X", "name": "engine.schedule", "ts": w0 - 2_000,
             "dur": 18_000_000},
            {"ph": "X", "name": "engine.block_on_result",
             "ts": w0 + 18_000_000, "dur": w1 - w0},
            {"ph": "X", "name": "engine.retire", "ts": w0 - 9_000,
             "dur": 7_000},                      # ends as the window begins
            {"ph": "X", "name": "engine.schedule", "ts": w1 - 2_000,
             "dur": 50},                         # begins as it ends
            {"ph": "i", "name": "engine.launch", "ts": w0 + 5, "dur": 0}]
    tr = run._reduce_trace(prof, {}, host)
    assert tr["window"] == (w0, w1) and tr["offset_ns"] == 2_000
    assert tr["busy_s"] == 143750309 / 1e9
    ops, idle = tr["breakdown"]["device_ops"], tr["breakdown"]["idle_gaps"]
    assert len(ops) == 10 and ops[0][0] \
        == "closed_call custom-call bf16[32,8,4,128]"
    assert idle[0] == ["engine.schedule", 18_759_000 / 1e9]   # a gap whole
    assert [k for k, _v in idle] == ["engine.schedule",
                                     "engine.block_on_result"]
    assert sum(v for _k, v in idle) * 1e9 \
        == pytest.approx((w1 - w0) - 143750309)
    cost = tr["cost"]
    assert (cost["spans"], cost["host_events"]) == (2, 6)
    assert cost["gaps"] == len(X.idle_gaps(X.clip(events, w0, w1), w0, w1))
    assert {"read_xplane_s", "clip_busy_self_s", "idle_gaps_s",
            "attribute_gaps_s", "device_events"} <= set(cost)


def test_the_cases_hold_what_they_say():
    """The generator is not all one easy kind: over its cases there are
    spans of no length, spans alike in start and length, a gap that
    touches a span it does not overlap, gaps out of order, a gap under
    several spans, and an answer that is not ``unattributed``."""
    seen = set()
    for seed in range(400):
        gaps, spans, offset = gap_case(seed)
        if any(s["dur"] == 0 for s in spans):
            seen.add("no length")
        if len({(s["ts"], s["dur"]) for s in spans}) < len(spans):
            seen.add("alike")
        ends = {s["ts"] + offset for s in spans} \
            | {s["ts"] + s["dur"] + offset for s in spans}
        if any(a in ends or b in ends for a, b in gaps):
            seen.add("touches")
        if gaps != sorted(gaps):
            seen.add("unsorted")
        if offset:
            seen.add("offset")
        for a, b in gaps:
            over = [s for s in spans if s["dur"] > 0
                    and s["ts"] + offset < b
                    and s["ts"] + s["dur"] + offset > a]
            if len(over) > 2:
                seen.add("nested")
        if set(attribute_gaps_oracle(gaps, spans, offset)) \
                - {"unattributed"}:
            seen.add("attributed")
    assert seen == {"no length", "alike", "touches", "unsorted", "offset",
                    "nested", "attributed"}


def engine_thread(n_spans: int, n_gaps: int, seed: int = 0) -> tuple:
    """A traced run's size: turns that follow one another with three
    spans inside each, and disjoint gaps, four in five of them under a
    span."""
    r = random.Random(seed)
    spans, t = [], 0
    while len(spans) < n_spans:
        d = r.randrange(40_000, 400_000)
        spans.append({"name": "engine.schedule", "ts": t, "dur": d // 4})
        spans.append({"name": "engine.device_launch", "ts": t + d // 4,
                      "dur": d // 2})
        spans.append({"name": "engine.block_on_result",
                      "ts": t + d - d // 4, "dur": d // 4})
        t += d + r.randrange(0, 50_000)
    step = max(2, t // n_gaps)
    gaps = [(k * step + r.randrange(step // 2),
             k * step + step // 2 + r.randrange(1, step // 2 + 1))
            for k in range(n_gaps)]
    return gaps, spans[:n_spans], 123_456_789


def test_a_traced_run_of_an_expert_cell_takes_seconds():
    """300,000 gaps against 30,000 spans: 9 x 10^9 pairs, over half an
    hour for the double loop; the walk has ten seconds and needs one."""
    gaps, spans, offset = engine_thread(30_000, 300_000)
    gaps = [(a + offset, b + offset) for a, b in gaps]
    t = time.monotonic()
    out = X.attribute_gaps(gaps, spans, offset)
    took = time.monotonic() - t
    assert took < 10.0
    assert sum(out.values()) == sum(b - a for a, b in gaps)
    assert set(out) == {"engine.schedule", "engine.device_launch",
                        "engine.block_on_result", "unattributed"}
    # and on a slice small enough for the double loop, the same
    assert X.attribute_gaps(gaps[:300], spans[:300], offset) \
        == attribute_gaps_oracle(gaps[:300], spans[:300], offset)


def test_launches_join_dispatch_to_device_time():
    sp = [{"ph": "X", "name": "engine.dispatch", "ts": 0, "dur": 5,
           "args": {"launched": True, "chunks": 2, "decode": 7}},
          {"ph": "X", "name": "engine.device_inflight", "ts": 4,
           "dur": 600_000_000, "args": {"rows": 9}},
          {"ph": "X", "name": "engine.dispatch", "ts": 700_000_000, "dur": 5,
           "args": {"launched": False, "chunks": 0, "decode": 0}},
          {"ph": "X", "name": "engine.dispatch", "ts": 800_000_000, "dur": 5,
           "args": {"launched": True, "chunks": 0, "decode": 8}},
          {"ph": "X", "name": "engine.device_inflight", "ts": 800_000_004,
           "dur": 150_000_000, "args": {"rows": 8}}]
    ls = S.launches(sp)
    assert [(l["chunks"], l["decode"], l["ms"]) for l in ls] == [
        (2, 7, 600.0), (0, 8, 150.0)]


def test_attention_rows_from_request_events():
    sp = [{"ph": "b", "name": "req", "ts": 0, "dur": 0,
           "args": {"rid": 1, "prompt_tokens": 100, "replayed": 0}},
          # 60 of the 100 tokens came from the cache: chunks of 30 and 10
          {"ph": "i", "name": "request.prefill_chunk", "ts": 10, "dur": 0,
           "args": {"rid": 1, "tokens": 30, "done": False}},
          {"ph": "i", "name": "request.prefill_chunk", "ts": 20, "dur": 0,
           "args": {"rid": 1, "tokens": 10, "done": True}},
          {"ph": "i", "name": "runner.deliver", "ts": 20, "dur": 0,
           "args": {"rid": 1, "tokens": 1}},
          {"ph": "i", "name": "runner.deliver", "ts": 30, "dur": 0,
           "args": {"rid": 1, "tokens": 2}},
          {"ph": "i", "name": "runner.deliver", "ts": 40, "dur": 0,
           "args": {"rid": 1, "tokens": 3}}]
    assert sorted(S.attention_rows(sp, 0, 100)) == [
        (1, 101), (1, 102), (10, 100), (30, 90)]
    assert sorted(S.attention_rows(sp, 15, 35)) == [(1, 101), (10, 100)]


def test_host_self_time_takes_out_nested_spans():
    sp = [{"ph": "X", "name": "engine.schedule", "ts": 0, "dur": 100,
           "args": {}},
          {"ph": "X", "name": "engine.pack", "ts": 10, "dur": 30, "args": {}}]
    assert S.self_time_ns(sp, ("engine.schedule",)) == 70
    assert S.self_time_ns(sp, ("engine.schedule", "engine.pack")) == 100
