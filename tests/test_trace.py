"""Step-timeline tracer: ring-buffer and nesting semantics, Chrome
trace-event export validity, the zero-cost disabled seam, greedy
byte-identity with tracing on, and cross-tier correlation through the
HTTP frontend's /debug/trace endpoint."""
import http.client
import json
import os
import re
import threading
import tracemalloc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference import LLMEngine
from paddle_tpu.inference.frontend import serve_background
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler import Tracer

VOCAB = 97
CFG = LlamaConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=4, ffn=64,
                       seq=64)


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM(CFG)


def _engine(model, **kw):
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("max_prefill_tokens", 128)
    kw.setdefault("prefill_token_bucket", 32)
    return LLMEngine(model, **kw)


def _post(port, obj, path="/v1/completions", timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", path, body=json.dumps(obj).encode(),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, body


def _get(port, path, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, body


# ---------------------------------------------------------------------------
# ring buffer + span stack semantics
# ---------------------------------------------------------------------------

def test_ring_drops_oldest_first_and_counts():
    tr = Tracer(capacity=4)
    track = tr.register("engine")
    for i in range(10):
        tr.instant(f"i{i}", track=track)
    assert [e[1] for e in tr.events()] == ["i6", "i7", "i8", "i9"]
    assert tr.dropped == 6
    assert len(tr) == 4
    assert tr.chrome_trace()["otherData"]["dropped_events"] == 6
    tr.clear()
    assert tr.dropped == 0 and len(tr) == 0


def test_span_nesting_is_strictly_per_thread():
    """Two threads interleaving nested spans never see each other's
    stack: every exit matches its own thread's enter."""
    tr = Tracer()
    track = tr.register("engine")
    barrier = threading.Barrier(2)
    errs = []

    def work():
        try:
            for _ in range(50):
                with tr.span("outer", track=track):
                    barrier.wait(10)      # force interleaving mid-span
                    with tr.span("inner", track=track):
                        pass
        except Exception as e:            # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=work) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not errs
    assert tr.unbalanced == 0
    assert len(tr.events()) == 200        # 2 threads * 50 * (outer+inner)


def test_mismatched_span_exit_counts_unbalanced_never_raises():
    tr = Tracer()
    track = tr.register("engine")
    outer, inner = tr.span("outer", track=track), tr.span("inner",
                                                          track=track)
    outer.__enter__()
    inner.__enter__()
    outer.__exit__(None, None, None)      # exits out of order
    inner.__exit__(None, None, None)
    assert tr.unbalanced == 2
    stray = tr.span("stray", track=track)
    stray.__enter__()
    tr._stack().clear()                   # exit against an empty stack
    stray.__exit__(None, None, None)
    assert tr.unbalanced == 3
    # the damaged stack never blocks recording: all 3 "X" events landed
    assert [e[0] for e in tr.events()] == ["X", "X", "X"]
    assert tr.chrome_trace()["otherData"]["unbalanced_spans"] == 3


# ---------------------------------------------------------------------------
# chrome trace-event export
# ---------------------------------------------------------------------------

def test_chrome_trace_export_is_valid_and_monotonic():
    tr = Tracer()
    track = tr.register("engine")
    tr.async_begin("request", "engine:req-0", args={"request_id": "r-0"})
    with tr.span("engine.step", track=track, step=1):
        with tr.span("engine.pack", track=track):
            pass
    tr.instant("engine.first_token", track=track, args={"rid": "req-0"})
    tr.async_end("request", "engine:req-0")
    doc = json.loads(json.dumps(tr.chrome_trace()))   # JSON round-trip
    evs = doc["traceEvents"]
    assert all({"ph", "name", "pid", "tid"} <= set(ev) for ev in evs)
    body = [ev for ev in evs if ev["ph"] != "M"]
    assert len(body) == 5
    # timestamps are non-decreasing after export sorting, even though
    # the wrapper "engine.step" X event is APPENDED after its inner span
    ts = [ev["ts"] for ev in body]
    assert ts == sorted(ts)
    for ev in body:
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        elif ev["ph"] == "i":
            assert ev["s"] == "t"
        else:
            assert ev["ph"] in ("b", "e")
            assert ev["cat"] == "request"
            assert ev["id"] == "engine:req-0"
    meta = {ev["args"]["name"] for ev in evs
            if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert "engine" in meta
    assert doc["otherData"]["clock"] == "perf_counter_ns"


# ---------------------------------------------------------------------------
# engine integration: byte-identity + zero-cost disabled seam
# ---------------------------------------------------------------------------

def test_tracing_on_off_byte_identical_with_pinned_compiles(model):
    """ISSUE acceptance: the 16-request ragged audit stream produces
    byte-identical greedy outputs with tracing on vs off, and the
    compile budget does not move."""
    def run_stream(tracer):
        eng = _engine(model, max_num_seqs=8, max_prefill_tokens=256,
                      prefill_token_bucket=64)
        if tracer is not None:
            eng.set_tracer(tracer)
        rng = np.random.RandomState(7)
        shapes = [(4, 8), (9, 8), (13, 6)]
        for i in range(16):
            n, max_new = shapes[i % len(shapes)]
            eng.add_request(rng.randint(0, VOCAB, n).tolist(),
                            max_new_tokens=max_new)
        outs = eng.run()
        return ([outs[rid].generated for rid in sorted(outs)],
                eng.num_decode_programs, dict(eng.compile_counts))

    base, base_programs, base_compiles = run_stream(None)
    tr = Tracer()
    traced, traced_programs, traced_compiles = run_stream(tr)
    assert traced == base
    assert traced_programs == base_programs
    assert traced_compiles == base_compiles
    assert tr.unbalanced == 0 and tr.dropped == 0
    names = {e[1] for e in tr.events()}
    for phase in ("engine.step", "engine.admit", "engine.schedule",
                  "engine.device_launch", "engine.block_on_result",
                  "engine.sample_commit", "engine.retire"):
        assert phase in names, phase
    # ``engine.schedule`` runs on over the packing of the rows it chose
    # and says how long the flat tokens and the table rows took of it;
    # admission shows only where somebody waited
    launches = [e for e in tr.events() if e[1] == "engine.device_launch"]
    packed = [e for e in tr.events() if e[1] == "engine.schedule"
              and "pack_ns" in e[5]]
    assert [e[5]["step"] for e in packed] == [e[5]["step"] for e in launches]
    assert all(e[5]["tokens"] == l[5]["tokens"] and e[5]["rows"] == l[5]["rows"]
               and 0 <= e[5]["pack_ns"] + e[5]["table_ns"] <= e[3]
               and e[2] + e[3] <= l[2] for e, l in zip(packed, launches))
    admits = [e for e in tr.events() if e[1] == "engine.admit"]
    assert 0 < len(admits) < len(launches)
    assert all(e[5]["admitted"] or e[5]["waiting"] for e in admits)
    # every request opened AND closed its lifecycle pair
    assert sum(1 for e in tr.events() if e[0] == "b") == 16
    assert sum(1 for e in tr.events() if e[0] == "e") == 16


def test_disabled_tracer_allocates_nothing_in_step_loop(model):
    """The zero-cost seam, pinned: with tracer=None the step loop never
    executes a line of profiler/trace.py, so tracemalloc filtered to
    that file sees zero allocations."""
    eng = _engine(model)
    rng = np.random.RandomState(11)
    eng.add_request(rng.randint(0, VOCAB, 8).tolist(), max_new_tokens=4)
    eng.run()                             # warm compiles outside the probe
    for _ in range(3):
        eng.add_request(rng.randint(0, VOCAB, 8).tolist(),
                        max_new_tokens=6)
    trace_file = os.path.join("*", "profiler", "trace.py")
    tracemalloc.start()
    try:
        while eng.has_unfinished():
            eng.step()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    stats = snap.filter_traces(
        [tracemalloc.Filter(True, trace_file)]).statistics("lineno")
    assert stats == []


# ---------------------------------------------------------------------------
# cross-tier: /debug/trace through the HTTP frontend
# ---------------------------------------------------------------------------

def test_debug_trace_endpoint_serves_cross_tier_json(model):
    tr = Tracer()
    eng = _engine(model, retain_outputs=False)
    eng.set_tracer(tr)
    srv = serve_background(eng, model_name="tiny")
    try:
        status, _ = _post(srv.port, {"model": "tiny",
                                     "prompt": list(range(6)),
                                     "max_tokens": 4})
        assert status == 200
        status, raw = _get(srv.port, "/debug/trace")
        assert status == 200
        doc = json.loads(raw)
    finally:
        srv.stop()
    tracks = {ev["args"]["name"] for ev in doc["traceEvents"]
              if ev.get("ph") == "M" and ev["name"] == "thread_name"}
    assert "engine" in tracks
    assert "http" in tracks
    assert any(t.startswith("runner") for t in tracks)
    # the request lifecycle pair is balanced and correlated by id
    bs = {ev["id"] for ev in doc["traceEvents"] if ev.get("ph") == "b"}
    es = {ev["id"] for ev in doc["traceEvents"] if ev.get("ph") == "e"}
    assert bs and bs == es
    # runner delivery instants join the engine rid to the frontend's
    # request id — the cross-tier correlation key
    joins = [ev["args"] for ev in doc["traceEvents"]
             if ev.get("ph") == "i" and ev["name"] == "runner.deliver"]
    assert joins and all("request_id" in a and "rid" in a for a in joins)
    # http tier saw the same request
    assert any(ev["name"] == "http.request"
               for ev in doc["traceEvents"] if ev.get("ph") == "i")


def test_debug_trace_404_without_tracer(model):
    eng = _engine(model, retain_outputs=False)
    srv = serve_background(eng, model_name="tiny")
    try:
        status, _ = _get(srv.port, "/debug/trace")
        assert status == 404
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# step ids and causes: every span says which launch it belongs to
# ---------------------------------------------------------------------------

def _spans(tr):
    return [{"ph": ph, "name": name, "ts": ts, "dur": dur,
             "args": args or {}}
            for ph, name, ts, dur, _tid, args, _id in tr.events()]


def _traced_run(model, prompts, **kw):
    tr = Tracer()
    eng = _engine(model, tracer=tr, **kw)
    for p, n in prompts:
        eng.add_request(p, max_new_tokens=n)
    eng.run()
    return eng, tr, _spans(tr)


def test_every_engine_span_and_request_instant_carries_a_known_step(model):
    rng = np.random.RandomState(3)
    _eng, tr, sp = _traced_run(
        model, [(rng.randint(0, VOCAB, n).tolist(), 5) for n in (20, 7, 9)],
        max_prefill_tokens=16, prefill_token_bucket=16)
    steps = {s["args"]["step"] for s in sp if s["name"] == "engine.step"}
    assert steps == set(range(1, max(steps) + 1))
    checked = 0
    for s in sp:
        if (s["ph"] == "X" and s["name"].startswith("engine.")) \
                or s["name"].startswith("request."):
            assert s["args"].get("step") in steps, s
            checked += 1
    assert checked > 40
    # the launch's own span says what rode in it
    dl = [s["args"] for s in sp if s["name"] == "engine.device_launch"]
    assert len(dl) == max(steps) - 1 or len(dl) == max(steps)
    assert all({"bucket", "tokens", "rows", "chunks", "decode",
                "logit_rows"} <= set(a) for a in dl)
    assert [a["step"] for a in dl] == list(range(1, len(dl) + 1))
    sched = [s["args"] for s in sp if s["name"] == "engine.schedule"]
    assert all({"evicted", "cow", "cow_ns"} <= set(a) for a in sched)
    assert tr.unbalanced == 0


def test_a_launch_leaves_four_engine_spans_for_the_idle_attribution(model):
    """The benchmark's trace reduction names every idle gap of the
    device after the engine span of the run, not a wrapper, that it
    falls in (``harness/xplane.py`` ``attribute_gaps``: one walk of the
    two sorted lists since PR 44, microseconds a span; until then a
    double loop, about 0.1 s of a traced run for each span, PERF.md
    sections 5 and 7), and its metrics name these spans: so what a
    launch leaves there is held: schedule (the packing with it), device
    launch, block on result, sample commit; admission and retirement
    only where there was one."""
    rng = np.random.RandomState(9)
    eng, _tr, sp = _traced_run(
        model, [(rng.randint(0, VOCAB, n).tolist(), 12) for n in (20, 7, 9)],
        max_prefill_tokens=16, prefill_token_bucket=16)
    wrappers = ("engine.step", "engine.dispatch", "engine.complete",
                "engine.device_inflight")
    per_launch: dict = {}
    for s in sp:
        if s["ph"] == "X" and s["name"].startswith("engine.") \
                and s["name"] not in wrappers:
            per_launch.setdefault(s["args"]["step"], []).append(s["name"])
    # the last turns found nothing to launch: a schedule span each
    assert set(per_launch.pop(eng.launches + 1, ())) <= {"engine.schedule"}
    assert sorted(per_launch) == list(range(1, eng.launches + 1))
    always = ["engine.block_on_result", "engine.device_launch",
              "engine.sample_commit", "engine.schedule"]
    for names in per_launch.values():
        assert sorted(n for n in names if n not in (
            "engine.admit", "engine.retire")) == always, names
    # three requests: admitted in one turn, retired one by one
    flat = [n for names in per_launch.values() for n in names]
    assert flat.count("engine.admit") == 1
    assert flat.count("engine.retire") == 3


def test_prefill_chunk_carries_the_launching_step_under_overlap(model):
    """A two-chunk prompt: chunk k is computed by launch k and committed
    inside the step() call that dispatches launch k + 1.  The instant
    says k."""
    prompt = np.random.RandomState(5).randint(0, VOCAB, 24).tolist()
    _eng, _tr, sp = _traced_run(model, [(prompt, 3)],
                                max_prefill_tokens=16,
                                prefill_token_bucket=16)
    chunks = [s for s in sp if s["name"] == "request.prefill_chunk"]
    assert [c["args"]["tokens"] for c in chunks] == [16, 8]
    assert [c["args"]["step"] for c in chunks] == [1, 2]
    step_spans = {s["args"]["step"]: s for s in sp
                  if s["name"] == "engine.step"}
    for c in chunks:
        k = c["args"]["step"]
        committing = step_spans[k + 1]      # the NEXT step() call
        assert committing["ts"] <= c["ts"] <= committing["ts"] \
            + committing["dur"]
        launch = next(s for s in sp if s["name"] == "engine.device_launch"
                      and s["args"]["step"] == k)
        assert launch["ts"] + launch["dur"] <= c["ts"]
    first = next(s for s in sp if s["name"] == "request.first_token")
    assert first["args"]["step"] == 2
    # the completion half carries its ticket's id too
    for name in ("engine.block_on_result", "engine.device_inflight",
                 "engine.sample_commit", "engine.complete"):
        got = [s["args"]["step"] for s in sp if s["name"] == name]
        assert got == sorted(got) and got[0] == 1, name


def test_admitted_instant_precedes_the_first_chunk(model):
    rng = np.random.RandomState(9)
    _eng, _tr, sp = _traced_run(
        model, [(rng.randint(0, VOCAB, n).tolist(), 2) for n in (12, 5)])
    for rid in (0, 1):
        mine = [s for s in sp if s["args"].get("rid") == rid]
        names = [s["name"] for s in mine]
        assert names.index("request.queued") \
            < names.index("request.admitted") \
            < names.index("request.prefill_chunk")
        adm = next(s for s in mine if s["name"] == "request.admitted")
        assert adm["args"]["cached"] == 0 and adm["args"]["step"] >= 1


def test_program_built_instants_name_the_jitted_programs(model):
    eng, _tr, sp = _traced_run(
        model, [(list(range(1, 20)), 3)], prefill_token_bucket=32)
    built = [s["args"]["name"] for s in sp
             if s["name"] == "engine.program_built"]
    assert built == ["ragged_step_t32", "ragged_step_t4"]
    assert len(built) == sum(eng.compile_counts.values())


def test_gc_spans_while_a_tracer_is_installed_and_not_after(model):
    import gc
    eng = _engine(model)
    gc.collect()                      # earlier tests' engines go first
    before = list(gc.callbacks)
    tr = Tracer()
    eng.set_tracer(tr)
    assert len(gc.callbacks) == len(before) + 1
    gc.collect()
    got = [e for e in tr.events() if e[1] == "host.gc"]
    assert got and got[-1][5]["generation"] == 2
    assert "collected" in got[-1][5] and got[-1][3] > 0
    doc = tr.chrome_trace()
    tracks = {ev["args"]["name"]: ev["tid"] for ev in doc["traceEvents"]
              if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert {ev["tid"] for ev in doc["traceEvents"]
            if ev["name"] == "host.gc"} == {tracks["host.gc"]}
    # a second engine on the same tracer adds no second hook
    eng2 = _engine(model, tracer=tr)
    assert len(gc.callbacks) == len(before) + 1
    eng.set_tracer(None)
    assert len(gc.callbacks) == len(before) + 1
    eng2.set_tracer(None)
    assert gc.callbacks == before
    n = len(tr.events())
    gc.collect()
    assert len(tr.events()) == n


def test_gc_hook_leaves_with_its_last_engine(model):
    import gc
    gc.collect()
    before = list(gc.callbacks)
    tr = Tracer()
    eng = _engine(model, tracer=tr)
    assert len(gc.callbacks) == len(before) + 1
    del eng
    gc.collect()                      # frees the engine: its finalizer
    assert gc.callbacks == before     # takes the hook along


def test_summary_counts_real_and_padded_tokens(model):
    eng, _tr, sp = _traced_run(
        model, [(list(range(1, 12)), 4), (list(range(3, 9)), 4)])
    s = eng.summary()
    for k in ("tokens_real", "tokens_padded"):
        assert isinstance(s[k], int), k
    # what nothing reads is not counted: logit rows ride on the launch's
    # span, built programs are compile_counts and engine.program_built
    assert "logit_rows_real" not in s and "programs_built" not in s
    assert s["tokens_padded"] >= s["tokens_real"] > 0
    assert s["tokens_real"] == 11 + 6 + 3 + 3      # prompts, then decodes
    dl = [x["args"] for x in sp if x["name"] == "engine.device_launch"]
    assert sum(a["tokens"] for a in dl) == s["tokens_real"]
    assert sum(a["bucket"] for a in dl) == s["tokens_padded"]
    # the pages the launches' rows hold keys in, of bucket * nblk slots
    assert 0 < sum(a["kv_pages"] for a in dl) == s["kv_pages_live"]
    assert all(a["rows"] <= a["kv_pages"] <= a["bucket"] * eng.nblk
               for a in dl)
    assert sum(a["logit_rows"] for a in dl) == 8   # every token sampled
    built = [x for x in sp if x["name"] == "engine.program_built"]
    assert len(built) == sum(eng.compile_counts.values()) == 2


@pytest.mark.parametrize("window", [1, 4])
@pytest.mark.parametrize("sampled", [False, True])
def test_the_launch_says_which_branch_the_sampler_took(model, sampled,
                                                       window):
    """``sample_chain`` on ``engine.device_launch`` is 1 where the
    launch's rows hold a sampled request (the predicate the step
    program branches on, read from the same host array), and the
    summary counts the passes of the epilogue on both sides."""
    tr = Tracer()
    eng = _engine(model, tracer=tr, decode_window=window)
    eng.add_request(list(range(1, 12)), max_new_tokens=6)
    eng.add_request(list(range(3, 9)), max_new_tokens=3,
                    temperature=0.8 if sampled else 0.0, seed=5)
    eng.run()
    dl = [x["args"] for x in _spans(tr)
          if x["name"] == "engine.device_launch"]
    assert all(a["sample_chain"] in (0, 1) for a in dl)
    s = eng.summary()
    assert s["sample_launches"] >= len(dl) > 0
    if window == 1:
        assert s["sample_launches"] == len(dl)
        assert s["sample_chain_launches"] \
            == sum(a["sample_chain"] for a in dl)
    if not sampled:
        assert s["sample_chain_launches"] == 0
        assert not any(a["sample_chain"] for a in dl)
    else:
        # from its first chunk to its last token, and not after it has
        # gone: the other request decodes on alone, greedy
        assert 0 < s["sample_chain_launches"] < s["sample_launches"]
        assert dl[0]["sample_chain"] == 1 and dl[-1]["sample_chain"] == 0


# ---------------------------------------------------------------------------
# the watcher does not change what is watched
# ---------------------------------------------------------------------------

def test_untraced_loop_makes_no_annotation_and_no_record_event(
        model, monkeypatch):
    """Without a tracer a step loop builds no TraceAnnotation and runs
    no line of profiler/profiler.py (RecordEvent is gone from the
    engine); with one, each launch is bracketed once, with its id."""
    from paddle_tpu.inference import serving

    assert not hasattr(serving, "RecordEvent")
    made = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        made.append((name, kw))
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    eng = _engine(model)
    eng.add_request(list(range(1, 9)), max_new_tokens=3)
    eng.run()                               # compiles outside the probe
    eng.add_request(list(range(2, 10)), max_new_tokens=5)
    tracemalloc.start()
    try:
        while eng.has_unfinished():
            eng.step()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    assert made == []
    for f in ("trace.py", "profiler.py"):
        pat = os.path.join("*", "paddle_tpu", "profiler", f)
        assert snap.filter_traces(
            [tracemalloc.Filter(True, pat)]).statistics("lineno") == []
    eng.set_tracer(Tracer())
    eng.add_request(list(range(3, 11)), max_new_tokens=3)
    eng.run()
    assert made and all(n == "engine.launch" for n, _ in made)
    launches = [e[5]["step"] for e in eng.tracer.events()
                if e[1] == "engine.device_launch"]
    assert [kw["step"] for _, kw in made] == launches
    assert all(kw["bucket"] in (4, 32) for _, kw in made)


def test_step_has_one_call_site_into_the_step_body():
    """The line a program is first reached from must not depend on a
    tracer: ``step()`` calls ``_step`` once."""
    import ast
    import inspect
    import textwrap

    fn = ast.parse(textwrap.dedent(inspect.getsource(LLMEngine.step)))
    calls = [n for n in ast.walk(fn) if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute)
             and n.func.attr == "_step"]
    assert len(calls) == 1
    src = inspect.getsource(LLMEngine._call_program)
    assert src.count("prog(*args)") == 1


def test_traced_and_untraced_engines_build_the_same_programs(model):
    def build(tracer):
        eng = _engine(model, tracer=tracer)
        eng.add_request(list(range(1, 15)), max_new_tokens=4)
        eng.run()
        structs = eng._ragged_arg_structs(32)
        return (dict(eng.compile_counts), sorted(eng._ragged_progs),
                eng._ragged_progs[32].lower(*structs).as_text())

    plain, traced = build(None), build(Tracer())
    assert plain[0] == traced[0] and plain[1] == traced[1] == [4, 32]
    assert plain[2] == traced[2]
    assert "module @jit_ragged_step_t32" in plain[2]


# ---------------------------------------------------------------------------
# names inside the compiled programs
# ---------------------------------------------------------------------------

SCOPES = ("embed", "norm", "qkv", "rope", "kv_write", "attn", "o_proj",
          "mlp", "head", "sample")


def _lowered(eng, prog, structs):
    text = prog.lower(*structs).as_text(debug_info=True)
    names = set()
    for loc in re.findall(r'loc\("([^"]+)"', text):
        names.update(loc.split("/"))
    return text, names


@pytest.mark.parametrize("kind", ["ragged", "ragged_q8", "window"])
def test_step_programs_carry_scope_and_kernel_names(model, kind,
                                                    monkeypatch):
    from paddle_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "INTERPRET", True)   # the kernel, not the
    kw = {}                                      # XLA reference
    if kind == "ragged_q8":
        kw["kv_dtype"] = "int8"
    if kind == "window":
        kw["decode_window"] = 4
    eng = _engine(model, **kw)
    assert eng.attention_path.startswith("pallas")
    if kind == "window":
        prog, structs = eng._get_window_prog(), eng._window_arg_structs()
        module = "jit_decode_window_k4"
    else:
        prog, structs = eng._get_ragged_prog(32), \
            eng._ragged_arg_structs(32)
        module = "jit_ragged_step_t32"
    text, names = _lowered(eng, prog, structs)
    assert f"module @{module}" in text
    for scope in SCOPES + ("layers",):
        assert scope in names, scope
    kernel = "ragged_paged_attention_q8" if kind == "ragged_q8" \
        else "ragged_paged_attention"
    assert kernel in names or kernel in text, kernel


def test_cow_program_is_named(model):
    eng = _engine(model)
    eng._apply_cow(1, 2)
    sds = jax.ShapeDtypeStruct
    text = eng._cow_prog.lower(
        sds(eng._kc.shape, eng._kc.dtype), sds(eng._vc.shape, eng._vc.dtype),
        sds((), jnp.int32), sds((), jnp.int32)).as_text()
    assert "module @jit_kv_cow" in text


# ---------------------------------------------------------------------------
# from an instruction's name back to its scope
# ---------------------------------------------------------------------------

_HLO = '''HloModule jit_ragged_step_t32, is_scheduled=true

%fused_computation.44.clone (param_0.1: bf16[32,4096], param_1.2: bf16[4096,4096]) -> bf16[32,4096] {
  %param_0.1 = bf16[32,4096]{1,0} parameter(0)
  %param_1.2 = bf16[4096,4096]{1,0} parameter(1)
  %convolution.5 = bf16[32,4096]{1,0} convolution(bf16[32,4096]{1,0} %param_0.1, bf16[4096,4096]{1,0} %param_1.2), dim_labels=bf_io->bf, metadata={op_name="jit(ragged_step_t32)/layers/while/body/o_proj/dot_general" source_file="x.py" source_line=7}
  ROOT %add.3 = bf16[32,4096]{1,0} add(bf16[32,4096]{1,0} %convolution.5, bf16[32,4096]{1,0} %param_0.1), metadata={op_name="jit(ragged_step_t32)/layers/while/body/o_proj/add"}
}

%fused_computation.7 (param_0.9: f32[32]) -> f32[32] {
  %param_0.9 = f32[32]{0} parameter(0)
  ROOT %rsqrt.1 = f32[32]{0} rsqrt(f32[32]{0} %param_0.9), metadata={op_name="jit(ragged_step_t32)/layers/while/body/norm/rsqrt"}
}

ENTRY %main.12 (a: bf16[32,4096], b: bf16[4096,4096], c: f32[32]) -> (bf16[32,4096], f32[32]) {
  %a = bf16[32,4096]{1,0} parameter(0)
  %b = bf16[4096,4096]{1,0} parameter(1)
  %c = f32[32]{0} parameter(2)
  %fusion.199 = bf16[32,4096]{1,0:T(8,128)(2,1)} fusion(bf16[32,4096]{1,0} %a, bf16[4096,4096]{1,0} %b), kind=kOutput, calls=%fused_computation.44.clone, metadata={op_name="jit(ragged_step_t32)/layers/while/body/o_proj/add"}
  %add_rsqrt_fusion.6 = f32[32]{0} fusion(f32[32]{0} %c), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(ragged_step_t32)/layers/while/body/norm/rsqrt"}
  %copy.137 = bf16[32,4096]{0,1} copy(bf16[32,4096]{1,0} %fusion.199)
  ROOT %tuple.1 = (bf16[32,4096]{0,1}, f32[32]{0}) tuple(bf16[32,4096]{0,1} %copy.137, f32[32]{0} %add_rsqrt_fusion.6)
}
'''


def test_instruction_scopes_reads_op_names_and_finds_fused_dots():
    from paddle_tpu.inference.serving import _instruction_scopes

    m = _instruction_scopes(_HLO)
    assert m["fusion.199"] == {
        "op_name": "jit(ragged_step_t32)/layers/while/body/o_proj/add",
        "dot": True}                      # the product is inside the fusion
    assert m["add_rsqrt_fusion.6"]["dot"] is False
    assert m["add_rsqrt_fusion.6"]["op_name"].endswith("/norm/rsqrt")
    assert m["convolution.5"]["dot"] is True
    assert m["copy.137"] == {"op_name": "", "dot": False}   # XLA's own
    assert "main.12" not in m and "fused_computation.7" not in m


def test_program_scopes_maps_the_built_programs_on_request(model):
    eng = _engine(model)
    eng.add_request(list(range(1, 12)), max_new_tokens=3)
    eng.run()
    built = dict(eng.compile_counts)
    m = eng.program_scopes()
    assert sorted(m) == ["ragged_step_t32", "ragged_step_t4"]
    assert dict(eng.compile_counts) == built       # asking builds nothing
    for prog in m.values():
        paths = [v["op_name"] for v in prog.values()]
        for scope in SCOPES + ("layers",):
            assert any(f"/{scope}/" in p for p in paths), scope
        dots = [v["op_name"] for v in prog.values() if v["dot"]]
        assert any("/qkv/" in p for p in dots)
        assert any("/head/" in p for p in dots)
        assert any(p.startswith("jit(ragged_step_t") for p in paths)
    # a bucket nobody launched yet can be asked for by size
    assert list(eng.program_scopes([64])) == ["ragged_step_t64"]
