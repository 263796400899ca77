"""The readers that rest on the program's own names (PR 25): scopes on
device operations, step ids on spans.  The arithmetic is checked against
numbers worked out by hand; the device side against a recording of two
launches from the chip (``data/recorded_step_scoped.json``: launches 75
and 76 of a ``mistral7b.chat`` run, from the ``engine.launch`` annotation
of 75 to that of 77, times rebased to it; ``events`` are the device
plane's "XLA Ops" as ``xplane.read_device_events`` gives them,
``modules`` its "XLA Modules" line, ``program_scopes`` the engine's map
cut to the instructions that ran, ``spans`` the Tracer's with those two
step ids)."""
import json
import os

import pytest

from harness import costs, peaks, scopes, spec
from harness import xplane as X

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = spec.load_shapes("llama_dense")


# ---------------------------------------------------------------------------
# host side, on spans written by hand
# ---------------------------------------------------------------------------

def _x(name, ts, dur, **args):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "args": args}


def _i(name, ts, **args):
    return {"ph": "i", "name": name, "ts": ts, "dur": 0, "args": args}


def test_scope_of_takes_the_innermost_known_name():
    def f(op_name):
        return scopes.scope_of(op_name, ARCH)

    assert f("jit(ragged_step_t192)/layers/while/body/qkv/dot_general") \
        == "qkv"
    assert f("jit(ragged_step_t32)/sample/jit(_where)/select_n") == "sample"
    assert f("jit(ragged_step_t32)/layers/while/body/dynamic_slice") \
        == "layers"
    assert f("jit(ragged_step_t32)/layers/while/body/attn/"
             "ragged_paged_attention/pallas_call") == "attn"
    assert f("jit(ragged_step_t32)/iota") is None and f("") is None
    # a name is a whole path component: no scope hides in "normalize"
    assert f("jit(f)/normalize/add") is None


def test_matmul_costs_by_hand():
    m = {"hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 32,
         "num_hidden_layers": 3}
    assert ARCH.dims(m) == {"H": 8, "nh": 2, "kvh": 1, "d": 4, "F": 16,
                            "V": 32, "L": 3}
    assert ARCH.layer_weights(ARCH.dims(m)) == 128 + 64 + 384
    ops, byt = ARCH.step_matmuls(m, 5, 2)
    assert ops == 2 * 5 * 576 * 3 + 2 * 2 * 8 * 32 == 18304
    # weights once, activations per token and layer, logit rows
    assert byt == (576 * 3 + 256) * 2 + 5 * 104 * 3 * 2 \
        + 2 * (8 * 2 + 32 * 4) == 7376
    # more tokens read the weights no more often
    ops2, byt2 = ARCH.step_matmuls(m, 10, 2)
    assert ops2 - ops == 2 * 5 * 576 * 3 and byt2 - byt == 5 * 104 * 3 * 2
    bench = spec.load_benchmark()
    mistral = spec.load_config(bench, "mistral-7b-v0.3-d8")
    assert ARCH.layer_weights(ARCH.dims(mistral)) == 218103808
    # a decode step of 32 tokens is bound by the weights' bytes
    pk = peaks.peaks("TPU v5 lite")
    o, b = ARCH.step_matmuls(mistral, 32, 32)
    least, bound = costs.least_seconds(o, b, pk)
    assert bound == "memory" and least == pytest.approx(4.62e-3, rel=0.01)
    assert costs.matmul_least_seconds(ARCH, mistral, [(32, 32), (32, 32)],
                                      pk) == pytest.approx(2 * least)


def test_prefill_wait_runs_to_the_launch_that_carried_the_first_chunk():
    spans = [
        _i("request.queued", 1000, rid=7, step=3),
        _i("request.queued", 1500, rid=8, step=3),
        _i("request.queued", 100, rid=6, step=1),       # before the window
        _x("engine.device_launch", 4000, 50, step=3, bucket=192),
        _x("engine.device_launch", 9000, 50, step=4, bucket=192),
        _x("engine.device_launch", 15000, 50, step=5, bucket=32),
        # committed later than launched: the instant's time is not used
        _i("request.prefill_chunk", 9500, rid=7, tokens=128, step=3),
        _i("request.prefill_chunk", 15500, rid=7, tokens=60, step=4),
        _i("request.prefill_chunk", 15600, rid=8, tokens=68, step=4),
        _i("request.prefill_chunk", 9600, rid=6, tokens=32, step=3),
    ]
    assert sorted(scopes.prefill_waits_ns(spans, 500, 20000)) \
        == [3000, 7500]
    # a program that puts no step on its spans gives nothing to read
    bare = [dict(s, args={k: v for k, v in s["args"].items()
                          if k != "step"}) for s in spans]
    assert scopes.prefill_waits_ns(bare, 500, 20000) == []


def _launch(step, t_end, bucket=32, chunks=0):
    return [_x("engine.device_launch", t_end - 900, 10, step=step,
               bucket=bucket, chunks=chunks),
            _x("engine.block_on_result", t_end - 500, 500, step=step)]


def test_stall_is_what_a_period_takes_over_twice_its_class_median():
    ms = 1_000_000
    spans, t = [], 0
    ends = {}
    for step, (period, bucket, chunks) in enumerate(
            [(100, 32, 0), (100, 32, 0), (110, 32, 0), (500, 192, 1),
             (520, 192, 1), (2600, 32, 0), (90, 32, 0), (1300, 192, 1),
             (100, 32, 0)], start=1):
        t += period * ms
        ends[step] = t
        spans += _launch(step, t, bucket, chunks)
    periods = scopes.launch_periods(spans, 0, t + 1)
    assert [p["step"] for p in periods] == list(range(2, 10))  # 1 has no
    assert periods[0]["ns"] == 100 * ms                       # predecessor
    total, over = scopes.stalls(periods)
    # decode class: periods 100 110 2600 90 100, median 100, line 200
    # chunk class: 500 520 1300, median 520, line 1040
    assert [(p["step"], p["ns"] // ms) for p in over] \
        == [(6, 2600), (8, 1300)]
    assert total == (2600 - 200) * ms + (1300 - 1040) * ms
    sound = [p for p in periods if p["step"] not in (6, 8)]
    assert scopes.stalls(sound) == (0, [])


def test_a_stalled_period_says_where_it_went():
    # step 5's period runs from 1000 to 4000 us; the wrapper
    # (engine.complete) and its first phase are clipped to the same
    # start and must not both count the time
    us = 1000
    spans = [dict(s, ts=s["ts"] * us, dur=s["dur"] * us) for s in [
        _x("engine.step", 900, 3300, step=5),
        _x("engine.complete", 950, 2100, step=4),
        _x("engine.block_on_result", 950, 50, step=4),       # ends 1000
        _x("engine.sample_commit", 1000, 2000, step=4),
        _x("engine.dispatch", 3100, 1000, step=5),
        _x("engine.schedule", 3100, 300, step=5),
        _x("engine.device_launch", 3400, 600, step=5),
        _x("engine.device_inflight", 4000, 900, step=5),
        _x("host.gc", 1200, 1500, generation=2, collected=10),
        _x("runner.between_steps", 500, 400, step=5, taken=1)]]
    p = {"step": 5, "bucket": 32, "chunk": False, "start": 1000 * us,
         "end": 4000 * us, "ns": 3000 * us, "line_ns": 400.0 * us}
    got = scopes.explain_period(p, spans)
    assert got["period_ms"] == 3.0 and got["line_ms"] == 0.4
    assert got["self_ms"] == {
        "engine.sample_commit": 2.0, "host.gc": 1.5,
        "engine.device_launch": 0.6, "engine.schedule": 0.3,
        "engine.step": 0.05,          # 3050 to 3100: between the halves
        "engine.complete": 0.05}      # 3000 to 3050: after the commit
    # every engine nanosecond of the period counted once
    assert sum(v for k, v in got["self_ms"].items()
               if k.startswith("engine.")) == pytest.approx(3.0)
    assert "device_busy_ms" not in got            # no profile given


def test_pad_share_reads_the_two_counters():
    read = spec.load_reader("engine.pad_share")
    assert read({"c0": {"tokens_real": 100, "tokens_padded": 200},
                 "c1": {"tokens_real": 1700, "tokens_padded": 2200}}) \
        == pytest.approx(20.0)
    assert read({"c0": {}, "c1": {}}) is None        # a program without


def test_whole_steps_need_the_next_annotation_inside_the_window():
    notes = [{"step": s, "bucket": 32, "start_ns": t}
             for s, t in ((4, 50), (5, 150), (6, 400), (7, 990))]
    assert scopes.whole_steps(notes, (100, 1000)) == {5, 6}
    assert scopes.whole_steps(notes, (0, 300)) == {4}


# ---------------------------------------------------------------------------
# device side, on the recording of two launches from the chip
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rec():
    with open(os.path.join(HERE, "data", "recorded_step_scoped.json")) as f:
        d = json.load(f)
    d["events"] = [dict(zip(d["fields"], r)) for r in d["events"]]
    bench = spec.load_benchmark()
    d["cfg"] = spec.load_config(bench, "mistral-7b-v0.3-d8")
    evs = X.self_times(X.clip(d["events"], *d["window"]))
    d["scoped"] = scopes.annotate(evs, d["modules"], d["launches"],
                                  d["program_scopes"], ARCH)
    return d


BUSY = 621313596            # ns, both launches; read off the recording


def test_every_operation_finds_its_program_its_step_and_a_class(rec):
    assert len(rec["events"]) == 2166 and len(rec["scoped"]) == 2156
    assert X.busy_ns(rec["scoped"]) == BUSY
    progs = {(e["program"], e["step"]) for e in rec["scoped"]}
    assert progs == {("ragged_step_t192", 75), ("ragged_step_t32", 76)}
    assert sum(1 for e in rec["scoped"] if e["step"] == 75) == 1133
    by = scopes.by_class(rec["scoped"], rec["cfg"], ARCH)
    # scopes, pool-shaped copies and the unscoped remainder are all of it
    assert sum(by.values()) == BUSY
    assert set(by) == set(ARCH.SCOPES) | {ARCH.LOOP, scopes.POOL_COPY,
                                          scopes.UNSCOPED}
    assert by["attn"] == 494468937 and by["sample"] == 48809572
    assert by["kvpool_copy"] == 52061825 and by["kv_write"] == 13482796
    assert by["mlp"] == 7635952 and by["layers"] == 2002990
    assert by["unscoped"] == 378863          # 0.06% of busy time
    assert 100 * by["unscoped"] / BUSY < 5


def test_a_map_that_does_not_fit_the_trace_shows_as_unscoped(rec):
    """The map is the live engine's own (``ctx["program_scopes"]``), so
    nothing guards the join any more; a map that does not name the
    trace's instructions would put all busy time in ``unscoped``, in
    plain sight on the ``[bench] scopes`` line, and no program's map at
    all gives the readers nothing."""
    other = {prog: {"x." + name: info for name, info in m.items()}
             for prog, m in rec["program_scopes"].items()}
    evs = X.self_times(X.clip(rec["events"], *rec["window"]))
    lost = scopes.annotate(evs, rec["modules"], rec["launches"], other, ARCH)
    by = scopes.by_class(lost, rec["cfg"], ARCH)
    assert set(by) == {scopes.UNSCOPED, scopes.POOL_COPY}
    assert sum(by.values()) == BUSY
    ctx = {"trace": {"plane": "/device:TPU:0", "window": rec["window"]},
           "arch": ARCH, "cfg": rec["cfg"]}
    assert scopes.scoped_events(dict(ctx, program_scopes={})) == []
    assert scopes.scoped_events(ctx) == []


def test_scoped_events_join_the_engines_map_once(rec, monkeypatch):
    """``ctx["program_scopes"]`` is what ``run.py`` asked of the engine
    that served; the join is worked out once for a run's readers."""
    calls = []

    def read(trace_dir, plane):
        calls.append(plane)
        return rec["events"], rec["modules"], rec["launches"]

    monkeypatch.setattr(scopes, "_read", read)
    ctx = {"trace": {"plane": "/device:TPU:0", "window": rec["window"]},
           "arch": ARCH, "cfg": rec["cfg"],
           "program_scopes": rec["program_scopes"]}
    evs = scopes.scoped_events(ctx)
    assert scopes.scoped_events(ctx) is evs and calls == ["/device:TPU:0"]
    assert [(e["name"], e["scope"], e["step"], e["self_ns"]) for e in evs] \
        == [(e["name"], e["scope"], e["step"], e["self_ns"])
            for e in rec["scoped"]]


def _shaped_like_the_kernel(e, kv_heads, group, head_dim):
    """How ``attn.*`` found the kernel before they took its name: a
    custom call whose result is [tokens, kv_heads, group, head_dim]."""
    import re
    if (e.get("category") or "").lower() != "custom-call":
        return False
    m = re.match(r"[a-z]+[0-9]*\[([0-9,]*)\]", e.get("shape") or "")
    if not m:
        return False
    dimsv = [int(x) for x in m.group(1).split(",") if x]
    return len(dimsv) == 4 and dimsv[1:] == [kv_heads, group, head_dim]


def test_kernel_found_by_name_and_by_shape_is_the_same_time(rec):
    """attn.* find the kernel by the names the architecture's file
    lists; on the recording that is what its result's shape found."""
    named = [e for e in rec["scoped"]
             if scopes.is_kernel_name(e["name"], ARCH)]
    shaped = [e for e in rec["scoped"]
              if _shaped_like_the_kernel(e, 8, 4, 128)]
    assert len(named) == len(shaped) == 16           # 8 layers, 2 launches
    assert [e["name"] for e in named] == [e["name"] for e in shaped]
    assert sum(e["self_ns"] for e in named) == 494468937 \
        == sum(e["self_ns"] for e in shaped) \
        == scopes.kernel_ns(rec["scoped"], ARCH)
    assert {e["scope"] for e in named} == {"attn"}
    assert all(e["op_name"].endswith(
        "/attn/ragged_paged_attention/pallas_call") for e in named)
    assert not scopes.is_kernel_name("closed_call.14", ARCH)
    assert scopes.is_kernel_name("%ragged_paged_attention_q8.3", ARCH)
    # attn.device_share on the recording: the kernel over busy time
    ctx = {"trace": {"events": rec["scoped"], "busy_s": BUSY / 1e9},
           "arch": ARCH}
    assert spec.load_reader("attn.device_share")(ctx) \
        == pytest.approx(100 * 494468937 / BUSY)


def test_pool_shaped_copies_are_not_the_page_writes(rec):
    shapes = ARCH.pool_shapes(rec["cfg"])
    assert (8, 4097, 8, 16, 128) in shapes and (4097, 8, 16, 128) in shapes
    copies = [e for e in rec["scoped"]
              if scopes.classify(e, shapes, ARCH) == "kvpool_copy"]
    # the two whole-pool copies that close every step, 3.2 ms each
    whole = [e for e in copies if e["category"] == "copy"
             and e["shape"] == "bf16[8,4097,8,16,128]"]
    assert len(whole) == 4
    assert all(3.1e6 < e["self_ns"] < 3.4e6 for e in whole)
    writes = [e for e in rec["scoped"] if e["scope"] == "kv_write"
              and scopes.is_pool_shaped(e, shapes)]
    assert writes and all(
        scopes.classify(e, shapes, ARCH) == "kv_write" for e in writes)


def test_matmul_time_takes_in_the_weight_slices(rec):
    shapes = ARCH.pool_shapes(rec["cfg"])
    mm = {s: sum(e["self_ns"] for e in rec["scoped"]
                 if e["step"] == s and scopes.is_matmul(e, shapes, ARCH))
          for s in (75, 76)}
    assert mm == {75: 6117199, 76: 5634547}
    # no product hides outside the four scopes in these programs
    assert not [e for e in rec["scoped"] if e["has_dot"]
                and e["scope"] not in ARCH.MATMUL_SCOPES]
    dots = sum(e["self_ns"] for e in rec["scoped"]
               if e["step"] == 76 and e["has_dot"])
    # the products alone read 99.9% of the memory's peak (4.636 ms of
    # weights in 4.641 ms): the q, k, v, o slices were moved beforehand
    assert dots == 4641005 and dots < mm[76]


def _ctx(rec, monkeypatch):
    monkeypatch.setattr(scopes, "scoped_events", lambda ctx: rec["scoped"])
    monkeypatch.setattr(scopes, "launch_annotations",
                        lambda ctx: rec["launches"])
    w0, w1 = rec["window"]
    return {"cfg": rec["cfg"], "spans": rec["spans"], "arch": ARCH,
            "device_kind": "TPU v5 lite",
            # one ns more: launch 77's annotation closes launch 76
            "trace": {"busy_s": BUSY / 1e9, "window": (w0, w1 + 1)}}


def test_device_readers_on_the_recording(rec, monkeypatch, capsys):
    ctx = _ctx(rec, monkeypatch)
    assert spec.load_reader("matmul.device_share")(ctx) \
        == pytest.approx(100 * (6117199 + 5634547) / BUSY)      # 1.89
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("[bench] scopes ")][0]
    shares = json.loads(line[len("[bench] scopes "):])
    assert sum(shares.values()) == pytest.approx(100.0, abs=0.01)
    assert shares["attn"] == pytest.approx(79.585, abs=1e-3)
    assert spec.load_reader("sampling.device_share")(ctx) \
        == pytest.approx(100 * 48809572 / BUSY)                 # 7.86
    assert spec.load_reader("kvpool.copy_share")(ctx) \
        == pytest.approx(100 * 52061825 / BUSY)                 # 8.38


def test_matmul_roofline_on_the_recording_is_under_100(rec, monkeypatch):
    ctx = _ctx(rec, monkeypatch)
    launched = scopes.launch_args(rec["spans"])
    assert {s: (a["tokens"], a["logit_rows"], a["bucket"])
            for s, a in launched.items()} \
        == {75: (146, 31, 192), 76: (31, 31, 32)}
    assert ARCH.step_matmuls(rec["cfg"], 146, 31) \
        == (517811994624, 3925073920)
    assert ARCH.step_matmuls(rec["cfg"], 31, 31) \
        == (116500987904, 3796951040)
    got = spec.load_reader("matmul.roofline_share")(ctx)
    least = 3925073920 / 819e9 + 3796951040 / 819e9     # both memory-bound
    assert got == pytest.approx(100 * least / 11751746e-9)
    assert got == pytest.approx(80.23, abs=0.01) and got < 100
    # each launch alone: the decode launch 82.3%, the chunk launch 78.3%
    assert 100 * (3796951040 / 819e9) / 5634547e-9 \
        == pytest.approx(82.28, abs=0.01)


def test_launch_period_from_the_recordings_spans(rec):
    periods = scopes.launch_periods(rec["spans"], 0, 10 ** 12)
    assert [(p["step"], p["bucket"], p["chunk"], p["ns"])
            for p in periods] == [(76, 32, False, 148056669)]
    assert scopes.stalls(periods) == (0, [])
    out = scopes.explain_period(dict(periods[0], line_ns=1.0),
                                rec["spans"])
    assert out["self_ms"]["engine.block_on_result"] \
        == pytest.approx(134.116, abs=1e-3)
    assert out["self_ms"]["runner.between_steps"] == 0.143


def test_a_program_without_names_gives_the_readers_nothing():
    """The parent of PR 25: modules are ``jit_run``, spans carry no
    ``step``, ``summary()`` has no token counters."""
    assert scopes.program_of("jit_run(123456)") == "run"
    assert scopes.scoped_events({"trace": None}) == []
    ctx = {"trace": None, "spans": [], "c0": {}, "c1": {}, "cfg": {},
           "t_open": 0, "t_close": 1, "arch": ARCH, "program_scopes": {}}
    for name in ("matmul.device_share", "matmul.roofline_share",
                 "sampling.device_share", "kvpool.copy_share",
                 "engine.pad_share", "engine.prefill_wait_p50_ms",
                 "step.stall_s"):
        assert spec.load_reader(name)(ctx) is None, name


def test_the_builders_tool_still_fits_run_py():
    """``tools/traced.py`` puts its own class where ``run._Profile`` is:
    a ``benchmark`` PR that renames it or changes its shape breaks the
    tool here and not on the chip."""
    import importlib.util
    import inspect
    import run
    path = os.path.join(os.path.dirname(HERE), "tools", "traced.py")
    sp = importlib.util.spec_from_file_location("traced_tool", path)
    tool = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(tool)
    assert list(inspect.signature(run._Profile.__init__).parameters) \
        == ["self", "t_open_ns", "seconds", "trace_dir"]
    assert issubclass(tool._WholeWindow, run._Profile)
    for name in ("join", "error"):
        assert hasattr(tool._NoProfile, name), name
    assert "_Profile(" in inspect.getsource(run.run_cell)
