"""The seven readers PR 37 adds, on a context written by hand: the five
that read ``summary()``'s counters (``c1`` less ``c0``) and the two that
read the step programs' executions on the device's own clock."""
import pytest

from harness import scopes, spec

COUNTER_READERS = ("engine.turn_ms", "engine.device_wait_share",
                   "engine.launch_call_ms", "engine.launch_arg_mb",
                   "engine.commit_ms")
DEVICE_READERS = ("step.device_prefill_ms", "step.device_decode_ms")
MS = 1_000_000


def _counters():
    c0 = {"launches": 100, "turn_time_s": 1.0, "block_time_s": 0.5,
          "launch_call_time_s": 0.4, "commit_time_s": 0.3,
          "launch_arg_bytes": 100 * 2_000_000}
    c1 = {"launches": 1100, "turn_time_s": 9.0, "block_time_s": 2.5,
          "launch_call_time_s": 4.4, "commit_time_s": 2.8,
          "launch_arg_bytes": 1100 * 2_000_000 + 1000 * 100_000}
    # a window of 10 s on the load generator's clock
    return {"c0": c0, "c1": c1, "t_open": 5 * 10**9, "t_close": 15 * 10**9}


def test_the_counter_readers_take_the_windows_part_a_launch():
    ctx = _counters()
    got = {n: spec.load_reader(n)(ctx) for n in COUNTER_READERS}
    assert got["engine.turn_ms"] == pytest.approx(8.0)
    assert got["engine.device_wait_share"] == pytest.approx(20.0)
    assert got["engine.launch_call_ms"] == pytest.approx(4.0)
    assert got["engine.launch_arg_mb"] == pytest.approx(2.1)
    assert got["engine.commit_ms"] == pytest.approx(2.5)
    assert got["engine.turn_ms"] >= got["engine.launch_call_ms"] \
        + got["engine.commit_ms"]


@pytest.mark.parametrize("name", COUNTER_READERS)
def test_a_program_without_the_counter_gives_nothing(name):
    """The parent of PR 37 has ``launches`` and ``block_time_s`` and
    none of the four new keys: the four readers that need one return
    None and do not raise; so does every reader in a window in which
    nothing was launched."""
    ctx = _counters()
    old = {k: {kk: v for kk, v in ctx[k].items()
               if kk in ("launches", "block_time_s")} for k in ("c0", "c1")}
    got = spec.load_reader(name)(dict(ctx, **old))
    if name == "engine.device_wait_share":
        assert got == pytest.approx(20.0)
    else:
        assert got is None
    still = dict(ctx, c1=dict(ctx["c1"], launches=100))
    if name != "engine.device_wait_share":
        assert spec.load_reader(name)(still) is None
    assert spec.load_reader(name)(dict(ctx, c0={}, c1={})) is None


def _x(name, ts, dur, **args):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "args": args}


def _device_ctx(monkeypatch):
    """A traced window of 1 to 9 ms on the profile's clock.  Launch 1
    began before it and launch 6 ends after it; launches 2 and 4 carried
    a chunk (1 and 2 ms on the device), 3 and 5 none (0.5, 0.7); a page
    copy runs between 3 and 4; launch 7's ``engine.device_launch`` fell
    out of the ring.  Launch k + 1 is annotated while k runs."""
    modules = [
        ("ragged_step_t64", 0.5, 1.0),     # 1: straddles the left edge
        ("ragged_step_t64", 1.6, 1.0),     # 2
        ("ragged_step_t32", 2.7, 0.5),     # 3
        ("kv_cow", 3.25, 0.05),
        ("ragged_step_t64", 3.4, 2.0),     # 4
        ("ragged_step_t32", 5.5, 0.7),     # 5
        ("ragged_step_t64", 6.3, 1.5),     # 7 (nothing known of it)
        ("ragged_step_t32", 8.5, 0.9),     # 6: straddles the right edge
    ]
    mods = [{"program": p, "start_ns": int(a * MS), "dur_ns": int(d * MS)}
            for p, a, d in modules]
    notes = [(1, 0.4), (2, 0.9), (3, 1.9), (4, 3.0), (5, 3.9), (7, 5.9),
             (6, 7.0)]
    launches = [{"step": s, "bucket": 0, "start_ns": int(t * MS)}
                for s, t in notes]
    monkeypatch.setattr(scopes, "_read",
                        lambda _dir, _plane: ([], mods, launches))
    spans = [_x("engine.device_launch", 10 * k, 5, step=k, chunks=c)
             for k, c in ((1, 1), (2, 1), (3, 0), (4, 2), (5, 0), (6, 0))]
    return {"trace": {"plane": "/device:TPU:0",
                      "window": (1 * MS, 9 * MS)},
            "spans": spans,
            "program_scopes": {"ragged_step_t32": {}, "ragged_step_t64": {}}}


def test_the_device_readers_take_whole_steps_by_their_launch(monkeypatch):
    ctx = _device_ctx(monkeypatch)
    prefill = spec.load_reader("step.device_prefill_ms")(ctx)
    decode = spec.load_reader("step.device_decode_ms")(ctx)
    assert prefill == pytest.approx((1.0 + 2.0) / 2)
    assert decode == pytest.approx((0.5 + 0.7) / 2)
    # worked out once for the two of them
    assert sorted(ctx["_device_steps"]) == [(0, 0.5), (0, 0.7), (1, 1.0),
                                            (2, 2.0)]


def test_a_window_with_no_whole_step_of_a_kind_gives_nothing(monkeypatch):
    ctx = _device_ctx(monkeypatch)
    ctx["trace"]["window"] = (int(1.5 * MS), int(2.65 * MS))   # launch 2
    assert spec.load_reader("step.device_prefill_ms")(ctx) \
        == pytest.approx(1.0)
    assert spec.load_reader("step.device_decode_ms")(ctx) is None


@pytest.mark.parametrize("name", DEVICE_READERS)
@pytest.mark.parametrize("lacks", ["trace", "program_scopes"])
def test_no_trace_or_no_named_programs_gives_nothing(monkeypatch, name,
                                                     lacks):
    """A CPU run has no profile; the parent of PR 25 names no program."""
    ctx = _device_ctx(monkeypatch)
    ctx[lacks] = None
    monkeypatch.setattr(scopes, "_read", None)      # and is not asked
    assert spec.load_reader(name)(ctx) is None


SIX = ["mistral7b.chat", "yi6b.chat", "mistral7b.sysprompt",
       "sarvam105b.docs", "smallthinker21b.mixedlen", "lagunaxs2.agentctx"]
FOUR = ["mistral7b.chat", "yi6b.chat", "mistral7b.sysprompt",
        "lagunaxs2.agentctx"]        # those of step.decode_ms


@pytest.mark.parametrize("name", COUNTER_READERS + DEVICE_READERS)
def test_the_entry_names_its_cells_and_its_reader_is_found(name):
    """Held by name and by ``in``: a later PR appends entries and cells."""
    bench = spec.load_benchmark()
    m = next(o for o in bench["per_layer"] if o["name"] == name)
    assert callable(spec.load_reader(name))
    want = FOUR if name == "step.device_decode_ms" else SIX
    assert m["workloads"][:len(want)] == want
    assert m["layer"] == ("step programs" if name in DEVICE_READERS
                          else "engine host loop")
    assert m["moves"] == ("gap_p95_ms" if name == "step.device_decode_ms"
                          else "out_tokens_per_s")
    assert m["source"] == ("device_trace" if name in DEVICE_READERS
                           else "program_counter")
