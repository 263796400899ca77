"""``engine.ahead_share`` (PR 34) and what dispatching ahead does to the
spans the older readers join: on spans written by hand, and on spans
recorded here from a small engine on the CPU, as ``run.py`` records them
(``spans.normalise`` of the Tracer's events).  Times from the CPU are
not asserted, only their order."""
import numpy as np
import pytest

from harness import scopes, spans as S, spec


def _x(name, ts, dur, **args):
    return {"ph": "X", "name": name, "ts": ts, "dur": dur, "args": args}


def test_ahead_share_counts_the_windows_launches_that_say_so():
    read = spec.load_reader("engine.ahead_share")
    spans = [_x("engine.device_launch", 10, 5, step=1, ahead=False,
                reason="idle"),
             _x("engine.device_launch", 30, 5, step=2, ahead=True),
             _x("engine.device_launch", 50, 5, step=3, ahead=True),
             _x("engine.device_launch", 70, 5, step=4, ahead=True),
             _x("engine.device_launch", 90, 5, step=5, ahead=False,
                reason="pool"),
             _x("engine.dispatch", 28, 8, step=2, launched=True, ahead=True)]
    ctx = {"spans": spans, "t_open": 0, "t_close": 80}
    assert read(ctx) == pytest.approx(75.0)         # step 5 ends outside
    assert read(dict(ctx, t_open=20)) == pytest.approx(100.0)
    assert read(dict(ctx, t_close=200)) == pytest.approx(60.0)


def test_a_program_whose_launches_do_not_say_gives_nothing():
    """The parent of PR 34: ``engine.device_launch`` carries no
    ``ahead``; the reader returns None and does not raise."""
    read = spec.load_reader("engine.ahead_share")
    spans = [_x("engine.device_launch", 10, 5, step=1, bucket=32),
             _x("engine.device_launch", 30, 5, step=2, bucket=32)]
    assert read({"spans": spans, "t_open": 0, "t_close": 100}) is None
    assert read({"spans": [], "t_open": 0, "t_close": 100}) is None


@pytest.fixture(scope="module")
def recorded():
    """Spans of a small dense engine that served five requests, three of
    them arriving while the first two decode: chunks ride beside decode
    rows, and every launch but the first goes ahead."""
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.profiler import Tracer

    model = LlamaForCausalLM(LlamaConfig.tiny(
        vocab=97, hidden=32, layers=2, heads=4, ffn=64, seq=64))
    tracer = Tracer()
    eng = LLMEngine(model, max_num_seqs=4, block_size=8, max_model_len=64,
                    max_prefill_tokens=16, prefill_token_bucket=16,
                    tracer=tracer)
    rng = np.random.RandomState(3)
    for n in (5, 20):
        eng.add_request(rng.randint(0, 97, n).tolist(), max_new_tokens=8)
    for _ in range(3):
        eng.step()
    for n in (9, 30, 4):
        eng.add_request(rng.randint(0, 97, n).tolist(), max_new_tokens=6)
    eng.run()
    return eng.summary(), S.normalise(tracer.events())


def test_ahead_share_on_recorded_spans(recorded):
    summary, spans = recorded
    n = summary["launches"]
    assert summary["launches_ahead"] == n - 1 >= 10
    assert summary["ahead_fallbacks"] == {"idle": 1}
    read = spec.load_reader("engine.ahead_share")
    ctx = {"spans": spans, "t_open": 0, "t_close": 1 << 62}
    assert read(ctx) == pytest.approx(100.0 * (n - 1) / n)
    # a window that opens after the first launch holds ahead launches only
    first = min(s["ts"] + s["dur"]
                for s in S.named(spans, "engine.device_launch", "X"))
    assert read(dict(ctx, t_open=first + 1)) == pytest.approx(100.0)


def test_inflight_windows_do_not_overlap_and_still_join(recorded):
    """A launch queued behind another has its ``engine.device_inflight``
    from the moment the one before it was seen complete: the windows of
    consecutive launches tile, ``spans.launches`` still joins each to the
    dispatch that made it (sorted by start, the dispatch of launch n
    lies before the window of n and after the window of n - 1), and
    ``scopes.launch_periods`` reads the same boundaries."""
    summary, spans = recorded
    wins = sorted((s["args"]["step"], s["ts"], s["ts"] + s["dur"])
                  for s in S.named(spans, "engine.device_inflight", "X"))
    assert [w[0] for w in wins] == list(range(1, summary["launches"] + 1))
    for (_, _, end), (step, start, _) in zip(wins, wins[1:]):
        assert start >= end, step
    joined = S.launches(spans)
    args = scopes.launch_args(spans)
    assert len(joined) == len(wins)
    for rec, (step, start, end) in zip(joined, wins):
        assert (rec["ts"], rec["end"]) == (start, end)
        assert rec["chunks"] == args[step]["chunks"]
        assert rec["decode"] == args[step]["decode"]
    assert any(r["chunks"] and r["decode"] for r in joined)
    periods = scopes.launch_periods(spans, 0, 1 << 62)
    assert [p["step"] for p in periods] == [w[0] for w in wins[1:]]
    for p, (_, start, end) in zip(periods, wins[1:]):
        # the window opens once the launch in front was seen complete
        # (the end of its block_on_result) and closes with its own
        assert p["start"] <= start and p["end"] <= end
