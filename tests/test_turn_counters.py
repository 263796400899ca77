"""The turn and the step, each read where it happens (PR 37).

Always-on counters in ``ServingStats`` (``turn_time_s``,
``launch_call_time_s``, ``commit_time_s``, ``launch_arg_bytes``), their
split as attributes on the two spans that were there
(``engine.device_launch``: ``call_ns``, ``arg_bytes``;
``engine.sample_commit``: ``rows``, ``notify_ns``, ``cache_ns``,
``retire_ns``), and what a launch leaves in the ring.  Times from the
CPU are compared with each other only, never with a number."""
import http.client
import json
import threading
import time

import numpy as np
import pytest

from paddle_tpu.inference import LLMEngine
from paddle_tpu.inference.frontend import serve_background
from paddle_tpu.inference.frontend.metrics import render_metrics
from paddle_tpu.inference.sampling import make_samp
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler import Tracer

VOCAB = 97
CFG = LlamaConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=4, ffn=64,
                       seq=64)
KEYS = ("turn_time_s", "launch_call_time_s", "commit_time_s",
        "launch_arg_bytes")


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM(CFG)


def _engine(model, **kw):
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("max_prefill_tokens", 16)
    kw.setdefault("prefill_token_bucket", 16)
    return LLMEngine(model, **kw)


def _requests(eng, seed=5, lens=(20, 7, 9), new=6):
    rng = np.random.RandomState(seed)
    for n in lens:
        eng.add_request(rng.randint(0, VOCAB, n).tolist(),
                        max_new_tokens=new)


def _spans(tr):
    return [{"ph": ph, "name": name, "ts": ts, "dur": dur, "tid": tid,
             "args": args or {}}
            for ph, name, ts, dur, tid, args, _id in tr.events()]


# ---------------------------------------------------------------------------
# the counters
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("overlap", [True, False])
def test_counters_rise_and_the_turn_holds_the_call_and_the_commit(
        model, overlap):
    eng = _engine(model, overlap=overlap)
    _requests(eng)
    before = eng.summary()
    assert all(before[k] == 0 for k in KEYS)
    seen = []
    while eng.has_unfinished():
        eng.step()
        s = eng.stats
        seen.append((s.turn_ns, s.launch_call_ns, s.commit_ns,
                     s.launch_arg_bytes, s.block_time))
        # the call and the commit are parts of some turn already counted
        assert s.turn_ns >= s.launch_call_ns + s.commit_ns
    for a, b in zip(seen, seen[1:]):
        assert all(y >= x for x, y in zip(a, b))
    after = eng.summary()
    assert all(isinstance(after[k], (int, float)) and after[k] > 0
               for k in KEYS)
    assert after["turn_time_s"] >= after["launch_call_time_s"] \
        + after["commit_time_s"] - 2e-6           # each rounded to 1 us
    # (under overlap the last call only commits the launch in flight)
    assert after["launches"] == len(seen) - int(overlap)
    # what the thread waited on the chip is no part of its turn: the two
    # together are the wall time of the step() calls
    assert after["block_time_s"] > 0


def test_the_turn_leaves_out_the_wait_on_the_chip(model, monkeypatch):
    """A launch whose result takes long to come is a long block and no
    longer a turn: ``turn_time_s`` is wall time less ``_complete``'s
    blocking read."""
    import paddle_tpu.inference.serving as serving

    eng = _engine(model, overlap=False)
    _requests(eng, lens=(6,), new=3)
    eng.run()                                   # compiles
    real = np.asarray
    reads = []

    def slow(x, *a, **k):
        if hasattr(x, "is_ready"):              # a device array
            reads.append(x.shape)
        if not isinstance(x, np.ndarray):
            time.sleep(0.02)                    # the device "still runs"
        return real(x, *a, **k)

    monkeypatch.setattr(serving.np, "asarray", slow)
    s = eng.stats
    t0, b0, n0 = s.turn_ns, s.block_time, eng.launches
    _requests(eng, seed=6, lens=(6,), new=4)
    wall0 = time.perf_counter_ns()
    eng.run()
    wall = time.perf_counter_ns() - wall0
    monkeypatch.undo()
    n = eng.launches - n0
    assert n >= 4
    blocked = s.block_time - b0
    # one read a launch since PR 38: tokens and flags in one vector
    assert reads == [(2 * eng._Lq,)] * n
    assert blocked >= 0.02 * n * 0.9
    assert (s.turn_ns - t0) / 1e9 + blocked <= wall / 1e9 + 1e-3
    assert (s.turn_ns - t0) / 1e9 < blocked / 2


def test_arg_bytes_a_launch_are_the_nbytes_of_its_host_arrays(model):
    """A pure-decode launch at the decode bucket hands the call toks
    [B], cu [B+1], kvl [B], bt [B+1, nblk], lidx [Lq], samp (one row a
    logit row) and src [B]; ``prev`` is on the device and is not
    counted."""
    eng = _engine(model)
    _requests(eng, lens=(5, 6), new=8)
    while eng.has_unfinished():           # past the prefill launches
        eng.step()
        if eng.launches >= 3:
            break
    b0, n0 = eng.stats.launch_arg_bytes, eng.launches
    while eng.has_unfinished() and eng.launches - n0 < 3:
        eng.step()
    n = eng.launches - n0
    assert n == 3
    B, nblk, Lq = eng.max_num_seqs, eng.nblk, eng._Lq
    i32 = 4
    one = (B * i32 + (B + 1) * i32 + B * i32 + (B + 1) * nblk * i32
           + Lq * i32 + B * i32
           + sum(v.nbytes for v in make_samp(Lq, VOCAB).values()))
    assert (eng.stats.launch_arg_bytes - b0) / n == one
    assert eng.summary()["launch_arg_bytes"] == eng.stats.launch_arg_bytes


def test_the_counters_reach_the_metrics_endpoint(model):
    eng = _engine(model)
    _requests(eng)
    eng.run()
    text = render_metrics(eng.summary())
    for name in ("engine_turn_seconds_total",
                 "engine_launch_call_seconds_total",
                 "engine_commit_seconds_total",
                 "engine_launch_arg_bytes_total",
                 "step_block_seconds_total"):
        line = [ln for ln in text.splitlines()
                if ln.startswith("paddle_tpu_" + name + " ")]
        assert line, name
        assert float(line[0].split()[-1]) > 0


# ---------------------------------------------------------------------------
# the split, on the spans that were there
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("caching", [True, False])
def test_the_two_spans_carry_the_split(model, caching):
    tr = Tracer()
    eng = _engine(model, tracer=tr, enable_prefix_caching=caching)
    _requests(eng)
    eng.run()
    sp = _spans(tr)
    dl = [s for s in sp if s["name"] == "engine.device_launch"]
    assert len(dl) == eng.launches
    for s in dl:
        a = s["args"]
        assert 0 < a["call_ns"] <= s["dur"]
        assert a["arg_bytes"] > 0
    assert sum(s["args"]["arg_bytes"] for s in dl) \
        == eng.stats.launch_arg_bytes
    assert sum(s["args"]["call_ns"] for s in dl) \
        == eng.stats.launch_call_ns
    sc = [s for s in sp if s["name"] == "engine.sample_commit"]
    assert len(sc) == eng.launches
    launched = {s["args"]["step"]: s["args"] for s in dl}
    for s in sc:
        a = s["args"]
        assert {"rows", "notify_ns", "cache_ns", "retire_ns"} <= set(a)
        assert a["rows"] == launched[a["step"]]["rows"] > 0
        assert a["notify_ns"] + a["cache_ns"] + a["retire_ns"] <= s["dur"]
        assert (a["cache_ns"] > 0) == caching
        if launched[a["step"]]["decode"]:      # a row that emits is asked
            assert a["retire_ns"] > 0 and a["notify_ns"] > 0
    # the retire span, where a row retired, lies inside retire_ns
    for r in (s for s in sp if s["name"] == "engine.retire"):
        host = next(s for s in sc
                    if s["args"]["step"] == r["args"]["step"])
        assert r["dur"] <= host["args"]["retire_ns"]


def test_without_a_tracer_the_rows_call_the_bare_methods(model):
    """No tracer: no wrapper is made, no args dict is built, and the
    commit's rows call what they always called."""
    eng = _engine(model)
    _requests(eng)
    eng.run()
    assert eng._launch_call == {} and eng._split is None
    calls = eng._row_calls()
    assert calls == (eng._notify_tokens, eng._maybe_retire,
                     eng.blocks.commit_prefill,
                     eng.blocks.commit_decode_token)
    split = eng._split = [0, 0, 0]
    timed = eng._row_calls()
    assert all(t is not c for t, c in zip(timed, calls))
    got = []
    eng.add_request([1, 2, 3], max_new_tokens=2,
                    on_token=lambda rid, t: got.append(t))
    req = eng._waiting[0]
    timed[0](req, (7,))
    assert got == [7] and split[0] > 0 and split[1:] == [0, 0]


# ---------------------------------------------------------------------------
# what a launch leaves in the ring
# ---------------------------------------------------------------------------

ENGINE_SPANS_A_LAUNCH = {
    "engine.step", "engine.dispatch", "engine.schedule",
    "engine.device_launch", "engine.device_inflight",
    "engine.block_on_result", "engine.sample_commit", "engine.complete"}


def _stream(port, prompt, n, out):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/completions",
                 body=json.dumps({"prompt": prompt, "max_tokens": n,
                                  "stream": True}).encode(),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    out.append((resp.status, resp.read().count(b"data: ")))
    conn.close()


def test_a_pure_decode_launch_of_n_rows_leaves_9_plus_n_events(model):
    """Through the HTTP frontend, streaming: a launch of n decode rows
    and no chunk that retires nobody leaves its eight engine spans, the
    runner's turn between two steps and one ``runner.deliver`` a row;
    the HTTP tier writes one event a REQUEST and none a token."""
    tr = Tracer()
    eng = _engine(model, retain_outputs=False)
    eng.set_tracer(tr)
    srv = serve_background(eng, model_name="tiny")
    n_req, n_tok = 3, 12
    out: list = []
    try:
        rng = np.random.RandomState(9)
        ts = [threading.Thread(
            target=_stream,
            args=(srv.port, rng.randint(0, VOCAB, 6).tolist(), n_tok, out))
            for _ in range(n_req)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
    finally:
        srv.stop()
    assert [s for s, _ in out] == [200] * n_req
    sp = _spans(tr)
    tracks = {}
    for s in sp:
        tracks.setdefault(s["tid"], set()).add(s["name"])
    by_first = {next(iter(sorted(v))).split(".")[0]: v
                for v in tracks.values()}
    assert by_first["http"] == {"http.request"}
    assert sum(s["name"] == "http.request" for s in sp) == n_req
    assert by_first["runner"] <= {"runner.deliver", "runner.between_steps"}
    assert sum(s["name"] == "runner.deliver" for s in sp) == n_req * n_tok
    commits = {s["args"]["step"]: s for s in sp
               if s["name"] == "engine.sample_commit"}
    checked = 0
    for dl in (s for s in sp if s["name"] == "engine.device_launch"):
        a = dl["args"]
        c = commits.get(a["step"])
        if a["chunks"] or not a["decode"] or c is None \
                or c["args"]["finished"]:
            continue
        n = a["decode"]
        mine = [s for s in sp if s["args"].get("step") == a["step"]
                and s["name"].startswith("engine.")]
        if any(s["name"] == "engine.program_built" for s in mine):
            continue                      # once a bucket, not a launch's
        assert {s["name"] for s in mine} == ENGINE_SPANS_A_LAUNCH
        assert len(mine) == 8
        delivered = [s for s in sp if s["name"] == "runner.deliver"
                     and c["ts"] <= s["ts"] <= c["ts"] + c["dur"]]
        assert len(delivered) == n
        turn = [s for s in sp if s["name"] == "runner.between_steps"
                and s["args"]["step"] == a["step"]]
        assert len(mine) + len(delivered) + len(turn) <= 9 + n
        checked += 1
    assert checked >= 3


def _notify_ns_a_launch(model, n):
    """Median ``notify_ns`` of the pure-decode commits of n rows that
    retire nobody, n streaming clients through the HTTP frontend."""
    tr = Tracer()
    eng = _engine(model, retain_outputs=False, max_num_seqs=32,
                  max_prefill_tokens=256, prefill_token_bucket=256)
    eng.set_tracer(tr)
    srv = serve_background(eng, model_name="tiny", max_pending=128)
    out: list = []
    try:
        rng = np.random.RandomState(n)
        ts = [threading.Thread(
            target=_stream,
            args=(srv.port, rng.randint(0, VOCAB, 6).tolist(), 40, out))
            for _ in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
    finally:
        srv.stop()
    assert [s for s, _ in out] == [200] * n
    launches = eng.summary()["launches"]
    assert eng.summary()["deliver_handovers"] <= launches
    v = sorted(s["args"]["notify_ns"] for s in _spans(tr)
               if s["name"] == "engine.sample_commit"
               and s["args"]["rows"] == n and not s["args"]["finished"])
    assert len(v) >= 10
    return v[len(v) // 2]


def test_notify_ns_does_not_grow_by_a_handover_a_row(model):
    """A launch's tokens cross to the event loop in ONE hand-over, so
    eight times the rows cost the commit's delivery well under eight
    times the time: the crossing is paid once, a row adds its journal
    entry and its Tracer instant.  (A crossing a row read 14 to 21 times
    here; one a launch 2.7 to 3.5.)  Ratio, not value; the better of two
    tries, since other processes share this machine's cores."""
    ratios = []
    for _ in range(2):
        ratios.append(_notify_ns_a_launch(model, 32)
                      / _notify_ns_a_launch(model, 4))
        if ratios[-1] < 6.0:
            break
    assert min(ratios) < 6.0, ratios
