"""A launch's tokens cross to their consumers in ONE hand-over (PR 45).

The engine hands what a commit emitted to ONE launch-level sink
(``add_request(sink=...)``); the runner takes it under ONE hold of its
lock (generation guard, journal append, finished marks, hand-over) and
crosses to an event loop ONCE a launch (``LoopDelivery.hand_over``); a
plain callable is still called an event at a time.  Counted in
``summary()``: ``deliver_handovers`` beside ``deliver_tokens`` and
``launches``."""
import asyncio
import http.client
import json
import queue
import threading
import time

import numpy as np
import pytest

from paddle_tpu.inference import LLMEngine
from paddle_tpu.inference.frontend import (EngineRunner, LoopDelivery,
                                           ReplicaRouter, build_replicas,
                                           serve_background)
from paddle_tpu.inference.frontend.metrics import render_metrics
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

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
    kw.setdefault("retain_outputs", False)
    return LLMEngine(model, **kw)


def _prompts(n, seed=3, new=14):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, VOCAB, [4, 9, 13, 21][i % 4]).tolist(), new)
            for i in range(n)]


def _direct(model, prompts, **engine_kw):
    """The uninterrupted run: one engine, no runner, per-request
    callbacks (the path a request without a sink keeps)."""
    eng = _engine(model, **engine_kw)
    toks = {i: [] for i in range(len(prompts))}
    outs = {}
    rids = {}
    for i, (p, n) in enumerate(prompts):
        rids[eng.add_request(
            p, max_new_tokens=n,
            on_token=lambda rid, t: toks[rids[rid]].append(t),
            on_finish=lambda o: outs.__setitem__(rids[o.rid], o))] = i
    while eng.has_unfinished():
        eng.step()
    for i in toks:
        assert toks[i] == outs[i].generated
    return [outs[i].generated for i in range(len(prompts))]


class _Loop:
    """Stands where an event loop stands: runs what crosses at once, on
    the calling (engine) thread, and keeps every crossing."""

    def __init__(self, probe=None):
        self.crossings = []           # [[(put, event), ...], ...]
        self.probe = probe            # called as a batch crosses
        self.probed = []

    def call_soon_threadsafe(self, fn, batch):
        self.crossings.append(list(batch))
        if self.probe is not None:
            self.probed.append(self.probe(batch))
        fn(batch)


class _Stream:
    def __init__(self):
        self.events = []
        self.done = threading.Event()

    def put(self, ev):
        self.events.append(ev)
        if ev[0] == "finish":
            self.done.set()

    @property
    def tokens(self):
        return [v for k, v in self.events if k == "token"]


def _submit_all(runner, loop, prompts):
    streams = [_Stream() for _ in prompts]
    for s, (p, n) in zip(streams, prompts):
        runner.submit(p, deliver=LoopDelivery(loop, s.put),
                      max_new_tokens=n)
    return streams


def _wait_all(streams, timeout=120.0):
    for s in streams:
        assert s.done.wait(timeout), "a stream never finished"


def _sse(port, prompt, n, out, i):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/completions",
                 body=json.dumps({"prompt": prompt, "max_tokens": n,
                                  "stream": True}).encode(),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    toks = []
    for frame in resp.read().split(b"\n\n"):
        data = frame.partition(b"data: ")[2]
        if data and data != b"[DONE]":
            tok = json.loads(data)["choices"][0]["token"]
            if tok is not None:
                toks.append(tok)
    conn.close()
    out[i] = (resp.status, toks)


# ---------------------------------------------------------------------------
# through the HTTP frontend: one hand-over a launch
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def http_run(model):
    """Four streaming clients through ``serve_background``; every
    crossing to the event loop is kept as the runner made it."""
    prompts = _prompts(4, new=20)
    crossings = []
    real = LoopDelivery.hand_over

    def spy(loop, batch):
        crossings.append(list(batch))
        real(loop, batch)

    eng = _engine(model)
    srv = serve_background(eng, model_name="tiny")
    LoopDelivery.hand_over = staticmethod(spy)
    try:
        # the first request compiles the programs the four then share
        warm: dict = {}
        _sse(srv.port, prompts[0][0], 2, warm, 0)
        assert warm[0][0] == 200
        crossings.clear()
        c0 = eng.summary()
        out: dict = {}
        ts = [threading.Thread(target=_sse, args=(srv.port, p, n, out, i))
              for i, (p, n) in enumerate(prompts)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
        c1 = eng.summary()
        metrics = render_metrics(eng.summary())
    finally:
        LoopDelivery.hand_over = staticmethod(real)
        srv.stop()
    return {"prompts": prompts, "out": out, "crossings": list(crossings),
            "c0": c0, "c1": c1, "metrics": metrics,
            "want": _direct(model, prompts)}


def test_handovers_rise_by_one_a_launch_and_not_by_one_a_token(http_run):
    c0, c1 = http_run["c0"], http_run["c1"]
    launches = c1["launches"] - c0["launches"]
    handovers = c1["deliver_handovers"] - c0["deliver_handovers"]
    tokens = c1["deliver_tokens"] - c0["deliver_tokens"]
    assert tokens == sum(n for _, n in http_run["prompts"])
    # every crossing the runner made is counted, and none but those
    assert handovers == len(http_run["crossings"])
    # one a launch that emitted: never more than the launches, and a
    # launch's rows share it
    assert 0 < handovers <= launches
    assert tokens > 2 * handovers
    # a crossing is ONE launch's: no stream has two tokens in it
    for batch in http_run["crossings"]:
        puts = [put.__self__ for put, ev in batch if ev[0] == "token"]
        assert len(puts) == len({id(q) for q in puts})


def test_streams_equal_the_unstreamed_output_in_order(http_run):
    for i, want in enumerate(http_run["want"]):
        status, toks = http_run["out"][i]
        assert status == 200
        assert toks == want


def test_a_finish_follows_its_last_token_in_the_same_handover(http_run):
    finishes = 0
    for batch in http_run["crossings"]:
        for j, (put, ev) in enumerate(batch):
            if ev[0] != "finish":
                continue
            finishes += 1
            mine = [e for p, e in batch[:j] if p.__self__ is put.__self__]
            assert mine and mine[-1] == ("token", ev[1].generated[-1])
            # and nothing of that stream after its finish
            assert not [e for p, e in batch[j + 1:]
                        if p.__self__ is put.__self__]
    assert finishes == len(http_run["prompts"])


def test_the_counters_are_exported(http_run):
    text = http_run["metrics"]
    for name in ("paddle_tpu_engine_deliver_handovers_total",
                 "paddle_tpu_engine_deliver_tokens_total"):
        line = [ln for ln in text.splitlines()
                if ln.startswith(name + " ") or ln.startswith(name + "{")]
        assert line, name
        assert float(line[0].rsplit(" ", 1)[1]) > 0


# ---------------------------------------------------------------------------
# consumers that hand in a plain callable: an event at a time, as before
# ---------------------------------------------------------------------------

def _collect(q, timeout=120.0):
    toks = []
    while True:
        kind, val = q.get(timeout=timeout)
        if kind == "finish":
            return toks, val
        toks.append(val)


def test_a_plain_callable_gets_an_event_at_a_time(model, monkeypatch):
    prompts = _prompts(4)
    want = _direct(model, prompts)
    crossed = []
    monkeypatch.setattr(LoopDelivery, "hand_over",
                        staticmethod(lambda loop, b: crossed.append(b)))
    eng = _engine(model)
    runner = EngineRunner(eng).start()
    try:
        qs = [queue.Queue() for _ in prompts]
        held = []
        for q, (p, n) in zip(qs, prompts):
            def deliver(ev, q=q):
                held.append(runner._lock.locked())
                q.put_nowait(ev)
            runner.submit(p, deliver=deliver if q is qs[0]
                          else q.put_nowait, max_new_tokens=n)
        got = [_collect(q) for q in qs]
    finally:
        assert runner.drain(timeout_s=60.0)
    for (toks, out), w in zip(got, want):
        assert toks == w == out.generated
    assert not crossed                   # nothing went by a loop
    # each under the hold of the lock that journaled it
    assert len(held) == len(want[0]) + 1 and all(held)
    s = eng.summary()
    n_tok = sum(len(w) for w in want)
    assert s["deliver_tokens"] == n_tok
    assert s["deliver_handovers"] == n_tok + len(prompts)   # + finishes


@pytest.mark.parametrize("consumer", ["callable", "loop_delivery"])
def test_the_routers_wrapper_still_settles_outstanding(model, consumer):
    """The router wraps whatever it is handed in a plain callable: an
    event at a time, the finish settles the replica's credit."""
    prompts = _prompts(6)
    want = _direct(model, prompts)

    def factory(replica=0):
        return _engine(model)

    router = ReplicaRouter(build_replicas(factory(), factory, 2),
                           policy="least").start()
    loop = _Loop()
    streams = [_Stream() for _ in prompts]
    try:
        for s, (p, n) in zip(streams, prompts):
            deliver = s.put if consumer == "callable" \
                else LoopDelivery(loop, s.put)
            router.submit(p, deliver=deliver, max_new_tokens=n)
        assert max(router.router_counters()["outstanding_tokens"]) > 0
        _wait_all(streams)
        assert router.router_counters()["outstanding_tokens"] == [0, 0]
    finally:
        assert router.drain(timeout_s=60.0)
    for s, w in zip(streams, want):
        assert s.tokens == w
        assert s.events[-1][0] == "finish"
    # through the wrapper even a LoopDelivery is served event by event
    assert all(len(b) == 1 for b in loop.crossings)
    events = sum(len(s.events) for s in streams)
    if consumer == "loop_delivery":
        assert len(loop.crossings) == events
    assert router.stats_snapshot()["deliver_handovers"] == events


# ---------------------------------------------------------------------------
# a loop torn down, and events made outside a step
# ---------------------------------------------------------------------------

def test_a_stopped_loop_does_not_kill_the_engine_thread(model):
    dead = asyncio.new_event_loop()
    dead.close()
    with pytest.raises(RuntimeError):
        dead.call_soon_threadsafe(print)
    eng = _engine(model)
    runner = EngineRunner(eng).start()
    try:
        lost = _Stream()
        # a launch's hand-over, then (the abort) an event at once
        rid = runner.submit([3, 1, 4, 1, 5], max_new_tokens=30,
                            deliver=LoopDelivery(dead, lost.put))
        runner.submit([2, 7, 1, 8], max_new_tokens=6,
                      deliver=LoopDelivery(dead, lost.put))
        deadline = time.monotonic() + 60
        while eng.stats.deliver_tokens < 8:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        runner.abort(rid)
        q = queue.Queue()
        runner.submit([3, 1, 4, 1, 5], deliver=q.put_nowait,
                      max_new_tokens=5)
        toks, out = _collect(q)
        assert toks == out.generated and len(toks) == 5
        assert runner._thread.is_alive()
        assert not lost.events
    finally:
        assert runner.drain(timeout_s=60.0)
    assert runner.restarts == 0
    assert eng.blocks.num_used == 0


@pytest.mark.parametrize("how", ["abort", "deadline", "abort_queued",
                                 "failed_admission"])
def test_an_event_made_between_steps_is_handed_over_at_once(model, how):
    """A terminal event that no commit made crosses alone, when it is
    made: nothing waits for a launch to ride on."""
    eng = _engine(model)
    runner = EngineRunner(eng)
    loop, s = _Loop(), _Stream()
    deliver = LoopDelivery(loop, s.put)
    try:
        if how == "abort_queued":
            rid = runner.submit([5, 6, 7], deliver=deliver,
                                max_new_tokens=8)
            runner.abort(rid, reason="aborted")
            runner.start()
        elif how == "failed_admission":
            runner.start()
            runner.submit(list(range(60)), deliver=deliver,
                          max_new_tokens=30)        # over max_model_len
        else:
            runner.start()
            rid = runner.submit(
                [5, 6, 7], deliver=deliver, max_new_tokens=50,
                deadline_s=None if how == "abort" else 600.0)
            deadline = time.monotonic() + 60
            while len(s.tokens) < 3:
                assert time.monotonic() < deadline
                time.sleep(0.002)
            if how == "abort":
                runner.abort(rid)
            else:
                with runner._lock:      # the deadline passes
                    runner._handles[rid].deadline = time.monotonic() - 1
        assert s.done.wait(60.0)
    finally:
        assert runner.drain(timeout_s=60.0)
    last = loop.crossings[-1]
    assert len(last) == 1 and last[0][1][0] == "finish"
    out = last[0][1][1]
    want = {"abort": "aborted", "deadline": "deadline",
            "abort_queued": "aborted"}.get(how)
    if want is None:
        assert out.finish_reason.startswith("error: ValueError")
    else:
        assert out.finish_reason == want
    if how in ("abort", "deadline"):
        # what the client saw is what the output reports
        assert s.tokens == out.generated
        assert 3 <= len(s.tokens) < 50
    else:
        assert s.events == [("finish", out)]
    assert eng.summary()["deliver_handovers"] == len(loop.crossings)
    assert eng.blocks.num_used == 0


# ---------------------------------------------------------------------------
# rows that emit several tokens a launch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine_kw", [
    {"drafter": "ngram", "spec_k": 4}, {"decode_window": 4}],
    ids=["speculative", "decode_window"])
def test_a_rows_several_tokens_arrive_in_order(model, engine_kw):
    rng = np.random.RandomState(5)
    prompts = []
    for i in range(4):
        motif = rng.randint(0, VOCAB, 3).tolist()
        prompts.append(((motif * 8)[:[6, 9, 12, 15][i]], 16))
    want = _direct(model, prompts)       # one token a row a launch
    eng = _engine(model, **engine_kw)
    runner = EngineRunner(eng).start()
    loop = _Loop()
    try:
        streams = _submit_all(runner, loop, prompts)
        _wait_all(streams)
    finally:
        assert runner.drain(timeout_s=60.0)
    for s, w in zip(streams, want):
        assert s.tokens == w
        assert s.events[-1][0] == "finish" and len(s.events) == len(w) + 1
    # some crossing carried several tokens of one stream, and still
    # every launch crossed once
    most = max(sum(1 for p, e in batch
                   if p.__self__ is s and e[0] == "token")
               for batch in loop.crossings for s in streams)
    assert most >= 2
    s = eng.summary()
    assert s["deliver_handovers"] == len(loop.crossings) <= s["launches"]
    assert s["deliver_tokens"] == sum(len(w) for w in want)


# ---------------------------------------------------------------------------
# recovery: guard + journal + hand-over are ONE hold of the lock
# ---------------------------------------------------------------------------

def _journal_is_what_clients_saw(runner, streams_by_id):
    with runner._lock:
        for rid, h in runner._handles.items():
            assert h.emitted == streams_by_id[rid].tokens


def test_a_crash_right_after_a_launchs_handover_loses_and_repeats_nothing(
        model):
    """The engine dies between the commit of a launch and the next: the
    journal holds exactly what the clients saw, and the replacement
    continues every stream where it stood."""
    prompts = _prompts(6, new=12)
    want = _direct(model, prompts)
    eng = _engine(model)
    runner = EngineRunner(eng, engine_factory=lambda: _engine(model))
    real = runner._take_launch
    calls = []

    def crashing(gen, launch):
        real(gen, launch)
        calls.append(gen)
        if len(calls) == 4:
            raise RuntimeError("dies after the fourth launch's hand-over")

    runner._take_launch = crashing

    def probe(batch):
        # as a launch crosses: the lock that journaled it is still
        # held, and the journal already holds every token of the batch
        journals = {id(h.deliver.put.__self__): h.emitted
                    for h in list(runner._handles.values())}
        seen = {}
        for put, ev in batch:
            if ev[0] == "token":
                s = put.__self__
                seen[id(s)] = seen.get(id(s), s.tokens) + [ev[1]]
        return runner._lock.locked() and all(
            journals[k] == v for k, v in seen.items() if k in journals)

    loop = _Loop(probe)
    try:
        streams = _submit_all(runner.start(), loop, prompts)
        _wait_all(streams)
    finally:
        assert runner.drain(timeout_s=120.0)
    assert runner.restarts == 1 and runner.engine is not eng
    assert len(loop.probed) == len(loop.crossings) and all(loop.probed)
    assert calls[:4] == [0] * 4 and set(calls[4:]) == {1}
    for s, w in zip(streams, want):
        assert s.tokens == w
        assert [k for k, _ in s.events].count("finish") == 1
    assert runner.engine.blocks.num_used == 0


def test_a_zombies_late_launch_is_dropped_whole(model):
    """A step hangs with a launch committed and not yet handed over; the
    watchdog takes over by a generation bump; the replacement emits
    those tokens itself.  When the zombie wakes and hands its launch
    over, all of it is dropped: no client sees a token twice."""
    prompts = _prompts(4, new=12)
    want = _direct(model, prompts)
    eng = _engine(model)
    runner = EngineRunner(eng, engine_factory=lambda: _engine(model),
                          step_deadline_s=10.0)
    release, hung = threading.Event(), threading.Event()
    real_hand_over = eng._hand_over
    n = [0]

    def hanging():
        n[0] += 1
        if n[0] == 3:
            hung.set()
            release.wait(120.0)
        real_hand_over()

    eng._hand_over = hanging
    taken = []
    real_take = runner._take_launch

    def spy(gen, launch):
        taken.append((gen, runner._gen, sum(len(t) for _, t, _ in launch)))
        real_take(gen, launch)

    runner._take_launch = spy
    loop = _Loop()
    try:
        streams = _submit_all(runner.start(), loop, prompts)
        assert hung.wait(60.0)
        by_id = {}
        with runner._lock:
            for h in runner._handles.values():
                by_id[h.request_id] = next(
                    s for s in streams if h.deliver.put.__self__ is s)
        # hung between commit and hand-over: journal == what was seen
        _journal_is_what_clients_saw(runner, by_id)
        _wait_all(streams)               # the replacement serves them
        seen = [list(s.events) for s in streams]
        crossings = len(loop.crossings)
        release.set()                    # the zombie wakes
        deadline = time.monotonic() + 60
        while not any(g == 0 and now >= 1 for g, now, _ in taken):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        time.sleep(0.05)
    finally:
        release.set()
        assert runner.drain(timeout_s=120.0)
    assert runner.restarts >= 1 and runner.engine is not eng
    late = [t for t in taken if t[0] == 0 and t[1] >= 1]
    assert late and late[0][2] >= 1      # it held tokens, and they went
    assert len(loop.crossings) == crossings
    assert [list(s.events) for s in streams] == seen
    for s, w in zip(streams, want):
        assert s.tokens == w
        assert [k for k, _ in s.events].count("finish") == 1
    assert runner.engine.blocks.num_used == 0
