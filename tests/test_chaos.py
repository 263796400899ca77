"""Seeded chaos harness: fault injection, supervised recovery,
quarantine, and graceful degradation.

The contract under test (CPU, tiny model, paged kernel in interpret
mode):

- a FaultPlan is deterministic: one seed -> one schedule, every fault
  fires exactly once, unconsumed faults stay armed across engine
  rebuilds;
- a NaN-poisoned logit row retires ONLY the offending sequence
  (finish_reason="numerical_error"); its batchmates stay byte-identical
  to the fault-free run and the pool stays clean;
- continuation replay (add_request(generated=...)) is byte-identical to
  the uninterrupted run, greedy and sampled, so the runner's journal
  replay reproduces exactly what the client already saw;
- the acceptance scenario: a seeded plan with a step crash, a hung step
  (watchdog), a NaN row, and a pool-exhaustion window over a 32-request
  mixed stream -> engine_restarts >= 1, every non-faulted output
  byte-identical to the fault-free baseline, zero leaked pages, and the
  rebuilt engine's compile budget EXACTLY the baseline's;
- the DegradationController engages cheaper levers (spec shrink, then
  admission pause) BEFORE any preemption, recovers tier by tier with
  hysteresis once pressure clears, and estimates Retry-After from the
  live free-page trend.
"""
import http.client
import json
import queue
import time

import numpy as np
import pytest

from paddle_tpu.inference import LLMEngine
from paddle_tpu.inference.faults import FaultPlan
from paddle_tpu.inference.kv_cache import BlockManager
from paddle_tpu.inference.pressure import (ADMIT_PAUSE, EVICT_PARKED,
                                           NORMAL, SPEC_SHRINK,
                                           DegradationController)
from paddle_tpu.inference.frontend import EngineRunner, serve_background
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

VOCAB = 97
CFG = LlamaConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=4, ffn=64,
                       seq=64)


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM(CFG)


def _engine(model, **kw):
    # prefill_token_bucket above max_prefill_tokens + max_num_seqs pins
    # the whole suite to exactly TWO ragged buckets (mixed -> 128,
    # pure-decode -> 8): the compile-budget assertion is exact, not
    # approximate.
    kw.setdefault("max_num_seqs", 8)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("max_prefill_tokens", 64)
    kw.setdefault("prefill_token_bucket", 128)
    kw.setdefault("retain_outputs", False)
    return LLMEngine(model, **kw)


def _requests(n, seed=7):
    """A mixed stream: ragged prompt lengths, a few sampled requests."""
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        r = {"prompt": rng.randint(0, VOCAB,
                                   [4, 9, 13, 20][i % 4]).tolist(),
             "max_new_tokens": int(rng.randint(4, 13)),
             "temperature": 0.0, "seed": 0}
        if i % 4 == 3:      # sampled rows prove PRNG keys survive replay
            r["temperature"] = 0.8
            r["seed"] = i
        reqs.append(r)
    return reqs


def _run_direct(model, reqs, **engine_kw):
    """Fault-free oracle: one engine, no runner, step to completion."""
    eng = _engine(model, **engine_kw)
    outs = {}
    for i, r in enumerate(reqs):
        eng.add_request(r["prompt"], max_new_tokens=r["max_new_tokens"],
                        temperature=r["temperature"], seed=r["seed"],
                        on_finish=lambda o, i=i: outs.__setitem__(i, o))
    while eng.has_unfinished():
        eng.step()
    assert len(outs) == len(reqs)
    return eng, outs


def _collect(q, timeout=300.0):
    toks = []
    while True:
        kind, val = q.get(timeout=timeout)
        if kind == "finish":
            return toks, val
        toks.append(val)


def _wait(pred, timeout_s=60.0, interval_s=0.01):
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > timeout_s:
            return False
        time.sleep(interval_s)
    return True


# ---------------------------------------------------------------------------
# FaultPlan: deterministic schedule semantics
# ---------------------------------------------------------------------------

def test_fault_plan_consumes_each_fault_once():
    plan = FaultPlan(seed=5, crash_steps=(3,), slow_steps={4: 0.5},
                     nan_steps=(5,), pool_window=(6, 7))
    fired = {"crash": 0, "slow": 0.0, "nan": [], "pool": 0}
    for _ in range(10):
        plan.advance()
        if plan.take_pool_entry():
            fired["pool"] += 1
        fired["slow"] += plan.take_slow()
        if plan.take_crash():
            fired["crash"] += 1
            assert plan.step == 3
        # a no-launch step (n_rows=0) must NOT consume an armed NaN
        assert plan.take_nan_row(0) is None
        row = plan.take_nan_row(4)
        if row is not None:
            fired["nan"].append((plan.step, row))
            assert 0 <= row < 4
    assert fired["crash"] == 1
    assert fired["slow"] == 0.5
    assert [s for s, _ in fired["nan"]] == [5]
    assert fired["pool"] == 1
    assert not plan.pool_exhausted()          # window closed
    assert plan.exhausted()


def test_fault_plan_armed_fault_survives_skipped_steps():
    # a crash scheduled at step 3 still fires when the counter jumps
    # straight past it (the restart-skipped-steps case)
    plan = FaultPlan(crash_steps=(3,))
    for _ in range(7):
        plan.advance()
    assert plan.take_crash()
    assert not plan.take_crash()


def test_fault_plan_seeded_is_deterministic():
    a = FaultPlan.seeded(123, n_conn_drop=2, n_requests=8)
    b = FaultPlan.seeded(123, n_conn_drop=2, n_requests=8)
    assert repr(a) == repr(b)
    assert a._conn_drop == b._conn_drop
    # steps 0/1 stay clean for first compiles
    assert all(s >= 2 for s in a._crash + a._nan)
    assert all(s >= 2 for s, _ in a._slow)
    assert a.pool_window[0] >= 2
    assert repr(a) != repr(FaultPlan.seeded(124, n_conn_drop=2,
                                            n_requests=8))


# ---------------------------------------------------------------------------
# quarantine: one poisoned row retires, batchmates unharmed
# ---------------------------------------------------------------------------

def test_quarantine_retires_only_poisoned_row(model):
    reqs = _requests(3, seed=11)
    _, base = _run_direct(model, reqs)

    eng = _engine(model, fault_plan=FaultPlan(seed=2, nan_steps=(3,)))
    outs = {}
    for i, r in enumerate(reqs):
        eng.add_request(r["prompt"], max_new_tokens=r["max_new_tokens"],
                        temperature=r["temperature"], seed=r["seed"],
                        on_finish=lambda o, i=i: outs.__setitem__(i, o))
    while eng.has_unfinished():
        eng.step()

    bad = [i for i, o in outs.items()
           if o.finish_reason == "numerical_error"]
    assert len(bad) == 1
    assert eng.stats.quarantined == 1
    assert eng.stats.fault_injections.get("nan") == 1
    for i, o in outs.items():
        if i in bad:
            continue
        assert o.generated == base[i].generated
        assert o.finish_reason == base[i].finish_reason
    # the poisoned sequence's pages left through release(): pool clean,
    # nothing corrupt parked in the prefix cache
    assert eng.blocks.num_used == 0
    eng.blocks.check_invariants()
    assert eng.stats.snapshot()["quarantined"] == 1


# ---------------------------------------------------------------------------
# continuation replay: the journal re-admission is byte-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("temperature,seed", [(0.0, 0), (0.9, 7)])
def test_continuation_replay_matches_uninterrupted(model, temperature,
                                                   seed):
    rng = np.random.RandomState(17)
    prompt = rng.randint(0, VOCAB, 10).tolist()
    _, full = _run_direct(model, [{"prompt": prompt, "max_new_tokens": 12,
                                   "temperature": temperature,
                                   "seed": seed}])
    full = full[0].generated
    assert len(full) == 12

    for split in (1, 5, 11):
        eng = _engine(model)
        new_tokens = []
        out = {}
        eng.add_request(prompt, max_new_tokens=12,
                        temperature=temperature, seed=seed,
                        generated=full[:split],
                        on_token=lambda rid, t: new_tokens.append(t),
                        on_finish=lambda o: out.setdefault("o", o))
        while eng.has_unfinished():
            eng.step()
        # the terminal output spans the whole request; the stream only
        # re-emits tokens the journal did NOT already deliver
        assert out["o"].generated == full
        assert new_tokens == full[split:]
        assert eng.blocks.num_used == 0


def test_continuation_already_at_cap_rejected(model):
    eng = _engine(model)
    with pytest.raises(ValueError):
        eng.add_request([1, 2, 3], max_new_tokens=4, generated=[5, 6, 7, 8])


# ---------------------------------------------------------------------------
# parked-page eviction (the EVICT_PARKED lever)
# ---------------------------------------------------------------------------

def test_evict_parked_frees_cached_pages():
    bm = BlockManager(17, 8, enable_prefix_caching=True)
    for s in range(3):
        toks = list(range(s * 100, s * 100 + 16))    # 2 full pages each
        assert bm.acquire(f"seq{s}", toks) is not None
        bm.commit_prefill(f"seq{s}", 16)    # KV written -> pages parkable
        bm.free(f"seq{s}")
    assert bm.num_cached == 6 and bm.num_used == 0
    free0 = bm.num_free
    assert bm.evict_parked(4) == 4
    assert bm.num_cached == 2
    assert bm.num_free == free0 + 4
    assert bm.parked_evicted == 4
    bm.check_invariants()
    # asking past the parked supply evicts what exists, no more
    assert bm.evict_parked(10) == 2
    assert bm.num_cached == 0
    assert bm.parked_evicted == 6
    bm.check_invariants()


# ---------------------------------------------------------------------------
# degradation controller: tier mechanics on a stub pool
# ---------------------------------------------------------------------------

class _StubBlocks:
    def __init__(self, total, free):
        self.num_blocks = total + 1      # slot 0 is the null block
        self.num_free = free


def test_degradation_controller_tiers_and_retry_after():
    ctrl = DegradationController(cooldown_steps=2, evict_batch=3)
    assert ctrl.update(_StubBlocks(100, 90)) == NORMAL
    # spike straight past two entry thresholds -> deepest matching tier
    assert ctrl.update(_StubBlocks(100, 9)) == EVICT_PARKED
    assert ctrl.evict_now and ctrl.admission_paused
    assert ctrl.spec_k_cap(8) == 0
    # one calm step is NOT enough (hysteresis)
    assert ctrl.update(_StubBlocks(100, 50)) == EVICT_PARKED
    assert ctrl.update(_StubBlocks(100, 50)) == ADMIT_PAUSE
    # a dip below the CURRENT tier's exit resets the cooldown
    assert ctrl.update(_StubBlocks(100, 50)) == ADMIT_PAUSE
    assert ctrl.update(_StubBlocks(100, 20)) == ADMIT_PAUSE
    assert ctrl.update(_StubBlocks(100, 50)) == ADMIT_PAUSE
    assert ctrl.update(_StubBlocks(100, 50)) == SPEC_SHRINK
    assert ctrl.spec_k_cap(8) == 4
    assert ctrl.update(_StubBlocks(100, 50)) == SPEC_SHRINK
    assert ctrl.update(_StubBlocks(100, 50)) == NORMAL
    assert [(f, t) for _, f, t in ctrl.transitions] == [
        (NORMAL, EVICT_PARKED), (EVICT_PARKED, ADMIT_PAUSE),
        (ADMIT_PAUSE, SPEC_SHRINK), (SPEC_SHRINK, NORMAL)]
    # retry-after: history shows pages freeing -> finite, clamped
    assert 1.0 <= ctrl.retry_after_s() <= 30.0


def test_degradation_controller_requires_hysteresis_gap():
    with pytest.raises(ValueError):
        DegradationController(enter=(0.3, 0.2, 0.1), exit=(0.3, 0.28, 0.2))


# ---------------------------------------------------------------------------
# degradation through the engine: levers engage BEFORE preemption
# ---------------------------------------------------------------------------

def test_degradation_engages_before_preemption(model):
    ctrl = DegradationController(cooldown_steps=3, evict_batch=2)
    eng = _engine(model, max_num_seqs=4, drafter="ngram", spec_k=4,
                  max_spec_k=4, pressure=ctrl, retain_outputs=True)
    total = eng.blocks.num_blocks - 1            # 32 usable pages
    rng = np.random.RandomState(23)
    eng.add_request(rng.randint(0, VOCAB, 8).tolist(), max_new_tokens=40)
    eng.step()                                    # prefill
    eng.step()                                    # first decodes
    assert ctrl.state == NORMAL

    # squeeze the pool from outside: free fraction 8/32 = 0.25 <= 0.30
    assert eng.blocks.allocate("ghost-0", (eng.blocks.num_free - 8) * 8)
    assert eng.blocks.num_free == 8
    eng.step()
    assert ctrl.state == SPEC_SHRINK
    assert ctrl.spec_k_cap(eng.max_spec_k) == 2
    assert eng.stats.preemptions == 0             # the cheap lever first

    # squeeze harder: free 5/32 = 0.156 <= 0.18 -> admission pauses
    assert eng.blocks.allocate("ghost-1", (eng.blocks.num_free - 5) * 8)
    rid_b = eng.add_request(rng.randint(0, VOCAB, 6).tolist(),
                            max_new_tokens=4)
    eng.step()
    assert ctrl.state == ADMIT_PAUSE
    assert ctrl.admission_paused
    # the new request is NOT admitted (no pages allocated for it) and
    # nothing was preempted to make room for it
    assert not eng.blocks.has(rid_b)
    assert eng.stats.preemptions == 0
    assert 1.0 <= ctrl.retry_after_s() <= 30.0
    assert eng.stats.degradation_state == ADMIT_PAUSE

    # pressure clears; recovery is tier-by-tier with hysteresis
    eng.blocks.release("ghost-0")
    eng.blocks.release("ghost-1")
    eng.step()
    assert ctrl.state == ADMIT_PAUSE              # calm 1 of 3: no drop yet
    assert not eng.blocks.has(rid_b)
    eng.step()
    eng.step()
    assert ctrl.state == SPEC_SHRINK              # one tier back, not two
    eng.step()                                    # admission resumed
    assert eng.blocks.has(rid_b)
    eng.step()
    eng.step()
    assert ctrl.state == NORMAL
    assert [(f, t) for _, f, t in ctrl.transitions] == [
        (NORMAL, SPEC_SHRINK), (SPEC_SHRINK, ADMIT_PAUSE),
        (ADMIT_PAUSE, SPEC_SHRINK), (SPEC_SHRINK, NORMAL)]
    assert eng.stats.preemptions == 0
    assert eng.stats.degradation_transitions == 4

    while eng.has_unfinished():
        eng.step()
    assert eng.blocks.num_used == 0
    eng.blocks.check_invariants()


# ---------------------------------------------------------------------------
# the acceptance scenario: crash + hang + NaN + pool window over a
# 32-request mixed stream, supervised recovery end to end
# ---------------------------------------------------------------------------

def test_chaos_acceptance_recovery_byte_identical(model):
    reqs = _requests(32, seed=7)
    base_eng, base = _run_direct(model, reqs)
    budget = dict(base_eng.compile_counts)
    assert budget == {"ragged": 2, "cow": 0}      # the two-bucket config

    # crash at 5 (in-thread recovery), hang at 9 (watchdog recovery),
    # NaN row at 12, pool exhausted over 15-18 (preempt + re-admit)
    plan = FaultPlan(seed=11, crash_steps=(5,), slow_steps={9: 45.0},
                     nan_steps=(12,), pool_window=(15, 18))

    def factory():
        return _engine(model)

    eng = factory()
    eng.set_fault_plan(plan)
    runner = EngineRunner(eng, max_pending=64, engine_factory=factory,
                          step_deadline_s=12.0).start()
    queues = []
    try:
        for r in reqs:
            q = queue.Queue()
            queues.append(q)
            runner.submit(r["prompt"], deliver=q.put_nowait,
                          max_new_tokens=r["max_new_tokens"],
                          temperature=r["temperature"], seed=r["seed"])
        streams = [_collect(q) for q in queues]
    finally:
        assert runner.drain(timeout_s=120.0)

    fin = runner.engine
    assert fin is not eng                         # the engine was rebuilt
    stats = fin.stats

    # every scheduled fault actually fired
    assert stats.fault_injections.get("crash") == 1
    assert stats.fault_injections.get("slow") == 1
    assert stats.fault_injections.get("nan") == 1
    assert stats.fault_injections.get("pool") == 1
    assert plan.exhausted()

    # both recovery paths ran: the in-thread crash recovery AND the
    # watchdog hang recovery
    assert stats.engine_restarts >= 2
    assert runner.restarts == stats.engine_restarts

    # exactly one sequence was poisoned; everything else is
    # byte-identical to the fault-free baseline, with the stream's
    # token-by-token view matching the terminal output (no duplicated
    # or reordered tokens across restarts)
    bad = [i for i, (_, out) in enumerate(streams)
           if out.finish_reason == "numerical_error"]
    assert len(bad) == 1
    assert stats.quarantined == 1
    for i, (toks, out) in enumerate(streams):
        assert toks == list(out.generated)
        if i in bad:
            continue
        assert out.generated == base[i].generated, f"request {i} diverged"
        assert out.finish_reason == base[i].finish_reason

    # zero leaked pages on the surviving engine
    assert fin.blocks.num_used == 0
    fin.blocks.check_invariants()

    # the rebuilt engine's compile budget is EXACTLY the baseline's:
    # recovery replays through the same two ragged buckets, no more
    assert fin.compile_counts == budget

    snap = stats.snapshot()
    assert snap["engine_restarts"] == stats.engine_restarts
    assert snap["faults_injected_total"] >= 4
    assert snap["uptime_seconds"] > 0.0


def test_inflight_fault_recovery_discards_the_launch_in_flight(model):
    """Crash and hang injected WHILE a step is in flight (the async
    pipeline's completion seam, between a launch and its
    materialization): the runner's journal replay must recover exactly
    as it does for synchronous faults — the in-flight launch simply
    dies with the old engine, never having touched the journal (an
    armed plan keeps the pipeline one deep: nothing is dispatched ahead
    of a commit until the last fault has fired, and the rebuilt engine
    goes ahead from there).  Every output is byte-identical to the
    fault-free baseline (these faults poison nothing), zero pages leak
    (including those a dispatch ahead reserved), and the restart
    counter advances once per fault."""
    reqs = _requests(24, seed=7)
    base_eng, base = _run_direct(model, reqs)
    budget = dict(base_eng.compile_counts)
    assert budget == {"ragged": 2, "cow": 0}

    # in-flight crash at 5 (in-thread recovery), in-flight hang at 9
    # (the sleep sits between launch and materialize; the watchdog must
    # still catch it there)
    plan = FaultPlan(seed=13, inflight_crash_steps=(5,),
                     inflight_slow_steps={9: 45.0})

    def factory():
        return _engine(model)

    eng = factory()
    assert eng.overlap                            # seams need the pipeline
    eng.set_fault_plan(plan)
    runner = EngineRunner(eng, max_pending=48, engine_factory=factory,
                          step_deadline_s=12.0).start()
    queues = []
    try:
        for r in reqs:
            q = queue.Queue()
            queues.append(q)
            runner.submit(r["prompt"], deliver=q.put_nowait,
                          max_new_tokens=r["max_new_tokens"],
                          temperature=r["temperature"], seed=r["seed"])
        streams = [_collect(q) for q in queues]
    finally:
        assert runner.drain(timeout_s=120.0)

    fin = runner.engine
    assert fin is not eng
    stats = fin.stats
    assert stats.fault_injections.get("inflight_crash") == 1
    assert stats.fault_injections.get("inflight_slow") == 1
    assert plan.exhausted()
    assert stats.engine_restarts >= 2
    assert runner.restarts == stats.engine_restarts

    # no poisoned rows here: EVERY stream is byte-identical to the
    # fault-free baseline, token-by-token view included — proof the
    # discarded in-flight step never leaked a token into the journal
    for i, (toks, out) in enumerate(streams):
        assert toks == list(out.generated)
        assert out.generated == base[i].generated, f"request {i} diverged"
        assert out.finish_reason == base[i].finish_reason

    # zero leaked pages, including those reserved ahead of a commit
    assert fin.blocks.num_used == 0
    assert fin._inflight is None and fin._queued is None
    fin.blocks.check_invariants()
    # the armed plan held the first engine to the synchronous order
    assert eng.launches_ahead == 0
    assert eng.ahead_fallbacks.get("fault_plan", 0) >= 1
    assert fin.compile_counts == budget


def test_inflight_fault_during_decode_window_replays_byte_identical(model):
    """Crash and hang injected while a K=4 decode WINDOW is in flight:
    the window ticket dies with the old engine before any of its K
    tokens reach the journal, so replay reproduces the fault-free
    stream byte-for-byte — sampled rows included (the on-device key
    schedule is position-derived, not step-derived) — with zero leaked
    pages and at most the one extra window-driver compile."""
    reqs = _requests(24, seed=7)
    base_eng, base = _run_direct(model, reqs)
    budget = dict(base_eng.compile_counts)
    assert budget == {"ragged": 2, "cow": 0}

    plan = FaultPlan(seed=13, inflight_crash_steps=(5,),
                     inflight_slow_steps={9: 45.0})

    def factory():
        return _engine(model, decode_window=4)

    eng = factory()
    assert eng.overlap and eng.decode_window == 4
    eng.set_fault_plan(plan)
    runner = EngineRunner(eng, max_pending=48, engine_factory=factory,
                          step_deadline_s=12.0).start()
    queues = []
    try:
        for r in reqs:
            q = queue.Queue()
            queues.append(q)
            runner.submit(r["prompt"], deliver=q.put_nowait,
                          max_new_tokens=r["max_new_tokens"],
                          temperature=r["temperature"], seed=r["seed"])
        streams = [_collect(q) for q in queues]
    finally:
        assert runner.drain(timeout_s=120.0)

    fin = runner.engine
    assert fin is not eng
    stats = fin.stats
    assert stats.fault_injections.get("inflight_crash") == 1
    assert stats.fault_injections.get("inflight_slow") == 1
    assert plan.exhausted()
    assert stats.engine_restarts >= 2

    for i, (toks, out) in enumerate(streams):
        assert toks == list(out.generated)
        assert out.generated == base[i].generated, f"request {i} diverged"
        assert out.finish_reason == base[i].finish_reason

    assert fin.blocks.num_used == 0
    assert fin._inflight is None and fin._queued is None
    fin.blocks.check_invariants()
    # loose on purpose: whether the rebuilt engine's stream reached a
    # window-eligible state again depends on where the faults landed —
    # but the ragged/cow budget is exact and the window driver is at
    # most ONE extra kind
    counts = dict(fin.compile_counts)
    assert counts.pop("scan", 0) <= 1
    assert counts == budget


def test_inflight_seams_never_fire_synchronously(model):
    """With overlap off no launch ever crosses a step boundary, so the
    in-flight seams must never fire: the plan stays armed and the run
    completes fault-free."""
    reqs = _requests(6, seed=7)
    plan = FaultPlan(seed=13, inflight_crash_steps=(2,),
                     inflight_slow_steps={3: 30.0})
    eng = _engine(model, overlap=False)
    eng.set_fault_plan(plan)
    outs = {}
    for i, r in enumerate(reqs):
        eng.add_request(r["prompt"], max_new_tokens=r["max_new_tokens"],
                        temperature=r["temperature"], seed=r["seed"],
                        on_finish=lambda o, i=i: outs.__setitem__(i, o))
    while eng.has_unfinished():
        eng.step()
    assert len(outs) == len(reqs)
    assert "inflight_crash" not in eng.stats.fault_injections
    assert "inflight_slow" not in eng.stats.fault_injections
    assert not plan.exhausted()                   # both still armed
    assert eng.blocks.num_used == 0


# ---------------------------------------------------------------------------
# injected connection drop at the frontend seam
# ---------------------------------------------------------------------------

def _stream_until_closed(port, obj):
    """Stream a completion, tolerating a server-side connection drop.
    Returns the number of data frames seen before the close."""
    obj = dict(obj, stream=True)
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("POST", "/v1/completions", body=json.dumps(obj).encode(),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    buf = b""
    try:
        while True:
            chunk = resp.read(64)
            if not chunk:
                break
            buf += chunk
    except Exception:
        pass                                      # dropped mid-chunk
    conn.close()
    return buf.count(b"data: "), b"[DONE]" in buf


def test_injected_conn_drop_aborts_request(model):
    eng = _engine(model,
                  fault_plan=FaultPlan(seed=3, conn_drop_requests=(0,)))
    srv = serve_background(eng, model_name="tiny")
    try:
        frames, done = _stream_until_closed(
            srv.port, {"prompt": [2, 7, 1, 8], "max_tokens": 48})
        # the drop fires after the first token frame: the client saw
        # SOMETHING, then the socket died without a [DONE]
        assert frames >= 1 and not done
        assert _wait(lambda: eng.blocks.num_used == 0, timeout_s=60)
        assert eng.stats.fault_injections.get("conn") == 1
        assert eng.stats.aborts >= 1
        # the NEXT streaming request (ordinal 1, not in the drop set)
        # completes normally
        frames, done = _stream_until_closed(
            srv.port, {"prompt": [2, 7, 1, 8], "max_tokens": 8})
        assert done
    finally:
        assert srv.stop()
    assert eng.blocks.num_used == 0
    eng.blocks.check_invariants()
