"""Async step pipeline: ``overlap=True`` dispatches launch n+1 AHEAD of
launch n's commit, and must be a pure latency optimization.

The contract (CPU, paged kernel in interpret mode):

- byte-identity: greedy outputs of an ``overlap=True`` engine match an
  ``overlap=False`` engine token for token on the 16-request ragged
  audit stream, across speculation on/off, prefix cache on/off,
  float32/int8 KV pages, tp=1/2, chunks beside decode rows, a
  copy-on-write made ahead of a commit, and the latent and the
  window-and-global models; a bucket still has ONE program (the launch
  dispatched ahead and the one that is not take the same arguments);
- pipeline shape: outputs surface one step() call later than the
  synchronous engine, between two calls at most one ticket is in
  flight, has_unfinished() covers it, and run() drains it;
- a row ended by a stop token while its next row is in flight: that row
  is dropped unapplied, and the request's pages are neither reused nor
  registered in the prefix cache before that launch has completed;
- abort of a row that rides the committed launch and the one in flight:
  the flush drops the victim's row unapplied (the abort output reports
  the tokens the caller has actually observed), batchmates lose
  nothing, and the pool comes back clean;
- every reason the engine falls back to commit-then-dispatch for is
  taken and counted (``summary()["ahead_fallbacks"]``), and a short
  pool falls back and then preempts as the synchronous engine does;
- tracing: ``engine.device_launch`` says ``ahead`` (and the reason where
  not), the ``engine.device_inflight`` windows of consecutive launches
  do not overlap; overlap-off emits none of the in-flight windows
  (step_timeline.py's "synchronous" reading).
"""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.inference.faults import FaultPlan
from paddle_tpu.inference.kv_tier import HostSpillPool
from paddle_tpu.inference.pressure import DegradationController
from paddle_tpu.profiler import Tracer

VOCAB = 97

from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

CFG = LlamaConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=4, ffn=64,
                       seq=64)


@pytest.fixture(scope="module")
def model():
    # seeded: one draw in eight repeats a token from its second step on,
    # and the stop-token tests need a run of distinct ones
    paddle.seed(0)
    return LlamaForCausalLM(CFG)


@pytest.fixture(scope="module")
def latent_model():
    from paddle_tpu.models.mla_moe import MlaMoeConfig, MlaMoeForCausalLM
    return MlaMoeForCausalLM(MlaMoeConfig.tiny(vocab=VOCAB, seq=64),
                             dtype="float32")


@pytest.fixture(scope="module")
def window_model():
    from paddle_tpu.models.smallthinker import (SmallThinkerConfig,
                                                SmallThinkerForCausalLM)
    return SmallThinkerForCausalLM(
        SmallThinkerConfig.tiny(vocab=VOCAB, layers=4, window=8, seq=64),
        dtype="float32")


def _engine(model, **kw):
    kw.setdefault("max_num_seqs", 8)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("max_prefill_tokens", 256)
    kw.setdefault("prefill_token_bucket", 64)
    return LLMEngine(model, **kw)


def _audit_drive(model, overlap, **kw):
    """The 16-request ragged audit stream; (engine, outputs-by-index)."""
    eng = _engine(model, overlap=overlap, **kw)
    rng = np.random.RandomState(7)
    shapes = [(4, 8), (9, 8), (13, 6)]
    order = {}
    for i in range(16):
        n, max_new = shapes[i % len(shapes)]
        p = rng.randint(0, VOCAB, n).tolist()
        order[eng.add_request(p, max_new_tokens=max_new)] = i
    outs = eng.run()
    assert len(outs) == 16
    return eng, {order[rid]: (tuple(o.generated), o.finish_reason)
                 for rid, o in outs.items()}


def _clean(eng):
    assert eng.blocks.num_used == 0
    eng.blocks.check_invariants()
    assert eng._inflight is None and eng._queued is None
    assert all(r.inflight == 0 for r in eng._running)


def _one_program_a_bucket(eng):
    """Each bucket's jit holds ONE compiled program, whether a launch
    was handed the zero ``prev`` or the sampled tokens of the launch in
    front of it."""
    assert {Tq: p._cache_size() for Tq, p in eng._ragged_progs.items()} \
        == {Tq: 1 for Tq in eng._ragged_progs}


# ---------------------------------------------------------------------------
# byte-identity across the config matrix, compile budget pinned
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    {},                                                   # baseline f32
    {"enable_prefix_caching": False},                     # cache off
    {"drafter": "ngram", "spec_k": 3},                    # speculation on
    {"kv_dtype": "int8"},                                 # quantized pages
    {"kv_dtype": "int8", "drafter": "ngram", "spec_k": 3},
    {"tp": 2},                                            # sharded step
    {"max_prefill_tokens": 16, "prefill_token_bucket": 16},
    {"max_prefill_tokens": 16, "prefill_token_bucket": 16,
     "kv_dtype": "int8"},
], ids=["f32", "cache-off", "spec", "int8", "int8-spec", "tp2",
        "chunks-beside-decode", "int8-chunks-beside-decode"])
def test_overlap_byte_identical_to_sync(model, kw):
    """Commit order == dispatch order, the dispatch ahead reads every
    row where the launch in front will leave it, the token it cannot
    know is taken on the device, and sampling keys are position-keyed —
    so the async engine's token stream is the synchronous engine's, bit
    for bit, and it compiles NOTHING new."""
    e_on, o_on = _audit_drive(model, True, **kw)
    e_off, o_off = _audit_drive(model, False, **kw)
    assert o_on == o_off
    assert e_on.compile_counts == e_off.compile_counts
    for eng in (e_on, e_off):
        _clean(eng)
        _one_program_a_bucket(eng)
    on, off = e_on.summary(), e_off.summary()
    assert off["launches_ahead"] == 0
    assert off["ahead_fallbacks"] == {"sync": off["launches"]}
    assert on["launches"] == on["launches_ahead"] \
        + sum(on["ahead_fallbacks"].values())
    if "drafter" in kw:
        # acceptance is on the host: nothing goes ahead of a commit
        assert on["launches_ahead"] == 0
        assert on["ahead_fallbacks"]["drafter"] == on["launches"] - 1
    else:
        # greedy, no stop token, a pool that never runs short: nothing
        # falls back but the first launch of an idle engine
        assert on["ahead_fallbacks"] == {"idle": 1}
        assert on["ahead_rows_dropped"] == 0


@pytest.mark.parametrize("which", ["latent", "window"])
def test_overlap_byte_identical_other_layer_kinds(which, latent_model,
                                                  window_model):
    """Latent and window-and-global layers differ in the program and
    the tables, not in what the scheduler must know: the same stream
    with chunks beside decode rows, ahead and synchronous."""
    m = latent_model if which == "latent" else window_model
    kw = {"max_prefill_tokens": 16, "prefill_token_bucket": 16}
    if which == "window":
        kw["enable_prefix_caching"] = False
    e_on, o_on = _audit_drive(m, True, **kw)
    e_off, o_off = _audit_drive(m, False, **kw)
    assert o_on == o_off
    for eng in (e_on, e_off):
        _clean(eng)
        _one_program_a_bucket(eng)
    assert e_on.summary()["ahead_fallbacks"] == {"idle": 1}
    if which == "window":
        # the window moved ahead of the commits too: pages came back
        assert e_on.blocks.window_returned > 0
        assert e_on.blocks.num_window_used == 0


def _cow_drive(model, overlap):
    """A long decode keeps a launch in flight; a short request finishes
    beside it and leaves a partly filled page in the cache; two
    follow-ups that extend exactly what it left cached arrive together,
    so the first to write copies the shared page — with overlap on, in
    a dispatch made ahead of the decode launch's commit."""
    eng = _engine(model, overlap=overlap)
    rng = np.random.RandomState(3)
    long_p = rng.randint(0, VOCAB, 6).tolist()
    short_p = rng.randint(0, VOCAB, 11).tolist()
    done = {}
    eng.add_request(long_p, max_new_tokens=40,
                    on_finish=lambda o: done.__setitem__("long", o))
    eng.add_request(short_p, max_new_tokens=3,
                    on_finish=lambda o: done.__setitem__("short", o))
    while "short" not in done:
        eng.step()
    # 11 + 3 - 1 = 13 positions cached: a full page and 5 slots
    left = short_p + list(done["short"].generated[:-1])
    assert len(left) == 13
    ahead_before = eng.launches_ahead
    for tag, tail in (("b", [5, 6, 7]), ("c", [8, 9])):
        eng.add_request(left + tail, max_new_tokens=5,
                        on_finish=lambda o, t=tag: done.__setitem__(t, o))
    eng.step()                       # admits both, copies, launches
    if overlap:
        assert eng.launches_ahead == ahead_before + 1
    while eng.has_unfinished():
        eng.step()
    return eng, {k: tuple(o.generated) for k, o in done.items()}


def test_copy_on_write_ahead_of_a_commit_is_byte_identical(model):
    e_on, o_on = _cow_drive(model, True)
    e_off, o_off = _cow_drive(model, False)
    assert o_on == o_off and set(o_on) == {"long", "short", "b", "c"}
    for eng in (e_on, e_off):
        assert eng.blocks.cow_count >= 1
        assert eng.blocks.cache_hit_tokens >= 2 * 13
        _clean(eng)
    assert e_on.summary()["ahead_fallbacks"] == {"idle": 1}


# ---------------------------------------------------------------------------
# pipeline shape: one ticket between calls, one extra draining step
# ---------------------------------------------------------------------------

def test_outputs_surface_one_step_later_and_run_drains(model):
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, VOCAB, 6).tolist()

    def steps_to_finish(overlap):
        eng = _engine(model, overlap=overlap)
        eng.add_request(prompt, max_new_tokens=4)
        first_returns, n = [], 0
        while eng.has_unfinished():
            outs = eng.step()
            n += 1
            assert eng._queued is None   # two tickets only INSIDE a call
            if n == 1:
                first_returns.extend(outs)
        assert eng.blocks.num_used == 0
        return first_returns, n

    sync_first, sync_n = steps_to_finish(False)
    async_first, async_n = steps_to_finish(True)
    # the async engine's first step() only FILLS the pipeline: the
    # prefill is launched but its outputs surface next call, and the
    # whole run takes exactly one extra draining call (the row's last
    # token is known to be its last: nothing is launched behind it)
    assert async_first == []
    assert async_n == sync_n + 1


def test_has_unfinished_covers_inflight_ticket(model):
    eng = _engine(model, overlap=True)
    eng.add_request([3, 1, 4, 1, 5], max_new_tokens=1)
    eng.step()                          # dispatched, nothing completed
    assert eng._inflight is not None
    assert eng.has_unfinished()         # only the ticket keeps it alive
    outs = eng.step()                   # completes (and dispatches nothing)
    assert [o for o in outs if o.finish_reason]
    assert eng._inflight is None
    assert not eng.has_unfinished()
    # the row's one token was its last: no launch was made behind it
    assert eng.launches == 1 and eng.launches_ahead == 0


# ---------------------------------------------------------------------------
# a stop token the host could not know ahead
# ---------------------------------------------------------------------------

def _stop_drive(model, overlap, eos, watch=None):
    rng = np.random.RandomState(31)
    pa = rng.randint(0, VOCAB, 9).tolist()
    pb = rng.randint(0, VOCAB, 5).tolist()
    eng = _engine(model, overlap=overlap)
    ra = eng.add_request(pa, max_new_tokens=12, eos_token_id=eos)
    rb = eng.add_request(pb, max_new_tokens=12)
    outs = {}
    while eng.has_unfinished():
        for o in eng.step():
            outs[o.rid] = o
            if watch is not None and o.rid == ra:
                watch(eng, ra, pa, o)
    return eng, ra, rb, outs


def test_stop_token_drops_the_row_in_flight_and_defers_its_pages(model):
    _e, ra, _rb, base = _stop_drive(model, False, None)
    free_run = list(base[ra].generated)
    # a token whose first appearance is mid-stream: the host learns it
    # at that launch's commit, when the next launch already holds the row
    k = next(i for i in range(2, 10) if free_run[i] not in free_run[:i])
    eos = free_run[k]
    seen = {}

    def watch(eng, rid, prompt, out):
        # the commit retired the request in THIS call: its output is
        # out, its slot is free, but the launch in flight still names
        # its pages, so they are neither free nor in the prefix cache
        seen["held"] = eng.blocks.has(rid)
        seen["inflight_names_it"] = rid in eng._inflight.slot_of \
            and eng._inflight.dropped == {rid: "free"}
        seen["running"] = [r.rid for r in eng._running]
        seen["cached"] = eng.blocks.num_cached
        seen["tail"] = prompt + list(out.generated[:-1])
        seen["hit"] = eng.blocks.match_prefix(seen["tail"] + [0])

    e_on, ra, rb, on = _stop_drive(model, True, eos, watch)
    e_off, _, _, off = _stop_drive(model, False, eos)
    assert on[ra].finish_reason == "eos" and \
        list(on[ra].generated) == free_run[:k + 1]
    assert {r: tuple(o.generated) for r, o in on.items()} \
        == {r: tuple(o.generated) for r, o in off.items()}
    assert tuple(on[rb].generated) == tuple(base[rb].generated)
    assert e_on.summary()["ahead_rows_dropped"] == 1
    assert e_off.summary()["ahead_rows_dropped"] == 0
    assert seen["held"] and seen["inflight_names_it"]
    assert ra not in seen["running"]
    # no page of it was parked, and its partly filled last page (which
    # the row in flight is writing) was not registered: a prefix match
    # finds only the full pages it registered while it lived
    n = len(seen["tail"])
    assert n % 8 and seen["cached"] == 0 and seen["hit"] == n - n % 8
    # once that launch has completed everything is, as after a
    # synchronous retirement: the written tail is a prefix-cache hit
    assert not e_on.blocks.has(ra)
    for eng in (e_on, e_off):
        assert eng.blocks.match_prefix(seen["tail"] + [0]) == n
    assert e_on.blocks.num_cached == e_off.blocks.num_cached > 0
    _clean(e_on)


# ---------------------------------------------------------------------------
# abort while in flight: flush, drop, nothing else disturbed
# ---------------------------------------------------------------------------

def test_abort_while_inflight_drops_victim_keeps_batchmates(model):
    rng = np.random.RandomState(19)
    pa = rng.randint(0, VOCAB, 8).tolist()
    pb = rng.randint(0, VOCAB, 11).tolist()

    base = _engine(model, overlap=False)
    base.add_request(pb, max_new_tokens=8)
    b_full = tuple(base.run().popitem()[1].generated)

    eng = _engine(model, overlap=True)
    ra = eng.add_request(pa, max_new_tokens=8)
    rb = eng.add_request(pb, max_new_tokens=8)
    for _ in range(4):
        eng.step()
    # the victim rode the launch the last call committed AND rides the
    # one it dispatched ahead of that commit, still in flight
    assert eng._inflight is not None and eng.launches_ahead == 3
    assert ra in eng._inflight.slot_of
    seen = len(next(r for r in eng._running if r.rid == ra).generated)
    out_a = eng.abort(ra)
    # the flush dropped the in-flight step's row for the victim: its
    # abort output is exactly the prefix the caller had already seen
    assert out_a.finish_reason == "aborted"
    assert eng._inflight is None
    assert len(out_a.generated) == seen < 8
    # the batchmate is untouched: it finishes byte-identical to a run
    # that never shared a batch with the aborted row
    outs = eng.run()
    assert tuple(outs[rb].generated) == b_full
    assert outs[rb].finish_reason in ("length", "eos")
    _clean(eng)
    # the flush left the engine idle; an abort is not a dropped row
    assert eng.ahead_fallbacks == {"idle": 2}
    assert eng.ahead_rows_dropped == 0


def test_abort_flush_buffers_batchmate_finishes(model):
    """If the abort's pipeline flush happens to FINISH a batchmate, its
    output must still come out of the step()-return channel (buffered,
    drained by the next step call) — never silently dropped, and
    has_unfinished() keeps the driving loop alive until it surfaces."""
    rng = np.random.RandomState(23)
    pa = rng.randint(0, VOCAB, 5).tolist()
    pb = rng.randint(0, VOCAB, 7).tolist()
    eng = _engine(model, overlap=True)
    ra = eng.add_request(pa, max_new_tokens=8)
    rb = eng.add_request(pb, max_new_tokens=1)   # finishes on its first token
    finishes = []
    assert eng.step() == []                       # both prefills in flight
    assert eng._inflight is not None
    # the flush inside abort() retires rb OUTSIDE any step() call
    out_a = eng.abort(ra)
    assert out_a.finish_reason == "aborted"
    assert eng._pending_finished                  # rb's output, buffered
    assert eng.has_unfinished()                   # loop must keep driving
    while eng.has_unfinished():
        finishes.extend(eng.step())
    by_rid = {o.rid: o for o in finishes}
    assert rb in by_rid                           # surfaced, not dropped
    assert len(by_rid[rb].generated) == 1
    assert by_rid[rb].finish_reason in ("length", "eos")
    assert not eng.has_unfinished()
    _clean(eng)


# ---------------------------------------------------------------------------
# falling back: decided from what the engine holds, counted by reason
# ---------------------------------------------------------------------------

def _mixed_drive(model, overlap, request_kw=(), **kw):
    """Eight requests, the second half arriving while the first decode;
    ``request_kw``: {index: add_request keywords}."""
    eng = _engine(model, overlap=overlap, **kw)
    rng = np.random.RandomState(11)
    extra = dict(request_kw)
    order, outs = {}, {}

    def add(i):
        p = rng.randint(0, VOCAB, 5 + 2 * i).tolist()
        order[eng.add_request(p, max_new_tokens=10, **extra.get(i, {}))] = i

    for i in range(4):
        add(i)
    for _ in range(5):
        for o in eng.step():
            outs[order[o.rid]] = tuple(o.generated)
    for i in range(4, 8):
        add(i)
    while eng.has_unfinished():
        for o in eng.step():
            outs[order[o.rid]] = tuple(o.generated)
    assert len(outs) == 8
    return eng, outs


@pytest.mark.parametrize("reason", ["decode_window", "drafter", "kv_tier",
                                    "pressure", "fault_plan", "penalty"])
def test_each_fallback_reason_is_taken_and_counted(model, reason):
    """What the next launch needs from the commit, the engine can see:
    a decode window on either side, a drafter, a spill tier, a pressure
    controller, an armed fault plan, a row with a repetition penalty.
    Each falls back to commit-then-dispatch for exactly as long as it
    holds, is counted under its name, and changes no token."""
    mk = {"decode_window": lambda: {"decode_window": 4,
                                    "max_prefill_tokens": 16,
                                    "prefill_token_bucket": 16},
          "drafter": lambda: {"drafter": "ngram", "spec_k": 2},
          "kv_tier": lambda: {"kv_tier": HostSpillPool(1 << 20)},
          "pressure": lambda: {"pressure": DegradationController()},
          "fault_plan": lambda: {"fault_plan": FaultPlan(
              slow_steps={6: 0.0})},
          "penalty": lambda: {}}[reason]
    req_kw = {1: {"repetition_penalty": 1.3}} if reason == "penalty" else {}
    e_on, o_on = _mixed_drive(model, True, req_kw, **mk())
    e_off, o_off = _mixed_drive(model, False, req_kw, **mk())
    assert o_on == o_off
    _clean(e_on)
    s = e_on.summary()
    assert s["ahead_fallbacks"].get(reason, 0) >= 1, s["ahead_fallbacks"]
    assert s["launches"] == s["launches_ahead"] \
        + sum(s["ahead_fallbacks"].values())
    assert set(s["ahead_fallbacks"]) <= {"idle", reason}
    if reason in ("drafter", "kv_tier", "pressure"):
        # holds for the engine's whole life
        assert s["launches_ahead"] == 0
    else:
        # holds for a while: pure-decode launches with a window
        # configured, until the plan's last fault has fired, while the
        # penalised row runs; the other launches go ahead
        assert s["launches_ahead"] >= 1
    if reason == "decode_window":
        assert e_on.compile_counts.get("scan") == 1
    if reason == "fault_plan":
        assert e_on.fault_plan.exhausted() and not e_on.fault_plan.armed()


def _short_pool_drive(model, overlap):
    eng = _engine(model, overlap=overlap, max_num_seqs=4, num_blocks=10,
                  max_prefill_tokens=128, prefill_token_bucket=32)
    rng = np.random.RandomState(1)
    order = {}
    for i in range(8):
        p = rng.randint(0, VOCAB, rng.randint(4, 12)).tolist()
        order[eng.add_request(p, max_new_tokens=20)] = i
    outs = eng.run()
    return eng, {order[r]: tuple(o.generated) for r, o in outs.items()}


def test_short_pool_falls_back_then_preempts_as_sync_does(model):
    """A reservation only a preemption could meet is never made ahead
    of a commit: the call commits first and then preempts, as the
    synchronous engine does, and every output stays exact."""
    e_on, o_on = _short_pool_drive(model, True)
    e_off, o_off = _short_pool_drive(model, False)
    assert o_on == o_off and len(o_on) == 8
    assert e_off.stats.preemptions > 0
    assert e_on.stats.preemptions > 0
    s = e_on.summary()
    assert s["ahead_fallbacks"].get("pool", 0) >= 1
    assert s["launches_ahead"] >= 1
    _clean(e_on)


# ---------------------------------------------------------------------------
# the scheduler's decisions on a short pool, held to a recording
# ---------------------------------------------------------------------------

_DECISIONS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "fixtures", "scheduler", "decisions_parent.json")


def _decisions_drive(model, overlap, drafter):
    """A finished conversation, three unrelated requests, then four
    follow-ups that extend the conversation and diverge inside its
    cached partial tail page, on a pool of eight pages: the follow-ups'
    first chunks copy that page on write with no page free
    (``_resolve_cow`` preempts), and the decode rows' growth preempts in
    ``_reserve_decode_pages``.  What every launch held, in order, who
    was preempted where, and every token."""
    kw = {"drafter": "ngram", "spec_k": 3} if drafter else {}
    eng = _engine(model, overlap=overlap, max_num_seqs=6, num_blocks=9,
                  max_prefill_tokens=32, prefill_token_bucket=32, **kw)
    launches, where = [], {}
    run_ragged, preempt = eng._run_ragged, eng._preempt

    def record_launch(chunks, spec, batch):
        launches.append([[[r.rid, n] for r, n in chunks],
                         [[r.rid, len(d)] for r, d, _ in spec],
                         [r.rid for r in batch]])
        return run_ragged(chunks, spec, batch)

    def record_preempt(req):
        by = sys._getframe(1).f_code.co_name
        where[by] = where.get(by, 0) + 1
        return preempt(req)

    eng._run_ragged, eng._preempt = record_launch, record_preempt
    rng = np.random.RandomState(1)
    pa = rng.randint(0, VOCAB, 11).tolist()
    ra = eng.add_request(pa, max_new_tokens=5)
    base = pa + eng.run()[ra].generated[:4]
    for _ in range(3):
        eng.add_request(rng.randint(0, VOCAB, rng.randint(4, 12)).tolist(),
                        max_new_tokens=12)
    eng.step()
    eng.step()
    for _ in range(4):
        eng.add_request(base + [int(rng.randint(0, VOCAB))],
                        max_new_tokens=12)
    outs = eng.run()
    return eng, {
        "launches": launches,
        "preempted_in": dict(sorted(where.items())),
        "preemptions": eng.stats.preemptions,
        "cow_copies": eng.stats.summary()["cow_copies"],
        "generated": {str(r): list(o.generated)
                      for r, o in sorted(outs.items())},
    }


@pytest.mark.parametrize("drafter", [False, True], ids=["plain", "drafter"])
@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
def test_short_pool_decisions_equal_the_parents_recording(model, overlap,
                                                          drafter):
    """Which rows ride which launch is the scheduler's decision, and a
    cheaper way to reach it must not change it: on a pool that forces
    preemptions while rows already taken stand reserved (a victim of a
    copy-on-write among the chunks, of a decode row's growth among the
    reserved decode and verify rows), every launch's chunks, verify
    rows and decode rows, the preemption count and every token equal
    what the parent commit (b650a05) did, recorded from this drive."""
    with open(_DECISIONS) as f:
        want = json.load(f)[f"overlap={int(overlap)},drafter={int(drafter)}"]
    eng, got = _decisions_drive(model, overlap, drafter)
    assert got["preempted_in"].get("_resolve_cow", 0) > 0
    assert got["preempted_in"].get("_reserve_decode_pages", 0) > 0
    if drafter:
        assert any(spec for _, spec, _ in got["launches"])
    assert got == want
    _clean(eng)


def test_precompiled_buckets_take_both_kinds_of_launch(model):
    """``precompile_buckets`` registers every bucket's ONE program; a
    run of launches that are ahead and launches that are not builds and
    compiles nothing more."""
    eng = _engine(model, overlap=True, max_prefill_tokens=16,
                  prefill_token_bucket=16)
    ladder = eng.precompile_buckets()
    counts = dict(eng.compile_counts)
    assert counts["ragged"] == len(ladder)
    rng = np.random.RandomState(5)
    for n in (4, 9, 13, 30):
        eng.add_request(rng.randint(0, VOCAB, n).tolist(), max_new_tokens=6)
    eng.run()
    assert eng.compile_counts == counts
    assert eng.launches_ahead > 0 and eng.ahead_fallbacks == {"idle": 1}
    used = {Tq: p._cache_size() for Tq, p in eng._ragged_progs.items()}
    assert set(used.values()) <= {0, 1} and sum(used.values()) >= 2


# ---------------------------------------------------------------------------
# trace surface: wrapper spans, in-flight windows, the ``ahead`` argument
# ---------------------------------------------------------------------------

def _traced_events(model, overlap):
    eng = _engine(model, overlap=overlap)
    tr = Tracer()
    eng.set_tracer(tr)
    rng = np.random.RandomState(29)
    for _ in range(3):
        eng.add_request(rng.randint(0, VOCAB, 6).tolist(),
                        max_new_tokens=6)
    eng.run()
    # raw tuples: (ph, name, ts_ns, dur_ns, tid, args, id)
    return eng, tr.events()


def test_overlap_trace_emits_pipeline_spans(model):
    eng, evs = _traced_events(model, True)
    names = [e[1] for e in evs]
    for span in ("engine.dispatch", "engine.complete",
                 "engine.device_inflight"):
        assert span in names, span
    assert "engine.prestage" not in names
    launches = sorted((e[5]["step"], e[5]) for e in evs
                      if e[1] == "engine.device_launch")
    assert [s for s, _ in launches] == list(range(1, eng.launches + 1))
    # the first launch found an idle engine; every other went ahead
    assert launches[0][1]["ahead"] is False \
        and launches[0][1]["reason"] == "idle"
    assert all(a["ahead"] is True and "reason" not in a
               for _, a in launches[1:])
    assert eng.launches_ahead == len(launches) - 1
    # the dispatch that launched ahead says so too
    assert sum(1 for e in evs if e[1] == "engine.dispatch"
               and e[5].get("launched") and e[5].get("ahead")) \
        == eng.launches_ahead


def test_inflight_windows_of_consecutive_launches_do_not_overlap(model):
    """A launch queued behind another has the device from the moment the
    one before it was seen complete, not from its own jit call: the
    windows tile, and no step is counted twice."""
    eng, evs = _traced_events(model, True)
    wins = sorted((e[5]["step"], e[2], e[2] + e[3]) for e in evs
                  if e[1] == "engine.device_inflight")
    assert [s for s, _, _ in wins] == list(range(1, eng.launches + 1))
    for (_, _, end), (step, start, _) in zip(wins, wins[1:]):
        assert start >= end, step
    # each ahead launch's window opens INSIDE the call that committed
    # the launch before it, after that launch's result was seen
    blocks = {e[5]["step"]: e[2] + e[3] for e in evs
              if e[1] == "engine.block_on_result"}
    for step, start, _ in wins[1:]:
        assert start >= blocks[step - 1]


def test_sync_trace_has_no_inflight_windows(model):
    eng, evs = _traced_events(model, False)
    names = [e[1] for e in evs]
    assert "engine.device_inflight" not in names
    assert "engine.prestage" not in names
    # the dispatch/complete wrappers still bracket the synchronous
    # step's two halves — the attribution split exists either way
    assert "engine.dispatch" in names
    assert "engine.complete" in names
    assert all(e[5]["ahead"] is False and e[5]["reason"] == "sync"
               for e in evs if e[1] == "engine.device_launch")
