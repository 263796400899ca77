"""The scheduler's question "is this row still running" has one answer,
``LLMEngine._is_running`` (``req.slot >= 0``), and costs O(1):

- a ``Request`` is itself and nothing else: identity comparison and
  hashing, no generated field-by-field ``__eq__``;
- ``req.slot >= 0`` holds for exactly the members of ``_running``
  through admission, retirement, preemption, quarantine and ``abort``;
- no ``==`` between requests is left on the turn: with
  ``Request.__eq__`` patched to raise, a mixed run is served to its end;
- rows already taken are filtered against the running set again only
  where something was preempted: ``summary()["sched_refilters"]``.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.inference.faults import FaultPlan
from paddle_tpu.inference.kv_cache import BlockPoolExhausted
from paddle_tpu.inference.serving import Request
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler import Tracer

VOCAB = 97
CFG = LlamaConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=4, ffn=64,
                       seq=64)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(CFG)


def _engine(model, **kw):
    kw.setdefault("max_num_seqs", 6)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("max_prefill_tokens", 32)
    kw.setdefault("prefill_token_bucket", 32)
    return LLMEngine(model, **kw)


def _request():
    return Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4,
                   temperature=0.0, eos_token_id=None, seed=0)


# ---------------------------------------------------------------------------
# (a) a request is itself
# ---------------------------------------------------------------------------

def test_request_compares_and_hashes_by_identity():
    a, b = _request(), _request()
    assert Request.__eq__ is object.__eq__
    assert Request.__hash__ is object.__hash__
    assert a != b and a == a
    assert len({a, b}) == 2 and a in {a} and b not in {a}
    assert [b, a].index(a) == 1


# ---------------------------------------------------------------------------
# (b) the invariant, over a run that takes every way in and out
# ---------------------------------------------------------------------------

def _holds(eng, every):
    running = {id(r) for r in eng._running}
    assert len(running) == len(eng._running)
    for r in every:
        assert eng._is_running(r) == (r.slot >= 0) == (id(r) in running), \
            r.rid
    slots = [r.slot for r in eng._running]
    assert len(set(slots)) == len(slots)
    assert [i for i, u in enumerate(eng._slot_used) if u] == sorted(slots)


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
def test_slot_marks_exactly_the_running_set(model, overlap):
    """Admission, retirement by length, preemption on a short pool, a
    quarantined row and aborts of a running, a waiting and a finished
    request: after every ``step()`` and every ``abort()`` the requests
    with a slot are the members of ``_running``, each once."""
    eng = _engine(model, overlap=overlap, num_blocks=10,
                  fault_plan=FaultPlan(seed=2, nan_steps=(6,)))
    rng = np.random.RandomState(3)
    every = []

    def add(n, max_new):
        rid = eng.add_request(rng.randint(0, VOCAB, n).tolist(),
                              max_new_tokens=max_new)
        every.append(eng._waiting[-1])
        assert every[-1].rid == rid and not eng._is_running(every[-1])
        return rid

    rids = [add(int(rng.randint(4, 12)), 6 + 7 * (i % 3)) for i in range(10)]
    _holds(eng, every)
    steps = 0
    while eng.has_unfinished():
        eng.step()
        steps += 1
        _holds(eng, every)
        if steps == 4:
            victim = eng._running[1]
            assert eng.abort(victim.rid) is not None
            assert not eng._is_running(victim)
            _holds(eng, every)
            queued = eng._waiting[-1]
            assert eng.abort(queued.rid) is not None
            _holds(eng, every)
        assert steps < 400
    assert eng.abort(rids[0]) is None           # finished: a counted no-op
    _holds(eng, every)
    assert not eng._running and not any(eng._slot_used)
    assert all(r.slot == -1 for r in every)
    assert eng.stats.preemptions > 0 and eng.stats.quarantined == 1
    assert eng.stats.abort_reasons["aborted"] == 2
    assert eng.blocks.num_used == 0
    eng.blocks.check_invariants()


# ---------------------------------------------------------------------------
# (c) nothing on the turn compares two requests
# ---------------------------------------------------------------------------

def _mixed_drive(model, **kw):
    """Chunks beside decode rows (prompts longer than a step's prefill
    budget, ragged arrivals), retirements, and a pool short enough to
    preempt."""
    eng = _engine(model, num_blocks=12, max_prefill_tokens=16,
                  prefill_token_bucket=16, **kw)
    rng = np.random.RandomState(11)
    sysp = rng.randint(0, VOCAB, 13).tolist()
    rids = []
    for i in range(10):
        p = (sysp if i % 2 else []) \
            + rng.randint(0, VOCAB, int(rng.randint(3, 20))).tolist()
        rids.append(eng.add_request(p, max_new_tokens=5 + 3 * (i % 4)))
        if i % 3 == 2:
            eng.step()
    outs = eng.run()
    assert sorted(outs) == rids
    return eng, {r: tuple(outs[r].generated) for r in rids}


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
@pytest.mark.parametrize("cache", [False, True],
                         ids=["cache-off", "cache-on"])
def test_a_mixed_run_never_compares_two_requests(model, monkeypatch, cache,
                                                 overlap):
    kw = {"enable_prefix_caching": cache, "overlap": overlap}
    _, want = _mixed_drive(model, **kw)

    def compared(self, other):
        raise AssertionError("a Request was compared by ==")

    monkeypatch.setattr(Request, "__eq__", compared)
    eng, got = _mixed_drive(model, **kw)
    monkeypatch.undo()
    assert got == want
    s = eng.summary()
    assert eng.stats.preemptions > 0 and s["sched_refilters"] > 0
    assert eng.stats.prefill_steps > 10          # chunks rode beside rows
    assert eng.blocks.num_used == 0
    eng.blocks.check_invariants()


# ---------------------------------------------------------------------------
# (d) the counter of the slow way
# ---------------------------------------------------------------------------

def _followups_drive(model, num_blocks, tracer=None):
    """Follow-ups that diverge inside a finished conversation's cached
    partial tail page: each first chunk but the last copies the page on
    write."""
    eng = _engine(model, num_blocks=num_blocks)
    if tracer is not None:
        eng.set_tracer(tracer)
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
    return eng, {r: tuple(o.generated) for r, o in outs.items()}


def test_sched_refilters_counts_only_where_a_preemption_struck(model):
    tr = Tracer()
    ample, o_ample = _followups_drive(model, num_blocks=64, tracer=tr)
    s = ample.summary()
    assert s["cow_copies"] >= 1 and ample.stats.preemptions == 0
    assert s["sched_refilters"] == 0
    sched = [e[5] for e in tr.events() if e[1] == "engine.schedule"]
    assert sched and all(a["refilters"] == 0 for a in sched
                         if "refilters" in a)
    assert any("refilters" in a for a in sched)

    tr = Tracer()
    short, o_short = _followups_drive(model, num_blocks=9, tracer=tr)
    s = short.summary()
    assert s["cow_copies"] >= 1 and short.stats.preemptions > 0
    assert s["sched_refilters"] > 0
    sched = [e[5] for e in tr.events() if e[1] == "engine.schedule"]
    assert sum(a.get("refilters", 0) for a in sched) == s["sched_refilters"]
    assert sorted(o_short) == sorted(o_ample)


def test_a_copy_on_write_victim_leaves_the_verify_rows_already_taken(model):
    """``_reserve_verify_pages``: a page copy that finds no free page
    preempts the youngest other row; where that row stood among the
    verify rows already reserved it is filtered out, and the counter
    says so.  (A pool that ends a verify row's copy so is hard to meet
    through ``add_request``: the exhaustion is injected.)"""
    eng = _engine(model, drafter="ngram", spec_k=3, overlap=False)
    rng = np.random.RandomState(5)
    for _ in range(3):
        p = rng.randint(0, VOCAB, 6).tolist()
        eng.add_request(p + p, max_new_tokens=16)
    for _ in range(3):
        eng.step()
    rows = [r for r in eng._running if eng._decode_ready(r)]
    assert len(rows) == 3
    spec = [(r, [1, 2], None) for r in rows]
    cow, fired = eng.blocks.cow_if_shared, []

    def exhausted_once(rid, pos):
        if rid == rows[0].rid and not fired:
            fired.append(rid)
            raise BlockPoolExhausted("injected")
        return cow(rid, pos)

    eng.blocks.cow_if_shared = exhausted_once
    before = eng.sched_refilters
    # the oldest row reserves last, so its copy's victim (the youngest)
    # is already among the reserved
    ok, demoted = eng._reserve_verify_pages(spec[::-1])
    youngest = max(rows, key=lambda r: r.arrival)
    assert fired and not eng._is_running(youngest)
    assert youngest in eng._waiting
    assert [r for r, _, _ in ok] == [r for r in rows[::-1]
                                     if r is not youngest]
    assert not demoted and eng.sched_refilters == before + 1
    assert eng.stats.preemptions == 1
