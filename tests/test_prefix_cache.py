"""Prefix-cached paged KV + chunked prefill (PR 2): BlockManager
content-addressing/refcount/CoW/LRU invariants under random interleavings,
byte-identical greedy output with the cache on vs off on shared-prefix
streams, chunked prefill equivalence, and the no-decode-starvation
guarantee while a long prompt prefills."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.inference import BlockManager, LLMEngine
from paddle_tpu.inference.kv_cache import BlockPoolExhausted, NULL_BLOCK

from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

VOCAB = 97
CFG = LlamaConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=4, ffn=64,
                       seq=64)


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM(CFG)


def _oracle(model, prompt, max_new, temperature=0.0, seed=0, eos=None):
    out = model.generate(jnp.asarray([prompt], jnp.int32),
                         max_new_tokens=max_new, temperature=temperature,
                         seed=seed, eos_token_id=eos)
    return np.asarray(out._data)[0, len(prompt):].tolist()


def _engine(model, **kw):
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("max_prefill_tokens", 128)
    kw.setdefault("prefill_token_bucket", 32)
    return LLMEngine(model, **kw)


# ---------------------------------------------------------------------------
# BlockManager: content addressing, refcounts, CoW, LRU
# ---------------------------------------------------------------------------

def test_acquire_hits_full_and_partial_pages():
    bm = BlockManager(12, 4, enable_prefix_caching=True)
    ids = list(range(10))
    assert bm.acquire("a", ids) == 0              # cold cache
    bm.commit_prefill("a", 10)                    # 2 full pages registered
    bm.free("a")                                  # partial tail (2) registered
    assert bm.num_cached == 3 and bm.num_used == 0
    # follow-up sharing the full 10-token prefix: 2 full pages + k=2 partial
    assert bm.acquire("b", ids + [99]) == 10
    assert bm.cache_hit_tokens == 10
    bm.check_invariants()


def test_full_coverage_match_is_capped():
    """A prompt fully present in the cache still (re)computes >= 1 token
    so the engine has logits to sample from."""
    bm = BlockManager(12, 4, enable_prefix_caching=True)
    bm.acquire("x", list(range(8)))
    bm.commit_prefill("x", 8)
    bm.free("x")
    assert bm.acquire("y", list(range(8))) == 4   # last full page dropped
    bm.check_invariants()


def test_cow_on_shared_partial_page():
    bm = BlockManager(12, 4, enable_prefix_caching=True)
    ids = list(range(10))
    bm.acquire("a", ids)
    bm.commit_prefill("a", 10)
    bm.free("a")
    assert bm.acquire("b", ids + [99]) == 10      # both share the tail page
    assert bm.acquire("c", ids + [55]) == 10
    shared = bm.block_table("b")[2]
    assert shared == bm.block_table("c")[2]
    cw = bm.cow_if_shared("c", 10)                # first writer copies
    assert cw is not None and cw[0] == shared
    assert bm.block_table("c")[2] != shared
    assert bm.cow_count == 1
    assert bm.cow_if_shared("b", 10) is None      # now private again
    bm.check_invariants()


@pytest.mark.xfail(strict=True, reason=(
    "found by PR 36, not its to mend (ROADMAP D17): a page written in place "
    "by its last owner keeps the full-page hash an earlier owner registered"))
def test_in_place_write_does_not_leave_an_earlier_owners_hash():
    """b fills a hit partial page in place and registers it as ITS full
    page; c hits the same page partially (k=2) while b lives; b retires
    before c's first write, so c finds the page private and writes slots
    2 and 3 in place, under b's hash.  d, whose prompt is b's, must not
    be served c's K/V.  (The engine meets it on a short pool: a
    copy-on-write's victim is the page's other owner; tests/
    test_async_step.py's recording holds one such wrong answer, rid 4.)"""
    bm = BlockManager(9, 4, enable_prefix_caching=True)
    a = [1, 2, 3, 4, 5, 6]
    assert bm.acquire("a", a + [7]) == 0
    bm.commit_prefill("a", 6)
    bm.free("a")                                  # tail (k=2) registered
    b = a + [10, 11]
    assert bm.acquire("b", b + [12]) == 6
    assert bm.cow_if_shared("b", 6) is None       # private: in place
    bm.commit_prefill("b", 2)                     # page 1 full: b's hash
    assert bm.acquire("c", a + [20, 21, 22]) == 6
    bm.free("b")
    assert bm.cow_if_shared("c", 6) is None       # private again: in place
    bm.commit_prefill("c", 2)
    bm.acquire("d", b + [12, 13])
    assert bm._tables["d"][1] != bm._tables["c"][1]


def test_lru_eviction_only_under_pressure():
    bm = BlockManager(4, 2, enable_prefix_caching=True)   # 3 usable pages
    bm.acquire("p", [7, 8, 9])
    bm.commit_prefill("p", 3)
    bm.free("p")
    assert bm.num_cached == 2 and bm.num_free == 1
    assert bm.eviction_count == 0                 # parked, not evicted
    assert bm.acquire("q", [1, 2, 3, 4, 5]) == 0  # needs all 3 pages
    assert bm.eviction_count == 2                 # pressure evicts the LRU
    bm.check_invariants()


def test_preempt_recompute_hits_own_pages():
    bm = BlockManager(10, 4, enable_prefix_caching=True)
    toks = list(range(9))
    bm.acquire("r", toks)
    bm.commit_prefill("r", 9)
    bm.free("r")                                  # preemption returns pages
    # recompute (prompt + generated so far) matches what it just freed
    assert bm.acquire("r", toks + [42]) == 9
    bm.check_invariants()


def test_double_free_raises_clear_error():
    bm = BlockManager(6, 2, enable_prefix_caching=True)
    bm.acquire("s", [1, 2, 3])
    bm.commit_prefill("s", 3)
    bm.free("s")
    with pytest.raises(ValueError, match="double free"):
        bm.free("s")
    with pytest.raises(ValueError, match="unknown"):
        bm.free("never-existed")
    bm.check_invariants()                         # pool not corrupted


def test_failed_acquire_leaves_no_state():
    bm = BlockManager(4, 4, enable_prefix_caching=True)   # 3 usable
    assert bm.acquire("big", list(range(20))) is None     # needs 5 pages
    assert not bm.has("big")
    assert bm.num_free == 3 and bm.num_used == 0
    bm.check_invariants()


def test_property_random_interleavings_hold_invariants():
    """Random add/prefill/decode/free interleavings with shared prefixes:
    after every operation refcounts match table membership, and
    used + free + cached == num_blocks - 1."""
    for seed in range(4):
        rng = np.random.RandomState(100 + seed)
        bm = BlockManager(num_blocks=17, block_size=4,
                          enable_prefix_caching=True)
        prefixes = [rng.randint(0, 50, rng.randint(4, 13)).tolist()
                    for _ in range(3)]
        live = {}                     # sid -> [ids, valid, target]
        sid_next = 0
        for _ in range(300):
            op = rng.randint(0, 4)
            if op == 0 and len(live) < 6:               # admit
                ids = list(prefixes[rng.randint(3)]) \
                    + rng.randint(0, 50, rng.randint(1, 6)).tolist()
                sid = sid_next
                sid_next += 1
                hit = bm.acquire(sid, ids)
                if hit is None:                         # pool full: preempt
                    if live:
                        bm.free(next(iter(live)))
                        live.pop(next(iter(live)))
                else:
                    live[sid] = [list(ids), hit,
                                 len(ids) + rng.randint(0, 6)]
            elif op == 1 and live:                      # prefill chunk
                sid = list(live)[rng.randint(len(live))]
                ids, valid, _ = live[sid]
                if valid < len(ids):
                    k = rng.randint(1, len(ids) - valid + 1)
                    try:
                        bm.cow_if_shared(sid, valid)
                        bm.commit_prefill(sid, k)
                        live[sid][1] = valid + k
                    except BlockPoolExhausted:
                        pass
            elif op == 2 and live:                      # decode token
                sid = list(live)[rng.randint(len(live))]
                ids, valid, target = live[sid]
                if valid == len(ids) and valid < target:
                    if bm.ensure(sid, valid + 1):
                        try:
                            bm.cow_if_shared(sid, valid)
                        except BlockPoolExhausted:
                            continue
                        tok = int(rng.randint(0, 50))
                        bm.commit_decode_token(sid, tok)
                        live[sid][0] = ids + [tok]
                        live[sid][1] = valid + 1
            elif op == 3 and live:                      # retire/preempt
                sid = list(live)[rng.randint(len(live))]
                bm.free(sid)
                live.pop(sid)
            bm.check_invariants()
        for sid in list(live):
            bm.free(sid)
        bm.check_invariants()
        assert bm.num_used == 0


# ---------------------------------------------------------------------------
# engine: byte-identical greedy with cache on vs off
# ---------------------------------------------------------------------------

def _shared_prefix_stream(rng, n_requests=16, n_shared=8):
    """16 ragged requests; 8 of them share one of 3 system prompts."""
    sys_prompts = [rng.randint(0, VOCAB, n).tolist() for n in (10, 14, 18)]
    stream = []
    for i in range(n_requests):
        if i % 2 == 0 and len([s for s in stream if s[2]]) < n_shared:
            sp = sys_prompts[i % 3]
            p = sp + rng.randint(0, VOCAB, rng.randint(3, 7)).tolist()
            shared = True
        else:
            p = rng.randint(0, VOCAB, rng.randint(4, 12)).tolist()
            shared = False
        stream.append((p, 4 + (i % 3) * 2, shared))
    return stream


def _run_stream(model, stream, **kw):
    eng = _engine(model, max_num_seqs=8, **kw)
    rids = []
    for p, max_new, _ in stream:
        rids.append(eng.add_request(p, max_new_tokens=max_new))
        eng.step()                    # ragged arrivals; lets pages register
    outs = eng.run()
    eng.blocks.check_invariants()
    return eng, {r: outs[r].generated for r in rids}


def test_greedy_identical_cache_on_vs_off(model):
    """ISSUE acceptance: 16-request stream, 8 sharing a 3-way system
    prompt prefix — greedy outputs byte-identical with the prefix cache
    enabled vs disabled, and both match generate()."""
    rng = np.random.RandomState(17)
    stream = _shared_prefix_stream(rng)
    eng_on, outs_on = _run_stream(model, stream, enable_prefix_caching=True)
    eng_off, outs_off = _run_stream(model, stream,
                                    enable_prefix_caching=False)
    assert outs_on == outs_off
    s = eng_on.stats.summary()
    assert s["cache_hit_tokens"] > 0              # sharing actually happened
    assert s["prefill_tokens_saved"] == s["cache_hit_tokens"]
    assert eng_off.stats.summary()["cache_hit_tokens"] == 0
    assert s["prefill_tokens"] < eng_off.stats.summary()["prefill_tokens"]
    for (p, max_new, _), rid in zip(stream, sorted(outs_on)):
        assert outs_on[rid] == _oracle(model, p, max_new), rid


def test_chunked_prefill_matches_oracle(model):
    """A prompt longer than max_prefill_tokens is prefilled across steps
    and still matches generate() byte-for-byte."""
    rng = np.random.RandomState(23)
    eng = _engine(model, max_prefill_tokens=8, prefill_token_bucket=8)
    p = rng.randint(0, VOCAB, 30).tolist()
    rid = eng.add_request(p, max_new_tokens=6)
    outs = eng.run()
    assert outs[rid].generated == _oracle(model, p, 6)
    assert eng.stats.prefill_steps >= 4           # actually chunked


def test_engine_cow_on_diverging_followups(model):
    """Two follow-ups that extend a finished request's conversation and
    diverge inside its cached partial tail page: one copy-on-write, both
    byte-identical to generate()."""
    rng = np.random.RandomState(4)
    eng = _engine(model)
    pa = rng.randint(0, VOCAB, 11).tolist()
    ra = eng.add_request(pa, max_new_tokens=5)
    gen_a = eng.run()[ra].generated
    base = pa + gen_a[:4]
    pb, pc = base + [3], base + [7]
    rb = eng.add_request(pb, max_new_tokens=4)
    rc = eng.add_request(pc, max_new_tokens=4)
    outs = eng.run()
    assert outs[rb].generated == _oracle(model, pb, 4)
    assert outs[rc].generated == _oracle(model, pc, 4)
    assert eng.stats.summary()["cow_copies"] >= 1
    eng.blocks.check_invariants()


def test_preemption_with_cache_stays_exact_and_hits(model):
    """Small pool forces preemption; the recompute hits the cache the
    preemption just populated, and greedy outputs stay identical."""
    eng = _engine(model, num_blocks=10)
    rng = np.random.RandomState(1)
    prompts = {}
    for _ in range(8):
        p = rng.randint(0, VOCAB, rng.randint(4, 12)).tolist()
        prompts[eng.add_request(p, max_new_tokens=20)] = p
    outs = eng.run()
    assert eng.stats.preemptions > 0
    assert eng.stats.summary()["cache_hit_tokens"] > 0
    for rid, p in prompts.items():
        assert outs[rid].generated == _oracle(model, p, 20), rid
    assert eng.blocks.num_used == 0
    eng.blocks.check_invariants()


def test_summary_surfaces_cache_and_queue_metrics(model):
    eng = _engine(model)
    eng.add_request(list(range(1, 9)), max_new_tokens=4)
    eng.run()
    s = eng.summary()
    for key in ("cache_hit_tokens", "cache_miss_tokens", "prefix_hit_rate",
                "prefill_tokens_saved", "cow_copies", "cache_evictions",
                "mean_prefill_queue_depth", "max_prefill_queue_depth",
                "ttft_p50_ms", "ttft_p99_ms"):
        assert key in s, key
    assert s["ttft_p50_ms"] > 0
    assert s["block_pool"]["prefix_caching"] is True


# ---------------------------------------------------------------------------
# chunked prefill never starves running decodes
# ---------------------------------------------------------------------------

def test_long_prompt_never_stalls_running_decode():
    """ISSUE acceptance: while a 4096-token prompt prefills in chunks, a
    running sequence emits a token at EVERY engine step."""
    cfg = LlamaConfig.tiny(vocab=64, hidden=16, layers=1, heads=2, ffn=32,
                           seq=4224)
    model = LlamaForCausalLM(cfg)
    eng = LLMEngine(model, max_num_seqs=2, block_size=16,
                    max_model_len=4224, max_prefill_tokens=256,
                    prefill_token_bucket=256)
    rng = np.random.RandomState(0)
    r0 = eng.add_request(rng.randint(0, 64, 8).tolist(), max_new_tokens=40)
    eng.step()
    req0 = next(r for r in eng._running if r.rid == r0)
    r1 = eng.add_request(rng.randint(0, 64, 4096).tolist(), max_new_tokens=2)
    steps = 0
    while any(r.rid == r1 and r.cached < len(r.tokens)
              for r in list(eng._running) + list(eng._waiting)):
        before = len(req0.generated)
        eng.step()
        steps += 1
        assert len(req0.generated) == before + 1, \
            f"running decode starved at step {steps}"
        if req0.rid in eng._finished:
            break
    assert steps >= 4096 // 256 - 1               # prefill really spanned steps
    eng.run()
    assert len(eng._finished) == 2
