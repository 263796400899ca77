"""Speculative decoding: BlockManager.truncate rollback semantics, the
drafters, rejection-sampling exactness, and e2e greedy byte-identity of
spec-on vs spec-off vs generate() — including streams that force
rollbacks, preemptions, and the sampling LogitProcessor chain."""
import numpy as np
import pytest

import paddle_tpu

import jax.numpy as jnp

from paddle_tpu.inference import (BlockManager, DraftModelDrafter,
                                  LLMEngine, NGramDrafter)
from paddle_tpu.inference.kv_cache import BlockPoolExhausted
from paddle_tpu.inference.spec_decode import Drafter, verify_and_accept
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

VOCAB = 97
CFG = LlamaConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=4, ffn=64,
                       seq=64)


@pytest.fixture(scope="module")
def model():
    # the weights these tests were written against: the draw depends on
    # the process's RNG state, which under several workers is whatever
    # the worker's earlier files left
    paddle_tpu.seed(0)
    return LlamaForCausalLM(CFG)


def _oracle(model, prompt, max_new, temperature=0.0, seed=0, eos=None,
            **kw):
    out = model.generate(jnp.asarray([prompt], jnp.int32),
                         max_new_tokens=max_new, temperature=temperature,
                         seed=seed, eos_token_id=eos, **kw)
    return np.asarray(out._data)[0, len(prompt):].tolist()


def _engine(model, **kw):
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("max_prefill_tokens", 128)
    kw.setdefault("prefill_token_bucket", 32)
    return LLMEngine(model, **kw)


# ---------------------------------------------------------------------------
# BlockManager.truncate: pages, refcounts, hash scrubbing
# ---------------------------------------------------------------------------

def test_truncate_releases_tail_pages():
    bm = BlockManager(10, 4, enable_prefix_caching=False)
    assert bm.allocate("a", 14)                   # 4 pages
    assert bm.truncate("a", 6) == 2               # back to 2 pages
    assert len(bm.block_table("a")) == 2
    assert bm.truncate("a", 6) == 0               # no-op
    assert bm.ensure("a", 14)                     # regrow after rollback
    assert len(bm.block_table("a")) == 4
    bm.check_invariants()


def test_truncate_errors():
    bm = BlockManager(10, 4, enable_prefix_caching=True)
    with pytest.raises(ValueError, match="unknown"):
        bm.truncate("ghost", 0)
    bm.acquire("a", [1, 2, 3, 4, 5])
    with pytest.raises(ValueError, match="-1"):
        bm.truncate("a", -1)
    with pytest.raises(ValueError):
        bm.truncate("a", 99)                      # beyond the table
    bm.check_invariants()


def test_truncate_scrubs_private_page_hashes():
    """Roll a committed full page back, rewrite its slots with different
    tokens: the ORIGINAL content hash must be gone — match_prefix must
    not serve rolled-back K/V to a later request."""
    bm = BlockManager(12, 4, enable_prefix_caching=True)
    ids = list(range(8))
    bm.acquire("a", ids)
    bm.commit_prefill("a", 8)                     # pages [0:4), [4:8)
    assert bm.truncate("a", 6) == 0               # mid page 2: no page drop
    # the rolled-back page-2 hash must be unregistered even though the
    # page itself stays in the table (its tail slots will be rewritten)
    bm.commit_decode_token("a", 60)               # rewrite slot 6
    bm.commit_decode_token("a", 61)               # rewrite slot 7 -> full
    bm.free("a")
    # original 8-token chain: only the first page may match now
    assert bm.match_prefix(ids + [99]) == 4
    # the rewritten chain is servable
    assert bm.match_prefix(ids[:6] + [60, 61, 99]) == 8
    bm.check_invariants()


def test_truncate_shared_page_never_serves_rolled_back_kv():
    """Truncating into a SHARED page keeps the other owner's content
    registered and valid; the truncating sequence's rewrites go through
    copy-on-write, so match_prefix keeps serving the ORIGINAL bytes for
    the original chain and the NEW bytes for the new chain."""
    bm = BlockManager(12, 4, enable_prefix_caching=True)
    ids = list(range(8))
    bm.acquire("a", ids)
    bm.commit_prefill("a", 8)
    bm.free("a")                                  # park both pages
    assert bm.acquire("b", ids + [50]) == 8       # shares both pages
    assert bm.acquire("c", ids + [70]) == 8
    shared = bm.block_table("b")[1]
    assert shared == bm.block_table("c")[1]
    # b rolls back into the shared page (speculative rejection)
    bm.truncate("b", 6)
    # shared page still registered: c's (and the cache's) content is valid
    assert bm.match_prefix(ids + [99]) >= 8 or bm.match_prefix(ids) == 4
    # b's rewrite must copy first — never clobber the shared bytes
    cw = bm.cow_if_shared("b", 6)
    assert cw is not None and cw[0] == shared
    assert bm.block_table("b")[1] != shared
    bm.commit_decode_token("b", 60)
    bm.commit_decode_token("b", 61)
    bm.free("c")
    bm.free("b")
    # both chains servable, each with its own content
    assert bm.match_prefix(ids + [99]) == 8
    assert bm.match_prefix(ids[:6] + [60, 61, 99]) == 8
    bm.check_invariants()


def test_truncate_random_interleavings_hold_invariants():
    """The PR-2 randomized pool fuzz, now with truncate in the op mix:
    refcounts, free/cached/live partition and hash maps stay coherent
    after every operation."""
    for seed in range(4):
        rng = np.random.RandomState(200 + seed)
        bm = BlockManager(num_blocks=17, block_size=4,
                          enable_prefix_caching=True)
        prefixes = [rng.randint(0, 50, rng.randint(4, 13)).tolist()
                    for _ in range(3)]
        live = {}                     # sid -> [ids, valid]
        sid_next = 0
        for _ in range(400):
            op = rng.randint(0, 5)
            if op == 0 and len(live) < 6:               # admit
                ids = list(prefixes[rng.randint(3)]) \
                    + rng.randint(0, 50, rng.randint(1, 6)).tolist()
                sid = sid_next
                sid_next += 1
                hit = bm.acquire(sid, ids)
                if hit is None:
                    if live:
                        victim = next(iter(live))
                        bm.free(victim)
                        live.pop(victim)
                else:
                    live[sid] = [list(ids), hit]
            elif op == 1 and live:                      # prefill chunk
                sid = list(live)[rng.randint(len(live))]
                ids, valid = live[sid]
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
                ids, valid = live[sid]
                if valid == len(ids) and bm.ensure(sid, valid + 1):
                    try:
                        bm.cow_if_shared(sid, valid)
                    except BlockPoolExhausted:
                        continue
                    tok = int(rng.randint(0, 50))
                    bm.commit_decode_token(sid, tok)
                    live[sid][0] = ids + [tok]
                    live[sid][1] = valid + 1
            elif op == 3 and live:                      # speculative window
                # grow for K drafts then roll back to a random point, the
                # exact shape of a verify round's ensure + truncate
                sid = list(live)[rng.randint(len(live))]
                ids, valid = live[sid]
                if valid == len(ids):
                    k = rng.randint(1, 5)
                    if bm.ensure(sid, valid + k + 1):
                        keep = valid + rng.randint(0, k + 1)
                        bm.truncate(sid, keep)
                        # ids unchanged: nothing past `valid` committed
            elif op == 4 and live:                      # retire/preempt
                sid = list(live)[rng.randint(len(live))]
                bm.free(sid)
                live.pop(sid)
            bm.check_invariants()
        for sid in list(live):
            bm.free(sid)
        bm.check_invariants()
        assert bm.num_used == 0


# ---------------------------------------------------------------------------
# drafters
# ---------------------------------------------------------------------------

def test_ngram_drafter_prompt_lookup():
    d = NGramDrafter(max_ngram=3, min_ngram=1)
    # trailing [2, 3] occurred earlier, followed by [4, 2]
    drafts, q = d.propose(0, [1, 2, 3, 4, 2, 3], k=2)
    assert drafts == [4, 2] and q is None
    # longest n-gram wins: 3-gram [2,3,4] beats shorter matches
    drafts, _ = d.propose(0, [9, 2, 3, 4, 7, 1, 2, 3, 4], k=3)
    assert drafts == [7, 1, 2]
    # no repeated suffix anywhere: no proposal
    assert d.propose(0, [1, 2, 3, 4, 5], k=4) == ([], None)
    # k caps the continuation length
    drafts, _ = d.propose(0, [5, 6, 7, 8, 5, 6], k=1)
    assert drafts == [7]


def test_ngram_drafter_is_stateless_hooks_are_noops():
    d = NGramDrafter()
    d.commit(0, 10)
    d.release(0)                                  # never raises


# ---------------------------------------------------------------------------
# rejection-sampling acceptance (host math)
# ---------------------------------------------------------------------------

def _rows(*argmaxes, V=7):
    lg = np.full((len(argmaxes), V), -2.0, np.float32)
    for i, a in enumerate(argmaxes):
        lg[i, a] = 3.0
    return lg


def test_accept_greedy_all_and_bonus():
    lg = _rows(4, 1, 6, 2)                        # row 3 is the bonus
    n, emitted = verify_and_accept(lg, [4, 1, 6])
    assert n == 3 and emitted == [4, 1, 6, 2]


def test_accept_greedy_first_rejection_emits_argmax():
    lg = _rows(4, 1, 6, 2)
    n, emitted = verify_and_accept(lg, [4, 5, 6])  # draft 5 != argmax 1
    assert n == 1 and emitted == [4, 1]


def test_accept_sampled_matches_target_distribution():
    """One-hot q: each emitted token must be distributed exactly as the
    target's softmax regardless of the draft — accept + residual resample
    together reconstruct p."""
    rng0 = np.random.RandomState(0)
    V = 5
    lg = rng0.randn(2, V).astype(np.float32) * 1.5
    e = np.exp(lg[0] - lg[0].max())
    p = e / e.sum()
    counts = np.zeros(V)
    N = 4000
    for t in range(N):
        rng = np.random.Generator(np.random.Philox(key=[7, t]))
        _, emitted = verify_and_accept(lg, [2], temperature=1.0, rng=rng)
        counts[emitted[0]] += 1
    freq = counts / N
    # 4-sigma binomial tolerance per token
    tol = 4 * np.sqrt(p * (1 - p) / N) + 1e-3
    assert np.all(np.abs(freq - p) <= tol), (freq, p)


def test_accept_sampled_respects_q_distribution():
    """Explicit q: a draft the proposer was certain about but the target
    dislikes is mostly rejected; the resample avoids the draft token via
    the residual max(p - q, 0)."""
    V = 4
    lg = np.zeros((2, V), np.float32)
    lg[0] = [3.0, 0.0, 0.0, 0.0]                  # target wants token 0
    q = np.zeros((1, V), np.float32)
    q[0, 3] = 1.0                                 # proposer was sure of 3
    rejects = 0
    N = 800
    for t in range(N):
        rng = np.random.Generator(np.random.Philox(key=[9, t]))
        n, emitted = verify_and_accept(lg, [3], q_dists=q,
                                       temperature=1.0, rng=rng)
        if n == 0:
            rejects += 1
            assert emitted[0] != 3                # residual zeroed q's mass
    e = np.exp(lg[0] - lg[0].max())
    p3 = (e / e.sum())[3]
    assert rejects / N == pytest.approx(1 - p3, abs=0.05)


# ---------------------------------------------------------------------------
# e2e: spec-on == spec-off == generate(), greedy
# ---------------------------------------------------------------------------

def _spec_stream(rng):
    """16 ragged requests; half repetitive (prompt-lookup should win),
    half random (drafts mostly rejected -> rollbacks)."""
    reqs = []
    for i in range(16):
        if i % 2 == 0:
            motif = rng.randint(0, VOCAB, rng.randint(2, 4)).tolist()
            p = (motif * 8)[: rng.randint(6, 14)]
        else:
            p = rng.randint(0, VOCAB, rng.randint(4, 12)).tolist()
        reqs.append((p, int(rng.randint(8, 24))))
    return reqs


def _run_stream(model, reqs, **kw):
    eng = _engine(model, **kw)
    rids = [eng.add_request(p, max_new_tokens=mn) for p, mn in reqs]
    outs = eng.run()
    eng.blocks.check_invariants()
    return [outs[r].generated for r in rids], eng


def test_spec_stream_byte_identical_greedy(model):
    """ISSUE acceptance: ragged 16-request stream, spec on vs off vs
    generate() — byte-identical greedy output, with real acceptances AND
    real rollbacks in the stream."""
    reqs = _spec_stream(np.random.RandomState(21))
    off, _ = _run_stream(model, reqs)
    on, eng = _run_stream(model, reqs, drafter="ngram", spec_k=4)
    assert on == off
    s = eng.stats
    assert s.draft_proposed > 0
    assert s.draft_accepted > 0                   # speculation really won
    assert s.rollback_tokens > 0                  # and really rolled back
    assert s.verify_steps > 0
    for (p, mn), got in zip(reqs[:6], on[:6]):    # spot-check vs oracle
        assert got == _oracle(model, p, mn)


def test_spec_stream_with_preemption_stays_exact(model):
    """Tight pool: speculation's extra pages + decode growth force
    preemptions; rolled-back and recomputed sequences still match the
    spec-off stream byte for byte."""
    reqs = _spec_stream(np.random.RandomState(33))[:8]
    off, _ = _run_stream(model, reqs, num_blocks=12)
    on, eng = _run_stream(model, reqs, num_blocks=12, drafter="ngram",
                          spec_k=4)
    assert on == off
    assert eng.stats.preemptions > 0
    assert eng.blocks.num_used == 0


def test_spec_with_prefix_cache_off_stays_exact(model):
    reqs = _spec_stream(np.random.RandomState(5))[:8]
    off, _ = _run_stream(model, reqs, enable_prefix_caching=False)
    on, eng = _run_stream(model, reqs, enable_prefix_caching=False,
                          drafter="ngram", spec_k=4)
    assert on == off
    assert eng.stats.draft_proposed > 0


def test_spec_respects_eos_inside_draft_window(model):
    """eos emitted mid-draft-window cuts the emission exactly as plain
    decode would: the eos lands last, nothing after it leaks out."""
    rng = np.random.RandomState(3)
    motif = rng.randint(0, VOCAB, 3).tolist()
    p = (motif * 4)[:10]
    base = _oracle(model, p, 16)
    eos = base[5]
    eng = _engine(model, drafter="ngram", spec_k=4)
    rid = eng.add_request(p, max_new_tokens=16, eos_token_id=eos)
    outs = eng.run()
    got = outs[rid].generated
    assert outs[rid].finish_reason == "eos"
    assert got[-1] == eos and eos not in got[:-1]
    assert got == base[:base.index(eos) + 1]


def test_spec_sampled_reproducible_and_well_formed(model):
    """Sampled speculation: the host rejection RNG is keyed by (seed,
    position), so a rerun reproduces the stream exactly."""
    rng = np.random.RandomState(13)
    motif = rng.randint(0, VOCAB, 3).tolist()
    p = (motif * 5)[:12]

    def once():
        eng = _engine(model, drafter="ngram", spec_k=4)
        rid = eng.add_request(p, max_new_tokens=12, temperature=0.8,
                              seed=11)
        return eng.run()[rid].generated

    first = once()
    assert len(first) == 12
    assert first == once()


def test_spec_auto_disable_on_hopeless_drafter(model):
    """A drafter that proposes garbage trips the acceptance floor: the
    request flips to plain decode (spec_disabled) and output stays
    exact."""

    class WrongDrafter(Drafter):
        def propose(self, rid, context, k):
            return [(context[-1] + 1) % VOCAB] * k, None

    reqs = [(np.random.RandomState(9).randint(0, VOCAB, 8).tolist(), 24)]
    off, _ = _run_stream(model, reqs)
    on, eng = _run_stream(model, reqs, drafter=WrongDrafter(), spec_k=4,
                          spec_accept_floor=0.9, spec_window=8)
    assert on == off
    assert eng.stats.spec_disables >= 1
    assert eng.stats.accept_rate() < 0.9


def test_draft_model_drafter_self_draft(model):
    """Draft model == target model: greedy drafts are the target's own
    argmax stream, so (numerical ties aside) every draft is accepted and
    output still matches plain decode exactly."""
    drafter = DraftModelDrafter(model, block_size=8, max_model_len=64,
                                capacity=4)
    reqs = _spec_stream(np.random.RandomState(17))[:4]
    off, _ = _run_stream(model, reqs)
    on, eng = _run_stream(model, reqs, drafter=drafter, spec_k=3)
    assert on == off
    s = eng.stats
    assert s.draft_proposed > 0
    assert s.draft_accepted / s.draft_proposed > 0.9
    # the drafter's own pool drained cleanly
    assert drafter.engine.blocks.num_used == 0


# ---------------------------------------------------------------------------
# LogitProcessor chain wired through add_request
# ---------------------------------------------------------------------------

def test_top_k1_is_greedy(model):
    rng = np.random.RandomState(41)
    p = rng.randint(0, VOCAB, 9).tolist()
    eng = _engine(model)
    rid = eng.add_request(p, max_new_tokens=8, temperature=1.0, top_k=1)
    assert eng.run()[rid].generated == _oracle(model, p, 8)


def test_tiny_top_p_is_greedy(model):
    rng = np.random.RandomState(43)
    p = rng.randint(0, VOCAB, 9).tolist()
    eng = _engine(model)
    rid = eng.add_request(p, max_new_tokens=8, temperature=1.0,
                          top_p=1e-6)
    assert eng.run()[rid].generated == _oracle(model, p, 8)


def test_repetition_penalty_matches_generate(model):
    rng = np.random.RandomState(47)
    p = rng.randint(0, VOCAB, 9).tolist()
    want = _oracle(model, p, 10, repetition_penalty=1.8)
    eng = _engine(model)
    rid = eng.add_request(p, max_new_tokens=10, repetition_penalty=1.8)
    assert eng.run()[rid].generated == want
    # and the greedy stream DOES differ from the unpenalized one
    # (otherwise this test proves nothing)
    assert want != _oracle(model, p, 10)


def test_repetition_penalty_with_speculation_matches_generate(model):
    """The verify path applies the penalty through the host chain with an
    incrementally-updated seen mask — same bytes as generate()."""
    rng = np.random.RandomState(53)
    motif = rng.randint(0, VOCAB, 3).tolist()
    p = (motif * 4)[:10]
    want = _oracle(model, p, 12, repetition_penalty=1.5)
    eng = _engine(model, drafter="ngram", spec_k=4)
    rid = eng.add_request(p, max_new_tokens=12, repetition_penalty=1.5)
    assert eng.run()[rid].generated == want


def test_sampling_params_validated(model):
    eng = _engine(model)
    with pytest.raises(ValueError):
        eng.add_request([1, 2], top_p=0.0)
    with pytest.raises(ValueError):
        eng.add_request([1, 2], top_k=-1)
    with pytest.raises(ValueError):
        eng.add_request([1, 2], repetition_penalty=0.0)
