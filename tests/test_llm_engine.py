"""Serving engine: BlockManager invariants, continuous-batching scheduler
behaviour, and e2e greedy equivalence against generate() (CPU, the paged
kernel running in interpret mode)."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.inference import BlockManager, LLMEngine
from paddle_tpu.inference.kv_cache import NULL_BLOCK
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
# BlockManager invariants
# ---------------------------------------------------------------------------

def test_block_manager_alloc_free_roundtrip():
    bm = BlockManager(num_blocks=9, block_size=4)
    assert bm.num_free == 8                      # block 0 reserved
    assert bm.allocate("a", 10)                  # 3 pages
    assert bm.allocate("b", 4)                   # 1 page
    assert bm.num_used == 4
    # no block owned twice, null never handed out
    owned = bm.block_table("a") + bm.block_table("b")
    assert len(owned) == len(set(owned))
    assert NULL_BLOCK not in owned
    bm.free("a")
    bm.free("b")
    assert bm.num_free == 8
    assert bm.num_used == 0
    assert bm.alloc_count == 4 and bm.free_count == 4


def test_block_manager_refuses_overcommit():
    bm = BlockManager(num_blocks=5, block_size=4)   # 4 usable pages
    assert bm.allocate("a", 12)                  # 3 pages
    assert not bm.allocate("b", 8)               # needs 2, only 1 free
    assert not bm.has("b")                       # refused alloc left no state
    assert bm.num_free == 1
    assert bm.allocate("c", 3)                   # 1 page still fits
    assert bm.num_free == 0


def test_block_manager_ensure_grows_on_page_boundary():
    bm = BlockManager(num_blocks=9, block_size=4)
    bm.allocate("a", 4)                          # exactly 1 full page
    assert len(bm.block_table("a")) == 1
    assert bm.ensure("a", 5)                     # crosses into page 2
    assert len(bm.block_table("a")) == 2
    assert bm.ensure("a", 8)                     # still inside page 2
    assert len(bm.block_table("a")) == 2


def test_block_manager_ensure_failure_is_preemption_signal():
    bm = BlockManager(num_blocks=3, block_size=4)   # 2 usable pages
    bm.allocate("a", 4)
    bm.allocate("b", 4)
    assert not bm.ensure("a", 5)                 # pool exhausted
    bm.free("b")
    assert bm.ensure("a", 5)                     # freed page reused


def test_block_manager_double_alloc_raises():
    bm = BlockManager(num_blocks=5, block_size=4)
    bm.allocate("a", 4)
    with pytest.raises(ValueError):
        bm.allocate("a", 4)


def test_block_manager_padded_table_and_stats():
    bm = BlockManager(num_blocks=9, block_size=4)
    bm.allocate("a", 6)                          # 2 pages, 6 tokens
    t = bm.padded_table("a", 5)
    assert t.dtype == np.int32 and t.shape == (5,)
    assert list(t[:2]) == bm.block_table("a")
    assert all(t[2:] == NULL_BLOCK)
    s = bm.stats()
    assert s["occupancy"] == pytest.approx(2 / 8)
    assert s["fragmentation"] == pytest.approx(1 - 6 / 8)


# ---------------------------------------------------------------------------
# scheduler: admission / retirement / preemption
# ---------------------------------------------------------------------------

def test_scheduler_admission_respects_batch_cap(model):
    eng = _engine(model, max_num_seqs=2)
    rng = np.random.RandomState(0)
    for _ in range(5):
        eng.add_request(rng.randint(0, VOCAB, 6).tolist(), max_new_tokens=4)
    eng.step()
    assert len(eng._running) <= 2
    outs = eng.run()
    assert len(outs) == 5
    assert eng.stats.admitted == 5 and eng.stats.retired == 5


def test_scheduler_ragged_arrivals_mid_stream(model):
    """Requests joining while others decode are admitted into the running
    batch (continuous batching), and everyone finishes correctly."""
    eng = _engine(model)
    rng = np.random.RandomState(2)
    prompts = {}
    prompts[eng.add_request(rng.randint(0, VOCAB, 5).tolist(),
                            max_new_tokens=10)] = None
    eng.step()                                   # first request decoding
    assert len(eng._running) == 1
    for _ in range(3):                           # arrive mid-decode
        p = rng.randint(0, VOCAB, rng.randint(3, 9)).tolist()
        prompts[eng.add_request(p, max_new_tokens=6)] = p
    eng.step()
    assert len(eng._running) == 4                # all admitted immediately
    outs = eng.run()
    assert sorted(outs) == sorted(prompts)
    for rid, p in prompts.items():
        if p is not None:
            assert outs[rid].generated == _oracle(model, p, 6)


def test_scheduler_retires_on_eos(model):
    """A sequence whose greedy continuation hits eos retires early with
    the eos token included (generate()'s freeze convention mirrored)."""
    rng = np.random.RandomState(3)
    p = rng.randint(0, VOCAB, 6).tolist()
    base = _oracle(model, p, 12)
    eos = base[4]                                # force a mid-stream eos
    eng = _engine(model)
    rid = eng.add_request(p, max_new_tokens=12, eos_token_id=eos)
    outs = eng.run()
    got = outs[rid].generated
    assert outs[rid].finish_reason == "eos"
    assert got[-1] == eos and eos not in got[:-1]
    assert got == base[:got.index(eos) + 1]


def test_scheduler_preemption_requeues_and_stays_exact(model):
    """With a pool too small for the running set's growth, the engine
    preempts, requeues, recomputes — and greedy outputs stay identical."""
    eng = _engine(model, num_blocks=10)          # 9 usable pages
    rng = np.random.RandomState(1)
    prompts = {}
    for _ in range(8):
        p = rng.randint(0, VOCAB, rng.randint(4, 12)).tolist()
        prompts[eng.add_request(p, max_new_tokens=20)] = p
    outs = eng.run()
    assert eng.stats.preemptions > 0             # the pool did run out
    assert len(outs) == 8
    for rid, p in prompts.items():
        assert outs[rid].generated == _oracle(model, p, 20), rid
    # every page returned
    assert eng.blocks.num_used == 0


def test_preempted_pool_never_leaks_null_block(model):
    eng = _engine(model, num_blocks=10)
    rng = np.random.RandomState(5)
    for _ in range(6):
        eng.add_request(rng.randint(0, VOCAB, 8).tolist(), max_new_tokens=16)
    while eng.has_unfinished():
        eng.step()
        for req in eng._running:
            table = eng.blocks.block_table(req.rid)
            assert NULL_BLOCK not in table
            assert len(table) == len(set(table))


# ---------------------------------------------------------------------------
# e2e: ragged stream vs generate(), compile counts
# ---------------------------------------------------------------------------

def test_engine_matches_generate_on_ragged_stream(model):
    """ISSUE acceptance: >= 16 requests with ragged prompt lengths and
    budgets, greedy outputs byte-identical to generate(), <= 2 decode
    compiles."""
    eng = _engine(model, max_num_seqs=8, max_prefill_tokens=256,
                  prefill_token_bucket=64)
    rng = np.random.RandomState(7)
    # few distinct (len, max_new) combos keep the generate() oracle cheap
    shapes = [(4, 8), (9, 8), (13, 6)]
    prompts = {}
    for i in range(16):
        n, max_new = shapes[i % len(shapes)]
        p = rng.randint(0, VOCAB, n).tolist()
        prompts[eng.add_request(p, max_new_tokens=max_new)] = (p, max_new)
    outs = eng.run()
    assert len(outs) == 16
    for rid, (p, max_new) in prompts.items():
        assert outs[rid].generated == _oracle(model, p, max_new), rid
    assert eng.num_decode_programs <= 2
    s = eng.stats.summary()
    assert s["decode_tokens"] > 0 and s["p50_token_ms"] > 0


def test_decode_repack_after_mid_batch_retirement(model):
    """The pure-decode fast path keys its persistent host buffers on the
    packed-row LAYOUT (the rid order behind cu_seqlens), not just on
    block-table versions.  Retiring a mid-batch sequence between steps
    shifts every later row up one slot; a layout-blind repack would
    decode row i against row i+1's pages and positions.  Outputs must
    stay byte-identical to the oracle through the retirement."""
    eng = _engine(model)
    rng = np.random.RandomState(17)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (5, 7, 6)]
    budgets = [12, 3, 12]                # middle row retires first
    rids = [eng.add_request(p, max_new_tokens=mn)
            for p, mn in zip(prompts, budgets)]
    outs = eng.run()
    for rid, p, mn in zip(rids, prompts, budgets):
        assert outs[rid].generated == _oracle(model, p, mn), rid


def test_engine_sampling_deterministic_per_seed(model):
    """Temperature sampling keys depend only on (seed, token index), so a
    rerun — and any scheduling order — reproduces the stream."""
    rng = np.random.RandomState(11)
    p = rng.randint(0, VOCAB, 7).tolist()

    def run_once(extra_load):
        eng = _engine(model)
        rid = eng.add_request(p, max_new_tokens=8, temperature=0.8, seed=3)
        for _ in range(extra_load):              # perturb scheduling
            eng.add_request(rng.randint(0, VOCAB, 5).tolist(),
                            max_new_tokens=4)
        return eng.run()[rid].generated

    first = run_once(0)
    assert first == run_once(0)
    assert first == run_once(3)


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_summary_counts_launches_that_ran_the_sampled_chain(model,
                                                            temperature):
    """``sample_chain_launches`` of ``sample_launches``: the launches
    whose rows held a request with a temperature, which are the ones in
    which the step program runs its top-k, top-p and draw.  A greedy run
    has none; greedy tokens are the oracle's either way."""
    rng = np.random.RandomState(17)
    greedy_p = rng.randint(0, VOCAB, 9).tolist()
    eng = _engine(model)
    g = eng.add_request(greedy_p, max_new_tokens=10)
    eng.add_request(rng.randint(0, VOCAB, 6).tolist(), max_new_tokens=4,
                    temperature=temperature, seed=2)
    outs = eng.run()
    s = eng.summary()
    assert s["sample_launches"] == eng.launches > 0
    if temperature > 0.0:
        assert 4 <= s["sample_chain_launches"] < s["sample_launches"]
    else:
        assert s["sample_chain_launches"] == 0
    assert outs[g].generated == _oracle(model, greedy_p, 10)


def test_engine_rejects_oversized_request(model):
    eng = _engine(model)
    with pytest.raises(ValueError):
        eng.add_request(list(range(30)), max_new_tokens=60)   # > max_model_len
    with pytest.raises(ValueError):
        eng.add_request([], max_new_tokens=4)
