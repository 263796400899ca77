"""Serving engine: BlockManager invariants, continuous-batching scheduler
behaviour, and e2e greedy equivalence against generate() (CPU, the paged
kernel running in interpret mode)."""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import BlockManager, LLMEngine, serving
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


# ---------------------------------------------------------------------------
# a launch's results reach the host in one array, on its way since dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,n_counts", [((12,), 0), ((12,), 4),
                                            ((3, 4), 0)])
def test_pack_and_unpack_are_inverse(shape, n_counts):
    """``_pack_results`` on the device, ``_unpack_results`` on the host:
    a step's [Lq] rows with and without expert counts behind them, a
    decode window's [K, B] grids; flags that are False come back False."""
    rng = np.random.RandomState(3)
    sampled = rng.randint(0, 64000, shape).astype(np.int32)
    fin = rng.rand(*shape) < 0.5
    assert fin.any() and not fin.all()
    counts = rng.randint(0, 9000, n_counts).astype(np.int32)
    packed = serving._pack_results(jnp.asarray(sampled), jnp.asarray(fin),
                                   jnp.asarray(counts) if n_counts else None)
    assert packed.dtype == jnp.int32
    assert packed.shape == (2 * sampled.size + n_counts,)
    got = serving._unpack_results(np.asarray(packed), shape)
    assert got[1].dtype == np.bool_
    for g, w in zip(got, (sampled, fin, counts)):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("kind", ["mixed", "decode_only", "padding_rows"])
def test_the_packed_vector_holds_the_launchs_results(model, launch_results,
                                                     kind):
    """What the host reads of a launch is that launch's ``sampled`` and
    finiteness flags, slice for slice: in a launch with a chunk beside
    decode rows, in one of decode rows alone, in one with rows to
    spare; and ``sampled`` itself still comes back first, for the launch
    behind to take on the device."""
    rng = np.random.RandomState(41)
    n_req = 4 if kind == "decode_only" else 2
    eng = _engine(model, max_prefill_tokens=8, prefill_token_bucket=8)
    prompts = [rng.randint(0, VOCAB, n).tolist()
               for n in (5, 7, 6, 4)[:n_req]]
    rids = [eng.add_request(p, max_new_tokens=9) for p in prompts]
    late = rng.randint(0, VOCAB, 19).tolist()
    n = 0
    while eng.has_unfinished():
        eng.step()
        n += 1
        if n == 3 and kind == "mixed":
            rids.append(eng.add_request(late, max_new_tokens=3))
    Lq = eng._Lq
    assert len(launch_results) == eng.launches >= 9
    assert kind in {rec["kind"] for rec in launch_results}
    for rec in launch_results:
        sampled, packed = rec["front"]
        dev_sampled, dev_fin = rec["parts"]
        assert packed.dtype == jnp.int32 and packed.shape == (2 * Lq,)
        toks, ok, counts = serving._unpack_results(np.asarray(packed), (Lq,))
        np.testing.assert_array_equal(toks, dev_sampled)
        np.testing.assert_array_equal(toks, np.asarray(sampled))
        np.testing.assert_array_equal(ok, dev_fin)
        assert not counts.size
    assert eng.summary()["host_round_trips"] == eng.launches


class _Recorded:
    """Stands in for the device array a ticket carries to ``_complete``:
    says what was asked of it, in order, and passes each on."""

    def __init__(self, arr, log, ready=None):
        self.arr, self.log, self.ready = arr, log, ready

    def copy_to_host_async(self):
        self.log.append("copy_to_host_async")
        self.arr.copy_to_host_async()

    def is_ready(self):
        self.log.append("is_ready")
        return self.arr.is_ready() if self.ready is None else self.ready

    def __array__(self, dtype=None, copy=None):
        self.log.append("read")
        return np.asarray(self.arr)


def _record_launches(eng, monkeypatch, ready=lambda step: None):
    """Each launch's packed vector goes to the engine as a ``_Recorded``
    (``ready(step)``: what it answers ``is_ready``, None for the
    truth); every entry to ``_complete`` is noted in the log of the
    ticket it completes.  Returns {step id: log}."""
    logs = {}
    real_call, real_complete = eng._call_program, eng._complete

    def call(prog, host_args, bucket):
        front = list(real_call(prog, host_args, bucket))
        at = len(front) > 1           # a step: [sampled, packed]
        log = logs[eng.launches] = []
        front[at] = _Recorded(front[at], log, ready(eng.launches))
        return tuple(front)

    def complete(*a, **k):
        logs[eng._inflight.step].append("_complete")
        return real_complete(*a, **k)

    monkeypatch.setattr(eng, "_call_program", call)
    monkeypatch.setattr(eng, "_complete", complete)
    return logs


@pytest.mark.parametrize("kw", [{"overlap": True}, {"overlap": False},
                                {"decode_window": 3}],
                         ids=["ahead", "synchronous", "decode_window"])
def test_one_read_a_launch_and_its_copy_starts_at_dispatch(model,
                                                           monkeypatch, kw):
    """The launch asks for the copy to the host before anyone completes
    it; ``_complete`` asks whether the result is there and reads it,
    once; nothing else touches it.  The K-step window goes the same
    way."""
    rng = np.random.RandomState(43)
    eng = _engine(model, **kw)
    logs = _record_launches(eng, monkeypatch)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (6, 9)]
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    outs = eng.run()
    assert len(logs) == eng.launches >= 4
    assert all(log == ["copy_to_host_async", "_complete", "is_ready", "read"]
               for log in logs.values())
    if "decode_window" in kw:
        # some launches were windows: fewer round trips than tokens
        assert eng.compile_counts["scan"] == 1
        assert eng.summary()["host_round_trips"] == eng.launches < 16
    for rid, p in zip(rids, prompts):
        assert outs[rid].generated == _oracle(model, p, 8)


def test_reads_ready_counts_the_results_that_were_there(model, monkeypatch):
    """``reads_ready``: completions that found the execution ended.  It
    never passes ``launches``, and ``engine.block_on_result`` says the
    same of each launch."""
    from paddle_tpu.profiler.trace import Tracer
    eng = _engine(model)
    tr = eng.tracer = Tracer()
    logs = _record_launches(eng, monkeypatch, ready=lambda step: step % 3 > 0)
    eng.add_request([5, 3, 9, 2, 7], max_new_tokens=10)
    eng.add_request([8, 1, 4], max_new_tokens=6)
    eng.run()
    s = eng.summary()
    want = {step: step % 3 > 0 for step in logs}
    assert 0 < s["reads_ready"] == sum(want.values()) < s["launches"]
    said = {e[5]["step"]: e[5]["ready"] for e in tr.events()
            if e[1] == "engine.block_on_result"}
    assert said == want
    # with nobody standing in, the truth: never more than the launches
    eng = _engine(model)
    eng.add_request([5, 3, 9, 2, 7], max_new_tokens=10)
    eng.run()
    s = eng.summary()
    assert 0 <= s["reads_ready"] <= s["launches"] == 10


# the parent's tokens (PR 37's tree, commit 1a9155d), from this drive
_PARENT_FREE = [
    ("length", [1, 6, 23, 91, 87, 10, 86, 39, 86, 75, 20, 23, 67, 86]),
    ("aborted", [33, 20, 41, 36, 36]),
    ("length", [32, 51, 58, 51, 58, 51, 58, 51, 58]),
    ("length", [86, 66, 40, 21, 6, 23, 91, 50, 27, 37, 57, 80]),
    ("length", [8, 18, 68, 96, 25, 80, 58])]


def _seeded_serve(model, eos=None):
    """Five requests over 18 launches: prompts that enter in chunks of
    8 beside decode rows, arrivals mid-stream, an abort of a row the
    launch in flight holds and, with ``eos`` (a token of request 0's
    free run), a stop the host learns of when the launch behind already
    carries the row (``ticket.dropped``)."""
    rng = np.random.RandomState(38)
    prompts = [rng.randint(0, VOCAB, n).tolist() for n in (21, 9, 13, 6, 17)]
    eng = _engine(model, max_prefill_tokens=8, prefill_token_bucket=8)
    rids = [eng.add_request(prompts[0], max_new_tokens=14, eos_token_id=eos),
            eng.add_request(prompts[1], max_new_tokens=10)]
    outs, n = {}, 0
    while eng.has_unfinished():
        for o in eng.step():
            outs[o.rid] = o
        n += 1
        if n == 3:
            rids.append(eng.add_request(prompts[2], max_new_tokens=9))
        if n == 6:
            rids.append(eng.add_request(prompts[3], max_new_tokens=12))
        if n == 9:
            assert rids[1] in eng._inflight.slot_of
            outs[rids[1]] = eng.abort(rids[1])
            rids.append(eng.add_request(prompts[4], max_new_tokens=7))
    return eng, [(outs[r].finish_reason, list(outs[r].generated))
                 for r in rids]


@pytest.mark.parametrize("stop", [False, True], ids=["free", "stop_token"])
def test_served_tokens_are_the_parents(stop):
    """Only WHEN the bytes travel changed: a seeded model serves, token
    for token, what the tree before PR 38 served (frozen above), through
    chunks, decode rows, an abort in flight and a dropped row."""
    paddle.seed(38)
    model = LlamaForCausalLM(CFG)
    want = list(_PARENT_FREE)
    if stop:
        # the parent's stop run is its free run cut at the stop token
        want[0] = ("eos", want[0][1][:4])
    eng, got = _seeded_serve(model, eos=want[0][1][3] if stop else None)
    assert got == want
    s = eng.summary()
    assert (s["launches"], s["launches_ahead"]) == (18, 16)
    assert s["ahead_rows_dropped"] == int(stop)
    assert 0 <= s["reads_ready"] <= s["launches"]
    assert eng.blocks.num_used == 0
