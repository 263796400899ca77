"""A decoder-hybrid-decoder (Mamba-1 layers with a state a sequence
beside the pages, differential attention under a window and over all,
gated memory units and cross layers on one layer's K/V) through
``LLMEngine``: small sizes on the CPU (4 query heads over 2 K/V heads of
16, d_inner 128 with 8 states, a window of 24, block 4, 12 layers),
weights from a seed.  The chunk (48, and 5 where a test says so) cuts
prompts INSIDE the convolution's 4 taps and inside the window.

The yardstick is the benchmark's plain reference
(``benchmark/references/phi4flash.py``: float32, one whole forward pass,
a ``lax.scan`` for the recurrence, its own weights from the seed),
reached the way the benchmark reaches it (``harness/spec.py`` by the
architecture's name), so these tests also hold the seam: shapes file,
builder and reference agree on every leaf."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import spec, weights as W                       # noqa: E402

from paddle_tpu.inference import LLMEngine, serving          # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as pa      # noqa: E402

SEED = 2**31 + 42
# float32 on both sides; what is left is the order of the sums (pages
# against whole masked rows, the widened query against two maps, chunks
# of a scan against the whole).  Logits here are of order 1; a state
# not carried, a tap lost at a chunk's edge or a key outside the window
# reads 1e-2 and over
TOL = 3e-4
WINDOW, BLOCK, CHUNK = 24, 4, 48


def _overlay(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _overlay(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


@pytest.fixture(scope="module")
def cfg():
    bench = spec.load_benchmark()
    with open(os.path.join(BENCH, "tests", "data",
                           "rehearsal_phi4flash.json")) as f:
        over = json.load(f)
    c = _overlay(spec.load_config(bench, "phi4-mini-flash-reasoning"),
                 over["config"])
    assert (c["sliding_window"], c["serving"]["block_size"],
            c["serving"]["max_prefill_tokens"]) == (WINDOW, BLOCK, CHUNK)
    return c


def _nonzero_biases(made, seed):
    """The biases (of the norms, ``Wqkv``, ``out_proj``, the
    convolution) are drawn as zeros; here they are not, so that every
    one is in the comparison."""
    key = jax.random.PRNGKey(seed)
    n = 0
    for group in [made["top"]] + made["layers"]:
        for name in sorted(group):
            if name.split(".")[-1] in ("ln1_b", "ln2_b", "norm_f_b", "bqkv",
                                       "bq", "bo", "conv_b"):
                group[name] = 0.2 * jax.random.normal(
                    jax.random.fold_in(key, n), group[name].shape,
                    group[name].dtype)
            n += 1
    return made


@pytest.fixture(scope="module")
def model(cfg):
    shapes = spec.load_shapes(cfg["reference"])
    builder = spec.load_builder(cfg["reference"])
    m = builder.construct(cfg)
    assert all(isinstance(p._data, jax.ShapeDtypeStruct)
               for p in m.parameters())              # nothing drawn yet
    builder.place(m, _nonzero_biases(
        W.make_all(shapes.leaves(cfg), SEED, jnp.dtype(cfg["dtype"])), 5))
    return m


class _Biased:
    """The reference with the test's nonzero biases: its weights come
    from the seed, so the biases are handed to it the way they were
    handed to the model."""

    def __init__(self, cfg):
        self.ref = spec.load_reference(cfg["reference"])
        self.cfg = cfg
        shapes = spec.load_shapes(cfg["reference"])
        self.made = _nonzero_biases(
            W.make_all(shapes.leaves(cfg), SEED, jnp.dtype(cfg["dtype"])), 5)

    def logits(self, prompt, generated, lower=None):
        seq = list(prompt) + list(generated)
        real_layer, real_top = W.make_layer, W.make_top
        W.make_layer = lambda leaves, seed, tag, dtype: \
            dict(self.made["layers"][tag])
        W.make_top = lambda leaves, seed, dtype: dict(self.made["top"])
        try:
            return self.ref.logits_at(self.cfg, SEED, [seq],
                                      [len(prompt) - 1], len(generated),
                                      256, lower=lower)[0][:len(generated)]
        finally:
            W.make_layer, W.make_top = real_layer, real_top


@pytest.fixture(scope="module")
def reference(cfg):
    return _Biased(cfg)


def _engine(model, **kw):
    kw = {"max_num_seqs": 4, "block_size": BLOCK, "max_model_len": 256,
          "max_prefill_tokens": CHUNK, "prefill_token_bucket": 8,
          "enable_prefix_caching": False, **kw}
    return LLMEngine(model, **kw)


@pytest.fixture()
def tap(monkeypatch):
    """Every launch's logits, taken where the step program hands them to
    the sampler (installed before any program of the test is built)."""
    launches = []
    # (this model's step programs end at the greedy token: the sampled
    # rows' chain is a program of its own, ``sampled_tail_apart``)
    real = serving.greedy_tokens

    def sample(logits, samp):
        jax.debug.callback(lambda l: launches.append(np.asarray(l)), logits,
                           ordered=True)
        return real(logits, samp)

    monkeypatch.setattr(serving, "greedy_tokens", sample)
    return launches


def _serve_with_logits(eng, prompts, max_new, tap):
    """Serve the prompts together; returns {rid: (generated tokens,
    logits [n generated, V] that each token was taken from)}."""
    jax.effects_barrier()
    first, applied = len(tap), []
    real_apply = eng._apply_ragged

    def apply(chunks, spec_, batch, sampled, ok, spec_ok, spec_logits,
              chunk_slots, batch_slots, dur, finished):
        rows = [(r.rid, s) for (r, n), s in zip(chunks, chunk_slots)
                if r.cached + n == len(r.tokens)]
        rows += [(r.rid, s) for r, s in zip(batch, batch_slots)]
        applied.append(rows)
        return real_apply(chunks, spec_, batch, sampled, ok, spec_ok,
                          spec_logits, chunk_slots, batch_slots, dur,
                          finished)

    eng._apply_ragged = apply
    rids = [eng.add_request(p, max_new_tokens=n)
            for p, n in zip(prompts, max_new)]
    outs = eng.run()
    jax.effects_barrier()
    eng._apply_ragged = real_apply
    launches = tap[first:]
    assert len(launches) == len(applied)
    got = {rid: [] for rid in rids}
    for lg, rows in zip(launches, applied):
        for rid, slot in rows:
            if rid in got:
                got[rid].append(lg[slot])
    return {rid: (outs[rid].generated, np.stack(got[rid])) for rid in rids}


def _prompt(n):
    return np.random.default_rng(n).integers(0, 512, n).tolist()


# prompt lengths under, at and past the convolution's taps (4), a page
# (4), the window (24) and a chunk; the chunk of 5 puts a boundary
# inside the taps and inside the window at every step
@pytest.mark.parametrize("n_prompt,n_new,chunk", [
    (1, 6, CHUNK), (3, 5, CHUNK), (4, 6, CHUNK), (23, 8, CHUNK),
    (25, 30, CHUNK), (49, 12, CHUNK), (50, 6, CHUNK), (130, 40, CHUNK),
    (37, 9, 5), (51, 4, 5)])
def test_chunked_prefill_then_decode_gives_the_references_logits(
        cfg, model, reference, tap, n_prompt, n_new, chunk):
    eng = _engine(model, max_prefill_tokens=chunk, prefill_token_bucket=8)
    nb = eng.blocks.num_blocks
    # ONE layer under the block table, three window layers, four
    # layers' state of 4 + 1 slots; K/V rows as 1 head of 32
    assert eng._kc.shape == (1, nb, 1, BLOCK, 32)
    assert eng._kw.shape == (3, eng._window_blocks, 1, BLOCK, 32)
    assert eng._sc.shape == (4, 5, 3, 128) and eng._ss.shape == (4, 5, 8, 128)
    assert eng._ss.dtype == jnp.float32
    prompt = _prompt(n_prompt)
    (gen, logits), = _serve_with_logits(eng, [prompt], [n_new], tap).values()
    assert len(gen) == n_new
    assert eng.stats.prefill_steps >= -(-n_prompt // chunk)
    want = reference.logits(prompt, gen)
    np.testing.assert_allclose(logits, want, atol=TOL, rtol=0)
    assert gen == want.argmax(-1).tolist()
    s = eng.summary()
    assert s["state_starts"] == 1 and s["state_slots"] == 0
    assert s["state_rows"] == n_prompt + n_new - 1
    assert (s["window_pages_returned"] > 0) \
        == (n_prompt + n_new - 1 >= WINDOW + BLOCK)
    eng.blocks.check_invariants()
    assert eng.blocks.num_used == eng.blocks.num_window_used == 0


def test_a_chunk_and_decode_rows_in_one_launch(cfg, model, reference, tap):
    """Short and long sequences in one queue: a chunk's scan beside
    decode rows' in one launch, rows of no tokens beside them, with the
    launch in front still in flight (the ahead pipeline is on)."""
    eng = _engine(model)
    assert eng.overlap
    rng = np.random.default_rng(8)
    lens = (70, 5, 33, 120)
    prompts = [rng.integers(0, 512, n).tolist() for n in lens]
    mixed = []
    real = eng._launch_ragged

    def launch(Tq, toks, cu, kvl, *a, **kw):
        n_q = np.diff(np.asarray(cu))[:len(kvl)]
        mixed.append((int((n_q > 1).sum()), int((n_q == 1).sum())))
        return real(Tq, toks, cu, kvl, *a, **kw)

    eng._launch_ragged = launch
    served = _serve_with_logits(eng, prompts, (25, 50, 8, 10), tap)
    for prompt, (gen, logits) in zip(prompts, served.values()):
        np.testing.assert_allclose(
            logits, reference.logits(prompt, gen), atol=TOL, rtol=0)
    assert any(c and d for c, d in mixed)      # a chunk beside decode rows
    s = eng.summary()
    assert s["launches_ahead"] > 0 and s["state_starts"] == 4
    eng.blocks.check_invariants()
    assert eng.blocks.num_used == eng.blocks.num_window_used == 0


def test_a_slot_reused_by_the_next_request_starts_from_zeros(
        cfg, model, reference, tap):
    """One batch slot, three requests one after another: each finds the
    state its predecessor left in the slot and must not read it."""
    eng = _engine(model, max_num_seqs=1)
    prompts = [_prompt(n) for n in (30, 9, 61)]
    served = _serve_with_logits(eng, prompts, (12, 20, 5), tap)
    for prompt, (gen, logits) in zip(prompts, served.values()):
        np.testing.assert_allclose(
            logits, reference.logits(prompt, gen), atol=TOL, rtol=0)
    assert eng.summary()["state_starts"] == 3
    # the slot still holds the last request's state: nothing clears it
    assert float(jnp.abs(eng._ss[:, 0]).max()) > 0


def test_preemption_recomputes_the_state_and_resumes_token_for_token(model):
    """A pool too small for both sequences: one is preempted while it
    decodes, recomputed from position 0 (which rebuilds its state from
    zeros) and must go on with the very tokens it would have made."""
    prompts = [_prompt(n) for n in (40, 44)]
    alone = []
    for p in prompts:
        eng = _engine(model)
        rid = eng.add_request(p, max_new_tokens=40)
        alone.append(eng.run()[rid].generated)
    # 21 pages of 4: both prompts fit, both grown sequences do not
    eng = _engine(model, num_blocks=33, max_model_len=96)
    rids = [eng.add_request(p, max_new_tokens=40) for p in prompts]
    done = eng.run()
    assert eng.stats.preemptions > 0
    assert [done[r].generated for r in rids] == alone
    assert eng.summary()["state_starts"] > 2           # begun again
    eng.blocks.check_invariants()


def test_a_row_dropped_from_a_launch_ahead_leaves_a_dead_state(
        cfg, model, reference, tap):
    """A request that stops on its end-of-sequence token finishes in the
    launch IN FRONT of one already dispatched with its next row: that
    row is dropped, the state it wrote into the slot is dead, and the
    request that takes the slot next starts from zeros."""
    # a prompt whose greedy continuation makes, after a few tokens, one
    # it has not made before: that one is the stop token
    for n in range(17, 40):
        probe = _engine(model)
        first = _prompt(n)
        rid = probe.add_request(first, max_new_tokens=12)
        gen = probe.run()[rid].generated
        at = [k for k in range(3, 12) if gen[k] not in gen[:k]]
        if at:
            break
    eos, gen = gen[at[0]], gen[:at[0] + 1]
    eng = _engine(model, max_num_seqs=2)
    assert eng.overlap
    a = eng.add_request(first, max_new_tokens=12, eos_token_id=eos)
    b = eng.add_request(_prompt(29), max_new_tokens=30)
    later = _prompt(33)
    c = eng.add_request(later, max_new_tokens=9)       # waits for a slot
    jax.effects_barrier()
    n0 = len(tap)
    done = eng.run()
    assert done[a].generated == gen
    assert eng.summary()["ahead_rows_dropped"] >= 1
    # the late request took the stopped one's slot: its tokens are the
    # reference's best at every position
    want = reference.logits(later, done[c].generated)
    assert done[c].generated == want.argmax(-1).tolist()
    want_b = reference.logits(_prompt(29), done[b].generated)
    assert done[b].generated == want_b.argmax(-1).tolist()
    assert len(tap) > n0


def test_the_ahead_pipeline_changes_no_token(model):
    prompts = [_prompt(n) for n in (60, 11, 37)]
    outs = []
    for overlap in (True, False):
        eng = _engine(model, overlap=overlap)
        rids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
        done = eng.run()
        outs.append([done[r].generated for r in rids])
    assert outs[0] == outs[1]


def test_padded_rows_and_segments_touch_no_state(model):
    """A launch of one live row in a bucket of 8 tokens and 4 rows: the
    slots of the rows it does not hold, and every other layer's view of
    them, are bit for bit what they were."""
    eng = _engine(model)
    eng._ss = eng._ss + 7.0                     # a mark in every slot
    eng._sc = eng._sc + 3.0
    before_s, before_c = np.asarray(eng._ss), np.asarray(eng._sc)
    rid = eng.add_request(_prompt(6), max_new_tokens=5)
    eng.run()
    after_s, after_c = np.asarray(eng._ss), np.asarray(eng._sc)
    # the request held slot 0; slots 1..3 are untouched (slot 4 is the
    # one nobody holds: rows of no tokens write there)
    np.testing.assert_array_equal(after_s[:, 1:4], before_s[:, 1:4])
    np.testing.assert_array_equal(after_c[:, 1:4], before_c[:, 1:4])
    assert (after_s[:, 0] != before_s[:, 0]).any()
    assert rid == 0


def test_the_kernels_give_what_the_xla_path_gives(model, monkeypatch):
    """The interpreted kernels (the selective scan over a launch's
    segments, the ragged kernel over the widened queries, under a
    window and under the cross layers' name) against the XLA forms,
    through the engine."""
    prompts = [_prompt(n) for n in (21, 6)]
    outs = {}
    for interpret in (None, True):
        monkeypatch.setattr(pa, "INTERPRET", interpret)
        eng = _engine(model)
        assert eng.attention_path.startswith(
            "pallas-interpret" if interpret else "xla-reference")
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        done = eng.run()
        outs[interpret] = [done[r].generated for r in rids]
    assert outs[None] == outs[True]


@pytest.mark.parametrize("option,value", [
    ("enable_prefix_caching", True), ("drafter", "ngram"),
    ("decode_window", 4), ("kv_tier", object()), ("kv_dtype", "int8"),
    ("weight_dtype", "int8"), ("tp", 2)])
def test_options_nothing_has_run_over_a_state_are_refused_by_name(
        model, option, value):
    kw = {"enable_prefix_caching": False, option: value}
    if option == "drafter":
        kw["spec_k"] = 2
    with pytest.raises(ValueError) as e:
        LLMEngine(model, max_num_seqs=4, block_size=BLOCK,
                  max_model_len=256, **kw)
    assert option in str(e.value) and "state-space layers" in str(e.value)


def test_the_state_counters_ride_on_the_launch(model):
    from paddle_tpu.profiler.trace import Tracer
    tr = Tracer(capacity=1 << 12)
    eng = _engine(model, tracer=tr)
    eng.add_request(_prompt(50), max_new_tokens=3)
    eng.run()
    launches = [dict(a) for _ph, name, _t, _d, _tid, a, _i in tr.events()
                if name == "engine.device_launch"]
    assert [a["state_starts"] for a in launches] == [1, 0, 0, 0]
    assert [a["state_rows"] for a in launches] == [48, 2, 1, 1]


# ---------------------------------------------------------------------------
# the sampled rows' chain, compiled apart (``sampled_tail_apart``)
# ---------------------------------------------------------------------------

def _mixed_sampling(eng):
    """One greedy request and two sampled ones (top-k and top-p; a
    temperature and a repetition penalty), served together."""
    rids = [eng.add_request(_prompt(21), max_new_tokens=10),
            eng.add_request(_prompt(9), max_new_tokens=10, temperature=0.9,
                            top_k=20, top_p=0.9, seed=5),
            eng.add_request(_prompt(5), max_new_tokens=10, temperature=1.3,
                            repetition_penalty=1.2, seed=7)]
    done = eng.run()
    return [done[r].generated for r in rids]


def test_the_sampled_tail_apart_draws_what_the_one_program_draws(
        model, monkeypatch):
    """A launch that holds a sampled row is followed by the tail
    program, built once: every row's token is what the step program
    that samples itself gives (same keys, same chain), the greedy
    row's too; and the packed vector keeps its finiteness flags."""
    apart = _engine(model)
    assert apart._tail_apart
    got = _mixed_sampling(apart)
    assert apart.compile_counts["sampled_tail"] == 1
    assert apart.summary()["sample_chain_launches"] > 0
    monkeypatch.setattr(type(model.config), "sampled_tail_apart", False)
    whole = _engine(model)
    assert not whole._tail_apart
    assert _mixed_sampling(whole) == got
    assert "sampled_tail" not in whole.compile_counts
    assert len({tuple(g) for g in got}) == 3


def test_greedy_traffic_never_builds_the_tail_and_no_step_program_sorts(
        model):
    """What the split is for: no token bucket's program holds the
    sampled rows' sorts, and greedy traffic launches one program a
    step."""
    eng = _engine(model)
    eng.add_request(_prompt(30), max_new_tokens=6)
    eng.run()
    assert "sampled_tail" not in eng.compile_counts
    fn, _donate = eng._make_ragged_fn(8)
    text = jax.jit(fn).lower(*eng._ragged_arg_structs(8)).as_text()
    assert "stablehlo.sort" not in text and "stablehlo.while" in text
    tail = jax.jit(serving._sampled_tail).lower(
        *[s.args for s in eng.program_specs()
          if s.name == "serving.sampled_tail"][0]).as_text()
    assert "stablehlo.sort" in tail
