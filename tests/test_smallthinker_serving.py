"""A decoder of global and sliding-window layers with ReGLU experts
through ``LLMEngine``: two page pools under two block tables, small
sizes on the CPU (window 32, block 4, two periods, 8 experts top 3,
group 7), weights from a seed.

The yardstick is the benchmark's plain reference
(``benchmark/references/smallthinker.py``: float32, one whole forward
pass, the window as a mask on the full score matrix, its own weights
from the seed), reached the way the benchmark reaches it
(``harness/spec.py`` by the architecture's name), so these tests also
hold the seam: shapes file, builder and reference agree on every leaf."""
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
from paddle_tpu.inference import layer_stack                 # noqa: E402
from paddle_tpu.models import smallthinker as M              # noqa: E402

SEED = 2**31 + 5
# float32 on both sides; what is left is the order of the sums:
# attention page by page (from the window's first page) against whole
# masked rows, a grouped product against a loop over experts.  Logits
# here are of order 1; a wrong expert, a key outside the window or a
# page given back too early reads 1e-1 and over
TOL = 2e-4
WINDOW, BLOCK = 32, 4


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
                           "rehearsal_smallthinker.json")) as f:
        over = json.load(f)
    c = _overlay(spec.load_config(bench, "smallthinker-21b-a3b-d8"),
                 over["config"])
    assert (c["sliding_window_size"], c["serving"]["block_size"]) == (
        WINDOW, BLOCK)
    assert c["num_attention_heads"] // c["num_key_value_heads"] == 7
    return c


@pytest.fixture(scope="module")
def model(cfg):
    shapes = spec.load_shapes(cfg["reference"])
    builder = spec.load_builder(cfg["reference"])
    m = builder.construct(cfg)
    assert all(isinstance(p._data, jax.ShapeDtypeStruct)
               for p in m.parameters())              # nothing drawn yet
    builder.place(m, W.make_all(shapes.leaves(cfg), SEED,
                                jnp.dtype(cfg["dtype"])))
    return m


def _engine(model, **kw):
    kw = {"max_num_seqs": 4, "block_size": BLOCK, "max_model_len": 256,
          "max_prefill_tokens": 24, "prefill_token_bucket": 8,
          "enable_prefix_caching": False, **kw}
    return LLMEngine(model, **kw)


@pytest.fixture()
def tap(monkeypatch):
    """Every launch's logits, taken where the step program hands them to
    the sampler (installed before any program of the test is built)."""
    launches = []
    real_sample = serving.sample_tokens

    def sample(logits, samp):
        jax.debug.callback(lambda l: launches.append(np.asarray(l)), logits,
                           ordered=True)
        return real_sample(logits, samp)

    monkeypatch.setattr(serving, "sample_tokens", sample)
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


def _reference_logits(cfg, prompt, generated):
    ref = spec.load_reference(cfg["reference"])
    seq = list(prompt) + list(generated)
    return ref.logits_at(cfg, SEED, [seq], [len(prompt) - 1],
                         len(generated), 256)[0]


# prompt lengths under, at and past the window; 24-token chunks, so the
# 45-token prompt's second chunk straddles the window's edge, and the
# 100-token one's third to fifth lie past it (a chunk at position p
# still needs keys from p - 31)
@pytest.mark.parametrize("n_prompt,n_new", [
    (9, 12), (20, 20), (31, 6), (32, 6), (33, 6), (45, 30), (100, 40)])
def test_chunked_prefill_then_decode_gives_the_references_logits(
        cfg, model, tap, n_prompt, n_new):
    eng = _engine(model)
    assert eng._kc.shape == (2, eng.blocks.num_blocks, 1, BLOCK, 16)
    assert eng._kw.shape == (6, eng._window_blocks, 1, BLOCK, 16)
    prompt = np.random.default_rng(n_prompt).integers(
        0, 512, n_prompt).tolist()
    (gen, logits), = _serve_with_logits(eng, [prompt], [n_new], tap).values()
    assert len(gen) == n_new
    assert eng.stats.prefill_steps >= -(-n_prompt // 24)
    want = _reference_logits(cfg, prompt, gen)
    np.testing.assert_allclose(logits, want, atol=TOL, rtol=0)
    assert gen == want.argmax(-1).tolist()
    s = eng.summary()
    assert s["moe_pairs_here"] == s["moe_pairs_all"] > 0  # all held here
    past = n_prompt + n_new > WINDOW + BLOCK
    assert (s["window_pages_returned"] > 0) == past
    assert (s["kv_pages_window"] < s["kv_pages_live"]) == past
    eng.blocks.check_invariants()
    assert eng.blocks.num_used == eng.blocks.num_window_used == 0


def test_rows_of_every_length_in_one_launch(cfg, model, tap):
    """Short and long sequences in one queue: chunks and decode rows
    under and past the window side by side, each row its own window."""
    eng = _engine(model)
    rng = np.random.default_rng(8)
    lens = (70, 5, 33, 120)
    prompts = [rng.integers(0, 512, n).tolist() for n in lens]
    served = _serve_with_logits(eng, prompts, (25, 50, 8, 10), tap)
    for prompt, (gen, logits) in zip(prompts, served.values()):
        np.testing.assert_allclose(
            logits, _reference_logits(cfg, prompt, gen), atol=TOL, rtol=0)
    eng.blocks.check_invariants()
    assert eng.blocks.num_used == eng.blocks.num_window_used == 0


def test_the_window_pool_is_the_engines_to_size(model):
    """``num_blocks`` governs the global layers alone: the window pool
    is what max_num_seqs running sequences can hold (a window, a chunk,
    one page more, each) and the null page, whatever ``num_blocks``."""
    for nb in (70, 257):
        eng = _engine(model, num_blocks=nb)
        assert eng.blocks.num_blocks == nb
        assert eng._window_blocks == 1 + 4 * (32 // 4 + 24 // 4 + 1) == 61
        assert eng._kw.shape[1] == eng.blocks.window_blocks == 61
    short = _engine(model, max_model_len=40, max_prefill_tokens=64)
    assert short._window_blocks == 1 + 4 * 10      # never over a table


def test_admit_step_abort_preempt_leave_both_pools_free(model):
    """A randomised run over a global pool too small for its requests:
    admissions, steps, aborts and preemptions; after every step both
    pools' accounts hold and a launch never holds more window pages than
    the engine's own pool; at the end every page of both is free and
    what was served is what a roomy engine serves."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 512, int(n)).tolist()
               for n in rng.integers(8, 110, 14)]
    news = [int(n) for n in rng.integers(4, 40, 14)]
    roomy = _engine(model)
    ids = [roomy.add_request(p, max_new_tokens=n)
           for p, n in zip(prompts, news)]
    outs = roomy.run()
    want = {i: outs[r].generated for i, r in enumerate(ids)}

    eng = _engine(model, num_blocks=60, max_model_len=160)
    seen = []
    real = eng._kv_pages_window

    def counted(cu, kvl):
        seen.append(real(cu, kvl))
        return seen[-1]

    eng._kv_pages_window = counted
    rid_of, aborted, todo = {}, set(), list(range(14))
    while todo or eng.has_unfinished():
        for _ in range(int(rng.integers(0, 3))):
            if todo:
                i = todo.pop(0)
                rid_of[i] = eng.add_request(prompts[i],
                                            max_new_tokens=news[i])
        if eng.has_unfinished():
            eng.step()
        if rid_of and rng.random() < 0.15:
            i = int(rng.choice(list(rid_of)))
            if i not in aborted and rid_of[i] not in eng._finished:
                eng.abort(rid_of[i])
                aborted.add(i)
        eng.blocks.check_invariants()
        assert eng.blocks.num_window_used < eng._window_blocks
    assert eng.stats.preemptions > 0 and aborted
    assert seen and max(seen) < eng._window_blocks
    outs = eng._finished
    for i, rid in rid_of.items():
        if i not in aborted:
            assert outs[rid].generated == want[i]
    assert eng.blocks.num_used == eng.blocks.num_window_used == 0
    assert eng.blocks.num_free == eng.blocks.num_blocks - 1
    assert eng.blocks.num_window_free == eng._window_blocks - 1
    s = eng.summary()
    assert s["block_pool"]["window_pages_returned"] \
        == s["window_pages_returned"] > 0


@pytest.mark.parametrize("option,value", [
    ("kv_dtype", "int8"), ("weight_dtype", "int8"), ("weight_dtype", "int4"),
    ("tp", 2), ("drafter", "ngram"), ("decode_window", 4),
    ("kv_tier", object()), ("enable_prefix_caching", True)])
def test_each_unsupported_option_raises_by_name(cfg, option, value):
    m = spec.load_builder("smallthinker").construct(cfg)
    with pytest.raises(ValueError, match=rf"^{option}=.*not supported for a "
                       "model with sliding-window layers"):
        _engine(m, **{option: value})


def test_the_launch_says_what_a_window_layer_holds(model):
    """``engine.device_launch`` carries ``kv_pages_window`` beside
    ``kv_pages_uniform`` (= ``kv_pages``), the expert counts ride on
    ``engine.sample_commit``, and the window layers' attention has a
    scope of its own in the lowered program."""
    from paddle_tpu.profiler.trace import Tracer
    tr = Tracer(capacity=1 << 14)
    eng = _engine(model, tracer=tr)
    prompt = np.random.default_rng(5).integers(0, 512, 90).tolist()
    eng.add_request(prompt, max_new_tokens=20)
    eng.run()
    launches = [dict(a) for ph, name, _t, _d, _tid, a, _i in tr.events()
                if name == "engine.device_launch"]
    assert launches and all(
        l["kv_pages_uniform"] == l["kv_pages"] >= l["kv_pages_window"] > 0
        for l in launches)
    last = launches[-1]                          # a decode row at 109 keys
    assert last["kv_pages_uniform"] == -(-109 // 4)
    assert last["kv_pages_window"] == -(-109 // 4) - (109 - 32) // 4
    commits = [dict(a) for ph, name, _t, _d, _tid, a, _i in tr.events()
               if name == "engine.sample_commit"]
    assert all(c["moe_pairs_here"] > 0 and c["moe_experts_touched"] > 0
               and c["moe_load_max"] > 0 for c in commits)
    names = [i["op_name"] for i in
             eng.program_scopes([8])["ragged_step_t8"].values()]
    assert any("/attn_window/" in n for n in names)
    assert any("/attn/" in n for n in names)
    assert any("/moe_experts/" in n for n in names)


def test_each_kind_is_traced_once_a_program(model, monkeypatch):
    """Segments [g], [w, w, w], [g], [w, w, w]: two kinds, two traced
    layers, not four."""
    traced = []
    real = layer_stack._gqa

    def spy(*a, kind):
        traced.append(kind)
        return real(*a, kind=kind)

    monkeypatch.setattr(layer_stack, "ATTENTION", {
        **layer_stack.ATTENTION,
        "gqa_window": lambda *a: spy(*a, kind="gqa_window"),
        "gqa_nope": lambda *a: spy(*a, kind="gqa_nope")})
    eng = _engine(model)
    eng._get_ragged_prog(8).lower(*eng._ragged_arg_structs(8))
    assert sorted(traced) == ["gqa_nope", "gqa_window"]


@pytest.mark.parametrize("kind", ["window", "dense"])
def test_the_first_program_is_lowered_once(model, kind):
    """The pools are committed to the device at construction, as a
    program's outputs are: the first program launched meets the same
    arguments at its next launch and is not lowered a second time (on
    the chip that second lowering fell inside the measured window
    whenever the first bucket warmed was a rare one)."""
    from jax._src import monitoring
    if kind == "dense":
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        eng = LLMEngine(LlamaForCausalLM(LlamaConfig.tiny(
            vocab=97, hidden=32, layers=2, heads=4, ffn=64, seq=64)),
            max_num_seqs=4, block_size=8, max_model_len=64,
            max_prefill_tokens=32, prefill_token_bucket=8,
            enable_prefix_caching=False)
    else:
        eng = _engine(model, max_prefill_tokens=32)
    compiles = []

    def on(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(eng.launches)

    monitoring.register_event_duration_secs_listener(on)
    try:
        for n in (12, 30, 13, 29):      # buckets 16, 32, 16, 32
            eng.add_request(list(range(1, n + 1)), max_new_tokens=1)
            eng.run()
    finally:
        monitoring.unregister_event_duration_listener(on)
    assert eng.launches == 4
    # a launch's program compiles while the launch is being counted
    assert sorted(set(compiles)) == [1, 2], compiles


def test_the_dense_models_tables_and_pools_are_untouched():
    """A dense decoder has one block table a launch and no window pool;
    its BlockManager keeps no window list."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    eng = LLMEngine(LlamaForCausalLM(LlamaConfig.tiny(
        vocab=97, hidden=32, layers=2, heads=4, ffn=64, seq=64)),
        max_num_seqs=4, block_size=8, max_model_len=64)
    assert eng._bt_shape == (5, 8) and len(eng._pools()) == 2
    assert eng._kw is None and not eng._windowed and eng._scanned
    assert eng._hd == 8 and eng.blocks.window == 0
    rid = eng.add_request(list(range(1, 30)), max_new_tokens=4)
    eng.run()
    s = eng.summary()
    assert "kv_pages_window" not in s and "window_pages_returned" not in s
    assert "window_blocks" not in s["block_pool"]
    eng.blocks.check_invariants()
    assert rid in eng._finished


def test_the_model_draws_in_its_own_type_and_forward_agrees():
    """The CLI's way: weights drawn leaf by leaf in the served type; the
    model's own whole-sequence pass agrees with what the engine serves,
    past the window."""
    c = M.SmallThinkerConfig.tiny()
    m = M.SmallThinkerForCausalLM(c, dtype="bfloat16")
    assert {str(p._data.dtype) for p in m.parameters()} == {"bfloat16"}
    assert c.layer_kinds()[:5] == [("gqa_nope", "moe_reglu")] + [
        ("gqa_window", "moe_reglu")] * 3 + [("gqa_nope", "moe_reglu")]
    m = M.SmallThinkerForCausalLM(c, dtype="float32", seed=3)
    eng = _engine(m)
    prompt = np.random.default_rng(0).integers(0, 96, 50).tolist()
    rid = eng.add_request(prompt, max_new_tokens=20)
    toks = eng.run()[rid].token_ids
    logits = np.asarray(m(jnp.asarray([toks]))._data[0])
    assert toks[50:] == logits[49:69].argmax(-1).tolist()
    with pytest.raises(ValueError, match="window layer without rotary"):
        M.SmallThinkerConfig.tiny(layers=4).__class__(
            num_hidden_layers=4, sliding_window_layout=[0, 1, 1, 1],
            rope_layout=[0, 0, 1, 1])
