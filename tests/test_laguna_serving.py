"""A decoder whose full and sliding-window layers differ in their query
heads and their rotary, with a gated attention output, a dense first
layer and small experts beside a shared one, through ``LLMEngine``: two
page pools under two block tables, small sizes on the CPU (6 and 8 query
heads over 2 K/V heads, window 32 SHORTER than the 48-token chunk, block
4, 7 layers, 16 experts top 4), weights from a seed.

The yardstick is the benchmark's plain reference
(``benchmark/references/laguna.py``: float32, one whole forward pass,
the window as a mask, its own weights from the seed, its own YaRN),
reached the way the benchmark reaches it (``harness/spec.py`` by the
architecture's name), so these tests also hold the seam: shapes file,
builder and reference agree on every leaf."""
import dataclasses
import json
import math
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
from paddle_tpu.models import laguna as M                    # noqa: E402
from paddle_tpu.models import mla_moe                        # noqa: E402

SEED = 2**31 + 35
# float32 on both sides; what is left is the order of the sums (pages
# against whole masked rows, a grouped product against a loop over
# experts).  Logits here are of order 1; a missing gate, a whole-head
# rotation or a key outside the window reads 1e-2 and over
TOL = 2e-4
WINDOW, BLOCK, CHUNK = 32, 4, 48


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
                           "rehearsal_laguna.json")) as f:
        over = json.load(f)
    c = _overlay(spec.load_config(bench, "laguna-xs2-d7"), over["config"])
    assert (c["sliding_window"], c["serving"]["block_size"],
            c["serving"]["max_prefill_tokens"]) == (WINDOW, BLOCK, CHUNK)
    assert c["num_attention_heads_per_layer"][:2] == [6, 8]
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
          "max_prefill_tokens": CHUNK, "prefill_token_bucket": 8,
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


def _prompt(n):
    return np.random.default_rng(n).integers(0, 512, n).tolist()


# prompt lengths under, at and past the window; 48-token chunks against
# a 32-key window: a chunk's page range in a window layer is shorter
# than the chunk, and pages come back within the chunk that took them
@pytest.mark.parametrize("n_prompt,n_new", [
    (9, 12), (20, 20), (31, 6), (32, 6), (33, 6), (49, 30), (130, 40)])
def test_chunked_prefill_then_decode_gives_the_references_logits(
        cfg, model, tap, n_prompt, n_new):
    eng = _engine(model)
    assert eng._kc.shape == (2, eng.blocks.num_blocks, 2, BLOCK, 16)
    assert eng._kw.shape == (5, eng._window_blocks, 2, BLOCK, 16)
    prompt = _prompt(n_prompt)
    (gen, logits), = _serve_with_logits(eng, [prompt], [n_new], tap).values()
    assert len(gen) == n_new
    assert eng.stats.prefill_steps >= -(-n_prompt // CHUNK)
    want = _reference_logits(cfg, prompt, gen)
    np.testing.assert_allclose(logits, want, atol=TOL, rtol=0)
    assert gen == want.argmax(-1).tolist()
    s = eng.summary()
    assert s["moe_pairs_here"] == s["moe_pairs_all"] > 0  # all held here
    assert s["moe_experts_held"] == 16 * 6
    past = n_prompt + n_new > WINDOW + BLOCK
    assert (s["window_pages_returned"] > 0) == past
    assert (s["kv_pages_window"] < s["kv_pages_live"]) == past
    eng.blocks.check_invariants()
    assert eng.blocks.num_used == eng.blocks.num_window_used == 0


def test_rows_of_every_length_in_one_launch(cfg, model, tap):
    """Short and long sequences in one queue: chunks and decode rows
    under and past the window side by side, each row its own window,
    both head counts in every launch."""
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


def _whole_head_rotary(model):
    """The model's kinds with the FULL layers' rotary over the whole
    head (the sliding layers' way), everything else its own."""
    c = model.config
    rp = {k: dict(v) for k, v in c.rope_parameters.items()}
    rp[M.FULL]["partial_rotary_factor"] = 1
    return dataclasses.replace(c, rope_parameters=rp).attention_by_kind()


@pytest.mark.parametrize("left_out", ["gate", "partial_rotary",
                                      "second_head_count"])
def test_the_comparison_fails_when_a_part_is_left_out(
        cfg, model, tap, monkeypatch, left_out):
    """What makes this model its own is what the comparison holds the
    step programs to: an engine without the gate, with the full layers'
    heads rotated whole, or with one head count for both kinds of layer
    does not give the reference's logits."""
    prompt = _prompt(45)
    if left_out == "gate":
        monkeypatch.setattr(layer_stack, "_GQA", {
            k: (window, False) for k, (window, _g)
            in layer_stack._GQA.items()})
    eng = _engine(model)
    if left_out == "partial_rotary":
        eng._attn = _whole_head_rotary(model)
    if left_out == "second_head_count":
        # 6 heads for both kinds: a sliding layer's wq is 8 heads wide
        full = eng._attn["gqa_gated"]
        eng._attn = {k: full for k in eng._attn}
        with pytest.raises(TypeError, match="reshape"):
            _serve_with_logits(eng, [prompt], [8], tap)
        return
    (gen, logits), = _serve_with_logits(eng, [prompt], [8], tap).values()
    want = _reference_logits(cfg, prompt, gen)
    assert np.abs(logits - want).max() > 50 * TOL


def test_yarn_frequencies_against_their_closed_form():
    """Laguna-XS.2's full layers: YaRN over the 64 rotated numbers of a
    head (theta 500000, factor 64 over 4096 positions, beta 64 and 1).
    Pair j turns ``4096 theta^(-2j/64) / 2 pi`` times over the original
    context: the pairs that turn 64 times and more keep their frequency,
    those that turn once or less run 64 times slower, a linear blend
    between the two correction dimensions."""
    c = M.LagunaConfig()
    inv, scale = c.rotary(M.FULL)
    assert inv.shape == (32,) and inv.dtype == np.float32
    assert scale == pytest.approx(0.1 * math.log(64) + 1, abs=1e-12)
    j = np.arange(32, dtype=np.float64)
    plain = 500000.0 ** (-2 * j / 64)
    turns = 4096 * plain / (2 * math.pi)
    low = math.floor(32 * math.log(4096 / (64 * 2 * math.pi))
                     / math.log(500000))
    high = math.ceil(32 * math.log(4096 / (2 * math.pi))
                     / math.log(500000))
    assert (low, high) == (5, 16)
    assert np.all(turns[:low + 1] >= 64 * 0.7) and np.all(turns[high:] <= 1)
    ramp = np.clip((j - low) / (high - low), 0, 1)
    want = plain * (1 - ramp) + plain / 64 * ramp
    np.testing.assert_allclose(inv, want.astype(np.float32), rtol=1e-6)
    assert np.all(inv[:low + 1] == plain[:low + 1].astype(np.float32))
    np.testing.assert_allclose(inv[high:], (plain / 64)[high:], rtol=1e-6)
    # the sliding layers: the whole head, plain frequencies, no factor
    inv, scale = c.rotary(M.SLIDING)
    assert (inv.shape, scale) == ((64,), 1.0)
    np.testing.assert_allclose(
        inv, 10000.0 ** (-np.arange(0, 128, 2) / 128), rtol=1e-6)
    # and the reference computes the same without the program
    ref = spec.load_reference("laguna")
    for kind in (M.FULL, M.SLIDING):
        got, s = ref.inv_freq(c.rope_parameters[kind], 128)
        np.testing.assert_array_equal(got, c.rotary(kind)[0])
        assert s == c.rotary(kind)[1]
    # the latent model's frequencies go through the same function
    lat = mla_moe.MlaMoeConfig()
    np.testing.assert_array_equal(
        mla_moe.yarn_inv_freq(lat),
        mla_moe.yarn_frequencies(64, 10000.0, 40.0, 4096.0, 32.0, 1.0))


def test_rope_partial_turns_the_first_numbers_and_passes_the_rest():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 3, 16)),
                    jnp.float32)
    pos = jnp.arange(5) + 7
    inv = np.float32(100.0) ** (-np.arange(0, 8, 2, dtype=np.float32) / 8)
    out = M.rope_partial(x, pos, inv, 1.5)
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    ang = np.asarray(pos, np.float32)[:, None, None] * inv
    x1, x2 = np.asarray(x[..., 0:8:2]), np.asarray(x[..., 1:8:2])
    np.testing.assert_allclose(out[..., 0:8:2],
                               1.5 * (x1 * np.cos(ang) - x2 * np.sin(ang)),
                               atol=1e-5)
    np.testing.assert_allclose(out[..., 1:8:2],
                               1.5 * (x2 * np.cos(ang) + x1 * np.sin(ang)),
                               atol=1e-5)
    # the whole head at scale 1 is the dense decoder's rotary
    from paddle_tpu.models.llama import _rope_positions
    inv16 = (100.0 ** (-np.arange(0, 16, 2, dtype=np.float64) / 16)
             ).astype(np.float32)
    np.testing.assert_allclose(M.rope_partial(x, pos, inv16),
                               _rope_positions(x, pos, 100.0), atol=1e-5)


def test_the_window_pool_holds_a_window_shorter_than_a_chunk(model):
    """``num_blocks`` governs the full layers alone: the window pool is
    what max_num_seqs running sequences can hold (a window, a chunk, one
    page more, each) and the null page; with the chunk the longer of the
    two, a long prompt gives pages back within the chunk that took
    them and never holds more than its share."""
    for nb in (70, 257):
        eng = _engine(model, num_blocks=nb)
        assert eng.blocks.num_blocks == nb
        assert eng._window_blocks == 1 + 4 * (32 // 4 + 48 // 4 + 1) == 85
        assert eng._kw.shape[1] == eng.blocks.window_blocks == 85
    eng = _engine(model)
    eng.add_request(_prompt(200), max_new_tokens=8)
    held = []
    while eng.has_unfinished():
        eng.step()
        held.append(eng.blocks.num_window_used)
        eng.blocks.check_invariants()
    assert 0 < max(held) <= 32 // 4 + 48 // 4 + 1
    assert eng.blocks.window_returned >= (200 - 32) // 4 - 1
    assert eng.blocks.num_window_used == 0


def test_admit_step_abort_preempt_leave_both_pools_free(model):
    """A randomised run over a full-layer pool too small for its
    requests: admissions, steps, aborts and preemptions; after every
    step both pools' accounts hold; at the end every page of both is
    free and what was served is what a roomy engine serves."""
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
    outs = eng._finished
    for i, rid in rid_of.items():
        if i not in aborted:
            assert outs[rid].generated == want[i]
    assert eng.blocks.num_used == eng.blocks.num_window_used == 0
    assert eng.blocks.num_free == eng.blocks.num_blocks - 1
    assert eng.blocks.num_window_free == eng._window_blocks - 1


@pytest.mark.parametrize("option,value", [
    ("kv_dtype", "int8"), ("weight_dtype", "int8"), ("weight_dtype", "int4"),
    ("tp", 2), ("drafter", "ngram"), ("decode_window", 4),
    ("kv_tier", object()), ("enable_prefix_caching", True)])
def test_each_unsupported_option_raises_by_name(cfg, option, value):
    m = spec.load_builder("laguna").construct(cfg)
    with pytest.raises(ValueError, match=rf"^{option}=.*not supported for a "
                       "model with sliding-window layers"):
        _engine(m, **{option: value})


def test_a_configuration_the_kinds_cannot_hold_is_refused():
    with pytest.raises(ValueError, match="one head count an attention kind"):
        M.LagunaConfig(
            num_hidden_layers=8, layer_types=[M.FULL] * 8,
            num_attention_heads_per_layer=[48] * 7 + [64],
            mlp_layer_types=["sparse"] * 8)
    with pytest.raises(ValueError, match=r"\[7\] query heads over 2 K/V heads"):
        M.LagunaConfig.tiny(full_heads=7)
    with pytest.raises(ValueError, match="entries for 41 layers"):
        M.LagunaConfig(num_hidden_layers=41)


def test_each_kind_is_traced_once_a_program(model, monkeypatch):
    """Segments [full + dense], [sliding x 3], [full], [sliding x 2]:
    three kinds of layer with weights of three shapes, three traced
    layers, not seven; each kind reads ITS head count and ITS rotary."""
    traced = []
    real = layer_stack._gqa

    def spy(kind):
        def run(x, h, p, pools, layer, c):
            a = c.attn[kind]
            traced.append((kind, a.nh, p["wq"].shape, p["wg"].shape,
                           2 * len(a.rope.keywords["inv_freq"]),
                           a.rope.keywords["scale"]))
            return real(x, h, p, pools, layer, c, kind=kind)
        return run

    monkeypatch.setattr(layer_stack, "ATTENTION", {
        **layer_stack.ATTENTION,
        **{k: spy(k) for k in ("gqa_gated", "gqa_gated_window")}})
    eng = _engine(model)
    eng._get_ragged_prog(8).lower(*eng._ragged_arg_structs(8))
    yarn = 0.1 * math.log(64) + 1
    assert sorted(traced) == [
        ("gqa_gated", 6, (48, 96), (48, 96), 8, yarn),
        ("gqa_gated", 6, (48, 96), (48, 96), 8, yarn),
        ("gqa_gated_window", 8, (48, 128), (48, 128), 16, 1.0)]
    assert eng._layer_kinds == (
        [("gqa_gated", "swiglu")] + [("gqa_gated_window", "moe")] * 3
        + [("gqa_gated", "moe")] + [("gqa_gated_window", "moe")] * 2)
    assert set(serving.ATTENTION_KINDS) >= set(eng._attn)


def test_the_launch_and_the_program_say_what_the_new_kinds_do(model):
    """The expert counts ride on ``engine.sample_commit`` against
    ``moe_experts_held`` of ``summary()``, and the gate, both rotaries,
    the shared expert, the router and layer 0's MLP have scopes in the
    lowered program."""
    from paddle_tpu.profiler.trace import Tracer
    tr = Tracer(capacity=1 << 14)
    eng = _engine(model, tracer=tr)
    eng.add_request(_prompt(90), max_new_tokens=20)
    eng.run()
    held = eng.summary()["moe_experts_held"]
    commits = [dict(a) for ph, name, _t, _d, _tid, a, _i in tr.events()
               if name == "engine.sample_commit"]
    assert commits and all(
        0 < c["moe_experts_touched"] <= held == 96 for c in commits)
    # a decode step of one row touches 4 experts in each of 6 layers
    assert commits[-1]["moe_experts_touched"] == 4 * 6
    launches = [dict(a) for ph, name, _t, _d, _tid, a, _i in tr.events()
                if name == "engine.device_launch"]
    assert all(l["kv_pages_uniform"] >= l["kv_pages_window"] > 0
               for l in launches)
    names = [i["op_name"] for i in
             eng.program_scopes([8])["ragged_step_t8"].values()]
    for scope in ("attn_gate", "rope", "attn", "attn_window", "kv_write",
                  "shared_expert", "router", "moe_experts", "mlp"):
        assert any(f"/{scope}/" in n for n in names), scope


def test_the_model_without_a_gate_is_served_by_the_ungated_kinds(tap):
    """``gating`` false is the same model on kinds ``gqa`` and
    ``gqa_window``, which read their heads and rotary by kind too."""
    c = M.LagunaConfig.tiny(vocab=512, gating=False)
    m = M.LagunaForCausalLM(c, dtype="float32", seed=5)
    assert {a for a, _ in c.layer_kinds()} == {"gqa", "gqa_window"}
    assert "wg" not in m.decode_params()["layers"][0]
    eng = _engine(m)
    prompt = _prompt(60)
    (gen, logits), = _serve_with_logits(eng, [prompt], [10], tap).values()
    want = np.asarray(m.forward(np.asarray([prompt + gen]))._data)[
        0, len(prompt) - 1:-1]
    np.testing.assert_allclose(logits, want, atol=TOL, rtol=0)
