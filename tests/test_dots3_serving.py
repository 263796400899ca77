"""A latent-attention decoder whose full layers attend to the keys a
learned indexer selects and whose sliding-window layers keep a latent of
their own, with a headwise gate, through ``LLMEngine``: three arrays of
cached rows under two block tables, small sizes on the CPU (4 heads on a
128-wide latent behind a 3-head top-8 indexer; 2 heads on a 256-wide
latent under a window of 5; block 4, 5 layers, 4 of 8 experts held, top
3), weights from a seed.  ``index_topk`` and the window are SMALLER than
the contexts: every request selects and every window moves.

The yardstick is the benchmark's plain reference
(``benchmark/references/dots3.py``: float32, one whole forward pass in
the expanded form, the selection a literal top-k and a mask, its own
weights from the seed), reached the way the benchmark reaches it
(``harness/spec.py`` by the architecture's name), so these tests also
hold the seam: shapes file, builder and reference agree on every
leaf."""
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
from paddle_tpu.models import dots3 as M                     # noqa: E402
from paddle_tpu.models import mla_moe                        # noqa: E402
from paddle_tpu.ops.pallas import mla_attention as mla       # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as pa      # noqa: E402

SEED = 2**31 + 39
# float32 on both sides; what is left is the order of the sums (pages
# against whole masked rows, the absorbed form against the expanded, a
# grouped product against a loop over experts).  Logits here are of
# order 1; a missing gate, a key outside the selection or the window
# reads 1e-2 and over
TOL = 3e-4
TOPK, WINDOW, BLOCK, CHUNK = 8, 5, 4, 48


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
                           "rehearsal_dots3.json")) as f:
        over = json.load(f)
    c = _overlay(spec.load_config(bench, "dots3-note-prev-ep8"),
                 over["config"])
    assert (c["index_topk"], c["sliding_window_size"],
            c["serving"]["block_size"], c["serving"]["max_prefill_tokens"]
            ) == (TOPK, WINDOW, BLOCK, CHUNK)
    return c


def _nonzero_biases(made, seed):
    """The router's expert bias and the index key's LayerNorm bias are
    drawn as zeros; here they are not, so that both are in the
    comparison."""
    key = jax.random.PRNGKey(seed)
    for i, w in enumerate(made["layers"]):
        for name in ("router_bias", "ik_bias"):
            if name in w:
                w[name] = 0.2 * jax.random.normal(
                    jax.random.fold_in(key, 2 * i + (name == "ik_bias")),
                    w[name].shape, w[name].dtype)
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


class _Biased:
    """The reference with the test's nonzero biases: its weights come
    from the seed, so the biases are handed to it the way they were
    handed to the model (``harness/weights.py`` ``make_layer``)."""

    def __init__(self, cfg):
        self.ref = spec.load_reference(cfg["reference"])
        self.cfg = cfg

    def logits(self, prompt, generated, lower=None):
        seq = list(prompt) + list(generated)
        real = W.make_layer

        def make_layer(leaves, seed, layer, dtype):
            made = {"layers": [{} for _ in range(layer)]
                    + [real(leaves, seed, layer, dtype)]}
            # fold the same keys as _nonzero_biases: by layer index
            key = jax.random.PRNGKey(5)
            w = made["layers"][layer]
            for name in ("router_bias", "ik_bias"):
                if name in w:
                    w[name] = 0.2 * jax.random.normal(
                        jax.random.fold_in(key, 2 * layer
                                           + (name == "ik_bias")),
                        w[name].shape, w[name].dtype)
            return w

        W.make_layer = make_layer
        try:
            return self.ref.logits_at(self.cfg, SEED, [seq],
                                      [len(prompt) - 1], len(generated),
                                      256, lower=lower)[0]
        finally:
            W.make_layer = real


@pytest.fixture(scope="module")
def reference(cfg):
    return _Biased(cfg)


def _prompt(n):
    return np.random.default_rng(n).integers(0, 512, n).tolist()


# prompt lengths under, at and past index_topk (8) and the window (5),
# under and over a page and a 48-token chunk
@pytest.mark.parametrize("n_prompt,n_new", [
    (3, 4), (7, 6), (8, 6), (9, 12), (20, 20), (48, 6), (49, 30),
    (130, 40)])
def test_chunked_prefill_then_decode_gives_the_references_logits(
        cfg, model, reference, tap, n_prompt, n_new):
    eng = _engine(model)
    nb = eng.blocks.num_blocks
    assert eng._kc.shape == (2, nb, BLOCK, 256)        # [c 128 | k_r 32]
    assert eng._ki.shape == (2, nb, BLOCK, 48)         # the index keys
    assert eng._kw.shape == (3, eng._window_blocks, BLOCK, 384)
    assert eng._vc is eng._vw is None
    assert (eng._hd, eng._hd_w) == (256, 384)
    prompt = _prompt(n_prompt)
    (gen, logits), = _serve_with_logits(eng, [prompt], [n_new], tap).values()
    assert len(gen) == n_new
    assert eng.stats.prefill_steps >= -(-n_prompt // CHUNK)
    want = reference.logits(prompt, gen)
    np.testing.assert_allclose(logits, want, atol=TOL, rtol=0)
    assert gen == want.argmax(-1).tolist()
    s = eng.summary()
    assert 0 < s["moe_pairs_here"] < s["moe_pairs_all"]    # 4 of 8 held
    assert s["moe_experts_held"] == 4 * 4
    total = n_prompt + n_new - 1                  # positions served
    assert s["index_keys_visible"] == total * (total + 1) // 2
    assert s["index_keys_selected"] == sum(min(p + 1, TOPK)
                                           for p in range(total))
    assert (s["window_pages_returned"] > 0) == (total >= WINDOW + BLOCK)
    eng.blocks.check_invariants()
    assert eng.blocks.num_used == eng.blocks.num_window_used == 0


def test_rows_of_every_length_in_one_launch(cfg, model, reference, tap):
    """Short and long sequences in one queue: chunks and decode rows
    under and past the selection and the window side by side, with the
    launch in front still in flight (the ahead pipeline is on)."""
    eng = _engine(model)
    assert eng.overlap
    rng = np.random.default_rng(8)
    lens = (70, 5, 33, 120)
    prompts = [rng.integers(0, 512, n).tolist() for n in lens]
    served = _serve_with_logits(eng, prompts, (25, 50, 8, 10), tap)
    for prompt, (gen, logits) in zip(prompts, served.values()):
        np.testing.assert_allclose(
            logits, reference.logits(prompt, gen), atol=TOL, rtol=0)
    assert eng.summary()["launches_ahead"] > 0
    eng.blocks.check_invariants()
    assert eng.blocks.num_used == eng.blocks.num_window_used == 0


def test_the_ahead_pipeline_changes_no_token(model):
    prompts = [_prompt(n) for n in (60, 11, 37)]
    outs = []
    for overlap in (True, False):
        eng = _engine(model, overlap=overlap)
        rids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
        done = eng.run()
        outs.append([done[r].generated for r in rids])
    assert outs[0] == outs[1]


def test_a_context_under_index_topk_is_dense_attention(cfg, model, tap):
    """While a sequence is shorter than ``index_topk`` the selection
    keeps every key: an engine told a top-k longer than the context
    gives the same logits, and past it does not."""
    import dataclasses
    wide = M.Dots3ForCausalLM(
        dataclasses.replace(model.config, index_topk=4096), dtype="float32",
        materialize=False)
    for a, b in zip(wide.parameters(), model.parameters()):
        a._data = b._data
    prompt = _prompt(5)
    (g0, l0), = _serve_with_logits(_engine(model), [prompt], [3],
                                   tap).values()        # positions 0..7
    (g1, l1), = _serve_with_logits(_engine(wide), [prompt], [3],
                                   tap).values()
    assert g0 == g1
    np.testing.assert_allclose(l0, l1, atol=1e-5, rtol=0)
    prompt = _prompt(40)
    (_, l0), = _serve_with_logits(_engine(model), [prompt], [3],
                                  tap).values()
    (_, l1), = _serve_with_logits(_engine(wide), [prompt], [3],
                                  tap).values()
    assert np.abs(l0 - l1).max() > 50 * TOL


def test_the_served_selection_is_the_references(model, monkeypatch):
    """``S_t`` as the step programs compute it (index scores over the
    paged index keys, the exact top-k by counting) is the set the
    model's whole-sequence forward takes with a literal ``lax.top_k``,
    for every position of a sequence served in chunks and decode
    steps, on both full layers."""
    taken = []
    real = mla.select_bias

    def select_bias(scores, rel, topk):
        bias = real(scores, rel, topk)
        jax.debug.callback(
            lambda b, r: taken.append((np.asarray(b) == 0, np.asarray(r))),
            bias, rel, ordered=True)
        return bias

    monkeypatch.setattr(mla, "select_bias", select_bias)
    eng = _engine(model, overlap=False)
    prompt = _prompt(61)
    rid = eng.add_request(prompt, max_new_tokens=7)
    gen = eng.run()[rid].generated
    jax.effects_barrier()
    seq = prompt + gen[:-1]
    _, selected = model.forward(np.asarray([seq]), return_selected=True)
    assert sorted(selected) == [0, 1]
    served = {0: {}, 1: {}}
    for n, (mask, rel) in enumerate(taken):
        for row, p in zip(mask, rel):
            if p >= 0:
                served[n % 2][int(p)] = np.flatnonzero(row)
    for layer in (0, 1):
        want = np.asarray(selected[layer][0])
        assert sorted(served[layer]) == list(range(len(seq)))
        for p, keys in served[layer].items():
            assert keys.tolist() == np.flatnonzero(want[p]).tolist(), p
            assert len(keys) == min(p + 1, TOPK)


def test_the_kernels_give_what_the_xla_path_gives(model, monkeypatch):
    """The interpreted kernels (the window walk, the index scores in
    item-major tiles, the attention under the selection's bias) against
    the XLA oracles, through the engine."""
    prompts = [_prompt(n) for n in (21, 6)]
    outs = {}
    for interpret in (None, True):
        monkeypatch.setattr(pa, "INTERPRET", interpret)
        eng = _engine(model, max_num_seqs=2, max_prefill_tokens=16)
        assert eng.attention_path.startswith(
            "pallas-interpret" if interpret else "xla-reference")
        rids = [eng.add_request(p, max_new_tokens=4) for p in prompts]
        done = eng.run()
        outs[interpret] = [done[r].generated for r in rids]
    assert outs[None] == outs[True]


@pytest.mark.parametrize("left_out", ["gate", "selection", "window",
                                      "index_rotary"])
def test_the_comparison_fails_when_a_part_is_left_out(
        cfg, model, reference, tap, monkeypatch, left_out):
    """What makes this model its own is what the comparison holds the
    step programs to."""
    import dataclasses
    from types import SimpleNamespace
    eng = _engine(model)
    attn = {k: SimpleNamespace(**vars(a)) for k, a in eng._attn.items()}
    if left_out == "gate":
        for a in attn.values():
            a.gated = False
    elif left_out == "selection":
        attn["mla_select"].index = SimpleNamespace(
            **{**vars(attn["mla_select"].index), "topk": 4096})
    elif left_out == "window":
        attn["mla_window"].window = 4096
    else:
        ix = attn["mla_select"].index
        attn["mla_select"].index = SimpleNamespace(
            **{**vars(ix), "inv_freq": ix.inv_freq[:0]})
    eng._attn = attn
    prompt = _prompt(45)
    (gen, logits), = _serve_with_logits(eng, [prompt], [8], tap).values()
    assert np.abs(logits - reference.logits(prompt, gen)).max() > 30 * TOL


def test_the_control_precision_is_not_the_reference(reference):
    prompt, gen = _prompt(50), [1, 2, 3, 4]
    a = reference.logits(prompt, gen)
    b = reference.logits(prompt, gen, lower="int8")
    assert 1e-3 < np.abs(a - b).max() < 2.0


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """An expert layer cut eight ways: what the eight chips' held experts
    give, with the shared expert (which every chip computes alike)
    counted once, is what the uncut layer gives."""
    whole = M.Dots3Config.tiny(experts=16, seq=64)
    model = M.Dots3ForCausalLM(whole, dtype="float32", seed=3)
    p = dict(model.decode_params()["layers"][1])
    p["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    h2 = jax.random.normal(jax.random.PRNGKey(2), (23, whole.hidden_size))
    want, counts = mla_moe.moe_ffn(h2, p, whole)
    shared = mla_moe.swiglu(h2, p["s_gate"], p["s_up"], p["s_down"])
    total = jnp.zeros_like(want)
    pairs = 0
    for rank in range(8):
        part = M.Dots3Config.tiny(experts=16, ep_size=8, ep_rank=rank,
                                  seq=64)
        assert (part.experts_held, part.first_expert) == (2, 2 * rank)
        mine = dict(p, **{k: p[k][2 * rank:2 * rank + 2]
                          for k in ("e_gate", "e_up", "e_down")})
        out, c = mla_moe.moe_ffn(h2, mine, part)
        total = total + (out - shared)
        pairs += int(c[0])
    np.testing.assert_allclose(total + shared, want, atol=1e-5, rtol=0)
    assert pairs == int(counts[0]) == int(counts[1]) == 23 * 3


@pytest.mark.parametrize("option,value,why", [
    ("kv_dtype", "int8", "latent-attention"),
    ("decode_window", 4, "latent-attention"),
    ("tp", 2, "latent-attention"),
    ("drafter", "ngram", "latent-attention"),
    ("weight_dtype", "int8", "latent-attention"),
    ("enable_prefix_caching", True, "sliding-window"),
])
def test_what_the_three_pools_do_not_serve_is_refused_by_name(
        model, option, value, why):
    with pytest.raises(ValueError, match=f"{option}=.*{why}"):
        _engine(model, **{option: value})


def test_int8_pages_name_the_index_keys():
    with pytest.raises(ValueError, match="indexer's keys quantised"):
        serving._refuse_latent_options(
            kv_dtype="int8", weight_dtype="float32", tp=1, drafter=None,
            decode_window=1, kv_tier=None)


def test_the_pools_and_the_window_pool_the_engine_derives(model):
    """``num_blocks`` governs the full layers' two arrays alone; the
    window pool is what ``max_num_seqs`` sequences hold (a window, a
    chunk, a page more, each) and the null page; the three arrays go to
    every program in one order and each latent kind knows its own."""
    eng = _engine(model, num_blocks=70)
    assert eng.blocks.num_blocks == 70
    per_seq = -(-WINDOW // BLOCK) + -(-CHUNK // BLOCK) + 1
    assert eng._window_blocks == 4 * per_seq + 1
    assert [p.shape for p in eng._pools()] == [
        (2, 70, BLOCK, 256), (2, 70, BLOCK, 48),
        (3, eng._window_blocks, BLOCK, 384)]
    assert eng._slots == {"mla_select": (0, 1), "mla_window": (2,)}
    assert eng._pool_index == [0, 1, 0, 1, 2]
    # a page of the block table's pools: latents and index keys
    assert eng.kv_page_bytes() == 2 * BLOCK * (256 + 48) * 4
    fn, donate = eng._make_cow_fn()
    assert donate == (0, 1, 2)
    assert set(layer_stack.LATENT_KINDS) >= set(eng._attn)
    assert "mla_window" in layer_stack.WINDOW_KINDS


def test_launches_carry_what_the_queries_saw_and_kept(model):
    from paddle_tpu.profiler.trace import Tracer
    tracer = Tracer(capacity=1 << 12)
    eng = _engine(model, tracer=tracer)
    rid = eng.add_request(_prompt(30), max_new_tokens=3)
    eng.run()
    launches = [args for _ph, name, _ts, _dur, _tid, args, _id
                in tracer.events() if name == "engine.device_launch"]
    assert launches
    a = launches[0]
    assert a["index_keys_visible"] == 30 * 31 // 2
    assert a["index_keys_selected"] == sum(min(p + 1, TOPK)
                                           for p in range(30))
    assert {"kv_pages", "kv_pages_window", "kv_pages_uniform"} <= set(a)
    del rid


def test_the_configuration_is_its_published_lists():
    c = M.Dots3Config()
    kinds = c.layer_kinds()
    full = [i for i, (a, _) in enumerate(kinds) if a == "mla_select"]
    assert full == [0, 1] + list(range(5, 46, 4)) and len(full) == 13
    assert kinds[0] == ("mla_select", "swiglu")
    assert kinds[1] == ("mla_select", "moe")
    assert kinds[2] == ("mla_window", "moe")
    a, w = c.sizes(M.FULL), c.sizes(M.SLIDING)
    assert (a.nh, a.rq, a.dc, a.dn, a.dr, a.dv) == (128, 1024, 512, 128,
                                                    64, 128)
    assert (w.nh, w.rq, w.dc, w.dn, w.dr, w.dv) == (64, 1024, 1024, 192,
                                                    64, 128)
    assert a.sm_scale == 192 ** -0.5 and w.sm_scale == 256 ** -0.5
    assert (a.window, w.window) == (None, 513) and w.index is None
    assert (a.index.nh, a.index.d, a.index.topk) == (64, 128, 2048)
    assert a.index.scale == pytest.approx(64 ** -0.5 * 128 ** -0.5)
    assert mla.page_width(a.dc + a.dr) == 640
    assert mla.page_width(w.dc + w.dr) == 1152
    np.testing.assert_allclose(
        a.inv_freq, 8e7 ** (-np.arange(0, 64, 2) / 64), rtol=1e-6)
    np.testing.assert_allclose(
        w.inv_freq, 5e4 ** (-np.arange(0, 64, 2) / 64), rtol=1e-6)
    with pytest.raises(ValueError, match="one number a head"):
        M.Dots3Config(attention_gate_type="elementwise")
    with pytest.raises(ValueError, match="n_routed_experts"):
        M.Dots3Config(experts_held=30, ep_size=8)


def test_a_depth_cut_to_full_layers_alone_serves(model):
    """``--layers 2`` leaves the dense and one sparse FULL layer: no
    window pool, no window table, the latents and their index keys."""
    import dataclasses
    cut = M.Dots3ForCausalLM(
        dataclasses.replace(model.config, num_hidden_layers=2),
        dtype="float32", materialize=False)
    for a, b in zip(cut.parameters(), model.parameters()):
        a._data = b._data
    eng = _engine(cut, enable_prefix_caching=True)
    assert not eng._windowed and eng._kw is None
    assert eng._slots == {"mla_select": (0, 1)}
    prompt = _prompt(30)
    rid = eng.add_request(prompt, max_new_tokens=4)
    gen = eng.run()[rid].generated
    want = np.asarray(cut.forward(np.asarray([prompt + gen]))._data)[0]
    assert gen == want[len(prompt) - 1:-1].argmax(-1).tolist()
