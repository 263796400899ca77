"""A latent-attention decoder with expert layers through ``LLMEngine``:
small sizes on the CPU, weights from a seed, a nonzero router bias.

The yardstick is the benchmark's plain reference
(``benchmark/references/mla_moe.py``: float32, expanded form, one whole
forward pass, its own weights from the seed), reached the way the
benchmark reaches it (``harness/spec.py`` by the architecture's name), so
these tests also hold the seam: shapes file, builder and reference agree
on every leaf."""
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
from paddle_tpu.models import mla_moe as M                   # noqa: E402

SEED = 2**31 + 5
# float32 on both sides; what is left is the order of the sums: absorbed
# against expanded products, attention page by page against whole rows,
# a grouped product against a loop over experts.  Logits here are of
# order 1; a wrong expert or a missed cached row reads 1e-1 and over
TOL = 2e-4


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
                           "rehearsal_mla_moe.json")) as f:
        over = json.load(f)
    return _overlay(spec.load_config(bench, "sarvam-105b-ep4"),
                    over["config"])


@pytest.fixture(autouse=True)
def nonzero_router_bias(monkeypatch):
    """The benchmark draws the router's bias as nought; here every
    ``zero`` leaf gets a draw of its own, in the program's weights and in
    the reference's alike (both come from ``weights.draw``)."""
    real = W.draw

    def draw(key, index, shape, kind, dtype):
        if kind == "zero":
            return (0.3 * jax.random.normal(jax.random.fold_in(key, index),
                                            shape)).astype(dtype)
        return real(key, index, shape, kind, dtype)

    monkeypatch.setattr(W, "draw", draw)


def _model(cfg):
    shapes = spec.load_shapes(cfg["reference"])
    builder = spec.load_builder(cfg["reference"])
    model = builder.construct(cfg)
    assert all(isinstance(p._data, jax.ShapeDtypeStruct)
               for p in model.parameters())          # nothing drawn yet
    builder.place(model, W.make_all(shapes.leaves(cfg), SEED,
                                    jnp.dtype(cfg["dtype"])))
    return model


def _engine(model, **kw):
    kw = {"max_num_seqs": 4, "block_size": 8, "max_model_len": 256,
          "max_prefill_tokens": 32, "prefill_token_bucket": 16, **kw}
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
    logits [n generated, V] that each token was taken from)}: the
    tapped logits joined to requests by the launch's own row
    bookkeeping."""
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
    rids = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
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
    out = ref.logits_at(cfg, SEED, [seq], [len(prompt) - 1],
                        len(generated), 256)
    return out[0]


def test_chunked_prefill_then_decode_gives_the_references_logits(
        cfg, tap):
    """(a) a 75-token prompt goes in as chunks of 32, 32 and 11, then
    decodes through the latent cache; every logit row a token was taken
    from is the reference's row of its one forward pass."""
    eng = _engine(_model(cfg))
    assert eng._kc.shape == (3, eng.blocks.num_blocks, 8, 256)
    assert eng._vc is None                      # one pool, not K and V
    prompt = np.random.default_rng(1).integers(0, 512, 75).tolist()
    (gen, logits), = _serve_with_logits(eng, [prompt], 9, tap).values()
    assert eng.stats.prefill_steps >= 3 and len(gen) == 9
    want = _reference_logits(cfg, prompt, gen)
    np.testing.assert_allclose(logits, want, atol=TOL, rtol=0)
    assert gen == want.argmax(-1).tolist()
    s = eng.summary()
    assert 0 < s["moe_pairs_here"] < s["moe_pairs_all"]
    assert s["moe_experts_touched"] > 0 and s["moe_load_max"] > 0


def test_prefix_hit_and_copy_on_write_give_a_cold_runs_logits(cfg, tap):
    """(e) two follow-ups extend what a finished request left cached and
    diverge inside its partly filled page: a prefix hit each, one
    copy-on-write of a latent page, and the logits of engines that
    never saw the first request."""
    model = _model(cfg)
    rng = np.random.default_rng(4)
    pa = rng.integers(0, 512, 29).tolist()
    eng = _engine(model)
    ra = eng.add_request(pa, max_new_tokens=7)
    base = pa + eng.run()[ra].generated[:6]
    followups = [base + [3], base + [7]]
    warm = list(_serve_with_logits(eng, followups, 5, tap).values())
    s = eng.summary()
    assert s["cow_copies"] >= 1 and s["cache_hit_tokens"] >= 2 * 24
    eng.blocks.check_invariants()
    for prompt, (gen, logits) in zip(followups, warm):
        (cold_gen, cold), = _serve_with_logits(
            _engine(model, enable_prefix_caching=False), [prompt], 5,
            tap).values()
        assert gen == cold_gen
        np.testing.assert_allclose(logits, cold, atol=TOL, rtol=0)
        np.testing.assert_allclose(
            logits, _reference_logits(cfg, prompt, gen), atol=TOL, rtol=0)


def test_preemption_and_abort_leave_the_pool_sound(cfg):
    """A pool too small for its requests preempts and recomputes through
    the latent cache; an abort frees its pages."""
    model = _model(cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 512, int(n)).tolist()
               for n in rng.integers(20, 60, 6)]
    roomy = _engine(model)
    ids = [roomy.add_request(p, max_new_tokens=12) for p in prompts]
    outs = roomy.run()
    want = [outs[r].generated for r in ids]
    eng = _engine(model, num_blocks=20, max_model_len=96)
    rids = [eng.add_request(p, max_new_tokens=12) for p in prompts]
    victim = eng.add_request(prompts[0], max_new_tokens=30)
    eng.step()
    eng.abort(victim)
    outs = eng.run()
    assert eng.stats.preemptions > 0
    assert [outs[r].generated for r in rids] == want
    eng.blocks.check_invariants()
    assert eng.blocks.num_used == 0


@pytest.mark.parametrize("kind", ["mixed", "decode_only", "padding_rows"])
def test_the_packed_vector_carries_the_expert_counts(cfg, launch_results,
                                                     monkeypatch, kind):
    """A launch's results reach the host in one int32 vector (PR 38):
    behind the sampled tokens and the finiteness flags lie the four
    numbers the step's expert layers counted, each as the device had
    it, whether a chunk rides beside decode rows, every row decodes or
    rows are to spare; ``summary()`` sums what the slices say, with no
    read of its own."""
    rng = np.random.default_rng(7)
    lens = (11, 9, 14, 6) if kind == "decode_only" else (11, 9)
    eng = _engine(_model(cfg), max_prefill_tokens=16)
    for n in lens:
        eng.add_request(rng.integers(0, 512, n).tolist(), max_new_tokens=8)
    # device arrays ``_complete`` reads: the packed vector and no other
    reads, real_asarray, real_complete = [], np.asarray, eng._complete

    def asarray(x, *a, **k):
        if reads and reads[-1] is not None and hasattr(x, "is_ready"):
            reads.append(x.shape)
        return real_asarray(x, *a, **k)

    def complete(*a, **k):
        reads.append(())
        try:
            return real_complete(*a, **k)
        finally:
            reads.append(None)

    monkeypatch.setattr(np, "asarray", asarray)
    monkeypatch.setattr(eng, "_complete", complete)
    n = 0
    while eng.has_unfinished():
        eng.step()
        n += 1
        if n == 3 and kind == "mixed":
            eng.add_request(rng.integers(0, 512, 40).tolist(),
                            max_new_tokens=2)
    monkeypatch.undo()
    Lq = eng._Lq
    assert reads == [(), (2 * Lq + 4,), None] * eng.launches
    assert kind in {rec["kind"] for rec in launch_results}
    total = np.zeros(4, np.int64)
    for rec in launch_results:
        sampled, packed = rec["front"]
        dev_sampled, dev_fin, dev_counts = rec["parts"]
        assert packed.dtype == jnp.int32 and packed.shape == (2 * Lq + 4,)
        toks, ok, counts = serving._unpack_results(np.asarray(packed), (Lq,))
        np.testing.assert_array_equal(toks, dev_sampled)
        np.testing.assert_array_equal(toks, np.asarray(sampled))
        np.testing.assert_array_equal(ok, dev_fin)
        np.testing.assert_array_equal(counts, dev_counts)
        assert counts[0] > 0
        total[:3] += counts[:3]
        total[3] = max(total[3], counts[3])
    s = eng.summary()
    assert [s[k] for k in eng.moe_counts] == total.tolist()
    assert s["host_round_trips"] == s["launches"] == len(launch_results)
    assert 0 <= s["reads_ready"] <= s["launches"]


def test_the_shares_add_up_to_the_whole_layer(cfg):
    """(b) four chips' routed parts, plus the shared expert once, are the
    uncut reference's whole expert layer."""
    ref = spec.load_reference("mla_moe")
    H, E, k, Fe = 64, 8, 3, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 9)
    full = {"router": jax.random.normal(ks[0], (H, E)) * 0.5,
            "router_bias": jax.random.normal(ks[1], (E,)) * 0.3,
            "e_gate": jax.random.normal(ks[2], (E, H, Fe)) * 0.2,
            "e_up": jax.random.normal(ks[3], (E, H, Fe)) * 0.2,
            "e_down": jax.random.normal(ks[4], (E, Fe, H)) * 0.2,
            "s_gate": jax.random.normal(ks[5], (H, Fe)) * 0.2,
            "s_up": jax.random.normal(ks[6], (H, Fe)) * 0.2,
            "s_down": jax.random.normal(ks[7], (Fe, H)) * 0.2}
    h2 = jax.random.normal(ks[8], (23, H))
    with jax.default_matmul_precision("highest"):
        whole = ref._experts(h2, full, {"k": k, "first": 0, "held": E}, 2.5)
        shared = M.swiglu(h2, full["s_gate"], full["s_up"], full["s_down"])
        routed, pairs = 0.0, 0
        for rank in range(4):
            c = M.MlaMoeConfig.tiny(hidden=H, experts=E, ep_size=4,
                                    ep_rank=rank)
            lo = c.first_expert
            p = {**full, **{n: full[n][lo:lo + c.experts_held]
                            for n in ("e_gate", "e_up", "e_down")}}
            out, counts = M.moe_ffn(h2, p, c)
            routed = routed + (out - shared)
            pairs += int(counts[0])
            assert int(counts[1]) == 23 * k
    assert pairs == 23 * k                      # every pair on some chip
    np.testing.assert_allclose(np.asarray(routed + shared),
                               np.asarray(whole), atol=2e-5, rtol=0)


@pytest.mark.parametrize("option,value", [
    ("kv_dtype", "int8"), ("weight_dtype", "int8"), ("weight_dtype", "int4"),
    ("tp", 2), ("drafter", "ngram"), ("decode_window", 4),
    ("kv_tier", object())])
def test_each_unsupported_option_raises_by_name(cfg, option, value):
    """(f)"""
    model = spec.load_builder("mla_moe").construct(cfg)
    with pytest.raises(ValueError, match=rf"^{option}=.*not supported"):
        _engine(model, **{option: value})


def test_the_model_draws_in_its_own_type_and_forward_agrees(cfg):
    """The CLI's way: weights drawn leaf by leaf in the served type; the
    model's own whole-sequence pass (expanded form) agrees with what the
    engine serves."""
    c = M.MlaMoeConfig.tiny(experts=8, ep_size=2, ep_rank=1)
    model = M.MlaMoeForCausalLM(c, dtype="bfloat16")
    assert {str(p._data.dtype) for p in model.parameters()} == {"bfloat16"}
    model = M.MlaMoeForCausalLM(c, dtype="float32", seed=3)
    for lyr in model.layers[c.first_k_dense_replace:]:
        b = lyr._parameters["router_bias"]
        b._data = 0.3 * jax.random.normal(jax.random.PRNGKey(9),
                                          b._data.shape)
    eng = _engine(model)
    prompt = np.random.default_rng(0).integers(0, 96, 39).tolist()
    rid = eng.add_request(prompt, max_new_tokens=8)
    toks = eng.run()[rid].token_ids
    logits = np.asarray(model(jnp.asarray([toks]))._data[0])
    assert toks[39:] == logits[38:46].argmax(-1).tolist()
