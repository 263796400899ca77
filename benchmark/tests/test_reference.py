"""The plain reference against the engine at a tiny size on the CPU:
prefill in chunks across steps, a prompt whose prefix came from the
cache (with the copy-on-write page copy), and decoding in a batch.  And
the control: the reference in int8 in the program's place comes out as
not correct."""
import pytest

from harness import compare, server, spec
from harness.kinds import closed_loop as CL

TINY = {"name": "tiny", "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 512, "rms_norm_eps": 1e-5,
        "rope_theta": 1e6, "dtype": "float32", "reference": "llama_dense",
        "serving": {"max_num_seqs": 4, "max_model_len": 256, "block_size": 8,
                    "num_blocks": 129, "max_prefill_tokens": 16,
                    "enable_prefix_caching": True}}


def _serve(cfg, seed, prompts, max_new, together=True):
    model = server.build_model(cfg, seed, {})
    eng = server.build_engine(cfg, model, {})
    done = {}

    def fin(o):
        done[o.rid] = o

    rids = []
    for p in prompts:
        rids.append(eng.add_request(p, max_new_tokens=max_new, on_finish=fin))
        if not together:
            eng.run()
    eng.run()
    return eng, [list(done[r].generated) for r in rids]


@pytest.fixture(scope="module")
def reference():
    return spec.load_reference("llama_dense")


def test_chunked_prefill_and_batched_decode_match(reference):
    seed = 5
    V = TINY["vocab_size"]
    prompts = [CL.tokens(seed, 0, j, n, V) for j, n in
               enumerate((70, 23, 41, 9))]       # 70 tokens: five chunks of 16
    eng, outs = _serve(TINY, seed, prompts, 12)
    assert eng.summary()["prefill_steps"] >= 5
    seqs = [p + o for p, o in zip(prompts, outs)]
    g = compare.served_gaps(reference, TINY, seed, seqs,
                            [len(p) for p in prompts], pad_to=128, n_score=16)
    assert g["tokens"] == 48
    assert g["max"] <= 1e-4, g                   # float32 both sides


def test_cached_prefix_with_copy_on_write_matches(reference):
    seed = 6
    V = TINY["vocab_size"]
    system = CL.tokens(seed, 1, 0, 43, V)        # 5 full pages and a tail of 3
    model = server.build_model(TINY, seed, {})
    eng = server.build_engine(TINY, model, {})
    done = {}
    eng.add_request(system, max_new_tokens=1,
                    on_finish=lambda o: done.__setitem__("sys", o))
    eng.run()
    turns = [system + CL.tokens(seed, 2, j, n, V)
             for j, n in enumerate((11, 20, 7))]
    rids = [eng.add_request(t, max_new_tokens=10,
                            on_finish=lambda o: done.__setitem__(o.rid, o))
            for t in turns]
    eng.run()
    s = eng.summary()
    assert s["cache_hit_tokens"] >= 3 * 40       # the system prompt's pages
    assert s["cow_copies"] >= 1                  # the shared tail page
    seqs = [t + list(done[r].generated) for t, r in zip(turns, rids)]
    g = compare.served_gaps(reference, TINY, seed, seqs,
                            [len(t) for t in turns], pad_to=128, n_score=16)
    assert g["tokens"] == 30 and g["max"] <= 1e-4, g


def test_weights_are_the_seeds_alone_and_differ_by_seed():
    import jax.numpy as jnp
    import numpy as np

    from harness import weights as W
    arch = spec.load_shapes("llama_dense")
    leaves = arch.leaves(TINY)
    a = W.make_all(leaves, 2**31 + 9, jnp.float32)
    b = W.make_layer(leaves, 2**31 + 9, 1, jnp.float32)
    assert list(b) == [n for n, _kind in arch.LAYER]
    for n in b:
        assert np.array_equal(np.asarray(a["layers"][1][n]), np.asarray(b[n]))
    t = W.make_top(leaves, 2**31 + 9, jnp.float32)
    assert list(t) == [n for n, _kind in arch.TOP]
    assert np.array_equal(np.asarray(a["top"]["head"]), np.asarray(t["head"]))
    c = W.make_layer(leaves, 2**31 + 10, 1, jnp.float32)
    assert not np.array_equal(np.asarray(b["wq"]), np.asarray(c["wq"]))
    assert a["layers"][0]["wk"].shape == (64, 2 * 16)


# sha256 over every leaf of make_all in bfloat16 at seed 2**31 + 9, taken
# with the weights.py of the commit before the leaves moved out of it
# (PR 26, 6ae3206): the two configurations' leaf lists at a tiny size,
# Mistral's K/V group of 4 and Yi's of 8
PARENT_DIGESTS = [
    ({"hidden_size": 64, "intermediate_size": 224, "num_attention_heads": 8,
      "num_key_value_heads": 2, "num_hidden_layers": 3, "vocab_size": 512},
     "abc7126832c5418eb71d301107dfad423e9ba2638a7caf6ef80943468cb7cec5"),
    ({"hidden_size": 64, "intermediate_size": 172, "num_attention_heads": 8,
      "num_key_value_heads": 1, "num_hidden_layers": 2, "vocab_size": 1000},
     "76ac00f216ffd8d24eb594bb6ba448c4dbf778f7b22334b35995b5ec4ef6e4cf")]


@pytest.mark.parametrize("cfg,digest", PARENT_DIGESTS,
                         ids=["mistral-like", "yi-like"])
def test_every_leaf_has_the_bits_the_parent_drew(cfg, digest):
    """The leaf order of ``shapes/llama_dense.py`` is the parent's
    ``leaf_index`` and ``weights.draw`` its ``_leaf``: the same weights
    at every seed, so the same served tokens."""
    import hashlib

    import jax.numpy as jnp
    import numpy as np

    from harness import weights as W
    arch = spec.load_shapes("llama_dense")
    made = W.make_all(arch.leaves(cfg), 2**31 + 9, jnp.bfloat16)
    h = hashlib.sha256()
    labelled = [(n, made["top"][n]) for n, _ in arch.TOP] + [
        (f"{i}.{n}", lyr[n]) for i, lyr in enumerate(made["layers"])
        for n, _ in arch.LAYER]
    assert len(labelled) == 3 + 9 * cfg["num_hidden_layers"]
    for label, a in labelled:
        h.update(label.encode())
        h.update(str(a.shape).encode())
        h.update(np.asarray(a).view(np.uint16).tobytes())
    assert h.hexdigest() == digest


def test_leaves_may_differ_by_layer_and_have_any_rank():
    """What the next architecture needs of the draw: a leading layer
    with leaves of its own, a stack of expert matrices, a bias that
    starts at nought; each leaf still a function of seed and index."""
    import math

    import jax.numpy as jnp
    import numpy as np

    from harness import weights as W
    leaves = [("embed", None, (32, 8), "embedding"),
              ("ln", 0, (8,), "norm"), ("up", 0, (8, 24), "matrix"),
              ("ln", 1, (8,), "norm"), ("router_bias", 1, (4,), "zero"),
              ("experts_up", 1, (4, 8, 16), "matrix")]
    made = W.make_all(leaves, 5, jnp.float32)
    assert list(made["top"]) == ["embed"]
    assert [list(l) for l in made["layers"]] \
        == [["ln", "up"], ["ln", "router_bias", "experts_up"]]
    e = np.asarray(made["layers"][1]["experts_up"])
    assert e.shape == (4, 8, 16)
    # Xavier over the last two dimensions, not over experts and inputs
    assert e.std() == pytest.approx(math.sqrt(2.0 / (8 + 16)), rel=0.1)
    assert not np.asarray(made["layers"][1]["router_bias"]).any()
    assert not np.array_equal(np.asarray(made["layers"][0]["ln"]),
                              np.asarray(made["layers"][1]["ln"]))
    one = W.make_layer(leaves, 5, 1, jnp.float32)
    assert np.array_equal(np.asarray(one["experts_up"]), e)
    with pytest.raises(ValueError):
        W.make_all([("x", None, (2, 2), "uniform")], 5, jnp.float32)


SMALL_BF16 = dict(TINY, hidden_size=256, intermediate_size=512,
                  num_attention_heads=8, vocab_size=8192, dtype="bfloat16")


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_int8_comes_out_not_correct(reference, seed):
    """At a size a test can hold (hidden 256, vocabulary 8192, bfloat16):
    the program's widest gap over three seeds was 0.0004 and the int8
    control's smallest 0.0047 (CPU, PR 24), so a limit of 0.0015 passes
    the one and fails the other.  The cells' own limit is set the same
    way from chip readings (PERF.md section 2)."""
    V = SMALL_BF16["vocab_size"]
    prompts = [CL.tokens(seed, 0, j, n, V) for j, n in
               enumerate((40, 23, 57, 31))]
    _, outs = _serve(SMALL_BF16, seed, prompts, 24)
    seqs = [p + o for p, o in zip(prompts, outs)]
    n_prompt = [len(p) for p in prompts]
    sound = compare.served_gaps(reference, SMALL_BF16, seed, seqs, n_prompt,
                                pad_to=128, n_score=32)
    control = compare.served_gaps(reference, SMALL_BF16, seed, seqs, n_prompt,
                                  pad_to=128, n_score=32, lower="int8")
    limit = 0.0015
    numbers = lambda g: [{"name": "served_gap_max", "value": g["max"],
                          "limit": limit, "sense": "max"}]
    assert compare.verdict(numbers(sound)) is True, sound
    assert compare.verdict(numbers(control)) is False, control


def test_verdict_fails_on_nan_and_on_too_few_tokens():
    assert not compare.verdict([{"name": "g", "value": float("nan"),
                                 "limit": 1.0, "sense": "max"}])
    assert not compare.verdict([{"name": "n", "value": 3, "limit": 8,
                                 "sense": "min"}])
    assert compare.verdict([{"name": "n", "value": 8, "limit": 8,
                             "sense": "min"}])


def test_sample_holds_the_longest_and_is_drawn_from_the_seed():
    recs = [{"prompt_tokens": 10 * i, "tokens": [0] * 5, "i": i}
            for i in range(20)]
    a = compare.draw_sample(recs, 1, 6)
    assert a[0]["i"] == 19 and len(a) == 6
    assert [r["i"] for r in a] == [r["i"] for r in compare.draw_sample(recs, 1, 6)]
    assert [r["i"] for r in a] != [r["i"] for r in compare.draw_sample(recs, 2, 6)]
    assert compare.draw_sample([], 1, 6) == []
