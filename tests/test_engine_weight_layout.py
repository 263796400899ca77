"""How the engine holds the q, k and v projections of its OWN stacked
copy (PR 40): [L, heads, d, in], the contraction axis last, where the
dense decoder's export is [L, in, heads * d]; the float ``mm`` contracts
those three on the weight's last axis.  Same sums: the served tokens are
``model.generate``'s, ``tp=2`` shards the head axis (now axis 1) and
answers as ``tp=1`` byte for byte.  What is not the engine's to lay out
stays as it was: int8 pools keep the kernel's [in, out], and arrays a
model exports layer by layer are held once, untouched."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

VOCAB = 97
CFG = LlamaConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=4, ffn=64,
                       seq=64)
QKV = ("wq", "wk", "wv")


def _model(dtype="float32", seed=42):
    # (the seed: in bfloat16 the engine and ``generate`` part on one
    # request of eight at seeds 40, 41, 44, 45, 47 and 50, before PR 40
    # as after it: a near tie between two attention spellings)
    paddle.seed(seed)
    model = LlamaForCausalLM(CFG)
    if dtype != "float32":
        model.to(dtype=dtype)
    return model


def _engine(model, **kw):
    kw = {"max_num_seqs": 4, "block_size": 8, "max_model_len": 64,
          "max_prefill_tokens": 128, "prefill_token_bucket": 32, **kw}
    return LLMEngine(model, **kw)


def _requests(n=8):
    rng = np.random.RandomState(11)
    return [(rng.randint(0, VOCAB, [4, 9, 13, 21][i % 4]).tolist(), 6)
            for i in range(n)]


def _serve(eng, reqs):
    rids = [eng.add_request(p, max_new_tokens=mx) for p, mx in reqs]
    outs = eng.run()
    return [outs[r].generated for r in rids]


def _oracle(model, prompt, max_new):
    out = model.generate(jnp.asarray([prompt], jnp.int32),
                         max_new_tokens=max_new, temperature=0.0)
    return np.asarray(out._data)[0, len(prompt):].tolist()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_stacked_copy_holds_qkv_out_major_and_serves_generates_tokens(
        dtype):
    """The engine's copy of q, k, v is the export's, transposed and
    split by head; every
    other leaf is the export's as it lies; the model's own arrays are
    unchanged; and greedy serving gives ``model.generate``'s tokens."""
    model = _model(dtype)
    export = model.decode_params()
    eng = _engine(model)
    heads = {"wq": CFG.num_attention_heads, "wk": CFG.num_key_value_heads,
             "wv": CFG.num_key_value_heads}
    assert eng._out_major == heads
    layers = eng.params["layers"]
    assert set(layers) == set(export["layers"])
    for name, want in export["layers"].items():
        got = layers[name]
        assert got.dtype == want.dtype == jnp.dtype(dtype)
        if name in QKV:
            n, width, out = want.shape
            assert got.shape == (n, heads[name], out // heads[name], width)
            want = jnp.swapaxes(want, 1, 2).reshape(got.shape)
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(want, np.float32)), name
    # the model's export is what it was: [L, in, out]
    again = model.decode_params()["layers"]
    assert all(again[n].shape == export["layers"][n].shape for n in again)
    reqs = _requests()
    assert _serve(eng, reqs) == [_oracle(model, p, mx) for p, mx in reqs]


def test_tp2_shards_the_head_axis_and_answers_as_tp1():
    """Under ``tp=2`` the three matrices are split along axis 1, their
    heads (the export's axis 2), and the rest replicate; the contraction
    is never split, so the tokens are ``tp=1``'s byte for byte."""
    model = _model()
    e1, e2 = _engine(model), _engine(model, tp=2)
    specs = e2._param_specs()["layers"]
    for name, x in e2.params["layers"].items():
        assert isinstance(x.sharding, NamedSharding)
        if name in QKV:
            assert specs[name] == x.sharding.spec == P(None, "tp")
            assert {s.data.shape for s in x.addressable_shards} \
                == {(x.shape[0], x.shape[1] // 2) + x.shape[2:]}
            # a shard holds a contiguous block of heads
            for s in x.addressable_shards:
                assert np.array_equal(
                    np.asarray(s.data),
                    np.asarray(e1.params["layers"][name])[s.index])
        else:
            assert specs[name] == P()
            assert all(s.data.shape == x.shape
                       for s in x.addressable_shards)
    assert e2.weight_bytes_resident_per_shard() \
        < e1.weight_bytes_resident_per_shard()
    reqs = _requests()
    assert _serve(e2, reqs) == _serve(e1, reqs)


# what the tree before PR 40 served from these int8 pools (``_model()``,
# ``_requests(4)``): the quantized path is quantized from the export as
# it lies, [in, out], and must not have moved
_PARENT_INT8 = [[93, 28, 63, 43, 9, 90], [70, 10, 68, 96, 39, 23],
                [78, 38, 0, 66, 76, 69], [77, 83, 55, 3, 45, 67]]


def test_an_int8_weight_engine_keeps_its_pools_and_answers_as_before():
    """No leaf is held out-major where the engine made no float copy:
    an int8-weight engine keeps the kernel's [L, in, out] pools,
    quantized from the export as it lies, and serves what it served."""
    model = _model()
    export = model.decode_params()["layers"]
    eng = _engine(model, weight_dtype="int8")
    assert eng._out_major == {}
    for name in QKV:
        assert eng.params["layers"][name + "_q"].shape == export[name].shape
        assert eng.params["layers"][name + "_q"].dtype == jnp.int8
        assert name not in eng.params["layers"]
    assert _serve(eng, _requests(4)) == _PARENT_INT8


def test_arrays_exported_layer_by_layer_are_held_once_as_they_lie():
    """A model that exports its arrays layer by layer has them held
    ONCE (the same buffers: no second copy to lay out) and contracted
    [hidden, out] as ever."""
    from paddle_tpu.models.smallthinker import (SmallThinkerConfig,
                                                SmallThinkerForCausalLM)
    cfg = SmallThinkerConfig.tiny()
    model = SmallThinkerForCausalLM(cfg, dtype="float32")
    eng = LLMEngine(model, max_num_seqs=4, block_size=8, max_model_len=64,
                    max_prefill_tokens=24, prefill_token_bucket=8,
                    enable_prefix_caching=False)
    assert eng._out_major == {}
    held, own = eng.params["layers"], model.decode_params()["layers"]
    nh, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    for i, p in enumerate(held):
        assert p["wq"].shape == (cfg.hidden_size, nh * d)
        assert p["wk"].shape == p["wv"].shape == (cfg.hidden_size, kvh * d)
        assert all(p[n].unsafe_buffer_pointer()
                   == own[i][n].unsafe_buffer_pointer() for n in QKV)
    # and it serves: the float ``mm`` contracts these [hidden, out]
    toks, = _serve(eng, [(list(range(1, 12)), 4)])
    assert len(toks) == 4 and all(0 <= t < cfg.vocab_size for t in toks)
