"""What building ONE step program traces, a served architecture a case:
each layer KIND's body exactly once, whatever the model's depth (a
scanned segment once; an unrolled model once a kind, through the kind's
inner jit), and the dense decoder still as one scan.

Set-up of every cell is tracing and lowering a token bucket: a change to
``layer_stack`` that makes a process trace a kind once a LAYER costs
every cell seconds a bucket at every process start and moves nothing
inside the measured window (PERF.md section 6, PR 41's refusal).  This
is the guard; tiny presets, the CPU, no compile."""
import collections

import jax
import pytest

from paddle_tpu.inference import LLMEngine, layer_stack


def _llama():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig.tiny(
        vocab=97, hidden=32, layers=6, heads=4, ffn=64, seq=64)), {}


def _mla_moe():
    from paddle_tpu.models.mla_moe import MlaMoeConfig, MlaMoeForCausalLM
    cfg = MlaMoeConfig.tiny(vocab=96, hidden=32, layers=5, heads=2,
                            experts=4, seq=64)
    return MlaMoeForCausalLM(cfg, dtype="float32"), {}


def _smallthinker():
    from paddle_tpu.models.smallthinker import (SmallThinkerConfig,
                                                SmallThinkerForCausalLM)
    cfg = SmallThinkerConfig.tiny(layers=8, seq=64)
    return SmallThinkerForCausalLM(cfg, dtype="float32"), \
        {"enable_prefix_caching": False}


def _laguna():
    from paddle_tpu.models.laguna import LagunaConfig, LagunaForCausalLM
    cfg = LagunaConfig.tiny(seq=64)
    return LagunaForCausalLM(cfg, dtype="float32"), \
        {"enable_prefix_caching": False}


def _dots3():
    from paddle_tpu.models.dots3 import Dots3Config, Dots3ForCausalLM
    cfg = Dots3Config.tiny(seq=64)
    return Dots3ForCausalLM(cfg, dtype="float32"), \
        {"enable_prefix_caching": False}


def _phi4flash():
    from paddle_tpu.models.phi4flash import (Phi4FlashConfig,
                                             Phi4FlashForCausalLM)
    cfg = Phi4FlashConfig.tiny(layers=16, seq=64)
    return Phi4FlashForCausalLM(cfg, dtype="float32"), \
        {"enable_prefix_caching": False}


ARCHITECTURES = {"llama_dense": _llama, "mla_moe": _mla_moe,
                 "smallthinker": _smallthinker, "laguna": _laguna,
                 "dots3": _dots3, "phi4flash": _phi4flash}


@pytest.mark.parametrize("arch", sorted(ARCHITECTURES))
def test_a_step_program_traces_each_kind_of_layer_once(arch, monkeypatch):
    model, kw = ARCHITECTURES[arch]()
    eng = LLMEngine(model, max_num_seqs=4, block_size=8, max_model_len=64,
                    max_prefill_tokens=16, prefill_token_bucket=8, **kw)
    traced = collections.Counter()
    scans = []

    def counted(kind, fn):
        def body(*a, **k):
            traced[kind] += 1
            return fn(*a, **k)
        return body

    for table in ("ATTENTION", "MIXERS"):
        monkeypatch.setattr(layer_stack, table, {
            kind: counted(kind, fn)
            for kind, fn in getattr(layer_stack, table).items()})
    real_scan = layer_stack.scan_layers

    def scan_layers(body, x, layers, pools):
        scans.append(jax.tree_util.tree_leaves(layers)[0].shape[0])
        return real_scan(body, x, layers, pools)

    monkeypatch.setattr(layer_stack, "scan_layers", scan_layers)
    tq = 8
    fn, _donate = eng._make_ragged_fn(tq)
    jax.jit(fn).lower(*eng._ragged_arg_structs(tq))
    # a layer kind is (attention kind, FFN kind): a dense first layer
    # and the expert layers after it are two kinds over one attention
    kinds = collections.Counter(eng._layer_kinds)
    assert max(kinds.values()) > 1          # depth would show
    want = collections.Counter(a for a, _ in kinds)
    assert traced == want, (traced, kinds)
    if arch == "llama_dense":
        assert scans == [model.config.num_hidden_layers]
    elif arch == "phi4flash":
        # the two repeats, each one scan over its pairs
        assert scans == [4, 3]
    else:
        assert scans == []
