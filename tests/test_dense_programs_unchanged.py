"""(g) The dense decoder's programs are the parent's: the float step
builder now runs on ``layer_stack`` (inference/layer_stack.py), and what
it traces must be, operation for operation, what the hand-written builder
of the commit before traced.  ``_parent_ragged_fn`` below is that
builder, frozen here (PR 27's ``LLMEngine._make_ragged_fn``, ``self``
spelled ``eng``, comments dropped); the copy-on-write program likewise."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu.inference import LLMEngine, serving
from paddle_tpu.inference.sampling import sample_tokens
from paddle_tpu.inference.serving import _scan_layers
from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                     _rms_weight, _rope_positions)
from paddle_tpu.ops.pallas import paged_attention as _pa


def _parent_ragged_fn(eng, Tq):
    nh, kvh, d = eng._nh, eng._kvh, eng._hd
    bs = eng.block_size
    B = eng.max_num_seqs
    with_logits = eng._with_logits
    eps = eng.config.rms_norm_eps
    theta = eng.config.rope_theta
    if eng.kv_dtype == "int8":
        return eng._make_ragged_fn_q8(Tq)
    tp = eng.tp
    nh, kvh = nh // tp, kvh // tp
    shard_head = eng._shard_head
    mm, embed, head_logits = eng._weight_ops()
    use_pallas = eng.attention_path.startswith("pallas")

    def run(params, kc, vc, toks, cu, kvl, bt, lidx, samp):
        seg, rel = _pa.ragged_segments(cu, kvl, Tq)
        with jax.named_scope("embed"):
            x = embed(params, toks)                       # [Tq, H]

        def body(x, inp):
            p, kcl, vcl = inp
            with jax.named_scope("norm"):
                h = _rms_weight(x, p["ln1"], eps)
            with jax.named_scope("qkv"):
                q = mm(h, p, "wq").reshape(Tq, nh, d)
                k = mm(h, p, "wk").reshape(Tq, kvh, d)
                v = mm(h, p, "wv").reshape(Tq, kvh, d)
            with jax.named_scope("rope"):
                q = _rope_positions(q, rel, theta)
                k = _rope_positions(k, rel, theta)
            with jax.named_scope("kv_write"):
                blk = bt[seg, rel // bs]                  # [Tq]
                slot = rel % bs
                kcl = kcl.at[blk, :, slot, :].set(k.astype(kcl.dtype))
                vcl = vcl.at[blk, :, slot, :].set(v.astype(vcl.dtype))
            with jax.named_scope("attn"):
                if use_pallas:
                    att = _pa.ragged_paged_attention_packed(
                        q, kcl, vcl, bt, cu, kvl)
                else:
                    att = _pa.ragged_paged_reference_segrel(
                        q, kcl, vcl, bt, seg, rel)
                if tp > 1:
                    att = lax.all_gather(att, "tp", axis=1,
                                         tiled=True)
            with jax.named_scope("o_proj"):
                x = x + mm(att.reshape(Tq, tp * nh * d), p, "wo")
            with jax.named_scope("norm"):
                h2 = _rms_weight(x, p["ln2"], eps)
            with jax.named_scope("mlp"):
                a = jax.nn.silu(mm(h2, p, "gate").astype(jnp.float32)
                                ).astype(h2.dtype) * mm(h2, p, "up")
                x = x + mm(a, p, "down")
            return x, (kcl, vcl)

        with jax.named_scope("layers"):
            x, (kc, vc) = _scan_layers(body, x, params["layers"],
                                       (kc, vc))
        with jax.named_scope("norm"):
            h = _rms_weight(x, params["norm_f"], eps)
        with jax.named_scope("head"):
            hsel = h[lidx]                                # [Lq, H]
            logits = head_logits(params, hsel)            # [Lq, V]
            if shard_head:
                logits = lax.all_gather(logits, "tp", axis=1,
                                        tiled=True)
        with jax.named_scope("sample"):
            sampled = sample_tokens(logits, samp)
            fin = jnp.all(jnp.isfinite(logits), axis=-1)  # [Lq]
        if with_logits:
            return sampled, fin, logits, kc, vc
        return sampled, fin, kc, vc

    return eng._wrap_tp(run, 6), (1, 2)


def _parent_cow_fn(eng):
    def run(kc, vc, s, d):
        kc = kc.at[:, d].set(kc[:, s])
        vc = vc.at[:, d].set(vc[:, s])
        return kc, vc

    return run, (0, 1)


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM(LlamaConfig.tiny(vocab=97, hidden=32, layers=3,
                                             heads=4, ffn=64, seq=64))


def _engine(model, **kw):
    return LLMEngine(model, max_num_seqs=4, block_size=8, max_model_len=64,
                     max_prefill_tokens=32, prefill_token_bucket=16, **kw)


@pytest.mark.parametrize("kw", [{}, {"drafter": "ngram", "spec_k": 2},
                                {"tp": 2}],
                         ids=["plain", "with_logits", "tp2"])
@pytest.mark.parametrize("Tq", [4, 32])
def test_dense_step_program_is_the_parents(model, kw, Tq):
    eng = _engine(model, **kw)
    args = eng._ragged_arg_structs(Tq)
    new, donate = eng._make_ragged_fn(Tq)
    old, old_donate = _parent_ragged_fn(eng, Tq)
    assert donate == old_donate == (1, 2)
    assert str(jax.make_jaxpr(new)(*args)) == str(jax.make_jaxpr(old)(*args))


def test_dense_programs_of_program_specs_are_the_parents(model):
    eng = _engine(model)
    specs = {s.name: s for s in eng.program_specs()}
    assert sorted(specs) == ["serving.cow_copy", "serving.ragged_step"]
    step, cow = specs["serving.ragged_step"], specs["serving.cow_copy"]
    old, _ = _parent_ragged_fn(eng, 16)
    assert str(jax.make_jaxpr(step.fn)(*step.args)) \
        == str(jax.make_jaxpr(old)(*step.args))
    assert tuple(step.donate_argnums) == (1, 2)
    old_cow, old_donate = _parent_cow_fn(eng)
    assert str(jax.make_jaxpr(cow.fn)(*cow.args)) \
        == str(jax.make_jaxpr(old_cow)(*cow.args))
    assert tuple(cow.donate_argnums) == old_donate


def test_layer_kinds_of_the_dense_decoder(model):
    eng = _engine(model)
    assert eng._layer_kinds == [("gqa", "swiglu")] * 3
    assert not eng._latent and len(eng._pools()) == 2
    assert eng.kv_page_bytes() == 2 * 3 * 4 * 8 * 8 * 4   # K, V: L Hkv bs D f32
    q8 = _engine(model, kv_dtype="int8")
    assert len(q8._pools()) == 4
    assert q8.kv_page_bytes() == 2 * 3 * 4 * 8 * 8 + 2 * 3 * 4 * 4
