"""Fused functional ops (reference: python/paddle/incubate/nn/functional/).

Each op executes as ONE compiled XLA program via the eager dispatch cache;
on TPU the hot ones additionally route to Pallas kernels (see
paddle_tpu.ops.pallas).
"""
from .fused_moe import fused_moe  # noqa: F401
from .fused_ops import (  # noqa: F401
    fused_bias_dropout_residual_layer_norm, fused_dropout_add,
    fused_layer_norm, fused_linear, fused_matmul_bias, fused_rms_norm,
    fused_rotary_position_embedding, swiglu,
)

__all__ = [
    "fused_moe", "fused_rms_norm", "fused_layer_norm",
    "fused_rotary_position_embedding", "swiglu", "fused_matmul_bias",
    "fused_linear", "fused_dropout_add",
    "fused_bias_dropout_residual_layer_norm",
    "fused_multi_head_attention", "fused_feedforward",
    "fused_multi_transformer", "fused_linear_activation", "fused_bias_act",
    "variable_length_memory_efficient_attention",
    "masked_multihead_attention", "blha_get_max_len",
    "block_multihead_attention",
]


# ---------------------------------------------------------------------------
# Remaining reference fused-op surface (incubate/nn/functional/
# {fused_transformer,fused_matmul_bias,masked_multihead_attention,
# block_multihead_attention}.py).  Under XLA "fused" means "one traced
# composition the compiler fuses" — these are faithful compositions with
# the reference call contracts; the CUDA megakernels they mirror are cited.
# ---------------------------------------------------------------------------

def fused_linear_activation(x, y, bias, trans_x=False, trans_y=False,
                            activation="gelu"):
    """linear + bias + act in one traced region (reference
    fused_linear_activation over cublasLt epilogue)."""
    from ....nn import functional as F
    from ....ops.manipulation import transpose as _tp
    if trans_x:
        x = _tp(x, list(range(x.ndim - 2)) + [x.ndim - 1, x.ndim - 2])
    out = fused_linear(x, y, bias, transpose_weight=trans_y)
    act = {"gelu": F.gelu, "relu": F.relu, "none": lambda t: t}[activation]
    return act(out)


def fused_bias_act(x, bias=None, dequant_scales=None, shift=None, smooth=None,
                   act_method="gelu", compute_dtype="default",
                   quant_scale=-1.0, quant_round_type=0, quant_max_bound=0.0,
                   quant_min_bound=0.0):
    """bias + activation (reference fused_bias_act kernel surface; the
    quant paths are inference-engine specials and unsupported here)."""
    if dequant_scales is not None or quant_scale != -1.0:
        raise NotImplementedError(
            "fused_bias_act quantized paths are inference-engine specials; "
            "use the float path")
    from ....nn import functional as F
    if bias is not None:
        x = x + bias
    acts = {"gelu": F.gelu, "relu": F.relu, "silu": F.silu,
            "swish": F.silu, "none": lambda t: t}
    return acts[act_method](x)


def fused_multi_head_attention(x, qkv_weight, linear_weight,
                               pre_layer_norm=False, pre_ln_scale=None,
                               pre_ln_bias=None, ln_scale=None, ln_bias=None,
                               pre_ln_epsilon=1e-5, qkv_bias=None,
                               linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.5,
                               attn_dropout_rate=0.5, ln_epsilon=1e-5,
                               training=True, mode="upscale_in_train",
                               ring_id=-1, add_residual=True, num_heads=None,
                               name=None):
    """Whole-MHA block (reference fused_attention op,
    fused_transformer.py:fused_multi_head_attention): [pre-LN] -> qkv ->
    SDPA -> out proj -> dropout -> [+residual] -> [post-LN]."""
    from ....nn import functional as F
    from ....ops.manipulation import reshape, transpose

    if cache_kv is not None:
        raise NotImplementedError(
            "fused_multi_head_attention with cache_kv (incremental decode) "
            "is not implemented; use LlamaForCausalLM.generate's compiled "
            "KV-cache loop")
    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, x.shape[-1:], weight=pre_ln_scale,
                         bias=pre_ln_bias, epsilon=pre_ln_epsilon)
    b, s, h = x.shape
    # qkv_weight [3, n_heads, head_dim, h] (reference layout)
    nh = qkv_weight.shape[1]
    hd = qkv_weight.shape[2]
    w = transpose(reshape(qkv_weight, [3 * nh * hd, h]), [1, 0])
    qkv = F.linear(x, w, None)
    if qkv_bias is not None:
        qkv = qkv + reshape(qkv_bias, [3 * nh * hd])
    qkv = reshape(qkv, [b, s, 3, nh, hd])
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    att = F.scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask,
        dropout_p=attn_dropout_rate if training else 0.0,
        is_causal=False, training=training)
    att = reshape(att, [b, s, nh * hd])
    out = F.linear(att, linear_weight, linear_bias)
    out = F.dropout(out, dropout_rate, training=training, mode=mode)
    if add_residual:
        out = residual + out
    if not pre_layer_norm:
        out = F.layer_norm(out, out.shape[-1:], weight=ln_scale,
                           bias=ln_bias, epsilon=ln_epsilon)
    return out


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True,
                      mode="upscale_in_train", ring_id=-1, name=None):
    """Transformer FFN block (reference fused_feedforward op)."""
    from ....nn import functional as F

    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, x.shape[-1:], weight=ln1_scale, bias=ln1_bias,
                         epsilon=ln1_epsilon)
    act = {"relu": F.relu, "gelu": F.gelu}[activation]
    h = act(F.linear(x, linear1_weight, linear1_bias))
    h = F.dropout(h, dropout1_rate, training=training, mode=mode)
    h = F.linear(h, linear2_weight, linear2_bias)
    h = F.dropout(h, dropout2_rate, training=training, mode=mode)
    out = residual + h
    if not pre_layer_norm:
        out = F.layer_norm(out, out.shape[-1:], weight=ln2_scale,
                           bias=ln2_bias, epsilon=ln2_epsilon)
    return out


def fused_multi_transformer(x, ln_scales, ln_biases, qkv_weights, qkv_biases,
                            linear_weights, linear_biases, ffn_ln_scales,
                            ffn_ln_biases, ffn1_weights, ffn1_biases,
                            ffn2_weights, ffn2_biases, pre_layer_norm=True,
                            epsilon=1e-05, cache_kvs=None, pre_caches=None,
                            seq_lens=None, rotary_embs=None, time_step=None,
                            attn_mask=None, dropout_rate=0.0,
                            rotary_emb_dims=0, activation="gelu",
                            training=False, mode="upscale_in_train",
                            trans_qkvw=True, ring_id=-1, name=None):
    """Stacked decoder blocks in one call (reference fused_multi_transformer
    inference op).  Composition over the per-layer fused blocks."""
    out = x
    for i in range(len(qkv_weights)):
        out = fused_multi_head_attention(
            out, qkv_weights[i], linear_weights[i],
            pre_layer_norm=pre_layer_norm, pre_ln_scale=ln_scales[i],
            pre_ln_bias=ln_biases[i] if ln_biases else None,
            ln_scale=ln_scales[i],
            ln_bias=ln_biases[i] if ln_biases else None,
            pre_ln_epsilon=epsilon, qkv_bias=(qkv_biases[i] if qkv_biases
                                              else None),
            linear_bias=(linear_biases[i] if linear_biases else None),
            attn_mask=attn_mask, dropout_rate=dropout_rate,
            attn_dropout_rate=dropout_rate, ln_epsilon=epsilon,
            training=training, mode=mode)
        out = fused_feedforward(
            out, ffn1_weights[i], ffn2_weights[i],
            linear1_bias=(ffn1_biases[i] if ffn1_biases else None),
            linear2_bias=(ffn2_biases[i] if ffn2_biases else None),
            ln1_scale=ffn_ln_scales[i],
            ln1_bias=(ffn_ln_biases[i] if ffn_ln_biases else None),
            ln2_scale=ffn_ln_scales[i],
            ln2_bias=(ffn_ln_biases[i] if ffn_ln_biases else None),
            dropout1_rate=dropout_rate, dropout2_rate=dropout_rate,
            activation=activation, ln1_epsilon=epsilon, ln2_epsilon=epsilon,
            pre_layer_norm=pre_layer_norm, training=training, mode=mode)
    return out


def variable_length_memory_efficient_attention(query, key, value, seq_lens,
                                               kv_seq_lens, mask=None,
                                               scale=None, causal=False,
                                               pre_cache_length=0):
    """Variable-length SDPA (reference memory_efficient_attention CUTLASS
    kernel surface): per-sequence length masks composed onto the fused
    attention path.  query [B, NH, S, D]."""
    import jax.numpy as jnp

    from ....core.tensor import Tensor
    from ....nn import functional as F
    from ....ops.manipulation import transpose

    q = transpose(query, [0, 2, 1, 3])      # -> [B, S, NH, D]
    k = transpose(key, [0, 2, 1, 3])
    v = transpose(value, [0, 2, 1, 3])
    if scale is not None:
        # SDPA divides by sqrt(d); pre-scale q so the net factor is `scale`
        d = q.shape[-1]
        q = q * float(scale * (d ** 0.5))
    B, S = q.shape[0], q.shape[1]
    Sk = k.shape[1]
    sl = seq_lens._data if isinstance(seq_lens, Tensor) else jnp.asarray(seq_lens)
    kl = kv_seq_lens._data if isinstance(kv_seq_lens, Tensor) \
        else jnp.asarray(kv_seq_lens)
    qpos = jnp.arange(S)[None, :]
    kpos = jnp.arange(Sk)[None, :]
    valid = (qpos < sl.reshape(-1, 1))[:, :, None] & \
            (kpos < kl.reshape(-1, 1))[:, None, :]
    if causal:
        valid = valid & (qpos[0][:, None] >= kpos[0][None, :])[None]
    bias = jnp.where(valid, 0.0, -jnp.inf)[:, None, :, :]
    if mask is not None:
        m = mask._data if isinstance(mask, Tensor) else jnp.asarray(mask)
        bias = bias + m
    out = F.scaled_dot_product_attention(q, k, v, attn_mask=Tensor(bias))
    # padding query rows see only -inf scores (NaN softmax) — zero them,
    # matching the reference's defined-zero contract for padded positions
    qvalid = (qpos < sl.reshape(-1, 1))[:, :, None, None]
    out = Tensor(jnp.where(qvalid, out._data, 0.0))
    return transpose(out, [0, 2, 1, 3])


def masked_multihead_attention(x, cache_kv=None, bias=None, src_mask=None,
                               cum_offsets=None, sequence_lengths=None,
                               rotary_tensor=None, beam_cache_offset=None,
                               qkv_out_scale=None, out_shift=None,
                               out_smooth=None, seq_len=1, rotary_emb_dims=1,
                               use_neox_rotary_style=False,
                               compute_dtype="default", out_scale=-1,
                               quant_round_type=1, quant_max_bound=127.0,
                               quant_min_bound=-127.0, name=None):
    """Single-token decode attention over a KV cache (reference
    incubate/nn/functional/masked_multihead_attention.py over the CUDA
    decode megakernel).  One jittable XLA step: split the fused qkv row,
    append k/v at each sequence's current position, attend over the cache.

    x [B, 3*H*D]; cache_kv [2, B, H, M, D]; bias [3, H, D];
    src_mask [B, 1, 1, S] additive over the first S cache positions;
    sequence_lengths [B, 1] = tokens already in the cache (defaults to
    S-1 from src_mask, else seq_len-1).  Returns (out [B, H*D],
    updated cache).  The int8-quant epilogues and beam-search cache
    reordering remain serving-engine deferrals.
    """
    if qkv_out_scale is not None or out_shift is not None \
            or out_smooth is not None or out_scale > 0:
        raise NotImplementedError(
            "masked_multihead_attention int8-quant epilogue is a serving "
            "deferral; run the float path (see quantization/ for PTQ/QAT)")
    if beam_cache_offset is not None:
        raise NotImplementedError(
            "beam_cache_offset reordering is a serving deferral; "
            "LlamaForCausalLM.generate covers sampled decode")
    if cache_kv is None:
        raise ValueError("masked_multihead_attention requires cache_kv")

    import jax
    import jax.numpy as jnp

    from ....core import dispatch as D

    def impl(xa, cache, *opt, has_bias, has_mask, has_len, has_rope,
             neox, rot_dims):
        it = iter(opt)
        ba = next(it) if has_bias else None
        mask = next(it) if has_mask else None
        slen = next(it) if has_len else None
        rope = next(it) if has_rope else None
        _, B, H, M, D = cache.shape
        qkv = xa.reshape(B, 3, H, D)
        if ba is not None:
            qkv = qkv + ba[None].astype(qkv.dtype)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]       # [B, H, D]
        if slen is not None:
            t = slen.reshape(B).astype(jnp.int32)        # per-seq position
        elif mask is not None:
            t = jnp.full((B,), mask.shape[-1] - 1, jnp.int32)
        else:
            t = jnp.full((B,), seq_len - 1, jnp.int32)
        if rope is not None:
            # rotary_tensor [B, 1, 1, S, D]: cos in d<D/2, sin mirrored
            # (non-neox interleaved style folded to half layout)
            rot = rope.reshape(B, -1, D)                 # [B, S, D]
            cur = jnp.take_along_axis(
                rot, t[:, None, None].astype(jnp.int32), axis=1)[:, 0]
            cos, sin = cur[..., :D // 2], cur[..., D // 2:]

            def rot_half(u):
                u1, u2 = u[..., :D // 2], u[..., D // 2:]
                return jnp.concatenate(
                    [u1 * cos[:, None] - u2 * sin[:, None],
                     u2 * cos[:, None] + u1 * sin[:, None]], axis=-1)
            q, k = rot_half(q), rot_half(k)
        # scatter k/v into each sequence's slot t[b]
        bidx = jnp.arange(B)
        cache = cache.at[0, bidx, :, t, :].set(k.astype(cache.dtype))
        cache = cache.at[1, bidx, :, t, :].set(v.astype(cache.dtype))
        kc = cache[0].astype(jnp.float32)                # [B, H, M, D]
        vc = cache[1].astype(jnp.float32)
        scores = jnp.einsum("bhd,bhmd->bhm", q.astype(jnp.float32),
                            kc) / jnp.sqrt(jnp.float32(D))
        pos = jnp.arange(M)[None, None, :]
        valid = pos <= t[:, None, None]
        if mask is not None:
            S = mask.shape[-1]
            add = jnp.zeros((B, 1, M), jnp.float32)
            add = add.at[:, :, :S].set(
                mask.reshape(B, 1, S).astype(jnp.float32))
            scores = scores + add
        scores = jnp.where(valid, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhm,bhmd->bhd", probs, vc)
        return out.reshape(B, H * D).astype(xa.dtype), cache

    opt_ts, flags = [], {}
    for key, tval in (("has_bias", bias), ("has_mask", src_mask),
                      ("has_len", sequence_lengths),
                      ("has_rope", rotary_tensor)):
        flags[key] = tval is not None
        if tval is not None:
            opt_ts.append(tval)
    return D.apply("masked_multihead_attention", impl,
                   (x, cache_kv, *opt_ts),
                   {**flags, "neox": bool(use_neox_rotary_style),
                    "rot_dims": int(rotary_emb_dims)}, num_outputs=2)


def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size):
    """Max enc/dec lengths for block attention (reference blha_get_max_len)."""
    import jax.numpy as jnp

    from ....core.tensor import Tensor
    e = seq_lens_encoder._data if isinstance(seq_lens_encoder, Tensor) \
        else jnp.asarray(seq_lens_encoder)
    d = seq_lens_decoder._data if isinstance(seq_lens_decoder, Tensor) \
        else jnp.asarray(seq_lens_decoder)
    return Tensor(jnp.max(e).reshape(1)), Tensor(jnp.max(d).reshape(1))


def block_multihead_attention(qkv, key_cache, value_cache, seq_lens_encoder,
                              seq_lens_decoder, seq_lens_this_time,
                              padding_offsets=None, cum_offsets=None,
                              cu_seqlens_q=None, cu_seqlens_k=None,
                              block_tables=None, pre_key_cache=None,
                              pre_value_cache=None,
                              cache_k_quant_scales=None,
                              cache_v_quant_scales=None,
                              cache_k_dequant_scales=None,
                              cache_v_dequant_scales=None,
                              qkv_out_scale=None, qkv_bias=None,
                              out_shift=None, out_smooth=None,
                              max_enc_len_this_time=None,
                              max_dec_len_this_time=None, rope_emb=None,
                              mask=None, tgt_mask=None, max_seq_len=-1,
                              block_size=64, use_neox_style=False, **kwargs):
    """Paged-KV attention (reference blha over the paged CUDA kernels).

    Implemented modes (jittable XLA):
    - DECODE: every sequence contributes one token
      (seq_lens_this_time == 1); k/v scatter into the page given by
      block_tables[b, pos // block_size] and attention runs over the
      sequence's gathered pages.
    - PREFILL: sequences run causal self-attention over their own fresh
      tokens (seq_lens_decoder == 0) and their k/v fill the pages.

    qkv [token_num, 3*H*D]; {key,value}_cache [max_blocks, H, bs, D];
    block_tables [B, blocks_per_seq].  Returns (out [token_num, H*D],
    qkv, updated key_cache, updated value_cache) like the reference's
    (fmha_out, qkv_out, cache_k_out, cache_v_out).  int8/fp8 cache quant,
    pre-caches and speculative verify remain serving deferrals.
    """
    if any(t is not None for t in (cache_k_quant_scales,
                                   cache_v_quant_scales,
                                   cache_k_dequant_scales,
                                   cache_v_dequant_scales, qkv_out_scale,
                                   out_shift, out_smooth)):
        raise NotImplementedError(
            "block_multihead_attention quantized-cache paths are serving "
            "deferrals; run the float cache")
    if pre_key_cache is not None or pre_value_cache is not None:
        raise NotImplementedError(
            "block_multihead_attention pre-cache (system prompt cache) is "
            "a serving deferral")
    if block_tables is None:
        raise ValueError("block_multihead_attention requires block_tables")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ....core import dispatch as D_
    from ....core.tensor import Tensor as T_

    def _arr(t):
        return t._data if isinstance(t, T_) else jnp.asarray(t)

    enc = np.asarray(_arr(seq_lens_encoder)).reshape(-1)
    dec = np.asarray(_arr(seq_lens_decoder)).reshape(-1)
    this = np.asarray(_arr(seq_lens_this_time)).reshape(-1)
    B = this.shape[0]
    decode_mode = bool((this == 1).all() and (dec > 0).any())
    prefill_mode = bool((dec == 0).all() and (this == enc).all())
    if not (decode_mode or prefill_mode):
        # MIXED batch (continuous batching): split by sequence kind, run
        # the prefill tokens then the decode tokens over the threaded
        # caches, and merge outputs back into original token order.
        is_dec = (this == 1) & (dec > 0)
        if not ((is_dec) | ((dec == 0) & (this == enc))).all():
            raise NotImplementedError(
                "sequences must be pure prefill (dec==0, this==enc) or "
                "single-token decode (this==1, dec>0)")
        starts = np.concatenate([[0], np.cumsum(this)])
        pre_sel = np.where(~is_dec)[0]
        dec_sel = np.where(is_dec)[0]
        idx_pre = np.concatenate(
            [np.arange(starts[b], starts[b + 1]) for b in pre_sel])
        idx_dec = starts[dec_sel]
        qkv_a = _arr(qkv)
        bt_a = _arr(block_tables)
        bias_kw = {"qkv_bias": qkv_bias}
        out_p, _, kc1, vc1 = block_multihead_attention(
            jnp.take(qkv_a, jnp.asarray(idx_pre), axis=0), key_cache,
            value_cache, enc[pre_sel], dec[pre_sel], this[pre_sel],
            block_tables=bt_a[np.asarray(pre_sel)], block_size=block_size,
            max_seq_len=max_seq_len, use_neox_style=use_neox_style,
            **bias_kw)
        out_d, _, kc2, vc2 = block_multihead_attention(
            jnp.take(qkv_a, jnp.asarray(idx_dec), axis=0), kc1, vc1,
            enc[dec_sel], dec[dec_sel], this[dec_sel],
            block_tables=bt_a[np.asarray(dec_sel)], block_size=block_size,
            max_seq_len=max_seq_len, use_neox_style=use_neox_style,
            **bias_kw)
        merged = jnp.zeros((qkv_a.shape[0], _arr(out_p).shape[1]),
                           _arr(out_p).dtype)
        merged = merged.at[jnp.asarray(idx_pre)].set(_arr(out_p))
        merged = merged.at[jnp.asarray(idx_dec)].set(_arr(out_d))
        return T_(merged), qkv, kc2, vc2

    Hc = _arr(key_cache).shape[1]
    Dh = _arr(key_cache).shape[3]
    bs = int(_arr(key_cache).shape[2])

    def decode_impl(xa, kc, vc, bt, dec_t, *maybe_bias, has_bias,
                    use_pallas):
        from ....ops.pallas import paged_attention as _pa

        qkv_ = xa.reshape(B, 3, Hc, Dh)
        if has_bias:
            qkv_ = qkv_ + maybe_bias[0].reshape(3, Hc, Dh)[None]
        q, k, v = qkv_[:, 0], qkv_[:, 1], qkv_[:, 2]
        t = dec_t.reshape(B).astype(jnp.int32)
        blk = jnp.take_along_axis(bt, (t // bs)[:, None], axis=1)[:, 0]
        slot = t % bs
        kc = kc.at[blk, :, slot, :].set(k.astype(kc.dtype))
        vc = vc.at[blk, :, slot, :].set(v.astype(vc.dtype))
        if use_pallas:
            # walk the block table page-by-page (scalar prefetch) — no
            # dense [B, nblk*bs] gather materializes; q joins the cache
            # dtype (the combination the eligibility check saw)
            out = _pa.paged_decode_attention(q.astype(kc.dtype), kc, vc,
                                             bt, t + 1)
        else:
            out = _pa.paged_decode_reference(q, kc, vc, bt, t + 1)
        return out.reshape(B, Hc * Dh).astype(xa.dtype), kc, vc

    def prefill_impl(xa, kc, vc, bt, lens, *maybe_bias, has_bias,
                     starts, use_varlen):
        import math as _math

        qkv_ = xa.reshape(-1, 3, Hc, Dh)
        if has_bias:
            qkv_ = qkv_ + maybe_bias[0].reshape(3, Hc, Dh)[None]
        q, k, v = qkv_[:, 0], qkv_[:, 1], qkv_[:, 2]   # [T, H, D]
        Ttot = q.shape[0]
        pos_g = jnp.arange(Ttot)
        starts_a = jnp.asarray(starts)
        seg = jnp.searchsorted(starts_a, pos_g, side="right") - 1
        rel = pos_g - starts_a[seg]
        if use_varlen:
            # the prefill IS varlen causal attention: ride the segment-
            # aware pallas flash kernel (flash_attention_varlen.py) — no
            # dense [H, T_total, T_total] score matrix materializes
            from ....ops.pallas.flash_attention_varlen import (
                _varlen_attention)
            cu = jnp.asarray(tuple(starts) + (int(Ttot),), jnp.int32)
            out = _varlen_attention(True, 1.0 / _math.sqrt(Dh),
                                    q, k, v, cu, cu)
        else:
            # segment-masked XLA composition
            same = seg[:, None] == seg[None, :]
            causal = rel[:, None] >= rel[None, :]
            m = same & causal
            scores = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                                k.astype(jnp.float32)) / jnp.sqrt(
                                    jnp.float32(Dh))
            scores = jnp.where(m[None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            probs = jnp.where(m[None], probs, 0.0)
            out = jnp.einsum("hqk,khd->qhd", probs, v.astype(jnp.float32))
        # scatter fresh k/v into pages: token (seg b, rel r) -> block
        # bt[b, r // bs], slot r % bs
        blk = bt[seg, rel // bs]
        kc = kc.at[blk, :, rel % bs, :].set(k.astype(kc.dtype))
        vc = vc.at[blk, :, rel % bs, :].set(v.astype(vc.dtype))
        return (out.reshape(Ttot, Hc * Dh).astype(xa.dtype), kc, vc)

    opt = (qkv_bias,) if qkv_bias is not None else ()
    if decode_mode:
        from ....core.flags import get_flag
        from ....ops.pallas import paged_attention as _pa
        use_pallas = bool(
            get_flag("use_pallas_kernels")
            and (_pa.interpret_mode() or jax.default_backend() == "tpu")
            and _pa.ineligible(Hc, Hc, Dh, bs,
                               _arr(key_cache).dtype) is None)
        out, kc2, vc2 = D_.apply(
            "block_multihead_attention_decode", decode_impl,
            (qkv, key_cache, value_cache, block_tables, seq_lens_decoder,
             *opt), {"has_bias": qkv_bias is not None,
                     "use_pallas": use_pallas}, num_outputs=3)
    else:
        starts = tuple(int(s) for s in np.concatenate([[0],
                                                       np.cumsum(this)[:-1]]))
        from ....core import amp_state
        from ....ops.pallas.flash_attention_varlen import use_varlen_flash
        # probe with the dtype the kernel ACTUALLY runs in (AMP autocasts
        # inside dispatch — attention.py:133 rationale), and a CANONICAL
        # token count: eligibility doesn't depend on T_total, and serving
        # varies it per request mix — probing per T would pay a throwaway
        # fwd+bwd compile on the request path
        cast_to = amp_state.autocast_dtype_for(
            "block_multihead_attention_prefill")
        eff_dtype = cast_to if cast_to is not None else _arr(qkv).dtype
        q_sds = jax.ShapeDtypeStruct((256, Hc, Dh), eff_dtype)
        use_varlen = bool(use_varlen_flash(q_sds, q_sds, True))
        out, kc2, vc2 = D_.apply(
            "block_multihead_attention_prefill", prefill_impl,
            (qkv, key_cache, value_cache, block_tables, seq_lens_this_time,
             *opt), {"has_bias": qkv_bias is not None, "starts": starts,
                     "use_varlen": use_varlen},
            num_outputs=3)
    return out, qkv, kc2, vc2
