"""Varlen (unpadded) flash attention — TPU Pallas, forward and backward.

TPU-native analog of the reference's FA2 varlen path
(/root/reference/paddle/phi/kernels/gpu/flash_attn_kernel.cu FlashAttnUnpadded
+ python/paddle/nn/functional/flash_attention.py:756 flash_attn_unpadded):
concatenated sequences [total_tokens, heads, head_dim] with cu_seqlens
offsets, no O(S^2) score materialization.

Design: segment-ids (the splash-attention idiom) instead of the CUDA
kernel's per-sequence grid — every token carries (segment, position-in-
segment); the online-softmax kernels mask cross-segment pairs, and per-block
[lo, hi) kv-ranges are precomputed with XLA and handed to the kernels via
scalar prefetch (SMEM), so compute stays O(sum s_i^2) like FA2-varlen, not
O(T^2).  Total-token counts are padded to the 128 lane quantum with a
sentinel segment that matches nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from .flash_attention import _dot_f32, _pick_block

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

_NEG_INF = float("-inf")


# ---------------------------------------------------------------------------
# Host-side (XLA) segment metadata
# ---------------------------------------------------------------------------

def _segment_meta(cu, total, pad_to, pad_seg):
    """seg[pad_to] (pad rows get pad_seg), rel[pad_to], both int32."""
    pos = jnp.arange(pad_to, dtype=jnp.int32)
    seg = jnp.searchsorted(cu.astype(jnp.int32), pos, side="right") - 1
    seg = jnp.where(pos < total, seg, pad_seg)
    rel = pos - cu.astype(jnp.int32)[jnp.clip(seg, 0, cu.shape[0] - 2)]
    return seg, rel


def _block_bounds_q(seg_q, rel_q, cu_k, block_q, block_k, nkb, causal):
    """Per-q-block kv row-range -> block range [lo_b, hi_b) (int32 [nqb])."""
    cu_k = cu_k.astype(jnp.int32)
    nseq = cu_k.shape[0] - 1
    valid = seg_q < nseq                          # pad rows contribute nothing
    seg_c = jnp.clip(seg_q, 0, nseq - 1)
    row_lo = jnp.where(valid, cu_k[seg_c], jnp.int32(2 ** 30))
    if causal:
        row_hi = jnp.where(valid, cu_k[seg_c] + rel_q + 1, 0)
    else:
        row_hi = jnp.where(valid, cu_k[seg_c + 1], 0)
    nqb = seg_q.shape[0] // block_q
    lo = jnp.min(row_lo.reshape(nqb, block_q), axis=1) // block_k
    hi = -(-jnp.max(row_hi.reshape(nqb, block_q), axis=1) // block_k)
    lo = jnp.clip(lo, 0, nkb)
    hi = jnp.clip(hi, lo, nkb)
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def _block_bounds_k(seg_k, rel_k, cu_q, block_q, block_k, nqb, causal):
    """Per-k-block q row-range -> block range [lo_b, hi_b) (int32 [nkb])."""
    cu_q = cu_q.astype(jnp.int32)
    nseq = cu_q.shape[0] - 1
    valid = seg_k < nseq
    seg_c = jnp.clip(seg_k, 0, nseq - 1)
    if causal:
        row_lo = jnp.where(valid, cu_q[seg_c] + rel_k, jnp.int32(2 ** 30))
    else:
        row_lo = jnp.where(valid, cu_q[seg_c], jnp.int32(2 ** 30))
    row_hi = jnp.where(valid, cu_q[seg_c + 1], 0)
    nkb = seg_k.shape[0] // block_k
    lo = jnp.min(row_lo.reshape(nkb, block_k), axis=1) // block_q
    hi = -(-jnp.max(row_hi.reshape(nkb, block_k), axis=1) // block_q)
    lo = jnp.clip(lo, 0, nqb)
    hi = jnp.clip(hi, lo, nqb)
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def _pad_tokens(x, pad_to):
    t = x.shape[0]
    if t == pad_to:
        return x
    return jnp.pad(x, ((0, pad_to - t),) + ((0, 0),) * (x.ndim - 1))


# ---------------------------------------------------------------------------
# Kernels.  Layout inside: q/k/v [H, T, D]; seg/rel [1, T] int32.
# Scalar-prefetch: lo_b/hi_b per grid block.
# ---------------------------------------------------------------------------

def _vfwd_kernel(lo_ref, hi_ref, q_ref, k_ref, v_ref, sq_ref, rq_ref,
                 sk_ref, rk_ref, o_ref, lse_ref, *, sm_scale, block_k,
                 causal):
    q = q_ref[...]
    block_q, d = q.shape
    qb = pl.program_id(1)

    acc = jnp.zeros((block_q, d), jnp.float32)
    m_i = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l_i = jnp.zeros((block_q,), jnp.float32)
    seg_q = sq_ref[0, :]
    rel_q = rq_ref[0, :]

    def body(kb, carry):
        acc, m_i, l_i = carry
        k = k_ref[pl.dslice(kb * block_k, block_k), :]
        v = v_ref[pl.dslice(kb * block_k, block_k), :]
        seg_k = sk_ref[0, pl.dslice(kb * block_k, block_k)]
        rel_k = rk_ref[0, pl.dslice(kb * block_k, block_k)]
        s = _dot_f32(q, k, ((1,), (1,))) * sm_scale
        ok = seg_q[:, None] == seg_k[None, :]
        if causal:
            ok &= rel_q[:, None] >= rel_k[None, :]
        s = jnp.where(ok, s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[:, None])           # masked entries -> 0
        alpha = jnp.where(jnp.isneginf(m_i), 0.0, jnp.exp(m_i - m_safe))
        l_new = alpha * l_i + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + _dot_f32(p.astype(v.dtype), v,
                                              ((1,), (0,)))
        return acc, m_new, l_new

    acc, m_i, l_i = jax.lax.fori_loop(lo_ref[qb], hi_ref[qb], body,
                                      (acc, m_i, l_i))
    has = l_i > 0.0
    o_ref[...] = jnp.where(has[:, None], acc / jnp.where(has, l_i, 1.0)[:, None],
                           0.0).astype(o_ref.dtype)
    lse_ref[...] = jnp.where(has, m_i + jnp.log(jnp.where(has, l_i, 1.0)),
                             _NEG_INF)[None, :]


def _vbwd_dkdv_kernel(lo_ref, hi_ref, q_ref, do_ref, lse_ref, delta_ref,
                      sq_ref, rq_ref, k_ref, v_ref, sk_ref, rk_ref,
                      dk_ref, dv_ref, *, sm_scale, block_q, causal):
    k = k_ref[...]
    v = v_ref[...]
    block_k, d = k.shape
    kb = pl.program_id(1)
    seg_k = sk_ref[0, :]
    rel_k = rk_ref[0, :]

    dk = jnp.zeros((block_k, d), jnp.float32)
    dv = jnp.zeros((block_k, d), jnp.float32)

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[pl.dslice(qb * block_q, block_q), :]
        do = do_ref[pl.dslice(qb * block_q, block_q), :]
        lse = lse_ref[0, pl.dslice(qb * block_q, block_q)]
        delta = delta_ref[0, pl.dslice(qb * block_q, block_q)]
        seg_q = sq_ref[0, pl.dslice(qb * block_q, block_q)]
        rel_q = rq_ref[0, pl.dslice(qb * block_q, block_q)]
        st = _dot_f32(k, q, ((1,), (1,))) * sm_scale   # [block_k, block_q]
        ok = seg_k[:, None] == seg_q[None, :]
        if causal:
            ok &= rel_q[None, :] >= rel_k[:, None]
        st = jnp.where(ok, st, _NEG_INF)
        lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
        pt = jnp.exp(st - lse_safe[None, :])           # masked -> 0
        ptc = pt.astype(do.dtype)
        dv = dv + _dot_f32(ptc, do, ((1,), (0,)))
        dpt = _dot_f32(v, do, ((1,), (1,)))
        dst = pt * (dpt - delta[None, :]) * sm_scale
        dk = dk + _dot_f32(dst.astype(q.dtype), q, ((1,), (0,)))
        return dk, dv

    dk, dv = jax.lax.fori_loop(lo_ref[kb], hi_ref[kb], body, (dk, dv))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _vbwd_dq_kernel(lo_ref, hi_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    q_ref, sq_ref, rq_ref, sk_ref, rk_ref, dq_ref, *,
                    sm_scale, block_k, causal):
    q = q_ref[...]
    do = do_ref[...]
    lse = lse_ref[0, :]
    delta = delta_ref[0, :]
    seg_q = sq_ref[0, :]
    rel_q = rq_ref[0, :]
    block_q, d = q.shape
    qb = pl.program_id(1)

    dq = jnp.zeros((block_q, d), jnp.float32)
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)

    def body(kb, dq):
        k = k_ref[pl.dslice(kb * block_k, block_k), :]
        v = v_ref[pl.dslice(kb * block_k, block_k), :]
        seg_k = sk_ref[0, pl.dslice(kb * block_k, block_k)]
        rel_k = rk_ref[0, pl.dslice(kb * block_k, block_k)]
        s = _dot_f32(q, k, ((1,), (1,))) * sm_scale
        ok = seg_q[:, None] == seg_k[None, :]
        if causal:
            ok &= rel_q[:, None] >= rel_k[None, :]
        s = jnp.where(ok, s, _NEG_INF)
        p = jnp.exp(s - lse_safe[:, None])             # masked -> 0
        dp = _dot_f32(do, v, ((1,), (1,)))
        ds = p * (dp - delta[:, None]) * sm_scale
        return dq + _dot_f32(ds.astype(k.dtype), k, ((1,), (0,)))

    dq = jax.lax.fori_loop(lo_ref[qb], hi_ref[qb], body, dq)
    dq_ref[...] = dq.astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

# Shared index maps over grid (head, block) + 2 prefetch refs (ignored):
# positioned blocks along the token dim vs whole-array blocks.
def _map_blk(hh, b, lo, hi):      # [H, T, D] block b along tokens
    return (hh, b, 0)


def _map_full(hh, b, lo, hi):     # [H, T, D] whole token dim
    return (hh, 0, 0)


def _map_vec_blk(hh, b, lo, hi):  # [1, T] int vectors, block b
    return (0, b)


def _map_vec_full(hh, b, lo, hi):
    return (0, 0)


def _map_hvec_blk(hh, b, lo, hi):  # [H, 1, T] lse/delta, block b
    return (hh, 0, b)



def _prep(q, k, v, cu_q, cu_k, causal):
    tq, h, d = q.shape
    tk = k.shape[0]
    nseq = cu_q.shape[0] - 1
    from ...tune import kernel_config
    cfg = kernel_config("flash_attention_varlen",
                        {"seq_q": tq, "seq_k": tk, "head_dim": d,
                         "dtype": jnp.dtype(q.dtype).name})
    block_q = _pick_block(max(128, -(-tq // 128) * 128), int(cfg["block_q"]))
    block_k = _pick_block(max(128, -(-tk // 128) * 128), int(cfg["block_k"]))
    pad_q = -(-tq // block_q) * block_q
    pad_k = -(-tk // block_k) * block_k
    # sentinel segments: q pads get nseq, k pads nseq+1 -> never equal
    seg_q, rel_q = _segment_meta(cu_q, tq, pad_q, nseq)
    seg_k, rel_k = _segment_meta(cu_k, tk, pad_k, nseq + 1)
    qr = jnp.swapaxes(_pad_tokens(q, pad_q), 0, 1)       # [H, Tq, D]
    kr = jnp.swapaxes(_pad_tokens(k, pad_k), 0, 1)
    vr = jnp.swapaxes(_pad_tokens(v, pad_k), 0, 1)
    return (qr, kr, vr, seg_q[None], rel_q[None], seg_k[None], rel_k[None],
            block_q, block_k, pad_q, pad_k, tq, h, d)


def _varlen_fwd(q, k, v, cu_q, cu_k, causal, sm_scale):
    (qr, kr, vr, sq, rq, sk, rk, block_q, block_k, pad_q, pad_k,
     tq, h, d) = _prep(q, k, v, cu_q, cu_k, causal)
    nqb, nkb = pad_q // block_q, pad_k // block_k
    lo, hi = _block_bounds_q(sq[0], rq[0], cu_k, block_q, block_k, nkb,
                             causal)

    kernel = functools.partial(_vfwd_kernel, sm_scale=sm_scale,
                               block_k=block_k, causal=causal)
    grid = (h, nqb)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, block_q, d), _map_blk),
                pl.BlockSpec((None, pad_k, d), _map_full),
                pl.BlockSpec((None, pad_k, d), _map_full),
                pl.BlockSpec((1, block_q), _map_vec_blk),      # seg_q
                pl.BlockSpec((1, block_q), _map_vec_blk),      # rel_q
                pl.BlockSpec((1, pad_k), _map_vec_full),        # seg_k
                pl.BlockSpec((1, pad_k), _map_vec_full),        # rel_k
            ],
            out_specs=[
                pl.BlockSpec((None, block_q, d), _map_blk),
                pl.BlockSpec((None, 1, block_q),
                             _map_hvec_blk),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((h, pad_q, d), q.dtype),
            jax.ShapeDtypeStruct((h, 1, pad_q), jnp.float32),
        ],
        interpret=_fa.INTERPRET,
    )(lo, hi, qr, kr, vr, sq, rq, sk, rk)
    return jnp.swapaxes(out, 0, 1)[:tq], lse


def _varlen_bwd(q, k, v, out, lse, g, cu_q, cu_k, causal, sm_scale):
    (qr, kr, vr, sq, rq, sk, rk, block_q, block_k, pad_q, pad_k,
     tq, h, d) = _prep(q, k, v, cu_q, cu_k, causal)
    tk = k.shape[0]
    nqb, nkb = pad_q // block_q, pad_k // block_k
    dor = jnp.swapaxes(_pad_tokens(g, pad_q), 0, 1)
    outr = jnp.swapaxes(_pad_tokens(out, pad_q), 0, 1)
    delta = jnp.sum(dor.astype(jnp.float32) * outr.astype(jnp.float32),
                    axis=-1)[:, None, :]             # [H, 1, pad_q]

    # ---- dk/dv over k blocks
    lo_k, hi_k = _block_bounds_k(sk[0], rk[0], cu_q, block_q, block_k, nqb,
                                 causal)
    dk, dv = pl.pallas_call(
        functools.partial(_vbwd_dkdv_kernel, sm_scale=sm_scale,
                          block_q=block_q, causal=causal),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h, nkb),
            in_specs=[
                pl.BlockSpec((None, pad_q, d), _map_full),   # q
                pl.BlockSpec((None, pad_q, d), _map_full),   # do
                pl.BlockSpec((None, 1, pad_q), _map_full),    # lse
                pl.BlockSpec((None, 1, pad_q), _map_full),    # delta
                pl.BlockSpec((1, pad_q), _map_vec_full),           # seg_q
                pl.BlockSpec((1, pad_q), _map_vec_full),           # rel_q
                pl.BlockSpec((None, block_k, d), _map_blk),  # k
                pl.BlockSpec((None, block_k, d), _map_blk),  # v
                pl.BlockSpec((1, block_k), _map_vec_blk),         # seg_k
                pl.BlockSpec((1, block_k), _map_vec_blk),         # rel_k
            ],
            out_specs=[
                pl.BlockSpec((None, block_k, d), _map_blk),
                pl.BlockSpec((None, block_k, d), _map_blk),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((h, pad_k, d), jnp.float32),
            jax.ShapeDtypeStruct((h, pad_k, d), jnp.float32),
        ],
        interpret=_fa.INTERPRET,
    )(lo_k, hi_k, qr, dor, lse, delta, sq, rq, kr, vr, sk, rk)
    dk = jnp.swapaxes(dk, 0, 1)[:tk].astype(k.dtype)
    dv = jnp.swapaxes(dv, 0, 1)[:tk].astype(v.dtype)

    # ---- dq over q blocks
    lo_q, hi_q = _block_bounds_q(sq[0], rq[0], cu_k, block_q, block_k, nkb,
                                 causal)
    dq = pl.pallas_call(
        functools.partial(_vbwd_dq_kernel, sm_scale=sm_scale,
                          block_k=block_k, causal=causal),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h, nqb),
            in_specs=[
                pl.BlockSpec((None, pad_k, d), _map_full),   # k
                pl.BlockSpec((None, pad_k, d), _map_full),   # v
                pl.BlockSpec((None, block_q, d), _map_blk),  # do
                pl.BlockSpec((None, 1, block_q),
                             _map_hvec_blk),  # lse
                pl.BlockSpec((None, 1, block_q),
                             _map_hvec_blk),  # delta
                pl.BlockSpec((None, block_q, d), _map_blk),  # q
                pl.BlockSpec((1, block_q), _map_vec_blk),          # seg_q
                pl.BlockSpec((1, block_q), _map_vec_blk),          # rel_q
                pl.BlockSpec((1, pad_k), _map_vec_full),            # seg_k
                pl.BlockSpec((1, pad_k), _map_vec_full),            # rel_k
            ],
            out_specs=pl.BlockSpec((None, block_q, d), _map_blk),
        ),
        out_shape=jax.ShapeDtypeStruct((h, pad_q, d), q.dtype),
        interpret=_fa.INTERPRET,
    )(lo_q, hi_q, kr, vr, dor, lse, delta, qr, sq, rq, sk, rk)
    dq = jnp.swapaxes(dq, 0, 1)[:q.shape[0]]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp + eligibility
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _varlen_attention(causal, sm_scale, q, k, v, cu_q, cu_k):
    out, _ = _varlen_fwd(q, k, v, cu_q, cu_k, causal, sm_scale)
    return out


def _varlen_fwd_rule(causal, sm_scale, q, k, v, cu_q, cu_k):
    out, lse = _varlen_fwd(q, k, v, cu_q, cu_k, causal, sm_scale)
    return out, (q, k, v, out, lse, cu_q, cu_k)


def _varlen_bwd_rule(causal, sm_scale, res, g):
    q, k, v, out, lse, cu_q, cu_k = res
    dq, dk, dv = _varlen_bwd(q, k, v, out, lse, g, cu_q, cu_k, causal,
                             sm_scale)
    return dq, dk, dv, None, None


_varlen_attention.defvjp(_varlen_fwd_rule, _varlen_bwd_rule)

def use_varlen_flash(q, k, causal) -> bool:
    """Eligibility (same policy as the fixed-shape kernel,
    flash_attention.py:use_flash): flag + the kernels' static claim on
    this platform and shape.  A launch Mosaic refuses fails the compile
    of the program that contains it."""
    from ...core.flags import get_flag
    if not _HAS_PALLAS or not get_flag("use_pallas_kernels"):
        return False
    if jax.default_backend() != "tpu" and not _fa.INTERPRET:
        return False
    if q.ndim != 3 or k.ndim != 3 or q.shape[2] != k.shape[2]:
        return False
    if q.shape[1] != k.shape[1]:      # GQA via composition fallback
        return False
    if q.shape[2] not in (64, 128, 256):
        return False
    return jnp.dtype(q.dtype).name in ("float32", "bfloat16")
