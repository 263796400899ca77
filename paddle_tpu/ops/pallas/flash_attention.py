"""Flash attention (TPU Pallas), forward AND backward.

TPU-native analog of the reference's FA2 CUDA kernels
(/root/reference/paddle/phi/kernels/gpu/flash_attn_kernel.cu and
flash_attn_grad_kernel.cu wrapping third_party/flashattn, surfaced at
python/paddle/nn/functional/flash_attention.py:358).

Forward: online-softmax kernel tiled for the MXU, emitting the per-row
logsumexp.  Backward: two Pallas kernels (dk/dv then dq) that RECOMPUTE the
probability tiles from q/k + the saved logsumexp — residuals are O(S·D+S),
never the O(S^2) score matrix.  GQA (num_kv_heads < num_heads) is handled in
the index maps; grouped dk/dv partials are summed over the query-head group.

Layout: q [batch, seq, heads, head_dim]; k/v [batch, seq, kv_heads, head_dim]
(paddle flash_attention layout), output [batch, seq, heads, head_dim].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

try:
    from jax.experimental import pallas as pl
    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

# Tests flip this to run the same kernels via the Pallas interpreter on CPU.
INTERPRET = False


def _fa_blocks(sq: int, sk: int, d: int, dtype_name: str,
               kernel: str = "flash_attention"):
    """Trace-time tuned (block_q, block_k) for this launch shape.

    Geometry flows from the tuning cache (env overrides and forced
    configs win inside kernel_config); _pick_block then snaps each
    preference to a power of two dividing the actual extent."""
    from ...tune import kernel_config
    cfg = kernel_config(kernel, {"seq_q": sq, "seq_k": sk, "head_dim": d,
                                 "dtype": dtype_name})
    return (_pick_block(sq, int(cfg["block_q"])),
            _pick_block(sk, int(cfg["block_k"])))


def _pick_block(seq_len: int, pref: int) -> int:
    """Largest power-of-two block <= pref that divides seq_len (>=128).

    Big blocks matter on TPU: grid programs run sequentially on the one
    TensorCore, so 128-wide tiles at head_dim 64 leave the MXU mostly idle
    on per-program overhead — 512-wide tiles amortize it (measured 2.4x
    step-time win at S=2048 on v5e, tmp/fa_block_sweep).
    """
    b = pref
    while b > 128 and seq_len % b:
        b //= 2
    return min(b, seq_len)


def _repeat_kv(x, group):
    if group == 1:
        return x
    b, s, hk, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, hk, group, d)
                            ).reshape(b, s, hk * group, d)


def _ref_attention(q, k, v, causal):
    """O(S^2) reference composition (numerics oracle + XLA fallback)."""
    group = q.shape[2] // k.shape[2]
    k, v = _repeat_kv(k, group), _repeat_kv(v, group)
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kt = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vt = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * s
    if causal:
        ql, kl = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _dot_f32(a, b, dims):
    """Matmul keeping operands in their storage dtype (bf16 runs the MXU at
    full rate; f32 operands would run at a fraction of it) with float32
    accumulation."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                causal, sm_scale, block_k, kv_len):
    # grid: (batch*heads, q_blocks); refs are [block_q, d] / [kv_len, d]
    # sm_scale folded into q ONCE ([block_q, d] pass) instead of into every
    # [block_q, block_k] score tile; causal masking (2 iotas + cmp + select
    # per tile, all VPU) runs ONLY on diagonal-crossing blocks — interior
    # blocks take the mask-free body.  The VPU passes per tile, not the MXU
    # matmuls, bound this kernel at head_dim 64 (measured on v5e).
    q = (q_ref[...].astype(jnp.float32) * sm_scale).astype(q_ref.dtype)
    block_q, d = q.shape
    q_idx = pl.program_id(1)

    acc = jnp.zeros((block_q, d), jnp.float32)
    m_i = jnp.full((block_q,), -jnp.inf, jnp.float32)
    l_i = jnp.zeros((block_q,), jnp.float32)

    num_k_blocks = kv_len // block_k

    def tile(kb, carry, masked):
        acc, m_i, l_i = carry
        k = k_ref[pl.dslice(kb * block_k, block_k), :]
        v = v_ref[pl.dslice(kb * block_k, block_k), :]
        s = _dot_f32(q, k, ((1,), (1,)))             # [block_q, block_k] f32
        if masked:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_i - m_new)
        l_new = alpha * l_i + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + _dot_f32(p.astype(v.dtype), v,
                                              ((1,), (0,)))
        return acc, m_new, l_new

    carry = (acc, m_i, l_i)
    if causal:
        # interior blocks (entirely below the diagonal): mask-free body
        q_lo = q_idx.astype(jnp.int32) * jnp.int32(block_q)
        q_end = q_lo + jnp.int32(block_q)
        full_hi = q_lo // jnp.int32(block_k)
        hi = jnp.minimum(jnp.int32(num_k_blocks),
                         (q_end - 1) // jnp.int32(block_k) + jnp.int32(1))
        carry = jax.lax.fori_loop(
            jnp.int32(0), full_hi,
            lambda kb, c: tile(kb, c, masked=False), carry)
        carry = jax.lax.fori_loop(
            full_hi, hi, lambda kb, c: tile(kb, c, masked=True), carry)
    else:
        carry = jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(num_k_blocks),
            lambda kb, c: tile(kb, c, masked=False), carry)
    acc, m_i, l_i = carry
    o_ref[...] = (acc / l_i[:, None]).astype(o_ref.dtype)
    # lse ref is [1, block_q]: kept 3-D as [BH, 1, Sq] outside so the block's
    # last-two dims (1, block_q) satisfy Mosaic's (8,128)-divisible-or-full
    # rule.  lse is in the SCALED (q*sm_scale) domain, matching what the
    # backward kernels recompute.
    lse_ref[...] = (m_i + jnp.log(l_i))[None, :]


def _gqa_maps(h, group):
    """Index maps over grid (bh, blk) for q-layout [B*H] and kv-layout
    [B*HK] flattened leading dims (HK = H // group)."""
    hk = h // group

    def q_map(bh, blk):
        return (bh, blk, 0)

    def kv_map(bh, blk):
        kvh = (bh // h) * hk + (bh % h) // group
        return (kvh, 0, 0)

    return q_map, kv_map


def _flash_fwd_pallas(q, k, v, causal):
    """Returns (out, lse); lse is [B*H, 1, Sq] float32 in the scaled domain
    (the singleton dim keeps the Pallas vector blocks TPU-tileable)."""
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    group = h // hk
    sm_scale = 1.0 / math.sqrt(d)
    # flatten batch*heads; layout [BH, S, D]
    qr = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, d)
    kr = jnp.swapaxes(k, 1, 2).reshape(b * hk, sk, d)
    vr = jnp.swapaxes(v, 1, 2).reshape(b * hk, sk, d)

    block_q, block_k = _fa_blocks(sq, sk, d, jnp.dtype(q.dtype).name)

    kernel = functools.partial(_fwd_kernel, causal=causal, sm_scale=sm_scale,
                               block_k=block_k, kv_len=sk)
    q_map, kv_map = _gqa_maps(h, group)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), q_map),
            pl.BlockSpec((None, sk, d), kv_map),
            pl.BlockSpec((None, sk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), q_map),
            pl.BlockSpec((None, 1, block_q), lambda bh, qb: (bh, 0, qb)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32),
        ],
        interpret=INTERPRET,
    )(qr, kr, vr)
    return jnp.swapaxes(out.reshape(b, h, sq, d), 1, 2), lse


def _bwd_dkdv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref,
                     dk_ref, dv_ref, *, causal, sm_scale, block_q, q_len):
    # grid: (batch*heads, k_blocks); k/v refs [block_k, d];
    # q/do refs [q_len, d]; lse/delta refs [1, q_len]
    k = k_ref[...]
    v = v_ref[...]
    block_k, d = k.shape
    k_idx = pl.program_id(1)

    dk = jnp.zeros((block_k, d), jnp.float32)
    dv = jnp.zeros((block_k, d), jnp.float32)
    num_q_blocks = q_len // block_q

    def tile(qb, carry, masked):
        dk, dv = carry
        # sm_scale folded into the [block_q, d] q slice, not the
        # [block_k, block_q] score tile; the dk matmul then needs NO extra
        # dst * sm_scale pass (dk = dst^T (q*sm)).
        q = (q_ref[pl.dslice(qb * block_q, block_q), :]
             .astype(jnp.float32) * sm_scale).astype(q_ref.dtype)
        do = do_ref[pl.dslice(qb * block_q, block_q), :]
        lse = lse_ref[0, pl.dslice(qb * block_q, block_q)]
        delta = delta_ref[0, pl.dslice(qb * block_q, block_q)]
        # transposed score tile: [block_k, block_q] f32
        st = _dot_f32(k, q, ((1,), (1,)))
        if masked:
            k_pos = k_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            st = jnp.where(q_pos >= k_pos, st, -jnp.inf)
        pt = jnp.exp(st - lse[None, :])
        ptc = pt.astype(do.dtype)
        dv = dv + _dot_f32(ptc, do, ((1,), (0,)))
        dpt = _dot_f32(v, do, ((1,), (1,)))  # [block_k, block_q] f32
        dst = pt * (dpt - delta[None, :])
        dk = dk + _dot_f32(dst.astype(q.dtype), q, ((1,), (0,)))
        return dk, dv

    carry = (dk, dv)
    if causal:
        # q blocks [lo, full_lo) cross the diagonal (masked body); q blocks
        # [full_lo, nqb) are entirely below it (mask-free body)
        k_lo = k_idx.astype(jnp.int32) * jnp.int32(block_k)
        lo = k_lo // jnp.int32(block_q)
        full_lo = jnp.minimum(
            jnp.int32(num_q_blocks),
            (k_lo + jnp.int32(block_k - 1)) // jnp.int32(block_q)
            + jnp.int32(1))
        carry = jax.lax.fori_loop(
            lo, full_lo, lambda qb, c: tile(qb, c, masked=True), carry)
        carry = jax.lax.fori_loop(
            full_lo, jnp.int32(num_q_blocks),
            lambda qb, c: tile(qb, c, masked=False), carry)
    else:
        carry = jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(num_q_blocks),
            lambda qb, c: tile(qb, c, masked=False), carry)
    dk, dv = carry
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(k_ref, v_ref, do_ref, lse_ref, delta_ref, q_ref,
                   dq_ref, *, causal, sm_scale, block_k, kv_len):
    # grid: (batch*heads, q_blocks); q/do/dq refs [block_q, d];
    # k/v refs [kv_len, d]; lse/delta refs [1, block_q]
    # sm_scale folded into q once; the dq matmul consumes a scaled k slice
    # (dq = ds (k*sm)), so no per-tile ds * sm_scale pass
    q = (q_ref[...].astype(jnp.float32) * sm_scale).astype(q_ref.dtype)
    do = do_ref[...]
    lse = lse_ref[0, :]
    delta = delta_ref[0, :]
    block_q, d = q.shape
    q_idx = pl.program_id(1)

    dq = jnp.zeros((block_q, d), jnp.float32)
    num_k_blocks = kv_len // block_k

    def tile(kb, dq, masked):
        k = k_ref[pl.dslice(kb * block_k, block_k), :]
        v = v_ref[pl.dslice(kb * block_k, block_k), :]
        ks = (k.astype(jnp.float32) * sm_scale).astype(k.dtype)
        s = _dot_f32(q, k, ((1,), (1,)))
        if masked:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        p = jnp.exp(s - lse[:, None])
        dp = _dot_f32(do, v, ((1,), (1,)))
        ds = p * (dp - delta[:, None])
        return dq + _dot_f32(ds.astype(k.dtype), ks, ((1,), (0,)))

    if causal:
        q_lo = q_idx.astype(jnp.int32) * jnp.int32(block_q)
        q_end = q_lo + jnp.int32(block_q)
        full_hi = q_lo // jnp.int32(block_k)
        hi = jnp.minimum(jnp.int32(num_k_blocks),
                         (q_end - 1) // jnp.int32(block_k) + jnp.int32(1))
        dq = jax.lax.fori_loop(
            jnp.int32(0), full_hi, lambda kb, a: tile(kb, a, masked=False),
            dq)
        dq = jax.lax.fori_loop(
            full_hi, hi, lambda kb, a: tile(kb, a, masked=True), dq)
    else:
        dq = jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(num_k_blocks),
            lambda kb, a: tile(kb, a, masked=False), dq)
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, g, causal):
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    group = h // hk
    sm_scale = 1.0 / math.sqrt(d)

    qr = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, d)
    kr = jnp.swapaxes(k, 1, 2).reshape(b * hk, sk, d)
    vr = jnp.swapaxes(v, 1, 2).reshape(b * hk, sk, d)
    dor = jnp.swapaxes(g, 1, 2).reshape(b * h, sq, d)
    outr = jnp.swapaxes(out, 1, 2).reshape(b * h, sq, d)

    # delta_i = rowsum(dO_i * O_i) — O(S·D) precompute, standard FA2 trick;
    # carried [BH, 1, Sq] like lse for TPU-legal vector tiling
    delta = jnp.sum(dor.astype(jnp.float32) * outr.astype(jnp.float32),
                    axis=-1)[:, None, :]

    block_q, block_k = _fa_blocks(sq, sk, d, jnp.dtype(q.dtype).name)
    q_map, kv_map = _gqa_maps(h, group)

    def vec_q_map(bh, blk):
        return (bh, 0, 0)

    # ---- dk/dv: grid over (B*H, k blocks); per-query-head partials are
    # summed over the GQA group afterwards (group is small).
    k_blk_map = lambda bh, kb: (bh, kb, 0)  # noqa: E731

    dk_part, dv_part = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, q_len=sq),
        grid=(b * h, sk // block_k),
        in_specs=[
            # q/do are full-seq blocks: the block index along seq must be a
            # literal 0 (kb-kb), NOT the k-block id — relying on Pallas's
            # out-of-range clamp would be wrong-by-construction
            pl.BlockSpec((None, sq, d), lambda bh, kb: (bh, 0, 0)),
            pl.BlockSpec((None, sq, d), lambda bh, kb: (bh, 0, 0)),
            pl.BlockSpec((None, 1, sq), vec_q_map),   # lse
            pl.BlockSpec((None, 1, sq), vec_q_map),   # delta
            pl.BlockSpec((None, block_k, d),
                         lambda bh, kb, _h=h, _g=group, _hk=hk:
                         ((bh // _h) * _hk + (bh % _h) // _g, kb, 0)),
            pl.BlockSpec((None, block_k, d),
                         lambda bh, kb, _h=h, _g=group, _hk=hk:
                         ((bh // _h) * _hk + (bh % _h) // _g, kb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), k_blk_map),
            pl.BlockSpec((None, block_k, d), k_blk_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, sk, d), jnp.float32),
        ],
        interpret=INTERPRET,
    )(qr, dor, lse, delta, kr, vr)

    if group > 1:
        dk_r = dk_part.reshape(b, hk, group, sk, d).sum(axis=2)
        dv_r = dv_part.reshape(b, hk, group, sk, d).sum(axis=2)
    else:
        dk_r = dk_part.reshape(b, hk, sk, d)
        dv_r = dv_part.reshape(b, hk, sk, d)
    dk = jnp.swapaxes(dk_r, 1, 2).astype(k.dtype)
    dv = jnp.swapaxes(dv_r, 1, 2).astype(v.dtype)

    # ---- dq: grid over (B*H, q blocks)
    dq_flat = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, sm_scale=sm_scale,
                          block_k=block_k, kv_len=sk),
        grid=(b * h, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, sk, d), kv_map),      # k
            pl.BlockSpec((None, sk, d), kv_map),      # v
            pl.BlockSpec((None, block_q, d), q_map),  # do
            pl.BlockSpec((None, 1, block_q), lambda bh, qb: (bh, 0, qb)),
            pl.BlockSpec((None, 1, block_q), lambda bh, qb: (bh, 0, qb)),
            pl.BlockSpec((None, block_q, d), q_map),  # q
        ],
        out_specs=pl.BlockSpec((None, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=INTERPRET,
    )(kr, vr, dor, lse, delta, qr)
    dq = jnp.swapaxes(dq_flat.reshape(b, h, sq, d), 1, 2)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_attention(causal, q, k, v):
    out, _ = _flash_fwd_pallas(q, k, v, causal)
    return out


def _flash_fwd_rule(causal, q, k, v):
    out, lse = _flash_fwd_pallas(q, k, v, causal)
    # residuals are O(S·D) + O(S): inputs, output, logsumexp — never scores
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, res, g):
    q, k, v, out, lse = res
    return _flash_bwd_pallas(q, k, v, out, lse, g, causal)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _shapes_eligible(shape, dtype_name, kv_shape=None, causal=True) -> bool:
    """Static shape heuristic: do these shapes tile onto the MXU at all?"""
    if not _HAS_PALLAS:
        return False
    if jax.default_backend() not in ("tpu",) and not INTERPRET:
        return False
    if len(shape) != 4:
        return False
    b, s, h, d = shape
    if d % 128 != 0 and d not in (64, 128, 256):
        return False
    if kv_shape is not None:
        if len(kv_shape) != 4 or kv_shape[0] != b or kv_shape[3] != d:
            return False
        hk = kv_shape[2]
        if hk == 0 or h % hk != 0:  # GQA group must divide heads
            return False
        if kv_shape[1] % 128 != 0:
            return False
        # the kernel's causal mask is top-left aligned (q_pos >= k_pos);
        # _ref_attention uses bottom-right alignment for sq != sk, so
        # cross-length causal must NOT take the kernel path
        if causal and kv_shape[1] != s:
            return False
    return s % 128 == 0 and dtype_name in ("float32", "bfloat16")


def use_flash(q, k, causal=True) -> bool:
    """THE eligibility predicate (single source of truth): flag + the
    kernels' static claim on this platform and shape.  Whether Mosaic
    accepts the launch is settled by compiling the program that contains
    it; a refusal there raises, it does not fall back."""
    from ...core.flags import get_flag
    if not get_flag("use_pallas_kernels"):
        return False
    return _shapes_eligible(tuple(q.shape), jnp.dtype(q.dtype).name,
                            tuple(k.shape), bool(causal))


def attention(q, k, v, causal=True):
    """Fused attention: the Pallas flash kernels wherever they claim the
    shape on this platform, else the XLA composition."""
    if use_flash(q, k, causal):
        return _flash_attention(bool(causal), q, k, v)
    return _ref_attention(q, k, v, causal)


class _FlashFwd:
    """Callable op with the centralized eligibility check."""

    def __call__(self, q, k, v, causal):
        return _flash_attention(bool(causal), q, k, v)

    @staticmethod
    def supports(shape, dtype_name, kv_shape=None, causal=True) -> bool:
        return _shapes_eligible(shape, dtype_name, kv_shape, bool(causal))

    # identity used as the dispatch cache key
    def __hash__(self):
        return hash("pallas_flash_attention")

    def __eq__(self, other):
        return isinstance(other, _FlashFwd)


flash_attention_fwd = _FlashFwd()
