"""Pallas paged-KV attention (TPU): ragged serving kernel + decode kernel.

The serving step attends query tokens against KV caches that live in
non-contiguous fixed-size pages addressed by block tables (the reference's
paged CUDA decode kernel,
/root/reference/paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu
-> block_attn.h).  The XLA composition must first GATHER every sequence's
pages into a dense [B, nblk*bs] buffer — O(B * max_len) HBM traffic twice
(gather + read).  These kernels instead walk the block table with Pallas
scalar prefetch: the grid's page dimension indexes the block table
directly in each page's BlockSpec index map, so pages stream from HBM to
VMEM exactly once, with no dense intermediate.

`ragged_paged_attention` is the serving workhorse (arxiv 2604.15464): the
grid runs over FLAT query tokens, each token resolves its owning row via
`cu_seqlens` and masks keys at its absolute position — so a prefill
chunk, a resumed chunk, a single decode token, and a k-draft verify row
are all just rows with different query lengths, served by ONE program.
`paged_decode_attention` is the original one-token-per-row special case,
kept for the incubating blha path and as a second oracle.

Layout: caches are [num_blocks, H_kv, bs, D] (blha cache layout), block
tables int32, per-row lengths int32.  GQA is native: grid runs over kv
heads, each kernel instance carries the q-head group [G, D] so the
[G, bs] score tile keeps the MXU busy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tri-state interpret override.  None (default) resolves per-backend:
# interpret everywhere except a real TPU, so kernel entry points work on
# CPU without mutating this global.  Tests that need a forced mode (the
# fixture in tests/test_paged_attention.py) may still assign True/False
# here and restore the old value after.  NOTE the serving engine does
# NOT ride the auto-resolved interpret mode: interpreted decode costs a
# Python step per (B, H_kv, nblk) grid cell, so LLMEngine uses the XLA
# reference path off-TPU unless INTERPRET is explicitly True.
INTERPRET = None


def interpret_mode() -> bool:
    """Resolved interpret flag: the module override wins when set."""
    if INTERPRET is None:
        return jax.default_backend() != "tpu"
    return bool(INTERPRET)


def _pages_per_step(tq, kv_heads, head_dim, page, nblk, dtype):
    """Trace-time tuned page-walk width for the paged kernels.

    The tuned value only widens the innermost grid step — pages are
    still visited in the same ascending order, so the online-softmax
    accumulation (and therefore every output byte) is invariant; only
    the launch-overhead amortization changes."""
    from ...tune import kernel_config
    cfg = kernel_config("paged_attention",
                        {"tq": tq, "kv_heads": kv_heads,
                         "head_dim": head_dim, "page": page, "nblk": nblk,
                         "dtype": jnp.dtype(dtype).name})
    return max(1, min(int(cfg["pages_per_step"]), nblk))


def _page_index(i, pages, j, nblk):
    """Block-table column for page-slot j of grid step i.  The final
    step may overhang nblk; the clamp keeps the DMA on a real page and
    the kernels' `base <= rel` / `base < seq_len` guards (base >=
    nblk*bs for overhang slots) skip its compute."""
    return jnp.minimum(i * pages + j, nblk - 1)


def _decode_kernel(bt_ref, len_ref, q_ref, *refs, bs, sm_scale, pages,
                   nblk):
    """grid (B, H_kv, ceil(nblk/pages)); refs: q [G, D], then `pages` k
    pages and `pages` v pages [bs, D] (one kv head each), o [G, D];
    scratch m/l [G, 1] f32, acc [G, D] f32.  Pages are walked j=0..pages
    in ascending order — identical accumulation order for any width."""
    k_refs = refs[:pages]
    v_refs = refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    b = pl.program_id(0)
    i = pl.program_id(2)
    steps = pl.num_programs(2)
    seq_len = len_ref[b]                      # valid positions this seq

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for j in range(pages):
        base = (i * pages + j) * bs

        @pl.when(base < seq_len)
        def _tile(base=base, k_ref=k_refs[j], v_ref=v_refs[j]):
            q = (q_ref[...].astype(jnp.float32) * sm_scale).astype(
                q_ref.dtype)
            k = k_ref[...]                     # [bs, D]
            v = v_ref[...]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # [G, bs]
            pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < seq_len, s, -jnp.inf)
            m_prev = m_ref[...]                # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)             # [G, bs]
            alpha = jnp.exp(m_prev - m_new)    # [G, 1]
            l_ref[...] = alpha * l_ref[...] + \
                jnp.sum(p, axis=1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

    @pl.when(i == steps - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _paged_decode_launch(q, key_cache, value_cache, block_tables,
                         lengths):
    """The raw decode launch.  Callers must satisfy the packed-operand
    invariant: block_tables/lengths already int32 with every table entry
    in [0, num_blocks) — the grid DMAs a page per table entry even past
    each sequence's length (compute is skipped, the copy is not), so an
    out-of-range entry is an out-of-bounds DMA."""
    B, H, D = q.shape
    _, Hkv, bs, _ = key_cache.shape
    G = H // Hkv
    nblk = block_tables.shape[1]
    sm_scale = 1.0 / (D ** 0.5)
    pages = _pages_per_step(B, Hkv, D, bs, nblk, q.dtype)

    kernel = functools.partial(_decode_kernel, bs=bs, sm_scale=sm_scale,
                               pages=pages, nblk=nblk)
    # q rows for kv head h are h*G..(h+1)*G: block (1, G, D) at index (b, h)
    qr = q.reshape(B, Hkv, G, D)

    def _kv_spec(j):
        return pl.BlockSpec(
            (None, None, bs, D),
            lambda b, h, i, bt, ln, _j=j:
            (bt[b, _page_index(i, pages, _j, nblk)], h, 0, 0))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,             # block_tables, lengths
            grid=(B, Hkv, -(-nblk // pages)),
            in_specs=[
                pl.BlockSpec((None, None, G, D),
                             lambda b, h, i, bt, ln: (b, h, 0, 0)),
            ] + [_kv_spec(j) for j in range(pages)] * 2,
            out_specs=pl.BlockSpec((None, None, G, D),
                                   lambda b, h, i, bt, ln: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret_mode(),
        name="paged_decode_attention",
    )(block_tables, lengths, qr,
      *([key_cache] * pages), *([value_cache] * pages))
    return out.reshape(B, H, D)


def paged_decode_attention(q, key_cache, value_cache, block_tables,
                           lengths):
    """One-token-per-sequence decode over paged KV.

    q [B, H, D]; caches [num_blocks, H_kv, bs, D]; block_tables [B, nblk]
    int32; lengths [B] int32 (valid positions incl. the fresh token).
    Returns [B, H, D].  Clamps the reference blha convention's -1 table
    padding to a valid block index before launching; callers that pack
    valid tables on the host should use
    :func:`paged_decode_attention_packed` instead.
    """
    block_tables = jnp.clip(block_tables, 0,
                            key_cache.shape[0] - 1).astype(jnp.int32)
    return _paged_decode_launch(q, key_cache, value_cache, block_tables,
                                lengths.astype(jnp.int32))


def paged_decode_attention_packed(q, key_cache, value_cache, block_tables,
                                  lengths):
    """Decode launch without the defensive table clip/casts, for callers
    owning the host packing path (serving.py keeps its table pool int32
    and NULL_BLOCK-padded with valid indices, so re-normalizing every
    launch is pure waste)."""
    return _paged_decode_launch(q, key_cache, value_cache, block_tables,
                                lengths)


def paged_decode_reference(q, key_cache, value_cache, block_tables,
                           lengths):
    """Dense-gather XLA oracle (the pre-r5 decode path's math)."""
    B, H, D = q.shape
    _, Hkv, bs, _ = key_cache.shape
    kpages = key_cache[block_tables]           # [B, nblk, Hkv, bs, D]
    vpages = value_cache[block_tables]
    ks = jnp.moveaxis(kpages, 2, 1).reshape(B, Hkv, -1, D)
    vs = jnp.moveaxis(vpages, 2, 1).reshape(B, Hkv, -1, D)
    if Hkv != H:
        g = H // Hkv
        ks = jnp.repeat(ks, g, axis=1)
        vs = jnp.repeat(vs, g, axis=1)
    scores = jnp.einsum("bhd,bhmd->bhm", q.astype(jnp.float32),
                        ks.astype(jnp.float32)) / jnp.sqrt(jnp.float32(D))
    pos = jnp.arange(ks.shape[2])[None, None, :]
    scores = jnp.where(pos < lengths[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhm,bhmd->bhd", probs, vs.astype(jnp.float32))
    return out.astype(q.dtype)


def _ragged_kernel(seg_ref, rel_ref, bt_ref, q_ref, *refs, bs, sm_scale,
                   pages, nblk):
    """grid (Tq, H_kv, ceil(nblk/pages)); refs: q [G, D] (one flat
    token's group for one kv head), then `pages` k pages and `pages` v
    pages [bs, D] of that token's owning row, o [G, D]; scratch m/l
    [G, 1] f32, acc [G, D] f32.

    seg[t] names the block-table row owning flat token t; rel[t] is the
    token's position within that row's KV (0-based), so causality is just
    `keypos <= rel[t]` — uniform across prefill/resume/decode/verify rows.
    Pages are walked j=0..pages in ascending order: the accumulation
    order — and therefore every output byte — is identical for any
    `pages` width; only launch-overhead amortization changes.
    """
    k_refs = refs[:pages]
    v_refs = refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    t = pl.program_id(0)
    i = pl.program_id(2)
    steps = pl.num_programs(2)
    rel = rel_ref[t]                          # absolute key budget, 0-based

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for j in range(pages):
        base = (i * pages + j) * bs

        @pl.when(base <= rel)
        def _tile(base=base, k_ref=k_refs[j], v_ref=v_refs[j]):
            q = (q_ref[...].astype(jnp.float32) * sm_scale).astype(
                q_ref.dtype)
            k = k_ref[...]                     # [bs, D]
            v = v_ref[...]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # [G, bs]
            pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos <= rel, s, -jnp.inf)
            m_prev = m_ref[...]                # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)             # [G, bs]
            alpha = jnp.exp(m_prev - m_new)    # [G, 1]
            l_ref[...] = alpha * l_ref[...] + \
                jnp.sum(p, axis=1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

    @pl.when(i == steps - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _ragged_quant_kernel(seg_ref, rel_ref, bt_ref, ksc_ref, vsc_ref,
                         q_ref, *refs, bs, sm_scale, pages, nblk):
    """Int8-page variant of `_ragged_kernel`: k/v refs are int8 pages and
    the per-page-per-head float32 scales ride the scalar-prefetch path
    (SMEM) next to the block table, so dequantization happens inline as
    each page streams into VMEM — no dense float intermediate ever
    exists.  ksc/vsc are [num_blocks, H_kv] f32; each page-slot's scale
    is looked up through the same clamped `bt[seg[t], i*pages+j]`
    indirection its BlockSpec index map uses.
    """
    k_refs = refs[:pages]
    v_refs = refs[pages:2 * pages]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * pages:]
    t = pl.program_id(0)
    h = pl.program_id(1)
    i = pl.program_id(2)
    steps = pl.num_programs(2)
    rel = rel_ref[t]                          # absolute key budget, 0-based

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for j in range(pages):
        base = (i * pages + j) * bs
        blk = bt_ref[seg_ref[t], _page_index(i, pages, j, nblk)]

        @pl.when(base <= rel)
        def _tile(base=base, blk=blk, k_ref=k_refs[j], v_ref=v_refs[j]):
            q = q_ref[...].astype(jnp.float32) * sm_scale
            k = k_ref[...].astype(jnp.float32) * ksc_ref[blk, h]  # [bs, D]
            v = v_ref[...].astype(jnp.float32) * vsc_ref[blk, h]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # [G, bs]
            pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos <= rel, s, -jnp.inf)
            m_prev = m_ref[...]                # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)             # [G, bs]
            alpha = jnp.exp(m_prev - m_new)    # [G, 1]
            l_ref[...] = alpha * l_ref[...] + \
                jnp.sum(p, axis=1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

    @pl.when(i == steps - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def ragged_segments(cu_seqlens, kv_lens, n_tokens):
    """Derive per-flat-token (seg, rel) from the ragged row layout.

    cu_seqlens [R+1] int32 (row r owns flat tokens cu[r]..cu[r+1]);
    kv_lens [R] int32 (valid KV positions per row AFTER this launch's
    inserts).  Padding tokens past cu[R] get seg == R and rel == 0 so the
    kernel computes a finite garbage row the caller discards.
    """
    cu = cu_seqlens.astype(jnp.int32)
    kvl = kv_lens.astype(jnp.int32)
    R = kvl.shape[0]
    tpos = jnp.arange(n_tokens, dtype=jnp.int32)
    seg = jnp.searchsorted(cu[1:], tpos, side="right").astype(jnp.int32)
    segc = jnp.minimum(seg, R - 1)
    qlen = cu[1:] - cu[:-1]
    rel = jnp.where(seg < R, kvl[segc] - qlen[segc] + tpos - cu[segc], 0)
    return seg, rel


def decode_window_segments(active, kv_lens):
    """Per-iteration (seg, rel) for the device-resident decode window.

    One window iteration carries exactly one flat token per batch row
    (token s belongs to row s), so the ragged searchsorted collapses to
    an identity map.  Rows frozen by the active-mask (eos/length hit
    mid-window) are redirected to the sentinel row B — the [B+1]-row
    block table's null row — so their K/V append and attention reads
    land in the reserved garbage page, exactly like ragged padding
    tokens, and never touch a live sequence's pages.

    active [B] bool (row still decoding), kv_lens [B] int32 (valid KV
    positions AFTER this iteration's insert).  Returns (seg [B], rel [B])
    int32 for the packed/reference segrel attention entry points.
    """
    B = active.shape[0]
    seg = jnp.where(active, jnp.arange(B, dtype=jnp.int32), jnp.int32(B))
    rel = jnp.where(active, kv_lens.astype(jnp.int32) - 1, 0)
    return seg, rel


def _ragged_launch(q, key_cache, value_cache, block_tables, seg, rel):
    """The raw ragged launch.  Callers must satisfy the packed-operand
    invariant: int32 scalar operands, table entries in [0, num_blocks),
    seg values naming real table rows (serving's [B+1]-row table makes
    the pad sentinel B a valid null row)."""
    Tq, H, D = q.shape
    _, Hkv, bs, _ = key_cache.shape
    G = H // Hkv
    R, nblk = block_tables.shape
    sm_scale = 1.0 / (D ** 0.5)
    pages = _pages_per_step(Tq, Hkv, D, bs, nblk, key_cache.dtype)

    kernel = functools.partial(_ragged_kernel, bs=bs, sm_scale=sm_scale,
                               pages=pages, nblk=nblk)
    qr = q.reshape(Tq, Hkv, G, D)

    def _kv_spec(j):
        return pl.BlockSpec(
            (None, None, bs, D),
            lambda t, h, i, sg, rl, bt, _j=j:
            (bt[sg[t], _page_index(i, pages, _j, nblk)], h, 0, 0))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,             # seg, rel, block_tables
            grid=(Tq, Hkv, -(-nblk // pages)),
            in_specs=[
                pl.BlockSpec((None, None, G, D),
                             lambda t, h, i, sg, rl, bt: (t, h, 0, 0)),
            ] + [_kv_spec(j) for j in range(pages)] * 2,
            out_specs=pl.BlockSpec((None, None, G, D),
                                   lambda t, h, i, sg, rl, bt: (t, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Tq, Hkv, G, D), q.dtype),
        interpret=interpret_mode(),
        name="ragged_paged_attention",
    )(seg, rel, block_tables, qr,
      *([key_cache] * pages), *([value_cache] * pages))
    return out.reshape(Tq, H, D)


def ragged_paged_attention_segrel(q, key_cache, value_cache, block_tables,
                                  seg, rel):
    """Ragged attention with precomputed (seg, rel) per flat token.

    q [Tq, H, D]; caches [num_blocks, H_kv, bs, D]; block_tables [R, nblk]
    int32; seg [Tq] int32 in [0, R] (R == padding sentinel); rel [Tq]
    int32.  Returns [Tq, H, D].

    Clamps table entries (blha -1 padding) AND seg (R == pad sentinel) so
    every index map resolves to a real page; padded/overhung tiles are
    DMA'd but masked or skipped in compute.  Callers that already pack
    valid int32 operands on the host should use
    :func:`ragged_paged_attention_segrel_packed`.
    """
    R = block_tables.shape[0]
    block_tables = jnp.clip(block_tables.astype(jnp.int32), 0,
                            key_cache.shape[0] - 1)
    seg = jnp.clip(seg.astype(jnp.int32), 0, R - 1)
    return _ragged_launch(q, key_cache, value_cache, block_tables, seg,
                          rel.astype(jnp.int32))


def ragged_paged_attention_segrel_packed(q, key_cache, value_cache,
                                         block_tables, seg, rel):
    """Ragged launch without the defensive clips/casts, for callers that
    guarantee the host-packing invariant (serving.py owns these buffers:
    its table pool is int32 and NULL_BLOCK-padded with valid indices,
    and its [B+1]-row table makes the seg pad sentinel a real null row,
    so re-normalizing every launch is pure waste)."""
    return _ragged_launch(q, key_cache, value_cache, block_tables, seg,
                          rel)


def ragged_paged_attention(q, key_cache, value_cache, block_tables,
                           cu_seqlens, kv_lens):
    """One ragged launch over flat query tokens from mixed-phase rows.

    q [Tq, H, D] (rows packed back-to-back, tail padding allowed);
    caches [num_blocks, H_kv, bs, D]; block_tables [R, nblk] int32;
    cu_seqlens [R+1] int32; kv_lens [R] int32 (valid KV per row AFTER
    this launch's inserts — a row's queries sit at its LAST kv_lens
    positions).  Returns [Tq, H, D]; padding rows are finite garbage.
    """
    seg, rel = ragged_segments(cu_seqlens, kv_lens, q.shape[0])
    return ragged_paged_attention_segrel(
        q, key_cache, value_cache, block_tables, seg, rel)


def _ragged_quant_launch(q, key_cache, value_cache, key_scales,
                         value_scales, block_tables, seg, rel):
    """The raw int8-page ragged launch; same packed-operand invariant as
    `_ragged_launch`, plus f32 scales."""
    Tq, H, D = q.shape
    _, Hkv, bs, _ = key_cache.shape
    G = H // Hkv
    R, nblk = block_tables.shape
    sm_scale = 1.0 / (D ** 0.5)
    pages = _pages_per_step(Tq, Hkv, D, bs, nblk, key_cache.dtype)

    kernel = functools.partial(_ragged_quant_kernel, bs=bs,
                               sm_scale=sm_scale, pages=pages, nblk=nblk)
    qr = q.reshape(Tq, Hkv, G, D)

    def _kv_spec(j):
        return pl.BlockSpec(
            (None, None, bs, D),
            lambda t, h, i, sg, rl, bt, ks, vs, _j=j:
            (bt[sg[t], _page_index(i, pages, _j, nblk)], h, 0, 0))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,     # seg, rel, block_tables, ksc, vsc
            grid=(Tq, Hkv, -(-nblk // pages)),
            in_specs=[
                pl.BlockSpec((None, None, G, D),
                             lambda t, h, i, sg, rl, bt, ks, vs:
                             (t, h, 0, 0)),
            ] + [_kv_spec(j) for j in range(pages)] * 2,
            out_specs=pl.BlockSpec((None, None, G, D),
                                   lambda t, h, i, sg, rl, bt, ks, vs:
                                   (t, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Tq, Hkv, G, D), q.dtype),
        interpret=interpret_mode(),
        name="ragged_paged_attention_q8",
    )(seg, rel, block_tables, key_scales, value_scales, qr,
      *([key_cache] * pages), *([value_cache] * pages))
    return out.reshape(Tq, H, D)


def ragged_paged_attention_quant_segrel(q, key_cache, value_cache,
                                        key_scales, value_scales,
                                        block_tables, seg, rel):
    """Ragged attention over int8 KV pages with per-page-per-head scales.

    q [Tq, H, D] float; caches [num_blocks, H_kv, bs, D] int8;
    key_scales/value_scales [num_blocks, H_kv] f32 (symmetric:
    float = int8 * scale); block_tables [R, nblk] int32; seg/rel as in
    `ragged_paged_attention_segrel`.  Returns [Tq, H, D] in q.dtype.
    """
    R = block_tables.shape[0]
    block_tables = jnp.clip(block_tables.astype(jnp.int32), 0,
                            key_cache.shape[0] - 1)
    seg = jnp.clip(seg.astype(jnp.int32), 0, R - 1)
    return _ragged_quant_launch(
        q, key_cache, value_cache, key_scales.astype(jnp.float32),
        value_scales.astype(jnp.float32), block_tables, seg,
        rel.astype(jnp.int32))


def ragged_paged_attention_quant_segrel_packed(q, key_cache, value_cache,
                                               key_scales, value_scales,
                                               block_tables, seg, rel):
    """Int8-page ragged launch without the defensive clips/casts, for
    callers that guarantee the host-packing invariant (serving.py packs
    int32 tables/seg/rel and f32 scale pools)."""
    return _ragged_quant_launch(q, key_cache, value_cache, key_scales,
                                value_scales, block_tables, seg, rel)


def ragged_paged_reference_quant_segrel(q, key_cache, value_cache,
                                        key_scales, value_scales,
                                        block_tables, seg, rel):
    """Fake-quant XLA oracle for the int8-page kernel: dequantize the
    whole pool densely (float = int8 * scale, the exact math the kernel
    applies per page) and delegate to the float reference, so CPU tests
    stay exact-vs-oracle in int8 mode."""
    kd = key_cache.astype(jnp.float32) * \
        key_scales.astype(jnp.float32)[:, :, None, None]
    vd = value_cache.astype(jnp.float32) * \
        value_scales.astype(jnp.float32)[:, :, None, None]
    return ragged_paged_reference_segrel(q, kd, vd, block_tables, seg, rel)


def ragged_paged_reference_segrel(q, key_cache, value_cache, block_tables,
                                  seg, rel):
    """Dense-gather XLA oracle for the ragged kernel (the engine's former
    chunked-resume math, term for term)."""
    Tq, H, D = q.shape
    _, Hkv, bs, _ = key_cache.shape
    R, nblk = block_tables.shape
    bt = jnp.clip(block_tables.astype(jnp.int32), 0,
                  key_cache.shape[0] - 1)
    seg = jnp.clip(seg.astype(jnp.int32), 0, R - 1)
    kg = key_cache[bt].transpose(0, 1, 3, 2, 4).reshape(
        R, nblk * bs, Hkv, D)                  # [R, S, Hkv, D]
    vg = value_cache[bt].transpose(0, 1, 3, 2, 4).reshape(
        R, nblk * bs, Hkv, D)
    kq = kg[seg]                               # [Tq, S, Hkv, D]
    vq = vg[seg]
    if Hkv != H:
        g = H // Hkv
        kq = jnp.repeat(kq, g, axis=2)
        vq = jnp.repeat(vq, g, axis=2)
    sm_scale = 1.0 / (D ** 0.5)
    scores = jnp.einsum("qhd,qshd->qhs", q.astype(jnp.float32),
                        kq.astype(jnp.float32)) * sm_scale
    keypos = jnp.arange(nblk * bs, dtype=jnp.int32)
    mask = keypos[None, None, :] <= rel[:, None, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("qhs,qshd->qhd", probs, vq.astype(jnp.float32))
    return out.astype(q.dtype)


def ragged_paged_reference(q, key_cache, value_cache, block_tables,
                           cu_seqlens, kv_lens):
    """Dense-gather XLA oracle with the public (cu, kv_lens) interface."""
    seg, rel = ragged_segments(cu_seqlens, kv_lens, q.shape[0])
    return ragged_paged_reference_segrel(
        q, key_cache, value_cache, block_tables, seg, rel)


# Scalar memory of one TensorCore of the TPU v5e, the only device this was
# measured on.  The ragged launches prefetch seg, rel, the block table and,
# over int8 pages, both scale pools into it.  A launch whose operands came
# to 1036 KiB was refused there at compile time ("Used 1.01M of 1.00M
# smem"), one of 269 KiB compiled, and the refusal listed a [1025, 32] f32
# operand at 516 KiB: 32-bit words in (8, 128) tiles.  What Mosaic keeps
# there for itself was not measured; _SMEM_RESERVE stands in for it.
_SMEM_BYTES = 1 << 20
_SMEM_RESERVE = 16 << 10


def scalar_prefetch_bytes(Tq, table_rows, nblk, num_blocks, Hkv,
                          int8: bool) -> int:
    """Scalar memory the operands a ragged launch prefetches take: seg
    and rel [Tq] (counted whole lanes of 128 words), the block table
    [table_rows, nblk] and, over int8 pages, the two [num_blocks, Hkv]
    f32 scale pools, each padded to (8, 128) tiles of 32-bit words."""
    def tiled(rows, cols):
        return -(-rows // 8) * 8 * -(-cols // 128) * 128 * 4

    need = 2 * -(-Tq // 128) * 128 * 4 + tiled(table_rows, nblk)
    if int8:
        need += 2 * tiled(num_blocks, Hkv)
    return need


def ineligible(H, Hkv, D, bs, kv_dtype=jnp.float32,
               launch=None) -> str | None:
    """Why the paged kernels do not claim this shape, or None when they
    do.  A static claim from shapes alone: whether Mosaic accepts the
    launch is settled by compiling the program that contains it, and a
    refusal there raises (interpret mode enforces no tiling rule, so
    only a compile on the chip can say).  Int8 pages carry a (32, 128)
    minimum tile, float pages (8, 128).  ``launch`` is the caller's
    largest ragged launch as ``(Tq, table_rows, nblk, num_blocks)``: its
    prefetched operands must fit the scalar memory, which bounds the
    int8 page pool (both scale pools ride there) and the block table
    (None: not checked, and an overrun fails the compile instead).
    Tensor-parallel callers pass per-shard head counts: the kernel
    launches inside shard_map and tiles against the shard-local
    shapes."""
    if H < 1 or Hkv < 1 or H % Hkv:
        return f"{H} query heads are not a multiple of {Hkv} kv heads"
    if D % 128 and D != 64:
        return f"head_dim {D} is neither 64 nor a multiple of 128"
    int8 = jnp.dtype(kv_dtype) == jnp.int8
    min_bs = 32 if int8 else 8
    if bs % min_bs:
        return (f"block_size {bs} is not a multiple of {min_bs} "
                f"({jnp.dtype(kv_dtype).name} pages)")
    if launch is not None:
        Tq, table_rows, nblk, num_blocks = launch
        need = scalar_prefetch_bytes(Tq, table_rows, nblk, num_blocks,
                                     Hkv, int8)
        if need > _SMEM_BYTES - _SMEM_RESERVE:
            return (f"a [{table_rows}, {nblk}] block table "
                    + (f"and the scale pools of {num_blocks} int8 pages "
                       "need" if int8 else "needs")
                    + f" {need >> 10} KiB of the "
                    f"{_SMEM_BYTES >> 10} KiB scalar memory (v5e)")
    return None
