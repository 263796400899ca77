"""Pallas paged-KV attention (TPU): ragged serving kernel + decode kernel.

The serving step attends query tokens against KV caches that live in
non-contiguous fixed-size pages addressed by block tables (the reference's
paged CUDA decode kernel,
/root/reference/paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu
-> block_attn.h).  The XLA composition must first GATHER every sequence's
pages into a dense [B, nblk*bs] buffer: O(B * max_len) HBM traffic twice
(gather + read).  These kernels walk the block table themselves, from
scalar memory, so pages stream from HBM to VMEM with no dense
intermediate.

`ragged_paged_attention` is the serving workhorse (arxiv 2604.15464).  It
is driven by the ROW layout the step program has (`cu_seqlens`, `kv_lens`,
the block table, all scalar-prefetched): a prefill chunk, a resumed chunk,
a single decode token and a k-draft verify row are all just rows with
different query lengths, served by ONE program.  Its unit of work is a
tile of one row's queries against that row's live pages: the queries of a
row are tiled together (tq*G score rows for a row of several queries, G
for a row of one), the pool stays in HBM and whole pages come from it by
double-buffered async copies, and the walk ends at the page of the last
key the tile's last query may see.  A page past a row's kv_len, a padded
token and a row of no keys get no copy, no loop turn and no arithmetic;
their output reads zero.  Int8 pages take the same body with a
dequantising load (`ragged_paged_attention_quant`).
`paged_decode_attention` is the original one-token-per-row grid kernel
(a grid step a page slot of the table), kept for the incubating blha path
and as a second oracle.

Layout: caches are [num_blocks, H_kv, bs, D] a layer (blha cache layout;
a step program hands the ragged launch the pools of all layers,
[L, num_blocks, H_kv, bs, D], and a layer index), block tables int32,
per-row lengths int32.  GQA is native: a score tile carries
the q-head group of one kv head, [tq*G, kv_pages*bs].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tri-state interpret override.  None (default) resolves per-backend:
# interpret everywhere except a real TPU, so kernel entry points work on
# CPU without mutating this global.  Tests that need a forced mode (the
# fixture in tests/test_paged_attention.py) may still assign True/False
# here and restore the old value after.  NOTE the serving engine does
# NOT ride the auto-resolved interpret mode: the interpreted kernels are
# slow (a step per grid cell, a host loop per row, block and page), so
# LLMEngine uses the XLA reference path off-TPU unless INTERPRET is
# explicitly True.
INTERPRET = None

# K/V heads the ragged kernel's body unrolls at a time (the rest loop)
_HEADS_UNROLL = 8


def interpret_mode() -> bool:
    """Resolved interpret flag: the module override wins when set."""
    if INTERPRET is None:
        return jax.default_backend() != "tpu"
    return bool(INTERPRET)


def _tuned(tq, kv_heads, head_dim, page, nblk, dtype) -> dict:
    """The paged kernels' trace-time lookup in the tuning cache."""
    from ...tune import kernel_config
    return kernel_config("paged_attention",
                         {"tq": tq, "kv_heads": kv_heads,
                          "head_dim": head_dim, "page": page, "nblk": nblk,
                          "dtype": jnp.dtype(dtype).name})


def _pages_per_step(tq, kv_heads, head_dim, page, nblk, dtype):
    """Trace-time tuned page-walk width of the decode grid kernel: the
    tunable's K/V block in pages, walked inside one grid step.

    The tuned value only widens the innermost grid step: pages are
    still visited in the same ascending order, so the online-softmax
    accumulation (and therefore every output byte) is invariant; only
    the launch-overhead amortization changes."""
    cfg = _tuned(tq, kv_heads, head_dim, page, nblk, dtype)
    return max(1, min(int(cfg["kv_pages"]), nblk))


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


# K/V pages in flight: two slots of a block of K and of V.  A block is
# cut to this many bytes, so a layout with fat pages (32 K/V heads: 128
# KB a page) walks fewer pages a block than one with thin ones.
_KV_BUFFER_BYTES = 8 << 20


def _ragged_tiles(Tq, Hkv, G, D, bs, nblk, dtype):
    """Trace-time tuned tile of the ragged kernel: (q tile in tokens,
    K/V block in pages).  The tuned `q_tile_rows` is the score tile's
    height, so the token tile follows the program's group size (a tile
    of tq tokens is tq*G rows of one K/V head); it is cut to a divisor
    of Tq so flat-token tiles never overhang the bucket."""
    cfg = _tuned(Tq, Hkv, D, bs, nblk, dtype)
    tq = max(1, min(int(cfg["q_tile_rows"]) // G, Tq))
    while Tq % tq:
        tq -= 1
    # a tile's tq*G score rows are sliced out of the head-major q at
    # j*tq*G: where a group that is no power of two (7) leaves that off
    # the sublane 8, take the largest tile below whose rows are on it
    if (tq * G) % 8:
        tq = next((t for t in range(tq, 0, -1)
                   if Tq % t == 0 and (t * G) % 8 == 0), tq)
    page_bytes = Hkv * bs * D * jnp.dtype(dtype).itemsize
    kvb = min(int(cfg["kv_pages"]), nblk,
              _KV_BUFFER_BYTES // (4 * page_bytes))
    return tq, max(1, kvb)


def _ragged_kernel(cu_ref, kvl_ref, bt_ref, layer_ref, *refs, rows, tq, kvb,
                   bs, nblk, quant, window=None):
    """One invocation walks the launch's rows in order.  Refs: q
    [Tq, Hkv, G, D] and the same tokens head-major qt [Hkv, Tq*G, D],
    both pre-scaled, in VMEM; the K and V pools of ALL layers
    [L, num_blocks, Hkv, bs, D] left in HBM and read at the prefetched
    layer index ``layer_ref[0]`` (no other layer is touched); o
    [Tq, Hkv, G, D] in VMEM.  Scratch: kbuf/vbuf
    [2, kvb, Hkv, bs, D] (two slots of kvb whole pages), DMA semaphores
    [2, 2] (K/V x slot), m/l [Hkv, tq*G, 1] and acc [Hkv, tq*G, D] f32.

    The unit of work is an ITEM: a tile of one row's queries against
    that row's live pages.  A row of one query (decode) is one item of
    G score rows read from q; a longer row (a chunk, a resumed chunk, a
    verify row) is one item for every flat-token tile of tq tokens it
    touches, tq*G score rows read from qt, its other rows masked at the
    store.  An item walks K/V blocks of kvb pages from page 0 to the
    page of the last key its last query may see and no further: pages
    come from the pool by one async copy each (a page's [Hkv, bs, D] is
    contiguous, in the stacked pool too), a block ahead of the
    arithmetic, and each item starts its successor's first block, so a
    copy is in flight across items too.  A row of no queries, or of no
    keys, is an item of no blocks; o is zeroed first, so padding and
    such rows read zero.

    Over int8 pages (``quant``) two more prefetched operands lead refs:
    this layer's [num_blocks, Hkv] f32 scale pools, in scalar memory
    beside the table.  A page is dequantized as it is read for the
    product (float = int8 * its page's scale for the head), q and the
    probabilities stay float32, and no dense float copy of a row's K/V
    ever exists.

    With ``window`` (a layer whose query at position i sees keys i -
    window < j <= i, its own among them) an item's walk has a FIRST page
    as well as a last: the page of the lowest key its first query sees.
    Pages below it are neither copied nor looked up in the table (a
    sequence that has moved on has given them back: their entries name
    the null page), and the mask has a lower bound beside the causal
    one.  Without it the first page is the literal 0 and the body is
    what it was.
    """
    if quant:
        ksc_ref, vsc_ref, *refs = refs
    (q_ref, qt_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, m_ref, l_ref,
     acc_ref) = refs
    Hkv, G, D = q_ref.shape[1:]
    kv = kvb * bs
    layer = layer_ref[0]

    def heads(fn):
        """fn(h) for every K/V head: unrolled eight at a time (a body of
        32 heads takes the compiler a minute)."""
        u = math.gcd(Hkv, _HEADS_UNROLL)
        if u == Hkv:
            for h in range(Hkv):
                fn(h)
            return

        def step(g, c):
            for i in range(u):
                fn(g * u + i)
            return c
        jax.lax.fori_loop(0, Hkv // u, step, 0)

    def n_pages(r, j):
        """Pages item (r, j) walks: up to the last key its last query
        sees (j is not read for a row of at most one query)."""
        qs, qe = cu_ref[r], cu_ref[r + 1]
        n_q = qe - qs
        last = jnp.where(n_q <= 1, qs, jnp.minimum(qe, (j + 1) * tq) - 1)
        rel_last = kvl_ref[r] - n_q + last - qs
        np_ = jnp.where(n_q > 0, rel_last // bs + 1, 0)
        return jnp.clip(np_, 0, nblk)

    def page_range(r, j):
        """(first page, pages) item (r, j) walks.  Without a window:
        from page 0.  With one: from the page of the lowest key the
        item's first query sees."""
        if window is None:
            return 0, n_pages(r, j)
        qs, qe = cu_ref[r], cu_ref[r + 1]
        n_q = qe - qs
        first = jnp.where(n_q <= 1, qs, jnp.maximum(qs, j * tq))
        rel_first = kvl_ref[r] - n_q + first - qs
        p0 = jnp.clip((rel_first - (window - 1)) // bs, 0, nblk)
        return p0, jnp.maximum(n_pages(r, j) - p0, 0)

    def tiles(r):
        """Tile range [j0, j1) of row r: one item for a row of at most
        one query, else the flat-token tiles it touches."""
        qs, qe = cu_ref[r], cu_ref[r + 1]
        one = qe - qs <= 1
        return (jnp.where(one, 0, qs // tq),
                jnp.where(one, 1, (qe + tq - 1) // tq))

    def each_copy(r, b, rng, slot, act):
        """``act`` on the K and the V copy of every live page of block b
        of row r (``rng``: its first page and how many are live) into
        ``slot``; returns their number."""
        p0, np_ = rng
        n = jnp.clip(np_ - b * kvb, 0, kvb)

        def one(p, c):
            # (no "0 +" where there is no window: the dense programs'
            # kernel lowers to the module it always did)
            blk = bt_ref[r, b * kvb + p if window is None
                         else p0 + b * kvb + p]
            act(pltpu.make_async_copy(k_hbm.at[layer, blk],
                                      kbuf.at[slot, p],
                                      sems.at[0, slot]))
            act(pltpu.make_async_copy(v_hbm.at[layer, blk],
                                      vbuf.at[slot, p],
                                      sems.at[1, slot]))
            return c
        jax.lax.fori_loop(0, n, one, 0)
        return n

    def start(r, b, rng, slot):
        each_copy(r, b, rng, slot, lambda d: d.start())

    def wait(r, b, rng, slot):
        n = each_copy(r, b, rng, slot, lambda d: d.wait())

        # pages of the block that were not copied hold what the slot
        # held before; masked scores give them probability 0, and
        # 0 * NaN is NaN in the P.V product: zero the V side
        def zero(p, c):
            vbuf[slot, p] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)
            return c
        jax.lax.fori_loop(n, kvb, zero, 0)

    def pages(buf, sc_ref, r, b, p0, slot, h):
        """Head h of the slot's int8 pages as float32 [kv, D], each page
        times its own scale."""
        return jnp.concatenate([
            buf[slot, p, h].astype(jnp.float32)
            * sc_ref[bt_ref[r, jnp.minimum(
                b * kvb + p if window is None else p0 + b * kvb + p,
                nblk - 1)], h]
            for p in range(kvb)], axis=0)

    def item(r, j, slot, *, one):
        """Attention of item (r, j): of a row of one query (G score rows
        read from q) or of a tile (tq*G rows read from qt).  Returns the
        slot after its last block."""
        width = 1 if one else tq
        M = width * G
        qs, qe = cu_ref[r], cu_ref[r + 1]
        n_q = qe - qs
        rng = page_range(r, j)
        p0, np_ = rng
        nb = (np_ + kvb - 1) // kvb
        # the successor: the row's next tile, else the next row's first
        # (past the last row: an item of no pages, nothing to start)
        more = j + 1 < tiles(r)[1]
        rn = jnp.where(more, r, r + 1)
        last = rn >= rows
        rn = jnp.minimum(rn, rows - 1)
        jn = jnp.where(more, j + 1, tiles(rn)[0])
        p0n, npn = page_range(rn, jn)
        rngn = (p0n, jnp.where(last, 0, npn))

        if one:
            rel = jnp.full((M, 1), kvl_ref[r] - 1, jnp.int32)
        else:
            tok = j * tq + jax.lax.broadcasted_iota(
                jnp.int32, (M, 1), 0) // G
            rel = jnp.where((tok >= qs) & (tok < qe),
                            kvl_ref[r] - n_q + tok - qs, -1)

        @heads
        def _init(h):
            m_ref[h, :M] = jnp.full((M, 1), -jnp.inf, jnp.float32)
            l_ref[h, :M] = jnp.zeros((M, 1), jnp.float32)
            acc_ref[h, :M] = jnp.zeros((M, D), jnp.float32)

        @pl.when(nb == 0)
        def _pass_on():
            start(rn, 0, rngn, slot)

        def block(b, slot):
            @pl.when(b + 1 < nb)
            def _next_block():
                start(r, b + 1, rng, 1 - slot)

            @pl.when(b + 1 == nb)
            def _next_item():
                start(rn, 0, rngn, 1 - slot)

            wait(r, b, rng, slot)
            keypos = b * kv + jax.lax.broadcasted_iota(
                jnp.int32, (1, kv), 1)
            if window is None:
                mask = keypos <= rel                   # [M, kv]
            else:
                keypos = keypos + p0 * bs
                mask = (keypos <= rel) & (keypos > rel - window)

            @heads
            def _head(h):
                if one:
                    q = q_ref[qs, h]                   # [G, D]
                else:
                    q = qt_ref[h, pl.ds(j * (tq * G), M), :]
                if quant:
                    k = pages(kbuf, ksc_ref, r, b, p0, slot, h)
                    v = pages(vbuf, vsc_ref, r, b, p0, slot, h)
                else:
                    k = kbuf[slot, :, h].reshape(kv, D)
                    v = vbuf[slot, :, h].reshape(kv, D)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [M, kv]
                s = jnp.where(mask, s, -jnp.inf)
                m_prev = m_ref[h, :M]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                # a score row of another row of the launch sees no key
                m_fin = jnp.where(m_new == -jnp.inf, 0.0, m_new)
                p = jnp.exp(s - m_fin)
                alpha = jnp.exp(m_prev - m_fin)
                l_ref[h, :M] = alpha * l_ref[h, :M] + \
                    jnp.sum(p, axis=1, keepdims=True)
                acc_ref[h, :M] = acc_ref[h, :M] * alpha + \
                    jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                m_ref[h, :M] = m_new
            return 1 - slot

        slot = jax.lax.fori_loop(0, nb, block, slot)

        @pl.when(nb > 0)
        def _store():
            @heads
            def _head(h):
                l = l_ref[h, :M]
                out = acc_ref[h, :M] / jnp.where(l == 0.0, 1.0, l)
                if one:
                    o_ref[qs, h] = out.astype(o_ref.dtype)
                else:
                    t0 = j * tq
                    tok = t0 + jax.lax.broadcasted_iota(
                        jnp.int32, (width, 1, 1), 0)
                    mine = (tok >= qs) & (tok < qe)
                    old = o_ref[pl.ds(t0, width), h]
                    o_ref[pl.ds(t0, width), h] = jnp.where(
                        mine, out.reshape(width, G, D).astype(o_ref.dtype),
                        old)
        return slot

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    j00, _ = tiles(0)
    start(0, 0, page_range(0, j00), 0)

    def row(r, slot):
        j0, j1 = tiles(r)
        one = cu_ref[r + 1] - cu_ref[r] <= 1

        def tile(j, slot):
            return jax.lax.cond(
                one,
                functools.partial(item, one=True),
                functools.partial(item, one=False), r, j, slot)
        return jax.lax.fori_loop(j0, j1, tile, slot)

    jax.lax.fori_loop(0, rows, row, 0)


def ragged_segments(cu_seqlens, kv_lens, n_tokens):
    """Derive per-flat-token (seg, rel) from the ragged row layout.

    cu_seqlens [R+1] int32 (row r owns flat tokens cu[r]..cu[r+1]);
    kv_lens [R] int32 (valid KV positions per row AFTER this launch's
    inserts).  Padding tokens past cu[R] get seg == R and rel == 0: rope
    and kv_write send them to the table's null row, and the XLA
    reference computes a finite garbage row the caller discards (the
    kernel reads the rows themselves and gives padding no work).
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
    """Per-iteration (seg, rel) for the device-resident decode window
    (what rope, kv_write and the XLA reference take).

    One window iteration carries exactly one flat token per batch row
    (token s belongs to row s), so the ragged searchsorted collapses to
    an identity map.  Rows frozen by the active-mask (eos/length hit
    mid-window) are redirected to the sentinel row B — the [B+1]-row
    block table's null row — so their K/V append and attention reads
    land in the reserved garbage page, exactly like ragged padding
    tokens, and never touch a live sequence's pages.

    active [B] bool (row still decoding), kv_lens [B] int32 (valid KV
    positions AFTER this iteration's insert).  Returns (seg [B], rel [B])
    int32.
    """
    B = active.shape[0]
    seg = jnp.where(active, jnp.arange(B, dtype=jnp.int32), jnp.int32(B))
    rel = jnp.where(active, kv_lens.astype(jnp.int32) - 1, 0)
    return seg, rel


def decode_window_rows(active, kv_lens):
    """The same iteration as rows for the ragged kernel: (cu [B+1],
    kv_lens [B]).  Every batch row is a row of one query; a frozen row
    is a row of no keys, which the kernel gives no work and a zero
    output."""
    B = active.shape[0]
    return (jnp.arange(B + 1, dtype=jnp.int32),
            jnp.where(active, kv_lens.astype(jnp.int32), 0))


WINDOW_KERNEL_NAME = "ragged_paged_attention_window"


def _ragged_launch(q, key_cache, value_cache, block_tables, cu_seqlens,
                   kv_lens, scales=(), layer=None, window=None,
                   sm_scale=None, name=None):
    """The raw ragged launch.  With ``layer`` (an int32 scalar, traced
    or static) the caches are the pools of ALL layers,
    [L, num_blocks, Hkv, bs, D], read where they lie at that index,
    which is prefetched beside the table (a layer-sized slice of a pool
    would cost a copy of it a layer a step); without it they are one
    layer's, a stack of one.  Callers must satisfy the packed-operand
    invariant: int32 scalar operands, cu_seqlens [R+1] non-decreasing
    with cu[R] <= Tq, and every table entry in [0, num_blocks).  The
    table may carry more rows than kv_lens (serving's null row): they
    are not read.  ``scales`` is empty over float pages and the
    layer's two [num_blocks, Hkv] f32 scale pools over int8 pages.
    ``window`` (a static int): a query sees its own position and the
    window - 1 before it; the table's entries for a row's pages below
    its window are not read.  Such a launch has a kernel name of its
    own, so that a device trace (which carries names and no scope)
    tells a window layer's launches from the others'.  ``sm_scale``:
    the softmax scale where it is not ``1 / sqrt(D)`` (heads narrower
    than the rows they are stored in); ``name``: a kernel name of the
    caller's own, for launches a trace has to tell from both."""
    if layer is None:
        key_cache, value_cache, layer = key_cache[None], value_cache[None], 0
    Tq, H, D = q.shape
    _, _, Hkv, bs, _ = key_cache.shape
    G = H // Hkv
    rows = kv_lens.shape[0]
    nblk = block_tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    quant = bool(scales)
    tq, kvb = _ragged_tiles(Tq, Hkv, G, D, bs, nblk, key_cache.dtype)
    M = tq * G

    kernel = functools.partial(_ragged_kernel, rows=rows, tq=tq, kvb=kvb,
                               bs=bs, nblk=nblk, quant=quant,
                               window=None if window is None
                               else int(window))
    # scaled once, and over float pages rounded to q's dtype, as the
    # score product's operand always was; the head-major copy is what a
    # tile of several tokens reads, so its tq*G score rows are contiguous
    qr = (q.astype(jnp.float32) * sm_scale).astype(
        jnp.float32 if quant else q.dtype).reshape(Tq, Hkv, G, D)
    qt = qr.transpose(1, 0, 2, 3).reshape(Hkv, Tq * G, D)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # q, qt and o whole (held twice by the pipeline), the page slots,
    # and m, l (a lane-padded column each) and acc
    need = 6 * qr.size * qr.dtype.itemsize \
        + 4 * kvb * Hkv * bs * D * key_cache.dtype.itemsize \
        + Hkv * M * (2 * 128 + D) * 4

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # cu, kv_lens, block_tables, the layer index and, over int8
            # pages, the scales
            num_scalar_prefetch=4 + len(scales),
            grid=(1,),
            in_specs=[vmem, vmem, hbm, hbm],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, kvb, Hkv, bs, D), key_cache.dtype),
                pltpu.VMEM((2, kvb, Hkv, bs, D), value_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((Hkv, M, 1), jnp.float32),
                pltpu.VMEM((Hkv, M, 1), jnp.float32),
                pltpu.VMEM((Hkv, M, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Tq, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(100 << 20, max(16 << 20, 2 * need))),
        interpret=interpret_mode(),
        name=name if name is not None
        else "ragged_paged_attention_q8" if quant
        else "ragged_paged_attention" if window is None
        else WINDOW_KERNEL_NAME,
    )(cu_seqlens, kv_lens, block_tables,
      jnp.asarray(layer, jnp.int32).reshape(1), *scales, qr, qt, key_cache,
      value_cache)
    return out.reshape(Tq, H, D)


def ragged_paged_attention_packed(q, key_cache, value_cache, block_tables,
                                  cu_seqlens, kv_lens, layer=None,
                                  window=None, sm_scale=None, name=None):
    """Ragged launch without the defensive clip/casts, for callers that
    guarantee the host-packing invariant (serving.py owns these buffers:
    its table pool is int32 and NULL_BLOCK-padded with valid indices,
    cu and kv_lens come int32 from the step's packing).  With ``layer``
    the caches are the pools of all layers, [L, num_blocks, H_kv, bs,
    D], read in place at that index (what a step program passes);
    without it, one layer's.  ``window``: a sliding-window layer's
    launch (``_ragged_launch``)."""
    return _ragged_launch(q, key_cache, value_cache, block_tables,
                          cu_seqlens, kv_lens, layer=layer, window=window,
                          sm_scale=sm_scale, name=name)


def ragged_paged_attention(q, key_cache, value_cache, block_tables,
                           cu_seqlens, kv_lens):
    """One ragged launch over flat query tokens from mixed-phase rows.

    q [Tq, H, D] (rows packed back-to-back, tail padding allowed);
    caches [num_blocks, H_kv, bs, D]; block_tables [R, nblk] int32;
    cu_seqlens [R+1] int32; kv_lens [R] int32 (valid KV per row AFTER
    this launch's inserts: a row's queries sit at its LAST kv_lens
    positions).  Returns [Tq, H, D]; padding tokens read zero.  Clamps
    table entries (the blha convention pads with -1) to real pages.
    """
    block_tables = jnp.clip(block_tables.astype(jnp.int32), 0,
                            key_cache.shape[0] - 1)
    return _ragged_launch(q, key_cache, value_cache, block_tables,
                          cu_seqlens.astype(jnp.int32),
                          kv_lens.astype(jnp.int32))


def ragged_paged_attention_quant(q, key_cache, value_cache, key_scales,
                                 value_scales, block_tables, cu_seqlens,
                                 kv_lens):
    """Ragged attention over int8 KV pages with per-page-per-head scales.

    q [Tq, H, D] float; caches [num_blocks, H_kv, bs, D] int8;
    key_scales/value_scales [num_blocks, H_kv] f32 (symmetric:
    float = int8 * scale); the row layout as in
    `ragged_paged_attention`.  Returns [Tq, H, D] in q.dtype.
    """
    block_tables = jnp.clip(block_tables.astype(jnp.int32), 0,
                            key_cache.shape[0] - 1)
    return _ragged_launch(
        q, key_cache, value_cache, block_tables,
        cu_seqlens.astype(jnp.int32), kv_lens.astype(jnp.int32),
        (key_scales.astype(jnp.float32), value_scales.astype(jnp.float32)))


def ragged_paged_attention_quant_packed(q, key_cache, value_cache,
                                        key_scales, value_scales,
                                        block_tables, cu_seqlens, kv_lens,
                                        layer=None):
    """Int8-page ragged launch without the defensive clip/casts, for
    callers that guarantee the host-packing invariant (serving.py packs
    int32 tables/cu/kv_lens and f32 scale pools).  With ``layer`` all
    four pools are those of all layers (the scales [L, num_blocks,
    H_kv]): the pages are read in place, the layer's scale rows, which
    ride in scalar memory, are sliced out here."""
    if layer is not None:
        key_scales, value_scales = key_scales[layer], value_scales[layer]
    return _ragged_launch(q, key_cache, value_cache, block_tables,
                          cu_seqlens, kv_lens, (key_scales, value_scales),
                          layer)


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
                                  seg, rel, window=None, sm_scale=None):
    """Dense-gather XLA oracle for the ragged kernel (the engine's former
    chunked-resume math, term for term).  ``window``: keys below a
    query's position less window - 1 are masked as well (what their
    table entries name is gathered and not used)."""
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
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    scores = jnp.einsum("qhd,qshd->qhs", q.astype(jnp.float32),
                        kq.astype(jnp.float32)) * sm_scale
    keypos = jnp.arange(nblk * bs, dtype=jnp.int32)
    mask = keypos[None, None, :] <= rel[:, None, None]
    if window is not None:
        mask &= keypos[None, None, :] > rel[:, None, None] - window
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("qhs,qshd->qhd", probs, vq.astype(jnp.float32))
    return out.astype(q.dtype)


def ragged_paged_reference(q, key_cache, value_cache, block_tables,
                           cu_seqlens, kv_lens, window=None):
    """Dense-gather XLA oracle with the public (cu, kv_lens) interface."""
    seg, rel = ragged_segments(cu_seqlens, kv_lens, q.shape[0])
    return ragged_paged_reference_segrel(
        q, key_cache, value_cache, block_tables, seg, rel, window=window)


# Scalar memory of one TensorCore of the TPU v5e, the only device this was
# measured on.  The ragged launch prefetches cu, kv_lens, the block table
# and, over int8 pages, both scale pools into it.  A launch whose operands
# came to 1036 KiB was refused there at compile time ("Used 1.01M of 1.00M
# smem"), one of 269 KiB compiled, and the refusal listed a [1025, 32] f32
# operand at 516 KiB: 32-bit words in (8, 128) tiles.  What Mosaic keeps
# there for itself was not measured; _SMEM_RESERVE stands in for it.
_SMEM_BYTES = 1 << 20
_SMEM_RESERVE = 16 << 10


def scalar_prefetch_bytes(table_rows, nblk, num_blocks, Hkv,
                          int8: bool) -> int:
    """Scalar memory the operands a ragged launch prefetches take: cu
    and kv_lens (a word a table row) and the layer index (one word),
    each counted in whole lanes of 128 words, the block table
    [table_rows, nblk] and, over int8 pages, the two [num_blocks, Hkv]
    f32 scale pools, each padded to (8, 128) tiles of 32-bit words."""
    def tiled(rows, cols):
        return -(-rows // 8) * 8 * -(-cols // 128) * 128 * 4

    need = (2 * -(-table_rows // 128) + 1) * 128 * 4 \
        + tiled(table_rows, nblk)
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
    largest ragged launch as ``(table_rows, nblk, num_blocks)``: the
    ragged kernel copies whole pages, which takes a head_dim that fills
    the 128 lanes (64 is the decode kernel's alone), and its prefetched
    operands must fit the scalar memory, which bounds the int8 page
    pool (both scale pools ride there) and the block table (None: the
    decode kernel's claim, nothing of a launch checked).
    Tensor-parallel callers pass per-shard head counts: the kernel
    launches inside shard_map and tiles against the shard-local
    shapes."""
    if H < 1 or Hkv < 1 or H % Hkv:
        return f"{H} query heads are not a multiple of {Hkv} kv heads"
    if D % 128 and (D != 64 or launch is not None):
        return (f"head_dim {D} is not a multiple of 128" if launch
                else f"head_dim {D} is neither 64 nor a multiple of 128")
    int8 = jnp.dtype(kv_dtype) == jnp.int8
    min_bs = 32 if int8 else 8
    if bs % min_bs:
        return (f"block_size {bs} is not a multiple of {min_bs} "
                f"({jnp.dtype(kv_dtype).name} pages)")
    if launch is not None:
        table_rows, nblk, num_blocks = launch
        need = scalar_prefetch_bytes(table_rows, nblk, num_blocks, Hkv,
                                     int8)
        if need > _SMEM_BYTES - _SMEM_RESERVE:
            return (f"a [{table_rows}, {nblk}] block table "
                    + (f"and the scale pools of {num_blocks} int8 pages "
                       "need" if int8 else "needs")
                    + f" {need >> 10} KiB of the "
                    f"{_SMEM_BYTES >> 10} KiB scalar memory (v5e)")
    return None
