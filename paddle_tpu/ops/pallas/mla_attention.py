"""Pallas ragged paged LATENT attention (TPU): the serving kernel of a
multi-head latent attention (MLA) layer in its absorbed form.

An MLA layer caches one row a token, ``[c | k_rope]`` (``kv_lora_rank``
normalised latent columns, then the rotated rope key that every head
shares): 576 numbers at the DeepSeek-V2 sizes against 2 x Hkv x D of a
K/V cache.  In the absorbed form the per-head key expansion is folded
into the query (``q'_h = W_kvb,h^K^T q_nope_h``) and the value expansion
is applied after the weighted sum, so attention reads the cached row
itself: every head scores ``[q'_h | q_rope_h]`` (576) against the row
and sums its first ``kv_lora_rank`` columns.  That is multi-query
attention with one shared key of width 576 whose value is a prefix of
the key, and this kernel is that and nothing else; the two expansions
stay with the caller as plain products.

It is driven by the row layout of ``paged_attention.ragged_paged_
attention`` (``cu_seqlens``, ``kv_lens``, the block table, all scalar
prefetched) and keeps that kernel's contract: a prefill chunk, a resumed
chunk and a decode token are rows of one launch; the unit of work is a
tile of one row's queries against that row's live pages; the pool stays
in HBM and whole pages come from it by double-buffered async copies; a
page past a row's ``kv_len``, a padded token and a row of no keys get no
copy, no loop turn and no arithmetic, and their output reads zero.  What
differs: the pool is ONE array for all layers, ``[L, num_blocks, bs,
width]``, read in place at a prefetched layer index (a layer-sized slice
of a 1.8 GB pool would cost a copy a layer a step), and the queries and
the output stay in HBM too (576 tokens of 64 heads are 42 MB): an item
copies its own query tile in and its output tile out.

``mla_ragged_reference`` is the XLA oracle (absorbed form, dense gather),
``mla_expanded_reference`` the same attention in the expanded form
(per-head keys and values made from the cached latents by ``W_kvb``):
the two are one function, which ``tests/test_mla_attention.py`` holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _pa

KERNEL_NAME = "ragged_latent_attention"


def page_width(row: int) -> int:
    """Stored width of a cached row of ``row`` numbers: the next
    multiple of the 128 lanes of an HBM tile."""
    return -(-int(row) // 128) * 128

# 16 tokens (1,024 score rows) against 512 keys a block: the best of the
# four pairs tried on the v5e at a 512-token chunk over 4k and 12k keys
# and at 20 decode rows (PERF.md, PR 28)
_DEFAULT_TILES = {"q_tile_tokens": 16, "kv_pages": 32}


def _tiles(Tq, G, width, bs, nblk, dtype):
    """(query tokens a tile, pages a K block) from the tuning cache."""
    from ...tune import kernel_config
    cfg = kernel_config("mla_attention",
                        {"tq": Tq, "heads": G, "width": width, "page": bs,
                         "nblk": nblk, "dtype": jnp.dtype(dtype).name},
                        defaults=_DEFAULT_TILES)
    tq = max(1, min(int(cfg["q_tile_tokens"]), Tq))
    return tq, max(1, min(int(cfg["kv_pages"]), nblk))


def _kernel(cu_ref, kvl_ref, bt_ref, lyr_ref, q_hbm, pool_hbm, _o_zero,
            o_hbm, qbuf, obuf, kbuf, sems, m_ref, l_ref, acc_ref, *,
            rows, tq, kvb, bs, nblk, G, dc):
    """One invocation walks the launch's rows in order.  q [(Tq+tq)*G,
    width] (token-major, pre-scaled), the pool [L, num_blocks, bs, width]
    and o [(Tq+tq)*G, dc] (zero on entry: aliased to a zero operand) stay
    in HBM.  Scratch: qbuf [tq*G, width], obuf [tq*G, dc], kbuf [2, kvb,
    bs, width] (two slots of kvb pages), DMA semaphores [4] (a page slot
    each, q, o), m/l [tq*G, 1] and acc [tq*G, dc] f32.

    An ITEM is a tile of one row's queries against that row's live
    pages: a row of one query is one item of G score rows; a longer row
    is an item for every tq of its tokens, counted from the row's first
    (the tile of a row's tail runs on into the tokens that follow; their
    score rows see no key, read zero, and are written again by the rows
    they belong to, which come later in the walk).  An item walks K
    blocks of kvb pages up to the page of the last key its last query
    sees; each block's pages are started a block ahead, across items
    too."""
    kv = kvb * bs
    lyr = lyr_ref[0]

    def n_pages(r, j):
        qs, qe = cu_ref[r], cu_ref[r + 1]
        n_q = qe - qs
        last = jnp.minimum(qe, qs + (j + 1) * tq) - 1
        rel_last = kvl_ref[r] - n_q + last - qs
        np_ = jnp.where(n_q > 0, rel_last // bs + 1, 0)
        return jnp.clip(np_, 0, nblk)

    def n_tiles(r):
        # a row of no queries is one item of no pages: it hands the
        # copy its predecessor started for it on to its successor
        return jnp.maximum((cu_ref[r + 1] - cu_ref[r] + tq - 1) // tq, 1)

    def each_copy(r, b, np_, slot, act):
        n = jnp.clip(np_ - b * kvb, 0, kvb)

        def one(p, c):
            blk = bt_ref[r, b * kvb + p]
            act(pltpu.make_async_copy(pool_hbm.at[lyr, blk],
                                      kbuf.at[slot, p], sems.at[slot]))
            return c
        jax.lax.fori_loop(0, n, one, 0)
        return n

    def start(r, b, np_, slot):
        each_copy(r, b, np_, slot, lambda d: d.start())

    def wait(r, b, np_, slot):
        n = each_copy(r, b, np_, slot, lambda d: d.wait())

        # pages of the block that were not copied hold what the slot
        # held before; masked scores give them probability 0, and
        # 0 * NaN is NaN in the P.V product
        def zero(p, c):
            kbuf[slot, p] = jnp.zeros(kbuf.shape[2:], kbuf.dtype)
            return c
        jax.lax.fori_loop(n, kvb, zero, 0)

    def item(r, j, slot, *, one):
        width = 1 if one else tq
        M = width * G
        qs, qe = cu_ref[r], cu_ref[r + 1]
        n_q = qe - qs
        t0 = qs + j * tq
        np_ = n_pages(r, j)
        nb = (np_ + kvb - 1) // kvb
        # the successor: the row's next tile, else the next row's first
        more = j + 1 < n_tiles(r)
        rn = jnp.where(more, r, r + 1)
        last = rn >= rows
        rn = jnp.minimum(rn, rows - 1)
        jn = jnp.where(more, j + 1, 0)
        npn = jnp.where(last, 0, n_pages(rn, jn))

        @pl.when(nb == 0)
        def _pass_on():
            start(rn, 0, npn, slot)

        @pl.when(nb > 0)
        def _work():
            qcopy = pltpu.make_async_copy(
                q_hbm.at[pl.ds(t0 * G, M)], qbuf.at[pl.ds(0, M)],
                sems.at[2])
            qcopy.start()
            tok = t0 + jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0) // G
            rel = jnp.where(tok < qe, kvl_ref[r] - n_q + tok - qs, -1)
            m_ref[:M] = jnp.full((M, 1), -jnp.inf, jnp.float32)
            l_ref[:M] = jnp.zeros((M, 1), jnp.float32)
            acc_ref[:M] = jnp.zeros((M, dc), jnp.float32)
            qcopy.wait()

            def block(b, slot):
                @pl.when(b + 1 < nb)
                def _next_block():
                    start(r, b + 1, np_, 1 - slot)

                @pl.when(b + 1 == nb)
                def _next_item():
                    start(rn, 0, npn, 1 - slot)

                wait(r, b, np_, slot)
                keypos = b * kv + jax.lax.broadcasted_iota(
                    jnp.int32, (1, kv), 1)
                mask = keypos <= rel                       # [M, kv]
                q = qbuf[:M]
                k = kbuf[slot].reshape(kv, kbuf.shape[-1])
                c = k[:, :dc]
                # one product over the whole stored row: the columns
                # past the rope key are zero in q and in the pool
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                s = jnp.where(mask, s, -jnp.inf)
                m_prev = m_ref[:M]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                # a score row of another row of the launch sees no key
                m_fin = jnp.where(m_new == -jnp.inf, 0.0, m_new)
                p = jnp.exp(s - m_fin)
                alpha = jnp.exp(m_prev - m_fin)
                l_ref[:M] = alpha * l_ref[:M] + \
                    jnp.sum(p, axis=1, keepdims=True)
                acc_ref[:M] = acc_ref[:M] * alpha + jax.lax.dot_general(
                    p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[:M] = m_new
                return 1 - slot

            jax.lax.fori_loop(0, nb, block, slot)
            l = l_ref[:M]
            obuf[:M] = (acc_ref[:M] / jnp.where(l == 0.0, 1.0, l)
                        ).astype(obuf.dtype)
            ocopy = pltpu.make_async_copy(
                obuf.at[pl.ds(0, M)], o_hbm.at[pl.ds(t0 * G, M)],
                sems.at[3])
            ocopy.start()
            # a later item may write the same tokens again (this tile's
            # overhang): its copy must land after this one
            ocopy.wait()
        return jnp.where(nb % 2 == 1, 1 - slot, slot)

    start(0, 0, n_pages(0, 0), 0)

    def row(r, slot):
        one = cu_ref[r + 1] - cu_ref[r] <= 1

        def tile(j, slot):
            return jax.lax.cond(
                one,
                functools.partial(item, one=True),
                functools.partial(item, one=False), r, j, slot)
        return jax.lax.fori_loop(0, n_tiles(r), tile, slot)

    jax.lax.fori_loop(0, rows, row, 0)


def ragged_latent_attention_packed(q, pool, layer, block_tables, cu_seqlens,
                                   kv_lens, *, latent_dim: int,
                                   sm_scale: float):
    """Absorbed-form latent attention of one layer over the paged pool.

    q [Tq, G, latent + rope]: per head ``[q' | q_rope]``; pool [L,
    num_blocks, bs, width] with ``[c | k_rope | 0...]`` rows, ``width``
    the row rounded up to the 128 lanes of an HBM tile (``page_width``:
    a 576-wide array is stored 640 wide whatever its declared shape, and
    a copy of whole rows has to name the stored width); ``layer`` an
    int32 scalar
    (traced or static); the row layout as ``ragged_paged_attention_
    packed`` takes it (int32, cu non-decreasing with cu[R] <= Tq, table
    entries in [0, num_blocks); the table may carry the serving null
    row).  Returns [Tq, G, latent_dim]: per head the probability-
    weighted sum of the row's latents, for the caller's ``W_kvb^V``."""
    Tq, G, wq = q.shape
    _, _, bs, width = pool.shape
    rows = kv_lens.shape[0]
    nblk = block_tables.shape[1]
    dc = int(latent_dim)
    tq, kvb = _tiles(Tq, G, width, bs, nblk, pool.dtype)
    M = tq * G
    # scaled once and rounded to the pool's type, the score product's
    # operand; tq tokens of zeros follow so that the tile of a launch's
    # last tokens reads inside the array
    q2 = (q.astype(jnp.float32) * sm_scale).astype(pool.dtype)
    q2 = jnp.pad(q2.reshape(Tq * G, wq), ((0, M), (0, width - wq)))
    o0 = jnp.zeros(((Tq + tq) * G, dc), pool.dtype)
    kernel = functools.partial(_kernel, rows=rows, tq=tq, kvb=kvb, bs=bs,
                               nblk=nblk, G=G, dc=dc)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    item = jnp.dtype(pool.dtype).itemsize
    need = M * (width + dc) * item + 2 * kvb * bs * width * item \
        + M * (2 * 128 + dc) * 4 + 3 * M * kvb * bs * 4
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,          # cu, kv_lens, table, layer
            grid=(1,),
            in_specs=[hbm, hbm, hbm],
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((M, width), pool.dtype),
                pltpu.VMEM((M, dc), pool.dtype),
                pltpu.VMEM((2, kvb, bs, width), pool.dtype),
                pltpu.SemaphoreType.DMA((4,)),
                pltpu.VMEM((M, 1), jnp.float32),
                pltpu.VMEM((M, 1), jnp.float32),
                pltpu.VMEM((M, dc), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(o0.shape, pool.dtype),
        # operand 6 of the call (after the four prefetched scalars, q and
        # the pool) is the zero output-to-be
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(100 << 20, max(16 << 20, 2 * need))),
        interpret=_pa.interpret_mode(),
        name=KERNEL_NAME,
    )(cu_seqlens, kv_lens, block_tables,
      jnp.asarray(layer, jnp.int32).reshape(1), q2, pool, o0)
    return out[:Tq * G].reshape(Tq, G, dc)


def _gather_rows(pool_layer, block_tables, seg, n_keys):
    """[Tq, n_keys, width]: each token's row's cached latents, densely."""
    bs = pool_layer.shape[1]
    pages = block_tables[seg]                          # [Tq, nblk]
    k = pool_layer[pages]                              # [Tq, nblk, bs, w]
    return k.reshape(k.shape[0], -1, k.shape[-1])[:, :n_keys]


def mla_ragged_reference_segrel(q, pool_layer, block_tables, seg, rel, *,
                                latent_dim: int, sm_scale: float):
    """The XLA oracle of the kernel, absorbed form, from per-token (seg,
    rel) as ``paged_attention.ragged_segments`` gives them: every token
    gathers its row's pages densely and masks keys past ``rel``.  A
    padded token (seg == R) resolves to the table's last row and gives a
    finite row the caller discards."""
    dc = int(latent_dim)
    nblk = block_tables.shape[1]
    bs = pool_layer.shape[1]
    segc = jnp.minimum(seg, block_tables.shape[0] - 1)
    k = _gather_rows(pool_layer, block_tables, segc, nblk * bs)
    s = jnp.einsum("tgw,tkw->tgk", q.astype(jnp.float32),
                   k[..., :q.shape[-1]].astype(jnp.float32)) * sm_scale
    keypos = jnp.arange(nblk * bs, dtype=jnp.int32)
    s = jnp.where((keypos[None, :] <= rel[:, None])[:, None, :], s,
                  -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("tgk,tkc->tgc", p, k[..., :dc].astype(jnp.float32))
    return out.astype(q.dtype)


def mla_ragged_reference(q, pool_layer, block_tables, cu_seqlens, kv_lens,
                         *, latent_dim: int, sm_scale: float):
    """``mla_ragged_reference_segrel`` from the row layout; rows of no
    keys and padded tokens read zero, as the kernel's do."""
    Tq = q.shape[0]
    seg, rel = _pa.ragged_segments(cu_seqlens, kv_lens, Tq)
    R = kv_lens.shape[0]
    live = (seg < R) & (kv_lens[jnp.minimum(seg, R - 1)] > 0)
    out = mla_ragged_reference_segrel(
        q, pool_layer, block_tables, seg, jnp.where(live, rel, 0),
        latent_dim=latent_dim, sm_scale=sm_scale)
    return jnp.where(live[:, None, None], out, 0).astype(q.dtype)


def mla_expanded_reference(q_nope, q_rope, pool_layer, w_kvb, block_tables,
                           cu_seqlens, kv_lens, *, nope_dim: int,
                           sm_scale: float):
    """The same attention in the EXPANDED form, for the test that the
    absorbed form is the same function: per-head keys and values are
    made from the cached latents, ``[k_nope | v]_h = W_kvb,h c``, the
    score is ``q_nope_h . k_nope_h + q_rope_h . k_rope``, and the output
    is the weighted sum of ``v_h`` ([Tq, G, v_dim]).  q_nope [Tq, G,
    nope], q_rope [Tq, G, rope], w_kvb [latent, G, nope + v]."""
    Tq, G, _ = q_nope.shape
    dc = w_kvb.shape[0]
    nblk = block_tables.shape[1]
    bs = pool_layer.shape[1]
    seg, rel = _pa.ragged_segments(cu_seqlens, kv_lens, Tq)
    R = kv_lens.shape[0]
    live = (seg < R) & (kv_lens[jnp.minimum(seg, R - 1)] > 0)
    rel = jnp.where(live, rel, 0)
    k = _gather_rows(pool_layer, block_tables,
                     jnp.minimum(seg, block_tables.shape[0] - 1),
                     nblk * bs).astype(jnp.float32)
    kv = jnp.einsum("tkc,cgd->tkgd", k[..., :dc],
                    w_kvb.astype(jnp.float32))
    k_nope, v = kv[..., :nope_dim], kv[..., nope_dim:]
    s = (jnp.einsum("tgd,tkgd->tgk", q_nope.astype(jnp.float32), k_nope)
         + jnp.einsum("tgr,tkr->tgk", q_rope.astype(jnp.float32),
                      k[..., dc:dc + q_rope.shape[-1]])) * sm_scale
    keypos = jnp.arange(nblk * bs, dtype=jnp.int32)
    s = jnp.where((keypos[None, :] <= rel[:, None])[:, None, :], s,
                  -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("tgk,tkgd->tgd", p, v)
    return jnp.where(live[:, None, None], out, 0).astype(q_nope.dtype)


def ineligible(heads: int, width: int, latent_dim: int, bs: int,
               dtype=jnp.bfloat16, *, launch=None):
    """Why the kernel does not take this shape on a TPU, or None.  It
    copies whole pages and takes the value as the key's first
    ``latent_dim`` columns: both widths must be lane tiles (multiples of
    128: ``page_width``), a page must fill the
    type's sublane tile, and the prefetched row layout (cu, kv_lens and
    the table, padded to (8, 128) words) must fit scalar memory."""
    if latent_dim % 128 or width % 128:
        return (f"latent width {latent_dim} or stored row {width} is not "
                "a multiple of 128")
    if width <= latent_dim:
        return f"stored row {width} holds no rope columns"
    sub = 32 // jnp.dtype(dtype).itemsize
    if bs % sub:
        return f"page of {bs} tokens does not fill a {sub}-row tile"
    if launch is not None:
        table_rows, nblk, _num_blocks = launch
        words = (-(-table_rows // 8) * 8) * (-(-nblk // 128) * 128)
        if 4 * words > (768 << 10):
            return (f"block table [{table_rows}, {nblk}] needs "
                    f"{4 * words >> 10} KiB of scalar memory")
    return None
