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
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _pa

KERNEL_NAME = "ragged_latent_attention"
# a sliding-window layer's launches, a selected-keys layer's and the
# indexer's score product, each under a name of its own in a trace
WINDOW_KERNEL_NAME = "ragged_latent_attention_window"
SELECT_KERNEL_NAME = "ragged_latent_attention_selected"
INDEX_KERNEL_NAME = "ragged_index_scores"


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


def _dividing_block(kvb: int, nblk: int, bs: int) -> int:
    """The largest K block of at most ``kvb`` pages that divides a row's
    ``nblk`` pages into whole blocks of whole lane tiles: what a kernel
    that copies [tokens, block of keys] tiles of a [tokens, nblk * bs]
    array needs."""
    lanes = 1 if _pa.interpret_mode() else 128
    for n in range(max(1, min(kvb, nblk)), 0, -1):
        if nblk % n == 0 and (n * bs) % lanes == 0:
            return n
    raise ValueError(f"no K block of whole lane tiles divides {nblk} "
                     f"pages of {bs}")


def _walk(cu_ref, kvl_ref, bt_ref, lyr, pool_hbm, kbuf, sems, *, rows, tq,
          kvb, bs, nblk, window):
    """The walk both kernels below make over a launch's rows: an ITEM is
    a tile of ``tq`` of one row's queries (counted from the row's first)
    against the pages that row's tile sees, in K blocks of ``kvb`` pages
    copied into ``kbuf`` [2, kvb, bs, width] a block ahead, across items
    too.  With ``window`` (a layer whose query at position i sees keys
    i - window < j <= i) an item's walk starts at the page of the lowest
    key its first query sees: the table's entries below it are not read.
    Returns the helpers the kernels share."""

    def n_pages(r, j):
        qs, qe = cu_ref[r], cu_ref[r + 1]
        n_q = qe - qs
        last = jnp.minimum(qe, qs + (j + 1) * tq) - 1
        rel_last = kvl_ref[r] - n_q + last - qs
        np_ = jnp.where(n_q > 0, rel_last // bs + 1, 0)
        return jnp.clip(np_, 0, nblk)

    def page_range(r, j):
        """(first page, pages) item (r, j) walks."""
        if window is None:
            return 0, n_pages(r, j)
        n_q = cu_ref[r + 1] - cu_ref[r]
        rel_first = kvl_ref[r] - n_q + j * tq
        p0 = jnp.clip((rel_first - (window - 1)) // bs, 0, nblk)
        return p0, jnp.maximum(n_pages(r, j) - p0, 0)

    def n_tiles(r):
        # a row of no queries is one item of no pages: it hands the
        # copy its predecessor started for it on to its successor
        return jnp.maximum((cu_ref[r + 1] - cu_ref[r] + tq - 1) // tq, 1)

    def each_copy(r, b, rng, slot, act):
        p0, np_ = rng
        n = jnp.clip(np_ - b * kvb, 0, kvb)

        def one(p, c):
            # (no "0 +" where there is no window: the kernel of a layer
            # without one lowers to the module it always did)
            blk = bt_ref[r, b * kvb + p if window is None
                         else p0 + b * kvb + p]
            act(pltpu.make_async_copy(pool_hbm.at[lyr, blk],
                                      kbuf.at[slot, p], sems.at[slot]))
            return c
        jax.lax.fori_loop(0, n, one, 0)
        return n

    def start(r, b, rng, slot):
        each_copy(r, b, rng, slot, lambda d: d.start())

    def wait(r, b, rng, slot):
        n = each_copy(r, b, rng, slot, lambda d: d.wait())

        # pages of the block that were not copied hold what the slot
        # held before; masked scores give them probability 0, and
        # 0 * NaN is NaN in the P.V product
        def zero(p, c):
            kbuf[slot, p] = jnp.zeros(kbuf.shape[2:], kbuf.dtype)
            return c
        jax.lax.fori_loop(n, kvb, zero, 0)

    def successor(r, j):
        """(row, tile, page range) of the item after (r, j): the row's
        next tile, else the next row's first; past the last row an item
        of no pages."""
        more = j + 1 < n_tiles(r)
        rn = jnp.where(more, r, r + 1)
        last = rn >= rows
        rn = jnp.minimum(rn, rows - 1)
        jn = jnp.where(more, j + 1, 0)
        p0n, npn = page_range(rn, jn)
        return rn, (p0n, jnp.where(last, 0, npn))

    def run(item):
        """Every item in order through ``item(r, j, slot, one=)`` ->
        slot; rows of at most one query take the ``one`` form."""
        start(0, 0, page_range(0, 0), 0)

        def row(r, slot):
            one = cu_ref[r + 1] - cu_ref[r] <= 1

            def tile(j, slot):
                return jax.lax.cond(
                    one,
                    functools.partial(item, one=True),
                    functools.partial(item, one=False), r, j, slot)
            return jax.lax.fori_loop(0, n_tiles(r), tile, slot)

        jax.lax.fori_loop(0, rows, row, 0)

    return SimpleNamespace(page_range=page_range, start=start, wait=wait,
                           successor=successor, run=run)


def _kernel(cu_ref, kvl_ref, bt_ref, lyr_ref, *rest, rows, tq, kvb, bs,
            nblk, G, dc, window=None, sel=False):
    """One invocation walks the launch's rows in order.  q [(Tq+tq)*G,
    width] (token-major, pre-scaled), the pool [L, num_blocks, bs, width]
    and o [(Tq+tq)*G, dc] (zero on entry: aliased to a zero operand) stay
    in HBM.  Scratch: qbuf [tq*G, width], obuf [tq*G, dc], kbuf [2, kvb,
    bs, width] (two slots of kvb pages), DMA semaphores [4] (a page slot
    each, q, o), m/l [tq*G, 1] and acc [tq*G, dc] f32.

    An ITEM is a tile of one row's queries against that row's live
    pages (``_walk``): a row of one query is one item of G score rows; a
    longer row is an item for every tq of its tokens, counted from the
    row's first (the tile of a row's tail runs on into the tokens that
    follow; their score rows see no key, read zero, and are written
    again by the rows they belong to, which come later in the walk).

    With ``sel`` (a layer whose queries attend to SELECTED keys only)
    two more operands: ``toff`` [rows + 1] (prefetched: the items before
    each row) and, in HBM, the additive selection ``bias`` [items * tq,
    nblk * bs] f32 in ITEM-major rows (item i's token k at row i * tq +
    k: ``item_layout``), 0 where the token's query selected the key and
    -inf elsewhere; an item copies its [tq, kv] tile a block and adds a
    token's row to that token's G score rows."""
    if sel:
        (toff_ref, q_hbm, pool_hbm, sel_hbm, _o_zero, o_hbm, qbuf, obuf,
         kbuf, sems, m_ref, l_ref, acc_ref, sbuf) = rest
    else:
        (q_hbm, pool_hbm, _o_zero, o_hbm, qbuf, obuf, kbuf, sems, m_ref,
         l_ref, acc_ref) = rest
    kv = kvb * bs
    w = _walk(cu_ref, kvl_ref, bt_ref, lyr_ref[0], pool_hbm, kbuf, sems,
              rows=rows, tq=tq, kvb=kvb, bs=bs, nblk=nblk, window=window)

    def item(r, j, slot, *, one):
        width = 1 if one else tq
        M = width * G
        qs, qe = cu_ref[r], cu_ref[r + 1]
        n_q = qe - qs
        t0 = qs + j * tq
        rng = w.page_range(r, j)
        p0, np_ = rng
        nb = (np_ + kvb - 1) // kvb
        rn, rngn = w.successor(r, j)

        @pl.when(nb == 0)
        def _pass_on():
            w.start(rn, 0, rngn, slot)

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
                    w.start(r, b + 1, rng, 1 - slot)

                @pl.when(b + 1 == nb)
                def _next_item():
                    w.start(rn, 0, rngn, 1 - slot)

                if sel:
                    scopy = pltpu.make_async_copy(
                        sel_hbm.at[pl.ds(pl.multiple_of(
                            (toff_ref[r] + j) * tq, tq), tq),
                            pl.ds(pl.multiple_of(b * kv, kv), kv)],
                        sbuf, sems.at[4])
                    scopy.start()
                w.wait(r, b, rng, slot)
                keypos = b * kv + jax.lax.broadcasted_iota(
                    jnp.int32, (1, kv), 1)
                if window is None:
                    mask = keypos <= rel                   # [M, kv]
                else:
                    keypos = keypos + p0 * bs
                    mask = (keypos <= rel) & (keypos > rel - window)
                q = qbuf[:M]
                k = kbuf[slot].reshape(kv, kbuf.shape[-1])
                c = k[:, :dc]
                # one product over the whole stored row: the columns
                # past the rope key are zero in q and in the pool
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                s = jnp.where(mask, s, -jnp.inf)
                if sel:
                    scopy.wait()
                    # a token's row of the bias onto its G score rows
                    s = jnp.concatenate(
                        [s[i * G:(i + 1) * G] + sbuf[i:i + 1, :]
                         for i in range(width)], axis=0)
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

    w.run(item)


def item_layout(cu_seqlens, rows: int, Tq: int, tq: int):
    """Where the kernels keep what is a row a TOKEN and a column a KEY
    position (the index scores, the selection's bias): ITEM-major, item
    i's token k at row ``i * tq + k``, so that an item's [tq, kv] tile
    starts on a whole tile of rows wherever its row starts in the
    launch.  Returns (toff [rows + 1] i32: the items before each row, as
    the kernels count them; slot [Tq] i32: each flat token's row, a
    padded token's the last; the rows in all)."""
    cu = cu_seqlens.astype(jnp.int32)
    n_q = cu[1:rows + 1] - cu[:rows]
    tiles = jnp.maximum((n_q + tq - 1) // tq, 1)
    toff = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(tiles).astype(jnp.int32)])
    n_slots = (Tq // tq + rows + 1) * tq
    t = jnp.arange(Tq, dtype=jnp.int32)
    seg = jnp.clip(jnp.searchsorted(cu[1:rows + 1], t, side="right"), 0,
                   rows - 1).astype(jnp.int32)
    k = t - cu[seg]
    slot = (toff[seg] + k // tq) * tq + k % tq
    return toff, jnp.where(t < cu[rows], slot, n_slots - 1), n_slots


def ragged_latent_attention_packed(q, pool, layer, block_tables, cu_seqlens,
                                   kv_lens, *, latent_dim: int,
                                   sm_scale: float, window=None,
                                   select=None):
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
    row).  ``window`` (a static int): a query sees its own position and
    the window - 1 before it, and the table's entries for a row's pages
    below its window are not read; such a launch is named
    ``WINDOW_KERNEL_NAME``.  ``select`` [Tq, nblk * bs] f32: the
    additive bias of a layer whose queries attend to selected keys only
    (``select_bias``: 0 at a selected key, -inf elsewhere), token-major;
    such a launch is named ``SELECT_KERNEL_NAME``.  Returns [Tq, G,
    latent_dim]: per head the probability-weighted sum of the row's
    latents, for the caller's ``W_kvb^V``."""
    Tq, G, wq = q.shape
    _, _, bs, width = pool.shape
    rows = kv_lens.shape[0]
    nblk = block_tables.shape[1]
    dc = int(latent_dim)
    tq, kvb = _tiles(Tq, G, width, bs, nblk, pool.dtype)
    sel = select is not None
    if sel:
        # the bias is copied in [tq, kv] tiles of whole (8, 128) words
        kvb = _dividing_block(kvb, nblk, bs)
        tq = -(-tq // 8) * 8
    M = tq * G
    # scaled once and rounded to the pool's type, the score product's
    # operand; tq tokens of zeros follow so that the tile of a launch's
    # last tokens reads inside the array
    q2 = (q.astype(jnp.float32) * sm_scale).astype(pool.dtype)
    q2 = jnp.pad(q2.reshape(Tq * G, wq), ((0, M), (0, width - wq)))
    o0 = jnp.zeros(((Tq + tq) * G, dc), pool.dtype)
    kernel = functools.partial(_kernel, rows=rows, tq=tq, kvb=kvb, bs=bs,
                               nblk=nblk, G=G, dc=dc,
                               window=None if window is None
                               else int(window), sel=sel)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    item = jnp.dtype(pool.dtype).itemsize
    need = M * (width + dc) * item + 2 * kvb * bs * width * item \
        + M * (2 * 128 + dc) * 4 + 3 * M * kvb * bs * 4
    scratch = [
        pltpu.VMEM((M, width), pool.dtype),
        pltpu.VMEM((M, dc), pool.dtype),
        pltpu.VMEM((2, kvb, bs, width), pool.dtype),
        pltpu.SemaphoreType.DMA((5 if sel else 4,)),
        pltpu.VMEM((M, 1), jnp.float32),
        pltpu.VMEM((M, 1), jnp.float32),
        pltpu.VMEM((M, dc), jnp.float32),
    ]
    prefetch = [cu_seqlens, kv_lens, block_tables,
                jnp.asarray(layer, jnp.int32).reshape(1)]
    operands = [q2, pool]
    if sel:
        toff, slot, n_slots = item_layout(cu_seqlens, rows, Tq, tq)
        # token-major -> item-major: a row gather (a padded token and an
        # item's overhang read a row of -inf: they see no key anyway)
        tok = jnp.full((n_slots,), Tq, jnp.int32).at[slot].set(
            jnp.arange(Tq, dtype=jnp.int32), mode="drop")
        bias = jnp.concatenate(
            [select.astype(jnp.float32),
             jnp.full((1, select.shape[1]), -jnp.inf, jnp.float32)])[tok]
        prefetch.append(toff)
        operands.append(bias)
        scratch.append(pltpu.VMEM((tq, kvb * bs), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),  # cu, kv_lens, table, layer
            grid=(1,),
            in_specs=[hbm] * (len(operands) + 1),
            out_specs=hbm,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct(o0.shape, pool.dtype),
        # the last operand of the call (after the prefetched scalars, q,
        # the pool and a selection's bias) is the zero output-to-be
        input_output_aliases={len(prefetch) + len(operands): 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(100 << 20, max(16 << 20, 2 * need))),
        interpret=_pa.interpret_mode(),
        name=SELECT_KERNEL_NAME if sel else KERNEL_NAME
        if window is None else WINDOW_KERNEL_NAME,
    )(*prefetch, *operands, o0)
    return out[:Tq * G].reshape(Tq, G, dc)


# ---------------------------------------------------------------------------
# the indexer of a learned sparse attention (DeepSeek-V3.2's): scores of
# every (query, key) pair of a row from a few narrow heads, and the
# selection of each query's ``topk`` keys from them
# ---------------------------------------------------------------------------

# 64 tokens of 64 index heads against 512 keys a block: a page of index
# keys is 4 KB, so a wider tile of queries reads each page fewer times
_INDEX_TILES = {"q_tile_tokens": 64, "kv_pages": 32}


def _index_kernel(cu_ref, kvl_ref, bt_ref, lyr_ref, toff_ref, q_hbm, w_hbm,
                  pool_hbm, o_hbm, qbuf, wbuf, obuf, kbuf, sems, *,
                  rows, tq, kvb, bs, nblk, G):
    """Index scores of a launch: for token t of a row and key s of that
    row, ``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])`` over the G
    index heads.  q [(Tq+tq)*G, d] (token-major), w [(Tq+tq)*G, 128]
    f32 (the heads' weights, the score's constant folded in, a weight
    across a row of lanes: a [rows, 1] array cannot be copied from),
    the pool of
    index keys [L, num_blocks, bs, d] and the output [items * tq, nblk *
    bs] f32 (ITEM-major rows, ``item_layout``) stay in HBM.  The walk
    is ``_walk``'s; an item writes one [tq, kv] tile a block, up to the
    block of its last query's own position.  What the output holds
    elsewhere (a token past its row's end, a key past a query's own
    position, a block no item reached) is whatever the memory held: for
    the caller to mask by position."""
    kv = kvb * bs
    w = _walk(cu_ref, kvl_ref, bt_ref, lyr_ref[0], pool_hbm, kbuf, sems,
              rows=rows, tq=tq, kvb=kvb, bs=bs, nblk=nblk, window=None)

    def item(r, j, slot, *, one):
        width = 1 if one else tq
        M = width * G
        t0 = cu_ref[r] + j * tq
        rng = w.page_range(r, j)
        _, np_ = rng
        nb = (np_ + kvb - 1) // kvb
        rn, rngn = w.successor(r, j)
        at = pl.multiple_of((toff_ref[r] + j) * tq, tq)

        @pl.when(nb == 0)
        def _pass_on():
            w.start(rn, 0, rngn, slot)

        @pl.when(nb > 0)
        def _work():
            qcopy = pltpu.make_async_copy(
                q_hbm.at[pl.ds(t0 * G, M)], qbuf.at[pl.ds(0, M)],
                sems.at[2])
            wcopy = pltpu.make_async_copy(
                w_hbm.at[pl.ds(t0 * G, M)], wbuf.at[pl.ds(0, M)],
                sems.at[3])
            qcopy.start()
            wcopy.start()
            qcopy.wait()
            wcopy.wait()

            def block(b, slot):
                @pl.when(b + 1 < nb)
                def _next_block():
                    w.start(r, b + 1, rng, 1 - slot)

                @pl.when(b + 1 == nb)
                def _next_item():
                    w.start(rn, 0, rngn, 1 - slot)

                w.wait(r, b, rng, slot)
                k = kbuf[slot].reshape(kv, kbuf.shape[-1])
                s = jax.lax.dot_general(
                    qbuf[:M], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                s = jnp.maximum(s, 0.0) * wbuf[:M, 0:1]
                for i in range(width):
                    obuf[i:i + 1, :] = jnp.sum(s[i * G:(i + 1) * G],
                                               axis=0, keepdims=True)
                ocopy = pltpu.make_async_copy(
                    obuf, o_hbm.at[pl.ds(at, tq),
                                   pl.ds(pl.multiple_of(b * kv, kv), kv)],
                    sems.at[4])
                ocopy.start()
                ocopy.wait()
                return 1 - slot

            jax.lax.fori_loop(0, nb, block, slot)
        return jnp.where(nb % 2 == 1, 1 - slot, slot)

    w.run(item)


def ragged_index_scores_packed(q, w, pool, layer, block_tables, cu_seqlens,
                               kv_lens):
    """Index scores of one layer over the paged pool of index keys.

    q [Tq, G, d]: the index heads' queries (rotated); w [Tq, G] f32:
    their weights with the score's constant folded in; pool [L,
    num_blocks, bs, d]: the cached index keys of all layers, read at
    ``layer``; the row layout as ``ragged_latent_attention_packed``
    takes it.  Returns [Tq, nblk * bs] f32, token-major: ``sum_j w[t, j]
    relu(q[t, j] . k[s])`` for the keys s of t's row up to its own
    position; what stands at a key past it (or in a padded token's row)
    is not a score and need not be a number (``select_bias`` masks by
    position)."""
    Tq, G, d = q.shape
    _, _, bs, _ = pool.shape
    rows = kv_lens.shape[0]
    nblk = block_tables.shape[1]
    from ...tune import kernel_config
    cfg = kernel_config("mla_index",
                        {"tq": Tq, "heads": G, "width": d, "page": bs,
                         "nblk": nblk, "dtype": jnp.dtype(pool.dtype).name},
                        defaults=_INDEX_TILES)
    tq = -(-max(1, min(int(cfg["q_tile_tokens"]), Tq)) // 8) * 8
    kvb = _dividing_block(int(cfg["kv_pages"]), nblk, bs)
    M = tq * G
    toff, slot, n_slots = item_layout(cu_seqlens, rows, Tq, tq)
    q2 = jnp.pad(q.astype(pool.dtype).reshape(Tq * G, d), ((0, M), (0, 0)))
    w2 = jnp.pad(jnp.broadcast_to(
        w.astype(jnp.float32).reshape(Tq * G, 1), (Tq * G, 128)),
        ((0, M), (0, 0)))
    kernel = functools.partial(_index_kernel, rows=rows, tq=tq, kvb=kvb,
                               bs=bs, nblk=nblk, G=G)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    item = jnp.dtype(pool.dtype).itemsize
    need = M * d * item + M * 128 * 4 + 2 * kvb * bs * d * item \
        + (tq + 3 * M) * kvb * bs * 4
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,    # cu, kv_lens, table, layer, toff
            grid=(1,),
            in_specs=[hbm] * 3,
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((M, d), pool.dtype),
                pltpu.VMEM((M, 128), jnp.float32),
                pltpu.VMEM((tq, kvb * bs), jnp.float32),
                pltpu.VMEM((2, kvb, bs, d), pool.dtype),
                pltpu.SemaphoreType.DMA((5,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots, nblk * bs), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(100 << 20, max(16 << 20, 2 * need))),
        interpret=_pa.interpret_mode(),
        name=INDEX_KERNEL_NAME,
    )(cu_seqlens, kv_lens, block_tables,
      jnp.asarray(layer, jnp.int32).reshape(1), toff, q2, w2, pool)
    return out[slot]


def index_scores_reference_segrel(q, w, pool_layer, block_tables, seg):
    """The XLA oracle of ``ragged_index_scores_packed`` from per-token
    ``seg``: every token gathers its row's index keys densely."""
    nblk = block_tables.shape[1]
    bs = pool_layer.shape[1]
    segc = jnp.minimum(seg, block_tables.shape[0] - 1)
    k = _gather_rows(pool_layer, block_tables, segc, nblk * bs)
    s = jnp.einsum("tgd,tkd->tgk", q.astype(pool_layer.dtype), k,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(s, 0.0)
                   * w.astype(jnp.float32)[:, :, None], axis=1)


def _sortable(x):
    """float32 -> uint32 whose order is the floats' (-0.0 below 0.0)."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)
    return jax.lax.bitcast_convert_type(key, jnp.uint32) \
        ^ jnp.uint32(0x80000000)


def select_mask(scores, rel, topk: int):
    """[Tq, K] bool: for each query the ``min(rel + 1, topk)`` keys of
    largest score among positions 0 .. rel, exactly ``lax.top_k``'s set
    (of equal scores the lower position first), with no sort: the
    ``topk``-th largest score by a search over the bits of its float32
    (its upper half, then among the keys that share it its lower half:
    32 counting passes over 16-bit arrays), then among the keys equal to
    it the lowest positions by a search over the bits of a position.
    ``scores`` [Tq, K] f32 (anything at positions past ``rel``), ``rel``
    [Tq] i32 (a query's own position; below 0: nothing selected)."""
    Tq, K = scores.shape
    k = jnp.int32(topk)
    pos = jnp.arange(K, dtype=jnp.int32)[None, :]
    vis = pos <= rel[:, None]
    key = jnp.where(vis, _sortable(scores), jnp.uint32(0))

    def count(pred):
        return jnp.sum(pred, axis=1, dtype=jnp.int32)

    def largest(half, floor):
        """The largest 16-bit value v with ``floor + count(half >= v) >=
        k`` (0 where none but 0 has), bit by bit from the top."""
        def bit(i, thr):
            cand = thr | (jnp.uint16(1) << (jnp.uint16(15)
                                            - i.astype(jnp.uint16)))
            return jnp.where(floor + count(half >= cand[:, None]) >= k,
                             cand, thr)
        return jax.lax.fori_loop(0, 16, bit, jnp.zeros((Tq,), jnp.uint16))

    zero = jnp.zeros((Tq,), jnp.int32)
    hi = (key >> 16).astype(jnp.uint16)
    thr_hi = largest(hi, zero)
    # among the keys whose upper half is the threshold's, the lower half
    # (the others read 0 and are reached by no candidate, which are >= 1)
    lo = jnp.where(hi == thr_hi[:, None], key.astype(jnp.uint16),
                   jnp.uint16(0))
    thr_lo = largest(lo, count(hi > thr_hi[:, None]))
    # the largest value that at least k keys reach (0: fewer than k do)
    thr = (thr_hi.astype(jnp.uint32) << 16) | thr_lo.astype(jnp.uint32)
    above = key > thr[:, None]
    equal = key == thr[:, None]
    need = k - count(above)                                # >= 1
    bits = max(1, int(K - 1).bit_length())

    def place_bit(i, cut):
        # the smallest position with `need` equal keys at or below it:
        # the largest cut with fewer than `need` below it
        cand = cut | (jnp.int32(1) << (jnp.int32(bits - 1) - i))
        few = count(equal & (pos < cand[:, None])) < need
        return jnp.where(few, cand, cut)

    cut = jax.lax.fori_loop(0, bits, place_bit, zero)
    chosen = above | (equal & (pos <= cut[:, None]))
    return jnp.where((rel[:, None] < k), vis, chosen & vis)


def select_bias(scores, rel, topk: int):
    """``select_mask`` as the additive bias the attention takes: 0 at a
    selected key, -inf elsewhere, float32 [Tq, K]."""
    return jnp.where(select_mask(scores, rel, topk), 0.0,
                     -jnp.inf).astype(jnp.float32)


def _gather_rows(pool_layer, block_tables, seg, n_keys):
    """[Tq, n_keys, width]: each token's row's cached latents, densely."""
    bs = pool_layer.shape[1]
    pages = block_tables[seg]                          # [Tq, nblk]
    k = pool_layer[pages]                              # [Tq, nblk, bs, w]
    return k.reshape(k.shape[0], -1, k.shape[-1])[:, :n_keys]


def mla_ragged_reference_segrel(q, pool_layer, block_tables, seg, rel, *,
                                latent_dim: int, sm_scale: float,
                                window=None, select=None):
    """The XLA oracle of the kernel, absorbed form, from per-token (seg,
    rel) as ``paged_attention.ragged_segments`` gives them: every token
    gathers its row's pages densely and masks keys past ``rel`` (with
    ``window``: and those below ``rel - window + 1``; with ``select``:
    and adds that bias).  A padded token (seg == R) resolves to the
    table's last row and gives a finite row the caller discards."""
    dc = int(latent_dim)
    nblk = block_tables.shape[1]
    bs = pool_layer.shape[1]
    segc = jnp.minimum(seg, block_tables.shape[0] - 1)
    k = _gather_rows(pool_layer, block_tables, segc, nblk * bs)
    s = jnp.einsum("tgw,tkw->tgk", q.astype(jnp.float32),
                   k[..., :q.shape[-1]].astype(jnp.float32)) * sm_scale
    keypos = jnp.arange(nblk * bs, dtype=jnp.int32)
    see = keypos[None, :] <= rel[:, None]
    if window is not None:
        see &= keypos[None, :] > rel[:, None] - window
    s = jnp.where(see[:, None, :], s, -jnp.inf)
    if select is not None:
        # a token that selected nothing (padding) keeps a finite row
        s = jnp.where(jnp.any(select == 0, axis=1)[:, None, None],
                      s + select[:, None, :], s)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("tgk,tkc->tgc", p, k[..., :dc].astype(jnp.float32))
    return out.astype(q.dtype)


def mla_ragged_reference(q, pool_layer, block_tables, cu_seqlens, kv_lens,
                         *, latent_dim: int, sm_scale: float, window=None,
                         select=None):
    """``mla_ragged_reference_segrel`` from the row layout; rows of no
    keys and padded tokens read zero, as the kernel's do."""
    Tq = q.shape[0]
    seg, rel = _pa.ragged_segments(cu_seqlens, kv_lens, Tq)
    R = kv_lens.shape[0]
    live = (seg < R) & (kv_lens[jnp.minimum(seg, R - 1)] > 0)
    out = mla_ragged_reference_segrel(
        q, pool_layer, block_tables, seg, jnp.where(live, rel, 0),
        latent_dim=latent_dim, sm_scale=sm_scale, window=window,
        select=select)
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
               dtype=jnp.bfloat16, *, launch=None, index_dim=None):
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
    if index_dim is not None and (index_dim % 128 or heads % 8):
        return (f"index key of {index_dim} is not a multiple of 128, or "
                f"{heads} heads do not fill whole sublane tiles a token")
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
