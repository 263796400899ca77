"""Pallas page writer (TPU): a launch's new K and V rows into the paged
pools, a PAGE at a time, in place.

The XLA composition (``inference/layer_stack._set_rows``) scatters one
row a (token, head) into the pool seen as rows of ``D``: on the v5e 72 ns
a 256-byte row, because a bf16 row is half of each 32-bit word of a
packed ``(16, 128)`` tile and every row written is a read-modify-write of
words it shares with its neighbour slot.  A page of one layer,
``[Hkv, bs, D]``, is contiguous in the pool and each head of it is whole
tiles; the ragged attention kernel already moves pages that way.  So the
writer does too: it walks the launch's ROWS (``cu_seqlens``, ``kv_lens``
and the block table, scalar-prefetched exactly as the ragged launch takes
them), and for every page a row's new tokens touch it copies the page
from the pool into fast memory, puts the new rows in with a select on the
slot index (whole tiles only), and copies it back.  Several pages are in
flight at a time (``page_slots`` in the tuning cache), so a copy's
latency hides behind the pages before it.  A page the launch fills
wholly is read like any other: skipping it saved one copy of 32 KB in
five and cost every process two more branches to trace and lower in
every token bucket's program, and set-up time is an end-to-end metric.

The pools are ``pl.ANY`` operands aliased to the outputs: the donated
buffers are written where they lie, K and V in ONE call.  Padded tokens
(past ``cu[R]``) and rows of no keys (a frozen row of the decode window)
are not written at all: the null page, where the scatter put them, is
left alone.  No page is touched by two rows of one launch (a written page
is private to its sequence: copy-on-write ran before the step), so the
copies of different pages do not race.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _pa

KERNEL_NAME = "kv_page_write"

# page slots in fast memory (a K and a V page each).  A page's read is
# started half of them ahead of the page being merged, and a slot is read
# into again ``page_slots`` pages after it was written back, so the
# write-back it waits for then is the other half old
_DEFAULTS = {"page_slots": 8}


def _slots(Tq, Hkv, D, bs, dtype) -> int:
    """Page slots from the tuning cache, at least 2."""
    from ...tune import kernel_config
    cfg = kernel_config("kv_page_write",
                        {"tq": Tq, "kv_heads": Hkv, "head_dim": D,
                         "page": bs, "dtype": jnp.dtype(dtype).name},
                        defaults=_DEFAULTS)
    return max(2, int(cfg["page_slots"]))


def _writer_kernel(cu_ref, kvl_ref, bt_ref, layer_ref, k_ref, v_ref,
                   k_in, v_in, k_hbm, v_hbm, kbuf, vbuf, sems, blk_s, t0_s,
                   lo_s, hi_s, *, rows, bs, pad):
    """cu [R+1], kv_lens [R], the block table and the layer index in
    scalar memory; ``k_ref``, ``v_ref`` [Hkv, pad + Tq + bs + 8.., D]
    float32 (head-major, ``pad`` >= bs - 1 rows of anything in front
    and a page and a tile behind, so that the tiles that hold a page's
    ``bs`` rows are there wherever the page starts);
    ``k_in``, ``v_in`` the pools [L, num_blocks, Hkv, bs, D], which
    ``k_hbm``, ``v_hbm`` (the outputs) alias.  Few operations, none
    unrolled over tokens, heads or pages: every process traces and
    lowers this body once a token bucket and kind of layer, compile
    cache or not, and set-up time is an end-to-end metric."""
    del k_in, v_in
    slots = kbuf.shape[0]
    ahead = slots // 2
    layer = layer_ref[0]
    div, rem = jax.lax.div, jax.lax.rem

    # the work list: one item a (row, page the row's new tokens touch)
    def row(r, n):
        qs = cu_ref[r]
        end = kvl_ref[r]
        base = end - (cu_ref[r + 1] - qs)        # position of token qs
        start = jax.lax.max(base, 0)             # (a frozen row: no keys)
        first = div(start, bs)

        def page(p, n):
            at = p * bs
            blk_s[n] = bt_ref[r, p]
            # the padded index of the token at the page's slot 0
            t0_s[n] = pad + qs + at - base
            lo_s[n] = jax.lax.max(start - at, 0)
            hi_s[n] = jax.lax.min(end - at, bs)
            return n + 1
        return jax.lax.fori_loop(
            first, jax.lax.select(end > start, div(end + (bs - 1), bs),
                                  first), page, n)

    n = jax.lax.fori_loop(0, rows, row, 0)

    def copies(blk, slot, read, act):
        for x, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
            page, here = hbm.at[layer, blk], buf.at[slot]
            act(pltpu.make_async_copy(
                *((page, here) if read else (here, page)),
                sems.at[x, slot]))

    def start(c):
        c.start()

    def wait(c):
        # (a wait reads the copy's size and its semaphore, not its
        # page: page 0 stands for whichever the slot's copy moves)
        c.wait()

    slot_of = jax.lax.broadcasted_iota(jnp.int32, (1, bs, 1), 1)

    # one loop, each copy named once: turn i frees the slot of page
    # j = i + ahead (the write-back of page j - slots) and starts j's
    # read, then merges page i and starts its write-back; the turns
    # before 0 only start reads, those from n on only wait for writes
    def turn(i, carry):
        j = i + ahead
        # (i + slots: the loop begins below 0, and rem keeps the sign)
        slot, ahead_slot = rem(i + slots, slots), rem(j, slots)

        @pl.when(j >= slots)
        def _free():
            copies(0, ahead_slot, False, wait)

        @pl.when(j < n)
        def _ahead():
            copies(blk_s[j], ahead_slot, True, start)

        @pl.when((i >= 0) & (i < n))
        def _merge():
            copies(0, slot, True, wait)
            mine = jax.lax.broadcast_in_dim(
                (slot_of >= lo_s[i]) & (slot_of < hi_s[i]), kbuf.shape[1:],
                (0, 1, 2))
            # a page's bs rows start at any token, a load at a whole
            # tile of 8: load the tiles that hold them and roll them up
            t0 = t0_s[i]
            at = pl.multiple_of(div(t0, 8) * 8, 8)
            up = rem(bs + 8 - rem(t0, 8), bs + 8)
            for src, buf in ((k_ref, kbuf), (v_ref, vbuf)):
                new = pltpu.roll(src[:, pl.ds(at, bs + 8), :], up, 1)[:, :bs]
                buf[slot] = jax.lax.select(
                    mine, new, buf[slot].astype(jnp.float32)
                ).astype(buf.dtype)
            copies(blk_s[i], slot, False, start)
        return carry

    jax.lax.fori_loop(-ahead, n + slots - ahead, turn, 0)


def kv_page_write(k, v, key_cache, value_cache, block_tables, cu_seqlens,
                  kv_lens, layer):
    """The launch's ``k``, ``v`` [Tq, Hkv, D] into the pools of ALL
    layers [L, num_blocks, Hkv, bs, D] at ``layer`` (an int32 scalar,
    traced or static), under the ragged launch's packed operands: int32
    ``cu_seqlens`` [R+1] non-decreasing with cu[R] <= Tq, ``kv_lens``
    [R] (valid keys a row AFTER this launch: a row's new tokens sit at
    its last positions) and a table whose entries for the pages written
    lie in [0, num_blocks).  Returns the two pools, which alias the
    operands: every page but those the rows' new tokens touch is what it
    was, and of those every slot but the new tokens'."""
    return _launch(k, v, key_cache, value_cache, block_tables, cu_seqlens,
                   kv_lens, jnp.asarray(layer, jnp.int32),
                   interpret=_pa.interpret_mode())


# a jit of its own: two kinds of layer of one step program that write the
# same pools (a model's dense first layer and its expert layers) then
# share ONE trace and ONE lowering of the kernel.  The tuning cache is
# read at trace time, here as in every step program: a sweep that changes
# it in one process calls ``_launch.clear_cache()`` between its settings
@functools.partial(jax.jit, static_argnames=("interpret",))
def _launch(k, v, key_cache, value_cache, block_tables, cu_seqlens, kv_lens,
            layer, *, interpret):
    Tq, Hkv, D = k.shape
    bs = key_cache.shape[3]
    slots = _slots(Tq, Hkv, D, bs, key_cache.dtype)
    rows = kv_lens.shape[0]
    # room for the tiles (8 float32 rows) that hold a page's bs rows,
    # wherever the page starts
    pad = -(-bs // 8) * 8
    total = -(-(pad + Tq + bs + 8) // 8) * 8

    def rows_of(x):
        # head-major, as a page holds them; bf16 -> float32 -> bf16 is
        # exact, and float32 tiles are 8 rows, not 16 packed
        return jax.lax.pad(x.transpose(1, 0, 2).astype(jnp.float32), 0.0,
                           ((0, 0, 0), (pad, total - Tq - pad, 0), (0, 0, 0)))

    items = -(-Tq // bs) + 2 * rows
    smem = pltpu.SMEM((items,), jnp.int32)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    need = 4 * total * Hkv * D * 4 \
        + 2 * slots * Hkv * bs * D * key_cache.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_writer_kernel, rows=rows, bs=bs, pad=pad),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # cu, kv_lens, block_tables and the layer index, as the
            # ragged launch prefetches them
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[vmem, vmem, hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[
                pltpu.VMEM((slots, Hkv, bs, D), key_cache.dtype),
                pltpu.VMEM((slots, Hkv, bs, D), value_cache.dtype),
                pltpu.SemaphoreType.DMA((2, slots)),
                smem, smem, smem, smem,
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(key_cache.shape, key_cache.dtype),
                   jax.ShapeDtypeStruct(value_cache.shape,
                                        value_cache.dtype)],
        # operands count the four prefetched scalars
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=min(100 << 20, max(16 << 20, 2 * need))),
        interpret=interpret,
        name=KERNEL_NAME,
    )(cu_seqlens, kv_lens, block_tables, layer.reshape(1), rows_of(k),
      rows_of(v), key_cache, value_cache)
