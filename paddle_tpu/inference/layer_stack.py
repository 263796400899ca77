"""The forward of a serving step as ONE function of what each layer is.

A step program embeds its flat tokens, runs the model's layers and
scores the logit rows: ``forward``, for every model the engine serves,
every page type and both drivers (the ragged step and the decode
window's loop, ``inference/serving.py``).  ``layer_stack`` is the middle
of it: each layer is (attention kind, FFN kind), and the function is
told how each kind computes, reads the paged cache and writes it.  A
model whose layers are all alike with their weights stacked (the dense
decoder) runs as one ``lax.scan`` over the stack; a model
whose layers differ, or whose weights are too large to hold a second,
stacked copy of (a latent-attention decoder with a leading dense layer
and expert layers after it), runs its layers one after another over the
model's own arrays.

Attention kinds: ``gqa`` (rotary grouped-query attention over K and V
pages ``[L, num_blocks, Hkv, bs, D]``; float pages, or int8 pages with
their two scale pools, by what it is handed) and, for a model that mixes
them (``models/smallthinker.py``), ``gqa_nope`` (``gqa`` without rotary
positions: a global layer) and ``gqa_window`` (rotary, a query sees the
``c.window`` positions up to its own), and ``gqa_gated`` /
``gqa_gated_window`` (those two with the attention output times
``sigmoid(h W_g)`` before ``W_o``: ``models/laguna.py``).  A
grouped-query kind reads ITS number of query heads and ITS rotary from
``c.attn[kind]``: the kinds of one model may differ in both (48 heads
half-rotated with YaRN frequencies on the global layers, 64 heads wholly
rotated on the window layers).  The LATENT kinds are one body
(``_latent``: latent attention in the absorbed form over a pool of ONE
cached row a token, ``[L, num_blocks, bs, width]``) told by
``c.attn[kind]`` what its layers have: their heads, ranks and head
sizes, a full or a low-rank query, their rotary and softmax scale, a
window, a headwise gate, an indexer.  A kind is then a name for the
POOLS its layers keep (``c.slots[kind]``): ``mla`` (one pool for all
layers: ``models/mla_moe.py``), ``mla_select`` (the latents and, beside
them under the same table and page ids, the keys of an indexer whose
scores pick the ``topk`` keys each query attends to) and ``mla_window``
(the window layers' latents, of a row width of their own:
``models/dots3.py``).  A model with window layers has TWO sets of pools
and two block tables: the global layers' ``[Lg, num_blocks, ...]`` under
``c.bt`` and the window layers' ``[Lw, Nw, ...]`` under ``c.btw``, where
a sequence's pages below its window have gone back to the pool.  One
contract for all, scanned or unrolled: a layer gets the pools of ALL
layers and its index among the layers that share its pools, writes the
step's rows in place at (layer, page, slot) and its kernel reads pages
where they lie at a prefetched layer index.  How the rows get there is
the kind's and the page type's: the dense decoder's grouped-query
layers over float pages move whole pages where the kernels run
(``_commit_float``: the page writer ``kv_page_write``) and scatter rows
elsewhere, in an unrolled model and over int8 pages (``_set_rows``); a
latent kind scatters its one row a token (``_put_rows``).  No layer-sized slice of a
pool is ever made.  A STATE-SPACE HYBRID (``models/phi4flash.py``) has
six kinds of its own: ``ssm`` / ``ssm_keep`` (a Mamba-1 layer, whose
state a sequence lives beside the pages by batch slot; the second also
leaves its scan output in the step's MEMORY), ``gmu`` (a gated unit on
that memory), and differential attention as one body (``_diff``) under a
window (``diff_window``), over all (``diff``) and over ANOTHER kind's
pages with no keys or values of its own (``diff_cross``).  Its stack is
runs of a PERIOD of kinds whose weights the model holds stacked: a
segment may be a period, one scan whose turn is the period's layers in
order.  What norm a model's layers have is the step context's to say
(``c.norm``: RMSNorm with a learned scale unless told).
FFN kinds: ``swiglu``, ``moe`` (routed experts held here plus
shared experts: ``models/mla_moe.py``) and ``moe_reglu`` (ReGLU experts
whose router reads ``h``, the attention block's input, not ``h2``).

The ``jax.named_scope`` names below are what a device trace is read by
(docs/observability.md): ``embed``, ``layers``, ``head``, ``norm``,
``qkv``/``q_proj``/``kv_latent``, ``rope``, ``kv_write``, ``attn_index``
and ``attn_select`` (an indexer's scores and its selection), ``attn``
(``attn_window`` on a window layer's, ``attn_cross`` on a layer's that
reads another's pages), ``attn_diff``, ``attn_gate``, ``o_proj``,
``ssm_proj``, ``ssm_conv``, ``ssm_scan``, ``gmu``, ``mlp``,
and for expert layers ``router``, ``moe_dispatch``, ``moe_experts``, ``moe_combine``,
``shared_expert``.
"""
from __future__ import annotations

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
from jax import lax

from ..models import dots3 as _d3
from ..models import mla_moe as _mm
from ..models import phi4flash as _ph
from ..models import smallthinker as _st
from ..models.llama import _rms_weight
from ..ops.pallas import kv_page_write as _kw
from ..ops.pallas import mla_attention as _mla
from ..ops.pallas import paged_attention as _pa
from ..ops.pallas import selective_scan as _ss


def scan_layers(body, x, layers, pools):
    """``lax.scan`` over the stacked layers with the stacked pools (K and
    V pages; over int8 pages their scales too) in the CARRY: each turn
    gives ``body(x, p, pools, l) -> (x, pools)`` its layer's weights,
    the WHOLE pools and the layer index, which is what an unrolled
    segment's layers get.  The kinds write and read the pools where they
    lie, at ``l`` (a page writer or a row scatter, and the kernel: both
    take the carried buffers as they are): a turn that sliced its
    layer's pages out and wrote
    them back cost four layer-sized copies of each pool a layer (three
    quarters of a dense step's device time on the v5e).  Scanned through
    as inputs and outputs the pools came back in a new buffer, and
    aliasing it to the donated input cost a copy of each whole pool a
    step (3.2 ms a GB) that XLA makes up, so that no scope names it in a
    trace."""
    n = jax.tree_util.tree_leaves(layers)[0].shape[0]

    def turn(carry, inp):
        (x, pools), (p, l) = carry, inp
        x, pools = body(x, p, pools, l)
        return (x, tuple(pools)), None

    (x, pools), _ = lax.scan(turn, (x, tuple(pools)),
                             (layers, jnp.arange(n, dtype=jnp.int32)))
    return x, pools


def step_context(**kw) -> SimpleNamespace:
    """What every layer of one step program shares: the row layout
    (``Tq``, ``seg``, ``rel``, ``bt``, ``cu``, ``kvl``, ``bs``), the
    products (``mm``, and ``embed`` and ``head_logits`` for ``forward``),
    the model's sizes (for the grouped-query kinds ``attn``: {kind: its
    query heads ``nh`` and its rotary ``rope(x, pos)``, None for a kind
    without positions}), whether the kernel runs (``use_pallas``) and,
    over int8 pages, ``fresh`` ([num_blocks] bool: the pages whose scale
    rows a layer's commit resets; None where the caller already did)."""
    return SimpleNamespace(**kw)


# ---------------------------------------------------------------------------
# attention kinds: (x, h, p, pools, layer, c) -> (x, pools), over the
# pools of ALL layers, written and read in place at ``layer``
# ---------------------------------------------------------------------------

# ``gqa`` over either page type: commit(k, v, pools, layer, bt, c) ->
# pools writes the step's rows into the pools of all layers at ``layer``,
# in the pages the table ``bt`` names; attend(q, pools, layer, c) ->
# [Tq, heads, d] reads that layer's pages where they lie.

def _row_at(c, bt):
    """(page [Tq], slot [Tq]) of the step's rows under table ``bt``."""
    return bt[c.seg, c.rel // c.bs], c.rel % c.bs


def _set_rows(pool, at, rows):
    """``rows`` [Tq, Hkv, D] into ``pool`` [L, num_blocks, Hkv, bs, D] at
    (layer, page [Tq], slot [Tq]): every page axis is indexed (layer,
    page, head, slot, folded into the row's number in the pool seen as
    ``[L * num_blocks * Hkv * bs, D]``, a bitcast), so the update window
    is the contiguous minor ``D`` and XLA scatters Tq * Hkv rows into
    the donated buffer in place: 0.22 ms a layer for K and V at 192
    tokens and 8 heads on the v5e, 0.04 at 32 (PERF.md, PR 31).  Who
    still calls it: the commit over float pages where the kernels do not
    run (``c.use_pallas`` false: off the chip, or a shape they do not
    claim) or the layers are unrolled, and the commit over int8 pages;
    the dense decoder's scanned programs write float pages a page at a
    time (``_commit_float``).
    ``pool.at[layer, page, :, slot]`` writes the same values, but its
    window [Hkv, D] straddles the slot axis: XLA then keeps the WHOLE
    pool in a slot-major layout through the layer loop and copies all
    of it back to row-major for the kernel's custom call in every layer
    (tests/test_chip_lowering.py)."""
    layer, blk, slot = at
    _, nb, hkv, bs, d = pool.shape
    heads = jnp.arange(hkv, dtype=jnp.int32)
    row = ((layer * nb + blk[:, None]) * hkv + heads[None, :]) * bs \
        + slot[:, None]                                   # [Tq, Hkv]
    return pool.reshape(-1, d).at[row].set(
        rows.astype(pool.dtype)).reshape(pool.shape)


def _commit_float(k, v, pools, layer, bt, c):
    """In the dense decoder's scanned step programs where the kernels
    run, the page writer (``ops/pallas/kv_page_write.py``: ONE launch
    named ``kv_page_write`` for K and V, which reads the row layout
    itself and moves whole pages, in place); elsewhere the row scatter.
    An unrolled model keeps the scatter on the chip too: a process
    traces the writer once a kind of layer in every token bucket's
    program, 0.23 s a time on the v5e's host, and SmallThinker's ten
    buckets of two kinds put 5 to 6 s on a set-up of 45 (PERF.md, PR 46);
    one scanned segment traces it once a bucket."""
    kc, vc = pools
    if c.use_pallas and c.scanned:
        return tuple(_kw.kv_page_write(k, v, kc, vc, bt, c.cu, c.kvl,
                                       layer))
    at = (layer, *_row_at(c, bt))
    return _set_rows(kc, at, k), _set_rows(vc, at, v)


def _attend_float(q, pools, layer, c, bt=None, window=None, sm_scale=None,
                  name=None):
    """``bt`` and ``window``: a window layer's table and its width (the
    dense decoder passes neither); ``sm_scale`` and ``name``: a softmax
    scale and a kernel name of the caller's own (``_diff``)."""
    bt = c.bt if bt is None else bt
    if c.use_pallas:
        # the host packing path owns these buffers: bt is the int32
        # NULL_BLOCK-padded pool table and cu, kvl come int32 from
        # the step's packing, so the packed entry skips the
        # per-launch re-clip and re-cast.  The kernel reads the row
        # layout itself; seg/rel are for rope and kv_write
        return _pa.ragged_paged_attention_packed(
            q, *pools, bt, c.cu, c.kvl, layer=layer, window=window,
            sm_scale=sm_scale, name=name)
    return _pa.ragged_paged_reference_segrel(
        q, *(pool[layer] for pool in pools), bt, c.seg, c.rel,
        window=window, sm_scale=sm_scale)


def _commit_int8(k, v, pools, layer, bt, c):
    """Quantize at commit, per layer, per launch:
    1. zero the scale rows of ``fresh`` pages (pages BlockManager handed
       out since the last launch: their old content AND old scales are
       dead; CoW destinations are excluded — the CoW program copied
       their scale rows with their data);
    2. scatter-max each touched page's scale with the incoming tokens'
       per-head amax/127 (scales only grow while a page is live, so
       previously committed int8 values never overflow);
    3. re-encode the touched pages' existing int8 content from the old
       scale to the grown scale (one extra rounding per growth event —
       the accepted precision cost of page-granular scales);
    4. quantize the new tokens at the settled scale and scatter them
       into their slots.
    Duplicate page indices across tokens are safe throughout: the
    scatter-max makes every duplicate observe the same settled scale,
    so duplicate re-encodes write identical bytes.  Under tp the scale
    pools slice along the same H_kv axis as the page pools, so all of
    this stays per-head-local.  The page pools are touched in place: a
    gather of the launch's pages, a scatter of whole pages back and the
    scatter of the rows; the layer's scale rows (a word a page and head)
    are taken out and put back."""
    kc, vc, ks, vs = pools
    blk, slot = _row_at(c, bt)
    at = (layer, blk, slot)
    ksl, vsl = ks[layer], vs[layer]                       # [num_blocks, kvh]
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if c.fresh is not None:
        ksl = jnp.where(c.fresh[:, None], 0.0, ksl)
        vsl = jnp.where(c.fresh[:, None], 0.0, vsl)
    ks_old = ksl[blk]                                     # [Tq, kvh]
    vs_old = vsl[blk]
    ksl = ksl.at[blk].max(jnp.max(jnp.abs(kf), axis=-1) / 127.0)
    vsl = vsl.at[blk].max(jnp.max(jnp.abs(vf), axis=-1) / 127.0)
    ks_new = ksl[blk]
    vs_new = vsl[blk]
    rk = jnp.where(ks_new > 0.0, ks_old / jnp.maximum(ks_new, 1e-30), 0.0)
    rv = jnp.where(vs_new > 0.0, vs_old / jnp.maximum(vs_new, 1e-30), 0.0)
    kp = jnp.round(kc[layer, blk].astype(jnp.float32) * rk[:, :, None, None])
    vp = jnp.round(vc[layer, blk].astype(jnp.float32) * rv[:, :, None, None])
    kc = kc.at[layer, blk].set(jnp.clip(kp, -127, 127).astype(jnp.int8))
    vc = vc.at[layer, blk].set(jnp.clip(vp, -127, 127).astype(jnp.int8))
    kq = jnp.round(kf / jnp.maximum(ks_new, 1e-30)[:, :, None])
    vq = jnp.round(vf / jnp.maximum(vs_new, 1e-30)[:, :, None])
    return (_set_rows(kc, at, jnp.clip(kq, -127, 127)),
            _set_rows(vc, at, jnp.clip(vq, -127, 127)),
            ks.at[layer].set(ksl), vs.at[layer].set(vsl))


def _attend_int8(q, pools, layer, c):
    if c.use_pallas:
        # packed-entry invariant as over float pages; the scale pools
        # are born f32 on the host
        att = _pa.ragged_paged_attention_quant_packed(q, *pools, c.bt,
                                                      c.cu, c.kvl,
                                                      layer=layer)
    else:
        att = _pa.ragged_paged_reference_quant_segrel(
            q, *(pool[layer] for pool in pools), c.bt, c.seg, c.rel)
    return att.astype(q.dtype)


# the grouped-query kinds: (a window layer, a gated output)
_GQA = {"gqa": (False, False), "gqa_nope": (False, False),
        "gqa_window": (True, False), "gqa_gated": (False, True),
        "gqa_gated_window": (True, True)}
WINDOW_KINDS = tuple(k for k, (window, _) in _GQA.items() if window) \
    + ("mla_window", "diff_window")


def _gqa(x, h, p, pools, layer, c, kind="gqa"):
    """Grouped-query attention over this layer's pages of the pools of
    all layers.  What differs between page types is a pair, picked by
    what the layer is handed: commit the step's K/V rows into the pools
    at (layer, page, slot), and attend over the layer's pages.

    A model of global and window layers (``c.window`` set) hands every
    layer both pairs of pools, (K, V of the global layers, K, V of the
    window layers): a layer writes and reads its own pair under its own
    table and passes the other through.  ``kind`` says whether it is a
    window layer, whose attention runs in scope ``attn_window`` under a
    kernel name of its own, and whether its output is gated; how many
    query heads the kind has and how it rotates q and k (not at all: a
    kind without positions) is the model's to say, ``c.attn[kind]``."""
    window, gated = _GQA[kind]
    nh, rope = c.attn[kind].nh, c.attn[kind].rope
    others = ()
    bt, scope, over = c.bt, "attn", {}
    if getattr(c, "window", None) is not None:
        if window:
            others, pools = pools[:2], pools[2:]
            bt, scope = c.btw, "attn_window"
            over = {"bt": bt, "window": c.window}
        else:
            pools, others = pools[:2], pools[2:]
    commit, attend = (_commit_int8, _attend_int8) \
        if pools[0].dtype == jnp.int8 else (_commit_float, _attend_float)
    Tq, kvh, d, tp, mm = c.Tq, c.kvh, c.d, c.tp, c.mm
    with jax.named_scope("qkv"):
        q = mm(h, p, "wq").reshape(Tq, nh, d)
        k = mm(h, p, "wk").reshape(Tq, kvh, d)
        v = mm(h, p, "wv").reshape(Tq, kvh, d)
    if rope is not None:
        with jax.named_scope("rope"):
            q = rope(q, c.rel)
            k = rope(k, c.rel)
    with jax.named_scope("kv_write"):
        pools = commit(k, v, pools, layer, bt, c)
    with jax.named_scope(scope):
        att = attend(q, pools, layer, c, **over)
        if tp > 1:
            # tiled gather concatenates shard head blocks in mesh order
            # — exactly the tp=1 head layout, so the replicated wo
            # matmul is byte-identical
            att = lax.all_gather(att, "tp", axis=1, tiled=True)
    att = att.reshape(Tq, tp * nh * d)
    if gated:
        with jax.named_scope("attn_gate"):
            g = jax.nn.sigmoid(mm(h, p, "wg").astype(jnp.float32))
            att = (att.astype(jnp.float32) * g).astype(att.dtype)
    with jax.named_scope("o_proj"):
        x = x + mm(att, p, "wo")
    if window:
        return x, tuple(others) + tuple(pools)
    return x, tuple(pools) + tuple(others)


# the latent kinds: ONE body, told by ``c.attn[kind]`` what its layer has
# (a low-rank query, a window, a headwise gate, an indexer); a kind is a
# name for the pools its layers keep
LATENT_KINDS = ("mla", "mla_select", "mla_window")


def _put_rows(pool, layer, blk, slot, rows):
    """``rows`` [Tq, n] into ``pool`` [L, pages, bs, width >= n] at
    (layer, page, slot), the columns past n zero."""
    rows = jnp.pad(rows, ((0, 0), (0, pool.shape[-1] - rows.shape[-1])))
    return pool.at[layer, blk, slot, :].set(rows.astype(pool.dtype))


def _latent(x, h, p, pools, layer, c, kind="mla"):
    """Latent attention, absorbed form, over this layer's pool of the
    pools of all layers: the launch's rows ``[c | k_rope | 0...]`` are
    written in place at (layer, page, slot) and the kernel reads pages
    where they lie.  ``c.attn[kind]`` is the kind's sizes and what its
    layers have besides (``models/mla_moe.py``, ``models/dots3.py``
    ``attention_by_kind``); ``c.slots[kind]`` the places of ITS pools
    among ``pools`` (the others pass through):

    - a window (``a.window``): the pool is the window layers', under the
      window table ``c.btw``; scope ``attn_window``, a kernel name of
      its own;
    - an indexer (``a.index``): a second pool beside the latents', the
      index keys ``[L, num_blocks, bs, d]`` under the same table and the
      same page ids.  The index heads score every key of a query's row
      (scope ``attn_index``), each query keeps its ``topk`` largest
      (``attn_select``: ``mla_attention.select_bias``, the exact set of
      a top-k, by counting), and attention runs over those alone;
    - a headwise gate (``a.gated``): each head's output times
      ``sigmoid(h W_g)`` before ``W_o`` (``attn_gate``)."""
    a = c.attn[kind]
    slots = c.slots[kind]
    mine = [pools[i] for i in slots]
    bt, scope = (c.bt, "attn") if a.window is None \
        else (c.btw, "attn_window")
    q, row, c_q = _mm.mla_project(h, p, a, c.rel)
    select = None
    if a.index is not None:
        with jax.named_scope("attn_index"):
            qi, ki, wi = _d3.index_project(h, c_q, p, a.index, c.rel)
    with jax.named_scope("kv_write"):
        blk, slot = _row_at(c, bt)
        mine[0] = _put_rows(mine[0], layer, blk, slot, row)
        if a.index is not None:
            mine[1] = _put_rows(mine[1], layer, blk, slot, ki)
    if a.index is not None:
        live = c.seg < c.kvl.shape[0]
        with jax.named_scope("attn_index"):
            if c.use_pallas:
                scores = _mla.ragged_index_scores_packed(
                    qi, wi, mine[1], layer, bt, c.cu, c.kvl)
            else:
                scores = _mla.index_scores_reference_segrel(
                    qi, wi, mine[1][layer], bt, c.seg)
        with jax.named_scope("attn_select"):
            select = _mla.select_bias(scores, jnp.where(live, c.rel, -1),
                                      a.index.topk)
    with jax.named_scope(scope):
        if c.use_pallas:
            lat = _mla.ragged_latent_attention_packed(
                q, mine[0], layer, bt, c.cu, c.kvl, latent_dim=a.dc,
                sm_scale=a.sm_scale, window=a.window, select=select)
        else:
            lat = _mla.mla_ragged_reference_segrel(
                q, mine[0][layer], bt, c.seg, c.rel, latent_dim=a.dc,
                sm_scale=a.sm_scale, window=a.window, select=select)
    gate = None
    if a.gated:
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid((h @ p["wg"]).astype(jnp.float32))
    with jax.named_scope("o_proj"):
        x = x + _mm.mla_output(lat, p, a, gate)
    pools = list(pools)
    for i, pool in zip(slots, mine):
        pools[i] = pool
    return x, tuple(pools)


# ---------------------------------------------------------------------------
# the kinds of a state-space hybrid (``models/phi4flash.py``).  Their
# pools, in this order: K and V of the layer whose pages live under the
# block table, K and V of the window layers, then what a SEQUENCE keeps
# beside its pages, by batch slot: the convolution's last inputs
# ``[Ls, slots, taps - 1, d_inner]`` and the scan's state
# ``[Ls, slots, d_state, d_inner]`` (float32), and last the step's
# MEMORY ``[Tq, d_inner]``, which no engine holds: ``forward`` makes it,
# the ``ssm_keep`` layer writes it, the ``gmu`` layers read it and it
# ends with the step.
# ---------------------------------------------------------------------------

STATE_KINDS = ("ssm", "ssm_keep")
# kinds that keep no rows of their own under either table
POOLLESS_KINDS = STATE_KINDS + ("gmu", "diff_cross")
CROSS_KERNEL_NAME = "ragged_paged_attention_cross"
_KV, _KVW, _CONV, _STATE, _MEMORY = slice(0, 2), slice(2, 4), 4, 5, 6


def _replaced(pools, at, new):
    pools = list(pools)
    pools[at] = new
    return tuple(pools)


def _conv_rows(u, p, tails, c, taps):
    """The causal convolution over the launch's rows.  ``u`` [Tq, di]
    its inputs; ``tails`` [R, taps - 1, di] what each row's sequence
    fed it last before this launch (zero where the row begins it).
    Returns the convolved rows [Tq, di] float32 and each row's tails
    after the launch."""
    Tq = u.shape[0]
    R, k = tails.shape[0], taps - 1
    seg = jnp.minimum(c.seg, R - 1)
    off = jnp.arange(Tq, dtype=jnp.int32) - c.cu[seg]     # place in its row
    w = p["conv_w"].astype(jnp.float32)
    uf, tf = u.astype(jnp.float32), tails.astype(jnp.float32)
    out = w[k] * uf + p["conv_b"].astype(jnp.float32)
    for back in range(1, taps):
        # the input ``back`` rows before: of this launch, or of the tail
        here = jnp.pad(uf, ((back, 0), (0, 0)))[:Tq]
        before = tf[seg, jnp.clip(k + off - back, 0, k - 1)]
        out += w[k - back] * jnp.where((off >= back)[:, None], here, before)
    # a row's last inputs now: tails ++ its rows, the last taps - 1
    n_q = (c.cu[1:] - c.cu[:-1])[:R]
    at = n_q[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]   # [R, k]
    new = u[jnp.clip(c.cu[:R, None] + at - k, 0, Tq - 1)]
    old = jnp.take_along_axis(tails, jnp.clip(at, 0, k - 1)[:, :, None],
                              axis=1)
    return out, jnp.where((at >= k)[:, :, None], new.astype(tails.dtype),
                          old)


def _tail_rows(pool, layer, slots):
    """The rows of ``pool`` [Ls, slots, k, di], seen as [Ls * slots * k,
    di], that hold (layer, slots [R])'s tails: [R, k].  As ``_set_rows``
    does, every axis but the minor one is folded into a row's number,
    so a gather or a scatter moves contiguous ``di``-wide rows of the
    donated buffer where it lies."""
    _, n, k, _ = pool.shape
    return (layer * n + slots[:, None]) * k \
        + jnp.arange(k, dtype=jnp.int32)[None, :]


def _ssm(x, h, p, pools, layer, c, kind="ssm"):
    """A Mamba-1 layer over the launch's rows: a row that begins its
    sequence (``c.state_start``) starts from zeros, one that continues
    it reads its slot (``c.state_slot``: the row's batch slot, or for a
    row of no tokens the slot nobody holds); every row writes back what
    its last token left.  ``ssm_keep`` also leaves its scan output in
    the step's memory."""
    a, mm = c.attn[kind], c.mm
    conv, state = pools[_CONV], pools[_STATE]
    slots, start = c.state_slot, c.state_start
    with jax.named_scope("ssm_proj"):
        uz = mm(h, p, "w_in")
        u, z = uz[:, :a.di], uz[:, a.di:]
    with jax.named_scope("ssm_conv"):
        rows = _tail_rows(conv, layer, slots)
        flat = conv.reshape(-1, a.di)
        tails = jnp.where(start[:, None, None], 0, flat[rows])
        u, tails = _conv_rows(u, p, tails, c, a.taps)
        u = jax.nn.silu(u).astype(h.dtype)
        conv = flat.at[rows].set(tails.astype(conv.dtype)) \
            .reshape(conv.shape)
    with jax.named_scope("ssm_proj"):
        delta, A, Bm, Cm = _ph.ssm_maps(u, p, a, mm)
    with jax.named_scope("ssm_scan"):
        y, state = _ss.selective_scan(
            u, delta, A, Bm, Cm, p["D"], state, layer, slots, c.cu, start,
            use_kernel=c.use_pallas)
    pools = _replaced(_replaced(pools, _CONV, conv), _STATE, state)
    if a.keep:
        pools = _replaced(pools, _MEMORY, y.astype(pools[_MEMORY].dtype))
    with jax.named_scope("ssm_proj"):
        gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype)
        x = x + mm(gated, p, "w_out")
    return x, pools


def _gmu(x, h, p, pools, layer, c, kind="gmu"):
    """A gated memory unit: the step's memory (the ``ssm_keep`` layer's
    scan output for these rows) gated by this layer's own projection."""
    mm = c.mm
    with jax.named_scope("gmu"):
        g = jax.nn.silu(mm(h, p, "w_in").astype(jnp.float32))
        gated = (pools[_MEMORY].astype(jnp.float32) * g).astype(h.dtype)
        x = x + mm(gated, p, "w_out")
    return x, pools


def _diff(x, h, p, pools, layer, c, kind="diff"):
    """Differential attention, window, full or cross by ``c.attn[kind]``:
    two softmax maps a head pair over a value pair, subtracted and
    normed.  K and V rows are cached as ``kvh`` heads of ``d`` = two
    heads side by side, and a query head is widened with zeros on the
    half it does not see: the ragged kernel as it is then returns both
    maps' outputs at the pair's width (the zeros double the score
    product's work and change no sum).  A layer with ``a.reads`` computes
    no keys or values: it attends over the pages of the ``a.reads``
    kind's ONE layer, under a kernel name of its own."""
    a, mm = c.attn[kind], c.mm
    Tq = c.Tq
    cross = a.reads is not None
    mine, bt, scope, over = _KV, c.bt, "attn", {}
    if a.window is not None:
        mine, bt, scope = _KVW, c.btw, "attn_window"
        over = {"bt": bt, "window": a.window}
    elif cross:
        scope, over = "attn_cross", {"name": CROSS_KERNEL_NAME}
    with jax.named_scope("qkv"):
        if cross:
            q = mm(h, p, "wq") + p["bq"]
        else:
            qkv = mm(h, p, "wqkv") + p["bqkv"]
            q = qkv[:, :a.nh * a.hd]
            k, v = (qkv[:, a.nh * a.hd:].reshape(Tq, 2, a.kvh, a.d)
                    .transpose(1, 0, 2, 3))
        q = _ph.widen(q.reshape(Tq, a.nh, a.hd))
    if not cross:
        with jax.named_scope("kv_write"):
            pools = list(pools)
            pools[mine] = _commit_float(k, v, pools[mine], layer, bt, c)
            pools = tuple(pools)
    with jax.named_scope(scope):
        # (the kind a cross layer reads keeps ONE layer: index 0)
        att = _attend_float(q, pools[mine], 0 if cross else layer, c,
                            sm_scale=a.hd ** -0.5, **over)
    with jax.named_scope("attn_diff"):
        l0 = _ph.lambda_init(a.depth0 + a.stride * layer)
        att = _ph.diff_combine(att, p, l0, a.eps)
    with jax.named_scope("o_proj"):
        x = x + mm(att, p, "wo") + p["bo"]
    return x, pools


# ---------------------------------------------------------------------------
# FFN kinds: (x, h, h2, p, c) -> (x, what an expert layer counted or
# None); h is the attention block's input, h2 the FFN's own
# ---------------------------------------------------------------------------

def _swiglu(x, h, h2, p, c):
    mm = c.mm
    with jax.named_scope("mlp"):
        a = jax.nn.silu(mm(h2, p, "gate").astype(jnp.float32)
                        ).astype(h2.dtype) * mm(h2, p, "up")
        x = x + mm(a, p, "down")
    return x, None


def _moe(x, h, h2, p, c):
    out, counts = _mm.moe_ffn(h2, p, c.cfg, valid=c.seg < c.kvl.shape[0],
                              use_kernel=c.use_pallas)
    with jax.named_scope("moe_combine"):
        return x + out, counts


def _moe_reglu(x, h, h2, p, c):
    """ReGLU experts routed by ``h``, the pre-attention norm's output."""
    out, counts = _st.moe_ffn(h, h2, p, c.cfg,
                              valid=c.seg < c.kvl.shape[0],
                              use_kernel=c.use_pallas)
    with jax.named_scope("moe_combine"):
        return x + out, counts


def _norm(x, p, name, c):
    """A layer's (or the model's last) norm: RMSNorm with a learned
    scale, or what the model hands the step context (``c.norm(x, p,
    name)``: a LayerNorm with weight and bias, ``models/phi4flash.py``)."""
    norm = getattr(c, "norm", None)
    if norm is not None:
        return norm(x, p, name)
    return _rms_weight(x, p[name], c.eps)


ATTENTION = {**{kind: functools.partial(_latent, kind=kind)
                for kind in LATENT_KINDS},
             **{kind: functools.partial(_gqa, kind=kind) for kind in _GQA},
             **{kind: functools.partial(_diff, kind=kind)
                for kind in ("diff", "diff_window", "diff_cross")}}
# what stands in attention's place in a layer that has none, same
# signature: a state-space layer, a gated memory unit
MIXERS = {**{kind: functools.partial(_ssm, kind=kind)
             for kind in STATE_KINDS},
          "gmu": _gmu}
FFN = {"swiglu": _swiglu, "moe": _moe, "moe_reglu": _moe_reglu}


def _unrolled(one, x, layers, pools):
    """Layers of one kind and shape, one after another, through ``one``,
    the kind's ONE traced copy (``layer_stack`` keeps it)."""
    counted = []
    for index, p in layers:
        x, pools, counts = one(x, p, pools, jnp.int32(index))
        if counts is not None:
            counted.append(counts)
    return x, pools, counted


def layer_stack(x, segments, pools, c):
    """Run the layers.  ``segments``: [((attention kind, FFN kind),
    layers, scanned)].  Either way a layer's kinds get its weights, the
    whole pools and the layer's index, and write and read the pools in
    place at that index.  A scanned segment's ``layers`` is a pytree
    with a leading layer axis, one ``lax.scan`` with the pools in its
    carry; an unrolled segment's is [(layer index, that layer's
    weights)]: its layers, alike in kind and shape, are traced and
    lowered ONCE and called with the index as an operand (traced layer
    by layer, six layers of two Pallas kernels each took 8.7 s a token
    bucket of every process start on the v5e's host, compile cache or
    not).  The traced copy is the KIND's, an inner jit built while the
    step program is traced and gone with that trace: segments that
    alternate between two kinds (global, window x 3, global, window x 3)
    trace two layers, not four.  Returns (x, pools, counts): what the
    expert layers counted, summed over layers (the largest load: the
    largest), int32 [4], or None without expert layers."""
    pools = tuple(pools)
    counted = []
    def layer_of(kind):
        attention = ATTENTION.get(kind[0]) or MIXERS[kind[0]]
        feed = FFN[kind[1]]

        def layer(x, p, pools, index):
            with jax.named_scope("norm"):
                h = _norm(x, p, "ln1", c)
            x, pools = attention(x, h, p, pools, index, c)
            with jax.named_scope("norm"):
                h2 = _norm(x, p, "ln2", c)
            x, counts = feed(x, h, h2, p, c)
            return x, pools, counts
        return layer

    @functools.cache
    def traced_layer(kind):
        """The kind's one inner jit."""
        return jax.jit(layer_of(kind))

    for kind, layers, scanned in segments:
        if scanned and isinstance(kind[0], tuple):
            # a PERIOD of kinds, each kind's weights stacked over the
            # repeats: one scan, a turn a repeat, whose number is each
            # of its layers' index
            period = [layer_of(k) for k in kind]

            def turn(x, ps, pools, l, period=period):
                for layer, p in zip(period, ps):
                    x, pools, _ = layer(x, p, pools, l)
                return x, pools
            x, pools = scan_layers(turn, x, layers, pools)
        elif scanned:
            layer = layer_of(kind)
            x, pools = scan_layers(lambda *a: layer(*a)[:2], x, layers,
                                   pools)
        else:
            x, pools, counts = _unrolled(traced_layer(kind), x, layers,
                                         pools)
            counted += counts
    if not counted:
        return x, pools, None
    all_ = jnp.stack(counted)                              # [layers, 4]
    return x, pools, jnp.concatenate([jnp.sum(all_[:, :3], axis=0),
                                      jnp.max(all_[:, 3:], axis=0)])


def forward(params, toks, pools, c, lidx=None):
    """One step's forward for every driver: embed the flat tokens, run
    the layers, norm, and score the logit rows (``lidx``: the flat index
    of each; None where every row is its own, as in the decode window).
    ``c.kinds`` is the model's (attention kind, FFN kind) a layer: the
    dense decoder (``c.scanned``) is one scanned segment over its
    stacked weights; otherwise runs of layers of one kind, each over its
    own arrays.  A layer's index into its pools is its place in the
    model, or ``c.pool_index[i]`` where kinds keep pools of their own.
    Returns (logits, pools, counts)."""
    with jax.named_scope("embed"):
        x = c.embed(params, toks)                             # [Tq, H]
    memory = getattr(c, "memory", None)
    if memory is not None:
        # what one layer of the step leaves for later ones (its width,
        # its type): a value of the step's own, carried with the pools
        pools = tuple(pools) + (jnp.zeros((x.shape[0], memory[0]),
                                          memory[1]),)
    if c.scanned:
        segments = [(c.kinds[0], params["layers"], True)]
    elif getattr(c, "periods", None) is not None:
        # runs of a period whose weights the model holds stacked, and
        # single layers between them (``config.periods()``)
        segments = [(kinds, ps, True) if n > 1
                    else (kinds[0], [(index, ps[0])], False)
                    for (kinds, n, index), ps in zip(c.periods,
                                                     params["layers"])]
    else:
        segments = []
        for i, k in enumerate(c.kinds):
            if not segments or segments[-1][0] != k:
                segments.append((k, [], False))
            at = i if getattr(c, "pool_index", None) is None \
                else c.pool_index[i]
            segments[-1][1].append((at, params["layers"][i]))
    with jax.named_scope("layers"):
        x, pools, counts = layer_stack(x, segments, pools, c)
    if memory is not None:
        pools = pools[:-1]
    with jax.named_scope("norm"):
        h = _norm(x, params, "norm_f", c)
    with jax.named_scope("head"):
        if lidx is not None:
            h = h[lidx]                                       # [Lq, H]
        logits = c.head_logits(params, h)                     # [Lq, V]
        if c.shard_head:
            # vocab-sliced logits -> one gather; sampling then runs
            # replicated on identical full-width rows
            logits = lax.all_gather(logits, "tp", axis=1, tiled=True)
    return logits, pools, counts
