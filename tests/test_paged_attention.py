"""Pallas paged-KV decode kernel + blha mixed batches.

Kernel numerics are pinned against the dense-gather XLA composition
(the pre-r5 decode path), reference
block_multi_head_attention_kernel.cu / block_attn.h semantics.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.ops.pallas.paged_attention as pa
from paddle_tpu.incubate.nn import functional as IF


@pytest.fixture(autouse=True)
def _interpret():
    old = pa.INTERPRET
    pa.INTERPRET = True
    yield
    pa.INTERPRET = old


@pytest.mark.parametrize("H,Hkv,D,bs,nblk", [
    (8, 4, 64, 16, 5),     # GQA
    (4, 4, 64, 8, 3),      # MHA
    (10, 5, 128, 16, 4),   # the d128 GQA lever layout
])
def test_paged_decode_kernel_matches_dense(H, Hkv, D, bs, nblk):
    rng = np.random.RandomState(0)
    B, num_blocks = 3, 64
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    kc = jnp.asarray(rng.randn(num_blocks, Hkv, bs, D), jnp.float32)
    vc = jnp.asarray(rng.randn(num_blocks, Hkv, bs, D), jnp.float32)
    bt = jnp.asarray(rng.choice(num_blocks, B * nblk,
                                replace=False).reshape(B, nblk), jnp.int32)
    max_len = nblk * bs
    lengths = jnp.asarray(rng.randint(1, max_len + 1, B), jnp.int32)
    out = pa.paged_decode_attention(q, kc, vc, bt, lengths)
    ref = pa.paged_decode_reference(q, kc, vc, bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _mk_caches(rng, num_blocks, H, bs, D):
    kc = paddle.to_tensor(rng.randn(num_blocks, H, bs, D).astype(np.float32))
    vc = paddle.to_tensor(rng.randn(num_blocks, H, bs, D).astype(np.float32))
    return kc, vc


def test_blha_decode_pallas_path_matches_dense():
    """The flag-gated pallas decode inside block_multihead_attention must
    reproduce the dense-gather path bit-for-bit at f32 tolerance."""
    rng = np.random.RandomState(1)
    B, H, D, bs, nblk = 2, 4, 64, 8, 3
    num_blocks = 16
    dec = np.array([5, 9])              # tokens already cached
    qkv = paddle.to_tensor(rng.randn(B, 3 * H * D).astype(np.float32))
    bt = paddle.to_tensor(
        rng.choice(num_blocks, B * nblk, replace=False)
        .reshape(B, nblk).astype(np.int32))

    outs = {}
    for flag in (False, True):
        paddle.set_flags({"use_pallas_kernels": flag})
        kc, vc = _mk_caches(np.random.RandomState(2), num_blocks, H, bs, D)
        out, _, kc2, vc2 = IF.block_multihead_attention(
            qkv, kc, vc,
            seq_lens_encoder=np.zeros(B, np.int32),
            seq_lens_decoder=dec.astype(np.int32),
            seq_lens_this_time=np.ones(B, np.int32),
            block_tables=bt, block_size=bs)
        outs[flag] = (out.numpy(), kc2.numpy(), vc2.numpy())
    paddle.set_flags({"use_pallas_kernels": True})
    np.testing.assert_allclose(outs[True][0], outs[False][0], atol=2e-5)
    np.testing.assert_allclose(outs[True][1], outs[False][1])
    np.testing.assert_allclose(outs[True][2], outs[False][2])


def test_blha_mixed_prefill_decode_batch():
    """Mixed continuous-batching step: seq0 prefills 6 tokens, seq1
    decodes its 4th token.  Outputs must match running the two pure-mode
    calls separately, in original token order."""
    rng = np.random.RandomState(3)
    H, D, bs, nblk = 4, 64, 8, 3
    num_blocks = 16
    n_pre, dec_len = 6, 3
    tok = n_pre + 1
    qkv = rng.randn(tok, 3 * H * D).astype(np.float32)
    bt = rng.choice(num_blocks, 2 * nblk, replace=False) \
        .reshape(2, nblk).astype(np.int32)
    kc0 = rng.randn(num_blocks, H, bs, D).astype(np.float32)
    vc0 = rng.randn(num_blocks, H, bs, D).astype(np.float32)

    # mixed call
    out_m, _, kc_m, vc_m = IF.block_multihead_attention(
        paddle.to_tensor(qkv), paddle.to_tensor(kc0.copy()),
        paddle.to_tensor(vc0.copy()),
        seq_lens_encoder=np.array([n_pre, 0], np.int32),
        seq_lens_decoder=np.array([0, dec_len], np.int32),
        seq_lens_this_time=np.array([n_pre, 1], np.int32),
        block_tables=paddle.to_tensor(bt), block_size=bs)

    # separate pure calls (prefill seq0, then decode seq1 over the
    # prefill-updated caches)
    out_p, _, kc_p, vc_p = IF.block_multihead_attention(
        paddle.to_tensor(qkv[:n_pre]), paddle.to_tensor(kc0.copy()),
        paddle.to_tensor(vc0.copy()),
        seq_lens_encoder=np.array([n_pre], np.int32),
        seq_lens_decoder=np.array([0], np.int32),
        seq_lens_this_time=np.array([n_pre], np.int32),
        block_tables=paddle.to_tensor(bt[:1]), block_size=bs)
    out_d, _, kc_d, vc_d = IF.block_multihead_attention(
        paddle.to_tensor(qkv[n_pre:]), kc_p, vc_p,
        seq_lens_encoder=np.array([0], np.int32),
        seq_lens_decoder=np.array([dec_len], np.int32),
        seq_lens_this_time=np.array([1], np.int32),
        block_tables=paddle.to_tensor(bt[1:]), block_size=bs)

    np.testing.assert_allclose(out_m.numpy()[:n_pre], out_p.numpy(),
                               atol=1e-5)
    np.testing.assert_allclose(out_m.numpy()[n_pre:], out_d.numpy(),
                               atol=1e-5)
    np.testing.assert_allclose(kc_m.numpy(), kc_d.numpy(), atol=1e-6)
    np.testing.assert_allclose(vc_m.numpy(), vc_d.numpy(), atol=1e-6)


def test_paged_decode_minus_one_padded_block_tables():
    """Reference blha convention pads block_tables with -1 past each
    sequence's allocated pages; the kernel must not read a negative HBM
    offset (entries are clamped; compute is masked by length anyway)."""
    rng = np.random.RandomState(7)
    B, H, Hkv, D, bs, nblk = 2, 4, 4, 64, 8, 4
    num_blocks = 16
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
    kc = jnp.asarray(rng.randn(num_blocks, Hkv, bs, D), jnp.float32)
    vc = jnp.asarray(rng.randn(num_blocks, Hkv, bs, D), jnp.float32)
    bt = np.full((B, nblk), -1, np.int32)
    bt[0, :2] = [3, 7]
    bt[1, :1] = [5]
    lengths = jnp.asarray([11, 6], jnp.int32)
    out = pa.paged_decode_attention(q, kc, vc, jnp.asarray(bt), lengths)
    # oracle over only the VALID pages
    bt_valid = np.where(bt < 0, 0, bt)
    ref = pa.paged_decode_reference(q, kc, vc, jnp.asarray(bt_valid),
                                    lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_blha_decode_pallas_mixed_dtype_cache():
    """bf16 KV cache + f32 qkv must work on the pallas path (q joins the
    cache dtype; the probe compiles that combination)."""
    rng = np.random.RandomState(8)
    B, H, D, bs, nblk = 2, 4, 64, 8, 3
    num_blocks = 16
    dec = np.array([5, 9])
    qkv = paddle.to_tensor(rng.randn(B, 3 * H * D).astype(np.float32))
    bt = paddle.to_tensor(
        rng.choice(num_blocks, B * nblk, replace=False)
        .reshape(B, nblk).astype(np.int32))
    paddle.set_flags({"use_pallas_kernels": True})
    kc = paddle.to_tensor(
        jnp.asarray(rng.randn(num_blocks, H, bs, D), jnp.bfloat16))
    vc = paddle.to_tensor(
        jnp.asarray(rng.randn(num_blocks, H, bs, D), jnp.bfloat16))
    out, _, kc2, vc2 = IF.block_multihead_attention(
        qkv, kc, vc,
        seq_lens_encoder=np.zeros(B, np.int32),
        seq_lens_decoder=dec.astype(np.int32),
        seq_lens_this_time=np.ones(B, np.int32),
        block_tables=bt, block_size=bs)
    assert np.isfinite(out.numpy()).all()
    assert "bfloat16" in str(kc2._data.dtype)


def test_blha_prefill_varlen_pallas_matches_dense():
    """The prefill path riding the varlen flash kernel must match the
    segment-masked dense composition."""
    import paddle_tpu.ops.pallas.flash_attention as fa
    rng = np.random.RandomState(9)
    H, D, bs, nblk = 4, 64, 8, 4
    num_blocks = 16
    lens = np.array([6, 3], np.int32)
    tok = int(lens.sum())
    qkv = rng.randn(tok, 3 * H * D).astype(np.float32)
    bt = rng.choice(num_blocks, 2 * nblk, replace=False) \
        .reshape(2, nblk).astype(np.int32)
    kc0 = rng.randn(num_blocks, H, bs, D).astype(np.float32)
    vc0 = rng.randn(num_blocks, H, bs, D).astype(np.float32)

    outs = {}
    old = fa.INTERPRET
    try:
        for flag, interp in ((False, False), (True, True)):
            fa.INTERPRET = interp     # varlen eligibility honors _fa.INTERPRET
            paddle.set_flags({"use_pallas_kernels": flag})
            out, _, kc2, vc2 = IF.block_multihead_attention(
                paddle.to_tensor(qkv), paddle.to_tensor(kc0.copy()),
                paddle.to_tensor(vc0.copy()),
                seq_lens_encoder=lens, seq_lens_decoder=np.zeros(2, np.int32),
                seq_lens_this_time=lens,
                block_tables=paddle.to_tensor(bt), block_size=bs)
            outs[flag] = (out.numpy(), kc2.numpy())
    finally:
        fa.INTERPRET = old
        paddle.set_flags({"use_pallas_kernels": True})
    np.testing.assert_allclose(outs[True][0], outs[False][0],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(outs[True][1], outs[False][1])


# ---------------------------------------------------------------------------
# ragged serving kernel: edge geometries vs the XLA gather oracle.
# Prefill chunks, resumed chunks, decode tokens and k-draft verify rows
# are all just rows with different query_lens: each geometry must match
# the dense-gather reference on every valid token, at both group sizes
# the benchmark's configurations have, and padding must read zero.
# The tile is forced small (8 score rows, 2 pages a block) so that these
# small cases cross q-tile and K/V-block boundaries as a real launch does.
# ---------------------------------------------------------------------------

BS, NBLK = 8, 6                          # 48 keys a row at most


@pytest.fixture()
def small_tiles(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TUNE_FORCE", json.dumps(
        {"paged_attention": {"q_tile_rows": 8, "kv_pages": 2}}))


def _ragged_case(rng, query_lens, kv_lens, Tq, *, H=4, Hkv=4, D=64, bs=BS,
                 nblk=NBLK, num_blocks=64, contiguous=True, rows=None):
    R = len(query_lens)
    rows = rows or R
    q = jnp.asarray(rng.randn(Tq, H, D), jnp.float32)
    kc = jnp.asarray(rng.randn(num_blocks, Hkv, bs, D), jnp.float32)
    vc = jnp.asarray(rng.randn(num_blocks, Hkv, bs, D), jnp.float32)
    if contiguous:
        picks = 1 + np.arange(rows * nblk).reshape(rows, nblk)
    else:
        picks = 1 + rng.choice(num_blocks - 1, rows * nblk,
                               replace=False).reshape(rows, nblk)
    bt = jnp.asarray(picks, jnp.int32)
    cu = np.full(rows + 1, int(np.sum(query_lens)), np.int32)
    cu[:R + 1] = np.concatenate([[0], np.cumsum(query_lens)])
    kvl = np.zeros(rows, np.int32)
    kvl[:R] = kv_lens
    return q, kc, vc, bt, jnp.asarray(cu), jnp.asarray(kvl)


def _check_ragged(q, kc, vc, bt, cu, kvl, atol=2e-5):
    out = np.asarray(pa.ragged_paged_attention(q, kc, vc, bt, cu, kvl))
    ref = np.asarray(pa.ragged_paged_reference(q, kc, vc, bt, cu, kvl))
    total = int(np.asarray(cu)[-1])
    # a query of a row of no keys (a frozen row of the decode window) is
    # NaN in the oracle's softmax; the kernel gives it no work
    keyed = np.isfinite(ref[:total]).all(axis=(1, 2))
    np.testing.assert_allclose(out[:total][keyed], ref[:total][keyed],
                               atol=atol)
    assert not out[:total][~keyed].any()
    assert not out[total:].any()         # padding: no work, zeros
    return out


# name: (query_lens, kv_lens, Tq, keyword arguments of _ragged_case)
GEOMETRIES = {
    # pure decode: every query_len is 1
    "all_decode": ([1] * 4, [1, 9, 17, 48], 4, dict(contiguous=False)),
    # a fresh chunk of 7 tokens: with tiles of 2 (G=4) or 1 (G=8) tokens
    # it crosses three q-tile boundaries, and starts inside a tile
    "chunk_crosses_q_tiles": ([1, 7], [5, 7], 8, {}),
    # a resumed chunk of 5 behind 40 cached tokens: its tiles walk three
    # K/V blocks, the last of them partly
    "resumed_behind_prefix": ([5, 1], [45, 3], 8, {}),
    # a verify row of k + 1 = 4 drafts next to decode rows
    "verify_row": ([1, 4, 1], [12, 21, 30], 8, dict(contiguous=False)),
    # one prefill owning every flat token and every page: cache full
    "one_row_owns_bucket": ([48], [48], 48, dict(num_blocks=8)),
    # 7 real tokens, 9 of padding, and table rows no row uses
    "empty_padded_tail": ([3, 4], [19, 11], 16, dict(rows=4)),
    # kv_len on a page boundary, one past it, and at nblk * bs
    "page_boundaries": ([1, 1, 2, 1], [8, 9, 16, 48], 8, {}),
    # scattered pages, all four row kinds in one launch: prefill chunk
    # (5), decode (1), verify (4), resumed chunk (3) at a deep offset
    "noncontiguous_mixed": ([5, 1, 4, 3], [5, 9, 17, 26], 16,
                            dict(contiguous=False)),
    # a row of no queries between live rows, and a row of no keys
    "empty_rows_between": ([2, 0, 1, 1], [10, 0, 0, 20], 4, {}),
}


@pytest.mark.parametrize("G", [4, 8])
@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_ragged_kernel_matches_reference(small_tiles, name, G):
    qlens, kvl, Tq, kw = GEOMETRIES[name]
    rng = np.random.RandomState(20)
    args = _ragged_case(rng, qlens, kvl, Tq, H=2 * G, Hkv=2, **kw)
    out = _check_ragged(*args)
    if name == "all_decode":
        # also the dedicated decode oracle, at each row's own position
        q, kc, vc, bt, _, kvl_j = args
        dec = pa.paged_decode_reference(q, kc, vc, bt, kvl_j)
        np.testing.assert_allclose(out, np.asarray(dec), atol=2e-5)


@pytest.mark.parametrize("tiles", [(128, 8), (16, 1), (8, 4)])
def test_ragged_kernel_at_other_tiles(monkeypatch, tiles):
    """The mixed launch at the built-in tile (one item a row: the whole
    case fits a tile and a block) and at two more widths."""
    monkeypatch.setenv("PADDLE_TPU_TUNE_FORCE", json.dumps(
        {"paged_attention": {"q_tile_rows": tiles[0],
                             "kv_pages": tiles[1]}}))
    qlens, kvl, Tq, kw = GEOMETRIES["noncontiguous_mixed"]
    _check_ragged(*_ragged_case(np.random.RandomState(23), qlens, kvl, Tq,
                                H=8, Hkv=4, **kw))


@pytest.mark.parametrize("G", [4, 8])
def test_ragged_kernel_reads_no_page_past_a_rows_length(small_tiles, G):
    """The mechanism: no copy and no arithmetic for a page at or past a
    row's ceil(kv_len / bs), for a padded token or for the null page.
    Every pool page that no row reaches within its kv_len is filled with
    NaN, page 0 (the null page the table's spare entries name) too: the
    live rows' output is finite and equal to the clean run's, the padded
    rows' output is zero."""
    rng = np.random.RandomState(24)
    qlens, kvl = [5, 1, 4, 1], [13, 9, 48, 0]
    q, kc, vc, bt, cu, kvl_j = _ragged_case(
        rng, qlens, kvl, 16, H=2 * G, Hkv=2, contiguous=False, rows=6)
    btn = np.asarray(bt).copy()
    reached = set()
    for r, n in enumerate(kvl):
        live = -(-n // BS)
        reached.update(btn[r, :live].tolist())
        btn[r, live:] = 0                # as the engine pads: null page
    dead = np.array(sorted(set(range(kc.shape[0])) - reached))
    assert 0 in dead and len(dead) > 40
    clean = np.asarray(pa.ragged_paged_attention(
        q, kc, vc, jnp.asarray(btn), cu, kvl_j))
    kcn = kc.at[dead].set(jnp.nan)
    vcn = vc.at[dead].set(jnp.nan)
    out = np.asarray(pa.ragged_paged_attention(
        q, kcn, vcn, jnp.asarray(btn), cu, kvl_j))
    total = sum(qlens)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[:total - 1], clean[:total - 1])
    assert not out[total - 1:].any()     # the row of no keys, and padding


def _int8_case(rng, G):
    """All four row kinds over int8 pages of 32 keys (the int8 tile's
    height), with each page's scale for each head."""
    Hkv, D, bs, nblk, nb = 2, 64, 32, 3, 16
    qlens, kvl, Tq = [5, 1, 4, 3], [5, 40, 90, 96], 16
    q = jnp.asarray(rng.randn(Tq, Hkv * G, D), jnp.float32)
    kc = jnp.asarray(rng.randint(-127, 128, (nb, Hkv, bs, D)), jnp.int8)
    vc = jnp.asarray(rng.randint(-127, 128, (nb, Hkv, bs, D)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.5, 1.5, (nb, Hkv)) / 127, jnp.float32)
    vs = jnp.asarray(rng.uniform(0.5, 1.5, (nb, Hkv)) / 127, jnp.float32)
    bt = jnp.asarray(1 + rng.choice(nb - 1, 4 * nblk, replace=False)
                     .reshape(4, nblk), jnp.int32)
    cu = jnp.asarray(np.concatenate([[0], np.cumsum(qlens)]), jnp.int32)
    return q, kc, vc, ks, vs, bt, cu, jnp.asarray(kvl, jnp.int32)


@pytest.mark.parametrize("G", [4, 8])
def test_ragged_int8_page_kernel_matches_reference(small_tiles, G):
    """Int8 pages take the same body with a dequantising load: all four
    row kinds against the fake-quant oracle (float = int8 * the page's
    scale for the head), pages of 32 keys as the int8 tile wants."""
    q, kc, vc, ks, vs, bt, cu, kvl = _int8_case(np.random.RandomState(25), G)
    Tq = q.shape[0]
    out = np.asarray(pa.ragged_paged_attention_quant(
        q, kc, vc, ks, vs, bt, cu, kvl))
    seg, rel = pa.ragged_segments(cu, kvl, Tq)
    ref = np.asarray(pa.ragged_paged_reference_quant_segrel(
        q, kc, vc, ks, vs, bt, seg, rel))
    np.testing.assert_allclose(out[:13], ref[:13], atol=2e-5)
    assert not out[13:].any()


def _among_layers(pool, layer, n_layers, other):
    """``pool`` as layer ``layer`` of ``n_layers``, every other layer
    filled with ``other``."""
    stacked = jnp.full((n_layers,) + pool.shape, other, pool.dtype)
    return stacked.at[layer].set(pool)


@pytest.mark.parametrize("G", [4, 8])
@pytest.mark.parametrize("name", ["all_decode", "noncontiguous_mixed",
                                  "empty_rows_between", "int8_pages"])
def test_launch_over_the_pools_of_all_layers_reads_its_layer(small_tiles,
                                                             name, G):
    """What a step program launches: the pools of all layers and a
    layer index, prefetched.  The result is, bit for bit, the launch
    over that layer's slice, and no other layer is read: the others
    hold NaN (over int8 pages, whose bytes cannot, their scales do and
    their pages are all 127)."""
    rng = np.random.RandomState(26)
    L, layer = 3, 1
    if name == "int8_pages":
        q, kc, vc, ks, vs, *rows = _int8_case(rng, G)
        one = pa.ragged_paged_attention_quant_packed(q, kc, vc, ks, vs,
                                                     *rows)
        out = pa.ragged_paged_attention_quant_packed(
            q, *(_among_layers(p, layer, L, 127) for p in (kc, vc)),
            *(_among_layers(s, layer, L, jnp.nan) for s in (ks, vs)),
            *rows, layer=jnp.int32(layer))
    else:
        qlens, kvl, Tq, kw = GEOMETRIES[name]
        q, kc, vc, *rows = _ragged_case(rng, qlens, kvl, Tq, H=2 * G,
                                        Hkv=2, **kw)
        one = pa.ragged_paged_attention_packed(q, kc, vc, *rows)
        out = pa.ragged_paged_attention_packed(
            q, *(_among_layers(p, layer, L, jnp.nan) for p in (kc, vc)),
            *rows, layer=jnp.int32(layer))
    assert np.isfinite(np.asarray(one)).all() and np.asarray(one).any()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(one))
