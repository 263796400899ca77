"""The ragged latent-attention kernel (ops/pallas/mla_attention.py)
against its XLA oracle, and the absorbed form against the expanded one.
Interpret mode: the kernel's row, block and page loops run as XLA loops
on the host, so the sizes are small."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import mla_attention as mla
from paddle_tpu.ops.pallas import paged_attention as pa

G, DC, DR, BS, L = 4, 128, 32, 8, 3
W = DC + DR
WP = mla.page_width(W)              # the stored row: 256
SCALE = 0.11


@pytest.fixture(autouse=True)
def _interpret():
    old = pa.INTERPRET
    pa.INTERPRET = True
    yield
    pa.INTERPRET = old


def _layout(rows, Tq, nblk, num_blocks, seed=0):
    """rows: [(n_q, kv_len)].  Returns cu, kvl, a table of distinct pages
    in a shuffled order (row R is the null row), and the live tokens."""
    rng = np.random.default_rng(seed)
    R = len(rows)
    cu = np.zeros(R + 1, np.int32)
    cu[1:] = np.cumsum([n for n, _ in rows])
    assert cu[-1] <= Tq
    kvl = np.asarray([k for _, k in rows], np.int32)
    free = list(rng.permutation(np.arange(1, num_blocks)))
    bt = np.zeros((R + 1, nblk), np.int32)
    for r, (_, k) in enumerate(rows):
        for p in range(-(-k // BS)):
            bt[r, p] = free.pop()
    return jnp.asarray(cu), jnp.asarray(kvl), jnp.asarray(bt)


def _pool(key, lead):
    """Random ``[c | k_rope]`` rows in their stored width: the columns
    past the rope key are zero, as the engine writes them."""
    rows = jax.random.normal(key, lead + (W,), jnp.float32)
    return jnp.pad(rows, [(0, 0)] * len(lead) + [(0, WP - W)])


CASES = {
    # a row of no keys and no queries between live rows; a one-token row;
    # a chunk that crosses a page boundary and resumes at a cached prefix
    "mixed": ([(5, 13), (0, 0), (1, 1), (1, 17), (9, 9), (3, 30)], 32),
    # every row a decode row
    "decode": ([(1, 7), (1, 8), (1, 9), (1, 24)], 8),
    # one chunk owns the bucket, its tail tile runs into padding
    "chunk": ([(21, 29)], 24),
    # a chunk, then decode rows inside its last tile's overhang
    "overhang": ([(10, 10), (1, 3), (1, 16), (2, 5)], 16),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("tiles", [(4, 2), (8, 1)])
def test_kernel_equals_oracle(case, tiles, monkeypatch):
    rows, Tq = CASES[case]
    monkeypatch.setenv(
        "PADDLE_TPU_TUNE_FORCE",
        '{"mla_attention": {"q_tile_tokens": %d, "kv_pages": %d}}' % tiles)
    nblk, num_blocks = 4, 24
    cu, kvl, bt = _layout(rows, Tq, nblk, num_blocks, seed=len(case))
    k0, k1 = jax.random.split(jax.random.PRNGKey(3))
    q = jax.random.normal(k0, (Tq, G, W), jnp.float32)
    pool = _pool(k1, (L, num_blocks, BS))
    want = mla.mla_ragged_reference(q, pool[1], bt, cu, kvl,
                                    latent_dim=DC, sm_scale=SCALE)
    got = mla.ragged_latent_attention_packed(
        q, pool, 1, bt, cu, kvl, latent_dim=DC, sm_scale=SCALE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # padded tokens and rows of no keys read zero
    live = np.zeros(Tq, bool)
    for r, (n, k) in enumerate(rows):
        if k > 0:
            live[int(cu[r]):int(cu[r]) + n] = True
    assert not np.asarray(got)[~live].any()


def test_layer_index_is_read():
    rows, Tq = CASES["decode"]
    cu, kvl, bt = _layout(rows, Tq, 4, 24)
    q = jax.random.normal(jax.random.PRNGKey(0), (Tq, G, W), jnp.float32)
    pool = _pool(jax.random.PRNGKey(1), (L, 24, BS))
    outs = [np.asarray(mla.ragged_latent_attention_packed(
        q, pool, jnp.int32(l), bt, cu, kvl, latent_dim=DC, sm_scale=SCALE))
        for l in range(L)]
    for l in range(L):
        want = mla.mla_ragged_reference(q, pool[l], bt, cu, kvl,
                                        latent_dim=DC, sm_scale=SCALE)
        np.testing.assert_allclose(outs[l], np.asarray(want), rtol=2e-5,
                                   atol=2e-5)
    assert np.abs(outs[0] - outs[2]).max() > 1e-2


def test_absorbed_equals_expanded():
    """q'_h = W_kvb,h^K^T q_nope_h against the cached latent, then
    W_kvb,h^V applied to the weighted latent sum, is the attention over
    per-head keys and values made from the latents: one function."""
    nope, vd = 16, 24
    rows, Tq = CASES["mixed"]
    cu, kvl, bt = _layout(rows, Tq, 4, 24)
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q_nope = jax.random.normal(ks[0], (Tq, G, nope), jnp.float32)
    q_rope = jax.random.normal(ks[1], (Tq, G, DR), jnp.float32)
    pool = _pool(ks[2], (24, BS))
    w_kvb = jax.random.normal(ks[3], (DC, G, nope + vd), jnp.float32) \
        / np.sqrt(DC)
    want = mla.mla_expanded_reference(q_nope, q_rope, pool, w_kvb, bt, cu,
                                      kvl, nope_dim=nope, sm_scale=SCALE)
    with jax.default_matmul_precision("highest"):
        q_abs = jnp.einsum("tgd,cgd->tgc", q_nope, w_kvb[..., :nope])
        lat = mla.mla_ragged_reference(
            jnp.concatenate([q_abs, q_rope], -1), pool, bt, cu, kvl,
            latent_dim=DC, sm_scale=SCALE)
        got = jnp.einsum("tgc,cgd->tgd", lat, w_kvb[..., nope:])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_ineligible_says_why():
    assert mla.page_width(576) == 640
    assert mla.ineligible(64, 640, 512, 16, jnp.bfloat16,
                          launch=(33, 1024, 16385)) is None
    assert "multiple of 128" in mla.ineligible(64, 576, 512, 16)
    assert "tile" in mla.ineligible(64, 640, 512, 8, jnp.bfloat16)
    assert "scalar memory" in mla.ineligible(
        64, 640, 512, 16, launch=(257, 1024, 16385))


# ---------------------------------------------------------------------------
# a window layer's walk, rows of another width, the indexer's scores and
# attention over selected keys (models/dots3.py)
# ---------------------------------------------------------------------------

def _force(monkeypatch, tiles, index=(8, 2)):
    monkeypatch.setenv(
        "PADDLE_TPU_TUNE_FORCE",
        '{"mla_attention": {"q_tile_tokens": %d, "kv_pages": %d}, '
        '"mla_index": {"q_tile_tokens": %d, "kv_pages": %d}}'
        % (tiles + index))


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("window", [1, 5, 9, 64])
def test_window_kernel_equals_oracle(case, window, monkeypatch):
    """A query sees its own position and the window - 1 before it: the
    walk starts at the page of the lowest key an item's first query
    sees, whatever the table holds below it."""
    rows, Tq = CASES[case]
    _force(monkeypatch, (4, 2))
    cu, kvl, bt = _layout(rows, Tq, 4, 24, seed=len(case))
    k0, k1 = jax.random.split(jax.random.PRNGKey(window))
    q = jax.random.normal(k0, (Tq, G, W), jnp.float32)
    pool = _pool(k1, (L, 24, BS))
    want = mla.mla_ragged_reference(q, pool[2], bt, cu, kvl, latent_dim=DC,
                                    sm_scale=SCALE, window=window)
    got = mla.ragged_latent_attention_packed(
        q, pool, 2, bt, cu, kvl, latent_dim=DC, sm_scale=SCALE,
        window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    if window == 64:            # wider than any row: the plain kernel's
        plain = mla.ragged_latent_attention_packed(
            q, pool, 2, bt, cu, kvl, latent_dim=DC, sm_scale=SCALE)
        np.testing.assert_allclose(np.asarray(got), np.asarray(plain),
                                   rtol=2e-5, atol=2e-5)
    # the pages below a row's window are not read: poison them
    first = np.asarray(kvl) - np.diff(np.asarray(cu))
    poisoned = np.asarray(pool).copy()
    for r, (n, k) in enumerate(rows):
        for p in range(max(0, int(first[r]) - window + 1) // BS):
            poisoned[2, int(bt[r, p])] = np.nan
    again = mla.ragged_latent_attention_packed(
        q, jnp.asarray(poisoned), 2, bt, cu, kvl, latent_dim=DC,
        sm_scale=SCALE, window=window)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_rows_stored_1152_wide_under_a_window(monkeypatch):
    """The sliding layers' row at the published sizes: a 1,024-wide
    latent and 64 rope numbers, 1,088 stored 1,152 wide."""
    dc, dr = 1024, 64
    wp = mla.page_width(dc + dr)
    assert wp == 1152 and mla.page_width(576) == 640
    assert mla.ineligible(64, wp, dc, 16) is None
    assert mla.ineligible(128, 640, 512, 16, index_dim=128) is None
    assert "index key of 48" in mla.ineligible(128, 640, 512, 16,
                                               index_dim=48)
    rows, Tq = [(6, 21), (1, 9)], 8
    _force(monkeypatch, (4, 2))
    cu, kvl, bt = _layout(rows, Tq, 4, 12)
    k0, k1 = jax.random.split(jax.random.PRNGKey(11))
    q = jax.random.normal(k0, (Tq, 2, dc + dr), jnp.float32)
    pool = jnp.pad(jax.random.normal(k1, (2, 12, BS, dc + dr), jnp.float32),
                   ((0, 0),) * 3 + ((0, wp - dc - dr),))
    want = mla.mla_ragged_reference(q, pool[1], bt, cu, kvl, latent_dim=dc,
                                    sm_scale=0.0625, window=7)
    got = mla.ragged_latent_attention_packed(
        q, pool, 1, bt, cu, kvl, latent_dim=dc, sm_scale=0.0625, window=7)
    assert got.shape == (Tq, 2, dc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def _index_inputs(Tq, num_blocks, heads=3, d=16, seed=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (Tq, heads, d), jnp.float32),
            jax.random.normal(ks[1], (Tq, heads), jnp.float32),
            jax.random.normal(ks[2], (L, num_blocks, BS, d), jnp.float32))


def _visible(cu, kvl, Tq, nblk):
    seg, rel = pa.ragged_segments(cu, kvl, Tq)
    rel = jnp.where(seg < kvl.shape[0], rel, -1)
    return rel, jnp.arange(nblk * BS)[None, :] <= rel[:, None]


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("index_tiles", [(8, 2), (16, 4)])
def test_index_score_kernel_equals_oracle(case, index_tiles, monkeypatch):
    """sum_j w[t, j] relu(q[t, j] . k[s]) for every key a query sees,
    from item-major tiles back in token order."""
    rows, Tq = CASES[case]
    _force(monkeypatch, (4, 2), index_tiles)
    cu, kvl, bt = _layout(rows, Tq, 4, 24, seed=len(case))
    qi, wi, ipool = _index_inputs(Tq, 24)
    seg, _ = pa.ragged_segments(cu, kvl, Tq)
    want = mla.index_scores_reference_segrel(qi, wi, ipool[1], bt, seg)
    got = mla.ragged_index_scores_packed(qi, wi, ipool, 1, bt, cu, kvl)
    _, vis = _visible(cu, kvl, Tq, 4)
    assert got.shape == (Tq, 4 * BS)
    np.testing.assert_allclose(np.asarray(jnp.where(vis, got, 0)),
                               np.asarray(jnp.where(vis, want, 0)),
                               rtol=2e-5, atol=2e-5)
    # by hand, one pair
    t = int(cu[0])
    k0 = ipool[1, int(bt[0, 0]), 0]
    by_hand = float(jnp.sum(wi[t] * jnp.maximum(qi[t] @ k0, 0)))
    assert float(got[t, 0]) == pytest.approx(by_hand, abs=1e-5)


def test_item_layout_gives_every_token_a_row_of_its_item():
    cu = jnp.asarray([0, 5, 5, 6, 7, 16, 19], jnp.int32)
    toff, slot, n = mla.item_layout(cu, 6, 24, 4)
    # tiles a row: 2, 1 (a row of no queries is one item), 1, 1, 3, 1
    assert toff.tolist() == [0, 2, 3, 4, 5, 8, 9]
    assert n == (24 // 4 + 6 + 1) * 4
    s = slot.tolist()
    assert s[:5] == [0, 1, 2, 3, 4]                 # row 0: items 0, 1
    assert s[5] == 3 * 4 and s[6] == 4 * 4          # rows 2, 3
    assert s[7:16] == [20, 21, 22, 23, 24, 25, 26, 27, 28]
    assert s[16:19] == [32, 33, 34]
    assert set(s[19:]) == {n - 1}                   # padding
    assert len(set(s[:19])) == 19


@pytest.mark.parametrize("topk", [1, 3, 6, 100])
def test_select_mask_is_the_literal_top_k(topk):
    """The set of ``lax.top_k`` over the keys a query sees, ties to the
    lower position, found by counting and not by sorting."""
    rng = np.random.default_rng(topk)
    scores = rng.normal(size=(9, 40)).astype(np.float32)
    scores[2, :] = 0.5                               # all equal
    scores[3, 5:20] = scores[3, 7]                   # a plateau
    scores[4, :] = -np.abs(scores[4])                # all negative
    scores[5, 3] = np.inf
    scores[6, :] = np.round(scores[6])               # many ties, +-0
    scores[6, ::3] = -0.0
    rel = jnp.asarray([39, 0, 39, 30, 17, 39, 39, -1, 5], jnp.int32)
    got = np.asarray(mla.select_mask(jnp.asarray(scores), rel, topk))
    for t in range(9):
        n = int(rel[t]) + 1
        if n <= 0:
            assert not got[t].any()
            continue
        _, idx = jax.lax.top_k(jnp.asarray(scores[t, :n]), min(topk, n))
        assert sorted(np.flatnonzero(got[t])) == sorted(np.asarray(idx)), t
    bias = np.asarray(mla.select_bias(jnp.asarray(scores), rel, topk))
    assert ((bias == 0) == got).all() and np.isneginf(bias[~got]).all()
    # scores that share the upper half of their float32 (the search's
    # second level decides), negative ones among them
    close = (1.0 + 1e-5 * rng.normal(size=(4, 300))).astype(np.float32)
    close[1] *= -1
    close[2, ::2] = close[2, 1]
    close[3] = rng.integers(-3, 3, 300) * np.float32(2.0 ** -130)
    rel_c = jnp.asarray([299, 299, 150, 299], jnp.int32)
    got_c = np.asarray(mla.select_mask(jnp.asarray(close), rel_c, topk))
    for t in range(4):
        n = int(rel_c[t]) + 1
        _, idx = jax.lax.top_k(jnp.asarray(close[t, :n]), min(topk, n))
        assert sorted(np.flatnonzero(got_c[t])) == sorted(np.asarray(idx)), t
    # garbage past a query's position is not read
    scores[:, 35:] = np.nan
    rel = jnp.minimum(rel, 30)
    again = np.asarray(mla.select_mask(jnp.asarray(scores), rel, topk))
    clean = np.asarray(mla.select_mask(
        jnp.asarray(np.nan_to_num(scores)), rel, topk))
    assert (again == clean).all()


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("topk", [2, 7])
def test_selected_keys_kernel_equals_oracle(case, topk, monkeypatch):
    """Attention over the keys each query selected, and no others: the
    bias rides in item-major tiles, a token's row on its G score rows;
    where a row is shorter than ``topk`` it is the plain kernel's."""
    rows, Tq = CASES[case]
    _force(monkeypatch, (8, 2))
    cu, kvl, bt = _layout(rows, Tq, 4, 24, seed=len(case))
    k0, k1 = jax.random.split(jax.random.PRNGKey(topk))
    q = jax.random.normal(k0, (Tq, 8, W), jnp.float32)
    pool = _pool(k1, (L, 24, BS))
    qi, wi, ipool = _index_inputs(Tq, 24)
    scores = mla.ragged_index_scores_packed(qi, wi, ipool, 0, bt, cu, kvl)
    rel, vis = _visible(cu, kvl, Tq, 4)
    bias = mla.select_bias(scores, rel, topk)
    kept = np.asarray(bias == 0)
    assert (kept.sum(1) == np.minimum(np.asarray(rel) + 1, topk)).all()
    want = mla.mla_ragged_reference(q, pool[0], bt, cu, kvl, latent_dim=DC,
                                    sm_scale=SCALE, select=bias)
    got = mla.ragged_latent_attention_packed(
        q, pool, 0, bt, cu, kvl, latent_dim=DC, sm_scale=SCALE, select=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # by hand: softmax over the selected keys alone
    t = int(cu[0])
    keys = np.flatnonzero(kept[t])
    rowk = np.stack([np.asarray(pool[0, int(bt[0, s // BS]), s % BS])
                     for s in keys])
    sc = np.asarray(q[t]) @ rowk[:, :W].T * SCALE
    p = np.exp(sc - sc.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    np.testing.assert_allclose(np.asarray(got[t]), p @ rowk[:, :DC],
                               rtol=1e-4, atol=1e-4)
    everything = mla.select_bias(scores, rel, 10_000)
    plain = mla.ragged_latent_attention_packed(
        q, pool, 0, bt, cu, kvl, latent_dim=DC, sm_scale=SCALE)
    both = mla.ragged_latent_attention_packed(
        q, pool, 0, bt, cu, kvl, latent_dim=DC, sm_scale=SCALE,
        select=everything)
    np.testing.assert_allclose(np.asarray(both), np.asarray(plain),
                               rtol=2e-5, atol=2e-5)


def test_the_new_launches_have_names_of_their_own():
    rows, Tq = CASES["decode"]
    cu, kvl, bt = _layout(rows, Tq, 4, 24)
    q = jnp.zeros((Tq, 8, W)); pool = jnp.zeros((L, 24, BS, WP))
    qi, wi, ipool = _index_inputs(Tq, 24)
    bias = jnp.zeros((Tq, 4 * BS))
    text = jax.jit(lambda: (
        mla.ragged_latent_attention_packed(
            q, pool, 0, bt, cu, kvl, latent_dim=DC, sm_scale=SCALE),
        mla.ragged_latent_attention_packed(
            q, pool, 0, bt, cu, kvl, latent_dim=DC, sm_scale=SCALE,
            window=5),
        mla.ragged_latent_attention_packed(
            q, pool, 0, bt, cu, kvl, latent_dim=DC, sm_scale=SCALE,
            select=bias),
        mla.ragged_index_scores_packed(qi, wi, ipool, 0, bt, cu, kvl))
    ).lower().as_text(debug_info=True)
    for name in (mla.KERNEL_NAME, mla.WINDOW_KERNEL_NAME,
                 mla.SELECT_KERNEL_NAME, mla.INDEX_KERNEL_NAME):
        assert f"{name}/" in text or f'"{name}"' in text, name
    assert len({mla.KERNEL_NAME, mla.WINDOW_KERNEL_NAME,
                mla.SELECT_KERNEL_NAME, mla.INDEX_KERNEL_NAME}) == 4
