"""The ragged kernel over a sliding-window layer's pages
(ops/pallas/paged_attention.py, ``window=``) against a masked plain
reference: a query at position i sees keys i - window < j <= i.  The
table entries of pages a row has given back name the null page, which
holds values that would show in the result if they were read.  Interpret
mode: small sizes, group 7 (28 query heads over 4 K/V heads at the
served widths)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import paged_attention as pa

HKV, G, D, BS, L, WINDOW = 2, 7, 128, 4, 3, 32
NBLK, NUM_BLOCKS = 24, 96


@pytest.fixture(autouse=True)
def _interpret():
    old = pa.INTERPRET
    pa.INTERPRET = True
    yield
    pa.INTERPRET = old


def _layout(rows, Tq, window, seed=0):
    """rows: [(n_q, kv_len)].  cu, kvl and a table of distinct pages in a
    shuffled order whose entries below each row's window are the null
    page, as a sequence that has moved on leaves them."""
    rng = np.random.default_rng(seed)
    R = len(rows)
    cu = np.zeros(R + 1, np.int32)
    cu[1:] = np.cumsum([n for n, _ in rows])
    assert cu[-1] <= Tq
    kvl = np.asarray([k for _, k in rows], np.int32)
    free = list(rng.permutation(np.arange(1, NUM_BLOCKS)))
    bt = np.zeros((R + 1, NBLK), np.int32)
    for r, (n, k) in enumerate(rows):
        first = 0 if window is None else max(0, k - n - window + 1) // BS
        for p in range(first, -(-k // BS)):
            bt[r, p] = free.pop()
    return jnp.asarray(cu), jnp.asarray(kvl), jnp.asarray(bt)


def _pools(key):
    kk, kv = jax.random.split(key)
    shape = (L, NUM_BLOCKS, HKV, BS, D)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    # the null page: read, it would swamp every score and every sum
    return k.at[:, 0].set(1e4), v.at[:, 0].set(1e4)


def _plain(q, k, v, bt, rows, cu, window):
    """Row by row: the row's keys gathered in order, the whole score
    matrix, the causal and the window mask."""
    out = np.zeros(q.shape, np.float32)
    q, k, v, bt = (np.asarray(a, np.float64) if a.dtype != np.int32
                   else np.asarray(a) for a in (q, k, v, bt))
    for r, (n, kv_len) in enumerate(rows):
        if n == 0 or kv_len == 0:
            continue
        pages = bt[r, :-(-kv_len // BS)]
        kr = k[pages].transpose(0, 2, 1, 3).reshape(-1, HKV, D)[:kv_len]
        vr = v[pages].transpose(0, 2, 1, 3).reshape(-1, HKV, D)[:kv_len]
        pos = kv_len - n + np.arange(n)
        key = np.arange(kv_len)
        mask = key[None, :] <= pos[:, None]
        if window is not None:
            mask &= key[None, :] > pos[:, None] - window
        qr = q[int(cu[r]):int(cu[r]) + n].reshape(n, HKV, -1, D)
        s = np.einsum("qhgd,khd->hgqk", qr, kr) / np.sqrt(D)
        s = np.where(mask[None, None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        # masked keys may lie in the null page: leave them out of the sum
        o = np.einsum("hgqk,khd->qhgd", p, np.where(
            (mask.any(0))[:, None, None], vr, 0.0))
        out[int(cu[r]):int(cu[r]) + n] = o.reshape(n, -1, D)
    return out


CASES = {
    # the geometries tests/test_mla_attention.py walks, at lengths under
    # the window
    "mixed": ([(5, 13), (0, 0), (1, 1), (1, 17), (9, 9), (3, 30)], 32),
    "decode": ([(1, 7), (1, 8), (1, 9), (1, 24)], 8),
    "chunk": ([(21, 29)], 24),
    "overhang": ([(10, 10), (1, 3), (1, 16), (2, 5)], 16),
    # decode rows at, one past and far past the window; the first key of
    # a window on a page's first and last slot
    "decode_past": ([(1, 32), (1, 33), (1, 35), (1, 36), (1, 90)], 8),
    # a chunk that straddles the window's edge, one wholly past it whose
    # first tile's window starts pages before its last tile's, and
    # decode rows beside them
    "chunk_past": ([(12, 40), (1, 61), (20, 88), (1, 5)], 40),
    # one chunk longer than the window
    "chunk_long": ([(40, 72)], 40),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("tiles", [(56, 2), (28, 3)])
def test_window_kernel_equals_plain(case, tiles, monkeypatch):
    rows, Tq = CASES[case]
    monkeypatch.setenv(
        "PADDLE_TPU_TUNE_FORCE",
        '{"paged_attention": {"q_tile_rows": %d, "kv_pages": %d}}' % tiles)
    cu, kvl, bt = _layout(rows, Tq, WINDOW, seed=len(case))
    q = jax.random.normal(jax.random.PRNGKey(3), (Tq, HKV * G, D),
                          jnp.float32)
    k, v = _pools(jax.random.PRNGKey(4))
    got = np.asarray(pa.ragged_paged_attention_packed(
        q, k, v, bt, cu, kvl, layer=1, window=WINDOW))
    want = _plain(q, k[1], v[1], bt, rows, cu, WINDOW)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    oracle = np.asarray(pa.ragged_paged_reference(
        q, k[1], v[1], bt, cu, kvl, window=WINDOW))
    live = np.zeros(Tq, bool)
    for r, (n, kv_len) in enumerate(rows):
        if kv_len > 0:
            live[int(cu[r]):int(cu[r]) + n] = True
    np.testing.assert_allclose(oracle[live], want[live], rtol=2e-5,
                               atol=2e-5)
    assert not got[~live].any()


@pytest.mark.parametrize("case", ["mixed", "decode_past", "chunk_past"])
def test_no_window_is_unchanged_at_group_7(case, monkeypatch):
    """The same launch with no window sees every key: at lengths past the
    window it differs from the window's, and equals the plain causal
    reference."""
    rows, Tq = CASES[case]
    monkeypatch.setenv(
        "PADDLE_TPU_TUNE_FORCE",
        '{"paged_attention": {"q_tile_rows": 56, "kv_pages": 2}}')
    cu, kvl, bt = _layout(rows, Tq, None, seed=1)
    q = jax.random.normal(jax.random.PRNGKey(5), (Tq, HKV * G, D),
                          jnp.float32)
    k, v = _pools(jax.random.PRNGKey(6))
    got = np.asarray(pa.ragged_paged_attention_packed(q, k, v, bt, cu, kvl,
                                                      layer=2))
    np.testing.assert_allclose(got, _plain(q, k[2], v[2], bt, rows, cu, None),
                               rtol=2e-5, atol=2e-5)
    if case != "mixed":
        win = np.asarray(pa.ragged_paged_attention_packed(
            q, k, v, bt, cu, kvl, layer=2, window=WINDOW))
        assert np.abs(win - got).max() > 1e-3


def test_tile_rows_stay_on_the_sublane_at_group_7():
    """A tile's score rows (tokens x group) are sliced out of the
    head-major query at a multiple of their count: a group of 7 takes
    tiles of a multiple of 8 tokens where the bucket has one; a group of
    4 or 8 keeps the tile it had."""
    for Tq in (64, 128, 320, 576):
        tq, _ = pa._ragged_tiles(Tq, 4, 7, 128, 16, 1024, jnp.bfloat16)
        assert Tq % tq == 0 and (tq * 7) % 8 == 0, (Tq, tq)
    assert pa._ragged_tiles(192, 8, 4, 128, 16, 256, jnp.bfloat16)[0] == 32
    assert pa._ragged_tiles(192, 4, 8, 128, 16, 256, jnp.bfloat16)[0] == 16


# ---------------------------------------------------------------------------
# groups 6 and 8 of one model, and a window SHORTER than the chunk
# (models/laguna.py: 48 and 64 query heads over 8 K/V heads, window 512
# under 512-token chunks)
# ---------------------------------------------------------------------------

SHORT = {
    # a chunk of 40 against a window of 16: every tile's page range is
    # shorter than the chunk, and its first page moves with the tile
    "chunk_over_the_window": ([(40, 40)], 40),
    # the same past the start, beside decode rows at, one past and far
    # past the window
    "chunk_past_beside_decode": ([(36, 90), (1, 16), (1, 17), (1, 77)], 40),
    # two chunks in one launch, one resumed
    "two_chunks": ([(20, 20), (19, 64)], 40),
}


@pytest.mark.parametrize("group", [6, 8])
@pytest.mark.parametrize("tiles", [(48, 2), (24, 3)])
@pytest.mark.parametrize("case", sorted(SHORT))
def test_a_window_shorter_than_the_chunk_at_groups_6_and_8(case, tiles, group,
                                                           monkeypatch):
    rows, Tq = SHORT[case]
    window = 16
    monkeypatch.setenv(
        "PADDLE_TPU_TUNE_FORCE",
        '{"paged_attention": {"q_tile_rows": %d, "kv_pages": %d}}' % tiles)
    cu, kvl, bt = _layout(rows, Tq, window, seed=len(case) + group)
    q = jax.random.normal(jax.random.PRNGKey(group), (Tq, HKV * group, D),
                          jnp.float32)
    k, v = _pools(jax.random.PRNGKey(9))
    got = np.asarray(pa.ragged_paged_attention_packed(
        q, k, v, bt, cu, kvl, layer=2, window=window))
    want = _plain(q, k[2], v[2], bt, rows, cu, window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    oracle = np.asarray(pa.ragged_paged_reference_segrel(
        q, k[2], v[2], bt, *pa.ragged_segments(cu, kvl, Tq), window=window))
    live = np.zeros(Tq, bool)
    for r, (n, kv_len) in enumerate(rows):
        live[int(cu[r]):int(cu[r]) + n] = kv_len > 0
    np.testing.assert_allclose(oracle[live], want[live], rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("case", ["mixed", "decode_past", "chunk_past"])
def test_no_window_at_group_6(case, monkeypatch):
    """The full layers' launch of the same model: group 6, every key."""
    rows, Tq = CASES[case]
    monkeypatch.setenv(
        "PADDLE_TPU_TUNE_FORCE",
        '{"paged_attention": {"q_tile_rows": 48, "kv_pages": 2}}')
    cu, kvl, bt = _layout(rows, Tq, None, seed=2)
    q = jax.random.normal(jax.random.PRNGKey(7), (Tq, HKV * 6, D),
                          jnp.float32)
    k, v = _pools(jax.random.PRNGKey(8))
    got = np.asarray(pa.ragged_paged_attention_packed(q, k, v, bt, cu, kvl,
                                                      layer=0))
    np.testing.assert_allclose(
        got, _plain(q, k[0], v[0], bt, rows, cu, None),
        rtol=2e-5, atol=2e-5)


def test_tile_rows_stay_on_the_sublane_at_group_6():
    """A group of 6 takes tiles of a multiple of 4 tokens (24 score rows
    and up) in every bucket of the benchmark's cell."""
    for Tq in (32, 64, 128, 320, 576):
        tq, _ = pa._ragged_tiles(Tq, 8, 6, 128, 16, 1024, jnp.bfloat16)
        assert Tq % tq == 0 and (tq * 6) % 8 == 0, (Tq, tq)
    assert pa._ragged_tiles(576, 8, 6, 128, 16, 1024, jnp.bfloat16)[0] == 16
    assert pa._ragged_tiles(576, 8, 8, 128, 16, 1024, jnp.bfloat16)[0] == 16
