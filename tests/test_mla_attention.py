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
