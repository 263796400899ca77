"""The routed experts' grouped products (ops/pallas/grouped_matmul.py):
the Pallas kernel (interpreted) and ``lax.ragged_dot`` against a loop
over experts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.ops.pallas import paged_attention as pa

E, K, N = 6, 32, 24


@pytest.fixture(autouse=True)
def _interpret():
    old = pa.INTERPRET
    pa.INTERPRET = True
    yield
    pa.INTERPRET = old


def _case(sizes, M, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (M, K), jnp.float32),
            jax.random.normal(ks[1], (E, K, N), jnp.float32),
            jax.random.normal(ks[2], (E, K, N), jnp.float32),
            jnp.asarray(sizes, jnp.int32))


CASES = {
    "uneven": ([5, 1, 7, 2, 9, 4], 32),
    "an_empty_expert": ([6, 0, 8, 0, 3, 5], 24),
    "every_row_on_one_expert": ([0, 0, 0, 19, 0, 0], 24),
    "rows_past_the_last_group": ([3, 2, 0, 4, 1, 2], 40),
    "a_group_over_three_tiles": ([1, 20, 0, 0, 2, 1], 24),
    "no_rows_at_all": ([0, 0, 0, 0, 0, 0], 8),
    "rows_not_a_multiple_of_the_tile": ([2, 3, 1, 0, 4, 1], 13),
}


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["kernel", "ragged_dot"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_products_equal_the_loop_over_experts(case, use_kernel,
                                                      monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TUNE_FORCE",
                       '{"grouped_matmul": {"row_tile": 8}}')
    sizes, M = CASES[case]
    x, w, w2, gs = _case(sizes, M)
    n = int(sum(sizes))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda *a: gm.grouped_matmul(
            *a, use_kernel=use_kernel))(x, w, gs))
        act = np.asarray(jax.jit(lambda *a: gm.grouped_swiglu(
            *a, use_kernel=use_kernel))(x, w, w2, gs))
        want = np.asarray(gm.grouped_matmul_reference(x, w, gs))
        up = np.asarray(gm.grouped_matmul_reference(x, w2, gs))
        # and the loop itself against each expert's plain product
        start = 0
        for e, s in enumerate(sizes):
            np.testing.assert_allclose(
                want[start:start + s], np.asarray(x[start:start + s] @ w[e]),
                atol=1e-5, rtol=0)
            start += s
    assert got.shape == (M, N) and act.shape == (M, N)
    np.testing.assert_allclose(got[:n], want[:n], atol=1e-5, rtol=0)
    np.testing.assert_allclose(
        act[:n], (np.asarray(jax.nn.silu(want)) * up)[:n], atol=1e-5, rtol=0)
    assert not want[n:].any()           # what the caller masks is masked


def test_the_walk_visits_each_groups_tiles_in_order():
    gid, mt, starts, ends, total = (np.asarray(a) for a in gm._visits(
        jnp.asarray([5, 0, 13, 2, 0, 4]), 32, 8))
    assert int(total[0]) == 6 and len(gid) == 32 // 8 + 6 - 1
    assert list(zip(gid[:6], mt[:6])) == [(0, 0), (2, 0), (2, 1), (2, 2),
                                          (3, 2), (5, 2)]
    assert (gid[6:] == 5).all() and (mt[6:] == 2).all()   # stands still
    assert list(starts) == [0, 5, 5, 18, 20, 20]
    assert list(ends) == [5, 5, 18, 20, 20, 24]


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["kernel", "ragged_dot"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_reglu_products_equal_ragged_dot(case, use_kernel, monkeypatch):
    """ReGLU's first half, ``relu(x G_e) * (x U_e)``: the kernel with
    ReLU where SwiGLU has SiLU, and its off-TPU path, against
    ``lax.ragged_dot`` of each matrix."""
    monkeypatch.setenv("PADDLE_TPU_TUNE_FORCE",
                       '{"grouped_matmul": {"row_tile": 8}}')
    sizes, M = CASES[case]
    x, w, w2, gs = _case(sizes, M, seed=1)
    n = int(sum(sizes))
    with jax.default_matmul_precision("highest"):
        act = np.asarray(jax.jit(lambda *a: gm.grouped_reglu(
            *a, use_kernel=use_kernel))(x, w, w2, gs))
        gate = np.asarray(jax.lax.ragged_dot(x, w, gs))
        up = np.asarray(jax.lax.ragged_dot(x, w2, gs))
    assert act.shape == (M, N)
    np.testing.assert_allclose(act[:n], (np.maximum(gate, 0.0) * up)[:n],
                               atol=1e-5, rtol=0)
    if n:
        assert (act[:n] == 0).any()     # ReLU, not SiLU: some gates shut


@pytest.mark.parametrize("use_kernel", [True, False],
                         ids=["kernel", "ragged_dot"])
@pytest.mark.parametrize("rows,tile", [(300, 16), (64, 8), (1024, 128)])
def test_256_groups_of_few_rows_equal_ragged_dot(rows, tile, use_kernel,
                                                 monkeypatch):
    """256 experts, 8 a token (models/laguna.py): about a row a group at
    a decode step, a few at a chunk step, many groups empty; the walk
    makes ``M / tile + 255`` visits and every group's rows come out as
    ``lax.ragged_dot`` gives them."""
    monkeypatch.setenv("PADDLE_TPU_TUNE_FORCE",
                       '{"grouped_matmul": {"row_tile": %d}}' % tile)
    experts = 256
    rng = np.random.default_rng(rows)
    # pairs as a router deals them: uneven, some experts with none
    picks = rng.choice(experts, size=rows - 40, p=rng.dirichlet(
        np.full(experts, 0.5)))
    sizes = np.bincount(picks, minlength=experts).astype(np.int32)
    n = int(sizes.sum())
    assert (sizes == 0).any() and sizes.max() > 2 * n / experts
    ks = jax.random.split(jax.random.PRNGKey(rows), 3)
    x = jax.random.normal(ks[0], (rows, K), jnp.float32)
    w = jax.random.normal(ks[1], (experts, K, N), jnp.float32)
    w2 = jax.random.normal(ks[2], (experts, K, N), jnp.float32)
    gs = jnp.asarray(sizes)
    gid = np.asarray(gm._visits(gs, rows, tile)[0])
    assert len(gid) == rows // tile + experts - 1
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda *a: gm.grouped_matmul(
            *a, use_kernel=use_kernel))(x, w, gs))
        act = np.asarray(jax.jit(lambda *a: gm.grouped_swiglu(
            *a, use_kernel=use_kernel))(x, w, w2, gs))
        want = np.asarray(jax.lax.ragged_dot(x, w, gs))
        up = np.asarray(jax.lax.ragged_dot(x, w2, gs))
    np.testing.assert_allclose(got[:n], want[:n], atol=1e-5, rtol=0)
    # a product of two sums of 32 terms: of order 100, so relative
    np.testing.assert_allclose(
        act[:n], (np.asarray(jax.nn.silu(want)) * up)[:n], atol=1e-4,
        rtol=1e-4)
