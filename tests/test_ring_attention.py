"""Ring attention (context parallelism) on the virtual 8-device CPU mesh.

The reference snapshot has no ring attention (SURVEY.md §5.7); these tests
validate our beyond-parity CP path: exact blockwise attention with KV rotating
via ppermute must match dense softmax attention, and the cp axis of the hybrid
trainer must track single-device numerics.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from paddle_tpu.parallel import (
    HybridParallelConfig, build_mesh, build_train_step, init_opt_state,
    init_params, ring_attention, ring_self_attention, shard_opt_state,
    shard_params, zigzag_permutation, zigzag_inverse_permutation,
)
from paddle_tpu.models.llama import LlamaConfig


def _dense_attention(q, k, v, causal):
    # q/k/v: [B, S, H, D]
    qf = jnp.swapaxes(q, 1, 2).astype(jnp.float32)
    kf = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vf = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) / math.sqrt(q.shape[-1])
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return jnp.swapaxes(out, 1, 2)


def _rand_qkv(B=2, S=32, H=4, D=8, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("cp", [2, 4, 8])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_dense(cp, causal):
    q, k, v = _rand_qkv(S=32)
    mesh = Mesh(np.asarray(jax.devices()[:cp]), ("sep",))
    out = ring_self_attention(q, k, v, mesh, axis_name="sep", causal=causal)
    ref = _dense_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_gradients_match_dense():
    q, k, v = _rand_qkv(S=16, seed=3)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("sep",))
    spec = P(None, "sep", None, None)

    def ring_loss(q, k, v):
        fn = shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sep", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return jnp.sum(fn(q, k, v) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, True).astype(q.dtype) ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for gr, gd in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                   rtol=2e-4, atol=2e-5)


def test_zigzag_layout_matches_dense():
    """Load-balanced zigzag sharding: permute tokens, run the ring with
    explicit shard_positions, un-permute — must equal dense attention."""
    cp, S = 4, 32
    q, k, v = _rand_qkv(S=S, seed=5)
    perm, shard_pos = zigzag_permutation(S, cp)
    inv = zigzag_inverse_permutation(S, cp)
    qz, kz, vz = q[:, perm], k[:, perm], v[:, perm]
    mesh = Mesh(np.asarray(jax.devices()[:cp]), ("sep",))
    spec = P(None, "sep", None, None)
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sep", causal=True,
                                       shard_positions=shard_pos),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    out = jax.jit(fn)(qz, kz, vz)[:, inv]
    ref = _dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


CFG = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4, ffn=64, seq=16)


def test_cp_trains():
    hp = HybridParallelConfig(dp=1, pp=1, tp=1, cp=4)
    mesh = build_mesh(hp)
    params = shard_params(init_params(CFG, hp, seed=0), hp, mesh)
    opt = shard_opt_state(init_opt_state(params), hp, mesh)
    step = build_train_step(CFG, hp, mesh)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, CFG.vocab_size, (2, 16)), jnp.int32)
    losses = []
    for _ in range(8):
        params, opt, loss = step(params, opt, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_cp_matches_single_device():
    """cp-sharded training must track single-device numerics (the
    accuracy-alignment strategy of SURVEY.md §4 applied to the cp axis)."""
    hp1 = HybridParallelConfig(dp=1, pp=1, tp=1, remat=False)
    hp_cp = HybridParallelConfig(dp=1, pp=1, tp=1, cp=4, remat=False)
    mesh1, meshc = build_mesh(hp1), build_mesh(hp_cp)
    p0 = init_params(CFG, hp1, seed=3)
    rng = np.random.RandomState(7)
    tokens = jnp.asarray(rng.randint(0, CFG.vocab_size, (2, 16)), jnp.int32)

    p1 = shard_params(jax.tree.map(jnp.copy, p0), hp1, mesh1)
    o1 = shard_opt_state(init_opt_state(p1), hp1, mesh1)
    p1, o1, loss1 = build_train_step(CFG, hp1, mesh1)(p1, o1, tokens)

    pc = shard_params(jax.tree.map(jnp.copy, p0), hp_cp, meshc)
    oc = shard_opt_state(init_opt_state(pc), hp_cp, meshc)
    pc, oc, lossc = build_train_step(CFG, hp_cp, meshc)(pc, oc, tokens)

    np.testing.assert_allclose(float(loss1), float(lossc), rtol=2e-4)
    w1 = np.asarray(jax.device_get(p1["layers"]["wq"]))
    wc = np.asarray(jax.device_get(pc["layers"]["wq"]))
    np.testing.assert_allclose(w1, wc, rtol=2e-3, atol=1e-4)


def test_full_hybrid_with_cp():
    """All four axes at once: pp=2, cp=2, tp=2."""
    hp = HybridParallelConfig(dp=1, pp=2, tp=2, cp=2, num_microbatches=2)
    mesh = build_mesh(hp)
    params = shard_params(init_params(CFG, hp, seed=0), hp, mesh)
    opt = shard_opt_state(init_opt_state(params), hp, mesh)
    step = build_train_step(CFG, hp, mesh)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, CFG.vocab_size, (4, 16)), jnp.int32)
    losses = []
    for _ in range(8):
        params, opt, loss = step(params, opt, tokens)
        losses.append(float(loss))
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_long_context_16k_ring():
    """Long-context scaling: 16k tokens over cp=8 — each device holds a
    2k slice and attends blockwise via the KV ring; numerics must match
    dense attention computed on one device."""
    q, k, v = _rand_qkv(B=1, S=16384, H=2, D=16, seed=3)
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("sep",))
    out = ring_self_attention(q, k, v, mesh, axis_name="sep", causal=True)
    ref = _dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
