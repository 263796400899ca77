"""MoE: gating math, eager MoELayer, fused_moe, and expert parallelism.

Mirrors the reference's MoE test strategy (test/collective/test_moe_api.py
runs gates + dispatch on a local group) on the virtual 8-device CPU mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.incubate.distributed.models.moe import (
    GShardGate, MoELayer, SwitchGate, capacity_for, topk_gating,
)
from paddle_tpu.incubate.nn.functional import fused_moe
from paddle_tpu.parallel import init_moe_params, moe_ffn


# ---------------- gating math ----------------

def test_gating_capacity_and_weights():
    rng = np.random.RandomState(0)
    T, E, k = 32, 4, 2
    C = capacity_for(T, E, k, 2.0)
    logits = jnp.asarray(rng.randn(T, E), jnp.float32)
    combine, dispatch, aux = jax.jit(
        lambda l: topk_gating(l, k, C))(logits)
    d = np.asarray(dispatch)
    c = np.asarray(combine)
    # every expert buffer slot is used by at most one token
    assert d.sum(axis=(0,)).max() <= 1.0 + 1e-6
    # each token occupies at most k slots
    assert d.sum(axis=(1, 2)).max() <= k + 1e-6
    # combine weights are a (sub-)probability distribution per token
    tot = c.sum(axis=(1, 2))
    assert tot.max() <= 1.0 + 1e-5
    assert float(aux) > 0


def test_gating_no_drop_when_capacity_large():
    """With generous capacity every token gets all k slots and weights
    sum exactly to 1."""
    rng = np.random.RandomState(1)
    T, E, k = 16, 4, 2
    logits = jnp.asarray(rng.randn(T, E), jnp.float32)
    combine, dispatch, _ = topk_gating(logits, k, capacity=T)
    np.testing.assert_allclose(np.asarray(dispatch).sum(axis=(1, 2)),
                               np.full(T, k), atol=1e-6)
    np.testing.assert_allclose(np.asarray(combine).sum(axis=(1, 2)),
                               np.ones(T), rtol=1e-5)


def test_switch_gating_topk1():
    rng = np.random.RandomState(2)
    logits = jnp.asarray(rng.randn(8, 4), jnp.float32)
    combine, dispatch, _ = topk_gating(logits, 1, capacity=8)
    # top-1: chosen expert must be the argmax
    chosen = np.asarray(dispatch).sum(axis=2).argmax(axis=1)
    np.testing.assert_array_equal(chosen, np.asarray(logits).argmax(axis=1))


# ---------------- eager MoELayer ----------------

def _experts(n, d, f):
    return [nn.Sequential(nn.Linear(d, f), nn.GELU(), nn.Linear(f, d))
            for _ in range(n)]


def test_moe_layer_forward_shape():
    paddle.seed(0)
    moe = MoELayer(d_model=16, experts=_experts(4, 16, 32), gate="gshard")
    x = paddle.randn([2, 8, 16])
    y = moe(x)
    assert y.shape == [2, 8, 16]
    assert moe.l_aux is not None and float(moe.l_aux.numpy()) > 0


def test_moe_layer_single_expert_equals_expert():
    """E=1: every token routes to the only expert with weight 1, so the MoE
    output equals the raw expert output (capacity covers all tokens)."""
    paddle.seed(0)
    expert = nn.Linear(8, 8)
    moe = MoELayer(d_model=8, experts=[expert], gate="switch",
                   capacity_factor=64.0)
    x = paddle.randn([4, 8])
    y = moe(x)
    ref = expert(x)
    np.testing.assert_allclose(y.numpy(), ref.numpy(), rtol=1e-5, atol=1e-6)


def test_moe_layer_trains():
    paddle.seed(0)
    moe = MoELayer(d_model=8, experts=_experts(2, 8, 16), gate="gshard",
                   capacity_factor=4.0)
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=moe.parameters())
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(16, 8).astype(np.float32))
    t = paddle.to_tensor(rng.randn(16, 8).astype(np.float32))
    losses = []
    for _ in range(20):
        loss = nn.functional.mse_loss(moe(x), t) + moe.l_aux * 0.01
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0]
    # router learns too: gate projection must receive gradient
    assert moe.gate.proj.weight.grad is None  # cleared
    loss = nn.functional.mse_loss(moe(x), t) + moe.l_aux * 0.01
    loss.backward()
    g = moe.gate.proj.weight.grad
    assert g is not None and float(paddle.abs(g).sum().numpy()) > 0


# ---------------- fused_moe ----------------

def test_fused_moe_matches_moe_ffn():
    rng = np.random.RandomState(3)
    H, F, E, T = 8, 16, 4, 32
    params = init_moe_params(jax.random.PRNGKey(0), H, F, E)
    x = paddle.to_tensor(rng.randn(T, H).astype(np.float32))
    y = fused_moe(x, paddle.to_tensor(params["gate"]),
                  paddle.to_tensor(params["w_in"]),
                  paddle.to_tensor(params["w_out"]), top_k=2)
    ref, _ = moe_ffn(jnp.asarray(x.numpy()), params, ep_axis=None)
    np.testing.assert_allclose(y.numpy(), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)


# ---------------- expert parallelism over the ep mesh axis ----------------

@pytest.mark.parametrize("ep", [2, 4])
def test_expert_parallel_matches_single_device(ep):
    """moe_ffn sharded over ep (tokens dp-sharded, experts ep-sharded,
    all_to_all dispatch) must equal the unsharded computation."""
    rng = np.random.RandomState(4)
    H, F, E = 8, 16, 4
    T = 64            # global tokens
    params = init_moe_params(jax.random.PRNGKey(1), H, F, E)
    x = jnp.asarray(rng.randn(T, H), jnp.float32)

    # generous capacity so no token is dropped in either layout (capacity is
    # computed from LOCAL token counts, which differ between the two runs)
    y_ref, aux_ref = moe_ffn(x, params, ep_axis=None, capacity_factor=8.0)

    mesh = Mesh(np.asarray(jax.devices()[:ep]), ("ep",))
    # tokens sharded over ep (acting as the dp axis too), experts sharded
    pspec = {"gate": P(), "w_in": P("ep"), "w_out": P("ep")}

    fn = shard_map(
        lambda x, p: moe_ffn(x, p, ep_axis="ep", capacity_factor=8.0),
        mesh=mesh, in_specs=(P("ep"), pspec), out_specs=(P("ep"), P()))
    y, aux = jax.jit(fn)(x, params)

    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)


def test_expert_parallel_gradients_flow():
    ep, H, F, E, T = 4, 8, 16, 4, 64
    params = init_moe_params(jax.random.PRNGKey(2), H, F, E)
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(T, H), jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()[:ep]), ("ep",))
    pspec = {"gate": P(), "w_in": P("ep"), "w_out": P("ep")}

    def loss_fn(params, x):
        fn = shard_map(
            lambda x, p: moe_ffn(x, p, ep_axis="ep"),
            mesh=mesh, in_specs=(P("ep"), pspec), out_specs=(P("ep"), P()))
        y, aux = fn(x, params)
        return jnp.sum(y ** 2) + 0.01 * aux

    grads = jax.jit(jax.grad(loss_fn))(params, x)
    for k, g in grads.items():
        assert float(jnp.sum(jnp.abs(g))) > 0, f"zero grad for {k}"
