"""TP (mpu) layers, sequence parallelism, and recompute.

Mirrors the reference tests for fleet.layers.mpu (test/collective/fleet/) but
runs single-controller on the virtual 8-device CPU mesh (SURVEY.md §4:
GPU-free distributed testing).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.fleet.layers.mpu import (
    ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
    VocabParallelEmbedding, get_rng_state_tracker,
)
from paddle_tpu.distributed.fleet.utils.sequence_parallel_utils import (
    AllGatherOp, ColumnSequenceParallelLinear, GatherOp, ReduceScatterOp,
    RowSequenceParallelLinear, ScatterOp,
    mark_as_sequence_parallel_parameter,
)


@pytest.fixture(scope="module")
def mp2():
    fleet.fleet.init(is_collective=True, strategy=_mp_strategy(2))
    yield fleet.fleet.get_hybrid_communicate_group()
    # reset to degenerate topology for other tests
    fleet.fleet.init(is_collective=True, strategy=fleet.DistributedStrategy())


def _mp_strategy(mp):
    s = fleet.DistributedStrategy()
    s.hybrid_configs["mp_degree"] = mp
    return s


def test_column_row_parallel_mp2_matches_serial(mp2):
    rng = np.random.RandomState(0)
    x = rng.randn(4, 16).astype(np.float32)
    col = ColumnParallelLinear(16, 32, gather_output=False, has_bias=True)
    row = RowParallelLinear(32, 16, input_is_parallel=True, has_bias=True)

    xt = paddle.to_tensor(x, stop_gradient=False)
    out = row(col(xt))
    assert out.shape == [4, 16]

    # serial reference with the same (full) weights
    ref = x @ col.weight.numpy() + col.bias.numpy()
    ref = ref @ row.weight.numpy() + row.bias.numpy()
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-5)

    # backward flows to weights
    out.backward(paddle.to_tensor(np.ones_like(ref)))
    assert col.weight.grad is not None
    assert row.weight.grad is not None


def test_vocab_parallel_embedding_mp2(mp2):
    emb = VocabParallelEmbedding(64, 8)
    ids = paddle.to_tensor(np.array([[1, 5, 63], [0, 33, 2]], np.int64))
    out = emb(ids)
    assert out.shape == [2, 3, 8]
    np.testing.assert_allclose(out.numpy(), emb.weight.numpy()[ids.numpy()],
                               rtol=1e-6)


def test_parallel_cross_entropy_degenerate():
    logits = paddle.to_tensor(
        np.random.RandomState(1).randn(4, 10).astype(np.float32),
        stop_gradient=False)
    label = paddle.to_tensor(np.array([1, 3, 9, 0], np.int64))
    loss = ParallelCrossEntropy()(logits, label)
    # reference: stable log-softmax pick
    lg = logits.numpy()
    m = lg.max(-1, keepdims=True)
    lse = m[..., 0] + np.log(np.exp(lg - m).sum(-1))
    ref = lse - lg[np.arange(4), label.numpy()]
    np.testing.assert_allclose(loss.numpy(), ref, rtol=1e-5)


def test_c_softmax_with_cross_entropy_sharded_matches_serial():
    """ParallelCrossEntropy inside shard_map over an mp axis == serial."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map
    from paddle_tpu.distributed import collective as C
    from paddle_tpu.distributed.fleet.layers.mpu.mp_ops import (
        _c_softmax_with_cross_entropy,
    )

    mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))
    group = C.new_group(list(range(4)), axis_name="mp")
    rng = np.random.RandomState(2)
    logits = rng.randn(6, 32).astype(np.float32)
    labels = rng.randint(0, 32, (6,)).astype(np.int64)

    def fn(lg, lb):
        return _c_softmax_with_cross_entropy(lg, lb, group=group)

    out = shard_map(fn, mesh=mesh, in_specs=(P(None, "mp"), P()),
                    out_specs=P(), check_vma=False)(logits, labels)

    m = logits.max(-1, keepdims=True)
    lse = m[..., 0] + np.log(np.exp(logits - m).sum(-1))
    ref = lse - logits[np.arange(6), labels]
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_sequence_parallel_linears_mp2(mp2):
    rng = np.random.RandomState(3)
    x = rng.randn(8, 2, 16).astype(np.float32)  # [s, b, h]
    col = ColumnSequenceParallelLinear(16, 32, gather_output=False)
    row = RowSequenceParallelLinear(32, 16, input_is_parallel=True)
    xt = paddle.to_tensor(x, stop_gradient=False)
    xs = ScatterOp.apply(xt)
    out = row(col(xs))
    out_full = GatherOp.apply(out)
    ref = x @ col.weight.numpy() + col.bias.numpy()
    ref = ref @ row.weight.numpy() + row.bias.numpy()
    np.testing.assert_allclose(out_full.numpy(), ref, rtol=1e-4, atol=1e-4)


def test_sequence_parallel_ops_traced_roundtrip():
    """Scatter->AllGather roundtrip and ReduceScatter correctness inside
    shard_map (the actual TP execution regime)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))
    x = np.random.RandomState(4).randn(8, 4).astype(np.float32)

    from paddle_tpu.distributed.fleet.utils import sequence_parallel_utils as spu

    # monkeypatch the axis context: traced path keys on axis name "mp"
    def fn(a):
        local = lax.dynamic_slice_in_dim(
            a, lax.axis_index("mp") * 2, 2, axis=0)          # scatter
        back = lax.all_gather(local, "mp", axis=0, tiled=True)  # gather
        return back

    out = shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                    check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(out), x, rtol=1e-6)


def test_rng_state_tracker():
    tracker = get_rng_state_tracker()
    tracker.reset()
    tracker.add("model_parallel_rng", 1234)
    with tracker.rng_state("model_parallel_rng"):
        a = paddle.ops.random.randn([4])
    with tracker.rng_state("model_parallel_rng"):
        b = paddle.ops.random.randn([4])
    # stream advances: draws differ, but both came from the tracked stream
    assert not np.allclose(a.numpy(), b.numpy())


def test_recompute_grads_match():
    from paddle_tpu.distributed.fleet.recompute import recompute

    lin1 = paddle.nn.Linear(8, 8)
    lin2 = paddle.nn.Linear(8, 8)

    def block(h):
        return lin2(paddle.nn.functional.relu(lin1(h)))

    x = paddle.to_tensor(
        np.random.RandomState(5).randn(4, 8).astype(np.float32),
        stop_gradient=False)

    out = block(x)
    out.backward(paddle.to_tensor(np.ones((4, 8), np.float32)))
    g_ref = lin1.weight.grad.numpy().copy()
    xg_ref = x.grad.numpy().copy()
    lin1.weight.clear_grad(); lin2.weight.clear_grad()
    lin1.bias.clear_grad(); lin2.bias.clear_grad()

    x2 = paddle.to_tensor(x.numpy(), stop_gradient=False)
    out2 = recompute(block, x2)
    out2.backward(paddle.to_tensor(np.ones((4, 8), np.float32)))
    np.testing.assert_allclose(lin1.weight.grad.numpy(), g_ref, rtol=1e-5)
    np.testing.assert_allclose(x2.grad.numpy(), xg_ref, rtol=1e-5)


def test_recompute_sequential():
    from paddle_tpu.distributed.fleet.recompute import recompute_sequential

    seq = paddle.nn.Sequential(
        paddle.nn.Linear(8, 8), paddle.nn.ReLU(), paddle.nn.Linear(8, 8))
    x = paddle.to_tensor(
        np.random.RandomState(6).randn(2, 8).astype(np.float32),
        stop_gradient=False)
    ref = seq(x)
    out = recompute_sequential({"segments": 2}, seq, x)
    np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-6)


def test_mark_sequence_parallel_parameter():
    lin = paddle.nn.Linear(4, 4)
    mark_as_sequence_parallel_parameter(lin.weight)
    from paddle_tpu.distributed.fleet.utils.sequence_parallel_utils import (
        is_sequence_parallel_parameter,
    )
    assert is_sequence_parallel_parameter(lin.weight)
    assert not is_sequence_parallel_parameter(lin.bias)
