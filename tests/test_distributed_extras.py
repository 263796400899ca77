"""distributed extras + intermediate parallelize API (reference
distributed/__init__ __all__ remainder, auto_parallel/intermediate/)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn


def test_surface_complete(reference_tree):
    import ast
    tree = ast.parse(open(
        reference_tree + "/python/paddle/distributed/__init__.py").read())
    names = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if getattr(t, "id", None) == "__all__":
                    names = [ast.literal_eval(e) for e in node.value.elts]
    missing = [n for n in names if not hasattr(dist, n)]
    assert not missing, missing


def test_single_process_collective_helpers():
    t = paddle.to_tensor(np.asarray([1.0, 2.0], np.float32))
    out: list = []
    dist.gather(t, out, dst=0)
    assert len(out) == 1
    np.testing.assert_allclose(out[0].numpy(), [1.0, 2.0])

    objs = [{"a": 1}, None]
    dist.broadcast_object_list(objs, src=0)
    assert objs[0] == {"a": 1}

    got: list = []
    dist.scatter_object_list(got, [{"x": 2}], src=0)
    assert got == [{"x": 2}]

    dist.wait(t)
    assert dist.get_backend() == "XLA"
    assert dist.is_available()
    assert dist.ParallelMode.TENSOR_PARALLEL == 1
    assert dist.ReduceType.kRedSum == 0
    assert dist.ShardingStage2().stage == 2


def test_parallelize_colwise_rowwise():
    class Blk(nn.Layer):
        def __init__(self):
            super().__init__()
            self.q_proj = nn.Linear(16, 32, bias_attr=False)
            self.o_proj = nn.Linear(32, 16, bias_attr=False)

        def forward(self, x):
            return self.o_proj(self.q_proj(x))

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.layers = nn.LayerList([Blk(), Blk()])

        def forward(self, x):
            for blk in self.layers:
                x = blk(x)
            return x

    mesh = dist.ProcessMesh(np.arange(8).reshape(1, 8),
                            dim_names=["dp", "mp"])
    model = Net()
    plan = {
        "layers.*.q_proj": dist.ColWiseParallel(),
        "layers.*.o_proj": dist.RowWiseParallel(),
    }
    model = dist.parallelize(model, mesh=mesh,
                             config={"mp_config": {"parallelize_plan": plan}})
    # weights really sharded over 8 devices
    for blk in model.layers:
        assert len(blk.q_proj.weight._data.sharding.device_set) == 8
    # and the model still runs (GSPMD completes the program)
    x = paddle.to_tensor(np.random.rand(4, 16).astype("float32"))
    assert model(x).shape == [4, 16]


def test_parallelize_warns_on_no_match(caplog):
    import logging
    model = nn.Linear(4, 4)
    pkg = logging.getLogger("paddle_tpu")
    pkg.propagate = True
    try:
        with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
            dist.parallelize(model, config={
                "mp_config": {"parallelize_plan": {
                    "nonexistent.*": dist.ColWiseParallel()}}})
        assert any("no layers match" in r.message for r in caplog.records)
    finally:
        pkg.propagate = False


def test_shard_dataloader():
    from paddle_tpu.io import DataLoader, TensorDataset

    xs = paddle.to_tensor(np.arange(64, dtype=np.float32).reshape(16, 4))
    loader = DataLoader(TensorDataset([xs]), batch_size=8)
    mesh = dist.ProcessMesh(np.arange(8).reshape(8,), dim_names=["dp"])
    sharded = dist.shard_dataloader(loader, mesh, "dp")
    batches = list(sharded)
    assert len(batches) == len(loader) == 2
    b0 = batches[0][0]
    assert len(b0._data.sharding.device_set) == 8


def test_strategy_and_ps_stubs():
    s = dist.Strategy({"sharding": {"enable": True, "stage": 2}})
    assert s.sharding.enable and s.sharding.stage == 2
    assert s.pipeline.enable is False
    with pytest.raises(NotImplementedError, match="parameter-server"):
        dist.InMemoryDataset()
    with pytest.raises(NotImplementedError, match="parameter-server"):
        dist.QueueDataset()


def test_io_persistables_roundtrip(tmp_path):
    net = nn.Linear(4, 2)
    dist.io.save_persistables(net, str(tmp_path))
    w0 = net.weight.numpy().copy()
    net.weight._data = net.weight._data * 0.0
    dist.io.load_persistables(net, str(tmp_path))
    np.testing.assert_allclose(net.weight.numpy(), w0)
