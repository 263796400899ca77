"""Namespace-surface completion tests: every reference __all__ this build
claims complete stays complete (incubate.nn.functional, audio, geometric,
text, vision.*, distributed, root, profiler...) plus behavior smoke for the
newest additions."""
import ast
import os

import numpy as np
import pytest

import paddle_tpu as paddle


def _ref_all(path):
    tree = ast.parse(open(path).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if getattr(t, "id", None) == "__all__":
                    return [ast.literal_eval(e) for e in node.value.elts]
    return []


SURFACES = [
    ("", "/root/reference/python/paddle/__init__.py"),
    ("nn", "/root/reference/python/paddle/nn/__init__.py"),
    ("nn.functional", "/root/reference/python/paddle/nn/functional/__init__.py"),
    ("distributed", "/root/reference/python/paddle/distributed/__init__.py"),
    ("optimizer", "/root/reference/python/paddle/optimizer/__init__.py"),
    ("distribution", "/root/reference/python/paddle/distribution/__init__.py"),
    ("incubate.nn.functional",
     "/root/reference/python/paddle/incubate/nn/functional/__init__.py"),
    ("audio", "/root/reference/python/paddle/audio/__init__.py"),
    ("geometric", "/root/reference/python/paddle/geometric/__init__.py"),
    ("text", "/root/reference/python/paddle/text/__init__.py"),
    ("vision.transforms",
     "/root/reference/python/paddle/vision/transforms/__init__.py"),
    ("vision.datasets",
     "/root/reference/python/paddle/vision/datasets/__init__.py"),
    ("vision.models",
     "/root/reference/python/paddle/vision/models/__init__.py"),
    ("profiler", "/root/reference/python/paddle/profiler/__init__.py"),
    ("metric", "/root/reference/python/paddle/metric/__init__.py"),
    ("jit", "/root/reference/python/paddle/jit/__init__.py"),
    ("io", "/root/reference/python/paddle/io/__init__.py"),
    ("amp", "/root/reference/python/paddle/amp/__init__.py"),
]


@pytest.mark.parametrize("mod,path", SURFACES,
                         ids=[m or "root" for m, _ in SURFACES])
def test_surface_complete(mod, path):
    if not os.path.exists(path):
        pytest.skip("reference path moved")
    names = _ref_all(path)
    obj = paddle
    for part in (mod.split(".") if mod else []):
        obj = getattr(obj, part)
    missing = [n for n in names if not hasattr(obj, n)]
    assert not missing, f"{mod or 'root'}: {missing}"


def test_audio_io_roundtrip(tmp_path):
    wav = np.sin(np.linspace(0, 100, 4800)).astype(np.float32)[None]
    p = str(tmp_path / "t.wav")
    paddle.audio.save(p, paddle.to_tensor(wav), 24000)
    meta = paddle.audio.info(p)
    assert meta.sample_rate == 24000 and meta.num_channels == 1
    back, sr = paddle.audio.load(p)
    assert sr == 24000
    np.testing.assert_allclose(back.numpy(), wav, atol=1e-3)


def test_fused_transformer_blocks():
    IF = paddle.incubate.nn.functional
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.rand(2, 6, 16).astype(np.float32))
    qkvw = paddle.to_tensor(rng.rand(3, 4, 4, 16).astype(np.float32) * 0.1)
    lw = paddle.to_tensor(rng.rand(16, 16).astype(np.float32) * 0.1)
    out = IF.fused_multi_head_attention(
        x, qkvw, lw, pre_layer_norm=True, pre_ln_scale=paddle.ones([16]),
        pre_ln_bias=paddle.zeros([16]), dropout_rate=0.0,
        attn_dropout_rate=0.0, training=False)
    assert out.shape == [2, 6, 16]
    assert np.isfinite(out.numpy()).all()

    # varlen memory-efficient attention zeroes padded rows
    q = paddle.to_tensor(rng.rand(2, 4, 6, 4).astype(np.float32))
    o = IF.variable_length_memory_efficient_attention(
        q, q, q, paddle.to_tensor(np.asarray([6, 3], np.int32)),
        paddle.to_tensor(np.asarray([6, 3], np.int32)), causal=True)
    assert np.isfinite(o.numpy()).all()
    assert (o.numpy()[1, :, 3:] == 0).all()


def test_weighted_sample_and_heter_reindex():
    G = paddle.geometric
    row = paddle.to_tensor(np.asarray([1, 2, 3, 4, 5], np.int64))
    colptr = paddle.to_tensor(np.asarray([0, 3, 5], np.int64))
    w = paddle.to_tensor(np.asarray([10., 1., 1., 5., 5.], np.float32))
    nodes = paddle.to_tensor(np.asarray([0, 1], np.int64))
    nbr, cnt = G.weighted_sample_neighbors(row, colptr, w, nodes,
                                           sample_size=2)
    assert cnt.numpy().tolist() == [2, 2]

    outs, uniq, counts = G.reindex_heter_graph(
        paddle.to_tensor(np.asarray([10, 20], np.int64)),
        [paddle.to_tensor(np.asarray([20, 30], np.int64)),
         paddle.to_tensor(np.asarray([10, 40], np.int64))],
        [paddle.to_tensor(np.asarray([2], np.int64)),
         paddle.to_tensor(np.asarray([2], np.int64))])
    assert uniq.numpy().tolist()[:2] == [10, 20]
    assert outs[0].numpy().tolist() == [1, 2]      # 20 -> 1, 30 -> new id 2
    assert outs[1].numpy().tolist()[0] == 0        # 10 -> 0


def test_text_datasets_and_viterbi_layer():
    ds = paddle.text.Imikolov(window_size=4)
    assert len(ds[0]) == 4
    wmt = paddle.text.WMT14(mode="test")
    src, trg, nxt = wmt[0]
    assert nxt[0] == trg[1]
    dec = paddle.text.ViterbiDecoder(
        paddle.to_tensor(np.random.rand(3, 3).astype(np.float32)),
        include_bos_eos_tag=False)
    scores, paths = dec(
        paddle.to_tensor(np.random.rand(1, 4, 3).astype(np.float32)),
        paddle.to_tensor(np.asarray([4], np.int64)))
    assert paths.shape == [1, 4]


def test_incubate_surfaces_complete(reference_tree):
    for mod, path in [
            ("incubate.nn", "python/paddle/incubate/nn/__init__.py"),
            ("incubate", "python/paddle/incubate/__init__.py")]:
        names = _ref_all(os.path.join(reference_tree, path))
        obj = paddle
        for part in mod.split("."):
            obj = getattr(obj, part)
        missing = [n for n in names if not hasattr(obj, n)]
        assert not missing, f"{mod}: {missing}"


def test_fused_layers_and_lookahead():
    import paddle_tpu.nn as nn
    IN = paddle.incubate.nn

    lyr = IN.FusedTransformerEncoderLayer(16, 4, 32, dropout_rate=0.0)
    lyr.eval()
    x = paddle.randn([2, 5, 16])
    out = lyr(x)
    assert out.shape == [2, 5, 16]
    assert np.isfinite(out.numpy()).all()

    fl = IN.FusedLinear(8, 4)
    assert fl(paddle.randn([3, 8])).shape == [3, 4]

    # LookAhead: slow weights only move every k steps
    net = nn.Linear(4, 1)
    inner = paddle.optimizer.SGD(learning_rate=0.1,
                                 parameters=net.parameters())
    look = paddle.incubate.LookAhead(inner, alpha=0.5, k=2)
    xb = paddle.to_tensor(np.ones((2, 4), np.float32))
    w_start = net.weight.numpy().copy()
    for _ in range(2):
        net(xb).sum().backward()
        look.step()
        look.clear_grad()
    assert not np.allclose(net.weight.numpy(), w_start)

    # ModelAverage apply/restore roundtrip
    ma = paddle.incubate.ModelAverage(parameters=net.parameters())
    w_before = net.weight.numpy().copy()
    ma.step()
    net.weight._data = net.weight._data * 2.0
    ma.step()
    ma.apply()
    averaged = net.weight.numpy().copy()
    assert not np.allclose(averaged, net.weight._data * 0 + w_before * 2)
    ma.restore()
    np.testing.assert_allclose(net.weight.numpy(), w_before * 2.0)

    # masked softmax helpers
    s = paddle.incubate.softmax_mask_fuse_upper_triangle(
        paddle.randn([1, 2, 4, 4]))
    sn = s.numpy()
    np.testing.assert_allclose(sn.sum(-1), 1.0, rtol=1e-4)
    assert (sn[..., 0, 1:] == 0).all()       # causal row 0 sees only col 0
