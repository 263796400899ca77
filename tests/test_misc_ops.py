"""Misc op-surface coverage tests (reference tensor/{manipulation,math,
linalg,creation}.py + ops.yaml entries; NumPy oracles)."""
import numpy as np
import pytest

import paddle_tpu as paddle


def test_cast_shape_mv_inverse():
    x = paddle.to_tensor(np.asarray([[1.5, 2.5], [3.0, 4.0]], np.float32))
    assert paddle.cast(x, "int32").numpy().dtype == np.int32
    np.testing.assert_array_equal(paddle.shape(x).numpy(), [2, 2])

    v = paddle.to_tensor(np.asarray([1.0, 2.0], np.float32))
    np.testing.assert_allclose(paddle.mv(x, v).numpy(), [6.5, 11.0])

    inv = paddle.inverse(x).numpy()
    np.testing.assert_allclose(inv @ x.numpy(), np.eye(2), atol=1e-5)


def test_multiplex_reverse():
    a = paddle.to_tensor(np.asarray([[1., 2.], [3., 4.]], np.float32))
    b = paddle.to_tensor(np.asarray([[10., 20.], [30., 40.]], np.float32))
    idx = paddle.to_tensor(np.asarray([[1], [0]], np.int32))
    out = paddle.multiplex([a, b], idx)
    np.testing.assert_allclose(out.numpy(), [[10., 20.], [3., 4.]])

    r = paddle.reverse(a, axis=0)
    np.testing.assert_allclose(r.numpy(), [[3., 4.], [1., 2.]])


def test_fill_family_and_diag_embed():
    x = paddle.zeros([3, 3])
    y = paddle.fill_diagonal(x, 5.0)
    np.testing.assert_allclose(y.numpy(), np.eye(3) * 5.0)
    y2 = paddle.fill_diagonal(x, 2.0, offset=1)
    assert y2.numpy()[0, 1] == 2.0 and y2.numpy()[0, 0] == 0.0

    d = paddle.to_tensor(np.asarray([1., 2., 3.], np.float32))
    fd = paddle.fill_diagonal_tensor(paddle.zeros([3, 3]), d)
    np.testing.assert_allclose(fd.numpy(), np.diag([1., 2., 3.]))

    de = paddle.diag_embed(d)
    np.testing.assert_allclose(de.numpy(), np.diag([1., 2., 3.]))
    de_off = paddle.diag_embed(d, offset=1)
    assert de_off.shape == [4, 4]
    np.testing.assert_allclose(np.diagonal(de_off.numpy(), 1), [1., 2., 3.])

    z = paddle.ones([2, 2])
    paddle.fill_(z, 7.0)
    np.testing.assert_allclose(z.numpy(), np.full((2, 2), 7.0))


def test_norm_helpers():
    x = paddle.to_tensor(np.asarray([[3., 4.], [0., 0.]], np.float32))
    np.testing.assert_allclose(paddle.frobenius_norm(x).numpy(), 5.0)
    np.testing.assert_allclose(paddle.squared_l2_norm(x).numpy(), 25.0)
    np.testing.assert_allclose(paddle.mean_all(x).numpy(), 1.75)

    big = paddle.to_tensor(np.asarray([6., 8.], np.float32))
    clipped = paddle.clip_by_norm(big, 5.0)
    np.testing.assert_allclose(np.linalg.norm(clipped.numpy()), 5.0,
                               rtol=1e-5)
    small = paddle.to_tensor(np.asarray([0.3, 0.4], np.float32))
    np.testing.assert_allclose(paddle.clip_by_norm(small, 5.0).numpy(),
                               [0.3, 0.4])


def test_sequence_mask_and_gather_tree():
    lens = paddle.to_tensor(np.asarray([1, 3, 2], np.int64))
    m = paddle.sequence_mask(lens, maxlen=4)
    np.testing.assert_array_equal(
        m.numpy(), [[1, 0, 0, 0], [1, 1, 1, 0], [1, 1, 0, 0]])

    # reference gather_tree docstring example
    ids = paddle.to_tensor(np.asarray(
        [[[2, 2], [6, 1]], [[3, 9], [6, 1]], [[0, 1], [9, 0]]], np.int64))
    parents = paddle.to_tensor(np.asarray(
        [[[0, 0], [1, 1]], [[1, 0], [1, 0]], [[0, 0], [0, 1]]], np.int64))
    out = paddle.gather_tree(ids, parents)
    np.testing.assert_array_equal(
        out.numpy(),
        [[[2, 2], [1, 6]], [[3, 3], [6, 1]], [[0, 1], [9, 0]]])


def test_top_p_sampling():
    probs = paddle.to_tensor(np.asarray(
        [[0.7, 0.2, 0.05, 0.05], [0.25, 0.25, 0.25, 0.25]], np.float32))
    ps = paddle.to_tensor(np.asarray([0.5, 0.9], np.float32))
    vals, ids = paddle.top_p_sampling(probs, ps, seed=3)
    # row 0: nucleus at p=0.5 is exactly {token 0}
    assert ids.numpy()[0, 0] == 0
    assert 0 <= ids.numpy()[1, 0] < 4
    np.testing.assert_allclose(
        vals.numpy()[0, 0], 0.7, rtol=1e-6)


def test_temporal_shift():
    nt, c, h, w = 4, 4, 2, 2   # n=2 segments of 2
    x = np.arange(nt * c * h * w, dtype=np.float32).reshape(nt, c, h, w)
    out = paddle.temporal_shift(paddle.to_tensor(x), seg_num=2,
                                shift_ratio=0.25).numpy()
    v = x.reshape(2, 2, c, h, w)
    # first c/4 channels shifted backward: out[:, t, 0] = v[:, t+1, 0]
    np.testing.assert_allclose(out.reshape(2, 2, c, h, w)[:, 0, 0],
                               v[:, 1, 0])
    np.testing.assert_allclose(out.reshape(2, 2, c, h, w)[:, 1, 0], 0.0)
    # next c/4 shifted forward
    np.testing.assert_allclose(out.reshape(2, 2, c, h, w)[:, 1, 1],
                               v[:, 0, 1])
    # the rest untouched
    np.testing.assert_allclose(out.reshape(2, 2, c, h, w)[:, :, 2:],
                               v[:, :, 2:])


def test_edit_distance():
    hyp = paddle.to_tensor(np.asarray([[1, 2, 3], [4, 5, 6]], np.int64))
    ref = paddle.to_tensor(np.asarray([[1, 2, 4, 0], [4, 5, 6, 7]],
                                      np.int64))
    hl = paddle.to_tensor(np.asarray([3, 3], np.int64))
    rl = paddle.to_tensor(np.asarray([3, 4], np.int64))
    d, n = paddle.edit_distance(hyp, ref, normalized=False,
                                input_length=hl, label_length=rl)
    np.testing.assert_allclose(d.numpy().reshape(-1), [1.0, 1.0])
    assert n.numpy()[0] == 2
    dn, _ = paddle.edit_distance(hyp, ref, normalized=True,
                                 input_length=hl, label_length=rl)
    np.testing.assert_allclose(dn.numpy().reshape(-1), [1 / 3, 1 / 4])


def test_viterbi_decode():
    rng = np.random.RandomState(0)
    B, T, N = 2, 5, 3
    emis = rng.rand(B, T, N).astype(np.float32)
    trans = rng.rand(N, N).astype(np.float32)
    lens = np.asarray([5, 3], np.int64)

    scores, paths = paddle.viterbi_decode(
        paddle.to_tensor(emis), paddle.to_tensor(trans),
        paddle.to_tensor(lens), include_bos_eos_tag=False)

    # brute-force oracle over all tag sequences for batch 0
    import itertools
    best, best_path = -1e9, None
    for seq in itertools.product(range(N), repeat=T):
        s = emis[0, 0, seq[0]] + sum(
            trans[seq[t - 1], seq[t]] + emis[0, t, seq[t]]
            for t in range(1, T))
        if s > best:
            best, best_path = s, seq
    np.testing.assert_allclose(scores.numpy()[0], best, rtol=1e-5)
    np.testing.assert_array_equal(paths.numpy()[0], best_path)


def test_as_strided():
    x = paddle.to_tensor(np.arange(12, dtype=np.float32))
    # overlapping windows: shape (5, 4) stride (2, 1)
    out = paddle.as_strided(x, [5, 4], [2, 1])
    want = np.lib.stride_tricks.as_strided(
        np.arange(12, dtype=np.float32), (5, 4), (8, 4))
    np.testing.assert_allclose(out.numpy(), want)


def test_tensor_method_surface_complete(reference_tree):
    """Every reference tensor_method_func name is bound on Tensor."""
    import ast
    src = open(reference_tree + "/python/paddle/tensor/__init__.py").read()
    names = []
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if getattr(t, "id", None) == "tensor_method_func":
                    names = [ast.literal_eval(e) for e in node.value.elts]
    missing = [n for n in names if not hasattr(paddle.Tensor, n)]
    assert not missing, missing


def test_new_linalg_ops():
    import scipy.linalg as sla

    A = np.asarray([[4., 0.], [0., 2.]], np.float32)
    np.testing.assert_allclose(
        float(paddle.linalg.cond(paddle.to_tensor(A)).numpy()), 2.0,
        rtol=1e-5)

    # non-diagonal factor: catches triangle-flag inversions that a
    # diagonal A cannot (both triangles coincide there)
    B2 = np.asarray([[4., 1.], [1., 3.]], np.float32)
    L = paddle.linalg.cholesky(paddle.to_tensor(B2))
    inv = paddle.linalg.cholesky_inverse(L)
    np.testing.assert_allclose(inv.numpy() @ B2, np.eye(2), atol=1e-5)
    U = paddle.to_tensor(np.linalg.cholesky(B2).T.astype(np.float32))
    inv_u = paddle.linalg.cholesky_inverse(U, upper=True)
    np.testing.assert_allclose(inv_u.numpy() @ B2, np.eye(2), atol=1e-5)

    # ormqr vs LAPACK Q
    B = np.random.RandomState(0).rand(5, 3).astype(np.float32)
    res = sla.qr(B, mode="raw")
    h = np.asarray(res[0][0], np.float32)
    tau = np.asarray(res[0][1], np.float32)
    y = np.random.RandomState(1).rand(5, 2).astype(np.float32)
    out = paddle.linalg.ormqr(paddle.to_tensor(h), paddle.to_tensor(tau),
                              paddle.to_tensor(y)).numpy()
    Q = np.linalg.qr(B, mode="complete")[0]
    np.testing.assert_allclose(out, Q @ y, atol=1e-5)

    # randomized low-rank SVD reconstructs a rank-2 matrix
    R = np.random.RandomState(2)
    M = (R.rand(10, 2) @ R.rand(2, 8)).astype(np.float32)
    u, s, v = paddle.linalg.svd_lowrank(paddle.to_tensor(M), q=4)
    recon = u.numpy() @ np.diag(s.numpy()) @ v.numpy().T
    np.testing.assert_allclose(recon, M, atol=1e-4)


def test_set_resize_sigmoid_methods():
    x = paddle.to_tensor(np.asarray([1., 2., 3., 4.], np.float32))
    x.resize_([2, 3])                 # grows with zeros
    assert x.shape == [2, 3] and x.numpy()[1, 2] == 0.0
    x.set_(paddle.to_tensor(np.ones((2,), np.float32)))
    np.testing.assert_allclose(x.numpy(), [1., 1.])
    s = paddle.to_tensor(np.asarray([0.0], np.float32))
    np.testing.assert_allclose(s.sigmoid().numpy(), [0.5])
    s.sigmoid_()
    np.testing.assert_allclose(s.numpy(), [0.5])
