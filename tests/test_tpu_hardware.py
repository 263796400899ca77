"""Hardware validation for the Pallas kernels on a REAL TPU chip.

Interpret mode enforces none of Mosaic's tiling rules: flash attention
once passed every interpret-mode test and failed the (8, 128) block rule
on its first chip run.  These tests compile and run the actual kernels.
They are opt-in (the suite runs on the CPU), and once opted in a missing
TPU is a failure, not a skip.  Through the chip tool:

    chiprun -- env PADDLE_TPU_HW_TESTS=1 python -m pytest \
        tests/test_tpu_hardware.py -q -p no:cacheprovider
"""
import os

import numpy as np
import pytest

if not os.environ.get("PADDLE_TPU_HW_TESTS"):
    pytest.skip("hardware tests opt-in via PADDLE_TPU_HW_TESTS=1 "
                "(suite conftest pins CPU)", allow_module_level=True)

import jax
import jax.numpy as jnp

if jax.default_backend() != "tpu":
    raise RuntimeError(
        "PADDLE_TPU_HW_TESTS=1 asks for the chip and JAX resolved to "
        f"{jax.default_backend()!r}")

from paddle_tpu.ops.pallas import flash_attention as FA
from paddle_tpu.ops.pallas import fused_norms as FN


def _rand(shape, seed, dtype=jnp.bfloat16):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


@pytest.mark.parametrize("b,s,h,hk,d,causal", [
    (2, 256, 4, 4, 64, True),
    (1, 512, 8, 2, 128, True),   # GQA group 4
    (2, 128, 4, 1, 64, False),   # MQA
])
def test_flash_attention_on_tpu(b, s, h, hk, d, causal):
    q = _rand((b, s, h, d), 0)
    k = _rand((b, s, hk, d), 1)
    v = _rand((b, s, hk, d), 2)
    assert FA.use_flash(q, k, causal)
    out = jax.jit(lambda q, k, v: FA.attention(q, k, v, causal))(q, k, v)
    ref = FA._ref_attention(q, k, v, causal)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < 0.06, err


def test_flash_attention_backward_on_tpu():
    q = _rand((1, 256, 4, 64), 0)
    k = _rand((1, 256, 4, 64), 1)
    v = _rand((1, 256, 4, 64), 2)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    g = jax.jit(jax.grad(loss(lambda q, k, v: FA._flash_attention(True, q, k, v)),
                         argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss(lambda q, k, v: FA._ref_attention(q, k, v, True)),
                          argnums=(0, 1, 2)))(q, k, v)
    for a, r in zip(g, gr):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - r.astype(jnp.float32))))
        assert err < 0.15, err


def test_ineligible_shape_falls_back():
    q = _rand((1, 100, 4, 64), 0)  # seq not /128
    assert not FA.use_flash(q, q, True)
    out = FA.attention(q, q, q, True)  # must not raise
    assert out.shape == q.shape


def test_fused_norms_on_tpu():
    x = _rand((16, 512), 0)
    w = jnp.ones((512,), jnp.bfloat16)
    b = jnp.zeros((512,), jnp.bfloat16)
    assert FN.rms_norm_fused.supports(x.shape, "bfloat16")
    y = jax.jit(lambda x, w: FN._rms_pallas(1e-6, x, w))(x, w)
    yr = FN._rms_ref(x, w, 1e-6)
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32)
                                 - yr.astype(jnp.float32)))) < 1e-2
    assert FN.layer_norm_fused.supports(x.shape, "bfloat16")
    y2 = jax.jit(lambda x, w, b: FN._ln_pallas(1e-6, x, w, b))(x, w, b)
    y2r = FN._ln_ref(x, w, b, 1e-6)
    assert float(jnp.max(jnp.abs(y2.astype(jnp.float32)
                                 - y2r.astype(jnp.float32)))) < 1e-2


def test_varlen_flash_attention_on_tpu():
    """Varlen kernel family lowers and matches the segment-masked oracle on
    real hardware (fwd + grads)."""
    from paddle_tpu.ops.pallas import flash_attention_varlen as FAVL

    cu = jnp.asarray([0, 200, 520, 640], jnp.int32)
    T, H, D = 640, 4, 64
    q = _rand((T, H, D), 0)
    k = _rand((T, H, D), 1)
    v = _rand((T, H, D), 2)
    assert FAVL.use_varlen_flash(q, k, True)
    sm = 1.0 / float(D) ** 0.5

    def oracle(q, k, v):
        qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
        pos = jnp.arange(T)
        seg = jnp.searchsorted(cu, pos, side="right") - 1
        ok = (seg[:, None] == seg[None, :]) & (pos[:, None] >= pos[None, :])
        s = jnp.einsum("qhd,khd->hqk", qf, kf) * sm
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vf)

    out = jax.jit(lambda q, k, v: FAVL._varlen_attention(
        True, sm, q, k, v, cu, cu))(q, k, v)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - oracle(q, k, v))))
    assert err < 0.06, err

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    g = jax.jit(jax.grad(loss(lambda q, k, v: FAVL._varlen_attention(
        True, sm, q, k, v, cu, cu)), argnums=(0, 1, 2)))(q, k, v)
    gr = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(g, gr):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - r.astype(jnp.float32))))
        assert err < 0.15, err


def test_capture_step_trains_on_tpu():
    """jit.capture_step (r4): the whole dygraph step compiles and trains
    on the real chip — one launch per step, loss decreasing."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(256, 512), nn.ReLU(), nn.Linear(512, 64))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(64, 256).astype(np.float32))
    y = paddle.to_tensor(rng.randn(64, 64).astype(np.float32))

    def step(x, y):
        loss = F.mse_loss(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    cap = paddle.jit.capture_step(step, models=net, optimizers=opt)
    first = float(cap(x, y).numpy())
    for _ in range(10):
        last = float(cap(x, y).numpy())
    assert last < first, (first, last)


def test_speculative_decode_on_tpu():
    """Speculative decoding compiles and preserves greedy exactness on
    the real chip."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                         speculative_generate)

    cfg = LlamaConfig.tiny(vocab=128, hidden=128, layers=2, heads=4,
                           ffn=256)
    paddle.seed(0)
    target = LlamaForCausalLM(cfg)
    paddle.seed(9)
    draft = LlamaForCausalLM(LlamaConfig.tiny(vocab=128, hidden=64,
                                              layers=1, heads=4, ffn=128))
    ids = paddle.to_tensor(np.asarray([[5, 9, 2, 7]]), dtype="int64")
    ref = target.generate(ids, max_new_tokens=8, temperature=0.0).numpy()
    spec = speculative_generate(target, draft, ids, max_new_tokens=8,
                                gamma=3, temperature=0.0).numpy()
    np.testing.assert_array_equal(spec, ref)


@pytest.mark.parametrize("H,Hkv,D,bs,nblk", [
    (16, 16, 128, 64, 8),    # the serving-decode bench shape family
    (8, 4, 64, 16, 5),       # GQA
])
def test_paged_decode_on_tpu(H, Hkv, D, bs, nblk):
    """The r5 paged-KV decode kernel must lower and match the dense
    composition on real hardware (interpret mode cannot enforce Mosaic
    tiling — the module's founding lesson)."""
    from paddle_tpu.ops.pallas import paged_attention as PA

    rng = np.random.RandomState(3)
    B = 2
    num_blocks = B * nblk
    q = jnp.asarray(rng.randn(B, H, D), jnp.bfloat16)
    kc = jnp.asarray(rng.randn(num_blocks, Hkv, bs, D), jnp.bfloat16)
    vc = jnp.asarray(rng.randn(num_blocks, Hkv, bs, D), jnp.bfloat16)
    bt = jnp.asarray(rng.permutation(num_blocks).reshape(B, nblk),
                     jnp.int32)
    lengths = jnp.asarray([nblk * bs - 7, bs + 3], jnp.int32)
    assert PA.ineligible(H, Hkv, D, bs, jnp.bfloat16) is None
    out = jax.jit(PA.paged_decode_attention)(q, kc, vc, bt, lengths)
    ref = PA.paged_decode_reference(q, kc, vc, bt, lengths)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < 0.06, err


def test_varlen_prefill_blha_on_tpu():
    """blha prefill riding the varlen flash kernel, on-chip."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import functional as IF

    rng = np.random.RandomState(4)
    H, D, bs, nblk = 8, 128, 64, 4
    num_blocks = 16
    lens = np.array([130, 70], np.int32)
    tok = int(lens.sum())
    qkv = paddle.to_tensor(
        jnp.asarray(rng.randn(tok, 3 * H * D), jnp.bfloat16))
    bt = paddle.to_tensor(rng.choice(num_blocks, 2 * nblk, replace=False)
                          .reshape(2, nblk).astype(np.int32))
    kc = paddle.to_tensor(
        jnp.asarray(rng.randn(num_blocks, H, bs, D), jnp.bfloat16))
    vc = paddle.to_tensor(
        jnp.asarray(rng.randn(num_blocks, H, bs, D), jnp.bfloat16))
    paddle.set_flags({"use_pallas_kernels": True})
    out, _, _, _ = IF.block_multihead_attention(
        qkv, kc, vc, seq_lens_encoder=lens,
        seq_lens_decoder=np.zeros(2, np.int32), seq_lens_this_time=lens,
        block_tables=bt, block_size=bs)
    paddle.set_flags({"use_pallas_kernels": False})
    ref, _, _, _ = IF.block_multihead_attention(
        qkv, paddle.to_tensor(kc._data), paddle.to_tensor(vc._data),
        seq_lens_encoder=lens, seq_lens_decoder=np.zeros(2, np.int32),
        seq_lens_this_time=lens, block_tables=bt, block_size=bs)
    paddle.set_flags({"use_pallas_kernels": True})
    err = float(np.max(np.abs(out.numpy().astype(np.float32)
                              - ref.numpy().astype(np.float32))))
    assert err < 0.06, err


# ---------------------------------------------------------------------------
# serving kernels at the shapes chip_smoke.py launches
# ---------------------------------------------------------------------------

import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chip_smoke import ragged_case  # noqa: E402  (the smoke's own case)


def _max_err(out, ref, live):
    return float(jnp.max(jnp.abs(out[:live].astype(jnp.float32)
                                 - ref[:live].astype(jnp.float32))))


@pytest.mark.parametrize("H,Hkv,dtype,tol", [
    # bf16 pages: scores accumulate in f32 from exact bf16 products; the
    # probabilities are rounded to bf16 for the PV matmul and the output
    # to bf16 (2^-9 relative each, on values of order 1-3)
    (32, 32, jnp.bfloat16, 2e-2),   # LLaMA-7B / OLMoE: MHA, G = 1
    (32, 4, jnp.bfloat16, 2e-2),    # GQA 32/4 (Trinity-Mini's shape)
    # f32 pages: the MXU takes f32 operands in bf16 passes, so this
    # bound is the bf16 one; an f32-exact kernel would sit near 1e-5
    (32, 32, jnp.float32, 2e-2),
])
def test_ragged_kernel_on_tpu(H, Hkv, dtype, tol):
    """The serving kernel at head width 128, block size 16: G = H/Hkv
    score rows a query (one for MHA), compared with the dense-gather
    reference computed in float32 at highest matmul precision."""
    from paddle_tpu.ops.pallas import paged_attention as PA

    D, bs, nblk = 128, 16, 8
    rng = np.random.RandomState(5)
    q, bt, cu, kvl, num_blocks, live = ragged_case(
        rng, H, D, bs, nblk, dtype)
    kc = jnp.asarray(rng.randn(num_blocks, Hkv, bs, D), dtype)
    vc = jnp.asarray(rng.randn(num_blocks, Hkv, bs, D), dtype)
    assert PA.ineligible(H, Hkv, D, bs, dtype, launch=(
        bt.shape[0], nblk, num_blocks)) is None
    out = jax.jit(PA.ragged_paged_attention_packed)(
        q, kc, vc, bt, cu, kvl)
    with jax.default_matmul_precision("highest"):
        ref = PA.ragged_paged_reference(
            q.astype(jnp.float32), kc.astype(jnp.float32),
            vc.astype(jnp.float32), bt, cu, kvl)
    err = _max_err(out, ref, live)
    print(f"ragged H={H} Hkv={Hkv} {jnp.dtype(dtype).name}: "
          f"max abs err {err:.3e}")
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    assert err < tol, err


def test_latent_kernel_on_tpu():
    """The latent-attention kernel at sarvam-105b-ep4's sizes (64 heads, a
    576-number row stored 640 wide, six layers in one pool, block 16):
    a 300-token chunk resumed at 1,000 cached rows, decode rows and a
    row of no keys, against the dense-gather oracle in float32."""
    from paddle_tpu.ops.pallas import mla_attention as MLA

    G, row, dc, bs, nblk, L, nb = 64, 576, 512, 16, 96, 6, 600
    rng = np.random.RandomState(7)
    rows = [(300, 1300), (1, 1), (0, 0), (1, 777), (1, 1536), (5, 40)]
    Tq = 320
    cu = np.zeros(33, np.int32)
    kvl = np.zeros(32, np.int32)
    bt = np.zeros((33, nblk), np.int32)
    free = iter(rng.permutation(np.arange(1, nb)))
    for r, (n, k) in enumerate(rows):
        cu[r + 1] = cu[r] + n
        kvl[r] = k
        for p in range(-(-k // bs)):
            bt[r, p] = next(free)
    cu[len(rows) + 1:] = cu[len(rows)]
    live = int(cu[len(rows)])
    q = jnp.asarray(rng.randn(Tq, G, row), jnp.bfloat16)
    pool = jnp.asarray(rng.randn(L, nb, bs, MLA.page_width(row)) * 0.5,
                       jnp.bfloat16).at[..., row:].set(0)
    args = (jnp.asarray(bt), jnp.asarray(cu), jnp.asarray(kvl))
    out = jax.jit(lambda q, pool, bt, cu, kvl:
                  MLA.ragged_latent_attention_packed(
                      q, pool, 4, bt, cu, kvl, latent_dim=dc,
                      sm_scale=0.05))(q, pool, *args)
    with jax.default_matmul_precision("highest"):
        ref = MLA.mla_ragged_reference(
            q.astype(jnp.float32), pool[4].astype(jnp.float32), *args,
            latent_dim=dc, sm_scale=0.05)
    err = _max_err(out, ref, live)
    print(f"latent kernel: max abs err {err:.3e} over {live} tokens")
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    assert not bool(jnp.any(out[live:]))        # padding reads zero
    # scores in f32 from exact bf16 products, probabilities and the
    # output rounded to bf16 (2^-9 relative, on values of order 1)
    assert err < 2e-2, err


def test_grouped_expert_kernel_on_tpu():
    """32 experts of 4096 x 2048, 4,608 sorted rows of which about a
    quarter are live, one expert empty and one over a row tile: the
    kernel against lax.ragged_dot, both halves of the SwiGLU."""
    from paddle_tpu.ops.pallas import grouped_matmul as GM

    rng = np.random.RandomState(3)
    sizes = rng.multinomial(1100, np.ones(32) / 32).astype(np.int32)
    sizes[5], sizes[9] = 0, 150
    n = int(sizes.sum())
    x = jnp.asarray(rng.randn(4608, 4096), jnp.bfloat16)
    wg = jnp.asarray(rng.randn(32, 4096, 2048) * 0.02, jnp.bfloat16)
    wu = jnp.asarray(rng.randn(32, 4096, 2048) * 0.02, jnp.bfloat16)
    wd = jnp.asarray(rng.randn(32, 2048, 4096) * 0.02, jnp.bfloat16)
    gs = jnp.asarray(sizes)
    for use_kernel in (True, False):
        a = jax.jit(lambda *t: GM.grouped_swiglu(
            *t, use_kernel=use_kernel))(x, wg, wu, gs)
        y = jax.jit(lambda *t: GM.grouped_matmul(
            *t, use_kernel=use_kernel))(a, wd, gs)
        if use_kernel:
            got = (a[:n].astype(jnp.float32), y[:n])
    err_a = float(jnp.max(jnp.abs(got[0] - a[:n].astype(jnp.float32))))
    err_y = float(jnp.max(jnp.abs(got[1] - y[:n])))
    print(f"grouped kernel against ragged_dot: swiglu {err_a:.3e}, "
          f"down {err_y:.3e}")
    assert err_a < 2e-2 and err_y < 5e-2, (err_a, err_y)


def _timed(fn, *args, n=10):
    """Median milliseconds of fn(*args) over n calls, compile left out."""
    import time
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(n):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t) * 1e3)
    return sorted(ts)[len(ts) // 2]


@pytest.mark.parametrize("window", [None, 4096])
def test_group_7_kernel_and_its_window_on_tpu(window):
    """28 query heads over 4 K/V heads (a decode item is 7 score rows,
    not a multiple of the sublane 8) at the served shapes: pools of all
    layers read at a layer index, a [33, 1024] table.  A 512-token chunk
    resumed at 9,000 keys, decode rows under, at and far past the
    window, a row of no keys; a window layer's table names the null
    page below each row's window.  Against the dense-gather reference in
    float32; the launch is timed."""
    _ragged_at_served_shapes(
        28, 4, window,
        [(512, 9512), (1, 1), (0, 0), (1, 4096), (1, 4097), (1, 12000),
         (1, 300), (5, 40), (1, 14999)], (6000, 15000))


@pytest.mark.parametrize("H,window", [(48, None), (64, 512)])
def test_groups_6_and_8_of_one_model_on_tpu(H, window):
    """Laguna-XS.2's two launches over 8 K/V heads: 48 query heads over
    whole contexts (a decode item is 6 score rows) and 64 over a window
    of 512, 32 pages, SHORTER than the 512-token chunk beside it (an
    item's page range ends before the chunk does); decode rows under, at
    and far past the window."""
    _ragged_at_served_shapes(
        H, 8, window,
        [(512, 9512), (1, 1), (0, 0), (1, 512), (1, 513), (1, 12000),
         (1, 300), (5, 40), (1, 13311), (40, 600)], (2000, 13000))


def _ragged_at_served_shapes(H, Hkv, window, rows, decode_keys):
    """The ragged kernel at a served model's heads over the pools of all
    layers, against the plain reference row by row, then timed at 28
    decode rows of ``decode_keys`` keys."""
    from paddle_tpu.ops.pallas import paged_attention as PA

    D, bs, nblk, L, nb = 128, 16, 1024, 2, 4000
    rng = np.random.RandomState(11)
    Tq = 576
    cu = np.zeros(33, np.int32)
    kvl = np.zeros(32, np.int32)
    bt = np.zeros((33, nblk), np.int32)
    free = iter(rng.permutation(np.arange(1, nb)))
    for r, (n, k) in enumerate(rows):
        cu[r + 1] = cu[r] + n
        kvl[r] = k
        first = 0 if window is None else max(0, k - n - window + 1) // bs
        for p in range(first, -(-k // bs)):
            bt[r, p] = next(free)
    cu[len(rows) + 1:] = cu[len(rows)]
    live = int(cu[len(rows)])
    q = jnp.asarray(rng.randn(Tq, H, D), jnp.bfloat16)
    kc = jnp.asarray(rng.randn(L, nb, Hkv, bs, D), jnp.bfloat16)
    vc = jnp.asarray(rng.randn(L, nb, Hkv, bs, D), jnp.bfloat16)
    # the null page, which a window layer's walk must not read
    kc, vc = kc.at[:, 0].set(100.0), vc.at[:, 0].set(100.0)
    args = (jnp.asarray(bt), jnp.asarray(cu), jnp.asarray(kvl))
    assert PA.ineligible(H, Hkv, D, bs, jnp.bfloat16,
                         launch=(33, nblk, nb)) is None
    fn = jax.jit(lambda q, kc, vc, bt, cu, kvl:
                 PA.ragged_paged_attention_packed(
                     q, kc, vc, bt, cu, kvl, layer=1, window=window))
    out = fn(q, kc, vc, *args)
    # the plain reference row by row (the dense-gather oracle would
    # gather [576, 16384] keys a token): a row's keys in order, the whole
    # score matrix, the causal and the window mask
    ref = np.zeros((Tq, H, D), np.float32)
    with jax.default_matmul_precision("highest"):
        for r, (n, k) in enumerate(rows):
            if n == 0 or k == 0:
                continue
            pages = jnp.asarray(bt[r, :-(-k // bs)])
            kr = kc[1][pages].astype(jnp.float32).transpose(
                0, 2, 1, 3).reshape(-1, Hkv, D)[:k]
            vr = vc[1][pages].astype(jnp.float32).transpose(
                0, 2, 1, 3).reshape(-1, Hkv, D)[:k]
            pos = k - n + jnp.arange(n)
            key = jnp.arange(k)
            see = key[None, :] <= pos[:, None]
            if window is not None:
                see &= key[None, :] > pos[:, None] - window
            qr = q[cu[r]:cu[r] + n].astype(jnp.float32).reshape(
                n, Hkv, H // Hkv, D)
            sc = jnp.einsum("qhgd,khd->hgqk", qr, kr) / np.sqrt(D)
            pr = jax.nn.softmax(jnp.where(see[None, None], sc, -jnp.inf), -1)
            vr = jnp.where(see.any(0)[:, None, None], vr, 0.0)
            ref[cu[r]:cu[r] + n] = np.asarray(jnp.einsum(
                "hgqk,khd->qhgd", pr, vr)).reshape(n, H, D)
    ref = jnp.asarray(ref)
    err = _max_err(out, ref, live)
    ms = _timed(fn, q, kc, vc, *args)
    print(f"group {H // Hkv} kernel window={window}: max abs err {err:.3e} "
          f"over {live} tokens, {ms:.3f} ms a launch")
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    assert not bool(jnp.any(out[live:]))
    assert err < 2e-2, err
    # 28 decode rows at long keys, as a step of the cell holds them
    rows = [(1, int(k)) for k in rng.randint(*decode_keys, 28)]
    cu = np.minimum(np.arange(33), 28).astype(np.int32)
    kvl = np.zeros(32, np.int32)
    bt = np.zeros((33, nblk), np.int32)
    for r, (n, k) in enumerate(rows):
        kvl[r] = k
        first = 0 if window is None else max(0, k - n - window + 1) // bs
        bt[r, first:-(-k // bs)] = rng.randint(1, nb, -(-k // bs) - first)
    q32 = q[:32]
    ms = _timed(fn, q32, kc, vc, jnp.asarray(bt), jnp.asarray(cu),
                jnp.asarray(kvl))
    print(f"group {H // Hkv} kernel window={window}: 28 decode rows at "
          f"{decode_keys} keys {ms:.3f} ms a launch")


def test_reglu_expert_kernel_on_tpu():
    """64 experts of 2560 x 768 with ReLU on the gate, 3,456 sorted rows
    (a 576-token step's pairs), one expert empty and one over a row
    tile: the kernel against lax.ragged_dot, both halves; timed at the
    step's pairs and at a decode step's 192."""
    from paddle_tpu.ops.pallas import grouped_matmul as GM

    rng = np.random.RandomState(3)
    wg = jnp.asarray(rng.randn(64, 2560, 768) * 0.02, jnp.bfloat16)
    wu = jnp.asarray(rng.randn(64, 2560, 768) * 0.02, jnp.bfloat16)
    wd = jnp.asarray(rng.randn(64, 768, 2560) * 0.02, jnp.bfloat16)
    for M, pairs in ((3456, 3456), (256, 192)):
        sizes = rng.multinomial(pairs - 150, np.ones(64) / 64).astype(
            np.int32)
        sizes[9] += 150 - sizes[5]
        sizes[5] = 0
        n = int(sizes.sum())
        x = jnp.asarray(rng.randn(M, 2560), jnp.bfloat16)
        gs = jnp.asarray(sizes)
        fns = {}
        for use_kernel in (True, False):
            up = jax.jit(lambda *t, k=use_kernel: GM.grouped_reglu(
                *t, use_kernel=k))
            down = jax.jit(lambda *t, k=use_kernel: GM.grouped_matmul(
                *t, use_kernel=k))
            a = up(x, wg, wu, gs)
            y = down(a, wd, gs)
            fns[use_kernel] = (up, down, a, y)
        (up, down, a, y), (_, _, a0, y0) = fns[True], fns[False]
        err_a = float(jnp.max(jnp.abs(a[:n].astype(jnp.float32)
                                      - a0[:n].astype(jnp.float32))))
        err_y = float(jnp.max(jnp.abs(y[:n] - y0[:n])))
        print(f"reglu kernel against ragged_dot at {n} pairs: reglu "
              f"{err_a:.3e}, down {err_y:.3e}; {_timed(up, x, wg, wu, gs):.3f}"
              f" + {_timed(down, a, wd, gs):.3f} ms")
        assert err_a < 2e-2 and err_y < 5e-2, (err_a, err_y)
        assert float(jnp.mean(a[:n] == 0)) > 0.3     # ReLU shut these


def test_256_small_experts_kernel_on_tpu():
    """256 experts of 2048 x 512 (a whole 2 MB matrix a block): 4,608
    sorted rows of a 576-token step's pairs, about 17 a group, some
    groups empty and one over a row tile, and a decode step's 256 pairs
    over about 160 experts: the kernel against lax.ragged_dot, both
    halves of the SwiGLU; timed."""
    from paddle_tpu.ops.pallas import grouped_matmul as GM

    rng = np.random.RandomState(5)
    wg = jnp.asarray(rng.randn(256, 2048, 512) * 0.02, jnp.bfloat16)
    wu = jnp.asarray(rng.randn(256, 2048, 512) * 0.02, jnp.bfloat16)
    wd = jnp.asarray(rng.randn(256, 512, 2048) * 0.02, jnp.bfloat16)
    for M, pairs in ((4608, 4320), (256, 256)):
        sizes = rng.multinomial(pairs - 150, np.ones(256) / 256).astype(
            np.int32)
        sizes[9] += 150 - sizes[5]
        sizes[5] = 0
        n = int(sizes.sum())
        x = jnp.asarray(rng.randn(M, 2048), jnp.bfloat16)
        gs = jnp.asarray(sizes)
        fns = {}
        for use_kernel in (True, False):
            up = jax.jit(lambda *t, k=use_kernel: GM.grouped_swiglu(
                *t, use_kernel=k))
            down = jax.jit(lambda *t, k=use_kernel: GM.grouped_matmul(
                *t, use_kernel=k))
            a = up(x, wg, wu, gs)
            y = down(a, wd, gs)
            fns[use_kernel] = (up, down, a, y)
        (up, down, a, y), (_, _, a0, y0) = fns[True], fns[False]
        err_a = float(jnp.max(jnp.abs(a[:n].astype(jnp.float32)
                                      - a0[:n].astype(jnp.float32))))
        err_y = float(jnp.max(jnp.abs(y[:n] - y0[:n])))
        print(f"256 experts against ragged_dot at {n} pairs over "
              f"{int((sizes > 0).sum())} experts: swiglu {err_a:.3e}, down "
              f"{err_y:.3e}; {_timed(up, x, wg, wu, gs):.3f} + "
              f"{_timed(down, a, wd, gs):.3f} ms")
        assert err_a < 2e-2 and err_y < 5e-2, (err_a, err_y)


def _int8_page_kernel_err(H, Hkv, pool=None):
    """The int8-page kernel against its reference on the smoke's case,
    over a pool of ``pool`` pages (default: the pages the case uses)."""
    from paddle_tpu.ops.pallas import paged_attention as PA

    D, bs, nblk = 128, 32, 4
    rng = np.random.RandomState(6)
    q, bt, cu, kvl, num_blocks, live = ragged_case(
        rng, H, D, bs, nblk, jnp.bfloat16)
    seg, rel = PA.ragged_segments(cu, kvl, q.shape[0])
    num_blocks = pool or num_blocks
    kc = jnp.asarray(rng.randint(-127, 128, (num_blocks, Hkv, bs, D),
                                 dtype=np.int8))
    vc = jnp.asarray(rng.randint(-127, 128, (num_blocks, Hkv, bs, D),
                                 dtype=np.int8))
    ks = jnp.asarray(rng.uniform(0.5, 1.5, (num_blocks, Hkv)) / 127.0,
                     jnp.float32)
    vs = jnp.asarray(rng.uniform(0.5, 1.5, (num_blocks, Hkv)) / 127.0,
                     jnp.float32)
    assert PA.ineligible(H, Hkv, D, bs, jnp.int8, launch=(
        bt.shape[0], nblk, num_blocks)) is None
    out = jax.jit(PA.ragged_paged_attention_quant_packed)(
        q, kc, vc, ks, vs, bt, cu, kvl)
    with jax.default_matmul_precision("highest"):
        ref = PA.ragged_paged_reference_quant_segrel(
            q.astype(jnp.float32), kc, vc, ks, vs, bt, seg, rel)
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    return _max_err(out, ref, live)


@pytest.mark.parametrize("H,Hkv", [(32, 32), (32, 4)])
def test_ragged_int8_page_kernel_on_tpu(H, Hkv):
    """Int8 pages at block size 32 (the int8 (32, 128) tile), scales in
    SMEM.  The kernel dequantizes to f32 before both matmuls, so the
    bound is the MXU's bf16-pass bound on f32 operands of order |int8 *
    scale| ~ 1."""
    err = _int8_page_kernel_err(H, Hkv)
    print(f"ragged int8 pages H={H} Hkv={Hkv}: max abs err {err:.3e}")
    assert err < 2e-2, err


def test_int8_page_kernel_at_the_scalar_memory_claim():
    """The largest int8 page pool ``ineligible`` admits beside this
    launch's block table compiles (both scale pools ride in scalar
    memory; 1025 pages overran it at compile time), and the claim is
    withdrawn eight pages later."""
    from paddle_tpu.ops.pallas import paged_attention as PA

    launch = (4, 4)                     # ragged_case: table rows, nblk

    def why(n):
        return PA.ineligible(32, 32, 128, 32, jnp.int8, launch=(*launch, n))

    pool = max(n for n in range(8, 2048, 8) if why(n) is None)
    assert "scalar memory" in why(pool + 8)
    need = PA.scalar_prefetch_bytes(*launch, pool, 32, True)
    err = _int8_page_kernel_err(32, 32, pool=pool)
    print(f"int8 pages, pool of {pool}: prefetched operands "
          f"{need >> 10} KiB, max abs err {err:.3e}")
    assert err < 2e-2, err


@pytest.mark.parametrize("K,N", [(4096, 4096), (4096, 11008),
                                 (11008, 4096), (4096, 32000)])
def test_quant_matmul_bf16_activations_on_tpu(K, N):
    """The fused dequant matmul as a bf16 engine launches it at decode:
    M = max_num_seqs = 8 rows of bf16 activations against LLaMA-7B's
    projection, MLP and head widths.  The oracle is the dense fake-quant
    product in float32 at highest precision.  The kernel upcasts x and
    the dequantized block to f32 and the MXU takes them in bf16 passes:
    a K-term sum of products of order 1 * 0.02 accumulates a relative
    error of about 2^-9, so the bound scales with the output's own
    magnitude (sqrt(K) * 0.02 * 0.6)."""
    from paddle_tpu.ops.pallas import quant_matmul as QM

    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(8, K), jnp.bfloat16)
    w = jnp.asarray(rng.randn(K, N) * 0.02, jnp.float32)
    q, s = QM.quantize_weight(w, "int8")
    assert QM.ineligible(K, N, "int8") is None
    out = jax.jit(lambda x, q, s: QM.matmul(x, q, s, weight_dtype="int8"))(
        x, q, s)
    with jax.default_matmul_precision("highest"):
        ref = QM.reference_matmul(x, q, s, "int8")
    scale = float(jnp.max(jnp.abs(ref)))
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"quant_matmul K={K} N={N}: max abs err {err:.3e} "
          f"(max |ref| {scale:.3f})")
    assert out.dtype == jnp.float32 and out.shape == (8, N)
    assert err < 1e-2 * scale, (err, scale)


# ---------------------------------------------------------------------------
# LLMEngine at LLaMA-7B widths (depth cut to 2), every path the smoke's
# float engine does not take
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def llama7b_width_model():
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.llama_7b()
    cfg.num_hidden_layers = 2           # depth cut; every width as published
    paddle.seed(0)
    return LlamaForCausalLM(cfg).to(dtype="bfloat16")


def _serve(engine, vocab, n_new=12):
    """Three requests through engine.run(): a prompt of one bucket, a
    prompt chunked over two launches, a short one.  Returns the
    generated tokens in submission order."""
    rng = np.random.RandomState(11)
    outs = {}
    rids = [engine.add_request(rng.randint(1, vocab, n).tolist(),
                               max_new_tokens=n_new,
                               on_finish=lambda o: outs.update({o.rid: o}))
            for n in (20, 150, 5)]
    engine.run()
    for rid in rids:
        out = outs[rid]
        # a non-finite logit row is quarantined, which ends the request
        # with its own finish reason
        assert out.finish_reason == "length", out.finish_reason
        assert len(out.generated) == n_new
        assert all(0 <= t < vocab for t in out.generated)
    return [outs[rid].generated for rid in rids]


@pytest.mark.parametrize("name,kw,matmul", [
    ("bf16 pages", {}, "xla-dense"),
    ("int8 pages", {"kv_dtype": "int8", "block_size": 32}, "xla-dense"),
    ("int8 weights", {"weight_dtype": "int8"}, "pallas-quant"),
    ("window 4", {"decode_window": 4}, "xla-dense"),
    ("window 4, int8 pages",
     {"decode_window": 4, "kv_dtype": "int8", "block_size": 32},
     "xla-dense"),
    ("window 4, int8 weights",
     {"decode_window": 4, "weight_dtype": "int8"}, "pallas-quant"),
])
def test_engine_variants_on_tpu(llama7b_width_model, name, kw, matmul):
    """Each engine variant compiles its kernels on the chip (a refusal
    by Mosaic raises out of run()), takes no reference path, and serves
    finite tokens.  With donated page pools and a launch in flight under
    overlap=True this is also the first run of both off the CPU."""
    import gc

    from paddle_tpu.inference import LLMEngine

    kw = {"block_size": 16, **kw}
    eng = LLMEngine(llama7b_width_model, max_num_seqs=8, max_model_len=256,
                    max_prefill_tokens=128, **kw)
    assert eng.attention_path == "pallas", eng.attention_path
    assert eng.matmul_path == matmul, eng.matmul_path
    toks = _serve(eng, eng.config.vocab_size)
    paths = eng.paths()
    print(f"{name}: programs {sorted(paths['programs'])} on "
          f"{paths['devices']} ({paths['device_kind']}); first tokens "
          f"{[t[:4] for t in toks]}")
    assert all(p == {"attention": "pallas", "matmul": matmul}
               for p in paths["programs"].values()), paths
    if kw.get("decode_window", 1) > 1:
        assert "window:4" in paths["programs"], paths
    del eng
    gc.collect()


# ---------------------------------------------------------------------------
# four chips (chiprun --chips 4); skipped on a one-chip machine
# ---------------------------------------------------------------------------

four_chips = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs a four-chip host")


def _cli_engines(argv):
    """Engines built the way the frontend CLI builds them."""
    from paddle_tpu.inference.frontend import __main__ as cli

    args = cli._parser().parse_args(
        ["--model", "llama-7b", "--layers", "2", "--dtype", "bfloat16",
         "--max-model-len", "256", "--max-prefill-tokens", "128", *argv])
    make_engine = cli._build_engine(args, cli._model_config(args))
    return make_engine, args


@four_chips
def test_tp4_engine_on_tpu():
    """--tp 4: the ragged kernel inside shard_map, 8 heads a shard."""
    make_engine, _ = _cli_engines(["--tp", "4"])
    eng = make_engine()
    assert eng.attention_path == "pallas"
    for name, x in (("wq", eng.params["layers"]["wq"]),
                    ("wo", eng.params["layers"]["wo"]),
                    ("k pages", eng._kc)):
        shards = [(str(s.device), s.data.shape)
                  for s in x.addressable_shards]
        print(f"tp=4 {name}: {shards}")
        assert len({d for d, _ in shards}) == 4
    toks = _serve(eng, eng.config.vocab_size)
    paths = eng.paths()
    print(f"tp=4 programs {sorted(paths['programs'])} on "
          f"{paths['devices']}; first tokens {[t[:4] for t in toks]}")
    assert len(paths["devices"]) == 4
    assert all(p["attention"] == "pallas"
               for p in paths["programs"].values()), paths


@four_chips
def test_four_replicas_each_on_its_own_chip():
    """--replicas 4: every replica's weights and pages on a device of
    its own, and every replica serves."""
    import threading

    from paddle_tpu.inference.frontend import ReplicaRouter, build_replicas

    make_engine, _ = _cli_engines(["--replicas", "4"])
    runners = build_replicas(make_engine(0), make_engine, 4)
    devices = []
    for i, r in enumerate(runners):
        e = r.engine
        where = {str(d) for x in (e.params["layers"]["wq"], e._kc)
                 for d in x.devices()}
        print(f"replica {i}: weights and pages on {sorted(where)}")
        assert len(where) == 1
        devices.append(where.pop())
    assert len(set(devices)) == 4, devices
    # the stacked copy is built on the replica's device: only chip 0,
    # which holds the model itself, may peak above the others
    for d in jax.devices():
        m = d.memory_stats()
        print(f"{d}: {m['bytes_in_use'] / 1e9:.2f} GB in use, peak "
              f"{m['peak_bytes_in_use'] / 1e9:.2f} GB")
    router = ReplicaRouter(runners, policy="least").start()
    try:
        rng = np.random.RandomState(13)
        done = []
        for _ in range(8):              # together, so that load spreads
            ev = threading.Event()
            out = {}

            def deliver(e, ev=ev, out=out):
                if e[0] == "finish":
                    out["finish"] = e[1]
                    ev.set()
            router.submit(rng.randint(1, 32000, 30).tolist(),
                          deliver=deliver, max_new_tokens=8)
            done.append((ev, out))
        for ev, out in done:
            assert ev.wait(600.0), "request never finished"
            assert out["finish"].finish_reason == "length"
            assert len(out["finish"].generated) == 8
    finally:
        router.close()
    routed = router.router_counters()["routed_requests"]
    print(f"requests per replica: {routed}")
    assert all(n > 0 for n in routed), routed
    for i, e in enumerate(router.engines):
        paths = e.paths()
        print(f"replica {i}: programs {sorted(paths['programs'])} on "
              f"{paths['devices']}")
        assert paths["programs"] and all(
            p["attention"] == "pallas"
            for p in paths["programs"].values()), paths


@four_chips
def test_train_step_on_four_chips():
    """Three steps of the hybrid trainer over a dp=2 x tp=2 mesh, bf16,
    head width 128 so that the flash kernels run: the loss is finite and
    falls on a repeated batch."""
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.parallel import (
        HybridParallelConfig, build_mesh, build_train_step, init_opt_state,
        init_params, shard_opt_state, shard_params)

    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                      intermediate_size=2816, num_hidden_layers=4,
                      num_attention_heads=8, num_key_value_heads=8,
                      max_position_embeddings=512)
    hp = HybridParallelConfig(dp=2, pp=1, tp=2, dtype=jnp.bfloat16)
    mesh = build_mesh(hp)
    print(f"mesh {dict(mesh.shape)}: {[str(d) for d in mesh.devices.flat]}")
    params = shard_params(init_params(cfg, hp, seed=0), hp, mesh)
    opt = shard_opt_state(init_opt_state(params), hp, mesh)
    step = build_train_step(cfg, hp, mesh)
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 512)), jnp.int32)
    losses = []
    for _ in range(3):
        params, opt, loss = step(params, opt, tokens)
        losses.append(float(loss))
    print(f"losses {losses}")
    assert all(np.isfinite(x) for x in losses), losses
    assert losses[-1] < losses[0], losses


def _latent_launch(rows, nblk, nb, bs=16, seed=11):
    """(cu, kvl, bt) of a launch of ``rows`` [(n_q, kv_len)] under a
    32-row table of distinct shuffled pages, and its live tokens."""
    rng = np.random.RandomState(seed)
    cu = np.zeros(33, np.int32)
    kvl = np.zeros(32, np.int32)
    bt = np.zeros((33, nblk), np.int32)
    free = iter(rng.permutation(np.arange(1, nb)))
    for r, (n, k) in enumerate(rows):
        cu[r + 1] = cu[r] + n
        kvl[r] = k
        for p in range(-(-k // bs)):
            bt[r, p] = next(free)
    cu[len(rows) + 1:] = cu[len(rows)]
    return (jnp.asarray(bt), jnp.asarray(cu), jnp.asarray(kvl)), \
        int(cu[len(rows)])


def test_windowed_latent_kernel_on_tpu():
    """The latent kernel under a window at dots3-note-prev's sliding
    layers' sizes (64 heads, a 1,088-number row stored 1,152 wide, window
    513): a 300-token chunk resumed at 2,700 cached rows, decode rows
    under, at and far past the window."""
    from paddle_tpu.ops.pallas import mla_attention as MLA

    G, row, dc, nblk, nb = 64, 1088, 1024, 256, 900
    rows = [(300, 3000), (1, 1), (0, 0), (1, 513), (1, 514), (1, 4000),
            (5, 40)]
    args, live = _latent_launch(rows, nblk, nb)
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(320, G, row) * 0.5, jnp.bfloat16)
    pool = jnp.asarray(rng.randn(2, nb, 16, MLA.page_width(row)) * 0.5,
                       jnp.bfloat16).at[..., row:].set(0)
    out = jax.jit(lambda q, pool, bt, cu, kvl:
                  MLA.ragged_latent_attention_packed(
                      q, pool, 1, bt, cu, kvl, latent_dim=dc,
                      sm_scale=0.0625, window=513))(q, pool, *args)
    with jax.default_matmul_precision("highest"):
        ref = MLA.mla_ragged_reference(
            q.astype(jnp.float32), pool[1].astype(jnp.float32), *args,
            latent_dim=dc, sm_scale=0.0625, window=513)
    err = _max_err(out, ref, live)
    print(f"windowed latent kernel: max abs err {err:.3e}")
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    assert not bool(jnp.any(out[live:]))
    assert err < 3e-2, err


def test_indexer_and_selected_keys_on_tpu():
    """dots3-note-prev's full layers on the chip: the index-score kernel
    (64 heads of 128) against its oracle, the selection against a literal
    ``lax.top_k`` of the same scores (the same SET, 2,048 a query), and
    the latent kernel (128 heads on a 640-wide row) over the selected
    keys alone."""
    from paddle_tpu.ops.pallas import mla_attention as MLA
    from paddle_tpu.ops.pallas import paged_attention as PA

    nblk, nb = 256, 1200
    rows = [(300, 3000), (1, 2500), (1, 17), (77, 77), (1, 4000), (0, 0),
            (20, 1000)]
    Tq = 448
    args, live = _latent_launch(rows, nblk, nb)
    bt, cu, kvl = args
    rng = np.random.RandomState(5)
    qi = jnp.asarray(rng.randn(Tq, 64, 128), jnp.bfloat16)
    wi = jnp.asarray(rng.randn(Tq, 64) * 0.01, jnp.float32)
    ipool = jnp.asarray(rng.randn(2, nb, 16, 128), jnp.bfloat16)
    seg, rel = PA.ragged_segments(cu[:len(rows) + 1], kvl[:len(rows)], Tq)
    rel = jnp.where(seg < len(rows), rel, -1)
    vis = jnp.arange(nblk * 16)[None, :] <= rel[:, None]
    got = jax.jit(lambda q, w, pool: MLA.ragged_index_scores_packed(
        q, w, pool, 1, *args))(qi, wi, ipool)
    with jax.default_matmul_precision("highest"):
        want = MLA.index_scores_reference_segrel(
            qi.astype(jnp.float32), wi, ipool[1].astype(jnp.float32), bt,
            jnp.minimum(seg, 32))
    err = float(jnp.max(jnp.abs(jnp.where(vis, got - want, 0))))
    print(f"index scores: max abs err {err:.3e}")
    assert err < 2e-2, err
    mask = jax.jit(lambda s: MLA.select_mask(s, rel, 2048))(got)
    _, idx = jax.lax.top_k(jnp.where(vis, got, -jnp.inf), 2048)
    lit = jnp.zeros_like(vis).at[jnp.arange(Tq)[:, None], idx].set(
        True) & vis
    assert int(jnp.sum(mask != lit)) == 0
    assert int(mask[0].sum()) == 2048 and int(mask[300].sum()) == 2048
    q = jnp.asarray(rng.randn(Tq, 128, 576) * 0.5, jnp.bfloat16)
    pool = jnp.asarray(rng.randn(2, nb, 16, 640) * 0.5,
                       jnp.bfloat16).at[..., 576:].set(0)
    bias = jnp.where(mask, 0.0, -jnp.inf).astype(jnp.float32)
    out = jax.jit(lambda q, pool, bias: MLA.ragged_latent_attention_packed(
        q, pool, 1, *args, latent_dim=512, sm_scale=0.072,
        select=bias))(q, pool, bias)
    with jax.default_matmul_precision("highest"):
        ref = MLA.mla_ragged_reference(
            q.astype(jnp.float32), pool[1].astype(jnp.float32), *args,
            latent_dim=512, sm_scale=0.072, select=bias)
    err = _max_err(out, ref, live)
    print(f"selected-keys latent kernel: max abs err {err:.3e}")
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    assert err < 3e-2, err


# -- the state-space hybrid's kernels (models/phi4flash.py) -----------------

@pytest.mark.parametrize("tq,lens", [
    (192, [128] + [1] * 30 + [0]), (32, [1] * 29 + [0, 0, 0]),
    (64, [0, 40, 1, 0] + [0] * 28)])
def test_selective_scan_kernel_on_tpu(tq, lens):
    """The ragged selective scan at Phi-4-mini-flash's widths (d_inner
    5120, 16 states, nine layers' state of 33 slots): a 128-row chunk
    beside 30 decode rows, a decode-only launch, rows of no tokens and
    padding, against the XLA form in float32; slots nobody names and
    the other layers' states stay as they were; the launch is timed."""
    from paddle_tpu.ops.pallas import selective_scan as SS

    di, n, L, S = 5120, 16, 9, 33
    rng = np.random.RandomState(5)
    R = len(lens)
    cu = np.zeros(R + 1, np.int32)
    cu[1:] = np.cumsum(lens)
    real = rng.permutation(S - 1)[:R]
    slots = np.where(np.asarray(lens) > 0, real, S - 1).astype(np.int32)
    start = (rng.rand(R) < 0.3)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    args = (f(tq, di), jax.nn.softplus(f(tq, di) - 3.0),
            -jnp.exp(0.3 * f(n, di)), f(tq, n), f(tq, n), f(di))
    state = f(L, S, n, di)
    tail = (2, jnp.asarray(slots), jnp.asarray(cu), jnp.asarray(start))
    assert SS.ineligible(di, n) is None
    kern = jax.jit(lambda *a: SS.selective_scan(*a, use_kernel=True))
    ref = jax.jit(lambda *a: SS.selective_scan_reference(*a))
    y, s1 = kern(*args, state, *tail)
    y0, s0 = ref(*args, state, *tail)
    live = int(cu[-1])
    err = float(jnp.max(jnp.abs(y[:live] - y0[:live])))
    scale = float(jnp.max(jnp.abs(y0[:live])))
    assert err <= 2e-3 * max(scale, 1.0), (err, scale)
    assert not np.asarray(y[live:]).any()
    used = sorted(set(slots[np.asarray(lens) > 0].tolist()))
    np.testing.assert_allclose(np.asarray(s1[2, used]),
                               np.asarray(s0[2, used]), rtol=2e-3,
                               atol=2e-3)
    idle = sorted(set(range(S - 1)) - set(used))
    np.testing.assert_array_equal(np.asarray(s1[2, idle]),
                                  np.asarray(state[2, idle]))
    np.testing.assert_array_equal(np.asarray(s1)[[0, 1, 3, 8]],
                                  np.asarray(state)[[0, 1, 3, 8]])
    ms = _timed(kern, *args, state, *tail)
    print(f"selective scan tq={tq} rows={live}: {ms:.3f} ms a layer")


@pytest.mark.parametrize("window", [None, 512])
def test_differential_attention_widened_on_tpu(window):
    """Differential attention as the engine computes it (40 query heads
    of 64 widened with zeros to 128 over 10 cached heads of 128, the
    ragged kernel at scale 1/8) against its plain two-map form (20 key
    and value heads of 64, a softmax a query head), at the served pool
    shapes: a 128-token chunk resumed at 1,428 keys, decode rows under,
    at and past the window."""
    from paddle_tpu.models.phi4flash import widen
    from paddle_tpu.ops.pallas import paged_attention as PA

    nh, kvh, hd, bs, nblk, nb = 40, 20, 64, 16, 512, 3000
    rows = [(128, 1556), (1, 1), (0, 0), (1, 512), (1, 513), (1, 6000),
            (1, 300), (5, 40)]
    rng = np.random.RandomState(3)
    Tq = 192
    cu = np.zeros(33, np.int32)
    kvl = np.zeros(32, np.int32)
    bt = np.zeros((33, nblk), np.int32)
    free = iter(rng.permutation(np.arange(1, nb)))
    for r, (n, k) in enumerate(rows):
        cu[r + 1] = cu[r] + n
        kvl[r] = k
        first = 0 if window is None else max(0, k - n - window + 1) // bs
        for p in range(first, -(-k // bs)):
            bt[r, p] = next(free)
    cu[len(rows) + 1:] = cu[len(rows)]
    q = jnp.asarray(rng.randn(Tq, nh, hd), jnp.bfloat16)
    kc = jnp.asarray(rng.randn(1, nb, kvh // 2, bs, 2 * hd), jnp.bfloat16)
    vc = jnp.asarray(rng.randn(1, nb, kvh // 2, bs, 2 * hd), jnp.bfloat16)
    kc, vc = kc.at[:, 0].set(100.0), vc.at[:, 0].set(100.0)
    fn = jax.jit(lambda q, kc, vc, bt, cu, kvl:
                 PA.ragged_paged_attention_packed(
                     widen(q), kc, vc, bt, cu, kvl, layer=0, window=window,
                     sm_scale=hd ** -0.5,
                     name="ragged_paged_attention_cross"))
    out = np.asarray(fn(q, kc, vc, jnp.asarray(bt), jnp.asarray(cu),
                        jnp.asarray(kvl)).astype(jnp.float32))
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for r, (n, k) in enumerate(rows):
            if n == 0:
                continue
            pages = jnp.asarray(bt[r, :-(-k // bs)])
            # rows in order, as 20 heads of 64
            kr = kc[0][pages].astype(jnp.float32).transpose(
                0, 2, 1, 3).reshape(-1, kvh, hd)[:k]
            vr = vc[0][pages].astype(jnp.float32).transpose(
                0, 2, 1, 3).reshape(-1, kvh // 2, 2 * hd)[:k]
            pos = k - n + jnp.arange(n)
            key = jnp.arange(k)
            see = key[None, :] <= pos[:, None]
            if window is not None:
                see &= key[None, :] > pos[:, None] - window
            qr = q[cu[r]:cu[r] + n].astype(jnp.float32)
            for h in range(nh):
                # head h: map h % 2 of pair h // 2, key head 2j + h % 2
                j = h // 4
                sc = qr[:, h] @ kr[:, 2 * j + h % 2].T / np.sqrt(hd)
                pr = jax.nn.softmax(jnp.where(see, sc, -jnp.inf), -1)
                vj = jnp.where(see.any(0)[:, None], vr[:, j], 0.0)
                worst = max(worst, float(jnp.max(jnp.abs(
                    pr @ vj - out[cu[r]:cu[r] + n, h]))))
    assert worst <= 3e-2, worst
