"""The decoder-hybrid-decoder model (``models/phi4flash.py``) on the
CPU, seeded random weights, the tiny preset: its whole-sequence forward
against the benchmark's plain reference on LOGITS, the widened-query
form of differential attention against the two-map form, the stack's
layout and the sizes the engine reads."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import spec, weights as W                       # noqa: E402

from paddle_tpu.models import phi4flash as M                 # noqa: E402

SEED = 2**31 + 7


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "tests", "data",
                           "rehearsal_phi4flash.json")) as f:
        over = json.load(f)["config"]
    c = dict(spec.load_config(spec.load_benchmark(),
                              "phi4-mini-flash-reasoning"))
    c.update({k: v for k, v in over.items() if k != "serving"})
    c["serving"] = {**c["serving"], **over["serving"]}
    return c


@pytest.fixture(scope="module")
def model(cfg):
    shapes = spec.load_shapes("phi4flash")
    builder = spec.load_builder("phi4flash")
    m = builder.construct(cfg)
    builder.place(m, W.make_all(shapes.leaves(cfg), SEED,
                                jnp.dtype(cfg["dtype"])))
    return m


@pytest.mark.parametrize("n", [5, 40, 131])
def test_forward_gives_the_references_logits(cfg, model, n):
    """One whole pass of the model (its own scan, its own masks, the
    widened queries) against the reference (blocks of rows with the
    state carried, two maps a head pair) on every position's logits:
    under, past and far past the window of 24."""
    seq = np.random.default_rng(n).integers(0, cfg["vocab_size"],
                                            n).tolist()
    want = spec.load_reference("phi4flash").logits_at(
        cfg, SEED, [seq], [0], n, 256)[0][:n]
    got = np.asarray(model.forward(np.asarray([seq]))._data[0])
    # float32 on both sides, another order of the sums; logits of order 1
    np.testing.assert_allclose(got, want, atol=3e-4, rtol=0)


def test_the_lower_precision_moves_the_references_logits(cfg):
    """The control rounds every matrix: the logits move, the vectors
    that are no matrix (the decay, the taps) are left alone."""
    ref = spec.load_reference("phi4flash")
    seq = np.random.default_rng(3).integers(0, cfg["vocab_size"],
                                            60).tolist()
    a = ref.logits_at(cfg, SEED, [seq], [0], 60, 256)[0]
    b = ref.logits_at(cfg, SEED, [seq], [0], 60, 256, lower="int8")[0]
    assert 1e-3 < np.abs(a - b).max() < 5.0      # logits of order 10
    with pytest.raises(ValueError):
        ref.logits_at(cfg, SEED, [seq], [0], 60, 256, lower="fp4")


def test_widened_queries_give_the_two_maps():
    """q1 widened to [q1 | 0] and q2 to [0 | q2] over key heads 2j and
    2j+1 side by side score what q1 . k_{2j} and q2 . k_{2j+1} score:
    the zeros add exact zeros, so the two forms agree to the last bit of
    a float32 sum of the same terms in the same order (tolerance: 0)."""
    rng = np.random.default_rng(0)
    T, nh, kvh, hd = 7, 8, 4, 16
    q = jnp.asarray(rng.standard_normal((T, nh, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((T, kvh, hd)), jnp.float32)
    wide = M.widen(q)                                   # [T, nh, 2 hd]
    rows = k.reshape(T, kvh // 2, 2 * hd)               # side by side
    g = nh // (kvh // 2)
    got = jnp.einsum("qhd,khd->hqk", wide, jnp.repeat(rows, g, axis=1))
    for h in range(nh):
        want = q[:, h] @ k[:, 2 * (h // g) + h % 2].T
        np.testing.assert_array_equal(np.asarray(got[h]), np.asarray(want))
    assert not np.asarray(wide[:, 0::2, hd:]).any()
    assert not np.asarray(wide[:, 1::2, :hd]).any()


def test_the_stack_and_what_the_engine_reads_of_it():
    c = M.Phi4FlashConfig()
    kinds = [a for a, _ in c.layer_kinds()]
    assert kinds[:16] == ["ssm", "diff_window"] * 8
    assert kinds[16:18] == ["ssm_keep", "diff"]
    assert kinds[18:] == ["gmu", "diff_cross"] * 7
    flat = [k for ks, n, _ in c.periods() for _ in range(n)
            for k, _f in ks]
    assert flat == kinds
    assert [(n, i) for _, n, i in c.periods()] == [(8, None), (1, 8),
                                                   (1, 0), (7, None)]
    assert c.page_shape() == (10, 128) and c.head_size == 64
    assert c.d_inner == 5120 and c.dt_rank == 160
    assert c.state_shapes(33) == ((9, 33, 3, 5120), (9, 33, 16, 5120))
    a = c.attention_by_kind()
    assert (a["diff_window"].depth0, a["diff"].depth0,
            a["diff_cross"].depth0) == (1, 17, 19)
    assert a["diff_cross"].reads == "diff" and a["diff"].window is None
    assert a["diff_window"].window == 512 and a["ssm_keep"].keep
    # l0 by depth, from a repeat's number: layer 2l + 1, 17, 19 + 2l
    for kind, layer, depth in (("diff_window", 3, 7), ("diff", 0, 17),
                               ("diff_cross", 6, 31)):
        k = a[kind]
        assert float(M.lambda_init(k.depth0 + k.stride * layer)) \
            == pytest.approx(0.8 - 0.6 * np.exp(-0.3 * depth), rel=1e-6)
    with pytest.raises(ValueError):
        M.Phi4FlashConfig(num_hidden_layers=10)


def test_the_model_holds_a_repeats_weights_stacked_and_once():
    """``decode_params`` hands the model's own arrays (nothing stacked
    or copied for the engine) and a repeat's leaves carry the repeats as
    their first axis."""
    m = M.Phi4FlashForCausalLM(M.Phi4FlashConfig.tiny(), dtype="float32")
    p = m.decode_params()
    assert "head" not in p                               # tied
    pairs, keep, full, tail = p["layers"]
    assert pairs[0]["w_in"].shape == (3, 32, 128)
    assert pairs[1]["wqkv"].shape == (3, 32, 64)
    assert keep[0]["A_log"].shape == (8, 64) and full[0]["wo"].shape \
        == (32, 32)
    assert tail[0]["w_in"].shape == (2, 32, 64) and tail[1]["wq"].shape \
        == (2, 32, 32)
    again = m.decode_params()
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(again)):
        assert a is b
    assert len(m.layer_params()) == 12
    # the published scales: A_log about log(1..N), D about 1
    A = np.asarray(keep[0]["A_log"])
    assert abs(A[0].mean()) < 0.05 and abs(A[-1].mean() - np.log(8)) < 0.05
