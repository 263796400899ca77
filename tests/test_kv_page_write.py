"""The page writer (ops/pallas/kv_page_write.py) against the row scatter
it stands in for (``layer_stack._set_rows``): the same rows in the same
slots of the same pages in the same type, every page but the null page
bit for bit (the scatter sends padded tokens there, the writer writes
none).  Interpret mode, small pools; the kernel itself compiles for the
described v5e in tests/test_chip_lowering.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference import layer_stack as ls
from paddle_tpu.ops.pallas import kv_page_write as kw
from paddle_tpu.ops.pallas import paged_attention as pa

BS, D, L, NBLK = 16, 128, 3, 16


@pytest.fixture(autouse=True)
def _interpret():
    old = pa.INTERPRET
    pa.INTERPRET = True
    yield
    pa.INTERPRET = old


def _launch(rows, seed=0):
    """rows: [(new tokens, keys before them)].  cu, kvl, a table of
    distinct pages in a shuffled order and a WINDOW table beside it:
    other pages, and the null page below each row's last two."""
    rng = np.random.default_rng(seed)
    R = len(rows)
    cu = np.zeros(R + 1, np.int32)
    cu[1:] = np.cumsum([n for n, _ in rows])
    kvl = np.asarray([n + before if n else before for n, before in rows],
                     np.int32)
    pages = rng.permutation(np.arange(1, 2 * R * NBLK + 1))
    bt = np.zeros((2, R + 1, NBLK), np.int32)
    bt[:, :R] = pages.reshape(2, R, NBLK)
    for r, (n, before) in enumerate(rows):
        bt[1, r, :max(0, before // BS - 1)] = 0
    return jnp.asarray(cu), jnp.asarray(kvl), jnp.asarray(bt)


def _both(rows, tq, hkv=4, dtype=jnp.bfloat16, layer=1, window=False):
    """(what the scatter leaves, what the writer leaves, the pools
    before): K and V, [L, num_blocks, hkv, BS, D]."""
    cu, kvl, bt = _launch(rows)
    bt = bt[1] if window else bt[0]
    keys = jax.random.split(jax.random.PRNGKey(tq + hkv), 4)
    k, v = (jax.random.normal(key, (tq, hkv, D), dtype) for key in keys[:2])
    shape = (L, 2 * len(rows) * NBLK + 1, hkv, BS, D)
    kc, vc = (jax.random.normal(key, shape, dtype) for key in keys[2:])
    seg, rel = pa.ragged_segments(cu, kvl, tq)
    at = (jnp.int32(layer), bt[seg, rel // BS], rel % BS)
    want = ls._set_rows(kc, at, k), ls._set_rows(vc, at, v)
    got = kw.kv_page_write(k, v, kc, vc, bt, cu, kvl, jnp.int32(layer))
    return want, got, (kc, vc)


LAYOUTS = {
    "decode_rows": ([(1, 5), (1, 15), (1, 16), (1, 0), (1, 47)], 32),
    "chunk_mid_page_to_mid_page": ([(37, 7), (1, 20)], 64),
    "chunk_ends_on_a_boundary": ([(25, 7), (1, 31)], 32),
    "chunk_fills_whole_pages": ([(48, 16), (1, 3)], 64),
    "rows_with_no_new_token": ([(0, 9), (5, 14), (0, 0), (1, 30)], 32),
    "padded_to_its_bucket": ([(3, 6), (1, 40)], 64),
    # a long chunk among rows: more pages than the slots in flight, the
    # scratch lists filled past the pages of one row
    "chunk_of_many_pages": ([(1, 5), (203, 7), (1, 20), (0, 3), (70, 33)],
                            320),
}


@pytest.mark.parametrize("name,hkv,dtype,layer,window", [
    *[(name, 4, jnp.bfloat16, 1, False) for name in LAYOUTS],
    ("chunk_mid_page_to_mid_page", 8, jnp.bfloat16, 1, False),
    ("chunk_mid_page_to_mid_page", 10, jnp.bfloat16, 1, False),
    ("chunk_mid_page_to_mid_page", 8, jnp.float32, 1, False),
    ("decode_rows", 10, jnp.float32, 0, False),
    ("chunk_mid_page_to_mid_page", 4, jnp.bfloat16, 2, False),
    ("chunk_mid_page_to_mid_page", 4, jnp.bfloat16, 1, True),
    ("decode_rows", 8, jnp.bfloat16, 1, True),
    ("chunk_of_many_pages", 10, jnp.bfloat16, 1, False),
    ("chunk_of_many_pages", 4, jnp.float32, 0, True),
])
def test_the_writer_leaves_what_the_scatter_leaves(name, hkv, dtype, layer,
                                                   window):
    """Every page but the null page is bit for bit the scatter's, in the
    layer written; the other layers' pages are what they were; under a
    window table beside a global one the pages written are that table's."""
    rows, tq = LAYOUTS[name]
    want, got, before = _both(rows, tq, hkv, dtype, layer, window)
    for w, g, b in zip(want, got, before):
        assert g.dtype == w.dtype == dtype and g.shape == w.shape
        w, g, b = (np.asarray(x.astype(jnp.float32)) for x in (w, g, b))
        np.testing.assert_array_equal(g[:, 1:], w[:, 1:])
        others = [i for i in range(L) if i != layer]
        np.testing.assert_array_equal(g[others], b[others])
        # the writer leaves the null page alone
        np.testing.assert_array_equal(g[:, 0], b[:, 0])
        assert (g[layer] != b[layer]).any()


def test_a_frozen_row_of_the_decode_window_writes_nothing():
    """A row the decode window froze is a row of one query and no keys
    (``decode_window_rows``): the scatter sends it to the null page, the
    writer gives it no page at all."""
    active = jnp.asarray([True, False, True])
    kvl = jnp.asarray([9, 17, 33], jnp.int32)
    cu, kvl_w = pa.decode_window_rows(active, kvl)
    bt = _launch([(1, 8), (1, 16), (1, 32)])[2][0]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    k, v = (jax.random.normal(key, (3, 4, D)) for key in keys[:2])
    kc, vc = (jax.random.normal(key, (L, 6 * NBLK + 1, 4, BS, D))
              for key in keys[2:])
    got = kw.kv_page_write(k, v, kc, vc, bt, cu, kvl_w, jnp.int32(0))
    seg, rel = pa.decode_window_segments(active, kvl)
    at = (jnp.int32(0), bt[seg, rel // BS], rel % BS)
    for g, w, b in zip(got, (ls._set_rows(kc, at, k),
                             ls._set_rows(vc, at, v)), (kc, vc)):
        np.testing.assert_array_equal(np.asarray(g)[:, 1:],
                                      np.asarray(w)[:, 1:])
        # the frozen row's page is what it was
        np.testing.assert_array_equal(np.asarray(g)[0, bt[1, 1]],
                                      np.asarray(b)[0, bt[1, 1]])


@pytest.mark.parametrize("slots", [2, 3, 16])
def test_the_result_does_not_depend_on_the_depth(slots, monkeypatch):
    """How many pages are in flight is the tuning cache's to say and
    changes no byte."""
    import json
    monkeypatch.setenv("PADDLE_TPU_TUNE_FORCE", json.dumps(
        {"kv_page_write": {"page_slots": slots}}))
    kw._launch.clear_cache()
    rows, tq = LAYOUTS["chunk_mid_page_to_mid_page"]
    want, got, _ = _both(rows + [(1, 9)] * 6, tq)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(
            np.asarray(g.astype(jnp.float32))[:, 1:],
            np.asarray(w.astype(jnp.float32))[:, 1:])
    kw._launch.clear_cache()


@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig.tiny(
        vocab=97, hidden=256, layers=2, heads=2, ffn=64, seq=96))


def _engine(model):
    from paddle_tpu.inference import LLMEngine
    return LLMEngine(model, max_num_seqs=4, block_size=8, max_model_len=96,
                     max_prefill_tokens=32, prefill_token_bucket=16)


def test_the_engine_serves_the_xla_paths_tokens_through_the_writer(
        model, monkeypatch):
    """The interpreted kernels (the writer, then the ragged kernel over
    what it wrote) against the XLA path through ``LLMEngine``: chunked
    prompts that start and end mid-page beside decode rows, the greedy
    tokens equal; and ``summary()`` counts the writer's pages a token."""
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, 97, n)] for n in (45, 6, 19)]
    outs = {}
    for interpret in (None, True):
        monkeypatch.setattr(pa, "INTERPRET", interpret)
        eng = _engine(model)
        assert eng.attention_path.startswith(
            "pallas-interpret" if interpret else "xla-reference")
        rids = [eng.add_request(p, max_new_tokens=7) for p in prompts]
        done = eng.run()
        outs[interpret] = [done[r].generated for r in rids]
        s = eng.summary()
        # every live token is written once (a layer's count); a decode
        # row moves a page a token, a chunk about one in block_size
        assert s["kv_write_tokens"] == s["tokens_real"]
        assert 1 / 8 <= s["kv_write_pages"] / s["kv_write_tokens"] <= 1.0
    assert outs[None] == outs[True]


def test_the_writers_counters_follow_the_launch(model):
    """``kv_write_pages`` / ``kv_write_tokens`` from a launch's packed
    rows: a decode-only launch moves a page a token, a chunk the pages
    its tokens touch."""
    eng = _engine(model)
    cu = np.asarray([0, 1, 2, 2, 22], np.int32)
    kvl = np.asarray([9, 16, 0, 27], np.int32)   # the chunk: 7 .. 26
    assert eng._launch_kv_args(cu, kvl)["kv_write_tokens"] == 22
    assert eng._launch_kv_args(cu, kvl)["kv_write_pages"] == 1 + 1 + 4
    cu = np.arange(5, dtype=np.int32)
    got = eng._launch_kv_args(cu, np.asarray([1, 8, 9, 30], np.int32))
    assert got["kv_write_pages"] == got["kv_write_tokens"] == 4
