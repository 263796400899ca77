"""What PR 39 added, all as new files and entries: the architecture
``dots3`` (reference, shapes, builder), the configuration
``dots3-note-prev-ep8``, the mix ``longdocs``, the cell
``dots3.longdocs`` and four readers (``attn.index_device_share``,
``attn.index_roofline_share``, ``attn.select_device_share``,
``attn.selected_share``)."""
import collections
import json
import math
import os
import statistics
import subprocess

import numpy as np
import pytest

from harness import scopes, spec
from harness.kinds import closed_loop as CL

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "data", "rehearsal_dots3.json")
CELL, CONFIG = "dots3.longdocs", "dots3-note-prev-ep8"
NEW_READERS = {"attn.index_device_share", "attn.index_roofline_share",
               "attn.select_device_share", "attn.selected_share"}
REDUCED = {"num_hidden_layers": (46, 5), "n_routed_experts": (256, 32),
           "vocab_size": (152064, 19008),
           "max_position_embeddings": (524288, 32768)}


@pytest.fixture(scope="module")
def cfg():
    return spec.load_config(spec.load_benchmark(), CONFIG)


def _overlay(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _overlay(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def _tiny():
    with open(REHEARSAL) as f:
        over = json.load(f)["config"]
    return _overlay(spec.load_config(spec.load_benchmark(), CONFIG), over)


def test_the_new_files_are_found_by_name(cfg):
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "longdocs", 1)
    assert cfg["reference"] == "dots3"
    assert callable(spec.load_reference("dots3").logits_at)
    assert callable(spec.load_builder("dots3").construct)
    assert callable(spec.load_shapes("dots3").index_row)
    assert spec.load_traffic("longdocs")["kind"] == "closed_loop"
    e2e = {m["name"] for m in spec.metrics_for(bench, "end_to_end", CELL)}
    assert e2e == {"out_tokens_per_s", "gap_p95_ms", "setup_s"}
    per = {m["name"] for m in spec.metrics_for(bench, "per_layer", CELL)}
    assert NEW_READERS | {
        "moe.roofline_share", "moe.device_share", "moe.load_max_over_mean",
        "moe.touched_share", "attn.roofline_share",
        "attn.window_roofline_share", "attn.window_device_share",
        "attn.gate_device_share", "kv.window_pages_share",
        "matmul.roofline_share", "device.idle_share"} <= per
    # prefill-bound: every step carries a chunk; and what reads nothing
    # here lists the cell nowhere
    assert not per & {"step.decode_ms", "step.device_decode_ms",
                      "kvpool.copy_share", "engine.prefix_hit_share",
                      "frontend.ttft_overhead_ms"}
    for name in per:
        assert callable(spec.load_reader(name))
    # held loosely: a later PR appends its cell to these lists too
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert CELL in m["workloads"]
            assert m["moves"] == "out_tokens_per_s"
            assert m["better"] == ("higher" if "roofline" in m["name"]
                                   else "lower")


def test_every_published_width_is_in_the_file(cfg):
    """The catalog row's numbers, key for key, but the four in
    ``reduced``; ``layer_types`` keeps its 46 published entries."""
    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog on this machine")
    with open(path, encoding="utf-8") as f:
        for line in f:
            d = json.loads(line)
            if d["name"] == "dots3-note-prev":
                row = d
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "-") != v}
    assert differs == set(cfg["reduced"]) == set(REDUCED)
    for key, (published, here) in REDUCED.items():
        r = cfg["reduced"][key]
        assert (r["published"], r["here"]) == (published, here) == (
            row["config"][key], cfg[key])
    assert len(cfg["layer_types"]) == 46
    assert cfg["layer_types"][:5] == ["full_attention"] * 2 \
        + ["sliding_attention"] * 3
    bench = spec.load_benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert set(entry["reduced"]) == differs
    assert entry["source"] == row["source_url"]
    assert len(cfg["assumed"]) >= 8 and "eight chips" in cfg["deployment"]
    ep = cfg["expert_parallel"]
    assert (ep["ep_size"], ep["ep_rank"], ep["router_width"]) == (8, 0, 256)
    assert 19008 * 8 == 152064 and 32 * 8 == 256
    assert any("multi-token-prediction" in a for a in cfg["assumed"])


def test_the_cut_and_its_arithmetic(cfg):
    """Layer 0 and one period: 4,087M parameters, 8.17 GB of bfloat16
    held once; three arrays of cached rows, 1.84 GB; 10.0 GB of 16."""
    arch = spec.load_shapes("dots3")
    m = arch.dims(cfg)
    assert m["window"] == [False, False, True, True, True]
    assert m["sparse"] == [False, True, True, True, True] and m["dense"] == 1
    assert (m["Lg"], m["Lw"], m["E"], m["held"], m["k"], m["first"]) == (
        2, 3, 256, 32, 8, 0)
    assert (m["ni"], m["di"], m["topk"], m["W"]) == (64, 128, 2048, 513)
    by_layer, by_name = collections.Counter(), collections.Counter()
    for n, at, shape, _k in arch.leaves(cfg):
        by_layer[at] += math.prod(shape)
        by_name[(at, n)] += math.prod(shape)
    full = 5120 * 1024 + 1024 * 128 * 192 + 5120 * 576 + 512 * 128 * 256 \
        + 128 * 128 * 5120 + 5120 * 128
    index = 1024 * 64 * 128 + 5120 * 128 + 5120 * 64
    sliding = 5120 * 1024 + 1024 * 64 * 256 + 5120 * 1088 \
        + 1024 * 64 * 320 + 64 * 128 * 5120 + 5120 * 64
    assert (round(full / 1e6, 2), round(index / 1e6, 2),
            round(sliding / 1e6, 2)) == (134.68, 9.37, 90.83)
    assert round((full + index) / 1e6, 2) == 144.05
    dense, expert = 3 * 5120 * 13824, 3 * 5120 * 1536
    router = 5120 * 256
    assert (round(dense / 1e6, 2), round(expert / 1e6, 3),
            round(32 * expert / 1e6, 2), round(router / 1e6, 2)) == (
        212.34, 23.593, 754.97, 1.31)
    norms = {False: 2 * 5120 + 1024 + 512 + 2 * 128,
             True: 2 * 5120 + 1024 + 1024}
    sparse = 33 * expert + router + 256
    assert by_layer[0] == full + index + dense + norms[False]
    assert by_layer[1] == full + index + sparse + norms[False]
    assert by_layer[2] == by_layer[3] == by_layer[4] \
        == sliding + sparse + norms[True]
    assert by_layer[None] == 2 * 19008 * 5120 + 5120
    assert [round(by_layer[i] / 1e6, 1) for i in (0, 1, 2)] == [
        356.4, 923.9, 870.7]
    assert round(by_layer[None] / 1e6, 1) == 194.6
    total = sum(by_layer.values())
    assert round(total / 1e6) == 4087 and round(2 * total / 1e9, 2) == 8.17
    # whole, one layer's experts are 12.08 GB; two periods leave no pool
    assert round(2 * 256 * expert / 1e9, 2) == 12.08
    assert round(2 * (total + by_layer[1] + 3 * by_layer[2]) / 1e9, 1) \
        == 15.2
    assert arch.window_blocks(cfg) == 32 * (33 + 32 + 1) + 1 == 2113
    pools = {s for s in arch.pool_shapes(cfg) if len(s) == 4 and s[0] > 1}
    assert pools == {(2, 32769, 16, 640), (2, 32769, 16, 128),
                     (3, 2113, 16, 1152)}
    gb = [round(2 * math.prod(p) / 1e9, 2) for p in sorted(pools)]
    assert gb == [0.27, 1.34, 0.23] and round(sum(
        2 * math.prod(p) for p in pools) / 1e9, 2) == 1.84
    assert round((2 * total + sum(2 * math.prod(p) for p in pools)) / 1e9,
                 1) == 10.0
    s = cfg["serving"]
    assert s["num_blocks"] == 1 + 524288 // s["block_size"]
    assert s["enable_prefix_caching"] is False
    assert 16e9 * 0.25 < 2 * total
    # the floors the guide sets a model_config PR
    assert m["L"] - m["dense"] >= 4 and m["held"] >= 8
    assert cfg["vocab_size"] * 8 >= 152064


def test_attention_counts_the_selection_and_the_window(cfg):
    arch = spec.load_shapes("dots3")
    full = 2 * 128 * (576 + 512)            # ops a (query, key), absorbed
    sliding = 2 * 64 * (1088 + 1024)
    # a decode row at 12,000 keys: 2,048 selected keys a full layer, 513 a
    # sliding one
    ow, bw = arch.window_attention_row(cfg, 1, 12000)
    assert ow == 3 * sliding * 513
    oa, ba = arch.attention_row(cfg, 1, 12000)
    assert oa == ow + 2 * full * 2048
    assert bw == 3 * (513 * 1088 + 1088 + 64 * (1088 + 1024)) * 2
    assert ba - bw == 2 * (2048 * 576 + 576 + 128 * (576 + 512)) * 2
    # under the selection and the window every key counts
    o, _ = arch.attention_row(cfg, 1, 300)
    assert o == (2 * full + 3 * sliding) * 300
    # a chunk is its tokens: at, across and past both edges
    for n_q, kv_len in ((512, 512), (512, 513), (512, 2048), (512, 2049),
                        (512, 2300), (512, 9000), (7, 2050), (300, 300)):
        rows = [arch.attention_row(cfg, 1, kv_len - n_q + 1 + i)[0]
                for i in range(n_q)]
        assert arch.attention_row(cfg, n_q, kv_len)[0] == sum(rows)
        sel, vis = arch.selected_pairs(cfg, n_q, kv_len)
        assert sel == sum(min(kv_len - n_q + 1 + i, 2048)
                          for i in range(n_q))
        assert vis == sum(kv_len - n_q + 1 + i for i in range(n_q))
    # a chunk past the selection reads its row's keys once, not a query's
    # 2,048 a query
    _, b = arch.attention_row(cfg, 512, 9000)
    _, bw = arch.window_attention_row(cfg, 512, 9000)
    assert b - bw == 2 * (9000 * 576 + 512 * 576
                          + 512 * 128 * (576 + 512)) * 2
    # the indexer scores every pair a query sees: 2 x 64 x 128 a pair
    oi, bi = arch.index_row(cfg, 512, 9500)
    pairs = 512 * 9500 - 512 * 511 // 2
    assert oi == 2 * 2 * 64 * 128 * pairs
    assert round(oi / 2 / 1e12, 2) == 0.08         # TFLOP a layer a chunk
    assert bi == 2 * ((9500 + 512) * 128 * 2 + 512 * 64 * (128 * 2 + 4))
    # at the mix's mean a chunk keeps about a fifth of what it sees
    sel, vis = arch.selected_pairs(cfg, 512, 9500)
    assert round(100 * sel / vis) == 22


def test_products_follow_the_work(cfg):
    arch = spec.load_shapes("dots3")
    m = arch.dims(cfg)
    dense = sum(arch.layer_dense_weights(m, i) for i in range(5))
    assert dense == 2 * 144_048_128 + 3 * 90_832_896 + 212_336_640 \
        + 4 * (1_310_720 + 23_592_960)
    ops0, byt0 = arch.step_matmuls(cfg, 0, 0)
    assert ops0 == 0 and byt0 == 2 * (dense + 5120 * 19008)
    ops, byt = arch.step_matmuls(cfg, 525, 14)
    assert ops == 2 * 525 * dense + 2 * 14 * 5120 * 19008 and byt > byt0
    # a chunk step touches every held expert of four layers: 6.04 GB
    e_ops, e_byt = arch.expert_products(cfg, 525 * 8 * 4 // 8, 128)
    assert e_ops == 2 * (525 * 4) * 3 * 5120 * 1536
    assert round(128 * 3 * 5120 * 1536 * 2 / 1e9, 2) == 6.04
    assert round(6.04e9 / 819e9 * 1e3, 1) == 7.4             # ms at peak
    assert e_byt > 6.04e9
    assert arch.expert_products(cfg, 0, 0) == (0, 0)


def test_longdocs_pairs_are_the_stated_laws_and_fit_the_pool(cfg):
    t = spec.load_traffic("longdocs")
    d = t["distribution"]
    assert (d["prompt"]["median"], d["prompt"]["sigma"], d["prompt"]["min"],
            d["prompt"]["max"]) == (8192, 0.5, 3072, 24576)
    assert (d["output"]["median"], d["output"]["sigma"], d["output"]["min"],
            d["output"]["max"]) == (192, 0.6, 32, 768)
    p = CL.stratified(8192, 0.5, 3072, 24576)
    o = CL.stratified(192, 0.6, 32, 768)
    assert len(t["pairs"]) == 64
    assert [a for a, _ in t["pairs"]] == p
    assert [b for _, b in t["pairs"]] == [o[(37 * i + 11) % 64]
                                          for i in range(64)]
    assert statistics.mean(p) == pytest.approx(9220, abs=1)
    assert statistics.mean(o) == pytest.approx(228, abs=0.5)
    # every prompt is past index_topk: the selection discards keys in
    # every request
    assert min(p) > cfg["index_topk"] > cfg["sliding_window_size"]
    order = t["deal"]["order"]
    assert order == CL.balanced_order(3761, 64, 16)
    for i in range(0, 64, 16):
        assert sorted(x // 4 for x in order[i:i + 16]) == list(range(16))
    docs = spec.load_traffic("docs")
    assert t["prefixes"] == [] and t["clients"] == 32
    assert t["primer"] == docs["primer"] == {"prompt_tokens": 512,
                                             "phase_max": 152}
    assert t["window_open"] == docs["window_open"]
    assert t["warmup"] == docs["warmup"]
    assert t["sampling"] == docs["sampling"]
    longest = max(a + b for a, b in t["pairs"])
    assert longest <= t["reference_pad_to"] == 25600
    assert t["reference_score_rows"] == 768 >= max(b for _, b in t["pairs"])
    assert longest <= cfg["serving"]["max_model_len"]
    # any 32 pairs dealt in a row, at their full lengths, need under two
    # thirds of the full layers' 32,768 pages: no preemption
    bs = cfg["serving"]["block_size"]
    pages = [-(-(a + b) // bs) for a, b in (t["pairs"][i] for i in order)]
    in_a_row = [sum((pages + pages)[i:i + 32]) for i in range(64)]
    assert max(in_a_row) < 2 / 3 * 32768
    for seed in (3, 2**31 + 17):
        got = collections.Counter()
        for j in range(64):
            r = CL.dealt_request(t, seed, j, vocab=1000)
            got[(len(r["prompt"]), r["max_tokens"])] += 1
        assert got == collections.Counter(map(tuple, t["pairs"]))


def test_the_reference_is_the_models_forward_and_the_control_is_not():
    """At the tiny size the plain reference, with its own weights from
    the seed, gives what ``Dots3ForCausalLM.forward`` gives on the
    builder's model; ``lower="int8"`` does not; the selection, the
    window, the gate and the indexer's rotary are in it."""
    import jax.numpy as jnp

    from harness import weights as W
    ref = spec.load_reference("dots3")
    cfg = _tiny()
    seq = np.random.default_rng(0).integers(0, 512, 90).tolist()
    a = ref.logits_at(cfg, 7, [seq], [80], 8, 128)
    b = ref.logits_at(cfg, 7, [seq], [80], 8, 128, lower="int8")
    again = ref.logits_at(cfg, 7, [seq, seq[:20]], [80, 10], 8, 128)
    assert a.shape == (1, 8, 512) and np.isfinite(a).all()
    np.testing.assert_allclose(again[0], a[0], atol=1e-5)
    assert 1e-3 < np.abs(a - b).max() < 2.0
    builder = spec.load_builder("dots3")
    model = builder.construct(cfg)
    builder.place(model, W.make_all(spec.load_shapes("dots3").leaves(cfg),
                                    7, jnp.dtype(cfg["dtype"])))
    fwd = np.asarray(model.forward(np.asarray([seq]))._data)[0, 80:88]
    np.testing.assert_allclose(a[0], fwd, atol=3e-4, rtol=0)
    # each part of the layer shows in the logits
    for other in (dict(cfg, index_topk=4096),
                  dict(cfg, sliding_window_size=4096),
                  dict(cfg, rope_theta=10000.0),
                  dict(cfg, swa_rope_theta=100.0)):
        c = ref.logits_at(other, 7, [seq], [80], 8, 128)
        assert np.abs(a - c).max() > 1e-3
    # under index_topk and the window nothing is discarded
    early = ref.logits_at(cfg, 7, [seq], [0], 5, 128)
    wide = dict(cfg, index_topk=4096, sliding_window_size=4096)
    np.testing.assert_allclose(
        early, ref.logits_at(wide, 7, [seq], [0], 5, 128), atol=1e-5)
    with pytest.raises(ValueError):
        ref.logits_at(cfg, 7, [seq], [80], 8, 64)      # over pad_to
    with pytest.raises(ValueError, match="headwise"):
        spec.load_shapes("dots3").dims(dict(cfg, attention_gate_type=None))


def test_new_readers_read_nothing_from_a_program_without_what_they_read():
    """On the parent, or in a cell of another architecture, the four
    readers return None and do not raise."""
    dense = spec.load_shapes("llama_dense")
    launch = {"ph": "X", "name": "engine.device_launch", "ts": 5, "dur": 1,
              "args": {"step": 1, "kv_pages": 7}}
    ctx = {"trace": {"busy_s": 1.0, "events": [], "host_window": (0, 1)},
           "arch": dense, "spans": [launch], "c0": {}, "c1": {},
           "t_open": 0, "t_close": 100, "cfg": {}, "program_scopes": {},
           "device_kind": "TPU v5 lite"}
    for name in sorted(NEW_READERS):
        assert spec.load_reader(name)(ctx) is None
        assert spec.load_reader(name)(dict(ctx, trace=None)) is None
    # an architecture with the scopes and a program without them
    ctx["arch"] = spec.load_shapes("dots3")
    for name in sorted(NEW_READERS):
        assert spec.load_reader(name)(ctx) is None


def test_selected_share_is_the_launches_mean(cfg):
    spans = [{"ph": "X", "name": "engine.device_launch", "ts": t, "dur": 1,
              "args": {"step": i, "index_keys_selected": s,
                       "index_keys_visible": v}}
             for i, (t, s, v) in enumerate([(5, 20, 100), (9, 50, 100),
                                            (13, 7, 7), (200, 1, 100)])]
    ctx = {"spans": spans, "t_open": 0, "t_close": 100}
    assert spec.load_reader("attn.selected_share")(ctx) \
        == pytest.approx((20 + 50 + 100) / 3)


def test_index_and_select_shares_are_the_time_under_their_scopes(
        cfg, monkeypatch):
    arch = spec.load_shapes("dots3")
    assert arch.INDEX_SCOPES == ("attn_index",)
    assert arch.SELECT_SCOPES == ("attn_select",)
    assert {"attn_index", "attn_select", "attn_gate"} <= set(arch.SCOPES)
    assert scopes.scope_of(
        "jit(ragged_step_t64)/layers/jit(layer)/attn_index/"
        "ragged_index_scores/pallas_call", arch) == "attn_index"
    assert scopes.scope_of("jit(run)/layers/jit(layer)/attn_select/while/"
                           "body/reduce_sum", arch) == "attn_select"
    assert scopes.is_kernel_name("ragged_latent_attention_selected.3", arch)
    assert scopes.is_kernel_name("ragged_latent_attention_window.1", arch)
    assert not scopes.is_kernel_name("ragged_index_scores.2", arch)
    evs = [{"name": "ragged_index_scores.1", "self_ns": 300,
            "scope": "attn_index", "shape": "f32[2688,32768]"},
           {"name": "fusion.2", "self_ns": 100, "scope": "attn_index",
            "shape": "bf16[576,8192]"},
           {"name": "fusion.9", "self_ns": 700, "scope": "attn_select",
            "shape": "f32[576,32768]"},
           {"name": "fusion.3", "self_ns": 5000, "scope": "moe_experts",
            "shape": "bf16[512,5120]"}]
    monkeypatch.setattr(scopes, "scoped_events", lambda ctx: evs)
    ctx = {"arch": arch, "cfg": cfg, "device_kind": "TPU v5 lite",
           "trace": {"busy_s": 1e-5, "host_window": (0, 100)},
           "spans": []}
    assert spec.load_reader("attn.index_device_share")(ctx) \
        == pytest.approx(100 * 400 / 1e4)
    assert spec.load_reader("attn.select_device_share")(ctx) \
        == pytest.approx(100 * 700 / 1e4)
    # no rows in the traced window: the roofline has nothing to count
    assert spec.load_reader("attn.index_roofline_share")(ctx) is None
    monkeypatch.setattr(scopes, "scoped_events", lambda ctx: evs[3:])
    assert spec.load_reader("attn.index_device_share")(ctx) is None
    assert spec.load_reader("attn.select_device_share")(ctx) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cells_command_end_to_end_at_a_tiny_size(trace):
    bench = spec.load_benchmark()
    cmd = bench["command"] + ["--workload", CELL, "--seed",
                              str(2**31 + 93), "--seconds", "3", "--trace",
                              str(trace), "--rehearsal", REHEARSAL]
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    p = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["compared"]["served_gap_max"]["value"] <= 1e-3
    assert res["compared"]["compiles_in_window"]["value"] == 0
    names = {m["name"] for m in spec.metrics_for(
        bench, "per_layer" if trace else "end_to_end", CELL)}
    assert set(res["metrics"]) <= names
    if trace:
        # counted by the program, so read on the CPU too: contexts of 12
        # to 116 tokens under a top-8 keep a fraction of what they see;
        # the device trace's readers return nothing there, never 0
        assert 5 < res["metrics"]["attn.selected_share"]["value"] < 90
        assert 20 < res["metrics"]["moe.touched_share"]["value"] <= 100
        assert 5 < res["metrics"]["kv.window_pages_share"]["value"] < 100
        assert "attn.index_device_share" not in res["metrics"]
        assert "attn.select_device_share" not in res["metrics"]
        assert "attn.index_roofline_share" not in res["metrics"]
    else:
        assert set(res["metrics"]) == names
