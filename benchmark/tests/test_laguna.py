"""What PR 35 added, all as new files and entries: the architecture
``laguna`` (reference, shapes, builder), the configuration
``laguna-xs2-d7``, the mix ``agentctx``, the cell ``lagunaxs2.agentctx``
and two readers (``moe.touched_share``, ``attn.gate_device_share``)."""
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
REHEARSAL = os.path.join(HERE, "data", "rehearsal_laguna.json")
CELL, CONFIG = "lagunaxs2.agentctx", "laguna-xs2-d7"
NEW_READERS = {"moe.touched_share", "attn.gate_device_share"}


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
        CONFIG, "agentctx", 1)
    assert cfg["reference"] == "laguna"
    assert callable(spec.load_reference("laguna").logits_at)
    assert callable(spec.load_builder("laguna").construct)
    assert callable(spec.load_shapes("laguna").attention_row)
    assert spec.load_traffic("agentctx")["kind"] == "closed_loop"
    e2e = {m["name"] for m in spec.metrics_for(bench, "end_to_end", CELL)}
    assert e2e == {"out_tokens_per_s", "gap_p95_ms", "setup_s"}
    per = {m["name"] for m in spec.metrics_for(bench, "per_layer", CELL)}
    assert NEW_READERS | {
        "moe.roofline_share", "moe.device_share", "moe.load_max_over_mean",
        "attn.roofline_share", "attn.window_roofline_share",
        "attn.window_device_share", "kv.window_pages_share",
        "matmul.roofline_share", "step.decode_ms"} <= per
    # what reads nothing here lists the cell nowhere
    assert not per & {"kvpool.copy_share", "engine.prefix_hit_share",
                      "frontend.ttft_overhead_ms"}
    for name in per:
        assert callable(spec.load_reader(name))
    # held loosely: a later PR appends its cell to these lists too
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"][0] == CELL
            assert (m["moves"], m["better"]) == ("out_tokens_per_s", "lower")


def test_every_published_width_is_in_the_file(cfg):
    """The catalog row's numbers, key for key, but the two in
    ``reduced``; the per-layer lists keep their 40 published entries."""
    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog on this machine")
    with open(path, encoding="utf-8") as f:
        for line in f:
            d = json.loads(line)
            if d["name"] == "Laguna-XS.2":
                row = d
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "-") != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers",
                                              "max_position_embeddings"}
    for key, (published, here) in {"num_hidden_layers": (40, 7),
                                   "max_position_embeddings": (262144, 16384)
                                   }.items():
        r = cfg["reduced"][key]
        assert (r["published"], r["here"]) == (published, here) == (
            row["config"][key], cfg[key])
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert len(cfg[key]) == 40
    bench = spec.load_benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert set(entry["reduced"]) == differs
    assert entry["source"] == row["source_url"]
    assert len(cfg["assumed"]) >= 5 and "deployment" in cfg


def test_the_cut_and_its_arithmetic(cfg):
    """Layers 0 to 6: 11.34 GB of bfloat16 weights held once, 2.15 GB of
    full-layer pages and 0.68 GB of sliding-layer pages; one table for
    all seven layers would be 7.5 GB, which does not fit beside the
    weights; eight layers would leave no room for a pool."""
    arch = spec.load_shapes("laguna")
    m = arch.dims(cfg)
    assert m["window"] == [False, True, True, True, False, True, True]
    assert m["heads"] == [48, 64, 64, 64, 48, 64, 64]
    assert m["sparse"] == [False] + [True] * 6 and m["dense"] == 1
    assert (m["Lg"], m["Lw"], m["E"], m["held"], m["k"]) == (2, 5, 256, 256,
                                                             8)
    by_layer, by_name = collections.Counter(), collections.Counter()
    for n, at, shape, _k in arch.leaves(cfg):
        by_layer[at] += math.prod(shape)
        by_name[(at, n)] += math.prod(shape)
    qog = 2048 * 48 * 128
    assert by_name[(0, "wq")] == by_name[(0, "wo")] == by_name[(0, "wg")] \
        == qog == 12_582_912
    assert by_name[(0, "wk")] == by_name[(0, "wv")] == 2048 * 8 * 128 \
        == 2_097_152
    full = 3 * qog + 2 * 2_097_152
    sliding = 3 * 2048 * 64 * 128 + 2 * 2_097_152
    assert (full, sliding) == (41_943_040, 54_525_952)
    experts = 256 * 3 * 2048 * 512
    shared, router, dense = 3 * 2048 * 512, 2048 * 256, 3 * 2048 * 8192
    assert (experts, shared, router, dense) == (805_306_368, 3_145_728,
                                               524_288, 50_331_648)
    norms = 2 * 2048
    assert by_layer[0] == full + dense + norms
    assert by_layer[1] == by_layer[6] == sliding + experts + shared \
        + router + norms
    assert by_layer[4] == full + experts + shared + router + norms
    assert by_layer[None] == 2 * 100352 * 2048 + 2048
    gb = {k: round(2 * v / 1e9, 3) for k, v in by_layer.items()}
    assert (gb[0], gb[1], gb[4], gb[None]) == (0.185, 1.727, 1.702, 0.822)
    assert round(2 * sum(by_layer.values()) / 1e9, 2) == 11.34
    # a window SHORTER than a chunk: 32 + 32 + 1 pages a sequence
    assert arch.window_blocks(cfg) == 32 * (32 + 32 + 1) + 1 == 2081
    page = 2 * 8 * 16 * 128 * 2                      # K and V, one layer
    assert page == 64 * 1024
    pools = {s for s in arch.pool_shapes(cfg) if len(s) == 5 and s[0] > 1}
    assert pools == {(2, 16385, 8, 16, 128), (5, 2081, 8, 16, 128)}
    assert round(2 * 16385 * page / 1e9, 2) == 2.15
    assert round(5 * 2081 * page / 1e9, 2) == 0.68
    assert round(7 * 16385 * page / 1e9, 1) == 7.5
    total = 2 * sum(by_layer.values()) + (2 * 16385 + 5 * 2081) * page
    assert round(total / 1e9, 2) == 14.17 and total < 15.2e9
    assert 11.34 + 7.5 > 16
    eighth = 2 * by_layer[1] / 1e9                    # layer 7 is sliding
    assert round(11.34 + eighth, 2) == 13.07
    s = cfg["serving"]
    assert s["num_blocks"] == 1 + 262144 // s["block_size"]
    assert s["enable_prefix_caching"] is False
    assert 16e9 * 0.25 < total


def test_attention_counts_each_kind_at_its_heads_and_its_window(cfg):
    arch = spec.load_shapes("laguna")
    full, sliding = 4 * 48 * 128, 4 * 64 * 128        # ops a (query, key)
    # a decode row at 12,000 keys: 512 keys a sliding layer, all of them
    # a full layer
    ow, bw = arch.window_attention_row(cfg, 1, 12000)
    assert ow == 5 * sliding * 512
    oa, ba = arch.attention_row(cfg, 1, 12000)
    assert oa == ow + 2 * full * 12000
    kv = 2 * 8 * 128 * 2                       # K and V of a position, bytes
    assert bw == 5 * (512 * kv + kv + 2 * 64 * 128 * 2)
    assert ba - bw == 2 * (12000 * kv + kv + 2 * 48 * 128 * 2)
    # under the window both kinds see the same keys, each at its heads
    o_w, _ = arch.window_attention_row(cfg, 1, 300)
    assert o_w == 5 * sliding * 300
    assert arch.attention_row(cfg, 1, 300)[0] == o_w + 2 * full * 300
    # a chunk is its tokens: at, across and past the window's edge, and
    # as long as the window itself
    for n_q, kv_len in ((512, 512), (512, 513), (512, 900), (512, 9000),
                        (7, 515), (300, 300)):
        chunk = arch.window_attention_row(cfg, n_q, kv_len)[0]
        rows = sum(arch.window_attention_row(cfg, 1, kv_len - n_q + 1 + i)[0]
                   for i in range(n_q))
        assert chunk == rows, (n_q, kv_len)
    # a chunk past the window reads its own rows and the window before
    _, b = arch.window_attention_row(cfg, 512, 9000)
    assert b == 5 * ((511 + 512) * kv + 512 * kv + 512 * 2 * 64 * 128 * 2)


def test_products_follow_the_work(cfg):
    arch = spec.load_shapes("laguna")
    dense = 2 * 41_943_040 + 5 * 54_525_952 + 50_331_648 \
        + 6 * (524_288 + 3_145_728)
    ops0, byt0 = arch.step_matmuls(cfg, 0, 0)
    assert ops0 == 0 and byt0 == 2 * (dense + 2048 * 100352)
    ops, byt = arch.step_matmuls(cfg, 540, 29)
    assert ops == 2 * 540 * dense + 2 * 29 * 2048 * 100352 and byt > byt0
    # the grouped products: K 2048 / N 512 twice and K 512 / N 2048 over
    # 256 groups; a chunk step touches every expert of six layers
    e_ops, e_byt = arch.expert_products(cfg, 540 * 8 * 6, 1536)
    assert e_ops == 2 * 540 * 8 * 6 * 3 * 2048 * 512
    assert e_byt > 1536 * 3 * 2048 * 512 * 2 == 6 * 805_306_368 * 2
    assert round(1536 * 3 * 2048 * 512 * 2 / 1e9, 2) == 9.66
    assert round(9.66e9 / 819e9 * 1e3, 1) == 11.8            # ms at peak
    assert arch.expert_products(cfg, 0, 0) == (0, 0)
    # a decode step's 32 x 8 pairs over 256 experts touch about 63%
    assert round(100 * (1 - (1 - 1 / 256) ** 256)) == 63


def test_agentctx_pairs_are_the_stated_laws_and_fit_the_pool(cfg):
    t = spec.load_traffic("agentctx")
    d = t["distribution"]
    assert (d["prompt"]["median"], d["prompt"]["sigma"], d["prompt"]["min"],
            d["prompt"]["max"]) == (4096, 0.6, 1024, 12288)
    assert (d["output"]["median"], d["output"]["sigma"], d["output"]["min"],
            d["output"]["max"]) == (320, 0.6, 48, 1024)
    p = CL.stratified(4096, 0.6, 1024, 12288)
    o = CL.stratified(320, 0.6, 48, 1024)
    assert len(t["pairs"]) == 64
    assert [a for a, _ in t["pairs"]] == p
    assert [b for _, b in t["pairs"]] == [o[(37 * i + 11) % 64]
                                          for i in range(64)]
    assert statistics.mean(p) == pytest.approx(4782, abs=1)
    assert statistics.mean(o) == pytest.approx(375.5, abs=0.5)
    order = t["deal"]["order"]
    assert order == CL.balanced_order(2877, 64, 16)
    for i in range(0, 64, 16):
        assert sorted(x // 4 for x in order[i:i + 16]) == list(range(16))
    docs = spec.load_traffic("docs")
    assert t["prefixes"] == [] and t["clients"] == 32
    assert t["primer"] == docs["primer"] == {"prompt_tokens": 512,
                                             "phase_max": 152}
    assert t["window_open"] == docs["window_open"]
    assert t["warmup"] == docs["warmup"]
    assert t["sampling"] == docs["sampling"]
    mixed = spec.load_traffic("mixedlen")
    longest = max(a + b for a, b in t["pairs"])
    assert t["reference_pad_to"] == 12288 + 1024 >= longest
    assert mixed["reference_pad_to"] == 14336 + 1024
    assert t["reference_score_rows"] == mixed["reference_score_rows"] \
        == 1024 >= max(b for _, b in t["pairs"])
    assert longest <= cfg["serving"]["max_model_len"]
    # any 32 pairs dealt in a row, at their full lengths, need under three
    # quarters of the full layers' 16,384 pages: no preemption
    bs = cfg["serving"]["block_size"]
    pages = [-(-(a + b) // bs) for a, b in (t["pairs"][i] for i in order)]
    assert max(pages) <= 832
    in_a_row = [sum((pages + pages)[i:i + 32]) for i in range(64)]
    assert max(in_a_row) < 0.75 * 16384
    assert statistics.mean(in_a_row) == pytest.approx(10300, abs=100)
    for seed in (3, 2**31 + 17):
        got = collections.Counter()
        for j in range(64):
            r = CL.dealt_request(t, seed, j, vocab=1000)
            got[(len(r["prompt"]), r["max_tokens"])] += 1
        assert got == collections.Counter(map(tuple, t["pairs"]))


def test_agentctx_warmup_builds_every_bucket_to_576(cfg):
    """Every token bucket a step of this cell can take (32 decode rows
    and a 512-token chunk: 32, then multiples of 64 up to 576) is the
    bucket of some warm-up step."""
    t = spec.load_traffic("agentctx")
    s = cfg["serving"]
    chunk, bucket = int(s["max_prefill_tokens"]), 64

    def buckets_of(prompt, beside):
        out, left = set(), prompt
        while left > 0:
            n = min(left, chunk) + beside
            out.add(32 if n <= 32 else -(-n // bucket) * bucket)
            left -= min(left, chunk)
        return out | {32}

    got = set()
    for stage in t["warmup"]:
        for i, r in enumerate(stage["requests"]):
            got |= buckets_of(r["prompt_tokens"], 1 if i > 0 else 0)
    assert got == {32} | set(range(64, 577, 64))


def test_the_reference_is_the_models_forward_and_the_control_is_not():
    """At the tiny size the plain reference, with its own weights from
    the seed and its own YaRN, gives what ``LagunaForCausalLM.forward``
    gives on the builder's model; ``lower="int8"`` does not; the gate,
    the half rotation and the window are in it."""
    import jax.numpy as jnp

    from harness import weights as W
    ref = spec.load_reference("laguna")
    cfg = _tiny()
    seq = np.random.default_rng(0).integers(0, 512, 90).tolist()
    a = ref.logits_at(cfg, 7, [seq], [80], 8, 128)
    b = ref.logits_at(cfg, 7, [seq], [80], 8, 128, lower="int8")
    again = ref.logits_at(cfg, 7, [seq, seq[:20]], [80, 10], 8, 128)
    assert a.shape == (1, 8, 512) and np.isfinite(a).all()
    np.testing.assert_allclose(again[0], a[0], atol=1e-5)
    assert 1e-3 < np.abs(a - b).max() < 1.0
    builder = spec.load_builder("laguna")
    model = builder.construct(cfg)
    builder.place(model, W.make_all(spec.load_shapes("laguna").leaves(cfg),
                                    7, jnp.dtype(cfg["dtype"])))
    fwd = np.asarray(model.forward(np.asarray([seq]))._data)[0, 80:88]
    np.testing.assert_allclose(a[0], fwd, atol=2e-4, rtol=0)
    # each part of the layer shows in the logits
    wide = dict(cfg, sliding_window=4096)
    whole = _overlay(cfg, {"rope_parameters": {"full_attention": {
        "partial_rotary_factor": 1}}})
    plain = _overlay(cfg, {"rope_parameters": {"full_attention": {
        "rope_type": "default"}}})
    for other in (wide, whole, plain):
        c = ref.logits_at(other, 7, [seq], [80], 8, 128)
        assert np.abs(a - c).max() > 1e-3
    early = ref.logits_at(cfg, 7, [seq], [20], 8, 128)
    early_wide = ref.logits_at(wide, 7, [seq], [20], 8, 128)
    np.testing.assert_allclose(early, early_wide, atol=1e-5)
    with pytest.raises(ValueError):
        ref.logits_at(cfg, 7, [seq], [80], 8, 64)      # over pad_to
    with pytest.raises(ValueError, match="gating"):
        spec.load_shapes("laguna").dims(dict(cfg, gating=False))


def test_new_readers_read_nothing_from_a_program_without_what_they_read():
    """On the parent, or in a cell of another architecture, the two
    readers return None and do not raise."""
    dense = spec.load_shapes("llama_dense")
    commit = {"ph": "X", "name": "engine.sample_commit", "ts": 5, "dur": 1,
              "args": {"step": 1, "finished": 0}}
    ctx = {"trace": {"busy_s": 1.0, "events": [], "host_window": (0, 1)},
           "arch": dense, "spans": [commit], "c0": {}, "c1": {},
           "t_open": 0, "t_close": 100, "cfg": {}, "program_scopes": {},
           "device_kind": "TPU v5 lite"}
    for name in sorted(NEW_READERS):
        assert spec.load_reader(name)(ctx) is None
        assert spec.load_reader(name)(dict(ctx, trace=None)) is None
    # the parent counts touched experts and not how many it holds
    commit["args"]["moe_experts_touched"] = 100
    assert spec.load_reader("moe.touched_share")(ctx) is None


def test_touched_share_is_the_launches_mean_over_what_is_held():
    spans = [{"ph": "X", "name": "engine.sample_commit", "ts": t, "dur": 1,
              "args": {"step": i, "moe_experts_touched": n}}
             for i, (t, n) in enumerate([(5, 1536), (9, 960), (13, 1536),
                                         (200, 7)])]
    ctx = {"spans": spans, "t_open": 0, "t_close": 100,
           "c0": {"moe_experts_held": 1536}, "c1": {"moe_experts_held": 1536}}
    got = spec.load_reader("moe.touched_share")(ctx)
    assert got == pytest.approx(100 * (1 + 0.625 + 1) / 3)


def test_gate_share_is_the_time_under_its_scope(cfg, monkeypatch):
    arch = spec.load_shapes("laguna")
    assert arch.GATE_SCOPES == ("attn_gate",) and "attn_gate" in arch.SCOPES
    assert scopes.scope_of(
        "jit(ragged_step_t64)/layers/jit(layer)/attn_gate/dot_general",
        arch) == "attn_gate"
    evs = [{"name": "fusion.1", "self_ns": 300, "scope": "attn_gate",
            "shape": "bf16[64,6144]"},
           {"name": "fusion.2", "self_ns": 100, "scope": "attn_gate",
            "shape": "bf16[64,8192]"},
           {"name": "fusion.3", "self_ns": 5000, "scope": "moe_experts",
            "shape": "bf16[512,2048]"}]
    monkeypatch.setattr(scopes, "scoped_events", lambda ctx: evs)
    ctx = {"arch": arch, "cfg": cfg, "trace": {"busy_s": 1e-5}}
    assert spec.load_reader("attn.gate_device_share")(ctx) \
        == pytest.approx(100 * 400 / 1e4)
    monkeypatch.setattr(scopes, "scoped_events", lambda ctx: evs[2:])
    assert spec.load_reader("attn.gate_device_share")(ctx) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cells_command_end_to_end_at_a_tiny_size(trace):
    bench = spec.load_benchmark()
    cmd = bench["command"] + ["--workload", CELL, "--seed",
                              str(2**31 + 91), "--seconds", "3", "--trace",
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
        # counted by the program, so read on the CPU too: 4 rows of 4
        # experts over 16 a layer touch most and not all; the device
        # trace's readers return nothing there, never 0
        assert 40 < res["metrics"]["moe.touched_share"]["value"] < 100
        assert 20 < res["metrics"]["kv.window_pages_share"]["value"] < 100
        assert res["metrics"]["step.decode_ms"]["value"] > 0
        assert "attn.gate_device_share" not in res["metrics"]
        assert "attn.window_roofline_share" not in res["metrics"]
    else:
        assert set(res["metrics"]) == names
