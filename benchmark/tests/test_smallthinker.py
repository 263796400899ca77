"""What PR 32 added, all as new files and entries: the architecture
``smallthinker`` (reference, shapes, builder), the configuration
``smallthinker-21b-a3b-d8``, the mix ``mixedlen``, the cell
``smallthinker21b.mixedlen`` and three readers (``attn.window_*``,
``kv.window_pages_share``)."""
import collections
import json
import math
import os
import statistics
import subprocess

import numpy as np
import pytest

from harness import spec
from harness.kinds import closed_loop as CL

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
REHEARSAL = os.path.join(HERE, "data", "rehearsal_smallthinker.json")
CELL, CONFIG = "smallthinker21b.mixedlen", "smallthinker-21b-a3b-d8"
NEW_READERS = {"attn.window_device_share", "attn.window_roofline_share",
               "kv.window_pages_share"}


@pytest.fixture(scope="module")
def cfg():
    return spec.load_config(spec.load_benchmark(), CONFIG)


def _tiny():
    cfg = spec.load_config(spec.load_benchmark(), CONFIG)
    with open(REHEARSAL) as f:
        over = json.load(f)["config"]
    for k, v in over.items():
        cfg[k] = {**cfg[k], **v} if isinstance(v, dict) else v
    return cfg


def test_the_new_files_are_found_by_name(cfg):
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "mixedlen", 1)
    assert cfg["reference"] == "smallthinker"
    assert callable(spec.load_reference("smallthinker").logits_at)
    assert callable(spec.load_builder("smallthinker").construct)
    assert callable(spec.load_shapes("smallthinker").attention_row)
    assert spec.load_traffic("mixedlen")["kind"] == "closed_loop"
    e2e = {m["name"] for m in spec.metrics_for(bench, "end_to_end", CELL)}
    assert e2e == {"out_tokens_per_s", "gap_p95_ms", "setup_s"}
    per = {m["name"] for m in spec.metrics_for(bench, "per_layer", CELL)}
    assert NEW_READERS | {"moe.roofline_share", "attn.roofline_share",
                          "matmul.roofline_share"} <= per
    # what reads nothing here lists the cell nowhere
    assert not per & {"kvpool.copy_share", "step.decode_ms",
                      "engine.prefix_hit_share",
                      "frontend.ttft_overhead_ms"}
    for name in per:
        assert callable(spec.load_reader(name))
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert CELL in m["workloads"]     # later cells list them too
            assert m["moves"] == "out_tokens_per_s"


def test_every_published_width_is_in_the_file(cfg):
    """The catalog row's numbers, key for key, but the one in
    ``reduced``; the layouts keep their 52 published entries."""
    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog on this machine")
    with open(path, encoding="utf-8") as f:
        for line in f:
            d = json.loads(line)
            if d["name"] == "SmallThinker-21BA3B-Instruct":
                row = d
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "-") != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers"}
    r = cfg["reduced"]["num_hidden_layers"]
    assert (r["published"], r["here"]) == (52, 8) == (
        row["config"]["num_hidden_layers"], cfg["num_hidden_layers"])
    bench = spec.load_benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == row["source_url"]


def test_the_cut_and_its_arithmetic(cfg):
    """Two whole periods: 7.93 GB of bfloat16 weights held once, 2.15 GB
    of global-layer pages and 1.82 GB of window-layer pages; one table
    for all eight layers would be 8.59 GB, which does not fit beside the
    weights."""
    arch = spec.load_shapes("smallthinker")
    m = arch.dims(cfg)
    assert m["window"] == [False, True, True, True] * 2
    assert (m["Lg"], m["Lw"], m["nh"] // m["kvh"]) == (2, 6, 7)
    assert m["nh"] * m["d"] == 3584 != m["H"]
    by_layer = collections.Counter()
    for _n, at, shape, _k in arch.leaves(cfg):
        by_layer[at] += math.prod(shape)
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert attention == 20_971_520
    assert by_layer[0] == by_layer[7] == attention + 163_840 \
        + 64 * 5_898_240 + 2 * 2560
    assert by_layer[None] == 2 * 151936 * 2560 + 2560
    assert round(2 * by_layer[0] / 1e9, 3) == 0.797
    assert round(2 * sum(by_layer.values()) / 1e9, 2) == 7.93
    assert arch.window_blocks(cfg) == 32 * (256 + 32 + 1) + 1 == 9249
    page = 2 * 4 * 16 * 128 * 2                      # K and V, one layer
    assert page == 32 * 1024
    pools = {s for s in arch.pool_shapes(cfg) if len(s) == 5 and s[0] > 1}
    assert pools == {(2, 32769, 4, 16, 128), (6, 9249, 4, 16, 128)}
    assert round(2 * 32769 * page / 1e9, 2) == 2.15
    assert round(6 * 9249 * page / 1e9, 2) == 1.82
    assert round(8 * 32769 * page / 1e9, 2) == 8.59
    assert 7.93 + 8.59 > 16 > 7.93 + 2.15 + 1.82
    s = cfg["serving"]
    assert s["num_blocks"] == 1 + s["max_num_seqs"] * (
        s["max_model_len"] // s["block_size"])
    assert s["enable_prefix_caching"] is False


def test_attention_counts_only_what_lies_inside_the_window(cfg):
    arch = spec.load_shapes("smallthinker")
    m = arch.dims(cfg)
    per_pair = 4 * m["nh"] * m["d"]
    # a decode row at 12,000 keys: 4,096 keys a window layer, all of them
    # a global layer
    ow, bw = arch.window_attention_row(cfg, 1, 12000)
    assert ow == 6 * per_pair * 4096
    oa, ba = arch.attention_row(cfg, 1, 12000)
    assert oa == ow + 2 * per_pair * 12000
    kv = 2 * 4 * 128 * 2                       # K and V of a position, bytes
    assert bw == 6 * (4096 * kv + kv + 2 * 28 * 128 * 2)
    assert ba - bw == 2 * (12000 * kv + kv + 2 * 28 * 128 * 2)
    # under the window both kinds see the same
    o_w, _ = arch.window_attention_row(cfg, 1, 3000)
    assert arch.attention_row(cfg, 1, 3000)[0] == o_w // 6 * 8
    # a chunk is its tokens, at, across and past the window's edge
    for n_q, kv_len in ((512, 512), (512, 4096), (512, 4300), (512, 9000),
                        (7, 4099)):
        chunk = arch.window_attention_row(cfg, n_q, kv_len)[0]
        rows = sum(arch.window_attention_row(cfg, 1, kv_len - n_q + 1 + i)[0]
                   for i in range(n_q))
        assert chunk == rows, (n_q, kv_len)
    # a chunk past the window reads its own rows and the window before
    _, b = arch.window_attention_row(cfg, 512, 9000)
    assert b == 6 * ((4095 + 512) * kv + 512 * kv + 512 * 2 * 28 * 128 * 2)


def test_products_follow_the_work(cfg):
    arch = spec.load_shapes("smallthinker")
    ops, byt = arch.step_matmuls(cfg, 540, 29)
    ops0, byt0 = arch.step_matmuls(cfg, 0, 0)
    assert ops0 == 0 and byt0 == 2 * (8 * (20_971_520 + 163_840)
                                      + 2560 * 151936)
    assert ops == 2 * 540 * 8 * (20_971_520 + 163_840) \
        + 2 * 29 * 2560 * 151936 and byt > byt0
    # ReGLU: three matrices an expert, [2560, 768] twice and [768, 2560]
    e_ops, e_byt = arch.expert_products(cfg, 540 * 6 * 8, 512)
    assert e_ops == 2 * 540 * 6 * 8 * 3 * 2560 * 768
    assert e_byt > 512 * 3 * 2560 * 768 * 2 == 8 * 64 * 5_898_240 * 2
    assert round(512 * 3 * 2560 * 768 * 2 / 1e9, 2) == 6.04
    assert arch.expert_products(cfg, 0, 0) == (0, 0)


def test_mixedlen_pairs_are_the_stated_laws():
    t = spec.load_traffic("mixedlen")
    d = t["distribution"]

    def law(name, n):
        return CL.stratified(d[name]["median"], d[name]["sigma"],
                             d[name]["min"], d[name]["max"], n=n)

    short, long_, out = law("short", 32), law("long", 32), law("output", 64)
    assert (d["short"]["median"], d["short"]["sigma"], d["short"]["min"],
            d["short"]["max"]) == (384, 0.8, 64, 2048)
    assert (d["long"]["median"], d["long"]["sigma"], d["long"]["min"],
            d["long"]["max"]) == (9216, 0.3, 5120, 14336)
    assert (d["output"]["median"], d["output"]["sigma"], d["output"]["min"],
            d["output"]["max"]) == (256, 0.6, 32, 1024)
    assert [a for a, _ in t["pairs"]] == short + long_
    assert [b for _, b in t["pairs"]] == [out[(37 * i + 11) % 64]
                                          for i in range(64)]
    assert statistics.mean(short) == pytest.approx(516, abs=1)
    assert statistics.mean(long_) == pytest.approx(9502, abs=1)
    assert statistics.mean(out) == pytest.approx(304, abs=1)
    # a short request never passes 3,072 positions: it gives no page back
    assert max(a + b for a, b in t["pairs"][:32]) < 3072
    # a long one is past the window from its tenth chunk on
    assert min(a for a, _ in t["pairs"][32:]) > 4096 + 512
    order = t["deal"]["order"]
    assert sorted(order) == list(range(64))
    assert all(x < 32 for x in order[0::2]) and all(
        x >= 32 for x in order[1::2])                # the laws interleave
    a = CL.balanced_order(2190, 32, 8)
    b = [32 + x for x in CL.balanced_order(2190 + 100003, 32, 8)]
    assert order == [x for ab in zip(a, b) for x in ab]
    assert t["prefixes"] == [] and t["clients"] == 32
    assert t["primer"] == {"prompt_tokens": 512, "phase_max": 152}
    assert t["window_open"] == spec.load_traffic("docs")["window_open"]
    assert t["reference_pad_to"] >= 15360 >= max(
        a + b for a, b in t["pairs"])
    assert t["reference_score_rows"] >= max(b for _, b in t["pairs"])
    for seed in (3, 2**31 + 17):
        got = collections.Counter()
        for j in range(64):
            r = CL.dealt_request(t, seed, j, vocab=1000)
            got[(len(r["prompt"]), r["max_tokens"])] += 1
        assert got == collections.Counter(map(tuple, t["pairs"]))


def test_mixedlen_warmup_builds_every_bucket_to_576(cfg):
    """Every token bucket a step of this cell can take (32 decode rows
    and a 512-token chunk: 32, then multiples of 64 up to 576) is the
    bucket of some warm-up step."""
    t = spec.load_traffic("mixedlen")
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


def test_the_control_is_a_different_reference():
    """``lower="int8"`` rounds every matrix, each expert's own: its
    logits are not the reference's.  The window is a mask the reference
    applies: past it the logits differ from a model without one."""
    ref = spec.load_reference("smallthinker")
    cfg = _tiny()
    seq = np.random.default_rng(0).integers(0, 512, 90).tolist()
    a = ref.logits_at(cfg, 7, [seq], [80], 8, 128)
    b = ref.logits_at(cfg, 7, [seq], [80], 8, 128, lower="int8")
    again = ref.logits_at(cfg, 7, [seq, seq[:20]], [80, 10], 8, 128)
    assert a.shape == (1, 8, 512) and np.isfinite(a).all()
    np.testing.assert_allclose(again[0], a[0], atol=1e-5)
    assert 1e-3 < np.abs(a - b).max() < 1.0
    wide = dict(cfg, sliding_window_size=4096)
    c = ref.logits_at(wide, 7, [seq], [80], 8, 128)
    early = ref.logits_at(cfg, 7, [seq], [20], 8, 128)
    early_wide = ref.logits_at(wide, 7, [seq], [20], 8, 128)
    assert np.abs(a - c).max() > 1e-3            # position 80 is past 32
    np.testing.assert_allclose(early, early_wide, atol=1e-5)
    with pytest.raises(ValueError):
        ref.logits_at(cfg, 7, [seq], [80], 8, 64)      # over pad_to


def test_window_readers_read_nothing_from_a_program_without_windows():
    """On the parent, or in a cell of another architecture, the three
    readers return None and do not raise."""
    dense = spec.load_shapes("llama_dense")
    ctx = {"trace": {"busy_s": 1.0, "events": [], "host_window": (0, 1)},
           "arch": dense, "spans": [
               {"ph": "X", "name": "engine.device_launch", "ts": 5, "dur": 1,
                "args": {"step": 1, "kv_pages": 7}}],
           "t_open": 0, "t_close": 100, "cfg": {}, "device_kind": "TPU v5 lite"}
    for name in sorted(NEW_READERS):
        assert spec.load_reader(name)(ctx) is None
        assert spec.load_reader(name)(dict(ctx, trace=None)) is None
    ctx["spans"][0]["args"].update(kv_pages_uniform=8, kv_pages_window=2)
    ctx["spans"].append({"ph": "X", "name": "engine.device_launch", "ts": 9,
                         "dur": 1, "args": {"step": 2, "kv_pages_uniform": 4,
                                            "kv_pages_window": 3}})
    assert spec.load_reader("kv.window_pages_share")(ctx) == 50.0


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
        # counted by the program, so read on the CPU too: prompts of 58
        # to 110 tokens under a window of 32 give pages back; the device
        # trace's readers return nothing there, never 0
        assert 20 < res["metrics"]["kv.window_pages_share"]["value"] < 100
        assert res["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
        assert "attn.window_roofline_share" not in res["metrics"]
        assert "attn.window_device_share" not in res["metrics"]
    else:
        assert set(res["metrics"]) == names
