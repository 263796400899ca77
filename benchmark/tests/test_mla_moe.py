"""What PR 28 added, all as new files and entries: the architecture
``mla_moe`` (reference, shapes, builder), the configuration
``sarvam-105b-ep4``, the mix ``docs``, the cell ``sarvam105b.docs`` and
the three ``moe.*`` readers."""
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
REHEARSAL = os.path.join(HERE, "data", "rehearsal_mla_moe.json")


@pytest.fixture(scope="module")
def cfg():
    return spec.load_config(spec.load_benchmark(), "sarvam-105b-ep4")


def test_the_new_files_are_found_by_name(cfg):
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, "sarvam105b.docs")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sarvam-105b-ep4", "docs", 1)
    assert cfg["reference"] == "mla_moe"
    assert callable(spec.load_reference("mla_moe").logits_at)
    assert callable(spec.load_builder("mla_moe").construct)
    assert spec.load_traffic("docs")["kind"] == "closed_loop"
    e2e = {m["name"] for m in spec.metrics_for(bench, "end_to_end",
                                               cell["name"])}
    assert e2e == {"out_tokens_per_s", "gap_p95_ms", "setup_s"}
    per = {m["name"] for m in spec.metrics_for(bench, "per_layer",
                                               cell["name"])}
    assert {"moe.device_share", "moe.roofline_share",
            "moe.load_max_over_mean", "attn.roofline_share"} <= per
    assert "engine.prefix_hit_share" not in per      # moves ttft_p50_ms
    for name in per:
        assert callable(spec.load_reader(name))


def test_every_published_width_is_in_the_file(cfg):
    """The catalog row's numbers, key for key, but the four in
    ``reduced``."""
    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog on this machine")
    with open(path, encoding="utf-8") as f:
        for line in f:
            d = json.loads(line)
            if d["name"] == "sarvam-105b":
                row = d
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "-") != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"}
    for k, r in cfg["reduced"].items():
        assert r["published"] == row["config"][k] and r["here"] == cfg[k]


def test_the_cut_and_its_arithmetic(cfg):
    """One dense and five expert layers of one chip's share: 10.92 GB of
    bfloat16 weights, and a pool of 262,144 tokens in 2.01 GB."""
    arch = spec.load_shapes("mla_moe")
    by_layer = collections.Counter()
    for _n, at, shape, _k in arch.leaves(cfg):
        by_layer[at] += math.prod(shape)
    assert by_layer[0] == 295_969_472                 # the dense layer
    assert by_layer[1] == 925_639_488                 # an expert layer
    assert by_layer[None] == 2 * 65536 * 4096 + 4096
    total = sum(by_layer.values())
    assert round(2 * total / 1e9, 2) == 10.92
    (pool,) = [s for s in arch.pool_shapes(cfg) if len(s) == 4
               and s[0] == 6]
    assert pool == (6, 16385, 16, 640)
    assert round(2 * math.prod(pool) / 1e9, 2) == 2.01
    m = arch.dims(cfg)
    assert (m["held"], m["E"], m["first"], m["k"]) == (32, 128, 0, 8)
    # 512 prompt tokens a step: what each held expert sees
    assert 512 * m["k"] / m["E"] == 32


def test_counts_follow_the_work(cfg):
    arch = spec.load_shapes("mla_moe")
    o1, b1 = arch.attention_row(cfg, 1, 4096)
    o2, b2 = arch.attention_row(cfg, 1, 8192)
    assert o2 == 2 * o1 and b1 < b2 < 2 * b1
    chunk = arch.attention_row(cfg, 512, 4096)[0]
    rows = sum(arch.attention_row(cfg, 1, 4096 - 511 + i)[0]
               for i in range(512))
    assert chunk == rows                       # a chunk is its tokens
    ops, byt = arch.step_matmuls(cfg, 532, 21)
    ops0, byt0 = arch.step_matmuls(cfg, 0, 0)
    assert ops0 == 0 and byt0 > 2.3e9          # weights read once a step
    assert ops > 0 and byt > byt0
    # the routed experts are not in it: 5 layers x 32 experts x 3 matrices
    e_ops, e_byt = arch.expert_products(cfg, 1064 * 5, 160)
    assert e_byt > 160 * 3 * 4096 * 2048 * 2
    assert e_ops == 2 * 1064 * 5 * 3 * 4096 * 2048
    assert arch.expert_products(cfg, 0, 0) == (0, 0)


def test_docs_pairs_are_the_stated_distribution():
    t = spec.load_traffic("docs")
    d = t["distribution"]
    p = CL.stratified(d["prompt"]["median"], d["prompt"]["sigma"],
                      d["prompt"]["min"], d["prompt"]["max"])
    o = CL.stratified(d["output"]["median"], d["output"]["sigma"],
                      d["output"]["min"], d["output"]["max"])
    assert [a for a, _ in t["pairs"]] == p
    assert [b for _, b in t["pairs"]] == [o[(37 * i + 11) % 64]
                                          for i in range(64)]
    assert statistics.mean(p) == pytest.approx(3827, abs=2)
    assert statistics.mean(o) == pytest.approx(152, abs=1)
    order = t["deal"]["order"]
    assert order == CL.balanced_order(3851, 64, 16)
    for i in range(0, 64, 16):
        assert sorted(x // 4 for x in order[i:i + 16]) == list(range(16))
    assert t["prefixes"] == [] and t["clients"] == 32
    assert t["primer"] == {"prompt_tokens": 512, "phase_max": 152}


def test_docs_warmup_builds_every_bucket_to_576(cfg):
    """Every token bucket a step of this cell can take (32 decode rows
    and a 512-token chunk: 32, then multiples of 64 up to 576) is the
    bucket of some warm-up step."""
    t = spec.load_traffic("docs")
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
        reqs = stage["requests"]
        for i, r in enumerate(reqs):
            got |= buckets_of(r["prompt_tokens"], 1 if i > 0 else 0)
    assert got == {32} | set(range(64, 577, 64))


def test_the_control_is_a_different_reference():
    """``lower="int8"`` rounds every matrix, each expert's own: its
    logits are not the reference's."""
    ref = spec.load_reference("mla_moe")
    with open(REHEARSAL) as f:
        over = json.load(f)["config"]
    cfg = spec.load_config(spec.load_benchmark(), "sarvam-105b-ep4")
    for k, v in over.items():
        cfg[k] = {**cfg[k], **v} if isinstance(v, dict) else v
    seq = np.random.default_rng(0).integers(0, 512, 40).tolist()
    a = ref.logits_at(cfg, 7, [seq], [30], 8, 64)
    b = ref.logits_at(cfg, 7, [seq], [30], 8, 64, lower="int8")
    again = ref.logits_at(cfg, 7, [seq, seq[:20]], [30, 10], 8, 64)
    assert a.shape == (1, 8, 512) and np.isfinite(a).all()
    np.testing.assert_allclose(again[0], a[0], atol=1e-5)
    assert 1e-3 < np.abs(a - b).max() < 1.0
    with pytest.raises(ValueError):
        ref.logits_at(cfg, 7, [seq], [30], 8, 32)      # over pad_to


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cells_command_end_to_end_at_a_tiny_size(trace):
    bench = spec.load_benchmark()
    cmd = bench["command"] + ["--workload", "sarvam105b.docs", "--seed",
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
        bench, "per_layer" if trace else "end_to_end", "sarvam105b.docs")}
    assert set(res["metrics"]) <= names
    if trace:
        # counted by the program, so read on the CPU too; the device
        # trace's readers return nothing there, never 0
        assert res["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
        assert "moe.roofline_share" not in res["metrics"]
        assert "moe.device_share" not in res["metrics"]
    else:
        assert set(res["metrics"]) == names
