"""What PR 42 added, all as new files and entries: the architecture
``phi4flash`` (reference, shapes, builder), the configuration
``phi4-mini-flash-reasoning`` (served whole), the mix ``reason``, the
cell ``phi4flash.reason`` and four readers (``ssm.device_share``,
``ssm.roofline_share``, ``attn.cross_device_share``,
``gmu.device_share``)."""
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
REHEARSAL = os.path.join(HERE, "data", "rehearsal_phi4flash.json")
CELL, CONFIG = "phi4flash.reason", "phi4-mini-flash-reasoning"
NEW_READERS = {"ssm.device_share", "ssm.roofline_share",
               "attn.cross_device_share", "gmu.device_share"}


@pytest.fixture(scope="module")
def cfg():
    return spec.load_config(spec.load_benchmark(), CONFIG)


@pytest.fixture(scope="module")
def arch():
    return spec.load_shapes("phi4flash")


def test_the_new_files_are_found_by_name(cfg):
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "reason", 1)
    assert cfg["reference"] == "phi4flash"
    assert callable(spec.load_reference("phi4flash").logits_at)
    assert callable(spec.load_builder("phi4flash").construct)
    assert callable(spec.load_shapes("phi4flash").scan_row)
    assert spec.load_traffic("reason")["kind"] == "closed_loop"
    e2e = {m["name"] for m in spec.metrics_for(bench, "end_to_end", CELL)}
    assert e2e == {"out_tokens_per_s", "gap_p95_ms", "setup_s"}
    per = {m["name"] for m in spec.metrics_for(bench, "per_layer", CELL)}
    assert NEW_READERS | {"attn.roofline_share", "matmul.roofline_share",
                          "attn.window_roofline_share", "step.decode_ms",
                          "step.device_decode_ms",
                          "kv.window_pages_share"} <= per
    # what reads nothing here lists the cell nowhere
    assert not per & {"kvpool.copy_share", "engine.prefix_hit_share",
                      "frontend.ttft_overhead_ms", "moe.device_share",
                      "attn.gate_device_share", "attn.index_device_share",
                      "attn.select_device_share", "attn.selected_share"}
    for name in per:
        assert callable(spec.load_reader(name))
    for m in bench["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "out_tokens_per_s"
    assert len(bench["workloads"]) == 8
    assert not [w for w in bench["workloads"] if w["chips"] != 1]


def test_the_configuration_is_the_catalog_rows_and_is_whole(cfg):
    """Every number of the catalog row under its key; ONE reduced key,
    and no width, no depth, no vocabulary among it."""
    row = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 10240, "layer_norm_eps": 1e-05,
           "mb_per_layer": 2, "model_type": "phi4flash",
           "num_attention_heads": 40, "num_hidden_layers": 32,
           "num_key_value_heads": 20, "resid_pdrop": 0,
           "sliding_window": 512, "tie_word_embeddings": True,
           "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}
    for k, v in row.items():
        assert cfg[k] == v, k
    assert list(cfg["reduced"]) == ["max_position_embeddings"]
    assert cfg["reduced"]["max_position_embeddings"]["published"] == 262144
    assert cfg["max_position_embeddings"] == 8192 \
        == cfg["serving"]["max_model_len"]
    entry, = [c for c in spec.load_benchmark()["configs"]
              if c["name"] == CONFIG]
    assert entry["reduced"] == ["max_position_embeddings"]
    assert cfg["assumed_sizes"] == {"d_state": 16, "d_conv": 4,
                                    "expand": 2, "dt_rank": 160}
    assert len(cfg["assumed"]) >= 9


def test_the_arithmetic_of_the_issue(cfg, arch):
    """3,852.1M parameters in matrices (3,852.6M with norms, biases and
    vectors), 7.70 GB in bfloat16, and the three sets of cached rows."""
    m = arch.dims(cfg)
    H, F, di = 2560, 10240, 5120
    ffn = 3 * H * F
    mamba = H * 2 * di + di * 4 + di + di * 192 + 160 * di + di \
        + di * 16 + di + di * H
    attn = H * 5120 + H * H
    cross = 2 * H * H
    gmu = 2 * H * di
    assert ffn == 78_643_200 and round(mamba / 1e6, 2) == 41.24
    assert (attn, cross, gmu) == (19_660_800, 13_107_200, 26_214_400)
    layers = 9 * (mamba + ffn) + 9 * (attn + ffn) + 7 * (gmu + ffn) \
        + 7 * (cross + ffn)
    total = layers + 200064 * H
    assert round(total / 1e6, 1) == 3852.1
    # every leaf: the norms, biases and lambda vectors on top
    assert 0 < arch.parameters(cfg) - total < 1e6
    assert round(arch.parameters(cfg) / 1e6, 1) == 3852.6
    assert round(arch.parameters(cfg) * 2 / 1e9, 2) == 7.71
    s = cfg["serving"]
    page = 10 * 16 * 128 * 2                       # one K or V page
    assert s["num_blocks"] == 32 * (8192 // 16) + 1 == 16385
    assert round(2 * 16385 * page / 1e9, 2) == 1.34
    assert arch.window_blocks(cfg) == 32 * (32 + 16 + 1) + 1 == 1569
    assert round(8 * 2 * 1569 * page / 1e9, 2) == 1.03
    assert arch.state_bytes(cfg) == 9 * 33 * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert round(arch.state_bytes(cfg) / 1e9, 2) == 0.11
    assert (m["Lw"], m["Ls"], m["Lx"]) == (8, 9, 7)
    assert arch.pool_shapes(cfg) >= {
        (1, 16385, 10, 16, 128), (8, 1569, 10, 16, 128),
        (9, 33, 3, 5120), (9, 33, 16, 5120), (33, 16, 5120)}


def test_leaves_are_declared_stacked_by_run(cfg, arch):
    leaves = arch.leaves(cfg)
    m = arch.dims(cfg)
    assert [l[0] for l in leaves[:3]] == ["embed", "norm_f", "norm_f_b"]
    by = collections.defaultdict(dict)
    for name, tag, shape, kind in leaves[3:]:
        by[tag][name] = (shape, kind)
    assert sorted(by) == [0, 1, 2, 3]
    assert by[0]["0.w_in"] == ((8, 2560, 10240), "matrix")
    assert by[0]["0.A_log"] == ((8, 16, 5120), "norm")
    assert by[0]["1.wqkv"] == ((8, 2560, 5120), "matrix")
    assert by[1]["0.w_out"] == ((5120, 2560), "matrix")
    assert by[2]["0.wo"] == ((2560, 2560), "matrix")
    assert by[3]["0.w_in"] == ((7, 2560, 5120), "matrix")     # no z half
    assert by[3]["1.wq"] == ((7, 2560, 2560), "matrix")
    assert "1.wqkv" not in by[3] and "0.conv_w" not in by[3]
    # a depth finds its run, its place and its repeat
    assert [arch.layer_of(m, i) for i in (0, 1, 15, 16, 17, 18, 31)] == [
        (0, 0, 0), (0, 1, 0), (0, 1, 7), (1, 0, 0), (2, 0, 0), (3, 0, 0),
        (3, 1, 6)]
    assert [arch.kind_of(m, i) for i in (0, 15, 16, 17, 18, 31)] == [
        "ssm", "diff_window", "ssm_keep", "diff", "gmu", "diff_cross"]


def test_published_scales_keep_a_state_alive_over_the_longest_request(arch):
    """A_log = log(1..16), softplus(b_dt) inside 1e-3..1e-1, D near 1,
    lambda vectors about 0: with them a unit-scale input leaves a state
    of order one after 6,752 rows, neither dead nor blown up."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    di, N, T = 64, 16, 6752
    drawn = lambda *s: jnp.asarray(1.0 + 0.1 * rng.standard_normal(s),
                                   jnp.bfloat16)
    A_log = arch.published("A_log", drawn(N, di)).astype(jnp.float32)
    assert np.allclose(np.asarray(A_log).mean(1), np.log(np.arange(1, 17)),
                       atol=0.06)
    dt = jax.nn.softplus(arch.published("b_dt", drawn(di))
                         .astype(jnp.float32))
    assert 0.9e-3 <= float(dt.min()) and float(dt.max()) <= 1.1e-1
    assert abs(float(arch.published("D", drawn(di)).astype(
        jnp.float32).mean()) - 1) < 0.1
    lam = arch.published("lq1", drawn(64)).astype(jnp.float32)
    assert abs(float(lam.mean())) < 0.05 and 0.05 < float(lam.std()) < 0.2
    taps = arch.published("conv_w", jnp.asarray(
        rng.standard_normal((4, 5120)) * math.sqrt(2 / 5124), jnp.bfloat16))
    assert 0.25 < float(taps.astype(jnp.float32).std()) < 0.33
    assert arch.published("w_in", drawn(4, 4)) is not None
    A = -jnp.exp(A_log)
    u = jnp.asarray(rng.standard_normal((T, di)), jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((T, N)), jnp.float32)

    def token(s, inp):
        u_t, b_t = inp
        return jnp.exp(dt[None] * A) * s + (dt * u_t)[None] * b_t[:, None], \
            None

    s, _ = jax.lax.scan(token, jnp.zeros((N, di)), (u, Bm))
    s = np.abs(np.asarray(s))
    assert np.isfinite(s).all() and 1e-3 < np.median(s) < 10


def test_the_traffic_files_64_pairs_from_its_laws():
    t = spec.load_traffic("reason")
    d = t["distribution"]
    assert (d["prompt"]["median"], d["prompt"]["sigma"], d["prompt"]["min"],
            d["prompt"]["max"]) == (384, 0.8, 64, 4096)
    assert (d["output"]["median"], d["output"]["sigma"], d["output"]["min"],
            d["output"]["max"]) == (1280, 0.5, 384, 4096)
    p = CL.stratified(384, 0.8, 64, 4096)
    o = CL.stratified(1280, 0.5, 384, 4096)
    assert [a for a, _ in t["pairs"]] == p
    assert [b for _, b in t["pairs"]] == [o[(37 * i + 11) % 64]
                                          for i in range(64)]
    assert round(statistics.mean(p)) == 524 and max(p) == 2656
    assert round(statistics.mean(o)) == 1443 and max(o) == 4096
    order = t["deal"]["order"]
    assert order == CL.balanced_order(2875, 64, 16)
    for i in range(0, 64, 16):
        assert sorted(x // 4 for x in order[i:i + 16]) == list(range(16))
    assert t["clients"] == 32 and t["prefixes"] == []
    assert t["primer"] == {"prompt_tokens": 64, "phase_max": 1408}
    assert t["window_open"] == {"after_dealt_sent": 4}
    assert max(a + b for a, b in t["pairs"]) <= t["reference_pad_to"]


def test_warmup_fills_every_bucket_the_cell_can_reach(cfg):
    # warm-up reaches every bucket of 256-token chunks beside 32 rows
    t = spec.load_traffic("reason")
    s = cfg["serving"]
    chunk, rows, tb = s["max_prefill_tokens"], s["max_num_seqs"], 64

    def bucket(n):
        return rows if n <= rows else -(-n // tb) * tb

    reachable = {bucket(n) for n in range(1, chunk + rows)}
    assert reachable == {32, 64, 128, 192, 256, 320}
    filled = {bucket(1)}
    for stage in t["warmup"]:
        reqs = stage["requests"]
        beside = len(reqs) - 1
        left = reqs[-1]["prompt_tokens"]
        while left > 0:
            n = min(left, chunk)
            filled.add(bucket(n + beside))
            left -= n
    assert filled == reachable


def test_counts_against_a_count_by_hand(cfg, arch):
    """The shapes file's counts at sizes small enough to do by hand."""
    # one decode row at 1,000 keys: the full layer and the seven cross
    # layers see 1,000 keys, the eight window layers 512
    pair = 2 * 40 * 64 + 2 * 20 * 2 * 128          # scores + two maps
    assert pair == 15360
    ops, byt = arch.attention_row(cfg, 1, 1000)
    assert ops == pair * (1000 * (1 + 7) + 512 * 8)
    kv = lambda keys: 2 * keys * 20 * 64
    io = 2 * 40 * 64 + 40 * 128                    # q in, both maps out
    new = 2 * 20 * 64
    assert byt == 2 * ((kv(1000) + io + new) + 7 * (kv(1000) + io)
                       + 8 * (kv(512) + io + new))
    ow, bw = arch.window_attention_row(cfg, 1, 1000)
    ox, bx = arch.cross_attention_row(cfg, 1, 1000)
    assert ow == pair * 512 * 8 and ox == pair * 1000 * 7
    assert bx == 2 * 7 * (kv(1000) + io)
    # a chunk of 4 queries that ends at 6 keys, no window in reach
    ops, _ = arch.attention_row(cfg, 4, 6)
    assert ops == pair * (3 + 4 + 5 + 6) * 16
    # the scan: 3 rows in 2 segments, one of them a start, 9 layers
    ops, byt = arch.scan_step(cfg, 3, 2, 1)
    assert ops == 9 * 3 * (7 * 16 * 5120 + 3 * 5120)
    assert byt == 9 * (3 * (3 * 5120 + 32) * 2 + 3 * 16 * 5120 * 4)
    assert arch.scan_row(cfg, 256, True) == arch.scan_step(cfg, 256, 1, 1)
    # the dots: every token reads every weight once
    ops, byt = arch.step_matmuls(cfg, 1, 1)
    w = 9 * (41_241_600 - 5120 * 4 - 5120 * 3 - 16 * 5120 + 78_643_200) \
        + 9 * (19_660_800 + 78_643_200) + 7 * (26_214_400 + 78_643_200) \
        + 7 * (13_107_200 + 78_643_200)
    assert ops == 2 * w + 2 * 2560 * 200064
    assert byt > 2 * (w + 2560 * 200064)


def test_readers_read_the_new_names_and_nothing_where_there_is_none(
        cfg, arch, monkeypatch):
    from harness import scopes
    evs = [{"name": "%fusion.1", "self_ns": 300, "scope": "ssm_proj",
            "has_dot": True, "step": 7, "shape": "bf16[32,10240]"},
           {"name": "%ragged_selective_scan.3", "self_ns": 500,
            "scope": "ssm_scan", "has_dot": False, "step": 7,
            "shape": "(f32[32,5120], f32[9,33,16,5120])"},
           {"name": "%fusion.9", "self_ns": 200, "scope": "gmu",
            "has_dot": True, "step": 7, "shape": "bf16[32,2560]"},
           {"name": "%ragged_paged_attention_cross.4", "self_ns": 700,
            "scope": "attn_cross", "has_dot": False, "step": 7,
            "shape": "bf16[32,10,4,128]"},
           {"name": "%fusion.2", "self_ns": 8300, "scope": "mlp",
            "has_dot": True, "step": 7, "shape": "bf16[32,10240]"}]
    monkeypatch.setattr(scopes, "scoped_events", lambda ctx: evs)
    monkeypatch.setattr(scopes, "launch_annotations", lambda ctx: [
        {"step": 7, "bucket": 32, "start_ns": 10},
        {"step": 8, "bucket": 32, "start_ns": 60}])
    spans = [{"ph": "X", "name": "engine.device_launch", "ts": 5, "dur": 3,
              "args": {"step": 7, "rows": 32, "state_rows": 32,
                       "state_starts": 0, "tokens": 32, "logit_rows": 32}}]
    ctx = {"arch": arch, "cfg": cfg, "device_kind": "TPU v5 lite",
           "trace": {"busy_s": 1e-5, "window": (0, 100), "events": evs,
                     "host_window": (0, 100)},
           "spans": spans}
    assert spec.load_reader("ssm.device_share")(ctx) \
        == pytest.approx(100 * 800 / 1e4)
    assert spec.load_reader("gmu.device_share")(ctx) \
        == pytest.approx(100 * 200 / 1e4)
    assert spec.load_reader("attn.cross_device_share")(ctx) \
        == pytest.approx(100 * 700 / 1e4)
    _ops, byt = arch.scan_step(cfg, 32, 32, 0)
    assert spec.load_reader("ssm.roofline_share")(ctx) \
        == pytest.approx(100 * (byt / 819e9) / 500e-9)
    # a program that counts no state rows, or has no such scope or
    # kernel: nothing to read, and no error
    spans[0]["args"].pop("state_rows")
    assert spec.load_reader("ssm.roofline_share")(ctx) is None
    monkeypatch.setattr(scopes, "scoped_events", lambda ctx: evs[4:])
    ctx["trace"]["events"] = evs[4:]
    for name in NEW_READERS:
        assert spec.load_reader(name)(ctx) is None
    dense = spec.load_shapes("llama_dense")
    ctx["arch"] = dense
    for name in NEW_READERS:
        assert spec.load_reader(name)(ctx) is None


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
        # counted by the program, so read on the CPU too; the device
        # trace's readers return nothing there, never 0
        assert 5 < res["metrics"]["kv.window_pages_share"]["value"] <= 100
        assert not NEW_READERS & set(res["metrics"])
    else:
        assert set(res["metrics"]) == names
