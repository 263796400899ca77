"""Autotuner contract: cache persistence/resolution, env migration,
tuned-config output invariance, and the CPU end-to-end sweep path.

Correctness bar: a tuning config may change WHEN work happens (block
shapes, pages per grid step) but never WHAT is computed — greedy outputs
must be byte-identical across tuned configs, and consulting the cache
must never add a compile (``compile_counts`` pinned)."""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.tune import (TuningCache, bucket_signature, cache_path,
                             current_cache, kernel_config,
                             kernel_config_with_meta, reset_provenance,
                             set_cache_path)
from paddle_tpu.tune import cache as tune_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def clean_tune(tmp_path, monkeypatch):
    """Isolated cache file + no env levers; restores global state."""
    monkeypatch.delenv("PADDLE_TPU_TUNE_CACHE", raising=False)
    monkeypatch.delenv("PADDLE_TPU_TUNE_FORCE", raising=False)
    monkeypatch.delenv("PADDLE_TPU_FA_BLOCK_Q", raising=False)
    monkeypatch.delenv("PADDLE_TPU_FA_BLOCK_K", raising=False)
    path = str(tmp_path / "tuning_cache.json")
    set_cache_path(path)
    reset_provenance()
    yield path
    set_cache_path(None)
    reset_provenance()


# ---------------------------------------------------------------------------
# cache persistence
# ---------------------------------------------------------------------------

def test_cache_round_trip(tmp_path):
    path = str(tmp_path / "c.json")
    c = TuningCache(path)
    c.put("cpu", "flash_attention", "head_dim=128,seq_q=2048",
          {"block_q": 1024, "block_k": 256}, score_s=1e-4,
          measure="cost-model")
    saved = c.save()
    assert saved == path and os.path.exists(path)
    # fresh instance reads the same winner back
    c2 = TuningCache(path)
    assert c2.lookup("cpu", "flash_attention", "head_dim=128,seq_q=2048") \
        == {"block_q": 1024, "block_k": 256}
    assert len(c2) == 1
    assert c2.kernels("cpu") == {"flash_attention"}
    doc = json.load(open(path))
    assert doc["version"] == 1
    rec = doc["entries"]["cpu|flash_attention|head_dim=128,seq_q=2048"]
    assert rec["measure"] == "cost-model" and rec["score_s"] == 1e-4


def test_corrupt_cache_degrades_to_defaults(clean_tune):
    with open(clean_tune, "w") as f:
        f.write("{not json at all")
    with pytest.warns(RuntimeWarning, match="unreadable"):
        cfg = kernel_config("flash_attention",
                            {"seq_q": 64, "seq_k": 64, "head_dim": 64,
                             "dtype": "float32"})
    # registry defaults, not a crash
    assert cfg == {"block_q": 512, "block_k": 512}
    # warns once per cache instance, not per lookup
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        kernel_config("flash_attention",
                      {"seq_q": 128, "seq_k": 128, "head_dim": 64,
                       "dtype": "float32"})


def test_missing_cache_is_empty_not_warning(clean_tune):
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        cfg, meta = kernel_config_with_meta(
            "fused_norms", {"rows": 32, "hidden": 64, "dtype": "float32"})
    assert meta["source"] == "default" and meta["hit"] is False
    assert cfg == {"block_r": 256}


# ---------------------------------------------------------------------------
# resolution chain: device key, exact, bucket, defaults
# ---------------------------------------------------------------------------

def test_bucket_signature_pow2_and_sorted():
    assert bucket_signature({"seq_q": 1000, "dtype": "bf16", "b": 1}) \
        == "b=1,dtype=bf16,seq_q=1024"


def test_device_key_isolates_entries(clean_tune, monkeypatch):
    shape = {"seq_q": 2048, "seq_k": 2048, "head_dim": 128,
             "dtype": "float32"}
    sig = bucket_signature(shape)
    c = current_cache()
    c.put("tpu-v4", "flash_attention", sig, {"block_q": 1024,
                                             "block_k": 1024})
    c.save()
    # this process resolves as some other device -> the tpu-v4 winner
    # must NOT leak into its launches
    monkeypatch.setattr(tune_cache, "device_kind", lambda: "cpu")
    cfg, meta = kernel_config_with_meta("flash_attention", shape)
    assert meta["source"] == "default" and cfg["block_q"] == 512
    # and the owning device sees it as an exact hit
    monkeypatch.setattr(tune_cache, "device_kind", lambda: "tpu-v4")
    cfg, meta = kernel_config_with_meta("flash_attention", shape)
    assert meta["source"] == "exact" and meta["hit"] is True
    assert cfg == {"block_q": 1024, "block_k": 1024}


def test_bucket_fallback_nearest_numeric(clean_tune, monkeypatch):
    monkeypatch.setattr(tune_cache, "device_kind", lambda: "cpu")
    c = current_cache()
    near = {"seq_q": 2048, "seq_k": 2048, "head_dim": 128,
            "dtype": "float32"}
    far = {"seq_q": 16384, "seq_k": 16384, "head_dim": 128,
           "dtype": "float32"}
    c.put("cpu", "flash_attention", bucket_signature(near),
          {"block_q": 1024, "block_k": 1024})
    c.put("cpu", "flash_attention", bucket_signature(far),
          {"block_q": 128, "block_k": 128})
    c.save()
    # 4096 is one bucket from 2048 and two from 16384 -> nearest wins
    cfg, meta = kernel_config_with_meta(
        "flash_attention", {"seq_q": 4096, "seq_k": 4096, "head_dim": 128,
                            "dtype": "float32"})
    assert meta["source"] == "bucket" and meta["hit"] is True
    assert meta["matched"] == bucket_signature(near)
    assert cfg == {"block_q": 1024, "block_k": 1024}


def test_bucket_fallback_never_crosses_dtype(clean_tune, monkeypatch):
    monkeypatch.setattr(tune_cache, "device_kind", lambda: "cpu")
    c = current_cache()
    c.put("cpu", "flash_attention",
          bucket_signature({"seq_q": 2048, "seq_k": 2048, "head_dim": 128,
                            "dtype": "bfloat16"}),
          {"block_q": 1024, "block_k": 1024})
    c.save()
    cfg, meta = kernel_config_with_meta(
        "flash_attention", {"seq_q": 2048, "seq_k": 2048, "head_dim": 128,
                            "dtype": "float32"})
    assert meta["source"] == "default"
    assert cfg == {"block_q": 512, "block_k": 512}


# ---------------------------------------------------------------------------
# env-var migration: deprecated levers still win, with a warning
# ---------------------------------------------------------------------------

def test_fa_env_override_wins_and_warns(clean_tune, monkeypatch):
    monkeypatch.setattr(tune_cache, "device_kind", lambda: "cpu")
    shape = {"seq_q": 2048, "seq_k": 2048, "head_dim": 128,
             "dtype": "float32"}
    c = current_cache()
    c.put("cpu", "flash_attention", bucket_signature(shape),
          {"block_q": 1024, "block_k": 1024})
    c.save()
    monkeypatch.setenv("PADDLE_TPU_FA_BLOCK_Q", "256")
    tune_cache._ENV_WARNED.clear()          # re-arm the once-per-process warn
    with pytest.warns(DeprecationWarning, match="PADDLE_TPU_FA_BLOCK_Q"):
        cfg, meta = kernel_config_with_meta("flash_attention", shape)
    # env beats the cache entry for the param it names; the cache still
    # answers the one it doesn't
    assert meta["source"] == "env"
    assert cfg == {"block_q": 256, "block_k": 1024}
    # second lookup: same answer, no second warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert kernel_config("flash_attention", shape)["block_q"] == 256


def test_forced_config_beats_everything(clean_tune, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TUNE_FORCE",
                       json.dumps({"flash_attention": {"block_q": 128,
                                                       "block_k": 128}}))
    monkeypatch.setenv("PADDLE_TPU_FA_BLOCK_Q", "1024")
    cfg, meta = kernel_config_with_meta(
        "flash_attention", {"seq_q": 64, "seq_k": 64, "head_dim": 64,
                            "dtype": "float32"})
    assert meta["source"] == "forced"
    assert cfg == {"block_q": 128, "block_k": 128}


# ---------------------------------------------------------------------------
# tuned configs change the schedule, never the result
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tiles", [(128, 32), (8, 1), (16, 2), (64, 4)])
def test_ragged_kernel_result_invariant_across_tiles(clean_tune,
                                                     monkeypatch, tiles):
    """(q_tile_rows, kv_pages): the built-in tile, then three that cut
    the same launch into more q tiles and K/V blocks.  A K/V block's
    width sets where the online softmax rescales, so the bytes differ
    between widths by summation order and no more: every width agrees
    with the oracle at this file's tolerance."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    monkeypatch.setattr(pa, "INTERPRET", True)
    rng = np.random.RandomState(0)
    Tq, R, nblk, bs, kvh, D = 12, 3, 5, 8, 2, 128
    q = jnp.asarray(rng.randn(Tq, kvh * 2, D), jnp.float32)
    kc = jnp.asarray(rng.randn(R * nblk, kvh, bs, D), jnp.float32)
    vc = jnp.asarray(rng.randn(R * nblk, kvh, bs, D), jnp.float32)
    bt = jnp.asarray(rng.permutation(R * nblk).reshape(R, nblk), jnp.int32)
    cu = jnp.asarray([0, 6, 7, 10], jnp.int32)       # chunk, decode, verify
    kvl = jnp.asarray([6, 40, 29], jnp.int32)
    monkeypatch.setenv("PADDLE_TPU_TUNE_FORCE", json.dumps(
        {"paged_attention": {"q_tile_rows": tiles[0],
                             "kv_pages": tiles[1]}}))
    out = np.asarray(pa.ragged_paged_attention(q, kc, vc, bt, cu, kvl))
    ref = np.asarray(pa.ragged_paged_reference(q, kc, vc, bt, cu, kvl))
    np.testing.assert_allclose(out[:10], ref[:10], rtol=2e-5, atol=2e-5)
    assert not out[10:].any()


def test_engine_outputs_byte_identical_across_tuned_configs(clean_tune,
                                                            tmp_path):
    """Three caches with three distinct tuned configs: the 16-request
    audit stream must produce identical greedy tokens and the identical
    compile footprint -- a cache consult can never add a compile."""
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.tune import device_kind

    vocab = 97
    cfg = LlamaConfig.tiny(vocab=vocab, hidden=32, layers=2, heads=4,
                           ffn=64, seq=64)
    model = LlamaForCausalLM(cfg)
    dev = device_kind()

    def run_with(configs, tag):
        path = str(tmp_path / f"cache_{tag}.json")
        c = TuningCache(path)
        for kern, (shape, conf) in configs.items():
            c.put(dev, kern, bucket_signature(shape), conf)
        c.save()
        set_cache_path(path)
        eng = LLMEngine(model, max_num_seqs=4, block_size=8,
                        max_model_len=64, max_prefill_tokens=128,
                        prefill_token_bucket=32)
        rng = np.random.RandomState(3)
        for i in range(16):
            n = [4, 9, 13, 21][i % 4]
            eng.add_request(rng.randint(0, vocab, n).tolist(),
                            max_new_tokens=4)
        outs = eng.run()
        toks = {rid: tuple(o.token_ids) for rid, o in outs.items()}
        return toks, eng.compile_counts, eng.summary()["tuning_cache"]

    fa_shape = {"seq_q": 64, "seq_k": 64, "head_dim": 8,
                "dtype": "float32"}
    pa_shape = {"tq": 32, "kv_heads": 4, "head_dim": 8, "page": 8,
                "nblk": 8, "dtype": "float32"}
    variants = [
        {"flash_attention": (fa_shape, {"block_q": 128, "block_k": 128}),
         "paged_attention": (pa_shape, {"q_tile_rows": 128,
                                        "kv_pages": 32})},
        {"flash_attention": (fa_shape, {"block_q": 512, "block_k": 256}),
         "paged_attention": (pa_shape, {"q_tile_rows": 64,
                                        "kv_pages": 8})},
        {"flash_attention": (fa_shape, {"block_q": 1024, "block_k": 1024}),
         "paged_attention": (pa_shape, {"q_tile_rows": 256,
                                        "kv_pages": 16})},
    ]
    results = [run_with(v, i) for i, v in enumerate(variants)]
    base_toks, base_compiles, _ = results[0]
    assert base_compiles == {"ragged": 2, "cow": 0}
    for toks, compiles, report in results[1:]:
        assert toks == base_toks
        assert compiles == base_compiles
    # each engine's report names the config its cache carried
    for (_, _, report), v in zip(results, variants):
        got = report["kernels"]["paged_attention"]["config"]
        assert got == v["paged_attention"][1]


# ---------------------------------------------------------------------------
# the CPU end-to-end path: sweep -> cache file -> engine reports hits
# ---------------------------------------------------------------------------

def test_autotune_cli_cost_model_end_to_end(clean_tune, tmp_path):
    cache_file = str(tmp_path / "swept.json")
    script = os.path.join(REPO, "tools", "perf", "autotune.py")
    out = subprocess.run(
        [sys.executable, script, "--cost-model", "--cache", cache_file],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.strip().splitlines() if ln.strip()]
    record = json.loads(lines[-1])
    assert record["metric"] == "autotune_cache_entries"
    assert record["measure"] == "cost-model"
    assert record["value"] > 0
    # the shipped ops/pallas tree has zero untuned launches
    assert record["untuned_launches"] == []
    # the sweep covered all five registered kernels
    c = TuningCache(cache_file)
    assert c.kernels() == {"flash_attention", "flash_attention_varlen",
                           "fused_norms", "paged_attention",
                           "quant_matmul"}
    # a subsequent engine build resolves every kernel from this cache
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    set_cache_path(cache_file)
    cfg = LlamaConfig.tiny(vocab=97, hidden=32, layers=2, heads=4, ffn=64,
                           seq=64)
    eng = LLMEngine(LlamaForCausalLM(cfg), max_num_seqs=4, block_size=8,
                    max_model_len=64, max_prefill_tokens=128,
                    prefill_token_bucket=32)
    report = eng.summary()["tuning_cache"]
    assert report["path"] == cache_file
    for name in ("flash_attention", "flash_attention_varlen",
                 "fused_norms", "paged_attention"):
        assert report["kernels"][name]["hit"] is True, report["kernels"]
    # an f32-weight engine never resolves quant_matmul ...
    assert "quant_matmul" not in report["kernels"]
    # ... and a quantized one resolves it from the same swept cache
    # (bucket: the sweep ran llama-class extents, the tiny engine's
    # shapes fall back to the nearest bucket entry)
    eng8 = LLMEngine(LlamaForCausalLM(cfg), max_num_seqs=4, block_size=8,
                     max_model_len=64, max_prefill_tokens=128,
                     prefill_token_bucket=32, weight_dtype="int8")
    report8 = eng8.summary()["tuning_cache"]
    info = report8["kernels"]["quant_matmul"]
    assert info["source"] in ("exact", "bucket"), report8["kernels"]


def test_run_sweep_cost_model_in_process(clean_tune, tmp_path,
                                         monkeypatch):
    from paddle_tpu.tune import CostModelMeasurer, run_sweep
    monkeypatch.setattr(tune_cache, "device_kind", lambda: "cpu")
    cache_file = str(tmp_path / "sweep.json")
    report = run_sweep(CostModelMeasurer(), cache_file,
                       kernels=["fused_norms"])
    assert report["measure"] == "cost-model"
    assert report["entries"] == 2                 # f32 + bf16 sweep shapes
    for row in report["results"]:
        assert row["kernel"] == "fused_norms"
        assert "error" not in row
        assert row["score_s"] <= row["default_s"]
    c = TuningCache(cache_file)
    assert c.kernels("cpu") == {"fused_norms"}


def test_untuned_launch_report_clean_on_shipped_tree():
    from paddle_tpu.tune import untuned_launch_report
    assert untuned_launch_report() == []
