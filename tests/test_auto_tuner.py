"""Strategy auto-tuner tests (reference auto_tuner/{tuner,search,prune}.py).

Covers: prune rules, candidate enumeration + cost-model ordering, recorder
sort/persist/resume, and the TPU-native compile-probe trial on the virtual
8-device CPU mesh.
"""
import jax
import pytest

from paddle_tpu.distributed.auto_tuner import (
    AutoTuner, GridSearch, HistoryRecorder, estimate_memory_bytes,
    estimate_step_time, prune_config,
)

MODEL = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
             num_hidden_layers=4, num_attention_heads=4,
             num_key_value_heads=4)
TUNER_CFG = dict(num_devices=8, model_cfg=MODEL, seq_len=128,
                 hbm_bytes=16e9)


def test_prune_rules():
    # wrong device product
    assert prune_config(TUNER_CFG, {"dp": 2, "tp": 2}) == \
        "prune_by_device_count"
    # tp does not divide heads
    assert prune_config(TUNER_CFG, {"dp": 1, "tp": 8, "pp": 1}) == \
        "prune_by_tp_divisibility"
    # pp needs microbatches >= pp
    assert prune_config(
        TUNER_CFG, {"dp": 2, "tp": 2, "pp": 2, "num_microbatches": 1}) == \
        "prune_by_pp_divisibility"
    # zero needs dp>1
    assert prune_config(
        TUNER_CFG, {"dp": 1, "tp": 4, "pp": 2, "num_microbatches": 2,
                    "zero_stage": 1}) == "prune_by_zero"
    # valid config passes every rule
    assert prune_config(
        TUNER_CFG, {"dp": 2, "tp": 2, "pp": 2, "num_microbatches": 2,
                    "micro_batch_size": 1, "seq_len": 128}) is None


def test_memory_model_sharding_monotonic():
    base = {"dp": 1, "tp": 1, "pp": 1, "micro_batch_size": 1,
            "seq_len": 128, "num_microbatches": 1}
    m_replicated = estimate_memory_bytes(MODEL, base)
    m_tp = estimate_memory_bytes(MODEL, {**base, "tp": 4})
    m_zero = estimate_memory_bytes(MODEL, {**base, "dp": 4, "zero_stage": 2})
    assert m_tp < m_replicated
    assert m_zero < m_replicated


def test_cost_model_prefers_fewer_bubbles():
    cfg_few_mb = {"dp": 1, "tp": 1, "pp": 4, "num_microbatches": 4,
                  "micro_batch_size": 1, "seq_len": 128}
    cfg_many_mb = {**cfg_few_mb, "num_microbatches": 16}
    t_few = estimate_step_time(MODEL, cfg_few_mb)
    t_many = estimate_step_time(MODEL, cfg_many_mb)
    # per-token time must be lower with more microbatches (smaller bubble)
    assert t_many / 16 < t_few / 4


def test_grid_search_orders_by_cost():
    gs = GridSearch(dict(TUNER_CFG))
    assert gs.num_candidates > 0
    first = gs.search_once([])
    second = gs.search_once([])
    assert first["_est_step_time"] <= second["_est_step_time"]
    # every yielded candidate covers the 8-device mesh
    assert first["dp"] * first["tp"] * first["pp"] * first.get("cp", 1) == 8


def test_recorder_sort_and_resume(tmp_path):
    rec = HistoryRecorder("tokens_per_sec", "max")
    rec.add_cfg(dp=8, tp=1, tokens_per_sec=100.0, status="ok")
    rec.add_cfg(dp=4, tp=2, tokens_per_sec=250.0, status="ok")
    rec.add_cfg(dp=2, tp=4, tokens_per_sec=None, status="oom")
    best, err = rec.get_best()
    assert not err and best["dp"] == 4
    p = tmp_path / "history.csv"
    rec.store_history(str(p))
    rec2 = HistoryRecorder("tokens_per_sec", "max")
    rec2.load_history(str(p))
    assert len(rec2.history) == 3
    assert rec2.get_best()[0]["dp"] == 4


def test_history_oom_prune():
    tuner = AutoTuner(dict(TUNER_CFG, global_batch_size=8))
    oom = {"dp": 8, "tp": 1, "pp": 1, "cp": 1, "zero_stage": 0,
           "micro_batch_size": 1, "num_microbatches": 1, "status": "oom",
           "tokens_per_sec": None}
    tuner.add_cfg(oom)
    seen = []
    while True:
        cfg = tuner.search_once()
        if cfg is None:
            break
        seen.append(cfg)
    # dominated config (same axes, >= micro batch) never comes back
    assert not any(c["dp"] == 8 and c["tp"] == 1 and c["pp"] == 1
                   and c["micro_batch_size"] >= 1 and c["zero_stage"] == 0
                   for c in seen)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_compile_probe_trial():
    """measure_cfg AOT-compiles the real hybrid step and scores it."""
    tuner = AutoTuner(dict(TUNER_CFG))
    cfg = {"dp": 2, "tp": 2, "pp": 2, "cp": 1, "vpp": 1, "zero_stage": 1,
           "micro_batch_size": 1, "num_microbatches": 2, "recompute": True,
           "seq_len": 128}
    out = tuner.measure_cfg(cfg)
    assert out["status"] == "ok", out.get("error")
    assert out["analyzed_bytes_per_chip"] > 0
    assert out["tokens_per_sec"] > 0


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_tune_loop_end_to_end(tmp_path):
    """Two-trial tune() returns a best config and persists history."""
    tuner = AutoTuner(dict(TUNER_CFG, task_limit=2))
    hist = tmp_path / "h.csv"
    best, err = tuner.tune(max_trials=2, history_path=str(hist))
    assert hist.exists()
    assert len(tuner.history_cfgs) == 2
    if not err:            # at least one trial compiled
        assert best["status"] == "ok"


def test_launch_auto_tuner_mode(tmp_path):
    """launch --auto_tuner_json scores configs via compile probes and
    exports the winner to workers as PADDLE_AUTO_TUNER_BEST."""
    import json
    import os
    import subprocess
    import sys
    import textwrap

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # pp=1 candidate: the pipeline-scan compile is minutes-cold on the
    # 1-core CI host; dp*tp covers the mesh and exercises the same plumbing
    cfg = dict(TUNER_CFG, max_trials=1, task_limit=1,
               candidates={"dp": [4], "tp": [2], "pp": [1], "cp": [1],
                           "vpp": [1], "zero_stage": [1],
                           "micro_batch_size": [1],
                           "num_microbatches": [1], "recompute": [True]})
    cfg_path = tmp_path / "tuner.json"
    cfg_path.write_text(json.dumps(cfg))

    script = tmp_path / "train.py"
    script.write_text(textwrap.dedent("""
        import json, os
        best = json.loads(os.environ["PADDLE_AUTO_TUNER_BEST"])
        assert best["dp"] * best["tp"] * best.get("pp", 1) == 8
        assert best["status"] == "ok"
        print("tuner_best_seen")
    """))
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--log_dir", str(tmp_path / "log"),
         "--auto_tuner_json", str(cfg_path), str(script)],
        env=env, capture_output=True, text=True, timeout=500,
        cwd=str(tmp_path))
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    assert "auto-tuner best config" in r.stderr
    assert (tmp_path / "log" / "auto_tuner_history.csv").exists()
    assert "tuner_best_seen" in \
        (tmp_path / "log" / "workerlog.0").read_text()
