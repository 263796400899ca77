"""MFU ablation trail (VERDICT r4 item 2): run the lever grid on the real
chip, append tagged records to bench_history.json, and write
MFU_ABLATION_r04.json.

Each lever runs in a SUBPROCESS (own backend init) so an OOM or lowering
failure in one variant cannot take down the trail, and env-var levers
(FA block sizes) apply cleanly.

Run on the chip:  python tools/perf/mfu_ablation.py
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

WORKER = r"""
import json, sys, time
import jax, jax.numpy as jnp, numpy as np
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.parallel import (HybridParallelConfig, build_mesh,
                                 build_train_step, init_opt_state,
                                 init_params, shard_opt_state, shard_params)

spec = json.loads(sys.argv[1])
if not spec.get("flash", True):
    from paddle_tpu.core.flags import set_flags
    set_flags({"use_pallas_kernels": False})
cfg = LlamaConfig(vocab_size=32000,
                  hidden_size=spec.get("hidden", 1024),
                  intermediate_size=spec.get("ffn", 2816),
                  num_hidden_layers=24,
                  num_attention_heads=spec.get("heads", 16),
                  num_key_value_heads=spec.get("kv", 4),
                  max_position_embeddings=2048)
hp = HybridParallelConfig(dp=1, pp=1, tp=1, num_microbatches=1,
                          remat=spec.get("remat", True),
                          remat_policy=spec.get("remat_policy", "full"),
                          xent_chunk=spec.get("xent_chunk", 0),
                          dtype=jnp.bfloat16)
mesh = build_mesh(hp)
params = shard_params(init_params(cfg, hp, seed=0), hp, mesh)
opt = shard_opt_state(init_opt_state(params), hp, mesh)
step = build_train_step(cfg, hp, mesh)
b, s, steps = spec.get("batch", 8), 2048, 6
tok = jnp.asarray(np.random.RandomState(0).randint(
    0, cfg.vocab_size, (b, s)), jnp.int32)
params, opt, loss = step(params, opt, tok); float(loss)
reps = []
for _ in range(3):
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt, loss = step(params, opt, tok)
    float(loss)
    reps.append(b * s * steps / (time.perf_counter() - t0))
reps.sort()
n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
tokps = reps[1]
from paddle_tpu.tune import provenance_snapshot
print(json.dumps({"tokens_per_sec": round(tokps, 1),
                  "reps": [round(r, 1) for r in reps],
                  "mfu": round(6.0 * n * tokps / 197e12, 4),
                  "n_params": n,
                  "tuning_cache": provenance_snapshot()}))
"""

LEVERS = [
    ("baseline_b8_remat_full", {}),
    ("no_remat_b2", {"remat": False, "batch": 2}),
    ("no_remat_b4", {"remat": False, "batch": 4}),
    ("remat_attn_b8", {"remat_policy": "attn"}),
    ("xent_chunk512_b8", {"xent_chunk": 512}),
    ("batch16_remat_full", {"batch": 16}),
    ("fa_block256", {"env": {"PADDLE_TPU_FA_BLOCK_Q": "256",
                             "PADDLE_TPU_FA_BLOCK_K": "256"}}),
    ("fa_block1024", {"env": {"PADDLE_TPU_FA_BLOCK_Q": "1024",
                              "PADDLE_TPU_FA_BLOCK_K": "1024"}}),
    ("xla_fallback_no_flash", {"flash": False, "batch": 4}),
    # combination levers: xent chunking frees the f32 [b,s,32k] logits
    # buffer, which is what OOMed no_remat_b4 in the first trail
    ("no_remat_b4_xchunk512", {"remat": False, "batch": 4,
                               "xent_chunk": 512}),
    ("no_remat_b2_xchunk512", {"remat": False, "batch": 2,
                               "xent_chunk": 512}),
    ("remat_attn_b4", {"remat_policy": "attn", "batch": 4}),
    ("remat_attn_b2", {"remat_policy": "attn", "batch": 2}),
    # head_dim=128 config (~560M): the 350M config's d=64 contracts over
    # half the MXU's 128 lanes inside the FA matmuls — this measures the
    # MFU headroom from a lane-filling head layout (the 7B-class shape)
    ("d128_560m_no_remat_b2", {"remat": False, "batch": 2, "hidden": 1280,
                               "heads": 10, "kv": 5, "ffn": 3456}),
    ("d128_560m_remat_attn_b4", {"remat_policy": "attn", "batch": 4,
                                 "hidden": 1280, "heads": 10, "kv": 5,
                                 "ffn": 3456}),
    # FA block retune at d128 (512 was tuned at d64; VERDICT r4 next-2)
    ("d128_560m_no_remat_b2_fablk256",
     {"remat": False, "batch": 2, "hidden": 1280, "heads": 10, "kv": 5,
      "ffn": 3456, "env": {"PADDLE_TPU_FA_BLOCK_Q": "256",
                           "PADDLE_TPU_FA_BLOCK_K": "256"}}),
    ("d128_560m_no_remat_b2_fablk1024",
     {"remat": False, "batch": 2, "hidden": 1280, "heads": 10, "kv": 5,
      "ffn": 3456, "env": {"PADDLE_TPU_FA_BLOCK_Q": "1024",
                           "PADDLE_TPU_FA_BLOCK_K": "1024"}}),
]


def main():
    # optional CLI lever subset: rerun only the named levers, merging into
    # the existing MFU_ABLATION_r04.json instead of clobbering it
    want = set(sys.argv[1:])
    known = {t for t, _ in LEVERS}
    if want - known:
        sys.exit(f"unknown lever(s) {sorted(want - known)}; "
                 f"choose from {sorted(known)}")
    levers = [(t, s) for t, s in LEVERS if not want or t in want]
    abl_path = os.path.join(REPO, "MFU_ABLATION_r04.json")
    results = {}
    if want:
        try:
            results = json.load(open(abl_path)).get("levers", {})
        except Exception:
            pass
    ran = []                       # only THESE get appended to the history
    for tag, spec in levers:
        env = dict(os.environ)
        env.update(spec.pop("env", {}))
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        t0 = time.time()
        try:
            out = subprocess.run(
                [sys.executable, "-c", WORKER, json.dumps(spec)],
                capture_output=True, text=True, timeout=900, env=env,
                cwd=REPO)
            if out.returncode == 0:
                results[tag] = json.loads(out.stdout.strip().splitlines()[-1])
            else:
                results[tag] = {"error": out.stderr[-400:]}
        except subprocess.TimeoutExpired:
            results[tag] = {"error": "timeout (> 900s)"}
        except Exception as e:   # bad stdout etc. — keep the trail alive
            results[tag] = {"error": f"{type(e).__name__}: {e}"[:400]}
        results[tag]["wall_s"] = round(time.time() - t0, 1)
        ran.append(tag)
        print(tag, json.dumps(results[tag]), flush=True)

    # append ONLY this invocation's runs to bench_history.json: preloaded
    # results from a prior grid must not reappear as fresh records
    hist_path = os.path.join(REPO, "bench_history.json")
    try:
        history = json.load(open(hist_path))
    except Exception:
        history = []
    stamp = time.strftime("%Y-%m-%dT%H:%M:%S")
    for tag in ran:
        rec = results[tag]
        if "tokens_per_sec" in rec:
            history.append({"tokens_per_sec": rec["tokens_per_sec"],
                            "reps": rec["reps"], "mfu": rec["mfu"],
                            "backend": "tpu", "config": f"ablation:{tag}",
                            "n_params": rec.get("n_params"),
                            "tuning_cache": rec.get("tuning_cache"),
                            "time": stamp})
    # atomic replace: a death mid-write must not truncate the
    # committed evidence file
    tmp = hist_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(history, f, indent=1)
    os.replace(tmp, hist_path)
    with open(abl_path + ".tmp", "w") as f:
        json.dump({"round": 4, "time": stamp, "levers": results}, f,
                  indent=1)
    os.replace(abl_path + ".tmp", abl_path)
    print("written MFU_ABLATION_r04.json")


if __name__ == "__main__":
    main()
