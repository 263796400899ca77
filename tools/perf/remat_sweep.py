"""Remat-policy sweep at the new 512-block FA config."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.parallel import (
    HybridParallelConfig, build_mesh, build_train_step, init_opt_state,
    init_params, shard_opt_state, shard_params,
)

CFG = dict(vocab_size=32000, hidden_size=1024, intermediate_size=2816,
           num_hidden_layers=24, num_attention_heads=16,
           num_key_value_heads=4, max_position_embeddings=2048)


def run(tag, batch=8, remat=True, remat_policy="full", steps=6):
    cfg = LlamaConfig(**CFG)
    hp = HybridParallelConfig(dp=1, pp=1, tp=1, num_microbatches=1,
                              remat=remat, remat_policy=remat_policy,
                              dtype=jnp.bfloat16)
    mesh = build_mesh(hp)
    try:
        params = shard_params(init_params(cfg, hp, seed=0), hp, mesh)
        opt = shard_opt_state(init_opt_state(params), hp, mesh)
        step = build_train_step(cfg, hp, mesh)
        tok = jnp.asarray(np.random.RandomState(0).randint(
            0, 32000, (batch, 2048)), jnp.int32)
        p, o, loss = step(params, opt, tok)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            p, o, loss = step(p, o, tok)
        float(loss)
        dt = (time.perf_counter() - t0) / steps
        tps = batch * 2048 / dt
        print(json.dumps({"tag": tag, "step_ms": round(dt * 1e3, 1),
                          "tok_per_s": round(tps, 1),
                          "mfu": round(6 * 336118784 * tps / 197e12, 4)}),
              flush=True)
    except Exception as e:
        print(json.dumps({"tag": tag, "error": str(e)[:200]}), flush=True)
    finally:
        for x in jax.live_arrays():
            x.delete()


run("b8_full")
run("b8_attn_policy", remat_policy="attn")
run("b4_noremat", batch=4, remat=False)
run("b2_noremat", batch=2, remat=False)
run("b16_attn", batch=16, remat_policy="attn")
