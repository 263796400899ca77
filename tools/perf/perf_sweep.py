"""One-off perf sweep on the real TPU chip: find what limits MFU."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.flags import set_flags
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.parallel import (
    HybridParallelConfig, build_mesh, build_train_step, init_opt_state,
    init_params, shard_opt_state, shard_params,
)

CFG = dict(vocab_size=32000, hidden_size=1024, intermediate_size=2816,
           num_hidden_layers=24, num_attention_heads=16,
           max_position_embeddings=2048)


def run(tag, batch=8, seq=2048, kv=4, remat=True, remat_policy="full",
        pallas=True, steps=6):
    set_flags({"use_pallas_kernels": pallas})
    cfg = LlamaConfig(num_key_value_heads=kv, **CFG)
    hp = HybridParallelConfig(dp=1, pp=1, tp=1, num_microbatches=1,
                              remat=remat, remat_policy=remat_policy,
                              dtype=jnp.bfloat16)
    mesh = build_mesh(hp)
    try:
        params = shard_params(init_params(cfg, hp, seed=0), hp, mesh)
        opt = shard_opt_state(init_opt_state(params), hp, mesh)
        step = build_train_step(cfg, hp, mesh)
        rng = np.random.RandomState(0)
        tok = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                          jnp.int32)
        params, opt, loss = step(params, opt, tok)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt, loss = step(params, opt, tok)
        float(loss)
        dt = time.perf_counter() - t0
        tps = batch * seq * steps / dt
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        mfu = 6.0 * n * tps / 197e12
        print(json.dumps({"tag": tag, "tokens_per_sec": round(tps, 1),
                          "mfu": round(mfu, 4)}), flush=True)
    except Exception as e:
        print(json.dumps({"tag": tag,
                          "error": f"{type(e).__name__}: {e}"[:200]}),
              flush=True)
    finally:
        # free device memory between configs
        for x in jax.live_arrays():
            x.delete()


run("base_b8_full_pallas")
run("xla_attn", pallas=False)
run("remat_attn_policy", remat_policy="attn")
run("b16", batch=16)
run("no_remat_b4", batch=4, remat=False)
run("b16_xla", batch=16, pallas=False)
run("b16_remat_attn", batch=16, remat_policy="attn")
