"""Component microbenchmarks on the real TPU: where does the step time go?

Every timing ends in a host readback (float of a reduction), which waits
for the device like block_until_ready does.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np


def _sync(out):
    leaves = jax.tree.leaves(out)
    return float(jnp.sum(leaves[0].astype(jnp.float32).ravel()[:1]))


def timeit(tag, fn, *args, n=10, flops=None):
    try:
        _sync(fn(*args))                    # warmup + compile
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn(*args)
        _sync(out)                          # one host roundtrip for n iters
        dt = (time.perf_counter() - t0) / n
        rec = {"tag": tag, "ms": round(dt * 1e3, 3)}
        if flops:
            rec["tflops_per_s"] = round(flops / dt / 1e12, 1)
            rec["pct_peak"] = round(100 * flops / dt / 197e12, 1)
        print(json.dumps(rec), flush=True)
    except Exception as e:
        print(json.dumps({"tag": tag, "error": str(e)[:200]}), flush=True)


B, S, H, FFN, NH, KV = 8, 2048, 1024, 2816, 16, 4
T = B * S
D = H // NH

k = jax.random.PRNGKey(0)
a = jax.random.normal(k, (T, H), jnp.bfloat16)
w = jax.random.normal(k, (H, H), jnp.bfloat16)
mm = jax.jit(lambda a, w: a @ w)
timeit("matmul_16384x1024x1024", mm, a, w, flops=2 * T * H * H, n=20)

wf = jax.random.normal(k, (H, FFN), jnp.bfloat16)
timeit("matmul_16384x1024x2816", mm, a, wf, flops=2 * T * H * FFN, n=20)

wv = jax.random.normal(k, (H, 32000), jnp.bfloat16)
timeit("lm_head_matmul_16384x1024x32000", mm, a, wv,
       flops=2 * T * H * 32000)

# flash attention fwd (pallas) vs xla ref — layout [B, S, NH, D]
from paddle_tpu.ops.pallas.flash_attention import (_flash_attention,
                                                   _ref_attention)
q = jax.random.normal(k, (B, S, NH, D), jnp.bfloat16)
kk = jax.random.normal(k, (B, S, KV, D), jnp.bfloat16)
vv = jax.random.normal(k, (B, S, KV, D), jnp.bfloat16)
att_flops = 4 * B * NH * S * S * D / 2  # causal half
fa = jax.jit(lambda q, kk, vv: _flash_attention(True, q, kk, vv))
timeit("flash_attn_fwd_pallas", fa, q, kk, vv, flops=att_flops)
ra = jax.jit(lambda q, kk, vv: _ref_attention(q, kk, vv, True))
timeit("attn_fwd_xla", ra, q, kk, vv, flops=att_flops)

fab = jax.jit(jax.grad(lambda q, kk, vv: _flash_attention(
    True, q, kk, vv).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
timeit("flash_attn_fwd_bwd_pallas", fab, q, kk, vv, flops=3.5 * att_flops)
rab = jax.jit(jax.grad(lambda q, kk, vv: _ref_attention(
    q, kk, vv, True).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
timeit("attn_fwd_bwd_xla", rab, q, kk, vv, flops=3.5 * att_flops)

# softmax xent over 32k vocab
logits = jax.random.normal(k, (T, 32000), jnp.bfloat16)
labels = jnp.zeros((T,), jnp.int32)


def xent(lg, lb):
    lg = lg.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    return (lse - jnp.take_along_axis(lg, lb[:, None], -1)[:, 0]).mean()


timeit("xent_loss_fwd_32k", jax.jit(xent), logits, labels)
timeit("xent_loss_fwd_bwd_32k", jax.jit(jax.grad(xent)), logits, labels)

# full model fwd / fwd+bwd under the trainer's shard_map (trivial 1-dev mesh)
from jax.sharding import PartitionSpec as P

from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.parallel import (
    HybridParallelConfig, build_mesh, build_train_step, init_opt_state,
    init_params, shard_opt_state, shard_params,
)
from paddle_tpu.parallel import transformer as TR

cfg = LlamaConfig(vocab_size=32000, hidden_size=H, intermediate_size=FFN,
                  num_hidden_layers=24, num_attention_heads=NH,
                  num_key_value_heads=KV, max_position_embeddings=S)
hp = HybridParallelConfig(dp=1, pp=1, tp=1, num_microbatches=1, remat=True,
                          dtype=jnp.bfloat16)
mesh = build_mesh(hp)
params = shard_params(init_params(cfg, hp, seed=0), hp, mesh)
rng = np.random.RandomState(0)
tok = jnp.asarray(rng.randint(0, 32000, (1, B, S)), jnp.int32)

n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
fwd_flops = 2 * n_params * T + att_flops * 24

ps = TR.param_specs(hp, False)
sm_kw = dict(mesh=mesh, check_vma=False)
from jax import shard_map as _shard_map

fwd = jax.jit(_shard_map(lambda p, t: TR._forward_loss(p, t, cfg, hp),
                         in_specs=(ps, P(None, "dp", None)), out_specs=P(),
                         **sm_kw))
timeit("model_fwd", fwd, params, tok, n=4, flops=fwd_flops)

fwdbwd = jax.jit(_shard_map(
    lambda p, t: jax.grad(lambda pp_: TR._forward_loss(pp_, t, cfg, hp))(p),
    in_specs=(ps, P(None, "dp", None)), out_specs=ps, **sm_kw))
timeit("model_fwd_bwd_remat", fwdbwd, params, tok, n=4, flops=4 * fwd_flops)

opt = shard_opt_state(init_opt_state(params), hp, mesh)
step = build_train_step(cfg, hp, mesh)
tok2 = jnp.asarray(rng.randint(0, 32000, (B, S)), jnp.int32)
p2, o2, loss = step(params, opt, tok2)
float(loss)
t0 = time.perf_counter()
N = 6
for _ in range(N):
    p2, o2, loss = step(p2, o2, tok2)
float(loss)
dt = (time.perf_counter() - t0) / N
step_flops = 8 * n_params * T + 3.5 * att_flops * 24
print(json.dumps({"tag": "full_train_step", "ms": round(dt * 1e3, 2),
                  "tok_per_s": round(T / dt, 1),
                  "pct_peak": round(100 * step_flops / dt / 197e12, 1)}),
      flush=True)
