"""Candidate search: enumerate, measure, pick winners, write the cache.

Two measurers share one search loop:

* ``CostModelMeasurer`` scores candidates in-process with the
  arithmetic-intensity model (:mod:`paddle_tpu.tune.cost`) — the CPU CI
  path, exercising the full search/persist/lookup pipeline with no chip.
* ``SubprocessMeasurer`` times real launches, one candidate per child
  process (the ``tools/perf/mfu_ablation.py`` worker pattern): a config
  that OOMs VMEM or wedges the compiler kills only its child, and every
  candidate compiles fresh instead of reusing a sibling's trace cache.
  Candidates are forced into the child via ``PADDLE_TPU_TUNE_FORCE``.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys

from . import cost
from .cache import TuningCache, bucket_signature, device_kind
from .registry import TunableKernel, all_kernels, candidate_configs

__all__ = ["CostModelMeasurer", "SubprocessMeasurer", "sweep_kernel",
           "run_sweep", "untuned_launch_report"]


class CostModelMeasurer:
    """Rank candidates with the roofline model; no chip."""

    kind = "cost-model"

    def measure(self, kernel: TunableKernel, shape: dict,
                config: dict) -> float:
        return cost.estimate(kernel.name, shape, config)

    def device_kind(self) -> str:
        return device_kind()


# Child source for wall-clock measurement.  It builds a representative
# launch for the named kernel from the shape key, forces the candidate
# config through the normal trace-time lookup (so the measured path IS
# the production path), and prints median seconds as JSON.
_WORKER = r"""
import json, sys, time
spec = json.loads(sys.argv[1])
import jax, jax.numpy as jnp
import numpy as np

def build(name, s):
    dt = jnp.dtype(s.get("dtype", "float32"))
    if name == "flash_attention":
        from paddle_tpu.ops.pallas import flash_attention as fa
        rng = np.random.RandomState(0)
        # [B, S, H, D] — the layout attention()/use_flash expect
        q = jnp.asarray(rng.randn(1, s["seq_q"], 8, s["head_dim"]), dt)
        k = jnp.asarray(rng.randn(1, s["seq_k"], 8, s["head_dim"]), dt)
        v = jnp.asarray(rng.randn(1, s["seq_k"], 8, s["head_dim"]), dt)
        fn = jax.jit(lambda q, k, v: fa.attention(q, k, v, causal=True))
        return fn, (q, k, v)
    if name == "flash_attention_varlen":
        import math
        from paddle_tpu.ops.pallas import flash_attention as fa
        from paddle_tpu.ops.pallas import flash_attention_varlen as favl
        rng = np.random.RandomState(0)
        tq, tk, d = s["seq_q"], s["seq_k"], s["head_dim"]
        # [T, H, D] flat tokens, two ragged sequences
        q = jnp.asarray(rng.randn(tq, 8, d), dt)
        k = jnp.asarray(rng.randn(tk, 8, d), dt)
        v = jnp.asarray(rng.randn(tk, 8, d), dt)
        cu_q = jnp.asarray([0, tq // 2, tq], jnp.int32)
        cu_k = jnp.asarray([0, tk // 2, tk], jnp.int32)
        sm = 1.0 / math.sqrt(d)
        if favl.use_varlen_flash(q, k, True):
            fn = jax.jit(lambda q, k, v, cq, ck: favl._varlen_attention(
                True, sm, q, k, v, cq, ck))
            return fn, (q, k, v, cu_q, cu_k)
        # off-chip grace: time the dense composition so candidates tie
        # and the winner degrades to the defaults
        fn = jax.jit(lambda q, k, v: fa._ref_attention(
            q[None], k[None], v[None], True))
        return fn, (q, k, v)
    if name == "fused_norms":
        from paddle_tpu.ops.pallas import fused_norms as fns
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(s["rows"], s["hidden"]), dt)
        w = jnp.ones((s["hidden"],), dt)
        # the fused op has no interpret path — honor its supports() gate
        # (off-chip every candidate times the reference and ties, so the
        # winner degrades to the defaults rather than crashing the child)
        if fns.rms_norm_fused.supports(x.shape, dt.name):
            fn = jax.jit(lambda x, w: fns.rms_norm_fused(x, w, 1e-6))
        else:
            fn = jax.jit(lambda x, w: fns._rms_ref(x, w, 1e-6))
        return fn, (x, w)
    if name == "paged_attention":
        from paddle_tpu.ops.pallas import paged_attention as pa
        rng = np.random.RandomState(0)
        tq, kvh, d = s["tq"], s["kv_heads"], s["head_dim"]
        page, nblk = s["page"], s["nblk"]
        R = 4
        int8 = s.get("dtype") == "int8"
        if int8:
            kc = jnp.asarray(rng.randint(-127, 128, (R * nblk, kvh, page, d)),
                             jnp.int8)
            vc = jnp.asarray(rng.randint(-127, 128, (R * nblk, kvh, page, d)),
                             jnp.int8)
        else:
            kc = jnp.asarray(rng.randn(R * nblk, kvh, page, d), dt)
            vc = jnp.asarray(rng.randn(R * nblk, kvh, page, d), dt)
        bt = jnp.asarray(
            rng.permutation(R * nblk).reshape(R, nblk), jnp.int32)
        q = jnp.asarray(rng.randn(tq, kvh * 2, d),
                        jnp.float32 if int8 else dt)
        # a chunk row and three rows of one query, each deep in its table
        qlens = [max(1, tq - 3), 1, 1, 1][:min(R, tq)]
        cu = np.full(R + 1, sum(qlens))
        cu[:len(qlens) + 1] = np.concatenate([[0], np.cumsum(qlens)])
        kvl = np.zeros(R, np.int64)
        kvl[:len(qlens)] = rng.randint(page * nblk // 2, page * nblk,
                                       len(qlens))
        kvl = np.maximum(kvl, cu[1:] - cu[:-1])
        cu, kvl = jnp.asarray(cu, jnp.int32), jnp.asarray(kvl, jnp.int32)
        if int8:
            ks = jnp.asarray(rng.uniform(0.5, 1.5, (R * nblk, kvh)) / 127,
                             jnp.float32)
            fn = jax.jit(lambda *a: pa.ragged_paged_attention_quant(*a))
            return fn, (q, kc, vc, ks, ks, bt, cu, kvl)
        fn = jax.jit(lambda *a: pa.ragged_paged_attention(*a))
        return fn, (q, kc, vc, bt, cu, kvl)
    if name == "quant_matmul":
        from paddle_tpu.ops.pallas import quant_matmul as qm
        rng = np.random.RandomState(0)
        m, k, n = s["m"], s["k"], s["n"]
        wdt = s.get("dtype", "int8")
        x = jnp.asarray(rng.randn(m, k), jnp.float32)
        w = jnp.asarray(rng.randn(k, n), jnp.float32)
        q, sc = qm.quantize_weight(w, wdt)
        why = qm.ineligible(k, n, wdt)
        if why is not None:
            raise SystemExit(f"quant_matmul does not take {s}: {why}")
        fn = jax.jit(lambda x, q, sc: qm.matmul(
            x, q, sc, weight_dtype=wdt))
        return fn, (x, q, sc)
    raise SystemExit(f"unknown kernel {name}")

fn, args = build(spec["kernel"], spec["shape"])
out = fn(*args)
jax.block_until_ready(out)
times = []
for _ in range(spec.get("iters", 5)):
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    times.append(time.perf_counter() - t0)
times.sort()
print(json.dumps({"seconds": times[len(times) // 2]}))
"""


class SubprocessMeasurer:
    """Wall-clock one candidate per child process on the real backend."""

    kind = "wall-clock"

    def __init__(self, timeout: int = 900, iters: int = 5):
        self.timeout = timeout
        self.iters = iters

    def device_kind(self) -> str:
        """The device key, asked of a child: a chip belongs to one
        process at a time, and the candidates' children need it, so the
        parent of a wall-clock sweep never initializes a backend."""
        proc = subprocess.run(
            [sys.executable, "-c",
             "from paddle_tpu.tune.cache import device_kind; "
             "print(device_kind())"],
            capture_output=True, text=True, timeout=self.timeout)
        if proc.returncode != 0:
            raise RuntimeError(
                f"device probe child failed ({proc.returncode}): "
                f"{proc.stderr.strip()[-2000:]}")
        return proc.stdout.strip().splitlines()[-1]

    def measure(self, kernel: TunableKernel, shape: dict,
                config: dict) -> float:
        spec = {"kernel": kernel.name, "shape": shape, "iters": self.iters}
        env = dict(os.environ)
        env["PADDLE_TPU_TUNE_FORCE"] = json.dumps({kernel.name: config})
        # the candidate, not a stale cache, must decide geometry
        env.pop("PADDLE_TPU_TUNE_CACHE", None)
        for var in kernel.env_overrides.values():
            env.pop(var, None)
        proc = subprocess.run(
            [sys.executable, "-c", _WORKER, json.dumps(spec)],
            capture_output=True, text=True, env=env, timeout=self.timeout)
        if proc.returncode != 0:
            return math.inf
        try:
            return float(json.loads(proc.stdout.strip().splitlines()[-1])
                         ["seconds"])
        except Exception:
            return math.inf


def sweep_kernel(kernel: TunableKernel, measurer, cache: TuningCache,
                 device: str | None = None, log=None) -> list:
    """Measure every candidate on every sweep shape; persist winners.

    Returns report rows: one dict per sweep shape with the winner, the
    default's score, and the modeled/measured speedup."""
    device = device or measurer.device_kind()
    rows = []
    for shape in kernel.sweep:
        sig = bucket_signature(shape)
        best_cfg, best_s, default_s = None, math.inf, math.inf
        for cfg in candidate_configs(kernel):
            s = measurer.measure(kernel, shape, cfg)
            if cfg == {k: kernel.defaults[k] for k in sorted(kernel.space)}:
                default_s = s
            if s < best_s:
                best_cfg, best_s = cfg, s
            if log:
                log(f"  {kernel.name} {sig} {cfg} -> "
                    f"{'inf' if math.isinf(s) else f'{s * 1e6:.2f}us'}")
        if best_cfg is None or math.isinf(best_s):
            rows.append({"kernel": kernel.name, "sig": sig,
                         "error": "no feasible candidate"})
            continue
        cache.put(device, kernel.name, sig, best_cfg,
                  score_s=best_s, measure=measurer.kind)
        rows.append({
            "kernel": kernel.name, "sig": sig, "config": best_cfg,
            "score_s": best_s, "default_s": default_s,
            "speedup": (default_s / best_s
                        if best_s > 0 and not math.isinf(default_s)
                        else None),
            "measure": measurer.kind,
        })
    return rows


def run_sweep(measurer, cache_file: str, kernels=None,
              device: str | None = None, log=None) -> dict:
    """Sweep (a subset of) the registry, save the cache, return a report."""
    cache = TuningCache(cache_file)
    device = device or measurer.device_kind()
    names = set(kernels) if kernels else None
    rows = []
    for kern in all_kernels():
        if names is not None and kern.name not in names:
            continue
        rows.extend(sweep_kernel(kern, measurer, cache, device, log=log))
    path = cache.save()
    return {"device": device, "cache": path, "measure": measurer.kind,
            "entries": len(cache), "results": rows}


def untuned_launch_report(root: str | None = None) -> list:
    """graft-lint-style rows for every Pallas launch whose geometry does
    not flow from the tuning-cache lookup helper."""
    from paddle_tpu.analysis import lint_paths
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    target = os.path.join(root, "paddle_tpu", "ops", "pallas")
    findings = lint_paths([target], root=root)
    return [
        {"rule": f.rule, "file": f.location.file, "line": f.location.line,
         "func": f.location.func, "message": f.message}
        for f in findings if f.rule == "untuned-pallas-launch"
    ]
