"""TunableKernel registry: the search space each Pallas kernel exposes.

A registration declares, per kernel: the tunable parameters and their
candidate values (``space``), the built-in defaults the fallback chain
bottoms out at, any deprecated env-var levers that still override the
cache, and a ``sweep`` of representative shape keys the autotuner
measures.  The registry is pure data — it imports no kernel module, so
the lint CLI and the subprocess sweep workers can enumerate it without
touching jax.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field

__all__ = ["TunableKernel", "register", "get_kernel", "all_kernels",
           "candidate_configs"]


@dataclass(frozen=True)
class TunableKernel:
    """Search-space declaration for one Pallas kernel.

    name           cache key component ("flash_attention", ...)
    space          param -> tuple of candidate values
    defaults       param -> built-in value (end of the fallback chain)
    env_overrides  param -> deprecated env var that still wins over the
                   cache (with a DeprecationWarning)
    sweep          representative shape keys measured by autotune.py;
                   trace-time lookups resolve to these via the bucket
                   fallback when their own bucket has no entry
    describe       one-line human summary for reports
    """
    name: str
    space: dict = field(default_factory=dict)
    defaults: dict = field(default_factory=dict)
    env_overrides: dict = field(default_factory=dict)
    sweep: tuple = ()
    describe: str = ""


_REGISTRY: dict = {}


def register(kernel: TunableKernel) -> TunableKernel:
    _REGISTRY[kernel.name] = kernel
    return kernel


def get_kernel(name: str):
    return _REGISTRY.get(name)


def all_kernels() -> tuple:
    return tuple(_REGISTRY[k] for k in sorted(_REGISTRY))


def candidate_configs(kernel: TunableKernel):
    """Cartesian product of the kernel's search space, defaults first."""
    names = sorted(kernel.space)
    seen = []
    default = {k: kernel.defaults[k] for k in names}
    seen.append(default)
    for combo in itertools.product(*(kernel.space[k] for k in names)):
        cfg = dict(zip(names, combo))
        if cfg not in seen:
            seen.append(cfg)
    return seen


# ---------------------------------------------------------------------------
# the five shipped kernels
# ---------------------------------------------------------------------------

# dense flash attention: block_q/block_k tile the (seq_q, seq_k) grid.
# Sweep covers the f32 CI shapes and the bf16 shapes real models run, so
# any device the sweep touches gets a same-dtype bucket for both.
register(TunableKernel(
    name="flash_attention",
    space={"block_q": (128, 256, 512, 1024), "block_k": (128, 256, 512, 1024)},
    defaults={"block_q": 512, "block_k": 512},
    env_overrides={"block_q": "PADDLE_TPU_FA_BLOCK_Q",
                   "block_k": "PADDLE_TPU_FA_BLOCK_K"},
    sweep=(
        {"seq_q": 2048, "seq_k": 2048, "head_dim": 128, "dtype": "float32"},
        {"seq_q": 2048, "seq_k": 2048, "head_dim": 128, "dtype": "bfloat16"},
        {"seq_q": 8192, "seq_k": 8192, "head_dim": 128, "dtype": "bfloat16"},
    ),
    describe="dense flash attention fwd/bwd q/k tile sizes",
))

# varlen flash attention shares the block vocabulary but tiles ragged
# token batches; its q-extent is the prefill token bucket, not seq_len.
register(TunableKernel(
    name="flash_attention_varlen",
    space={"block_q": (128, 256, 512, 1024), "block_k": (128, 256, 512, 1024)},
    defaults={"block_q": 512, "block_k": 512},
    env_overrides={"block_q": "PADDLE_TPU_FA_BLOCK_Q",
                   "block_k": "PADDLE_TPU_FA_BLOCK_K"},
    sweep=(
        {"seq_q": 1024, "seq_k": 2048, "head_dim": 128, "dtype": "float32"},
        {"seq_q": 1024, "seq_k": 2048, "head_dim": 128, "dtype": "bfloat16"},
    ),
    describe="varlen (packed-prefill) flash attention tile sizes",
))

# fused RMS/LayerNorm: rows-per-program blocking.
register(TunableKernel(
    name="fused_norms",
    space={"block_r": (64, 128, 256, 512)},
    defaults={"block_r": 256},
    sweep=(
        {"rows": 2048, "hidden": 4096, "dtype": "float32"},
        {"rows": 2048, "hidden": 4096, "dtype": "bfloat16"},
    ),
    describe="fused RMS/LayerNorm rows-per-program block",
))

# ragged paged attention: the tile of one row's queries against that
# row's live pages.  q_tile_rows is the score tile's height (a tile of
# tq tokens is tq * G rows of one K/V head, so the token tile follows the
# program's group size); kv_pages is the K/V block, in pages, that one
# turn of the page walk copies and multiplies (cut where two slots of it
# would overrun the VMEM set aside; the decode grid kernel walks as many
# pages a grid step).  Defaults: chosen on the v5e at both benchmark
# layouts (8 K/V heads of group 4, 4 of group 8), 256-page rows: the
# widest block won at every row length tried, and q_tile_rows made no
# difference between 128 and 256 (PERF.md, PR 26).
register(TunableKernel(
    name="paged_attention",
    space={"q_tile_rows": (64, 128, 256), "kv_pages": (8, 16, 32, 64)},
    defaults={"q_tile_rows": 128, "kv_pages": 32},
    sweep=(
        {"tq": 8, "kv_heads": 4, "head_dim": 128, "page": 16, "nblk": 128,
         "dtype": "float32"},
        {"tq": 8, "kv_heads": 4, "head_dim": 128, "page": 16, "nblk": 128,
         "dtype": "bfloat16"},
        {"tq": 8, "kv_heads": 4, "head_dim": 128, "page": 32, "nblk": 256,
         "dtype": "int8"},
    ),
    describe="ragged paged attention q tile (score rows) and K/V block "
             "(pages)",
))

# fused dequant matmul: int8/int4 weight blocks stream from HBM and
# upcast in VMEM against their scale rows.  block_m/n/k tile the
# (M, N, K) grid; the launch clamps each to a divisor of its dim (and
# block_k to the int4 128-row scale-group nesting), so every candidate
# is feasible at every shape and only the tiling — never the math —
# changes.  Sweep shapes are llama-class decode launches: M is the
# decode batch, K/N the projection and MLP extents.
register(TunableKernel(
    name="quant_matmul",
    space={"block_m": (8, 16, 32), "block_n": (128, 256, 512),
           "block_k": (128, 256, 512)},
    defaults={"block_m": 8, "block_n": 256, "block_k": 256},
    sweep=(
        {"m": 8, "k": 4096, "n": 4096, "dtype": "int8"},
        {"m": 8, "k": 4096, "n": 11008, "dtype": "int8"},
        {"m": 8, "k": 4096, "n": 4096, "dtype": "int4"},
        {"m": 8, "k": 4096, "n": 11008, "dtype": "int4"},
    ),
    describe="fused dequant-matmul weight-block tiles (int8/int4)",
))
