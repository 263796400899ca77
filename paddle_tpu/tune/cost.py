"""Arithmetic-intensity cost model: scores candidates without a chip.

Off-chip (CPU CI) the autotuner cannot time kernels, but it can still
rank them: each candidate's runtime is modeled as the roofline max of
compute time and memory time plus a per-grid-program launch overhead,
with a VMEM-working-set feasibility gate.  The peaks are those of one
named device, ``core.runtime.MODELED_DEVICE``, from the published
table; the overhead constants were never fitted, so absolute numbers
are meaningless: the RANKING is what the sweep persists, and on-chip
wall-clock measurement replaces this model entirely (the default mode
of autotune.py).
"""
from __future__ import annotations

import math

from ..core.runtime import MODELED_DEVICE, device_peaks

__all__ = ["estimate", "f32_matmul_estimate", "PEAK_FLOPS", "PEAK_BW",
           "VMEM_BYTES"]

PEAK_FLOPS = device_peaks(MODELED_DEVICE)["bf16_flops"]
PEAK_BW = device_peaks(MODELED_DEVICE)["hbm_bytes_per_s"]
VMEM_BYTES = 64 << 20   # per-core VMEM working-set budget
PER_PROGRAM_S = 1.2e-6  # grid-program launch/prologue overhead
PER_TILE_S = 0.1e-6     # per inner-tile loop overhead (k-blocks, pages)

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def _bytes(dtype: str) -> int:
    return _DTYPE_BYTES.get(dtype, 4)


def _roofline(flops: float, traffic: float, programs: float,
              tiles: float, vmem: float):
    if vmem > VMEM_BYTES:
        return math.inf
    return (max(flops / PEAK_FLOPS, traffic / PEAK_BW)
            + programs * PER_PROGRAM_S + tiles * PER_TILE_S)


def _flash(shape: dict, config: dict) -> float:
    sq, sk, d = shape["seq_q"], shape["seq_k"], shape["head_dim"]
    eb = _bytes(shape.get("dtype", "float32"))
    bq = min(config["block_q"], sq)
    bk = min(config["block_k"], sk)
    heads = shape.get("heads", 8)
    programs = heads * math.ceil(sq / bq)
    tiles = programs * math.ceil(sk / bk)
    flops = 4.0 * heads * sq * sk * d
    # each q-block streams the full K/V once; bigger q-blocks mean fewer
    # K/V passes, bigger k-blocks amortize tile overhead
    traffic = eb * heads * (sq * d * 2 + math.ceil(sq / bq) * sk * d * 2)
    vmem = eb * (bq * d + 2 * bk * d) + 4 * bq * d + 4 * bq * 2
    return _roofline(flops, traffic, programs, tiles, vmem)


def _norms(shape: dict, config: dict) -> float:
    rows, hidden = shape["rows"], shape["hidden"]
    eb = _bytes(shape.get("dtype", "float32"))
    br = min(config["block_r"], rows)
    programs = math.ceil(rows / br)
    flops = 8.0 * rows * hidden
    traffic = eb * rows * hidden * 2
    vmem = eb * br * hidden * 2 + 4 * br * hidden
    return _roofline(flops, traffic, programs, programs, vmem)


def _paged(shape: dict, config: dict) -> float:
    """The ragged kernel on a decode launch: the key carries no row
    layout, so the model takes `tq` rows of one query, each holding
    keys in half its table.  What is true of the kernel and modeled:
    it reads a row's live pages once (all K/V heads of a page in one
    copy), an item a row and a loop turn a K/V block of `kv_pages`
    pages, and two slots of a block of K and of V live in VMEM.
    `q_tile_rows` does not touch a one-query row, so candidates tie on
    it and the default stands; measured on the v5e (PERF.md, PR 26)
    the wider block won at every row length tried."""
    tq, kvh, d = shape["tq"], shape["kv_heads"], shape["head_dim"]
    page, nblk = shape["page"], shape["nblk"]
    eb = _bytes(shape.get("dtype", "float32"))
    kvb = max(1, min(config["kv_pages"], nblk))
    live = max(1, nblk // 2)
    blocks = tq * math.ceil(live / kvb)
    flops = 4.0 * tq * kvh * live * page * d
    traffic = eb * tq * kvh * live * page * d * 2 + 2.0 * eb * tq * kvh * d
    vmem = eb * 4 * kvb * kvh * page * d + 4 * kvh * (d + 2 * 128)
    return _roofline(flops, traffic, tq, blocks, vmem)


def _weight_bytes_per_elem(dtype: str) -> float:
    # int4 nibble-packs two weights per byte; scales ride separately
    return 0.5 if dtype == "int4" else float(_bytes(dtype))


def _quant_matmul(shape: dict, config: dict) -> float:
    """Fused dequant matmul: x [M, K] f32 against a quantized [K, N]
    weight pool.  Traffic is the decode story — activations and the f32
    output are tiny next to the weight bytes, which shrink 4x/8x vs a
    dense f32 operand.  VMEM holds one x block, one quantized weight
    block plus its f32 upcast (the dequant temporary), and the f32
    accumulator/output tile."""
    m, k, n = shape["m"], shape["k"], shape["n"]
    dtype = shape.get("dtype", "int8")
    wb = _weight_bytes_per_elem(dtype)
    bm = min(config["block_m"], m)
    bn = min(config["block_n"], n)
    bk = min(config["block_k"], k)
    programs = math.ceil(m / bm) * math.ceil(n / bn)
    tiles = programs * math.ceil(k / bk)
    flops = 2.0 * m * k * n
    scale_rows = math.ceil(k / 128) if dtype == "int4" else 1
    traffic = (4.0 * m * k                    # activations
               + wb * k * n                   # quantized weight stream
               + 4.0 * scale_rows * n         # scales
               + 4.0 * m * n)                 # f32 output
    vmem = (4.0 * bm * bk                     # x block
            + wb * bk * bn                    # quantized weight block
            + 4.0 * bk * bn                   # f32 dequant temporary
            + 4.0 * bm * bn * 2)              # accumulator + out tile
    return _roofline(flops, traffic, programs, tiles, vmem)


def f32_matmul_estimate(m: int, k: int, n: int) -> float:
    """Roofline seconds for the dense f32 XLA matmul at the same shape —
    the A/B baseline serve_bench and the acceptance gate quote against
    the tuned ``quant_matmul`` estimate.  One program (XLA fuses the
    whole contraction), full-width f32 weight traffic."""
    flops = 2.0 * m * k * n
    traffic = 4.0 * (m * k + k * n + m * n)
    return max(flops / PEAK_FLOPS, traffic / PEAK_BW) + PER_PROGRAM_S


_MODELS = {
    "flash_attention": _flash,
    "flash_attention_varlen": _flash,
    "fused_norms": _norms,
    "paged_attention": _paged,
    "quant_matmul": _quant_matmul,
}


def estimate(kernel: str, shape: dict, config: dict) -> float:
    """Modeled seconds for one launch; math.inf when infeasible."""
    fn = _MODELS.get(kernel)
    if fn is None:
        raise KeyError(f"no cost model for kernel {kernel!r}")
    return fn(shape, config)
