"""Pallas fused dequant-matmul (TPU): int8/int4 weight streaming.

Decode is HBM-bandwidth-bound: every step re-streams the full weight
set per token, so weight BYTES — not FLOPs — set the decode ceiling.
These kernels store transformer weights as quantized pools (int8 with
per-output-channel f32 scales; int4 nibble-packed two-per-byte with
per-128-row-group scales) and dequantize INLINE in the matmul: each
grid step streams one quantized [bk, bn] weight block from HBM,
upcasts it in VMEM against its scale rows, and feeds the MXU — the
weight traffic per decode step drops ~4x (int8) / ~8x (int4) vs f32
while activations and accumulation stay full f32.

Layout contract (shared with LLMEngine's weight pools):

* int8: ``q`` is [K, N] int8, ``s`` is [N] f32 — symmetric
  per-output-channel scales, float = int8 * s[n].
* int4: ``q`` is [K//2, N] int8 with two signed nibbles per byte —
  packed row r holds unpacked rows 2r (low nibble) and 2r+1 (high
  nibble) of column n; ``s`` is [ceil(K/128), N] f32 — one scale per
  128 consecutive K rows per output column, float = nibble * s[r//128,
  n].  K must be even.

Column-sliced TP sharding commutes with both layouts: slicing q and s
by the same output-column blocks IS the quantization of the sliced f32
weight, so tp=N engines shard the pools with zero resharding.

``reference_matmul`` is the term-identical XLA fake-quant oracle
(dense dequantize, then one f32 matmul) — the CPU/test path and the
correctness baseline; kernel-vs-oracle parity is allclose, not
bit-identical, because the blocked k-loop sums partial products in a
different order than the dense contraction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tri-state interpret override, same contract as paged_attention.py:
# None (default) resolves per-backend — interpret everywhere except a
# real TPU — so the kernel entry points work on CPU without mutating
# this global.  NOTE the serving engine does NOT ride the auto-resolved
# mode: interpreted matmul costs a Python step per (M/bm, N/bn, K/bk)
# grid cell, so LLMEngine uses the XLA fake-quant reference off-TPU
# unless INTERPRET is explicitly True.
INTERPRET = None

GROUP = 128             # int4 scale-group length along K


def interpret_mode() -> bool:
    """Resolved interpret flag: the module override wins when set."""
    if INTERPRET is None:
        return jax.default_backend() != "tpu"
    return bool(INTERPRET)


# ---------------------------------------------------------------------------
# quantize / dequantize (build-time host transforms + oracle half)
# ---------------------------------------------------------------------------

def quantize_weight(w, weight_dtype: str):
    """Quantize one [K, N] f32 weight to ``(q, s)`` in the pool layout.

    int8: per-output-channel symmetric, s[n] = amax(w[:, n]) / 127.
    int4: per-128-row-group per-output-channel, s[g, n] =
    amax(group) / 7, nibbles packed two-per-byte along K.  All-zero
    channels/groups quantize against scale 1.0 (q == 0 regardless).
    """
    w = jnp.asarray(w, jnp.float32)
    if w.ndim != 2:
        raise ValueError(f"expected a [K, N] weight, got shape {w.shape}")
    K, N = w.shape
    if weight_dtype == "int8":
        amax = jnp.max(jnp.abs(w), axis=0)                   # [N]
        s = jnp.where(amax > 0.0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(w / s[None, :]), -127, 127).astype(jnp.int8)
        return q, s
    if weight_dtype == "int4":
        if K % 2:
            raise ValueError(f"int4 packing needs even K, got K={K}")
        G = -(-K // GROUP)
        pad = G * GROUP - K
        wp = jnp.pad(w, ((0, pad), (0, 0))) if pad else w
        gmax = jnp.max(jnp.abs(wp.reshape(G, GROUP, N)), axis=1)  # [G, N]
        s = jnp.where(gmax > 0.0, gmax / 7.0, 1.0)
        srow = jnp.repeat(s, GROUP, axis=0)[:K]              # [K, N]
        q = jnp.clip(jnp.round(w / srow), -8, 7).astype(jnp.int32)
        lo, hi = q[0::2], q[1::2]                            # [K//2, N]
        packed = ((hi << 4) | (lo & 0xF)) & 0xFF
        return jax.lax.bitcast_convert_type(
            packed.astype(jnp.uint8), jnp.int8), s
    raise ValueError(
        f"weight_dtype must be 'int8' or 'int4', got {weight_dtype!r}")


def unpack_int4(packed):
    """[K//2, N] nibble-packed int8 -> [K, N] int32 in [-8, 7]; packed
    row r expands to rows 2r (low nibble) and 2r+1 (high nibble)."""
    p = packed.astype(jnp.int32)
    lo = (p << 28) >> 28            # sign-extend the low nibble
    hi = p >> 4                     # int8->int32 sign-extended already
    Kh, N = packed.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * Kh, N)


def dequantize_weight(q, s, weight_dtype: str):
    """Dense f32 [K, N] weight from a quantized pool entry — the XLA
    fake-quant half of the oracle, and the engine's off-TPU path."""
    if weight_dtype == "int8":
        return q.astype(jnp.float32) * s[None, :]
    if weight_dtype == "int4":
        w = unpack_int4(q).astype(jnp.float32)
        K = w.shape[0]
        srow = jnp.repeat(s, GROUP, axis=0)[:K]
        return w * srow
    raise ValueError(
        f"weight_dtype must be 'int8' or 'int4', got {weight_dtype!r}")


def quantize_embedding(embed, weight_dtype: str):
    """Quantize a [V, H] embedding table with per-vocab-row symmetric
    scales — the gather axis, so a token lookup dequantizes exactly the
    rows it reads.  int4 packs column PAIRS two-per-byte along H (byte
    column c holds columns 2c low / 2c+1 high); H must be even."""
    embed = jnp.asarray(embed, jnp.float32)
    V, H = embed.shape
    amax = jnp.max(jnp.abs(embed), axis=1)                   # [V]
    if weight_dtype == "int8":
        s = jnp.where(amax > 0.0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(embed / s[:, None]),
                     -127, 127).astype(jnp.int8)
        return q, s
    if weight_dtype == "int4":
        if H % 2:
            raise ValueError(f"int4 packing needs even H, got H={H}")
        s = jnp.where(amax > 0.0, amax / 7.0, 1.0)
        q = jnp.clip(jnp.round(embed / s[:, None]), -8, 7) \
            .astype(jnp.int32)
        lo, hi = q[:, 0::2], q[:, 1::2]                      # [V, H//2]
        packed = ((hi << 4) | (lo & 0xF)) & 0xFF
        return jax.lax.bitcast_convert_type(
            packed.astype(jnp.uint8), jnp.int8), s
    raise ValueError(
        f"weight_dtype must be 'int8' or 'int4', got {weight_dtype!r}")


def dequantize_rows(q_rows, s_rows, weight_dtype: str):
    """Inline gather-dequant: gathered embedding rows ``q_rows``
    [T, H or H//2] with their per-row scales ``s_rows`` [T] -> [T, H]
    f32.  This is the embedding's whole bandwidth win — only the rows a
    launch actually reads are ever upcast."""
    if weight_dtype == "int8":
        return q_rows.astype(jnp.float32) * s_rows[:, None]
    if weight_dtype == "int4":
        p = q_rows.astype(jnp.int32)
        lo = (p << 28) >> 28
        hi = p >> 4
        T, Hh = q_rows.shape
        rows = jnp.stack([lo, hi], axis=2).reshape(T, 2 * Hh)
        return rows.astype(jnp.float32) * s_rows[:, None]
    raise ValueError(
        f"weight_dtype must be 'int8' or 'int4', got {weight_dtype!r}")


def reference_matmul(x, q, s, weight_dtype: str):
    """Term-identical XLA fake-quant oracle: dense dequant then one f32
    contraction.  ``x`` [M, K] (any float dtype), result [M, N] f32."""
    w = dequantize_weight(q, s, weight_dtype)
    return jax.lax.dot_general(
        x.astype(jnp.float32), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# tuned launch geometry
# ---------------------------------------------------------------------------

def _fit(dim: int, want: int) -> int:
    """Largest block <= want that divides dim (block grids never pad)."""
    b = max(1, min(int(want), dim))
    while dim % b:
        b -= 1
    return b


def _fit_k(K: int, want: int, packed: bool) -> int:
    """k-block fit.  int4 blocks must additionally pack (even) and nest
    with the 128-row scale groups: a block is either a multiple of the
    group (one scale row per 128 rows) or a divisor of it (the whole
    block inside one group)."""
    b = max(1, min(int(want), K))
    while b > 1:
        if K % b == 0 and (
                not packed
                or (b % 2 == 0 and (b % GROUP == 0 or GROUP % b == 0))):
            return b
        b -= 1
    return 1


def _block_geometry(m: int, k: int, n: int, weight_dtype: str):
    """Trace-time tuned (bm, bn, bk) for one quantized matmul launch.

    The tuned values only re-tile the SAME contraction — k-blocks are
    visited in ascending order whatever bk is, so accumulation order
    within a block boundary family is fixed by the config, and the
    result is allclose-stable across configs (blocked f32 partial
    sums)."""
    from ...tune import kernel_config
    cfg = kernel_config("quant_matmul",
                        {"m": m, "k": k, "n": n, "dtype": weight_dtype})
    packed = weight_dtype == "int4"
    bm = _fit(m, cfg["block_m"])
    bn = _fit(n, cfg["block_n"])
    bk = _fit_k(k, cfg["block_k"], packed)
    return bm, bn, bk


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, bk, packed):
    """grid (M/bm, N/bn, K/bk), k innermost.  x block [bm, bk]; w block
    [bk, bn] int8 (int4: [bk//2, bn] nibble-packed); s block [gb, bn]
    f32 scale rows covering the block's K rows; o [bm, bn]; scratch acc
    [bm, bn] f32.  Dequant happens HERE, in VMEM, on the streamed
    block — the f32 weight tile never exists in HBM."""
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    if packed:
        p = w.astype(jnp.int32)
        lo = (p << 28) >> 28
        hi = p >> 4
        w = jnp.stack([lo, hi], axis=1).reshape(bk, w.shape[1])
    s = s_ref[...].astype(jnp.float32)               # [gb, bn]
    s = jnp.repeat(s, bk // s.shape[0], axis=0)      # [bk, bn]
    wf = w.astype(jnp.float32) * s
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), wf, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kstep == pl.num_programs(2) - 1)
    def _out():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("weight_dtype",))
def matmul(x, q, s, *, weight_dtype: str):
    """Fused gather-dequant matmul: ``x @ dequant(q, s)`` -> [M, N] f32.

    ``x`` [M, K] float; ``q``/``s`` in the pool layout documented in
    the module header.  Geometry flows from the tuning cache via
    ``_block_geometry``; callers off-TPU should prefer
    ``reference_matmul`` unless INTERPRET is forced True (the engine's
    contract — the interpreter pays a Python step per grid cell)."""
    if weight_dtype not in ("int8", "int4"):
        raise ValueError(
            f"weight_dtype must be 'int8' or 'int4', got {weight_dtype!r}")
    packed = weight_dtype == "int4"
    M, K = x.shape
    N = q.shape[1]
    s2 = jnp.atleast_2d(s)                           # [G, N] (int8: G=1)
    bm, bn, bk = _block_geometry(M, K, N, weight_dtype)
    if packed:
        w_spec = pl.BlockSpec((bk // 2, bn), lambda m, n, k: (k, n))
        gb = max(1, bk // GROUP)
        if bk % GROUP == 0:
            s_spec = pl.BlockSpec((gb, bn), lambda m, n, k: (k, n))
        else:
            # whole k-block inside one 128-row group
            s_spec = pl.BlockSpec(
                (1, bn), lambda m, n, k: ((k * bk) // GROUP, n))
    else:
        w_spec = pl.BlockSpec((bk, bn), lambda m, n, k: (k, n))
        s_spec = pl.BlockSpec((1, bn), lambda m, n, k: (0, n))
    kern = functools.partial(_qmm_kernel, bk=bk, packed=packed)
    return pl.pallas_call(
        kern,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            w_spec,
            s_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret_mode(),
    )(x, q, s2)


# ---------------------------------------------------------------------------
# eligibility: a static claim from shapes
# ---------------------------------------------------------------------------

def ineligible(K, N, weight_dtype: str) -> str | None:
    """Why the fused kernel does not claim a [K, N] weight, or None when
    it does.  Static: whether Mosaic accepts the launch is settled by
    compiling the program that contains it, and a refusal there raises.
    Under tensor parallelism callers pass the PER-SHARD N —
    column-sharded pools launch inside shard_map, so Mosaic tiles
    against the shard-local width."""
    if weight_dtype not in ("int8", "int4"):
        return f"weight_dtype {weight_dtype!r} is not quantized"
    if K < 2 or N < 1:
        return f"degenerate weight [{K}, {N}]"
    if weight_dtype == "int4" and K % 2:
        return f"int4 packing needs an even K, got {K}"
    if N % 128:     # lane tiling: quantized blocks want full lanes
        return f"N={N} is not a multiple of 128 lanes"
    return None
