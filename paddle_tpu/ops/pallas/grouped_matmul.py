"""The routed experts' products (TPU): rows sorted by expert, one matrix
an expert, no row dropped and none padded to a capacity.

``grouped_matmul(x, w, group_sizes)`` multiplies rows ``[start_e, start_e
+ group_sizes[e])`` of x [M, K] by w[e] of w [E, K, N], for every expert
e in order (``start_e`` the sum of the sizes before it);
``grouped_swiglu(x, w_gate, w_up, group_sizes)`` gives ``silu(x w_gate[e])
* (x w_up[e])`` for the same rows in one pass over x, ``grouped_reglu``
the same with ReLU on the gate.  Rows past the last
group are not computed: what the result holds there is undefined, and the
caller masks it.  The work follows the sizes: an expert of no rows costs
nothing, and all rows on one expert is one dense product.

With a few dozen rows an expert the product is bound by reading each
touched expert's matrix once, so the kernel is built around that read.
The rows are walked in VISITS: a (group, row tile) pair for every tile of
``tm`` rows a group's rows touch, in order (``_visits``: at most M / tm +
E - 1 of them, the bound that fixes the grid; the rest of the grid stands
still on the last visit and does nothing).  The grid is (column blocks,
visits); a step takes the visit's row tile whole in K, the visit's
expert's ``[K, tn]`` block (4 MB: a copy long enough to hide a grid
step), and stores the rows of the tile that are the group's.  Visits of
one tile follow each other, so the output block stays in fast memory
between them; every matrix block is read once for each row tile its
group touches, which for groups under ``tm`` rows is once or twice.

``jax.lax.ragged_dot`` is the same function and the oracle beside the
kernel (and what runs off the TPU); ``grouped_matmul_reference`` is the
plain loop over experts both are held to.  On the v5e the kernel took
the engine builder's choice over ``ragged_dot`` (which libtpu lowers to a
grouped kernel of its own, 2.7 ms a product at 4,608 rows of which 1,150
live, and outside every ``jax.named_scope``) and over JAX's megablox
kernel at its default tiles (2.0 ms): PERF.md, PR 28.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _pa

KERNEL_NAME = "grouped_expert_matmul"
_DEFAULTS = {"row_tile": 128, "block_bytes": 4 << 20}


def _tiles(M, K, N, dtype):
    """(rows a tile, columns a block) from the tuning cache: the column
    block is the widest multiple of 128 that divides N with a ``[K, tn]``
    block within ``block_bytes``."""
    from ...tune import kernel_config
    cfg = kernel_config("grouped_matmul",
                        {"m": M, "k": K, "n": N,
                         "dtype": jnp.dtype(dtype).name},
                        defaults=_DEFAULTS)
    tm = min(int(cfg["row_tile"]), M)
    want = max(128, int(cfg["block_bytes"])
               // (K * jnp.dtype(dtype).itemsize))
    tn = N
    for cand in range(128, N + 1, 128):
        if N % cand == 0 and cand <= want:
            tn = cand
    return tm, tn


def _visits(group_sizes, M, tm):
    """The walk over (group, row tile) pairs, as int32 arrays: the group
    and the row tile of every visit (length M / tm + E - 1; visits past
    the last real one repeat it), each group's first row and the row
    after its last, and the number of real visits."""
    E = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    total = visit_ends[-1]
    v = jnp.minimum(jnp.arange(M // tm + E - 1, dtype=jnp.int32),
                    jnp.maximum(total - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(visit_ends, v, side="right"),
                      E - 1).astype(jnp.int32)
    mt = first[gid] + v - (visit_ends[gid] - tiles[gid])
    return gid, mt.astype(jnp.int32), starts, ends, total.reshape(1)


_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _kernel(gid_ref, mt_ref, starts_ref, ends_ref, n_ref, x_ref, *refs, tm,
            gate):
    """grid (column blocks, visits).  x_ref [tm, K]: the visit's row
    tile; one or two [K, tn] blocks of the visit's expert (two with
    ``gate``, the name of the function on the first product); o_ref
    [tm, tn] stays in place while consecutive visits share a row tile."""
    *w_refs, o_ref = refs
    v = pl.program_id(1)

    @pl.when(v < n_ref[0])
    def _visit():
        g = gid_ref[v]
        x = x_ref[...]
        out = jax.lax.dot_general(x, w_refs[0][...],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        if gate is not None:
            out = _GATES[gate](out) * jax.lax.dot_general(
                x, w_refs[1][...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        row = mt_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (row >= starts_ref[g]) & (row < ends_ref[g])
        o_ref[...] = jnp.where(mine, out.astype(o_ref.dtype), o_ref[...])


def _launch(x, ws, group_sizes, out_dtype, gate):
    M, K = x.shape
    N = ws[0].shape[-1]
    tm, tn = _tiles(M, K, N, ws[0].dtype)
    pad = -M % tm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    meta = _visits(group_sizes, M + pad, tm)
    n_visits = meta[0].shape[0]
    w_spec = pl.BlockSpec((None, K, tn),
                          lambda n, v, gid, *_: (gid[v], 0, n))
    item = jnp.dtype(ws[0].dtype).itemsize
    need = 2 * (len(ws) * K * tn * item + tm * K * x.dtype.itemsize
                + tm * tn * jnp.dtype(out_dtype).itemsize) \
        + 3 * tm * tn * 4
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, gate=gate),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N // tn, n_visits),
            in_specs=[pl.BlockSpec((tm, K),
                                   lambda n, v, gid, mt, *_: (mt[v], 0))]
            + [w_spec] * len(ws),
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, v, gid, mt, *_: (mt[v], n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M + pad, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(100 << 20, max(16 << 20, 2 * need))),
        interpret=_pa.interpret_mode(),
        name=KERNEL_NAME,
    )(*meta, x, *ws)
    return out[:M] if pad else out


def grouped_matmul(x, w, group_sizes, *, use_kernel: bool,
                   out_dtype=jnp.float32):
    """x [M, K], w [E, K, N], group_sizes [E] int (sum <= M) -> [M, N] in
    ``out_dtype`` (float32 accumulation either way).  ``use_kernel``: the
    Pallas kernel, else ``lax.ragged_dot``."""
    if use_kernel:
        return _launch(x, (w,), group_sizes, out_dtype, None)
    return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32),
                              preferred_element_type=out_dtype)


def grouped_swiglu(x, w_gate, w_up, group_sizes, *, use_kernel: bool,
                   gate: str = "silu"):
    """``silu(x w_gate[e]) * (x w_up[e])`` of each group's rows, in x's
    type: the first half of an expert's SwiGLU, x read once.  ``gate``
    names the function on the first product (``relu``: ReGLU)."""
    if use_kernel:
        return _launch(x, (w_gate, w_up), group_sizes, x.dtype, gate)
    gs = group_sizes.astype(jnp.int32)
    g = jax.lax.ragged_dot(x, w_gate, gs,
                           preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(x, w_up, gs, preferred_element_type=jnp.float32)
    return (_GATES[gate](g) * up).astype(x.dtype)


def grouped_reglu(x, w_gate, w_up, group_sizes, *, use_kernel: bool):
    """``relu(x w_gate[e]) * (x w_up[e])``: the first half of a ReGLU
    expert."""
    return grouped_swiglu(x, w_gate, w_up, group_sizes,
                          use_kernel=use_kernel, gate="relu")


def grouped_matmul_reference(x, w, group_sizes, *, out_dtype=jnp.float32):
    """The same by a loop over experts: every expert multiplies all rows
    and keeps its own.  Rows past the last group read zero."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    row = jnp.arange(x.shape[0])[:, None]
    out = jnp.zeros((x.shape[0], w.shape[-1]), jnp.float32)
    for e in range(w.shape[0]):
        mine = (row >= starts[e]) & (row < ends[e])
        out = out + jnp.where(mine, jnp.dot(
            x, w[e], preferred_element_type=jnp.float32), 0.0)
    return out.astype(out_dtype)
