"""Ragged selective scan (TPU): Mamba-1's recurrence over the flat rows
of one serving launch, a state a SEQUENCE carried between launches.

For token t of a segment (a row of the launch: a prefill chunk of
hundreds of tokens or one decode token), with ``u`` the convolved input,
``delta`` the step size, ``B`` and ``C`` the token's input and output
maps and ``A`` the layer's decay:

    s_t = exp(delta_t * A) * s_{t-1} + (delta_t * u_t) (x) B_t    [N, d_inner]
    y_t = s_t . C_t + D * u_t                                     [d_inner]

``s`` before a segment's first token is the segment's slot of the state
pool ``[L, slots, N, d_inner]`` (float32), or zero where the segment
begins its sequence (``start``: decided on the device from the row
layout, so the host never clears a slot); after its last token ``s``
goes back to the slot.  Segments are given as the step program has
them: ``cu [R+1]`` (row r owns flat tokens cu[r] .. cu[r+1]) and
``slots [R]``.  A row of no tokens names a slot nobody holds (the
pool's last) and leaves every other alone; flat tokens past ``cu[R]``
are padding and read zero.

The kernel keeps ``d_inner`` across lanes and the N states across
sublanes: a grid step is one segment of one block of lanes, ``s``
[N, block] lives in registers for the whole segment, and the pool is
read and written in place (an aliased operand) a block a segment.  The
recurrence is sequential in t by nature: a chunk of 256 tokens is 256
dependent updates of the tile, which is why a block is kept to what the
register file holds.  ``selective_scan_reference`` is the same sum as an
XLA ``lax.scan`` over the flat tokens: the engine's path off the TPU
and the kernel's oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _pa

KERNEL_NAME = "ragged_selective_scan"
# lanes of d_inner a grid step holds: s [16, 1024] float32 is 16 vector
# registers of the 64
_DEFAULTS = {"lane_block": 1024}
_LANES = 128


def lane_layout(x):
    """``x`` [Tq, N] as the kernel reads a token's B or C: [Tq, N, 128],
    the N values down the sublanes and each repeated across a tile's
    lanes, so that a token's map is one aligned tile (Mosaic copies no
    ``[rows, 1]`` column)."""
    return jnp.broadcast_to(x[:, :, None], x.shape + (_LANES,))


def _block(Tq: int, di: int, n: int) -> int:
    """Lanes a grid step holds, from the tuning cache: the widest
    power-of-two share of ``lane_block`` that divides d_inner."""
    from ...tune import kernel_config
    cfg = kernel_config("selective_scan", {"tq": Tq, "di": di, "n": n},
                        defaults=_DEFAULTS)
    blk = min(int(cfg["lane_block"]), di)
    while di % blk:
        blk //= 2
    return blk


def ineligible(di: int, n: int) -> str | None:
    """Why the kernel does not claim these sizes, or None."""
    if di % _LANES:
        return f"d_inner {di} is not a multiple of {_LANES} lanes"
    if n % 8:
        return f"d_state {n} is not a multiple of 8 sublanes"
    return None


def _scan_kernel(cu_ref, start_ref, slots_ref, layer_ref, u_ref, dt_ref,
                 b_ref, c_ref, a_ref, d_ref, s_in_ref, y_ref, s_out_ref, *,
                 blk):
    del slots_ref, layer_ref                   # the index maps' alone
    r = pl.program_id(1)
    rep = blk // _LANES

    @pl.when(r == 0)
    def _zero():
        # padding tokens are no segment's: they read zero, not what the
        # block held before
        y_ref[...] = jnp.zeros_like(y_ref)

    a = a_ref[...]                                        # [N, blk]
    dvec = d_ref[...]                                     # [1, blk]
    s0 = jnp.where(start_ref[r] != 0, 0.0, s_in_ref[0, 0])

    def wide(tile):
        return tile if rep == 1 else jnp.concatenate([tile] * rep, axis=1)

    def token(t, s):
        u_t = u_ref[pl.ds(t, 1), :]                       # [1, blk]
        d_t = dt_ref[pl.ds(t, 1), :]
        s = jnp.exp(d_t * a) * s + (d_t * u_t) * wide(b_ref[t])
        y_ref[pl.ds(t, 1), :] = jnp.sum(s * wide(c_ref[t]), axis=0,
                                        keepdims=True) + dvec * u_t
        return s

    s_out_ref[0, 0] = lax.fori_loop(cu_ref[r], cu_ref[r + 1], token, s0)


def _scan_launch(u, delta, A, Bm, Cm, D, state, layer, slots, cu, start):
    Tq, di = u.shape
    n = A.shape[0]
    R = slots.shape[0]
    blk = _block(Tq, di, n)
    f32 = jnp.float32

    def lanes(j, r, *_):
        return (0, j)

    def whole(j, r, *_):
        return (0, 0, 0)

    def slot(j, r, cu, start, slots, layer):
        return (layer[0], slots[r], 0, j)

    rows = pl.BlockSpec((Tq, blk), lanes)
    maps = pl.BlockSpec((Tq, n, _LANES), whole)
    tile = pl.BlockSpec((1, 1, n, blk), slot)
    need = 2 * 4 * (3 * Tq * blk + 2 * Tq * n * _LANES + 3 * n * blk)
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, blk=blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(di // blk, R),
            in_specs=[rows, rows, maps, maps,
                      pl.BlockSpec((n, blk), lanes),
                      pl.BlockSpec((1, blk), lanes), tile],
            out_specs=[rows, tile]),
        out_shape=[jax.ShapeDtypeStruct((Tq, di), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the pool is updated in place: operand 10 (after the four
        # prefetched) is output 1
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(100 << 20, max(16 << 20, 2 * need))),
        interpret=_pa.interpret_mode(),
        name=KERNEL_NAME,
    )(cu, start.astype(jnp.int32), slots,
      jnp.asarray(layer, jnp.int32).reshape(1), u.astype(f32),
      delta.astype(f32), lane_layout(Bm.astype(f32)),
      lane_layout(Cm.astype(f32)), A.astype(f32),
      D.astype(f32).reshape(1, di), state)
    return y, state


def selective_scan(u, delta, A, Bm, Cm, D, state, layer, slots, cu, start,
                   *, use_kernel: bool):
    """y [Tq, d_inner] float32 and the state pool after the launch.

    u, delta [Tq, d_inner]; A [N, d_inner] (negative); Bm, Cm [Tq, N];
    D [d_inner]; state [L, slots, N, d_inner] float32, read and written
    at ``layer`` (int32, traced or static); slots [R] int32 the slot of
    each row (a row of no tokens: one nobody holds); cu [R+1] int32;
    start [R] bool, the rows that begin their sequence.  ``use_kernel``
    takes the Pallas kernel (``ineligible`` says what it claims)."""
    if use_kernel:
        return _scan_launch(u, delta, A, Bm, Cm, D, state, layer, slots,
                            cu, start)
    return selective_scan_reference(u, delta, A, Bm, Cm, D, state, layer,
                                    slots, cu, start)


def selective_scan_reference(u, delta, A, Bm, Cm, D, state, layer, slots,
                             cu, start):
    """The same sum in XLA: one ``lax.scan`` over the flat tokens, the
    state of every row in its carry (a token updates its row's)."""
    f32 = jnp.float32
    Tq = u.shape[0]
    R = slots.shape[0]
    u, delta, Bm, Cm = (x.astype(f32) for x in (u, delta, Bm, Cm))
    A, D = A.astype(f32), D.astype(f32)
    s0 = jnp.where(start.astype(bool)[:, None, None], 0.0,
                   state[layer, slots])                   # [R, N, di]
    # one row more: padding tokens' (never kept)
    s0 = jnp.concatenate([s0, jnp.zeros_like(s0[:1])])
    seg = jnp.searchsorted(cu[1:], jnp.arange(Tq, dtype=jnp.int32),
                           side="right").astype(jnp.int32)
    live = seg < R

    def token(S, inp):
        r, ok, u_t, d_t, b_t, c_t = inp
        s = jnp.exp(d_t[None, :] * A) * S[r] \
            + (d_t * u_t)[None, :] * b_t[:, None]
        y = jnp.sum(s * c_t[:, None], axis=0) + D * u_t
        return S.at[r].set(s), jnp.where(ok, y, 0.0)

    S, y = lax.scan(token, s0, (jnp.minimum(seg, R), live, u, delta, Bm,
                                Cm))
    return y, state.at[layer, slots].set(S[:R])
