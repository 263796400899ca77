"""Ring attention: exact blockwise attention over a sequence-sharded mesh axis.

This is the TPU-native long-context answer the reference snapshot lacks
(SURVEY.md §5.7: no ring attention / context parallelism in Paddle 3.0-rc —
its long-context story is flash-attention + Megatron-SP).  We exceed parity:
the sequence is sharded over a context-parallel mesh axis ("cp"/"sep") and
each device computes flash-style online-softmax blocks while KV shards rotate
around the ring via `lax.ppermute` — compute on block t overlaps the ICI
transfer of block t+1, and `jax.grad` transposes the rotation automatically
(ppermute^T = reverse ppermute), so the backward pass is also a ring.

All math accumulates in float32 regardless of input dtype (matches the
reference flash-attention contract, paddle/phi/kernels/gpu/flash_attn_kernel.cu).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import axis_size as _axis_size, pcast as _pcast
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention", "ring_self_attention", "zigzag_permutation",
           "zigzag_inverse_permutation"]

_NEG_INF = float(-1e30)  # finite sentinel: avoids -inf NaN traps in exp/max


def _block_attn_step(q, k, v, m_i, l_i, acc, qpos, kpos, causal):
    """One online-softmax accumulation against a single KV block.

    q [B,h,Sq,d] / k,v [B,h,Sk,d] float32; m_i,l_i [B,h,Sq]; acc like q.
    qpos/kpos are GLOBAL token positions used for causal masking across
    ring steps.
    """
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    if causal:
        mask = kpos[None, None, None, :] <= qpos[None, None, :, None]
        s = jnp.where(mask, s, _NEG_INF)
    m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_i - m_new)
    p = jnp.exp(s - m_new[..., None])
    if causal:
        p = jnp.where(mask, p, 0.0)  # kill exp(NEG_INF - m) residue exactly
    l_new = alpha * l_i + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, axis_name: str, causal: bool = True,
                   shard_positions=None):
    """Exact attention with q/k/v sequence-sharded over ``axis_name``.

    Call INSIDE shard_map/pjit manual region.  q/k/v: [B, S_local, H, D]
    (batch, local seq, heads, head_dim).  Returns [B, S_local, H, D] in the
    input dtype.

    shard_positions: optional [axis_size, S_local] int32 array giving the
    global token positions held by each shard (for zigzag/load-balanced
    layouts).  Default: contiguous — shard i holds [i*S_local, (i+1)*S_local).
    """
    cp = _axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, S, H, D = q.shape
    in_dtype = q.dtype

    qf = jnp.swapaxes(q, 1, 2).astype(jnp.float32)   # [B,h,S,d]
    kf = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vf = jnp.swapaxes(v, 1, 2).astype(jnp.float32)

    if shard_positions is None:
        base = jnp.arange(S, dtype=jnp.int32)
        qpos = my * S + base
        pos_of = lambda idx: idx * S + base
    else:
        shard_positions = jnp.asarray(shard_positions, jnp.int32)
        qpos = shard_positions[my]
        pos_of = lambda idx: shard_positions[idx]

    # KV travels forward around the ring: after t hops this device holds the
    # block originally on rank (my - t) % cp.
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    # scan needs carry-in vma == carry-out vma: mark the fresh accumulators
    # as varying over the ring axis (kf/vf/qf already are).
    m0 = _pcast(jnp.full((B, H, S), _NEG_INF, jnp.float32),
                axis_name, to="varying")
    l0 = _pcast(jnp.zeros((B, H, S), jnp.float32), axis_name, to="varying")
    acc0 = jnp.zeros_like(qf)  # zeros_like inherits qf's varying vma

    # Block 0 (own KV) is computed outside the loop; each remaining step
    # permutes then computes, so exactly cp-1 KV hops ride the ICI ring.
    m_f, l_f, acc = _block_attn_step(qf, kf, vf, m0, l0, acc0,
                                     qpos, pos_of(my), causal)

    def step(carry, t):
        k_cur, v_cur, m_i, l_i, acc = carry
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        src = (my - t) % cp
        kpos = pos_of(src)
        m_i, l_i, acc = _block_attn_step(qf, k_cur, v_cur, m_i, l_i, acc,
                                         qpos, kpos, causal)
        return (k_cur, v_cur, m_i, l_i, acc), None

    if cp > 1:
        (_, _, m_f, l_f, acc), _ = lax.scan(
            step, (kf, vf, m_f, l_f, acc), jnp.arange(1, cp))

    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    return jnp.swapaxes(out, 1, 2).astype(in_dtype)


def zigzag_permutation(seq_len: int, cp: int):
    """Load-balanced ("zigzag") context-parallel layout.

    With contiguous causal sharding, rank 0 attends to 1 block and rank cp-1
    to cp blocks — a cp/2 load imbalance.  The zigzag layout gives each rank
    one chunk from the front and the mirrored chunk from the back
    (rank i holds chunks i and 2cp-1-i of 2cp chunks), equalising causal work.

    Returns (perm, shard_positions): ``tokens[:, perm]`` reorders a global
    sequence so a plain contiguous split over cp ranks realises the layout,
    and shard_positions[i] are the global positions rank i holds (feed to
    ring_attention).
    """
    assert seq_len % (2 * cp) == 0, "seq_len must be divisible by 2*cp"
    chunk = seq_len // (2 * cp)
    import numpy as np
    order = []
    for i in range(cp):
        order.extend(range(i * chunk, (i + 1) * chunk))
        j = 2 * cp - 1 - i
        order.extend(range(j * chunk, (j + 1) * chunk))
    perm = np.asarray(order, np.int32)
    shard_positions = perm.reshape(cp, 2 * chunk)
    return perm, shard_positions


def zigzag_inverse_permutation(seq_len: int, cp: int):
    import numpy as np
    perm, _ = zigzag_permutation(seq_len, cp)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(seq_len, dtype=np.int32)
    return inv


@functools.lru_cache(maxsize=64)
def _ring_self_attention_fn(mesh: Mesh, axis_name: str, causal: bool):
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return jax.jit(fn)


def ring_self_attention(q, k, v, mesh: Mesh, axis_name: str = "sep",
                        causal: bool = True):
    """User-facing wrapper: global [B, S, H, D] arrays, seq sharded over
    ``axis_name`` of ``mesh``.  The shard_map'd program is built and
    compiled once per (mesh, axis, causal) and cached.

    Analog slot of paddle.nn.functional.flash_attention for long sequences;
    the reference has no CP equivalent (SURVEY.md §5.7).
    """
    return _ring_self_attention_fn(mesh, axis_name, bool(causal))(q, k, v)
