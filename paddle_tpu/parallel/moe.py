"""Expert parallelism: MoE FFN block sharded over an "ep" mesh axis.

TPU-native equivalent of the reference's global_scatter/global_gather
all-to-all dispatch (/root/reference/python/paddle/incubate/distributed/
models/moe/moe_layer.py + paddle/phi/kernels/gpu/global_scatter_kernel.cu):
tokens stay data-sharded, experts are sharded over "ep", and two
`lax.all_to_all` collectives carry (token-slot -> expert) buffers across the
ICI ring.  Everything runs inside shard_map so XLA overlaps the a2a with
expert GEMMs.

Usage (inside shard_map over a mesh containing axis "ep"):
    y, aux = moe_ffn(x_local, params, ep_axis="ep")
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.lax import axis_size as _axis_size

from ..incubate.distributed.models.moe.gating import (
    capacity_for, combine_output, expert_silu_ffn, gate_dispatch)

__all__ = ["moe_ffn", "init_moe_params"]


def init_moe_params(key, d_model: int, d_ffn: int, num_experts: int,
                    dtype=jnp.float32, scale=0.02):
    """Returns {gate [H,E], w_in [E,H,F], w_out [E,F,H]} (GLOBAL shapes;
    shard w_in/w_out dim 0 over ep)."""
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": (scale * jax.random.normal(k1, (d_model, num_experts),
                                           jnp.float32)).astype(dtype),
        "w_in": (scale * jax.random.normal(k2, (num_experts, d_model, d_ffn),
                                           jnp.float32)).astype(dtype),
        "w_out": (scale * jax.random.normal(k3, (num_experts, d_ffn, d_model),
                                            jnp.float32)).astype(dtype),
    }


def moe_ffn(x, params, ep_axis: str | None = "ep", top_k: int = 2,
            capacity_factor: float = 2.0):
    """Gated MoE feed-forward over locally-sharded tokens.

    x: LOCAL [T_loc, H].  params: gate [H, E] replicated; w_in/w_out LOCAL
    expert shards [E_loc, H, F] / [E_loc, F, H] (E = ep * E_loc).
    Returns (y [T_loc, H], aux_loss scalar — already pmean'd over ep).
    """
    ep = _axis_size(ep_axis) if ep_axis else 1
    E_loc = params["w_in"].shape[0]
    E = ep * E_loc
    T_loc, H = x.shape

    C = capacity_for(T_loc, E, top_k, capacity_factor)
    # local buffers for EVERY global expert: [E, C, H]
    combine, expert_in, aux = gate_dispatch(x, params["gate"], top_k, C)

    if ep > 1:
        # exchange: rank r keeps its E_loc experts and receives those
        # experts' slots from every rank, concatenated in rank order:
        # [E, C, H] -> [E_loc, ep*C, H]
        expert_in = lax.all_to_all(expert_in, ep_axis, split_axis=0,
                                   concat_axis=1, tiled=True)
    expert_out = expert_silu_ffn(expert_in, params["w_in"], params["w_out"])
    if ep > 1:
        # reverse exchange: [E_loc, ep*C, H] -> [E, C, H]
        expert_out = lax.all_to_all(expert_out, ep_axis, split_axis=1,
                                    concat_axis=0, tiled=True)

    y = combine_output(combine, expert_out, x.dtype)
    if ep_axis:
        aux = lax.pmean(aux, ep_axis)
    return y, aux
