"""Semi-auto parallel (DistTensor) API.

Parity with /root/reference/python/paddle/distributed/auto_parallel/api.py
(shard_tensor :220, reshard :797, shard_layer :908, shard_optimizer :1735,
to_static :2952).

TPU-native: a DistTensor is a paddle_tpu Tensor whose jax.Array carries a
NamedSharding over the ProcessMesh's jax Mesh — placements map 1:1 onto
PartitionSpec entries, and GSPMD performs the SPMD-rule propagation the
reference implements in 25k LoC of spmd_rules (SURVEY.md §2.5).  reshard is
a device_put to a new sharding (XLA inserts the collectives).
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec

from ...core.tensor import Parameter, Tensor
from .process_mesh import ProcessMesh

__all__ = ["Shard", "Replicate", "Partial", "Placement", "shard_tensor",
           "dtensor_from_fn", "reshard", "shard_layer", "shard_optimizer",
           "to_static", "dist_attr", "DistAttr", "unshard_dtensor"]


class Placement:
    def is_shard(self, dim=None):
        return False

    def is_replicate(self):
        return False

    def is_partial(self):
        return False


class Shard(Placement):
    def __init__(self, dim):
        self.dim = int(dim)

    def is_shard(self, dim=None):
        return dim is None or dim == self.dim

    def get_dim(self):
        return self.dim

    def __repr__(self):
        return f"Shard(dim={self.dim})"

    def __eq__(self, other):
        return isinstance(other, Shard) and other.dim == self.dim

    def __hash__(self):
        return hash(("shard", self.dim))


class Replicate(Placement):
    def is_replicate(self):
        return True

    def __repr__(self):
        return "Replicate()"

    def __eq__(self, other):
        return isinstance(other, Replicate)

    def __hash__(self):
        return hash("replicate")


class Partial(Placement):
    def __init__(self, reduce_type=None):
        self.reduce_type = reduce_type

    def is_partial(self):
        return True

    def __repr__(self):
        return "Partial()"

    def __eq__(self, other):
        return isinstance(other, Partial)

    def __hash__(self):
        return hash("partial")


class DistAttr:
    def __init__(self, mesh: ProcessMesh, placements):
        self.process_mesh = mesh
        self.placements = list(placements)


dist_attr = DistAttr


def _to_partition_spec(mesh: ProcessMesh, placements, ndim: int) -> PartitionSpec:
    """placements[i] describes mesh axis i; build a dim->axis-names spec."""
    entries: list = [None] * ndim
    for axis_idx, pl in enumerate(placements):
        if isinstance(pl, Shard):
            name = mesh.dim_names[axis_idx]
            if entries[pl.dim] is None:
                entries[pl.dim] = name
            elif isinstance(entries[pl.dim], tuple):
                entries[pl.dim] = entries[pl.dim] + (name,)
            else:
                entries[pl.dim] = (entries[pl.dim], name)
        # Replicate/Partial leave dims unsharded (Partial is a reduction
        # bookkeeping state; GSPMD resolves it at use sites)
    return PartitionSpec(*entries)


def _sharding_for(mesh: ProcessMesh, placements, ndim):
    return NamedSharding(mesh.jax_mesh(),
                         _to_partition_spec(mesh, placements, ndim))


def shard_tensor(data, mesh: ProcessMesh, placements, dtype=None, place=None,
                 stop_gradient=None):
    """Create a DistTensor: place `data` on `mesh` with `placements`."""
    if isinstance(data, Tensor):
        t = data
    else:
        from ...core.tensor import to_tensor
        t = to_tensor(data, dtype=dtype)
    sharding = _sharding_for(mesh, placements, t.ndim)
    arr = jax.device_put(t._data, sharding)
    out = Parameter(arr, name=t.name) if isinstance(t, Parameter) else \
        Tensor(arr, stop_gradient=t.stop_gradient if stop_gradient is None else stop_gradient,
               name=t.name)
    if isinstance(t, Parameter) and stop_gradient is not None:
        out.stop_gradient = stop_gradient
    out._dist_attr = DistAttr(mesh, placements)
    if isinstance(out, Parameter):
        out.optimize_attr = getattr(t, "optimize_attr", {"learning_rate": 1.0})
        out.regularizer = getattr(t, "regularizer", None)
    return out


def dtensor_from_fn(fn, mesh: ProcessMesh, placements, *args, **kwargs):
    return shard_tensor(fn(*args, **kwargs), mesh, placements)


def _resolve_partial(dist_tensor, target_placements):
    """Sum per-device partial values over every mesh axis whose Partial
    placement is being dropped (reference p_to_r / p_to_s reshard
    functions, phi/core/distributed/auto_parallel/reshard/)."""
    src_attr = dist_tensor._dist_attr
    if src_attr is None:
        return dist_tensor._data
    mesh = src_attr.process_mesh
    reduce_axes = []
    for i, pl in enumerate(src_attr.placements):
        tgt = (target_placements[i]
               if i < len(target_placements) else Replicate())
        if isinstance(pl, Partial) and not isinstance(tgt, Partial):
            reduce_axes.append(mesh.dim_names[i])
    if not reduce_axes:
        return dist_tensor._data
    jm = mesh.jax_mesh()
    spec = _to_partition_spec(mesh, src_attr.placements, dist_tensor.ndim)
    return _partial_sum_prog(jm, spec, tuple(reduce_axes))(
        dist_tensor._data)


# graft-lint caught the original inline `jax.jit(shard_map(...))(x)` here:
# a fresh lambda per reshard meant a fresh jit cache entry — i.e. one XLA
# compile per p->r/p->s reshard call.  Keyed on (mesh, spec, axes) the
# psum program compiles once per distinct reshard shape.
_PSUM_PROGS: dict = {}


def _partial_sum_prog(jm, spec, reduce_axes):
    key = (jm, spec, reduce_axes)
    prog = _PSUM_PROGS.get(key)
    if prog is None:
        from jax import lax

        from jax import shard_map
        # check_vma=False: the "replicated" input really carries per-device
        # partial values; psum performs the pending reduction
        prog = jax.jit(shard_map(lambda x: lax.psum(x, reduce_axes),
                                 mesh=jm, in_specs=spec, out_specs=spec,
                                 check_vma=False))
        _PSUM_PROGS[key] = prog
    return prog


def reshard(dist_tensor, mesh: ProcessMesh, placements):
    """Convert placements (XLA emits the collectives: allgather for s->r,
    slice for r->s, psum for p->r, reduce_scatter for p->s, all_to_all for
    s->s')."""
    arr = _resolve_partial(dist_tensor, placements)
    sharding = _sharding_for(mesh, placements, dist_tensor.ndim)
    arr = jax.device_put(arr, sharding)
    out = Tensor(arr, stop_gradient=dist_tensor.stop_gradient,
                 name=dist_tensor.name)
    out._dist_attr = DistAttr(mesh, placements)
    out._grad_node = dist_tensor._grad_node
    out._output_index = dist_tensor._output_index
    return out


def unshard_dtensor(dist_tensor):
    full = NamedSharding(dist_tensor._dist_attr.process_mesh.jax_mesh(),
                         PartitionSpec()) if dist_tensor._dist_attr else None
    arr = jax.device_put(dist_tensor._data, full) if full else dist_tensor._data
    return Tensor(arr, stop_gradient=dist_tensor.stop_gradient)


def shard_layer(layer, process_mesh: ProcessMesh, shard_fn=None,
                input_fn=None, output_fn=None):
    """Shard every parameter of `layer` (reference api.py:908).  Default:
    replicate everything on the mesh; shard_fn(name, layer, mesh) customizes."""
    def default_fn(name, sublayer, mesh):
        for pname, p in list(sublayer._parameters.items()):
            if p is None:
                continue
            sublayer._parameters[pname] = shard_tensor(
                p, mesh, [Replicate() for _ in range(mesh.ndim)])

    fn = shard_fn or default_fn
    for name, sub in layer.named_sublayers(include_self=True):
        fn(name, sub, process_mesh)
    if input_fn is not None:
        layer.register_forward_pre_hook(
            lambda l, inputs: input_fn(inputs, process_mesh))
    if output_fn is not None:
        layer.register_forward_post_hook(
            lambda l, inputs, outputs: output_fn(outputs, process_mesh))
    return layer


def shard_optimizer(optimizer, shard_fn=None):
    """ZeRO-style optimizer-state sharding: accumulator slots inherit each
    parameter's sharding automatically (they are created zeros_like on the
    sharded param), so GSPMD already partitions optimizer state; shard_fn can
    re-place them explicitly."""
    if shard_fn is not None:
        orig_init = optimizer._init_slot

        def wrapped(name, p):
            base = orig_init(name, p)
            return shard_fn(name, p, base)
        optimizer._init_slot = wrapped
    return optimizer


def to_static(layer, loader=None, loss=None, optimizer=None, strategy=None):
    """Whole-graph capture of a distributed train step (reference api.py:2952
    Engine path).  Returns a DistModel-like callable whose step is one pjit'd
    program over the mesh."""
    from ...jit import to_static as _jit_to_static
    return _jit_to_static(layer)
