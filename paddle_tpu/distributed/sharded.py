"""Sharded execution helpers: shard_map + axis context.

This is where the reference's "ProcessGroup as runtime library" becomes
"collectives as compiled ops": wrap a framework function in `sharded_fn` and
every paddle_tpu.distributed collective inside it lowers to the XLA collective
on the named mesh axes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.tensor import Tensor
from .collective import axis_context
from .mesh import get_mesh

shard_map = jax.shard_map  # re-exported: distributed.shard_map


def _to_vals(x):
    return jax.tree_util.tree_map(
        lambda v: v._value if isinstance(v, Tensor) else v, x,
        is_leaf=lambda v: isinstance(v, Tensor),
    )


def _to_tensors(x):
    return jax.tree_util.tree_map(
        lambda v: Tensor(v) if isinstance(v, jax.Array) else v, x
    )


def sharded_fn(fn, mesh: Optional[Mesh] = None, in_specs=None, out_specs=None,
               axes=None, check_vma=False):
    """Wrap a Tensor-level function for SPMD execution over `mesh`.

    fn sees per-shard Tensors; collectives from distributed.collective bind to
    the mesh axes listed in `axes` (default: all mesh axis names).
    """

    def wrapper(*args):
        m = mesh or get_mesh()
        assert m is not None, "no device mesh set (distributed.set_mesh / fleet.init)"
        bound_axes = tuple(axes) if axes is not None else tuple(m.axis_names)

        def inner(*vals):
            with axis_context(*bound_axes):
                out = fn(*_to_tensors(vals))
            return _to_vals(out)

        smapped = shard_map(
            inner, mesh=m,
            in_specs=in_specs if in_specs is not None
            else PartitionSpec(),
            out_specs=out_specs if out_specs is not None
            else PartitionSpec(),
            check_vma=check_vma,
        )
        return _to_tensors(smapped(*_to_vals(args)))

    return wrapper


def shard_tensor_to(value, mesh: Mesh, spec: PartitionSpec):
    """device_put with a NamedSharding (DistTensor construction analog)."""
    v = value._value if isinstance(value, Tensor) else value
    out = jax.device_put(v, NamedSharding(mesh, spec))
    if isinstance(value, Tensor):
        value._value = out
        return value
    return Tensor(out)
