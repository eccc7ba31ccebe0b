"""Collective communication API.

Reference: ProcessGroup virtual API (paddle/fluid/distributed/collective/
process_group.h:53) + python/paddle/distributed/communication/*.

TPU-native (SURVEY.md §5.8): collectives are *compiled program ops* — inside a
shard_map/jit trace over a Mesh they lower to XLA all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute riding ICI. The Group object
carries the mesh axis name(s) (the "communicator"); channel ids are XLA's
problem. Outside any mesh context (single chip eager) they degenerate to
identity, matching the reference's world_size==1 behavior.
"""
from __future__ import annotations

import threading
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..core.tensor import Tensor
from ..ops.registry import register_op, api


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communicator: a set of ranks bound to one or more mesh axis names."""

    def __init__(self, rank, world_size, id=0, ranks=None, axis_name: Optional[str] = None):
        self.rank = rank
        self.nranks = world_size
        self.id = id
        self.ranks = ranks or list(range(world_size))
        self.axis_name = axis_name

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"Group(id={self.id}, n={self.nranks}, axis={self.axis_name})"


_groups = {}
_next_group_id = [1]
_world_group: Optional[Group] = None


def _get_world_group() -> Group:
    global _world_group
    if _world_group is None:
        from .env import get_rank, get_world_size

        _world_group = Group(get_rank(), get_world_size(), 0, axis_name=None)
    return _world_group


def get_group(gid=0) -> Group:
    if gid == 0:
        return _get_world_group()
    return _groups[gid]


def new_group(ranks=None, backend=None, timeout=None, axis_name=None) -> Group:
    from .env import get_rank

    gid = _next_group_id[0]
    _next_group_id[0] += 1
    if ranks is not None:
        ranks = list(ranks)
    elif axis_name is not None:
        # size from the mesh axis the group binds to (single-controller: the
        # "ranks" of an axis group are positions along that mesh axis)
        from .mesh import get_mesh

        mesh = get_mesh()
        n = mesh.shape[axis_name] if mesh is not None and axis_name in mesh.axis_names else _get_world_group().nranks
        ranks = list(range(n))
    else:
        ranks = list(range(_get_world_group().nranks))
    g = Group(get_rank(), len(ranks), gid, ranks, axis_name=axis_name)
    _groups[gid] = g
    return g


# --- mesh-axis context: set while tracing inside shard_map -------------------
class _AxisCtx(threading.local):
    def __init__(self):
        self.axes: List[str] = []


_axis_ctx = _AxisCtx()


class axis_context:
    """Marks that the enclosed trace runs under shard_map with `axes` bound.
    Used by the sharded executor (distributed/sharded.py) and tests."""

    def __init__(self, *axes):
        self.axes = [a for a in axes if a]

    def __enter__(self):
        _axis_ctx.axes.extend(self.axes)
        return self

    def __exit__(self, *exc):
        for _ in self.axes:
            _axis_ctx.axes.pop()
        return False


def _bound_axis(group: Optional[Group]) -> Optional[str]:
    """Resolve the mesh axis this collective should use, if we're inside a
    shard_map trace that bound it."""
    if group is not None and group.axis_name and group.axis_name in _axis_ctx.axes:
        return group.axis_name
    if group is None and _axis_ctx.axes:
        return _axis_ctx.axes[-1]
    return None


def _axis_size(axis_name: str, group: Optional[Group]) -> int:
    """Size of a bound mesh axis, resolved INSIDE the trace (the binding mesh
    may differ from the global one, and groups may predate the mesh)."""
    try:
        return int(jax.lax.axis_size(axis_name))
    except NameError:   # axis not bound by the enclosing trace
        pass
    from .mesh import get_mesh

    mesh = get_mesh()
    if mesh is not None and axis_name in mesh.axis_names:
        return mesh.shape[axis_name]
    return group.nranks if group is not None else 1


def _resolve_axis_rank(group: Optional[Group], axis_name: str, rank: int) -> int:
    """Map a user-facing rank to a position along the bound axis, validating
    against the *current* axis size rather than the group's creation-time
    snapshot."""
    n = _axis_size(axis_name, group)
    if group is not None and len(group.ranks) == n:
        local = group.get_group_rank(rank)
    else:
        local = rank  # group created under a different mesh: ranks ARE positions
    if not (0 <= local < n):
        ranks = group.ranks if group is not None else list(range(n))
        raise ValueError(f"rank {rank} is not in group ranks {ranks} (axis size {n})")
    return local


def _val(x):
    return x._value if isinstance(x, Tensor) else x


def _wrap(v, like: Optional[Tensor] = None):
    t = Tensor(v)
    if like is not None:
        t.stop_gradient = like.stop_gradient
    return t


# --- collectives -------------------------------------------------------------
def all_reduce(tensor: Tensor, op=ReduceOp.SUM, group: Optional[Group] = None, sync_op=True):
    axis = _bound_axis(group)
    if axis is None:
        return tensor  # world of 1 / outside mesh: identity
    v = _val(tensor)
    if op in (ReduceOp.SUM, ReduceOp.AVG):
        out = lax.psum(v, axis)
        if op == ReduceOp.AVG:
            out = out / lax.psum(jnp.ones((), v.dtype), axis)
    elif op == ReduceOp.MAX:
        out = lax.pmax(v, axis)
    elif op == ReduceOp.MIN:
        out = lax.pmin(v, axis)
    elif op == ReduceOp.PROD:
        # sign/zero-safe product: magnitude via log-sum, sign via parity count
        mag = jnp.exp(lax.psum(jnp.log(jnp.maximum(jnp.abs(v), 1e-300)), axis))
        neg_parity = lax.psum((v < 0).astype(v.dtype), axis) % 2
        has_zero = lax.pmax((v == 0).astype(v.dtype), axis)
        out = jnp.where(has_zero > 0, 0.0, mag * (1.0 - 2.0 * neg_parity)).astype(v.dtype)
    else:
        raise ValueError(f"unknown reduce op {op}")
    tensor._value = out
    return tensor


def all_gather(tensor_list: Optional[list], tensor: Tensor, group: Optional[Group] = None, sync_op=True, axis=0):
    bound = _bound_axis(group)
    if bound is None:
        if tensor_list is not None:
            tensor_list.append(tensor)
            return tensor_list
        return tensor
    v = _val(tensor)
    out = lax.all_gather(v, bound, axis=0, tiled=False)
    if tensor_list is not None:
        n = out.shape[0]
        for i in range(n):
            tensor_list.append(_wrap(out[i], tensor))
        return tensor_list
    return _wrap(out, tensor)


def all_gather_concat(tensor: Tensor, axis=0, group: Optional[Group] = None):
    """all_gather + concat along `axis` (tiled) — the SP/TP building block."""
    bound = _bound_axis(group)
    if bound is None:
        return tensor
    out = lax.all_gather(_val(tensor), bound, axis=axis, tiled=True)
    return _wrap(out, tensor)


def reduce_scatter(tensor: Tensor, op=ReduceOp.SUM, group: Optional[Group] = None, sync_op=True, axis=0):
    bound = _bound_axis(group)
    if bound is None:
        return tensor
    out = lax.psum_scatter(_val(tensor), bound, scatter_dimension=axis, tiled=True)
    return _wrap(out, tensor)


def broadcast(tensor: Tensor, src=0, group: Optional[Group] = None, sync_op=True):
    bound = _bound_axis(group)
    if bound is None:
        return tensor
    v = _val(tensor)
    src_local = _resolve_axis_rank(group, bound, src)
    idx = lax.axis_index(bound)
    masked = jnp.where(idx == src_local, v, jnp.zeros_like(v))
    tensor._value = lax.psum(masked, bound)
    return tensor


def reduce(tensor: Tensor, dst=0, op=ReduceOp.SUM, group: Optional[Group] = None, sync_op=True):
    # On TPU a reduce is an all-reduce (result replicated; dst semantics kept at API level).
    return all_reduce(tensor, op, group, sync_op)


def all_to_all(out_tensor_list, in_tensor_list, group: Optional[Group] = None, sync_op=True):
    bound = _bound_axis(group)
    if bound is None:
        out_tensor_list.extend(in_tensor_list)
        return out_tensor_list
    stacked = jnp.stack([_val(t) for t in in_tensor_list], axis=0)
    out = lax.all_to_all(stacked, bound, split_axis=0, concat_axis=0, tiled=False)
    for i in range(out.shape[0]):
        out_tensor_list.append(Tensor(out[i]))
    return out_tensor_list


alltoall = all_to_all  # reference exposes both spellings


def gather(tensor: Tensor, gather_list: Optional[list] = None, dst=0,
           group: Optional[Group] = None, sync_op=True):
    """Reference communication/gather: dst receives the per-rank list. In
    single-controller SPMD the gathered list is materialized on every rank
    (an all-gather — XLA has no rooted gather on ICI); dst semantics are
    preserved at the API level."""
    return all_gather(gather_list if gather_list is not None else [],
                      tensor, group, sync_op)


def alltoall_single(tensor: Tensor, group: Optional[Group] = None, split_axis=0, concat_axis=0):
    """Single-tensor all-to-all (the EP/Ulysses building block)."""
    bound = _bound_axis(group)
    if bound is None:
        return tensor
    out = lax.all_to_all(_val(tensor), bound, split_axis=split_axis, concat_axis=concat_axis, tiled=True)
    return _wrap(out, tensor)


def collective_permute(tensor: Tensor, perm: Sequence[tuple], group: Optional[Group] = None):
    """Ring shift over ICI neighbors (reference analog: p2p send/recv pairs in
    PP; here one XLA collective-permute)."""
    bound = _bound_axis(group)
    if bound is None:
        return tensor
    out = lax.ppermute(_val(tensor), bound, list(perm))
    return _wrap(out, tensor)


def scatter(tensor: Tensor, tensor_list=None, src=0, group: Optional[Group] = None, sync_op=True):
    bound = _bound_axis(group)
    if bound is None:
        return tensor
    stacked = jnp.stack([_val(t) for t in tensor_list], axis=0) if tensor_list else _val(tensor)
    idx = lax.axis_index(bound)
    out = jnp.take(stacked, idx, axis=0)
    tensor._value = out
    return tensor


def barrier(group: Optional[Group] = None):
    bound = _bound_axis(group)
    if bound is None:
        return
    lax.psum(jnp.ones(()), bound)


def get_rank(group=None):
    from .env import get_rank as _gr

    return _gr()


def get_world_size(group=None):
    from .env import get_world_size as _gw

    return _gw()


# --- p2p: send/recv lower to collective-permute edges ------------------------
#
# Reference: ProcessGroup::Send/Recv (process_group.h:53) and the PP p2p layer
# (fleet/meta_parallel/pp_utils/p2p_communication.py batched isend/irecv).
#
# Single-controller SPMD semantics: the program is uniform across ranks, so a
# matched send(dst=d) + recv(src=s) pair *declares one edge s->d* of a
# collective-permute; batch_isend_irecv collects many edges into ONE ppermute
# (the analog of the reference's ncclGroupStart/End batching). Ranks that are
# not the destination of any edge receive zeros (in the reference they simply
# would not call recv).
class _P2PState(threading.local):
    def __init__(self):
        self.pending = []  # list of (tensor_value, dst)


_p2p_state = _P2PState()


class P2POp:
    """One half of a p2p edge (reference: distributed.P2POp)."""

    def __init__(self, op, tensor, peer, group=None):
        self.op = op  # the send or recv function below (isend/irecv aliases ok)
        self.tensor = tensor
        self.peer = peer
        self.group = group


def send(tensor, dst=0, group=None, sync_op=True):
    """Queue this tensor for the next matching recv (the pair forms one
    ppermute edge). Outside a mesh trace this is an identity no-op."""
    if _bound_axis(group) is None:
        return tensor
    _p2p_state.pending.append((_val(tensor), dst))
    return tensor


def recv(tensor, src=0, group=None, sync_op=True):
    """Complete a send/recv pair: performs ppermute over the bound axis with
    the single edge (src -> dst-of-matching-send). The received value is
    written into `tensor` (zeros on ranks outside the edge)."""
    bound = _bound_axis(group)
    if bound is None:
        return tensor
    if not _p2p_state.pending:
        raise RuntimeError(
            "recv() without a matching send(): in the single-controller SPMD "
            "model p2p pairs must both appear in the (uniform) program; use "
            "batch_isend_irecv for many edges at once."
        )
    value, dst = _p2p_state.pending.pop(0)
    src_local = _resolve_axis_rank(group, bound, src)
    dst_local = _resolve_axis_rank(group, bound, dst)
    out = lax.ppermute(value, bound, [(src_local, dst_local)])
    tensor._value = out
    return tensor


isend = send
irecv = recv


def batch_isend_irecv(p2p_op_list):
    """Execute a batch of P2POps as ONE collective-permute (reference:
    batch_isend_irecv over grouped NCCL calls). Send/recv ops are paired in
    order; each pair (send dst=d, recv src=s) contributes the edge (s, d).
    Returns the list of recv tensors (filled in place)."""
    sends = [op for op in p2p_op_list if op.op in (send, isend)]
    recvs = [op for op in p2p_op_list if op.op in (recv, irecv)]
    if len(sends) != len(recvs):
        raise ValueError(
            f"batch_isend_irecv needs matched send/recv pairs, got "
            f"{len(sends)} sends / {len(recvs)} recvs")
    group = sends[0].group if sends else None
    bound = _bound_axis(group)
    if bound is None:
        for s_op, r_op in zip(sends, recvs):
            r_op.tensor._value = _val(s_op.tensor)
        return [r.tensor for r in recvs]
    edges = []
    for s_op, r_op in zip(sends, recvs):
        edges.append((
            _resolve_axis_rank(r_op.group, bound, r_op.peer),
            _resolve_axis_rank(s_op.group, bound, s_op.peer),
        ))
    # ppermute needs distinct sources and destinations; batch conflict-free
    # rounds (a pipeline shift pattern is always a single round).
    remaining = list(range(len(edges)))
    while remaining:
        round_ids, srcs, dsts = [], set(), set()
        for i in remaining:
            s, d = edges[i]
            if s not in srcs and d not in dsts:
                round_ids.append(i)
                srcs.add(s)
                dsts.add(d)
        remaining = [i for i in remaining if i not in round_ids]
        by_shape = {}
        for i in round_ids:
            v = _val(sends[i].tensor)
            by_shape.setdefault((v.shape, str(v.dtype)), []).append(i)
        for ids in by_shape.values():
            stacked = jnp.stack([_val(sends[i].tensor) for i in ids], axis=0)
            out = lax.ppermute(stacked, bound, [edges[i] for i in ids])
            for k, i in enumerate(ids):
                recvs[i].tensor._value = out[k]
    return [r.tensor for r in recvs]


# -- megatron-style split helper (reference python/paddle/distributed/
# collective.py split: partitions a linear/embedding computation across the
# model-parallel group, creating the sharded weight on first use) -----------

_split_layer_cache: dict = {}


def split(x, size, operation="linear", axis=0, num_partitions=None,
          gather_out=True, weight_attr=None, bias_attr=None, name=None):
    """Distributed fc/embedding over the model-parallel axis. `size` is the
    FULL (in, out) shape (or (vocab, embed) for embedding); the sharded
    layer is created once per call-site `name` and cached, mirroring the
    reference's parameter creation inside split()."""
    from .fleet.mp_layers import (ColumnParallelLinear, RowParallelLinear,
                                  VocabParallelEmbedding)

    key = name or f"dist_split_{operation}_{axis}_{tuple(size)}"
    layer = _split_layer_cache.get(key)
    if layer is None:
        if operation == "embedding":
            layer = VocabParallelEmbedding(int(size[0]), int(size[1]))
        elif operation == "linear" and axis == 0:
            # weight rows (input dim) partitioned -> row-parallel
            layer = RowParallelLinear(int(size[0]), int(size[1]),
                                      input_is_parallel=False,
                                      has_bias=bias_attr is not False)
        elif operation == "linear" and axis == 1:
            layer = ColumnParallelLinear(int(size[0]), int(size[1]),
                                         gather_output=gather_out,
                                         has_bias=bias_attr is not False)
        else:
            raise ValueError(
                f"split: unsupported operation={operation!r} axis={axis}")
        _split_layer_cache[key] = layer
    return layer(x)
