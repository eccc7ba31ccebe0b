"""Grouped matmul as a Pallas TPU kernel: the products of a dropless
mixture-of-experts layer over the experts a chip holds.

    lhs          [m, k]        rows sorted by group (expert); rows past the
                               last group's end belong to no group
    rhs          [groups, k, n]  one matrix per group
    group_sizes  [groups] int32  rows of each group, in order
    out          [m, n]        out[r] = lhs[r] @ rhs[group of r]; rows of no
                               group are zero

Reference analog: the grouped GEMM of MegaBlocks-style dropless MoE
(PAPERS.md) and jax's megablox. Written for serving: a decode step has one or
two rows an expert, so one row tile spans many groups and the time is the
reading of each touched group's matrix once; an empty group is never visited
and its matrix never read.

Kernel shape: the work list is every (group, row tile) pair that shares a
row, in row order, at most m/tm + groups - 1 of them; it is computed in XLA
and handed over as scalar prefetch, so each grid step's BlockSpec index_map
picks the group's matrix and the row tile to DMA. Grid (n tiles, work items);
k is not tiled (an expert's k is the hidden or the expert width: a [k, tn]
block fits VMEM). A row tile shared by several groups is visited on
consecutive steps and stays resident: each visit writes only its group's
rows.

`interpret=True` runs the same kernel on the CPU; `grouped_matmul_xla`
(jax.lax.ragged_dot) is the default CPU path and the numerics oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def work_list(group_sizes, m: int, tm: int):
    """(group of each work item, row tile of each work item, row at which
    each group starts [groups + 1], number of work items [1]) for row tiles
    of tm. Items past the count repeat the last real one, so they move no
    data."""
    groups = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    n_items = m // tm + groups - 1
    count = jnp.sum(tiles)
    item = jnp.arange(n_items, dtype=jnp.int32)
    item = jnp.minimum(item, jnp.maximum(count - 1, 0))
    item_end = jnp.cumsum(tiles)
    gid = jnp.searchsorted(item_end, item, side="right").astype(jnp.int32)
    gid = jnp.minimum(gid, groups - 1)
    tid = first[gid] + item - (item_end[gid] - tiles[gid])
    tid = jnp.clip(tid, 0, m // tm - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return gid, tid, offsets, count.reshape(1)


def _kernel(gid_ref, tid_ref, off_ref, cnt_ref, lhs_ref, rhs_ref, out_ref,
            *, tm):
    w = pl.program_id(1)

    @pl.when(w < cnt_ref[0])
    def _item():
        g, t = gid_ref[w], tid_ref[w]
        # a tile's first visit clears it; later visits keep other groups' rows
        first = jnp.logical_or(w == 0, tid_ref[jnp.maximum(w - 1, 0)] != t)
        rows = t * tm + jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
        mine = jnp.logical_and(rows >= off_ref[g], rows < off_ref[g + 1])
        acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                      preferred_element_type=jnp.float32).astype(out_ref.dtype)
        kept = jnp.where(first, jnp.zeros_like(acc), out_ref[...])
        out_ref[...] = jnp.where(mine, acc, kept)


def _pick_tn(k, n, itemsize, budget=4 << 20):
    """Widest n tile, a multiple of 128 dividing n, whose [k, tn] block is
    within the budget (it is double-buffered)."""
    tn = n
    while tn % 256 == 0 and k * tn * itemsize > budget:
        tn //= 2
    return tn


def grouped_matmul(lhs, rhs, group_sizes, *, tm, interpret=False):
    """tm: the row tile; the caller pads lhs to a multiple of it (the
    expert layer takes 32 for a decode step's pairs, 128 for a chunk's)."""
    m, k = lhs.shape
    groups, _, n = rhs.shape
    if m % tm:
        raise ValueError(f"grouped_matmul: {m} rows are not a multiple of "
                         f"the row tile {tm}")
    tn = _pick_tn(k, n, rhs.dtype.itemsize)
    gid, tid, offsets, count = work_list(group_sizes, m, tm)
    n_items = gid.shape[0]
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, n_items),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, w, gid, tid, off, cnt:
                             (tid[w], 0)),
                pl.BlockSpec((None, k, tn), lambda j, w, gid, tid, off, cnt:
                             (gid[w], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, w, gid, tid, off, cnt:
                                   (tid[w], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        name="moe_grouped_matmul",
        interpret=interpret,
    )(gid, tid, offsets, count, lhs, rhs)
    # tiles no group touched were never written
    live = jnp.arange(m, dtype=jnp.int32)[:, None] < offsets[-1]
    return jnp.where(live, out, jnp.zeros_like(out))


def grouped_matmul_xla(lhs, rhs, group_sizes):
    """jax.lax.ragged_dot: rows past the groups' total come out zero."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)
