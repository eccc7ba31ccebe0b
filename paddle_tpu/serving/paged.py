"""Device-side paged KV pool + the cache view the models consume.

The pool holds, per layer, what the layer's cache spec states
(models/generation.LayerCacheSpec): a PAIR of preallocated arrays for a full
or a window layer,

    k_pages, v_pages : [num_blocks, kv_heads, block_size, head_dim]

and ONE for a latent layer,

    latent_pages     : [num_blocks, 1, block_size, head_dim]

two for a latent layer with a pooled index key, by groups of `pool`
positions (a group a row: `write_groups`),

    latent_pages     : [num_blocks, block_size / pool, pool * head_dim]
    index_pages      : [num_blocks, block_size / pool, index_dim]

and, for a state layer, arrays of a fixed size a SLOT and not a page
([slots, ...]: a recurrent state and a convolution's tail), which the decode
step reads and writes at the slot's row and the engine sets at admission
from the prefill workspace,

with the layer's own heads and head size, and as many blocks as its CACHE
GROUP has: full and latent layers share the engine's block table and its
num_blocks, the layers of one window a ring of blocks a slot
(blocks.WindowRings). Every program that moves pages takes a layer as the
tuple of its arrays and does the same to each.

Head-major inside a page: one (page, kv head) is a contiguous
[block_size, head_dim] tile, which is what the TPU lowering of the paged
attention kernel needs for the last two dims of its K/V block
(ops/pallas/paged_attention.py owns the layout: to_pages / from_pages).
Block ids from blocks.BlockAllocator index the leading dim directly. A
sequence's KV lives in the (non-contiguous) blocks its table names; the
ragged paged attention op (ops/pallas/paged_attention.py) computes straight
from (pages, block_table, context_lens) without ever materializing a
contiguous per-sequence cache.

PagedLayerCache is the per-layer view threaded through the models' existing
`caches=` plumbing: gpt/llama attention layers duck-type on `.block_table`
to pick the paged decode path over the static-ring path. It is constructed
inside the compiled decode step (engine.py), so its fields are Tensors of
traced values.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas.paged_attention import to_pages


class PagedLayerCache:
    """Per-layer paged-KV view: pages + the batch's block tables/lengths.

    seq_lens counts tokens ALREADY in the cache for each slot (the new
    token of the current decode step is written at position seq_lens and
    included in attention by the op)."""

    __slots__ = ("k_pages", "v_pages", "block_table", "seq_lens",
                 "counters")

    def __init__(self, k_pages, v_pages, block_table, seq_lens,
                 counters=None):
        self.k_pages = k_pages              # a latent layer's one array
        self.v_pages = v_pages              # None for a latent layer
        self.block_table = block_table      # the layer's group's table
        self.seq_lens = seq_lens
        # LayerCacheSpec.counters: the layer returns (k, v, counters + its
        # own) and the engine keeps the sum on the device
        self.counters = counters


class PagedKVPool:
    """Owns the per-layer page arrays. Holds plain jax arrays (not Tensors):
    the compiled decode step takes and returns them as donated buffers."""

    def __init__(self, layer_arrays, block_size: int, dtype=jnp.float32):
        """layer_arrays: for each layer in the model's order, the (shape,
        dtype) of each of its arrays (LayerCacheSpec.page_shapes)."""
        self.block_size = int(block_size)
        self.dtype = dtype
        self.layers: List[Tuple[jax.Array, ...]] = [
            tuple(jnp.zeros(shape, dt) for shape, dt in arrays)
            for arrays in layer_arrays]

    def nbytes(self) -> int:
        """Bytes of every array of every layer as the shapes give them (the
        device may pad a last dimension that is no multiple of its lanes)."""
        return sum(a.size * a.dtype.itemsize
                   for arrays in self.layers for a in arrays)

    def replace(self, new_layers) -> None:
        """Swap in the page arrays a compiled step returned (the old ones
        were donated into it)."""
        self.layers = [tuple(arrays) for arrays in new_layers]


def write_prefix(pages, rows, table, *, block_size):
    """Scatter a contiguous prefix into its pages, array by array of one
    layer (K and V, or the one latent).

    rows: for each array [plen_padded, kv_heads, d] with plen_padded a
    multiple of block_size; table: [plen_padded // block_size] int32 block
    ids. Garbage rows past the real prompt length land in the tail of the
    last block — they are masked by context_lens until the decode steps
    that overwrite them. Used by the engine after chunked prefill (which
    runs in a contiguous workspace); jit-compiled per padded length."""
    return tuple(
        p.at[table].set(to_pages(r, block_size).astype(p.dtype))
        for p, r in zip(pages, rows))


def write_groups(pages, rows, table):
    """Scatter a contiguous prefix of a grouped layer (a latent layer with a
    pooled index key: rows of groups of positions, LayerCacheSpec.index_dim)
    into its pages: rows, for each array, [groups, width] with the groups a
    whole number of pages; table [pages] int32 block ids. Indexes leading
    dimensions only, like write_prefix."""
    return tuple(
        p.at[table].set(r.reshape(table.shape[0], p.shape[1],
                                  p.shape[2]).astype(p.dtype))
        for p, r in zip(pages, rows))


def write_ring(pages, rows, ring, last_block, *, block_size):
    """Scatter the END of a contiguous KV prefix into a window layer's ring.

    pages: the layer's (k_pages, v_pages); rows: its (k, v), each
    [plen_padded, kv_heads, d]; ring: [ring_blocks] int32, the slot's
    ring; last_block: int32, the logical block of the prompt's last token.
    Logical blocks last_block - ring_blocks + 1 .. last_block (those that
    exist) go to ring entries block % ring_blocks, where the decode step's
    append and attention expect them; earlier blocks are behind every
    window that will ever be asked for. Indexes leading dimensions only,
    like write_prefix."""
    n = ring.shape[0]
    blocks = last_block - (n - 1) + jnp.arange(n, dtype=jnp.int32)
    src = jnp.clip(blocks, 0, rows[0].shape[0] // block_size - 1)
    # a block before the prompt's first: to the null page
    dst = jnp.where(blocks >= 0, ring[blocks % n], 0)
    return tuple(
        p.at[dst].set(to_pages(r, block_size)[src].astype(p.dtype))
        for p, r in zip(pages, rows))
