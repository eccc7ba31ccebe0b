"""Device-side paged KV pool + the cache view the models consume.

The pool is ONE preallocated array pair per layer:

    k_pages, v_pages : [num_blocks, kv_heads, block_size, head_dim]

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

    __slots__ = ("k_pages", "v_pages", "block_table", "seq_lens")

    def __init__(self, k_pages, v_pages, block_table, seq_lens):
        self.k_pages = k_pages
        self.v_pages = v_pages
        self.block_table = block_table
        self.seq_lens = seq_lens


class PagedKVPool:
    """Owns the per-layer page arrays. Holds plain jax arrays (not Tensors):
    the compiled decode step takes and returns them as donated buffers."""

    def __init__(self, num_blocks: int, block_size: int, num_layers: int,
                 num_kv_heads: int, head_dim: int, dtype=jnp.float32):
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.num_layers = int(num_layers)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.dtype = dtype
        shape = (self.num_blocks, self.num_kv_heads, self.block_size,
                 self.head_dim)
        self.layers: List[Tuple[jax.Array, jax.Array]] = [
            (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
            for _ in range(self.num_layers)
        ]

    def nbytes(self) -> int:
        k, _ = self.layers[0]
        return 2 * self.num_layers * k.size * k.dtype.itemsize

    def replace(self, new_layers) -> None:
        """Swap in the page arrays a compiled step returned (the old ones
        were donated into it)."""
        self.layers = [(k, v) for k, v in new_layers]


def write_prefix(k_pages, v_pages, k, v, table, *, block_size):
    """Scatter a contiguous KV prefix into its pages.

    k, v: [plen_padded, kv_heads, d] with plen_padded a multiple of
    block_size; table: [plen_padded // block_size] int32 block ids.
    Garbage rows past the real prompt length land in the tail of the last
    block — they are masked by context_lens until the decode steps that
    overwrite them. Used by the engine after chunked prefill (which runs in
    a contiguous workspace); jit-compiled per padded length."""
    return (
        k_pages.at[table].set(to_pages(k, block_size).astype(k_pages.dtype)),
        v_pages.at[table].set(to_pages(v, block_size).astype(v_pages.dtype)))
