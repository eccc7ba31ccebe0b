"""Learned sparse latent attention (DeepSeek sparse attention over a NoPE
latent, the sparse layers of GLM-5.3-Flash): the indexer's block scores, the
exact top-k selection, and attention over the selected 4-row groups of the
latent cache.

A layer caches two arrays a position: the latent c_s (after its norm, the
keys' and values' one source) and, pooled over the POOL = 4 positions of a
block, the indexer's key kbar_b = mean(k^I_{4b .. 4b+3}). A query at
position t with indexer queries q^I_j (J heads) and weights w_j scores every
COMPLETE block before its own,

    I_{t,b} = sum_j w_j ReLU(q^I_j . kbar_b),     b < t // POOL,

keeps the TOPK largest (exact: lax.top_k, lower index first on a tie) and
always its own block t // POOL (the tail, whose positions up to t are live;
its pooled key is never scored). With at most TOPK complete blocks before it
a query selects them all: attention is then dense. Attention is the absorbed
form over the selected rows: a head's score is q'_h . c_s, its output the
softmax-weighted sum of the same rows (every head shares the one selection
and the one row).

One Pallas kernel, `dsa_index`: a prefill chunk's scores over the pooled
keys, the ReLU-weighted head sum in VMEM, never a [queries, blocks, heads]
array in HBM; lax.top_k reads its scores. The attention over the selected
groups is XLA's gather: a group is 4 rows of a bf16 page, whose tiles are 16
rows high, and Mosaic's DMA refuses a slice that is not whole tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

POOL = 4
NEG_INF = -1e30
F32 = jnp.float32


# ------------------------------------------------------------------ index
def index_scores_xla(qi, w, kpool, complete):
    """qi [n, J, D] indexer queries; w [n, J] their weights; kpool [n, B, D]
    (each query's own pooled keys: a decode step's slots) or [B, D] (one
    sequence's, for every query of a chunk); complete [n] blocks each query
    may score. Products in the keys' dtype, sums in float32. Returns [n, B]
    float32, -inf where a block is not complete. Each slot's keys are read
    once for all its heads; a chunk's a head at a time, so no [n, B, J]
    array of a chunk exists."""
    qi = qi.astype(kpool.dtype)
    n, b = qi.shape[0], kpool.shape[-2]
    if kpool.ndim == 3:
        s = jnp.einsum("njd,nbd->njb", qi, kpool, preferred_element_type=F32)
        acc = jnp.einsum("nj,njb->nb", w.astype(F32), jax.nn.relu(s))
    else:
        def head(acc, xs):
            q, wj = xs
            s = jnp.einsum("nd,bd->nb", q, kpool, preferred_element_type=F32)
            return acc + wj[:, None] * jax.nn.relu(s), None

        acc, _ = lax.scan(head, jnp.zeros((n, b), F32),
                          (jnp.moveaxis(qi, 1, 0),
                           jnp.moveaxis(w.astype(F32), 1, 0)))
    live = jnp.arange(b)[None, :] < complete[:, None]
    return jnp.where(live, acc, -jnp.inf)


def _index_kernel(q_ref, w_ref, k_ref, o_ref, *, heads, width):
    k = k_ref[0]                                        # [blocks, D]
    acc = jnp.zeros(o_ref.shape[1:], F32)
    for j in range(heads):
        s = lax.dot_general(q_ref[0, :, j * width:(j + 1) * width], k,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=F32)
        acc = acc + w_ref[0, :, j:j + 1] * jnp.maximum(s, 0.0)
    o_ref[0] = acc


QB_INDEX = 128
KB_INDEX = 2048


def dsa_index_supports(n: int, b: int, heads: int, d: int) -> bool:
    return n % QB_INDEX == 0 and b % KB_INDEX == 0 and d % 128 == 0 \
        and heads <= 128


def dsa_index(qi, w, kpool, complete, *, interpret=False):
    """index_scores_xla for ONE sequence's pooled keys [B, D] as a kernel:
    a grid over (query blocks of 128, key blocks of 2,048), the heads' ReLU
    sum in VMEM."""
    n, heads, d = qi.shape
    b = kpool.shape[0]
    q2 = qi.reshape(1, n, heads * d).astype(kpool.dtype)
    wp = jnp.pad(w.astype(F32), ((0, 0), (0, 128 - heads)))[None]
    out = pl.pallas_call(
        functools.partial(_index_kernel, heads=heads, width=d),
        grid=(n // QB_INDEX, b // KB_INDEX),
        in_specs=[pl.BlockSpec((1, QB_INDEX, heads * d),
                               lambda i, j: (0, i, 0)),
                  pl.BlockSpec((1, QB_INDEX, 128), lambda i, j: (0, i, 0)),
                  pl.BlockSpec((1, KB_INDEX, d), lambda i, j: (0, j, 0))],
        out_specs=pl.BlockSpec((1, QB_INDEX, KB_INDEX),
                               lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((1, n, b), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="dsa_index",
        interpret=interpret,
    )(q2, wp, kpool[None])[0]
    live = jnp.arange(b)[None, :] < complete[:, None]
    return jnp.where(live, out, -jnp.inf)


def index_scores(qi, w, kpool, complete):
    """The kernel on the TPU (and in interpret mode) where the shapes fit,
    the XLA form elsewhere."""
    from . import interpret_mode, pallas_enabled

    if kpool.ndim == 2 and pallas_enabled() and dsa_index_supports(
            qi.shape[0], kpool.shape[0], qi.shape[1], qi.shape[2]):
        return dsa_index(qi, w, kpool, complete, interpret=interpret_mode())
    return index_scores_xla(qi, w, kpool, complete)


def select(scores, topk_blocks: int, positions):
    """The groups each query attends: its top `topk_blocks` complete blocks
    by score and its tail block. Returns (groups [n, topk + 1] int32: block
    ids, the tail last; rows [n, topk + 1, POOL] bool: which of a group's
    rows are live keys)."""
    n, b = scores.shape
    if b < topk_blocks:
        scores = jnp.pad(scores, ((0, 0), (0, topk_blocks - b)),
                         constant_values=-jnp.inf)
    top, idx = lax.top_k(scores, topk_blocks)
    chosen = top > -jnp.inf
    tail = positions // POOL
    groups = jnp.concatenate([jnp.where(chosen, idx, 0), tail[:, None]], -1)
    row = jnp.arange(POOL)[None, :]
    tail_rows = POOL * tail[:, None] + row <= positions[:, None]
    rows = jnp.concatenate(
        [jnp.broadcast_to(chosen[..., None], chosen.shape + (POOL,)),
         tail_rows[:, None, :]], 1)
    return groups.astype(jnp.int32), rows


# --------------------------------------------------------------- attention
def _attend(q, lat, rows, scale):
    """q [n, H, W]; lat [n, G, POOL * W] the gathered groups, a group's
    POOL rows side by side; rows [n, G, POOL] live. A quarter of the lanes
    at a time (lat[..., r W:(r + 1) W] is row r of every group), so the
    groups are read where they lie and never laid out as rows. Returns
    [n, H, W] float32."""
    w = q.shape[-1]
    q = q.astype(lat.dtype)
    part = [lat[..., r * w:(r + 1) * w] for r in range(POOL)]
    sc = jnp.concatenate(
        [jnp.where(rows[:, None, :, r],
                   jnp.einsum("nhw,ngw->nhg", q, x,
                              preferred_element_type=F32) * scale, NEG_INF)
         for r, x in enumerate(part)], axis=-1)          # [n, H, POOL G]
    p = jax.nn.softmax(sc, axis=-1).astype(lat.dtype)
    g = lat.shape[1]
    return sum(jnp.einsum("nhg,ngw->nhw", p[..., r * g:(r + 1) * g], x,
                          preferred_element_type=F32)
               for r, x in enumerate(part))


def sparse_attention(q, groups_of, gid, rows, scale, block=64):
    """q [n, H, W] absorbed queries; groups_of [groups, POOL * W] the cache
    as groups of POOL rows (a group a row); gid [n, G] int32 the groups each
    query reads; rows [n, G, POOL] which of their rows are live keys. The
    groups are gathered for a block of `block` queries at a time, so that a
    block's are all that is held. Returns [n, H, W] float32."""
    n = q.shape[0]
    if n <= block:
        return _attend(q, groups_of[gid], rows, scale)
    pad = (-n) % block
    padq = lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))  # noqa: E731
    xs = tuple(padq(x).reshape((-1, block) + x.shape[1:])
               for x in (q, gid, rows))
    out = lax.map(lambda a: _attend(a[0], groups_of[a[1]], a[2], scale), xs)
    return out.reshape((-1,) + out.shape[2:])[:n]
