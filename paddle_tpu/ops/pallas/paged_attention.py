"""Ragged paged attention (decode) as a Pallas TPU kernel.

Reference analog: the paged attention of vLLM-style serving stacks and the
TPU ragged-paged-attention line of work (PAPERS.md: "Ragged Paged
Attention: A High-Performance and Flexible LLM Inference Kernel for TPU").
The serving engine (paddle_tpu/serving/) keeps every sequence's KV in
fixed-size token blocks scattered across one preallocated pool; this kernel
computes one decode step of attention STRAIGHT from

    q            [slots, q_heads, d]        one query token per slot
    k/v_pages    [num_blocks, kv_heads, block_size, d]   (head-major: one
                 (page, kv head) is a contiguous [block_size, d] tile, the
                 shape the TPU lowering needs for the K/V block's last two
                 dims)
    block_tables [slots, max_blocks]  int32 page ids per slot (0 = null)
    context_lens [slots]              int32 valid tokens incl. current

without materializing contiguous per-sequence caches — the "ragged" part:
every slot attends over its own length, fully-masked pages are skipped.

The pool's layout is a contract with its writers, stated in one place
(ops/kernels/nn_ops.py: paged_cached_attention's docstring) and held by
tests/test_tpu_compile.py: pages are [num_blocks, kv_heads, block_size, d]
row-major; every writer (the decode step's append, the engine's scatter,
batched prefill and copy-on-write admit) indexes leading dimensions only,
so no program relayouts the pool around these kernels.

Kernel shape: grid (slots, kv_heads, kv_splits, pages_per_split) with the
block table + context lens as SCALAR-PREFETCH operands, so each grid step's
BlockSpec index_map picks the next physical page to DMA (data-dependent
paging — the whole point of scalar prefetch). Online softmax (m, l, acc)
carried in VMEM scratch across the page loop; the kv_splits dimension is
flash-decoding-style split-K over the context: each split reduces its page
range to a partial (acc, m, l) and an XLA epilogue combines splits by
logsumexp weighting. kv_splits is the block-autotuned knob (core/autotune):
1 split minimizes combine overhead, more splits expose parallelism when
slots*kv_heads is small relative to the context length.

GQA layout convention matches cached_multihead_attention's jnp.repeat: kv
head h serves q heads [h*g, (h+1)*g), g = q_heads // kv_heads.

Same portability contract as flash_attention.py: interpret=True runs the
identical kernel on CPU (opt-in via FLAGS_pallas_interpret); the XLA
gather composition (paged_attention_xla) is the default CPU fallback.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ------------------------------------------------------------------- kernel
def _first_block(cl, window, block_size):
    """Logical block of the oldest key a window layer's query still sees
    (context cl counts the current token): keys cl - window .. cl - 1."""
    return jnp.maximum(cl - window, 0) // block_size


def _decode_kernel(bt_ref, cl_ref, q_ref, k_ref, v_ref,
                   acc_ref, m_ref, l_ref,
                   acc_s, m_s, l_s, *, block_size, pages_per_split, scale,
                   window=None):
    # scalar prefetch: bt_ref [slots, max_blocks], cl_ref [slots] (SMEM)
    # blocks: q_ref [g, d]; k_ref/v_ref [block_size, d] (one physical page,
    # this kv head); outputs are per-split partials.
    # window: the grid's pages are the LOGICAL blocks from the window's
    # first on (the index_map wraps them onto the slot's ring of blocks),
    # and keys older than the window are masked by absolute position.
    i = pl.program_id(0)           # slot
    s = pl.program_id(2)           # split
    j = pl.program_id(3)           # page within split

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    cl = cl_ref[i]
    page_idx = s * pages_per_split + j
    if window is not None:
        page_idx = page_idx + _first_block(cl, window, block_size)

    @pl.when(page_idx * block_size < cl)   # ragged skip: page has live tokens
    def _compute():
        g = q_ref.shape[0]
        q = q_ref[:].astype(jnp.float32) * scale
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [g, block_size]
        pos = page_idx * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (g, block_size), 1)
        live = pos < cl
        if window is not None:
            live = jnp.logical_and(live, pos >= cl - window)
        sc = jnp.where(live, sc, NEG_INF)
        m_prev = m_s[:]                       # [g, 1]
        l_prev = l_s[:]
        m_cur = jnp.max(sc, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(live, jnp.exp(sc - m_new), 0.0)
        m_s[:] = m_new
        l_s[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == pages_per_split - 1)
    def _out():
        acc_ref[:] = acc_s[:]
        m_ref[:] = m_s[:]
        l_ref[:] = l_s[:]


def window_pages(window, block_size, table_width):
    """Pages a window layer's decode visits a slot: the window's blocks and
    one more for a window that starts inside a block, never more than the
    slot's ring holds."""
    return min(table_width, -(-window // block_size) + 1)


def _paged_pallas(q, k_pages, v_pages, block_tables, context_lens, scale,
                  kv_splits, interpret, window=None):
    slots, hq, d = q.shape
    hkv = k_pages.shape[1]
    bs = k_pages.shape[2]
    g = hq // hkv
    max_bps = block_tables.shape[1]
    if window is None:
        pad = (-max_bps) % kv_splits
        if pad:
            # padded entries point at the null page; context_lens masks them
            block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
        nps = (max_bps + pad) // kv_splits

        def page_of(i, s, j, bt, cl):
            return bt[i, s * nps + j]
    else:
        # the table is a ring: logical block b lives in entry b % max_bps
        nps = -(-window_pages(window, bs, max_bps) // kv_splits)

        def page_of(i, s, j, bt, cl):
            return bt[i, (_first_block(cl[i], window, bs) + s * nps + j)
                      % max_bps]
    qr = q.reshape(slots, hkv, g, d)
    bt = block_tables.astype(jnp.int32)
    cl = context_lens.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, hkv, kv_splits, nps),
        in_specs=[
            pl.BlockSpec((None, None, g, d),
                         lambda i, h, s, j, bt, cl: (i, h, 0, 0)),
            pl.BlockSpec((None, None, bs, d),
                         lambda i, h, s, j, bt, cl:
                         (page_of(i, s, j, bt, cl), h, 0, 0)),
            pl.BlockSpec((None, None, bs, d),
                         lambda i, h, s, j, bt, cl:
                         (page_of(i, s, j, bt, cl), h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, None, g, d),
                         lambda i, h, s, j, bt, cl: (i, h, s, 0, 0)),
            pl.BlockSpec((None, None, None, g, 1),
                         lambda i, h, s, j, bt, cl: (i, h, s, 0, 0)),
            pl.BlockSpec((None, None, None, g, 1),
                         lambda i, h, s, j, bt, cl: (i, h, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=bs,
                          pages_per_split=nps, scale=scale, window=window),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((slots, hkv, kv_splits, g, d), jnp.float32),
            jax.ShapeDtypeStruct((slots, hkv, kv_splits, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((slots, hkv, kv_splits, g, 1), jnp.float32),
        ],
        name="paged_decode",
        interpret=interpret,
    )(bt, cl, qr, k_pages, v_pages)

    # flash-decoding combine: logsumexp-weight the per-split partials
    m_g = jnp.max(m, axis=2, keepdims=True)
    w = jnp.exp(m - m_g)                       # empty splits -> weight 0
    num = jnp.sum(acc * w, axis=2)             # [slots, hkv, g, d]
    den = jnp.maximum(jnp.sum(l * w, axis=2), 1e-30)
    return (num / den).astype(q.dtype).reshape(slots, hq, d)


# -------------------------------------------------- multi-query (verify)
def _verify_kernel(bt_ref, cl_ref, q_ref, k_ref, v_ref,
                   acc_ref, m_ref, l_ref,
                   acc_s, m_s, l_s, *, block_size, pages_per_split, scale,
                   sq, g):
    # Speculative-verification variant of _decode_kernel: the q block holds
    # sq query tokens folded into rows ([sq*g, d], row r = query r // g,
    # head r % g) and cl_ref[i] is the BASE context (tokens cached before
    # this window), so query qi attends over pos < cl + qi + 1 — causal
    # within the window, full context before it.
    i = pl.program_id(0)           # slot
    s = pl.program_id(2)           # split
    j = pl.program_id(3)           # page within split

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    page_idx = s * pages_per_split + j
    cl = cl_ref[i]

    @pl.when(page_idx * block_size < cl + sq)   # window tokens count too
    def _compute():
        rows = sq * g
        q = q_ref[:].astype(jnp.float32) * scale
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [rows, block_size]
        pos = page_idx * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_size), 1)
        qi = jax.lax.broadcasted_iota(jnp.int32, (rows, block_size), 0) // g
        live = pos < cl + qi + 1
        sc = jnp.where(live, sc, NEG_INF)
        m_prev = m_s[:]                       # [rows, 1]
        l_prev = l_s[:]
        m_cur = jnp.max(sc, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(live, jnp.exp(sc - m_new), 0.0)
        m_s[:] = m_new
        l_s[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_s[:] = acc_s[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == pages_per_split - 1)
    def _out():
        acc_ref[:] = acc_s[:]
        m_ref[:] = m_s[:]
        l_ref[:] = l_s[:]


def _paged_pallas_multi(q, k_pages, v_pages, block_tables, context_lens,
                        scale, kv_splits, interpret):
    slots, sq, hq, d = q.shape
    hkv = k_pages.shape[1]
    bs = k_pages.shape[2]
    g = hq // hkv
    max_bps = block_tables.shape[1]
    pad = (-max_bps) % kv_splits
    if pad:
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
    nps = (max_bps + pad) // kv_splits
    rows = sq * g
    # fold queries into rows: [slots, hkv, sq*g, d], row r = (qi=r//g, r%g)
    qr = (q.reshape(slots, sq, hkv, g, d)
          .transpose(0, 2, 1, 3, 4).reshape(slots, hkv, rows, d))
    bt = block_tables.astype(jnp.int32)
    cl = context_lens.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, hkv, kv_splits, nps),
        in_specs=[
            pl.BlockSpec((None, None, rows, d),
                         lambda i, h, s, j, bt, cl: (i, h, 0, 0)),
            pl.BlockSpec((None, None, bs, d),
                         lambda i, h, s, j, bt, cl, nps=nps:
                         (bt[i, s * nps + j], h, 0, 0)),
            pl.BlockSpec((None, None, bs, d),
                         lambda i, h, s, j, bt, cl, nps=nps:
                         (bt[i, s * nps + j], h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, None, rows, d),
                         lambda i, h, s, j, bt, cl: (i, h, s, 0, 0)),
            pl.BlockSpec((None, None, None, rows, 1),
                         lambda i, h, s, j, bt, cl: (i, h, s, 0, 0)),
            pl.BlockSpec((None, None, None, rows, 1),
                         lambda i, h, s, j, bt, cl: (i, h, s, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_verify_kernel, block_size=bs,
                          pages_per_split=nps, scale=scale, sq=sq, g=g),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((slots, hkv, kv_splits, rows, d),
                                 jnp.float32),
            jax.ShapeDtypeStruct((slots, hkv, kv_splits, rows, 1),
                                 jnp.float32),
            jax.ShapeDtypeStruct((slots, hkv, kv_splits, rows, 1),
                                 jnp.float32),
        ],
        name="paged_verify",
        interpret=interpret,
    )(bt, cl, qr, k_pages, v_pages)

    m_g = jnp.max(m, axis=2, keepdims=True)
    w = jnp.exp(m - m_g)
    num = jnp.sum(acc * w, axis=2)             # [slots, hkv, rows, d]
    den = jnp.maximum(jnp.sum(l * w, axis=2), 1e-30)
    out = (num / den).astype(q.dtype)
    return (out.reshape(slots, hkv, sq, g, d)
            .transpose(0, 2, 1, 3, 4).reshape(slots, sq, hq, d))


def to_pages(x, block_size):
    """Token-major KV [..., n_tokens, kv_heads, d] (n_tokens a multiple of
    block_size) -> page layout [..., n_blocks, kv_heads, block_size, d]."""
    *lead, n, hkv, d = x.shape
    return jnp.swapaxes(
        x.reshape(*lead, n // block_size, block_size, hkv, d), -3, -2)


def from_pages(pages):
    """Inverse of to_pages: [..., n_blocks, kv_heads, block_size, d] ->
    [..., n_blocks * block_size, kv_heads, d]."""
    *lead, nb, hkv, bs, d = pages.shape
    return jnp.swapaxes(pages, -3, -2).reshape(*lead, nb * bs, hkv, d)


def paged_attention_xla_multi(q, k_pages, v_pages, block_tables,
                              context_lens, scale=None):
    """Dense-gather reference for the multi-query verify window.
    q: [slots, sq, q_heads, d]; context_lens is the BASE context (tokens
    cached before the window) — query i sees pos < context_lens + i + 1."""
    slots, sq, hq, d = q.shape
    hkv = k_pages.shape[1]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    k, v = (from_pages(p[block_tables]) for p in (k_pages, v_pages))
    max_ctx = k.shape[1]
    qg = (q.reshape(slots, sq, hkv, g, d)
          .transpose(0, 2, 1, 3, 4).astype(jnp.float32))  # [b,h,sq,g,d]
    sc = jnp.einsum("bhsgd,bkhd->bhsgk", qg,
                    k.astype(jnp.float32)) * scale
    live = (jnp.arange(max_ctx)[None, None, :]
            < (context_lens.astype(jnp.int32)[:, None, None]
               + jnp.arange(sq)[None, :, None] + 1))  # [slots, sq, max_ctx]
    sc = jnp.where(live[:, None, :, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhsgk,bkhd->bhsgd", p, v.astype(jnp.float32))
    return (out.astype(q.dtype)
            .transpose(0, 2, 1, 3, 4).reshape(slots, sq, hq, d))


def paged_attention_multi(q, k_pages, v_pages, block_tables, context_lens,
                          scale=None, kv_splits=1, interpret=False):
    """Speculative-verification attention: sq query tokens per slot against
    the paged KV pool, causal within the window. q: [slots, sq, q_heads, d];
    context_lens = tokens cached BEFORE the window. Returns the same shape
    as q."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_pallas_multi(q, k_pages, v_pages, block_tables,
                               context_lens, scale, kv_splits, interpret)


# ------------------------------------------------------------- XLA fallback
def paged_attention_xla(q, k_pages, v_pages, block_tables, context_lens,
                        scale=None, window=None):
    """Dense-gather reference: gather each slot's pages into a contiguous
    [max_ctx] view, mask past context_lens, fp32 softmax. The default CPU
    path and the numerics oracle for the kernel tests. With `window` the
    table is a ring (logical block b in entry b % width): an entry's keys
    are masked by the absolute position of the newest block it can hold."""
    slots, hq, d = q.shape
    hkv = k_pages.shape[1]
    bs = k_pages.shape[2]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    k, v = (from_pages(p[block_tables]) for p in (k_pages, v_pages))
    max_ctx = k.shape[1]
    qg = q.reshape(slots, hkv, g, d).astype(jnp.float32)
    sc = jnp.einsum("bhgd,bkhd->bhgk", qg,
                    k.astype(jnp.float32)) * scale
    cl = context_lens.astype(jnp.int32)[:, None]
    if window is None:
        live = jnp.arange(max_ctx)[None, :] < cl        # [slots, max_ctx]
    else:
        width = block_tables.shape[1]
        last = (cl - 1) // bs
        entry = jnp.arange(width, dtype=jnp.int32)[None, :]
        block = last - (last - entry) % width           # newest block there
        pos = (block[:, :, None] * bs
               + jnp.arange(bs, dtype=jnp.int32)[None, None, :]
               ).reshape(slots, max_ctx)
        live = (pos >= 0) & (pos < cl) & (pos >= cl - window)
    sc = jnp.where(live[:, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype).reshape(slots, hq, d)


# ---------------------------------------------------------------- public API
def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    scale=None, kv_splits=1, interpret=False, window=None):
    """One decode step of ragged paged attention (see module docstring).
    q: [slots, q_heads, d]; returns [slots, q_heads, d]. `window`: a window
    layer, whose table row is the slot's ring of blocks."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _paged_pallas(q, k_pages, v_pages, block_tables, context_lens,
                         scale, kv_splits, interpret, window)


def supports(q_shape, k_pages_shape) -> bool:
    """Shape gate for the kernel path (XLA fallback otherwise)."""
    slots, hq, d = q_shape
    hkv = k_pages_shape[1]
    return d <= 256 and hkv >= 1 and hq % hkv == 0


# ---- autotuned entry (split-K over the context is the tunable block) ----
from ...core.autotune import autotune as _autotune  # noqa: E402

_SPLIT_CANDIDATES = [
    {"kv_splits": 1},   # default 1st: no combine overhead
    {"kv_splits": 2},
    {"kv_splits": 4},
    {"kv_splits": 8},
]


@_autotune(_SPLIT_CANDIDATES)
def paged_attention_tuned(q, k_pages, v_pages, block_tables, context_lens,
                          scale=None, interpret=False, window=None, *,
                          kv_splits):
    """paged_attention with the flash-decoding split count chosen by the
    autotune cache when FLAGS_use_autotune is on; otherwise 1 split."""
    if block_tables.shape[1] < kv_splits:
        raise ValueError("more splits than pages")  # tuner skips
    return paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                           scale, kv_splits, interpret, window)
