"""Ragged paged attention (decode) as a Pallas TPU kernel.

Reference analog: the paged attention of vLLM-style serving stacks and the
TPU ragged-paged-attention line of work (PAPERS.md: "Ragged Paged
Attention: A High-Performance and Flexible LLM Inference Kernel for TPU").
The serving engine (paddle_tpu/serving/) keeps every sequence's KV in
fixed-size token blocks scattered across one preallocated pool; this kernel
computes one decode step of attention STRAIGHT from

    q            [slots, q_heads, d]        one query token per slot
    k/v_pages    [num_blocks, kv_heads, block_size, d]   (head-major: one
                 page of all K/V heads is one contiguous run of the pool)
    block_tables [slots, max_blocks]  int32 page ids per slot (0 = null)
    context_lens [slots]              int32 valid tokens incl. current

without materializing contiguous per-sequence caches — the "ragged" part:
every slot attends over its own length, and a page past it is neither
fetched nor computed.

The pool's layout is a contract with its writers, stated in one place
(ops/kernels/nn_ops.py: paged_cached_attention's docstring) and held by
tests/test_tpu_compile.py: pages are [num_blocks, kv_heads, block_size, d]
row-major; every writer (the decode step's append, the engine's scatter,
batched prefill and copy-on-write admit) indexes leading dimensions only,
so no program relayouts the pool around these kernels.

The decode kernel (`paged_decode`): ONE GRID STEP A SLOT, the block table
and context lens as scalar-prefetch operands (SMEM), the pool left in HBM
(memory_space ANY). Inside the step a loop over the slot's LIVE pages
(`live_pages`: ceil(context / block) of them for a full layer, the window's
blocks wrapped onto the slot's ring for a window layer), `pages_per_fetch`
pages at a time: each page is one copy of K and one of V
(pltpu.make_async_copy) into one of two VMEM buffers laid out
[kv_heads, fetch * block_size, d], and the next fetch (after a slot's last,
the next slot's first) is in flight under the current one's products. Both
products are batched over the K/V heads on the MXU: q.k takes q and K in
the pool's dtype (bf16 x bf16 products are exact in float32, the
accumulator's type); scores, the online softmax's running max and sum, the
probabilities and the accumulator are float32, and p.v takes V widened to
float32. What bounds its time: the bytes of the live keys (at the serve
cells' shapes and contexts it reads them at 0.67-0.89 of the chip's HBM
bandwidth, PERF.md section 6, PR 31) and about a microsecond a slot of loop
set-up, which is what an idle slot (null table, context 1) costs. The
table's width costs nothing. How many pages a fetch is a constant of the
shapes the kernel is traced with (block size, heads, d, dtype): some
hundreds of KiB of K and of V in flight, at most `_FETCH_PAGES` copies. There is no split
of a context across grid steps: a v5e has one TensorCore and the page loop
is inside the step (the former kernel's `kv_splits` bought nothing on the
chip: 4.66 ms a layer at 1, 2, 4 and 8).

A latent layer (`latent_decode`) is the same kernel over ONE pool: a page
row is a token's compressed latent and rotary key, every query head reads
it (one K/V "head"), scores go over the whole row and the weighted sum over
its first `v_dim` values, so a page is fetched once. p goes into p.v in the
pool's dtype there: 20 heads share each key, and a float32 product would
bound the step where the bytes should.

The speculative verify kernel (`paged_verify`, `_verify_kernel`) is the
older design, a grid step a (slot, K/V head, table entry): no configuration
of the benchmark turns it on (ROADMAP S11).

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
# One fetch of K (and one of V) aims at this many bytes in flight: several
# pages of all K/V heads, each page one contiguous run of the pool.
_FETCH_BYTES = 512 * 1024
# ... and never at more pages than this: a page is a copy to start and to
# wait for, and the loop over a fetch's pages is unrolled.
_FETCH_PAGES = 8


def live_pages(context_lens, block_size, window=None):
    """(first, pages): the logical blocks a slot's decode visits at context
    `context_lens` (the current token counted): `pages` blocks from `first`
    on. A full layer visits blocks 0 .. ceil(cl / block) - 1; a window
    layer the blocks that hold keys cl - window .. cl - 1 (which the ring
    holds at entries `block % ring`). The kernel's loop bounds (a traced
    scalar) and the engine's serving_*_keys_total counters (a NumPy array
    of contexts) are both this arithmetic."""
    last = (context_lens - 1) // block_size
    if window is None:
        return last * 0, last + 1
    first = (context_lens - window).clip(0) // block_size
    return first, last - first + 1


def pages_per_fetch(kv_heads, block_size, d, itemsize, table_width):
    """Pages of all K/V heads that one fetch brings from the pool: a
    constant of the shapes the kernel is traced with."""
    page_bytes = kv_heads * block_size * d * itemsize
    return max(1, min(_FETCH_BYTES // page_bytes, _FETCH_PAGES, table_width))


def _decode_kernel(bt_ref, cl_ref, q_ref, k_hbm, *rest, block_size, fetch,
                   scale, window, v_dim=None):
    # scalar prefetch: bt_ref [slots, width], cl_ref [slots] (SMEM)
    # q_ref, o_ref [hkv, g, d] (this slot); k_hbm, v_hbm: the whole pool,
    # left in HBM; k_buf, v_buf [2, hkv, fetch * block_size, d]: two
    # buffers of `fetch` pages, head-major so that a head's keys are rows;
    # sem [2 (K, V), 2 (buffer)]; parity [1] (SMEM): the buffer that holds
    # this slot's first fetch, which the step before started.
    # A latent layer (`v_dim`): one pool and one buffer, no v_hbm and no
    # v_buf; the values are the first v_dim columns of the keys' rows, so a
    # page is fetched once, and o_ref is [1, g, v_dim].
    if v_dim is None:
        v_hbm, o_ref, k_buf, v_buf, sem, parity = rest
        pools = ((k_hbm, k_buf), (v_hbm, v_buf))
    else:
        o_ref, k_buf, sem, parity = rest
        v_buf = k_buf
        pools = ((k_hbm, k_buf),)
    i = pl.program_id(0)
    width = bt_ref.shape[1]
    bs = block_size
    hkv, g, d = q_ref.shape
    keys = fetch * bs

    def span(slot):
        """(context, first logical block, pages, fetches) of a slot. A
        context is at least the current token and at most what the slot's
        table holds, whatever the caller hands in."""
        cl = jnp.clip(cl_ref[slot], 1, None if window is not None
                      else width * bs)
        first, pages = live_pages(cl, bs, window)
        return cl, first, pages, (pages + fetch - 1) // fetch

    def copies(slot, f, buf):
        """(is the page live, its copy from each pool: K and V, or the one
        latent) for each page of a slot's fetch f. A dead page (past the
        context) is neither looked up in the table nor fetched; a wait needs
        the shapes alone."""
        _, first, pages, _ = span(slot)
        out = []
        for p in range(fetch):
            blk = f * fetch + p
            live = blk < pages
            entry = (first + blk) % width if window is not None else blk
            page = bt_ref[slot, jnp.where(live, entry, 0)]
            rows = pl.ds(p * bs, bs)
            out.append((live, [
                pltpu.make_async_copy(hbm.at[page], vmem.at[buf, :, rows, :],
                                      sem.at[j, buf])
                for j, (hbm, vmem) in enumerate(pools)]))
        return out

    def start(slot, f, buf):
        for live, page_copies in copies(slot, f, buf):
            @pl.when(live)
            def _():
                for c in page_copies:
                    c.start()

    @pl.when(i == 0)
    def _():
        parity[0] = 0
        start(0, 0, 0)

    cl, first, pages, n_fetch = span(i)
    buf0 = parity[0]
    q = q_ref[...]

    def body(f, carry):
        m_prev, l_prev, acc = carry
        buf = (buf0 + f) % 2
        last = f == n_fetch - 1

        # the next fetch flies under this one's products: this slot's, or
        # after its last the next slot's first
        @pl.when(jnp.logical_not(last))
        def _():
            start(i, f + 1, 1 - buf)

        @pl.when(jnp.logical_and(last, i + 1 < pl.num_programs(0)))
        def _():
            start(i + 1, 0, 1 - buf)

        for live, page_copies in copies(i, f, buf):
            @pl.when(live)
            def _():
                for c in page_copies:
                    c.wait()

        base = (first + f * fetch) * bs

        @pl.when(last)
        def _():
            # rows past the context hold what the buffer held before, or
            # the page's stale tail. p is 0 there, and 0 * NaN is not.
            row = base + jax.lax.broadcasted_iota(jnp.int32, (keys, d), 0)
            v_buf[buf] = jnp.where((row < cl)[None], v_buf[buf], 0)

        sc = jax.lax.dot_general(
            q, k_buf[buf], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale     # [hkv, g, keys]
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, keys), 2)
        live = pos < cl
        if window is not None:
            live = jnp.logical_and(live, pos >= cl - window)
        sc = jnp.where(live, sc, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # every fetch holds a live key, so m_new is finite and a masked
        # score's exp is exactly 0
        p = jnp.exp(sc - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        if v_dim is None:
            v = v_buf[buf].astype(jnp.float32)
        else:
            # 20 heads a key: the products, not the bytes, would bound a
            # float32 p.v, so p goes to the pool's dtype (sums in float32)
            v = v_buf[buf][:, :, :v_dim]
            p = p.astype(v.dtype)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)              # [hkv, g, d]
        return m_new, l_new, acc

    _, l, acc = jax.lax.fori_loop(
        0, n_fetch, body,
        (jnp.full((hkv, g, 1), NEG_INF, jnp.float32),
         jnp.zeros((hkv, g, 1), jnp.float32),
         jnp.zeros((hkv, g, v_dim or d), jnp.float32)))
    parity[0] = (buf0 + n_fetch) % 2
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def window_pages(window, block_size, table_width):
    """The most pages a window layer's decode visits a slot: the window's
    blocks and one more for a window that starts inside a block, never more
    than the slot's ring holds."""
    return min(table_width, -(-window // block_size) + 1)


# ------------------------------------------------------- the decode entry
# jitted so that a model's layers, which call it with the same shapes, are
# traced and lowered once a program: tracing the kernel is a few tenths of a
# second, 24 of which were set-up time of every serve run
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "window"))
def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    scale=None, interpret=False, window=None):
    """One decode step of ragged paged attention (see module docstring).
    q: [slots, q_heads, d]; returns [slots, q_heads, d]. `window`: a window
    layer, whose table row is the slot's ring of blocks."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    slots, hq, d = q.shape
    _, hkv, bs, _ = k_pages.shape
    g = hq // hkv
    width = block_tables.shape[1]
    fetch = pages_per_fetch(hkv, bs, d, k_pages.dtype.itemsize,
                            width if window is None
                            else window_pages(window, bs, width))
    # the products take their operands in the pool's dtype
    qr = q.reshape(slots, hkv, g, d).astype(k_pages.dtype)
    slot_block = pl.BlockSpec((None, hkv, g, d),
                              lambda i, bt, cl: (i, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots,),
        in_specs=[slot_block,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=slot_block,
        scratch_shapes=[
            pltpu.VMEM((2, hkv, fetch * bs, d), k_pages.dtype),
            pltpu.VMEM((2, hkv, fetch * bs, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=bs, fetch=fetch,
                          scale=scale, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, hkv, g, d), q.dtype),
        name="paged_decode",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32), qr,
      k_pages, v_pages)
    return out.reshape(slots, hq, d)


@functools.partial(jax.jit, static_argnames=("v_dim", "scale", "interpret"))
def latent_decode(q, pages, block_tables, context_lens, *, v_dim, scale,
                  interpret=False):
    """One decode step of absorbed latent attention over paged latents: the
    decode kernel above with one pool. q [slots, heads, w]: a head's
    absorbed query; pages [num_blocks, 1, block_size, w]: a token's latent
    and rotary key in one row. Every head reads the one latent: scores over
    all w values, the weighted sum over the first v_dim of the same rows,
    which are fetched once. Returns [slots, heads, v_dim]."""
    slots, heads, w = q.shape
    _, _, bs, _ = pages.shape
    fetch = pages_per_fetch(1, bs, w, pages.dtype.itemsize,
                            block_tables.shape[1])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots,),
        in_specs=[pl.BlockSpec((None, 1, heads, w),
                               lambda i, bt, cl: (i, 0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, 1, heads, v_dim),
                               lambda i, bt, cl: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, 1, fetch * bs, w), pages.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=bs, fetch=fetch,
                          scale=scale, window=None, v_dim=v_dim),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, 1, heads, v_dim), q.dtype),
        name="latent_decode",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      q[:, None].astype(pages.dtype), pages)
    return out[:, 0]


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


# --------------------------------------------------------------- shape gate
def supports(q_shape, k_pages_shape) -> bool:
    """Shape gate for the K/V kernels (`paged_decode`, `paged_verify`): a
    head size over 256 or query heads that K/V heads do not divide fall to
    the XLA gather composition. A latent layer does not come here: its one
    576-wide array goes through `latent_decode`, whatever its width."""
    slots, hq, d = q_shape
    hkv = k_pages_shape[1]
    return d <= 256 and hkv >= 1 and hq % hkv == 0
