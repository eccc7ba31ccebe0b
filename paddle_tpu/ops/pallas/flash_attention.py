"""Flash attention (forward + backward) as Pallas TPU kernels.

Reference behavior: phi/kernels/gpu/flash_attn_kernel.cu (+ flash_attn_grad)
which dynloads the flash-attention CUDA library; Python surface
paddle.nn.functional.scaled_dot_product_attention. Here the kernel is written
for the TPU memory hierarchy instead: Q/K/V blocks staged in VMEM, online
softmax carried in fp32, logsumexp residual saved for a recompute backward.

Layout: inputs are [batch, seq, heads, head_dim] (the reference layout); the
kernel internally processes one (batch*head) slice per grid row.

The three training kernels (flash_fwd, flash_bwd_dq, flash_bwd_dkv):
  * multiply in the dtype they are given: every MXU operand (q, k, v, dO, and
    p and dS as operands of their products) has the dtype of the inputs,
    every product sums in float32, and m, l, lse, delta, the accumulators
    and every exponent are float32. bf16 inputs (amp O1) so take one MXU
    pass a product; float32 inputs keep float32 products. The softmax scale
    goes once on the [tile, d] q (or k) tile and once on dq / dk, never on a
    score tile;
  * build the causal mask only for the score tiles the diagonal crosses:
    tiles wholly under it run a body with no iota / compare / select, tiles
    wholly over it are not visited;
  * walk a grid step in score tiles of block_q x block_k. `_plan` chooses
    the tile and the step for each kernel from the shapes it is traced with
    (sequences, head size, dtype) when the caller names no tile: where one
    head's tiles are few, a grid step is the head's whole sequence and the
    tile loops are unrolled into straight-line code (static bounds, which
    the scheduler overlaps across tiles); longer sequences take larger tiles
    in rolled loops whose bounds follow the step's place. No run tunes
    anything.

The per-row statistics (lse, delta) are stored [bh, 1, sq], rows along the
lanes: a [bh, sq, 1] float32 array is tiled (8, 128) in HBM and so padded
128 times (64 MiB a layer at [8, 1024, 16, .] where 512 KiB are data). The
forward and dq kernels, whose score tiles have queries as rows, turn a
[1, block_q] slice into a column once a q tile; the dkv kernel computes its
score tiles transposed ([block_k, block_q] = k.qT), so that the statistics
are rows as stored and pT.dO and dST.q are plain products with no transposed
operand. delta = rowsum(dO * O) is precomputed once (an XLA fused reduce).

Algorithm (standard online softmax):
  fwd:  for each q tile, stream k/v tiles, carry (m, l, acc); save
        lse = m + log(l) per row.
  bwd:  two kernels — dQ streams K/V per q tile, dK/dV streams Q/dO per
        k tile — both recompute P from Q,K,lse.
"""
from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
_LANES = 128

_NT = (((1,), (1,)), ((), ()))   # a[m, c] . b[n, c]T
_NN = (((1,), (0,)), ((), ()))   # a[m, c] . b[c, n]


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying `like`'s varying-manual-axes type, so the
    kernels compose with shard_map(check_vma=True) — e.g. as ring-attention
    chunks over the 'sep' axis."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _to_row(col):
    """[n, 1] -> [1, n] (through a lane-wide tile: the TPU transposes
    those)."""
    return jnp.broadcast_to(col, (col.shape[0], _LANES)).T[:1, :]


def _to_col(row):
    """[1, n] -> [n, 1]."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T[:, :1]


def _seen(q0, k0, shape, q_axis):
    """Causal visibility of a score tile whose first query is q0 and first
    key k0; queries run along `q_axis` of the tile."""
    q_ids = q0 + lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_ids = k0 + lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return q_ids >= k_ids


def _scaled(x, scale, dtype):
    return (x.astype(jnp.float32) * scale).astype(dtype)


def _static(*xs):
    return all(isinstance(x, int) for x in xs)


def _div(a, b):
    return a // b if _static(a) else lax.div(a, b)


def _min(a, b):
    return min(a, b) if _static(a, b) else jnp.minimum(a, b)


def _k_tile_ranges(q0, block_q, block_k, nk, causal):
    """(n_clear, n_live) for the q tile starting at row q0: k tiles
    [0, n_clear) lie wholly under the diagonal and need no mask, tiles
    [n_clear, n_live) are crossed by it, the rest are never seen."""
    if not causal:
        return nk, nk
    n_live = _min(_div(q0 + block_q + block_k - 1, block_k), nk)
    return _min(_div(q0 + 1, block_k), nk), n_live


def _tile_loops(lo, mid, hi, body, masked_first, init):
    """body(j, carry, masked) over [lo, hi), masked on one side of mid.
    Bounds that are Python ints (an unrolled grid step, `_plan`) give
    straight-line code, which the TPU's scheduler overlaps across tiles: the
    same tiles in a rolled loop take three times as long (PERF.md, PR 33)."""
    first = functools.partial(body, masked=masked_first)
    second = functools.partial(body, masked=not masked_first)
    if not _static(lo, mid, hi):
        return lax.fori_loop(mid, hi, second,
                             lax.fori_loop(lo, mid, first, init))
    carry = init
    for j in range(lo, hi):
        carry = (first if j < mid else second)(j, carry)
    return carry


# Score tiles (block_q, block_k), in order of preference, read off chip runs
# of each kernel alone at [8, 1024, 16, 64] and [8, 1024, 16, 128] bf16
# (PERF.md section 6, PR 33): all three kernels want the same. Constants, so
# nothing is timed at warm-up.
_TILES_UNROLLED = ((256, 256), (512, 512), (128, 128))
_TILES_ROLLED = ((512, 512), (256, 256), (128, 128))
_UNROLLED_TILES = 64          # most score tiles a kernel unrolls for one head
_WHOLE_BYTES = 512 * 1024     # a [s, d] operand an unrolled step holds whole
_STEP_BYTES = 256 * 1024      # one [rows, d] operand of a rolled grid step


def _plan(kernel, sq, sk, d, dtype, block_q=None, block_k=None):
    """(block_q, block_k, rows, unrolled) for `kernel` ("fwd", "dq", "dkv")
    at these shapes. block_q x block_k is the score tile (the caller's, or
    chosen here); `rows` what a grid step covers of the sequence the kernel's
    grid runs over (queries; keys for dkv). Where one head's tiles are few
    and its operands small, a grid step is the whole sequence and its tile
    loops are unrolled; else it is as many tiles as _STEP_BYTES holds, in
    rolled loops whose bounds follow the step's place in the sequence."""
    size = jnp.dtype(dtype).itemsize * d

    def unrolls(bq, bk):
        # tiles narrower than a lane tile (short ring chunks) have only this
        # form on the chip: a step's statistics are sliced along the lanes,
        # which a rolled loop can do only at multiples of 128
        few = (sq // bq) * (sk // bk) <= _UNROLLED_TILES
        return ((few or bq % _LANES or bk % _LANES)
                and max(sq, sk) * size <= _WHOLE_BYTES)

    def divides(tile):
        return sq % tile[0] == 0 and sk % tile[1] == 0

    if block_q is None:
        block_q, block_k = next(
            itertools.chain(
                (t for t in _TILES_UNROLLED if divides(t) and unrolls(*t)),
                (t for t in _TILES_ROLLED if divides(t))),
            (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K))  # fails the shape gate
    s, block = (sk, block_k) if kernel == "dkv" else (sq, block_q)
    if unrolls(block_q, block_k):
        return block_q, block_k, s, True
    if block % _LANES:
        return block_q, block_k, block, False   # interpret mode only
    n = s // block
    most = max(1, _STEP_BYTES // (block * size))
    tiles = max(c for c in range(1, n + 1) if n % c == 0 and c <= most)
    return block_q, block_k, block * tiles, False


# ------------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_k, unrolled):
    # q_ref/o_ref: [rows, d], the grid step's rows of one head; k_ref/v_ref:
    # [sk, d]; lse_ref: [1, rows]
    rows, d = q_ref.shape
    nk = k_ref.shape[0] // block_k
    op = q_ref.dtype
    first = 0 if unrolled else pl.program_id(1) * rows
    for i in range(rows // block_q):
        q0 = first + i * block_q
        tile = pl.ds(i * block_q, block_q)
        q = _scaled(q_ref[tile, :], scale, op)

        def body(j, carry, masked):
            m_prev, l_prev, acc = carry
            k = k_ref[pl.ds(j * block_k, block_k), :]
            v = v_ref[pl.ds(j * block_k, block_k), :]
            s = _dot(q, k, _NT)  # [block_q, block_k]
            if masked:
                s = jnp.where(_seen(q0, j * block_k, s.shape, 0), s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            return m_new, l_new, acc * alpha + _dot(p.astype(op), v, _NN)

        n_clear, n_live = _k_tile_ranges(q0, block_q, block_k, nk, causal)
        init = (jnp.full((block_q, 1), NEG_INF, jnp.float32),
                jnp.zeros((block_q, 1), jnp.float32),
                jnp.zeros((block_q, d), jnp.float32))
        m, l, acc = _tile_loops(0, n_clear, n_live, body, False, init)
        l = jnp.maximum(l, 1e-30)
        o_ref[tile, :] = (acc * (1.0 / l)).astype(o_ref.dtype)
        lse_ref[:, tile] = _to_row(m + jnp.log(l))


def _fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    b, sq, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    sk = k.shape[1]
    bh = b * h
    block_q, block_k, rows, unrolled = _plan(
        "fwd", sq, sk, d, q.dtype, block_q, block_k)
    # [b, s, h, d] -> [b*h, s, d]
    qr = q.transpose(0, 2, 1, 3).reshape(bh, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(bh, sk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(bh, sk, d)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          unrolled=unrolled),
        grid=(bh, sq // rows),
        in_specs=[
            pl.BlockSpec((None, rows, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, rows, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, rows), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            _sds((bh, sq, d), q.dtype, qr),
            _sds((bh, 1, sq), jnp.float32, qr),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(qr, kr, vr)
    o = out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return o, (qr, kr, vr, out, lse)


# ------------------------------------------------------------------ backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *, scale, causal, block_q, block_k, unrolled):
    rows, d = q_ref.shape
    nk = k_ref.shape[0] // block_k
    op = q_ref.dtype
    first = 0 if unrolled else pl.program_id(1) * rows
    for i in range(rows // block_q):
        q0 = first + i * block_q
        tile = pl.ds(i * block_q, block_q)
        q = _scaled(q_ref[tile, :], scale, op)
        do = do_ref[tile, :]
        lse = _to_col(lse_ref[:, tile])      # [block_q, 1]
        delta = _to_col(delta_ref[:, tile])

        def body(j, dq, masked):
            k = k_ref[pl.ds(j * block_k, block_k), :]
            v = v_ref[pl.ds(j * block_k, block_k), :]
            s = _dot(q, k, _NT)
            if masked:
                s = jnp.where(_seen(q0, j * block_k, s.shape, 0), s, NEG_INF)
            p = jnp.exp(s - lse)
            ds = p * (_dot(do, v, _NT) - delta)
            return dq + _dot(ds.astype(op), k, _NN)

        n_clear, n_live = _k_tile_ranges(q0, block_q, block_k, nk, causal)
        dq = _tile_loops(0, n_clear, n_live, body, False,
                         jnp.zeros((block_q, d), jnp.float32))
        dq_ref[tile, :] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                *, scale, causal, block_q, block_k, unrolled):
    # k_ref/v_ref/dk_ref/dv_ref: [rows, d], the grid step's keys of one head;
    # q_ref/do_ref: [sq, d]; lse_ref/delta_ref: [1, sq]. Score tiles are
    # transposed: keys are rows, queries run along the lanes.
    rows, d = k_ref.shape
    nq = q_ref.shape[0] // block_q
    op = q_ref.dtype
    first = 0 if unrolled else pl.program_id(1) * rows
    for i in range(rows // block_k):
        k0 = first + i * block_k
        tile = pl.ds(i * block_k, block_k)
        k = _scaled(k_ref[tile, :], scale, op)
        v = v_ref[tile, :]

        def body(j, carry, masked):
            dk, dv = carry
            at = pl.ds(j * block_q if _static(j)
                       else pl.multiple_of(j * block_q, block_q), block_q)
            q = q_ref[at, :]
            do = do_ref[at, :]
            s = _dot(k, q, _NT)  # [block_k, block_q]
            if masked:
                s = jnp.where(_seen(j * block_q, k0, s.shape, 1), s, NEG_INF)
            p = jnp.exp(s - lse_ref[:, at])
            dv = dv + _dot(p.astype(op), do, _NN)
            ds = p * (_dot(v, do, _NT) - delta_ref[:, at])
            return dk + _dot(ds.astype(op), q, _NN), dv

        if causal:
            # q tiles [j0, j_clear) are crossed by the diagonal, tiles from
            # j_clear on lie wholly under it, tiles before j0 see no key here
            j0 = _div(k0, block_q)
            j_clear = _min(_div(k0 + block_k + block_q - 2, block_q), nq)
        else:
            j0 = j_clear = 0
        zero = jnp.zeros((block_k, d), jnp.float32)
        dk, dv = _tile_loops(j0, j_clear, nq, body, True, (zero, zero))
        dk_ref[tile, :] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[tile, :] = dv.astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, interpret, res, g, dlse=None):
    """Backward. When `dlse` ([bh, 1, sq] fp32 cotangent of the logsumexp
    output) is given, it folds into the delta term: the score gradient is
    ds = p*(dp - delta + dlse) and d(lse)/ds = p, so passing
    delta' = delta - dlse to the unchanged kernels yields the exact joint
    gradient — this is what lets ring attention differentiate through the
    per-chunk (o, lse) pair (VERDICT r3 item 3)."""
    qr, kr, vr, outr, lse = res
    bh, sq, d = qr.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    sk = kr.shape[1]
    do = g.transpose(0, 2, 1, 3).reshape(bh, sq, d)
    # delta = rowsum(dO * O), fp32, same [bh, 1, sq] layout as lse
    delta = jnp.sum(do.astype(jnp.float32) * outr.astype(jnp.float32),
                    axis=-1)[:, None, :]
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    bq, bk, rows, unrolled = _plan("dq", sq, sk, d, qr.dtype, block_q, block_k)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, unrolled=unrolled),
        grid=(bh, sq // rows),
        in_specs=[
            pl.BlockSpec((None, rows, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, rows, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, rows), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, 1, rows), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, rows, d), lambda i, j: (i, j, 0)),
        out_shape=_sds((bh, sq, d), qr.dtype, qr),
        name="flash_bwd_dq",
        interpret=interpret,
    )(qr, kr, vr, do, lse, delta)

    bq, bk, rows, unrolled = _plan("dkv", sq, sk, d, qr.dtype, block_q,
                                   block_k)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, unrolled=unrolled),
        grid=(bh, sk // rows),
        in_specs=[
            pl.BlockSpec((None, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, rows, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, rows, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, sq), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, sq), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, rows, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, rows, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            _sds((bh, sk, d), kr.dtype, qr),
            _sds((bh, sk, d), vr.dtype, qr),
        ],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(qr, kr, vr, do, lse, delta)

    b = g.shape[0]
    h = g.shape[2]
    un = lambda x, s: x.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return un(dq, sq), un(dk, sk), un(dv, sk)


# ---------------------------------------------------------------- public API
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(
    q, k, v, scale=None, causal=False,
    block_q=None, block_k=None, interpret=False,
):
    """Flash attention on [b, s, h, d] inputs. Differentiable (custom VJP with
    Pallas backward). block_q x block_k is the score tile of all three
    kernels; left None, each kernel takes its own from the shapes (`_plan`).
    Requires seq lengths divisible by the tile sides."""
    o, _ = _fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return o


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k, interpret):
    o, res = _fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return o, res


def _flash_bwd_rule(scale, causal, block_q, block_k, interpret, res, g):
    return _bwd(scale, causal, block_q, block_k, interpret, res, g)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ------------------------------------------- platform-deferred entry point
def _dense_fwd(q, k, v, scale, causal):
    """XLA forward producing residuals in the SAME kernel layout as _fwd
    ((qr, kr, vr, out, lse) with [bh, s, d] / [bh, 1, sq] fp32 lse), so a
    lax.platform_dependent can pick pallas-vs-XLA per lowering target."""
    b, sq, h, d = q.shape
    sc = 1.0 / math.sqrt(d) if scale is None else scale
    sk = k.shape[1]
    bh = b * h
    qr = q.transpose(0, 2, 1, 3).reshape(bh, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(bh, sk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(bh, sk, d)
    s = jnp.einsum("bqd,bkd->bqk", qr.astype(jnp.float32),
                   kr.astype(jnp.float32)) * sc
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool))[None], s, -1e30)
    lse = jax.nn.logsumexp(s, -1, keepdims=True)  # [bh, sq, 1]
    p = jnp.exp(s - lse)
    out = jnp.einsum("bqk,bkd->bqd", p, vr.astype(jnp.float32)).astype(q.dtype)
    o = out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return o, (qr, kr, vr, out, lse.reshape(bh, 1, sq))


def _dense_bwd(scale, causal, res, g, dlse=None):
    """XLA backward from the kernel-layout residuals (same math as the
    pallas kernels: ds = p * (dp - delta [+ dlse fold])."""
    qr, kr, vr, outr, lse = res
    bh, sq, d = qr.shape
    sc = 1.0 / math.sqrt(d) if scale is None else scale
    sk = kr.shape[1]
    do = g.transpose(0, 2, 1, 3).reshape(bh, sq, d).astype(jnp.float32)
    s = jnp.einsum("bqd,bkd->bqk", qr.astype(jnp.float32),
                   kr.astype(jnp.float32)) * sc
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool))[None], s, -1e30)
    p = jnp.exp(s - lse.reshape(bh, sq, 1))
    dv = jnp.einsum("bqk,bqd->bkd", p, do)
    dp = jnp.einsum("bqd,bkd->bqk", do, vr.astype(jnp.float32))
    delta = jnp.sum(do * outr.astype(jnp.float32), -1, keepdims=True)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32).reshape(bh, sq, 1)
    ds = p * (dp - delta)
    dq = jnp.einsum("bqk,bkd->bqd", ds, kr.astype(jnp.float32)) * sc
    dk = jnp.einsum("bqk,bqd->bkd", ds, qr.astype(jnp.float32)) * sc
    b = g.shape[0]
    h = g.shape[2]
    un = lambda x, s_, dt: x.astype(dt).reshape(b, h, s_, d).transpose(0, 2, 1, 3)
    return un(dq, sq, qr.dtype), un(dk, sk, kr.dtype), un(dv, sk, vr.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_platform(q, k, v, scale=None, causal=False,
                             block_q=None, block_k=None):
    """flash_attention whose pallas-vs-XLA choice happens at LOWERING time
    (lax.platform_dependent): a program exported for 'tpu' from any host
    embeds the Mosaic kernel, while the same trace stays runnable on CPU.
    The platform cond sits INSIDE the custom-vjp fwd/bwd, so nothing ever
    differentiates through it (jax cannot JVP a pallas_call inside a cond
    branch)."""
    o, _ = _platform_fwd(q, k, v, scale, causal, block_q, block_k)
    return o


def _platform_fwd(q, k, v, scale, causal, block_q, block_k):
    return lax.platform_dependent(
        q, k, v,
        tpu=lambda q, k, v: _fwd(q, k, v, scale, causal, block_q, block_k,
                                 False),
        default=lambda q, k, v: _dense_fwd(q, k, v, scale, causal))


def _platform_fwd_rule(q, k, v, scale, causal, block_q, block_k):
    return _platform_fwd(q, k, v, scale, causal, block_q, block_k)


def _platform_bwd_rule(scale, causal, block_q, block_k, res, g):
    return lax.platform_dependent(
        *res, g,
        tpu=lambda *a: _bwd(scale, causal, block_q, block_k, False,
                            a[:5], a[5]),
        default=lambda *a: _dense_bwd(scale, causal, a[:5], a[5]))


flash_attention_platform.defvjp(_platform_fwd_rule, _platform_bwd_rule)


def on_mesh(fn, q, k, v):
    """fn(q, k, v) — a flash-attention entry on [b, s, h, d] — under the
    active device mesh. GSPMD cannot partition a Mosaic kernel ("Mosaic
    kernels cannot be automatically partitioned. Please wrap the call in a
    shard_map"), and attention is independent per batch row and head: so the
    call goes inside a shard_map over every mesh axis that is not manual
    already, batch split over the data axes and heads over 'mp' where they
    divide, replicated over the rest."""
    from jax.sharding import PartitionSpec

    from ...distributed.mesh import get_mesh

    mesh = get_mesh()
    if mesh is None:
        return fn(q, k, v)
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    free = [a for a in mesh.axis_names if a not in manual]
    if all(mesh.shape[a] == 1 for a in free):
        return fn(q, k, v)
    batch_axes, n = [], 1
    for a in ("dp", "sharding"):
        if a in free and q.shape[0] % (n * mesh.shape[a]) == 0:
            batch_axes.append(a)
            n *= mesh.shape[a]
    head_axis = "mp" if "mp" in free and all(
        x.shape[2] % mesh.shape["mp"] == 0 for x in (q, k)) else None
    spec = PartitionSpec(tuple(batch_axes) or None, None, head_axis, None)
    # inside another shard_map the context mesh is the one to map over
    return jax.shard_map(
        fn, mesh=None if manual else mesh, in_specs=(spec,) * 3,
        out_specs=spec, axis_names=frozenset(free), check_vma=False)(q, k, v)


# ----------------------------------------------- varlen (segmented) flash
# Reference: phi flash_attn_unpadded / flash_attn_varlen
# (paddle/phi/kernels/gpu/flash_attn_kernel.cu varlen entries) — packed
# sequences with a block-diagonal mask. TPU-native shape: SEGMENT IDS
# (splash-attention style) — the kernels stream K/V blocks exactly like the
# dense flash kernels and add a seg_q == seg_k visibility test, so packed
# pretraining batches keep O(block) memory instead of a [total, total]
# mask.
def _fwd_seg_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref,
                    *, scale, causal, block_k, sk):
    qi = pl.program_id(1)
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    q = q_ref[:].astype(jnp.float32) * scale
    seg_q = sq_ref[:]  # [block_q, 1] int32

    def body(j, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        seg_k = sk_ref[pl.ds(j * block_k, block_k), :]  # [block_k, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        live = seg_q == seg_k.reshape(1, block_k)  # [block_q, block_k]
        if causal:
            q_ids = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            live = live & (q_ids >= k_ids)
        s = jnp.where(live, s, NEG_INF)
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(live, p, 0.0)  # fully-masked rows stay exactly zero
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, sk // block_k, body, (m0, l0, acc0))
    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[:] = (m + jnp.log(l))[:, None]


def _bwd_seg_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, do_ref, lse_ref,
                    delta_ref, dq_ref, *, scale, causal, block_k, sk):
    qi = pl.program_id(1)
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    q = q_ref[:].astype(jnp.float32) * scale
    do = do_ref[:].astype(jnp.float32)
    lse = lse_ref[:]
    delta = delta_ref[:]
    seg_q = sq_ref[:]

    def body(j, dq):
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        seg_k = sk_ref[pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        live = seg_q == seg_k.reshape(1, block_k)
        if causal:
            q_ids = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            live = live & (q_ids >= k_ids)
        p = jnp.where(live, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, sk // block_k, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _dkv_seg_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, *, scale, causal, block_q, sq):
    ki = pl.program_id(1)
    block_k = k_ref.shape[0]
    d = k_ref.shape[1]
    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    seg_k = sk_ref[:]  # [block_k, 1]

    def body(j, carry):
        dk, dv = carry
        q = q_ref[pl.ds(j * block_q, block_q), :].astype(jnp.float32) * scale
        do = do_ref[pl.ds(j * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(j * block_q, block_q), :]
        delta = delta_ref[pl.ds(j * block_q, block_q), :]
        seg_q = sq_ref[pl.ds(j * block_q, block_q), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        live = seg_q == seg_k.reshape(1, block_k)
        if causal:
            q_ids = j * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            live = live & (q_ids >= k_ids)
        p = jnp.where(live, jnp.exp(s - lse), 0.0)
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, sq // block_q, body, (dk0, dv0))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _seg_fwd(q, k, v, seg, scale, causal, block_q, block_k, interpret):
    b, sq, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    sk = k.shape[1]
    bh = b * h
    qr = q.transpose(0, 2, 1, 3).reshape(bh, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(bh, sk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(bh, sk, d)
    segr = seg.astype(jnp.int32).reshape(b, sq, 1)

    seg_block = pl.BlockSpec((None, block_q, 1),
                             lambda i, j, h=h: (i // h, j, 0))
    seg_full = pl.BlockSpec((None, sk, 1), lambda i, j, h=h: (i // h, 0, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_seg_kernel, scale=scale, causal=causal,
                          block_k=block_k, sk=sk),
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
            seg_block,
            seg_full,
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            _sds((bh, sq, d), q.dtype, qr),
            _sds((bh, sq, 1), jnp.float32, qr),
        ],
        name="flash_seg_fwd",
        interpret=interpret,
    )(qr, kr, vr, segr, segr)
    o = out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return o, (qr, kr, vr, segr, out, lse)


def _seg_bwd(scale, causal, block_q, block_k, interpret, res, g):
    qr, kr, vr, segr, outr, lse = res
    bh, sq, d = qr.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    sk = kr.shape[1]
    b = segr.shape[0]
    h = bh // b
    do = g.transpose(0, 2, 1, 3).reshape(bh, sq, d)
    delta = jnp.sum(do.astype(jnp.float32) * outr.astype(jnp.float32),
                    axis=-1, keepdims=True)

    seg_block_q = pl.BlockSpec((None, block_q, 1),
                               lambda i, j, h=h: (i // h, j, 0))
    seg_full_q = pl.BlockSpec((None, sq, 1), lambda i, j, h=h: (i // h, 0, 0))
    seg_full_k = pl.BlockSpec((None, sk, 1), lambda i, j, h=h: (i // h, 0, 0))
    seg_block_k = pl.BlockSpec((None, block_k, 1),
                               lambda i, j, h=h: (i // h, j, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_seg_kernel, scale=scale, causal=causal,
                          block_k=block_k, sk=sk),
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
            seg_block_q,
            seg_full_k,
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=_sds((bh, sq, d), qr.dtype, qr),
        name="flash_seg_bwd_dq",
        interpret=interpret,
    )(qr, kr, vr, segr, segr, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_seg_kernel, scale=scale, causal=causal,
                          block_q=block_q, sq=sq),
        grid=(bh, sk // block_k),
        in_specs=[
            pl.BlockSpec((None, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            seg_full_q,
            seg_block_k,
            pl.BlockSpec((None, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sq, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sq, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            _sds((bh, sk, d), kr.dtype, qr),
            _sds((bh, sk, d), vr.dtype, qr),
        ],
        name="flash_seg_bwd_dkv",
        interpret=interpret,
    )(qr, kr, vr, segr, segr, do, lse, delta)

    un = lambda x, s: x.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return un(dq, sq), un(dk, sk), un(dv, sk), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention_segmented(
    q, k, v, segment_ids, scale=None, causal=False,
    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K, interpret=False,
):
    """Varlen flash attention via segment ids: q/k/v [b, s, h, d],
    segment_ids [b, s] int32 — tokens attend only within their segment
    (block-diagonal mask), streamed with O(block) memory. Differentiable."""
    o, _ = _seg_fwd(q, k, v, segment_ids, scale, causal, block_q, block_k,
                    interpret)
    return o


def _seg_fwd_rule(q, k, v, segment_ids, scale, causal, block_q, block_k,
                  interpret):
    return _seg_fwd(q, k, v, segment_ids, scale, causal, block_q, block_k,
                    interpret)


flash_attention_segmented.defvjp(_seg_fwd_rule, _seg_bwd)


# --------------------------------------------- (o, lse) entry for ring CP
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_with_lse(
    q, k, v, scale=None, causal=False,
    block_q=None, block_k=None, interpret=False,
):
    """Flash attention that ALSO returns the per-row logsumexp as a
    first-class differentiable output: (o [b,sq,h,d], lse [b,h,sq] fp32).

    This is the chunk kernel for ring attention
    (distributed/context_parallel.py): the ring's online-softmax combine
    consumes lse, so the chunk must expose it and its VJP must accept lse
    cotangents — plain AD cannot differentiate through pallas_call
    (the round-3 deferred item)."""
    o, res = _fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    b, sq, h, _ = q.shape
    lse = res[4].reshape(b, h, sq)
    return o, lse


def _flash_lse_fwd_rule(q, k, v, scale, causal, block_q, block_k, interpret):
    o, res = _fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    b, sq, h, _ = q.shape
    lse = res[4].reshape(b, h, sq)
    return (o, lse), res


def _flash_lse_bwd_rule(scale, causal, block_q, block_k, interpret, res, g):
    do, dlse = g
    bh, sq, _ = res[0].shape
    return _bwd(scale, causal, block_q, block_k, interpret, res, do,
                dlse=dlse.reshape(bh, 1, sq))


flash_attention_with_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


# ----------------------------------------------- serving prefill (forward)
def _prefill_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, *, scale, window,
                    block_k, sk):
    # The forward kernel for a chunk of queries at an offset into a cache:
    # off_ref [1] (scalar prefetch) is the absolute position of the chunk's
    # first query; q_ref [block_q, d] one query head; k_ref/v_ref [sk, d]
    # the K/V head it reads (the index_map maps a group of query heads onto
    # one K/V head, which is fetched once a group). Query at position p sees
    # keys p - window < j <= p; key blocks wholly outside that band are not
    # visited.
    qi = pl.program_id(1)
    block_q, d = q_ref.shape
    q = q_ref[:].astype(jnp.float32) * scale
    q_lo = off_ref[0] + qi * block_q
    hi = jnp.minimum(jax.lax.div(q_lo + block_q - 1, block_k) + 1,
                     sk // block_k)
    lo = 0 if window is None else \
        jax.lax.div(jnp.maximum(q_lo - window + 1, 0), block_k)

    def body(j, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        q_ids = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_ids = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        seen = q_ids >= k_ids
        if window is not None:
            seen = jnp.logical_and(seen, k_ids > q_ids - window)
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.where(seen, jnp.exp(s - m_new[:, None]), 0.0)
        l_new = l_prev * alpha + jnp.sum(pr, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            pr, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def prefill_supports(q_shape, k_shape, block_q=DEFAULT_BLOCK_Q,
                     block_k=DEFAULT_BLOCK_K) -> bool:
    b, sq, hq, d = q_shape
    sk, hkv = k_shape[1], k_shape[2]
    return (sq % block_q == 0 and sk % block_k == 0 and d % 128 == 0
            and d <= 256 and hq % hkv == 0)


def flash_attention_prefill(q, k, v, q_offset, *, scale=None, window=None,
                            block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                            interpret=False):
    """Attention of a chunk of queries over a cache that already holds
    their keys (serving prefill; forward only). q [b, sq, hq, d]; k, v
    [b, sk, hkv, d] with hq a multiple of hkv (query head h reads K/V head
    h // (hq / hkv), unrepeated); q_offset: scalar int32, the absolute
    position of q[:, 0]. Query at position p sees keys p - window < j <= p
    (`window` None: every key up to p). Returns [b, sq, hq, d]."""
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qr = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    off = jnp.asarray(q_offset, jnp.int32).reshape(1)
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, window=window,
                          block_k=block_k, sk=sk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * hq, sq // block_q),
            in_specs=[
                pl.BlockSpec((None, block_q, d), lambda i, j, off: (i, j, 0)),
                pl.BlockSpec((None, sk, d), lambda i, j, off: (i // g, 0, 0)),
                pl.BlockSpec((None, sk, d), lambda i, j, off: (i // g, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, block_q, d),
                                   lambda i, j, off: (i, j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        name="flash_prefill",
        interpret=interpret,
    )(off, qr, kr, vr)
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)


# ------------------------------------- serving prefill over a latent cache
LATENT_BLOCK_Q = 128
LATENT_BLOCK_K = 512


def _latent_prefill_kernel(off_ref, q_ref, c_ref, o_ref, m_s, l_s, acc_s, *,
                           scale, block_q, block_k, v_dim):
    # One (query block, key block) of a chunk's absorbed latent attention.
    # off_ref [1] (scalar prefetch): the absolute position of the chunk's
    # first query. q_ref [heads * block_q, w]: every head's queries of the
    # block, head-major (row r is head r // block_q, token r % block_q), so
    # the one latent block c_ref [block_k, w] is read once for all heads.
    # Scores over all w values, the weighted sum over the first v_dim of
    # the same rows. m_s, l_s [rows, 1], acc_s [rows, v_dim]: the online
    # softmax across the key blocks, which are the grid's last axis. Key
    # blocks after the diagonal are not computed (nor fetched: the index
    # map holds on to the last block that is).
    qi, kj = pl.program_id(0), pl.program_id(1)
    q_lo = off_ref[0] + qi * block_q
    hi = jax.lax.div(q_lo + block_q - 1, block_k)

    @pl.when(kj == 0)
    def _():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(kj <= hi)
    def _():
        c = c_ref[...]
        s = jax.lax.dot_general(q_ref[...], c, _NT,
                                preferred_element_type=jnp.float32) * scale
        rows = s.shape[0]
        q_ids = q_lo + jnp.bitwise_and(
            jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 0),
            block_q - 1)
        k_ids = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        s = jnp.where(q_ids >= k_ids, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # key 0 is seen by every query, so m_new is finite from the first
        # block on and a masked score's exp is exactly 0
        p = jnp.exp(s - m_new)
        m_s[...] = m_new
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c[:, :v_dim], _NN,
            preferred_element_type=jnp.float32)

    @pl.when(kj == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)


def latent_prefill_supports(q_shape, cache_shape, v_dim,
                            block_q=LATENT_BLOCK_Q,
                            block_k=LATENT_BLOCK_K) -> bool:
    b, sq, _, w = q_shape
    return (b == 1 and sq % block_q == 0 and cache_shape[1] % block_k == 0
            and v_dim % _LANES == 0 and v_dim <= w)


@functools.partial(jax.jit, static_argnames=("v_dim", "scale", "block_q",
                                             "block_k", "interpret"))
def latent_prefill(q, latent, q_offset, *, v_dim, scale,
                   block_q=LATENT_BLOCK_Q, block_k=LATENT_BLOCK_K,
                   interpret=False):
    """Absorbed latent attention of a chunk of queries over a contiguous
    latent cache that already holds their rows (serving prefill; forward
    only). q [1, sq, heads, w]: a head's absorbed query; latent [1, sk, w]:
    a token's compressed latent and rotary key in one row, read ONCE a
    query block for all heads; q_offset: scalar int32, the absolute position
    of q[:, 0]. Query at position p sees keys <= p: score over all w values,
    weighted sum over the first v_dim of the same rows. Returns
    [1, sq, heads, v_dim]. Its time follows the keys before the diagonal:
    later key blocks cost a grid step each (~0.35 us) and no product."""
    _, sq, heads, w = q.shape
    sk = latent.shape[1]
    nq, nk = sq // block_q, sk // block_k
    assert block_q & (block_q - 1) == 0, "block_q: a power of two"
    rows = heads * block_q
    # [nq, heads * block_q, w]: a query block's heads stacked as rows
    qr = (q[0].reshape(nq, block_q, heads, w).transpose(0, 2, 1, 3)
          .reshape(nq, rows, w).astype(latent.dtype))
    off = jnp.asarray(q_offset, jnp.int32).reshape(1)

    def last_seen(qi, off):
        return jax.lax.div(off[0] + (qi + 1) * block_q - 1, block_k)

    out = pl.pallas_call(
        functools.partial(_latent_prefill_kernel, scale=scale,
                          block_q=block_q, block_k=block_k, v_dim=v_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nq, nk),
            in_specs=[
                pl.BlockSpec((None, rows, w), lambda i, j, off: (i, 0, 0)),
                pl.BlockSpec((None, block_k, w), lambda i, j, off: (
                    0, jnp.minimum(j, last_seen(i, off)), 0)),
            ],
            out_specs=pl.BlockSpec((None, rows, v_dim),
                                   lambda i, j, off: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, v_dim), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((nq, rows, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=96 << 20),
        name="latent_prefill",
        interpret=interpret,
    )(off, qr, latent)
    return (out.reshape(nq, heads, block_q, v_dim).transpose(0, 2, 1, 3)
            .reshape(1, sq, heads, v_dim))


def supports(q_shape, k_shape, attn_mask, dropout_p, is_causal=False,
             block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K) -> bool:
    """Shape gate: fall back to the XLA composition otherwise. The default
    blocks are the smallest tiles `_plan` falls back to, so every sequence
    they divide has a tile.

    Causal with sq != sk is rejected: this kernel's mask is top-left aligned
    (absolute q_id >= k_id) while the sdpa fallback is bottom-right aligned
    (query i sees keys j <= i + sk - sq, the KV-cache decode convention).
    """
    b, sq, h, d = q_shape
    sk = k_shape[1]
    return (
        attn_mask is None
        and dropout_p == 0.0
        and sq % block_q == 0
        and sk % block_k == 0
        and sq >= block_q
        and sk >= block_k
        and d <= 256
        and not (is_causal and sq != sk)
    )


def _RING_BLOCK(s_local):
    """Block sizes for ring-chunk flash: the kernels' own choice when the
    local shard is a multiple of the TPU-native 128, else the largest
    8-aligned divisor so small CPU-mesh parity tests still route through the
    kernel (interpret mode)."""
    if s_local % DEFAULT_BLOCK_Q == 0 and s_local >= DEFAULT_BLOCK_Q:
        return None, None  # each kernel's own tile, from the shapes
    for b in (64, 32, 16, 8):
        if s_local % b == 0 and s_local >= b:
            return b, b
    return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K  # will fail the divisibility gate


# ---- autotuned entry (reference: phi autotune cache + switch_autotune) ----
from ...core.autotune import autotune as _autotune  # noqa: E402

# The first candidate is what runs unless FLAGS_use_autotune is on and the call
# is eager: None leaves each of the three kernels the tile `_plan` derives
# from its shapes. The others are explicit tiles, the same for all three, for
# a tuning run to time against it.
_BLOCK_CANDIDATES = [
    {"block_q": None, "block_k": None},
    {"block_q": 128, "block_k": 128},
    {"block_q": 256, "block_k": 256},
    {"block_q": 512, "block_k": 256},
    {"block_q": 256, "block_k": 512},
    {"block_q": 512, "block_k": 512},
]


@_autotune(_BLOCK_CANDIDATES,
           key_extra=lambda q, k, v, scale=None, causal=False,
           interpret=False: bool(causal))
def flash_attention_tuned(q, k, v, scale=None, causal=False, interpret=False,
                          *, block_q, block_k):
    """flash_attention with block sizes chosen by the autotune cache when
    FLAGS_use_autotune is on (invalid candidates — seq not divisible by the
    block — are skipped by the tuner); otherwise the hand-picked defaults."""
    if block_q and (q.shape[1] % block_q or k.shape[1] % block_k):
        raise ValueError("block does not divide sequence")  # tuner skips
    return flash_attention(q, k, v, scale, causal, block_q, block_k, interpret)


@_autotune(_BLOCK_CANDIDATES,
           key_extra=lambda q, k, v, scale=None,
           causal=False: bool(causal))
def flash_attention_platform_tuned(q, k, v, scale=None, causal=False,
                                   *, block_q, block_k):
    """flash_attention_platform (lowering-time pallas/XLA choice) with the
    same autotuned block-size selection as flash_attention_tuned."""
    if block_q and (q.shape[1] % block_q or k.shape[1] % block_k):
        raise ValueError("block does not divide sequence")  # tuner skips
    return flash_attention_platform(q, k, v, scale, causal, block_q, block_k)
