"""Fused RMSNorm as a Pallas kernel (fwd + custom-VJP bwd).

Reference: paddle.incubate.nn.functional.rms_norm
(python/paddle/incubate/nn/functional/ -> phi fused rms_norm kernels). On TPU
the win is keeping the row in VMEM for the two passes (square-mean + scale) in
one HBM read, fp32 statistics regardless of input dtype.

TPU lowering notes: per-row residuals are kept 2-D ([n, 1] — a size-1 minor
dim equals the full array dim, which Pallas TPU accepts), and the dw partial
is accumulated across the sequential TPU grid into a single [1, d] output
block (constant index map; initialized on the first grid step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Half of the 16 MiB of scoped VMEM a TPU v5e kernel gets by default; the
# rest is headroom for what the compiler adds.
_VMEM_BUDGET = 8 << 20


def _block_rows(n, d, dtype):
    """Rows per grid step, sized from the hidden width and dtype: the
    backward kernel holds x, g and dx blocks (double-buffered by the
    pipeline) plus about four fp32 temporaries of the same extent, and that
    has to fit _VMEM_BUDGET at any width. A multiple of the dtype's sublane
    tile (8 rows fp32, 16 bf16) unless the whole array is smaller."""
    itemsize = jnp.dtype(dtype).itemsize
    per_row = d * (3 * 2 * itemsize + 4 * 4)
    sub = 32 // itemsize
    rows = max(sub, min(1024, _VMEM_BUDGET // per_row) // sub * sub)
    return min(rows, n)


def _fwd_kernel(x_ref, w_ref, y_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    y = x * rstd
    y_ref[:] = (y * w_ref[:].astype(jnp.float32)).astype(y_ref.dtype)
    rstd_ref[:] = rstd


def _bwd_kernel(x_ref, w_ref, rstd_ref, g_ref, dx_ref, dw_ref):
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]                      # [rows, 1]
    xhat = x * rstd
    gw = g * w
    # dx = rstd * (gw - xhat * mean(gw * xhat))
    c = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (gw - xhat * c)).astype(dx_ref.dtype)
    # dw accumulated across the (sequential) grid into one [1, d] block
    part = jnp.sum(g * xhat, axis=0, keepdims=True)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    dw_ref[:] += part


def _run_fwd(x, w, eps, interpret):
    orig_shape = x.shape
    d = x.shape[-1]
    n = x.size // d
    xr = x.reshape(n, d)
    wr = w.reshape(1, d)
    rows = _block_rows(n, d, x.dtype)
    # Pad the row dim to a block multiple (padded rows compute rsqrt(eps),
    # sliced away below) rather than shrinking the block to a divisor.
    pad = (-n) % rows
    xp = jnp.pad(xr, ((0, pad), (0, 0))) if pad else xr
    np_ = n + pad
    y, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(np_ // rows,),
        in_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, d), x.dtype),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        ],
        name="rms_norm_fwd",
        interpret=interpret,
    )(xp, wr)
    if pad:
        y, rstd = y[:n], rstd[:n]
    return y.reshape(orig_shape), (xr, w, rstd, orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_rms_norm(x, weight, epsilon=1e-6, interpret=False):
    """RMSNorm over the last axis; weight shape [d]."""
    y, _ = _run_fwd(x, weight, epsilon, interpret)
    return y


def _fwd_rule(x, weight, epsilon, interpret):
    return _run_fwd(x, weight, epsilon, interpret)


def _bwd_rule(epsilon, interpret, res, g):
    xr, w, rstd, orig_shape = res
    n, d = xr.shape
    rows = _block_rows(n, d, xr.dtype)
    pad = (-n) % rows
    gr = g.reshape(n, d)
    if pad:
        # Padded rows carry zero upstream grad, so their dw contribution
        # is zero and their dx rows are sliced away.
        xr_p = jnp.pad(xr, ((0, pad), (0, 0)))
        gr_p = jnp.pad(gr, ((0, pad), (0, 0)))
        rstd_p = jnp.pad(rstd, ((0, pad), (0, 0)))
    else:
        xr_p, gr_p, rstd_p = xr, gr, rstd
    np_ = n + pad
    nblocks = np_ // rows
    dx, dw = pl.pallas_call(
        _bwd_kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, d), xr.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        name="rms_norm_bwd",
        interpret=interpret,
    )(xr_p, w.reshape(1, d), rstd_p, gr_p)
    return dx[:n].reshape(orig_shape), dw.reshape(d).astype(w.dtype)


fused_rms_norm.defvjp(_fwd_rule, _bwd_rule)
