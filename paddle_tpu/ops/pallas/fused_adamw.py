"""Fused AdamW update as a single Pallas kernel over a flat parameter buffer.

Reference: phi/kernels/gpu/fused_adam_kernel.cu (multi-tensor Adam) and
paddle.optimizer.AdamW's multi_tensor path. TPU design: the caller flattens
all params of one dtype into a single 1-D buffer (the jit trainer already
holds them as one pytree), and the kernel streams chunks through VMEM doing
p/m/v updates in fp32 in one pass — one HBM round-trip for the whole
optimizer step instead of one per parameter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_CHUNK = 64 * 1024


def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                  p_out, m_out, v_out):
    # sc: [8] fp32 scalars: lr, beta1, beta2, eps, weight_decay, bc1, bc2, grad_scale
    lr = sc_ref[0]
    beta1 = sc_ref[1]
    beta2 = sc_ref[2]
    eps = sc_ref[3]
    wd = sc_ref[4]
    bc1 = sc_ref[5]  # 1 - beta1**t
    bc2 = sc_ref[6]  # 1 - beta2**t
    gscale = sc_ref[7]

    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) * gscale
    m = m_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)

    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * g * g
    mhat = m / bc1
    vhat = v / bc2
    p = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)

    p_out[:] = p.astype(p_out.dtype)
    m_out[:] = m.astype(m_out.dtype)
    v_out[:] = v.astype(v_out.dtype)


def fused_adamw_update(p, g, m, v, *, lr, beta1=0.9, beta2=0.999, eps=1e-8,
                       weight_decay=0.0, step=1, grad_scale=1.0,
                       bias_correction1=None, bias_correction2=None,
                       chunk=DEFAULT_CHUNK, interpret=False):
    """One AdamW step on flat 1-D buffers. Returns (p, m, v) updated.

    bias_correction1/2 override the step-derived 1-beta**t factors so the
    caller can use per-parameter-group beta_pow state (params that skipped
    steps must not use the global step count).
    """
    n = p.shape[0]
    c = min(chunk, n)
    pad = (-n) % c
    if pad:
        p_, g_, m_, v_ = (jnp.pad(x, (0, pad)) for x in (p, g, m, v))
    else:
        p_, g_, m_, v_ = p, g, m, v
    nt = p_.shape[0] // c

    step_f = jnp.asarray(step, jnp.float32)
    bc1 = (jnp.asarray(bias_correction1, jnp.float32)
           if bias_correction1 is not None
           else 1.0 - jnp.asarray(beta1, jnp.float32) ** step_f)
    bc2 = (jnp.asarray(bias_correction2, jnp.float32)
           if bias_correction2 is not None
           else 1.0 - jnp.asarray(beta2, jnp.float32) ** step_f)
    sc = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(beta1, jnp.float32),
        jnp.asarray(beta2, jnp.float32),
        jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
        bc1,
        bc2,
        jnp.asarray(grad_scale, jnp.float32),
    ])

    po, mo, vo = pl.pallas_call(
        _adamw_kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((c,), lambda i: (i,)),
            pl.BlockSpec((c,), lambda i: (i,)),
            pl.BlockSpec((c,), lambda i: (i,)),
            pl.BlockSpec((c,), lambda i: (i,)),
            pl.BlockSpec((8,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((c,), lambda i: (i,)),
            pl.BlockSpec((c,), lambda i: (i,)),
            pl.BlockSpec((c,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(p_.shape, p.dtype),
            jax.ShapeDtypeStruct(m_.shape, m.dtype),
            jax.ShapeDtypeStruct(v_.shape, v.dtype),
        ],
        name="fused_adamw",
        interpret=interpret,
    )(p_, g_, m_, v_, sc)
    if pad:
        po, mo, vo = po[:n], mo[:n], vo[:n]
    return po, mo, vo
