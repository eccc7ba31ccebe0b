"""Quantized matmul kernels (reference: phi weight_only_linear / matmul_int8 /
llm_int8_matmul, paddle/phi/kernels/fusion/cutlass_*).

TPU design: int8 weights live in HBM at 1 byte/param; lax.dot_general with
preferred_element_type=int32 runs on the MXU's int8 path where available and
dequantization fuses into the epilogue. Per-channel scales follow the
reference's weight-only scheme (absmax over the input dim).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def quantize_weight_absmax(w, axis=0):
    """-> (int8 weight, fp scales) with per-output-channel absmax scaling.
    w: [in, out] (paddle linear layout); scales: [out]."""
    absmax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, jnp.squeeze(scale, axis)


def dequantize_weight(qweight, scales, dtype=jnp.float32):
    """Scale-folded dequantization: fp [in, out] table from the int8 weight +
    per-output-channel scales. This is weight_only_matmul's epilogue hoisted
    out of the hot path: on backends with no int8 GEMM (XLA:CPU) the per-call
    convert MATERIALIZES a full fp copy of the weight every decode step.
    Dequantizing once and reusing the fp table makes int8 decode run the
    identical GEMM as fp."""
    return qweight.astype(dtype) * scales.astype(dtype)


def weight_only_matmul(x, qweight, scales, bias=None, dequant=None):
    """phi weight_only_linear: fp activations x int8 weights. x: [..., in],
    qweight: [in, out] int8.

    Two epilogue structures, chosen by the caller per backend:
      * dequant=None — dequantize into the matmul epilogue (int8 stream from
        HBM, convert fused into the MXU feed): the TPU path, where 4x less
        weight traffic is the decode-phase win.
      * dequant=<fp table> — the hoisted form (dequantize_weight, computed
        ONCE): the CPU path, where XLA has no int8 GEMM and the per-call
        convert is pure overhead. Scales are folded into the table, so the
        hot loop is exactly the fp GEMM.
    """
    if dequant is not None:
        out = jnp.matmul(x, dequant.astype(x.dtype))
    else:
        out = jnp.matmul(x, qweight.astype(x.dtype)) * scales.astype(x.dtype)
    if bias is not None:
        out = out + bias
    return out


def matmul_int8(x, y, scale_x=1.0, scale_y=1.0):
    """phi matmul_int8: int8 x int8 -> int32 accumulate on the MXU, scaled
    back to fp32."""
    acc = lax.dot_general(
        x.astype(jnp.int8), y.astype(jnp.int8),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * (scale_x * scale_y)


def llm_int8_matmul(x, qweight, scales, threshold=6.0):
    """phi llm_int8_matmul (LLM.int8()): columns of x with outliers beyond
    `threshold` run in fp16/fp32; the rest run int8."""
    absx = jnp.max(jnp.abs(x), axis=tuple(range(x.ndim - 1)))
    outlier = absx > threshold                          # [in]
    x_reg = jnp.where(outlier[None, :], 0.0, x.reshape(-1, x.shape[-1]))
    x_out = jnp.where(outlier[None, :], x.reshape(-1, x.shape[-1]), 0.0)
    sx = jnp.maximum(jnp.max(jnp.abs(x_reg)), 1e-8) / 127.0
    xq = jnp.clip(jnp.round(x_reg / sx), -127, 127).astype(jnp.int8)
    reg = lax.dot_general(xq, qweight.astype(jnp.int8),
                          (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.int32)
    reg = reg.astype(jnp.float32) * (sx * scales.astype(jnp.float32))
    outl = jnp.matmul(x_out, qweight.astype(jnp.float32) * scales.astype(jnp.float32))
    out = reg + outl
    return out.reshape(x.shape[:-1] + (qweight.shape[1],))


# phi reference name
quant_for_compress = quantize_weight_absmax
