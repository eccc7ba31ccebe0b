"""Fused rotary position embedding (RoPE) as a Pallas kernel.

Reference: paddle.incubate.nn.functional.fused_rotary_position_embedding
(phi fused_rope kernels). Applies the rotation to q and k in one VMEM pass
(one HBM read/write per tensor instead of the 4+ intermediate arrays the
naive composition materializes when XLA fails to fuse across the concat).

Linear in its inputs, so the VJP is the same rotation with transposed sign —
expressed here via jax.custom_vjp reusing the forward kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, sign):
    # x: [s, h, d] for one batch row; cos/sin: [s, d]
    x = x_ref[:].astype(jnp.float32)
    cos = cos_ref[:].astype(jnp.float32)[:, None, :]
    sin = sin_ref[:].astype(jnp.float32)[:, None, :]
    d = x.shape[-1]
    x1 = x[..., : d // 2]
    x2 = x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    o_ref[:] = (x * cos + sign * rot * sin).astype(o_ref.dtype)


def _seq_block(s, h, d, itemsize):
    """Largest seq chunk whose (block_s, h, d) block stays well under VMEM
    (the whole (s, h, d) row of a long-context batch does not fit: 2048x16x128
    bf16 is 8M per input before fp32 staging)."""
    # fp32 staging + rot/concat temporaries + double buffering multiply the
    # live block ~8x, so keep the raw operand block well under 1/8 of VMEM
    budget = 512 * 1024  # per-operand block budget in bytes
    for bs in (512, 256, 128, 64, 32, 16, 8):
        if s % bs == 0 and bs * h * d * itemsize <= budget:
            return bs
    return s


def _name(sign):
    """Kernel name in the trace: the VJP is the forward kernel run with the
    sign turned."""
    return "rope_fwd" if sign > 0 else "rope_bwd"


def _apply(x, cos, sin, sign, interpret):
    b, s, h, d = x.shape
    bs = _seq_block(s, h, d, x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_rope_kernel, sign=sign),
        grid=(b, s // bs),
        in_specs=[
            pl.BlockSpec((None, bs, h, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((bs, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bs, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bs, h, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, h, d), x.dtype),
        name=_name(sign),
        interpret=interpret,
    )(x, cos, sin)


def _apply_xla(x, cos, sin, sign):
    """XLA composition of the same rotate_half math (platform fallback)."""
    d = x.shape[-1]
    x1 = x[..., : d // 2]
    x2 = x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    c = cos[None, :, None, :].astype(jnp.float32)
    s = sin[None, :, None, :].astype(jnp.float32)
    xf = x.astype(jnp.float32)
    return (xf * c + sign * rot.astype(jnp.float32) * s).astype(x.dtype)


def _apply_platform(x, cos, sin, sign, interpret):
    """Pallas kernel on TPU, XLA composition elsewhere — chosen at
    LOWERING time (lax.platform_dependent), sitting INSIDE the custom-vjp
    rules so it is never itself differentiated (jax cannot JVP a
    pallas_call inside a cond branch)."""
    if interpret:
        return _apply(x, cos, sin, sign, True)
    return lax.platform_dependent(
        x, cos, sin,
        tpu=lambda x, c, s: _apply(x, c, s, sign, False),
        default=lambda x, c, s: _apply_xla(x, c, s, sign))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rope_one(x, cos, sin, interpret=False):
    return _apply_platform(x, cos, sin, 1.0, interpret)


def _rope_one_fwd(x, cos, sin, interpret):
    return _apply_platform(x, cos, sin, 1.0, interpret), (cos, sin)


def _rope_one_bwd(interpret, res, g):
    cos, sin = res
    # transpose of the rotation: rotate the other way
    return _apply_platform(g, cos, sin, -1.0, interpret), None, None


_rope_one.defvjp(_rope_one_fwd, _rope_one_bwd)


def fused_rope(q, k, cos, sin, interpret=False):
    """q, k: [b, s, h, d]; cos, sin: [s, d] or [1, s, 1, d] (rotate_half)."""

    def to_2d(c):
        if c.ndim == 2:
            return c
        if c.ndim == 4 and c.shape[0] == 1 and c.shape[2] == 1:
            return c.reshape(c.shape[1], c.shape[3])
        raise ValueError(f"fused_rope: unsupported cos/sin shape {c.shape}")

    cos, sin = to_2d(cos), to_2d(sin)
    if cos.shape[0] != q.shape[1]:
        raise ValueError(
            f"fused_rope: cos seq {cos.shape[0]} != q seq {q.shape[1]}"
        )
    return _rope_one(q, cos, sin, interpret), _rope_one(k, cos, sin, interpret)


# ------------------------------------------------ packed (per-token) rope
def _rope_packed_kernel(x_ref, pos_ref, cos_ref, sin_ref, o_ref, *, sign):
    """Rope with PER-TOKEN positions (packed-document pretraining): the
    cos/sin rows are gathered in-kernel via a one-hot MXU matmul — the
    canonical TPU table lookup (mosaic has no general vector gather) —
    so the [b, s, d] gathered tables never round-trip HBM."""
    x = x_ref[...].astype(jnp.float32)       # [bs, h, d]
    pos = pos_ref[...][0]                    # [8, bs] replicated -> [bs]
    cos_t = cos_ref[...]                     # [P, d] fp32
    # clamp: out-of-range positions take the last row on EVERY platform
    # (matches jnp.take's default clip; an unclamped one-hot would
    # silently zero the rotation instead)
    pos = jnp.clip(pos, 0, cos_t.shape[0] - 1)
    sin_t = sin_ref[...]
    onehot = (pos[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (1, cos_t.shape[0]), 1)).astype(jnp.float32)
    cos = (onehot @ cos_t)[:, None, :]       # [bs, 1, d]
    sin = (onehot @ sin_t)[:, None, :]
    d = x.shape[-1]
    x1 = x[..., : d // 2]
    x2 = x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    o_ref[...] = (x * cos + sign * rot * sin).astype(o_ref.dtype)


# table bytes allowed resident in VMEM for the in-kernel lookup
_PACKED_TABLE_VMEM_BUDGET = 4 << 20


def _packed_supported(x, cos_tab):
    s = x.shape[1]
    P = cos_tab.shape[0]
    bs = _seq_block(s, x.shape[2], x.shape[3], x.dtype.itemsize)
    table_bytes = 2 * P * cos_tab.shape[1] * 4
    onehot_bytes = bs * P * 4  # the in-kernel [bs, P] fp32 lookup matrix
    return (s % bs == 0
            and table_bytes + onehot_bytes <= _PACKED_TABLE_VMEM_BUDGET)


def _apply_packed(x, pos2d, cos_tab, sin_tab, sign, interpret):
    b, s, h, d = x.shape
    bs = _seq_block(s, h, d, x.dtype.itemsize)
    pos8 = jnp.repeat(pos2d.astype(jnp.int32)[:, None, :], 8, axis=1)
    return pl.pallas_call(
        functools.partial(_rope_packed_kernel, sign=sign),
        grid=(b, s // bs),
        in_specs=[
            pl.BlockSpec((None, bs, h, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((None, 8, bs), lambda i, j: (i, 0, j)),
            pl.BlockSpec(cos_tab.shape, lambda i, j: (0, 0)),
            pl.BlockSpec(sin_tab.shape, lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bs, h, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name=_name(sign),
        interpret=interpret,
    )(x, pos8, cos_tab.astype(jnp.float32), sin_tab.astype(jnp.float32))


def _xla_packed(x, pos2d, cos_tab, sin_tab, sign):
    cos = jnp.take(cos_tab, pos2d, axis=0)[:, :, None, :].astype(jnp.float32)
    sin = jnp.take(sin_tab, pos2d, axis=0)[:, :, None, :].astype(jnp.float32)
    d = x.shape[-1]
    x1 = x[..., : d // 2]
    x2 = x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    xf = x.astype(jnp.float32)
    return (xf * cos + sign * rot.astype(jnp.float32) * sin).astype(x.dtype)


def _apply_packed_platform(x, pos2d, cos_tab, sin_tab, sign, interpret):
    if interpret:
        return _apply_packed(x, pos2d, cos_tab, sin_tab, sign, True)
    if not _packed_supported(x, cos_tab):
        return _xla_packed(x, pos2d, cos_tab, sin_tab, sign)
    return lax.platform_dependent(
        x, pos2d, cos_tab, sin_tab,
        tpu=lambda x, p, c, s: _apply_packed(x, p, c, s, sign, False),
        default=lambda x, p, c, s: _xla_packed(x, p, c, s, sign))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _rope_one_packed(x, pos2d, cos_tab, sin_tab, interpret=False):
    return _apply_packed_platform(x, pos2d, cos_tab, sin_tab, 1.0, interpret)


def _rope_one_packed_fwd(x, pos2d, cos_tab, sin_tab, interpret):
    return (_apply_packed_platform(x, pos2d, cos_tab, sin_tab, 1.0,
                                   interpret),
            (pos2d, cos_tab, sin_tab))


def _rope_one_packed_bwd(interpret, res, g):
    pos2d, cos_tab, sin_tab = res
    return (_apply_packed_platform(g, pos2d, cos_tab, sin_tab, -1.0,
                                   interpret), None, None, None)


_rope_one_packed.defvjp(_rope_one_packed_fwd, _rope_one_packed_bwd)


def fused_rope_packed(q, k, cos_tab, sin_tab, pos2d, interpret=False):
    """q, k: [b, s, h, d]; cos/sin tables: [P, d]; pos2d: [b, s] int32
    per-token positions (packed documents restart at 0). Out-of-range
    positions clamp to the last table row on every platform."""
    return (_rope_one_packed(q, pos2d, cos_tab, sin_tab, interpret),
            _rope_one_packed(k, pos2d, cos_tab, sin_tab, interpret))
