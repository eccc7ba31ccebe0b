"""Manifold-constrained hyper-connections (arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606) as two Pallas TPU kernels: the mix that
opens a sublayer (`mhc_pre`) and the one that closes it (`mhc_post`).

The residual state of a token is n streams of width C, kept contiguous:
x [tokens, n * C], stream j in columns [j * C, (j + 1) * C). A sublayer F is
wrapped as

    x~     = x / sqrt(mean(x^2) + eps)              one norm over all n * C
    h      = a * (phi x~) + b                       n (n + 2) values a token
    H_pre  = sigmoid(h[:n])        H_post = 2 sigmoid(h[n:2n])
    H_res  = Sinkhorn-Knopp(exp(clip(h[2n:])))      [n, n], `iters` rounds of
                                                    columns then rows
    u      = H_pre x                                the sublayer's input [C]
    x'     = H_res x + H_post^T F(u)                [n, C]

`mhc_pre` reads x once and writes u and the token's maps; `mhc_post` reads x,
y = F(u) and the maps and writes x'. Left to XLA the first is a norm, a thin
product, twenty rounds of small reductions and a weighted sum, each a pass
or a launch of its own.

The maps of a token travel between the two as ONE float32 row of MAPS_WIDTH
lanes: [H_pre (n), H_post (n), H_res (n * n, row-major), imbalance, zeros],
`imbalance` the largest distance of a column sum of H_res from 1 after the
last round (the rows were normalised last and sum to 1).

Kernel shape, both: a grid over blocks of 128 tokens (`supports`: the tokens
fill whole blocks, a prefill chunk's 512; a decode step's 24 rows take the
XLA form of ops/kernels/nn_ops.py), a block's streams whole in VMEM. In
`mhc_pre` the product with phi runs on the MXU in ONE bf16 pass that is exact
to float32: the streams ARE bf16, and phi (float32) is
split once a call into its leading, middle and trailing bf16 parts, kept in
scratch side by side as rows of one matrix, so x (phi_hi + phi_mid +
phi_lo) costs what one thin bf16 product costs (a float32 product at
precision highest is six passes and would outlast the reading of x). The 24
values a token are then turned so that tokens lie along the lanes: the
twenty Sinkhorn rounds are a few hundred vector operations a block there,
and twenty times that with a token a sublane.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MAPS_WIDTH = LANES      # one float32 row a token: see the module docstring
BLOCK_TOKENS = 128
_PART = 32              # lanes between phi's bf16 parts in the product
_VMEM_LIMIT = 64 << 20


def supports(x_shape, n: int, dtype) -> bool:
    """Whole blocks of tokens, whole lanes a stream, bf16 streams (the
    one-pass product is exact for them alone), and maps that fit a part. A
    decode step's few rows take the XLA form: alone on the chip it is within
    a tenth of the kernels there (18.3 against 16.6 us an application at 24
    rows; PERF.md section 6, PR 36), and a block that reaches past the array
    is not asked of the chip."""
    width = x_shape[-1]
    tokens = math.prod(x_shape[:-1])
    return (tokens > 0 and tokens % BLOCK_TOKENS == 0 and width % n == 0
            and (width // n) % LANES == 0 and n * (n + 2) + 1 <= _PART
            and dtype == jnp.bfloat16)


def _pre_kernel(a_ref, x_ref, phi_ref, b_ref, u_ref, maps_ref, parts_ref, *,
                n, eps, clamp, iters):
    m, tm = n * (n + 2), x_ref.shape[0]
    c = x_ref.shape[1] // n

    @pl.when(pl.program_id(0) == 0)
    def _split_phi():
        # phi = hi + mid + lo, each bf16: rows [0, m), [32, 32 + m), [64, ..)
        parts_ref[...] = jnp.zeros_like(parts_ref)
        rest = phi_ref[...]
        for k in range(3):
            part = rest.astype(jnp.bfloat16)
            parts_ref[k * _PART:k * _PART + m, :] = part
            rest = rest - part.astype(jnp.float32)

    h3 = jax.lax.dot_general(x_ref[...], parts_ref[...],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [tm, 128]
    ss = jnp.zeros((tm, 1), jnp.float32)
    for j in range(n):
        xj = x_ref[:, j * c:(j + 1) * c].astype(jnp.float32)
        ss = ss + jnp.sum(xj * xj, axis=1, keepdims=True)
    r = jax.lax.rsqrt(ss / (n * c) + eps)                         # [tm, 1]
    h = (h3[:, :_PART] + h3[:, _PART:2 * _PART]
         + h3[:, 2 * _PART:3 * _PART]) * r
    # tokens along the lanes from here: [128, tm], rows [0, m) the maps
    ht = jnp.concatenate(
        [h, jnp.zeros((tm, LANES - _PART), jnp.float32)], axis=1).T
    row = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    a = jnp.where(row < n, a_ref[0], jnp.where(row < 2 * n, a_ref[1],
                                               a_ref[2]))
    ht = ht[:m] * a + b_ref[...]
    pre = jax.nn.sigmoid(ht[:n])
    post = 2.0 * jax.nn.sigmoid(ht[n:2 * n])
    # H_res by rows: rows[i] is [n (column j), tm]
    rows = [jnp.exp(jnp.clip(ht[2 * n + i * n:2 * n + (i + 1) * n],
                             clamp[0], clamp[1])) for i in range(n)]
    for _ in range(iters):
        inv = 1.0 / (functools.reduce(jnp.add, rows) + eps)   # column sums
        rows = [mi * inv for mi in rows]
        rows = [mi / (jnp.sum(mi, axis=0, keepdims=True) + eps)
                for mi in rows]
    off = jnp.max(jnp.abs(functools.reduce(jnp.add, rows) - 1.0), axis=0,
                  keepdims=True)
    maps = jnp.concatenate(
        [pre, post, *rows, off,
         jnp.zeros((LANES - m - 1, tm), jnp.float32)], axis=0).T  # [tm, 128]
    maps_ref[...] = maps
    u = jnp.zeros((tm, c), jnp.float32)
    for j in range(n):
        u = u + maps[:, j:j + 1] * x_ref[:, j * c:(j + 1) * c].astype(
            jnp.float32)
    u_ref[...] = u.astype(u_ref.dtype)


def _post_kernel(x_ref, y_ref, maps_ref, o_ref, *, n):
    c = y_ref.shape[1]
    maps = maps_ref[...]
    y = y_ref[...].astype(jnp.float32)
    xs = [x_ref[:, j * c:(j + 1) * c].astype(jnp.float32) for j in range(n)]
    for i in range(n):
        acc = maps[:, n + i:n + i + 1] * y
        for j in range(n):
            k = 2 * n + i * n + j
            acc = acc + maps[:, k:k + 1] * xs[j]
        o_ref[:, i * c:(i + 1) * c] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "eps", "clamp", "iters",
                                             "interpret"))
def mhc_pre(x, phi, a, b, *, n, eps, clamp, iters, interpret=False):
    """x [tokens, n * C] bf16; phi [n (n + 2), n * C] float32, rows the maps
    of H_pre, H_post, then H_res row-major; a [3] and b [n (n + 2)]
    float32. Returns (u [tokens, C], maps [tokens, MAPS_WIDTH] float32)."""
    t, width = x.shape
    m, c = n * (n + 2), width // n
    tm = BLOCK_TOKENS
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t // tm,),
        in_specs=[pl.BlockSpec((tm, width), lambda i, a: (i, 0)),
                  pl.BlockSpec((m, width), lambda i, a: (0, 0)),
                  pl.BlockSpec((m, 1), lambda i, a: (0, 0))],
        out_specs=[pl.BlockSpec((tm, c), lambda i, a: (i, 0)),
                   pl.BlockSpec((tm, MAPS_WIDTH), lambda i, a: (i, 0))],
        scratch_shapes=[pltpu.VMEM((LANES, width), jnp.bfloat16)],
    )
    return pl.pallas_call(
        functools.partial(_pre_kernel, n=n, eps=eps, clamp=clamp,
                          iters=iters),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t, c), x.dtype),
                   jax.ShapeDtypeStruct((t, MAPS_WIDTH), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="mhc_pre",
        interpret=interpret,
    )(a.astype(jnp.float32), x, phi.astype(jnp.float32),
      b.astype(jnp.float32).reshape(m, 1))


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def mhc_post(x, y, maps, *, n, interpret=False):
    """x [tokens, n * C], y [tokens, C], maps as mhc_pre returns them ->
    x' [tokens, n * C] = H_res x + H_post^T y."""
    t, width = x.shape
    c = width // n
    tm = BLOCK_TOKENS
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n),
        grid=(t // tm,),
        in_specs=[pl.BlockSpec((tm, width), lambda i: (i, 0)),
                  pl.BlockSpec((tm, c), lambda i: (i, 0)),
                  pl.BlockSpec((tm, MAPS_WIDTH), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tm, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, width), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="mhc_post",
        interpret=interpret,
    )(x, y.astype(x.dtype), maps)
