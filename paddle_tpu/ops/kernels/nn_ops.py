"""NN kernels: activations, norms, conv/pool, embedding, losses, attention.

Reference surface: paddle/phi/kernels/*/{activation,softmax,conv,pool,
batch_norm,layer_norm,embedding,cross_entropy,...}_kernel plus the fused ops in
paddle/fluid/operators/fused/. On TPU each is a handful of jnp/lax ops that XLA
fuses; attention additionally has a Pallas fast path (ops/pallas/flash_attention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
import numpy as _np

from ...core import random as _random
from ...core.dtype import convert_dtype


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


# ---------------------------------------------------------------- activations
def relu(x):
    return jnp.maximum(x, 0)


def relu6(x):
    return jnp.clip(x, 0, 6)


def sigmoid(x):
    return jax.nn.sigmoid(x)


def log_sigmoid(x):
    return jax.nn.log_sigmoid(x)


def gelu(x, approximate=False):
    return jax.nn.gelu(x, approximate=approximate)


def silu(x):
    return jax.nn.silu(x)


def swish(x):
    return jax.nn.silu(x)


def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


def leaky_relu(x, negative_slope=0.01):
    return jax.nn.leaky_relu(x, negative_slope)


def elu(x, alpha=1.0):
    return jax.nn.elu(x, alpha)


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772):
    return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))


def celu(x, alpha=1.0):
    return jax.nn.celu(x, alpha)


def softplus(x, beta=1.0, threshold=20.0):
    return jnp.where(x * beta > threshold, x, (1.0 / beta) * jax.nn.softplus(beta * x))


def softshrink(x, threshold=0.5):
    return jnp.where(x > threshold, x - threshold, jnp.where(x < -threshold, x + threshold, 0.0))


def hardshrink(x, threshold=0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


def hardtanh(x, min=-1.0, max=1.0):
    return jnp.clip(x, min, max)


def hardsigmoid(x, slope=1.0 / 6, offset=0.5):
    return jnp.clip(x * slope + offset, 0.0, 1.0)


def hardswish(x):
    return x * jnp.clip(x + 3.0, 0.0, 6.0) / 6.0


def tanhshrink(x):
    return x - jnp.tanh(x)


def thresholded_relu(x, threshold=1.0):
    return jnp.where(x > threshold, x, 0.0)


def prelu(x, weight):
    w = weight
    if w.ndim == 1 and x.ndim > 1 and w.shape[0] > 1:
        w = w.reshape((1, -1) + (1,) * (x.ndim - 2))
    return jnp.where(x >= 0, x, w * x)


def rrelu(x, lower=1.0 / 8, upper=1.0 / 3, training=True):
    if training:
        key = _random.next_key()
        a = jax.random.uniform(key, x.shape, x.dtype, lower, upper)
    else:
        a = (lower + upper) / 2.0
    return jnp.where(x >= 0, x, a * x)


def glu(x, axis=-1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)


def maxout(x, groups, axis=1):
    shape = list(x.shape)
    c = shape[axis]
    shape[axis : axis + 1] = [c // groups, groups]
    return jnp.max(jnp.reshape(x, shape), axis=axis + 1)


# ----------------------------------------------------------------- softmaxes
def softmax(x, axis=-1):
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1):
    key = _random.next_key()
    g = -jnp.log(-jnp.log(jax.random.uniform(key, x.shape, x.dtype, 1e-20, 1.0)))
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:
        idx = jnp.argmax(y, axis=axis, keepdims=True)
        y_hard = jnp.put_along_axis(jnp.zeros_like(y), idx, 1.0, axis=axis, inplace=False)
        y = lax.stop_gradient(y_hard - y) + y  # straight-through estimator
    return y


# ------------------------------------------------------------------- linear
def linear(x, weight, bias=None):
    """paddle: weight is [in, out] (not transposed)."""
    out = jnp.matmul(x, weight)
    if bias is not None:
        out = out + bias
    return out


def embedding(x, weight, padding_idx=None, sparse=False):
    out = jnp.take(weight, x, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (x == padding_idx)[..., None]
        out = jnp.where(mask, jnp.zeros((), out.dtype), out)
    return out


def one_hot(x, num_classes):
    return jax.nn.one_hot(x, num_classes, dtype=jnp.float32)


# ------------------------------------------------------------------- dropout
def dropout(x, p=0.5, training=True, mode="upscale_in_train", axis=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return x * (1.0 - p)
        return x
    key = _random.next_key()
    shape = x.shape
    if axis is not None:
        axes = (axis,) if isinstance(axis, int) else tuple(axis)
        shape = tuple(s if i in axes else 1 for i, s in enumerate(x.shape))
    keep = jax.random.bernoulli(key, 1.0 - p, shape)
    if mode == "upscale_in_train":
        return jnp.where(keep, x / (1.0 - p), jnp.zeros((), x.dtype))
    return jnp.where(keep, x, jnp.zeros((), x.dtype))


def dropout2d(x, p=0.5, training=True, data_format="NCHW"):
    axis = (0, 1) if data_format == "NCHW" else (0, 3)
    return dropout(x, p, training, axis=axis)


# ---------------------------------------------------------------------- norm
def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    axes = tuple(range(x.ndim - len(normalized_shape), x.ndim))
    # TPU numerics: accumulate statistics in fp32 regardless of input dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + epsilon)
    y = y.astype(x.dtype)
    if weight is not None:
        y = y * weight
    if bias is not None:
        y = y + bias
    return y


def rms_norm(x, weight=None, epsilon=1e-6):
    from .. import pallas as _pallas

    if (
        weight is not None
        and weight.ndim == 1
        and _pallas.pallas_enabled()
    ):
        from ..pallas.fused_norm import fused_rms_norm as _fused

        return _fused(x, weight, epsilon,
                      interpret=_pallas.interpret_mode())
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = (xf * lax.rsqrt(ms + epsilon)).astype(x.dtype)
    if weight is not None:
        y = y * weight
    return y


def batch_norm(
    x, running_mean, running_var, weight=None, bias=None,
    training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW",
):
    """Returns (y, new_running_mean, new_running_var)."""
    c_axis = 1 if data_format == "NCHW" else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    bshape = tuple(x.shape[c_axis] if i == c_axis else 1 for i in range(x.ndim))
    xf = x.astype(jnp.float32)
    if training:
        mean = jnp.mean(xf, axis=axes)
        var = jnp.var(xf, axis=axes)
        new_mean = momentum * running_mean + (1.0 - momentum) * mean
        new_var = momentum * running_var + (1.0 - momentum) * var
    else:
        mean, var = running_mean, running_var
        new_mean, new_var = running_mean, running_var
    y = (xf - mean.reshape(bshape)) * lax.rsqrt(var.reshape(bshape) + epsilon)
    y = y.astype(x.dtype)
    if weight is not None:
        y = y * weight.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    return y, new_mean, new_var


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5, data_format="NCHW"):
    if data_format != "NCHW":
        x = jnp.moveaxis(x, -1, 1)
    n, c = x.shape[0], x.shape[1]
    g = num_groups
    xr = x.reshape((n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, xr.ndim))
    mean = jnp.mean(xr, axis=axes, keepdims=True)
    var = jnp.var(xr, axis=axes, keepdims=True)
    y = ((xr - mean) * lax.rsqrt(var + epsilon)).reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if weight is not None:
        y = y * weight.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    if data_format != "NCHW":
        y = jnp.moveaxis(y, 1, -1)
    return y


def instance_norm(x, weight=None, bias=None, epsilon=1e-5, data_format="NCHW"):
    axes = tuple(range(2, x.ndim)) if data_format == "NCHW" else tuple(range(1, x.ndim - 1))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + epsilon)
    c = x.shape[1] if data_format == "NCHW" else x.shape[-1]
    bshape = (1, c) + (1,) * (x.ndim - 2) if data_format == "NCHW" else (1,) * (x.ndim - 1) + (c,)
    if weight is not None:
        y = y * weight.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    return y


def normalize(x, p=2, axis=1, epsilon=1e-12):
    norm = jnp.linalg.norm(x, ord=p, axis=axis, keepdims=True)
    return x / jnp.maximum(norm, epsilon)


# ---------------------------------------------------------------- conv/pool
def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCHW"):
    stride, dilation = _pair(stride), _pair(dilation)
    if isinstance(padding, str):
        pad = padding.upper()
    else:
        p = _pair(padding) if not (isinstance(padding, (list, tuple)) and len(padding) == 4) else padding
        pad = [(p[0], p[0]), (p[1], p[1])] if len(p) == 2 else [(p[0], p[1]), (p[2], p[3])]
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape,
        ("NCHW", "OIHW", "NCHW") if data_format == "NCHW" else ("NHWC", "HWIO", "NHWC"),
    )
    out = lax.conv_general_dilated(
        x, weight, window_strides=stride, padding=pad,
        rhs_dilation=dilation, dimension_numbers=dn, feature_group_count=groups,
        preferred_element_type=jnp.float32 if x.dtype == jnp.float32 else None,
    )
    if bias is not None:
        bshape = (1, -1, 1, 1) if data_format == "NCHW" else (1, 1, 1, -1)
        out = out + bias.reshape(bshape)
    return out


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCL"):
    x4 = x[..., None]  # NCL -> NCL1
    w4 = weight[..., None]
    s = stride if isinstance(stride, int) else stride[0]
    p = padding if isinstance(padding, (int, str)) else padding[0]
    d = dilation if isinstance(dilation, int) else dilation[0]
    pad = p if isinstance(p, str) else (p, 0)
    out = conv2d(x4, w4, bias, stride=(s, 1), padding=pad if isinstance(pad, str) else [pad[0], 0], dilation=(d, 1), groups=groups)
    return out[..., 0]


def conv2d_transpose(
    x, weight, bias=None, stride=1, padding=0, output_padding=0, dilation=1, groups=1, data_format="NCHW",
):
    stride, dilation = _pair(stride), _pair(dilation)
    p = _pair(padding)
    op = _pair(output_padding)
    # weight layout paddle: [in, out//groups, kh, kw]
    kh, kw = weight.shape[2], weight.shape[3]
    pad = [
        (dilation[0] * (kh - 1) - p[0], dilation[0] * (kh - 1) - p[0] + op[0]),
        (dilation[1] * (kw - 1) - p[1], dilation[1] * (kw - 1) - p[1] + op[1]),
    ]
    w = jnp.flip(weight, axis=(2, 3))
    w = jnp.swapaxes(w, 0, 1)  # -> [out//groups, in, kh, kw]
    if groups > 1:
        w = jnp.concatenate(jnp.split(w, groups, axis=1), axis=0)
    dn = lax.conv_dimension_numbers(x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))
    out = lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=pad, lhs_dilation=stride,
        rhs_dilation=dilation, dimension_numbers=dn, feature_group_count=groups,
    )
    if bias is not None:
        out = out + bias.reshape(1, -1, 1, 1)
    return out


def _ceil_hi_pad(dim, k, s, p):
    """Extra high padding so ceil_mode keeps a partial final window — but 0
    if that extra window would lie entirely in padding (the reference drops
    it: pooling output-size rule `(out-1)*stride >= dim + pad` => out -= 1).
    Without the drop, exclusive avg pools divide by a 0 count (NaN) and max
    pools emit a -inf rim."""
    size = dim + 2 * p
    rem = (size - k) % s
    if rem == 0:
        return 0
    start = ((size - k) // s + 1) * s
    if start >= dim + p:
        return 0
    return s - rem


def _pool2d_geometry(x, k, s, p, ceil_mode, data_format):
    """Window/stride/pad tuples for a 2-d pool; ceil_mode extends the high
    pad so a partial final window is kept (reference pooling.cc ceil path)."""
    hw = (x.shape[2], x.shape[3]) if data_format == "NCHW" else (x.shape[1], x.shape[2])
    hi = list(p)
    if ceil_mode:
        for i in range(2):
            hi[i] += _ceil_hi_pad(hw[i], k[i], s[i], p[i])
    if data_format == "NCHW":
        return ((1, 1, k[0], k[1]), (1, 1, s[0], s[1]),
                ((0, 0), (0, 0), (p[0], hi[0]), (p[1], hi[1])))
    return ((1, k[0], k[1], 1), (1, s[0], s[1], 1),
            ((0, 0), (p[0], hi[0]), (p[1], hi[1]), (0, 0)))


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False, data_format="NCHW"):
    k = _pair(kernel_size)
    s = _pair(stride) if stride is not None else k
    p = _pair(padding)
    window, strides, pads = _pool2d_geometry(x, k, s, p, ceil_mode, data_format)
    # -inf init keeps this on the reduce_window_max primitive (differentiable)
    neg = -jnp.inf if jnp.issubdtype(x.dtype, jnp.inexact) else jnp.iinfo(x.dtype).min
    return lax.reduce_window(x, neg, lax.max, window, strides, pads)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False, exclusive=True, data_format="NCHW"):
    k = _pair(kernel_size)
    s = _pair(stride) if stride is not None else k
    p = _pair(padding)
    window, strides, pads = _pool2d_geometry(x, k, s, p, ceil_mode, data_format)
    summed = lax.reduce_window(x, _np.zeros((), x.dtype), lax.add, window, strides, pads)
    if exclusive and (p[0] or p[1] or ceil_mode):
        # exclusive divides by the count of REAL elements; padding and the
        # ceil-mode extension both count as excluded padding
        ones = jnp.ones_like(x)
        counts = lax.reduce_window(ones, _np.zeros((), x.dtype), lax.add, window, strides, pads)
        return summed / counts
    return summed / (k[0] * k[1])


def adaptive_avg_pool2d(x, output_size, data_format="NCHW"):
    out_h, out_w = _pair(output_size)
    if data_format == "NCHW":
        h, w = x.shape[2], x.shape[3]
    else:
        h, w = x.shape[1], x.shape[2]
    if h % out_h == 0 and w % out_w == 0:
        k = (h // out_h, w // out_w)
        return avg_pool2d(x, k, stride=k, padding=0, data_format=data_format)
    # general case: mean over computed bins via resize trick
    axes = (2, 3) if data_format == "NCHW" else (1, 2)
    return jnp.mean(x, axis=axes, keepdims=True) if (out_h, out_w) == (1, 1) else _adaptive_pool_general(x, out_h, out_w, axes)


def _adaptive_pool_general(x, out_h, out_w, axes, reducer=jnp.mean):
    import numpy as np

    h, w = x.shape[axes[0]], x.shape[axes[1]]
    rows = [slice(int(np.floor(i * h / out_h)), int(np.ceil((i + 1) * h / out_h))) for i in range(out_h)]
    cols = [slice(int(np.floor(j * w / out_w)), int(np.ceil((j + 1) * w / out_w))) for j in range(out_w)]
    out_rows = []
    for r in rows:
        row_cells = []
        for c in cols:
            idx = [jnp.s_[:]] * x.ndim
            idx[axes[0]], idx[axes[1]] = r, c
            cell = reducer(x[tuple(idx)], axis=axes, keepdims=True)
            row_cells.append(cell)
        out_rows.append(jnp.concatenate(row_cells, axis=axes[1]))
    return jnp.concatenate(out_rows, axis=axes[0])


def adaptive_max_pool2d(x, output_size, data_format="NCHW"):
    out_h, out_w = _pair(output_size)
    axes = (2, 3) if data_format == "NCHW" else (1, 2)
    h, w = x.shape[axes[0]], x.shape[axes[1]]
    if h % out_h == 0 and w % out_w == 0:
        k = (h // out_h, w // out_w)
        return max_pool2d(x, k, stride=k, padding=0, data_format=data_format)
    return _adaptive_pool_general(x, out_h, out_w, axes, reducer=jnp.max)


def interpolate(x, size=None, scale_factor=None, mode="nearest", align_corners=False, data_format="NCHW"):
    """Reference F.interpolate: 3-D (linear, NCW), 4-D (bilinear/bicubic,
    NCHW) and 5-D (trilinear, NCDHW) resampling, channel-first or -last."""
    nsp = x.ndim - 2
    channel_last = data_format in ("NWC", "NHWC", "NDHWC")
    if channel_last:
        n, c, sp = x.shape[0], x.shape[-1], x.shape[1:-1]
    else:
        n, c, sp = x.shape[0], x.shape[1], x.shape[2:]
    if size is None:
        sf = (tuple(scale_factor) if isinstance(scale_factor, (list, tuple))
              else (scale_factor,) * nsp)
        size = tuple(int(s * f) for s, f in zip(sp, sf))
    elif isinstance(size, (list, tuple)):
        size = tuple(int(s) for s in size)
    else:
        size = (int(size),) * nsp
    if mode == "area":
        # reference 'area' = adaptive average pooling (block means), NOT a
        # linear resample
        out = x
        for ax_i, new_len in enumerate(size):
            axis = (1 + ax_i) if channel_last else (2 + ax_i)
            old_len = out.shape[axis]
            if new_len == old_len:
                continue
            # mean over each adaptive window [floor(i*old/new),
            # ceil((i+1)*old/new)) along this axis
            starts = (jnp.arange(new_len) * old_len) // new_len
            ends = -(-(jnp.arange(1, new_len + 1) * old_len) // new_len)
            pos = jnp.arange(old_len)
            w = ((pos[None, :] >= starts[:, None])
                 & (pos[None, :] < ends[:, None])).astype(out.dtype)
            w = w / w.sum(axis=1, keepdims=True)
            out = jnp.moveaxis(
                jnp.tensordot(w, jnp.moveaxis(out, axis, 0), axes=1),
                0, axis)
        return out
    if mode == "nearest" and not align_corners:
        # reference nearest (align_corners=False): src = floor(dst*scale),
        # not jax.image.resize's half-pixel rounding
        out = x
        for ax_i, new_len in enumerate(size):
            axis = (1 + ax_i) if channel_last else (2 + ax_i)
            old_len = out.shape[axis]
            if new_len == old_len:
                continue
            src = jnp.clip((jnp.arange(new_len) * old_len) // new_len, 0,
                           old_len - 1)
            out = jnp.take(out, src, axis=axis)
        return out
    method = {"nearest": "nearest", "linear": "linear", "bilinear": "bilinear",
              "trilinear": "trilinear", "bicubic": "bicubic",
              "cubic": "bicubic"}[mode]
    if align_corners and mode != "nearest":
        # jax.image.resize only samples the half-pixel grid, so build the
        # corner-aligned grid explicitly: out coord i maps to
        # i*(in-1)/(out-1), then separable linear interpolation via one
        # gather+lerp per spatial axis (reference bilinear_interp_kernel
        # align_corners branch).
        if mode in ("bicubic", "cubic"):
            raise NotImplementedError(
                "align_corners=True bicubic is not supported; use "
                "bilinear or align_corners=False")
        out = x
        for ax_i, new_len in enumerate(size):
            axis = (1 + ax_i) if channel_last else (2 + ax_i)
            old_len = out.shape[axis]
            if new_len == old_len:
                continue
            if new_len == 1 or old_len == 1:
                coords = jnp.zeros((new_len,), x.dtype)
            else:
                coords = jnp.arange(new_len, dtype=jnp.float32) \
                    * ((old_len - 1) / (new_len - 1))
            lo = jnp.clip(jnp.floor(coords).astype(jnp.int32), 0, old_len - 1)
            hi = jnp.clip(lo + 1, 0, old_len - 1)
            w_hi = (coords - lo.astype(coords.dtype)).astype(x.dtype)
            shape = [1] * out.ndim
            shape[axis] = new_len
            w_hi = w_hi.reshape(shape)
            out = jnp.take(out, lo, axis=axis) * (1 - w_hi) \
                + jnp.take(out, hi, axis=axis) * w_hi
        return out
    target = (n, *size, c) if channel_last else (n, c, *size)
    return jax.image.resize(x, target, method=method)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1):
    k = _pair(kernel_sizes)
    s = _pair(strides)
    p = _pair(paddings)
    d = _pair(dilations)
    n, c, h, w = x.shape
    patches = lax.conv_general_dilated_patches(
        x, filter_shape=k, window_strides=s, padding=[(p[0], p[0]), (p[1], p[1])],
        rhs_dilation=d, dimension_numbers=lax.conv_dimension_numbers(x.shape, (1, c, *k), ("NCHW", "OIHW", "NCHW")),
    )
    return patches.reshape(n, c * k[0] * k[1], -1)


def pixel_shuffle(x, upscale_factor, data_format="NCHW"):
    r = upscale_factor
    n, c, h, w = x.shape
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
    return x.reshape(n, c // (r * r), h * r, w * r)


# ------------------------------------------------------------------- losses
def mse_loss(input, label, reduction="mean"):
    loss = jnp.square(input - label)
    return _reduce(loss, reduction)


def l1_loss(input, label, reduction="mean"):
    return _reduce(jnp.abs(input - label), reduction)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0):
    diff = jnp.abs(input - label)
    loss = jnp.where(diff < delta, 0.5 * diff * diff / delta, diff - 0.5 * delta)
    return _reduce(loss, reduction)


def _reduce(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def cross_entropy(
    input, label, weight=None, ignore_index=-100, reduction="mean",
    soft_label=False, axis=-1, use_softmax=True, label_smoothing=0.0,
):
    """paddle.nn.functional.cross_entropy (logits in, per reference default)."""
    if use_softmax:
        logp = jax.nn.log_softmax(input, axis=axis)
    else:
        logp = jnp.log(jnp.clip(input, 1e-12, None))
    n_classes = input.shape[axis]
    if soft_label:
        soft = label
        if label_smoothing > 0.0:
            soft = soft * (1.0 - label_smoothing) + label_smoothing / n_classes
        loss = -jnp.sum(soft * logp, axis=axis)
        valid = None
    else:
        lbl = label
        if lbl.ndim == input.ndim and lbl.shape[axis] == 1:
            lbl = jnp.squeeze(lbl, axis=axis)
        lbl = lbl.astype(jnp.int32)
        valid = lbl != ignore_index
        safe = jnp.where(valid, lbl, 0)
        picked = jnp.take_along_axis(logp, safe[..., None] if axis in (-1, input.ndim - 1) else jnp.expand_dims(safe, axis), axis=axis)
        picked = jnp.squeeze(picked, axis=axis)
        if label_smoothing > 0.0:
            smooth_term = -jnp.mean(logp, axis=axis)
            loss = (1.0 - label_smoothing) * (-picked) + label_smoothing * smooth_term
        else:
            loss = -picked
        sample_w = jnp.take(weight, safe) if weight is not None else None
        if sample_w is not None:
            loss = loss * sample_w
        loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        if valid is not None:
            if weight is not None:
                # paddle semantics: weighted mean divides by the weight sum
                denom = jnp.maximum(jnp.sum(jnp.where(valid, sample_w, 0.0)), 1e-12)
            else:
                denom = jnp.maximum(jnp.sum(valid.astype(loss.dtype)), 1.0)
            return jnp.sum(loss) / denom
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def causal_lm_loss(logits, labels, segments=None, ignore_index=-100):
    """Next-token loss of a causal language model: the mean, over the rows
    that count, of -log softmax(logits[b, i])[labels[b, i + 1]].

    logits [b, s, v] in the dtype the head produced, labels [b, s] UNSHIFTED
    (labels = input_ids is the natural call), segments optional [b, s]
    packed-document ids (padding -1). The shift is on the labels: every one
    of the b x s rows is read, and the last position of a sequence, a pair
    that crosses a packed document's boundary, padding and a label equal to
    ignore_index count for nothing. Equal to cross_entropy(logits[:, :-1]
    .reshape(-1, v), labels[:, 1:].reshape(-1)), without the copy that slice
    forces (s - 1 rows fill no tile) and without an array of the logits'
    size in float32: the row statistics are float32 sums inside reductions
    over the logits as they lie, and the gradient is one elementwise pass
    written in the logits' dtype (see _next_token_nll)."""
    lab = labels.astype(jnp.int32)
    s = lab.shape[1]
    keep = lax.broadcasted_iota(jnp.int32, lab.shape, 1) < s - 1
    if segments is not None:
        nxt = jnp.roll(segments, -1, axis=1)
        keep &= (nxt == segments) & (nxt >= 0)
    target = jnp.where(keep, jnp.roll(lab, -1, axis=1), ignore_index)
    return _next_token_nll(logits, target, int(ignore_index))


def _row_targets(logits, target):
    """[.., v] bool: the class each row is scored against. A comparison with
    an iota and not a gather: it fuses into the pass that reads the logits,
    partitions over a sharded vocabulary, and no class equals a negative
    ignore_index."""
    classes = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return classes == target[..., None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _next_token_nll(logits, target, ignore_index):
    return _next_token_nll_fwd(logits, target, ignore_index)[0]


def _next_token_nll_fwd(logits, target, ignore_index):
    x = logits.astype(jnp.float32)  # in registers: every use is a reduction
    m = jnp.max(x, axis=-1)
    shifted = x - m[..., None]
    log_z = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    picked = jnp.sum(jnp.where(_row_targets(logits, target), shifted, 0.0),
                     axis=-1)
    valid = target != ignore_index
    count = jnp.maximum(jnp.sum(valid), 1).astype(jnp.float32)
    loss = jnp.sum(jnp.where(valid, log_z - picked, 0.0)) / count
    return loss, (logits, m + log_z, target, count)


def _next_token_nll_bwd(ignore_index, residuals, g):
    logits, lse, target, count = residuals
    weight = jnp.where(target != ignore_index, g / count, 0.0)
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    d = (p - _row_targets(logits, target)) * weight[..., None]
    return d.astype(logits.dtype), None


_next_token_nll.defvjp(_next_token_nll_fwd, _next_token_nll_bwd)


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean"):
    return _nll(input, label, weight, ignore_index, reduction)


def _nll(logp, label, weight, ignore_index, reduction):
    lbl = label.astype(jnp.int32)
    valid = lbl != ignore_index
    safe = jnp.where(valid, lbl, 0)
    picked = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    loss = -picked
    if weight is not None:
        w = jnp.take(weight, safe)
        loss = loss * w
    loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        if weight is not None:
            denom = jnp.sum(jnp.where(valid, jnp.take(weight, safe), 0.0))
        else:
            denom = jnp.maximum(jnp.sum(valid.astype(loss.dtype)), 1.0)
        return jnp.sum(loss) / denom
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def binary_cross_entropy(input, label, weight=None, reduction="mean"):
    eps = 1e-12
    loss = -(label * jnp.log(jnp.clip(input, eps, None)) + (1 - label) * jnp.log(jnp.clip(1 - input, eps, None)))
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


def binary_cross_entropy_with_logits(input, label, weight=None, reduction="mean", pos_weight=None):
    max_val = jnp.maximum(-input, 0.0)
    if pos_weight is not None:
        log_w = (pos_weight - 1.0) * label + 1.0
        loss = (1 - label) * input + log_w * (jnp.log(1 + jnp.exp(-jnp.abs(input))) + max_val)
    else:
        loss = (1 - label) * input + max_val + jnp.log(jnp.exp(-max_val) + jnp.exp(-input - max_val))
    if weight is not None:
        loss = loss * weight
    return _reduce(loss, reduction)


def kl_div(input, label, reduction="mean", log_target=False):
    if log_target:
        loss = jnp.exp(label) * (label - input)
    else:
        loss = label * (jnp.log(jnp.clip(label, 1e-12, None)) - input)
    if reduction == "batchmean":
        return jnp.sum(loss) / input.shape[0]
    return _reduce(loss, reduction)


def label_smooth(label, prior_dist=None, epsilon=0.1):
    n = label.shape[-1]
    if prior_dist is not None:
        return (1.0 - epsilon) * label + epsilon * prior_dist
    return (1.0 - epsilon) * label + epsilon / n


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean"):
    loss = jnp.where(label == 1.0, input, jnp.maximum(0.0, margin - input))
    return _reduce(loss, reduction)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    dot = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.linalg.norm(x1, axis=axis)
    n2 = jnp.linalg.norm(x2, axis=axis)
    return dot / jnp.maximum(n1 * n2, eps)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0, reduction="sum"):
    p = jax.nn.sigmoid(logit)
    ce = binary_cross_entropy_with_logits(logit, label, reduction="none")
    p_t = p * label + (1 - p) * (1 - label)
    loss = ce * ((1 - p_t) ** gamma)
    if alpha >= 0:
        alpha_t = alpha * label + (1 - alpha) * (1 - label)
        loss = alpha_t * loss
    if normalizer is not None:
        loss = loss / normalizer
    return _reduce(loss, reduction)


# ----------------------------------------------------------------- attention
def scaled_dot_product_attention(
    query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False, training=True, scale=None,
):
    """Reference attention (paddle incubate F.scaled_dot_product_attention;
    fused flash kernel at phi/kernels/gpu/flash_attn_kernel.cu). Layout:
    [batch, seq, heads, head_dim]. The Pallas flash path (ops/pallas) overrides
    this for long sequences on real TPU.
    """
    b, sq, h, d = query.shape
    sk = key.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    from ...core import flags as _flags
    from .. import pallas as _pallas
    from ..pallas.flash_attention import supports as _flash_supports

    flash_ok = (
        _flags.get_flag("use_flash_attention")
        and _flash_supports(
            query.shape, key.shape, attn_mask,
            dropout_p if training else 0.0, is_causal,
        )
    )
    if flash_ok and _pallas.interpret_mode():
        from ..pallas.flash_attention import flash_attention_tuned as _flash

        return _flash(query, key, value, scale, is_causal, interpret=True)
    if flash_ok:
        # the pallas-vs-XLA choice happens at LOWERING time inside the
        # kernel's custom vjp (lax.platform_dependent): a program lowered
        # for 'tpu' — including jax.export from a CPU host — embeds the
        # Mosaic kernel, while the same trace stays runnable on CPU.
        # Block-size autotuning only on a real TPU backend: timing the
        # dense fallback (where blocks are no-ops) would cache a noise
        # winner that later steers the TPU export.
        from ..pallas.flash_attention import (
            flash_attention_platform,
            flash_attention_platform_tuned,
            on_mesh,
        )

        _flash_pd = (flash_attention_platform_tuned
                     if jax.default_backend() == "tpu"
                     else flash_attention_platform)
        # under a device mesh the kernel has to sit inside a shard_map
        return on_mesh(lambda q, k, v: _flash_pd(q, k, v, scale, is_causal),
                       query, key, value)
    return _sdpa_xla(query, key, value, attn_mask, dropout_p, is_causal,
                     training, scale)


def _sdpa_xla(query, key, value, attn_mask, dropout_p, is_causal, training,
              scale):
    b, sq, h, d = query.shape
    sk = key.shape[1]
    q = jnp.einsum("bqhd->bhqd", query)
    k = jnp.einsum("bkhd->bhkd", key)
    v = jnp.einsum("bkhd->bhkd", value)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if is_causal:
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, jnp.finfo(jnp.float32).min)
        else:
            logits = logits + attn_mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(query.dtype)
    if dropout_p > 0.0 and training:
        probs = dropout(probs, dropout_p, training=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return jnp.einsum("bhqd->bqhd", out)


# ------------------------------------------------------------ rope (fused op)
def rotary_position_embedding(q, k, cos, sin, rotate_half=True):
    """Reference: incubate fused_rotary_position_embedding.
    q,k: [b, s, h, d]; cos,sin: [s, d] or broadcastable."""
    from .. import pallas as _pallas

    # fused path accepts cos/sin as [s, d] or the canonical broadcast layout
    # [1, s, 1, d] (seq at axis 1); anything else uses the XLA composition
    def _seq_major(c):
        return c.ndim == 2 or (
            c.ndim == 4 and c.shape[0] == 1 and c.shape[2] == 1
        )

    fused_ok = (
        rotate_half
        and _seq_major(cos)
        and _seq_major(sin)
        and q.shape[1] == (cos.shape[1] if cos.ndim == 4 else cos.shape[0])
    )
    if fused_ok:
        from ..pallas.rope import fused_rope as _fused

        # kernel on TPU, XLA composition elsewhere — the choice happens at
        # lowering time inside _rope_one's custom vjp (see ops/pallas/rope)
        return _fused(q, k, cos, sin, interpret=_pallas.interpret_mode())
    return _rope_xla(q, k, cos, sin, rotate_half)


def _rope_xla(q, k, cos, sin, rotate_half):
    def rot(x):
        if rotate_half:
            x1, x2 = jnp.split(x, 2, axis=-1)
            return jnp.concatenate([-x2, x1], axis=-1)
        x1 = x[..., 0::2]
        x2 = x[..., 1::2]
        return jnp.stack([-x2, x1], axis=-1).reshape(x.shape)

    cos = cos[None, :, None, :] if cos.ndim == 2 else cos
    sin = sin[None, :, None, :] if sin.ndim == 2 else sin
    q_out = q * cos + rot(q) * sin
    k_out = k * cos + rot(k) * sin
    return q_out, k_out


def rotary_from_positions(q, k, positions, inv_freq, factor=1.0):
    """Rotary embedding computed from the positions themselves: no table, so
    a model of a million positions costs nothing until they are used.
    q, k: [b, s, heads, d]; positions: [b, s] int32; inv_freq: the r/2
    inverse frequencies (a tuple of floats, r <= d). Rotates the first r
    dimensions, pairing i with i + r/2, and passes the other d - r through;
    cos and sin are multiplied by `factor` (YaRN's attention factor)."""
    inv = jnp.asarray(inv_freq, jnp.float32)
    r = 2 * inv.shape[0]
    ang = positions.astype(jnp.float32)[..., None] * inv        # [b, s, r/2]
    cos = (jnp.cos(ang) * factor)[:, :, None, :]
    sin = (jnp.sin(ang) * factor)[:, :, None, :]

    def rot(x):
        xf = x.astype(jnp.float32)
        x1, x2 = xf[..., :r // 2], xf[..., r // 2:r]
        out = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
        if r < x.shape[-1]:
            out.append(xf[..., r:])
        return jnp.concatenate(out, axis=-1).astype(x.dtype)

    return rot(q), rot(k)


# ------------------------------------------------ sparse experts (dropless)
def moe_experts(x, router_w, w13, w2, expert_lo=0, top_k=1, scale=1.0,
                norm_topk=True, scoring="softmax", select_bias=None):
    """The routed experts of a dropless top-k mixture-of-experts layer, for
    the experts whose weights are HERE: a chip's share under expert
    parallelism, or all of them.

    x [tokens, hidden]; router_w [hidden, experts published]; w13
    [held, hidden, 2 * width] (an expert's gate and up projections, gate
    columns first); w2 [held, width, hidden]. The held experts are ids
    expert_lo .. expert_lo + held - 1 of the router's. Routing is over ALL
    the router's experts: scores in float32 (`scoring`: "softmax" over the
    experts, or "sigmoid" of each logit), the top_k largest, their scores
    normalised to sum 1 (norm_topk) and times `scale`. `select_bias`
    [experts published] is added to the scores for the CHOICE alone: the
    weights are the chosen experts' scores without it (the selection bias of
    auxiliary-loss-free balancing). Every (token, expert) pair whose
    expert is held is computed, none is dropped; a pair whose expert lives
    elsewhere adds nothing here (on one chip there is no exchange, and no
    code stands in for one).

    The products are ONE grouped matmul each way over the pairs sorted by
    expert (ops/pallas/grouped_matmul.py on the TPU, jax.lax.ragged_dot
    elsewhere): an expert nobody was routed to is never read.

    Returns (y [tokens, hidden], counts [held + 1] int32: pairs by held
    expert, and last the pairs routed to experts not held).
    """
    from .. import pallas as _pallas
    from ..pallas.grouped_matmul import grouped_matmul, grouped_matmul_xla

    tokens, _ = x.shape
    held, width = w2.shape[0], w2.shape[1]
    with jax.named_scope("router"):
        logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)
        if scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        elif scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f"moe_experts: scoring {scoring!r}: softmax "
                             f"or sigmoid")
        if select_bias is None:
            top, idx = lax.top_k(scores, top_k)
        else:
            _, idx = lax.top_k(
                scores + select_bias.astype(jnp.float32)[None, :], top_k)
            top = jnp.take_along_axis(scores, idx, axis=-1)
        if norm_topk:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        top = top * scale
    with jax.named_scope("experts"):
        local = idx.reshape(-1) - expert_lo                      # [pairs]
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)          # pairs of others: last
        order = jnp.argsort(key, stable=True)
        counts = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)
        pairs = tokens * top_k
        tm = 128 if pairs >= 1024 else 32
        rows = x[order // top_k]
        pad = (-pairs) % tm
        if pad:                              # rows of no group, at the end
            rows = jnp.pad(rows, ((0, pad), (0, 0)))
        if _pallas.pallas_enabled():
            gmm = functools.partial(grouped_matmul, tm=tm,
                                    interpret=_pallas.interpret_mode())
        else:
            gmm = grouped_matmul_xla
        h = gmm(rows, w13, counts[:held])
        act = (jax.nn.silu(h[:, :width].astype(jnp.float32))
               * h[:, width:].astype(jnp.float32)).astype(x.dtype)
        out = gmm(act, w2, counts[:held])[:pairs]
        # back to (token, choice) order; a pair of another chip's adds 0
        back = jnp.zeros((pairs,), jnp.int32).at[order].set(
            jnp.arange(pairs, dtype=jnp.int32))
        w = jnp.where(mine, top.reshape(-1), 0.0)
        y = jnp.sum((out[back].astype(jnp.float32) * w[:, None])
                    .reshape(tokens, top_k, -1), axis=1)
    return y.astype(x.dtype), counts


# ------------------------------ manifold-constrained hyper-connections
def mhc_unpack(maps, n):
    """(H_pre [..., n], H_post [..., n], H_res [..., n, n], imbalance [...])
    of the maps row mhc_pre returns (ops/pallas/hyper_connection.py)."""
    res = maps[..., 2 * n:2 * n + n * n]
    return (maps[..., :n], maps[..., n:2 * n],
            res.reshape(res.shape[:-1] + (n, n)), maps[..., n * (n + 2)])


def _mhc_maps_xla(x, phi, a, b, n, eps, clamp, iters):
    xf = x.astype(jnp.float32)
    r = lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    h = jnp.einsum("tk,mk->tm", xf, phi.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) * r
    a = a.astype(jnp.float32)
    h = h * jnp.repeat(a, _np.array([n, n, n * n]),
                       total_repeat_length=n * (n + 2)) \
        + b.astype(jnp.float32)
    pre = jax.nn.sigmoid(h[:, :n])
    post = 2.0 * jax.nn.sigmoid(h[:, n:2 * n])
    m = jnp.exp(jnp.clip(h[:, 2 * n:], clamp[0], clamp[1])).reshape(-1, n, n)
    for _ in range(iters):      # columns (sums over the rows i), then rows
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)
    off = jnp.max(jnp.abs(jnp.sum(m, axis=1) - 1.0), axis=-1, keepdims=True)
    return pre, post, m, off


def mhc_pre(x, phi, a, b, n=4, eps=1e-6, clamp=(-30.0, 30.0), iters=20):
    """Opens a sublayer under manifold-constrained hyper-connections
    (arXiv:2512.24880). x [..., n * C]: a token's n residual streams, stream
    j in columns [j * C, (j + 1) * C); phi [n (n + 2), n * C] float32, its
    rows the maps of H_pre, H_post, then H_res row-major; a [3]: the scalars
    of the three; b [n (n + 2)]. One RMS norm (no weight) over all n * C
    values, h = a * (phi x~) + b, H_pre = sigmoid, H_post = 2 sigmoid, H_res
    = exp(clip(h, *clamp)) made doubly stochastic by `iters` Sinkhorn-Knopp
    rounds (each column over its sum + eps, then each row), all in float32.
    Returns (u [..., C] = H_pre x in x's dtype: the sublayer's input; maps
    [..., 128] float32: [H_pre, H_post, H_res, the largest distance of a
    column sum of H_res from 1, zeros], which mhc_post and mhc_unpack
    read). The kernels of ops/pallas/hyper_connection.py on the TPU and in
    interpret mode, the XLA composition elsewhere."""
    from .. import pallas as _pallas
    from ..pallas import hyper_connection as _hc

    lead, width = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, width)
    clamp = (float(clamp[0]), float(clamp[1]))
    if _pallas.pallas_enabled() and _hc.supports(x.shape, n, x.dtype):
        u, maps = _hc.mhc_pre(x2, phi, a, b, n=n, eps=float(eps),
                              clamp=clamp, iters=int(iters),
                              interpret=_pallas.interpret_mode())
    else:
        pre, post, m, off = _mhc_maps_xla(x2, phi, a, b, n, eps, clamp,
                                          iters)
        t = x2.shape[0]
        u = jnp.einsum("tj,tjc->tc", pre,
                       x2.reshape(t, n, -1).astype(jnp.float32)
                       ).astype(x.dtype)
        maps = jnp.concatenate(
            [pre, post, m.reshape(t, n * n), off,
             jnp.zeros((t, _hc.MAPS_WIDTH - n * (n + 2) - 1), jnp.float32)],
            axis=-1)
    return u.reshape(lead + (width // n,)), \
        maps.reshape(lead + (_hc.MAPS_WIDTH,))


def mhc_post(x, y, maps, n=4):
    """Closes a sublayer: x' = H_res x + H_post^T y, x [..., n * C] the
    streams mhc_pre read, y [..., C] the sublayer's output, maps what
    mhc_pre returned. The sum in float32, x' in x's dtype."""
    from .. import pallas as _pallas
    from ..pallas import hyper_connection as _hc

    lead, width = x.shape[:-1], x.shape[-1]
    x2, y2 = x.reshape(-1, width), y.reshape(-1, width // n)
    maps2 = maps.reshape(-1, maps.shape[-1])
    if _pallas.pallas_enabled() and _hc.supports(x.shape, n, x.dtype):
        out = _hc.mhc_post(x2, y2, maps2, n=n,
                           interpret=_pallas.interpret_mode())
    else:
        _, post, res, _ = mhc_unpack(maps2, n)
        t = x2.shape[0]
        out = (jnp.einsum("tij,tjc->tic", res,
                          x2.reshape(t, n, -1).astype(jnp.float32))
               + post[:, :, None] * y2.astype(jnp.float32)[:, None, :]
               ).reshape(t, width).astype(x.dtype)
    return out.reshape(lead + (width,))


# ------------------------------------------------- cached decode attention
def cached_multihead_attention(q, k, v, k_cache, v_cache, pos, scale=None,
                               window=None):
    """Cache-carrying attention for autoregressive decoding (reference: the
    cache-KV path of fused_multi_transformer —
    paddle/fluid/operators/fused/fused_multi_transformer_op.cu — which fuses
    cache write + masked attention per step).

    TPU-first: caches are STATIC-shape rings [b, max_len, kv_heads, d]; the
    new K/V of this step is written at [pos, pos+sq) with a dynamic slice and
    attention masks out positions >= pos+sq, so a single compiled program
    serves every decode step (no shape-polymorphic recompiles). GQA caches
    store unrepeated KV heads and broadcast at compute time.

    q: [b, sq, hq, d]; k,v: [b, sq, hkv, d]; pos: scalar int32 (tokens
    already in the cache) — or a PER-ROW int32 vector [b] for ragged
    batched prefill (each row's new tokens land at its own offset; writes
    past max_len are dropped, and each row masks to its own prefix).
    `window`: query i sees the last `window` keys only (its own included).
    Returns (out [b, sq, hq, d], k_cache, v_cache).

    A chunk of queries at a scalar offset over grouped K/V heads or under a
    window runs, on the TPU, the prefill kernel of ops/pallas/
    flash_attention.py (K/V heads unrepeated, key blocks outside the causal
    band or the window skipped); everything else, and every other platform,
    the masked XLA composition below.
    """
    b, sq, hq, d = q.shape
    max_len = k_cache.shape[1]
    hkv = k_cache.shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1 and pos.shape[0] == b:
        pos = pos.reshape(b)
        idx = pos[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
        bidx = jnp.arange(b)[:, None]
        # per-row scatter (out-of-bounds rows/positions drop harmlessly)
        k_cache = k_cache.at[bidx, idx].set(k.astype(k_cache.dtype))
        v_cache = v_cache.at[bidx, idx].set(v.astype(v_cache.dtype))
        # [b, sq, max_len]: row r's query i sees keys <= pos[r] + i
        keys = jnp.arange(max_len)[None, None, :]
        mask = keys <= idx[:, :, None]
        if window is not None:
            mask = mask & (keys > idx[:, :, None] - window)
        attn_mask = mask[:, None]        # broadcast over heads
    else:
        pos = pos.reshape(())
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, pos, 0, 0))
        if hkv != hq or window is not None:
            from .. import pallas as _pallas
            from ..pallas.flash_attention import (
                flash_attention_prefill as _prefill,
                prefill_supports as _prefill_ok,
            )

            if _prefill_ok(q.shape, k_cache.shape) and (
                    _pallas.interpret_mode()
                    or jax.default_backend() == "tpu"):
                out = _prefill(q, k_cache.astype(q.dtype),
                               v_cache.astype(q.dtype), pos, scale=scale,
                               window=window,
                               interpret=_pallas.interpret_mode())
                return out, k_cache, v_cache
        # rows: new queries at absolute positions pos..pos+sq-1; each sees
        # keys at absolute positions <= its own (causal over the prefix)
        rows = pos + jnp.arange(sq)[:, None]
        mask = jnp.arange(max_len)[None, :] <= rows     # [sq, max_len]
        if window is not None:
            mask = mask & (jnp.arange(max_len)[None, :] > rows - window)
        attn_mask = mask[None, None]
    k_all, v_all = k_cache, v_cache
    if hkv != hq:
        rep = hq // hkv
        k_all = jnp.repeat(k_all, rep, axis=2)
        v_all = jnp.repeat(v_all, rep, axis=2)
    out = scaled_dot_product_attention(
        q, k_all.astype(q.dtype), v_all.astype(q.dtype),
        attn_mask=attn_mask, is_causal=False, training=False,
        scale=scale)
    return out, k_cache, v_cache


def paged_cached_attention(q, k, v, k_pages, v_pages, block_table, seq_lens,
                           scale=None, window=None):
    """One decode step of attention over a PAGED KV cache (the serving
    engine's per-step op; see paddle_tpu/serving/ and
    ops/pallas/paged_attention.py).

    Each slot's KV lives in fixed-size token blocks scattered across a
    preallocated pool; block_table names them. This op (1) writes the step's
    new K/V at each slot's next position (seq_lens tokens already present),
    then (2) attends each slot's single query over its own ragged context —
    Pallas kernel on TPU / interpret mode, XLA gather composition otherwise.

    q: [slots, sq, q_heads, d]; k, v: [slots, sq, kv_heads, d];
    k_pages, v_pages: [num_blocks, kv_heads, block_size, d];
    block_table: [slots, max_blocks] int32; seq_lens: [slots] int32.
    Returns (out [slots, sq, q_heads, d], k_pages, v_pages).

    The append: slot s's token i (position p = seq_lens[s] + i) lands, cast
    to the pool's dtype, at pages[block_table[s, p // block_size], :,
    p % block_size, :]; every other element of the pool comes back
    bit-identical. A position past the slot's block table goes to the null
    page 0 instead (never clamped onto the table's last real block), and an
    idle slot's table is all null pages, so duplicate targets occur only on
    page 0, whose content is never read — the engine masks idle slots'
    tokens and rolls rejected window tokens back by length.

    Layout contract (stated here, enforced by tests/test_tpu_compile.py):
    the pool is row-major [num_blocks, kv_heads, block_size, d], the layout
    the Pallas kernels read and every engine program takes and returns.
    Every writer indexes LEADING dimensions only — here all three, so the
    scatter's window is the [d] row — and the pool is updated where it
    lies. An index with a slice between two index arrays
    (`pages.at[page, :, off]`) makes the TPU compiler relayout the whole
    pool into the scatter's preferred layout and back: four pool-sized
    copies a layer a step.

    sq > 1 is the speculative-verification window: each query attends
    causally within the window (query i sees pos < seq_lens + i + 1).

    `window` (a window layer of a per-layer cache spec): the query sees the
    last `window` keys only, and block_table's row is the slot's RING of
    blocks: position p lives in entry (p // block_size) % ring, so a slot
    holds the window however long its context grows. Masks go by absolute
    position; the kernel visits the window's pages alone. One query token a
    slot: a verify window over a ring is not written.

    The decode kernel (sq == 1; `paged_decode`): one grid step a slot, a
    loop over that slot's live pages read from seq_lens (an idle slot, whose
    table is null and whose length the engine keeps at 0, costs one short
    step), K and V copied from the pool in HBM several pages a fetch with
    the next fetch in flight under the current products. Its time follows
    the bytes of the live keys, not the table's width. q goes in cast to the
    pool's dtype; the softmax and both accumulators are float32.
    """
    slots, sq, hq, d = q.shape
    if window is not None and sq != 1:
        raise NotImplementedError(
            "paged_cached_attention: a multi-token verify window over a "
            "window layer's ring of blocks is not written")
    bs = k_pages.shape[2]
    seq_lens = jnp.asarray(seq_lens, jnp.int32).reshape(slots)

    from .. import pallas as _pallas
    from ..pallas.paged_attention import (
        paged_attention_multi as _paged_multi,
        paged_attention as _paged_kernel,
        paged_attention_xla as _paged_xla,
        paged_attention_xla_multi as _paged_xla_multi,
        supports as _paged_supports,
    )

    with jax.named_scope("kv_append"):
        bt = block_table.astype(jnp.int32)
        pos = seq_lens[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
        page_idx = pos // bs                                 # [slots, sq]
        if window is not None:
            page = jnp.take_along_axis(bt, page_idx % bt.shape[1],
                                       axis=1)[..., None]
        else:
            gathered = jnp.take_along_axis(
                bt, jnp.minimum(page_idx, bt.shape[1] - 1), axis=1)
            # overflow -> null page
            page = jnp.where(page_idx < bt.shape[1], gathered,
                             0)[..., None]
        off = (pos % bs)[..., None]
        head = jnp.arange(k_pages.shape[1], dtype=jnp.int32)[None, None, :]
        k_pages = k_pages.at[page, head, off].set(k.astype(k_pages.dtype))
        v_pages = v_pages.at[page, head, off].set(v.astype(v_pages.dtype))

    if sq == 1:
        ctx = seq_lens + 1  # the token just written attends to itself

        q2 = q[:, 0]
        kernel_ok = _paged_supports(q2.shape, k_pages.shape)
        if kernel_ok and _pallas.interpret_mode():
            out = _paged_kernel(q2, k_pages, v_pages, block_table, ctx,
                                scale, interpret=True, window=window)
        elif kernel_ok and jax.default_backend() == "tpu":
            out = _paged_kernel(q2, k_pages, v_pages, block_table, ctx,
                                scale, window=window)
        else:
            out = _paged_xla(q2, k_pages, v_pages, block_table, ctx, scale,
                             window)
        return out[:, None], k_pages, v_pages

    # ---- multi-token verify window ----
    kernel_ok = _paged_supports((slots, hq, d), k_pages.shape)
    if kernel_ok and _pallas.interpret_mode():
        out = _paged_multi(q, k_pages, v_pages, block_table, seq_lens,
                           scale, interpret=True)
    elif kernel_ok and jax.default_backend() == "tpu":
        out = _paged_multi(q, k_pages, v_pages, block_table, seq_lens,
                           scale)
    else:
        out = _paged_xla_multi(q, k_pages, v_pages, block_table, seq_lens,
                               scale)
    return out, k_pages, v_pages


# --------------------------------------------------- latent attention (MLA)
def _latent_scores_xla(q, latent, live, scale, v_dim):
    """q [b, sq, h, w] over latent [b, sk, w] under `live` [b, sq, sk]:
    softmax in float32 of q . latent, then the sum over the first `v_dim`
    values of the same rows. The masked XLA composition."""
    lat = latent.astype(jnp.float32)
    sc = jnp.einsum("bqhw,bkw->bhqk", q.astype(jnp.float32), lat) * scale
    p = jax.nn.softmax(jnp.where(live[:, None], sc, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkv->bqhv", p, lat[..., :v_dim]).astype(q.dtype)


def latent_cached_attention(q, new, cache, pos, v_dim, scale):
    """Attention in the latent space over a contiguous latent cache: the
    absorbed form of multi-head latent attention, a chunk of queries at a
    scalar offset (prefill through a workspace, generate()).

    q [b, sq, heads, w]: absorbed queries, a head's [q_nope W_uk, q_rope];
    new [b, sq, w]: the chunk's latents [c, r], after the norm and the
    rotation; cache [b, max_len, 1, w]; pos: scalar int32, tokens already in
    the cache. Writes `new` at [pos, pos + sq), then query i sees keys
    <= pos + i: score q . cache row over all w values, the weighted sum over
    the first v_dim values of the same rows (every head reads the one
    latent). Returns (out [b, sq, heads, v_dim], cache). On the TPU, for
    chunks and caches in whole tiles, the kernel of
    ops/pallas/flash_attention.py (`latent_prefill`); else the masked XLA
    composition."""
    b, sq, h, w = q.shape
    pos = jnp.asarray(pos, jnp.int32).reshape(())
    with jax.named_scope("kv_append"):
        cache = jax.lax.dynamic_update_slice(
            cache, new[:, :, None, :].astype(cache.dtype), (0, pos, 0, 0))
    from .. import pallas as _pallas
    from ..pallas.flash_attention import (
        latent_prefill,
        latent_prefill_supports as prefill_supports,
    )

    if prefill_supports(q.shape, cache.shape, v_dim) and (
            _pallas.interpret_mode() or jax.default_backend() == "tpu"):
        out = latent_prefill(q, cache[:, :, 0], pos, v_dim=v_dim,
                             scale=scale, interpret=_pallas.interpret_mode())
        return out, cache
    rows = pos + jnp.arange(sq)[:, None]
    live = jnp.arange(cache.shape[1])[None, :] <= rows          # [sq, sk]
    out = _latent_scores_xla(q, cache[:, :, 0], live[None], scale, v_dim)
    return out, cache


def latent_paged_attention(q, new, pages, block_table, seq_lens, v_dim,
                           scale):
    """One decode step of absorbed latent attention over PAGED latents (the
    serving engine's per-step op for a layer of cache kind "latent").

    q [slots, 1, heads, w]; new [slots, 1, w]; pages [num_blocks, 1,
    block_size, w] (the layout and the append of paged_cached_attention,
    one array and one head); block_table [slots, max_blocks]; seq_lens
    [slots]. Appends `new` at each slot's next position, then each slot's
    query attends over its own context: a page is read once, for the scores
    (all w values) and for the weighted sum (its first v_dim). Returns
    (out [slots, 1, heads, v_dim], pages). The `latent_decode` kernel on
    the TPU and in interpret mode, the XLA gather composition elsewhere."""
    slots, sq, h, w = q.shape
    if sq != 1:
        raise NotImplementedError(
            "latent_paged_attention: a multi-token verify window over "
            "latent pages is not written")
    bs = pages.shape[2]
    seq_lens = jnp.asarray(seq_lens, jnp.int32).reshape(slots)
    bt = block_table.astype(jnp.int32)
    with jax.named_scope("kv_append"):
        page_idx = seq_lens // bs
        page = jnp.where(
            page_idx < bt.shape[1],
            jnp.take_along_axis(
                bt, jnp.minimum(page_idx, bt.shape[1] - 1)[:, None],
                axis=1)[:, 0], 0)                   # overflow -> null page
        pages = pages.at[page, 0, seq_lens % bs].set(
            new[:, 0].astype(pages.dtype))
    ctx = seq_lens + 1
    from .. import pallas as _pallas
    from ..pallas.paged_attention import from_pages, latent_decode

    if _pallas.interpret_mode() or jax.default_backend() == "tpu":
        out = latent_decode(q[:, 0], pages, bt, ctx, v_dim=v_dim,
                            scale=scale, interpret=_pallas.interpret_mode())
        return out[:, None], pages
    lat = from_pages(pages[bt])[:, :, 0]             # [slots, max_ctx, w]
    live = jnp.arange(lat.shape[1])[None, :] < ctx[:, None]
    return _latent_scores_xla(q, lat, live[:, None], scale, v_dim), pages


def softsign(x):
    return x / (1.0 + jnp.abs(x))


def alpha_dropout(x, p=0.5, training=True):
    """SELU-preserving dropout (reference nn/functional/common.py
    alpha_dropout)."""
    if not training or p == 0.0:
        return x
    alpha = -1.7580993408473766
    keep = 1.0 - p
    a = (keep + alpha * alpha * keep * (1 - keep)) ** -0.5
    b = -a * alpha * (1 - keep)
    mask = jax.random.bernoulli(_random.next_key(), keep, x.shape).astype(x.dtype)
    return a * (x * mask + alpha * (1 - mask)) + b


def dropout3d(x, p=0.5, training=True, data_format="NCDHW"):
    if not training or p == 0.0:
        return x
    n = x.shape[0]
    if data_format == "NCDHW":
        shape = (n, x.shape[1], 1, 1, 1)
    else:  # NDHWC: drop whole channels, not depth slices
        shape = (n, 1, 1, 1, x.shape[4])
    mask = jax.random.bernoulli(_random.next_key(), 1.0 - p,
                                shape).astype(x.dtype)
    return x * mask / (1.0 - p)


def zeropad2d(x, padding, data_format="NCHW"):
    l, r, t, b = padding
    if data_format == "NCHW":
        return jnp.pad(x, ((0, 0), (0, 0), (t, b), (l, r)))
    return jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0)))


def spectral_norm(weight, u, v, dim=0, power_iters=1, eps=1e-12):
    """phi spectral_norm_kernel: weight / sigma_max estimated by power
    iteration; u, v are the persistent iteration vectors."""
    w = jnp.moveaxis(weight, dim, 0)
    h = w.shape[0]
    wm = w.reshape(h, -1)
    for _ in range(max(power_iters, 0)):
        v = wm.T @ u
        v = v / jnp.maximum(jnp.linalg.norm(v), eps)
        u = wm @ v
        u = u / jnp.maximum(jnp.linalg.norm(u), eps)
    sigma = u @ (wm @ v)
    return weight / jnp.maximum(sigma, eps)


def bilinear(x1, x2, weight, bias=None):
    """phi bilinear_kernel: out[b, o] = x1[b] @ W[o] @ x2[b] (+ bias)."""
    out = jnp.einsum("bi,oij,bj->bo", x1, weight, x2)
    if bias is not None:
        out = out + bias.reshape(1, -1)
    return out


def pad3d(x, paddings, mode="constant", value=0.0, data_format="NCDHW"):
    """phi pad3d_kernel: paddings = [left, right, top, bottom, front, back]
    over (W, H, D)."""
    l, r, t, b, f, bk = (int(p) for p in paddings)
    if data_format == "NCDHW":
        width = [(0, 0), (0, 0), (f, bk), (t, b), (l, r)]
    else:  # NDHWC
        width = [(0, 0), (f, bk), (t, b), (l, r), (0, 0)]
    jmode = {"constant": "constant", "reflect": "reflect",
             "replicate": "edge", "circular": "wrap"}[mode]
    if jmode == "constant":
        return jnp.pad(x, width, mode="constant", constant_values=value)
    return jnp.pad(x, width, mode=jmode)


def memory_efficient_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                               is_causal=False, scale=None, training=True):
    """Reference memory_efficient_attention op: same contract as
    scaled_dot_product_attention (the TPU path is already streaming/fused)."""
    return scaled_dot_product_attention(
        query, key, value, attn_mask=attn_mask, dropout_p=dropout_p,
        is_causal=is_causal, training=training, scale=scale)


logsigmoid = log_sigmoid
tanh_shrink = tanhshrink
bce_loss = binary_cross_entropy
kldiv_loss = kl_div


# ----- phi reference-name surface (aliases/wrappers over existing kernels)
def add_n(inputs):
    """phi add_n_kernel: elementwise sum of a tensor list."""
    out = inputs[0]
    for t in inputs[1:]:
        out = out + t
    return out


def shape(x):
    """legacy shape op: the tensor's shape as an int32 tensor."""
    return jnp.asarray(x.shape, jnp.int32)


def linear_interp(x, size=None, scale_factor=None, align_corners=False,
                  data_format="NCW"):
    return interpolate(x, size=size, scale_factor=scale_factor, mode="linear",
                       align_corners=align_corners, data_format=data_format)


def bilinear_interp(x, size=None, scale_factor=None, align_corners=False,
                    data_format="NCHW"):
    return interpolate(x, size=size, scale_factor=scale_factor,
                       mode="bilinear", align_corners=align_corners,
                       data_format=data_format)


def nearest_interp(x, size=None, scale_factor=None, align_corners=False,
                   data_format="NCHW"):
    return interpolate(x, size=size, scale_factor=scale_factor,
                       mode="nearest", align_corners=align_corners,
                       data_format=data_format)


def bicubic_interp(x, size=None, scale_factor=None, align_corners=False,
                   data_format="NCHW"):
    return interpolate(x, size=size, scale_factor=scale_factor,
                       mode="bicubic", align_corners=align_corners,
                       data_format=data_format)


def trilinear_interp(x, size=None, scale_factor=None, align_corners=False,
                     data_format="NCDHW"):
    return interpolate(x, size=size, scale_factor=scale_factor,
                       mode="trilinear", align_corners=align_corners,
                       data_format=data_format)


def cross_entropy_with_softmax(logits, label, soft_label=False,
                               ignore_index=-100, axis=-1):
    return cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, axis=axis,
                         reduction="none")


def flash_attn(q, k, v, dropout=0.0, causal=False, return_softmax=False,
               training=True):
    """phi flash_attn op name for the fused attention path."""
    return scaled_dot_product_attention(q, k, v, dropout_p=dropout,
                                        is_causal=causal, training=training)


def flash_attn_unpadded(q, k, v, cu_seqlens_q, cu_seqlens_k, max_seqlen_q,
                        max_seqlen_k, scale=None, dropout=0.0, causal=False):
    """Varlen attention over packed sequences (phi flash_attn_unpadded,
    paddle/phi/kernels/gpu/flash_attn_kernel.cu varlen entries): tokens from
    different sequences must not attend to each other.

    Streaming path: when self-attention packing applies (identical q/k
    offsets) and shapes tile, the segment-id Pallas kernel
    (ops/pallas/flash_attention.flash_attention_segmented) runs the
    block-diagonal mask with O(block) memory; otherwise a dense mask over
    the packed [total, total] scores is the fallback."""
    total = q.shape[0]
    pos = jnp.arange(total)
    seg_q = jnp.searchsorted(cu_seqlens_q[1:], pos, side="right")
    seg_k = jnp.searchsorted(cu_seqlens_k[1:], jnp.arange(k.shape[0]),
                             side="right")

    from ...core import flags as _flags
    from .. import pallas as _pallas

    # identity, not shape: equal-shape but different-valued offsets would
    # silently mis-segment K (values are traced, so only the self-attention
    # same-object case is provably safe)
    same_packing = (q.shape[0] == k.shape[0]
                    and cu_seqlens_q is cu_seqlens_k)
    if (
        _flags.get_flag("use_flash_attention")
        and _pallas.pallas_enabled()
        and same_packing
        and dropout == 0.0
        and total % 128 == 0
        and q.shape[-1] <= 256
    ):
        from ..pallas.flash_attention import flash_attention_segmented

        out = flash_attention_segmented(
            q[None], k[None], v[None], seg_q[None].astype(jnp.int32),
            scale, causal, interpret=_pallas.interpret_mode())
        return out[0]

    mask = seg_q[:, None] == seg_k[None, :]
    if causal:
        off_q = pos - jnp.take(cu_seqlens_q, seg_q)
        off_k = jnp.arange(k.shape[0]) - jnp.take(cu_seqlens_k, seg_k)
        mask = mask & (off_q[:, None] >= off_k[None, :])
    out = scaled_dot_product_attention(
        q[None], k[None], v[None], attn_mask=mask[None, None],
        dropout_p=dropout, scale=scale, training=dropout > 0)
    return out[0]


def rotary_position_embedding_packed(q, k, cos, sin, pos):
    """Rope with PER-TOKEN positions (packed-document pretraining):
    q/k [b, s, h, d], cos/sin TABLES [P, d], pos [b, s] int32. The TPU
    lowering gathers the table rows in-kernel (one-hot MXU lookup inside
    ops/pallas/rope._rope_packed_kernel) so the gathered [b, s, d] cos/sin
    never materialize in HBM; other platforms take the gather+rotate XLA
    composition. The VJP reuses the forward with sign=-1, valid for REAL
    rope tables (duplicated half structure, cos/sin of the same angles) —
    not for arbitrary tables."""
    from ..pallas.rope import fused_rope_packed
    from .. import pallas as _pallas

    cv = cos if not hasattr(cos, "_value") else cos._value
    sv = sin if not hasattr(sin, "_value") else sin._value
    pv = pos if not hasattr(pos, "_value") else pos._value
    return fused_rope_packed(q, k, cv, sv, pv.astype(jnp.int32),
                             interpret=_pallas.interpret_mode())


def segmented_attention(q, k, v, segment_ids, causal=True, scale=None):
    """Batched packed-sequence attention: q/k/v [b, s, h, d] with
    segment_ids [b, s] (same id = same document; padding uses -1, which
    only matches itself). The batch-granular sibling of
    flash_attn_unpadded (reference FlashAttnUnpaddedKernel,
    paddle/phi/kernels/gpu/flash_attn_kernel.cu) for the packed GPT
    pretrain path: tokens attend only within their document, causally."""
    b, s, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    seg = segment_ids.astype(jnp.int32)

    from ...core import flags as _flags
    from .. import pallas as _pallas

    if (
        _flags.get_flag("use_flash_attention")
        and _pallas.pallas_enabled()
        and s % 128 == 0
        and d <= 256
    ):
        from ..pallas.flash_attention import flash_attention_segmented

        return flash_attention_segmented(
            q, k, v, seg, scale, causal,
            interpret=_pallas.interpret_mode())
    mask = seg[:, :, None] == seg[:, None, :]
    if causal:
        mask = mask & jnp.tril(jnp.ones((s, s), bool))[None]
    return scaled_dot_product_attention(
        q, k, v, attn_mask=mask[:, None], is_causal=False, scale=scale)


def pool2d(x, kernel_size, stride=None, padding=0, pooling_type="max",
           ceil_mode=False, exclusive=True, adaptive=False,
           data_format="NCHW", global_pooling=False):
    """legacy pool2d op: one entry dispatching on pooling_type."""
    if global_pooling:
        kernel_size = (x.shape[2], x.shape[3]) if data_format == "NCHW" \
            else (x.shape[1], x.shape[2])
        stride, padding = kernel_size, 0
    if adaptive:
        if pooling_type == "max":
            return adaptive_max_pool2d(x, kernel_size, data_format)
        return adaptive_avg_pool2d(x, kernel_size, data_format)
    if pooling_type == "max":
        return max_pool2d(x, kernel_size, stride, padding, ceil_mode,
                          data_format)
    return avg_pool2d(x, kernel_size, stride, padding, ceil_mode, exclusive,
                      data_format)
