"""paddle.nn.functional — thin paddle-signature layer over the op registry.

Reference: python/paddle/nn/functional/*. Most functions ARE the registered
ops; only signature shims live here.
"""
from __future__ import annotations

from ..ops.api import (  # noqa: F401
    adaptive_avg_pool2d,
    adaptive_max_pool2d,
    avg_pool2d,
    batch_norm,
    binary_cross_entropy,
    binary_cross_entropy_with_logits,
    celu,
    conv1d,
    conv2d,
    conv2d_transpose,
    cosine_similarity,
    cross_entropy,
    dropout,
    dropout2d,
    elu,
    embedding as _embedding_op,
    gelu,
    glu,
    group_norm,
    gumbel_softmax,
    hardshrink,
    hardsigmoid,
    hardswish,
    hardtanh,
    hinge_embedding_loss,
    instance_norm,
    interpolate,
    kl_div,
    l1_loss,
    label_smooth,
    layer_norm as _layer_norm_op,
    leaky_relu,
    linear,
    log_sigmoid,
    log_softmax,
    max_pool2d,
    maxout,
    mish,
    mse_loss,
    nll_loss,
    normalize,
    one_hot,
    pad,
    pixel_shuffle,
    prelu,
    relu,
    relu6,
    rms_norm,
    rrelu,
    selu,
    sigmoid,
    sigmoid_focal_loss,
    silu,
    smooth_l1_loss,
    softmax,
    softplus,
    softshrink,
    swish,
    tanhshrink,
    thresholded_relu,
    unfold,
    scaled_dot_product_attention,
    conv3d,
    conv1d_transpose,
    conv3d_transpose,
    max_pool1d,
    avg_pool1d,
    max_pool3d,
    avg_pool3d,
    max_unpool1d,
    max_unpool2d,
    adaptive_avg_pool1d,
    adaptive_max_pool1d,
    adaptive_avg_pool3d,
    adaptive_max_pool3d,
    lp_pool2d,
    grid_sample,
    affine_grid,
    pixel_unshuffle,
    channel_shuffle,
    fold,
    local_response_norm,
    softsign,
    alpha_dropout,
    dropout3d,
    zeropad2d,
    ctc_loss,
    margin_ranking_loss,
    pairwise_distance,
    triplet_margin_loss,
    triplet_margin_with_distance_loss,
    cosine_embedding_loss,
    soft_margin_loss,
    multi_label_soft_margin_loss,
    multi_margin_loss,
    poisson_nll_loss,
    gaussian_nll_loss,
    square_error_cost,
    log_loss,
    dice_loss,
    npair_loss,
    hsigmoid_loss,
)
def softmax_(x, axis=-1, dtype=None, name=None):
    """In-place softmax (reference F.softmax_): rebinds x's value like the
    other *_ shims — the previous alias to the out-of-place op silently
    left x untouched."""
    out = _api.softmax(x, axis=axis)
    x._value = out._value
    x._grad_node = out._grad_node
    if not out.stop_gradient:
        x.stop_gradient = False
    return x
from ..ops import api as _api


def embedding(x, weight, padding_idx=None, sparse=False):
    return _embedding_op(x, weight, padding_idx=padding_idx)


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5):
    return _layer_norm_op(x, normalized_shape, weight, bias, epsilon)


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False, data_format="NCHW"):
    return interpolate(x, size, scale_factor, mode, align_corners, data_format)


def tanh(x):
    return _api.tanh(x)


def flatten(x, start_axis=0, stop_axis=-1):
    return _api.flatten(x, start_axis, stop_axis)


def square_error_cost(input, label):
    return _api.square(input - label)


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100, axis=-1, return_softmax=False):
    loss = cross_entropy(logits, label, soft_label=soft_label, ignore_index=ignore_index, axis=axis, reduction="none")
    if loss.ndim == logits.ndim - 1:
        loss = _api.unsqueeze(loss, axis)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def causal_lm_loss(logits, labels, segments=None, ignore_index=-100):
    """The next-token loss every causal LM here trains on (op
    causal_lm_loss, under the named scope `loss`): logits [b, s, v] as the
    head produced them, labels [b, s] unshifted, segments the optional
    packed-document ids. Labels and segments may be plain arrays."""
    import jax

    from ..core.tensor import Tensor

    def tensor(x):
        return x if x is None or isinstance(x, Tensor) else Tensor(x)

    with jax.named_scope("loss"):
        return _api.causal_lm_loss(logits, tensor(labels), tensor(segments),
                                   ignore_index)


def sequence_mask(lengths, maxlen=None, dtype="int64"):
    if maxlen is None:
        maxlen = int(lengths.max().item())
    rng = _api.arange(0, maxlen, 1, dtype="int64")
    return _api.cast(_api.less_than(rng, _api.unsqueeze(lengths, -1)), dtype)


# -- round-5 API parity (reference nn/functional/__init__.py __all__) -------

from ..ops.api import (  # noqa: F401, E402
    bilinear,
    class_center_sample,
    diag_embed,
    gather_tree,
    max_unpool3d,
    temporal_shift,
)
from ..ops.api import margin_cross_entropy as _margin_ce_op  # noqa: E402
from ..ops.api import rnnt_loss as _rnnt_op  # noqa: E402


def _reduce(loss, reduction):
    if reduction == "mean":
        return _api.mean(loss)
    if reduction == "sum":
        return _api.sum(loss)
    return loss


def rnnt_loss(input, label, input_lengths, label_lengths, blank=0,
              fastemit_lambda=0.001, reduction="mean", name=None):
    return _reduce(_rnnt_op(input, label, input_lengths, label_lengths,
                            blank, fastemit_lambda), reduction)


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, group=None,
                         return_softmax=False, reduction="mean"):
    out = _margin_ce_op(logits, label, margin1, margin2, margin3, scale,
                        return_softmax=return_softmax)
    if return_softmax:
        loss, sm = out
        return _reduce(loss, reduction), sm
    return _reduce(out, reduction)


def relu_(x):
    return x.relu_()


def elu_(x, alpha=1.0):
    out = _api.elu(x, alpha)
    x._value = out._value
    x._grad_node = out._grad_node
    if not out.stop_gradient:
        x.stop_gradient = False
    return x


def tanh_(x):
    return x.tanh_()


def sparse_attention(query, key, value, sparse_csr_offset,
                     sparse_csr_columns, key_padding_mask=None,
                     attn_mask=None):
    """Block-sparse attention with a per-head CSR connectivity pattern
    (reference phi/kernels/sparse/gpu/sparse_attention via cusparse; here
    the CSR pattern gates a masked dense softmax — exact semantics, with
    the density caveat documented: for long-sequence sparse patterns use
    paddle_tpu.sparse attention or flash_attn_unpadded, which tile).

    query/key/value: [B, H, T, D]; offset: [B, H, T+1]; columns: [B, H, nnz].
    """
    import jax.numpy as jnp

    from ..core.tensor import Tensor as _T

    off = sparse_csr_offset._value if hasattr(sparse_csr_offset, "_value") \
        else jnp.asarray(sparse_csr_offset)
    cols = sparse_csr_columns._value if hasattr(sparse_csr_columns, "_value") \
        else jnp.asarray(sparse_csr_columns)
    b, h, t, d = query.shape
    nnz = cols.shape[-1]
    # CSR pattern -> boolean mask (integer-only; grads flow through q/k/v
    # below via registered ops). Row of each slot: searchsorted on offsets.
    slot = jnp.arange(nnz)
    rows = jax.vmap(jax.vmap(
        lambda o: jnp.searchsorted(o, slot, side="right") - 1))(off)
    mask = jnp.zeros((b, h, t, t), bool)
    bi = jnp.arange(b)[:, None, None]
    hi = jnp.arange(h)[None, :, None]
    valid = slot[None, None, :] < off[..., -1:]
    mask = mask.at[bi, hi, jnp.clip(rows, 0, t - 1),
                   jnp.clip(cols, 0, t - 1)].max(valid)
    neg = _T(jnp.where(mask, 0.0, -1e30).astype(jnp.float32))
    scores = _api.scale(
        _api.matmul(query, key, transpose_y=True), 1.0 / (d ** 0.5))
    scores = _api.add(scores, neg)
    if attn_mask is not None:
        scores = _api.add(scores, attn_mask)
    p = softmax(scores, axis=-1)
    return _api.matmul(p, value)


import jax  # noqa: E402  (used by sparse_attention row recovery)
