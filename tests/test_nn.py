"""nn.Layer system + layers tests (reference: test/legacy_test layer tests)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn


def _f32(*shape):
    return np.random.randn(*shape).astype(np.float32)


def test_linear_matches_numpy():
    lin = nn.Linear(4, 3)
    x = _f32(2, 4)
    out = lin(paddle.to_tensor(x))
    ref = x @ lin.weight.numpy() + lin.bias.numpy()
    np.testing.assert_allclose(out.numpy(), ref, atol=1e-5)


def test_conv2d_shape_and_grad():
    conv = nn.Conv2D(3, 8, 3, stride=2, padding=1)
    x = paddle.to_tensor(_f32(2, 3, 16, 16), stop_gradient=False)
    out = conv(x)
    assert out.shape == [2, 8, 8, 8]
    out.sum().backward()
    assert conv.weight.grad is not None and conv.weight.grad.shape == [8, 3, 3, 3]


def test_conv2d_groups_depthwise():
    conv = nn.Conv2D(4, 4, 3, padding=1, groups=4)
    out = conv(paddle.to_tensor(_f32(1, 4, 8, 8)))
    assert out.shape == [1, 4, 8, 8]


def test_conv2d_transpose_shape():
    deconv = nn.Conv2DTranspose(4, 2, 2, stride=2)
    out = deconv(paddle.to_tensor(_f32(1, 4, 8, 8)))
    assert out.shape == [1, 2, 16, 16]


def test_pools():
    x = paddle.to_tensor(_f32(1, 2, 8, 8))
    assert nn.MaxPool2D(2, 2)(x).shape == [1, 2, 4, 4]
    assert nn.AvgPool2D(2, 2)(x).shape == [1, 2, 4, 4]
    assert nn.AdaptiveAvgPool2D(1)(x).shape == [1, 2, 1, 1]
    np.testing.assert_allclose(
        nn.AdaptiveAvgPool2D(1)(x).numpy()[..., 0, 0], x.numpy().mean((2, 3)), atol=1e-5)


def test_batchnorm_updates_stats_and_eval_uses_them():
    bn = nn.BatchNorm2D(3, momentum=0.5)
    x = paddle.to_tensor(_f32(4, 3, 5, 5) * 2 + 1)
    bn.train()
    y = bn(x)
    # normalized output ~ zero mean unit var per channel
    yn = y.numpy()
    assert abs(yn.mean()) < 1e-4
    m_after = bn._mean.numpy().copy()
    assert not np.allclose(m_after, 0)
    bn.eval()
    y2 = bn(x)
    assert not np.allclose(y2.numpy(), yn)  # eval path uses running stats


def test_layernorm_normalizes():
    ln = nn.LayerNorm(8)
    x = paddle.to_tensor(_f32(4, 8) * 3 + 2)
    y = ln(x).numpy()
    np.testing.assert_allclose(y.mean(-1), np.zeros(4), atol=1e-4)
    np.testing.assert_allclose(y.std(-1), np.ones(4), atol=1e-2)


def test_rmsnorm():
    rn = nn.RMSNorm(8)
    x = paddle.to_tensor(_f32(2, 8))
    y = rn(x).numpy()
    ms = np.sqrt((x.numpy() ** 2).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(y, x.numpy() / ms, atol=1e-4)


def test_dropout_train_eval():
    do = nn.Dropout(0.5)
    x = paddle.to_tensor(np.ones((100, 100), np.float32))
    do.train()
    y = do(x).numpy()
    assert (y == 0).mean() > 0.3
    # upscale keeps expectation
    assert abs(y.mean() - 1.0) < 0.1
    do.eval()
    np.testing.assert_array_equal(do(x).numpy(), x.numpy())


def test_embedding_padding_idx():
    emb = nn.Embedding(10, 4, padding_idx=0)
    out = emb(paddle.to_tensor(np.array([0, 3])))
    np.testing.assert_allclose(out.numpy()[0], np.zeros(4))
    assert not np.allclose(out.numpy()[1], 0)


def test_sequential_and_layerlist():
    seq = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    out = seq(paddle.to_tensor(_f32(3, 4)))
    assert out.shape == [3, 2]
    assert len(list(seq.parameters())) == 4
    ll = nn.LayerList([nn.Linear(2, 2) for _ in range(3)])
    ll.append(nn.Linear(2, 2))
    assert len(ll) == 4 and len(list(ll.parameters())) == 8


def test_state_dict_roundtrip():
    m1 = nn.Sequential(nn.Linear(4, 4), nn.LayerNorm(4))
    m2 = nn.Sequential(nn.Linear(4, 4), nn.LayerNorm(4))
    m2.set_state_dict(m1.state_dict())
    x = paddle.to_tensor(_f32(2, 4))
    np.testing.assert_allclose(m1(x).numpy(), m2(x).numpy(), atol=1e-6)


def test_named_parameters_structure():
    m = nn.Sequential(nn.Linear(2, 2), nn.Linear(2, 2))
    names = [n for n, _ in m.named_parameters()]
    assert names == ["0.weight", "0.bias", "1.weight", "1.bias"]


def test_buffers_in_state_dict():
    bn = nn.BatchNorm2D(2)
    sd = bn.state_dict()
    assert "_mean" in sd and "_variance" in sd


def test_forward_hooks():
    lin = nn.Linear(2, 2)
    calls = []
    h1 = lin.register_forward_pre_hook(lambda layer, inp: calls.append("pre"))
    h2 = lin.register_forward_post_hook(lambda layer, inp, out: calls.append("post"))
    lin(paddle.to_tensor(_f32(1, 2)))
    assert calls == ["pre", "post"]
    h1.remove()
    h2.remove()
    calls.clear()
    lin(paddle.to_tensor(_f32(1, 2)))
    assert calls == []


def test_multihead_attention():
    mha = nn.MultiHeadAttention(16, 4)
    x = paddle.to_tensor(_f32(2, 5, 16), stop_gradient=False)
    out = mha(x)
    assert out.shape == [2, 5, 16]
    out.sum().backward()
    assert mha.q_proj.weight.grad is not None


def test_transformer_encoder():
    layer = nn.TransformerEncoderLayer(d_model=16, nhead=4, dim_feedforward=32, dropout=0.0)
    enc = nn.TransformerEncoder(layer, num_layers=2)
    out = enc(paddle.to_tensor(_f32(2, 6, 16)))
    assert out.shape == [2, 6, 16]


def test_losses():
    logits = paddle.to_tensor(_f32(4, 5))
    labels = paddle.to_tensor(np.array([1, 2, 0, 4]))
    ce = nn.CrossEntropyLoss()(logits, labels)
    ref = -np.log(np.exp(logits.numpy() - logits.numpy().max(1, keepdims=True)) /
                  np.exp(logits.numpy() - logits.numpy().max(1, keepdims=True)).sum(1, keepdims=True))
    ref = ref[np.arange(4), labels.numpy()]
    np.testing.assert_allclose(float(ce.item()), ref.mean(), atol=1e-5)

    pred = paddle.to_tensor(_f32(3, 2))
    tgt = paddle.to_tensor(_f32(3, 2))
    np.testing.assert_allclose(float(nn.MSELoss()(pred, tgt).item()),
                               ((pred.numpy() - tgt.numpy()) ** 2).mean(), atol=1e-6)
    np.testing.assert_allclose(float(nn.L1Loss()(pred, tgt).item()),
                               np.abs(pred.numpy() - tgt.numpy()).mean(), atol=1e-6)


def test_cross_entropy_ignore_index():
    logits = paddle.to_tensor(_f32(4, 5))
    labels = paddle.to_tensor(np.array([1, -100, 0, -100]))
    loss = nn.CrossEntropyLoss(ignore_index=-100)(logits, labels)
    full = nn.CrossEntropyLoss(reduction="none")(logits, paddle.to_tensor(np.array([1, 0, 0, 0])))
    expected = (full.numpy()[0] + full.numpy()[2]) / 2
    np.testing.assert_allclose(float(loss.item()), expected, atol=1e-5)


# ---- the next-token loss (op causal_lm_loss): the shift on the labels, the
# ---- logits read in the dtype the head produced
def _shifted_cross_entropy(logits, labels, segments=None):
    """What every causal LM here computed before the op: slice, reshape,
    cross_entropy (float32 inside), packed pairs masked on the labels."""
    from paddle_tpu.nn import functional as F
    from paddle_tpu.ops import api

    lab = np.asarray(labels)[:, 1:].copy()
    if segments is not None:
        seg = np.asarray(segments)
        lab[~((seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] >= 0))] = -100
    v = logits.shape[-1]
    return F.cross_entropy(
        api.reshape(api.cast(logits, "float32")[:, :-1, :], [-1, v]),
        paddle.to_tensor(lab.reshape(-1)))


def _lm_case(case, b=3, s=17, v=97):
    rng = np.random.default_rng(37)
    logits = (3 * rng.standard_normal((b, s, v))).astype(np.float32)
    labels = rng.integers(0, v, (b, s)).astype(np.int32)
    segments = None
    if case == "ignored_labels":
        labels[0, 3] = labels[2, 1:9] = labels[1, -1] = -100
    elif case == "packed_segments":
        segments = np.sort(rng.integers(0, 3, (b, s)), axis=1).astype(np.int32)
        segments[1, -4:] = -1                       # padding
    return logits, labels, segments


@pytest.mark.parametrize("case", ["plain", "ignored_labels", "packed_segments"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_causal_lm_loss_is_the_shifted_cross_entropy(dtype, case):
    from paddle_tpu.nn import functional as F

    logits, labels, segments = _lm_case(case)
    x = paddle.to_tensor(logits).astype(dtype)
    x.stop_gradient = False
    loss = F.causal_lm_loss(x, labels, segments)    # plain arrays are taken
    loss.backward()
    ref_x = paddle.to_tensor(x.numpy().astype(np.float32), stop_gradient=False)
    want = _shifted_cross_entropy(ref_x, labels, segments)
    want.backward()
    assert str(loss.dtype).endswith("float32") and x.grad.dtype == x.dtype
    got_g = x.grad.numpy().astype(np.float32)
    want_g = ref_x.grad.numpy()
    # float32: both are float32 throughout. bfloat16: the value from the
    # same rounded logits with float32 sums; the gradient rounded once
    value_tol, grad_tol = (1e-6, 1e-6) if dtype == "float32" else (1e-5, 2 ** -8)
    assert abs(float(loss) - float(want)) <= value_tol * abs(float(want))
    assert np.all(np.abs(got_g - want_g)
                  <= grad_tol * np.maximum(np.abs(want_g), 1e-3 / labels.size))
    # the last position of every sequence scores nothing
    assert not got_g[:, -1, :].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_causal_lm_loss_with_every_label_ignored(dtype):
    from paddle_tpu.nn import functional as F

    logits, labels, _ = _lm_case("plain")
    x = paddle.to_tensor(logits).astype(dtype)
    x.stop_gradient = False
    loss = F.causal_lm_loss(x, np.full_like(labels, -100))
    loss.backward()
    assert float(loss) == 0.0
    assert not x.grad.numpy().astype(np.float32).any()      # zeros, no NaN


def _lm_model(family):
    from paddle_tpu import models

    paddle.seed(37)
    if family == "gpt":
        return models.GPTForCausalLM(models.GPTConfig(
            vocab_size=96, hidden_size=32, num_layers=1, num_heads=2,
            max_position_embeddings=32, hidden_dropout_prob=0.0,
            attention_dropout_prob=0.0))
    if family == "llama":
        return models.LlamaForCausalLM(models.LlamaConfig(
            vocab_size=96, hidden_size=32, num_layers=1, num_heads=2,
            num_key_value_heads=2, intermediate_size=64,
            max_position_embeddings=32))
    return models.LagunaForCausalLM(models.LagunaConfig.tiny())


@pytest.mark.parametrize("site", ["gpt", "gpt_segments", "llama",
                                  "llama_segments", "laguna",
                                  "gpt_pipeline", "llama_pipeline"])
def test_every_causal_lm_gives_the_loss_it_gave(site):
    """The five places that held the shift-and-cross_entropy code call the
    one op now: each still returns that loss."""
    from paddle_tpu.models import gpt, llama

    family, _, variant = site.partition("_")
    if variant == "pipeline":
        logits, labels, _ = _lm_case("plain")
        out = paddle.to_tensor(logits)
        loss_fn = {"gpt": gpt._gpt_pipeline_loss,
                   "llama": llama._llama_pipeline_loss}[family]
        got, want = loss_fn(out, paddle.to_tensor(labels)), \
            _shifted_cross_entropy(out, labels)
    else:
        model = _lm_model(family)
        model.eval()
        ids = np.random.default_rng(5).integers(0, 96, (2, 16)).astype(np.int32)
        kw = {}
        if variant == "segments":
            seg = np.repeat(np.array([[0, 1, 2, -1], [0, 0, 1, 1]], np.int32),
                            4, axis=1)
            kw["segments"] = paddle.to_tensor(seg)
        t = paddle.to_tensor(ids)
        got = model(t, labels=t, **kw)
        want = _shifted_cross_entropy(model(t, **kw), ids,
                                      seg if kw else None)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)


def test_clip_grad_by_global_norm():
    p1 = paddle.to_tensor(np.zeros(3, np.float32), stop_gradient=False)
    p2 = paddle.to_tensor(np.zeros(4, np.float32), stop_gradient=False)
    g1 = paddle.to_tensor(np.full(3, 3.0, np.float32))
    g2 = paddle.to_tensor(np.full(4, 4.0, np.float32))
    clip = nn.ClipGradByGlobalNorm(1.0)
    out = clip([(p1, g1), (p2, g2)])
    total = np.sqrt(sum((g.numpy() ** 2).sum() for _, g in out))
    np.testing.assert_allclose(total, 1.0, atol=1e-5)


def test_initializers():
    from paddle_tpu.nn import initializer as I

    w = I.XavierUniform()([64, 64], "float32")
    limit = np.sqrt(6.0 / 128)
    assert np.abs(np.asarray(w)).max() <= limit + 1e-6
    c = I.Constant(3.0)([2, 2], "float32")
    np.testing.assert_allclose(np.asarray(c), np.full((2, 2), 3.0))
