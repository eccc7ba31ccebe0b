"""End-to-end model tests (reference: test/book/ pattern)."""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.jit.trainer import TrainStep
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.vision.models import LeNet, resnet18


def test_lenet_mnist_converges():
    """The M0-M2 e2e slice (LeNet on MNIST)."""
    from paddle_tpu.vision.datasets import MNIST

    paddle.seed(0)
    ds = MNIST(mode="train")
    model = LeNet()
    opt = optimizer.Adam(1e-3, parameters=model.parameters())
    loss_fn = nn.CrossEntropyLoss()
    step = TrainStep(model, lambda a, b: loss_fn(model(a), b), opt)

    from paddle_tpu.io import DataLoader

    loader = DataLoader(ds, batch_size=128, shuffle=True)
    losses = []
    for i, (x, y) in enumerate(loader):
        losses.append(float(step(x, y).item()))
        if i >= 20:
            break
    assert np.mean(losses[-3:]) < np.mean(losses[:3]) * 0.5, losses

    # accuracy on a fresh batch
    model.eval()
    x, y = next(iter(DataLoader(MNIST(mode="test"), batch_size=256)))
    pred = model(x).numpy().argmax(-1)
    acc = (pred == y.numpy()).mean()
    assert acc > 0.6, acc


def test_resnet18_forward_backward():
    model = resnet18(num_classes=10)
    x = paddle.to_tensor(np.random.randn(2, 3, 32, 32).astype(np.float32))
    out = model(x)
    assert out.shape == [2, 10]
    loss = out.sum()
    loss.backward()
    assert model.conv1.weight.grad is not None


def test_gpt_forward_loss_and_step():
    cfg = GPTConfig.tiny()
    model = GPTForCausalLM(cfg)
    ids = paddle.to_tensor(np.random.randint(0, cfg.vocab_size, (2, 16)), dtype="int32")
    logits = model(ids)
    assert logits.shape == [2, 16, cfg.vocab_size]
    loss = model(ids, labels=ids)
    assert abs(float(loss.item()) - np.log(cfg.vocab_size)) < 1.0

    opt = optimizer.AdamW(1e-3, parameters=model.parameters())
    step = TrainStep(model, lambda a: model(a, labels=a), opt)
    losses = [float(step(ids).item()) for _ in range(8)]
    assert losses[-1] < losses[0]  # memorizing a fixed batch


def test_gpt_rotary_variant():
    cfg = GPTConfig.tiny()
    cfg.use_rotary = True
    model = GPTForCausalLM(cfg)
    ids = paddle.to_tensor(np.random.randint(0, cfg.vocab_size, (1, 8)), dtype="int32")
    assert model(ids).shape == [1, 8, cfg.vocab_size]


def test_gpt_causality():
    """Changing a future token must not affect earlier logits."""
    cfg = GPTConfig.tiny()
    model = GPTForCausalLM(cfg)
    model.eval()
    ids1 = np.random.randint(0, cfg.vocab_size, (1, 10)).astype(np.int32)
    ids2 = ids1.copy()
    ids2[0, -1] = (ids2[0, -1] + 1) % cfg.vocab_size
    l1 = model(paddle.to_tensor(ids1)).numpy()
    l2 = model(paddle.to_tensor(ids2)).numpy()
    np.testing.assert_allclose(l1[0, :9], l2[0, :9], atol=1e-4)
    assert not np.allclose(l1[0, 9], l2[0, 9], atol=1e-4)
