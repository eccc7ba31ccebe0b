"""Model-family benchmarks filling BASELINE.md's 'to be measured' rows:

  lenet   — LeNet MNIST dygraph fp32, steps/s (BASELINE configs[0])
  resnet  — ResNet-50 static-graph Executor + AMP O2, images/s (configs[1])
  bert    — BERT-base dygraph + fused attention path, tokens/s (configs[2])

Usage: python tools/modelbench.py [lenet resnet bert]
Each measurement appends a row, naming the backend it ran on, to
MODELBENCH_r05.jsonl the moment it lands, so a run that dies part-way keeps
its earlier rows. Every timed loop ends in a value fetch, which waits for
the device. One process, one platform: a model that fails is reported as
failed, not re-run somewhere else."""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
OUT = os.path.join(_REPO, "MODELBENCH_r05.jsonl")


def _persist(row):
    import jax

    row = dict(row, backend=jax.default_backend(),
               ts=time.strftime("%Y-%m-%dT%H:%M:%S"))
    with open(OUT, "a") as f:
        f.write(json.dumps(row) + "\n")
    print(json.dumps(row), flush=True)


def bench_lenet():
    import paddle_tpu as paddle
    from paddle_tpu.jit.trainer import TrainStep
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet(num_classes=10)
    opt = paddle.optimizer.Adam(1e-3, parameters=model.parameters())
    ce = paddle.nn.CrossEntropyLoss()
    batch = 8 if os.environ.get("MODELBENCH_SMOKE") else 256

    def loss_fn(x, y):
        return ce(model(x), y)

    step = TrainStep(model, loss_fn, opt)
    x = paddle.to_tensor(
        np.random.RandomState(0).rand(batch, 1, 28, 28).astype(np.float32))
    y = paddle.to_tensor(
        np.random.RandomState(1).randint(0, 10, batch).astype(np.int64))
    t0 = time.time()
    float(step(x, y).item())
    compile_s = time.time() - t0
    float(step(x, y).item())
    n = 3 if os.environ.get("MODELBENCH_SMOKE") else 50
    t0 = time.time()
    for _ in range(n):
        loss = step(x, y)
    float(loss.item())
    dt = (time.time() - t0) / n
    _persist({"model": "lenet_mnist_dygraph_fp32", "batch": batch,
              "steps_per_sec": round(1 / dt, 2),
              "images_per_sec": round(batch / dt, 1),
              "compile_s": round(compile_s, 1)})


def bench_resnet():
    import paddle_tpu as paddle
    import paddle_tpu.static as static
    from paddle_tpu import amp
    from paddle_tpu.vision.models import resnet50

    batch = int(os.environ.get("RESNET_BATCH",
                               "2" if os.environ.get("MODELBENCH_SMOKE") else "64"))
    paddle.seed(0)
    # build the model eagerly (params init), then capture the train step
    # as a static Program: the reference config is static-graph
    # StandaloneExecutor + AMP O2
    model = resnet50(num_classes=1000)
    model, opt = amp.decorate(
        model, paddle.optimizer.Momentum(0.1, parameters=model.parameters()),
        level="O2", dtype="bfloat16")
    ce = paddle.nn.CrossEntropyLoss()
    paddle.enable_static()
    try:
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [batch, 3, 224, 224])
            y = static.data("y", [batch], dtype="int64")
            # O2 scheme: decorate() cast every param to bf16 and the input
            # is cast explicitly — the recorded tape IS the O2 program
            # (auto_cast's per-op hook is a dygraph-dispatch feature)
            loss = ce(model(paddle.cast(x, "bfloat16")), y)
            opt.minimize(loss)
        exe = static.Executor()
        feed = {
            "x": np.random.RandomState(0).rand(
                batch, 3, 224, 224).astype(np.float32),
            "y": np.random.RandomState(1).randint(
                0, 1000, batch).astype(np.int64),
        }
        t0 = time.time()
        exe.run(prog, feed=feed, fetch_list=[loss])
        compile_s = time.time() - t0
        exe.run(prog, feed=feed, fetch_list=[loss])
        n = 2 if os.environ.get("MODELBENCH_SMOKE") else 20
        t0 = time.time()
        for _ in range(n):
            out = exe.run(prog, feed=feed, fetch_list=[loss])
        float(np.asarray(out[0]).ravel()[0])
        dt = (time.time() - t0) / n
    finally:
        paddle.disable_static()
    _persist({"model": "resnet50_static_amp_o2", "batch": batch,
              "images_per_sec": round(batch / dt, 1),
              "step_ms": round(dt * 1000, 2),
              "compile_s": round(compile_s, 1)})


def bench_bert():
    import paddle_tpu as paddle
    from paddle_tpu.jit.trainer import TrainStep
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    smoke = bool(os.environ.get("MODELBENCH_SMOKE"))
    batch, seq = (2, 64) if smoke else (16, 512)
    cfg = BertConfig() if not smoke else BertConfig(
        vocab_size=1000, hidden_size=64, num_layers=2, num_heads=4,
        intermediate_size=128)  # base: L12 H768 A12
    paddle.seed(0)
    model = BertForPretraining(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())

    from paddle_tpu import amp

    def loss_fn(ids, mlm_labels):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return model(ids, masked_lm_labels=mlm_labels)

    step = TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    # 15% MLM positions; the rest ignored (-100)
    lab_np = np.full((batch, seq), -100, np.int32)
    mask = rng.rand(batch, seq) < 0.15
    lab_np[mask] = rng.randint(0, cfg.vocab_size, int(mask.sum()))
    lab = paddle.to_tensor(lab_np)
    t0 = time.time()
    float(step(ids, lab).item())
    compile_s = time.time() - t0
    float(step(ids, lab).item())
    n = 2 if os.environ.get("MODELBENCH_SMOKE") else 10
    t0 = time.time()
    for _ in range(n):
        loss = step(ids, lab)
    float(loss.item())
    dt = (time.time() - t0) / n
    tps = batch * seq / dt
    _persist({"model": "bert_base_pretrain_dygraph", "batch": batch,
              "seq": seq, "params_millions": round(n_params / 1e6, 1),
              "tokens_per_sec": round(tps, 1),
              "step_ms": round(dt * 1000, 2),
              "compile_s": round(compile_s, 1)})


def _count_rows() -> int:
    try:
        with open(OUT) as f:
            return sum(1 for line in f if line.strip())
    except OSError:
        return 0


def main() -> int:
    names = sys.argv[1:] or ["lenet", "resnet", "bert"]
    import jax

    backend = jax.default_backend()
    print(f"backend={backend}", flush=True)
    fns = {"lenet": bench_lenet, "resnet": bench_resnet, "bert": bench_bert}
    rows_before = _count_rows()
    failures = []
    for n in names:
        try:
            fns[n]()
        except Exception as e:  # keep harvesting the rest
            msg = f"{type(e).__name__}: {str(e)[:300]}"
            print(f"{n} FAILED: {msg}", flush=True)
            failures.append({"model": n, "error": msg})
    if _count_rows() == rows_before:
        # NOTHING landed: write an explicit error row (never a silent empty
        # file) and fail the process so CI can't mistake this for success
        with open(OUT, "a") as f:
            f.write(json.dumps({
                "model": "modelbench", "error": "no measurements landed",
                "backend": backend, "failures": failures,
                "ts": time.strftime("%Y-%m-%dT%H:%M:%S")}) + "\n")
        print("modelbench: FAILED — no measurements landed", file=sys.stderr)
        return 1
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
