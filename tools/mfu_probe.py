"""MFU ablation probe: run the flagship train step on the real chip under
several knob settings and print per-config tokens/s + MFU.

Usage: python tools/mfu_probe.py [config ...]
Configs: baseline flashoff batch16 seq2048 o2 o2b16 o2b32flash

Every completed measurement is ALSO appended immediately as a JSON line to
MFU_PROBE.jsonl at the repo root (override with MFU_PROBE_OUT), so a run
that dies part-way keeps what it already measured.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
OUT_PATH = os.environ.get("MFU_PROBE_OUT",
                          os.path.join(_REPO, "MFU_PROBE.jsonl"))


def measure(name, hidden=1024, layers=24, heads=16, batch=8, seq=1024,
            steps=5, flash=True, o2=False, recompute=False, packed=False):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.jit.trainer import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability.telemetry import peak_flops

    _flags.set_flags({"use_flash_attention": flash})
    cfg = GPTConfig(vocab_size=50304, hidden_size=hidden, num_layers=layers,
                    num_heads=heads, max_position_embeddings=max(seq, 1024),
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    recompute=recompute)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    n_params = sum(int(np.prod(q.shape)) for q in model.parameters())
    opt = optimizer.AdamW(1e-4, parameters=model.parameters(), weight_decay=0.01)
    level = "O1"
    if o2:
        model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
        level = "O2"

    if packed:
        # varlen path: packed documents, segmented flash attention
        from paddle_tpu.io.packing import pack_examples

        rng = np.random.RandomState(0)
        docs, total = [], 0
        while total < batch * seq:
            n = int(rng.randint(seq // 8, seq))
            docs.append(rng.randint(0, cfg.vocab_size, n).astype(np.int32))
            total += n
        ids_np, seg_np, lab_np = (a[:batch] for a in
                                  pack_examples(docs, seq))

        def loss_fn(ids, seg, lab):
            with amp.auto_cast(level=level, dtype="bfloat16"):
                return model(ids, labels=lab, segments=seg)

        _step = TrainStep(model, loss_fn, opt)
        _seg = paddle.to_tensor(seg_np)
        _lab = paddle.to_tensor(lab_np)
        step = lambda ids: _step(ids, _seg, _lab)  # noqa: E731
        ids = paddle.to_tensor(ids_np)
    else:
        def loss_fn(ids):
            with amp.auto_cast(level=level, dtype="bfloat16"):
                return model(ids, labels=ids)

        step = TrainStep(model, loss_fn, opt)
        ids = paddle.to_tensor(
            np.random.randint(0, cfg.vocab_size,
                              (batch, seq)).astype(np.int32))
    t0 = time.time()
    loss = step(ids)
    float(loss.item())
    compile_s = time.time() - t0
    float(step(ids).item())
    t0 = time.time()
    for _ in range(steps):
        loss = step(ids)
    float(loss.item())
    dt = (time.time() - t0) / steps
    tps = batch * seq / dt
    fpt = 6.0 * n_params + 12.0 * layers * hidden * seq
    mfu = tps * fpt / peak_flops()   # raises off the peaks table
    print(f"{name:12s} params={n_params/1e6:.0f}M batch={batch} seq={seq} "
          f"flash={int(flash)} o2={int(o2)} compile={compile_s:.0f}s "
          f"step={dt*1000:.1f}ms tok/s={tps:,.0f} MFU={mfu:.3f}",
          flush=True)
    with open(OUT_PATH, "a") as f:
        f.write(json.dumps({
            "config": name, "backend": jax.default_backend(),
            "params_millions": round(n_params / 1e6, 1),
            "batch": batch, "seq": seq, "flash": flash, "o2": o2,
            "recompute": recompute, "packed": packed,
            "compile_s": round(compile_s, 1),
            "step_ms": round(dt * 1000, 2), "tokens_per_sec": round(tps, 1),
            "mfu": round(mfu, 4), "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }) + "\n")
    del step, model, opt
    return mfu


CONFIGS = {
    "baseline": dict(),
    "flashoff": dict(flash=False),
    "batch16": dict(batch=16),
    "batch32": dict(batch=32),
    "seq2048": dict(batch=4, seq=2048),
    "o2": dict(o2=True),
    "o2b16": dict(o2=True, batch=16),
    "o2b32": dict(o2=True, batch=32),
    "o2b32r": dict(o2=True, batch=32, recompute=True),
    "o2b16flashoff": dict(o2=True, batch=16, flash=False),
    "o2b64r": dict(o2=True, batch=64, recompute=True),
    "o2s2048b16r": dict(o2=True, batch=16, seq=2048, recompute=True),
    "o2b16packed": dict(o2=True, batch=16, packed=True),
    "o2s2048b8packed": dict(o2=True, batch=8, seq=2048, packed=True),
}


def main():
    import jax

    names = sys.argv[1:] or ["baseline", "flashoff", "o2", "batch16"]
    print(f"backend={jax.default_backend()} devices={len(jax.devices())}",
          flush=True)
    for n in names:
        try:
            measure(n, **CONFIGS[n])
        except Exception as e:
            print(f"{n:12s} FAILED: {type(e).__name__}: {str(e)[:200]}",
                  flush=True)


if __name__ == "__main__":
    main()
