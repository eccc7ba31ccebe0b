"""Measure the GPT pretrain step of one named preset, in this process, on the
TPU jax finds. Fails when there is none: a number from another backend is
never printed under a chip metric's name.

Usage:  python bench.py [preset]        (default: large = GPT 355M b8 s1024)

Prints ONE JSON line on stdout naming the device it ran on (platform,
device_kind, device count). The measured step is the full compiled train
step (forward + backward + AdamW, donated buffers) with bf16 compute via amp
auto_cast and Pallas flash attention on (FLAGS_use_flash_attention). MFU is
against the published peak of the device kind
(observability/telemetry.DEVICE_PEAKS); an unknown device raises.

One process owns the chip: nothing here starts a child. The benchmark PR
(ROADMAP S1) rewrites this file as a list of cells.
"""
from __future__ import annotations

import json
import sys
import time

# (model kwargs, batch, seq, timed_steps)
PRESETS = {
    # bf16 params via amp O2 (fp32 master in the optimizer), batch 32, remat
    "large_o2b32": dict(hidden_size=1024, num_layers=24, num_heads=16,
                        batch=32, seq=1024, timed_steps=10,
                        o2=True, recompute=True),
    "large_o2b16": dict(hidden_size=1024, num_layers=24, num_heads=16,
                        batch=16, seq=1024, timed_steps=10, o2=True),
    # GPT-3 Medium, ~355M params: the configuration of MFU_PROBE.jsonl
    "large": dict(hidden_size=1024, num_layers=24, num_heads=16,
                  batch=8, seq=1024, timed_steps=10),
    "medium": dict(hidden_size=1024, num_layers=12, num_heads=16,
                   batch=8, seq=1024, timed_steps=10),
    "small": dict(hidden_size=768, num_layers=12, num_heads=12,
                  batch=8, seq=512, timed_steps=10),
}


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def run(preset: str) -> dict:
    p = PRESETS[preset]
    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures on a TPU and jax found {dev.platform!r} "
            f"({dev.device_kind}); nothing measured")

    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.jit import enable_persistent_cache
    from paddle_tpu.jit.trainer import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import telemetry as _telemetry

    cache_dir = enable_persistent_cache()   # before the first compile

    cfg = GPTConfig(
        vocab_size=50304, hidden_size=p["hidden_size"],
        num_layers=p["num_layers"], num_heads=p["num_heads"],
        max_position_embeddings=1024,
        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
        recompute=p.get("recompute", False),
    )
    batch, seq, timed_steps = p["batch"], p["seq"], p["timed_steps"]

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    n_params = sum(int(np.prod(q.shape)) for q in model.parameters())
    log(f"[{preset}] {dev.device_kind} params: {n_params / 1e6:.1f}M "
        f"batch={batch} seq={seq} o2={p.get('o2', False)} "
        f"recompute={p.get('recompute', False)}")

    opt = optimizer.AdamW(1e-4, parameters=model.parameters(), weight_decay=0.01)
    amp_level = "O1"
    if p.get("o2"):
        # O2: bf16 params (fp32 master weights in the optimizer) + O2 cast
        # rules in the forward — the idiomatic decorate/auto_cast pairing
        model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
        amp_level = "O2"

    def loss_fn(ids):
        with amp.auto_cast(level=amp_level, dtype="bfloat16"):
            return model(ids, labels=ids)

    step = TrainStep(model, loss_fn, opt)
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32))

    t0 = time.perf_counter()
    first_loss = float(jax.block_until_ready(step(ids)._value))
    compile_s = time.perf_counter() - t0
    log(f"[{preset}] compile+first step: {compile_s:.1f}s "
        f"loss={first_loss:.3f}")
    jax.block_until_ready(step(ids)._value)  # warm
    t0 = time.perf_counter()
    for _ in range(timed_steps):
        loss = step(ids)
    jax.block_until_ready(loss._value)
    dt = time.perf_counter() - t0
    tokens_per_sec = timed_steps / dt * batch * seq

    # FLOPs/token: 6*N (fwd+bwd matmuls) + 12*L*h*s attention term
    flops_per_token = (6.0 * n_params
                       + 12.0 * cfg.num_layers * cfg.hidden_size * seq)
    mfu = tokens_per_sec * flops_per_token / _telemetry.peak_flops(
        dev.device_kind)

    result = {
        "metric": "gpt_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "mfu": round(mfu, 4),
        "step_ms": round(dt / timed_steps * 1e3, 2),
        "compile_s": round(compile_s, 1),
        "params_millions": round(n_params / 1e6, 1),
        "batch": batch,
        "seq": seq,
        "preset": preset,
        "flash_attention": bool(_flags.get_flag("use_flash_attention")),
        "compile_cache": cache_dir,
        "final_loss": round(float(loss._value), 4),
    }
    # with FLAGS_metrics=on the TrainStep itself recorded per-step
    # loss/gnorm/phase times: attach the runtime's own accounting
    if _telemetry.enabled():
        tele = _telemetry.get_telemetry()
        tele.finalize()
        result["telemetry"] = tele.summary()
    return result


if __name__ == "__main__":
    name = sys.argv[1] if len(sys.argv) > 1 else "large"
    if name not in PRESETS:
        raise SystemExit(f"unknown preset {name!r}; known: {sorted(PRESETS)}")
    print(json.dumps(run(name)), flush=True)
