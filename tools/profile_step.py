"""On-chip step profile: capture the XLA device timeline (xplane) for the
flagship pretrain step and write a per-op device-time breakdown.

Usage: python tools/profile_step.py [config]   (config from mfu_probe.CONFIGS,
default 'baseline'; output PROFILE_r05.json + raw trace under /tmp)

This is the measurement that directs MFU work: the step-time gap vs roofline
can hide in the attention kernel, the lm-head/CE traffic, the optimizer, or
host gaps — the xplane breakdown says which. Reference process model: the
reference profiles kernels via CUPTI and reports per-op device totals
(paddle/fluid/platform/profiler/profiler_statistic.cc SumEvent); here the
device timeline comes from jax.profiler's xplane protobufs parsed by
paddle_tpu.profiler.xplane.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import mfu_probe  # noqa: E402  (sibling tool: reuses model/step setup)


def main():
    name = sys.argv[1] if len(sys.argv) > 1 else "baseline"
    configs = dict(mfu_probe.CONFIGS,
                   tiny=dict(hidden=128, layers=2, heads=4, batch=2, seq=128))
    knobs = dict(configs[name])
    out_path = os.path.join(_REPO, os.environ.get("PROFILE_OUT",
                                                  "PROFILE_r05.json"))
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import amp, optimizer
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.jit.trainer import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.profiler.xplane import device_events

    backend = jax.default_backend()
    print(f"backend={backend}", flush=True)

    hidden = knobs.pop("hidden", 1024)
    layers = knobs.pop("layers", 24)
    heads = knobs.pop("heads", 16)
    batch = knobs.pop("batch", 8)
    seq = knobs.pop("seq", 1024)
    flash = knobs.pop("flash", True)
    o2 = knobs.pop("o2", False)
    recompute = knobs.pop("recompute", False)
    knobs.pop("packed", None)  # profile uses the rectangular path

    _flags.set_flags({"use_flash_attention": flash})
    cfg = GPTConfig(vocab_size=50304, hidden_size=hidden, num_layers=layers,
                    num_heads=heads, max_position_embeddings=max(seq, 1024),
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    recompute=recompute)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = optimizer.AdamW(1e-4, parameters=model.parameters(),
                          weight_decay=0.01)
    level = "O1"
    if o2:
        model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")
        level = "O2"

    def loss_fn(ids):
        with amp.auto_cast(level=level, dtype="bfloat16"):
            return model(ids, labels=ids)

    step = TrainStep(model, loss_fn, opt)
    ids = paddle.to_tensor(
        np.random.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))

    t0 = time.time()
    float(step(ids).item())  # compile
    print(f"compile {time.time() - t0:.0f}s", flush=True)
    float(step(ids).item())  # warm

    trace_dir = tempfile.mkdtemp(prefix="ptpu_profile_")
    n_steps = 3
    with jax.profiler.trace(trace_dir):
        loss = None
        for _ in range(n_steps):
            loss = step(ids)
        float(loss.item())

    # Aggregate: device planes only (TPU plane names carry 'TPU'/'device');
    # keep XLA-op lanes, drop derived/utility lines (steps, scopes).
    evs = list(device_events(trace_dir))
    plane_names = {ev["plane"] for ev in evs}
    device_planes = {p for p in plane_names
                     if "TPU" in p or "Device" in p or "device" in p}
    if not device_planes:  # CPU fallback: everything is on the host plane
        device_planes = plane_names
    totals: dict = {}
    for ev in evs:
        if ev["plane"] not in device_planes:
            continue
        line = ev["line"].lower()
        if "step" in line or "scope" in line:
            continue
        t = totals.setdefault(ev["name"], [0, 0])
        t[0] += ev["dur_ns"]
        t[1] += 1
    top = sorted(totals.items(), key=lambda kv: -kv[1][0])[:40]
    dev_total_ms = sum(v[0] for v in totals.values()) / 1e6 / n_steps
    report = {
        "config": name, "backend": backend, "batch": batch, "seq": seq,
        "flash": flash, "o2": o2, "recompute": recompute,
        "steps_profiled": n_steps,
        "device_time_ms_per_step": round(dev_total_ms, 2),
        "planes": sorted(plane_names),
        "top_ops": [{"name": k[:160], "total_ms_per_step":
                     round(v[0] / 1e6 / n_steps, 3), "count": v[1]}
                    for k, v in top],
        "trace_dir": trace_dir,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    print(f"wrote {out_path}: device {dev_total_ms:.1f} ms/step over "
          f"{len(totals)} ops; top: "
          + ", ".join(f"{k[:40]}={v[0] / 1e6 / n_steps:.2f}ms"
                      for k, v in top[:5]), flush=True)


if __name__ == "__main__":
    main()
