"""Op-level TPU parity microbenchmarks.

BASELINE.md last row: per-op gap vs native JAX/XLA must be <= 5% on
matmul / layer_norm / flash_attn / embedding. Process model: the reference's
perf-gated CI (tools/ci_op_benchmark.sh + check_op_benchmark_result.py:1) —
each op timed against an independent hand-written jax implementation, JSON
out, ratio > threshold flags a regression.

Usage: python tools/opbench.py [--out OPBENCH.json]
Every op is timed compiled (jit + block_until_ready), median of `reps` runs.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sync(out):
    """Wait for every output leaf (dispatch is asynchronous)."""
    import jax

    jax.block_until_ready(out)


def time_fn(fn, *args, reps=5, warmup=3, inner=20):
    """Median over `reps` of (launch `inner` executions, sync once) / inner.
    Device queues are FIFO, so one trailing sync covers the whole batch —
    amortizing host dispatch latency that would otherwise floor every
    measurement of a small kernel."""
    import jax

    f = jax.jit(fn)
    for _ in range(warmup):
        _sync(f(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = None
        for _ in range(inner):
            out = f(*args)
        _sync(out)
        times.append((time.perf_counter() - t0) * 1e6 / inner)
    return statistics.median(times)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    backend = jax.default_backend()
    rng = np.random.default_rng(0)

    from paddle_tpu.ops.kernels import nn_ops
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw_update
    from paddle_tpu.ops.pallas.fused_norm import fused_rms_norm
    from paddle_tpu.ops.pallas.rope import fused_rope

    results = {"backend": backend, "ops": {}}

    def bench(name, ours, native, *arrays):
        t_ours = time_fn(ours, *arrays, reps=args.reps)
        t_native = time_fn(native, *arrays, reps=args.reps)
        ratio = t_ours / t_native
        results["ops"][name] = {
            "ours_us": round(t_ours, 1),
            "native_jax_us": round(t_native, 1),
            "ratio": round(ratio, 4),
        }
        print(f"  {name:24s} ours={t_ours:9.1f}us native={t_native:9.1f}us "
              f"ratio={ratio:.3f}", file=sys.stderr)

    bf16 = jnp.bfloat16

    # matmul — the MXU headliner
    a = jnp.asarray(rng.standard_normal((4096, 4096)), bf16)
    b = jnp.asarray(rng.standard_normal((4096, 4096)), bf16)
    bench("matmul_4096_bf16",
          lambda a, b: nn_ops.linear(a, b),
          lambda a, b: a @ b, a, b)

    # layer_norm
    x = jnp.asarray(rng.standard_normal((8192, 2048)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(2048), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(2048), jnp.float32)

    def native_ln(x, w, bias):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * w + bias

    bench("layer_norm_8192x2048",
          lambda x, w, b_: nn_ops.layer_norm(x, (2048,), w, b_),
          native_ln, x, w, bias)

    # rms_norm: Pallas kernel vs XLA composition
    def native_rms(x, w):
        ms = jnp.mean(x * x, -1, keepdims=True)
        return x * jax.lax.rsqrt(ms + 1e-6) * w

    bench("rms_norm_8192x2048",
          lambda x, w: fused_rms_norm(x, w),
          native_rms, x, w)

    # flash attention vs XLA sdpa
    q = jnp.asarray(rng.standard_normal((4, 2048, 16, 128)), bf16)

    def native_sdpa(q, k, v):
        qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / math.sqrt(128)
        mask = jnp.tril(jnp.ones((2048, 2048), bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s.astype(jnp.float32), -1).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vt).transpose(0, 2, 1, 3)

    bench("flash_attn_2048_causal",
          lambda q, k, v: flash_attention(q, k, v, None, True),
          native_sdpa, q, q, q)

    # embedding gather
    ids = jnp.asarray(rng.integers(0, 50304, (8, 2048)), jnp.int32)
    table = jnp.asarray(rng.standard_normal((50304, 2048)), bf16)
    bench("embedding_50k_2048",
          lambda ids, t: nn_ops.embedding(ids, t),
          lambda ids, t: jnp.take(t, ids, axis=0), ids, table)

    # softmax
    logits = jnp.asarray(rng.standard_normal((8192, 4096)), jnp.float32)
    bench("softmax_8192x4096",
          lambda x: nn_ops.softmax(x, axis=-1),
          lambda x: jax.nn.softmax(x, axis=-1), logits)

    # fused AdamW vs unfused composition
    n = 50_000_000
    p = jnp.asarray(rng.standard_normal(n), jnp.float32)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)
    m = jnp.zeros(n, jnp.float32)
    v = jnp.zeros(n, jnp.float32)

    def native_adamw(p, g, m, v):
        lr, b1, b2, eps, wd = 1e-3, 0.9, 0.999, 1e-8, 0.01
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh = m / (1 - b1)
        vh = v / (1 - b2)
        return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p), m, v

    bench("adamw_50M",
          lambda p, g, m, v: fused_adamw_update(p, g, m, v, lr=1e-3,
                                                weight_decay=0.01),
          native_adamw, p, g, m, v)

    # RoPE fused vs composition
    qr = jnp.asarray(rng.standard_normal((8, 2048, 16, 128)), bf16)
    pos = np.arange(2048)[:, None]
    inv = 1.0 / (10000 ** (np.arange(0, 128, 2) / 128))
    ang = np.concatenate([pos * inv, pos * inv], axis=1)
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)

    def native_rope(x, cos, sin):
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
        x1, x2 = x[..., :64], x[..., 64:]
        rot = jnp.concatenate([-x2, x1], axis=-1)
        return (x * c + rot * s).astype(x.dtype)

    bench("rope_8x2048x16x128",
          lambda x, c, s: fused_rope(x, x, c, s)[0],
          native_rope, qr, cos, sin)

    # conv2d (ResNet-shaped)
    img = jnp.asarray(rng.standard_normal((32, 64, 56, 56)), bf16)
    kern = jnp.asarray(rng.standard_normal((64, 64, 3, 3)), bf16)

    def native_conv(img, kern):
        dn = jax.lax.conv_dimension_numbers(img.shape, kern.shape,
                                            ("NCHW", "OIHW", "NCHW"))
        return jax.lax.conv_general_dilated(img, kern, (1, 1),
                                            [(1, 1), (1, 1)],
                                            dimension_numbers=dn)

    bench("conv2d_resnet_block",
          lambda i, k: nn_ops.conv2d(i, k, padding=1),
          native_conv, img, kern)

    worst = max(r["ratio"] for r in results["ops"].values())
    results["worst_ratio"] = round(worst, 4)
    # the BASELINE.md gate covers these ops only; the rest are informational
    gated = [r["ratio"] for name, r in results["ops"].items()
             if name.startswith(("matmul", "layer_norm", "flash_attn",
                                 "embedding"))]
    results["gated_worst_ratio"] = round(max(gated), 4)
    results["pass_5pct_gate"] = bool(max(gated) <= 1.05)
    out = json.dumps(results)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
