"""Chipless validation of the full Pallas kernel suite: evidence that every
kernel (a) LOWERS through the Pallas->Mosaic pipeline and (b) is NUMERICALLY
correct in interpret mode at chip-realistic shapes. Lowering is not
compiling: block shapes and scoped VMEM are held to the TPU compiler by
tests/test_tpu_compile.py, and execution on hardware by chip_smoke.py.

(a) uses `jax.export.export(jax.jit(f), platforms=["tpu"])`, which runs the
    Pallas->Mosaic lowering (the stage that rejected the r02 lse block
    shape) without needing a TPU client — a negative control with a
    misaligned block shape asserts the check actually catches that class.
(b) runs the kernels in interpret mode against dense jnp references.

Writes PALLAS_VALIDATION_r05.json at the repo root:
  {"ts": ..., "lowering": {case: {"ok": bool, ...}},
   "interpret": {case: {"ok": bool, "max_abs_err": float}},
   "negative_control_caught": bool}

Reference process model: tools/ci_op_benchmark.sh (the reference gates op
changes on benchmark+accuracy runs; this is the chipless analog).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# a CPU tool: pin the platform (and the 8-device host mesh) before jax loads
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(_REPO, "PALLAS_VALIDATION_r05.json")

report = {"ts": time.strftime("%Y-%m-%dT%H:%M:%S"), "backend": "chipless",
          "lowering": {}, "interpret": {}, "negative_control_caught": False}


def lower_tpu(name, fn, *avals):
    """Export `fn` for the TPU platform (runs Mosaic lowering) and record."""
    t0 = time.time()
    try:
        exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*avals)
        mlir = exp.mlir_module()
        report["lowering"][name] = {
            "ok": True,
            "tpu_custom_call": "tpu_custom_call" in mlir,
            "mlir_bytes": len(exp.mlir_module_serialized),
            "seconds": round(time.time() - t0, 2),
        }
        print(f"[lower] {name}: OK ({report['lowering'][name]['seconds']}s, "
              f"custom_call={report['lowering'][name]['tpu_custom_call']})")
    except Exception as e:  # noqa: BLE001 - recorded, not hidden
        report["lowering"][name] = {
            "ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
        print(f"[lower] {name}: FAIL {type(e).__name__}: {str(e)[:200]}")


def check_interp(name, got, want, tol):
    err = float(jnp.max(jnp.abs(jnp.asarray(got, jnp.float32)
                                - jnp.asarray(want, jnp.float32))))
    ok = bool(err <= tol)
    report["interpret"][name] = {"ok": ok, "max_abs_err": err, "tol": tol}
    print(f"[interp] {name}: {'OK' if ok else 'FAIL'} err={err:.3e}")


def dense_attn(q, k, v, causal, seg=None):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(d)
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        mask = jnp.tril(mask)
    if seg is not None:
        mask = mask & (seg[:, :, None] == seg[:, None, :])[:, None][0]
    if seg is not None:
        segm = (seg[:, :, None] == seg[:, None, :])[:, None, :, :]
        base = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool)) if causal \
            else jnp.ones((q.shape[1], k.shape[1]), bool)
        m = segm & base[None, None]
        s = jnp.where(m, s, -1e30)
    else:
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def main():
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention, flash_attention_segmented, flash_attention_with_lse)
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw_update
    from paddle_tpu.ops.pallas.fused_norm import fused_rms_norm
    from paddle_tpu.ops.pallas.rope import fused_rope

    # ---------------- (a) Mosaic lowering at chip-realistic shapes -------
    for tag, (b, s, h, d), dt in [
        ("b4_s2048_h16_d128_bf16", (4, 2048, 16, 128), jnp.bfloat16),
        ("b2_s4096_h8_d128_bf16", (2, 4096, 8, 128), jnp.bfloat16),
        ("b8_s1024_h12_d64_f32", (8, 1024, 12, 64), jnp.float32),
    ]:
        qa = jax.ShapeDtypeStruct((b, s, h, d), dt)
        lower_tpu(f"flash_fwd_causal_{tag}",
                  lambda q, k, v: flash_attention(q, k, v, causal=True),
                  qa, qa, qa)
        lower_tpu(
            f"flash_fwd_bwd_{tag}",
            lambda q, k, v: jax.grad(
                lambda q, k, v: jnp.sum(
                    flash_attention(q, k, v, causal=True)
                    .astype(jnp.float32) ** 2),
                argnums=(0, 1, 2))(q, k, v),
            qa, qa, qa)

    # ring-flash backward: the custom VJP that accepts LSE cotangents
    # (dlse folds into delta) — the exact path context_parallel drives
    qa = jax.ShapeDtypeStruct((2, 2048, 8, 128), jnp.bfloat16)

    def lse_loss(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse * 0.1)

    lower_tpu("flash_with_lse_bwd_b2_s2048_h8_d128_bf16",
              lambda q, k, v: jax.grad(lse_loss, argnums=(0, 1, 2))(q, k, v),
              qa, qa, qa)

    # varlen / segmented flash fwd+bwd
    qa = jax.ShapeDtypeStruct((2, 2048, 8, 128), jnp.bfloat16)
    sega = jax.ShapeDtypeStruct((2, 2048), jnp.int32)

    def seg_loss(q, k, v, seg):
        o = flash_attention_segmented(q, k, v, seg, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    lower_tpu("flash_segmented_fwd_b2_s2048_h8_d128_bf16",
              lambda q, k, v, seg: flash_attention_segmented(
                  q, k, v, seg, causal=True), qa, qa, qa, sega)
    lower_tpu("flash_segmented_bwd_b2_s2048_h8_d128_bf16",
              lambda q, k, v, seg: jax.grad(seg_loss, argnums=(0, 1, 2))(
                  q, k, v, seg), qa, qa, qa, sega)

    # fused elementwise kernels
    xa = jax.ShapeDtypeStruct((8, 2048, 4096), jnp.bfloat16)
    wa = jax.ShapeDtypeStruct((4096,), jnp.bfloat16)
    lower_tpu("fused_rms_norm_8x2048x4096_bf16",
              lambda x, w: fused_rms_norm(x, w), xa, wa)
    qr = jax.ShapeDtypeStruct((4, 2048, 16, 128), jnp.bfloat16)
    cosa = jax.ShapeDtypeStruct((2048, 128), jnp.float32)
    lower_tpu("rope_4x2048x16x128_bf16",
              lambda q, k, c, s: fused_rope(q, k, c, s), qr, qr, cosa, cosa)
    posa = jax.ShapeDtypeStruct((4, 2048), jnp.int32)
    taba = jax.ShapeDtypeStruct((2048, 128), jnp.float32)
    from paddle_tpu.ops.pallas.rope import fused_rope_packed

    lower_tpu("rope_packed_4x2048x16x128_bf16",
              lambda q, k, c, s, p_: fused_rope_packed(q, k, c, s, p_),
              qr, qr, taba, taba, posa)
    pa = jax.ShapeDtypeStruct((4096 * 4096,), jnp.float32)
    lower_tpu("fused_adamw_16M_flat_f32",
              lambda p, g, m, v: fused_adamw_update(p, g, m, v, lr=1e-3,
                                                    weight_decay=0.01,
                                                    step=1),
              pa, pa, pa, pa)

    # whole-model lowering: GPT fwd+bwd with the flash kernel enabled, and
    # the int8 weight-only decode matmuls (XLA path, TPU target)
    import paddle_tpu as paddle
    from paddle_tpu.ops.kernels.quant import weight_only_matmul

    paddle.set_flags({"use_flash_attention": True})
    try:
        from paddle_tpu import optimizer as popt
        from paddle_tpu.jit.trainer import TrainStep
        from paddle_tpu.models import GPTConfig, GPTForCausalLM

        paddle.seed(0)
        cfg = GPTConfig(vocab_size=8192, hidden_size=512, num_layers=2,
                        num_heads=8, max_position_embeddings=2048,
                        hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
        model = GPTForCausalLM(cfg)
        opt = popt.AdamW(1e-4, parameters=model.parameters())
        step = TrainStep(model, lambda ids: model(ids, labels=ids), opt,
                         donate=False)

        aval = lambda t: (jax.ShapeDtypeStruct(jnp.shape(t),
                                               jnp.result_type(t)))
        pv_a = [aval(p._value) for p in step.params]
        bv_a = [aval(b._value) for b in step.buffers]
        st_a = jax.tree_util.tree_map(aval, step.opt_state)
        lr_a = jax.ShapeDtypeStruct((), jnp.float32)
        seed_a = jax.ShapeDtypeStruct((), jnp.int32)
        ids_a = (jax.ShapeDtypeStruct((2, 1024), jnp.int32),)
        t0 = time.time()
        try:
            exp = jax.export.export(step._jitted, platforms=["tpu"])(
                pv_a, bv_a, st_a, lr_a, seed_a, ids_a)
            mlir = exp.mlir_module()
            report["lowering"]["gpt_trainstep_flash_b2_s1024"] = {
                "ok": True, "tpu_custom_call": "tpu_custom_call" in mlir,
                "mlir_bytes": len(exp.mlir_module_serialized),
                "seconds": round(time.time() - t0, 2),
            }
            print(f"[lower] gpt_trainstep_flash_b2_s1024: OK "
                  f"(custom_call={'tpu_custom_call' in mlir})")
        except Exception as e:  # noqa: BLE001
            report["lowering"]["gpt_trainstep_flash_b2_s1024"] = {
                "ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
            print(f"[lower] gpt_trainstep_flash_b2_s1024: FAIL "
                  f"{type(e).__name__}: {str(e)[:200]}")
    finally:
        paddle.set_flags({"use_flash_attention": False})

    xa8 = jax.ShapeDtypeStruct((1, 4096), jnp.bfloat16)
    w8 = jax.ShapeDtypeStruct((4096, 4096), jnp.int8)
    s8 = jax.ShapeDtypeStruct((4096,), jnp.float32)
    lower_tpu("int8_weight_only_decode_matmul_4096",
              lambda x, w, s: weight_only_matmul(x, w, s), xa8, w8, s8)

    # negative control: a block shape Mosaic must REJECT — proves the
    # lowering check can fail
    try:
        jax.export.export(
            jax.jit(lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=7, block_k=24)),
            platforms=["tpu"],
        )(jax.ShapeDtypeStruct((1, 840, 2, 128), jnp.bfloat16),
          jax.ShapeDtypeStruct((1, 840, 2, 128), jnp.bfloat16),
          jax.ShapeDtypeStruct((1, 840, 2, 128), jnp.bfloat16))
        print("[lower] negative control: NOT caught (check is toothless!)")
    except Exception:
        report["negative_control_caught"] = True
        print("[lower] negative control: caught (check has teeth)")

    # -------- (b) interpret-mode numerics at chip block shapes ----------
    rng = np.random.RandomState(0)
    b, s, h, d = 1, 1024, 2, 128
    mk = lambda dt: tuple(jnp.asarray(rng.randn(b, s, h, d) * 0.5, dt)
                          for _ in range(3))

    for dt, tol_o, tol_g in [(jnp.float32, 2e-5, 2e-4),
                             (jnp.bfloat16, 2e-2, 1e-1)]:
        q, k, v = mk(dt)
        for causal in (False, True):
            tag = f"s1024_d128_{'causal' if causal else 'full'}_{dt.__name__}"
            o = flash_attention(q, k, v, causal=causal, interpret=True)
            check_interp(f"flash_fwd_{tag}", o,
                         dense_attn(q, k, v, causal).astype(dt), tol_o)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, interpret=True).astype(jnp.float32) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(dense_attn(q, k, v, True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for nm, a, r in zip("qkv", gf, gr):
            check_interp(f"flash_bwd_d{nm}_s1024_{dt.__name__}", a, r,
                         tol_g * float(jnp.max(jnp.abs(r)) + 1))

    # with_lse backward incl. the dlse cotangent (ring path) vs autodiff
    # of the dense attention-with-lse
    q, k, v = mk(jnp.float32)

    def dense_lse_loss(q, k, v):
        dd = q.shape[-1]
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dd)
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        sc = jnp.where(mask[None, None], sc, -1e30)
        lse = jax.nn.logsumexp(sc, -1)  # (b,h,q)
        o = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(sc - lse[..., None]), v)
        return jnp.sum(o ** 2) + jnp.sum(jnp.sin(lse))

    def flash_lse_loss(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=True,
                                          interpret=True)
        return (jnp.sum(o.astype(jnp.float32) ** 2)
                + jnp.sum(jnp.sin(lse)))  # lse: (b, h, sq), same as dense

    gf = jax.grad(flash_lse_loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(dense_lse_loss, argnums=(0, 1, 2))(q, k, v)
    for nm, a, r in zip("qkv", gf, gr):
        check_interp(f"flash_with_lse_bwd_d{nm}_s1024_f32", a, r,
                     2e-4 * float(jnp.max(jnp.abs(r)) + 1))

    # segmented (varlen) fwd+bwd vs dense-masked, packed seqs of mixed len
    seg_np = np.zeros((b, s), np.int32)
    bounds = [0, 200, 456, 1000, s]
    for i in range(len(bounds) - 1):
        seg_np[:, bounds[i]:bounds[i + 1]] = i
    seg = jnp.asarray(seg_np)

    def dense_seg(q, k, v, causal=True):
        dd = q.shape[-1]
        sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(dd)
        m = (seg[:, :, None] == seg[:, None, :])[:, None]
        if causal:
            m = m & jnp.tril(jnp.ones((s, s), bool))[None, None]
        sc = jnp.where(m, sc, -1e30)
        p = jax.nn.softmax(sc, -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))

    q, k, v = mk(jnp.float32)
    o = flash_attention_segmented(q, k, v, seg, causal=True, interpret=True)
    check_interp("flash_segmented_fwd_s1024_packed4_f32", o,
                 dense_seg(q, k, v), 2e-5)

    def seg_loss_i(q, k, v):
        return jnp.sum(flash_attention_segmented(
            q, k, v, seg, causal=True, interpret=True) ** 2)

    def seg_loss_r(q, k, v):
        return jnp.sum(dense_seg(q, k, v) ** 2)

    gf = jax.grad(seg_loss_i, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(seg_loss_r, argnums=(0, 1, 2))(q, k, v)
    for nm, a, r in zip("qkv", gf, gr):
        check_interp(f"flash_segmented_bwd_d{nm}_s1024_f32", a, r,
                     2e-4 * float(jnp.max(jnp.abs(r)) + 1))

    # fused_rms_norm / rope at wide shapes vs jnp references
    x = jnp.asarray(rng.randn(4, 512, 1024), jnp.float32)
    w = jnp.asarray(rng.randn(1024) * 0.1 + 1.0, jnp.float32)
    ref = (x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)) * w
    check_interp("fused_rms_norm_4x512x1024_f32",
                 fused_rms_norm(x, w, interpret=True), ref, 1e-5)

    qr_ = jnp.asarray(rng.randn(2, 512, 8, 128), jnp.float32)
    pos = np.arange(512)[:, None]
    inv = 1.0 / (10000 ** (np.arange(0, 64) / 64.0))
    ang = pos * inv[None]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), jnp.float32)
    x1, x2 = qr_[..., :64], qr_[..., 64:]
    rot = jnp.concatenate([-x2, x1], -1)  # rotate_half, matching the kernel
    ref = qr_ * cos[None, :, None, :] + rot * sin[None, :, None, :]
    got_q, _got_k = fused_rope(qr_, qr_, cos, sin, interpret=True)
    check_interp("rope_2x512x8x128_f32", got_q, ref, 1e-5)

    p0 = jnp.asarray(rng.randn(512 * 1024), jnp.float32)
    g0 = jnp.asarray(rng.randn(512 * 1024) * 0.1, jnp.float32)
    m0 = jnp.zeros_like(p0)
    v0 = jnp.zeros_like(p0)
    p1, m1, v1 = fused_adamw_update(p0, g0, m0, v0, lr=1e-3,
                                    weight_decay=0.01, step=1,
                                    interpret=True)
    b1, b2, eps = 0.9, 0.999, 1e-8
    mr = (1 - b1) * g0
    vr = (1 - b2) * g0 * g0
    mh, vh = mr / (1 - b1), vr / (1 - b2)
    pr = p0 - 1e-3 * (mh / (jnp.sqrt(vh) + eps) + 0.01 * p0)
    check_interp("fused_adamw_512x1024_f32_p", p1, pr, 1e-6)

    # ------------------------------------------------------------ summary
    n_low = len(report["lowering"])
    ok_low = sum(1 for r in report["lowering"].values() if r["ok"])
    n_int = len(report["interpret"])
    ok_int = sum(1 for r in report["interpret"].values() if r["ok"])
    report["summary"] = {
        "lowering_ok": f"{ok_low}/{n_low}",
        "interpret_ok": f"{ok_int}/{n_int}",
        "all_ok": bool(ok_low == n_low and ok_int == n_int
                       and report["negative_control_caught"]),
    }
    with open(OUT, "w") as f:
        json.dump(report, f, indent=1)
    print(f"\nPALLAS VALIDATION: lowering {ok_low}/{n_low}, "
          f"interpret {ok_int}/{n_int}, negative control "
          f"{'caught' if report['negative_control_caught'] else 'MISSED'} "
          f"-> {os.path.basename(OUT)}")
    return 0 if report["summary"]["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
