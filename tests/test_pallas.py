"""Pallas kernel tests (interpret mode on the CPU mesh).

Reference test model: the flash_attn op tests in test/legacy_test/ compare the
fused kernel against the unfused composition for fwd values and analytic
grads; same structure here (SURVEY.md §4).
"""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.fused_adamw import fused_adamw_update
from paddle_tpu.ops.pallas.fused_norm import fused_rms_norm
from paddle_tpu.ops.pallas.rope import fused_rope

# the package exports the function under the module's name
_fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

B, S, H, D = 2, 256, 4, 64


def _qkv(seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    return mk(), mk(), mk()


def _ref_attn(q, k, v, causal):
    scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        m = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(m, logits, -1e30)
    p = jax.nn.softmax(logits, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_forward(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, None, causal, 128, 128, True)
    ref = _ref_attn(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(causal):
    q, k, v = _qkv(1)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, None, causal, 128, 128, True) ** 2).sum()

    def loss_ref(q, k, v):
        return (_ref_attn(q, k, v, causal) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=0.15, rtol=5e-2)


# ---- the train kernels' inner loop: operands in the dtype of the inputs,
# ---- the causal mask only on crossed tiles, a grid step of several tiles.
# At 512 positions tiles of 128 and 256 give, per kernel, tiles wholly under
# the diagonal, tiles it crosses corner to corner and (unequal sides) tiles it
# crosses only in part. The reference is float32 on the same (rounded) inputs.
# float32 inputs: the tolerances of the segmented kernel's float32 test. bf16:
# twice what the kernels before PR 33 (float32 casts of every operand) read on
# these cases in interpret mode, output 7.3e-3 and gradients 1.2e-2; lse they
# read to 1e-6, and so does this one at head size 64, where the scale is a
# power of two and q.kT exact; at 128 the scaled q tile is rounded to bf16
# once (1.8e-3 read).
_S = 512
_TOL = {"float32": {"o": 2e-5, "grad": 2e-4, "lse": 2e-5},
        "bfloat16": {"o": 1.5e-2, "grad": 2.5e-2, "lse": 2e-5}}
_LSE_TOL_ROUNDED_SCALE = 4e-3


@functools.lru_cache(maxsize=None)
def _inner_loop_case(dtype, d, causal, blocks, lse_weight, s=_S):
    rng = np.random.default_rng(7)
    mk = lambda: jnp.asarray(rng.standard_normal((1, s, 2, d)),
                             jnp.float32).astype(dtype)
    q, k, v = mk(), mk(), mk()
    w = jnp.asarray(rng.standard_normal((1, s, 2, d)), jnp.float32)
    u = lse_weight * jnp.asarray(rng.standard_normal((1, 2, s)), jnp.float32)
    f32 = lambda x: x.astype(jnp.float32)

    def ref(q, k, v):
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") / np.sqrt(d)
        if causal:
            sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -1e30)
        lse = jax.nn.logsumexp(sc, -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(sc - lse[..., None]), v,
                       precision="highest")
        return o, lse

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            return jnp.sum(f32(o) * w) + jnp.sum(lse * u), (o, lse)
        return jax.value_and_grad(f, (0, 1, 2), has_aux=True)

    (_, (o, lse)), g = loss(lambda q, k, v: _fa.flash_attention_with_lse(
        q, k, v, None, causal, *blocks, True))(q, k, v)
    (_, (o_r, lse_r)), g_r = loss(ref)(f32(q), f32(k), f32(v))
    assert o.dtype == q.dtype and all(x.dtype == q.dtype for x in g)
    assert lse.dtype == jnp.float32
    return (f32(o), lse, [f32(x) for x in g]), (o_r, lse_r, g_r)


@pytest.mark.parametrize("what", ["forward", "grads"])
@pytest.mark.parametrize("blocks", [(128, 128), (128, 256), (256, 128),
                                    (None, None)],
                         ids=["128x128", "128x256", "256x128", "derived"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flash_inner_loop_parity(dtype, d, causal, blocks, what):
    _assert_parity(_inner_loop_case(dtype, d, causal, blocks, 0.0),
                   dtype, d, what)


def _assert_parity(case, dtype, d, what):
    (o, lse, g), (o_r, lse_r, g_r) = case
    tol = _TOL[dtype]
    if what == "forward":
        np.testing.assert_allclose(o, o_r, atol=tol["o"], rtol=tol["o"])
        lse_tol = _LSE_TOL_ROUNDED_SCALE if (dtype, d) == ("bfloat16", 128) \
            else tol["lse"]
        np.testing.assert_allclose(lse, lse_r, atol=lse_tol, rtol=lse_tol)
    else:
        for a, b in zip(g, g_r):
            np.testing.assert_allclose(a, b, atol=tol["grad"],
                                       rtol=tol["grad"])


@pytest.mark.parametrize("what", ["forward", "grads"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("dtype,d,blocks", [
    ("float32", 128, (128, 384)), ("float32", 128, (384, 128)),
    ("bfloat16", 64, (128, 128))], ids=["f32-128x384", "f32-384x128",
                                        "bf16-128x128"])
def test_flash_rolled_steps_parity(dtype, d, causal, blocks, what):
    """The same inner loop where a head is not unrolled (too many tiles, or
    operands too large to hold whole): several grid steps a head, several
    tiles a step, loop bounds that follow the step's place."""
    fa = _fa
    s = 1152
    for kernel in ("fwd", "dq", "dkv"):
        assert not fa._plan(kernel, s, s, d, dtype, *blocks)[3]
    if blocks == (128, 384):     # three steps a head, three tiles a step
        assert fa._plan("fwd", s, s, d, dtype, *blocks)[2] == 384
    _assert_parity(_inner_loop_case(dtype, d, causal, blocks, 0.0, s),
                   dtype, d, what)


@pytest.mark.parametrize("blocks", [(128, 256), (None, None)],
                         ids=["128x256", "derived"])
@pytest.mark.parametrize("d", [64, 128])
def test_flash_with_lse_cotangent_bf16(d, blocks):
    """Ring attention's chunk kernel: a non-zero cotangent of the lse output
    folds into delta (float32) and reaches dq and dk."""
    (_, _, g), (_, _, g_r) = _inner_loop_case("bfloat16", d, True, blocks, 1.0)
    (_, _, g0), _ = _inner_loop_case("bfloat16", d, True, blocks, 0.0)
    tol = _TOL["bfloat16"]["grad"]
    for a, b in zip(g, g_r):
        np.testing.assert_allclose(a, b, atol=tol, rtol=tol)
    assert float(jnp.max(jnp.abs(g[0] - g0[0]))) > 10 * tol  # dlse did reach dq


def test_flash_tiles_are_derived_from_shapes_and_divide_them():
    """No tuning run: each kernel's tile and grid step are constants of the
    shapes, every sequence the gate admits has them, and a grid step holds
    whole tiles."""
    fa = _fa
    for s in (128, 384, 640, 1024, 2048, 4096, 8192):
        assert fa.supports((2, s, 4, 64), (2, s, 4, 64), None, 0.0, True)
        for kernel in ("fwd", "dq", "dkv"):
            for d, dt in ((64, jnp.bfloat16), (128, jnp.bfloat16),
                          (128, jnp.float32)):
                bq, bk, rows, unrolled = fa._plan(kernel, s, s, d, dt)
                assert s % bq == 0 and s % bk == 0 and s % rows == 0
                assert rows % (bk if kernel == "dkv" else bq) == 0
                assert unrolled == (rows == s and (s // bq) * (s // bk) <= 64)
    # the train cell and the published 2,048 positions: a head a grid step
    assert fa._plan("fwd", 1024, 1024, 64, jnp.bfloat16)[2:] == (1024, True)
    assert fa._plan("dkv", 2048, 2048, 128, jnp.bfloat16)[2:] == (2048, True)
    # a caller's tile is kept; ring chunks narrower than a lane tile compile
    # for the chip only unrolled
    assert fa._plan("dq", 1024, 1024, 64, jnp.float32, 128, 256)[:2] == (128, 256)
    assert fa._plan("fwd", 192, 192, 64, jnp.float32, 64, 64) == (64, 64, 192, True)
    assert fa._BLOCK_CANDIDATES[0] == {"block_q": None, "block_k": None}


class TestSegmentedFlash:
    """Varlen (packed-sequence) flash via segment ids — VERDICT r3
    Missing #5. Oracle: dense attention under the block-diagonal mask."""

    def _data(self, seed=0):
        rng = np.random.default_rng(seed)
        b, s, h, d = 2, 64, 2, 16
        mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d)),
                                 jnp.float32)
        seg = np.zeros((b, s), np.int32)
        seg[:, 20:44] = 1
        seg[:, 44:] = 2
        return mk(), mk(), mk(), jnp.asarray(seg), seg

    def _dense(self, q, k, v, seg_np, causal):
        s = q.shape[1]
        scale = 1.0 / np.sqrt(q.shape[-1])
        sm = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        live = (seg_np[:, :, None] == seg_np[:, None, :])[:, None]
        if causal:
            ids = np.arange(s)
            live = live & (ids[:, None] >= ids[None, :])[None, None]
        sm = jnp.where(jnp.asarray(live), sm, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sm, -1), v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_and_grads(self, causal):
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_segmented,
        )

        q, k, v, segj, seg_np = self._data()
        scale = 1.0 / np.sqrt(q.shape[-1])
        o = flash_attention_segmented(q, k, v, segj, scale, causal, 16, 16,
                                      True)
        ref = self._dense(q, k, v, seg_np, causal)
        np.testing.assert_allclose(o, ref, atol=2e-5, rtol=2e-5)

        rng = np.random.default_rng(9)
        wo = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
        gf = jax.grad(
            lambda q, k, v: jnp.sum(flash_attention_segmented(
                q, k, v, segj, scale, causal, 16, 16, True) * wo),
            argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(
            lambda q, k, v: jnp.sum(self._dense(q, k, v, seg_np, causal) * wo),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)

    def test_flash_attn_unpadded_routes_through_kernel(self):
        import paddle_tpu as paddle
        from paddle_tpu.ops import api

        rng = np.random.default_rng(3)
        total = 128
        mk = lambda: paddle.to_tensor(
            rng.standard_normal((total, 2, 16)).astype(np.float32))
        qp, kp, vp = mk(), mk(), mk()
        cu = paddle.to_tensor(np.array([0, 50, 90, 128], np.int32))
        paddle.set_flags({"use_flash_attention": True,
                          "pallas_interpret": True})
        try:
            out_flash = api.flash_attn_unpadded(qp, kp, vp, cu, cu, 50, 50,
                                                causal=True)
        finally:
            paddle.set_flags({"use_flash_attention": False,
                              "pallas_interpret": False})
        out_dense = api.flash_attn_unpadded(qp, kp, vp, cu, cu, 50, 50,
                                            causal=True)
        np.testing.assert_allclose(out_flash.numpy(), out_dense.numpy(),
                                   atol=3e-5, rtol=3e-5)


def test_flash_attention_bf16():
    q, k, v = _qkv(2)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, None, True, 128, 128, True)
    ref = _ref_attn(q, k, v, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref, atol=5e-2, rtol=5e-2
    )


def test_flash_via_sdpa_op():
    """The registered op routes to the pallas kernel under the flag."""
    import paddle_tpu as paddle

    paddle.set_flags({"pallas_interpret": True, "use_flash_attention": True})
    try:
        q, k, v = _qkv(3)
        tq, tk, tv = (paddle.to_tensor(np.asarray(x)) for x in (q, k, v))
        tq.stop_gradient = False
        out = paddle.nn.functional.scaled_dot_product_attention(
            tq, tk, tv, is_causal=True
        )
        ref = _ref_attn(q, k, v, True)
        np.testing.assert_allclose(out.numpy(), ref, atol=2e-2, rtol=2e-2)
        out.sum().backward()
        assert tq.grad is not None and tq.grad.shape == list(q.shape)
    finally:
        paddle.set_flags({"pallas_interpret": False})


# (3, 100, 2048): the row block (sized from the width) is 96 of 300 rows, so
# the grid has several steps and a padded tail; (8, 33, 128) is one block
@pytest.mark.parametrize("shape", [(8, 33, 128), (3, 100, 2048)])
def test_fused_rms_norm(shape):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    w = jnp.asarray(rng.standard_normal(shape[-1:]), jnp.float32)
    y = fused_rms_norm(x, w, 1e-6, True)
    ref = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w
    np.testing.assert_allclose(y, ref, atol=1e-5)

    g1 = jax.grad(
        lambda x, w: (fused_rms_norm(x, w, 1e-6, True) ** 2).sum(),
        argnums=(0, 1),
    )(x, w)
    g2 = jax.grad(
        lambda x, w: (
            (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w)
            ** 2
        ).sum(),
        argnums=(0, 1),
    )(x, w)
    np.testing.assert_allclose(g1[0], g2[0], atol=1e-4)
    np.testing.assert_allclose(g1[1], g2[1], atol=1e-3)


def test_fused_rope():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    inv = 1.0 / (10000 ** (jnp.arange(0, D, 2) / D))
    fr = jnp.einsum("s,f->sf", jnp.arange(S).astype(jnp.float32), inv)
    cos = jnp.concatenate([jnp.cos(fr)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(fr)] * 2, -1)

    def ref(x):
        x1, x2 = jnp.split(x, 2, -1)
        rot = jnp.concatenate([-x2, x1], -1)
        return x * cos[None, :, None, :] + rot * sin[None, :, None, :]

    qo, ko = fused_rope(q, k, cos, sin, True)
    np.testing.assert_allclose(qo, ref(q), atol=1e-5)
    np.testing.assert_allclose(ko, ref(k), atol=1e-5)

    gq = jax.grad(lambda q: (fused_rope(q, k, cos, sin, True)[0] ** 2).sum())(q)
    gq2 = jax.grad(lambda q: (ref(q) ** 2).sum())(q)
    np.testing.assert_allclose(gq, gq2, atol=1e-4)


def test_fused_adamw():
    rng = np.random.default_rng(0)
    n = 1000
    p = jnp.asarray(rng.standard_normal(n), jnp.float32)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)
    m = jnp.zeros(n)
    v = jnp.zeros(n)
    po, mo, vo = fused_adamw_update(
        p, g, m, v, lr=1e-3, weight_decay=0.01, step=1, interpret=True
    )
    m2 = 0.1 * g
    v2 = 0.001 * g * g
    mh = m2 / (1 - 0.9)
    vh = v2 / (1 - 0.999)
    p2 = p - 1e-3 * (mh / (jnp.sqrt(vh) + 1e-8) + 0.01 * p)
    np.testing.assert_allclose(po, p2, atol=1e-6)
    np.testing.assert_allclose(mo, m2, atol=1e-7)
    np.testing.assert_allclose(vo, v2, rtol=1e-4, atol=1e-7)


def test_incubate_namespace():
    import paddle_tpu as paddle

    f = paddle.incubate.nn.functional
    assert callable(f.fused_rotary_position_embedding)
    assert callable(f.rms_norm)
    assert callable(f.memory_efficient_attention)


def test_fused_adamw_wiring(monkeypatch):
    """AdamW.step routes through the fused kernel (forced via monkeypatched
    backend + interpret mode) and matches the per-param path."""
    import paddle_tpu as paddle

    np.random.seed(0)
    x = np.random.randn(4, 8).astype(np.float32)

    def build():
        paddle.seed(0)
        lin = paddle.nn.Linear(8, 8)
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-2, parameters=lin.parameters(), weight_decay=0.01
        )
        return lin, opt

    def run_steps(lin, opt, n=3):
        for _ in range(n):
            loss = (lin(paddle.to_tensor(x)) ** 2).sum()
            loss.backward()
            opt.step()
            opt.clear_grad()
        return [p.numpy().copy() for p in lin.parameters()]

    lin1, opt1 = build()
    ref = run_steps(lin1, opt1)

    lin2, opt2 = build()
    paddle.set_flags({"pallas_interpret": True})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        fused = run_steps(lin2, opt2)
    finally:
        monkeypatch.undo()
        paddle.set_flags({"pallas_interpret": False})

    for a, b in zip(ref, fused):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5)


def test_fused_rope_packed():
    """Packed rope: in-kernel one-hot MXU table lookup vs the XLA gather
    composition, fwd + bwd (interpret mode)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.rope import _xla_packed, fused_rope_packed

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 256, 4, 16), jnp.float32)
    k = jnp.asarray(rng.randn(2, 256, 4, 16), jnp.float32)
    # REAL rope tables (halves duplicated): the linear-VJP identity
    # sign=-1 == transpose holds only for this production structure
    t = np.arange(64)[:, None]
    inv = 1.0 / (10000 ** (np.arange(8) / 8.0))
    ang = t * inv[None]
    tab_c = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), jnp.float32)
    tab_s = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), jnp.float32)
    pos = jnp.asarray(rng.randint(0, 64, (2, 256)), jnp.int32)

    qo, ko = fused_rope_packed(q, k, tab_c, tab_s, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(qo),
                               np.asarray(_xla_packed(q, pos, tab_c, tab_s,
                                                      1.0)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ko),
                               np.asarray(_xla_packed(k, pos, tab_c, tab_s,
                                                      1.0)), atol=1e-5)

    def loss_k(q):
        qo, _ = fused_rope_packed(q, k, tab_c, tab_s, pos, interpret=True)
        return jnp.sum(qo * qo)

    def loss_r(q):
        return jnp.sum(_xla_packed(q, pos, tab_c, tab_s, 1.0) ** 2)

    gk = jax.grad(loss_k)(q)
    gr = jax.grad(loss_r)(q)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=1e-4)
