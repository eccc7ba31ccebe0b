"""The Pallas kernels of chip_smoke.py's two phases, compiled by the real TPU
compiler for a described (not attached) v5e at the smoke's shapes.

Interpret mode cannot see what the chip's compiler refuses: block shapes the
TPU lowering rejects, kernels over the scoped-VMEM limit. These compiles can,
at no chip time (on-chip-measurement guide, section 2, third rehearsal).
Nothing runs, so nothing here says anything about results or speed.

Only one process may hold libtpu, so the topology is described inside a
module-scoped fixture: never at import, in a skipif or in parametrize.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile fn for the described chip; shapes are (shape, dtype) pairs."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel in it"
    return compiled


# ---- train phase: GPT 355M b8 s1024 (head_dim 64); 1.3B prefill (128) ----
@pytest.mark.parametrize("heads,head_dim", [(16, 64), (16, 128)])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention(one_chip, heads, head_dim, grad):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32) ** 2)

    qkv = ((8, 1024, heads, head_dim), jnp.bfloat16)
    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    _compile(fn, one_chip, qkv, qkv, qkv)


def test_fused_adamw_flat(one_chip):
    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw_update

    n = 1024 * 1024 * 12 + 1024 * 3        # one block's params, ragged tail
    flat = ((n,), jnp.float32)
    _compile(lambda p, g, m, v: fused_adamw_update(
        p, g, m, v, lr=1e-4, weight_decay=0.01, step=3),
        one_chip, flat, flat, flat, flat)


# ---- serve phase: GPT-3 1.3B decode (8 slots, block 16, 2048 positions) ----
_SLOTS, _BLOCK, _MAX_BLOCKS, _D = 8, 16, 128, 128


def _paged_shapes(hq, hkv, dtype, sq=None):
    pages = ((_SLOTS * _MAX_BLOCKS + 1, hkv, _BLOCK, _D), dtype)
    q = ((_SLOTS, hq, _D) if sq is None else (_SLOTS, sq, hq, _D), dtype)
    return (q, pages, pages, ((_SLOTS, _MAX_BLOCKS), jnp.int32),
            ((_SLOTS,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("hq,hkv", [(16, 16), (32, 8)], ids=["mha", "gqa"])
@pytest.mark.parametrize("kv_splits", [1, 4])
def test_paged_attention_decode(one_chip, hq, hkv, dtype, kv_splits):
    from paddle_tpu.ops.pallas.paged_attention import paged_attention

    _compile(lambda q, k, v, bt, cl: paged_attention(
        q, k, v, bt, cl, kv_splits=kv_splits),
        one_chip, *_paged_shapes(hq, hkv, dtype))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("hq,hkv", [(16, 16), (32, 8)], ids=["mha", "gqa"])
def test_paged_attention_verify_window(one_chip, hq, hkv, dtype):
    from paddle_tpu.ops.pallas.paged_attention import paged_attention_multi

    _compile(paged_attention_multi, one_chip,
             *_paged_shapes(hq, hkv, dtype, sq=5))       # spec_k = 4


# ---- LLaMA-family fused ops at the widths the issue names ----
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("hidden", [2048, 4096])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_fused_rms_norm(one_chip, hidden, dtype, grad):
    from paddle_tpu.ops.pallas.fused_norm import fused_rms_norm

    def loss(x, w):
        return jnp.sum(fused_rms_norm(x, w).astype(jnp.float32) ** 2)

    fn = jax.grad(loss, argnums=(0, 1)) if grad else fused_rms_norm
    _compile(fn, one_chip, ((8, 1024, hidden), dtype), ((hidden,), dtype))


def test_fused_rope(one_chip):
    from paddle_tpu.ops.pallas.rope import fused_rope

    x = ((8, 1024, 16, 128), jnp.bfloat16)
    tab = ((1024, 128), jnp.float32)
    _compile(fused_rope, one_chip, x, x, tab, tab)
