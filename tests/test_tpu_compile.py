"""The Pallas kernels of chip_smoke.py's two phases, compiled by the real TPU
compiler for a described (not attached) v5e at the smoke's shapes.

Interpret mode cannot see what the chip's compiler refuses: block shapes the
TPU lowering rejects, kernels over the scoped-VMEM limit. These compiles can,
at no chip time (on-chip-measurement guide, section 2, third rehearsal).
Nothing runs, so nothing here says anything about results or speed.

Only one process may hold libtpu, so the topology is described inside a
module-scoped fixture: never at import, in a skipif or in parametrize.
"""
import re

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


_TRAIN_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def _mosaic_modules(lowered_text):
    """{kernel name: its Mosaic module as text} of a lowered program: each
    tpu_custom_call carries its module as bytecode, ops under the
    `stable_mosaic.` prefix of the serialized form."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    out = {}
    with ctx:
        for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                               lowered_text):
            text = str(ir.Module.parse(base64.b64decode(body)))
            out[re.match(r"module @(\w+)", text).group(1)] = text
    return out


def _matmul_operands(module_text):
    """[(lhs type, rhs type, the op's line)] of a Mosaic module's matmuls."""
    ops = [ln for ln in module_text.splitlines()
           if '"stable_mosaic.tpu.matmul"' in ln]
    return [re.search(r": \((vector<[^>]+>), (vector<[^>]+>), ", ln).groups()
            + (ln,) for ln in ops]


def _flash_grad_lowered(one_chip, shape, dtype):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x)


@pytest.mark.parametrize("shape", [
    (8, 1024, 16, 64), (8, 1024, 16, 128), (8, 2048, 16, 64),
    (8, 2048, 16, 128), (2, 4096, 8, 128), (1, 8192, 8, 64)],
    ids=lambda s: "x".join(map(str, s)))
def test_train_flash_kernels_multiply_in_bf16(one_chip, shape):
    """bf16 in (amp O1: the train cell's [8, 1024, 16, 64], and head size 128,
    at the cell's 1,024 positions and the published 2,048, a head a grid step
    and unrolled; 4,096 and 8,192 positions in rolled loops): every product
    of the three train kernels takes bf16 operands into a float32 sum, none
    has a transposed left operand, and at the tiles the kernels choose for
    these shapes they compile under the scoped-VMEM limit."""
    lowered = _flash_grad_lowered(one_chip, shape, jnp.bfloat16)
    modules = _mosaic_modules(lowered.as_text())
    assert sorted(modules) == sorted(_TRAIN_KERNELS)
    for name, text in modules.items():
        matmuls = _matmul_operands(text)
        assert matmuls, name
        for lhs, rhs, ln in matmuls:
            assert lhs.endswith("xbf16>") and rhs.endswith("xbf16>"), (name, ln)
            assert ln.rstrip().endswith("xf32>"), (name, ln)
            assert "transpose_lhs = false" in ln, (name, ln)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_train_flash_kernels_keep_float32_products(one_chip):
    """float32 in: the same kernels, the dtype read off the refs, multiply
    in float32 (the eager tests and float32 ring chunks)."""
    lowered = _flash_grad_lowered(one_chip, (2, 1024, 4, 64), jnp.float32)
    modules = _mosaic_modules(lowered.as_text())
    assert sorted(modules) == sorted(_TRAIN_KERNELS)
    for name, text in modules.items():
        for lhs, rhs, ln in _matmul_operands(text):
            assert lhs.endswith("xf32>") and rhs.endswith("xf32>"), (name, ln)
    lowered.compile()


@pytest.mark.parametrize("seq,block", [(192, 64), (96, 32), (64, 64)])
def test_ring_chunks_narrower_than_a_lane_tile(one_chip, seq, block):
    """Ring attention's local shards need not be multiples of 128
    (`_RING_BLOCK` then names a tile of 64, 32, 16 or 8): the chunk kernel
    with its lse cotangent still compiles for the chip, its statistics
    sliced along the lanes at static places."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_with_lse

    def loss(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, None, True, block, block)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse * 0.1)

    x = ((2, seq, 4, 64), jnp.bfloat16)
    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip, x, x, x)


@pytest.mark.parametrize("variant", ["segmented", "with_lse"])
def test_flash_attention_variants(one_chip, variant):
    """The packed-document (segment ids) and ring-attention (lse as an
    output with its own cotangent) forms of the flash kernel, fwd + bwd."""
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention_segmented, flash_attention_with_lse)

    def seg_loss(q, k, v, seg):
        o = flash_attention_segmented(q, k, v, seg, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def lse_loss(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse * 0.1)

    qkv = ((2, 2048, 8, 128), jnp.bfloat16)
    if variant == "segmented":
        _compile(jax.grad(seg_loss, argnums=(0, 1, 2)), one_chip,
                 qkv, qkv, qkv, ((2, 2048), jnp.int32))
    else:
        _compile(jax.grad(lse_loss, argnums=(0, 1, 2)), one_chip,
                 qkv, qkv, qkv)


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
    pages = ((_NUM_BLOCKS, hkv, _BLOCK, _D), dtype)     # the serve cells' pool
    q = ((_SLOTS, hq, _D) if sq is None else (_SLOTS, sq, hq, _D), dtype)
    return (q, pages, pages, ((_SLOTS, _MAX_BLOCKS), jnp.int32),
            ((_SLOTS,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("hq,hkv", [(16, 16), (32, 8)], ids=["mha", "gqa"])
def test_paged_attention_decode(one_chip, hq, hkv, dtype):
    from paddle_tpu.ops.pallas.paged_attention import paged_attention

    shapes = _paged_shapes(hq, hkv, dtype)       # mha-bf16: the GPT cells'
    _reads_the_pool_where_it_lies(
        _compile(paged_attention, one_chip, *shapes), *shapes[1])


def _reads_the_pool_where_it_lies(compiled, pool, dtype):
    """The decode kernel is in the program under its name, takes the pool as
    it lies in HBM (no copy, no transpose of it), and its scoped VMEM (the
    two buffers of K and of V and what the compiler adds) fits without a
    raised limit."""
    import json

    text = compiled.as_text()
    assert not _pool_relayouts(text, pool, dtype)
    call, = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert "%paged_decode" in call
    config = json.loads(re.search(r"backend_config=(\{.*\})\s*$",
                                  call).group(1))
    assert config["scoped_memory_configs"] == []
    used = sum(int(c["size"]) for c in config["used_scoped_memory_configs"])
    assert 0 < used < 8 * 2 ** 20


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("hq,hkv", [(16, 16), (32, 8)], ids=["mha", "gqa"])
def test_paged_attention_verify_window(one_chip, hq, hkv, dtype):
    from paddle_tpu.ops.pallas.paged_attention import paged_attention_multi

    _compile(paged_attention_multi, one_chip,
             *_paged_shapes(hq, hkv, dtype, sq=5))       # spec_k = 4


# ---- the KV pool's layout contract (paged_cached_attention's docstring): every
# ---- writer updates the pool where it lies. A scatter whose indexed dimensions
# ---- are not the leading ones made the TPU compiler relayout the whole pool
# ---- there and back, 96 copies of 168 MB a decode step at these shapes
_KV_HEADS, _NUM_BLOCKS = 16, 2560        # the serve cells' pool, per layer
_POOL = (_NUM_BLOCKS, _KV_HEADS, _BLOCK, _D)
_POOL_BYTES = 2 * _NUM_BLOCKS * _KV_HEADS * _BLOCK * _D


def _pool_relayouts(text, pool=None, dtype=jnp.bfloat16):
    """The optimized HLO's instructions that copy or transpose a whole pool."""
    name = {"bfloat16": "bf16", "float32": "f32"}[jnp.dtype(dtype).name]
    made = (re.escape("= %s[%d,%d,%d,%d]{" % (name, *(pool or _POOL)))
            + r"[^}]*\} (copy|transpose)\(")
    return [ln.strip()[:200] for ln in text.splitlines() if re.search(made, ln)]


@pytest.mark.parametrize("sq", [1, 4], ids=["decode", "verify_window"])
def test_kv_append_writes_the_pool_in_place(one_chip, monkeypatch, sq):
    from paddle_tpu.ops.kernels.nn_ops import paged_cached_attention

    qkv = ((_SLOTS, sq, _KV_HEADS, _D), jnp.bfloat16)
    pool = (_POOL, jnp.bfloat16)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        qkv, qkv, qkv, pool, pool, ((_SLOTS, _MAX_BLOCKS), jnp.int32),
        ((_SLOTS,), jnp.int32))]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(paged_cached_attention,
                       donate_argnums=(3, 4)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in it"
    assert not _pool_relayouts(text)
    aliases = re.search(r"input_output_alias=\{(.*?) \}, ", text).group(1)
    assert {3, 4} <= {int(n) for n in re.findall(r"\((\d+), ", aliases)}
    assert compiled.memory_analysis().temp_size_in_bytes < _POOL_BYTES


@pytest.fixture(scope="module")
def pool_programs(one_chip):
    """Every engine program that returns the pool, with the shapes to lower
    it: a 2-layer model whose pages have the serve cells' shape."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingEngine

    cfg = GPTConfig(vocab_size=512, hidden_size=_KV_HEADS * _D, num_layers=2,
                    num_heads=_KV_HEADS, intermediate_size=256,
                    max_position_embeddings=_BLOCK * _MAX_BLOCKS,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    # the engine's own pool stays tiny: the programs are lowered from shapes
    engine = ServingEngine(GPTForCausalLM(cfg).bfloat16(), max_slots=_SLOTS,
                           block_size=_BLOCK, num_blocks=2, prefill_chunk=256)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32, f32 = jnp.int32, jnp.float32
    pv, bv = jax.tree_util.tree_map(lambda x: sd(x.shape, x.dtype),
                                    engine._functional()[2:])
    pages = [(sd(_POOL, jnp.bfloat16),) * 2] * cfg.num_layers
    toks, lens, temps = sd((_SLOTS,), i32), sd((_SLOTS,), i32), sd((_SLOTS,), f32)
    tables, row = sd((_SLOTS, _MAX_BLOCKS), i32), sd((_MAX_BLOCKS,), i32)
    one = seed = sd((), i32)
    W, S, P = 5, 32, 1280                 # spec_k 4; a docqa question on its document
    work = [(sd((1, P, _KV_HEADS, _D), jnp.bfloat16),) * 2] * cfg.num_layers
    decode = (pv, bv, toks, pages, tables, lens, temps, seed)
    return {
        "step": (engine._decode_jit(False), decode),
        "serve_decode_fused": (engine._decode_multi_jit(4), decode),
        "serve_spec_verify": (engine._spec_jit(W, False), (
            pv, bv, sd((_SLOTS, W), i32), pages, tables, lens, lens, temps,
            seed)),
        "serve_scatter": (engine._scatter_jit(P, P // _BLOCK), (
            pages, work, row, one, one)),
        "serve_batched_prefill": (engine._batched_prefill_jit(S, P), (
            pv, bv, pages, sd((_SLOTS, S), i32), lens,
            sd((_SLOTS, P // _BLOCK), i32), lens, lens, tables, lens, temps,
            toks, tables, lens, temps)),
        "serve_admit_cow": (engine._admit_cow_jit(), (
            pages, toks, tables, lens, temps, one, one, one, row, one, one,
            sd((), f32))),
    }


@pytest.mark.parametrize("name", [
    "step", "serve_decode_fused", "serve_spec_verify", "serve_scatter",
    "serve_batched_prefill", "serve_admit_cow"])
def test_no_engine_program_relayouts_the_pool(pool_programs, monkeypatch,
                                              name):
    fn, args = pool_programs[name]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert text.startswith(f"HloModule jit_{name},")
    assert not _pool_relayouts(text)
    # both pools of both layers are updated in the buffers they came in
    assert compiled.memory_analysis().alias_size_in_bytes >= 4 * _POOL_BYTES


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


@pytest.mark.parametrize("packed", [False, True], ids=["plain", "packed"])
def test_fused_rope(one_chip, packed):
    from paddle_tpu.ops.pallas.rope import fused_rope, fused_rope_packed

    x = ((8, 1024, 16, 128), jnp.bfloat16)
    tab = ((1024, 128), jnp.float32)
    if packed:      # per-token positions: packed documents restart at 0
        _compile(fused_rope_packed, one_chip, x, x, tab, tab,
                 ((8, 1024), jnp.int32))
    else:
        _compile(fused_rope, one_chip, x, x, tab, tab)


# ---- the names a device trace shows: XLA modules after the jitted function,
# ---- kernel events after pl.pallas_call(name=) (the benchmark's readers
# ---- find them by these, so a rename is a change to the yardstick)
def _gpt_train_step(**config):
    """TrainStep over a GPT under amp O1, as the cell and the smoke build it."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    model = GPTForCausalLM(GPTConfig(hidden_dropout_prob=0.0,
                                     attention_dropout_prob=0.0, **config))
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())

    def loss_fn(b):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            return model(b, labels=b)

    return TrainStep(model, loss_fn, opt)


def _tiny_train_step():
    return _gpt_train_step(vocab_size=1024, hidden_size=128, num_layers=2,
                           num_heads=2, max_position_embeddings=256)


def _compiled_train_step_text(step, one_chip, monkeypatch, batch):
    """The step's program for the described chip, as the chip runs it (the
    attention op asks jax.default_backend() and is steered here)."""
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        ([p._value for p in step.params], [b._value for b in step.buffers],
         step.opt_state, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32),
         [jnp.zeros(batch, jnp.int32)]))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = step._jitted.lower(*args).compile().as_text()
    assert text.startswith("HloModule jit_train_step,")
    return text


def test_train_step_is_jit_train_step_with_named_flash_kernels(
        one_chip, monkeypatch):
    """Through TrainStep, as the chip runs it: under the program's own
    autograd the kernels come out as %flash_fwd.N, %flash_bwd_dq.N and
    %flash_bwd_dkv.N (a plain jax.grad would call them %jvp_flash_fwd_.N)."""
    text = _compiled_train_step_text(_tiny_train_step(), one_chip,
                                     monkeypatch, (8, 256))
    for kernel in ("%flash_fwd.", "%flash_bwd_dq.", "%flash_bwd_dkv."):
        assert kernel in text, kernel
    kernels = [ln.split()[0] for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels and all(k.startswith("%flash_") for k in kernels), kernels
    for scope in ("h0/attn", "h1/mlp", "loss", "optimizer", "lm_head"):
        assert f'op_name="jit(train_step)/{scope}/' in text, scope


def _vocab_wide_arrays(text, vocab):
    """(dtype, dims) of every array an instruction of the ENTRY computation
    produces that has a `vocab`-wide dimension: what the step keeps in HBM
    (instructions inside fused computations live in registers and VMEM)."""
    entry = re.search(r"\nENTRY [^\n]*\{\n(.*?)\n\}", text, re.S).group(1)
    found = []
    for line in entry.splitlines():
        lhs, _, rhs = line.partition(" = ")
        if "parameter(" in rhs:
            continue
        result = rhs[:rhs.index("(", 1)] if rhs.startswith("(") \
            else rhs.split(" ", 1)[0]
        for dtype, dims in re.findall(r"\b([a-z]+[0-9]+)\[([0-9,]+)\]",
                                      result):
            dims = tuple(int(d) for d in dims.split(","))
            if vocab in dims:
                found.append((dtype, dims))
    return found


def test_train_step_keeps_no_float32_array_of_the_logits_size(
        one_chip, monkeypatch):
    """The train cell's shape (b8, s1024, v50304, amp O1; two layers, the
    head and the loss do not depend on the depth): the next-token loss reads
    the head's bf16 logits where they lie. No float32 array with a
    50,304-wide dimension and 8,184 or more rows reaches HBM (the parent
    wrote two, 1.65 GB each, and crossed them five times a step), the
    last position is masked and not sliced (no `[8,1023,50304]` array of any
    dtype), and the loss adds no kernel: three flash kernels a layer, which
    is all `flash_attn_roofline` reads."""
    import numpy as np

    b, s, v, layers = 8, 1024, 50304, 2
    step = _gpt_train_step(vocab_size=v, hidden_size=1024, num_layers=layers,
                           num_heads=16, max_position_embeddings=s)
    text = _compiled_train_step_text(step, one_chip, monkeypatch, (b, s))
    wide = _vocab_wide_arrays(text, v)
    assert ("bf16", (b, s, v)) in wide, wide          # the logits themselves
    for dtype, dims in wide:
        rows = int(np.prod(dims)) // v
        assert not (dtype == "f32" and rows >= b * (s - 1)), (dtype, dims)
        assert s - 1 not in dims, (dtype, dims)
    kernels = [ln.split()[0] for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(kernels) == 3 * layers, kernels
    assert all(k.startswith("%flash_") for k in kernels), kernels
    assert 'op_name="jit(train_step)/loss/' in text


def test_decode_program_is_jit_step_with_a_named_kernel(pool_programs,
                                                        monkeypatch):
    """The engine's decode program at head size 128, compiled as on the
    chip (the op asks jax.default_backend() which attention to take, and
    is steered here, not by an option of the program)."""
    fn, args = pool_programs["step"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = fn.lower(*args).compile().as_text()
    assert text.startswith("HloModule jit_step,")
    assert "%paged_decode." in text and "tpu_custom_call" in text
    # the kernel's entry is jitted (a program traces it once for all its
    # layers); each call keeps its layer's scope
    assert ('op_name="jit(step)/h1/attn/jit(paged_attention)/paged_decode'
            in text)
    for scope in ("embed", "h0/attn/kv_append", "h1/mlp", "final_norm",
                  "lm_head", "sample"):
        assert f'op_name="jit(step)/{scope}/' in text, scope


def test_every_way_to_build_the_train_step_names_it_train_step(one_chip):
    import numpy as np

    import paddle_tpu as paddle

    step = _tiny_train_step()
    ids = paddle.to_tensor(np.zeros((2, 16), "int32"))
    assert "module @jit_train_step " in step.lower(ids).as_text()
    step.invalidate_executables()       # the re-traced wrapper too
    assert "module @jit_train_step " in step.lower(ids).as_text()


# ---- Laguna-S-2.1 at the published widths: 8 K/V heads of 128, 48 query
# ---- heads in full layers and 72 in window layers (groups of 6 and 9), a
# ---- 512-key window, 128 experts held of width 1,024 over a hidden of 3,072
_L_SLOTS, _L_KV, _L_WINDOW, _L_FULL_BLOCKS = 32, 8, 512, 384


@pytest.mark.parametrize("block", [16, 128])
@pytest.mark.parametrize("hq,window", [(48, None), (72, _L_WINDOW)],
                         ids=["full_g6", "window_g9"])
def test_paged_decode_at_lagunas_heads_and_window(one_chip, hq, window, block):
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    from paddle_tpu.serving.blocks import WindowRings

    if window is None:
        width = _L_FULL_BLOCKS * 16 // block
        blocks = 163840 // block + 1
    else:
        rings = WindowRings(_L_SLOTS, window, block)
        width, blocks = rings.ring_blocks, rings.num_blocks
    pages = ((blocks, _L_KV, block, _D), jnp.bfloat16)
    # at block 128 these are the codegen cell's tables and pools: 48 wide
    # over 1,281 blocks, and rings of 5 over 161
    _reads_the_pool_where_it_lies(_compile(
        lambda q, k, v, bt, cl: paged_attention(q, k, v, bt, cl,
                                                window=window),
        one_chip, ((_L_SLOTS, hq, _D), jnp.bfloat16), pages, pages,
        ((_L_SLOTS, width), jnp.int32), ((_L_SLOTS,), jnp.int32)), *pages)


@pytest.mark.parametrize("hq,window", [(48, None), (72, _L_WINDOW)],
                         ids=["full_g6", "window_g9"])
@pytest.mark.parametrize("cached", [512, 4096])
def test_windowed_prefill_at_lagunas_heads(one_chip, hq, window, cached):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_prefill

    kv = ((1, cached, _L_KV, _D), jnp.bfloat16)
    _compile(lambda q, k, v, off: flash_attention_prefill(
        q, k, v, off, window=window),
        one_chip, ((1, 512, hq, _D), jnp.bfloat16), kv, kv, ((), jnp.int32))


@pytest.mark.parametrize("rows,tm", [(320, 32), (5120, 128)],
                         ids=["decode_32x10", "prefill_512x10"])
@pytest.mark.parametrize("k,n", [(3072, 2048), (1024, 3072)],
                         ids=["gate_up", "down"])
def test_grouped_products_at_lagunas_widths(one_chip, rows, tm, k, n):
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul

    compiled = _compile(lambda a, w, g: grouped_matmul(a, w, g, tm=tm),
                        one_chip, ((rows, k), jnp.bfloat16),
                        ((128, k, n), jnp.bfloat16), ((128,), jnp.int32))
    assert "moe_grouped_matmul" in compiled.as_text()


# ---- GLM-4.7-Flash at the published widths: 20 heads over one latent row
# ---- of 576 values (512 + 64), stored in whole lanes: 640; block 128; the
# ---- longdoc cell's 24 slots, 260-wide tables and 5,633 blocks
_G_HEADS, _G_ROW, _G_RANK = 20, 640, 512


def test_latent_decode_at_the_published_widths(one_chip):
    from paddle_tpu.ops.pallas.paged_attention import latent_decode

    pages = ((5633, 1, 128, _G_ROW), jnp.bfloat16)
    compiled = _compile(
        lambda q, p, bt, cl: latent_decode(q, p, bt, cl, v_dim=_G_RANK,
                                           scale=1 / 16),
        one_chip, ((24, _G_HEADS, _G_ROW), jnp.bfloat16), pages,
        ((24, 260), jnp.int32), ((24,), jnp.int32))
    # the pool is taken as it lies in HBM: no copy, no transpose of it
    assert not _pool_relayouts(compiled.as_text(), *pages)
    assert "%latent_decode" in compiled.as_text()


@pytest.mark.parametrize("chunk,cached", [(512, 8704), (1024, 33792),
                                          (512, 33280), (2048, 34816)])
def test_latent_prefill_at_the_published_widths(one_chip, chunk, cached):
    from paddle_tpu.ops.pallas.flash_attention import latent_prefill

    compiled = _compile(
        lambda q, lat, off: latent_prefill(q, lat, off, v_dim=_G_RANK,
                                           scale=1 / 16),
        one_chip, ((1, chunk, _G_HEADS, _G_ROW), jnp.bfloat16),
        ((1, cached, _G_ROW), jnp.bfloat16), ((), jnp.int32))
    assert "latent_prefill" in compiled.as_text()


@pytest.fixture(scope="module")
def laguna_pool_programs(one_chip):
    """The engine programs that return a Laguna model's pools, both cache
    groups at the cell's page shapes; the model's hidden size and experts
    are small, its heads, K/V heads and window the published ones."""
    from paddle_tpu.models import LagunaConfig, LagunaForCausalLM
    from paddle_tpu.serving import ServingEngine

    cfg = LagunaConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512, num_layers=5,
        num_experts=16, experts_held=(0, 8), num_experts_per_tok=4,
        moe_intermediate_size=128, shared_expert_intermediate_size=128,
        max_position_embeddings=8192)
    engine = ServingEngine(LagunaForCausalLM(cfg).bfloat16(),
                           max_slots=_L_SLOTS, block_size=_BLOCK,
                           num_blocks=2, prefill_chunk=512,
                           max_model_len=_L_FULL_BLOCKS * _BLOCK)
    rings, = engine.window_rings
    shapes = {"full": (10241, _L_KV, _BLOCK, _D),
              "window": (rings.num_blocks, _L_KV, _BLOCK, _D)}

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = jnp.int32
    pv, bv = jax.tree_util.tree_map(lambda x: sd(x.shape, x.dtype),
                                    engine._functional()[2:])
    pages = [(sd(shapes[l.kind], jnp.bfloat16),) * 2
             for l in engine._spec.layers]
    lens = sd((_L_SLOTS,), i32)
    tables = sd((_L_SLOTS, engine._table_cols), i32)
    counters = tuple(sd((9,), i32) for _ in engine._counter_layers)
    P = 4096
    work = [(sd((1, P, _L_KV, _D), jnp.bfloat16),) * 2] * cfg.num_layers
    pool_bytes = sum(2 * 2 * n * _L_KV * _BLOCK * _D
                     for n in (10241, 10241, rings.num_blocks,
                               rings.num_blocks, rings.num_blocks))
    return shapes, pool_bytes, {
        "step": (engine._decode_jit(False), (
            pv, bv, lens, pages, tables, lens, sd((_L_SLOTS,), jnp.float32),
            sd((), i32), counters)),
        "serve_scatter": (engine._scatter_jit(P, P // _BLOCK), (
            pages, work, sd((engine._table_cols,), i32), sd((), i32),
            sd((), i32))),
    }


@pytest.mark.parametrize("name", ["step", "serve_scatter"])
def test_no_program_relayouts_either_cache_groups_pool(laguna_pool_programs,
                                                       monkeypatch, name):
    shapes, pool_bytes, programs = laguna_pool_programs
    fn, args = programs[name]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert text.startswith(f"HloModule jit_{name},")
    for shape in shapes.values():
        made = re.escape("= bf16[%d,%d,%d,%d]{" % shape) \
            + r"[^}]*\} (copy|transpose)\("
        assert not [ln for ln in text.splitlines() if re.search(made, ln)]
    # every layer's K and V pool is updated in the buffer it came in
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes
    if name == "step":
        for kernel in ("paged_decode", "moe_grouped_matmul"):
            assert kernel in text


# ---- Xing4.0-29B-A4B (PR 36): the residual mix's two kernels at 4 streams
# ---- of 3,584, one block of 128 tokens and a chunk's 512; and the agent
# ---- cell's step and prefill programs at the published widths (32 heads,
# ---- value 128, a 640-lane latent row, 64 groups of 1,024), two layers
_X_STREAMS, _X_HIDDEN = 4, 3584


@pytest.mark.parametrize("tokens", [128, 512], ids=["block", "chunk"])
def test_residual_mix_kernels_at_the_published_widths(one_chip, tokens):
    from paddle_tpu.ops.pallas import hyper_connection as hc

    width, maps = _X_STREAMS * _X_HIDDEN, _X_STREAMS * (_X_STREAMS + 2)
    pre = _compile(
        lambda x, phi, a, b: hc.mhc_pre(x, phi, a, b, n=_X_STREAMS, eps=1e-6,
                                        clamp=(-30.0, 30.0), iters=20),
        one_chip, ((tokens, width), jnp.bfloat16), ((maps, width), jnp.float32),
        ((3,), jnp.float32), ((maps,), jnp.float32))
    assert "%mhc_pre" in pre.as_text()
    post = _compile(
        lambda x, y, m: hc.mhc_post(x, y, m, n=_X_STREAMS), one_chip,
        ((tokens, width), jnp.bfloat16), ((tokens, _X_HIDDEN), jnp.bfloat16),
        ((tokens, hc.MAPS_WIDTH), jnp.float32))
    assert "%mhc_post" in post.as_text()


@pytest.fixture(scope="module")
def xing_programs(one_chip):
    """The decode step and a prefill chunk of a Xing4 model at the published
    widths, a dense and a sparse layer, the agent cell's slots, block, pool
    and longest workspace; the vocabulary and the dense width are small."""
    import paddle_tpu as paddle
    from paddle_tpu.models import Xing4Config, Xing4ForCausalLM
    from paddle_tpu.nn import initializer as I
    from paddle_tpu.serving import ServingEngine

    cfg = Xing4Config(vocab_size=1024, intermediate_size=1024, num_layers=2,
                      first_k_dense_replace=1)
    paddle.set_default_dtype("bfloat16")
    I.set_global_initializer(I.Constant(0.0))
    try:
        model = Xing4ForCausalLM(cfg)
    finally:
        I.set_global_initializer(None)
        paddle.set_default_dtype("float32")
    # the longest workspace: a 16,384-token context and a 1,024-token turn
    slots, blocks, block, padded = 24, 2561, 128, 17408
    engine = ServingEngine(model, max_slots=slots, block_size=block,
                           num_blocks=2, prefill_chunk=512,
                           max_model_len=17664)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = jnp.int32
    pv, bv = jax.tree_util.tree_map(lambda x: sd(x.shape, x.dtype),
                                    engine._functional()[2:])
    pool = (blocks, 1, block, cfg.cache_row_width)
    pages = [(sd(pool, jnp.bfloat16),)] * cfg.num_layers
    lens = sd((slots,), i32)
    counters = tuple(sd((engine._spec.layers[i].counters,), i32)
                     for i in engine._counter_layers)
    work = [(sd((1, padded, 1, cfg.cache_row_width), jnp.bfloat16),)] \
        * cfg.num_layers
    return pool, {
        "step": (engine._decode_jit(False), (
            pv, bv, lens, pages, sd((slots, engine._table_cols), i32), lens,
            sd((slots,), jnp.float32), sd((), i32), counters)),
        "serve_prefill": (engine._prefill_jit(512, padded), (
            pv, bv, sd((1, 512), i32), work, sd((), i32))),
    }


@pytest.mark.parametrize("name", ["step", "serve_prefill"])
def test_xing_programs_hold_the_mix_and_leave_the_pool_where_it_lies(
        xing_programs, monkeypatch, name):
    pool, programs = xing_programs
    fn, args = programs[name]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert text.startswith(f"HloModule jit_{name},")
    assert not _pool_relayouts(text, pool)
    # a decode step's 24 rows take the mix's XLA form
    kernels = {"step": ("latent_decode", "moe_grouped_matmul"),
               "serve_prefill": ("mhc_pre", "mhc_post", "latent_prefill",
                                 "moe_grouped_matmul")}[name]
    for kernel in kernels:
        assert f"%{kernel}" in text, kernel
    # the two mixes of a layer, under their scopes
    for scope in ("h0/hc.attn", "h0/hc.mlp", "h1/hc.attn", "h1/hc.mlp"):
        assert scope in text, scope
    if name == "step":  # both layers' pools updated where they came in
        assert compiled.memory_analysis().alias_size_in_bytes \
            >= 2 * 2 * pool[0] * pool[2] * pool[3]


# ---- GLM-5.3-Flash: the delta-rule kernels and the indexer, published widths
@pytest.mark.parametrize("tokens", [2048, 1024])
def test_kda_chunk_at_the_published_widths(one_chip, tokens):
    """A prefill chunk of 2,048 tokens, and the long-context cell's 1,024,
    64 heads of 128, the state in and out in float32; the kernel keeps its
    name."""
    from paddle_tpu.ops.pallas.delta_rule import kda_chunk

    tok = ((1, tokens, 64, 128), jnp.float32)
    compiled = _compile(lambda q, k, v, g, b, s: kda_chunk(
        q, k, v, g, b, s, scale=128 ** -0.5), one_chip, tok, tok, tok, tok,
        ((1, tokens, 64), jnp.float32), ((1, 64, 128, 128), jnp.float32))
    assert re.search(r"kda_chunk", compiled.as_text())


def test_kda_decode_at_the_published_widths(one_chip):
    from paddle_tpu.ops.pallas.delta_rule import kda_decode

    vec = ((32, 64, 128), jnp.float32)
    compiled = _compile(lambda q, k, v, g, b, s: kda_decode(
        q, k, v, g, b, s, scale=128 ** -0.5), one_chip, vec, vec, vec, vec,
        ((32, 64), jnp.float32), ((32, 64, 128, 128), jnp.float32))
    assert re.search(r"kda_decode", compiled.as_text())


@pytest.mark.parametrize("blocks", [2048, 16384])
def test_dsa_index_at_the_published_widths(one_chip, blocks):
    """A chunk of 2,048 queries of 32 heads of 128 over the pooled keys of
    an 8k and of a 64k workspace."""
    from paddle_tpu.ops.pallas.sparse_latent import dsa_index

    compiled = _compile(lambda q, w, k, c: dsa_index(q, w, k, c), one_chip,
                        ((2048, 32, 128), jnp.float32),
                        ((2048, 32), jnp.float32),
                        ((blocks, 128), jnp.bfloat16), ((2048,), jnp.int32))
    assert re.search(r"dsa_index", compiled.as_text())
