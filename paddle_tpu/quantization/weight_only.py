"""Weight-only int8 quantization for serving (reference: the int8 variant
of the fused decoder — paddle/fluid/operators/fused/
fused_multi_transformer_int8_op.cu — plus python/paddle quantization's
weight_only_linear pass).

TPU-native shape: per-output-channel absmax int8 weights dequantized in
the matmul epilogue (ops/kernels/quant.py weight_only_matmul). Quantized
weights/scales are registered BUFFERS, so the compiled decode step
(models/generation.py swaps parameters AND buffers) runs straight off the
int8 tables — 4x less HBM traffic for the weight stream, which is the
decode-phase bottleneck.
"""
from __future__ import annotations

import types
from typing import List

from ..core.tensor import Tensor
from ..ops import api


def _dequant_cache_enabled() -> bool:
    """Whether to hoist int8 dequantization out of the decode loop by caching
    a scale-folded fp table per quantized layer (registered buffer
    'dequant_weight'): on backends with no int8 GEMM (everything but TPU),
    where the per-call convert materializes an fp copy of the weight every
    step. The int8 tables remain the storage/wire format either way."""
    import jax

    return jax.default_backend() != "tpu"


def _quantize_linear_like(layer, kind: str) -> None:
    from ..distributed.fleet.mp_layers import all_gather_concat
    from ..distributed.collective import _bound_axis
    from ..ops.kernels.quant import dequantize_weight, quantize_weight_absmax

    import jax.numpy as jnp

    compute_dtype = layer.weight._value.dtype
    q, s = quantize_weight_absmax(layer.weight._value)
    # drop the fp parameter; register int8 + scales as buffers so the
    # generation/TrainStep functional swap carries them
    layer._parameters.pop("weight", None)
    layer.weight = None
    layer.register_buffer("quant_weight", Tensor(q))
    layer.register_buffer("quant_scales", Tensor(s.astype(jnp.float32)))
    use_cache = _dequant_cache_enabled()
    if use_cache:
        # CPU fast path: one scale-folded dequant pass now, so every decode
        # step runs the identical fp GEMM the unquantized model runs.
        # Registered as a buffer so compiled decode programs stream it like
        # any weight.
        layer.register_buffer(
            "dequant_weight",
            Tensor(dequantize_weight(q, s, dtype=compute_dtype)))
    # the int8 tables inherit the fp weight's TP layout, or a TP serving
    # run would replicate every table and lose the sharded matmul
    from ..distributed.mesh import annotate_param
    from jax.sharding import PartitionSpec as P

    if kind == "column":
        annotate_param(layer.quant_weight, P(None, "mp"))
        annotate_param(layer.quant_scales, P("mp"))
        if use_cache:
            annotate_param(layer.dequant_weight, P(None, "mp"))
    elif kind == "row":
        annotate_param(layer.quant_weight, P("mp", None))
        annotate_param(layer.quant_scales, P())
        if use_cache:
            annotate_param(layer.dequant_weight, P("mp", None))

    def _wom(self, x, bias):
        return api.weight_only_matmul(
            x, self.quant_weight, self.quant_scales, bias,
            dequant=getattr(self, "dequant_weight", None))

    if kind == "column":
        def fwd(self, x):
            out = _wom(self, x, self.bias)
            if self.gather_output and (_bound_axis(self.group) is not None):
                out = all_gather_concat(out, axis=-1, group=self.group)
            return out
    elif kind == "row":
        def fwd(self, x):
            from ..distributed.collective import all_reduce

            axis = _bound_axis(self.group) if self.group is not None else None
            if axis is None:
                return _wom(self, x, self.bias)
            out = _wom(self, x, None)
            out = all_reduce(out, group=self.group)
            if self.bias is not None:
                out = out + self.bias
            return out
    else:  # plain linear
        def fwd(self, x):
            return _wom(self, x, self.bias)

    layer.forward = types.MethodType(fwd, layer)
    layer._weight_only_quantized = True


def _quantize_tied_head(model, emb_weight) -> None:
    """Weight-only int8 for the TIED LM head (GPT-style `h @ wte.weight^T`).

    The head projection is the single biggest GEMM of a decode step
    (hidden x vocab) and the tied form runs it TRANSPOSED, which XLA:CPU
    executes slower than the straight [in, out] layout. Quantizing the head
    stores the int8 table (and its scale-folded dequant cache)
    PRE-TRANSPOSED as [hidden, vocab]: the
    int8 model's head streams 4x fewer HBM bytes on TPU and runs the fast
    GEMM layout everywhere. The embedding lookup keeps the fp table."""
    import jax.numpy as jnp

    from ..distributed.mesh import annotate_param
    from ..ops.kernels.quant import dequantize_weight, quantize_weight_absmax
    from jax.sharding import PartitionSpec as P

    compute_dtype = emb_weight._value.dtype
    wt = emb_weight._value.T  # [hidden, vocab] projection view
    q, s = quantize_weight_absmax(wt)  # per-vocab-column scales
    model.register_buffer("head_quant_weight", Tensor(q))
    model.register_buffer("head_quant_scales", Tensor(s.astype(jnp.float32)))
    # vocab is the output dim -> column-parallel layout over 'mp'
    annotate_param(model.head_quant_weight, P(None, "mp"))
    annotate_param(model.head_quant_scales, P("mp"))
    if _dequant_cache_enabled():
        model.register_buffer(
            "head_dequant_weight",
            Tensor(dequantize_weight(q, s, dtype=compute_dtype)))
        annotate_param(model.head_dequant_weight, P(None, "mp"))

    def _head(self, h):
        return api.weight_only_matmul(
            h, self.head_quant_weight, self.head_quant_scales,
            dequant=getattr(self, "head_dequant_weight", None))

    model._head = types.MethodType(_head, model)
    model._head_weight_only = True


def quantize_for_generation(model, algo: str = "weight_only_int8") -> List[str]:
    """Convert every linear-family sublayer of a (causal LM) model to
    int8 weight-only serving form, in place. Returns the names of the
    quantized sublayers. Embeddings, norms, and biases stay fp (the
    reference int8 decoder does the same)."""
    if algo != "weight_only_int8":
        raise ValueError(f"unsupported algo {algo!r}")
    from ..distributed.fleet.mp_layers import (
        ColumnParallelLinear,
        RowParallelLinear,
    )
    from ..nn import Linear

    done = []
    for name, sub in model.named_sublayers():
        if getattr(sub, "_weight_only_quantized", False):
            continue
        if isinstance(sub, ColumnParallelLinear):
            _quantize_linear_like(sub, "column")
        elif isinstance(sub, RowParallelLinear):
            _quantize_linear_like(sub, "row")
        elif isinstance(sub, Linear):
            _quantize_linear_like(sub, "linear")
        else:
            continue
        done.append(name)
    # tied LM heads bypass the Linear sweep (`h @ wte.weight^T`): quantize
    # the projection view too, or the biggest GEMM of every decode step
    # stays fp (and in the slow transposed layout)
    if not getattr(model, "_head_weight_only", False) \
            and getattr(getattr(model, "config", None),
                        "tie_word_embeddings", False) \
            and hasattr(model, "_head"):
        emb = None
        if hasattr(model, "gpt"):  # GPTForCausalLM
            emb = model.gpt.wte.weight
        elif hasattr(model, "model"):  # LlamaForCausalLM (tied config)
            emb = model.model.embed_tokens.weight
        if emb is not None:
            _quantize_tied_head(model, emb)
            done.append("_head")
    # stale compiled decode programs captured the fp parameter list
    if hasattr(model, "_gen_exec_cache"):
        model._gen_exec_cache.clear()
    return done
