"""The system under test for the `glm_moe_lite` configurations: the program's
own GlmMoeLiteForCausalLM and ServingEngine, built from a configuration file
and loaded with the benchmark's seeded weights, leaf after leaf (at the
published widths the weights fill a third of a chip: they are never held
twice). Everything here is the program's public surface."""
from __future__ import annotations

import re

import numpy as np

from . import glm_moe_lite_reference as ref

# program parameter or buffer name -> reference leaf name
_TOP = {"model.embed_tokens.weight": "embed", "model.norm.weight": "norm_f",
        "lm_head.weight": "head"}
_LAYER = {"input_layernorm.weight": "ln1",
          "post_attention_layernorm.weight": "ln2",
          "self_attn.q_a_proj.weight": "wqa",
          "self_attn.q_a_layernorm.weight": "q_norm",
          "self_attn.q_b_proj.weight": "wqb",
          "self_attn.kv_a_proj.weight": "wkva",
          "self_attn.kv_a_layernorm.weight": "kv_norm",
          "self_attn.kv_b_proj.weight": "wkvb",
          "self_attn.o_proj.weight": "wo",
          "mlp.gate_proj.weight": "w1", "mlp.up_proj.weight": "w3",
          "mlp.down_proj.weight": "w2",
          "mlp.router.weight": "router",
          "mlp.e_score_correction_bias": "e_bias",
          "mlp.w13": "e_w13", "mlp.w2": "e_w2",
          "mlp.shared.gate_proj.weight": "s_w1",
          "mlp.shared.up_proj.weight": "s_w3",
          "mlp.shared.down_proj.weight": "s_w2"}


def ref_name(program_name: str) -> str:
    if program_name in _TOP:
        return _TOP[program_name]
    m = re.fullmatch(r"model\.layers\.(\d+)\.(.+)", program_name)
    if not m or m.group(2) not in _LAYER:
        raise KeyError(f"no reference leaf for {program_name!r}")
    return f"layers.{m.group(1)}.{_LAYER[m.group(2)]}"


def model_config(cfg: dict):
    """The program's GlmMoeLiteConfig of a configuration file: the router
    keeps the published width, the experts held are the file's range."""
    from paddle_tpu.models import GlmMoeLiteConfig

    return GlmMoeLiteConfig(
        vocab_size=int(cfg["vocab_size"]), hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        num_layers=int(cfg["num_hidden_layers"]),
        num_attention_heads=int(cfg["num_attention_heads"]),
        q_lora_rank=int(cfg["q_lora_rank"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        rope_theta=float(cfg["rope_theta"]),
        max_position_embeddings=int(cfg["max_position_embeddings"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        first_k_dense_replace=int(cfg["first_k_dense_replace"]),
        n_routed_experts=ref.router_width(cfg),
        experts_held=ref.experts_held(cfg),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        n_shared_experts=int(cfg["n_shared_experts"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        n_group=int(cfg["n_group"]), topk_group=int(cfg["topk_group"]))


def build_model(cfg: dict, seed: int, dtype: str):
    """GlmMoeLiteForCausalLM at the configuration's sizes holding the
    benchmark's weights for `seed` in `dtype` (the selection bias, a buffer,
    in float32). Returns (model, [reference leaf names loaded, parameters
    then buffers])."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GlmMoeLiteForCausalLM
    from paddle_tpu.nn import initializer as I

    # parameters are made in `dtype` and as zeros, then take the seed's
    # leaves one at a time
    before = np.dtype(paddle.get_default_dtype()).name
    paddle.set_default_dtype(dtype)
    I.set_global_initializer(I.Constant(0.0))
    try:
        model = GlmMoeLiteForCausalLM(model_config(cfg))
    finally:
        I.set_global_initializer(None)
        paddle.set_default_dtype(before)
    names = []
    for name, p in (*model.named_parameters(), *model.named_buffers()):
        leaf = ref_name(name)
        w = ref.init_leaf(cfg, seed, leaf, dtype)
        if tuple(w.shape) != tuple(p.shape) or w.dtype != p._value.dtype:
            raise ValueError(f"{name}: program holds {tuple(p.shape)} "
                             f"{p._value.dtype}, weights are {w.shape} {w.dtype}")
        p._value = w
        names.append(leaf)
    return model, names


def build_engine(cfg: dict, seed: int):
    """(model, ServingEngine) with the deployment's engine settings."""
    from paddle_tpu.serving import ServingEngine

    sv = cfg["serve"]
    model, _ = build_model(cfg, seed, sv["weight_dtype"])
    model.eval()
    engine = ServingEngine(
        model, max_slots=int(sv["slots"]), block_size=int(sv["block_size"]),
        num_blocks=int(sv["num_blocks"]),
        prefill_chunk=int(sv["prefill_chunk"]),
        max_model_len=int(sv["max_model_len"]),
        prefix_cache=bool(sv["prefix_cache"]), spec_k=int(sv["spec_k"]))
    if engine.fuse_steps != int(sv["fuse_steps"]):
        raise ValueError(f"engine fuse_steps {engine.fuse_steps} is not the "
                         f"configuration's {sv['fuse_steps']}")
    return model, engine
