"""The system under test for the `xing` configurations: the program's own
Xing4ForCausalLM and ServingEngine, built from a configuration file and
loaded with the benchmark's seeded weights, leaf after leaf (at the published
widths the weights fill two thirds of a chip: they are never held twice).
Everything here is the program's public surface."""
from __future__ import annotations

import re

from . import glm_moe_lite_program as glm
from . import xing_reference as ref

_LAYER = {**glm._LAYER,
          **{f"hc_{w}.{k}": f"hc_{w}.{k}"
             for w in ("attn", "mlp") for k in ("phi", "a", "b")}}


def ref_name(program_name: str) -> str:
    if program_name in glm._TOP:
        return glm._TOP[program_name]
    m = re.fullmatch(r"model\.layers\.(\d+)\.(.+)", program_name)
    if not m or m.group(2) not in _LAYER:
        raise KeyError(f"no reference leaf for {program_name!r}")
    return f"layers.{m.group(1)}.{_LAYER[m.group(2)]}"


def model_config(cfg: dict):
    """The program's Xing4Config of a configuration file: the router keeps
    the published width, the experts held are the file's range."""
    from paddle_tpu.models import Xing4Config

    return Xing4Config(
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
        rope_scaling=cfg.get("rope_scaling"),
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
        n_group=int(cfg["n_group"]), topk_group=int(cfg["topk_group"]),
        initializer_range=float(cfg.get("initializer_range", 0.02)),
        hc_mult=int(cfg["hc_mult"]),
        hc_sinkhorn_iters=int(cfg["hc_sinkhorn_iters"]),
        hc_eps=float(cfg["hc_eps"]),
        mhc_h_res_clamp=(float(cfg["mhc_h_res_clamp_min"]),
                         float(cfg["mhc_h_res_clamp_max"])))


def build_model(cfg: dict, seed: int, dtype: str):
    """Xing4ForCausalLM at the configuration's sizes holding the benchmark's
    weights for `seed` in `dtype` (the maps' parameters and the selection
    bias in float32). Returns (model, [reference leaf names loaded])."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import Xing4ForCausalLM
    from paddle_tpu.nn import initializer as I

    before = np.dtype(paddle.get_default_dtype()).name
    paddle.set_default_dtype(dtype)
    I.set_global_initializer(I.Constant(0.0))
    try:
        model = Xing4ForCausalLM(model_config(cfg))
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
