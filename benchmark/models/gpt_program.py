"""The system under test for the GPT configurations: the program's own model,
train step and serving engine, built from a configuration file and loaded
with the benchmark's seeded weights. Everything here is the program's public
surface as `chip_smoke.py` and `bench.py` use it."""
from __future__ import annotations

import re

from . import gpt_reference as ref

# program parameter name -> reference leaf name
_TOP = {"gpt.wte.weight": "wte", "gpt.wpe.weight": "wpe",
        "gpt.ln_f.weight": "lnf_g", "gpt.ln_f.bias": "lnf_b"}
_LAYER = {"ln_1.weight": "ln1_g", "ln_1.bias": "ln1_b",
          "attn.qkv_proj.weight": "qkv_w", "attn.qkv_proj.bias": "qkv_b",
          "attn.out_proj.weight": "proj_w", "attn.out_proj.bias": "proj_b",
          "ln_2.weight": "ln2_g", "ln_2.bias": "ln2_b",
          "mlp.fc_in.weight": "fc_w", "mlp.fc_in.bias": "fc_b",
          "mlp.fc_out.weight": "out_w", "mlp.fc_out.bias": "out_b"}


def ref_name(program_name: str) -> str:
    if program_name in _TOP:
        return _TOP[program_name]
    m = re.fullmatch(r"gpt\.blocks\.(\d+)\.(.+)", program_name)
    if not m or m.group(2) not in _LAYER:
        raise KeyError(f"no reference leaf for parameter {program_name!r}")
    return f"blocks.{m.group(1)}.{_LAYER[m.group(2)]}"


def build_model(cfg: dict, seed: int, dtype: str):
    """GPTForCausalLM at the configuration's sizes holding the benchmark's
    weights for `seed` in `dtype`. Returns (model, [reference leaf names in
    the order of model.parameters()])."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    gcfg = GPTConfig(
        vocab_size=int(cfg["vocab_size"]), hidden_size=int(cfg["hidden_size"]),
        num_layers=int(cfg["num_layers"]), num_heads=int(cfg["num_heads"]),
        intermediate_size=int(cfg.get("intermediate_size") or 0),
        max_position_embeddings=int(cfg["max_position_embeddings"]),
        # the default 0.1 fails the flash gate (dropout_p must be 0)
        hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(int(seed) & 0x7FFFFFFF)
    model = GPTForCausalLM(gcfg)
    if dtype == "bfloat16":
        model = model.bfloat16()
    weights = ref.init_weights(cfg, seed, dtype, per_layer=True)
    names = []
    for name, p in model.named_parameters():
        leaf = ref_name(name)
        w = weights[leaf]
        if tuple(w.shape) != tuple(p.shape) or w.dtype != p._value.dtype:
            raise ValueError(f"{name}: program holds {tuple(p.shape)} "
                             f"{p._value.dtype}, weights are {w.shape} {w.dtype}")
        p._value = w
        names.append(leaf)
    return model, names


def build_train_step(cfg: dict, seed: int):
    """(model, TrainStep, leaf names) as bench.py builds them."""
    from paddle_tpu import amp, optimizer
    from paddle_tpu.jit.trainer import TrainStep

    tr = cfg["train"]
    opt_cfg = tr["optimizer"]
    if opt_cfg["name"] != "AdamW":
        raise ValueError(f"optimizer {opt_cfg['name']!r} is not built here")
    model, names = build_model(cfg, seed, tr["param_dtype"])
    opt = optimizer.AdamW(
        float(opt_cfg["learning_rate"]), beta1=float(opt_cfg["beta1"]),
        beta2=float(opt_cfg["beta2"]), epsilon=float(opt_cfg["epsilon"]),
        parameters=model.parameters(),
        weight_decay=float(opt_cfg["weight_decay"]))
    level, amp_dtype = tr["amp_level"], tr["amp_dtype"]

    def loss_fn(ids):
        with amp.auto_cast(level=level, dtype=amp_dtype):
            return model(ids, labels=ids)

    step = TrainStep(model, loss_fn, opt, nan_guard=bool(tr["nan_guard"]))
    return model, step, names


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
