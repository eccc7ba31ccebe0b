"""Operations and bytes that the WORK of the `xing` configurations needs,
from the configuration file and from facts about what was served, never from
the implementation. Plain arithmetic; imports nothing of the program and
nothing of the harness.

Latent attention and the sparse experts are counted as glm_moe_lite_work
counts them (the same keys: a cache row of kv_lora_rank + qk_rope_head_dim
values a layer, the expanded form for the model's own operations, the
absorbed form for the two kernels; pairs by the share of experts held, here
every one: held_share 1). What this family adds is the residual mix
(manifold-constrained hyper-connections): 2 applications a layer a token,
each with n (n + 2) maps of n C parameters, float32. The LEAST an application
has to move is the n streams read once and written once, the sublayer's
input written once and its output read once: (2 n + 2) C values in the
streams' dtype, whatever implements it (two kernels that each read the
streams move (3 n + 2) C: 71% is then the most the share can read)."""
from __future__ import annotations

from .glm_moe_lite_work import (DTYPE_BYTES, attention_params,
                                cache_bytes_per_token,  # noqa: F401 (the counted row)
                                expanded_attention_flops, expert_params,
                                experts_touched, held_share, latent_decode,
                                latent_prefill, layers, moe_experts,
                                published_experts, sparse_layers)

MAP_BYTES = 4       # the maps' parameters are float32


def streams(cfg) -> int:
    return int(cfg["hc_mult"])


def mhc_params(cfg) -> int:
    """One sublayer's maps: phi [n (n + 2), n C], b [n (n + 2)], a [3]."""
    n = streams(cfg)
    m = n * (n + 2)
    return m * n * int(cfg["hidden_size"]) + m + 3


def mhc_applications(cfg) -> int:
    """A token forward: two a layer."""
    return 2 * layers(cfg)


def layer_params_outside_experts(cfg) -> int:
    """A sparse layer's attention, router (published width), shared expert
    and its two mixes."""
    d = int(cfg["hidden_size"])
    return attention_params(cfg) + d * published_experts(cfg) \
        + expert_params(cfg) * int(cfg["n_shared_experts"]) \
        + 2 * mhc_params(cfg)


def dense_layer_params(cfg) -> int:
    return attention_params(cfg) + 3 * int(cfg["hidden_size"]) \
        * int(cfg["intermediate_size"]) + 2 * mhc_params(cfg)


def dense_params(cfg) -> int:
    """Parameters every token goes through: every layer's attention and
    mixes, the dense layers' feed-forward, a sparse layer's router and
    shared expert, and the head."""
    return (int(cfg["hidden_size"]) * int(cfg["vocab_size"])
            + int(cfg["first_k_dense_replace"]) * dense_layer_params(cfg)
            + sparse_layers(cfg) * layer_params_outside_experts(cfg))


def weight_params(cfg) -> int:
    """Every parameter held here: dense_params, the embedding, and the held
    routed experts (norms and the selection bias are thousands)."""
    return dense_params(cfg) \
        + int(cfg["hidden_size"]) * int(cfg["vocab_size"]) \
        + sparse_layers(cfg) * int(cfg["n_routed_experts"]) * expert_params(cfg)


def weight_bytes(cfg) -> int:
    """The weights as held: the serving dtype, the maps in float32."""
    wb = DTYPE_BYTES[cfg["serve"]["weight_dtype"]]
    maps = mhc_applications(cfg) * mhc_params(cfg)
    return (weight_params(cfg) - maps) * wb + maps * MAP_BYTES


def matmul_params(cfg) -> float:
    """Matmul parameters a token goes through HERE, in expectation."""
    return dense_params(cfg) + sparse_layers(cfg) * expert_params(cfg) \
        * int(cfg["num_experts_per_tok"]) * held_share(cfg)


def mhc(cfg, tokens: float) -> dict:
    """The residual mix of `tokens` tokens through every layer. Operations:
    the product with phi (2 x n C x n (n + 2)), u = H_pre X (2 n C), X' =
    H_res X + H_post^T y (2 n n C + 2 n C); the Sinkhorn rounds are
    hundreds. Bytes: the module docstring's least."""
    n, c = streams(cfg), int(cfg["hidden_size"])
    wb = DTYPE_BYTES[cfg["serve"]["weight_dtype"]]
    per = 2.0 * n * c * n * (n + 2) + 2.0 * n * c + 2.0 * n * n * c \
        + 2.0 * n * c
    apps = mhc_applications(cfg) * tokens
    return {"flops": per * apps, "bytes": (2 * n + 2) * c * wb * apps}


def stored_cache_bytes_per_token(cfg) -> int:
    """What the pool takes a token: a row in whole lanes of 128 values
    (576 in 640 at the published widths), every layer."""
    lanes = -(-(int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"]))
              // 128) * 128
    return layers(cfg) * lanes * DTYPE_BYTES[cfg["serve"]["weight_dtype"]]


def mhc_bytes_per_application(cfg) -> int:
    return int(mhc(cfg, 1)["bytes"] / mhc_applications(cfg))


def forward_flops(cfg, spans) -> float:
    """2 a matmul parameter a token (the product with phi among them),
    attention in the expanded form, and the mix's own sums."""
    tokens = sum(max(0, b - a + 1) for a, b in spans)
    n, c = streams(cfg), int(cfg["hidden_size"])
    mix = mhc(cfg, tokens)["flops"] \
        - 2.0 * n * c * n * (n + 2) * mhc_applications(cfg) * tokens
    return 2.0 * matmul_params(cfg) * tokens \
        + expanded_attention_flops(cfg, spans) + mix


def served_flops(cfg, requests) -> float:
    """Forward operations of what a serving window computed: the prompt less
    its cached prefix is prefilled, every later token is a decode step at its
    live context."""
    spans = []
    for plen, matched, n in requests:
        if n >= 1:
            spans += [(matched + 1, plen), (plen + 1, plen + n - 1)]
    return forward_flops(cfg, spans)


def traced_work(cfg, facts) -> dict:
    """{work name: {"flops", "bytes"}} of a traced serving period, from
    `decode_contexts`, `ticks` and `prefill_tokens`; `latent_prefill` where
    the facts hold `prefill_chunks` (glm_moe_lite_work.traced_work)."""
    if "decode_contexts" not in facts:
        return {}
    contexts = facts["decode_contexts"]
    ticks = float(facts["ticks"])
    prefilled = float(facts.get("prefill_tokens", 0))
    spans = [(c, c) for c in contexts]
    latent = latent_decode(cfg, contexts)
    wb = DTYPE_BYTES[cfg["serve"]["weight_dtype"]]
    per_tick = len(contexts) / ticks if ticks else 0.0
    maps = mhc_applications(cfg) * mhc_params(cfg)
    weights = (dense_params(cfg) - maps) * wb + maps * MAP_BYTES \
        + sparse_layers(cfg) * experts_touched(cfg, per_tick) \
        * expert_params(cfg) * wb
    work = {
        "latent_decode": latent,
        "decode_step": {"flops": forward_flops(cfg, spans),
                        "bytes": ticks * weights + latent["bytes"]
                        + mhc(cfg, len(contexts))["bytes"]},
        "moe_experts": moe_experts(cfg, len(contexts), ticks, prefilled),
        # the mix's KERNELS run where tokens fill whole blocks of 128: the
        # prefill chunks. A decode step's 24 rows take the XLA form, whose
        # time is in decode_step_ms and whose work is in decode_step above
        "mhc": mhc(cfg, prefilled),
    }
    if facts.get("prefill_chunks"):
        work["latent_prefill"] = latent_prefill(cfg, facts["prefill_chunks"])
    return work


def train_flops_per_token(cfg, sequence) -> float:
    # forward and backward: 3 x a forward pass at the mean causal context
    half = max(1, int(sequence) // 2)
    return 3.0 * forward_flops(cfg, [(half, half)])
