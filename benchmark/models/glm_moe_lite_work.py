"""Operations and bytes that the WORK of the `glm_moe_lite` configurations
needs, from the configuration file and from facts about what was served,
never from the implementation. Plain arithmetic; imports nothing of the
program and nothing of the harness.

What this family counts that the others do not: latent attention. A token's
cache row is `kv_lora_rank + qk_rope_head_dim` values a layer (1,152 B in
bf16 at the published widths: what has to be read, whatever the device pads
it to), shared by every head. The model's own operations (`served_flops`,
`decode_step`) count attention in the EXPANDED form, which is the published
mathematics and needs the fewest: 2 x heads x (nope + rope + v) a (query,
key). The two kernels are held to the ABSORBED form they compute, 2 x heads x
(row + kv_lora_rank) a (query, key), and the prefill kernel only for the
queries that are tokens of a prompt: the rows that pad a chunk are work it
did not need. Sparse experts under a chip's share are counted as laguna_work
counts them: `num_experts_per_tok x held / published` pairs a token here in
expectation, a tick reads the experts its tokens touched."""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def published_experts(cfg) -> int:
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def held_share(cfg) -> float:
    return int(cfg["n_routed_experts"]) / published_experts(cfg)


def layers(cfg) -> int:
    return int(cfg["num_hidden_layers"])


def sparse_layers(cfg) -> int:
    return layers(cfg) - int(cfg["first_k_dense_replace"])


def expert_params(cfg) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def attention_params(cfg) -> int:
    """q_a, q_b, kv_a, kv_b and the output projection of one layer."""
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    qr, kr = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    vd = int(cfg["v_head_dim"])
    return (d * qr + qr * h * (nope + rope) + d * (kr + rope)
            + kr * h * (nope + vd) + h * vd * d)


def layer_params_outside_experts(cfg) -> int:
    """A sparse layer's attention, router (published width) and shared
    expert."""
    d = int(cfg["hidden_size"])
    return attention_params(cfg) + d * published_experts(cfg) \
        + expert_params(cfg) * int(cfg["n_shared_experts"])


def dense_params(cfg) -> int:
    """Matmul parameters every token goes through: every layer's attention,
    the dense layers' feed-forward, a sparse layer's router and shared
    expert, and the head over the vocabulary slice."""
    d = int(cfg["hidden_size"])
    n_dense = int(cfg["first_k_dense_replace"])
    return (d * int(cfg["vocab_size"])
            + n_dense * (attention_params(cfg)
                         + 3 * d * int(cfg["intermediate_size"]))
            + sparse_layers(cfg) * layer_params_outside_experts(cfg))


def weight_params(cfg) -> int:
    """Every parameter held here: dense_params, the embedding, and the held
    routed experts (norms and the bias are thousands)."""
    return dense_params(cfg) \
        + int(cfg["hidden_size"]) * int(cfg["vocab_size"]) \
        + sparse_layers(cfg) * int(cfg["n_routed_experts"]) * expert_params(cfg)


def matmul_params(cfg) -> float:
    """Matmul parameters a token goes through HERE, in expectation."""
    return dense_params(cfg) + sparse_layers(cfg) * expert_params(cfg) \
        * int(cfg["num_experts_per_tok"]) * held_share(cfg)


def latent_row_bytes(cfg) -> int:
    """Bytes of one token's cache row in one layer, as the mathematics
    needs them."""
    return (int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])) \
        * DTYPE_BYTES[cfg["serve"]["weight_dtype"]]


def cache_bytes_per_token(cfg) -> int:
    return layers(cfg) * latent_row_bytes(cfg)


def _keys(spans) -> float:
    """Sum over contexts c = a..b of c, for each (a, b): the (query, key)
    pairs of tokens at every context of the spans, in one layer."""
    tri = lambda n: n * (n + 1) / 2.0       # noqa: E731  1 + ... + n
    return sum(tri(b) - tri(a - 1) for a, b in spans if b >= a)


def expanded_attention_flops(cfg, spans) -> float:
    """q.k over nope + rope and p.v over v, every head, all layers."""
    per_pair = 2.0 * int(cfg["num_attention_heads"]) * (
        int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
        + int(cfg["v_head_dim"]))
    return layers(cfg) * per_pair * _keys(spans)


def absorbed_attention_flops(cfg, spans) -> float:
    """q'.row over the whole row and p.latent over kv_lora_rank, every
    head, all layers: what the latent kernels compute."""
    kr = int(cfg["kv_lora_rank"])
    per_pair = 2.0 * int(cfg["num_attention_heads"]) * (
        kr + int(cfg["qk_rope_head_dim"]) + kr)
    return layers(cfg) * per_pair * _keys(spans)


def forward_flops(cfg, spans) -> float:
    tokens = sum(max(0, b - a + 1) for a, b in spans)
    return 2.0 * matmul_params(cfg) * tokens \
        + expanded_attention_flops(cfg, spans)


def served_flops(cfg, requests) -> float:
    """Forward operations of what a serving window computed: the prompt less
    its cached prefix is prefilled, every later token is a decode step at its
    live context."""
    spans = []
    for plen, matched, n in requests:
        if n >= 1:
            spans += [(matched + 1, plen), (plen + 1, plen + n - 1)]
    return forward_flops(cfg, spans)


def experts_touched(cfg, tokens: float) -> float:
    """Held experts that `tokens` tokens routed at random touch, in
    expectation: each is missed by a token with chance 1 - k / published."""
    miss = 1.0 - int(cfg["num_experts_per_tok"]) / published_experts(cfg)
    return int(cfg["n_routed_experts"]) * (1.0 - miss ** max(tokens, 0.0))


def moe_experts(cfg, decode_tokens, ticks, prefill_tokens) -> dict:
    """The grouped products of the routed experts held here, all sparse
    layers: 2 x 3 x hidden x width a pair computed; bytes are each touched
    expert's weights once a decode tick and once a prefill chunk, and the
    pairs' rows in and out."""
    wb = DTYPE_BYTES[cfg["serve"]["weight_dtype"]]
    chunk = int(cfg["serve"]["prefill_chunk"])
    d = int(cfg["hidden_size"])
    pairs = (decode_tokens + prefill_tokens) \
        * int(cfg["num_experts_per_tok"]) * held_share(cfg)
    chunks = prefill_tokens / chunk
    reads = 0.0
    if ticks:
        reads += ticks * experts_touched(cfg, decode_tokens / ticks)
    if chunks:
        reads += chunks * experts_touched(cfg, prefill_tokens / chunks)
    n = sparse_layers(cfg)
    return {"flops": n * 2.0 * expert_params(cfg) * pairs,
            "bytes": n * (reads * expert_params(cfg) * wb
                          + pairs * 2 * d * wb)}


def latent_decode(cfg, contexts) -> dict:
    """The decode kernel's work: every live key's row read once a layer, and
    the absorbed products over it."""
    spans = [(c, c) for c in contexts]
    return {"flops": absorbed_attention_flops(cfg, spans),
            "bytes": cache_bytes_per_token(cfg) * _keys(spans)}


def latent_prefill(cfg, chunks) -> dict:
    """The prefill kernel's work for chunks [(first position, tokens)]: the
    absorbed products of the prompt's own tokens against the keys before
    and at them; a chunk reads the keys before and in it once."""
    spans = [(start + 1, start + n) for start, n in chunks]
    return {"flops": absorbed_attention_flops(cfg, spans),
            "bytes": cache_bytes_per_token(cfg)
            * sum(start + n for start, n in chunks)}


def traced_work(cfg, facts) -> dict:
    """{work name: {"flops", "bytes"}} of a traced serving period, from
    `decode_contexts`, `ticks` and `prefill_tokens`; and `latent_prefill`
    where the facts hold `prefill_chunks`, [(first position, tokens)] of the
    period's chunks (the harness's facts have no contexts of prefilled
    tokens: layer_metrics/latent_prefill_roofline.py reads them from the
    program's serving.prefill_chunk spans)."""
    if "decode_contexts" not in facts:
        return {}
    contexts = facts["decode_contexts"]
    ticks = float(facts["ticks"])
    spans = [(c, c) for c in contexts]
    latent = latent_decode(cfg, contexts)
    wb = DTYPE_BYTES[cfg["serve"]["weight_dtype"]]
    per_tick = len(contexts) / ticks if ticks else 0.0
    weights = dense_params(cfg) * wb + sparse_layers(cfg) \
        * experts_touched(cfg, per_tick) * expert_params(cfg) * wb
    work = {
        "latent_decode": latent,
        "decode_step": {"flops": forward_flops(cfg, spans),
                        "bytes": ticks * weights + latent["bytes"]},
        "moe_experts": moe_experts(cfg, len(contexts), ticks,
                                   float(facts.get("prefill_tokens", 0))),
    }
    if facts.get("prefill_chunks"):
        work["latent_prefill"] = latent_prefill(cfg, facts["prefill_chunks"])
    return work


def train_flops_per_token(cfg, sequence) -> float:
    # forward and backward: 3 x a forward pass at the mean causal context
    half = max(1, int(sequence) // 2)
    return 3.0 * forward_flops(cfg, [(half, half)])
