"""Operations and bytes that the WORK of the `laguna` configurations needs,
from the configuration file and from facts about what was served, never from
the implementation. Plain arithmetic; imports nothing of the program and
nothing of the harness.

What this family counts that GPT's does not: query heads by layer
(`num_attention_heads_per_layer`) over grouped K/V heads, so K and V are
`num_key_value_heads x head_dim` wide and not the hidden size; window layers,
which read at most `sliding_window` keys a token; a per-head gate; and sparse
experts under a chip's share: a token goes through `num_experts_per_tok`
routed experts of the published count, of which this chip holds
`num_experts`, so in expectation `num_experts_per_tok x held / published` of
its pairs are computed here, and a decode tick reads the experts its tokens
touched, not all of them."""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _layers(cfg):
    """[(window or 0, query heads, dense?)] of the layers kept."""
    n = int(cfg["num_hidden_layers"])
    dense = set(int(i) for i in cfg["mlp_only_layers"])
    return [(int(cfg["sliding_window"])
             if cfg["layer_types"][i] == "sliding_attention" else 0,
             int(cfg["num_attention_heads_per_layer"][i]), i in dense)
            for i in range(n)]


def held_share(cfg) -> float:
    return int(cfg["num_experts"]) / int(
        cfg.get("published", {}).get("num_experts", cfg["num_experts"]))


def expert_params(cfg) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def attention_params(cfg, heads) -> int:
    """q, k, v, the per-head gate and the output projection of one layer."""
    d, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    kv = int(cfg["num_key_value_heads"]) * hd
    return d * heads * hd + 2 * d * kv + d * heads + heads * hd * d


def dense_params(cfg) -> int:
    """Matmul parameters every token goes through: attention of each layer at
    its own head count, the dense layers' feed-forward, and in a sparse layer
    the router (published width) and the shared expert; and the head over
    the vocabulary slice."""
    d = int(cfg["hidden_size"])
    published = int(cfg.get("published", {}).get("num_experts",
                                                  cfg["num_experts"]))
    total = d * int(cfg["vocab_size"])
    for _, heads, dense in _layers(cfg):
        total += attention_params(cfg, heads)
        if dense:
            total += 3 * d * int(cfg["intermediate_size"])
        else:
            total += d * published \
                + 3 * d * int(cfg["shared_expert_intermediate_size"])
    return total


def sparse_layers(cfg) -> int:
    return sum(1 for _, _, dense in _layers(cfg) if not dense)


def matmul_params(cfg) -> float:
    """Matmul parameters a token goes through HERE, in expectation: the
    dense ones and num_experts_per_tok x (held / published) routed experts a
    sparse layer."""
    return dense_params(cfg) + sparse_layers(cfg) * expert_params(cfg) \
        * int(cfg["num_experts_per_tok"]) * held_share(cfg)


def _sum_contexts(a: int, b: int, window: int) -> float:
    """Sum over contexts c = a..b of the keys a token at context c reads:
    c, or min(c, window) in a window layer."""
    if b < a:
        return 0.0
    tri = lambda n: n * (n + 1) / 2.0       # noqa: E731  1 + ... + n
    if not window:
        return tri(b) - tri(a - 1)
    under = min(b, window)                  # contexts that read all of c
    part = tri(under) - tri(a - 1) if under >= a else 0.0
    return part + window * max(0, b - max(a, window + 1) + 1)


def attention_work(cfg, spans, kv_bytes=2) -> dict:
    """q.k and p.v, and the K and V read, for tokens at every context of
    each (first, last) span, over all layers: 4 x query heads x head size a
    key, and 2 x K/V heads x head size x bytes a key."""
    hd, nkv = int(cfg["head_dim"]), int(cfg["num_key_value_heads"])
    flops = bytes_ = 0.0
    for window, heads, _ in _layers(cfg):
        keys = sum(_sum_contexts(a, b, window) for a, b in spans)
        flops += 4.0 * heads * hd * keys
        bytes_ += 2.0 * nkv * hd * kv_bytes * keys
    return {"flops": flops, "bytes": bytes_}


def forward_flops(cfg, spans) -> float:
    tokens = sum(max(0, b - a + 1) for a, b in spans)
    return 2.0 * matmul_params(cfg) * tokens \
        + attention_work(cfg, spans)["flops"]


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
    published = int(cfg.get("published", {}).get("num_experts",
                                                  cfg["num_experts"]))
    miss = 1.0 - int(cfg["num_experts_per_tok"]) / published
    return int(cfg["num_experts"]) * (1.0 - miss ** max(tokens, 0.0))


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


def traced_work(cfg, facts) -> dict:
    """{work name: {"flops", "bytes"}} of a traced serving period, from
    `decode_contexts`, `ticks` and `prefill_tokens`."""
    if "decode_contexts" not in facts:
        return {}
    contexts = facts["decode_contexts"]
    ticks = float(facts["ticks"])
    spans = [(c, c) for c in contexts]
    paged = attention_work(cfg, spans)
    wb = DTYPE_BYTES[cfg["serve"]["weight_dtype"]]
    per_tick = len(contexts) / ticks if ticks else 0.0
    weights = dense_params(cfg) * wb + sparse_layers(cfg) \
        * experts_touched(cfg, per_tick) * expert_params(cfg) * wb
    return {
        "paged_attention": paged,
        "decode_step": {"flops": forward_flops(cfg, spans),
                        "bytes": ticks * weights + paged["bytes"]},
        "moe_experts": moe_experts(cfg, len(contexts), ticks,
                                   float(facts.get("prefill_tokens", 0))),
    }


def train_flops_per_token(cfg, sequence) -> float:
    # forward and backward: 3 x a forward pass at the mean causal context
    half = max(1, int(sequence) // 2)
    return 3.0 * forward_flops(cfg, [(half, half)])
