"""Test fixture: the work module of a second model family, one with grouped
KV heads and window layers, written to manifest.py's contract. The tests
hang it under the family name "tinygqa" (with GPT's reference and program
under it: what runs is still the tiny GPT) to see that `model_flops` and
`work[...]` come from the family's module and from nothing under harness/.

Configuration keys beyond GPT's: "num_kv_heads", "head_dim",
"sliding_window", and "layer_types", one of "sliding_attention" or
"full_attention" a layer."""


def _sizes(cfg):
    return (int(cfg["hidden_size"]), int(cfg["intermediate_size"]),
            int(cfg["num_heads"]), int(cfg["num_kv_heads"]), int(cfg["head_dim"]))


def matmul_params(cfg) -> int:
    h, f, nq, nkv, d = _sizes(cfg)
    attn = h * nq * d + 2 * h * nkv * d + nq * d * h    # q; k and v; out
    return int(cfg["vocab_size"]) * h + len(cfg["layer_types"]) * (attn + 2 * h * f)


def keys_read(cfg, context) -> int:
    """Keys one token at live context `context` attends to, summed over the
    layers: a window layer sees at most its window."""
    w = int(cfg["sliding_window"])
    return sum(min(context, w) if kind == "sliding_attention" else context
               for kind in cfg["layer_types"])


def forward_flops(cfg, contexts) -> float:
    """One token at each of `contexts`: 2 a matmul parameter, and 4 x query
    heads x head size a key read (q.k and p.v)."""
    _, _, nq, _, d = _sizes(cfg)
    return 2.0 * matmul_params(cfg) * len(contexts) \
        + 4.0 * nq * d * sum(keys_read(cfg, c) for c in contexts)


def served_flops(cfg, requests) -> float:
    flops = 0.0
    for plen, matched, n in requests:
        if n >= 1:
            flops += forward_flops(cfg, range(matched + 1, plen + 1))
            flops += forward_flops(cfg, range(plen + 1, plen + n))
    return flops


def traced_work(cfg, facts) -> dict:
    if "decode_contexts" not in facts:
        return {}           # no flash kernel of this family's is measured
    _, _, nq, nkv, d = _sizes(cfg)
    contexts = facts["decode_contexts"]
    keys = sum(keys_read(cfg, c) for c in contexts)
    # K and V of the KV heads alone, bf16, once a key read
    paged = {"flops": 4.0 * nq * d * keys, "bytes": 2.0 * nkv * d * 2 * keys}
    return {"paged_attention": paged,
            "decode_step": {"flops": forward_flops(cfg, contexts),
                            "bytes": facts["ticks"] * matmul_params(cfg) * 2.0
                            + paged["bytes"]}}


def train_flops_per_token(cfg, sequence) -> float:
    # forward and backward: 3 x a forward pass at the mean causal context
    return 3.0 * forward_flops(cfg, [max(1, int(sequence) // 2)])
