"""Operations and bytes that the WORK of the GPT configurations needs, from
the configuration file and from facts about what was served or trained,
never from the implementation: a later kernel that does the same work
another way is measured by the same yardstick. Counts are of the algorithm:
recomputed operations do not count. Plain arithmetic; imports nothing of
the program and nothing of the harness.

This is GPT-3's arithmetic and nobody else's: every head has K and V of its
own (KV width = hidden size), every layer attends to the whole context and
every token goes through every parameter. A family with grouped KV heads,
window layers or sparse experts brings a work module of its own (the
contract is in harness/manifest.py)."""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def matmul_params(cfg) -> int:
    """Parameters that take part in a matmul per token: everything except
    the position table (a lookup); the tied embedding counts once, as the
    output head."""
    h, nl = int(cfg["hidden_size"]), int(cfg["num_layers"])
    f = int(cfg.get("intermediate_size") or 4 * h)
    v = int(cfg["vocab_size"])
    per_layer = h * 3 * h + h * h + h * f + f * h
    return v * h + nl * per_layer


def n_params(cfg) -> int:
    h, nl = int(cfg["hidden_size"]), int(cfg["num_layers"])
    f = int(cfg.get("intermediate_size") or 4 * h)
    v, p = int(cfg["vocab_size"]), int(cfg["max_position_embeddings"])
    per_layer = 4 * h + (h * 3 * h + 3 * h) + (h * h + h) + (h * f + f) + (f * h + h)
    return v * h + p * h + nl * per_layer + 2 * h


def train_flops_per_token(cfg, sequence) -> float:
    """bench.py's and MFU_PROBE.jsonl's formula: 6 N for the forward and
    backward matmuls over all N parameters, plus 12 L h s for attention
    (PaLM appendix B; causal masking not discounted)."""
    return 6.0 * n_params(cfg) + 12.0 * int(cfg["num_layers"]) \
        * int(cfg["hidden_size"]) * int(sequence)


def forward_flops(cfg, n_tokens, attended) -> float:
    """A forward pass over n_tokens new tokens that between them attend to
    `attended` (query, key) pairs: 2 per matmul parameter per token, and
    4 h per pair per layer (q.k and p.v)."""
    return 2.0 * matmul_params(cfg) * n_tokens \
        + 4.0 * int(cfg["num_layers"]) * int(cfg["hidden_size"]) * attended


def served_flops(cfg, requests) -> float:
    """Forward operations of what a serving window computed. `requests`:
    one (prompt_tokens, prefix_matched, tokens_at_close) for each request
    handed over: the prompt less its cached prefix is prefilled (the first
    token comes out of that pass), every later token is a decode step at
    its live context."""
    flops = 0.0
    for plen, m, n in requests:
        if n < 1:
            continue
        pairs = (plen * (plen + 1) - m * (m + 1)) / 2.0
        flops += forward_flops(cfg, plen - m, pairs)
        d = n - 1
        flops += forward_flops(cfg, d, d * plen + d * (d + 1) / 2.0)
    return flops


def flash_attention_train(cfg, batch, seq) -> dict:
    """Causal self-attention forward and backward for one step, all layers.
    Forward: q.k and p.v over the lower triangle, 2 matmuls x 2 flops x
    b x heads x s(s+1)/2 x d. Backward: dv, dp, dq, dk, 4 such matmuls (the
    recomputation of the scores is the kernel's choice, not the work's).
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o, do
    and writes dq, dk, dv; bf16."""
    h, nl = int(cfg["hidden_size"]), int(cfg["num_layers"])
    pairs = batch * seq * (seq + 1) / 2.0
    one = 2.0 * pairs * h                 # one matmul over the triangle
    act = batch * seq * h * 2.0           # one [b, s, h] bf16 array
    return {"flops": nl * 6.0 * one, "bytes": nl * 12.0 * act}


def paged_attention_decode(cfg, contexts, kv_bytes=2) -> dict:
    """Decode attention for the given list of live context lengths (one
    entry per token decoded, over the period measured), all layers: each
    token reads its context's K and V once and does q.k and p.v over it."""
    h, nl = int(cfg["hidden_size"]), int(cfg["num_layers"])
    ctx = float(sum(contexts))
    return {"flops": nl * 4.0 * h * ctx,
            "bytes": nl * 2.0 * h * kv_bytes * ctx}


def decode_step(cfg, contexts, ticks) -> dict:
    """The whole decode step for the tokens decoded in a period, each at
    its live context: the forward pass of one token (every matmul parameter
    and the attention over its context). Bytes are the least the steps can
    move: the matmul weights once a tick, whatever the batch, and each
    context's K and V once."""
    attn = paged_attention_decode(cfg, contexts)
    weights = matmul_params(cfg) * DTYPE_BYTES[cfg["serve"]["weight_dtype"]]
    return {"flops": forward_flops(cfg, len(contexts), 0) + attn["flops"],
            "bytes": float(ticks) * weights + attn["bytes"]}


def traced_work(cfg, facts) -> dict:
    """{work name: {"flops", "bytes"}} of a traced period. Serving facts
    (`decode_contexts`, `ticks`, `prefill_tokens`) give the paged decode
    kernel's work and the whole decode step's; training facts (`batch`,
    `sequence`, `steps`) give flash attention's, forward and backward."""
    if "decode_contexts" in facts:
        contexts = facts["decode_contexts"]
        return {"paged_attention": paged_attention_decode(cfg, contexts),
                "decode_step": decode_step(cfg, contexts, facts["ticks"])}
    per_step = flash_attention_train(cfg, facts["batch"], facts["sequence"])
    return {"flash_attention": {k: v * facts["steps"]
                                for k, v in per_step.items()}}
