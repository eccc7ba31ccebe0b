"""Operations and bytes that the WORK needs, from shapes and live context,
never from the implementation: a later kernel that does the same work
another way is measured by the same yardstick. Counts are of the algorithm:
recomputed operations do not count."""
from __future__ import annotations


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


def train_flops_per_token(cfg, seq) -> float:
    """bench.py's and MFU_PROBE.jsonl's formula: 6 N for the forward and
    backward matmuls over all N parameters, plus 12 L h s for attention
    (PaLM appendix B; causal masking not discounted)."""
    return 6.0 * n_params(cfg) + 12.0 * int(cfg["num_layers"]) \
        * int(cfg["hidden_size"]) * int(seq)


def forward_flops(cfg, n_tokens, attended) -> float:
    """A forward pass over n_tokens new tokens that between them attend to
    `attended` (query, key) pairs: 2 per matmul parameter per token, and
    4 h per pair per layer (q.k and p.v)."""
    return 2.0 * matmul_params(cfg) * n_tokens \
        + 4.0 * int(cfg["num_layers"]) * int(cfg["hidden_size"]) * attended


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


def roofline_seconds(work: dict, peaks: dict):
    """The least time the chip could take, and which bound gives it."""
    t_f = work["flops"] / peaks["flops_per_s"]
    t_b = work["bytes"] / peaks["bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
