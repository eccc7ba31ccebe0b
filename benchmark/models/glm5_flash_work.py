"""Operations and bytes that the WORK of the `glm5_flash` configurations
needs, from the configuration file and from facts about what was served,
never from the implementation. Plain arithmetic; imports nothing of the
program and nothing of the harness.

What this family counts that the others do not:

- Linear layers. A token through a layer's state costs three products with
  the H x K x V state (S^T k, the rank-one write k u^T, S^T q) and its decay:
  2 x 3 x H K V operations, whatever form computes them. Decode reads and
  writes each running slot's float32 state once a step (2 x 4 B x H K V)
  beside its q, k, beta k, v and g in float32. A prefill chunk reads the
  state and writes it once, and each of its tokens' q, k, beta k, beta v and
  cumulative g in float32 and writes o in float32.
- Sparse layers: the indexer scores a query's COMPLETE blocks (2 x J x D a
  block), and attention reads the selected keys alone: at a position p,
  POOL x min(p // POOL, topk blocks) + p % POOL + 1 of them (expanded form
  for the model's operations: 2 x heads x (nope + v) a key). The indexer's
  kernel reads the chunk's queries and the pooled keys and writes a float32
  score a (query, block) of the workspace.
- The residual mix and the sparse experts as xing_work and
  glm_moe_lite_work count them (every layer has two mixes; the routed
  experts' pairs by the share held)."""
from __future__ import annotations

from .glm_moe_lite_work import (DTYPE_BYTES, experts_touched, held_share,  # noqa: F401
                                layers, moe_experts, published_experts,
                                sparse_layers)
from .xing_work import mhc, mhc_params

F32 = 4


def _served(cfg):
    return cfg["layers_served"]


def kinds(cfg):
    return [cfg["layer_types"][i] for i in _served(cfg)]


def linear_layers(cfg) -> int:
    return sum(1 for k in kinds(cfg) if k == "linear_attention")


def dsa_layers(cfg) -> int:
    return layers(cfg) - linear_layers(cfg)


def _lin(cfg):
    c = cfg["linear_attn_config"]
    return int(c["num_heads"]), int(c["head_dim"]), \
        int(c["short_conv_kernel_size"])


def topk_blocks(cfg) -> int:
    return int(cfg["index_topk"]) // int(cfg["index_kpool"])


# -------------------------------------------------------------- parameters
def linear_params(cfg) -> int:
    """q, k, v, the two gate factorizations, beta and the output of one
    linear layer (the convolution, A_log, dt_bias and norms are thousands)."""
    d = int(cfg["hidden_size"])
    h, k, _ = _lin(cfg)
    r = int(cfg["kda_gate_rank"])
    hk = h * k
    return 3 * d * hk + 2 * (d * r + r * hk) + d * h + hk * d


def dsa_params(cfg) -> int:
    """The latent projections and the indexer of one sparse layer."""
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    qr, kr = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, vd = int(cfg["qk_nope_head_dim"]), int(cfg["v_head_dim"])
    ih, idim = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    return (d * qr + qr * h * nope + d * kr + kr * h * (nope + vd)
            + h * vd * d + qr * ih * idim + d * idim + d * ih)


def expert_params(cfg) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def dense_params(cfg) -> int:
    """Parameters every token goes through: every layer's attention and
    mixes, the dense feed-forward, a sparse layer's router and shared
    expert, and the head (over the vocabulary slice)."""
    d = int(cfg["hidden_size"])
    attn = linear_layers(cfg) * linear_params(cfg) \
        + dsa_layers(cfg) * dsa_params(cfg)
    ffn = int(cfg["first_k_dense_replace"]) * 3 * d \
        * int(cfg["intermediate_size"]) + sparse_layers(cfg) * (
            d * published_experts(cfg)
            + expert_params(cfg) * int(cfg["n_shared_experts"]))
    return d * int(cfg["vocab_size"]) + attn + ffn \
        + 2 * layers(cfg) * mhc_params(cfg)


def weight_params(cfg) -> int:
    """Every parameter held here: dense_params, the embedding and the held
    routed experts."""
    return dense_params(cfg) \
        + int(cfg["hidden_size"]) * int(cfg["vocab_size"]) \
        + sparse_layers(cfg) * int(cfg["n_routed_experts"]) * expert_params(cfg)


def matmul_params(cfg) -> float:
    return dense_params(cfg) + sparse_layers(cfg) * expert_params(cfg) \
        * int(cfg["num_experts_per_tok"]) * held_share(cfg)


# ------------------------------------------------------------------ state
def state_bytes_per_slot(cfg) -> int:
    """A slot's float32 state and bf16 convolution tail, every linear layer."""
    h, k, ks = _lin(cfg)
    wb = DTYPE_BYTES[cfg["serve"]["weight_dtype"]]
    return linear_layers(cfg) * (h * k * k * F32 + (ks - 1) * 3 * h * k * wb)


def cache_bytes_per_token(cfg) -> float:
    """A position of every sparse layer: the latent and a quarter of a pooled
    index key."""
    wb = DTYPE_BYTES[cfg["serve"]["weight_dtype"]]
    return dsa_layers(cfg) * wb * (int(cfg["kv_lora_rank"])
                                   + int(cfg["index_head_dim"])
                                   / int(cfg["index_kpool"]))


def state_flops_per_token(cfg) -> float:
    h, k, _ = _lin(cfg)
    return linear_layers(cfg) * 2.0 * 3 * h * k * k


def selected(cfg, p: int) -> int:
    """Keys a query at position p attends to in a sparse layer."""
    pool = int(cfg["index_kpool"])
    return pool * min(p // pool, topk_blocks(cfg)) + p % pool + 1


def _floor_sum(n: int, pool: int) -> float:
    """Sum over p = 0..n of p // pool."""
    if n < 0:
        return 0.0
    q, r = divmod(n, pool)
    return pool * q * (q - 1) / 2.0 + q * (r + 1)


def _blocks_scored(cfg, a: int, b: int) -> float:
    """Sum over positions p = a..b of the complete blocks p // pool."""
    pool = int(cfg["index_kpool"])
    return _floor_sum(b, pool) - _floor_sum(a - 1, pool)


def _sum_selected(cfg, a: int, b: int) -> float:
    """Sum over positions p = a..b of selected(cfg, p): p + 1 below the
    cap, then POOL x topk blocks + p % POOL + 1."""
    pool, k = int(cfg["index_kpool"]), topk_blocks(cfg)
    cap = pool * k
    total = 0.0
    lo, hi = a, min(b, cap - 1)
    if hi >= lo:
        total += (hi + 1) * (hi + 2) / 2.0 - lo * (lo + 1) / 2.0
    lo = max(a, cap)
    if b >= lo:
        n = b - lo + 1
        cycles, rest = divmod(n, pool)
        total += n * (cap + 1) + cycles * pool * (pool - 1) / 2.0 \
            + sum((lo + cycles * pool + i) % pool for i in range(rest))
    return total


def attention_flops(cfg, spans) -> float:
    """Sparse layers' attention (expanded) over the selected keys and their
    indexers over the complete blocks, for queries at positions p, (a, b)
    spans of CONTEXTS (p + 1)."""
    h = int(cfg["num_attention_heads"])
    per_key = 2.0 * h * (int(cfg["qk_nope_head_dim"])
                         + int(cfg["v_head_dim"]))
    per_block = 2.0 * int(cfg["index_n_heads"]) * int(cfg["index_head_dim"])
    total = 0.0
    for a, b in spans:
        if b >= a:
            total += per_key * _sum_selected(cfg, a - 1, b - 1) \
                + per_block * _blocks_scored(cfg, a - 1, b - 1)
    return dsa_layers(cfg) * total


def forward_flops(cfg, spans) -> float:
    tokens = sum(max(0, b - a + 1) for a, b in spans)
    n, c = int(cfg["hc_mult"]), int(cfg["hidden_size"])
    mix = mhc(cfg, tokens)["flops"] \
        - 2.0 * n * c * n * (n + 2) * 2 * layers(cfg) * tokens
    return 2.0 * matmul_params(cfg) * tokens + attention_flops(cfg, spans) \
        + state_flops_per_token(cfg) * tokens + mix


def served_flops(cfg, requests) -> float:
    """Forward operations of what a serving window computed: each prompt is
    prefilled (no prefix cache here), every later token is a decode step at
    its live context."""
    spans = []
    for plen, matched, n in requests:
        if n >= 1:
            spans += [(matched + 1, plen), (plen + 1, plen + n - 1)]
    return forward_flops(cfg, spans)


# ------------------------------------------------------------------ kernels
def kda_decode(cfg, tokens: float) -> dict:
    """The decode kernel over `tokens` decoded tokens, every linear layer."""
    h, k, _ = _lin(cfg)
    per = {"flops": 2.0 * 3 * h * k * k + h * k * k,
           "bytes": 2.0 * F32 * h * k * k + 6.0 * F32 * h * k}
    n = linear_layers(cfg) * tokens
    return {key: v * n for key, v in per.items()}


def kda_chunk(cfg, tokens: float, chunks: float) -> dict:
    """The chunk kernel over `tokens` prefilled tokens in `chunks` chunks,
    every linear layer: the three state products a token and the
    sub-chunk's own products (A and B: 2 x 16 x K a token a head each; W
    and U: 2 x 16 x (K + V)); the tokens' inputs and outputs in float32 and
    the state in and out once a chunk."""
    h, k, _ = _lin(cfg)
    sub = 16
    flops = tokens * h * (2.0 * 3 * k * k + 2.0 * sub * (4 * k))
    nbytes = tokens * F32 * h * 6 * k + chunks * 2.0 * F32 * h * k * k
    n = linear_layers(cfg)
    return {"flops": n * flops, "bytes": n * nbytes}


def dsa_index(cfg, chunks) -> dict:
    """The indexer's kernel for prefill chunks [(first position, tokens)]:
    2 J D a (query, complete block); bytes the chunk's queries, the pooled
    keys up to the chunk's end, and a float32 score a (query, block)."""
    pool = int(cfg["index_kpool"])
    ih, idim = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    wb = DTYPE_BYTES[cfg["serve"]["weight_dtype"]]
    flops = nbytes = 0.0
    for start, n in chunks:
        end = start + n
        flops += 2.0 * ih * idim * _blocks_scored(cfg, start, end - 1)
        blocks = -(-end // pool)
        nbytes += n * ih * idim * wb + blocks * idim * wb + n * blocks * F32
    return {"flops": dsa_layers(cfg) * flops,
            "bytes": dsa_layers(cfg) * nbytes}


def traced_work(cfg, facts) -> dict:
    """{work name: {"flops", "bytes"}} of a traced serving period, from
    `decode_contexts`, `ticks` and `prefill_tokens`; `dsa_index` where the
    facts hold `prefill_chunks` (layer_metrics/dsa_index_roofline.py reads
    them from the program's serving.prefill_chunk spans)."""
    if "decode_contexts" not in facts:
        return {}
    contexts = facts["decode_contexts"]
    ticks = float(facts["ticks"])
    prefilled = float(facts.get("prefill_tokens", 0))
    chunk = int(cfg["serve"]["prefill_chunk"])
    wb = DTYPE_BYTES[cfg["serve"]["weight_dtype"]]
    spans = [(c, c) for c in contexts]
    per_tick = len(contexts) / ticks if ticks else 0.0
    maps = 2 * layers(cfg) * mhc_params(cfg)
    weights = (dense_params(cfg) - maps) * wb + maps * F32 \
        + sparse_layers(cfg) * experts_touched(cfg, per_tick) \
        * expert_params(cfg) * wb
    keys = sum(selected(cfg, c - 1) for c in contexts) \
        * dsa_layers(cfg) * int(cfg["kv_lora_rank"]) * wb
    state = kda_decode(cfg, len(contexts))
    work = {
        "kda_decode": state,
        "kda_chunk": kda_chunk(cfg, prefilled, prefilled / chunk),
        "decode_step": {"flops": forward_flops(cfg, spans),
                        "bytes": ticks * weights + keys + state["bytes"]
                        + mhc(cfg, len(contexts))["bytes"]},
        "moe_experts": moe_experts(cfg, len(contexts), ticks, prefilled),
        "mhc": mhc(cfg, prefilled),
    }
    if facts.get("prefill_chunks"):
        work["dsa_index"] = dsa_index(cfg, facts["prefill_chunks"])
    return work


def train_flops_per_token(cfg, sequence) -> float:
    half = max(1, int(sequence) // 2)
    return 3.0 * forward_flops(cfg, [(half, half)])
