"""Laguna (poolside Laguna-S-2.1, config.json `model_type: laguna`): a decoder
whose layers differ in kind.

Every layer: pre-norm RMSNorm, grouped-query attention over 8 K/V heads of
128 with a sigmoid gate a query head (from the layer's normalised input) on
the attention output, no biases. By `layer_types`, a layer is FULL (YaRN
rotary over the first half of the head, every earlier key) or SLIDING (plain
rotary over the whole head, the last `sliding_window` keys), and the number
of query heads differs with the kind (`num_attention_heads_per_layer`). The
feed-forward of a layer in `mlp_only_layers` is a dense SwiGLU; every other
layer's is sparse: a router over `num_experts` experts, the
`num_experts_per_tok` largest after a float32 softmax, their weights
normalised and scaled by `moe_routed_scaling_factor`, plus one shared expert.

Built from the pieces the LLaMA decoder has (nn.RMSNorm, the parallel linear
layers, the cached and paged attention ops) and two ops of its own:
`rotary_from_positions` (no table: the config declares a million positions)
and `moe_experts`, the dropless expert layer THAT IS TOLD WHICH EXPERTS IT
HOLDS (`experts_held`): under expert parallelism a chip holds a range of the
experts, routes over all of them and computes its own experts' part.

The cache: `cache_spec()` states, per layer, full or window, and the serving
engine keeps a window layer's keys in a ring of blocks a slot
(serving/engine.py). A sparse layer also keeps, beside its cache, a count of
pairs by held expert (`LayerCacheSpec.counters`), added up on the device in
the decode step and fetched with the engine's stats.

Assumed, where the config names a mechanism and not its formula: the gate
(head-wise sigmoid gate, arXiv:2505.06708), softmax before top-k, the shared
expert ungated, silu, no q/k norm, rotate-half pairing; benchmark/models/
laguna_reference.py writes the equations out and the tests hold this file
to it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..distributed.fleet.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import api
from .generation import CacheSpec, GenerationMixin, LayerCacheSpec
from .llama import LlamaMLP

_PUBLISHED_ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1},
}


@dataclass
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288            # the dense layers' width
    num_layers: int = 48
    head_dim: int = 128
    num_key_value_heads: int = 8
    num_attention_heads_per_layer: Tuple[int, ...] = ()   # () -> 48 / 72
    layer_types: Tuple[str, ...] = ()         # () -> full, 3 x sliding, ...
    sliding_window: int = 512
    rope_parameters: dict = field(
        default_factory=lambda: {k: dict(v)
                                 for k, v in _PUBLISHED_ROPE.items()})
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-6
    mlp_only_layers: Tuple[int, ...] = (0,)
    num_experts: int = 256                    # the router's width
    experts_held: Optional[Tuple[int, int]] = None   # [lo, hi); None: all
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    moe_routed_scaling_factor: float = 2.5
    initializer_range: float = 0.02

    def __post_init__(self):
        n = self.num_layers
        if not self.layer_types:
            self.layer_types = tuple(
                "full_attention" if i % 4 == 0 else "sliding_attention"
                for i in range(n))
        if not self.num_attention_heads_per_layer:
            self.num_attention_heads_per_layer = tuple(
                48 if t == "full_attention" else 72
                for t in self.layer_types)
        self.layer_types = tuple(self.layer_types[:n])
        self.num_attention_heads_per_layer = tuple(
            int(h) for h in self.num_attention_heads_per_layer[:n])
        if len(self.layer_types) != n or \
                len(self.num_attention_heads_per_layer) != n:
            raise ValueError("layer_types and num_attention_heads_per_layer "
                             "must cover num_layers")
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        lo, hi = (int(e) for e in self.experts_held)
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.num_experts} experts")
        self.experts_held = (lo, hi)
        for h in self.num_attention_heads_per_layer:
            if h % self.num_key_value_heads:
                raise ValueError("query heads must be a multiple of "
                                 "num_key_value_heads in every layer")

    @property
    def hidden_layers_dense(self):
        return set(int(i) for i in self.mlp_only_layers)

    @staticmethod
    def tiny(**kw):
        """Every mechanism at a size the CPU tests can run: a dense layer,
        then full / sliding / sliding / full, groups of 2 and 3 query heads
        a K/V head, 8 experts with 3 a token, a window of 8."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=5, head_dim=16, num_key_value_heads=2,
            num_attention_heads_per_layer=(4, 6, 6, 6, 4),
            layer_types=("full_attention", "sliding_attention",
                         "sliding_attention", "sliding_attention",
                         "full_attention"),
            sliding_window=8, max_position_embeddings=256, num_experts=8,
            num_experts_per_tok=3, moe_intermediate_size=32,
            shared_expert_intermediate_size=32)
        base.update(kw)
        return LagunaConfig(**base)


def rope_inv_freq(rp: dict, head_dim: int):
    """(inverse frequencies as a tuple, attention factor) of one kind of
    layer: plain rotary, or YaRN's blend of extrapolated and interpolated
    frequencies between its two correction dimensions."""
    dim = int(head_dim * float(rp.get("partial_rotary_factor", 1)))
    base = float(rp["rope_theta"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp["rope_type"] == "default":
        return tuple(float(f) for f in 1.0 / pos_freqs), 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r} is not supported")
    factor = float(rp["factor"])
    orig = float(rp["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rp["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)
    return tuple(float(f) for f in inv), float(rp["attention_factor"])


def _normal(std):
    """A layer's own initialiser, unless the caller set a global one
    (nn.initializer.set_global_initializer: a loader of weights sets a
    cheap one, so that the published widths are never initialised twice)."""
    return I._global_weight_init or I.Normal(0.0, std)


def _linear(n_in, n_out, std, column=True):
    attr = nn.ParamAttr(initializer=_normal(std))
    if column:
        return ColumnParallelLinear(n_in, n_out, weight_attr=attr,
                                    has_bias=False, gather_output=False)
    return RowParallelLinear(n_in, n_out, weight_attr=attr, has_bias=False,
                             input_is_parallel=True)


class LagunaAttention(nn.Layer):
    def __init__(self, config: LagunaConfig, layer: int):
        super().__init__()
        c = config
        self.num_heads = c.num_attention_heads_per_layer[layer]
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = c.head_dim
        self.kind = c.layer_types[layer]
        self.window = (int(c.sliding_window)
                       if self.kind == "sliding_attention" else None)
        self.inv_freq, self.rope_factor = rope_inv_freq(
            c.rope_parameters[self.kind], c.head_dim)
        std = c.initializer_range
        kv = self.num_kv_heads * self.head_dim
        self.q_proj = _linear(c.hidden_size, self.num_heads * self.head_dim, std)
        self.k_proj = _linear(c.hidden_size, kv, std)
        self.v_proj = _linear(c.hidden_size, kv, std)
        self.gate_proj = _linear(c.hidden_size, self.num_heads, std)
        self.o_proj = _linear(self.num_heads * self.head_dim, c.hidden_size,
                              std / math.sqrt(2 * c.num_layers), column=False)

    def forward(self, x, positions, cache=None, pos=None):
        b, s, _ = x.shape
        hq, hkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        q = api.reshape(self.q_proj(x), [b, s, hq, d])
        k = api.reshape(self.k_proj(x), [b, s, hkv, d])
        v = api.reshape(self.v_proj(x), [b, s, hkv, d])
        gate = F.sigmoid(self.gate_proj(x))                    # [b, s, hq]
        q, k = api.rotary_from_positions(q, k, positions, self.inv_freq,
                                         self.rope_factor)
        new_cache = None
        if cache is None:
            rep = hq // hkv
            pv = positions._value
            seen = pv[:, None, :, None] >= pv[:, None, None, :]
            if self.window is not None:
                seen = seen & (pv[:, None, None, :]
                               > pv[:, None, :, None] - self.window)
            out = F.scaled_dot_product_attention(
                q, api.repeat_interleave(k, rep, axis=2),
                api.repeat_interleave(v, rep, axis=2),
                attn_mask=Tensor(seen), is_causal=False, training=False)
        elif hasattr(cache, "block_table"):
            # paged decode (serving engine): this layer's pages and its
            # kind's block table, a ring of blocks for a window layer
            out, nk, nv = api.paged_cached_attention(
                q, k, v, cache.k_pages, cache.v_pages, cache.block_table,
                cache.seq_lens, window=self.window)
            new_cache = (nk, nv)
        else:
            out, nk, nv = api.cached_multihead_attention(
                q, k, v, cache[0], cache[1], pos, window=self.window)
            new_cache = (nk, nv)
        out = out * api.unsqueeze(gate, -1)
        out = self.o_proj(api.reshape(out, [b, s, hq * d]))
        return out, new_cache


class LagunaSparseMLP(nn.Layer):
    """Router, the routed experts held here, and the shared expert."""

    def __init__(self, config: LagunaConfig):
        super().__init__()
        c = config
        std = c.initializer_range
        out_std = std / math.sqrt(2 * c.num_layers)
        lo, hi = c.experts_held
        self.expert_lo, self.held = lo, hi - lo
        self.top_k = c.num_experts_per_tok
        self.scale = float(c.moe_routed_scaling_factor)
        self.norm_topk = bool(c.norm_topk_prob)
        d, f = c.hidden_size, c.moe_intermediate_size
        self.router = _linear(d, c.num_experts, std)
        self.w13 = self.create_parameter(
            [self.held, d, 2 * f], default_initializer=_normal(std))
        self.w2 = self.create_parameter(
            [self.held, f, d], default_initializer=_normal(out_std))
        self.shared = LlamaMLP(SimpleNamespace(
            hidden_size=d,
            intermediate_size=c.shared_expert_intermediate_size))

    def forward(self, x):
        """x [b, s, hidden] -> (y, counts [held + 1])."""
        b, s, d = x.shape
        flat = api.reshape(x, [b * s, d])
        y, counts = api.moe_experts(
            flat, self.router.weight, self.w13, self.w2,
            expert_lo=self.expert_lo, top_k=self.top_k, scale=self.scale,
            norm_topk=self.norm_topk)
        with jax.named_scope("shared"):
            y = api.reshape(y, [b, s, d]) + self.shared(x)
        return y, counts


class LagunaDecoderLayer(nn.Layer):
    def __init__(self, config: LagunaConfig, layer: int):
        super().__init__()
        c = config
        self.input_layernorm = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.self_attn = LagunaAttention(c, layer)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   epsilon=c.rms_norm_eps)
        self.sparse = layer not in c.hidden_layers_dense
        if self.sparse:
            self.mlp = LagunaSparseMLP(c)
        else:
            self.mlp = LlamaMLP(SimpleNamespace(
                hidden_size=c.hidden_size,
                intermediate_size=c.intermediate_size))

    def forward(self, x, positions, cache=None, pos=None):
        # the scope says the layer's kind: h{i}/attn.full, h{i}/attn.window
        kind = "window" if self.self_attn.window is not None else "full"
        with jax.named_scope(f"attn.{kind}"):
            a, new_cache = self.self_attn(self.input_layernorm(x), positions,
                                          cache=cache, pos=pos)
            x = x + a
        m = self.post_attention_layernorm(x)
        if not self.sparse:
            with jax.named_scope("mlp"):
                return x + self.mlp(m), new_cache
        with jax.named_scope("moe"):
            y, counts = self.mlp(m)
        aux = getattr(cache, "counters", None)
        if new_cache is not None and aux is not None:
            new_cache = new_cache + (aux + counts,)
        return x + y, new_cache


class LagunaModel(nn.Layer):
    def __init__(self, config: LagunaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = nn.LayerList([LagunaDecoderLayer(config, i)
                                    for i in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, caches=None, pos=None):
        b, s = input_ids.shape
        ar = jnp.arange(s, dtype=jnp.int32)[None, :]
        if caches is None:
            start = jnp.zeros((b, 1), jnp.int32)
        elif hasattr(caches[0], "block_table"):
            sl = caches[0].seq_lens
            sl = sl._value if isinstance(sl, Tensor) else jnp.asarray(sl)
            start = sl.astype(jnp.int32)[:, None]
            pos = None
        else:
            pv = pos._value if isinstance(pos, Tensor) else jnp.asarray(pos)
            if pv.ndim:
                raise NotImplementedError(
                    f"{type(self).__name__}: a prefill batch with an offset "
                    f"a row (the engine's batched prefill) is not written; "
                    f"the engine turns it off for a model with window or "
                    f"latent layers")
            start = jnp.broadcast_to(pv.astype(jnp.int32), (b, 1))
            pos = Tensor(pv.astype(jnp.int32))
        positions = Tensor(start + ar)
        with jax.named_scope("embed"):
            h = self.open_streams(self.embed_tokens(input_ids))
        new_caches = []
        for i, layer in enumerate(self.layers):
            with jax.named_scope(f"h{i}"):
                h, nc = layer(h, positions,
                              cache=None if caches is None else caches[i],
                              pos=pos)
            new_caches.append(nc)
        with jax.named_scope("final_norm"):
            h = self.norm(self.close_streams(h))
        return h if caches is None else (h, new_caches)

    def open_streams(self, h):
        """What the layers pass on, made from the embedding: the one
        residual stream itself; models/xing.py fans it out."""
        return h

    def close_streams(self, h):
        return h


class LagunaForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, config: LagunaConfig):
        super().__init__()
        self.config = config
        self.model = LagunaModel(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            weight_attr=nn.ParamAttr(
                initializer=_normal(config.initializer_range)))

    def cache_spec(self) -> CacheSpec:
        c = self.config
        layers = []
        for i, t in enumerate(c.layer_types):
            sparse = i not in c.hidden_layers_dense
            held = c.experts_held[1] - c.experts_held[0]
            window = c.sliding_window if t == "sliding_attention" else 0
            layers.append(LayerCacheSpec(
                "window" if window else "full", c.num_key_value_heads,
                c.head_dim, window=window,
                counters=held + 1 if sparse else 0))
        return CacheSpec(tuple(layers), c.max_position_embeddings)

    def forward(self, input_ids, labels=None, caches=None, pos=None):
        if caches is not None:
            h, new_caches = self.model(input_ids, caches=caches, pos=pos)
            with jax.named_scope("lm_head"):
                return self.lm_head(h), new_caches
        logits = self.lm_head(self.model(input_ids))
        if labels is not None:
            return F.causal_lm_loss(logits, labels)
        return logits
