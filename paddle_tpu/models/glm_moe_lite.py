"""GLM-MoE-Lite (zai-org GLM-4.7-Flash, config.json `model_type:
glm4_moe_lite`): a decoder with multi-head LATENT attention and sparse
experts routed by sigmoid scores under a selection bias.

Every layer: pre-norm RMSNorm, no biases. Attention compresses the queries
through a `q_lora_rank` bottleneck (with a norm) and the keys and values
through ONE `kv_lora_rank` latent a token (with a norm) beside ONE rotary
key of `qk_rope_head_dim` shared by all heads; `kv_b_proj` expands the
latent into a head's `qk_nope_head_dim` key part and its `v_head_dim` value.
A head's score is q_nope . k_nope + q_rope . r over sqrt(nope + rope).

What the cache keeps a token is the latent and the rotary key, after the
norm and the rotation: `kv_lora_rank + qk_rope_head_dim` values, no V
(`cache_spec()`: kind "latent"; a row is padded with zeros to whole lanes of
128, `cache_row_width`, which is what the device stores anyway). Over a
cache, attention is ABSORBED: the query goes through the key half of
`kv_b_proj` into the latent space (q' = q_nope W_uk^T), scores and the
weighted sum are over the cached rows themselves (ops latent_paged_attention
/ latent_cached_attention: all heads share the one row, fetched once), and
the value half of `kv_b_proj` takes the sum out (o = o' W_uv). Without a
cache the forward is the EXPANDED form as published; the two agree to
rounding and the tests hold them to it. Prefill too stays in the latent
space: alone on the chip a chunk of 1,024 at a context of 32k took 8.6 ms
absorbed against 9.4-9.8 ms expanded through `kv_b_proj` again for every
chunk (PERF.md section 6, PR 34), so the expanded form is not on any cached
path.

Layers before `first_k_dense_replace` have a dense SwiGLU; the others a
router over `n_routed_experts`: sigmoid scores in float32, the
`num_experts_per_tok` largest of score + `e_score_correction_bias` (a
buffer: it enters the CHOICE, not the weight), weights the chosen scores
normalised and times `routed_scaling_factor`, plus one ungated shared
SwiGLU. `n_group` = `topk_group` = 1 is asserted: group-limited routing is
then the identity and is not written. Experts run through `moe_experts`,
told which they hold (`experts_held`), as Laguna's do.

Not served: the multi-token-prediction module (`num_nextn_predict_layers`),
a draft head for self-speculation (ROADMAP M5).

A value may be narrower than a key (`v_head_dim` < nope + rope; Xing4.0,
models/xing.py): over a cache nothing changes (the value is the latent), the
no-cache forward pads it with zeros for its one attention op. `rope_scaling`
(YaRN) blends the rotary part's frequencies and scales the softmax
(`GlmMoeLiteConfig.rotary`).

Assumed, where the config names a mechanism and not its formula: rotate-half
pairing over all `qk_rope_head_dim` dimensions;
benchmark/models/glm_moe_lite_reference.py writes the equations out and the
tests hold this file to it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..distributed.fleet.mp_layers import (
    ColumnParallelLinear,
    VocabParallelEmbedding,
)
from ..nn import functional as F
from ..ops import api
from .generation import CacheSpec, LayerCacheSpec
from .laguna import (LagunaForCausalLM, LagunaModel, _linear, _normal,
                     rope_inv_freq)
from .llama import LlamaMLP


@dataclass
class GlmMoeLiteConfig:
    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240            # the dense layers' width
    num_layers: int = 47
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1000000.0
    rope_scaling: Optional[dict] = None       # None, or YaRN's (see rotary())
    max_position_embeddings: int = 202752
    rms_norm_eps: float = 1e-5
    first_k_dense_replace: int = 1
    n_routed_experts: int = 64                # the router's width
    experts_held: Optional[Tuple[int, int]] = None   # [lo, hi); None: all
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    n_group: int = 1
    topk_group: int = 1
    initializer_range: float = 0.02

    def __post_init__(self):
        if (self.n_group, self.topk_group) != (1, 1):
            raise ValueError(
                "GlmMoeLiteConfig: n_group = topk_group = 1 only: "
                "group-limited routing is then the identity and is not "
                "written")
        if self.experts_held is None:
            self.experts_held = (0, self.n_routed_experts)
        lo, hi = (int(e) for e in self.experts_held)
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.n_routed_experts} experts")
        self.experts_held = (lo, hi)
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        if not 0 < self.v_head_dim <= (self.qk_nope_head_dim
                                       + self.qk_rope_head_dim):
            raise ValueError("v_head_dim: at most a key's width (the "
                             "no-cache forward pads a value to it)")

    @property
    def latent_width(self) -> int:
        """What the cache keeps a token a layer: the latent and the rotary
        key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row_width(self) -> int:
        """The width of a cache row: latent_width in whole lanes of 128,
        the rest zeros. The TPU pads a narrower last dimension in HBM to
        that anyway, and a kernel's copy of a page takes whole tiles (a
        576-wide slice of the padded array is refused by the compiler)."""
        return -(-self.latent_width // 128) * 128

    def rotary(self):
        """(inverse frequencies of the rotary part, the factor on cos and
        sin, the softmax scale). Plain rotary, or under `rope_scaling`
        {"type": "yarn", "factor", "original_max_position_embeddings",
        "beta_fast", "beta_slow", "mscale", "mscale_all_dim"} YaRN's
        blended frequencies (laguna.rope_inv_freq), cos and sin times
        m(mscale) / m(mscale_all_dim) and the scale times m(mscale_all_dim)^2,
        m(s) = 0.1 s ln(factor) + 1."""
        plain = 1.0 / math.sqrt(self.qk_nope_head_dim + self.qk_rope_head_dim)
        rs = self.rope_scaling
        if not rs:
            inv, _ = rope_inv_freq({"rope_theta": self.rope_theta,
                                    "rope_type": "default"},
                                   self.qk_rope_head_dim)
            return inv, 1.0, plain
        if rs["type"] != "yarn":
            raise ValueError(f"rope_scaling type {rs['type']!r} is not "
                             f"supported")
        m = lambda s: 0.1 * float(s) * math.log(float(rs["factor"])) + 1.0 \
            if float(rs["factor"]) > 1 else 1.0             # noqa: E731
        m_all = m(rs["mscale_all_dim"])
        inv, factor = rope_inv_freq(
            {**rs, "rope_theta": self.rope_theta, "rope_type": "yarn",
             "attention_factor": m(rs["mscale"]) / m_all},
            self.qk_rope_head_dim)
        return inv, factor, m_all * m_all * plain

    @staticmethod
    def tiny(**kw):
        """Every mechanism at a size the CPU tests can run: a dense layer
        and four sparse ones, 4 heads, ranks 24 / 16, 8 experts with 2 a
        token."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=5, num_attention_heads=4, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
            v_head_dim=16, max_position_embeddings=256, n_routed_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32)
        base.update(kw)
        return GlmMoeLiteConfig(**base)


class GlmLatentAttention(nn.Layer):
    def __init__(self, config: GlmMoeLiteConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_attention_heads
        self.nope, self.rope, self.v_dim = \
            c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        self.rank = c.kv_lora_rank
        self.row_pad = c.cache_row_width - c.latent_width
        self.inv_freq, self.rope_factor, self.scale = c.rotary()
        std, h = c.initializer_range, self.num_heads
        self.q_a_proj = _linear(c.hidden_size, c.q_lora_rank, std)
        self.q_a_layernorm = nn.RMSNorm(c.q_lora_rank, epsilon=c.rms_norm_eps)
        self.q_b_proj = _linear(c.q_lora_rank, h * (self.nope + self.rope),
                                std)
        self.kv_a_proj = _linear(c.hidden_size, self.rank + self.rope, std)
        self.kv_a_layernorm = nn.RMSNorm(self.rank, epsilon=c.rms_norm_eps)
        self.kv_b_proj = _linear(self.rank, h * (self.nope + self.v_dim), std)
        self.o_proj = _linear(h * self.v_dim, c.hidden_size,
                              std / math.sqrt(2 * c.num_layers), column=False)

    def forward(self, x, positions, cache=None, pos=None):
        b, s, _ = x.shape
        h, nope, rope, vd, rank = (self.num_heads, self.nope, self.rope,
                                   self.v_dim, self.rank)
        q = api.reshape(self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x))),
                        [b, s, h, nope + rope])
        kv = self.kv_a_proj(x)
        c = self.kv_a_layernorm(kv[:, :, :rank])                # [b, s, rank]
        q_rope, r = api.rotary_from_positions(
            q[:, :, :, nope:], api.unsqueeze(kv[:, :, rank:], 2), positions,
            self.inv_freq, factor=self.rope_factor)
        q_nope = q[:, :, :, :nope]
        # kv_b_proj by head: the key half W_uk and the value half W_uv
        w_kvb = api.reshape(self.kv_b_proj.weight, [rank, h, nope + vd])
        new_cache = None
        if cache is None:
            # the published, expanded form
            kvx = api.reshape(self.kv_b_proj(c), [b, s, h, nope + vd])
            k = api.concat([kvx[:, :, :, :nope],
                            api.expand(r, [b, s, h, rope])], axis=-1)
            v = kvx[:, :, :, nope:]
            if vd < nope + rope:    # one attention op: a value as wide as
                v = api.concat(     # a key, the rest zeros
                    [v, api.zeros([b, s, h, nope + rope - vd], v.dtype)],
                    axis=-1)
            out = F.scaled_dot_product_attention(
                api.concat([q_nope, q_rope], axis=-1), k, v,
                is_causal=True, training=False, scale=self.scale)
            out = out[:, :, :, :vd]
        else:
            with jax.named_scope("absorb"):
                q_lat = api.einsum("bshn,rhn->bshr", q_nope,
                                   w_kvb[:, :, :nope])
                # a cache row: [latent, rotary key, zeros to whole lanes]
                q_abs = api.concat(
                    [q_lat, q_rope,
                     api.zeros([b, s, h, self.row_pad], q.dtype)], axis=-1)
                new = api.concat(
                    [c, r[:, :, 0],
                     api.zeros([b, s, self.row_pad], c.dtype)], axis=-1)
            if hasattr(cache, "block_table"):
                o_lat, pages = api.latent_paged_attention(
                    q_abs, new, cache.k_pages, cache.block_table,
                    cache.seq_lens, v_dim=rank, scale=self.scale)
            else:
                o_lat, pages = api.latent_cached_attention(
                    q_abs, new, cache[0], pos, v_dim=rank, scale=self.scale)
            new_cache = (pages,)
            with jax.named_scope("absorb"):
                out = api.einsum("bshr,rhv->bshv", o_lat, w_kvb[:, :, nope:])
        return self.o_proj(api.reshape(out, [b, s, h * vd])), new_cache


class GlmSparseMLP(nn.Layer):
    """Router with its selection bias, the routed experts held here, and the
    shared expert."""

    def __init__(self, config: GlmMoeLiteConfig):
        super().__init__()
        c = config
        std = c.initializer_range
        out_std = std / math.sqrt(2 * c.num_layers)
        lo, hi = c.experts_held
        self.expert_lo, self.held = lo, hi - lo
        self.top_k = c.num_experts_per_tok
        self.scale = float(c.routed_scaling_factor)
        self.norm_topk = bool(c.norm_topk_prob)
        d, f = c.hidden_size, c.moe_intermediate_size
        self.router = _linear(d, c.n_routed_experts, std)
        self.register_buffer(
            "e_score_correction_bias",
            Tensor(jnp.zeros((c.n_routed_experts,), jnp.float32)))
        self.w13 = self.create_parameter(
            [self.held, d, 2 * f], default_initializer=_normal(std))
        self.w2 = self.create_parameter(
            [self.held, f, d], default_initializer=_normal(out_std))
        self.shared = LlamaMLP(SimpleNamespace(
            hidden_size=d, intermediate_size=f * c.n_shared_experts))

    def forward(self, x):
        """x [b, s, hidden] -> (y, counts [held + 1])."""
        b, s, d = x.shape
        y, counts = api.moe_experts(
            api.reshape(x, [b * s, d]), self.router.weight, self.w13,
            self.w2, expert_lo=self.expert_lo, top_k=self.top_k,
            scale=self.scale, norm_topk=self.norm_topk, scoring="sigmoid",
            select_bias=self.e_score_correction_bias)
        with jax.named_scope("shared"):
            y = api.reshape(y, [b, s, d]) + self.shared(x)
        return y, counts


class GlmDecoderLayer(nn.Layer):
    def __init__(self, config: GlmMoeLiteConfig, layer: int):
        super().__init__()
        c = config
        self.input_layernorm = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.self_attn = GlmLatentAttention(c)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   epsilon=c.rms_norm_eps)
        self.sparse = layer >= c.first_k_dense_replace
        if self.sparse:
            self.mlp = GlmSparseMLP(c)
        else:
            self.mlp = LlamaMLP(SimpleNamespace(
                hidden_size=c.hidden_size,
                intermediate_size=c.intermediate_size))

    def forward(self, x, positions, cache=None, pos=None):
        with jax.named_scope("attn.latent"):
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


class GlmMoeLiteModel(LagunaModel):
    """Embedding, the layers and the last norm; the forward (positions from
    the cache's lengths or the offset, the layers in their scopes) is
    LagunaModel's."""

    def __init__(self, config: GlmMoeLiteConfig):
        nn.Layer.__init__(self)
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = nn.LayerList([GlmDecoderLayer(config, i)
                                    for i in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)


class GlmMoeLiteForCausalLM(LagunaForCausalLM):
    """The model and its untied head; logits, loss and the cached forward
    are LagunaForCausalLM's."""

    def __init__(self, config: GlmMoeLiteConfig):
        nn.Layer.__init__(self)
        self.config = config
        self.model = GlmMoeLiteModel(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            weight_attr=nn.ParamAttr(
                initializer=_normal(config.initializer_range)))

    def cache_spec(self) -> CacheSpec:
        c = self.config
        held = c.experts_held[1] - c.experts_held[0]
        return CacheSpec(tuple(
            LayerCacheSpec("latent", 1, c.cache_row_width,
                           counters=held + 1
                           if i >= c.first_k_dense_replace else 0)
            for i in range(c.num_layers)), c.max_position_embeddings)
