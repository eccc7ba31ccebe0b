"""Xing4.0 (XingChen-AGI Xing4.0-29B-A4B, config.json `model_type:
xing4_0`): GLM-MoE-Lite's latent attention and sigmoid-routed experts under a
residual path of FOUR streams, mixed at every sublayer by maps made doubly
stochastic with Sinkhorn-Knopp (manifold-constrained hyper-connections,
arXiv:2512.24880, over hyper-connections, arXiv:2409.19606).

Between layers a token's state is `hc_mult` streams of `hidden_size`, kept
contiguous: [b, s, hc_mult * hidden_size], stream j in columns [j * hidden,
(j + 1) * hidden) (a [b, s, 4, 3584] array would be padded to 8 or 16
sublanes a token on the TPU; flat it is the same bytes in the same order).
The embedding is copied into every stream; around EACH sublayer (latent
attention; the dense SwiGLU or the experts; each with its RMSNorm inside)

    u, maps = mhc_pre(X, phi, a, b)        u = H_pre X, the sublayer's input
    X'      = mhc_post(X, F(u), maps)      H_res X + H_post^T F(u)

(ops mhc_pre / mhc_post: one norm over all the streams, a product with phi,
sigmoids, `hc_sinkhorn_iters` Sinkhorn rounds on a 4 x 4 a token, all in
float32; ops/pallas/hyper_connection.py on the TPU); the last norm and the
head see the sum of the streams. benchmark/models/xing_reference.py writes
the equations out and tests/test_xing.py holds this file to it.

Attention is `GlmLatentAttention` with a value (128) narrower than a key
(192) and YaRN on the rotary part (`rope_scaling`); the feed-forward layers
are GLM-MoE-Lite's, every routed expert held (`ep_size` 1 as published) or
a chip's share (`experts_held`). The cache is GLM-MoE-Lite's latent page
kind: the streams live inside one program and the serving engine meets this
model only through `cache_spec()`.

Beside its cache a layer keeps, after a sparse layer's pairs by expert, two
int32 counters (`LayerCacheSpec.extra`): the token-applications of the mix a
decode step ran, and those whose H_res had a column sum off 1 by more than
UNBALANCED_TOL after the last round: whether the rounds converged under the
served inputs (serving_mhc_applications_total, serving_mhc_unbalanced_total).

Not served: the multi-token-prediction module (`num_nextn_predict_layers`),
as in GLM-MoE-Lite (ROADMAP M5).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
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
from ..nn import initializer as I
from ..ops import api
from ..ops.kernels.nn_ops import mhc_unpack
from .generation import CacheSpec, LayerCacheSpec
from .glm_moe_lite import GlmLatentAttention, GlmMoeLiteConfig, GlmSparseMLP
from .laguna import LagunaForCausalLM, LagunaModel, _normal
from .llama import LlamaMLP

UNBALANCED_TOL = 1e-3
MHC_COUNTERS = ("mhc_applications", "mhc_unbalanced")


def _yarn_as_published():
    return {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}


@dataclass
class Xing4Config(GlmMoeLiteConfig):
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    num_layers: int = 40
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = field(default_factory=_yarn_as_published)
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    first_k_dense_replace: int = 2
    moe_intermediate_size: int = 1024
    routed_scaling_factor: float = 2.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp: Tuple[float, float] = (-30.0, 30.0)

    def __post_init__(self):
        super().__post_init__()
        if self.hc_mult < 1 or self.hc_sinkhorn_iters < 0:
            raise ValueError("hc_mult >= 1 streams, hc_sinkhorn_iters >= 0")
        lo, hi = (float(v) for v in self.mhc_h_res_clamp)
        self.mhc_h_res_clamp = (lo, hi)

    @staticmethod
    def tiny(**kw):
        """Every mechanism at a size the CPU tests can run: a dense layer
        and four sparse ones, 4 heads, 4 streams, a value (8) narrower than
        a key (12 + 4), YaRN over 32 original positions, 8 experts with 2 a
        token."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=5, num_attention_heads=4, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
            v_head_dim=8, max_position_embeddings=256,
            rope_scaling={**_yarn_as_published(), "factor": 8,
                          "original_max_position_embeddings": 32},
            first_k_dense_replace=1, n_routed_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32)
        base.update(kw)
        return Xing4Config(**base)


class HyperConnection(nn.Layer):
    """The maps of one sublayer: phi [n (n + 2), n * hidden] (a row a map:
    H_pre's n, H_post's n, H_res's n * n row-major), the three scalars a and
    the biases b, float32 whatever the weights' dtype."""

    def __init__(self, config: Xing4Config):
        super().__init__()
        c = config
        n, width = c.hc_mult, c.hc_mult * c.hidden_size
        self.n = n
        self.kw = dict(n=n, eps=c.hc_eps, clamp=c.mhc_h_res_clamp,
                       iters=c.hc_sinkhorn_iters)
        self.phi = self.create_parameter(
            [n * (n + 2), width], dtype="float32",
            default_initializer=_normal(1.0 / math.sqrt(width)))
        self.a = self.create_parameter(
            [3], dtype="float32",
            default_initializer=I._global_weight_init or I.Constant(0.5))
        # H_res leans to the identity until trained or loaded
        self.b = self.create_parameter(
            [n * (n + 2)], dtype="float32",
            default_initializer=I._global_weight_init or I.Assign(
                jnp.concatenate([jnp.zeros(2 * n), 2.0 * jnp.eye(n).ravel()])))

    def open(self, x):
        """(u, maps): the sublayer's input and the token's maps."""
        return api.mhc_pre(x, self.phi, self.a, self.b, **self.kw)

    def close(self, x, y, maps):
        return api.mhc_post(x, y, maps, n=self.n)


def _unbalanced(n, *maps):
    """[token-applications, those whose H_res has a column sum off 1 by more
    than UNBALANCED_TOL] of the sublayers' maps, int32."""
    off = [mhc_unpack(m._value, n)[3] for m in maps]
    return jnp.stack([
        jnp.asarray(sum(o.size for o in off), jnp.int32),
        sum(jnp.sum(o > UNBALANCED_TOL, dtype=jnp.int32) for o in off)])


class Xing4DecoderLayer(nn.Layer):
    def __init__(self, config: Xing4Config, layer: int):
        super().__init__()
        c = config
        self.n = c.hc_mult
        self.hc_attn = HyperConnection(c)
        self.hc_mlp = HyperConnection(c)
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
        with jax.named_scope("hc.attn"):
            u, maps_a = self.hc_attn.open(x)
        with jax.named_scope("attn.latent"):
            y, new_cache = self.self_attn(self.input_layernorm(u), positions,
                                          cache=cache, pos=pos)
        with jax.named_scope("hc.attn"):
            x = self.hc_attn.close(x, y, maps_a)
        with jax.named_scope("hc.mlp"):
            u, maps_m = self.hc_mlp.open(x)
        m = self.post_attention_layernorm(u)
        counts = []
        if self.sparse:
            with jax.named_scope("moe"):
                y, pairs = self.mlp(m)
            counts.append(pairs._value)
        else:
            with jax.named_scope("mlp"):
                y = self.mlp(m)
        with jax.named_scope("hc.mlp"):
            x = self.hc_mlp.close(x, y, maps_m)
        aux = getattr(cache, "counters", None)
        if new_cache is not None and aux is not None:
            counts.append(_unbalanced(self.n, maps_a, maps_m))
            new_cache = new_cache + (aux + Tensor(jnp.concatenate(counts)),)
        return x, new_cache


class Xing4Model(LagunaModel):
    """Embedding, the layers and the last norm; the forward is
    LagunaModel's, between its two hooks: the embedding into every stream,
    the streams' sum to the last norm."""

    def __init__(self, config: Xing4Config):
        nn.Layer.__init__(self)
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = nn.LayerList([Xing4DecoderLayer(config, i)
                                    for i in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def open_streams(self, h):
        return api.tile(h, [1, 1, self.config.hc_mult])

    def close_streams(self, h):
        b, s, _ = h.shape
        n = self.config.hc_mult
        return api.sum(api.reshape(h, [b, s, n, -1]), axis=2)


class Xing4ForCausalLM(LagunaForCausalLM):
    """The model and its untied head; logits, loss and the cached forward
    are LagunaForCausalLM's."""

    def __init__(self, config: Xing4Config):
        nn.Layer.__init__(self)
        self.config = config
        self.model = Xing4Model(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            weight_attr=nn.ParamAttr(
                initializer=_normal(config.initializer_range)))

    def cache_spec(self) -> CacheSpec:
        c = self.config
        held = c.experts_held[1] - c.experts_held[0]
        return CacheSpec(tuple(
            LayerCacheSpec("latent", 1, c.cache_row_width,
                           counters=(held + 1) * (i >= c.first_k_dense_replace)
                           + len(MHC_COUNTERS), extra=MHC_COUNTERS)
            for i in range(c.num_layers)), c.max_position_embeddings)
