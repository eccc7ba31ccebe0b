"""LLaMA family (LLaMA-2 70B-class, sharding stage 3).

Reference analog: the llama models driven through Paddle's fleet/DistTensor
examples (semi-auto LLaMA in python/paddle/distributed/auto_parallel docs,
fused rope/rms_norm ops at python/paddle/incubate/nn/functional/
fused_rotary_position_embedding.py, rms_norm.py).

TPU-first: pre-norm RMSNorm + SwiGLU + rotary, grouped-query attention
(num_key_value_heads < num_heads repeats K/V — keeps KV cache and HBM traffic
small), bf16-friendly throughout, attention via the Pallas flash kernel path
of F.scaled_dot_product_attention. TP = Column/Row/Vocab parallel shardings;
long context composes with the 'sep' mesh axis (distributed/context_parallel).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..distributed.fleet.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..nn import functional as F
from ..ops import api
from .generation import GenerationMixin, uniform_cache_spec


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_key_value_heads: int = 0  # 0 -> num_heads (MHA); < num_heads -> GQA
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    recompute: bool = False           # activation checkpointing per decoder layer
    recompute_policy: str = None      # jax.checkpoint policy name (None=full)

    def __post_init__(self):
        if not self.num_key_value_heads:
            self.num_key_value_heads = self.num_heads

    @staticmethod
    def llama2_7b():
        return LlamaConfig()

    @staticmethod
    def llama2_70b():
        return LlamaConfig(hidden_size=8192, intermediate_size=28672,
                           num_layers=80, num_heads=64, num_key_value_heads=8)

    @staticmethod
    def tiny():
        return LlamaConfig(vocab_size=512, hidden_size=128, intermediate_size=256,
                           num_layers=2, num_heads=4, num_key_value_heads=2,
                           max_position_embeddings=128)


def _rope_tables(head_dim, max_len, theta, dtype=jnp.float32):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # [S, D/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return Tensor(jnp.cos(emb).astype(dtype)), Tensor(jnp.sin(emb).astype(dtype))


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_heads
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = c.hidden_size // c.num_heads
        self.q_proj = ColumnParallelLinear(c.hidden_size, c.num_heads * self.head_dim,
                                           has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(c.hidden_size, self.num_kv_heads * self.head_dim,
                                           has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(c.hidden_size, self.num_kv_heads * self.head_dim,
                                           has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(c.num_heads * self.head_dim, c.hidden_size,
                                        has_bias=False, input_is_parallel=True)

    def forward(self, x, rope, cache=None, pos=None, segments=None):
        b, s, h = x.shape
        q = api.reshape(self.q_proj(x), [b, s, self.num_heads, self.head_dim])
        k = api.reshape(self.k_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        v = api.reshape(self.v_proj(x), [b, s, self.num_kv_heads, self.head_dim])
        if len(rope) == 3:  # packed: (cos_table, sin_table, pos2d)
            q, k = api.rotary_position_embedding_packed(
                q, k, rope[0], rope[1], rope[2])
        else:
            q, k = api.rotary_position_embedding(q, k, rope[0], rope[1])
        if cache is not None:
            if hasattr(cache, "block_table"):
                # paged decode (serving engine): KV in fixed-size blocks,
                # ragged per-slot lengths; GQA pages keep unrepeated kv heads
                out, new_k, new_v = api.paged_cached_attention(
                    q, k, v, cache.k_pages, cache.v_pages,
                    cache.block_table, cache.seq_lens)
                out = api.reshape(out, [b, s, self.num_heads * self.head_dim])
                return self.o_proj(out), (new_k, new_v)
            # GQA caches keep the UNREPEATED kv heads (HBM = kv_heads/d of
            # MHA); the cached op broadcasts per q-head group at compute time
            out, new_k, new_v = api.cached_multihead_attention(
                q, k, v, cache[0], cache[1], pos)
            out = api.reshape(out, [b, s, self.num_heads * self.head_dim])
            return self.o_proj(out), (new_k, new_v)
        if self.num_kv_heads != self.num_heads:
            rep = self.num_heads // self.num_kv_heads
            k = api.repeat_interleave(k, rep, axis=2)
            v = api.repeat_interleave(v, rep, axis=2)
        if segments is not None:
            # packed-document path (varlen pretrain): attention restricted
            # to each document, causally
            out = api.segmented_attention(q, k, v, segments, causal=True)
        else:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        out = api.reshape(out, [b, s, self.num_heads * self.head_dim])
        return self.o_proj(out)


class LlamaMLP(nn.Layer):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.gate_proj = ColumnParallelLinear(c.hidden_size, c.intermediate_size,
                                              has_bias=False, gather_output=False)
        self.up_proj = ColumnParallelLinear(c.hidden_size, c.intermediate_size,
                                            has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(c.intermediate_size, c.hidden_size,
                                           has_bias=False, input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, rope, cache=None, pos=None, segments=None):
        if cache is not None:
            a, new_cache = self.self_attn(self.input_layernorm(x), rope,
                                          cache=cache, pos=pos)
            x = x + a
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x, new_cache
        x = x + self.self_attn(self.input_layernorm(x), rope,
                               segments=segments)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size, config.hidden_size)
        self.layers = nn.LayerList([LlamaDecoderLayer(config)
                                    for _ in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        head_dim = config.hidden_size // config.num_heads
        self._rope = _rope_tables(head_dim, config.max_position_embeddings,
                                  config.rope_theta)

    def forward(self, input_ids, caches=None, pos=None, segments=None):
        s = input_ids.shape[1]
        if caches is not None:
            if segments is not None:
                raise NotImplementedError(
                    "packed (segments=) batches are not supported with "
                    "KV-cache decoding")
            from jax import lax

            if hasattr(caches[0], "block_table"):
                # paged decode: per-slot positions via the packed-rope form;
                # s > 1 is the speculative verify window at seq_lens..+s-1
                pos_v = caches[0].seq_lens
                pos_v = (pos_v._value if isinstance(pos_v, Tensor)
                         else jnp.asarray(pos_v)).astype(jnp.int32)
                s = input_ids.shape[1]
                pos2d = pos_v[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
                rope = (self._rope[0], self._rope[1], Tensor(pos2d))
                h = self.embed_tokens(input_ids)
                new_caches = []
                for layer, cache in zip(self.layers, caches):
                    h, nc = layer(h, rope, cache=cache, pos=None)
                    new_caches.append(nc)
                return self.norm(h), new_caches
            pos_v = pos._value if isinstance(pos, Tensor) else jnp.asarray(pos)
            pos_v = pos_v.astype(jnp.int32)
            if pos_v.ndim == 1 and pos_v.shape[0] == input_ids.shape[0]:
                # ragged batched prefill (serving engine): per-row offsets
                # via the packed-rope form; cached attention takes the
                # offset vector
                pos2d = pos_v[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
                rope = (self._rope[0], self._rope[1], Tensor(pos2d))
                h = self.embed_tokens(input_ids)
                new_caches = []
                for layer, cache in zip(self.layers, caches):
                    h, nc = layer(h, rope, cache=cache, pos=Tensor(pos_v))
                    new_caches.append(nc)
                return self.norm(h), new_caches
            pos_v = pos_v.reshape(())
            d = self._rope[0].shape[-1]
            cos = Tensor(lax.dynamic_slice(self._rope[0]._value,
                                           (pos_v, 0), (s, d)))
            sin = Tensor(lax.dynamic_slice(self._rope[1]._value,
                                           (pos_v, 0), (s, d)))
            h = self.embed_tokens(input_ids)
            new_caches = []
            for layer, cache in zip(self.layers, caches):
                h, nc = layer(h, (cos, sin), cache=cache, pos=Tensor(pos_v))
                new_caches.append(nc)
            return self.norm(h), new_caches
        if segments is not None:
            # per-document positions (restart at each packed doc) drive a
            # per-token rope gather -> [b, s, 1, d] broadcast layout
            from .generation import packed_positions

            seg_v = (segments._value if isinstance(segments, Tensor)
                     else jnp.asarray(segments)).astype(jnp.int32)
            pos2d = packed_positions(seg_v, s)
            # slice tables to s (positions are < s): smaller in-kernel
            # lookup and it keeps long-context configs on the kernel path
            rope = (Tensor(self._rope[0]._value[:s]),
                    Tensor(self._rope[1]._value[:s]), Tensor(pos2d))
        else:
            rope = (Tensor(self._rope[0]._value[:s]),
                    Tensor(self._rope[1]._value[:s]))
        h = self.embed_tokens(input_ids)
        for layer in self.layers:
            if self.config.recompute and self.training:
                from ..distributed.fleet.recompute import recompute

                h = recompute(layer, h, rope, segments=segments,
                              policy=self.config.recompute_policy)
            else:
                h = layer(h, rope, segments=segments)
        return self.norm(h)


class LlamaForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = ColumnParallelLinear(config.hidden_size, config.vocab_size,
                                                has_bias=False)

    def cache_spec(self):
        c = self.config
        return uniform_cache_spec(c.num_layers, c.num_key_value_heads,
                                  c.hidden_size // c.num_heads,
                                  c.max_position_embeddings)

    def _head(self, h):
        if self.lm_head is None:
            return api.matmul(h, api.t(self.model.embed_tokens.weight))
        return self.lm_head(h)

    def forward(self, input_ids, labels=None, caches=None, pos=None,
                segments=None):
        """segments: optional [b, s] packed-document ids (padding -1);
        the shifted loss masks pairs that would cross a document
        boundary."""
        if caches is not None:
            if segments is not None:
                raise NotImplementedError(
                    "packed (segments=) batches are not supported with "
                    "KV-cache decoding")
            h, new_caches = self.model(input_ids, caches=caches, pos=pos)
            return self._head(h), new_caches
        h = self.model(input_ids, segments=segments)
        logits = self._head(h)
        if labels is not None:
            return F.causal_lm_loss(logits, labels, segments)
        return logits


# --------------------------------------------------- pipeline decomposition
class _LlamaPipeBlock(nn.Layer):
    """LlamaDecoderLayer with its own rope tables so the stage is
    self-contained (cos/sin recomputed per stage — position-only)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.block = LlamaDecoderLayer(config)
        head_dim = config.hidden_size // config.num_heads
        self._rope = _rope_tables(head_dim, config.max_position_embeddings,
                                  config.rope_theta)

    def forward(self, h):
        s = h.shape[1]
        cos = Tensor(self._rope[0]._value[:s])
        sin = Tensor(self._rope[1]._value[:s])
        return self.block(h, (cos, sin))


class _LlamaPipeEmbed(nn.Layer):
    """Stage-0 pre: token embedding; also holds the final RMSNorm the
    (tied) head applies, keeping middle stages homogeneous."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.embed = VocabParallelEmbedding(config.vocab_size,
                                            config.hidden_size)
        if config.tie_word_embeddings:
            # final norm applied by the tied head; untied configs keep it
            # in their own head stage
            self.norm = nn.RMSNorm(config.hidden_size,
                                   epsilon=config.rms_norm_eps)

    @property
    def weight(self):
        return self.embed.weight

    def forward(self, ids):
        return self.embed(ids)


class _LlamaPipeHead(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        self.proj = ColumnParallelLinear(config.hidden_size,
                                         config.vocab_size, has_bias=False)

    @property
    def weight(self):
        return self.proj.weight

    def forward(self, h):
        return self.proj(self.norm(h))


def _llama_tied_head_fwd(layer, h):
    return api.matmul(layer.norm(h), api.t(layer.embed.weight))


def _llama_untied_head_fwd(layer, h):
    return layer(h)


def _llama_pipeline_loss(out, label):
    return F.causal_lm_loss(out, label)


def _llama_pipeline_descs(self):
    """LayerDesc decomposition (see GPTForCausalLM.pipeline_descs).
    Returns (descs, loss_fn, copy_weights)."""
    from ..distributed.fleet.pipeline_parallel import (
        LayerDesc, SharedLayerDesc)

    cfg = self.config
    descs = [SharedLayerDesc("embed", _LlamaPipeEmbed, None, "weight", cfg)]
    descs += [LayerDesc(_LlamaPipeBlock, cfg)
              for _ in range(cfg.num_layers)]
    if cfg.tie_word_embeddings:
        descs.append(SharedLayerDesc("embed", _LlamaPipeEmbed,
                                     _llama_tied_head_fwd, "weight", cfg))
    else:
        descs.append(SharedLayerDesc("head", _LlamaPipeHead,
                                     _llama_untied_head_fwd, "weight", cfg))

    model = self

    def copy_weights(pl, reverse=False):
        """model -> pipeline (default) or pipeline -> model (reverse)."""
        pre = pl.shared_pre
        pairs = [(model.model.embed_tokens.weight, pre.embed.weight)]
        if cfg.tie_word_embeddings:
            pairs.append((model.model.norm.weight, pre.norm.weight))
        for src_l, dst in zip(model.model.layers, pl.run_function):
            pairs += list(zip(src_l.parameters(), dst.block.parameters()))
        if not cfg.tie_word_embeddings:
            head = pl.shared_post[0]
            pairs += [(model.model.norm.weight, head.norm.weight),
                      (model.lm_head.weight, head.proj.weight)]
        for m_p, p_p in pairs:
            assert tuple(m_p.shape) == tuple(p_p.shape)
            if reverse:
                m_p._value = p_p._value
            else:
                p_p._value = m_p._value

    return descs, _llama_pipeline_loss, copy_weights


LlamaForCausalLM.pipeline_descs = _llama_pipeline_descs
