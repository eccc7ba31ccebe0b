"""GPT family — the flagship model (GPT-3 1.3B hybrid-parallel).

Reference model zoo analog: the fleetx/gpt models used by Fleet hybrid
examples (hybrid_parallel_pp_amp.py payloads, fused_multi_transformer ops in
paddle/fluid/operators/fused/).

TPU-first design decisions:
  * pre-LN transformer, bf16-friendly (fp32 softmax/norm statistics inside
    the kernels);
  * attention lowers to the Pallas flash kernel on TPU (ops/pallas), else the
    jnp reference path;
  * TP is expressed as weight shardings (Column/Row/VocabParallel layers) —
    GSPMD inserts the collectives; the same module runs single-chip unchanged;
  * rotary or learned positions; weight-tied LM head.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax

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
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 0  # 0 -> 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    use_rotary: bool = False
    tie_word_embeddings: bool = True
    recompute: bool = False           # activation checkpointing per block
    recompute_policy: str = None      # jax.checkpoint policy name (None=full)
    sequence_parallel: str = None     # None | 'ring' | 'ulysses': attention
                                      # over the 'sep' mesh axis (long context)
    sep_axis: str = "sep"

    def __post_init__(self):
        if not self.intermediate_size:
            self.intermediate_size = 4 * self.hidden_size

    @staticmethod
    def gpt3_1p3b():
        return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                         max_position_embeddings=2048)

    @staticmethod
    def tiny():
        return GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                         num_heads=4, max_position_embeddings=256,
                         hidden_dropout_prob=0.0, attention_dropout_prob=0.0)


class CausalSelfAttention(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_heads
        self.head_dim = c.hidden_size // c.num_heads
        self.hidden_size = c.hidden_size
        self.qkv_proj = ColumnParallelLinear(c.hidden_size, 3 * c.hidden_size, gather_output=False)
        self.out_proj = RowParallelLinear(c.hidden_size, c.hidden_size, input_is_parallel=True)
        self.attn_dropout_p = c.attention_dropout_prob
        self.resid_dropout = nn.Dropout(c.hidden_dropout_prob)
        self.sequence_parallel = c.sequence_parallel
        self.sep_axis = c.sep_axis
        if c.sequence_parallel and c.sequence_parallel not in ("ring", "ulysses"):
            raise ValueError(
                f"GPTConfig.sequence_parallel must be None, 'ring' or "
                f"'ulysses', got {c.sequence_parallel!r}")
        if c.sequence_parallel and c.attention_dropout_prob:
            raise ValueError(
                "attention dropout is not supported under context "
                "parallelism (the ring/Ulysses kernels are deterministic); "
                "set attention_dropout_prob=0")

    def forward(self, x, rope=None, cache=None, pos=None, segments=None):
        b, s, h = x.shape
        qkv = self.qkv_proj(x)
        qkv = api.reshape(qkv, [b, s, self.num_heads, 3 * self.head_dim])
        q, k, v = api.split(qkv, 3, axis=-1)
        if rope is not None:
            if len(rope) == 3:  # packed: (cos_table, sin_table, pos2d)
                q, k = api.rotary_position_embedding_packed(
                    q, k, rope[0], rope[1], rope[2])
            else:
                q, k = api.rotary_position_embedding(q, k, rope[0], rope[1])
        if cache is not None:
            if self.sequence_parallel:
                raise NotImplementedError(
                    "KV-cache decoding under sequence_parallel is not "
                    "supported; gather the sequence (sequence_parallel=None) "
                    "for generation")
            if hasattr(cache, "block_table"):
                # paged decode (serving engine): one query token per slot,
                # KV scattered across fixed-size blocks; ragged per-slot
                # lengths live in the cache view (ops paged_cached_attention)
                out, new_k, new_v = api.paged_cached_attention(
                    q, k, v, cache.k_pages, cache.v_pages,
                    cache.block_table, cache.seq_lens)
                out = api.reshape(out, [b, s, h])
                return self.resid_dropout(self.out_proj(out)), (new_k, new_v)
            # decode path: static-shape KV ring updated in place, causal
            # masking against the absolute position (models/generation.py)
            out, new_k, new_v = api.cached_multihead_attention(
                q, k, v, cache[0], cache[1], pos)
            out = api.reshape(out, [b, s, h])
            return self.resid_dropout(self.out_proj(out)), (new_k, new_v)
        if segments is not None:
            if self.sequence_parallel:
                raise NotImplementedError(
                    "packed (segments=) batches are not supported under "
                    "sequence_parallel; gather the sequence first")
            # packed-document path: attention restricted to each document
            # (native pack_varlen batches; varlen flash kernel on TPU)
            out = api.segmented_attention(q, k, v, segments, causal=True)
        elif self.sequence_parallel:
            # long-context path: sequence sharded over the 'sep' mesh axis,
            # ring/Ulysses attention as one registered op (context_parallel)
            out = api.sequence_parallel_attention(
                q, k, v, axis_name=self.sep_axis,
                mode=self.sequence_parallel, causal=True)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True,
                dropout_p=self.attn_dropout_p if self.training else 0.0,
                training=self.training,
            )
        out = api.reshape(out, [b, s, h])
        return self.resid_dropout(self.out_proj(out))


class GPTMLP(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.fc_in = ColumnParallelLinear(config.hidden_size, config.intermediate_size, gather_output=False)
        self.fc_out = RowParallelLinear(config.intermediate_size, config.hidden_size, input_is_parallel=True)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, x):
        return self.dropout(self.fc_out(F.gelu(self.fc_in(x), approximate=True)))


class GPTBlock(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = nn.LayerNorm(config.hidden_size)
        self.attn = CausalSelfAttention(config)
        self.ln_2 = nn.LayerNorm(config.hidden_size)
        self.mlp = GPTMLP(config)

    def forward(self, x, rope=None, cache=None, pos=None, segments=None):
        # named scopes reach op_name in the HLO and the device trace; the
        # caller's scope (GPTModel: h{i}) is the layer
        if cache is not None:
            with jax.named_scope("attn"):
                a, new_cache = self.attn(self.ln_1(x), rope=rope,
                                         cache=cache, pos=pos)
                x = x + a
            with jax.named_scope("mlp"):
                x = x + self.mlp(self.ln_2(x))
            return x, new_cache
        with jax.named_scope("attn"):
            x = x + self.attn(self.ln_1(x), rope=rope, segments=segments)
        with jax.named_scope("mlp"):
            x = x + self.mlp(self.ln_2(x))
        return x


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.wte = VocabParallelEmbedding(config.vocab_size, config.hidden_size)
        if not config.use_rotary:
            self.wpe = nn.Embedding(config.max_position_embeddings, config.hidden_size)
        self.drop = nn.Dropout(config.hidden_dropout_prob)
        self.blocks = nn.LayerList([GPTBlock(config) for _ in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size)
        self._rope_cache = None

    def _rope(self, seq_len):
        if self.config.use_rotary:
            import jax.numpy as jnp

            import jax as _jax

            cached = self._rope_cache
            if cached is None or cached[0].shape[0] < seq_len:
                # build once up to max_position_embeddings (llama.py does
                # the same); slicing a cached table beats rebuilding the
                # outer product on every forward / decode step
                d = self.config.hidden_size // self.config.num_heads
                n = max(seq_len, self.config.max_position_embeddings)
                inv = 1.0 / (10000 ** (jnp.arange(0, d, 2,
                                                  dtype=jnp.float32) / d))
                t = jnp.arange(n, dtype=jnp.float32)
                freqs = jnp.outer(t, inv)
                emb = jnp.concatenate([freqs, freqs], axis=-1)
                cached = (jnp.cos(emb), jnp.sin(emb))
                if not isinstance(cached[0], _jax.core.Tracer):
                    # never cache a TRACED table — it would escape the
                    # trace and poison later calls; jit's own cache makes
                    # the traced rebuild free anyway
                    self._rope_cache = cached
            return (Tensor(cached[0][:seq_len]),
                    Tensor(cached[1][:seq_len]))
        return None

    def _embed(self, input_ids, positions=None):
        """Token embedding, plus the learned positions where the model has
        them (rotary models take theirs inside attention), and dropout."""
        with jax.named_scope("embed"):
            h = self.wte(input_ids)
            if positions is not None:
                h = h + self.wpe(positions)
            return self.drop(h)

    def _cached_blocks(self, h, caches, rope, pos):
        new_caches = []
        for i, (block, cache) in enumerate(zip(self.blocks, caches)):
            with jax.named_scope(f"h{i}"):
                h, nc = block(h, rope=rope, cache=cache, pos=pos)
            new_caches.append(nc)
        with jax.named_scope("final_norm"):
            return self.ln_f(h), new_caches

    def forward(self, input_ids, caches=None, pos=None, segments=None):
        b, s = input_ids.shape
        rotary = self.config.use_rotary
        rope = None
        if caches is not None:
            if segments is not None:
                raise NotImplementedError(
                    "packed (segments=) batches are not supported with "
                    "KV-cache decoding")
            import jax.numpy as jnp
            from jax import lax

            paged = hasattr(caches[0], "block_table")
            if paged:
                # paged decode: PER-SLOT positions (each slot is mid-way
                # through its own sequence) ride the packed-rope / gathered
                # wpe form instead of a scalar offset; s > 1 is the
                # speculative verify window at positions seq_lens..+s-1
                pos = caches[0].seq_lens
            pos_v = pos._value if isinstance(pos, Tensor) else jnp.asarray(pos)
            pos_v = pos_v.astype(jnp.int32)
            if paged or (pos_v.ndim == 1 and pos_v.shape[0] == b):
                # the same form serves ragged batched prefill (serving
                # engine): each row starts at its OWN offset, and the
                # cached attention op takes the per-row offset vector
                pos2d = pos_v[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
                if rotary:
                    cos, sin = self._rope(
                        self.config.max_position_embeddings)
                    rope = (cos, sin, Tensor(pos2d))
                h = self._embed(input_ids, None if rotary else Tensor(pos2d))
                return self._cached_blocks(
                    h, caches, rope, None if paged else Tensor(pos_v))
            pos_v = pos_v.reshape(())
            if rotary:
                cos, sin = self._rope(self.config.max_position_embeddings)
                rope = (Tensor(lax.dynamic_slice(
                            cos._value, (pos_v, 0), (s, cos.shape[-1]))),
                        Tensor(lax.dynamic_slice(
                            sin._value, (pos_v, 0), (s, sin.shape[-1]))))
                h = self._embed(input_ids)
            else:
                h = self._embed(
                    input_ids,
                    api.arange(0, s, 1, dtype="int32") + Tensor(pos_v))
            return self._cached_blocks(h, caches, rope, Tensor(pos_v))
        positions = None
        if segments is not None:
            # positions RESTART at each packed document so a packed row
            # embeds exactly like the same documents padded separately
            import jax.numpy as jnp

            from .generation import packed_positions

            seg_v = (segments._value if isinstance(segments, Tensor)
                     else jnp.asarray(segments)).astype(jnp.int32)
            pos2d = packed_positions(seg_v, s)  # [b, s] per-doc positions
            if rotary:
                # packed rope rides tables + per-token positions; the TPU
                # kernel gathers rows in-kernel (one-hot MXU lookup)
                cos_t, sin_t = self._rope(s)
                rope = (cos_t, sin_t, Tensor(pos2d))
            else:
                positions = Tensor(pos2d)
        elif rotary:
            rope = self._rope(s)
        else:
            positions = api.arange(0, s, 1, dtype="int32")
        h = self._embed(input_ids, positions)
        for i, block in enumerate(self.blocks):
            with jax.named_scope(f"h{i}"):
                if self.config.recompute and self.training:
                    from ..distributed.fleet.recompute import recompute

                    h = recompute(block, h, rope=rope, segments=segments,
                                  policy=self.config.recompute_policy)
                else:
                    h = block(h, rope=rope, segments=segments)
        with jax.named_scope("final_norm"):
            return self.ln_f(h)


class GPTForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(config.hidden_size, config.vocab_size,
                                                has_bias=False, gather_output=True)

    def cache_spec(self):
        c = self.config
        return uniform_cache_spec(c.num_layers, c.num_heads,
                                  c.hidden_size // c.num_heads,
                                  c.max_position_embeddings)

    def _head(self, h):
        with jax.named_scope("lm_head"):
            if self.config.tie_word_embeddings:
                return api.matmul(h, self.gpt.wte.weight, transpose_y=True)
            return self.lm_head(h)

    def forward(self, input_ids, labels=None, caches=None, pos=None,
                segments=None):
        """segments: optional [b, s] packed-document ids (padding -1) —
        the varlen pretrain path (native pack_varlen + segmented
        attention); labels at padding should be -100 (ignored)."""
        if caches is not None:
            if segments is not None:
                raise NotImplementedError(
                    "packed (segments=) batches are not supported with "
                    "KV-cache decoding; generate per document")
            h, new_caches = self.gpt(input_ids, caches=caches, pos=pos)
            return self._head(h), new_caches
        h = self.gpt(input_ids, segments=segments)
        logits = self._head(h)
        if labels is not None:
            # next-token objective: logits[i] predicts labels[i+1]
            # (labels=input_ids is the natural call, as in the reference
            # pretrain pipeline). An unshifted CE here would train the
            # copy task — causal attention sees token i at position i.
            return F.causal_lm_loss(logits, labels, segments)
        return logits


# --------------------------------------------------- pipeline decomposition
class _GPTPipeEmbed(nn.Layer):
    """Stage-0 pre layer: token + positional embedding + dropout, and the
    final LayerNorm that the (tied) head applies — kept here so the
    pipeline's middle stages are HOMOGENEOUS GPTBlocks (the schedule
    engine requires structurally identical stages; embedding/head run
    fused into the first/last stages via SharedLayerDesc)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.wte = nn.Embedding(config.vocab_size, config.hidden_size)
        self.wpe = nn.Embedding(config.max_position_embeddings,
                                config.hidden_size)
        self.drop = nn.Dropout(config.hidden_dropout_prob)
        if config.tie_word_embeddings:
            # the tied head applies the final norm from this shared layer;
            # untied configs keep ln_f in their own head stage instead
            self.ln_f = nn.LayerNorm(config.hidden_size)

    @property
    def weight(self):
        return self.wte.weight  # the shared (tied) embedding weight

    def forward(self, ids):
        s = ids.shape[1]
        p = api.arange(0, s, 1, dtype="int32")
        return self.drop(self.wte(ids) + self.wpe(p))


class _GPTPipeHead(nn.Layer):
    """Untied head: final norm + projection (shared_post, own weights)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_f = nn.LayerNorm(config.hidden_size)
        self.proj = ColumnParallelLinear(config.hidden_size,
                                         config.vocab_size,
                                         has_bias=False, gather_output=True)

    @property
    def weight(self):
        return self.proj.weight

    def forward(self, h):
        return self.proj(self.ln_f(h))


def _gpt_tied_head_fwd(layer, h):
    return api.matmul(layer.ln_f(h), layer.wte.weight, transpose_y=True)


def _gpt_untied_head_fwd(layer, h):
    return layer(h)


def _gpt_pipeline_loss(out, label):
    # the objective of GPTForCausalLM.forward, so pipeline-vs-sequential
    # parity compares the same thing
    return F.causal_lm_loss(out, label)


def _gpt_pipeline_descs(self):
    """LayerDesc decomposition of this model for pipeline engines
    (reference: PipeLayer desc lists in python/paddle/distributed/fleet/
    meta_parallel/parallel_layers/pp_layers.py; the fleet GPT benchmarks
    build [embedding] + [TransformerLayer]*L + [norm+head] descs).

    Returns (descs, loss_fn, copy_weights) where copy_weights(pipeline_
    layer) copies THIS model's weights into the built pipeline. Rotary
    configs are rejected (rope tables are shared state the desc layers
    don't carry)."""
    from ..distributed.fleet.pipeline_parallel import (
        LayerDesc, SharedLayerDesc)

    cfg = self.config
    if cfg.use_rotary:
        raise ValueError("pipeline_descs: rotary GPT configs are not "
                         "pipeline-decomposable (rope is shared state)")
    descs = [SharedLayerDesc("embed", _GPTPipeEmbed, None, "weight", cfg)]
    descs += [LayerDesc(GPTBlock, cfg) for _ in range(cfg.num_layers)]
    if cfg.tie_word_embeddings:
        descs.append(SharedLayerDesc("embed", _GPTPipeEmbed,
                                     _gpt_tied_head_fwd, "weight", cfg))
    else:
        descs.append(SharedLayerDesc("head", _GPTPipeHead,
                                     _gpt_untied_head_fwd, "weight", cfg))

    model = self

    def copy_weights(pl, reverse=False):
        """model -> pipeline (default) or pipeline -> model (reverse,
        used to sync trained weights back after a pp fit)."""
        pre = pl.shared_pre
        pairs = [(model.gpt.wte.weight, pre.wte.weight),
                 (model.gpt.wpe.weight, pre.wpe.weight)]
        if cfg.tie_word_embeddings:
            pairs += [(model.gpt.ln_f.weight, pre.ln_f.weight),
                      (model.gpt.ln_f.bias, pre.ln_f.bias)]
        for src_blk, dst_blk in zip(model.gpt.blocks, pl.run_function):
            pairs += list(zip(src_blk.parameters(), dst_blk.parameters()))
        if not cfg.tie_word_embeddings:
            head = pl.shared_post[0]
            pairs += [(model.gpt.ln_f.weight, head.ln_f.weight),
                      (model.gpt.ln_f.bias, head.ln_f.bias),
                      (model.lm_head.weight, head.proj.weight)]
        for m_p, p_p in pairs:
            assert tuple(m_p.shape) == tuple(p_p.shape)
            if reverse:
                m_p._value = p_p._value
            else:
                p_p._value = m_p._value

    return descs, _gpt_pipeline_loss, copy_weights


GPTForCausalLM.pipeline_descs = _gpt_pipeline_descs
