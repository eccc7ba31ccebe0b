"""BERT family (BERT-base pretrain, DP + fused attention).

Reference analog: the fleet BERT payloads and fused_attention/
fused_feedforward ops (paddle/fluid/operators/fused/fused_attention_op.cu) —
here the "fusion" is XLA's, with the Pallas flash kernel behind
F.scaled_dot_product_attention for the non-causal path.

Includes the pretraining heads (masked LM + next-sentence prediction) and a
sequence-classification head, mirroring the reference model zoo surface.
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


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    layer_norm_eps: float = 1e-12
    initializer_range: float = 0.02

    @staticmethod
    def base():
        return BertConfig()

    @staticmethod
    def large():
        return BertConfig(hidden_size=1024, num_layers=24, num_heads=16,
                          intermediate_size=4096)

    @staticmethod
    def tiny():
        return BertConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                          num_heads=4, intermediate_size=256,
                          max_position_embeddings=128,
                          hidden_dropout_prob=0.0, attention_dropout_prob=0.0)


class BertEmbeddings(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        c = config
        self.word_embeddings = VocabParallelEmbedding(c.vocab_size, c.hidden_size)
        self.position_embeddings = nn.Embedding(c.max_position_embeddings, c.hidden_size)
        self.token_type_embeddings = nn.Embedding(c.type_vocab_size, c.hidden_size)
        self.layer_norm = nn.LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.dropout = nn.Dropout(c.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None):
        b, s = input_ids.shape
        pos = Tensor(jnp.arange(s, dtype=jnp.int32))
        e = self.word_embeddings(input_ids) + self.position_embeddings(pos)
        if token_type_ids is not None:
            e = e + self.token_type_embeddings(token_type_ids)
        return self.dropout(self.layer_norm(e))


class BertSelfAttention(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_heads
        self.head_dim = c.hidden_size // c.num_heads
        self.qkv = ColumnParallelLinear(c.hidden_size, 3 * c.hidden_size,
                                        gather_output=False)
        self.out = RowParallelLinear(c.hidden_size, c.hidden_size,
                                     input_is_parallel=True)
        self.attn_dropout_p = c.attention_dropout_prob
        self.dropout = nn.Dropout(c.hidden_dropout_prob)

    def forward(self, x, attention_mask=None):
        b, s, h = x.shape
        qkv = api.reshape(self.qkv(x), [b, s, self.num_heads, 3 * self.head_dim])
        q, k, v = api.split(qkv, 3, axis=-1)
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attention_mask, is_causal=False,
            dropout_p=self.attn_dropout_p if self.training else 0.0,
            training=self.training,
        )
        out = api.reshape(out, [b, s, h])
        return self.dropout(self.out(out))


class BertLayer(nn.Layer):
    """Post-LN encoder block (original BERT ordering)."""

    def __init__(self, config: BertConfig):
        super().__init__()
        c = config
        self.attention = BertSelfAttention(c)
        self.attn_norm = nn.LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.fc_in = ColumnParallelLinear(c.hidden_size, c.intermediate_size,
                                          gather_output=False)
        self.fc_out = RowParallelLinear(c.intermediate_size, c.hidden_size,
                                        input_is_parallel=True)
        self.ffn_norm = nn.LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.dropout = nn.Dropout(c.hidden_dropout_prob)

    def forward(self, x, attention_mask=None):
        x = self.attn_norm(x + self.attention(x, attention_mask))
        h = self.fc_out(F.gelu(self.fc_in(x), approximate=False))
        return self.ffn_norm(x + self.dropout(h))


class BertModel(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        self.embeddings = BertEmbeddings(config)
        self.encoder = nn.LayerList([BertLayer(config)
                                     for _ in range(config.num_layers)])
        self.pooler = nn.Linear(config.hidden_size, config.hidden_size)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        """Returns (sequence_output [b,s,h], pooled_output [b,h])."""
        if attention_mask is not None:
            # [b, s] 1/0 -> additive [b, 1, 1, s] broadcastable mask
            m = attention_mask._value.astype(jnp.float32)
            add = (1.0 - m)[:, None, None, :] * -1e9
            attention_mask = Tensor(add)
        h = self.embeddings(input_ids, token_type_ids)
        for layer in self.encoder:
            h = layer(h, attention_mask)
        pooled = api.tanh(self.pooler(h[:, 0]))
        return h, pooled


class BertPretrainingHeads(nn.Layer):
    def __init__(self, config: BertConfig, embedding_weight):
        super().__init__()
        self.transform = nn.Linear(config.hidden_size, config.hidden_size)
        self.transform_norm = nn.LayerNorm(config.hidden_size,
                                           epsilon=config.layer_norm_eps)
        self._embedding_weight = embedding_weight  # tied decoder
        self.decoder_bias = self.create_parameter(
            [config.vocab_size], is_bias=True)
        self.seq_relationship = nn.Linear(config.hidden_size, 2)

    def forward(self, sequence_output, pooled_output):
        h = self.transform_norm(F.gelu(self.transform(sequence_output)))
        mlm_logits = api.matmul(h, api.t(self._embedding_weight)) + self.decoder_bias
        nsp_logits = self.seq_relationship(pooled_output)
        return mlm_logits, nsp_logits


class BertForPretraining(nn.Layer):
    """MLM + NSP (reference pretraining objective)."""

    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        self.bert = BertModel(config)
        self.cls = BertPretrainingHeads(config,
                                        self.bert.embeddings.word_embeddings.weight)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                masked_lm_labels=None, next_sentence_labels=None):
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        mlm_logits, nsp_logits = self.cls(seq, pooled)
        if masked_lm_labels is None:
            return mlm_logits, nsp_logits
        v = mlm_logits.shape[-1]
        mlm_loss = F.cross_entropy(
            api.reshape(mlm_logits, [-1, v]),
            api.reshape(masked_lm_labels, [-1]),
            ignore_index=-100,
        )
        loss = mlm_loss
        if next_sentence_labels is not None:
            loss = loss + F.cross_entropy(nsp_logits,
                                          api.reshape(next_sentence_labels, [-1]))
        return loss


class BertForSequenceClassification(nn.Layer):
    def __init__(self, config: BertConfig, num_classes: int = 2):
        super().__init__()
        self.bert = BertModel(config)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)
        self.classifier = nn.Linear(config.hidden_size, num_classes)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                labels=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        logits = self.classifier(self.dropout(pooled))
        if labels is not None:
            return F.cross_entropy(logits, labels)
        return logits
