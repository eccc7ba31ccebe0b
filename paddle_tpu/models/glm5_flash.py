"""GLM-5.3-Flash (zai-org, config.json `model_type: glm5_next_text`): a
decoder whose layers are of two kinds, under Xing4.0's four-stream residual
path (manifold-constrained hyper-connections, models/xing.py) and with
GLM-MoE-Lite's sigmoid-routed experts behind a clipped SwiGLU.

Linear layers (`linear_attention`; Kimi delta attention), H heads of key =
value = K, from the sublayer's input u (after its RMSNorm):

    q, k, v = SiLU(conv(W_{q,k,v} u))    a causal depthwise convolution of
                                        `short_conv_kernel_size` over the
                                        3 H K channels; q, k L2-normalised
                                        a head
    beta    = sigmoid(W_beta u)          a head
    g       = lb sigmoid(exp(A_log) (W_fb W_fa u + dt_bias))   [H, K], lb =
                                        `gate_lower_bound`
    S_t     = (I - beta k k^T) diag(exp g) S_{t-1} + beta k v^T
    o       = S_t^T q / sqrt(K)
    y       = W_o [RMSNorm_head(o) * sigmoid(W_gb W_ga u)]

What such a layer keeps a sequence is fixed whatever its length: S (H x K x
V float32) and the convolution's last kernel - 1 inputs (`cache_spec()`:
kind "state"). The scan is ops/pallas/delta_rule.py: the chunk-wise form over
a prefill chunk (kernel `kda_chunk`), one token a slot in decode
(`kda_decode`).

Sparse layers (`deepseek_sparse_attention`): GLM-MoE-Lite's latent attention
with no rotary part (`qk_rope_head_dim` 0: the cache keeps the normed latent
c alone, `kv_lora_rank` wide) and an indexer that chooses the keys:

    q^I_j = rope(W_Iq c_q)_j   k^I = rope(LayerNorm(W_Ik a))   J heads of D,
                                        interleaved rotary on the first
                                        `index_rope_dim` values
    w_j   = (W_Iw a)_j / sqrt(J D)
    kbar_b = mean of k^I over positions POOL b .. POOL b + POOL - 1
    I_{t,b} = sum_j w_j ReLU(q^I_j . kbar_b)  over complete blocks b < t / POOL

and a query attends (absorbed, ops/pallas/sparse_latent.py) to the top
`index_topk / POOL` blocks and always its own. Its cache is two arrays by
groups of POOL positions: the latents and the pooled keys (kind "latent"
with an `index_dim`). A pooled key is the sum of its positions' keys over
POOL, added as they are written (a prefill chunk writes whole groups, a
decode step adds its own); it is final at the group's last position and the
tail is never scored, so no partial key is read.

Layers are of the kinds `layer_types` and `mlp_layer_types` name ("dense":
a SwiGLU of intermediate_size; "sparse": the router over all published
experts, the experts held here and one ungated shared expert, their SwiGLU
clipped at `swiglu_limit`). Scopes: h{i}/attn.kda/{conv,gate,scan,out},
h{i}/attn.dsa/{index,select,sparse,kv_append}.

Not served: the multi-token-prediction module (ROADMAP M5) and the vision
tower, which is not part of the text model's configuration.
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
from ..nn import initializer as I
from ..ops import api
from ..ops.pallas import delta_rule as _dr
from ..ops.pallas import sparse_latent as _sl
from .generation import CacheSpec, LayerCacheSpec
from .glm_moe_lite import GlmLatentAttention, GlmSparseMLP
from .laguna import _linear, _normal
from .llama import LlamaMLP
from .xing import (MHC_COUNTERS, HyperConnection, Xing4Config,
                   Xing4ForCausalLM, Xing4Model, _unbalanced)

LINEAR, SPARSE = "linear_attention", "deepseek_sparse_attention"


def published_layer_types(n: int):
    """Three linear layers, then a sparse one, as published."""
    return tuple(SPARSE if i % 4 == 3 else LINEAR for i in range(n))


@dataclass
class Glm5FlashConfig(Xing4Config):
    vocab_size: int = 154880
    hidden_size: int = 4096
    intermediate_size: int = 12288
    num_layers: int = 45
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 256
    qk_rope_head_dim: int = 0
    v_head_dim: int = 256
    rope_theta: float = 1000000.0           # the indexer's rotary
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-5
    first_k_dense_replace: int = 3
    n_routed_experts: int = 288
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    routed_scaling_factor: float = 2.5
    layer_types: Optional[Tuple[str, ...]] = None
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    gate_lower_bound: float = -5.0
    gate_rank: int = 128
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048                  # positions
    index_kpool: int = 4
    index_rope_dim: int = 64
    swiglu_limit: Optional[float] = 10.0

    def __post_init__(self):
        super().__post_init__()
        if self.layer_types is None:
            self.layer_types = published_layer_types(self.num_layers)
        if self.mlp_layer_types is None:
            self.mlp_layer_types = tuple(
                "dense" if i < self.first_k_dense_replace else "sparse"
                for i in range(self.num_layers))
        self.layer_types = tuple(self.layer_types)
        self.mlp_layer_types = tuple(self.mlp_layer_types)
        if len(self.layer_types) != self.num_layers \
                or len(self.mlp_layer_types) != self.num_layers:
            raise ValueError("layer_types and mlp_layer_types: one a layer")
        if set(self.layer_types) - {LINEAR, SPARSE} \
                or set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError(f"layer kinds {LINEAR!r} / {SPARSE!r} and mlp "
                             f"kinds 'dense' / 'sparse' only")
        if self.qk_rope_head_dim:
            raise ValueError("the sparse layers' latent attention has no "
                             "rotary part (qk_rope_head_dim 0)")
        if self.index_topk % self.index_kpool \
                or self.index_rope_dim % 2 \
                or self.index_rope_dim > self.index_head_dim:
            raise ValueError("index_topk a whole number of pooled blocks; "
                             "an even index_rope_dim within the head")

    @property
    def topk_blocks(self) -> int:
        return self.index_topk // self.index_kpool

    @staticmethod
    def tiny(**kw):
        """Every mechanism at a size the CPU tests can run: a linear layer
        with a dense SwiGLU, a sparse layer and three linear ones with 8
        experts (2 a token), 2 linear heads of 16, 4 latent heads, an
        indexer of 2 heads choosing 4 blocks of 4 (16 positions)."""
        base = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_layers=5, num_attention_heads=4, q_lora_rank=24,
            kv_lora_rank=32, qk_nope_head_dim=16, v_head_dim=16,
            max_position_embeddings=1024, first_k_dense_replace=1,
            layer_types=(LINEAR, SPARSE, LINEAR, LINEAR, LINEAR),
            n_routed_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, linear_num_heads=2,
            linear_head_dim=16, gate_rank=8, index_n_heads=2,
            index_head_dim=16, index_topk=16, index_rope_dim=8,
            swiglu_limit=1.0)
        base.update(kw)
        return Glm5FlashConfig(**base)


def _val(x):
    return x._value if isinstance(x, Tensor) else x


def _silu_f32(x):
    x = x.astype(jnp.float32)
    return x * jax.nn.sigmoid(x)


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


class KimiDeltaAttention(nn.Layer):
    """A linear-attention sublayer (see the module docstring)."""

    def __init__(self, config: Glm5FlashConfig):
        super().__init__()
        c = config
        self.heads, self.dim = c.linear_num_heads, c.linear_head_dim
        self.kernel = c.short_conv_kernel_size
        self.lower = float(c.gate_lower_bound)
        self.eps = c.rms_norm_eps
        std, d, hk = c.initializer_range, c.hidden_size, \
            c.linear_num_heads * c.linear_head_dim
        self.q_proj = _linear(d, hk, std)
        self.k_proj = _linear(d, hk, std)
        self.v_proj = _linear(d, hk, std)
        self.conv = self.create_parameter(
            [self.kernel, 3 * hk], default_initializer=_normal(0.5))
        self.f_a_proj = _linear(d, c.gate_rank, std)
        self.f_b_proj = _linear(c.gate_rank, hk, std)
        self.A_log = self.create_parameter(
            [self.heads], dtype="float32",
            default_initializer=I._global_weight_init or I.Constant(0.0))
        self.dt_bias = self.create_parameter(
            [hk], dtype="float32",
            default_initializer=I._global_weight_init or I.Constant(-2.0))
        self.b_proj = _linear(d, self.heads, std)
        self.g_a_proj = _linear(d, c.gate_rank, std)
        self.g_b_proj = _linear(c.gate_rank, hk, std)
        self.o_norm = nn.RMSNorm(self.dim, epsilon=c.rms_norm_eps)
        self.o_proj = _linear(hk, d, std / math.sqrt(2 * c.num_layers),
                              column=False)

    def state_shapes(self):
        hk = self.heads * self.dim
        return ((self.heads, self.dim, self.dim),
                (self.kernel - 1, 3 * hk))

    def forward(self, x, positions, cache=None, pos=None):
        b, s, _ = x.shape
        h, dk = self.heads, self.dim
        paged = hasattr(cache, "block_table")
        with jax.named_scope("conv"):
            qkv = _val(api.concat([self.q_proj(x), self.k_proj(x),
                                   self.v_proj(x)], axis=-1))
            if cache is None:
                tail = jnp.zeros((b, self.kernel - 1, qkv.shape[-1]),
                                 qkv.dtype)
                state = jnp.zeros((b, h, dk, dk), jnp.float32)
                limit = jnp.full((b,), s, jnp.int32)
            elif paged:
                state, tail = _val(cache.k_pages), _val(cache.v_pages)
            else:
                state, tail, limit = (_val(t) for t in cache)
            xx = jnp.concatenate([tail.astype(qkv.dtype), qkv], axis=1)
            w = _val(self.conv).astype(jnp.float32)
            y = sum(xx[:, i:i + s].astype(jnp.float32) * w[i]
                    for i in range(self.kernel))
            y = _silu_f32(y).reshape(b, s, 3, h, dk)
            q, k, v = _l2norm(y[:, :, 0]), _l2norm(y[:, :, 1]), y[:, :, 2]
            if paged:
                new_tail = xx[:, s:]
            else:
                # the last inputs of the sequence, not of the padded chunk
                start = jnp.asarray(0 if pos is None else _val(pos),
                                    jnp.int32).reshape(())
                n = jnp.clip(limit - start, 0, s)
                new_tail = jax.vmap(lambda r, i: jax.lax.dynamic_slice_in_dim(
                    r, i, self.kernel - 1, 0))(xx, n)
        with jax.named_scope("gate"):
            beta = jax.nn.sigmoid(_val(self.b_proj(x)).astype(jnp.float32))
            z = _val(self.f_b_proj(self.f_a_proj(x))).astype(jnp.float32) \
                + _val(self.dt_bias)
            a = jnp.exp(_val(self.A_log))[:, None]
            g = self.lower * jax.nn.sigmoid(a * z.reshape(b, s, h, dk))
            if not paged:
                live = (start + jnp.arange(s))[None, :] < limit[:, None]
                beta = jnp.where(live[..., None], beta, 0.0)
                g = jnp.where(live[..., None, None], g, 0.0)
        scale = 1.0 / math.sqrt(dk)
        with jax.named_scope("scan"):
            if paged:
                o, state = _dr.delta_rule_step(q[:, 0], k[:, 0], v[:, 0],
                                               g[:, 0], beta[:, 0], state,
                                               scale)
                o = o[:, None]
            else:
                unit = _dr.BT if s >= _dr.BT else _dr.SUB
                pad = (-s) % unit
                padt = lambda t: jnp.pad(                     # noqa: E731
                    t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                o, state = _dr.delta_rule_chunked(
                    padt(q), padt(k), padt(v), padt(g), padt(beta), state,
                    scale)
                o = o[:, :s]
        with jax.named_scope("out"):
            o = Tensor(o)
            gate = api.reshape(self.g_b_proj(self.g_a_proj(x)), [b, s, h, dk])
            o = self.o_norm(o) * F.sigmoid(api.cast(gate, "float32"))
            out = self.o_proj(api.reshape(api.cast(o, x.dtype), [b, s, -1]))
        if cache is None:
            return out, None
        if paged:
            return out, (Tensor(state), Tensor(new_tail.astype(tail.dtype)))
        return out, (Tensor(state), Tensor(new_tail.astype(tail.dtype)),
                     Tensor(limit))


def _rope_interleaved(x, positions, dim, theta):
    """x [b, s, ..., D] float32: rotates the first `dim` values in pairs
    (2i, 2i + 1) by position times theta^(-2i / dim)."""
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[..., None] * inv
    extra = x.ndim - ang.ndim
    ang = ang.reshape(ang.shape[:2] + (1,) * extra + ang.shape[2:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0:dim:2], x[..., 1:dim:2]
    rot = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot.reshape(x.shape[:-1] + (dim,)),
                            x[..., dim:]], -1)


class SparseLatentAttention(GlmLatentAttention):
    """A sparse sublayer: GLM-MoE-Lite's latent projections with no rotary
    part, absorbed over the selected groups of the cache, and the indexer
    that selects them (see the module docstring)."""

    def __init__(self, config: Glm5FlashConfig):
        super().__init__(config)
        c = config
        std = c.initializer_range
        self.pool = c.index_kpool
        self.topk = c.topk_blocks
        self.ih, self.idim = c.index_n_heads, c.index_head_dim
        self.rope_dim, self.theta = c.index_rope_dim, float(c.rope_theta)
        self.index_q = _linear(c.q_lora_rank, self.ih * self.idim, std)
        self.index_k = _linear(c.hidden_size, self.idim, std)
        self.index_k_norm = nn.LayerNorm(self.idim, epsilon=1e-6)
        self.index_w = _linear(c.hidden_size, self.ih, std)

    def forward(self, x, positions, cache=None, pos=None):
        b, s, _ = x.shape
        h, nope, vd, rank = self.num_heads, self.nope, self.v_dim, self.rank
        c_q = self.q_a_layernorm(self.q_a_proj(x))
        q = api.reshape(self.q_b_proj(c_q), [b, s, h, nope])
        c = _val(self.kv_a_layernorm(self.kv_a_proj(x)))         # [b, s, r]
        w_kvb = api.reshape(self.kv_b_proj.weight, [rank, h, nope + vd])
        with jax.named_scope("absorb"):
            q_abs = _val(api.einsum("bshn,rhn->bshr", q, w_kvb[:, :, :nope]))
        pv = _val(positions)
        with jax.named_scope("index"):
            qi = _val(self.index_q(c_q)).astype(jnp.float32).reshape(
                b, s, self.ih, self.idim)
            qi = _rope_interleaved(qi, pv, self.rope_dim, self.theta)
            ki = _val(self.index_k_norm(self.index_k(x))).astype(jnp.float32)
            ki = _rope_interleaved(ki, pv, self.rope_dim, self.theta)
            wts = _val(self.index_w(x)).astype(jnp.float32) \
                / math.sqrt(self.ih * self.idim)
        if hasattr(cache, "block_table"):
            o_lat, new_cache = self._paged(q_abs, c, qi, ki, wts, cache)
        else:
            o_lat, new_cache = self._contiguous(q_abs, c, qi, ki, wts, cache,
                                                pos)
        with jax.named_scope("absorb"):
            out = api.einsum("bshr,rhv->bshv", Tensor(o_lat.astype(c.dtype)),
                             w_kvb[:, :, nope:])
        return self.o_proj(api.reshape(out, [b, s, h * vd])), new_cache

    def _contiguous(self, q_abs, c, qi, ki, wts, cache, pos):
        """A chunk at a scalar offset over a contiguous workspace (prefill),
        or the whole sequence with no cache."""
        b, s = c.shape[:2]
        P = self.pool
        if cache is None:
            n = -(-s // P)
            lat_ws = jnp.zeros((b, n, P * self.rank), c.dtype)
            idx_ws = jnp.zeros((b, n, self.idim), c.dtype)
            limit = jnp.full((b,), s, jnp.int32)
            start = jnp.zeros((), jnp.int32)
        else:
            lat_ws, idx_ws, limit = (_val(t) for t in cache)
            start = jnp.asarray(_val(pos), jnp.int32).reshape(())
        pad = (-s) % P
        padt = lambda t: jnp.pad(                             # noqa: E731
            t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        sp = s + pad
        t = start + jnp.arange(sp)
        with jax.named_scope("kv_append"):
            g0 = start // P
            lat_ws = jax.lax.dynamic_update_slice(
                lat_ws, padt(c).reshape(b, sp // P, P * self.rank)
                .astype(lat_ws.dtype), (0, g0, 0))
            live = t[None, :] < limit[:, None]
            kin = jnp.where(live[..., None], padt(ki), 0.0)
            pooled = jnp.sum(kin.reshape(b, sp // P, P, -1), axis=2) / P
            idx_ws = jax.lax.dynamic_update_slice(
                idx_ws, pooled.astype(idx_ws.dtype), (0, g0, 0))
        outs = []
        for i in range(b):
            with jax.named_scope("select"):
                sc = _sl.index_scores(padt(qi)[i], padt(wts)[i], idx_ws[i],
                                      t // P)
                gid, rows = _sl.select(sc, self.topk, t)
            with jax.named_scope("sparse"):
                outs.append(_sl.sparse_attention(
                    padt(q_abs)[i], lat_ws[i], gid, rows, self.scale)[:s])
        o_lat = jnp.stack(outs)
        if cache is None:
            return o_lat, None
        return o_lat, (Tensor(lat_ws), Tensor(idx_ws), Tensor(limit))

    def _paged(self, q_abs, c, qi, ki, wts, cache):
        """One decode step a slot over the paged groups."""
        lat_pages, idx_pages = _val(cache.k_pages), _val(cache.v_pages)
        bt = _val(cache.block_table).astype(jnp.int32)
        p = jnp.asarray(_val(cache.seq_lens), jnp.int32).reshape(-1)
        P, rank = self.pool, self.rank
        gpb = lat_pages.shape[1]                        # groups a page
        slots = bt.shape[0]
        with jax.named_scope("kv_append"):
            blk = p // (gpb * P)
            page = jnp.where(
                blk < bt.shape[1],
                jnp.take_along_axis(bt, jnp.minimum(blk, bt.shape[1] - 1)
                                    [:, None], axis=1)[:, 0], 0)
            grp = (p // P) % gpb
            r = p % P
            old = lat_pages[page, grp]                  # [slots, P * rank]
            mine = (jnp.arange(P * rank)[None, :] // rank) == r[:, None]
            lat_pages = lat_pages.at[page, grp].set(jnp.where(
                mine, jnp.tile(c[:, 0], (1, P)).astype(old.dtype), old))
            oldk = idx_pages[page, grp].astype(jnp.float32)
            newk = jnp.where(r[:, None] == 0, 0.0, oldk) + ki[:, 0] / P
            idx_pages = idx_pages.at[page, grp].set(newk.astype(
                idx_pages.dtype))
        with jax.named_scope("select"):
            kp = idx_pages[bt].reshape(slots, -1, self.idim)
            sc = _sl.index_scores(qi[:, 0], wts[:, 0], kp, p // P)
            groups, rows = _sl.select(sc, self.topk, p)
            gid = jnp.take_along_axis(bt, groups // gpb, axis=1) * gpb \
                + groups % gpb
        with jax.named_scope("sparse"):
            o = _sl.sparse_attention(
                q_abs[:, 0], lat_pages.reshape(-1, P * rank), gid, rows,
                self.scale)
        return o[:, None], (Tensor(lat_pages), Tensor(idx_pages))


class ClippedSwiGLU(LlamaMLP):
    """down(silu(min(gate, limit)) * clip(up, -limit, limit))."""

    def __init__(self, hidden: int, width: int, limit):
        super().__init__(SimpleNamespace(hidden_size=hidden,
                                         intermediate_size=width))
        self.limit = limit

    def forward(self, x):
        gate, up = self.gate_proj(x), self.up_proj(x)
        if self.limit is not None:
            gate = api.clip(gate, max=self.limit)
            up = api.clip(up, min=-self.limit, max=self.limit)
        return self.down_proj(F.silu(gate) * up)


class Glm5SparseMLP(GlmSparseMLP):
    """GLM-MoE-Lite's router and experts with the SwiGLU clipped, the
    shared expert's too."""

    def __init__(self, config: Glm5FlashConfig):
        super().__init__(config)
        c = config
        self.limit = c.swiglu_limit
        self.shared = ClippedSwiGLU(
            c.hidden_size, c.moe_intermediate_size * c.n_shared_experts,
            c.swiglu_limit)

    def forward(self, x):
        b, s, d = x.shape
        y, counts = api.moe_experts(
            api.reshape(x, [b * s, d]), self.router.weight, self.w13,
            self.w2, expert_lo=self.expert_lo, top_k=self.top_k,
            scale=self.scale, norm_topk=self.norm_topk, scoring="sigmoid",
            select_bias=self.e_score_correction_bias,
            swiglu_limit=self.limit)
        with jax.named_scope("shared"):
            y = api.reshape(y, [b, s, d]) + self.shared(x)
        return y, counts


class Glm5FlashDecoderLayer(nn.Layer):
    def __init__(self, config: Glm5FlashConfig, layer: int):
        super().__init__()
        c = config
        self.n = c.hc_mult
        self.kind = c.layer_types[layer]
        self.hc_attn = HyperConnection(c)
        self.hc_mlp = HyperConnection(c)
        self.input_layernorm = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.self_attn = KimiDeltaAttention(c) if self.kind == LINEAR \
            else SparseLatentAttention(c)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   epsilon=c.rms_norm_eps)
        self.sparse = c.mlp_layer_types[layer] == "sparse"
        self.mlp = Glm5SparseMLP(c) if self.sparse else LlamaMLP(
            SimpleNamespace(hidden_size=c.hidden_size,
                            intermediate_size=c.intermediate_size))

    def forward(self, x, positions, cache=None, pos=None):
        with jax.named_scope("hc.attn"):
            u, maps_a = self.hc_attn.open(x)
        scope = "attn.kda" if self.kind == LINEAR else "attn.dsa"
        with jax.named_scope(scope):
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


class Glm5FlashModel(Xing4Model):
    def __init__(self, config: Glm5FlashConfig):
        nn.Layer.__init__(self)
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = nn.LayerList([Glm5FlashDecoderLayer(config, i)
                                    for i in range(config.num_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)


class Glm5FlashForCausalLM(Xing4ForCausalLM):
    """The model and its untied head; logits and the cached forward are
    LagunaForCausalLM's."""

    def __init__(self, config: Glm5FlashConfig):
        nn.Layer.__init__(self)
        self.config = config
        self.model = Glm5FlashModel(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            weight_attr=nn.ParamAttr(
                initializer=_normal(config.initializer_range)))

    def cache_spec(self) -> CacheSpec:
        c = self.config
        held = c.experts_held[1] - c.experts_held[0]
        layers = []
        for i, layer in enumerate(self.model.layers):
            counters = (held + 1) * layer.sparse + len(MHC_COUNTERS)
            if layer.kind == LINEAR:
                layers.append(LayerCacheSpec(
                    "state", c.linear_num_heads, c.linear_head_dim,
                    counters=counters, extra=MHC_COUNTERS,
                    state=layer.self_attn.state_shapes()))
            else:
                layers.append(LayerCacheSpec(
                    "latent", 1, c.kv_lora_rank, counters=counters,
                    extra=MHC_COUNTERS, index_dim=c.index_head_dim,
                    pool=c.index_kpool, index_topk=c.topk_blocks))
        return CacheSpec(tuple(layers), c.max_position_embeddings)
