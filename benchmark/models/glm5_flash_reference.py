"""The plain reference for the `glm5_flash` configurations (zai-org
GLM-5.3-Flash, config.json `model_type: glm5_next_text`), and their seeded
weights.

Straightforward jax.numpy in float32 with matmuls at precision "highest": no
kernels, no cache, no batching, nothing imported from the program. The
residual path is Xing4.0's four streams (xing_reference: hc_maps, sinkhorn,
hc_leaf; every sublayer F has its own RMSNorm inside it, and the maps are
float32 at precision highest whatever `mm` is). A layer's attention is of
the kind `layer_types` names for the published layer it stands for
(`layers_served`), its feed-forward of the kind `mlp_layer_types` names.
Every key is the config's; the linear layers' are in `linear_attn_config`.

Linear (`linear_attention`), H heads of K = V, from u (a = RMSNorm(u)):

    q, k, v = SiLU(conv(a W_{q,k,v}))   causal, depthwise, kernel
                 short_conv_kernel_size over the 3 H K channels; y_t =
                 sum_i w_i x_{t - kernel + 1 + i} (zeros before the first)
    q, k    L2-normalised a head (over K, eps 1e-6)
    beta    = sigmoid(a W_beta)                               [H]
    g       = lb sigmoid(exp(A_log)_h (a W_fa W_fb + dt_bias))  [H, K],
              lb = gate_lower_bound
    S_t     = (I - beta k k^T) diag(exp g) S_{t-1} + beta k v^T    token by
              token, S_0 = 0, float32: the RECURRENCE, not a chunk-wise form
    o_t     = S_t^T q_t / sqrt(K)
    y       = (RMSNorm_K(o) * sigmoid(a W_ga W_gb)) W_o

Sparse (`deepseek_sparse_attention`, no rotary part), 64 heads:

    c_q = RMSNorm(a W_qa)    q_h = c_q W_qb,h [256]
    c   = RMSNorm(a W_kva)   k_h = c W_uk,h, v_h = c W_uv,h [256]  (expanded)
    q^I_j = rope(c_q W_Iq)_j   k^I = rope(LayerNorm(a W_Ik))   J x D,
            rotary interleaved (pairs 2i, 2i + 1) on the first index_rope_dim
            values, theta index_rope_theta, at the token's position
    w_j = (a W_Iw)_j / sqrt(J D)
    kbar_b = mean(k^I_{4b .. 4b+3})        (index_kpool 4)
    I_{t,b} = sum_j w_j ReLU(q^I_j . kbar_b)    every block b < t // 4
    selected: lax.top_k of I_t over those blocks, index_topk / 4 of them
              (fewer where fewer exist), and t's own block
    score_h(t, s) = q_h(t) . k_h(s) / sqrt(256) where s <= t lies in a
              selected block, else masked: masked dense attention
    y = concat_h(sum_s p v_h) W_o

Feed-forward (m = RMSNorm(u)): "dense" a SwiGLU of intermediate_size;
"sparse" GLM-MoE-Lite's router (glm_moe_lite_reference.routing: sigmoid
over the PUBLISHED experts, top num_experts_per_tok of score + bias,
normalised, times routed_scaling_factor) over the experts held and one
ungated shared expert, each a SwiGLU with gate = min(gate, swiglu_limit) and
up = clip(up, -swiglu_limit, swiglu_limit).

The multi-token-prediction module and the vision tower are left out.

Assumed (the configuration file lists the same): index_topk counts
positions; a pooled key is its block's mean; the gate form of
gate_lower_bound; the indexer's rotary width and theta; the convolution's
form; the norms' places; the clip on the shared expert too; the seeded
values (`init_leaf`); Xing4.0's residual path at hidden 4,096.

Departures, so that the same weights mean the same function as in the
program: an expert's gate and up projections are one leaf `e_w13`; the maps'
parameters as xing_reference keeps them.

Controls (`served_gaps(control=)`, never the reference): "fp8" (every matmul
but the maps' one precision down; the harness's --with-control, which also
reads the other two), "bf16_state" (the linear layers' state rounded to bf16
after every token) and "no_decay" (g = 0: the state never forgets).

Sized for rows of 66k tokens on a 16 GB chip: a layer's weights are made
from the seed when the row reaches it; the streams [t, 4, 4,096] float32 are
mixed in blocks of tokens and updated in place; a linear layer runs over
blocks of tokens with its state and convolution tail carried; attention a
head at a time over blocks of queries, the output projection summed a head
at a time; the routed experts one at a time.
"""
from __future__ import annotations

import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import xing_reference as xr
from .glm_moe_lite_reference import BIAS_STD, GAP_QUANTILE, routing  # noqa: F401
from .laguna_reference import (MATMULS, _leaf, _rms, highest_matmul,  # noqa: F401
                               seed_key)

LINEAR, SPARSE = "linear_attention", "deepseek_sparse_attention"
BLOCKS = (1024, 512, 256, 128, 64, 32, 16, 8, 4)
CONV_STD = 0.5
A_LOG_RANGE = (0.0, math.log(2.0))     # exp(A_log) uniform-ish in [1, 2]
# sigmoid(x) ~ exp(x) for x << 0, so a channel's memory is spread evenly over
# its logarithm, from one token to far past the longest context: trained
# delta-rule layers keep many channels that hardly decay beside fast ones
DT_BIAS_RANGE = (-12.0, -1.0)
CONTROLS = ("fp8", "bf16_state", "no_decay")


# -------------------------------------------------------------------- sizes
def lin(cfg: dict) -> dict:
    return cfg["linear_attn_config"]


def layer_kind(cfg: dict, i: int) -> str:
    return cfg["layer_types"][cfg["layers_served"][i]]


def is_dense(cfg: dict, i: int) -> bool:
    return cfg["mlp_layer_types"][cfg["layers_served"][i]] == "dense"


def experts_held(cfg: dict):
    lo, hi = cfg.get("experts_held", [0, int(cfg["n_routed_experts"])])
    if hi - lo != int(cfg["n_routed_experts"]):
        raise ValueError("experts_held does not span n_routed_experts")
    return int(lo), int(hi)


def router_width(cfg: dict) -> int:
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def topk_blocks(cfg: dict) -> int:
    return int(cfg["index_topk"]) // int(cfg["index_kpool"])


def leaf_shapes(cfg: dict) -> dict:
    """name -> (shape, std; None for a norm's ones, or a seeded kind:
    "hc_a", "hc_b", "a_log", "dt_bias", "zeros")."""
    if (int(cfg["n_group"]), int(cfg["topk_group"])) != (1, 1):
        raise ValueError("n_group = topk_group = 1 only")
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    qr, kr = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, vd = int(cfg["qk_nope_head_dim"]), int(cfg["v_head_dim"])
    v, f = int(cfg["vocab_size"]), int(cfg["intermediate_size"])
    fe = int(cfg["moe_intermediate_size"])
    fs = fe * int(cfg["n_shared_experts"])
    e = int(cfg["n_routed_experts"])
    lh, ld = int(lin(cfg)["num_heads"]), int(lin(cfg)["head_dim"])
    ks, gr = int(lin(cfg)["short_conv_kernel_size"]), int(cfg["kda_gate_rank"])
    ih, idim = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    depth = int(cfg.get("published", {}).get("num_hidden_layers",
                                             cfg["num_hidden_layers"]))
    std = float(cfg.get("initializer_range", 0.02))
    out_std = std / math.sqrt(2 * depth)
    nc, m = xr.streams(cfg) * d, xr.n_maps(cfg)
    hk = lh * ld
    out = {"embed": ((v, d), std), "head": ((d, v), std), "norm_f": ((d,), None)}
    for i in range(int(cfg["num_hidden_layers"])):
        p = f"layers.{i}."
        for hc in ("hc_attn.", "hc_mlp."):
            out.update({p + hc + "phi": ((m, nc), 1.0 / math.sqrt(nc)),
                        p + hc + "a": ((3,), "hc_a"),
                        p + hc + "b": ((m,), "hc_b")})
        out.update({p + "ln1": ((d,), None), p + "ln2": ((d,), None)})
        if layer_kind(cfg, i) == LINEAR:
            out.update({p + "wq": ((d, hk), std), p + "wk": ((d, hk), std),
                        p + "wv": ((d, hk), std),
                        p + "conv": ((ks, 3 * hk), CONV_STD),
                        p + "wfa": ((d, gr), std), p + "wfb": ((gr, hk), std),
                        p + "a_log": ((lh,), "a_log"),
                        p + "dt_bias": ((hk,), "dt_bias"),
                        p + "wb": ((d, lh), std),
                        p + "wga": ((d, gr), std), p + "wgb": ((gr, hk), std),
                        p + "o_norm": ((ld,), None),
                        p + "wo": ((hk, d), out_std)})
        else:
            out.update({p + "wqa": ((d, qr), std), p + "q_norm": ((qr,), None),
                        p + "wqb": ((qr, h * nope), std),
                        p + "wkva": ((d, kr), std),
                        p + "kv_norm": ((kr,), None),
                        p + "wkvb": ((kr, h * (nope + vd)), std),
                        p + "wo": ((h * vd, d), out_std),
                        p + "wiq": ((qr, ih * idim), std),
                        p + "wik": ((d, idim), std),
                        p + "ik_norm_w": ((idim,), None),
                        p + "ik_norm_b": ((idim,), "zeros"),
                        p + "wiw": ((d, ih), std)})
        if is_dense(cfg, i):
            out.update({p + "w1": ((d, f), std), p + "w3": ((d, f), std),
                        p + "w2": ((f, d), out_std)})
        else:
            out.update({p + "router": ((d, router_width(cfg)), std),
                        p + "e_bias": ((router_width(cfg),), BIAS_STD),
                        p + "e_w13": ((e, d, 2 * fe), std),
                        p + "e_w2": ((e, fe, d), out_std),
                        p + "s_w1": ((d, fs), std), p + "s_w3": ((d, fs), std),
                        p + "s_w2": ((fs, d), out_std)})
    return out


def float32_leaf(name: str) -> bool:
    """The maps' parameters, the selection bias, A_log and dt_bias are
    float32 whatever the weights' dtype."""
    return name.endswith(("e_bias", "a_log", "dt_bias")) or ".hc_" in name


def init_leaf(cfg: dict, seed: int, name: str, dtype="float32"):
    """One leaf from the seed, on the device: its numbers depend on the seed
    and on the place of its name in leaf_shapes() alone. A_log uniform in
    A_LOG_RANGE and dt_bias in DT_BIAS_RANGE: a channel's decay a token
    lies between about -2 and -1e-10, its memory between one token and far
    past the longest context."""
    shapes = leaf_shapes(cfg)
    shape, std = shapes[name]
    key = jax.random.fold_in(seed_key(seed), list(shapes).index(name))
    if std in ("hc_a", "hc_b"):
        return xr.hc_leaf(key, std, xr.streams(cfg))
    if std in ("a_log", "dt_bias"):
        lo, hi = A_LOG_RANGE if std == "a_log" else DT_BIAS_RANGE
        return jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if std == "zeros":
        return jnp.zeros(shape, jnp.dtype(dtype))
    return _leaf(key, shape, std,
                 "float32" if float32_leaf(name) else str(dtype))


def init_weights(cfg: dict, seed: int, dtype="float32"):
    return {name: init_leaf(cfg, seed, name, dtype)
            for name in leaf_shapes(cfg)}


def layer_params(params, i):
    p = f"layers.{i}."
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p)}


def _block(t: int) -> int:
    return next((b for b in BLOCKS if t % b == 0), t)


def _by_blocks(x, blk):
    return x.reshape((x.shape[0] // blk, blk) + x.shape[1:])


# --------------------------------------------------------- linear attention
def linear_attention(u, lp, cfg, mm, control=None):
    """u [t, hidden] float32 -> y. Blocks of tokens in order, the state S
    [H, K, V] and the convolution's tail carried; inside a block the
    recurrence token by token."""
    t, d = u.shape
    lh, ld = int(lin(cfg)["num_heads"]), int(lin(cfg)["head_dim"])
    ks = int(lin(cfg)["short_conv_kernel_size"])
    lb = float(lin(cfg)["gate_lower_bound"])
    eps = float(cfg["rms_norm_eps"])
    blk = _block(t)
    w = lp["conv"].astype(jnp.float32)
    decay_a = jnp.exp(lp["a_log"].astype(jnp.float32))[:, None]

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def token(s, xs):
        q, k, v, g, b = xs                  # [H, K] x 2, [H, V], [H, K], [H]
        s = s * jnp.exp(g)[:, :, None]
        s = s - b[:, None, None] * k[:, :, None] * jnp.einsum(
            "hk,hkv->hv", k, s, precision="highest")[:, None, :] \
            + b[:, None, None] * k[:, :, None] * v[:, None, :]
        if control == "bf16_state":
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
        return s, jnp.einsum("hk,hkv->hv", q, s, precision="highest")

    def one_block(carry, ub):
        s, tail = carry
        a = _rms(ub, lp["ln1"], eps)
        x = jnp.concatenate([mm(a, lp["wq"]), mm(a, lp["wk"]),
                             mm(a, lp["wv"])], -1)          # [blk, 3 H K]
        xx = jnp.concatenate([tail, x], 0)
        y = sum(xx[i:i + blk] * w[i] for i in range(ks))
        y = jax.nn.silu(y).reshape(blk, 3, lh, ld)
        q, k, v = l2(y[:, 0]), l2(y[:, 1]), y[:, 2]
        b = jax.nn.sigmoid(mm(a, lp["wb"]))
        z = mm(mm(a, lp["wfa"]), lp["wfb"]) + lp["dt_bias"]
        g = lb * jax.nn.sigmoid(decay_a * z.reshape(blk, lh, ld))
        if control == "no_decay":
            g = jnp.zeros_like(g)
        s, o = jax.lax.scan(token, s, (q, k, v, g, b))
        o = _rms(o / math.sqrt(ld), lp["o_norm"], eps)
        gate = jax.nn.sigmoid(mm(mm(a, lp["wga"]), lp["wgb"]))
        out = mm((o.reshape(blk, -1) * gate), lp["wo"])
        return (s, xx[blk:]), out

    s0 = jnp.zeros((lh, ld, ld), jnp.float32)
    tail0 = jnp.zeros((ks - 1, 3 * lh * ld), jnp.float32)
    _, y = jax.lax.scan(one_block, (s0, tail0), _by_blocks(u, blk))
    return y.reshape(t, d)


# --------------------------------------------------------- sparse attention
def _rope_interleaved(x, positions, dim, theta):
    """x [t, ..., D]: the first `dim` values rotated in pairs (2i, 2i + 1)
    by position times theta^(-2i / dim)."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (dim // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0:dim:2], x[..., 1:dim:2]
    rot = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot.reshape(x.shape[:-1] + (dim,)),
                            x[..., dim:]], -1)


def _layer_norm(x, w, b, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)


def selection(a, c_q, lp, cfg, mm):
    """[t, ceil(t / pool)] bool: the blocks each query attends, its top
    index_topk / pool complete blocks by the indexer's score and its own.
    Scores and top-k in blocks of queries."""
    t = a.shape[0]
    ih, idim = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    pool, k = int(cfg["index_kpool"]), topk_blocks(cfg)
    rd, theta = int(cfg["index_rope_dim"]), float(cfg["index_rope_theta"])
    pos = jnp.arange(t)
    ki = _rope_interleaved(
        _layer_norm(mm(a, lp["wik"]), lp["ik_norm_w"], lp["ik_norm_b"]),
        pos, rd, theta)
    nb = -(-t // pool)
    # a last block the row does not fill is never scored: no query lies
    # after it
    ki = jnp.pad(ki, ((0, nb * pool - t), (0, 0)))
    kbar = jnp.mean(ki.reshape(nb, pool, idim), axis=1)         # [B, D]
    blk = _block(t)

    def one_block(i):
        rows = i * blk + jnp.arange(blk)
        cq = jax.lax.dynamic_slice_in_dim(c_q, i * blk, blk)
        ab = jax.lax.dynamic_slice_in_dim(a, i * blk, blk)
        qi = _rope_interleaved(mm(cq, lp["wiq"]).reshape(blk, ih, idim),
                               rows, rd, theta)
        w = mm(ab, lp["wiw"]) / math.sqrt(ih * idim)
        sc = jnp.einsum("qjd,bd->qjb", qi, kbar, precision="highest")
        sc = jnp.sum(w[:, :, None] * jax.nn.relu(sc), axis=1)    # [blk, B]
        complete = jnp.arange(nb)[None, :] < (rows // pool)[:, None]
        sc = jnp.where(complete, sc, -jnp.inf)
        kk = min(k, nb)
        top, idx = jax.lax.top_k(sc, kk)
        sel = jnp.zeros((blk, nb), bool).at[
            jnp.arange(blk)[:, None], idx].set(top > -jnp.inf)
        return sel | (jnp.arange(nb)[None, :] == (rows // pool)[:, None])

    return jax.lax.map(one_block, jnp.arange(t // blk)).reshape(t, nb)


def sparse_attention(u, lp, cfg, mm):
    """u [t, hidden] float32 -> y: the expanded form a head at a time over
    blocks of queries, masked to the selected positions."""
    t, d = u.shape
    h = int(cfg["num_attention_heads"])
    kr = int(cfg["kv_lora_rank"])
    nope, vd = int(cfg["qk_nope_head_dim"]), int(cfg["v_head_dim"])
    eps, pool = float(cfg["rms_norm_eps"]), int(cfg["index_kpool"])
    a = _rms(u, lp["ln1"], eps)
    c_q = _rms(mm(a, lp["wqa"]), lp["q_norm"], eps)
    c = _rms(mm(a, lp["wkva"]), lp["kv_norm"], eps)             # [t, kr]
    sel = selection(a, c_q, lp, cfg, mm)                        # [t, t / 4]
    pos = jnp.arange(t)
    blk = _block(t)
    scale = 1.0 / math.sqrt(nope)

    def one_head(acc, w):
        wq, wkv, wo = w             # [qr, nope], [kr, nope + vd], [vd, d]
        k, v = mm(c, wkv[:, :nope]), mm(c, wkv[:, nope:])

        def one_block(i):
            rows = i * blk + pos[:blk]
            q = mm(jax.lax.dynamic_slice_in_dim(c_q, i * blk, blk), wq)
            s = mm(q, k.T) * scale
            mine = jax.lax.dynamic_slice_in_dim(sel, i * blk, blk)
            live = jnp.repeat(mine, pool, axis=1)[:, :t] \
                & (pos[None, :] <= rows[:, None])
            p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
            return mm(p, v)

        o = jax.lax.map(one_block, jnp.arange(t // blk)).reshape(t, vd)
        return acc + mm(o, wo), None

    by_head = lambda w: w.reshape(w.shape[0], h, -1).transpose(1, 0, 2)  # noqa: E731
    y, _ = jax.lax.scan(one_head, jnp.zeros((t, d), jnp.float32),
                        (by_head(lp["wqb"]), by_head(lp["wkvb"]),
                         lp["wo"].reshape(h, vd, d)))
    return y


# ------------------------------------------------------------ feed-forward
def clipped_swiglu(gate, up, limit):
    """silu(min(gate, limit)) * clip(up, -limit, limit); no clip at None."""
    if limit is not None:
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def mlp(u, lp, cfg, dense, mm):
    m = _rms(u, lp["ln2"], float(cfg["rms_norm_eps"]))
    if dense:
        return mm(jax.nn.silu(mm(m, lp["w1"])) * mm(m, lp["w3"]), lp["w2"])
    limit = cfg.get("swiglu_limit")
    limit = None if limit is None else float(limit)
    lo, hi = experts_held(cfg)
    w = routing(m, lp, cfg, mm)[:, lo:hi]                      # [t, held]
    f = int(cfg["moe_intermediate_size"])

    def one_expert(acc, args):
        w13, w2, we = args
        hh = mm(m, w13)
        out = mm(clipped_swiglu(hh[:, :f], hh[:, f:], limit), w2)
        return acc + out * we[:, None], None

    acc, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                          (lp["e_w13"], lp["e_w2"], w.T))
    shared = clipped_swiglu(mm(m, lp["s_w1"]), mm(m, lp["s_w3"]), limit)
    return acc + mm(shared, lp["s_w2"])


# ------------------------------------------------------------------ streams
def mhc_open(x, hp, cfg):
    """x [t, n, C] -> (u [t, C], post [t, n], res [t, n, n]) in blocks of
    tokens."""
    blk = _block(x.shape[0])

    def one(xb):
        pre, post, res = xr.hc_maps(xb, hp, cfg)
        return jnp.einsum("tj,tjc->tc", pre, xb), post, res

    u, post, res = jax.lax.map(one, _by_blocks(x, blk))
    return (u.reshape(-1, u.shape[-1]), post.reshape(-1, post.shape[-1]),
            res.reshape((-1,) + res.shape[2:]))


def mhc_close(x, y, post, res):
    """X' = H_res X + H_post^T y, in blocks of tokens, x updated in place
    (donated by the caller)."""
    blk = _block(x.shape[0])

    def body(i, x):
        sl = lambda z: jax.lax.dynamic_slice_in_dim(z, i * blk, blk)  # noqa: E731
        xb = sl(x)
        new = jnp.einsum("tij,tjc->tic", sl(res), xb) \
            + sl(post)[:, :, None] * sl(y)[:, None, :]
        return jax.lax.dynamic_update_slice_in_dim(x, new, i * blk, 0)

    return jax.lax.fori_loop(0, x.shape[0] // blk, body, x)


def hc_params(lp, which):
    return {k: lp[f"hc_{which}.{k}"] for k in ("phi", "a", "b")}


def attention(u, lp, cfg, kind, mm, control=None):
    if kind == LINEAR:
        return linear_attention(u, lp, cfg, mm, control)
    return sparse_attention(u, lp, cfg, mm)


def layer(x, lp, cfg, kind, dense, mm=highest_matmul, control=None):
    """x [t, n, C] -> the layer's output streams (the unblocked composition
    the CPU tests call; served_gaps runs the same functions a sublayer a
    program)."""
    u, post, res = mhc_open(x, hc_params(lp, "attn"), cfg)
    x = mhc_close(x, attention(u, lp, cfg, kind, mm, control), post, res)
    u, post, res = mhc_open(x, hc_params(lp, "mlp"), cfg)
    return mhc_close(x, mlp(u, lp, cfg, dense, mm), post, res)


def hidden_states(params, ids, cfg, mm=highest_matmul, control=None):
    """[t] token ids of one row -> final-norm hidden states [t, hidden]."""
    x = xr.open_streams(params["embed"], ids, cfg)
    for i in range(int(cfg["num_hidden_layers"])):
        x = layer(x, layer_params(params, i), cfg, layer_kind(cfg, i),
                  is_dense(cfg, i), mm, control)
    return xr.close_streams(x, params["norm_f"], cfg)


def logits_at(params, ids, positions, cfg, mm=highest_matmul, control=None):
    """Logits [n, vocab] at the given positions of ONE row of ids [t]."""
    return mm(hidden_states(params, ids, cfg, mm, control)[positions],
              params["head"])


# ------------------------------------------------------------ served tokens
def _row_logits(cfg, seed, dtype, top, ids, positions, mm, control):
    """logits_at over weights made a layer at a time from the seed, a
    sublayer a program, the streams donated from one to the next."""
    names = list(leaf_shapes(cfg))
    mmf = MATMULS[mm]
    opener = jax.jit(lambda x, hp: mhc_open(x, hp, cfg))
    closer = jax.jit(mhc_close, donate_argnums=(0,))
    attn = jax.jit(lambda u, lp, kind: attention(u, lp, cfg, kind, mmf,
                                                 control),
                   static_argnames=("kind",))
    ffn = jax.jit(lambda u, lp, dense: mlp(u, lp, cfg, dense, mmf),
                  static_argnames=("dense",))
    x = jax.jit(lambda e, ids: xr.open_streams(e, ids, cfg))(top["embed"],
                                                             ids)
    for i in range(int(cfg["num_hidden_layers"])):
        p = f"layers.{i}."
        lp = {k[len(p):]: init_leaf(cfg, seed, k, dtype)
              for k in names if k.startswith(p)}
        hp = hc_params(lp, "attn")
        u, post, res = opener(x, hp)
        y = attn(u, {k: v for k, v in lp.items() if not k.startswith("hc_")},
                 kind=layer_kind(cfg, i))
        del u
        x = closer(x, y, post, res)
        u, post, res = opener(x, hc_params(lp, "mlp"))
        y = ffn(u, {k: v for k, v in lp.items() if not k.startswith("hc_")},
                dense=is_dense(cfg, i))
        del u
        x = closer(x, y, post, res)
        del lp, y
    return jax.jit(lambda x, nf, head, pos: mmf(
        xr.close_streams(x, nf, cfg)[pos], head))(x, top["norm_f"],
                                                  top["head"], positions)


def served_gaps(cfg, seed, rows, dtype="bfloat16", precision="highest",
                control=None, width=None, n_pos=None, pad_to=128):
    """rows: [(prompt ids, served token ids)]. One teacher-forced pass of the
    reference over each prompt with its served tokens; per row the gap that
    nine in ten of its served tokens stay within (GAP_QUANTILE, as in
    glm_moe_lite_reference.served_gaps: 8 experts a token, a bf16 router
    near a tie). With `control` it reads instead, at the same positions, the
    gap of the token that the control puts first. `control="fp8"` (the
    harness's --with-control) goes over every control of CONTROLS and prints
    each to stderr: the readings a limits file is set between."""
    top = {k: init_leaf(cfg, seed, k, dtype)
           for k in ("embed", "head", "norm_f")}

    @jax.jit
    def gap(lg, nxt):
        return jnp.max(lg, -1) - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]

    width = max([width or 0] + [len(p) + len(t) for p, t in rows])
    width = -(-width // pad_to) * pad_to
    n_pos = max([n_pos or 0] + [len(t) for _, t in rows])
    n_pos = -(-n_pos // pad_to) * pad_to
    controls = [control] if control != "fp8" else list(CONTROLS)
    gaps = {c: [] for c in controls}
    for prompt, toks in rows:
        t0 = time.monotonic()
        ids = np.zeros(width, np.int32)
        ids[:len(prompt) + len(toks)] = list(prompt) + list(toks)
        pos = np.full(n_pos, len(prompt) - 1, np.int32)
        pos[:len(toks)] = len(prompt) - 1 + np.arange(len(toks))
        served = np.full(n_pos, toks[0], np.int32)
        served[:len(toks)] = toks
        ids, pos = jnp.asarray(ids), jnp.asarray(pos)
        lg = _row_logits(cfg, seed, dtype, top, ids, pos, precision, None)
        for c in controls:
            nxt = jnp.asarray(served)
            if c in MATMULS:
                nxt = jnp.argmax(_row_logits(cfg, seed, dtype, top, ids, pos,
                                             c, None), -1)
            elif c is not None:
                nxt = jnp.argmax(_row_logits(cfg, seed, dtype, top, ids, pos,
                                             precision, c), -1)
            g = np.sort(np.asarray(gap(lg, nxt))[:len(toks)])[::-1]
            gaps[c].append(float(np.quantile(g, GAP_QUANTILE)))
            print(f"served_gaps {c or 'served'} row of {len(prompt)} + "
                  f"{len(toks)}: p90 {gaps[c][-1]:.4f} widest "
                  f"{g[:6].round(3).tolist()} mean {g.mean():.4f} over 0.1: "
                  f"{int((g > 0.1).sum())}; {time.monotonic() - t0:.1f} s "
                  f"from the row's start", file=sys.stderr, flush=True)
    return gaps[controls[0]]
