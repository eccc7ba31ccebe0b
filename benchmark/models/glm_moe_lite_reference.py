"""The plain reference for the `glm_moe_lite` configurations (zai-org
GLM-4.7-Flash, config.json `model_type: glm4_moe_lite`), and their seeded
weights.

Straightforward jax.numpy in float32 with matmuls at precision "highest": no
kernels, no cache, no batching, nothing imported from the program. Attention
is the EXPANDED form only (the program decodes in the absorbed form, so it is
checked against other mathematics). The equations, for layer l with input x,
H = num_attention_heads heads (every key is the config's):

    a = RMSNorm(x)                          eps rms_norm_eps, no biases anywhere
    c_q = RMSNorm(a Wqa) [q_lora_rank]      q = c_q Wqb, a head [q_nope, q_rope]
    [c_raw, r_raw] = a Wkva                 [kv_lora_rank, qk_rope_head_dim]
    c = RMSNorm(c_raw)    r = RoPE(r_raw)   ONE rotary key, shared by all heads
    q_rope = RoPE(q_rope)                   theta rope_theta over all
                                            qk_rope_head_dim dims, no scaling
    [k_nope_h, v_h] = c Wkvb                a head [qk_nope_head_dim, v_head_dim]
    score_h(t, s) = (q_nope_h(t).k_nope_h(s) + q_rope_h(t).r(s))
                    / sqrt(qk_nope_head_dim + qk_rope_head_dim), causal softmax
    x <- x + concat_h(sum_s p v_h) Wo ;  m = RMSNorm(x)
    layer < first_k_dense_replace:  x <- x + (silu(m W1) * m W3) W2
    other layers:  s = sigmoid_f32(m Wr) over the PUBLISHED experts; the
            num_experts_per_tok largest of s + b (e_score_correction_bias);
            w = routed_scaling_factor * s_top / sum s_top  (s WITHOUT b);
            x <- x + sum_e w_e E_e(m) + S(m);  E_e, S SwiGLU
    last    RMSNorm, then the untied head.

n_group = topk_group = 1: group-limited routing is the identity (asserted).

A chip's share: `experts_held` = [lo, hi) are the routed experts whose weights
are here; routing is over all published experts, an absent expert adds
nothing, and that partial result goes on to the next layer. The vocabulary is
the configuration's slice. The multi-token-prediction module is left out.

Sized for rows of 33k tokens: attention a head at a time in blocks of queries,
the routed experts one at a time (their weights cast to float32 one expert at
a time).

Assumed (the configuration file lists the same): rotate-half pairing
(dimension i with i + dim/2); b's seeded values N(0, 0.02), nonzero, so that
a path which drops it fails (a tenth of the scores' spread: it moves the
choice where the fourth and fifth scores lie close, at about a third of the
tokens a layer, and leaves the experts' load even; at N(0, 0.1) the busiest
held expert took 4 to 7 times the mean's pairs, by the seed, and the decode
step's time went with it); initialisation.

Departures, so that the same weights mean the same function as in the
program: an expert's gate and up projections are one leaf `e_w13`
[experts, hidden, 2 * width], gate columns first.
"""
from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

# the seed's keys, the leaf maker and the one place precision enters are the
# laguna reference's: nothing of the program
from .laguna_reference import (MATMULS, _leaf, _rms, _swiglu, fp8_matmul,  # noqa: F401
                               highest_matmul, seed_key)

BIAS_STD = 0.02
GAP_QUANTILE = 0.9
QUERY_BLOCKS = (1024, 512, 256, 128)


# -------------------------------------------------------------------- sizes
def experts_held(cfg: dict):
    lo, hi = cfg.get("experts_held", [0, int(cfg["n_routed_experts"])])
    if hi - lo != int(cfg["n_routed_experts"]):
        raise ValueError("experts_held does not span n_routed_experts")
    return int(lo), int(hi)


def router_width(cfg: dict) -> int:
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < int(cfg["first_k_dense_replace"])


def leaf_shapes(cfg: dict) -> dict:
    """name -> (shape, std or None for a norm's ones). Output projections
    (wo, w2, e_w2, s_w2) are scaled by 1/sqrt(2 * published depth)."""
    if (int(cfg["n_group"]), int(cfg["topk_group"])) != (1, 1):
        raise ValueError("n_group = topk_group = 1 only")
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    qr, kr = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    vd = int(cfg["v_head_dim"])
    v, f = int(cfg["vocab_size"]), int(cfg["intermediate_size"])
    fe = int(cfg["moe_intermediate_size"])
    fs = fe * int(cfg["n_shared_experts"])
    e = int(cfg["n_routed_experts"])
    depth = int(cfg.get("published", {}).get("num_hidden_layers",
                                             cfg["num_hidden_layers"]))
    std, out_std = 0.02, 0.02 / math.sqrt(2 * depth)
    out = {"embed": ((v, d), std), "head": ((d, v), std), "norm_f": ((d,), None)}
    for i in range(int(cfg["num_hidden_layers"])):
        p = f"layers.{i}."
        out.update({p + "ln1": ((d,), None), p + "ln2": ((d,), None),
                    p + "wqa": ((d, qr), std), p + "q_norm": ((qr,), None),
                    p + "wqb": ((qr, h * (nope + rope)), std),
                    p + "wkva": ((d, kr + rope), std),
                    p + "kv_norm": ((kr,), None),
                    p + "wkvb": ((kr, h * (nope + vd)), std),
                    p + "wo": ((h * vd, d), out_std)})
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


def init_leaf(cfg: dict, seed: int, name: str, dtype="float32"):
    """One leaf from the seed, on the device, in `dtype` (the selection bias,
    a buffer, always float32): its numbers depend on the seed and on the
    place of its name in leaf_shapes() alone, so the program can load leaf
    after leaf and never hold the weights twice."""
    shapes = leaf_shapes(cfg)
    shape, std = shapes[name]
    key = jax.random.fold_in(seed_key(seed), list(shapes).index(name))
    return _leaf(key, shape, std,
                 "float32" if name.endswith("e_bias") else str(dtype))


def init_weights(cfg: dict, seed: int, dtype="float32"):
    return {name: init_leaf(cfg, seed, name, dtype)
            for name in leaf_shapes(cfg)}


# ------------------------------------------------------------------ forward
def _rope(x, positions, theta):
    """x [t, heads, dim]; rotates all dim dimensions, i with i + dim/2."""
    dim = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, lp, cfg, mm):
    """x [t, hidden] float32 -> the attention block's addition to x: the
    expanded form, a head at a time (its queries, keys and values made from
    the two latents inside the head's turn, so that no array over all heads
    and 33k tokens exists) in blocks of queries."""
    t = x.shape[0]
    h = int(cfg["num_attention_heads"])
    qr, kr = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    vd, eps = int(cfg["v_head_dim"]), float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    a = _rms(x, lp["ln1"], eps)
    c_q = _rms(mm(a, lp["wqa"]), lp["q_norm"], eps)              # [t, qr]
    kva = mm(a, lp["wkva"])
    c = _rms(kva[:, :kr], lp["kv_norm"], eps)                    # [t, kr]
    pos = jnp.arange(t)
    r = _rope(kva[:, None, kr:], pos, theta)[:, 0]               # [t, rope]
    blk = next((b for b in QUERY_BLOCKS if t % b == 0), t)
    scale = 1.0 / math.sqrt(nope + rope)

    def one_head(w):
        wq, wkv = w                         # [qr, nope + rope], [kr, nope + vd]
        q = mm(c_q, wq)
        q = jnp.concatenate(
            [q[:, :nope], _rope(q[:, None, nope:], pos, theta)[:, 0]], -1)
        kv = mm(c, wkv)
        k, v = jnp.concatenate([kv[:, :nope], r], -1), kv[:, nope:]

        def one_block(i):
            rows = i * blk + jnp.arange(blk)
            s = mm(jax.lax.dynamic_slice_in_dim(q, i * blk, blk), k.T) * scale
            p = jax.nn.softmax(
                jnp.where(pos[None, :] <= rows[:, None], s, -jnp.inf), axis=-1)
            return mm(p, v)

        return jax.lax.map(one_block, jnp.arange(t // blk)).reshape(t, vd)

    by_head = lambda w, d: w.reshape(w.shape[0], h, d).transpose(1, 0, 2)  # noqa: E731
    o = jax.lax.map(one_head, (by_head(lp["wqb"], nope + rope),
                               by_head(lp["wkvb"], nope + vd)))  # [h, t, vd]
    return mm(o.transpose(1, 0, 2).reshape(t, h * vd), lp["wo"])


def routing(m, lp, cfg, mm):
    """[t, published experts] float32: each token's weight on every expert,
    zero off its num_experts_per_tok chosen ones. The bias enters the choice
    alone."""
    s = jax.nn.sigmoid(mm(m, lp["router"]).astype(jnp.float32))
    _, idx = jax.lax.top_k(s + lp["e_bias"].astype(jnp.float32)[None, :],
                           int(cfg["num_experts_per_tok"]))
    top = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * float(cfg["routed_scaling_factor"])
    return jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(top)


def routed_experts(m, lp, cfg, mm, held=None):
    """The routed experts' part of the layer for the experts whose weights
    `lp` holds, ids `held` = [lo, hi) of the published ones: every token
    through every held expert, weighted by its routing weight (zero for an
    expert it was not routed to), one expert at a time."""
    lo, hi = held if held is not None else experts_held(cfg)
    w = routing(m, lp, cfg, mm)[:, lo:hi]                      # [t, held]
    f = int(cfg["moe_intermediate_size"])

    def one_expert(acc, args):
        w13, w2, we = args                      # [d, 2f], [f, d], [t]
        hh = mm(m, w13)
        act = jax.nn.silu(hh[:, :f]) * hh[:, f:] * we[:, None]
        return acc + mm(act, w2), None

    acc, _ = jax.lax.scan(one_expert, jnp.zeros_like(m),
                          (lp["e_w13"], lp["e_w2"], w.T))
    return acc


def mlp(x, lp, cfg, dense, mm, held=None):
    m = _rms(x, lp["ln2"], float(cfg["rms_norm_eps"]))
    if dense:
        return _swiglu(m, lp["w1"], lp["w3"], lp["w2"], mm)
    return routed_experts(m, lp, cfg, mm, held) \
        + _swiglu(m, lp["s_w1"], lp["s_w3"], lp["s_w2"], mm)


def layer_params(params, i):
    p = f"layers.{i}."
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p)}


def hidden_states(params, ids, cfg, mm=highest_matmul):
    """[t] token ids of one row -> final-norm hidden states [t, hidden]."""
    x = params["embed"].astype(jnp.float32)[ids]
    for i in range(int(cfg["num_hidden_layers"])):
        lp = layer_params(params, i)
        x = x + attention(x, lp, cfg, mm)
        x = x + mlp(x, lp, cfg, is_dense(cfg, i), mm)
    return _rms(x, params["norm_f"], float(cfg["rms_norm_eps"]))


def logits_at(params, ids, positions, cfg, mm=highest_matmul):
    """Logits [n, vocab] at the given positions of ONE row of ids [t]: the
    whole row goes through the model, the head only over `positions`."""
    return mm(hidden_states(params, ids, cfg, mm)[positions], params["head"])


# ------------------------------------------------------------ served tokens
def served_gaps(cfg, seed, rows, dtype="bfloat16", precision="highest",
                control=None, width=None, n_pos=None, pad_to=128):
    """rows: [(prompt ids, served token ids)]. One teacher-forced pass of the
    reference over each prompt with its served tokens.

    A served token's gap is how far its reference logit lies below the
    reference's best at that position. Returns per row the gap that nine in
    ten of its served tokens stay within (GAP_QUANTILE), not the widest: with
    4 experts a token at weights of 0.45, a bf16 router within rounding of a
    tie picks another expert at about one position in fourteen, which moves
    that position's logits by up to 1.5 and its token's gap as far as
    reading in fp8 moves every position's (PERF.md section 6, PR 34). So the
    widest gap of a sound row (0.17-1.75) cannot be told from the control's
    (1.07-1.44), and the share of positions that moved can. The widest and
    the mean go to stderr beside it. With `control` (a precision name) it
    reads instead, at the same positions, the gap of the token that the
    lower precision puts first: the control need not decode.
    """
    params = init_weights(cfg, seed, dtype)

    @functools.partial(jax.jit, static_argnames=("mm",))
    def logits(params, ids, positions, mm):
        return logits_at(params, ids, positions, cfg, MATMULS[mm])

    @jax.jit
    def gap(lg, nxt):
        return jnp.max(lg, -1) - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]

    # fixed by the mix where given, so that every run compiles one shape
    width = max([width or 0] + [len(p) + len(t) for p, t in rows])
    width = -(-width // pad_to) * pad_to
    n_pos = max([n_pos or 0] + [len(t) for _, t in rows])
    n_pos = -(-n_pos // pad_to) * pad_to
    gaps = []
    for prompt, toks in rows:
        ids = np.zeros(width, np.int32)
        ids[:len(prompt) + len(toks)] = list(prompt) + list(toks)
        # logits at position len(prompt)-1+j choose served token j
        pos = np.full(n_pos, len(prompt) - 1, np.int32)
        pos[:len(toks)] = len(prompt) - 1 + np.arange(len(toks))
        nxt = np.full(n_pos, toks[0], np.int32)
        nxt[:len(toks)] = toks
        ids, pos, nxt = jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(nxt)
        lg = logits(params, ids, pos, mm=precision)
        if control is not None:
            # one pass at a time: a row of 33k tokens leaves no room for two
            nxt = jnp.argmax(logits(params, ids, pos, mm=control), -1)
        g = np.sort(np.asarray(gap(lg, nxt))[:len(toks)])[::-1]
        gaps.append(float(np.quantile(g, GAP_QUANTILE)))
        print(f"served_gaps {control or 'served'} row of {len(prompt)} + "
              f"{len(toks)}: p90 {gaps[-1]:.4f} widest "
              f"{g[:6].round(3).tolist()} mean {g.mean():.4f} over 0.1: "
              f"{int((g > 0.1).sum())}", file=sys.stderr, flush=True)
    return gaps
