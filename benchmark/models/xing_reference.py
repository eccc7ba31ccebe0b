"""The plain reference for the `xing` configurations (XingChen-AGI
Xing4.0-29B-A4B, config.json `model_type: xing4_0`), and their seeded
weights.

Straightforward jax.numpy in float32 with matmuls at precision "highest": no
kernels, no cache, no batching, nothing imported from the program. The
residual state of a token is X in R^{n x C}, n = hc_mult streams of C =
hidden_size (manifold-constrained hyper-connections, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606). A layer is two sublayers F (latent
attention, then a dense SwiGLU or the experts), each with its own RMSNorm
inside it. Around EACH sublayer, with its own phi, a, b (every key is the
config's):

    x~      = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)    one norm over n C values
    H~_pre  = a_pre  * (x~ phi_pre)  + b_pre            [n]
    H~_post = a_post * (x~ phi_post) + b_post           [n]
    H~_res  = a_res  * mat(x~ phi_res) + b_res          [n, n]
    H_pre   = sigmoid(H~_pre)      H_post = 2 sigmoid(H~_post)
    M_0     = exp(clip(H~_res, mhc_h_res_clamp_min, mhc_h_res_clamp_max))
    M_t     = rows(cols(M_{t-1})), t = 1..hc_sinkhorn_iters
              cols: each column over (its sum + hc_eps); rows likewise
    H_res   = M_last
    u       = H_pre X                                   [C], F's input
    y       = F(u)
    X'      = H_res X + H_post^T y                      [n, C]

Open: X_0 is the token's embedding in each of the n streams; close: the last
RMSNorm and the untied head see the sum of the streams. All `hc_sinkhorn_iters`
rounds run. The maps and what they are made from are float32 at precision
highest whatever `mm` is (the configuration states them in float32, so a
control one precision down does not touch them).

F, attention (H heads): GLM-MoE-Lite's latent attention with a value of
v_head_dim beside a key of qk_nope_head_dim + qk_rope_head_dim, and YaRN:

    a = RMSNorm(u)   c_q = RMSNorm(a Wqa)   q = c_q Wqb, a head [q_nope, q_rope]
    [c_raw, r_raw] = a Wkva    c = RMSNorm(c_raw)    r = RoPE(r_raw), ONE key
    RoPE    rotate-half over all qk_rope_head_dim dims; inverse frequencies
            theta^(-2i/d) (extrapolated) and that over `factor` (interpolated)
            blended by the linear ramp between the correction dims of
            beta_fast and beta_slow at original_max_position_embeddings;
            cos and sin times m(mscale) / m(mscale_all_dim),
            m(s) = 0.1 s ln(factor) + 1
    [k_nope_h, v_h] = c Wkvb
    score_h(t, s) = (q_nope_h(t).k_nope_h(s) + q_rope_h(t).r(s))
                    * m(mscale_all_dim)^2 / sqrt(nope + rope), causal softmax
    y = concat_h(sum_s p v_h) Wo
F, feed-forward: m = RMSNorm(u); layer < first_k_dense_replace a SwiGLU;
others GLM-MoE-Lite's router (sigmoid scores over the PUBLISHED experts, the
num_experts_per_tok largest of score + e_score_correction_bias, weights the
scores without it, normalised, times routed_scaling_factor; n_group =
topk_group = 1) over the experts held and one ungated shared SwiGLU: the
functions of glm_moe_lite_reference, which read the same keys.

The multi-token-prediction module is left out.

Assumed (the configuration file lists the same): the order columns then rows
and hc_eps inside both divisions; the clamp before exp; one norm over the
flattened streams with no learned weight; the open (copy) and the close
(sum); rotate-half pairing; the norms' places and the ungated shared expert;
the seeded values of phi, a, b (`hc_leaf`); b's N(0, 0.02); initialisation.

Departures, so that the same weights mean the same function as in the
program: an expert's gate and up projections are one leaf `e_w13`; the three
phi of a sublayer are one leaf `hc_*.phi` [n (n + 2), n C], TRANSPOSED (a row
a map: n rows of phi_pre^T, n of phi_post^T, n n of phi_res^T, row i n + j
the map of H_res[i, j]); `hc_*.a` is [a_pre, a_post, a_res], `hc_*.b`
[b_pre, b_post, vec(b_res)].

Controls (`served_gaps(control=)`, never the reference): "fp8" (every matmul
but the maps' one precision down), "plain_residual" (H_res = I, H_pre = 1/n,
H_post = 2/n: the n streams stay copies of one plain residual stream),
"static_maps" (a = 0: the maps forget the token).

Sized for rows of 17.7k tokens at 11 GB of bf16 weights on a 16 GB chip:
`served_gaps` makes a layer's weights from the seed when the row reaches the
layer and drops them after it; attention a head at a time in blocks of
queries, the routed experts one at a time.
"""
from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

# nothing of the program: the seed's keys, the leaf maker, the one place
# precision enters, and GLM-MoE-Lite's feed-forward (the same router keys)
from .glm_moe_lite_reference import (BIAS_STD, GAP_QUANTILE, QUERY_BLOCKS,  # noqa: F401
                                     experts_held, is_dense, layer_params,
                                     mlp, router_width)
from .laguna_reference import (MATMULS, _leaf, _rms, highest_matmul,  # noqa: F401
                               seed_key)

# the seeded maps (the configuration's `assumed` gives the readings behind
# them). phi N(0, 1 / (n C)), so x~ phi is N(0, 1) a map; a = HC_A each: the
# part of a map's logit that follows the token has std 0.5. b_pre N(0, 1);
# b_post N(-3, 0.5): H_post about 0.1, or the dense layer's 9,216-wide
# SwiGLU alone lifts the streams' RMS to 9 times the embedding's (at N(0, 1):
# measured, PERF.md section 6, PR 36); b_res = I + N(0, 0.3): leaning to the
# diagonal, as a trained model's maps stay near the identity, and no
# further: twenty rounds leave a column sum off 1 by more than 1e-3 at 0.26%
# of tokens under 2 I + N(0, 0.5), at 15% under 3 I, at none of 2 million
# under I + N(0, 0.3) (the widest 3.3e-4; numpy, PR 36)
HC_A = 0.5
HC_B_MEAN = (0.0, -3.0, 0.0)
HC_B_STD = (1.0, 0.5, 0.3)
HC_RES_DIAG = 1.0
RESIDUALS = ("mhc", "plain_residual", "static_maps")


# -------------------------------------------------------------------- sizes
def streams(cfg: dict) -> int:
    return int(cfg["hc_mult"])


def n_maps(cfg: dict) -> int:
    n = streams(cfg)
    return n * (n + 2)


def leaf_shapes(cfg: dict) -> dict:
    """name -> (shape, std; None for a norm's ones, "hc_a" / "hc_b" for the
    maps' scalars and biases). std is `initializer_range` (0.02 where the
    file has none: the published config gives none; a tiny test
    configuration states a larger one, so that at hidden 64 a sublayer
    weighs in the streams as it does at 3,584). Output projections (wo, w2,
    e_w2, s_w2) are scaled by 1/sqrt(2 * published depth)."""
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
    std = float(cfg.get("initializer_range", 0.02))
    out_std = std / math.sqrt(2 * depth)
    nc, m = streams(cfg) * d, n_maps(cfg)
    out = {"embed": ((v, d), std), "head": ((d, v), std), "norm_f": ((d,), None)}
    for i in range(int(cfg["num_hidden_layers"])):
        p = f"layers.{i}."
        for hc in ("hc_attn.", "hc_mlp."):
            out.update({p + hc + "phi": ((m, nc), 1.0 / math.sqrt(nc)),
                        p + hc + "a": ((3,), "hc_a"),
                        p + hc + "b": ((m,), "hc_b")})
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


def float32_leaf(name: str) -> bool:
    """The maps' parameters and the selection bias are float32 whatever the
    weights' dtype."""
    return name.endswith("e_bias") or ".hc_" in name


def hc_leaf(key, kind: str, n: int):
    """`hc_a`: [a_pre, a_post, a_res] = HC_A. `hc_b`: [b_pre, b_post,
    vec(b_res)], normal about HC_B_MEAN with HC_B_STD by group, b_res about
    HC_RES_DIAG I."""
    if kind == "hc_a":
        return jnp.full((3,), HC_A, jnp.float32)
    z = jax.random.normal(key, (n * (n + 2),), jnp.float32)
    std = np.repeat(np.asarray(HC_B_STD, np.float32), [n, n, n * n])
    mean = np.repeat(np.asarray(HC_B_MEAN, np.float32), [n, n, n * n])
    mean[2 * n:] += HC_RES_DIAG * np.eye(n, dtype=np.float32).ravel()
    return z * std + mean


def init_leaf(cfg: dict, seed: int, name: str, dtype="float32"):
    """One leaf from the seed, on the device, in `dtype` (float32_leaf()'s
    always float32): its numbers depend on the seed and on the place of its
    name in leaf_shapes() alone."""
    shapes = leaf_shapes(cfg)
    shape, std = shapes[name]
    key = jax.random.fold_in(seed_key(seed), list(shapes).index(name))
    if isinstance(std, str):
        return hc_leaf(key, std, streams(cfg))
    return _leaf(key, shape, std,
                 "float32" if float32_leaf(name) else str(dtype))


def init_weights(cfg: dict, seed: int, dtype="float32"):
    return {name: init_leaf(cfg, seed, name, dtype)
            for name in leaf_shapes(cfg)}


# -------------------------------------------------------------------- rotary
def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary(cfg: dict):
    """(inverse frequencies [rope / 2], factor on cos and sin, softmax
    scale) of the configuration's rope_scaling (yarn, or none)."""
    dim = int(cfg["qk_rope_head_dim"])
    base = float(cfg["rope_theta"])
    plain = 1.0 / math.sqrt(int(cfg["qk_nope_head_dim"]) + dim)
    freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = cfg.get("rope_scaling")
    if not rs:
        return 1.0 / freqs, 1.0, plain
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling type {rs['type']!r} is not written down")
    factor = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * freqs)) * ramp + (1.0 / freqs) * (1.0 - ramp)
    m_all = yarn_mscale(factor, float(rs["mscale_all_dim"]))
    return inv, yarn_mscale(factor, float(rs["mscale"])) / m_all, \
        m_all * m_all * plain


def _rope(x, positions, inv_freq, factor):
    """x [t, heads, dim]; rotates all dim dimensions, i with i + dim/2."""
    dim = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ------------------------------------------------------------------ forward
def attention(u, lp, cfg, mm):
    """u [t, hidden] float32, the sublayer's input -> its output y: the
    expanded form, a head at a time in blocks of queries."""
    t = u.shape[0]
    h = int(cfg["num_attention_heads"])
    kr = int(cfg["kv_lora_rank"])
    nope = int(cfg["qk_nope_head_dim"])
    vd, eps = int(cfg["v_head_dim"]), float(cfg["rms_norm_eps"])
    inv_freq, factor, scale = rotary(cfg)
    a = _rms(u, lp["ln1"], eps)
    c_q = _rms(mm(a, lp["wqa"]), lp["q_norm"], eps)
    kva = mm(a, lp["wkva"])
    c = _rms(kva[:, :kr], lp["kv_norm"], eps)
    pos = jnp.arange(t)
    r = _rope(kva[:, None, kr:], pos, inv_freq, factor)[:, 0]
    blk = next((b for b in QUERY_BLOCKS if t % b == 0), t)

    def one_head(w):
        wq, wkv = w
        q = mm(c_q, wq)
        q = jnp.concatenate(
            [q[:, :nope], _rope(q[:, None, nope:], pos, inv_freq, factor)[:, 0]],
            -1)
        kv = mm(c, wkv)
        k, v = jnp.concatenate([kv[:, :nope], r], -1), kv[:, nope:]

        def one_block(i):
            rows = i * blk + jnp.arange(blk)
            s = mm(jax.lax.dynamic_slice_in_dim(q, i * blk, blk), k.T) * scale
            p = jax.nn.softmax(
                jnp.where(pos[None, :] <= rows[:, None], s, -jnp.inf), axis=-1)
            return mm(p, v)

        return jax.lax.map(one_block, jnp.arange(t // blk)).reshape(t, vd)

    by_head = lambda w: w.reshape(w.shape[0], h, -1).transpose(1, 0, 2)  # noqa: E731
    o = jax.lax.map(one_head, (by_head(lp["wqb"]), by_head(lp["wkvb"])))
    return mm(o.transpose(1, 0, 2).reshape(t, h * vd), lp["wo"])


def sinkhorn(m, iters: int, eps: float):
    """m [t, n, n] positive -> doubly stochastic to within the rounds run:
    each column over (its sum + eps), then each row likewise, `iters` times."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def hc_maps(x, hp, cfg, residual="mhc"):
    """x [t, n, C] -> (H_pre [t, n], H_post [t, n], H_res [t, n, n]) of one
    sublayer with parameters hp = {"phi", "a", "b"}."""
    t, n, _ = x.shape
    if residual == "plain_residual":
        return (jnp.full((t, n), 1.0 / n), jnp.full((t, n), 2.0 / n),
                jnp.broadcast_to(jnp.eye(n), (t, n, n)))
    eps = float(cfg["hc_eps"])
    v = x.reshape(t, -1)
    xt = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True) + eps)
    a = hp["a"] * (0.0 if residual == "static_maps" else 1.0)
    h = highest_matmul(xt, hp["phi"].T)                       # [t, n (n + 2)]
    b = hp["b"]
    h_pre = a[0] * h[:, :n] + b[:n]
    h_post = a[1] * h[:, n:2 * n] + b[n:2 * n]
    h_res = (a[2] * h[:, 2 * n:] + b[2 * n:]).reshape(t, n, n)
    m0 = jnp.exp(jnp.clip(h_res, float(cfg["mhc_h_res_clamp_min"]),
                          float(cfg["mhc_h_res_clamp_max"])))
    return (jax.nn.sigmoid(h_pre), 2.0 * jax.nn.sigmoid(h_post),
            sinkhorn(m0, int(cfg["hc_sinkhorn_iters"]), eps))


def sublayer(x, hp, f, cfg, residual="mhc"):
    """X' = H_res X + H_post^T F(H_pre X)."""
    pre, post, res = hc_maps(x, hp, cfg, residual)
    y = f(jnp.einsum("tj,tjc->tc", pre, x))
    return jnp.einsum("tij,tjc->tic", res, x) + post[:, :, None] * y[:, None, :]


def hc_params(lp, which):
    return {k: lp[f"hc_{which}.{k}"] for k in ("phi", "a", "b")}


def layer(x, lp, cfg, dense, mm=highest_matmul, residual="mhc", held=None):
    """x [t, n, C] -> the layer's output streams. glm_moe_lite_reference.mlp
    holds its own RMSNorm (ln2), as attention() holds ln1."""
    x = sublayer(x, hc_params(lp, "attn"),
                 lambda u: attention(u, lp, cfg, mm), cfg, residual)
    return sublayer(x, hc_params(lp, "mlp"),
                    lambda u: mlp(u, lp, cfg, dense, mm, held), cfg, residual)


def open_streams(embed, ids, cfg):
    e = embed[ids].astype(jnp.float32)
    return jnp.broadcast_to(e[:, None, :], (e.shape[0], streams(cfg), e.shape[1]))


def close_streams(x, norm_f, cfg):
    return _rms(jnp.sum(x, axis=1), norm_f, float(cfg["rms_norm_eps"]))


def hidden_states(params, ids, cfg, mm=highest_matmul, residual="mhc"):
    """[t] token ids of one row -> final-norm hidden states [t, hidden]."""
    x = open_streams(params["embed"], ids, cfg)
    for i in range(int(cfg["num_hidden_layers"])):
        x = layer(x, layer_params(params, i), cfg, is_dense(cfg, i), mm,
                  residual)
    return close_streams(x, params["norm_f"], cfg)


def logits_at(params, ids, positions, cfg, mm=highest_matmul, residual="mhc"):
    """Logits [n, vocab] at the given positions of ONE row of ids [t]."""
    return mm(hidden_states(params, ids, cfg, mm, residual)[positions],
              params["head"])


# ------------------------------------------------------------ served tokens
def _row_logits(cfg, seed, dtype, top, ids, positions, mm, residual):
    """logits_at over weights made a layer at a time from the seed."""
    names = list(leaf_shapes(cfg))

    @functools.partial(jax.jit, static_argnames=("dense",))
    def one_layer(x, lp, dense):
        return layer(x, lp, cfg, dense, MATMULS[mm], residual)

    x = jax.jit(lambda e, ids: open_streams(e, ids, cfg))(top["embed"], ids)
    for i in range(int(cfg["num_hidden_layers"])):
        p = f"layers.{i}."
        lp = {k[len(p):]: init_leaf(cfg, seed, k, dtype)
              for k in names if k.startswith(p)}
        x = one_layer(x, lp, dense=is_dense(cfg, i))
        del lp
    return jax.jit(lambda x, nf, head, pos: MATMULS[mm](
        close_streams(x, nf, cfg)[pos], head))(x, top["norm_f"], top["head"],
                                               positions)


def served_gaps(cfg, seed, rows, dtype="bfloat16", precision="highest",
                control=None, width=None, n_pos=None, pad_to=128):
    """rows: [(prompt ids, served token ids)]. One teacher-forced pass of the
    reference over each prompt with its served tokens; per row the gap that
    nine in ten of its served tokens stay within (GAP_QUANTILE: see
    glm_moe_lite_reference.served_gaps, whose statistic and whose reasons
    these are: 4 experts a token, a bf16 router near a tie). With `control`
    it reads instead, at the same positions, the gap of the token that the
    control puts first. `control="fp8"` (the harness's --with-control) goes
    over the residual controls too and prints them to stderr beside it: the
    builder's readings for the limits file."""
    top = {k: init_leaf(cfg, seed, k, dtype)
           for k in ("embed", "head", "norm_f")}

    @jax.jit
    def gap(lg, nxt):
        return jnp.max(lg, -1) - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]

    width = max([width or 0] + [len(p) + len(t) for p, t in rows])
    width = -(-width // pad_to) * pad_to
    n_pos = max([n_pos or 0] + [len(t) for _, t in rows])
    n_pos = -(-n_pos // pad_to) * pad_to
    controls = [control] if control != "fp8" else ["fp8", *RESIDUALS[1:]]
    gaps = {c: [] for c in controls}
    for prompt, toks in rows:
        ids = np.zeros(width, np.int32)
        ids[:len(prompt) + len(toks)] = list(prompt) + list(toks)
        pos = np.full(n_pos, len(prompt) - 1, np.int32)
        pos[:len(toks)] = len(prompt) - 1 + np.arange(len(toks))
        served = np.full(n_pos, toks[0], np.int32)
        served[:len(toks)] = toks
        ids, pos = jnp.asarray(ids), jnp.asarray(pos)
        lg = _row_logits(cfg, seed, dtype, top, ids, pos, precision, "mhc")
        for c in controls:
            nxt = jnp.asarray(served)
            if c in MATMULS:
                nxt = jnp.argmax(_row_logits(cfg, seed, dtype, top, ids, pos,
                                             c, "mhc"), -1)
            elif c is not None:
                nxt = jnp.argmax(_row_logits(cfg, seed, dtype, top, ids, pos,
                                             precision, c), -1)
            g = np.sort(np.asarray(gap(lg, nxt))[:len(toks)])[::-1]
            gaps[c].append(float(np.quantile(g, GAP_QUANTILE)))
            print(f"served_gaps {c or 'served'} row of {len(prompt)} + "
                  f"{len(toks)}: p90 {gaps[c][-1]:.4f} widest "
                  f"{g[:6].round(3).tolist()} mean {g.mean():.4f} over 0.1: "
                  f"{int((g > 0.1).sum())}", file=sys.stderr, flush=True)
    return gaps[controls[0]]
