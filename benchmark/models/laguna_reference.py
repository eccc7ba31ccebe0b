"""The plain reference for the `laguna` configurations (poolside Laguna-S-2.1,
config.json `model_type: laguna`), and their seeded weights.

Straightforward jax.numpy in float32 with matmuls at precision "highest": no
kernels, no cache, no batching, nothing imported from the program. The
equations, for layer l of kind t with H = num_attention_heads_per_layer[l]
query heads, 8 K/V heads of 128, input x (every key is the config's):

    a = RMSNorm(x)                          eps rms_norm_eps, no biases anywhere
    q = a Wq [H,128]   k = a Wk [8,128]   v = a Wv [8,128]     (no q/k norm)
    rotary  sliding layers: all 128 dims, inv_freq = 10000^(-2i/128)
            full layers: the first 64 dims (partial_rotary_factor 0.5), the
            other 64 pass through; inv_freq by YaRN over dim 64, base 500000:
            base^(-2i/64) (extrapolated) and that over factor 128
            (interpolated) blended by the linear ramp between the correction
            dims of beta_fast 32 and beta_slow 1 at 8192 original positions;
            cos and sin times attention_factor
    attention  scale 128^-0.5, causal, query head h reads K/V head h // (H/8);
            in sliding layers key j is seen by query i iff i - 512 < j <= i
    gate    g = sigmoid(a Wg), Wg [hidden, H]; head h's output times g[h]
    x <- x + concat(o) Wo ;  m = RMSNorm(x)
    layer in mlp_only_layers:  x <- x + (silu(m W1) * m W3) W2
    other layers:  p = softmax_f32(m Wr) over the PUBLISHED experts (256);
            the num_experts_per_tok largest; w = scale * p_top / sum p_top;
            x <- x + sum_e w_e E_e(m) + S(m);  E_e, S SwiGLU
    last    RMSNorm, then the untied head.

A chip's share: `experts_held` = [lo, hi) are the routed experts whose weights
are here; routing is over all published experts, an absent expert adds
nothing, and that partial result goes on to the next layer. The vocabulary is
the configuration's slice (`vocab_size` rows of embedding and head).

Assumed, where the config names a mechanism and not its formula (the
configuration file lists the same): the per-head gate is the head-wise
sigmoid gate of arXiv:2505.06708 taken from the layer's normalised input;
softmax before top-k; the shared expert ungated; silu; no q/k norm; rotary
pairs dimension i with i + dim/2 ("rotate half"); initialisation.

Departures, so that the same weights mean the same function as in the
program: an expert's gate and up projections are one leaf `e_w13`
[experts, hidden, 2 * width], gate columns first.

`matmul` is the one place precision enters: `highest` is the reference,
`fp8` (operands rounded to float8_e4m3 under a per-tensor scale) the control
for a configuration that states bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_GROUP = 16       # experts cast to float32 at a time (a whole layer's
#                         128 in float32 are 4.8 GB at the published widths)


# -------------------------------------------------------------------- sizes
def layer_kinds(cfg: dict):
    """[(kind, query heads, dense?)] for the layers the configuration keeps."""
    n = int(cfg["num_hidden_layers"])
    dense = set(int(i) for i in cfg["mlp_only_layers"])
    return [("window" if cfg["layer_types"][i] == "sliding_attention"
             else "full", int(cfg["num_attention_heads_per_layer"][i]),
             i in dense) for i in range(n)]


def experts_held(cfg: dict):
    lo, hi = cfg.get("experts_held", [0, int(cfg["num_experts"])])
    if hi - lo != int(cfg["num_experts"]):
        raise ValueError("experts_held does not span num_experts")
    return int(lo), int(hi)


def router_width(cfg: dict) -> int:
    return int(cfg.get("published", {}).get("num_experts", cfg["num_experts"]))


def leaf_shapes(cfg: dict) -> dict:
    """name -> (shape, std or None for a norm's ones). Output projections
    (wo, w2, e_w2, s_w2) are scaled by 1/sqrt(2 * published depth)."""
    d, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    kv = int(cfg["num_key_value_heads"]) * hd
    v, f = int(cfg["vocab_size"]), int(cfg["intermediate_size"])
    fe, fs = int(cfg["moe_intermediate_size"]), \
        int(cfg["shared_expert_intermediate_size"])
    e = int(cfg["num_experts"])
    depth = int(cfg.get("published", {}).get("num_hidden_layers",
                                             cfg["num_hidden_layers"]))
    std, out_std = 0.02, 0.02 / math.sqrt(2 * depth)
    out = {"embed": ((v, d), std), "head": ((d, v), std), "norm_f": ((d,), None)}
    for i, (_, heads, dense) in enumerate(layer_kinds(cfg)):
        p = f"layers.{i}."
        out.update({p + "ln1": ((d,), None), p + "ln2": ((d,), None),
                    p + "wq": ((d, heads * hd), std), p + "wk": ((d, kv), std),
                    p + "wv": ((d, kv), std), p + "wg": ((d, heads), std),
                    p + "wo": ((heads * hd, d), out_std)})
        if dense:
            out.update({p + "w1": ((d, f), std), p + "w3": ((d, f), std),
                        p + "w2": ((f, d), out_std)})
        else:
            out.update({p + "router": ((d, router_width(cfg)), std),
                        p + "e_w13": ((e, d, 2 * fe), std),
                        p + "e_w2": ((e, fe, d), out_std),
                        p + "s_w1": ((d, fs), std), p + "s_w3": ((d, fs), std),
                        p + "s_w2": ((fs, d), out_std)})
    return out


def seed_key(seed: int):
    """Any whole number up to a little over 2**31 (more than int32 holds)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _leaf(key, shape, std, dtype):
    dt = jnp.dtype(dtype)
    if std is None:
        return jnp.ones(shape, dt)
    if len(shape) == 3:     # experts: one at a time, so no float32 copy of
        #                     the whole leaf ever exists
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape[1:], jnp.float32)
                       * std).astype(dt), jax.random.split(key, shape[0]))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)


def init_leaf(cfg: dict, seed: int, name: str, dtype="float32"):
    """One leaf from the seed, on the device, in `dtype`: its numbers depend
    on the seed and on the place of its name in leaf_shapes() alone, so the
    program can load leaf after leaf and never hold the weights twice."""
    shapes = leaf_shapes(cfg)
    shape, std = shapes[name]
    key = jax.random.fold_in(seed_key(seed), list(shapes).index(name))
    return _leaf(key, shape, std, str(dtype))


def init_weights(cfg: dict, seed: int, dtype="float32", per_layer=True):
    """{leaf: array}, every leaf of init_leaf. Layers differ in shape, so
    the leaves are always one per layer."""
    return {name: init_leaf(cfg, seed, name, dtype)
            for name in leaf_shapes(cfg)}


# ------------------------------------------------------------------ matmuls
def highest_matmul(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _fake_quant(x, dtype, top):
    x = x.astype(jnp.float32)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def fp8_matmul(a, b):
    """Both operands rounded to float8_e4m3 under a per-tensor scale."""
    return highest_matmul(_fake_quant(a, jnp.float8_e4m3fn, 448.0),
                          _fake_quant(b, jnp.float8_e4m3fn, 448.0))


def bf16_matmul(a, b):
    """What the configuration states (bf16 operands, fp32 accumulation)."""
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


MATMULS = {"highest": highest_matmul, "fp8": fp8_matmul, "bf16": bf16_matmul}


# ------------------------------------------------------------------- rotary
def rope_inv_freq(cfg: dict, kind: str):
    """(inv_freq [rotary_dim / 2] float64, rotary_dim, attention_factor)."""
    rp = cfg["rope_parameters"]["sliding_attention" if kind == "window"
                                else "full_attention"]
    hd = int(cfg["head_dim"])
    dim = int(hd * float(rp.get("partial_rotary_factor", 1)))
    base = float(rp["rope_theta"])
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp["rope_type"] == "default":
        return 1.0 / pos_freqs, dim, 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r} is not written down")
    factor, orig = float(rp["factor"]), float(rp["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rp["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1.0 - ramp)
    return inv, dim, float(rp["attention_factor"])


def _rope(x, positions, inv_freq, dim, factor):
    """x [t, heads, head_dim]; rotates the first `dim` dimensions."""
    ang = positions.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


# ------------------------------------------------------------------ forward
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def attention(x, lp, cfg, kind, heads, mm):
    """x [t, hidden] float32 -> the attention block's addition to x."""
    t = x.shape[0]
    hd, nkv = int(cfg["head_dim"]), int(cfg["num_key_value_heads"])
    g = heads // nkv
    a = _rms(x, lp["ln1"], float(cfg["rms_norm_eps"]))
    q = mm(a, lp["wq"]).reshape(t, heads, hd)
    k = mm(a, lp["wk"]).reshape(t, nkv, hd)
    v = mm(a, lp["wv"]).reshape(t, nkv, hd)
    pos = jnp.arange(t)
    inv, dim, af = rope_inv_freq(cfg, kind)
    q, k = _rope(q, pos, inv, dim, af), _rope(k, pos, inv, dim, af)
    seen = pos[None, :] <= pos[:, None]
    if kind == "window":
        seen = seen & (pos[None, :] > pos[:, None] - int(cfg["sliding_window"]))

    qt, kt, vt = (z.transpose(1, 0, 2) for z in (q, k, v))    # [heads, t, hd]

    def one_head(h):                # a head at a time: [t, t] scores fit
        s = mm(qt[h], kt[h // g].T) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return mm(p, vt[h // g])

    o = jax.lax.map(one_head, jnp.arange(heads)).transpose(1, 0, 2)
    gate = jax.nn.sigmoid(mm(a, lp["wg"]))                   # [t, heads]
    return mm((o * gate[..., None]).reshape(t, heads * hd), lp["wo"])


def _swiglu(m, w1, w3, w2, mm):
    return mm(jax.nn.silu(mm(m, w1)) * mm(m, w3), w2)


def routing(m, router, cfg, mm):
    """[t, published experts] float32: each token's weight on every expert,
    zero off its num_experts_per_tok largest."""
    p = jax.nn.softmax(mm(m, router).astype(jnp.float32), axis=-1)
    top, idx = jax.lax.top_k(p, int(cfg["num_experts_per_tok"]))
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * float(cfg["moe_routed_scaling_factor"])
    return jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None], idx].set(top)


def routed_experts(m, lp, cfg, mm, held=None):
    """The routed experts' part of the layer for the experts whose weights
    `lp` holds, ids `held` = [lo, hi) of the published ones: every token
    through every held expert, weighted by its routing weight (zero for an
    expert it was not routed to), a group of experts at a time."""
    lo, hi = held if held is not None else experts_held(cfg)
    w = routing(m, lp["router"], cfg, mm)[:, lo:hi]            # [t, held]
    f = int(cfg["moe_intermediate_size"])
    n = hi - lo
    grp = math.gcd(n, EXPERT_GROUP)

    def one_group(acc, args):
        w13, w2, wg = args              # [grp, d, 2f], [grp, f, d], [grp, t]
        h = mm(m[None], w13)                                   # [grp, t, 2f]
        act = jax.nn.silu(h[..., :f]) * h[..., f:] * wg[..., None]
        return acc + jnp.sum(mm(act, w2), axis=0), None

    acc, _ = jax.lax.scan(
        one_group, jnp.zeros_like(m),
        (lp["e_w13"].reshape(n // grp, grp, *lp["e_w13"].shape[1:]),
         lp["e_w2"].reshape(n // grp, grp, *lp["e_w2"].shape[1:]),
         w.T.reshape(n // grp, grp, -1)))
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
    for i, (kind, heads, dense) in enumerate(layer_kinds(cfg)):
        lp = layer_params(params, i)
        x = x + attention(x, lp, cfg, kind, heads, mm)
        x = x + mlp(x, lp, cfg, dense, mm)
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

    Returns per row the widest gap by which a served token's reference logit
    lies below the reference's best at that position. With `control` (a
    precision name) it reads instead, at the same positions, the gap of the
    token that the lower precision puts first: the control need not decode.
    """
    params = init_weights(cfg, seed, dtype)
    mm = MATMULS[precision]

    @functools.partial(jax.jit, static_argnames=("lower",))
    def run(params, ids, positions, nxt, lower=None):
        lg = logits_at(params, ids, positions, cfg, mm)
        lg = jax.lax.optimization_barrier(lg)
        best = jnp.max(lg, -1)
        if lower is not None:
            low = logits_at(params, ids, positions, cfg, MATMULS[lower])
            nxt = jnp.argmax(low, -1)
        got = jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]
        return best - got

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
        g = np.asarray(run(params, jnp.asarray(ids), jnp.asarray(pos),
                           jnp.asarray(nxt), lower=control))
        gaps.append(float(g[:len(toks)].max()))
    return gaps
