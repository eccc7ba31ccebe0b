"""The plain reference for the GPT configurations, and their seeded weights.

Straightforward jax.numpy in float32 with matmuls at precision "highest": no
kernels, no cache, no batching tricks, nothing imported from the program. It
follows Radford et al. 2019 / Brown et al. 2020 (pre-LN decoder, learned
positions, tanh GELU, tied head). One departure, so that the same weights mean
the same function as in the program: the fused QKV projection's columns are
ordered head by head, [q_h | k_h | v_h] for each head h.

`matmul` is the one place precision enters. `HIGHEST` is the reference;
`fp8_matmul` (operands rounded to float8_e4m3 under a per-tensor scale, the
usual fp8 recipe) is the control for a configuration that states bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
                "ln2_g", "ln2_b", "fc_w", "fc_b", "out_w", "out_b")
TOP_LEAVES = ("wte", "wpe", "lnf_g", "lnf_b")
LN_EPS = 1e-5


def sizes(cfg: dict):
    h, nl = int(cfg["hidden_size"]), int(cfg["num_layers"])
    f = int(cfg.get("intermediate_size") or 4 * h)
    return (int(cfg["vocab_size"]), h, nl, int(cfg["num_heads"]), f,
            int(cfg["max_position_embeddings"]))


def seed_key(seed: int):
    """Any whole number up to a little over 2**31 (more than int32 holds)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "per_layer"))
def _init(key, shape, dtype, per_layer):
    v, h, nl, _, f, p = shape
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(dtype)

    def normal(k, shp, std):
        return (jax.random.normal(k, shp, jnp.float32) * std).astype(dt)

    out = {
        "wte": normal(ks[0], (v, h), 0.02),
        "wpe": normal(ks[1], (p, h), 0.02),
        "lnf_g": jnp.ones((h,), dt), "lnf_b": jnp.zeros((h,), dt),
        "ln1_g": jnp.ones((nl, h), dt), "ln1_b": jnp.zeros((nl, h), dt),
        "ln2_g": jnp.ones((nl, h), dt), "ln2_b": jnp.zeros((nl, h), dt),
        "qkv_w": normal(ks[2], (nl, h, 3 * h), 0.02),
        "qkv_b": jnp.zeros((nl, 3 * h), dt),
        # GPT-2: residual projections scaled by 1/sqrt(2 * layers)
        "proj_w": normal(ks[3], (nl, h, h), 0.02 / math.sqrt(2 * nl)),
        "proj_b": jnp.zeros((nl, h), dt),
        "fc_w": normal(ks[4], (nl, h, f), 0.02),
        "fc_b": jnp.zeros((nl, f), dt),
        "out_w": normal(ks[5], (nl, f, h), 0.02 / math.sqrt(2 * nl)),
        "out_b": jnp.zeros((nl, h), dt),
    }
    if per_layer:
        flat = {k: out[k] for k in TOP_LEAVES}
        for name in LAYER_LEAVES:
            for i in range(nl):
                flat[f"blocks.{i}.{name}"] = out[name][i]
        return flat
    return out


def init_weights(cfg: dict, seed: int, dtype="float32", per_layer=False):
    """Weights from the seed, on the device, in one jitted call, in `dtype`.
    Stacked over layers ([L, ...], what `forward` scans), or with
    per_layer=True one leaf per layer under "blocks.<i>.<leaf>": the same
    numbers either way."""
    return _init(seed_key(seed), sizes(cfg), str(dtype), bool(per_layer))


def leaf_names(cfg: dict):
    nl = int(cfg["num_layers"])
    return list(TOP_LEAVES) + [f"blocks.{i}.{n}" for n in LAYER_LEAVES
                               for i in range(nl)]


# ------------------------------------------------------------------ matmuls
def highest_matmul(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _fake_quant(x, dtype, top):
    x = x.astype(jnp.float32)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(dtype).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)      # straight-through


def fp8_matmul(a, b):
    """Both operands rounded to float8_e4m3 under a per-tensor scale."""
    return highest_matmul(_fake_quant(a, jnp.float8_e4m3fn, 448.0),
                          _fake_quant(b, jnp.float8_e4m3fn, 448.0))


def bf16_matmul(a, b):
    """What the configuration states (bf16 operands, fp32 accumulation):
    used by tests to show the limits let the stated precision pass."""
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


MATMULS = {"highest": highest_matmul, "fp8": fp8_matmul, "bf16": bf16_matmul}


# ------------------------------------------------------------------ forward
def _ln(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _layer(x, lp, n_heads, mm):
    b, t, h = x.shape
    hd = h // n_heads
    a = _ln(x, lp["ln1_g"], lp["ln1_b"])
    qkv = mm(a, lp["qkv_w"]) + lp["qkv_b"].astype(jnp.float32)
    qkv = qkv.reshape(b, t, n_heads, 3 * hd)
    q, k, v = qkv[..., :hd], qkv[..., hd:2 * hd], qkv[..., 2 * hd:]
    q, k, v = (z.transpose(0, 2, 1, 3) for z in (q, k, v))   # [b, nh, t, hd]
    s = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm(p, v).transpose(0, 2, 1, 3).reshape(b, t, h)
    x = x + mm(o, lp["proj_w"]) + lp["proj_b"].astype(jnp.float32)
    m = _ln(x, lp["ln2_g"], lp["ln2_b"])
    m = _gelu_tanh(mm(m, lp["fc_w"]) + lp["fc_b"].astype(jnp.float32))
    return x + mm(m, lp["out_w"]) + lp["out_b"].astype(jnp.float32)


def hidden_states(params, ids, n_heads, mm=highest_matmul, remat=False):
    """[b, t] token ids -> final-norm hidden states [b, t, h], float32."""
    t = ids.shape[1]
    x = params["wte"].astype(jnp.float32)[ids] \
        + params["wpe"].astype(jnp.float32)[:t][None]
    layer = functools.partial(_layer, n_heads=n_heads, mm=mm)
    if remat:
        layer = jax.checkpoint(layer)

    def body(x, lp):
        return layer(x, lp), None

    x, _ = jax.lax.scan(body, x, {k: params[k] for k in LAYER_LEAVES})
    return _ln(x, params["lnf_g"], params["lnf_b"])


def logits_at(params, ids, positions, n_heads, mm=highest_matmul):
    """Logits [n, vocab] at the given positions of ONE row of ids [t]: the
    whole row goes through the model, only the head is cut to `positions`
    so that long rows fit."""
    hs = hidden_states(params, ids[None], n_heads, mm)[0]
    return mm(hs[positions], params["wte"].astype(jnp.float32).T)


def loss_fn(params, ids, n_heads, mm=highest_matmul, remat=True):
    """Mean next-token cross-entropy over [b, t] ids (labels = ids shifted)."""
    hs = hidden_states(params, ids, n_heads, mm, remat=remat)
    lg = mm(hs[:, :-1], params["wte"].astype(jnp.float32).T)
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


# ------------------------------------------------------------- train steps
def comparison_parts(name, x, cfg):
    """One program-sized leaf as the parts whose norms are compared. The
    fused QKV bias is three leaves in one: its key part has no gradient
    under softmax (it moves under Adam by round-off alone) and would hide in
    the norm of the whole, so q, k and v are compared apart."""
    if name.endswith("qkv_b"):
        r = x.reshape(int(cfg["num_heads"]), 3, -1)
        return {name + ".q": r[:, 0], name + ".k": r[:, 1], name + ".v": r[:, 2]}
    return {name: x}


def _leaf_norms(tree, cfg):
    """name -> l2 norm, one entry per compared part of a per-layer leaf."""
    out = {}
    for k in TOP_LEAVES:
        out[k] = tree[k]
    for k in LAYER_LEAVES:
        for i in range(tree[k].shape[0]):
            out.update(comparison_parts(f"blocks.{i}.{k}", tree[k][i], cfg))
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in out.items()}


def train_reference(cfg, seed, batches, opt, precision="highest",
                    row_block=2, half_batch=False):
    """Follow the first len(batches) AdamW steps from the seed's weights.

    Returns {"losses": [...], "grad_norm": {leaf: norm of the first
    gradient}, "change_norm": {leaf: norm of parameters' change after the
    last step}}. Gradients are summed over blocks of `row_block` rows so a
    float32 backward fits beside the optimizer state. `half_batch` plants
    the fault "half of the batch left out, the mean taken over the rest".
    """
    mm = MATMULS[precision]
    n_heads = int(cfg["num_heads"])
    lr, wd = float(opt["learning_rate"]), float(opt["weight_decay"])
    b1, b2, eps = float(opt["beta1"]), float(opt["beta2"]), float(opt["epsilon"])

    grad_block = jax.jit(jax.value_and_grad(
        lambda p, ids: loss_fn(p, ids, n_heads, mm)))

    @jax.jit
    def adamw(p, g, m, v, t):
        def one(p, g, m, v):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
            mh, vh = m / (1 - b1 ** t), v / (1 - b2 ** t)
            return p * (1.0 - lr * wd) - lr * mh / (jnp.sqrt(vh) + eps), m, v
        out = {k: one(p[k], g[k], m[k], v[k]) for k in p}
        return ({k: o[0] for k, o in out.items()},
                {k: o[1] for k, o in out.items()},
                {k: o[2] for k, o in out.items()})

    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    scale = jax.jit(lambda a, s: jax.tree_util.tree_map(lambda x: x * s, a))
    norms = jax.jit(lambda a: _leaf_norms(a, cfg))
    diff_norms = jax.jit(lambda a, b: _leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b), cfg))

    p0 = init_weights(cfg, seed, "float32")
    p = p0
    m = jax.tree_util.tree_map(jnp.zeros_like, p0)
    v = jax.tree_util.tree_map(jnp.zeros_like, p0)
    losses, grad_norm = [], None
    for t, ids in enumerate(batches, start=1):
        ids = np.asarray(ids, np.int32)
        if half_batch:
            ids = ids[:ids.shape[0] // 2]
        blocks = [ids[i:i + row_block] for i in range(0, ids.shape[0], row_block)]
        loss, g = 0.0, None
        for blk in blocks:
            l, gb = grad_block(p, jnp.asarray(blk))
            loss += float(l) * blk.shape[0] / ids.shape[0]
            gb = scale(gb, blk.shape[0] / ids.shape[0])
            g = gb if g is None else add(g, gb)
        losses.append(loss)
        if grad_norm is None:
            grad_norm = {k: float(x) for k, x in norms(g).items()}
        p, m, v = adamw(p, g, m, v, float(t))
    change = {k: float(x) for k, x in diff_norms(p, p0).items()}
    return {"losses": losses, "grad_norm": grad_norm, "change_norm": change}


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
    n_heads = int(cfg["num_heads"])
    params = init_weights(cfg, seed, dtype)
    mm = MATMULS[precision]

    @functools.partial(jax.jit, static_argnames=("lower",))
    def run(params, ids, positions, nxt, lower=None):
        lg = logits_at(params, ids, positions, n_heads, mm)
        lg = jax.lax.optimization_barrier(lg)
        best = jnp.max(lg, -1)
        if lower is not None:
            low = logits_at(params, ids, positions, n_heads, MATMULS[lower])
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
