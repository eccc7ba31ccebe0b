"""GLM-5.3-Flash (models/glm5_flash.py): delta-rule linear-attention layers
with a recurrent state a slot (ops/pallas/delta_rule.py), learned sparse
latent attention with a pooled indexer cache (ops/pallas/sparse_latent.py),
the four-stream residual path and clipped sparse experts, served through
ServingEngine, against the plain reference (benchmark/models/
glm5_flash_reference.py): seeded random weights at a small size on the CPU.

Tolerances, each with its reason, are by the tests that use them."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.models import glm5_flash_program as prog
from benchmark.models import glm5_flash_reference as ref
from paddle_tpu.core import flags
from paddle_tpu.models import Glm5FlashConfig
from paddle_tpu.models.generation import LayerCacheSpec
from paddle_tpu.observability.registry import default_registry
from paddle_tpu.ops.kernels import nn_ops
from paddle_tpu.ops.pallas import delta_rule as dr
from paddle_tpu.ops.pallas import sparse_latent as sl
from paddle_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIN, SP = ref.LINEAR, ref.SPARSE
# at hidden 64 an initialisation of 0.02 leaves a sublayer a hundredth of the
# embedding; 0.2 gives it the weight it has at the published widths
TINY_STD = 0.2


def file_config(c: Glm5FlashConfig) -> dict:
    """The configuration file of a tiny Glm5FlashConfig, as benchmark/configs
    has them: the published pattern of nine layers (three linear, a sparse
    one) and layers 0, 3, 4, 5, 6 of it served; n_routed_experts the experts
    HELD, the published count beside."""
    lo, hi = c.experts_held
    return {
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "intermediate_size": c.intermediate_size,
        "num_hidden_layers": c.num_layers,
        "num_attention_heads": c.num_attention_heads,
        "q_lora_rank": c.q_lora_rank, "kv_lora_rank": c.kv_lora_rank,
        "qk_nope_head_dim": c.qk_nope_head_dim, "qk_rope_head_dim": 0,
        "v_head_dim": c.v_head_dim,
        "max_position_embeddings": c.max_position_embeddings,
        "rms_norm_eps": c.rms_norm_eps, "first_k_dense_replace": 1,
        "layer_types": [LIN, LIN, LIN, SP] * 2 + [LIN],
        "mlp_layer_types": ["dense"] * 3 + ["sparse"] * 6,
        "layers_served": [0, 3, 4, 5, 6],
        "n_routed_experts": hi - lo, "experts_held": [lo, hi],
        "published": {"n_routed_experts": c.n_routed_experts,
                      "num_hidden_layers": 9},
        "num_experts_per_tok": c.num_experts_per_tok,
        "moe_intermediate_size": c.moe_intermediate_size,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": c.routed_scaling_factor,
        "n_group": 1, "topk_group": 1,
        "initializer_range": c.initializer_range,
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "linear_attn_config": {"num_heads": c.linear_num_heads,
                               "head_dim": c.linear_head_dim,
                               "short_conv_kernel_size": 4,
                               "gate_lower_bound": -5},
        "kda_gate_rank": c.gate_rank, "index_n_heads": c.index_n_heads,
        "index_head_dim": c.index_head_dim, "index_topk": c.index_topk,
        "index_kpool": 4, "index_rope_dim": c.index_rope_dim,
        "index_rope_theta": 1e6, "swiglu_limit": c.swiglu_limit,
    }


def build(held=(0, 8), dtype="float32", seed=5, **kw):
    cfg = file_config(Glm5FlashConfig.tiny(
        experts_held=held, initializer_range=TINY_STD, **kw))
    model, _ = prog.build_model(cfg, seed, dtype)
    model.eval()
    return cfg, model, ref.init_weights(cfg, seed, dtype)


def ref_logits(w, ids, pos, cfg, mm=ref.highest_matmul, control=None):
    return jax.jit(lambda w, ids, pos: ref.logits_at(
        w, ids, pos, cfg, mm, control))(w, jnp.asarray(ids), jnp.asarray(pos))


def _engine(model, **kw):
    kw = {"max_slots": 2, "block_size": 16, "prefill_chunk": 16,
          "max_model_len": 160, **kw}
    return ServingEngine(model, **kw)


class _Tap:
    """Every logit row the engine's programs produce while it is on (the
    programs have to be traced under it)."""

    def __init__(self, model):
        self.model, self.rows = model, []

    def __enter__(self):
        head = self.model.lm_head
        self.real = real = head.forward

        def tap(x):
            out = real(x)
            jax.debug.callback(
                lambda v: self.rows.append(np.asarray(v, np.float32)),
                out._value, ordered=True)
            return out

        head.forward = tap
        return self

    def take(self):
        jax.effects_barrier()
        rows, self.rows = list(self.rows), []
        return rows

    def __exit__(self, *exc):
        self.model.lm_head.forward = self.real


def _served_logits(eng, model, prompts, n_new):
    """[(sequence, logit rows)] a prompt, served one after the other."""
    served = []
    with _Tap(model) as tap:
        for prompt in prompts:
            out = eng.generate([prompt], max_new_tokens=n_new)[0]
            served.append((out, tap.take()))
    return served


def _rows_of(prompt, rows, chunk=16):
    """The logits that chose each served token: the last real row of the
    last prefill chunk, then one a decode step."""
    chunks = [r for r in rows if r.shape[1] > 1]
    steps = [r[0, 0] for r in rows if r.shape[1] == 1]
    last = chunks[-1][0, len(prompt) - 1 - chunk * (len(chunks) - 1)]
    return chunks, np.stack([last] + steps)


# ---------------------------------------------------------- the delta rule
def _recurrence(q, k, v, g, beta, s, scale):
    def step(s, x):
        o, s = dr.delta_rule_step_xla(*x, s, scale)
        return s, o

    s, o = jax.lax.scan(step, s, tuple(jnp.moveaxis(x, 1, 0)
                                       for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def _delta_inputs(t, h=8, d=128, seed=0):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    f = lambda x: jnp.asarray(x, jnp.float32)                        # noqa: E731
    return (f(unit(rng.normal(size=(1, t, h, d)))),
            f(unit(rng.normal(size=(1, t, h, d)))),
            f(rng.normal(size=(1, t, h, d))),
            # decays over the whole range the gate allows, -5 to 0
            f(-5.0 * rng.uniform(size=(1, t, h, d)) ** 3),
            f(rng.uniform(size=(1, t, h))))


def _extreme_decays(decays):
    """4 heads over two chunks of 128: `floor`, the gate's lower bound -5 in
    every channel over the whole second block (a sub-chunk's factors reach
    e^{+-40}, the masked blocks between sub-chunks e^75); `padded`, rows of
    g 0 and beta 0 inside the first block and at the second block's end,
    as a padded prefill chunk's."""
    q, k, v, g, beta = _delta_inputs(256, h=4)
    if decays == "floor":
        g = g.at[:, 128:].set(-5.0)
    else:
        for rows in (slice(40, 72), slice(216, 256)):
            g, beta = g.at[:, rows].set(0.0), beta.at[:, rows].set(0.0)
    return q, k, v, g, beta


@pytest.mark.parametrize("form,decays", [
    ("xla", "mixed"), ("kernel", "mixed"), ("xla", "floor"),
    ("kernel", "floor"), ("xla", "padded"), ("kernel", "padded")],
    ids=["xla", "kernel", "xla-floor", "kernel-floor", "xla-padded",
         "kernel-padded"])
def test_the_chunked_forms_carry_the_state_over_three_chunks(form, decays):
    """Three prefill chunks of 128 (two at the decay's extremes), each from
    the state the last left, against the token recurrence over all their
    tokens; a chunk's edge falls on a sub-chunk's. float32 both sides,
    another order of summation: round-off (reads 3e-8 on outputs of size
    1; a state rounded to bf16 a chunk reads 1e-3)."""
    q, k, v, g, beta = (_delta_inputs(384) if decays == "mixed"
                        else _extreme_decays(decays))
    s0 = jnp.zeros((1, q.shape[2], 128, 128), jnp.float32)
    want_o, want_s = _recurrence(q, k, v, g, beta, s0, 0.1)
    run = jax.jit(
        (lambda *a: dr.delta_rule_chunked_xla(*a, 0.1)) if form == "xla"
        else (lambda *a: dr.kda_chunk(*a, scale=0.1, interpret=True)))
    s, outs = s0, []
    for c in range(q.shape[1] // 128):
        sl_ = slice(128 * c, 128 * (c + 1))
        o, s = run(q[:, sl_], k[:, sl_], v[:, sl_], g[:, sl_], beta[:, sl_],
                   s)
        outs.append(o)
    assert float(jnp.max(jnp.abs(jnp.concatenate(outs, 1) - want_o))) < 2e-5
    assert float(jnp.max(jnp.abs(s - want_s))) < 2e-5


def test_the_decode_kernel_is_the_one_token_update():
    q, k, v, g, beta = (x[:, 0] for x in _delta_inputs(1, h=16, seed=3))
    s0 = jnp.asarray(np.random.default_rng(4).normal(
        size=(1, 16, 128, 128)) * 0.1, jnp.float32)
    want_o, want_s = dr.delta_rule_step_xla(q, k, v, g, beta, s0, 0.2)
    got_o, got_s = dr.kda_decode(q, k, v, g, beta, s0, scale=0.2,
                                 interpret=True)
    assert float(jnp.max(jnp.abs(got_o - want_o))) < 1e-5
    assert float(jnp.max(jnp.abs(got_s - want_s))) < 1e-5


def test_a_padded_token_leaves_the_state_as_it_was():
    q, k, v, g, beta = _delta_inputs(32)
    s0 = jnp.asarray(np.random.default_rng(2).normal(size=(1, 8, 128, 128)),
                     jnp.float32)
    _, s = dr.delta_rule_chunked_xla(q, k, v, g * 0.0, beta * 0.0, s0, 0.1)
    assert float(jnp.max(jnp.abs(s - s0))) < 1e-6


# ---------------------------------------------------------------- the model
def test_full_forward_matches_the_reference():
    cfg, model, w = build()
    ids = np.random.default_rng(0).integers(0, 255, (1, 48)).astype(np.int32)
    got = model(paddle.to_tensor(ids))._value[0]
    want = ref_logits(w, ids[0], np.arange(48), cfg)
    # float32 both sides, the chunk-wise scan against the recurrence and the
    # absorbed sparse form against the expanded masked one: round-off of
    # logits of size ~1.6 (reads 2.4e-6)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    assert float(jnp.std(want)) > 1.0


@pytest.fixture(scope="module")
def served():
    """One float32 model and one engine of one slot, for every test of the
    served path below, with a tap on the logits from its first program on:
    the programs compile once for all of them."""
    cfg, model, w = build()
    eng = _engine(model, max_slots=1)
    with _Tap(model) as tap:
        yield cfg, model, w, eng, tap


def _check_served(cfg, w, prompt, out, rows, tol, fp8=True):
    """The median over a request's positions of the widest logit difference
    at a position, program against reference, and the fp8 control's."""
    chunks, got = _rows_of(prompt, rows)
    assert len(chunks) == -(-len(prompt) // 16)
    assert len(got) == len(out) - len(prompt)
    ids = np.asarray(out, np.int32)
    pos = np.arange(len(prompt) - 1, len(out) - 1)
    want = np.asarray(ref_logits(w, ids, pos, cfg))
    gap = float(np.median(np.max(np.abs(got - want), -1)))
    low = np.asarray(ref_logits(w, ids, pos, cfg, ref.MATMULS["fp8"])) \
        if fp8 else None
    control = np.inf if low is None else float(np.median(
        np.max(np.abs(low - want), -1)))
    print("served logits against the reference:", gap, "fp8:", control)
    assert gap < tol, gap
    assert control > tol, control


def test_served_logits_match_the_reference_over_chunks_and_decode(served):
    """A 45-token prompt in three chunks of 16 then 12 decode steps, past
    the 20 positions beyond which the indexer selects, then a 23-token one
    in the same slot: logits of the engine's own programs against the
    reference's full forward. float32 everywhere: the chunk-wise scan and
    the sparse form over the cache against the recurrence and the masked
    dense form (reads 1.4e-6; a state rounded to bf16 in the reference
    reads 6e-3, no decay 0.7; the fp8 control 0.25)."""
    cfg, model, w, eng, tap = served
    rng = np.random.default_rng(1)
    for n in (45, 23):
        prompt = [int(t) for t in rng.integers(0, 255, n)]
        out = eng.generate([prompt], max_new_tokens=12)[0]
        _check_served(cfg, w, prompt, out, tap.take(), 5e-5)


def test_served_bf16_logits_match_the_reference():
    """bf16 weights, activations, streams, latents and pooled keys against
    float32 `highest` over the same bf16 weights, the state float32 (reads
    0.03-0.06 on logits of std 1.6; the fp8 control 0.3 and more); and the
    number the cell's `correct` is decided on, served_gaps over the same
    row, within it."""
    cfg, model, w = build(dtype="bfloat16")
    prompt = [int(t) for t in np.random.default_rng(1).integers(0, 255, 45)]
    (out, rows), = _served_logits(_engine(model, max_slots=1), model,
                                  [prompt], 12)
    _check_served(cfg, w, prompt, out, rows, 0.15)
    gaps = ref.served_gaps(cfg, 5, [(prompt, out[45:])], dtype="bfloat16")
    assert 0.0 <= gaps[0] < 0.15


@pytest.mark.parametrize("control", ["bf16_state", "no_decay"])
def test_a_bf16_state_or_a_dropped_decay_fails_the_float32_tolerance(
        served, control):
    """The reference's own controls read far outside the served float32
    tolerance of 5e-5: a state rounded to bf16 after every token (6e-3
    over 48 tokens), no decay (0.7)."""
    cfg, _, w, _, _ = served
    ids = np.random.default_rng(0).integers(0, 255, 48)
    want = ref_logits(w, ids, np.arange(48), cfg)
    low = ref_logits(w, ids, np.arange(48), cfg, control=control)
    assert float(jnp.max(jnp.abs(low - want))) > 50 * 5e-5


def test_the_seeded_decays_spread_memories_past_the_longest_context():
    """The seeded decays of the configuration's first linear layer, its gate
    input at zero: memories (1 / |g| tokens) from under one token to past
    the longest context (66,048) in a quarter of the channels and more,
    spread evenly over their logarithm (quartiles near 10^1.6, 10^3.3,
    10^5; dt_bias uniform, sigmoid(x) ~ exp(x) below zero)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-5.3-flash-ep8.json")) as f:
        cfg = json.load(f)
    a = np.asarray(ref.init_leaf(cfg, 4100000301, "layers.0.a_log"))
    dt = np.asarray(ref.init_leaf(cfg, 4100000301, "layers.0.dt_bias"))
    lb = cfg["linear_attn_config"]["gate_lower_bound"]
    g = lb / (1.0 + np.exp(-np.exp(a)[:, None] * dt.reshape(a.size, -1)))
    memory = np.log10(1.0 / np.abs(g))
    assert memory.min() < 0.0 and memory.max() > 9.0
    assert 0.2 < float(np.mean(memory > np.log10(66048))) < 0.4
    q1, q2, q3 = np.quantile(memory, [0.25, 0.5, 0.75])
    assert abs((q3 - q2) - (q2 - q1)) < 0.5


def test_a_reused_slot_starts_from_a_zero_state(served):
    """The slot that served the requests before serves a 60-token prompt,
    then a 21-token one: each is held to the reference's full forward of
    its own tokens alone (a state carried over would move its logits by
    far more than round-off), and each admission resets the slot once."""
    cfg, model, w, eng, tap = served
    rng = np.random.default_rng(3)
    resets = default_registry().get("serving_state_resets_total")
    before = resets.value()
    for n in (60, 21):
        prompt = [int(t) for t in rng.integers(0, 255, n)]
        out = eng.generate([prompt], max_new_tokens=8)[0]
        _check_served(cfg, w, prompt, out, tap.take(), 5e-5, fp8=False)
    assert resets.value() - before == 2


def test_an_evicted_request_re_prefilled_gives_the_same_logits(served):
    """The engine reserves a request's whole context at admission and never
    preempts; a request taken off mid-decode (cancel) and sent again with
    the tokens it had is prefilled from a zero state, and its next logits
    are those of the run that was not interrupted."""
    cfg, model, w, eng, tap = served
    prompt = [int(t) for t in np.random.default_rng(6).integers(0, 255, 37)]
    whole = eng.generate([prompt], max_new_tokens=12)[0]
    _, want = _rows_of(prompt, tap.take())
    req = eng.submit(prompt, max_new_tokens=12)
    while len(req.output_tokens) < 5:
        eng.step()
    assert eng.cancel(req, "preempted")
    tap.take()
    head = prompt + req.output_tokens[:5]
    again = eng.generate([head], max_new_tokens=7)[0]
    _, got = _rows_of(head, tap.take())
    assert again == whole
    # the same mathematics over another cut of the chunks: round-off
    assert float(np.max(np.abs(got - want[5:]))) < 5e-5


# -------------------------------------------------------------- the indexer
@pytest.mark.parametrize("length", [20, 64])
def test_the_selection_is_the_references(length):
    """Below topk + POOL positions every complete block and the tail are
    selected (dense); above, the indexer's top blocks, exactly the
    reference's, and always the tail."""
    cfg, model, w = build()
    lp = ref.layer_params(w, 1)
    rng = np.random.default_rng(length)
    u = jnp.asarray(rng.normal(size=(length, 64)), jnp.float32)
    a = ref._rms(u, lp["ln1"], 1e-5)
    c_q = ref._rms(ref.highest_matmul(a, lp["wqa"]), lp["q_norm"], 1e-5)
    want = np.asarray(ref.selection(a, c_q, lp, cfg, ref.highest_matmul))
    attn = model.model.layers[1].self_attn
    pos = jnp.arange(length)
    qi = sl_rope(ref.highest_matmul(c_q, lp["wiq"]).reshape(length, 2, 16),
                 pos)
    ki = sl_rope(ref._layer_norm(ref.highest_matmul(a, lp["wik"]),
                                 lp["ik_norm_w"], lp["ik_norm_b"]), pos)
    wts = ref.highest_matmul(a, lp["wiw"]) / np.sqrt(32)
    kbar = ki.reshape(-1, 4, 16).mean(1)
    sc = sl.index_scores_xla(qi, wts, kbar, pos // 4)
    gid, rows = sl.select(sc, attn.topk, pos)
    got = np.zeros_like(want)
    for t in range(length):
        got[t, np.asarray(gid[t])[np.asarray(rows[t]).any(-1)]] = True
    assert (got == want).all()
    assert all(want[t, t // 4] for t in range(length))
    n = want.sum(1)
    if length <= attn.topk * 4 + 4:
        assert (n == np.arange(length) // 4 + 1).all()
    else:
        assert n.max() == attn.topk + 1


def sl_rope(x, pos):
    return ref._rope_interleaved(x, pos, 8, 1e6)


def test_the_index_kernel_in_interpret_mode_is_the_xla_form():
    rng = np.random.default_rng(9)
    n, b = 128, 2048
    qi = jnp.asarray(rng.normal(size=(n, 32, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(n, 32)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(b, 128)), jnp.bfloat16)
    complete = jnp.asarray(rng.integers(0, b, n), jnp.int32)
    want = sl.index_scores_xla(qi.astype(jnp.bfloat16), w, kp, complete)
    got = sl.dsa_index(qi, w, kp, complete, interpret=True)
    live = np.isfinite(np.asarray(want))
    assert (np.isfinite(np.asarray(got)) == live).all()
    # bf16 operands, float32 sums in another order
    assert float(np.max(np.abs(np.asarray(got)[live]
                               - np.asarray(want)[live]))) < 1e-2


# ---------------------------------------------------------------- experts
def test_eight_shares_of_experts_and_vocabulary_add_up_to_the_uncut_layer():
    """Each of 8 chips holds 1 of 8 experts: the routed parts add up to the
    uncut layer's, the shared expert (which every chip computes alike)
    counted once."""
    cfg, _, w = build()
    lp = ref.layer_params(w, 2)
    m = jnp.asarray(np.random.default_rng(2).normal(size=(24, 64)),
                    jnp.float32)
    whole = ref.mlp(m, lp, cfg, False, ref.highest_matmul)
    mm, x = ref.highest_matmul, ref._rms(m, lp["ln2"], 1e-5)
    shared = mm(ref.clipped_swiglu(mm(x, lp["s_w1"]), mm(x, lp["s_w3"]),
                                   cfg["swiglu_limit"]), lp["s_w2"])
    parts = []
    for i in range(8):
        c = dict(cfg, n_routed_experts=1, experts_held=[i, i + 1])
        part = dict(lp, e_w13=lp["e_w13"][i:i + 1], e_w2=lp["e_w2"][i:i + 1])
        parts.append(ref.mlp(m, part, c, False, ref.highest_matmul) - shared)
    assert float(jnp.max(jnp.abs(sum(parts) + shared - whole))) < 1e-5
    # and the vocabulary: 8 slices of the head give the whole logits
    h = jnp.asarray(np.random.default_rng(3).normal(size=(4, 64)), jnp.float32)
    head = w["head"]
    cut = jnp.concatenate([h @ head[:, 32 * i:32 * (i + 1)] for i in range(8)],
                          -1)
    assert float(jnp.max(jnp.abs(cut - h @ head))) < 1e-5


def test_the_swiglu_limit_clamps_the_experts():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 8)) * 4, jnp.float32)
    router = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
    w13 = jnp.asarray(rng.normal(size=(4, 8, 12)), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(4, 6, 8)), jnp.float32)
    kw = dict(top_k=2, scoring="sigmoid")
    y, _ = nn_ops.moe_experts(x, router, w13, w2, swiglu_limit=1.0, **kw)
    y0, _ = nn_ops.moe_experts(x, router, w13, w2, **kw)
    # the clamp by hand, expert by expert
    s = jax.nn.sigmoid(x @ router)
    top, idx = jax.lax.top_k(s, 2)
    top = top / top.sum(-1, keepdims=True)
    want = jnp.zeros_like(x)
    for j in range(2):
        h = jnp.einsum("td,tdf->tf", x, w13[idx[:, j]])
        act = jax.nn.silu(jnp.minimum(h[:, :6], 1.0)) * jnp.clip(h[:, 6:],
                                                                -1, 1)
        want = want + top[:, j:j + 1] * jnp.einsum("tf,tfd->td", act,
                                                   w2[idx[:, j]])
    assert float(jnp.max(jnp.abs(y - want))) < 1e-4
    assert float(jnp.max(jnp.abs(y0 - want))) > 1.0


# ------------------------------------------------------ the cache contract
def test_the_model_states_state_and_two_array_latent_layers():
    _, model, _ = build()
    spec = model.cache_spec()
    kinds = [l.kind for l in spec.layers]
    assert kinds == ["state", "latent", "state", "state", "state"]
    st, dsa = spec.layers[0], spec.layers[1]
    assert st.state == ((2, 16, 16), (3, 96)) and st.arrays == 2
    assert (dsa.arrays, dsa.index_dim, dsa.pool, dsa.index_topk) \
        == (2, 16, 4, 4)
    shapes = [s for s, _ in dsa.page_shapes(10, 16, 3, jnp.bfloat16)]
    assert shapes == [(10, 4, 128), (10, 4, 16)]
    assert [s for s, _ in st.page_shapes(10, 16, 3, jnp.bfloat16)] \
        == [(3, 2, 16, 16), (3, 3, 96)]
    with pytest.raises(ValueError, match="states the shapes"):
        LayerCacheSpec("state", 2, 16)


@pytest.mark.parametrize("kw,name", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"prefill_bucket": 16}, "prefill_bucket"),
    ({"spec_k": 2}, "spec_k"),
])
def test_what_a_state_layer_cannot_serve_refuses_by_name(kw, name):
    _, model, _ = build()
    with pytest.raises(ValueError, match=f"{name}=.*state layers"):
        _engine(model, **kw)


def test_fused_steps_refuse_a_state_layer_by_name():
    _, model, _ = build()
    flags.set_flags({"serving_fuse_steps": 4})
    try:
        with pytest.raises(ValueError, match="FLAGS_serving_fuse_steps"):
            _engine(model)
    finally:
        flags.set_flags({"serving_fuse_steps": 1})
