"""Xing4.0 (models/xing.py): the four-stream residual path (manifold-
constrained hyper-connections: ops mhc_pre / mhc_post and their kernels)
around GLM-MoE-Lite's latent attention, here with a value narrower than a key
and YaRN, and sparse experts all held, against the plain reference
(benchmark/models/xing_reference.py): seeded random weights at a small size
on the CPU.

Tolerances, each with its reason, are by the tests that use them."""
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.models import xing_program as prog
from benchmark.models import xing_reference as ref
from paddle_tpu.core import flags
from paddle_tpu.models import Xing4Config
from paddle_tpu.models.generation import LayerCacheSpec, init_kv_cache
from paddle_tpu.models.xing import MHC_COUNTERS
from paddle_tpu.observability.registry import default_registry
from paddle_tpu.ops.kernels import nn_ops
from paddle_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# at hidden 64 an initialisation of 0.02 leaves a sublayer a hundredth of the
# embedding and the residual path nothing to mix; 0.2 gives it the weight it
# has at hidden 3,584 (streams at 1.8 times the embedding after 5 layers)
TINY_STD = 0.2


def file_config(c: Xing4Config) -> dict:
    """The configuration file of a Xing4Config, as benchmark/configs has
    them: n_routed_experts is the experts HELD, the published count beside."""
    lo, hi = c.experts_held
    return {
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "intermediate_size": c.intermediate_size,
        "num_hidden_layers": c.num_layers,
        "num_attention_heads": c.num_attention_heads,
        "q_lora_rank": c.q_lora_rank, "kv_lora_rank": c.kv_lora_rank,
        "qk_nope_head_dim": c.qk_nope_head_dim,
        "qk_rope_head_dim": c.qk_rope_head_dim, "v_head_dim": c.v_head_dim,
        "rope_theta": c.rope_theta, "rope_scaling": c.rope_scaling,
        "max_position_embeddings": c.max_position_embeddings,
        "rms_norm_eps": c.rms_norm_eps,
        "first_k_dense_replace": c.first_k_dense_replace,
        "n_routed_experts": hi - lo, "experts_held": [lo, hi],
        "published": {"n_routed_experts": c.n_routed_experts,
                      "num_hidden_layers": c.num_layers},
        "num_experts_per_tok": c.num_experts_per_tok,
        "moe_intermediate_size": c.moe_intermediate_size,
        "n_shared_experts": c.n_shared_experts,
        "norm_topk_prob": c.norm_topk_prob,
        "routed_scaling_factor": c.routed_scaling_factor,
        "n_group": c.n_group, "topk_group": c.topk_group,
        "initializer_range": c.initializer_range,
        "hc_mult": c.hc_mult, "hc_sinkhorn_iters": c.hc_sinkhorn_iters,
        "hc_eps": c.hc_eps, "mhc_h_res_clamp_min": c.mhc_h_res_clamp[0],
        "mhc_h_res_clamp_max": c.mhc_h_res_clamp[1],
    }


def build(held=(0, 8), dtype="float32", seed=5, **kw):
    cfg = file_config(Xing4Config.tiny(
        experts_held=held, initializer_range=TINY_STD, **kw))
    model, _ = prog.build_model(cfg, seed, dtype)
    model.eval()
    return cfg, model, ref.init_weights(cfg, seed, dtype)


def ref_logits(w, ids, pos, cfg, mm=ref.highest_matmul, residual="mhc"):
    return jax.jit(lambda w, ids, pos: ref.logits_at(
        w, ids, pos, cfg, mm, residual))(w, jnp.asarray(ids), jnp.asarray(pos))


def _engine(model, **kw):
    kw = {"max_slots": 2, "block_size": 4, "prefill_chunk": 16,
          "max_model_len": 96, **kw}
    return ServingEngine(model, **kw)


def row_gap(want, got):
    """benchmark/models/xing_reference.served_gaps' statistic for one row:
    the gap, under the reference's logits `want`, of the token that `got`
    puts first, that nine in ten positions stay within."""
    nxt = np.argmax(got, -1)
    gap = np.max(want, -1) - np.take_along_axis(want, nxt[:, None], -1)[:, 0]
    return float(np.quantile(gap, ref.GAP_QUANTILE))


# ---------------------------------------------------------------- the model
def test_model_config_of_a_file_round_trips():
    cfg = file_config(Xing4Config.tiny(experts_held=(4, 8),
                                       initializer_range=TINY_STD))
    c = prog.model_config(cfg)
    assert c.n_routed_experts == 8 and c.experts_held == (4, 8)
    assert (c.hc_mult, c.hc_sinkhorn_iters, c.v_head_dim) == (4, 20, 8)
    assert file_config(c) == cfg
    with pytest.raises(ValueError, match="v_head_dim"):
        Xing4Config.tiny(v_head_dim=24)         # wider than a key
    with pytest.raises(ValueError, match="n_group"):
        Xing4Config.tiny(n_group=2)


def test_the_published_rotary_is_yarn_with_the_softmax_scaled():
    c = Xing4Config()
    inv, factor, scale = c.rotary()
    want_inv, want_factor, want_scale = ref.rotary({
        "qk_rope_head_dim": 64, "qk_nope_head_dim": 128, "rope_theta": 10000,
        "rope_scaling": c.rope_scaling})
    assert np.allclose(inv, want_inv, rtol=1e-12) and len(inv) == 32
    assert factor == want_factor == 1.0
    m = 0.1 * np.log(64) + 1
    assert round(m, 4) == 1.4159
    assert scale == pytest.approx(m * m / np.sqrt(192), rel=1e-12)
    assert scale == pytest.approx(want_scale, rel=1e-12)
    # the fastest dimension extrapolates, the slowest is interpolated by 64
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert inv[0] == pytest.approx(plain[0])
    assert inv[-1] == pytest.approx(plain[-1] / 64)


def test_full_forward_matches_the_reference():
    cfg, model, w = build()
    ids = np.random.default_rng(0).integers(0, 255, (1, 40)).astype(np.int32)
    got = model(paddle.to_tensor(ids))._value[0]
    want = ref_logits(w, ids[0], np.arange(40), cfg)
    # float32 both sides, another order of summation: round-off of logits
    # of size ~2
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    assert float(jnp.std(want)) > 1.0
    # the streams do not stay copies of one another: the mix is at work
    x = ref.open_streams(w["embed"], jnp.asarray(ids[0]), cfg)
    x = ref.layer(x, ref.layer_params(w, 0), cfg, True)
    assert float(jnp.max(jnp.abs(x[:, 0] - x[:, 1]))) > 1e-2


def test_absorbed_attention_over_a_cache_is_the_expanded_form():
    """No cache (expanded, a value of 8 padded to the key's 16 for the one
    attention op) against a contiguous cache (absorbed), in chunks."""
    _, model, _ = build()
    ids = np.random.default_rng(4).integers(0, 255, (1, 32)).astype(np.int32)
    expanded = model(paddle.to_tensor(ids))._value[0]
    fn, params, buffers = model._functional_forward()
    pv, bv = [p._value for p in params], [b._value for b in buffers]
    caches = init_kv_cache(1, 32, model.cache_spec(), jnp.float32)
    rows = []
    for start in (0, 16):
        logits, caches = fn(pv, bv, jnp.asarray(ids[:, start:start + 16]),
                            caches, jnp.asarray(start, jnp.int32))
        rows.append(logits[0])
    assert float(jnp.max(jnp.abs(jnp.concatenate(rows) - expanded))) < 2e-5


def _served_logits(eng, model, prompts, n_new):
    """[(sequence, logit rows)] a prompt, served one after the other: every
    logit row the engine's programs produced for it (tests/
    test_glm_moe_lite.py has the same tap)."""
    rows = []
    head, real = model.lm_head, model.lm_head.forward

    def tap(x):
        out = real(x)
        jax.debug.callback(
            lambda v: rows.append(np.asarray(v, np.float32)), out._value,
            ordered=True)
        return out

    head.forward = tap
    served = []
    try:
        for prompt in prompts:
            del rows[:]
            out = eng.generate([prompt], max_new_tokens=n_new)[0]
            jax.effects_barrier()
            served.append((out, list(rows)))
    finally:
        head.forward = real
    return served


@pytest.mark.parametrize("dtype,tol", [
    # float32 everywhere: only the order of summation differs (reads 0.0;
    # the fp8 control 0.40 and 0.52)
    ("float32", 5e-5),
    # bf16 weights, activations, streams and latents against float32
    # `highest` over the same bf16 weights: reads 0.049 and 0.047 here on
    # logits of std 1.6; the fp8 control 0.47 and 0.39
    ("bfloat16", 0.15),
])
def test_served_logits_match_the_reference_across_chunks_and_a_prefix_hit(
        dtype, tol):
    cfg, model, w = build(dtype=dtype)
    rng = np.random.default_rng(1)
    doc = [int(t) for t in rng.integers(0, 255, 40)]
    turns = [doc + [int(t) for t in rng.integers(0, 255, n)] for n in (5, 7)]
    eng = _engine(model, max_slots=1)
    worst, control = 0.0, np.inf
    served = _served_logits(eng, model, turns, 12)
    # 45 tokens in 3 chunks of 16; then the document's 10 blocks of latents
    # from the prefix cache and the 7 tokens after them
    assert eng.prefill_tokens == 45 + 7
    for turn, (prompt, (out, rows)) in enumerate(zip(turns, served)):
        chunks = [r for r in rows if r.shape[1] > 1]
        steps = [r[0, 0] for r in rows if r.shape[1] == 1]
        assert len(chunks) == (3, 1)[turn] and len(steps) == 11
        matched = (0, 40)[turn]
        last = chunks[-1][0, len(prompt) - 1 - matched - 16 * (len(chunks) - 1)]
        got = np.stack([last] + steps)
        ids = np.asarray(out, np.int32)
        pos = np.arange(len(prompt) - 1, len(out) - 1)
        want = np.asarray(ref_logits(w, ids, pos, cfg))
        # a turn's number: the MEDIAN over its 12 positions of the widest
        # logit difference at a position (in bf16 a router within rounding of
        # a tie picks another second expert at one position in twelve and
        # moves that position's logits by 3, as far as fp8 moves none)
        worst = max(worst, float(np.median(
            np.max(np.abs(got - want), axis=-1))))
        low = np.asarray(ref_logits(w, ids, pos, cfg, ref.MATMULS["fp8"]))
        control = min(control, float(np.median(
            np.max(np.abs(low - want), axis=-1))))
    print("served logits against the reference:", dtype, worst,
          "the fp8 control:", control)
    assert worst < tol, worst
    assert control > tol, control
    assert eng.stats()["prefix_cache"] is True


def test_engine_serves_what_generate_gives():
    _, model, _ = build()
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(0, 255, n)] for n in (19, 50)]
    outs = _engine(model).generate(prompts, max_new_tokens=24)
    for p, o in zip(prompts, outs):
        want = model.generate(paddle.to_tensor(np.asarray([p], np.int32)),
                              max_new_tokens=24)._value[0]
        assert [int(t) for t in want] == o


# ------------------------------------------------------ the residual path
def _tiny_limit():
    with open(os.path.join(ROOT, "benchmark", "limits", "tiny.agent.json")) as f:
        return json.load(f)["logit_gap"]


def _plain_maps(x, phi, a, b, n, eps, clamp, iters):
    t = x.shape[0]
    return (jnp.full((t, n), 1.0 / n), jnp.full((t, n), 2.0 / n),
            jnp.broadcast_to(jnp.eye(n), (t, n, n)), jnp.zeros((t, 1)))


def test_the_sound_program_passes_the_tiny_limit():
    cfg, model, w = build()
    ids = np.random.default_rng(7).integers(0, 255, (1, 48)).astype(np.int32)
    want = np.asarray(ref_logits(w, ids[0], np.arange(48), cfg))
    got = np.asarray(model(paddle.to_tensor(ids))._value[0])
    assert row_gap(want, got) <= _tiny_limit()


@pytest.mark.parametrize("control", ["plain_residual", "static_maps"])
def test_a_program_without_the_dynamic_maps_fails_the_tiny_limit(
        control, monkeypatch):
    """A plain residual stream (H_res = I, H_pre = 1/n, H_post = 2/n) and
    maps that forget the token (a = 0), planted in the PROGRAM's op: their
    logits against the reference's fail the limit the tiny cell runs under,
    and agree with the reference's own form of the same control."""
    from paddle_tpu.ops import registry

    cfg, model, w = build()
    real = nn_ops._mhc_maps_xla
    # an eager op is compiled once a shape: drop what the sound tests left,
    # and what this one leaves
    registry._EXEC_CACHE.clear()
    monkeypatch.setattr(registry, "_EXEC_CACHE", type(registry._EXEC_CACHE)())
    if control == "plain_residual":
        monkeypatch.setattr(nn_ops, "_mhc_maps_xla", _plain_maps)
    else:
        monkeypatch.setattr(
            nn_ops, "_mhc_maps_xla",
            lambda x, phi, a, b, *rest: real(x, phi, a * 0.0, b, *rest))
    ids = np.random.default_rng(7).integers(0, 255, (1, 48)).astype(np.int32)
    want = np.asarray(ref_logits(w, ids[0], np.arange(48), cfg))
    got = np.asarray(model(paddle.to_tensor(ids))._value[0])
    gap = row_gap(want, got)
    print(control, "gap", gap, "widest logit", np.abs(got - want).max())
    assert gap > 10 * _tiny_limit()
    same = np.asarray(ref_logits(w, ids[0], np.arange(48), cfg,
                                 residual=control))
    assert np.abs(got - same).max() < 2e-5


def test_twenty_rounds_balance_the_maps_and_the_counter_stays_at_zero():
    cfg, model, w = build()
    # the reference's own maps, layer 2's feed-forward sublayer, on the
    # streams that reach it
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 255, 24))
    x = ref.open_streams(w["embed"], ids, cfg)
    for i in range(2):
        x = ref.layer(x, ref.layer_params(w, i), cfg, ref.is_dense(cfg, i))
    hp = ref.hc_params(ref.layer_params(w, 2), "attn")
    _, _, res = ref.hc_maps(x, hp, cfg)
    assert float(jnp.max(jnp.abs(res.sum(-1) - 1))) < 1e-3
    assert float(jnp.max(jnp.abs(res.sum(-2) - 1))) < 1e-3
    assert float(res.min()) > 0 and float(res.max()) < 1
    # the program's op gives the same maps and calls them balanced
    _, maps = nn_ops.mhc_pre(x.reshape(24, -1), hp["phi"], hp["a"], hp["b"],
                             n=4, eps=cfg["hc_eps"])
    _, _, got, off = nn_ops.mhc_unpack(maps, 4)
    assert float(jnp.max(jnp.abs(got - res))) < 1e-6
    assert float(off.max()) < 1e-3
    # served: every decode step counts its applications, none unbalanced
    reg = default_registry()
    runs = reg.get("serving_mhc_applications_total")
    bad = reg.get("serving_mhc_unbalanced_total")
    r0, b0 = runs.total(), bad.total()
    eng = _engine(model)
    eng.generate([[1, 2, 3, 4, 5], [9, 8, 7]], max_new_tokens=11)
    st = eng.stats()
    assert set(st["layer_counters"]) == {f"h{i}" for i in range(5)}
    # 10 decode steps of 2 slots, two applications a layer a token
    assert [c[-2:] for c in st["layer_counters"].values()] == [[40, 0]] * 5
    assert len(st["layer_counters"]["h0"]) == 2
    assert len(st["layer_counters"]["h1"]) == 8 + 1 + 2
    assert runs.total() - r0 == 5 * 40 and bad.total() == b0
    assert runs.value(layer="h0") >= 40


def test_one_round_leaves_columns_off_and_the_counter_counts():
    """The test's own copy of the configuration with the rounds cut to 1:
    the reference's maps have columns off 1 by more than 1e-3, the
    program's counter says so."""
    cfg, model, w = build(hc_sinkhorn_iters=1)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 255, 24))
    x = ref.open_streams(w["embed"], ids, cfg)
    _, _, res = ref.hc_maps(x, ref.hc_params(ref.layer_params(w, 0), "attn"),
                            cfg)
    assert float(jnp.max(jnp.abs(res.sum(-1) - 1))) < 1e-3      # rows: last
    assert float(jnp.max(jnp.abs(res.sum(-2) - 1))) > 1e-2      # columns
    bad = default_registry().get("serving_mhc_unbalanced_total")
    b0 = bad.total()
    eng = _engine(model)
    eng.generate([[1, 2, 3, 4, 5], [9, 8, 7]], max_new_tokens=11)
    counts = eng.stats()["layer_counters"]
    assert all(0 < c[-1] <= c[-2] == 40 for c in counts.values())
    assert bad.total() - b0 == sum(c[-1] for c in counts.values())


def test_the_kernels_are_taken_for_whole_blocks_of_bf16_streams_alone():
    from paddle_tpu.ops.pallas import hyper_connection as hc

    bf = jnp.bfloat16
    assert hc.supports((1, 512, 4 * 3584), 4, bf)           # a chunk
    assert not hc.supports((24, 1, 4 * 3584), 4, bf)        # a decode step
    assert not hc.supports((130, 4 * 3584), 4, bf)
    assert not hc.supports((128, 4 * 3584), 4, jnp.float32)
    assert not hc.supports((128, 4 * 64), 4, bf)            # lanes


@pytest.mark.parametrize("tokens", [128, 384])
def test_the_kernels_in_interpret_mode_agree_with_the_xla_forms(tokens):
    """ops mhc_pre / mhc_post: the Pallas forms are taken in interpret mode
    (bf16 streams of whole lanes in whole blocks of 128 tokens), as on the
    TPU."""
    n, c = 4, 128
    k = jax.random.split(jax.random.PRNGKey(tokens), 5)
    x = (jax.random.normal(k[0], (tokens, n * c)) * 0.3).astype(jnp.bfloat16)
    y = (jax.random.normal(k[1], (tokens, c)) * 0.3).astype(jnp.bfloat16)
    phi = jax.random.normal(k[2], (n * (n + 2), n * c)) / np.sqrt(n * c)
    a = jnp.asarray([0.5, 0.7, 0.4])
    b = ref.hc_leaf(k[3], "hc_b", n)

    def both():
        u, maps = nn_ops.mhc_pre(x, phi, a, b, n=n)
        return u, maps, nn_ops.mhc_post(x, y, maps, n=n)

    xla = both()
    from paddle_tpu.ops.pallas import hyper_connection as hc
    assert hc.supports(x.shape, n, x.dtype)
    flags.set_flags({"pallas_interpret": True})
    try:
        kernel = both()
    finally:
        flags.set_flags({"pallas_interpret": False})
    # the maps: float32 both ways; the kernel's product with phi is exact
    # to float32 (bf16 streams times phi in three bf16 parts)
    assert float(jnp.max(jnp.abs(xla[1] - kernel[1]))) < 2e-6
    _, _, res, off = nn_ops.mhc_unpack(kernel[1], n)
    # the imbalance it reports is its own map's worst column
    assert float(jnp.max(jnp.abs(
        jnp.max(jnp.abs(res.sum(-2) - 1), -1) - off))) < 1e-6
    assert float(off.max()) < 1e-3
    # u and x': bf16, at most one rounding apart
    for i in (0, 2):
        d = jnp.abs(xla[i].astype(jnp.float32) - kernel[i].astype(jnp.float32))
        assert float(d.max()) <= 2 ** -7 * float(jnp.abs(xla[i]).max())
        assert float((d > 0).mean()) < 0.01
    # and against the reference's equations
    want = ref.sublayer(x.astype(jnp.float32).reshape(tokens, n, c),
                        {"phi": phi, "a": a, "b": b},
                        lambda u: y.astype(jnp.float32),
                        {"hc_eps": 1e-6, "hc_sinkhorn_iters": 20,
                         "mhc_h_res_clamp_min": -30,
                         "mhc_h_res_clamp_max": 30})
    got = kernel[2].astype(jnp.float32).reshape(tokens, n, c)
    assert float(jnp.max(jnp.abs(got - want))) < 0.01   # one bf16 rounding


def test_the_clamp_holds_the_maps_finite():
    n, c = 4, 16
    x = jnp.ones((3, n * c), jnp.float32)
    phi = jnp.zeros((n * (n + 2), n * c))
    b = jnp.concatenate([jnp.zeros(2 * n), 200.0 * jnp.eye(n).ravel()
                         - 100.0])
    _, maps = nn_ops.mhc_pre(x, phi, jnp.ones(3), b, n=n)
    _, _, res, off = nn_ops.mhc_unpack(maps, n)
    # exp(100) would be inf in float32; clipped to exp(30) against
    # exp(-30) the map is the identity to 1e-26
    assert bool(jnp.isfinite(maps).all())
    assert float(jnp.max(jnp.abs(res - jnp.eye(n)))) < 1e-6
    assert float(off.max()) < 1e-6


# ------------------------------------------------------------- the experts
def test_every_expert_held_is_two_shares_and_the_shared_expert_once():
    """8 of 8 held (the benchmark's 64 of 64): no left-out term, the
    reference is the uncut layer; and the parts that two shares would give,
    with the shared expert counted once, add up to it."""
    cfg, _, w = build()
    lp = ref.layer_params(w, 1)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(12, 64)), jnp.float32)
    mm = ref.highest_matmul
    whole = ref.mlp(x, lp, cfg, False, mm, held=(0, 8))
    m = ref._rms(x, lp["ln2"], cfg["rms_norm_eps"])
    y, counts = nn_ops.moe_experts(
        m, lp["router"], lp["e_w13"], lp["e_w2"], top_k=2, scale=2.0,
        scoring="sigmoid", select_bias=lp["e_bias"])
    assert int(counts[-1]) == 0 and int(counts.sum()) == 24   # none elsewhere
    from benchmark.models.laguna_reference import _swiglu
    shared = _swiglu(m, lp["s_w1"], lp["s_w3"], lp["s_w2"], mm)
    assert float(jnp.max(jnp.abs(y + shared - whole))) < 1e-4
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        part, counts = nn_ops.moe_experts(
            m, lp["router"], lp["e_w13"][lo:hi], lp["e_w2"][lo:hi],
            expert_lo=lo, top_k=2, scale=2.0, scoring="sigmoid",
            select_bias=lp["e_bias"])
        assert int(counts.sum()) == 24
        parts.append(part)
    assert float(jnp.max(jnp.abs(parts[0] + parts[1] + shared - whole))) < 1e-4
    assert min(float(jnp.max(jnp.abs(p))) for p in parts) > 1e-3


# --------------------------------------------------- the cache and engine
def test_the_model_states_latent_layers_and_where_its_counters_lie():
    _, model, _ = build(held=(0, 4))
    spec = model.cache_spec()
    assert [l.kind for l in spec.layers] == ["latent"] * 5
    assert [l.counters for l in spec.layers] == [2, 7, 7, 7, 7]
    assert {l.extra for l in spec.layers} == {MHC_COUNTERS}
    assert {(l.kv_heads, l.head_dim, l.arrays) for l in spec.layers} \
        == {(1, 128, 1)}
    with pytest.raises(ValueError, match="counters"):
        LayerCacheSpec("latent", 1, 128, counters=1, extra=MHC_COUNTERS)
    with pytest.raises(ValueError, match="counters"):
        LayerCacheSpec("latent", 1, 128, counters=3, extra=MHC_COUNTERS)
    eng = _engine(model)
    assert eng.prefix_cache and eng.prefill_bucket == 0
    assert len(eng.pool.layers) == 5


def test_a_counter_the_engine_does_not_know_refuses_by_name(monkeypatch):
    _, model, _ = build()
    spec = model.cache_spec()
    odd = type(spec)(tuple(
        LayerCacheSpec(l.kind, l.kv_heads, l.head_dim, counters=l.counters,
                       extra=("mhc_applications", "mhc_rounds"))
        for l in spec.layers), spec.max_positions)
    monkeypatch.setattr(model, "cache_spec", lambda: odd)
    with pytest.raises(ValueError, match="mhc_rounds"):
        _engine(model)


@pytest.mark.parametrize("kw,name", [
    ({"spec_k": 2}, "spec_k"),
    ({"prefill_bucket": 16}, "prefill_bucket"),
])
def test_what_a_latent_spec_cannot_serve_still_refuses_by_name(kw, name):
    _, model, _ = build()
    with pytest.raises(ValueError, match=re.escape(name) + r"=.*latent"):
        _engine(model, **kw)


def test_fused_steps_still_refuse_a_latent_spec_by_name():
    _, model, _ = build()
    flags.set_flags({"serving_fuse_steps": 4})
    try:
        with pytest.raises(ValueError, match="FLAGS_serving_fuse_steps=4"):
            _engine(model)
    finally:
        flags.set_flags({"serving_fuse_steps": 1})
