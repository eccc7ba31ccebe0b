"""GLM-MoE-Lite (models/glm_moe_lite.py), its latent cache, its absorbed
attention and its biased sigmoid routing, held to the plain reference
(benchmark/models/glm_moe_lite_reference.py, expanded attention only): seeded
random weights at a small size on the CPU.

Tolerances, each with its reason, are by the tests that use them."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.models import glm_moe_lite_program as prog
from benchmark.models import glm_moe_lite_reference as ref
from paddle_tpu.models import GlmMoeLiteConfig
from paddle_tpu.models.generation import LayerCacheSpec, init_kv_cache
from paddle_tpu.observability.registry import default_registry
from paddle_tpu.ops.kernels import nn_ops
from paddle_tpu.serving import ServingEngine


def file_config(c: GlmMoeLiteConfig) -> dict:
    """The configuration file of a GlmMoeLiteConfig, as benchmark/configs has
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
        "rope_theta": c.rope_theta,
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
    }


def build(held=(0, 8), dtype="float32", seed=5, **kw):
    cfg = file_config(GlmMoeLiteConfig.tiny(experts_held=held, **kw))
    model, _ = prog.build_model(cfg, seed, dtype)
    model.eval()
    return cfg, model, ref.init_weights(cfg, seed, dtype)


def ref_logits(w, ids, pos, cfg, mm=ref.highest_matmul):
    """ref.logits_at as one compiled program (op by op it is most of this
    file's time)."""
    return jax.jit(lambda w, ids, pos: ref.logits_at(w, ids, pos, cfg, mm))(
        w, jnp.asarray(ids), jnp.asarray(pos))


def _engine(model, **kw):
    kw = {"max_slots": 2, "block_size": 4, "prefill_chunk": 16,
          "max_model_len": 96, **kw}
    return ServingEngine(model, **kw)


def test_model_config_of_a_file_round_trips():
    cfg = file_config(GlmMoeLiteConfig.tiny(experts_held=(4, 8)))
    c = prog.model_config(cfg)
    assert c.n_routed_experts == 8 and c.experts_held == (4, 8)
    assert file_config(c) == cfg
    with pytest.raises(ValueError, match="n_group"):
        GlmMoeLiteConfig.tiny(n_group=2)


def test_full_forward_matches_the_reference():
    cfg, model, w = build()
    ids = np.random.default_rng(0).integers(0, 255, (1, 40)).astype(np.int32)
    got = model(paddle.to_tensor(ids))._value[0]
    want = ref_logits(w, ids[0], np.arange(40), cfg)
    # float32 both sides, another order of summation: round-off of logits
    # of size ~1
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6
    # the seeded selection bias is there, and not zero
    bias = w["layers.1.e_bias"]
    assert bias.dtype == jnp.float32 and float(jnp.min(jnp.abs(bias))) > 0


def test_absorbed_attention_over_a_cache_is_the_expanded_form():
    """The program's two forms of one layer's mathematics: no cache
    (expanded, as published) and a contiguous cache (absorbed into the
    latent space), in chunks."""
    _, model, _ = build()
    ids = np.random.default_rng(4).integers(0, 255, (1, 32)).astype(np.int32)
    expanded = model(paddle.to_tensor(ids))._value[0]
    fn, params, buffers = model._functional_forward()
    pv, bv = [p._value for p in params], [b._value for b in buffers]
    caches = init_kv_cache(1, 32, model.cache_spec(), jnp.float32)
    assert all(len(c) == 1 and c[0].shape == (1, 32, 1, 128) for c in caches)
    rows = []
    for start in (0, 16):
        logits, caches = fn(pv, bv, jnp.asarray(ids[:, start:start + 16]),
                            caches, jnp.asarray(start, jnp.int32))
        rows.append(logits[0])
    # float32, the same sums in another association (q_nope W_uk^T . c
    # against q_nope . c W_uk): round-off
    assert float(jnp.max(jnp.abs(jnp.concatenate(rows) - expanded))) < 5e-6
    # a cache row is [latent 16, rotary key 4, zeros to 128 lanes]
    row = np.asarray(caches[1][0][0, :, 0])
    assert np.abs(row[:, :20]).min() > 0 and not row[:, 20:].any()


def _served_logits(eng, model, prompts, n_new):
    """[(sequence, logit rows)] a prompt, served one after the other: every
    logit row the engine's programs produced for it, in the order the head
    was called: prefill chunks (all their rows), then decode steps (slot
    0). Read where the model's head returns."""
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
    # float32 everywhere: only the order of summation differs (absorbed
    # against expanded, chunked prefill, paged decode with online softmax)
    ("float32", 2e-5),
    # bf16 weights, activations and latents against float32 `highest` over
    # the same bf16 weights: 8 bits of mantissa through 5 layers read
    # 0.0041 here; the fp8 control (3 bits under a per-tensor scale) 0.0252
    ("bfloat16", 0.009),
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
    # the first turn prefills its 45 tokens in 3 chunks of 16; the second
    # finds the document's 10 blocks of latents in the prefix cache and
    # prefills the 7 tokens after them
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
        worst = max(worst, float(np.max(np.abs(got - want))))
        low = np.asarray(ref_logits(w, ids, pos, cfg, ref.fp8_matmul))
        control = min(control, float(np.max(np.abs(low - want))))
    print("served logits against the reference:", dtype, worst,
          "the fp8 control:", control)
    assert worst < tol, worst
    # the control, one precision down, fails the same tolerance
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


# ------------------------------------------------------------ the routing
def test_the_bias_moves_the_choice_and_not_the_weight():
    cfg, _, w = build()
    lp = ref.layer_params(w, 1)
    m = jnp.asarray(np.random.default_rng(2).normal(size=(12, 64)), jnp.float32)
    mm = ref.highest_matmul
    scores = np.asarray(jax.nn.sigmoid(mm(m, lp["router"])))
    # a bias that lifts experts 6 and 7 over every score
    bias = jnp.zeros((8,), jnp.float32).at[6:].set(1.0)
    routed = np.asarray(ref.routing(m, dict(lp, e_bias=bias), cfg, mm))
    assert (np.count_nonzero(routed, axis=1) == 2).all()
    assert (routed[:, :6] == 0).all()              # the choice: 6 and 7
    want = 1.8 * scores[:, 6:] / scores[:, 6:].sum(-1, keepdims=True)
    assert np.abs(routed[:, 6:] - want).max() < 1e-6  # the weight: s alone
    plain = np.asarray(ref.routing(m, dict(lp, e_bias=bias * 0), cfg, mm))
    assert (np.argsort(-plain, axis=1)[:, :2]
            != np.argsort(-routed, axis=1)[:, :2]).any()
    # the program's layer, with and without the bias, against the reference
    for b in (lp["e_bias"], bias, None):
        y, counts = nn_ops.moe_experts(
            m, lp["router"], lp["e_w13"], lp["e_w2"], top_k=2, scale=1.8,
            scoring="sigmoid", select_bias=b)
        lp_b = dict(lp, e_bias=jnp.zeros((8,)) if b is None else b)
        part = ref.routed_experts(m, lp_b, cfg, mm, held=(0, 8))
        assert float(jnp.max(jnp.abs(y - part))) < 1e-5
        assert int(counts.sum()) == 12 * 2
    assert int(nn_ops.moe_experts(
        m, lp["router"], lp["e_w13"], lp["e_w2"], top_k=2, scale=1.8,
        scoring="sigmoid", select_bias=bias)[1][6:8].sum()) == 24
    with pytest.raises(ValueError, match="scoring"):
        nn_ops.moe_experts(m, lp["router"], lp["e_w13"], lp["e_w2"],
                           scoring="tanh")


def test_two_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Guide section 4: the parts that all the shares give, with what every
    chip computes alike counted once, add up to the uncut reference."""
    cfg, _, w = build()
    lp = ref.layer_params(w, 1)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(12, 64)), jnp.float32)
    mm = ref.highest_matmul
    whole = ref.mlp(x, lp, cfg, False, mm, held=(0, 8))
    m = ref._rms(x, lp["ln2"], 1e-5)
    shared = ref._swiglu(m, lp["s_w1"], lp["s_w3"], lp["s_w2"], mm)
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        half = dict(lp, e_w13=lp["e_w13"][lo:hi], e_w2=lp["e_w2"][lo:hi])
        parts.append(ref.routed_experts(m, half, cfg, mm, held=(lo, hi)))
        y, counts = nn_ops.moe_experts(
            m, lp["router"], half["e_w13"], half["e_w2"], expert_lo=lo,
            top_k=2, scale=1.8, scoring="sigmoid", select_bias=lp["e_bias"])
        # the program's layer, told which experts it holds, gives its share
        assert float(jnp.max(jnp.abs(y - parts[-1]))) < 1e-5
        assert int(counts.sum()) == 12 * 2
    # experts 0-3, experts 4-7, and the shared expert counted once
    assert float(jnp.max(jnp.abs(parts[0] + parts[1] + shared - whole))) < 1e-6
    assert min(float(jnp.max(jnp.abs(p))) for p in parts) > 1e-3


def test_a_share_of_the_model_matches_the_reference_of_that_share():
    cfg, model, w = build(held=(4, 8))
    ids = np.random.default_rng(3).integers(0, 255, (1, 24)).astype(np.int32)
    got = model(paddle.to_tensor(ids))._value[0]
    want = ref_logits(w, ids[0], np.arange(24), cfg)
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6
    whole_cfg, _, whole_w = build(held=(0, 8))
    other = ref_logits(whole_w, ids[0], np.arange(24), whole_cfg)
    assert float(jnp.max(jnp.abs(other - want))) > 1e-4   # a share differs


# ------------------------------------------------------------ the kernels
def _latents(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


@pytest.mark.parametrize("lens", [(1, 37, 96), (8, 9, 24)])
def test_latent_decode_kernel_reads_each_slots_live_pages(lens):
    from paddle_tpu.ops.pallas import paged_attention as pa

    rng = np.random.default_rng(0)
    slots, heads, w, vd, bs, nb, width = 3, 4, 24, 16, 8, 40, 12
    pages = _latents(rng, nb, 1, bs, w)
    q = _latents(rng, slots, heads, w)
    bt = jnp.asarray(rng.permutation(np.arange(1, nb))[:slots * width]
                     .reshape(slots, width), jnp.int32)
    cl = jnp.asarray(lens, jnp.int32)
    # what lies past a context must not reach the result: poison it
    flat = np.array(pa.from_pages(pages[bt]))
    for s, n in enumerate(lens):
        flat[s, n:] = np.nan
    poisoned = pages.at[bt].set(pa.to_pages(jnp.asarray(flat), bs))
    got = pa.latent_decode(q, poisoned, bt, cl, v_dim=vd, scale=0.3,
                           interpret=True)
    lat = pa.from_pages(pages[bt])[:, :, 0]
    sc = jnp.einsum("shw,skw->shk", q, lat) * 0.3
    live = jnp.arange(lat.shape[1])[None, :] < cl[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None], sc, -jnp.inf), axis=-1)
    want = jnp.einsum("shk,skv->shv", p, lat[..., :vd])
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6


@pytest.mark.parametrize("offset", [0, 32, 96])
def test_latent_prefill_kernel_matches_plain_attention_at_an_offset(offset):
    from paddle_tpu.ops.pallas.flash_attention import latent_prefill

    rng = np.random.default_rng(offset)
    sq, sk, heads, w, vd = 32, 128, 4, 24, 16
    q = _latents(rng, 1, sq, heads, w)
    lat = np.array(_latents(rng, 1, sk, w))
    lat[0, offset + sq:] = np.nan           # keys after the chunk: unseen
    got = latent_prefill(q, jnp.asarray(lat), offset, v_dim=vd, scale=0.3,
                         block_q=16, block_k=32, interpret=True)
    lat = jnp.nan_to_num(jnp.asarray(lat))
    sc = jnp.einsum("bqhw,bkw->bhqk", q, lat) * 0.3
    seen = jnp.arange(sk)[None, :] <= offset + jnp.arange(sq)[:, None]
    p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bkv->bqhv", p, lat[..., :vd])
    assert float(jnp.max(jnp.abs(got - want))) < 2e-6


def test_the_ops_take_the_kernels_in_interpret_mode_and_agree_with_xla():
    from paddle_tpu.core import flags

    rng = np.random.default_rng(3)
    heads, w, vd, bs = 4, 128, 16, 8
    q = _latents(rng, 2, 1, heads, w)
    new = _latents(rng, 2, 1, w)
    pages = _latents(rng, 9, 1, bs, w)
    bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    sl = jnp.asarray([5, 31], jnp.int32)
    qc = _latents(rng, 1, 128, heads, w)
    newc = _latents(rng, 1, 128, w)
    cache = _latents(rng, 1, 512, 1, w)

    def both():
        return (nn_ops.latent_paged_attention(q, new, pages, bt, sl, vd, 0.3),
                nn_ops.latent_cached_attention(qc, newc, cache, 128, vd, 0.3))

    xla = both()
    flags.set_flags({"pallas_interpret": True})
    try:
        kernel = both()
    finally:
        flags.set_flags({"pallas_interpret": False})
    for (a, pa_), (b, pb) in zip(xla, kernel):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-6
        assert bool(jnp.array_equal(pa_, pb))
    # the append: slot 1's row 31 went to its table's last page, last row
    assert bool(jnp.array_equal(xla[0][1][8, 0, 7], new[1, 0]))


# --------------------------------------------------- the cache and engine
def test_the_model_states_latent_layers_and_the_pool_keeps_one_array():
    _, model, _ = build(held=(0, 4))
    spec = model.cache_spec()
    assert [l.kind for l in spec.layers] == ["latent"] * 5
    assert [l.counters for l in spec.layers] == [0, 5, 5, 5, 5]
    assert {(l.kv_heads, l.head_dim, l.arrays) for l in spec.layers} \
        == {(1, 128, 1)}
    assert model.config.latent_width == 20
    with pytest.raises(ValueError, match="one head"):
        LayerCacheSpec("latent", 2, 16)
    assert LayerCacheSpec("full", 2, 16).arrays == 2
    eng = _engine(model)
    assert eng.prefix_cache and eng.prefill_bucket == 0
    assert len(eng.pool.layers) == 5
    for arrays in eng.pool.layers:
        assert len(arrays) == 1
        assert arrays[0].shape == (eng.num_blocks, 1, 4, 128)
    assert eng.pool.nbytes() == 5 * eng.num_blocks * 4 * 128 * 4


@pytest.mark.parametrize("kw,name", [
    ({"spec_k": 2}, "spec_k"),
    ({"prefill_bucket": 16}, "prefill_bucket"),
])
def test_what_a_latent_spec_cannot_serve_refuses_by_name(kw, name):
    _, model, _ = build()
    with pytest.raises(ValueError, match=re.escape(name) + r"=.*latent"):
        _engine(model, **kw)


def test_fused_steps_refuse_a_latent_spec_by_name():
    from paddle_tpu.core import flags

    _, model, _ = build()
    flags.set_flags({"serving_fuse_steps": 4})
    try:
        with pytest.raises(ValueError, match="FLAGS_serving_fuse_steps=4"):
            _engine(model)
    finally:
        flags.set_flags({"serving_fuse_steps": 1})


@pytest.mark.parametrize("call", ["export_kv_blocks", "ingest_kv_blocks"])
def test_the_kv_wire_refuses_a_latent_spec_by_name(call):
    _, model, _ = build()
    with pytest.raises(NotImplementedError, match=call + ".*latent"):
        getattr(_engine(model), call)([1, 2, 3])


def test_keys_and_pairs_are_counted_by_the_kernels_own_bounds():
    _, model, _ = build(held=(0, 4))
    eng = _engine(model)
    reg = default_registry()
    keys = reg.get("serving_latent_keys_total")
    pairs = reg.get("serving_moe_pairs_total")
    paged = reg.get("serving_paged_keys_total")
    k0 = {k: keys.value(kind=k) for k in ("fetched", "live")}
    p0, paged0 = pairs.total(), paged.total()
    eng.generate([[1, 2, 3, 4, 5], [9, 8, 7]], max_new_tokens=11)
    fetched = keys.value(kind="fetched") - k0["fetched"]
    live = keys.value(kind="live") - k0["live"]
    # 10 decode steps of 2 slots over 5 layers: contexts 6..15 and 4..13
    assert live == 5 * (sum(range(6, 16)) + sum(range(4, 14)))
    # whole pages of 4 are fetched: never less than the live keys, never a
    # page a slot a layer a step more
    assert live <= fetched < live + 5 * 2 * 10 * 4
    assert paged.total() == paged0          # no full layers: not theirs
    st = eng.stats()
    assert set(st["layer_counters"]) == {"h1", "h2", "h3", "h4"}
    for counts in st["layer_counters"].values():
        assert len(counts) == 5 and sum(counts) == 10 * 2 * 2
    assert pairs.total() - p0 == 4 * 40
