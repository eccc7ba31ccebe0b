"""Laguna (models/laguna.py), its expert layer and its window cache, held to
the plain reference (benchmark/models/laguna_reference.py): seeded random
weights at a small size on the CPU.

Tolerances, each with its reason, are by the tests that use them."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.models import laguna_program as prog
from benchmark.models import laguna_reference as ref
from paddle_tpu.models import (GPTConfig, GPTForCausalLM, LagunaConfig,
                               LagunaForCausalLM, LlamaConfig,
                               LlamaForCausalLM)
from paddle_tpu.models.generation import LayerCacheSpec
from paddle_tpu.observability.registry import default_registry
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.blocks import WindowRings


def file_config(c: LagunaConfig) -> dict:
    """The configuration file of a LagunaConfig, as benchmark/configs has
    them: num_experts is the experts HELD, the published count beside it."""
    lo, hi = c.experts_held
    return {
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "intermediate_size": c.intermediate_size,
        "num_hidden_layers": c.num_layers, "head_dim": c.head_dim,
        "num_key_value_heads": c.num_key_value_heads,
        "num_attention_heads_per_layer": list(c.num_attention_heads_per_layer),
        "layer_types": list(c.layer_types),
        "sliding_window": c.sliding_window,
        "rope_parameters": c.rope_parameters,
        "max_position_embeddings": c.max_position_embeddings,
        "rms_norm_eps": c.rms_norm_eps,
        "mlp_only_layers": list(c.mlp_only_layers),
        "num_experts": hi - lo, "experts_held": [lo, hi],
        "published": {"num_experts": c.num_experts,
                      "num_hidden_layers": c.num_layers},
        "num_experts_per_tok": c.num_experts_per_tok,
        "moe_intermediate_size": c.moe_intermediate_size,
        "shared_expert_intermediate_size": c.shared_expert_intermediate_size,
        "norm_topk_prob": c.norm_topk_prob,
        "moe_routed_scaling_factor": c.moe_routed_scaling_factor,
    }


def build(held=(0, 8), dtype="float32", seed=5, **kw):
    cfg = file_config(LagunaConfig.tiny(experts_held=held, **kw))
    model, _ = prog.build_model(cfg, seed, dtype)
    model.eval()
    return cfg, model, ref.init_weights(cfg, seed, dtype)


def test_model_config_of_a_file_round_trips():
    cfg = file_config(LagunaConfig.tiny(experts_held=(4, 8)))
    c = prog.model_config(cfg)
    assert c.num_experts == 8 and c.experts_held == (4, 8)
    assert file_config(c) == cfg


def test_full_forward_matches_the_reference():
    cfg, model, w = build()
    ids = np.random.default_rng(0).integers(0, 255, (1, 40)).astype(np.int32)
    got = model(paddle.to_tensor(ids))._value[0]
    want = ref.logits_at(w, jnp.asarray(ids[0]), jnp.arange(40), cfg)
    # float32 both sides, another order of summation: round-off of logits
    # of size ~1
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6


def _served_logits(model, prompt, n_new, **engine_kw):
    """Every logit row the engine's programs produced for one request, by
    position: prefill chunks through the contiguous workspace, then decode
    steps through the paged cache. Read where the model's head returns."""
    rows = {}
    head, real = model.lm_head, model.lm_head.forward
    state = {"pos": 0}

    def tap(x):
        out = real(x)

        def keep(v):
            v = np.asarray(v, np.float32)
            if v.shape[1] > 1:                      # a prefill chunk
                for i in range(v.shape[1]):
                    rows[state["pos"] + i] = v[0, i]
                state["pos"] += v.shape[1]
            else:                                   # a decode step: slot 0
                rows[state["pos"]] = v[0, 0]
                state["pos"] += 1
        jax.debug.callback(keep, out._value, ordered=True)
        return out

    head.forward = tap
    try:
        eng = ServingEngine(model, max_slots=1, **engine_kw)
        out = eng.generate([prompt], max_new_tokens=n_new)[0]
        jax.effects_barrier()
    finally:
        head.forward = real
    # prefill pads its last chunk: positions from the prompt's end on were
    # overwritten by the decode steps' rows, which start at len(prompt)
    return out, rows, eng


@pytest.mark.parametrize("dtype,tol", [
    # float32 everywhere: only the order of summation differs (chunked
    # prefill, paged decode with online softmax); logits are of size ~1
    ("float32", 2e-5),
    # bf16 weights and activations against float32 `highest` over the same
    # bf16 weights: 8 bits of mantissa through 5 layers read 0.0116 here;
    # the fp8 control (3 bits under a per-tensor scale) reads 0.0279
    ("bfloat16", 0.018),
])
def test_served_logits_match_the_reference_across_window_and_chunk(dtype, tol):
    cfg, model, w = build(dtype=dtype)
    prompt = [int(t) for t in
              np.random.default_rng(1).integers(0, 255, 21)]
    # window 8, chunk 16: the prompt's second chunk crosses the window, and
    # 30 decode steps carry the context four windows further
    out, rows, eng = _served_logits(model, prompt, 30, block_size=4,
                                    prefill_chunk=16, max_model_len=96)
    assert eng.prefill_programs == 2 and len(out) == 51
    ids = jnp.asarray(out, jnp.int32)
    pos = jnp.arange(len(prompt) - 1, len(out) - 1)
    want = np.asarray(ref.logits_at(w, ids, pos, cfg))
    # the decode steps shifted the tap's counter from the padded chunk end
    padded = 32
    got = np.stack([rows[len(prompt) - 1]]
                   + [rows[padded + j] for j in range(29)])
    gap = float(np.max(np.abs(got - want)))
    print("served logits against the reference:", dtype, gap)
    assert gap < tol, gap
    # the control, one precision down, fails the same tolerance
    low = np.asarray(ref.logits_at(w, ids, pos, cfg, ref.fp8_matmul))
    assert float(np.max(np.abs(low - want))) > tol


def test_engine_serves_what_generate_gives():
    _, model, _ = build()
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(0, 255, n)] for n in (5, 19, 33, 50)]
    eng = ServingEngine(model, max_slots=3, block_size=4, prefill_chunk=16,
                        max_model_len=96)
    outs = eng.generate(prompts, max_new_tokens=24)
    for p, o in zip(prompts, outs):
        want = model.generate(paddle.to_tensor(np.asarray([p], np.int32)),
                              max_new_tokens=24)._value[0]
        assert [int(t) for t in want] == o


# ------------------------------------------------------------- the share
def test_two_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Guide section 4: the parts that all the shares give, with what every
    chip computes alike counted once, add up to the uncut reference."""
    cfg, _, w = build()
    lp = ref.layer_params(w, 1)
    m = jnp.asarray(np.random.default_rng(2).normal(size=(12, 64)), jnp.float32)
    mm = ref.highest_matmul
    whole = ref.routed_experts(m, lp, cfg, mm, held=(0, 8))
    shared = ref._swiglu(m, lp["s_w1"], lp["s_w3"], lp["s_w2"], mm)
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        half = dict(lp, e_w13=lp["e_w13"][lo:hi], e_w2=lp["e_w2"][lo:hi])
        parts.append(ref.routed_experts(m, half, cfg, mm, held=(lo, hi)))
    assert float(jnp.max(jnp.abs(parts[0] + parts[1] - whole))) < 1e-6
    assert float(jnp.max(jnp.abs(whole))) > 1e-3
    # and the program's layer, told which experts it holds, gives its share
    from paddle_tpu.ops.kernels.nn_ops import moe_experts

    for (lo, hi), part in zip(((0, 4), (4, 8)), parts):
        y, counts = moe_experts(m, lp["router"], lp["e_w13"][lo:hi],
                                lp["e_w2"][lo:hi], expert_lo=lo, top_k=3,
                                scale=2.5)
        assert float(jnp.max(jnp.abs(y - part))) < 1e-5
        assert int(counts.sum()) == 12 * 3
    both = sum(moe_experts(m, lp["router"], lp["e_w13"][lo:hi],
                           lp["e_w2"][lo:hi], expert_lo=lo, top_k=3,
                           scale=2.5)[0] for lo, hi in ((0, 4), (4, 8)))
    assert float(jnp.max(jnp.abs(both + shared - (whole + shared)))) < 1e-5


def test_a_share_of_the_model_matches_the_reference_of_that_share():
    cfg, model, w = build(held=(4, 8))
    ids = np.random.default_rng(3).integers(0, 255, (1, 24)).astype(np.int32)
    got = model(paddle.to_tensor(ids))._value[0]
    want = ref.logits_at(w, jnp.asarray(ids[0]), jnp.arange(24), cfg)
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6
    whole_cfg, _, whole_w = build(held=(0, 8))
    other = ref.logits_at(whole_w, jnp.asarray(ids[0]), jnp.arange(24),
                          whole_cfg)
    assert float(jnp.max(jnp.abs(other - want))) > 1e-3   # a share differs


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
def test_routing_is_dropless_when_every_token_goes_to_two_experts(interpret):
    from paddle_tpu.core import flags
    from paddle_tpu.ops.kernels.nn_ops import moe_experts

    rng = np.random.default_rng(4)
    d, f, e, t = 32, 16, 8, 40
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    # a router that sends every token to experts 2 and 5, whatever it holds
    router = np.zeros((d, e), np.float32)
    x = x.at[:, 0].set(10.0)
    router[0, 2], router[0, 5] = 3.0, 2.0
    w13 = jnp.asarray(rng.normal(size=(e, d, 2 * f)) * 0.1, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(e, f, d)) * 0.1, jnp.float32)
    if interpret:
        flags.set_flags({"pallas_interpret": True})
    try:
        y, counts = moe_experts(x, jnp.asarray(router), w13, w2, top_k=2,
                                scale=1.0)
    finally:
        if interpret:
            flags.set_flags({"pallas_interpret": False})
    assert [int(c) for c in counts] == [0, 0, t, 0, 0, t, 0, 0, 0]
    p = jax.nn.softmax(x @ jnp.asarray(router), -1)
    top = p[:, [2, 5]] / jnp.sum(p[:, [2, 5]], -1, keepdims=True)
    want = 0
    for j, ex in enumerate((2, 5)):
        h = x @ w13[ex]
        want = want + top[:, j:j + 1] * ((jax.nn.silu(h[:, :f]) * h[:, f:])
                                         @ w2[ex])
    assert float(jnp.max(jnp.abs(y - want))) < 1e-4      # every pair is in


@pytest.mark.parametrize("sizes", [[10, 0, 33, 7, 20], [0, 0, 0, 0, 0],
                                   [96, 0, 0, 0, 0], [1, 1, 1, 1, 1],
                                   [0, 40, 0, 0, 56], [31, 1, 32, 0, 3]])
def test_grouped_matmul_kernel_matches_ragged_dot(sizes):
    from paddle_tpu.ops.pallas.grouped_matmul import (grouped_matmul,
                                                      grouped_matmul_xla)

    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.normal(size=(96, 64)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(5, 64, 256)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul(lhs, rhs, gs, tm=32, interpret=True)
    assert float(jnp.max(jnp.abs(got - grouped_matmul_xla(lhs, rhs, gs)))) < 1e-4
    assert not np.any(np.asarray(got[sum(sizes):]))


# ----------------------------------------------------- window attention
def _dense_attention(q, k, v, q_pos, k_pos, window):
    g = q.shape[1] // k.shape[1]
    kr, vr = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, kr) / np.sqrt(q.shape[-1])
    seen = k_pos[None, :] <= q_pos[:, None]
    if window:
        seen = seen & (k_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(seen[None], s, -1e30)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vr)


@pytest.mark.parametrize("offset,window", [(0, None), (128, None), (256, 100),
                                           (128, 300), (0, 64)])
def test_prefill_kernel_skips_outside_the_band_and_matches(offset, window):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_prefill

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 256, 6, 128)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 512, 2, 128)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 512, 2, 128)), jnp.float32)
    got = flash_attention_prefill(q, k, v, offset, window=window,
                                  interpret=True)[0]
    want = _dense_attention(q[0], k[0], v[0], offset + jnp.arange(256),
                            jnp.arange(512), window)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("lens", [[3, 30, 77], [8, 24, 25], [1, 40, 41],
                                  [36, 47, 84]])
def test_paged_decode_over_a_ring_sees_the_window_only(kernel, lens):
    from paddle_tpu.ops.pallas import paged_attention as pa

    rng = np.random.default_rng(0)
    slots, hq, hkv, d, bs, window = 3, 6, 2, 128, 8, 24
    rings = WindowRings(slots, window, bs)
    ring = rings.ring_blocks
    assert ring == 4
    # one fetch of the kernel brings the whole ring, so a window whose
    # blocks pass the ring's last entry wraps inside a fetch: the last case
    # is made of those (first entry + pages > ring, in every slot)
    assert pa.pages_per_fetch(hkv, bs, d, 4, pa.window_pages(
        window, bs, ring)) == ring
    first, pages = pa.live_pages(np.asarray(lens), bs, window)
    if lens[0] == 36:
        assert ((first % ring + pages) > ring).all()
    tables = np.asarray([rings.reserve(i) for i in range(slots)], np.int32)
    kp = np.zeros((rings.num_blocks, hkv, bs, d), np.float32)
    vp = np.zeros_like(kp)
    keys = [rng.normal(size=(n, hkv, d)).astype(np.float32) for n in lens]
    vals = [rng.normal(size=(n, hkv, d)).astype(np.float32) for n in lens]
    for s, n in enumerate(lens):
        for p in range(n):
            blk = tables[s, (p // bs) % ring]
            kp[blk, :, p % bs], vp[blk, :, p % bs] = keys[s][p], vals[s][p]
    q = jnp.asarray(rng.normal(size=(slots, hq, d)), jnp.float32)
    args = (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
            jnp.asarray(lens, jnp.int32))
    got = (pa.paged_attention(*args, interpret=True, window=window)
           if kernel else pa.paged_attention_xla(*args, window=window))
    for s, n in enumerate(lens):
        want = _dense_attention(q[s][None], jnp.asarray(keys[s]),
                                jnp.asarray(vals[s]), jnp.asarray([n - 1]),
                                jnp.arange(n), window)[0]
        assert float(jnp.max(jnp.abs(got[s] - want))) < 1e-5


# ------------------------------------------------- the cache contract
def test_window_group_never_holds_more_than_its_ring():
    _, model, _ = build()
    eng = ServingEngine(model, max_slots=2, block_size=4, prefill_chunk=16,
                        max_model_len=128)
    rings, = eng.window_rings
    assert rings.ring_blocks == 3 and rings.window == 8
    # window + prefill_chunk, in blocks, is the most the contract allows
    assert rings.ring_blocks * 4 <= 8 + 16
    for (a, b), layer in zip(eng._layer_cols, eng._spec.layers):
        assert (b - a == 3) == (layer.kind == "window")
    for (k, _), layer in zip(eng.pool.layers, eng._spec.layers):
        blocks = rings.num_blocks if layer.kind == "window" else eng.num_blocks
        assert k.shape == (blocks, 2, 4, 16)
    assert rings.num_blocks == 1 + 2 * 3
    counter = default_registry().get("serving_window_blocks_total")
    before = {e: counter.value(event=e) for e in ("written", "recycled")}
    rng = np.random.default_rng(0)
    reqs = [eng.submit([int(t) for t in rng.integers(0, 255, n)],
                       max_new_tokens=m)
            for n, m in ((40, 60), (7, 9), (90, 30))]
    while eng.sched.has_work():
        eng.step()
        assert rings.used_blocks <= 2 * rings.ring_blocks
        assert rings.conservation_ok() and eng.allocator.conservation_ok()
        rings.check_invariants()
        eng.allocator.check_invariants()
        for row in eng._tables:              # a row's ring columns: its ring
            ring_cols = row[eng.max_blocks_per_seq:]
            assert len(ring_cols) == 3 and len(set(ring_cols) - {0}) in (0, 3)
    assert all(r.finish_reason == "length" for r in reqs)
    assert rings.used_blocks == 0
    written = counter.value(event="written") - before["written"]
    recycled = counter.value(event="recycled") - before["recycled"]
    # 99, 15 and 119 positions cached: 25 + 4 + 30 blocks went in, and all
    # but a ring of each (4 < ring stays whole) were overwritten
    assert written == 25 + 4 + 30 and recycled == 22 + 1 + 27
    keys = default_registry().get("serving_window_keys_total")
    assert 0 < keys.value(kind="read") < keys.value(kind="context")


@pytest.mark.parametrize("make,kv_heads,head_dim", [
    (lambda: GPTForCausalLM(GPTConfig.tiny()), 4, 32),
    (lambda: LlamaForCausalLM(LlamaConfig.tiny()), 2, 32),
], ids=["gpt", "llama"])
def test_uniform_models_state_full_layers_and_get_the_pool_they_had(
        make, kv_heads, head_dim):
    model = make()
    spec = model.cache_spec()
    c = model.config
    assert spec.max_positions == c.max_position_embeddings
    assert spec.layers == (LayerCacheSpec("full", kv_heads, head_dim),) \
        * c.num_layers
    eng = ServingEngine(model, max_slots=2, block_size=8, num_blocks=20)
    assert not eng.window_rings and not eng._counters
    assert eng._table_cols == eng.max_blocks_per_seq
    assert eng._tables.shape == (2, eng.max_blocks_per_seq)
    assert len(eng.pool.layers) == c.num_layers
    for k, v in eng.pool.layers:
        assert k.shape == v.shape == (20, kv_heads, 8, head_dim)
    assert not hasattr(model, "_decode_geometry")


def test_laguna_states_its_layers():
    spec = LagunaForCausalLM(LagunaConfig.tiny(experts_held=(0, 4))).cache_spec()
    assert [l.kind for l in spec.layers] == ["full", "window", "window",
                                             "window", "full"]
    assert [l.window for l in spec.layers] == [0, 8, 8, 8, 0]
    assert [l.counters for l in spec.layers] == [0, 5, 5, 5, 5]
    assert {(l.kv_heads, l.head_dim) for l in spec.layers} == {(2, 16)}
    with pytest.raises(ValueError):
        LayerCacheSpec("window", 2, 16)
    with pytest.raises(ValueError):
        LayerCacheSpec("ring", 2, 16, window=4)


@pytest.mark.parametrize("kw,name", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"spec_k": 2}, "spec_k"),
    ({"prefill_bucket": 16}, "prefill_bucket"),
])
def test_what_a_window_spec_cannot_serve_refuses_by_name(kw, name):
    _, model, _ = build()
    with pytest.raises(ValueError, match=re.escape(name) + r"=.*window"):
        ServingEngine(model, max_slots=2, block_size=4, prefill_chunk=16,
                      max_model_len=96, **kw)


def test_fused_steps_refuse_a_window_spec_by_name():
    from paddle_tpu.core import flags

    _, model, _ = build()
    flags.set_flags({"serving_fuse_steps": 4})
    try:
        with pytest.raises(ValueError, match="FLAGS_serving_fuse_steps=4"):
            ServingEngine(model, max_slots=2, block_size=4, prefill_chunk=16,
                          max_model_len=96)
    finally:
        flags.set_flags({"serving_fuse_steps": 1})


@pytest.mark.parametrize("call", ["export_kv_blocks", "ingest_kv_blocks"])
def test_the_kv_wire_refuses_a_window_spec_by_name(call):
    _, model, _ = build()
    eng = ServingEngine(model, max_slots=2, block_size=4, prefill_chunk=16,
                        max_model_len=96)
    with pytest.raises(NotImplementedError, match=call):
        getattr(eng, call)([1, 2, 3])


def test_expert_counters_ride_the_decode_step_and_reach_the_stats():
    _, model, _ = build(held=(0, 4))
    eng = ServingEngine(model, max_slots=2, block_size=4, prefill_chunk=16,
                        max_model_len=96)
    pairs = default_registry().get("serving_moe_pairs_total")
    before = pairs.total()
    eng.generate([[1, 2, 3, 4, 5], [9, 8, 7]], max_new_tokens=11)
    st = eng.stats()
    assert set(st["layer_counters"]) == {"h1", "h2", "h3", "h4"}
    # 10 decode steps of 2 slots, 3 experts a token, in every sparse layer
    for counts in st["layer_counters"].values():
        assert len(counts) == 5 and sum(counts) == 10 * 2 * 3
    assert pairs.total() - before == 4 * 60
    load = default_registry().get("serving_moe_expert_load_max_over_mean")
    assert all(v >= 1.0 for _, v in load.samples())


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_rotary_from_positions_is_the_references_rotary(kind):
    """Plain rotary over the whole head in sliding layers; in full layers
    YaRN's frequencies over the first half of the head, the rest passed
    through, cos and sin times the attention factor."""
    from paddle_tpu.models.laguna import rope_inv_freq
    from paddle_tpu.ops.kernels.nn_ops import rotary_from_positions

    c = LagunaConfig()                       # the published rope parameters
    cfg = {"rope_parameters": c.rope_parameters, "head_dim": 128}
    inv, factor = rope_inv_freq(c.rope_parameters[kind], 128)
    want_inv, dim, want_factor = ref.rope_inv_freq(
        cfg, "window" if kind == "sliding_attention" else "full")
    assert len(inv) == dim // 2 == (32 if kind == "full_attention" else 64)
    assert factor == want_factor and np.allclose(inv, want_inv, rtol=1e-12)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 5, 3, 128)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 5, 1, 128)), jnp.float32)
    pos = jnp.asarray([[0, 1, 2, 3, 4], [4000, 4001, 4002, 4003, 4004]])
    got_q, got_k = rotary_from_positions(q, k, pos, inv, factor)
    for b in range(2):
        assert float(jnp.max(jnp.abs(
            got_q[b] - ref._rope(q[b], pos[b], want_inv, dim, factor)))) < 1e-5
        assert float(jnp.max(jnp.abs(
            got_k[b] - ref._rope(k[b], pos[b], want_inv, dim, factor)))) < 1e-5
    if kind == "full_attention":             # the second half is untouched
        assert bool(jnp.all(got_q[..., 64:] == q[..., 64:]))
