"""Self-speculative decoding tests (ISSUE r13): n-gram drafter, adaptive
throttle, multi-query verify attention numerics, allocator rollback edge
cases, live KV dedup, and end-to-end engine parity (greedy outputs must be
bitwise-identical with speculation on vs off, prefix cache on and off).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import (
    BlockAllocator,
    NgramDrafter,
    ServingEngine,
    SpecState,
)


# ------------------------------------------------------------- drafter
class TestNgramDrafter:
    def test_periodic_history_proposes_continuation(self):
        cyc = [3, 9, 17, 42]
        d = NgramDrafter(max_n=3)
        toks = cyc * 4
        assert d.propose(toks, 4) == cyc

    def test_no_match_returns_empty(self):
        d = NgramDrafter(max_n=3)
        assert d.propose([1, 2, 3, 4, 5, 6, 7], 4) == []

    def test_constant_tail_extrapolates_full_k(self):
        # the latest occurrence of (0, 0) sits one position back; the
        # periodic extrapolation must still fill all k draft slots
        d = NgramDrafter(max_n=3)
        assert d.propose([7, 0, 0, 0, 0, 0], 5) == [0] * 5

    def test_short_cycle_wraps_past_history_end(self):
        d = NgramDrafter(max_n=3)
        toks = [1, 2] * 6
        assert d.propose(toks, 6) == [1, 2, 1, 2, 1, 2]

    def test_longest_gram_wins(self):
        # suffix (5, 1, 2): the 3-gram occurred once (followed by 9); the
        # 2-gram (1, 2) also occurred followed by 8 — longest must win
        d = NgramDrafter(max_n=3, min_n=2)
        toks = [5, 1, 2, 9, 1, 2, 8, 5, 1, 2]
        assert d.propose(toks, 1) == [9]

    def test_incremental_history_extension(self):
        d = NgramDrafter(max_n=3)
        toks = [4, 6, 4, 6, 4]
        assert d.propose(toks, 2) == [6, 4]
        # extend the same history (as the engine does after a commit)
        toks = toks + [6, 4]
        assert d.propose(toks, 2) == [6, 4]

    def test_min_n_validation(self):
        with pytest.raises(ValueError):
            NgramDrafter(min_n=0)


class TestSpecState:
    def test_zero_accept_halves_then_pauses(self):
        s = SpecState(k_max=8, pause_ticks=10, miss_limit=2)
        assert s.draft_k(0) == 8
        s.record(8, 0, tick=0)
        assert s.k == 4
        s.record(4, 0, tick=1)          # second miss -> pause
        assert s.draft_k(2) == 0 and s.draft_k(10) == 0
        assert s.draft_k(11) == 2       # resumes with the halved k

    def test_no_match_tick_keeps_k(self):
        # a tick with nothing to draft is not evidence against drafts
        s = SpecState(k_max=8, miss_limit=4)
        s.record(0, 0, tick=0)
        assert s.k == 8

    def test_fruitless_probe_repauses_with_backoff(self):
        s = SpecState(k_max=4, pause_ticks=10, miss_limit=2)
        s.record(4, 0, tick=0)
        s.record(2, 0, tick=1)          # pause until 11
        assert s.draft_k(10) == 0 and s.draft_k(11) > 0
        s.record(1, 0, tick=11)         # ONE fruitless probe
        assert s.draft_k(12) == 0       # re-paused immediately
        assert s.draft_k(30) == 0       # ...and for twice as long
        assert s.draft_k(31) > 0
        s.record(1, 1, tick=31)         # acceptance resets the backoff
        assert s._pause == 10

    def test_lucky_low_acceptance_keeps_backoff_armed(self):
        # a chance 1-of-8 accept on random text must NOT re-enable a
        # fresh run of miss_limit probes — only decent acceptance
        # (>= 1/4 of the window) resets the backoff
        s = SpecState(k_max=8, pause_ticks=10, miss_limit=2)
        s.record(8, 0, tick=0)
        s.record(4, 0, tick=1)          # pause until 11, _pause -> 20
        assert s.draft_k(11) > 0
        s.record(8, 1, tick=11)         # lucky probe: 1 of 8 accepted
        assert s._pause == 20           # backoff NOT reset...
        s.record(2, 0, tick=12)         # ...so ONE miss re-pauses
        assert s.draft_k(13) == 0
        s.record(8, 2, tick=40)         # 2/8 = 1/4: decent -> reset
        assert s._pause == 10 and s._miss == 0

    def test_growth_on_high_acceptance(self):
        s = SpecState(k_max=8)
        s.k = 2
        s.record(2, 2, tick=0)
        assert s.k == 3
        s.record(3, 1, tick=1)          # below half: shrink
        assert s.k == 2

    def test_counters_and_acceptance(self):
        s = SpecState(k_max=4)
        s.record(4, 3, tick=0)
        s.record(4, 4, tick=1)
        assert (s.proposed, s.accepted, s.rollbacks) == (8, 7, 1)
        assert s.acceptance == pytest.approx(7 / 8)
        assert SpecState(k_max=4).acceptance == 0.0


# --------------------------------------------- multi-query verify numerics
def _dense_multi_oracle(q, k_pages, v_pages, tables, lens):
    """numpy reference: query i of slot s attends pos < lens[s] + i + 1."""
    slots, sq, hq, d = q.shape
    hkv = k_pages.shape[1]          # pages: [blocks, hkv, bs, d]
    g = hq // hkv
    scale = 1.0 / np.sqrt(d)
    out = np.zeros_like(q, dtype=np.float32)
    for s in range(slots):
        k = k_pages[tables[s]].transpose(0, 2, 1, 3).reshape(-1, hkv, d)
        v = v_pages[tables[s]].transpose(0, 2, 1, 3).reshape(-1, hkv, d)
        for i in range(sq):
            ctx = int(lens[s]) + i + 1
            for h in range(hq):
                kv_h = h // g
                sc = (k[:ctx, kv_h] @ q[s, i, h]).astype(np.float64) * scale
                sc -= sc.max()
                p = np.exp(sc)
                p /= p.sum()
                out[s, i, h] = p @ v[:ctx, kv_h]
    return out


def _multi_case(slots=3, sq=4, hq=4, hkv=2, d=8, bs=4, bps=4, seed=0):
    rng = np.random.default_rng(seed)
    num_blocks = 1 + slots * bps
    q = rng.standard_normal((slots, sq, hq, d)).astype(np.float32)
    k_pages = rng.standard_normal((num_blocks, hkv, bs, d)).astype(np.float32)
    v_pages = rng.standard_normal((num_blocks, hkv, bs, d)).astype(np.float32)
    tables = np.arange(1, num_blocks, dtype=np.int32).reshape(slots, bps)
    # base contexts leave room for the sq window inside the table
    lens = np.array([bps * bs - sq, 1, bs + 2], np.int32)[:slots]
    return q, k_pages, v_pages, tables, lens


class TestMultiQueryVerifyAttention:
    def test_xla_multi_matches_dense_oracle(self):
        from paddle_tpu.ops.pallas.paged_attention import (
            paged_attention_xla_multi)

        q, kp, vp, bt, lens = _multi_case()
        got = np.asarray(paged_attention_xla_multi(q, kp, vp, bt, lens))
        want = _dense_multi_oracle(q, kp, vp, bt, lens)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("kv_splits", [1, 2])
    def test_kernel_interpret_matches_oracle(self, kv_splits):
        from paddle_tpu.ops.pallas.paged_attention import (
            paged_attention_multi)

        q, kp, vp, bt, lens = _multi_case(seed=3)
        got = np.asarray(paged_attention_multi(
            q, kp, vp, bt, lens, kv_splits=kv_splits, interpret=True))
        want = _dense_multi_oracle(q, kp, vp, bt, lens)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_gqa_and_mha_shapes(self):
        from paddle_tpu.ops.pallas.paged_attention import (
            paged_attention_multi, paged_attention_xla_multi)

        for hq, hkv in ((4, 4), (8, 2)):
            q, kp, vp, bt, lens = _multi_case(hq=hq, hkv=hkv, seed=5)
            a = np.asarray(paged_attention_multi(q, kp, vp, bt, lens,
                                                 interpret=True))
            b = np.asarray(paged_attention_xla_multi(q, kp, vp, bt, lens))
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

    def test_sq1_window_matches_single_query_path(self):
        # a 1-token window must agree with the plain decode attention at
        # context len + 1 (same tokens visible)
        from paddle_tpu.ops.pallas.paged_attention import (
            paged_attention_xla, paged_attention_xla_multi)

        q, kp, vp, bt, lens = _multi_case(sq=1, seed=7)
        a = np.asarray(paged_attention_xla_multi(q, kp, vp, bt, lens))[:, 0]
        b = np.asarray(paged_attention_xla(q[:, 0], kp, vp, bt, lens + 1))
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


class TestPagedCachedAttentionWindow:
    def test_window_write_then_attend_matches_sequential(self):
        """One sq=4 verify dispatch == four single-token steps: identical
        page contents afterwards and identical attention outputs."""
        from paddle_tpu.ops.kernels.nn_ops import paged_cached_attention

        rng = np.random.default_rng(11)
        slots, sq, hq, hkv, d, bs, bps = 2, 4, 4, 2, 8, 4, 4
        nb = 1 + slots * bps
        q = rng.standard_normal((slots, sq, hq, d)).astype(np.float32)
        k = rng.standard_normal((slots, sq, hkv, d)).astype(np.float32)
        v = rng.standard_normal((slots, sq, hkv, d)).astype(np.float32)
        kp = rng.standard_normal((nb, hkv, bs, d)).astype(np.float32)
        vp = rng.standard_normal((nb, hkv, bs, d)).astype(np.float32)
        bt = np.arange(1, nb, dtype=np.int32).reshape(slots, bps)
        lens = np.array([3, 7], np.int32)   # crosses a block boundary

        import jax.numpy as jnp

        out_w, kp_w, vp_w = paged_cached_attention(
            q, k, v, jnp.asarray(kp), jnp.asarray(vp), bt, lens)
        kp_s, vp_s = jnp.asarray(kp), jnp.asarray(vp)
        outs = []
        for i in range(sq):
            o, kp_s, vp_s = paged_cached_attention(
                q[:, i:i + 1], k[:, i:i + 1], v[:, i:i + 1],
                kp_s, vp_s, bt, lens + i)
            outs.append(np.asarray(o))
        np.testing.assert_allclose(np.asarray(kp_w), np.asarray(kp_s),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(vp_w), np.asarray(vp_s),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(out_w),
                                   np.concatenate(outs, axis=1),
                                   rtol=2e-5, atol=2e-5)

    def test_window_overflow_lands_in_null_page(self):
        """Window positions past a slot's block table must write to the
        null page 0, not clamp onto the table's last real block."""
        from paddle_tpu.ops.kernels.nn_ops import paged_cached_attention

        rng = np.random.default_rng(13)
        slots, sq, hq, hkv, d, bs = 1, 4, 2, 2, 8, 4
        nb = 3
        q = rng.standard_normal((slots, sq, hq, d)).astype(np.float32)
        k = np.ones((slots, sq, hkv, d), np.float32)
        v = np.ones((slots, sq, hkv, d), np.float32)
        kp = np.zeros((nb, hkv, bs, d), np.float32)
        vp = np.zeros((nb, hkv, bs, d), np.float32)
        bt = np.array([[2, 1]], np.int32)          # 2 blocks = 8 positions
        lens = np.array([6], np.int32)             # window 6..9 overflows
        import jax.numpy as jnp

        _, kp2, vp2 = paged_cached_attention(q, k, v, jnp.asarray(kp),
                                             jnp.asarray(vp), bt, lens)
        kp2 = np.asarray(kp2)
        # positions 6, 7 land in block 1 (offsets 2, 3); 8, 9 overflow to
        # the null page — block 2 (the table head) must be untouched
        assert kp2[1, :, 2:].min() == 1.0 and kp2[1, :, :2].max() == 0.0
        assert kp2[2].max() == 0.0
        assert kp2[0].max() == 1.0                 # null page took the spill


# ------------------------------------------------------ allocator rollback
class TestAllocatorRollback:
    def test_rollback_rewinds_length_within_block(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        a.allocate("s", 2)
        for _ in range(2):
            a.append_token("s")
        t = a.rollback("s", 1)
        assert a.seq_len("s") == 3 and len(t) == 1
        a.check_invariants()

    def test_rollback_across_block_boundary_frees_block(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        a.allocate("s", 4)                 # exactly one full block
        before = a.free_blocks
        a.append_token("s")                # crosses into a 2nd block
        assert a.free_blocks == before - 1
        a.rollback("s", 1)                 # rejection right ON the boundary
        assert a.seq_len("s") == 4
        assert a.free_blocks == before     # the appended block came back
        a.check_invariants()

    def test_rollback_never_trims_reservation(self):
        a = BlockAllocator(num_blocks=10, block_size=4)
        a.reserve("s", 2, total_tokens=16)     # 4 blocks reserved up front
        assert len(a.table("s")) == 4
        a.append_token("s")
        a.rollback("s", 2)                     # down to 1 live token
        assert a.seq_len("s") == 1
        assert len(a.table("s")) == 4          # reservation intact
        a.check_invariants()

    def test_rollback_into_cow_forked_shared_block(self):
        """Speculative appends after a full-prompt cache hit write into the
        COW fork; rolling them back must trim only private blocks and leave
        the shared source referenced and shared."""
        a = BlockAllocator(num_blocks=16, block_size=4, prefix_cache=True)
        prompt = list(range(8))                # 2 full blocks
        a.allocate("s0", 8)
        a.register_prefix("s0", prompt)
        shared_last = a.table("s0")[-1]
        # full-prompt hit: reserve_prefix forks the last shared block
        table, matched, cow_src, _ = a.reserve_prefix("s1", prompt, 12)
        assert matched == 8 and cow_src == shared_last
        fork = table[1]
        assert fork != shared_last
        # speculative window: 3 appends (into the fork + a fresh block),
        # then reject all 3
        for _ in range(3):
            a.append_token("s1")
        assert a.seq_len("s1") == 11
        a.rollback("s1", 3)
        assert a.seq_len("s1") == 8
        assert a.table("s1")[1] == fork        # fork stays in the table
        assert a.refcount(shared_last) >= 1    # source still alive
        a.check_invariants()
        a.free("s1")
        a.free("s0")
        a.check_invariants()

    def test_rollback_validation(self):
        a = BlockAllocator(num_blocks=4, block_size=4)
        a.allocate("s", 2)
        with pytest.raises(ValueError):
            a.rollback("s", -1)
        with pytest.raises(ValueError):
            a.rollback("s", 3)
        assert a.rollback("s", 0) == a.table("s")


# ------------------------------------------------------------- live dedup
class TestLiveDedup:
    def test_register_prefix_swaps_duplicate_for_canonical(self):
        """Two identical prompts prefilled concurrently (neither saw the
        other in the index): the second register must adopt the canonical
        blocks and return the private duplicates to the pool."""
        a = BlockAllocator(num_blocks=16, block_size=4, prefix_cache=True)
        prompt = list(range(8))
        a.allocate("s0", 8)
        a.allocate("s1", 8)                     # admitted before s0 registers
        free_before = a.free_blocks
        a.register_prefix("s0", prompt)
        canon = list(a.table("s0"))
        assert a.register_prefix("s1", prompt) == 0   # nothing newly indexed
        assert a.table("s1") == canon
        assert len(a.last_dedup) == 2
        for i, dup, c in a.last_dedup:
            assert c == canon[i] and dup not in a.table("s1")
        assert a.free_blocks == free_before + 2  # duplicates recycled
        assert all(a.refcount(b) == 2 for b in canon)
        a.check_invariants()
        a.free("s0")
        a.free("s1")
        a.check_invariants()

    def test_engine_counts_dedup_admissions(self):
        cfg = GPTConfig.tiny()
        m = GPTForCausalLM(cfg)
        m.eval()
        rng = np.random.default_rng(2)
        p = [int(x) for x in rng.integers(0, cfg.vocab_size, 16)]
        eng = ServingEngine(m, max_slots=4, block_size=8, prefill_chunk=16)
        # two identical prompts in one burst: batched prefill runs both
        # before either registers, so the second's blocks dedup at register
        got = eng.generate([p, list(p)], max_new_tokens=4)
        assert got[0] == got[1]
        assert eng.stats()["dedup_admissions"] >= 1
        assert eng.stats()["kv"]["used_blocks"] == 0   # clean drain


# ------------------------------------------------------------ engine e2e
def _tiny():
    cfg = GPTConfig.tiny()
    m = GPTForCausalLM(cfg)
    m.eval()
    return cfg, m


def _zero_model():
    """All-zero weights: logits are identically 0, greedy emits token 0
    forever — a deterministic, perfectly-draftable stream with no training."""
    cfg, m = _tiny()
    for p in m.parameters():
        p.set_value(paddle.to_tensor(np.zeros(p.shape, np.float32)))
    return cfg, m


class TestSpeculativeEngine:
    def test_fuse_steps_and_spec_are_mutually_exclusive(self):
        from paddle_tpu.core import flags as _flags

        _, m = _tiny()
        old = _flags.get_flag("serving_fuse_steps")
        _flags.set_flags({"serving_fuse_steps": 4})
        try:
            with pytest.raises(ValueError, match="mutually exclusive"):
                ServingEngine(m, spec_k=4)
        finally:
            _flags.set_flags({"serving_fuse_steps": old})

    @pytest.mark.slow
    def test_greedy_parity_spec_on_vs_off_cache_on_and_off(self):
        cfg, m = _tiny()
        rng = np.random.default_rng(0)
        prompts = [
            [7, 8] * 10,                                   # repetitive
            [int(x) for x in rng.integers(0, cfg.vocab_size, 13)],
            [5, 5, 5, 5, 5, 5, 5, 5],                      # constant
        ]
        for cache in (True, False):
            kw = dict(max_slots=3, block_size=8, prefill_chunk=8,
                      prefix_cache=cache)
            on = ServingEngine(m, spec_k=4, **kw)
            off = ServingEngine(m, spec_k=0, **kw)
            got_on = on.generate(prompts, max_new_tokens=12)
            got_off = off.generate(prompts, max_new_tokens=12)
            assert got_on == got_off, f"prefix_cache={cache}"
            st = on.stats()
            assert st["kv"]["used_blocks"] == 0
            assert st["speculative"]["proposed"] >= st[
                "speculative"]["accepted"]

    def test_spec_actually_speculates_and_saves_steps(self):
        _, m = _zero_model()
        kw = dict(max_slots=2, block_size=8, prefill_chunk=8)
        prompt = [5, 0, 0, 0, 0]
        on = ServingEngine(m, spec_k=4, **kw)
        out_on = on.generate([prompt], max_new_tokens=24)
        off = ServingEngine(m, spec_k=0, **kw)
        out_off = off.generate([prompt], max_new_tokens=24)
        assert out_on == out_off
        s = on.stats()["speculative"]
        assert s["accepted"] > 0 and s["ticks"] > 0
        assert s["acceptance"] == 1.0 and s["rollbacks"] == 0
        assert on.steps < off.steps          # fewer dispatches, same tokens

    def test_rejection_rollback_keeps_parity(self):
        """A prompt whose n-gram history suggests the WRONG continuation
        for the zero model (which always emits 0): the first draft is
        rejected in full, the rollback rewinds it exactly, and later
        ticks recover on the constant stream — with exact greedy parity."""
        _, m = _zero_model()
        # after the first emitted 0, the history suffix is (3, 0) — whose
        # earlier occurrence continues with 9, so the draft is wrong
        prompt = [3, 0, 9, 5, 3]
        kw = dict(max_slots=2, block_size=8, prefill_chunk=8)
        on = ServingEngine(m, spec_k=4, spec_pause=4, **kw)
        off = ServingEngine(m, spec_k=0, **kw)
        assert on.generate([prompt], max_new_tokens=16) == \
            off.generate([prompt], max_new_tokens=16)
        s = on.stats()["speculative"]
        assert s["proposed"] > 0             # it really speculated
        assert s["rollbacks"] >= 1           # the bad draft was rejected
        assert s["accepted"] > 0             # and it recovered on the 0s

    def test_mixed_batch_sampled_rider_single_token_fallback(self):
        """temperature > 0 requests ride the spec tick with a zero draft
        length; the greedy request keeps parity, the sampled one advances
        one token per tick and completes."""
        _, m = _zero_model()
        kw = dict(max_slots=2, block_size=8, prefill_chunk=8)
        eng = ServingEngine(m, spec_k=4, **kw)
        greedy = eng.submit([5, 0, 0, 0, 0], max_new_tokens=16)
        rider = eng.submit([3, 1, 4, 1, 5], max_new_tokens=6,
                           temperature=0.8)
        eng.run_until_idle()
        assert len(rider.output_tokens) == 6
        off = ServingEngine(m, spec_k=0, **kw)
        want = off.generate([[5, 0, 0, 0, 0]], max_new_tokens=16)
        assert greedy.prompt + greedy.output_tokens == want[0]
        assert eng.stats()["speculative"]["accepted"] > 0

    def test_eos_inside_accepted_window_truncates(self):
        _, m = _zero_model()
        eng = ServingEngine(m, spec_k=4, max_slots=2, block_size=8,
                            prefill_chunk=8)
        out = eng.generate([[5, 0, 0, 0, 0]], max_new_tokens=24,
                           eos_token_id=0)
        assert out[0][-1] == 0 and len(out[0]) == 6   # stops at first 0
        st = eng.stats()
        assert st["kv"]["used_blocks"] == 0

    def test_max_new_tokens_respected_through_windows(self):
        # budget NOT a multiple of the window: the cap on draft length
        # must stop the window from overshooting
        _, m = _zero_model()
        eng = ServingEngine(m, spec_k=4, max_slots=2, block_size=8,
                            prefill_chunk=8)
        out = eng.generate([[5, 0, 0, 0, 0]], max_new_tokens=7)
        assert len(out[0]) == 5 + 7

    def test_stats_and_telemetry_expose_speculation(self):
        _, m = _zero_model()
        eng = ServingEngine(m, spec_k=4, max_slots=2, block_size=8,
                            prefill_chunk=8)
        req = eng.submit([5, 0, 0, 0, 0], max_new_tokens=12)
        eng.run_until_idle()
        s = eng.stats()["speculative"]
        assert s["enabled"] and s["k"] == 4
        assert set(s) >= {"ticks", "proposed", "accepted", "rollbacks",
                          "acceptance"}
        t = req.telemetry()
        assert t["spec_proposed"] >= t["spec_accepted"] > 0
        assert 0.0 <= t["spec_acceptance"] <= 1.0

    def test_spec_counters_registered_in_observability(self):
        from paddle_tpu.observability.registry import REGISTRY

        names = {m.name for m in REGISTRY.metrics()}
        assert {"serving_spec_proposed_total",
                "serving_spec_accepted_total",
                "serving_spec_rollbacks_total"} <= names
