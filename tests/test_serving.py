"""Serving-stack tests (ISSUE r11): paged KV allocator invariants, ragged
paged-attention numerics vs a dense oracle, continuous-batching scheduler
admission/eviction, engine decode parity with model.generate(), and an HTTP
round-trip smoke over the stdlib front end.
"""
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (
    GPTConfig,
    GPTForCausalLM,
    LlamaConfig,
    LlamaForCausalLM,
)
from paddle_tpu.serving import (
    BlockAllocator,
    Request,
    Scheduler,
    ServingEngine,
    ServingServer,
)


# ------------------------------------------------------------- allocator
class TestBlockAllocator:
    def test_null_block_never_handed_out(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        handed = a.allocate("s0", 4 * 7)  # drain the whole pool
        assert sorted(handed) == list(range(1, 8))
        assert BlockAllocator.NULL_BLOCK not in handed
        assert a.free_blocks == 0

    def test_alloc_append_free_conservation(self):
        a = BlockAllocator(num_blocks=10, block_size=4)
        t0 = a.allocate("s0", 5)          # 2 blocks (ceil 5/4)
        t1 = a.allocate("s1", 4)          # exactly 1 block
        assert len(t0) == 2 and len(t1) == 1
        assert a.used_blocks == 3 and a.free_blocks == 6
        # appends within the last block don't grow the table...
        for _ in range(3):                # 5 -> 8 tokens, still 2 blocks
            assert len(a.append_token("s0")) == 2
        # ...and the boundary-crossing append grows it by exactly one
        assert len(a.append_token("s0")) == 3
        assert a.seq_len("s0") == 9
        # free returns every block; the pool is conserved
        assert a.free("s0") == 3
        assert a.free("s1") == 1
        assert a.used_blocks == 0 and a.free_blocks == 9
        assert a.sequences() == []

    def test_exhaustion_and_duplicates_raise(self):
        a = BlockAllocator(num_blocks=3, block_size=2)
        a.allocate("s0", 4)               # both allocatable blocks
        with pytest.raises(MemoryError):
            a.allocate("s1", 1)
        with pytest.raises(KeyError):
            a.allocate("s0", 1)
        with pytest.raises(MemoryError):
            a.append_token("s0")          # 4 -> 5 needs a 3rd block
        a.free("s0")
        assert a.can_allocate(4) and not a.can_allocate(5)

    def test_reserve_claims_worst_case_upfront(self):
        a = BlockAllocator(num_blocks=10, block_size=4)
        t = a.reserve("s0", 5, 12)        # live len 5, worst case 12 tokens
        assert len(t) == 3                # ceil(12/4) blocks immediately
        assert a.seq_len("s0") == 5
        # appends never grow a reserved table (the whole point: the table
        # can be uploaded to the device once and never touched again)
        for _ in range(7):                # 5 -> 12 tokens
            assert len(a.append_token("s0")) == 3
        assert a.free("s0") == 3
        assert a.used_blocks == 0
        with pytest.raises(MemoryError):
            a.reserve("big", 1, 100)

    def test_occupancy_report_math(self):
        a = BlockAllocator(num_blocks=9, block_size=4)
        a.allocate("s0", 6)               # 2 blocks, 6 of 8 token slots
        r = a.occupancy_report()
        assert r["num_blocks"] == 8 and r["block_size"] == 4
        assert r["used_blocks"] == 2 and r["tokens"] == 6
        assert r["occupancy"] == pytest.approx(2 / 8)
        assert r["fragmentation"] == pytest.approx(1 - 6 / 8)

    def test_lifo_reuse(self):
        a = BlockAllocator(num_blocks=6, block_size=2)
        t = a.allocate("s0", 6)
        a.free("s0")
        assert a.allocate("s1", 6) == t   # hottest blocks come back first

    def test_randomized_interleaved_stress_conservation(self):
        """Hammer every mutating op in random interleavings under pool
        pressure; the conservation law (live + evictable + free ==
        allocatable) and the full invariant sweep must hold after EVERY
        op — including the export/import streaming path into a second
        allocator and rejected corrupt imports."""
        rng = np.random.default_rng(0xC0FFEE)
        bs = 4
        a = BlockAllocator(num_blocks=24, block_size=bs)
        b = BlockAllocator(num_blocks=24, block_size=bs)  # stream target

        def check():
            for al in (a, b):
                al.check_invariants()
                assert al.conservation_ok()
                assert (al.used_blocks + al.cached_blocks + al.free_blocks
                        == al.num_blocks - 1)

        prompts = {}                     # seq_id -> prompt token ids
        seq_no = 0
        check()
        for _ in range(700):
            op = int(rng.integers(0, 7))
            sids = a.sequences()
            try:
                if op == 0 or not sids:          # admit (3 entry points)
                    seq_no += 1
                    sid = f"s{seq_no}"
                    plen = int(rng.integers(1, 13))
                    # tiny vocab: later prompts really share prefixes
                    toks = [int(t) for t in rng.integers(0, 5, plen)]
                    mode = int(rng.integers(3))
                    total = plen + int(rng.integers(0, 9))
                    if mode == 0:
                        a.allocate(sid, plen)
                    elif mode == 1:
                        a.reserve(sid, plen, total)
                    else:
                        a.reserve_prefix(sid, toks, total)
                    prompts[sid] = toks
                elif op == 1:                    # decode one token
                    a.append_token(sids[int(rng.integers(len(sids)))])
                elif op == 2:                    # speculative rollback
                    sid = sids[int(rng.integers(len(sids)))]
                    n = int(rng.integers(0, a.seq_len(sid) + 1))
                    a.rollback(sid, min(n, 5))
                elif op == 3:                    # publish prompt blocks
                    sid = sids[int(rng.integers(len(sids)))]
                    a.register_prefix(sid, prompts[sid])
                elif op == 4:                    # finish
                    sid = sids[int(rng.integers(len(sids)))]
                    a.free(sid)
                    prompts.pop(sid, None)
                elif op == 5:                    # stream: export -> import
                    sid = sids[int(rng.integers(len(sids)))]
                    for rec in a.export_prefix(prompts[sid]):
                        _, imp = a.import_block(rec["prev"], rec["tokens"],
                                                rec["digest"])
                        assert imp is False      # self-import dedups
                        b.import_block(rec["prev"], rec["tokens"],
                                       rec["digest"])
                else:                            # corrupt stream rejected
                    sid = sids[int(rng.integers(len(sids)))]
                    recs = a.export_prefix(prompts[sid])
                    if recs:
                        bad = dict(recs[0])
                        bad["tokens"] = [t + 1 for t in bad["tokens"]]
                        with pytest.raises(ValueError):
                            b.import_block(bad["prev"], bad["tokens"],
                                           bad["digest"])
            except MemoryError:
                # pool pressure is part of the schedule: evict a victim
                victims = a.sequences()
                if victims:
                    v = victims[int(rng.integers(len(victims)))]
                    a.free(v)
                    prompts.pop(v, None)
            check()
        for sid in a.sequences():                # drain to empty
            a.free(sid)
            check()
        assert a.used_blocks == 0


# ------------------------------------------------- paged attention numerics
def _dense_oracle(q, k_pages, v_pages, tables, lens, scale):
    """Hand-built numpy reference: per-slot gather + masked softmax."""
    slots, hq, d = q.shape
    hkv = k_pages.shape[1]          # pages: [blocks, hkv, bs, d]
    g = hq // hkv
    out = np.zeros_like(q, dtype=np.float32)
    for s in range(slots):
        ctx = int(lens[s])
        k = (k_pages[tables[s]].transpose(0, 2, 1, 3)
             .reshape(-1, hkv, d)[:ctx])                   # [ctx, hkv, d]
        v = (v_pages[tables[s]].transpose(0, 2, 1, 3)
             .reshape(-1, hkv, d)[:ctx])
        for h in range(hq):
            kv_h = h // g
            sc = (k[:, kv_h] @ q[s, h]).astype(np.float64) * scale
            sc -= sc.max()
            p = np.exp(sc)
            p /= p.sum()
            out[s, h] = p @ v[:, kv_h]
    return out


def _make_case(slots=3, hq=4, hkv=2, d=8, bs=4, blocks_per_seq=3, seed=0):
    rng = np.random.default_rng(seed)
    num_blocks = 1 + slots * blocks_per_seq
    q = rng.standard_normal((slots, hq, d)).astype(np.float32)
    k_pages = rng.standard_normal((num_blocks, hkv, bs, d)).astype(np.float32)
    v_pages = rng.standard_normal((num_blocks, hkv, bs, d)).astype(np.float32)
    tables = np.arange(1, num_blocks, dtype=np.int32)
    tables = tables.reshape(slots, blocks_per_seq)
    max_ctx = blocks_per_seq * bs
    # ragged: one full, one one-token, one mid-block context
    lens = np.array([max_ctx, 1, bs + 2], np.int32)[:slots]
    return q, k_pages, v_pages, tables, lens


class TestPagedAttentionNumerics:
    def test_xla_fallback_matches_oracle(self):
        from paddle_tpu.ops.pallas.paged_attention import paged_attention_xla

        q, kp, vp, bt, cl = _make_case()
        scale = 1.0 / np.sqrt(q.shape[-1])
        got = np.asarray(paged_attention_xla(q, kp, vp, bt, cl))
        want = _dense_oracle(q, kp, vp, bt, cl, scale)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_kernel_interpret_matches_oracle(self):
        from paddle_tpu.ops.pallas.paged_attention import paged_attention

        q, kp, vp, bt, cl = _make_case(seed=1)
        scale = 1.0 / np.sqrt(q.shape[-1])
        got = np.asarray(paged_attention(q, kp, vp, bt, cl, interpret=True))
        want = _dense_oracle(q, kp, vp, bt, cl, scale)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    # the three serve cells' head shapes at small sizes: GPT-3 XL's MHA
    # (g = 1), Laguna's full layers (g = 6) and window layers' group (g = 9)
    @pytest.mark.parametrize("contexts", ["edges", "wide_table"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("bs", [16, 128])
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (12, 2), (18, 2)],
                             ids=["g1", "g6", "g9"])
    def test_kernel_follows_each_slots_live_pages(self, hq, hkv, bs, dtype,
                                                  contexts):
        """The kernel loops over a slot's live pages, several a fetch:
        contexts of 1, a block's edge and one past it, one that ends inside
        a multi-page fetch, a full table, an idle slot (null table) between
        two live ones; and a table wider than any context."""
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import paged_attention as pa

        d = 128
        fetch_keys = bs * pa.pages_per_fetch(
            hkv, bs, d, jnp.dtype(dtype).itemsize, 1 << 30)
        if contexts == "edges":
            width = 2 * fetch_keys // bs + 1           # two fetches and a page
            lens = [1, bs, 0, bs + 1, fetch_keys + bs + 3, width * bs]
        else:
            width = 4 * fetch_keys // bs
            lens = [fetch_keys // 2 + 1, 0, fetch_keys + 1, 2 * bs - 1]
        assert width * bs > fetch_keys > bs, "no multi-page fetch to test"
        slots = len(lens)
        rng = np.random.default_rng(hq * 1000 + bs + len(dtype))
        nb = 1 + slots * width
        q = jnp.asarray(rng.standard_normal((slots, hq, d)), dtype)
        kp = jnp.asarray(rng.standard_normal((nb, hkv, bs, d)), dtype)
        vp = jnp.asarray(rng.standard_normal((nb, hkv, bs, d)), dtype)
        bt = rng.permutation(np.arange(1, nb, dtype=np.int32)).reshape(
            slots, width)
        idle = [i for i, n in enumerate(lens) if n == 0]
        bt[idle] = 0                     # the engine hands an idle slot a
        cl = np.maximum(lens, 1).astype(np.int32)   # null row and context 1
        got = np.asarray(pa.paged_attention(q, kp, vp, bt, cl,
                                            interpret=True), np.float32)
        want = _dense_oracle(np.asarray(q, np.float32),
                             np.asarray(kp, np.float32),
                             np.asarray(vp, np.float32), bt, cl,
                             1.0 / np.sqrt(d))
        # float32: the oracle's own; a bf16 pool: the output's rounding
        tol = 1e-5 if dtype == "float32" else 2e-2
        live = [i for i in range(slots) if i not in idle]
        np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
        assert np.isfinite(got).all()

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_kernel_reads_nothing_past_a_context(self, dtype):
        """NaN in every page past each context and in every row past it in
        its last page: nothing dead reaches the output."""
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.paged_attention import paged_attention

        slots, hq, hkv, d, bs, width = 4, 8, 4, 128, 16, 40
        lens = np.array([1, bs + 5, 300, 2 * bs], np.int32)
        rng = np.random.default_rng(5)
        nb = 1 + slots * width
        q = jnp.asarray(rng.standard_normal((slots, hq, d)), dtype)
        kp = rng.standard_normal((nb, hkv, bs, d)).astype(np.float32)
        vp = rng.standard_normal((nb, hkv, bs, d)).astype(np.float32)
        bt = rng.permutation(np.arange(1, nb, dtype=np.int32)).reshape(
            slots, width)
        kp_bad, vp_bad = kp.copy(), vp.copy()
        for s in range(slots):
            full, rest = divmod(int(lens[s]), bs)
            for pool in (kp_bad, vp_bad):
                pool[bt[s, full + (rest > 0):]] = np.nan
                if rest:
                    pool[bt[s, full], :, rest:] = np.nan
        clean = np.asarray(paged_attention(
            q, jnp.asarray(kp, dtype), jnp.asarray(vp, dtype), bt, lens,
            interpret=True), np.float32)
        got = np.asarray(paged_attention(
            q, jnp.asarray(kp_bad, dtype), jnp.asarray(vp_bad, dtype), bt,
            lens, interpret=True), np.float32)
        np.testing.assert_array_equal(got, clean)

    def test_gqa_head_mapping(self):
        # hq=6 over hkv=3: kv head h must serve exactly q heads [2h, 2h+1]
        from paddle_tpu.ops.pallas.paged_attention import (
            paged_attention,
            supports,
        )

        q, kp, vp, bt, cl = _make_case(slots=2, hq=6, hkv=3, d=4,
                                       blocks_per_seq=2, seed=7)
        assert supports(q.shape, kp.shape)
        scale = 1.0 / np.sqrt(q.shape[-1])
        got = np.asarray(paged_attention(q, kp, vp, bt, cl, interpret=True))
        want = _dense_oracle(q, kp, vp, bt, cl, scale)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_paged_cached_attention_appends_then_attends(self):
        # the engine's per-step op: write this step's K/V at each slot's
        # next position, then attend over the now ctx+1 ragged context
        import jax.numpy as jnp

        from paddle_tpu.ops import api

        q, kp, vp, bt, cl = _make_case(seed=11)
        bs = kp.shape[2]
        # every slot needs a free next position inside its table
        cl = np.minimum(cl, bt.shape[1] * bs - 1).astype(np.int32)
        rng = np.random.default_rng(11)
        slots, hq, d = q.shape
        hkv = kp.shape[1]
        k_new = rng.standard_normal((slots, 1, hkv, d)).astype(np.float32)
        v_new = rng.standard_normal((slots, 1, hkv, d)).astype(np.float32)
        out, kp2, vp2 = api.paged_cached_attention(
            jnp.asarray(q)[:, None], jnp.asarray(k_new), jnp.asarray(v_new),
            jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
            jnp.asarray(cl))
        # reference: scatter the new token into a copy, then dense oracle
        kp_ref, vp_ref = kp.copy(), vp.copy()
        for s in range(slots):
            pg = bt[s, cl[s] // bs]
            kp_ref[pg, :, cl[s] % bs] = k_new[s, 0]
            vp_ref[pg, :, cl[s] % bs] = v_new[s, 0]
        want = _dense_oracle(q, kp_ref, vp_ref, bt, cl + 1,
                             1.0 / np.sqrt(d))
        np.testing.assert_allclose(np.asarray(out)[:, 0], want,
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(kp2), kp_ref)
        np.testing.assert_array_equal(np.asarray(vp2), vp_ref)

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("hkv", [4, 1], ids=["mha", "gqa4"])
    @pytest.mark.parametrize("sq", [1, 4])
    def test_append_writes_rows_where_the_pool_lies(self, sq, hkv, dtype):
        """The append against a NumPy loop that writes row by row: a live
        slot's token at position p lands at (block_table[p // bs], :,
        p % bs), every other element of every real page comes back
        bit-identical, and idle slots and positions past the block table
        touch only the null page 0."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.ops.kernels.nn_ops import paged_cached_attention
        from paddle_tpu.ops.pallas.paged_attention import (
            paged_attention_xla,
            paged_attention_xla_multi,
        )

        rng = np.random.default_rng(100 * sq + 10 * hkv
                                    + (dtype == "float32"))
        slots, hq, d, bs, bps = 5, 4, 8, 4, 3
        nb = 1 + slots * bps
        pool_dt = jnp.dtype(dtype)
        q = rng.standard_normal((slots, sq, hq, d)).astype(np.float32)
        k = rng.standard_normal((slots, sq, hkv, d)).astype(np.float32)
        v = rng.standard_normal((slots, sq, hkv, d)).astype(np.float32)
        kp = rng.standard_normal((nb, hkv, bs, d)).astype(pool_dt)
        vp = rng.standard_normal((nb, hkv, bs, d)).astype(pool_dt)
        bt = np.arange(1, nb, dtype=np.int32).reshape(slots, bps)
        bt[2] = 0                                    # slot 2 is idle
        # block start; crossing a block boundary (sq 4); idle; the table's
        # last position (the window overflows); past the table altogether
        lens = np.array([0, bs + 2, 0, bps * bs - 1, bps * bs], np.int32)

        out, kp2, vp2 = jax.jit(paged_cached_attention)(
            q, k, v, jnp.asarray(kp), jnp.asarray(vp), bt, lens)
        kp2, vp2 = np.asarray(kp2), np.asarray(vp2)
        assert kp2.dtype == pool_dt and vp2.dtype == pool_dt

        kp_ref, vp_ref = kp.copy(), vp.copy()
        null_offsets = set()
        for s in range(slots):
            for i in range(sq):
                p = int(lens[s]) + i
                pg = int(bt[s, p // bs]) if p // bs < bps else 0
                if pg == 0:
                    null_offsets.add(p % bs)
                    continue
                kp_ref[pg, :, p % bs] = k[s, i].astype(pool_dt)
                vp_ref[pg, :, p % bs] = v[s, i].astype(pool_dt)
        bits = f"uint{8 * pool_dt.itemsize}"
        for got, ref, before in ((kp2, kp_ref, kp), (vp2, vp_ref, vp)):
            np.testing.assert_array_equal(got[1:].view(bits),
                                          ref[1:].view(bits))
            spared = sorted(set(range(bs)) - null_offsets)
            np.testing.assert_array_equal(got[0][:, spared].view(bits),
                                          before[0][:, spared].view(bits))
        assert null_offsets, "no slot exercised the null page"

        if sq == 1:
            live = [0, 1, 3]                # slots whose window is in table
            want = np.asarray(paged_attention_xla(
                q[:, 0], kp_ref, vp_ref, bt, lens + 1))[:, None]
        else:
            live = [0, 1]
            want = np.asarray(paged_attention_xla_multi(
                q, kp_ref, vp_ref, bt, lens))
        np.testing.assert_allclose(np.asarray(out)[live], want[live],
                                   rtol=1e-5, atol=1e-5)

    def test_null_block_rows_are_ignored(self):
        # poison the null block: masked idle context must not leak into out
        from paddle_tpu.ops.pallas.paged_attention import paged_attention_xla

        q, kp, vp, bt, cl = _make_case(seed=3)
        out_clean = np.asarray(paged_attention_xla(q, kp, vp, bt, cl))
        kp2, vp2 = kp.copy(), vp.copy()
        kp2[0] = 1e6
        vp2[0] = -1e6
        # point the dead tail of slot 1 (ctx=1) at the poisoned null block
        bt2 = bt.copy()
        bt2[1, 1:] = 0
        out_poison = np.asarray(paged_attention_xla(q, kp2, vp2, bt2, cl))
        np.testing.assert_allclose(out_poison[1], out_clean[1],
                                   rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- scheduler
def _req(plen, max_new=4, **kw):
    return Request(list(range(1, plen + 1)), max_new_tokens=max_new, **kw)


class TestScheduler:
    def test_admission_respects_kv_reservation(self):
        # 4 allocatable blocks of 4 tokens; each request reserves
        # ceil((6+6)/4)=3 worst-case blocks -> only one fits at a time
        a = BlockAllocator(num_blocks=5, block_size=4)
        s = Scheduler(a, max_slots=4, max_model_len=16)
        r0, r1 = _req(6, 6), _req(6, 6)
        s.submit(r0)
        s.submit(r1)
        assert [r.request_id for r in s.admit()] == [r0.request_id]
        assert r0.state == "prefill" and r1.state == "queued"
        assert s.admit() == []            # reservation blocks r1
        s.finish(r0, "stop")              # eviction frees blocks + slot...
        assert a.used_blocks == 0 and r0.wait(0)
        assert [r.request_id for r in s.admit()] == [r1.request_id]

    def test_admission_respects_slots(self):
        a = BlockAllocator(num_blocks=64, block_size=4)
        s = Scheduler(a, max_slots=2, max_model_len=32)
        reqs = [_req(4) for _ in range(3)]
        for r in reqs:
            s.submit(r)
        admitted = s.admit()
        assert len(admitted) == 2 and len(s.waiting) == 1
        slots = {r.slot for r in admitted}
        assert len(slots) == 2            # distinct slots
        s.finish(admitted[0], "length")
        again = s.admit()
        assert len(again) == 1 and again[0].slot in slots  # slot reused

    def test_finish_from_prefill_state(self):
        a = BlockAllocator(num_blocks=16, block_size=4)
        s = Scheduler(a, max_slots=2, max_model_len=32)
        r = _req(4)
        s.submit(r)
        s.admit()
        s.finish(r, "stop")               # evict mid-prefill
        assert r.state == "finished" and not s.has_work()
        assert s.counts()["reserved_blocks"] == 0
        assert a.used_blocks == 0

    def test_submit_validation(self):
        a = BlockAllocator(num_blocks=16, block_size=4)
        s = Scheduler(a, max_slots=2, max_model_len=8)
        with pytest.raises(ValueError):
            s.submit(_req(8))             # 8 + 1 > max_model_len
        with pytest.raises(ValueError):
            s.submit(Request([]))

    def test_finish_queued_request_is_dequeued(self):
        # cancel/timeout of a never-admitted request: finish() must drop it
        # from the waiting deque, or admit() later re-admits a finished
        # request and overwrites its state
        a = BlockAllocator(num_blocks=16, block_size=4)
        s = Scheduler(a, max_slots=1, max_model_len=32)
        r0, r1 = _req(4), _req(4)
        s.submit(r0)
        s.submit(r1)
        s.admit()                         # r0 takes the only slot; r1 waits
        s.finish(r1, "cancelled")
        assert r1.state == "finished" and r1.wait(0)
        assert not s.waiting
        assert s.admit() == []            # r1 must NOT come back
        assert r1.state == "finished"
        s.finish(r0, "stop")
        assert not s.has_work() and a.used_blocks == 0


# ------------------------------------------------------------- engine
def _tiny_gpt():
    cfg = GPTConfig.tiny()
    m = GPTForCausalLM(cfg)
    m.eval()
    return cfg, m


def _greedy(m, prompt, n):
    ids = np.asarray([prompt], np.int32)
    return [int(t) for t in m.generate(
        paddle.to_tensor(ids), max_new_tokens=n).numpy()[0, len(prompt):]]


def _generate_cut(m, prompt, max_new, eos, max_model_len):
    """(tokens, finish_reason) as model.generate has them, cut where a
    served request ends: at its eos, its budget or the context cap."""
    toks = _greedy(m, prompt, min(max_new, max_model_len + 1 - len(prompt)))
    if eos is not None and eos in toks:
        return toks[:toks.index(eos) + 1], "stop"
    return toks, "length"


def _late_finish_cases():
    """name -> (cfg, m, rng) -> (engine arguments, [(prompt, max_new, eos)]).
    Three slots and blocks of 8 in every case; the request the case is
    named for runs beside two that outlive it."""
    def prompt(cfg, rng, n):
        return [int(t) for t in rng.integers(0, cfg.vocab_size, n)]

    def neighbours(cfg, rng):
        return [(prompt(cfg, rng, 7), 20, None), (prompt(cfg, rng, 12), 24,
                                                  cfg.vocab_size)]

    def eos_at(m, p, i):
        """A prompt's i-th greedy token, if no earlier one equals it."""
        toks = _greedy(m, p, i + 1)
        return toks[i] if toks[i] not in toks[:i] else None

    def with_eos(i):
        def case(cfg, m, rng):
            for _ in range(32):
                p = prompt(cfg, rng, 10)
                eos = eos_at(m, p, i)
                if eos is not None:
                    return {}, [(p, 9, eos)] + neighbours(cfg, rng)
            pytest.fail("no prompt whose greedy tokens differ")
        return case

    def eos_never(cfg, m, rng):
        return {}, [(prompt(cfg, rng, 10), 9, cfg.vocab_size)] \
            + neighbours(cfg, rng)

    def max_new_tokens(cfg, m, rng):
        # budgets of 1 and 2, and one whose reservation ends on a block's
        # edge (5 + 3 = 8): the overshoot's position is the block's last,
        # the step after it falls past the reservation, on the null block
        return {"num_blocks": 12}, [
            (prompt(cfg, rng, 5), 3, None), (prompt(cfg, rng, 6), 1, None),
            (prompt(cfg, rng, 9), 2, cfg.vocab_size)] + neighbours(cfg, rng)

    def max_model_len(cfg, m, rng):
        # the context cap on a block's edge: the overshoot's position, 32,
        # is past the table's last column
        return {"max_model_len": 32}, [
            (prompt(cfg, rng, 20), 64, None), (prompt(cfg, rng, 31), 8, None),
            (prompt(cfg, rng, 27), 64, cfg.vocab_size),
            (prompt(cfg, rng, 7), 20, None)]

    def queue_and_prefix_hit(cfg, m, rng):
        doc = prompt(cfg, rng, 16)
        first = with_eos(0)(cfg, m, rng)[1][0]
        mid = with_eos(3)(cfg, m, rng)[1][0]
        return {"num_blocks": 14}, [
            (doc + prompt(cfg, rng, 3), 6, None), first, mid,
            (prompt(cfg, rng, 5), 1, None),
            (doc + prompt(cfg, rng, 5), 7, cfg.vocab_size),
            (prompt(cfg, rng, 13), 11, None),
            (doc + prompt(cfg, rng, 2), 4, None)]

    return {"eos_first_token": with_eos(0), "eos_mid_decode": with_eos(3),
            "eos_never": eos_never, "max_new_tokens": max_new_tokens,
            "max_model_len": max_model_len,
            "queue_and_prefix_hit": queue_and_prefix_hit}


_LATE_FINISH_CASES = _late_finish_cases()


class TestServingEngine:
    @pytest.mark.slow
    def test_gpt_greedy_parity_with_static_generate(self):
        cfg, m = _tiny_gpt()
        rng = np.random.default_rng(0)
        prompts = [list(rng.integers(0, cfg.vocab_size, n))
                   for n in (5, 19, 33, 7)]
        n_new = 6
        eng = ServingEngine(m, max_slots=3, block_size=16, prefill_chunk=16)
        got = eng.generate(prompts, max_new_tokens=n_new)
        for p, full in zip(prompts, got):
            ids = np.asarray([p], np.int32)
            want = m.generate(paddle.to_tensor(ids),
                              max_new_tokens=n_new).numpy()[0]
            assert full == [int(t) for t in want]
        # clean drain: no leaked blocks or reservations
        st = eng.stats()
        assert st["kv"]["used_blocks"] == 0
        assert st["reserved_blocks"] == 0 and st["running"] == 0

    @pytest.mark.slow
    def test_llama_gqa_greedy_parity(self):
        cfg = LlamaConfig.tiny()
        m = LlamaForCausalLM(cfg)
        m.eval()
        rng = np.random.default_rng(1)
        prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (9, 4)]
        eng = ServingEngine(m, max_slots=2, block_size=8, prefill_chunk=8)
        got = eng.generate(prompts, max_new_tokens=5)
        for p, full in zip(prompts, got):
            ids = np.asarray([p], np.int32)
            want = m.generate(paddle.to_tensor(ids),
                              max_new_tokens=5).numpy()[0]
            assert full == [int(t) for t in want]

    def test_prefill_chunk_must_align_to_block_size(self):
        _, m = _tiny_gpt()
        with pytest.raises(ValueError):
            ServingEngine(m, block_size=16, prefill_chunk=8)

    def test_fused_decode_matches_unfused(self):
        cfg, m = _tiny_gpt()
        rng = np.random.default_rng(5)
        prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (5, 9)]
        eng1 = ServingEngine(m, max_slots=2, block_size=16, prefill_chunk=16)
        eng4 = ServingEngine(m, max_slots=2, block_size=16, prefill_chunk=16)
        eng4.fuse_steps = 4               # FLAGS_serving_fuse_steps analog
        # 6 tokens with k=4 forces a mid-chunk budget overshoot: the extra
        # fused steps must be dropped at flush, not returned
        out1 = eng1.generate(prompts, max_new_tokens=6)
        out4 = eng4.generate(prompts, max_new_tokens=6)
        assert out1 == out4
        assert all(len(o) == len(p) + 6 for o, p in zip(out4, prompts))

    @pytest.mark.parametrize("fuse_steps", [1, 4])
    def test_decode_hands_the_kernel_live_contexts_and_counts_them(
            self, fuse_steps):
        """The paged kernel fetches by context, so an idle slot must stay at
        context 1 on the device (it used to drift by one a tick), the host's
        lengths are the device's, and serving_paged_keys_total is the
        kernel's own page arithmetic over them."""
        from paddle_tpu.observability.registry import default_registry
        from paddle_tpu.ops.pallas.paged_attention import live_pages

        cfg, m = _tiny_gpt()
        bs = 16
        eng = ServingEngine(m, max_slots=3, block_size=bs, prefill_chunk=16)
        eng.fuse_steps = fuse_steps
        keys = default_registry().get("serving_paged_keys_total")
        before = {k: keys.value(kind=k) for k in ("fetched", "live")}
        rng = np.random.default_rng(3)
        eng.submit(list(rng.integers(0, cfg.vocab_size, 21)),
                   max_new_tokens=30)
        handed, decode_step = [], eng._decode_step

        def recording():
            handed.append((eng._lens.copy(), list(eng.sched.running)))
            return decode_step()

        eng._decode_step = recording
        while eng.sched.has_work():
            eng.step()
            if eng._dev is not None:
                np.testing.assert_array_equal(np.asarray(eng._dev[2]),
                                              eng._lens)
        fetched = live = 0
        for lens, running in handed:
            assert len(running) == 1
            for step in range(fuse_steps):
                ctx = lens.astype(np.int64) + 1 + step
                fetched += int(live_pages(ctx, bs)[1].sum()) * bs
                live += int(ctx[running].sum())
        assert live > 0 and (eng._lens == 0).all()
        layers = cfg.num_layers
        assert keys.value(kind="live") - before["live"] == live * layers
        assert (keys.value(kind="fetched") - before["fetched"]
                == fetched * layers)
        # one running slot of three: a last page rounded up, and a page for
        # each idle slot, not the table's width
        assert live < fetched <= live + 30 * 3 * bs

    def test_eos_stops_early_and_reports_reason(self):
        cfg, m = _tiny_gpt()
        rng = np.random.default_rng(2)
        prompt = list(rng.integers(0, cfg.vocab_size, 6))
        # learn what greedy emits first, then declare it the eos token
        ids = np.asarray([prompt], np.int32)
        first = int(m.generate(paddle.to_tensor(ids),
                               max_new_tokens=1).numpy()[0, -1])
        eng = ServingEngine(m, max_slots=2, block_size=16, prefill_chunk=16)
        req = eng.submit(prompt, max_new_tokens=8, eos_token_id=first)
        eng.run_until_idle()
        assert req.finish_reason == "stop"
        assert req.output_tokens == [first]
        t = req.telemetry()
        assert t["queue_s"] is not None and t["ttft_s"] is not None

    @pytest.mark.slow
    def test_finish_clears_device_slot_no_cross_request_corruption(self):
        """Regression (r11 review, high): after a finish, the slot's DEVICE
        block table / seq_len must be cleared, not just the host mirrors —
        the compiled decode step keeps running over EVERY slot, and the
        stale slot's K/V writes at advancing positions land in its freed
        blocks, which the allocator hands to a newly admitted request in a
        DIFFERENT slot.

        Construction: A (3 blocks, finishes by eos MID-reservation, so its
        frozen write pointer sits behind its reservation's end) and B (1
        block, finishes by length) end in the SAME fetch, A first — so C
        is admitted into B's slot while A's slot stays stale, and C's
        LIFO-popped table is [B's block, A's blocks...]. C's 24-token
        prompt therefore extends into A's old blocks BEHIND A's frozen
        pointer (len 11 -> C position 19): as C decodes, the stale slot
        sprays garbage over C's already-scattered, always-attended prompt
        tail and then trails two positions behind C's own write head —
        unless _finish cleared the device-side slot. D is a long-lived
        request: its fused admission makes the device state a
        genuine jit output (on CPU, jnp.asarray(host_mirror) can ALIAS the
        numpy buffer, so _finish's host-mirror zeroing would mask the
        stale-slot bug), and it keeps the decode loop ticking while C
        prefills."""
        cfg, m = _tiny_gpt()
        rng = np.random.default_rng(4)
        # A must finish by eos in DECODE (not at prefill): pick a prompt
        # whose first two greedy continuations differ, eos = the second
        for _ in range(32):
            prompt_a = [int(t) for t in rng.integers(0, cfg.vocab_size, 10)]
            ids = np.asarray([prompt_a], np.int32)
            pair = m.generate(paddle.to_tensor(ids),
                              max_new_tokens=2).numpy()[0, -2:]
            if pair[0] != pair[1]:
                break
        else:
            pytest.fail("no prompt with two distinct greedy tokens found")
        eos_a = int(pair[1])
        prompt_b = [int(t) for t in rng.integers(0, cfg.vocab_size, 5)]
        prompt_d = [int(t) for t in rng.integers(0, cfg.vocab_size, 4)]
        prompt_c = [int(t) for t in rng.integers(0, cfg.vocab_size, 24)]
        # B gets an eos it never emits. Both finishes are found by one
        # fetch, in slot order: A (slot 0) then B (slot 1), so C
        # deterministically inherits B's slot
        want_b = m.generate(paddle.to_tensor(np.asarray([prompt_b],
                                                        np.int32)),
                            max_new_tokens=2).numpy()[0]
        eos_b = next(t for t in range(cfg.vocab_size)
                     if t not in [int(x) for x in want_b[-2:]])
        # 7 allocatable blocks of 8: A reserves 3 (10+8), B 1 (5+2), D 3
        # (4+20) -> C (24+8 tokens, 4 blocks) must wait for A's AND B's
        # frees, and pops exactly [B's block, A's three blocks]
        eng = ServingEngine(m, max_slots=3, block_size=8, num_blocks=8,
                            prefill_chunk=8)
        ra = eng.submit(prompt_a, max_new_tokens=8, eos_token_id=eos_a)
        rb = eng.submit(prompt_b, max_new_tokens=2, eos_token_id=eos_b)
        rd = eng.submit(prompt_d, max_new_tokens=20)
        rc = eng.submit(prompt_c, max_new_tokens=8)
        eng.run_until_idle()
        assert ra.finish_reason == "stop"
        assert ra.output_tokens == [int(pair[0]), eos_a]
        assert rb.finish_reason == "length"
        for prompt, req, n_new in ((prompt_b, rb, 2), (prompt_d, rd, 20),
                                   (prompt_c, rc, 8)):
            ids = np.asarray([prompt], np.int32)
            want = m.generate(paddle.to_tensor(ids),
                              max_new_tokens=n_new).numpy()[0]
            assert prompt + req.output_tokens == [int(t) for t in want]
        st = eng.stats()
        assert st["kv"]["used_blocks"] == 0 and st["running"] == 0

    @pytest.mark.parametrize("case", sorted(_LATE_FINISH_CASES))
    def test_tokens_and_reasons_equal_generate_however_late_the_finish(
            self, case):
        """A finish is found a tick after the step that caused it, so the
        finishing slot decodes one step too many beside its neighbours:
        every request's tokens and reason are model.generate's all the
        same, no step writes a block that no live request holds (the
        overshoot lands in the finishing request's own pages or the null
        block, at the end of a reservation and at max_model_len too), and
        every block and slot is free at the end."""
        cfg, m = _tiny_gpt()
        rng = np.random.default_rng(11)
        engine_kw, specs = _LATE_FINISH_CASES[case](cfg, m, rng)
        eng = ServingEngine(m, max_slots=3, block_size=8, prefill_chunk=8,
                            **engine_kw)
        want = [_generate_cut(m, p, n, eos, eng.max_model_len)
                for p, n, eos in specs]
        reqs = [eng.submit(p, max_new_tokens=n, eos_token_id=eos)
                for p, n, eos in specs]

        def held():
            return {b for rid in eng.allocator.sequences()
                    for b in eng.allocator.table(rid)} | {0}

        waited = False
        for _ in range(2000):
            if not eng.sched.has_work():
                break
            waited |= bool(eng.sched.waiting) and not eng.sched._free_slots
            before, pages = held(), np.asarray(eng.pool.layers[0][0])
            eng.step()
            untouched = sorted(set(range(eng.num_blocks)) - before - held())
            np.testing.assert_array_equal(
                np.asarray(eng.pool.layers[0][0])[untouched],
                pages[untouched])
            for r in reqs:      # finished only with every token in
                if r.state == "finished":
                    assert r._pending_n == 0 and r.output_tokens
        assert not eng.sched.has_work() and not eng._pending
        for r, (toks, reason) in zip(reqs, want):
            assert (r.output_tokens, r.finish_reason) == (toks, reason)
        if case == "queue_and_prefix_hit":
            assert waited and any(r.prefix_matched for r in reqs)
        st = eng.stats()
        assert st["kv"]["used_blocks"] == 0 and st["reserved_blocks"] == 0
        assert st["running"] == 0 and st["free_slots"] == 3
        assert (eng._lens == 0).all() and (eng._tables == 0).all()
        np.testing.assert_array_equal(np.asarray(eng._dev[1]), 0)

    def test_one_dispatch_in_flight_after_a_steady_tick_none_after_an_idle(
            self):
        """Tick n's tokens come to the host under tick n + 1's programs:
        after a steady decode tick exactly one dispatch is in flight (the
        tick's own), the host has every token but that one's, and a tick
        that dispatches nothing (the budget is already in flight) fetches
        at once and leaves none."""
        from paddle_tpu.observability.registry import default_registry

        cfg, m = _tiny_gpt()
        fetches = default_registry().get("serving_fetches_total")
        before = {k: fetches.value(kind=k)
                  for k in ("under_dispatch", "exposed")}
        eng = ServingEngine(m, max_slots=2, block_size=16, prefill_chunk=16)
        req = eng.submit([3, 1, 4, 1, 5], max_new_tokens=6,
                         eos_token_id=cfg.vocab_size)   # never emitted
        eng.step()                      # prefill, admit, the first step
        assert [len(e.items) for e in eng._pending] == [1, 1]
        assert req.output_tokens == [] and req.first_token_time is None
        for n in range(2, 6):           # steady: one step a tick
            eng.step()
            assert len(eng._pending) == 1
            assert eng._pending[0].tick == eng.steps - 1
            assert len(req.output_tokens) == n and req.state == "running"
            assert req.first_token_time is not None
        eng.step()                      # all 6 dispatched: nothing to decode
        assert eng._pending == [] and req.state == "finished"
        assert len(req.output_tokens) == 6 and req.finish_reason == "length"
        assert not eng.sched.has_work()
        got = {k: fetches.value(kind=k) - before[k] for k in before}
        assert got == {"under_dispatch": 4, "exposed": 1}

    def test_a_prompt_alone_keeps_two_prefill_chunks_in_flight(self):
        """While nothing decodes no fetch paces the host, and every chunk
        in flight holds its logits on the device: before chunk k + 1 is
        dispatched the engine waits for chunk k - 1, never for chunk k."""
        cfg, m = _tiny_gpt()
        eng = ServingEngine(m, max_slots=2, block_size=8, prefill_chunk=8)
        events, real = [], eng._prefill_jit

        class Unfinished:
            """A chunk's logits that have not run until waited for."""

            def __init__(self, k):
                self.k, self.ran = k, False

            def is_ready(self):
                return self.ran

            def block_until_ready(self):
                events.append(("waited", self.k))
                self.ran = True

        def recording(chunk, padded):
            fn = real(chunk, padded)

            def call(pv, bv, ids, caches, pos):
                k = int(pos) // chunk
                events.append(("dispatched", k))
                logits, caches = fn(pv, bv, ids, caches, pos)
                # the last chunk's logits are the admission's to read
                return (logits if k == 5 else Unfinished(k)), caches
            return call

        eng._prefill_jit = recording
        prompt = [int(t) for t in
                  np.random.default_rng(13).integers(0, cfg.vocab_size, 44)]
        req = eng.submit(prompt, max_new_tokens=3)
        eng.run_until_idle()
        assert req.output_tokens == _greedy(m, prompt, 3)
        assert events == [
            ("dispatched", 0), ("dispatched", 1),
            ("waited", 0), ("dispatched", 2), ("waited", 1),
            ("dispatched", 3), ("waited", 2), ("dispatched", 4),
            ("waited", 3), ("dispatched", 5)]

    def test_greedy_eos_admission_on_a_warm_engine_is_one_program(self):
        """An eos id used to send a greedy admission down the host path (a
        wait for the chunk's logits, argmax on the host, four eager
        scatters under serving.host_upload). It takes the fused admit
        program like any greedy request: no slot_state upload, no wait
        inside the admission, and nothing built on a warmed engine."""
        from paddle_tpu.core import flags as _flags
        from paddle_tpu.observability import spans

        cfg, m = _tiny_gpt()
        rng = np.random.default_rng(12)
        eng = ServingEngine(m, max_slots=2, block_size=16, prefill_chunk=16)

        def prompt():
            return [int(t) for t in rng.integers(0, cfg.vocab_size, 9)]

        eng.submit(prompt(), max_new_tokens=4, eos_token_id=cfg.vocab_size)
        eng.run_until_idle()            # warm: every program of the shape
        built = len(eng._jit)
        old = _flags.get_flag("metrics")
        _flags.set_flags({"metrics": "on"})
        try:
            mark = spans.mark()
            req = eng.submit(prompt(), max_new_tokens=4,
                             eos_token_id=cfg.vocab_size)
            eng.step()
            assert req.state == "running" and req.output_tokens == []
            eng.run_until_idle()
            seen = spans.since(mark)
        finally:
            _flags.set_flags({"metrics": old})
        assert len(req.output_tokens) == 4 and len(eng._jit) == built
        names = {s["name"] for s in seen}
        assert "serving.prefill_chunk" in names and "serving.fetch" in names
        assert "serving.program_build" not in names
        assert "serving.host_upload" not in names
        assert {s["args"]["what"] for s in seen
                if s["name"] == "serving.fetch"} == {"tokens"}

    def test_cancel_running_request_frees_capacity(self):
        cfg, m = _tiny_gpt()
        rng = np.random.default_rng(6)
        p0 = [int(t) for t in rng.integers(0, cfg.vocab_size, 5)]
        p1 = [int(t) for t in rng.integers(0, cfg.vocab_size, 7)]
        eng = ServingEngine(m, max_slots=2, block_size=16, prefill_chunk=16)
        victim = eng.submit(p0, max_new_tokens=64)
        for _ in range(4):                # running, a step's tokens in flight
            eng.step()
        assert victim.state == "running" and eng._pending
        got = list(victim.output_tokens)  # every tick's but the last one's
        want0 = m.generate(paddle.to_tensor(np.asarray([p0], np.int32)),
                           max_new_tokens=8).numpy()[0, len(p0):]
        assert 0 < len(got) < 8 and got == [int(t) for t in want0[:len(got)]]
        assert eng.cancel(victim, reason="timeout")
        assert not eng._pending           # dropped with the cancel, unfetched
        assert victim.state == "finished"
        assert victim.finish_reason == "timeout" and victim.wait(0)
        assert not eng.cancel(victim)     # already finished: no-op
        st = eng.stats()
        assert st["kv"]["used_blocks"] == 0 and st["running"] == 0
        # the recycled slot + blocks still serve correctly (and the
        # cancelled request's tokens in flight never reach it)
        out = eng.generate([p1], max_new_tokens=5)[0]
        want = m.generate(paddle.to_tensor(np.asarray([p1], np.int32)),
                          max_new_tokens=5).numpy()[0]
        assert out == [int(t) for t in want]
        assert victim.output_tokens == got  # no fetch resurrects it

    def test_same_tick_sampled_admissions_draw_distinct_streams(self):
        # r11 review: two temperature>0 requests admitted in one tick must
        # not sample from identical RNG streams (_step_seed alone doesn't
        # advance between same-tick admissions)
        _, m = _tiny_gpt()
        eng = ServingEngine(m, max_slots=2, block_size=16, prefill_chunk=16)
        logits = np.zeros(64, np.float32)   # flat: the draw IS the stream
        reqs = [Request([1], temperature=0.7) for _ in range(8)]
        draws = [eng._sample_host(logits, r) for r in reqs]
        assert len(set(draws)) > 1
        # same engine history -> same stream (threefry fold_in, like the
        # compiled decode path; not wall-clock or os entropy)
        eng2 = ServingEngine(m, max_slots=2, block_size=16, prefill_chunk=16)
        assert [eng2._sample_host(logits, r) for r in reqs] == draws


# ------------------------------------------------------------- HTTP smoke
class TestServingHTTP:
    def test_generate_roundtrip_and_stats(self):
        cfg, m = _tiny_gpt()
        eng = ServingEngine(m, max_slots=2, block_size=16, prefill_chunk=16)
        srv = ServingServer(eng, port=0)
        try:
            prompt = list(np.random.default_rng(3).integers(
                0, cfg.vocab_size, 5))
            body = json.dumps({"prompt": [int(t) for t in prompt],
                               "max_new_tokens": 4}).encode()
            req = urllib.request.Request(
                srv.url() + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                assert resp.status == 200
                out = json.loads(resp.read())
            assert len(out["output_tokens"]) == 4
            assert out["finish_reason"] == "length"
            assert out["telemetry"]["ttft_s"] is not None
            # static greedy agrees with what came over the wire
            ids = np.asarray([prompt], np.int32)
            want = m.generate(paddle.to_tensor(ids),
                              max_new_tokens=4).numpy()[0, -4:]
            assert out["output_tokens"] == [int(t) for t in want]

            with urllib.request.urlopen(srv.url() + "/stats",
                                        timeout=30) as resp:
                st = json.loads(resp.read())
            assert st["kv"]["used_blocks"] == 0
            with urllib.request.urlopen(srv.url() + "/healthz",
                                        timeout=30) as resp:
                assert json.loads(resp.read())["ok"] is True

            bad = urllib.request.Request(
                srv.url() + "/generate",
                data=json.dumps({"prompt": "not-a-list"}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(bad, timeout=30)
            assert ei.value.code == 400
        finally:
            srv.stop()

    def test_timeout_cancels_request_and_frees_capacity(self):
        # r11 review: a 504 must evict the abandoned request — its slot and
        # worst-case KV reservation go back to the pool instead of decoding
        # to completion for a client that already gave up
        from paddle_tpu.core import flags as _flags

        cfg, m = _tiny_gpt()
        eng = ServingEngine(m, max_slots=2, block_size=16, prefill_chunk=16)
        srv = ServingServer(eng, port=0)
        old = _flags.get_flag("serving_request_timeout_s")
        _flags.set_flags({"serving_request_timeout_s": 0.05})
        try:
            prompt = [int(t) for t in np.random.default_rng(8).integers(
                0, cfg.vocab_size, 5)]
            body = json.dumps({"prompt": prompt,
                               "max_new_tokens": 5000}).encode()
            req = urllib.request.Request(
                srv.url() + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=120)
            assert ei.value.code == 504
            assert json.loads(ei.value.read())["cancelled"] is True
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                st = eng.stats()
                if (st["kv"]["used_blocks"] == 0 and st["running"] == 0
                        and st["waiting"] == 0 and st["prefilling"] == 0):
                    break
                time.sleep(0.01)
            else:
                pytest.fail(f"capacity not released after timeout: {st}")
        finally:
            _flags.set_flags({"serving_request_timeout_s": old})
            srv.stop()


# ------------------------------------------------------------ queue limits
class TestQueueFull:
    def test_engine_submit_sheds_past_max_queue(self):
        from paddle_tpu.core import flags as _flags
        from paddle_tpu.observability import registry
        from paddle_tpu.serving import QueueFullError

        cfg, m = _tiny_gpt()
        eng = ServingEngine(m, max_slots=2, block_size=16, prefill_chunk=16)
        old = _flags.get_flag("serving_max_queue")
        _flags.set_flags({"serving_max_queue": 2})
        try:
            shed = registry.REGISTRY.get("serving_shed_requests_total")
            before = shed.value(tier="default", reason="queue_full")
            eng.submit([1, 2, 3])          # no engine loop: both wait
            eng.submit([1, 2, 3])
            with pytest.raises(QueueFullError) as ei:
                eng.submit([1, 2, 3])
            assert ei.value.depth == 2 and ei.value.limit == 2
            assert ei.value.retry_after_s > 0
            assert "FLAGS_serving_max_queue" in str(ei.value)
            assert shed.value(tier="default",
                              reason="queue_full") == before + 1
            assert len(eng.sched.waiting) == 2  # rejected one never queued
        finally:
            _flags.set_flags({"serving_max_queue": old})

    def test_http_503_with_retry_after(self):
        from paddle_tpu.core import flags as _flags

        cfg, m = _tiny_gpt()
        eng = ServingEngine(m, max_slots=1, block_size=16, prefill_chunk=16)
        srv = ServingServer(eng, port=0)
        old = _flags.get_flag("serving_max_queue")
        _flags.set_flags({"serving_max_queue": 1})
        try:
            # occupy the only slot so queued requests cannot drain
            hog = eng.submit([1, 2, 3], max_new_tokens=5000)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                st = eng.stats()
                if st["waiting"] == 0 and st["running"] + st["prefilling"]:
                    break
                time.sleep(0.01)
            # the hog ends at the model's 256 positions, well within a
            # second of ticks: hold the loop still while the queue is probed
            eng.step = lambda: time.sleep(0.01)
            filler = eng.submit([4, 5, 6], max_new_tokens=8)  # fills queue
            body = json.dumps({"prompt": [7, 8, 9],
                               "max_new_tokens": 4}).encode()
            req = urllib.request.Request(
                srv.url() + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            assert ei.value.code == 503
            assert int(ei.value.headers["Retry-After"]) >= 1
            payload = json.loads(ei.value.read())
            assert payload["queue_depth"] == 1
            assert payload["queue_limit"] == 1
            assert payload["retry_after_s"] > 0
            del eng.step
            eng.cancel(hog, reason="cancelled")
            eng.cancel(filler, reason="cancelled")
        finally:
            _flags.set_flags({"serving_max_queue": old})
            srv.stop()
