"""ServingEngine: continuous-batching decode over the paged KV pool.

One engine tick (`step()`) = admit -> prefill chunk(s) -> one decode step:

  * decode is ONE compiled program over a fixed set of slots: every running
    sequence contributes its single last token; the paged ragged attention
    op reads each slot's own block table / length (idle slots point at the
    null block and are ignored). Page buffers are DONATED, so the pool is
    updated in place in HBM; sampling (greedy / per-slot temperature)
    happens inside the program.
  * prefill runs the model's existing contiguous cached path in a private
    workspace, one bounded chunk per tick per prompt (so long prompts
    interleave with decode instead of stalling it; a burst of short
    prompts may finish up to one prefill per IDLE slot in a tick), then
    scatters the finished prefix into the sequence's pages
    (paged.write_prefix) and joins the decode batch.
  * the int8 weight-only swap (quantization/weight_only.py) composes
    unchanged: quantized tables are buffers, and every compiled program
    here threads buffer values exactly like models/generation.py.

The decode loop is device-resident: block tables are the full worst-case
admission reservation uploaded once per request, the compiled step feeds
its own outputs (next tokens, advanced lengths, RNG seed) straight back
in, and a greedy admission is one fused program (first-token argmax + slot
scatter). A tick never waits for work it has just dispatched: tick n + 1's
programs (admissions, the prefill chunk, the decode step) go to the device
first, and only then are tick n's tokens fetched, so the fetch and all of
the host's bookkeeping run under the device's tick n + 1 (`_fetch`). An
eos is therefore found one tick late: the request decodes one token too
many, which is dropped (serving_overshoot_tokens_total) and whose K/V
lands in pages the request still holds or in the null block. A request is
`finished` only once every token of it is in `output_tokens`.

The cache is the model's to state (`model.cache_spec()`: per layer, full,
window or latent, K/V heads, head size): layers of one kind share a block
table, a range of columns of a slot's table row, and each layer has a pool of
its group's size: a pair of arrays (K, V), or the one array of a latent
layer, which shares the full group's table. The full group's table is the
worst-case reservation; a window group's is a fixed ring of blocks a slot
(blocks.WindowRings), so a window layer holds the window however long the
context. What the rings do not serve yet (prefix-cache hits, speculation,
fused steps, the KV wire) refuses by name for a model with window layers,
and what is not written over latent pages (speculation, fused steps, the KV
wire, batched prefill) for a model with latent layers; the prefix cache
serves latent pages as it serves K/V pages.

Compiled-program keys are shape-stable: one decode program per engine, one
prefill/admit program per chunk bucket, one scatter per (workspace, block
count) — no per-request recompiles at steady state.
"""
from __future__ import annotations

import random
import threading
import time
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import flags as _flags
from ..core.tensor import Tensor
from ..observability.registry import counter as _counter, gauge as _gauge
from ..models.generation import init_kv_cache
from .blocks import BlockAllocator, WindowRings
from .observability import (
    _PREFILL_TOKENS,
    FETCHES,
    OVERSHOOT_TOKENS,
    PROGRAMS_BUILT,
    SUBMIT_LOCK_WAIT_H,
    EngineStats,
    ServingObservability,
    new_engine_id,
)
from ..ops.pallas.paged_attention import (
    from_pages,
    live_pages,
    to_pages,
)
from .paged import PagedKVPool, PagedLayerCache, write_prefix, write_ring
from .scheduler import Request, Scheduler
from .speculative import NgramDrafter, SpecState

# What ServingEngine's arguments mean when left out. prefill_chunk bounds how
# long a prompt can stall the running decode batch; prefill_bucket is the
# length bucket of the batched multi-prompt prefill program (0 = per-prompt
# chunked prefill only); prefix_cache content-addresses full KV blocks so
# prompts sharing a prefix skip its prefill. The last two stay `None` in the
# signature because over window or latent layers "left out" means off and
# "asked for" refuses (_refuse_over).
DEFAULT_PREFIX_CACHE = True
DEFAULT_PREFILL_BUCKET = 16

_flags.define_flag("serving_fuse_steps", 1,
                   "Greedy decode steps fused into one compiled dispatch. "
                   "1 (default) disables fusion: on CPU the fused loop's "
                   "carried KV pool costs more than the dispatches it "
                   "saves; worth >1 where dispatch latency dominates. "
                   "Sampled batches never fuse.")
_flags.define_flag("serving_max_queue", 0,
                   "Admission control: maximum requests waiting in the "
                   "scheduler queue. A submit() past this depth raises "
                   "QueueFullError (HTTP 503 + Retry-After at the server) "
                   "instead of growing the queue without bound. 0 = "
                   "unbounded (default).")
_flags.define_flag("serving_retry_after_s", 1.0,
                   "Base Retry-After hint (seconds) returned with 503 "
                   "queue-full responses.")
_flags.define_flag("serving_retry_after_jitter", 0.5,
                   "Fractional forward jitter on queue-full Retry-After "
                   "hints: each shed client is told to come back after "
                   "uniform[base, base * (1 + jitter)] seconds, so a burst "
                   "shed together does not retry in lockstep against a "
                   "recovering fleet. 0 disables jitter.")


_MOE_PAIRS = _counter(
    "serving_moe_pairs_total",
    "(token, expert) pairs a decode step routed, by whether the expert's "
    "weights are held here (a chip's share under expert parallelism). "
    "Added up on the device, published when a request finishes and with "
    "stats().", labelnames=("held",), always=True)
_MOE_LOAD = _gauge(
    "serving_moe_expert_load_max_over_mean",
    "Busiest held expert's pairs over the mean held expert's, by layer, "
    "over the decode steps so far.", labelnames=("layer",), always=True)
# LayerCacheSpec.extra: a layer's own additive counters, by name
_LAYER_COUNTERS = {
    "mhc_applications": _counter(
        "serving_mhc_applications_total",
        "Token-applications of the residual mix (manifold-constrained "
        "hyper-connections: two a layer a token) the decode steps ran, by "
        "layer. Added up on the device, published as the pairs are.",
        labelnames=("layer",), always=True),
    "mhc_unbalanced": _counter(
        "serving_mhc_unbalanced_total",
        "Of those, the ones whose residual map had a column sum off 1 by "
        "more than 1e-3 after the last Sinkhorn round: 0 while the rounds "
        "converge under the served inputs.",
        labelnames=("layer",), always=True),
}
_WINDOW_KEYS = _counter(
    "serving_window_keys_total",
    "Keys of window layers a decode step's attention fetched (`read`: the "
    "pages the kernel visits, in tokens) beside the keys of the same "
    "contexts (`context`: what a full layer would fetch), a layer a slot.",
    labelnames=("kind",), always=True)


_LATENT_KEYS = _counter(
    "serving_latent_keys_total",
    "Keys of latent layers a decode step's attention fetched (`fetched`: "
    "the pages the kernel visits in every slot, idle ones too, in tokens) "
    "beside the keys of the running contexts (`live`), a layer a slot.",
    labelnames=("kind",), always=True)
_PAGED_KEYS = _counter(
    "serving_paged_keys_total",
    "Keys of full layers a decode step's attention fetched (`fetched`: the "
    "pages the kernel visits in every slot, idle ones too, in tokens) "
    "beside the keys of the running contexts (`live`), a layer a slot.",
    labelnames=("kind",), always=True)


def _holds_blocks(block_tables):
    """[slots] 1 where a slot's table row names a block, 0 for an idle
    slot (a null row)."""
    return jnp.any(block_tables != 0, axis=1).astype(jnp.int32)


class _InFlight(NamedTuple):
    """A dispatch whose sampled tokens no fetch has brought to the host."""
    tokens: jax.Array       # int32 on the device
    items: list             # [(index into tokens, slot, request)]
    tick: int               # engine.steps at the dispatch
    counters: tuple = ()    # the layers' counters a decode step returned


class QueueFullError(RuntimeError):
    """submit() rejected: the scheduler queue is at FLAGS_serving_max_queue.
    Carries the depth/limit and a Retry-After hint so the HTTP layer can
    answer 503 with an honest backoff instead of a generic error."""

    def __init__(self, depth: int, limit: int,
                 retry_after_s: Optional[float] = None):
        self.depth = int(depth)
        self.limit = int(limit)
        if retry_after_s is None:
            base = float(_flags.get_flag("serving_retry_after_s"))
            jitter = max(0.0, float(
                _flags.get_flag("serving_retry_after_jitter")))
            # forward-only jitter: never tell a client to come back
            # EARLIER than the base hint, just spread the retry wave out
            retry_after_s = base * (1.0 + random.uniform(0.0, jitter))
        self.retry_after_s = float(retry_after_s)
        super().__init__(
            f"serving queue full: {self.depth} requests waiting >= "
            f"FLAGS_serving_max_queue={self.limit}; retry after "
            f"{self.retry_after_s:g}s")


class EngineDrainingError(RuntimeError):
    """submit() rejected: the engine is draining for a rolling restart.
    New work belongs on another replica; in-flight requests finish."""

    def __init__(self):
        super().__init__("serving engine is draining: not admitting new "
                         "requests (in-flight work will complete)")

# SLO histograms (TTFT/queue/TPOT/e2e/tokrate, tier-labeled) and the
# per-request lifecycle trace live in serving/observability.py; the engine
# reports transitions through self.obs. The per-tick speculation counters
# moved into SpecState.record (speculative.py).


class ServingEngine:
    """Continuous-batching serving runtime for a GenerationMixin causal LM
    (GPTForCausalLM / LlamaForCausalLM), int8-quantized or not.

    Quantize BEFORE constructing the engine: compiled programs capture the
    model's parameter/buffer lists at first use.

    max_slots: sequences decoding concurrently. block_size: tokens per KV
    page. num_blocks: KV pool size in blocks; None or 0 = enough for every
    slot at max_model_len (no admission ever blocks on KV). prefill_chunk:
    prompt tokens prefilled per tick, a multiple of block_size.
    max_model_len: context cap (prompt + generated); None or 0 = the
    model's max positions. prefix_cache, prefill_bucket: None = the
    DEFAULT_* above (off over window layers). spec_k: self-speculative
    decoding, max draft tokens verified per tick from the request's own
    n-gram history (greedy requests only; 0 = off; mutually exclusive with
    FLAGS_serving_fuse_steps > 1). spec_ngram: longest n-gram the drafter
    matches (tries n down to 2). spec_pause: ticks a request stops drafting
    after 4 consecutive fruitless verify windows."""

    def __init__(self, model, *, max_slots: int = 4, block_size: int = 16,
                 num_blocks: Optional[int] = None, prefill_chunk: int = 32,
                 max_model_len: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 prefill_bucket: Optional[int] = None,
                 spec_k: int = 0, spec_ngram: int = 3, spec_pause: int = 32):
        self.model = model
        model.eval()
        # the one cache contract: per layer, what it keeps (models/
        # generation.LayerCacheSpec)
        spec = self._spec = model.cache_spec()
        max_pos = spec.max_positions
        self.block_size = int(block_size)
        self.max_slots = int(max_slots)
        self.prefill_chunk = int(prefill_chunk)
        self.max_model_len = min(int(max_model_len or max_pos), int(max_pos))
        if self.prefill_chunk % self.block_size:
            raise ValueError(
                f"ServingEngine(prefill_chunk={self.prefill_chunk}) must be "
                f"a multiple of block_size={self.block_size}")
        self.max_blocks_per_seq = -(-self.max_model_len // self.block_size)
        auto_blocks = self.max_slots * self.max_blocks_per_seq + 1
        self.num_blocks = int(num_blocks or auto_blocks)
        self._dtype = model._cache_dtype()
        # cache groups: the layers of one kind share a block table, which is
        # a range of columns of a slot's table row. The full group's is the
        # worst-case reservation [0, max_blocks_per_seq); a window group's
        # is the slot's ring of blocks (blocks.WindowRings), whatever the
        # context
        windows = sorted({l.window for l in spec.layers
                          if l.kind == "window"})
        self.window_rings = [WindowRings(self.max_slots, w, self.block_size)
                             for w in windows]
        cols, at = {0: (0, self.max_blocks_per_seq)}, self.max_blocks_per_seq
        for rings in self.window_rings:
            cols[rings.window] = (at, at + rings.ring_blocks)
            at += rings.ring_blocks
        self._table_cols = at
        self._layer_cols = [cols[l.window] for l in spec.layers]
        # (rings, its columns, how many layers read them), for the tick
        self._ring_cols = [
            (r, cols[r.window],
             sum(1 for l in spec.layers if l.window == r.window))
            for r in self.window_rings]
        # (counter, its kinds, window, layers) by cache group, for the tick
        kinds = [l.kind for l in spec.layers]
        self._key_counters = [
            (counter, ("fetched", "live"), None, kinds.count(kind))
            for counter, kind in ((_PAGED_KEYS, "full"),
                                  (_LATENT_KEYS, "latent"))
            if kind in kinds] + [
            (_WINDOW_KEYS, ("read", "context"), r.window, n)
            for r, _, n in self._ring_cols]
        # the kinds of layer that do not serve every feature yet: what they
        # lack refuses below, by name
        self._unserved = \
            [f"window layers (windows {windows})"] * bool(windows) \
            + ["latent layers"] * ("latent" in kinds)
        if windows:
            prefix_cache = self._refuse_over(
                "prefix_cache", prefix_cache, False,
                "a prefix hit would have to find the window layers' keys, "
                "which a ring keeps for the last window only")
        if self._unserved:
            prefill_bucket = self._refuse_over(
                "prefill_bucket", prefill_bucket, 0,
                "the batched prefill program writes whole prompts back "
                "through one block table, and runs the model at an offset "
                "a row, which latent attention does not take")
        self.prefix_cache = bool(DEFAULT_PREFIX_CACHE if prefix_cache is None
                                 else prefix_cache)
        self.prefill_bucket = int(DEFAULT_PREFILL_BUCKET
                                  if prefill_bucket is None
                                  else prefill_bucket)
        by_window = {r.window: r.num_blocks for r in self.window_rings}
        self.pool = PagedKVPool(
            [(by_window.get(l.window, self.num_blocks), l.kv_heads,
              l.head_dim, l.arrays) for l in spec.layers],
            self.block_size, self._dtype)
        self.allocator = BlockAllocator(self.num_blocks, self.block_size,
                                        prefix_cache=self.prefix_cache)
        self.sched = Scheduler(self.allocator, self.max_slots,
                               self.max_model_len, self.window_rings)
        # LayerCacheSpec.counters: a layer's int32 counters, kept on the
        # device beside the pool and threaded through the decode step
        self._counter_layers = [i for i, l in enumerate(spec.layers)
                                if l.counters]
        self._counters = tuple(
            jnp.zeros((spec.layers[i].counters,), jnp.int32)
            for i in self._counter_layers)
        self._counters_published = [np.zeros(spec.layers[i].counters,
                                             np.int64)
                                    for i in self._counter_layers]
        unknown = {name for l in spec.layers for name in l.extra} \
            - set(_LAYER_COUNTERS)
        if unknown:
            raise ValueError(f"LayerCacheSpec.extra names {sorted(unknown)}: "
                             f"the engine publishes {sorted(_LAYER_COUNTERS)}")
        # host mirror of per-slot decode state; the authoritative copies
        # live on device in _dev and are updated incrementally (per-slot
        # scatter on admission / block-table growth) — the decode loop
        # feeds its own outputs (next tokens, advanced seq_lens, RNG seed)
        # straight back in, and a tick's tokens are fetched under the NEXT
        # tick's programs (_fetch), so the device never waits for the host
        self._tables = np.zeros((self.max_slots, self._table_cols),
                                np.int32)
        self._lens = np.zeros(self.max_slots, np.int32)
        self._toks = np.zeros(self.max_slots, np.int32)
        self._temps = np.zeros(self.max_slots, np.float32)
        # greedy decode steps fused per dispatch (1 = no fusion); sampled
        # batches always run unfused so every token sees a fresh seed tick
        self.fuse_steps = int(_flags.get_flag("serving_fuse_steps"))
        # self-speculative decoding (speculative.py): drafts verified in
        # one multi-token dispatch; 0 = off
        self.spec_k = int(spec_k)
        self.spec_ngram = int(spec_ngram)
        self.spec_pause = int(spec_pause)
        if self._unserved:
            self._refuse_over(
                "spec_k", self.spec_k, 0,
                "a verify window of several tokens over a ring of blocks "
                "or over latent pages is not written, nor its rollback")
            self._refuse_over(
                "FLAGS_serving_fuse_steps", self.fuse_steps, 1,
                "the fused loop threads neither the window layers' state "
                "nor a layer of one array")
        if self.spec_k > 0 and self.fuse_steps > 1:
            raise ValueError(
                "FLAGS_serving_fuse_steps > 1 and speculative decoding "
                "(ServingEngine(spec_k > 0)) are mutually exclusive decode "
                "shapes: the fused loop carries a fixed one-token-per-"
                "step schedule that a variable-width verify window would "
                "miscompile. Disable one of them.")
        self._dev = None        # (toks, tables, lens, temps, seed) on device
        # dispatched, not fetched, in order; no entry names a finished
        # request (_finish)
        self._pending: List[_InFlight] = []
        # logits of the prefill chunks that may not have run yet, oldest
        # first (_pace_prefill)
        self._chunks: List[jax.Array] = []
        self._dispatched = False    # a program went to the device this tick
        self._counters_due = False  # a finish since the counters' last fetch
        self._jit = {}
        self._fns = None
        self._lock = threading.RLock()
        self._draining = False
        self._step_seed = 0
        self._sample_nonce = 0   # per-admission entropy for _sample_host
        self.steps = 0
        # prefill + speculation accounting now lives on the metrics
        # registry (serving_engine_events_total, labeled per engine
        # instance — see observability.EngineStats); the properties below
        # keep the int-attribute reads (the benchmark's deltas, tests) and
        # stats() keeps its JSON shape
        self._stats = EngineStats(new_engine_id())
        # lifecycle hooks: request traces, SLO histograms, per-tick
        # gauges, serving anomaly detectors + flight arm
        self.obs = ServingObservability(self)

    def _refuse_over(self, name, asked, allowed, why):
        """A feature that window rings or latent pages do not serve yet:
        left at its default it is off; asked for, it refuses by name."""
        if asked is None or asked == allowed:
            return allowed
        raise ValueError(
            f"ServingEngine: {name}={asked!r} cannot serve a model whose "
            f"cache spec has {' and '.join(self._unserved)}: {why}. "
            f"Leave it at {allowed!r}.")

    # -- registry-backed counter views (historical int attributes) --------
    @property
    def prefill_programs(self) -> int:
        """Prefill dispatches, chunked + batched."""
        return self._stats["prefill_programs"]

    @property
    def batched_prefills(self) -> int:
        """Batched multi-prompt dispatches."""
        return self._stats["batched_prefills"]

    @property
    def prefill_tokens(self) -> int:
        """Prompt tokens actually computed (cache hits skip theirs)."""
        return self._stats["prefill_tokens"]

    @property
    def cow_admissions(self) -> int:
        """Full-prompt cache hits (zero prefill)."""
        return self._stats["cow_admissions"]

    @property
    def dedup_admissions(self) -> int:
        """Register-time block dedups applied."""
        return self._stats["dedup_admissions"]

    @property
    def spec_ticks(self) -> int:
        """Ticks that ran a verify window."""
        return self._stats["spec_ticks"]

    @property
    def spec_proposed(self) -> int:
        """Draft tokens offered."""
        return self._stats["spec_proposed"]

    @property
    def spec_accepted(self) -> int:
        """Draft tokens accepted."""
        return self._stats["spec_accepted"]

    @property
    def spec_rollbacks(self) -> int:
        """Ticks that rolled back >= 1 token."""
        return self._stats["spec_rollbacks"]

    # ------------------------------------------------------- compiled fns
    def _functional(self):
        """(paged_fn, static_fn, param_vals, buffer_vals) — built lazily so
        an int8 swap applied before first use is captured."""
        if self._fns is None:
            model = self.model
            static_fn, params, buffers = model._functional_forward()

            cols, n_cols = self._layer_cols, self._table_cols
            counted = set(self._counter_layers)

            def paged_fn(pv, bv, ids, pages, bt, sl, counters=()):
                """counters: the arrays of the layers that keep some, in
                layer order, or () to leave them be. Returns (logits, new
                pages, new counters)."""
                saved_p = [(p._value, p.stop_gradient) for p in params]
                saved_b = [b._value for b in buffers]
                try:
                    for p, v in zip(params, pv):
                        p._value = v
                        p.stop_gradient = True
                    for b, v in zip(buffers, bv):
                        b._value = v
                    mine = iter(counters)
                    caches_t = []
                    for i, (arrays, (a, b)) in enumerate(zip(pages, cols)):
                        # the layer's group's columns of the table row
                        t = bt if (a, b) == (0, n_cols) else bt[:, a:b]
                        c = Tensor(next(mine)) \
                            if counters and i in counted else None
                        # (k, v), or a latent layer's one array and no V
                        k, v = (*map(Tensor, arrays), None)[:2]
                        caches_t.append(PagedLayerCache(
                            k, v, Tensor(t), Tensor(sl), c))
                    logits, ncs = model.forward(Tensor(ids), caches=caches_t,
                                                pos=None)
                    # a layer returns its arrays, then its counters if any
                    n = [len(arrays) for arrays in pages]
                    return (logits._value,
                            [tuple(x._value for x in nc[:k])
                             for nc, k in zip(ncs, n)],
                            tuple(nc[k]._value for nc, k in zip(ncs, n)
                                  if len(nc) > k))
                finally:
                    for p, (v, sg) in zip(params, saved_p):
                        p._value, p.stop_gradient = v, sg
                    for b, v in zip(buffers, saved_b):
                        b._value = v

            self._fns = (paged_fn, static_fn, params, buffers)
        paged_fn, static_fn, params, buffers = self._fns
        return (paged_fn, static_fn,
                [p._value for p in params], [b._value for b in buffers])

    def _program(self, kind: str, key, build):
        """The compiled program under `key`. One not seen before is built
        (`build()` returns the jitted function, whose name is the XLA
        module's in a device trace), counted, and handed back so that its
        first call, the one that traces and compiles, is inside a
        `serving.program_build` span."""
        fn = self._jit.get(key)
        if fn is not None:
            return fn
        PROGRAMS_BUILT.inc(kind=kind)
        fn = self._jit[key] = build()
        obs = self.obs

        def first_call(*args):
            with obs.span("serving.program_build", kind=kind, key=str(key)):
                return fn(*args)

        first_call.lower = fn.lower     # chip_smoke and the compile tests
        return first_call

    def _decode_jit(self, sampled: bool):
        """Two compiled variants: the all-greedy batch skips the threefry
        key derivation + Gumbel draw entirely (~0.2ms/step on CPU for a
        tiny model — a real fraction of the tick); temperature batches pay
        it. Both share the (tok, pages, bt, sl, temps, seed) signature so
        the engine can switch per tick as the batch mix changes."""
        key = ("decode", self.max_slots, self._table_cols, sampled)

        def build():
            paged_fn = self._functional()[0]

            # the one program whose function keeps the name `step`: the
            # benchmark's decode_step_ms reads XLA module jit_step
            def step(pv, bv, tok, pages, bt, sl, temps, seed, counters=()):
                logits, new_pages, counters = paged_fn(
                    pv, bv, tok[:, None], pages, bt, sl, counters)
                with jax.named_scope("sample"):
                    lg = logits[:, -1, :].astype(jnp.float32)
                    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                    if sampled:
                        key_ = jax.random.fold_in(jax.random.PRNGKey(0),
                                                  seed)
                        t = jnp.maximum(temps, 1e-6)[:, None]
                        draw = jax.random.categorical(
                            key_, lg / t, axis=-1).astype(jnp.int32)
                        nxt = jnp.where(temps > 0.0, draw, greedy)
                    else:
                        nxt = greedy
                # sl/seed advance on device so steady-state ticks feed these
                # outputs straight back in. An idle slot (a null table row)
                # stays at length 0: the paged kernel fetches by context
                return nxt, new_pages, sl + _holds_blocks(bt), seed + 1, \
                    counters

            # the counters are not donated: the arrays a step returned are
            # fetched beside its tokens, under the step after it (_fetch)
            return jax.jit(step, donate_argnums=(3, 5, 7))

        return self._program("decode", key, build)

    def _decode_multi_jit(self, k: int):
        """k decode steps fused into ONE compiled program (all-greedy
        batches only): per-dispatch host overhead — pytree flatten of ~30
        param leaves, pjit fast path, eager scatter bookkeeping — is a
        real fraction of a small model's step on CPU, and it amortizes
        k-fold. Returns the k sampled tokens flattened [k * slots] for the
        deferred-flush path plus the same carry as the 1-step program."""
        key = ("decode_multi", self.max_slots, self._table_cols, k)

        def build():
            paged_fn = self._functional()[0]

            def serve_decode_fused(pv, bv, tok, pages, bt, sl, temps, seed):
                def body(i, carry):
                    tok, pages, sl, out = carry
                    logits, new_pages, _ = paged_fn(pv, bv, tok[:, None],
                                                    pages, bt, sl)
                    with jax.named_scope("sample"):
                        lg = logits[:, -1, :].astype(jnp.float32)
                        nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                    return (nxt, new_pages, sl + _holds_blocks(bt),
                            out.at[i].set(nxt))

                out0 = jnp.zeros((k, tok.shape[0]), jnp.int32)
                tok, pages, sl, out = jax.lax.fori_loop(
                    0, k, body, (tok, pages, sl, out0))
                return tok, pages, sl, seed + k, out.reshape(-1)

            return jax.jit(serve_decode_fused, donate_argnums=(3, 5, 7))

        return self._program("decode_multi", key, build)

    def _spec_jit(self, W: int, sampled: bool):
        """Speculative verify: score a W-token window (current token +
        W-1 drafts, zero-padded past each slot's own draft length) in ONE
        dispatch through the multi-query paged attention path, and accept
        the longest draft prefix that matches the greedy targets — all on
        device. Returns per-slot greedy targets [slots, W] (targets 0..acc
        are this tick's emitted tokens), the accepted count, the
        fed-back next token, and lengths advanced by acc+1 — an EXACT
        rollback of every rejected position, whose garbage KV stays
        masked behind the length in the slot's own private blocks.
        Sampled slots (temperature > 0) ride with a zero draft length:
        their column-0 logits are the same distribution the plain step
        would compute, and their next token is the categorical draw."""
        key = ("spec", self.max_slots, self._table_cols, W, sampled)

        def build():
            paged_fn = self._functional()[0]

            def serve_spec_verify(pv, bv, win, pages, bt, sl, dls, temps,
                                  seed):
                logits, new_pages, _ = paged_fn(pv, bv, win, pages, bt, sl)
                with jax.named_scope("sample"):
                    lg = logits.astype(jnp.float32)   # [slots, W, vocab]
                    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                    # accepted = longest prefix where draft i+1 equals the
                    # greedy target after window position i
                    ok = ((win[:, 1:] == greedy[:, :-1])
                          & (jnp.arange(W - 1, dtype=jnp.int32)[None, :]
                             < dls[:, None]))
                    acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                                  axis=1)
                    nxt = jnp.take_along_axis(greedy, acc[:, None],
                                              axis=1)[:, 0]
                    if sampled:
                        key_ = jax.random.fold_in(jax.random.PRNGKey(0),
                                                  seed)
                        t = jnp.maximum(temps, 1e-6)[:, None]
                        draw = jax.random.categorical(
                            key_, lg[:, 0, :] / t,
                            axis=-1).astype(jnp.int32)
                        nxt = jnp.where(temps > 0.0, draw, nxt)
                return greedy, acc, nxt, new_pages, sl + acc + 1, seed + 1

            return jax.jit(serve_spec_verify, donate_argnums=(3, 5, 8))

        return self._program("spec", key, build)

    def _clear_slot_jit(self):
        """Fused device-side slot clear for _finish: zero the slot's token,
        block-table row, length and temperature in ONE dispatch. The decode
        program keeps running over EVERY slot after a finish, so leaving
        the device copies stale would keep writing the dead sequence's K/V
        at advancing positions into its freed blocks — which the allocator
        may have already handed to a newly admitted request in a DIFFERENT
        slot (slot-LIFO and block-LIFO reuse can misalign). An all-zero
        table row points the idle slot at the null block, where its writes
        are harmless and its (len 0) context is never read."""
        key = ("clear_slot", self.max_slots, self._table_cols)

        def build():
            def serve_clear_slot(toks, bt, sl, temps, slot):
                return (toks.at[slot].set(0),
                        bt.at[slot].set(jnp.zeros((bt.shape[1],), bt.dtype)),
                        sl.at[slot].set(0),
                        temps.at[slot].set(0.0))

            return jax.jit(serve_clear_slot)

        return self._program("clear_slot", key, build)

    def _admit_jit(self, chunk):
        """Fused admission for greedy requests: the first token (argmax of
        the prefill logits, ON device — no host sync per admitted prompt)
        plus the slot's scatter into the live decode state, one dispatch.
        Eager per-field at[].set scatters cost ~0.5ms EACH on CPU; this is
        the difference between admission costing a tick and costing
        nothing. The slot index is traced, so one program serves every
        slot. No donation: the incoming token vector is also referenced by
        the entries in flight (_pending)."""
        key = ("admit", chunk, self.max_slots, self._table_cols)

        def build():
            def serve_admit(logits, idx, toks, bt, sl, temps, slot, table,
                            plen, temp):
                lg = logits[0, idx].astype(jnp.float32)
                first = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                return (first[None],
                        toks.at[slot].set(first),
                        bt.at[slot].set(table),
                        sl.at[slot].set(plen),
                        temps.at[slot].set(temp))

            return jax.jit(serve_admit)

        return self._program("admit", key, build)

    def _workspace_jit(self, padded):
        """A first-turn prompt's empty prefill workspace, every array of
        every layer from ONE program (eagerly it is a dispatch an array: 48
        for a 24-layer model, each a gap on the device)."""
        key = ("workspace", padded)

        def build():
            spec, dtype = self._spec, self._dtype

            def serve_workspace():
                return init_kv_cache(1, padded, spec, dtype)

            return jax.jit(serve_workspace)

        return self._program("workspace", key, build)

    def _prefill_jit(self, chunk, padded):
        key = ("prefill", chunk, padded)

        def build():
            static_fn = self._functional()[1]

            def serve_prefill(pv, bv, ids, caches, pos):
                return static_fn(pv, bv, ids, caches, pos)

            return jax.jit(serve_prefill, donate_argnums=(3,))

        return self._program("prefill", key, build)

    def _gather_jit(self, padded, mb):
        """Materialize a prefill workspace whose head is a cached prefix
        gathered from the pool pages (prefix-cache partial hit: the suffix
        chunks run the contiguous cached path on top of it). Pages are NOT
        donated — they stay the live pool."""
        key = ("gather", padded, mb)

        def build():
            bs = self.block_size
            n = mb * bs

            def serve_gather(pages, table):
                def head(p):
                    ws = jnp.zeros((1, padded, p.shape[1], p.shape[3]),
                                   p.dtype)
                    return ws.at[0, :n].set(from_pages(p[table]))

                return [tuple(head(p) for p in arrays) for arrays in pages]

            return jax.jit(serve_gather)

        return self._program("gather", key, build)

    def _admit_cow_jit(self):
        """Full-prompt cache hit: fork the last shared block (device copy
        src -> dst across every layer — the only block the re-decoded last
        prompt token will write) and scatter the slot's decode state, one
        dispatch. Pages are donated (in-place pool update); the decode
        state tensors are not (the token vector may be referenced by the
        entries in flight)."""
        key = ("admit_cow", self.max_slots, self._table_cols)

        def build():
            def serve_admit_cow(pages, toks, bt, sl, temps, src, dst, slot,
                                table, plen, tok, temp):
                new = [tuple(p.at[dst].set(p[src]) for p in arrays)
                       for arrays in pages]
                return (new,
                        toks.at[slot].set(tok),
                        bt.at[slot].set(table),
                        sl.at[slot].set(plen),
                        temps.at[slot].set(temp))

            return jax.jit(serve_admit_cow, donate_argnums=(0,))

        return self._program("admit_cow", key, build)

    def _batched_prefill_jit(self, S, P):
        """ONE compiled program admitting up to max_slots prompts: gather
        each row's cached prefix into a contiguous [n, P] workspace, run
        the model over the padded [n, S] suffixes with PER-ROW position
        offsets, argmax each row's first token at its own last real index,
        scatter the workspaces back to the pool pages and the rows' decode
        state into the live slots — so a burst of N admissions costs one
        dispatch instead of N.

        Padding rows are inert by construction: their block tables are all
        null (write-back garbage lands in block 0, the idle-slot dumping
        ground) and their slot index is max_slots, which jax's scatter
        drops as out-of-bounds. Shared prefix blocks appear in several
        rows' tables; every row scatters back the IDENTICAL bytes it
        gathered, so duplicate-index writes are deterministic."""
        n = self.max_slots
        key = ("batched_prefill", n, S, P)

        def build():
            static_fn = self._functional()[1]
            bs = self.block_size

            def serve_batched_prefill(pv, bv, pages, ids, pos, tP, last,
                                      slots, bt_rows, plens, temps, d_toks,
                                      d_bt, d_sl, d_temps):
                caches = [(from_pages(kp[tP]), from_pages(vp[tP]))
                          for kp, vp in pages]
                logits, ncs = static_fn(pv, bv, ids, caches, pos)
                lg = logits[jnp.arange(n), last].astype(jnp.float32)
                first = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                flat = tP.reshape(-1)
                new_pages = []
                for (kp, vp), (k, v) in zip(pages, ncs):
                    # rows back to back: [n * P, hkv, d] -> n * P/bs pages
                    new_pages.append(
                        (kp.at[flat].set(
                            to_pages(k.reshape(-1, *k.shape[2:]), bs)),
                         vp.at[flat].set(
                            to_pages(v.reshape(-1, *v.shape[2:]), bs))))
                return (first, new_pages,
                        d_toks.at[slots].set(first),
                        d_bt.at[slots].set(bt_rows),
                        d_sl.at[slots].set(plens),
                        d_temps.at[slots].set(temps))

            return jax.jit(serve_batched_prefill, donate_argnums=(2,))

        return self._program("batched_prefill", key, build)

    def _scatter_jit(self, padded, nb):
        """Scatter a prefilled workspace prefix into the pool pages. The
        workspace slicing happens INSIDE the program (an eager slice per
        layer per prompt is pure dispatch overhead); both the pool and the
        spent workspace are donated."""
        key = ("scatter", padded, nb)

        def build():
            bs = self.block_size
            n = nb * bs
            cols = self._layer_cols
            full = (0, self.max_blocks_per_seq)

            def serve_scatter(pages, caches, row, last_block):
                """row: the slot's table row, every group's columns;
                last_block: the logical block of the prompt's last token
                (a window layer keeps the blocks that end there)."""
                out = []
                for arrays, ws, (a, b) in zip(pages, caches, cols):
                    if (a, b) == full:
                        out.append(write_prefix(
                            arrays, [w[0, :n] for w in ws], row[a:a + nb],
                            block_size=bs))
                    else:
                        out.append(write_ring(
                            arrays, [w[0] for w in ws], row[a:b],
                            last_block, block_size=bs))
                return out

            return jax.jit(serve_scatter, donate_argnums=(0,))

        return self._program("scatter", key, build)

    # ------------------------------------------------------------- intake
    def submit(self, prompt: List[int], max_new_tokens: int = 16,
               temperature: float = 0.0,
               eos_token_id: Optional[int] = None,
               request_id: Optional[str] = None,
               tier: str = "default",
               trace_ctx: Optional[dict] = None,
               prefill_only: bool = False) -> Request:
        req = Request(prompt, max_new_tokens=max_new_tokens,
                      temperature=temperature, eos_token_id=eos_token_id,
                      request_id=request_id, tier=tier, trace_ctx=trace_ctx,
                      prefill_only=prefill_only)
        max_queue = int(_flags.get_flag("serving_max_queue"))
        # step() holds the lock all through a tick: what a request waits
        # here is counted on /metrics always, and a span while one records
        t0 = time.monotonic()
        with self.obs.span("serving.submit_wait",
                           request_id=req.request_id):
            self._lock.acquire()
        try:
            SUBMIT_LOCK_WAIT_H.observe(time.monotonic() - t0)
            if self._draining:
                self.obs.on_shed(req, "draining")
                raise EngineDrainingError()
            depth = len(self.sched.waiting)
            if max_queue > 0 and depth >= max_queue:
                self.obs.on_shed(req, "queue_full")
                raise QueueFullError(depth, max_queue)
            self.obs.on_submit(req)
            self.sched.submit(req)
        finally:
            self._lock.release()
        return req

    # ----------------------------------------------------------- drain
    def drain(self):
        """Graceful drain for rolling restarts: stop admitting new
        requests (submit() raises EngineDrainingError) while everything
        already accepted — queued, prefilling, running — completes
        normally. /healthz reports `draining` with ok=False so a load
        balancer takes the replica out of rotation."""
        with self._lock:
            self._draining = True

    def resume(self):
        """Re-open admissions after a drain()."""
        with self._lock:
            self._draining = False

    @property
    def draining(self) -> bool:
        return self._draining

    def drained(self) -> bool:
        """True once a draining engine has no in-flight work left."""
        with self._lock:
            return self._draining and not self.sched.has_work()

    def cancel(self, req: Request, reason: str = "cancelled") -> bool:
        """Evict a request in any pre-finished state — queued, prefilling,
        or running — releasing its slot and worst-case KV reservation
        immediately. Used by the HTTP front end when a client times out or
        disconnects, so abandoned requests stop consuming serving capacity.
        Returns False if the request had already finished."""
        with self._lock:
            if req.state == "finished":
                return False
            self._finish(req, reason)
            return True

    # ------------------------------------------- KV-block streaming wire
    def _no_kv_wire(self, name):
        if self._unserved:
            raise NotImplementedError(
                f"ServingEngine.{name}: the KV wire carries prefix-cache "
                f"blocks of one block table as (K, V) pairs of one "
                f"geometry, and this model's cache spec has "
                f"{' and '.join(self._unserved)}: a ring has no prefix "
                f"cache over it, and a latent layer's one array is not "
                f"in the wire's format")

    def export_kv_blocks(self, tokens: List[int]) -> List[dict]:
        """Serialize the RESIDENT full-block prefix of `tokens` for
        streaming to another replica: one record per indexed block, chain
        order, each carrying the chain digest (hex), the previous link's
        digest, the block's token ids, and the raw per-layer (K, V) page
        bytes gathered from the device pool. Read-only; the wire format is
        what ingest_kv_blocks() (and the HTTP /kv/ingest endpoint, after
        base64) accepts."""
        self._no_kv_wire("export_kv_blocks")
        with self._lock:
            recs = self.allocator.export_prefix(tokens)
            if not recs:
                return []
            blks = np.asarray([r["block"] for r in recs], np.int32)
            layers = [(np.asarray(jax.device_get(kp[blks])),
                       np.asarray(jax.device_get(vp[blks])))
                      for kp, vp in self.pool.layers]
            out = []
            for i, r in enumerate(recs):
                out.append({
                    "digest": r["digest"].hex(),
                    "prev": r["prev"].hex(),
                    "tokens": r["tokens"],
                    "layers": [(k[i].tobytes(), v[i].tobytes())
                               for k, v in layers],
                })
            return out

    def ingest_kv_blocks(self, records: List[dict]) -> dict:
        """Admit streamed KV blocks into the local pool as prefix-cache
        entries. Each record is verified against the chain hash
        (allocator.import_block) and its byte payload against the pool
        geometry BEFORE anything is claimed; a failed link stops the chain
        (descendants could never be matched past the hole). Idempotent:
        already-resident digests are deduped without touching the pool.
        Returns {"imported", "dedup", "rejected", "skipped", "bytes"}."""
        self._no_kv_wire("ingest_kv_blocks")
        n_layers = len(self.pool.layers)
        kp0 = self.pool.layers[0][0]
        np_dtype = np.dtype(kp0.dtype)
        blk_shape = kp0.shape[1:]
        blk_bytes = int(np.prod(blk_shape)) * np_dtype.itemsize
        imported = dedup = rejected = skipped = nbytes = 0
        with self._lock:
            prev = b""
            pend = []               # (block_id, [(k_arr, v_arr), ...])
            for i, rec in enumerate(records):
                try:
                    digest = bytes.fromhex(rec["digest"])
                    rec_prev = bytes.fromhex(rec["prev"])
                    layers = rec["layers"]
                    if rec_prev != prev:
                        raise ValueError("broken chain: prev digest does "
                                         "not match the previous record")
                    if len(layers) != n_layers or any(
                            len(k) != blk_bytes or len(v) != blk_bytes
                            for k, v in layers):
                        raise ValueError("payload does not match the pool "
                                         "geometry")
                    blk, fresh = self.allocator.import_block(
                        prev, rec["tokens"], digest)
                except ValueError:
                    # corrupt/mislabeled link: everything after it hangs
                    # off an unverifiable digest — drop the rest
                    rejected += 1
                    skipped += len(records) - i - 1
                    break
                except MemoryError:
                    # pool full: a mid-chain hole makes descendants
                    # unmatchable, so don't import past it either
                    skipped += len(records) - i
                    break
                prev = digest
                if fresh:
                    imported += 1
                    nbytes += 2 * n_layers * blk_bytes
                    pend.append((blk, [
                        (np.frombuffer(k, np_dtype).reshape(blk_shape),
                         np.frombuffer(v, np_dtype).reshape(blk_shape))
                        for k, v in layers]))
                else:
                    dedup += 1
            if pend:
                idx = jnp.asarray(np.asarray([b for b, _ in pend],
                                             np.int32))
                new_layers = []
                for li, (kp, vp) in enumerate(self.pool.layers):
                    k_new = jnp.asarray(np.stack([a[li][0]
                                                  for _, a in pend]))
                    v_new = jnp.asarray(np.stack([a[li][1]
                                                  for _, a in pend]))
                    new_layers.append((kp.at[idx].set(k_new),
                                       vp.at[idx].set(v_new)))
                self.pool.replace(new_layers)
        return {"imported": imported, "dedup": dedup, "rejected": rejected,
                "skipped": skipped, "bytes": nbytes}

    # ------------------------------------------------------------ tick
    def step(self) -> dict:
        """One engine tick: admissions, one prefill chunk, one decode step
        over the running batch. Returns per-tick stats."""
        with self._lock:
            self.obs.tick_begin()
            with self.obs.span("serving.tick") as tick:
                out = self._tick()
                tick.set(step=self.steps, decoded=out["decoded_tokens"],
                         running=out["running"], waiting=out["waiting"])
            self.obs.on_tick(out)
            return out

    def _tick(self) -> dict:
        self._dispatched = False
        with self.obs.span("serving.schedule") as sp:
            admitted = self.sched.admit()
            for req in admitted:
                self.obs.on_admitted(req)
            # full-prompt cache hits never prefill: copy-on-write the last
            # shared block and drop straight into the decode batch
            for req in [r for r in self.sched.prefilling
                        if r._cow_src is not None]:
                self._admit_cached(req)
            sp.set(admitted=len(admitted), waiting=len(self.sched.waiting),
                   reserved_full=self.sched._reserved_blocks,
                   reserved_window=sum(r.used_blocks
                                       for r in self.window_rings))
        # batched multi-prompt prefill: a burst of short unmatched
        # suffixes admits in ONE dispatch instead of one per prompt
        if self.prefill_bucket > 0:
            batch = [r for r in self.sched.prefilling
                     if r._ws_caches is None and r.temperature <= 0.0
                     and 0 < (len(r.prompt) - r.prefill_pos)
                     <= self.prefill_chunk]
            if len(batch) >= 2:
                self._batched_prefill(batch[:self.max_slots])
        # one prefill chunk per tick bounds how long a prompt can stall
        # the running batch — but a slot with NOTHING to decode isn't
        # stalled, so after a burst (many admissions, few running) keep
        # prefilling up to one chunk per idle slot and the whole wave
        # joins decode this tick instead of trickling in serially
        budget = max(1, self.max_slots - len(self.sched.running))
        for _ in range(budget):
            req = self.sched.next_prefill()
            if req is None:
                break
            self._prefill_one_chunk(req)
            if self.sched.next_prefill() is req:
                break   # long prompt mid-prefill: one chunk per tick
        # a request whose budget the tokens in flight already fill has
        # nothing left to decode: a batch of such alone dispatches no step
        decoded = self._decode_step() if any(
            len(r.output_tokens) + r._pending_n < self._token_cap(r)
            for r in self.sched.running.values()) else 0
        # the tokens of the tick before come to the host under this tick's
        # programs; a tick that dispatched nothing fetches whatever is in
        # flight at once
        self._fetch(behind=self._dispatched)
        self.steps += 1
        return {"admitted": len(admitted), "decoded_tokens": decoded,
                **self.sched.counts()}

    def run_until_idle(self, max_steps: int = 1_000_000) -> int:
        steps = 0
        while self.sched.has_work():
            self.step()
            steps += 1
            if steps >= max_steps:
                raise RuntimeError("serving engine did not drain "
                                   f"within {max_steps} steps")
        return steps

    def generate(self, prompts, max_new_tokens: int = 16,
                 temperature: float = 0.0,
                 eos_token_id: Optional[int] = None):
        """Blocking convenience (tests): submit all, drain, return the full
        sequences (prompt + generated) as lists of ints."""
        reqs = [self.submit(list(p), max_new_tokens=max_new_tokens,
                            temperature=temperature,
                            eos_token_id=eos_token_id) for p in prompts]
        self.run_until_idle()
        return [r.prompt + r.output_tokens for r in reqs]

    # ----------------------------------------------------------- prefill
    def _table_row(self, req: Request) -> np.ndarray:
        """The request's table row: every cache group's table in its
        columns, the full group's reservation first."""
        row = np.zeros(self._table_cols, np.int32)
        table = self.allocator.table(req.request_id)
        row[:len(table)] = table
        for rings, (a, b), _ in self._ring_cols:
            row[a:b] = rings.table(req.request_id)
        return row

    def _admit_cached(self, req: Request) -> None:
        """Full-prompt prefix-cache hit: every prompt block is already in
        the pool, so the request enters decode DIRECTLY — zero prefill
        dispatches. The decode program recomputes the last prompt token's
        step (token = prompt[-1] at seq_len = plen - 1): its K/V write
        lands in the copy-on-write fork of the final shared block, and its
        logits yield the first generated token on the next decode tick."""
        if req.prefill_only:
            # every prompt block is already resident and indexed: a
            # prefill-only pass has nothing to compute OR publish — finish
            # without the COW dispatch (the fork block frees with the
            # reservation)
            self._finish(req, "prefill_complete")
            return
        plen = len(req.prompt)
        slot = req.slot
        table = np.asarray(self.allocator.table(req.request_id), np.int32)
        dst = int(table[plen // self.block_size - 1])
        src = int(req._cow_src)
        self._tables[slot] = self._table_row(req)
        self._lens[slot] = plen - 1
        self._toks[slot] = req.prompt[-1]
        self._temps[slot] = req.temperature
        if self._dev is None:
            self._dev_init()
        d_toks, d_tables, d_lens, d_temps, d_seed = self._dev
        new_layers, n_toks, n_bt, n_sl, n_temps = self._admit_cow_jit()(
            self.pool.layers, d_toks, d_tables, d_lens, d_temps,
            src, dst, slot, self._tables[slot], plen - 1,
            int(req.prompt[-1]), req.temperature)
        self.pool.replace(new_layers)
        self._dev = (n_toks, n_bt, n_sl, n_temps, d_seed)
        self._dispatched = True
        self._stats.inc("cow_admissions")
        self.sched.start_running(req)

    def _batched_prefill(self, reqs: List[Request]) -> None:
        """Admit a burst of prompts in ONE dispatch (see
        _batched_prefill_jit). Rows are the burst's unmatched suffixes,
        padded to a bucketed [n, S]; the workspace holds each row's full
        context (cached prefix + suffix) padded to P tokens. Greedy-only:
        each row's first token is argmaxed on device and fetched like any
        decode token, under the next tick's programs."""
        suffixes = [len(r.prompt) - r.prefill_pos for r in reqs]
        with self.obs.span("serving.prefill_chunk", reqs,
                           tokens=sum(suffixes), batched=True):
            _, _, pv, bv = self._functional()
            n = self.max_slots
            bs = self.block_size
            bucket = max(self.prefill_bucket, 1)
            S = -(-max(suffixes) // bucket) * bucket
            ctx = max(r.prefill_pos + S for r in reqs)
            # quantize the workspace length to the CHUNK grid, not the bucket
            # grid: P drives the compiled shape, and a fine grid means a fresh
            # XLA compile per burst composition (prefill_pos varies with cache
            # hits) — a compile storm costs far more than the extra padding
            P = -(-ctx // self.prefill_chunk) * self.prefill_chunk
            nb = P // bs
            ids = np.zeros((n, S), np.int32)
            pos = np.zeros(n, np.int32)
            tP = np.zeros((n, nb), np.int32)
            last = np.zeros(n, np.int32)
            slots = np.full(n, self.max_slots, np.int32)   # OOB -> dropped
            bt_rows = np.zeros((n, self._table_cols), np.int32)
            plens = np.zeros(n, np.int32)
            temps = np.zeros(n, np.float32)
            for r, req in enumerate(reqs):
                plen = len(req.prompt)
                start = req.prefill_pos
                take = plen - start
                ids[r, :take] = req.prompt[start:]
                pos[r] = start
                table = self.allocator.table(req.request_id)
                tP[r, :min(nb, len(table))] = table[:nb]
                last[r] = take - 1
                slots[r] = req.slot
                bt_rows[r] = self._table_row(req)
                plens[r] = plen
                temps[r] = req.temperature
            if self._dev is None:
                self._dev_init()
            d_toks, d_tables, d_lens, d_temps, d_seed = self._dev
            first_dev, new_layers, n_toks, n_bt, n_sl, n_temps = \
                self._batched_prefill_jit(S, P)(
                    pv, bv, self.pool.layers, jnp.asarray(ids),
                    jnp.asarray(pos), jnp.asarray(tP), jnp.asarray(last),
                    jnp.asarray(slots), jnp.asarray(bt_rows),
                    jnp.asarray(plens), jnp.asarray(temps),
                    d_toks, d_tables, d_lens, d_temps)
            self.pool.replace(new_layers)
            self._dev = (n_toks, n_bt, n_sl, n_temps, d_seed)
            self._dispatched = True
            self._stats.inc("batched_prefills")
            self._stats.inc("prefill_programs")
            computed = sum(suffixes)
            self._stats.inc("prefill_tokens", computed)
            _PREFILL_TOKENS.inc(computed)
        self._pending.append(_InFlight(
            first_dev, [(r, req.slot, req) for r, req in enumerate(reqs)],
            self.steps))
        for r, req in enumerate(reqs):
            slot = req.slot
            self._tables[slot] = bt_rows[r]
            self._lens[slot] = plens[r]
            self._toks[slot] = 0          # known at the next fetch
            self._temps[slot] = req.temperature
            req.prefill_pos = len(req.prompt)
            req._pending_n += 1
            if self.prefix_cache:
                self.allocator.register_prefix(req.request_id, req.prompt)
                if self.allocator.last_dedup:
                    # live dedup: identical blocks prefilled concurrently
                    # in this burst now share storage — adopt the swapped
                    # table on host AND in the already-uploaded device row
                    self._tables[slot] = self._table_row(req)
                    d_toks, d_tables, d_lens, d_temps, d_seed = self._dev
                    with self.obs.span("serving.host_upload",
                                       what="dedup_table"):
                        self._dev = (
                            d_toks,
                            d_tables.at[slot].set(
                                jnp.asarray(self._tables[slot])),
                            d_lens, d_temps, d_seed)
                    self._stats.inc("dedup_admissions")
            if req.prefill_only:
                # the row rode the shared dispatch for its KV only; finish
                # instead of joining decode (its first token is dropped
                # with the finish, unfetched)
                self._finish(req, "prefill_complete")
                continue
            self.sched.start_running(req)

    def _pace_prefill(self) -> None:
        """Before a prefill chunk is dispatched: wait until at most one is
        on the device. A chunk holds its logits (chunk x vocabulary: 158 MB
        for 512 rows of 77k words) and its temporaries from its dispatch
        until it has run, and while no request decodes nothing else paces
        the host: a long prompt alone, or a burst of short ones in one
        tick, had sixteen chunks in flight, all the runtime queues. Two
        keep the device fed: the wait is for the chunk BEFORE the one in
        flight, and is over at once where a decode step's fetch paced the
        tick already."""
        self._chunks = [c for c in self._chunks if not c.is_ready()]
        while len(self._chunks) > 1:
            with self.obs.span("serving.fetch", what="prefill_chunk",
                               behind=1, ticks=0, tokens=0):
                self._chunks.pop(0).block_until_ready()

    def _prefill_one_chunk(self, req: Request) -> None:
        plen = len(req.prompt)
        chunk = self.prefill_chunk
        start = req.prefill_pos
        take = min(chunk, plen - start)
        self._pace_prefill()
        with self.obs.request_span("serving.prefill_chunk", req,
                                   tokens=take, start=start, batched=False):
            _, _, pv, bv = self._functional()
            # chunk writes start at prefix_matched (a block multiple, not
            # necessarily a chunk multiple): the workspace must cover the LAST
            # chunk window, or dynamic_update_slice would clamp it backwards
            padded = (req.prefix_matched
                      + -(-(plen - req.prefix_matched) // chunk) * chunk)
            if req._ws_caches is None:
                if req.prefix_matched:
                    # partial prefix hit: seed the workspace with the cached
                    # blocks so the suffix chunks run on top of real context
                    mb = req.prefix_matched // self.block_size
                    head = np.asarray(
                        self.allocator.table(req.request_id)[:mb], np.int32)
                    req._ws_caches = self._gather_jit(padded, mb)(
                        self.pool.layers, head)
                else:
                    req._ws_caches = self._workspace_jit(padded)()
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :take] = req.prompt[start:start + take]
            logits, req._ws_caches = self._prefill_jit(chunk, padded)(
                pv, bv, ids, req._ws_caches, np.int32(start))
            self._chunks.append(logits)
            self._dispatched = True
            req.prefill_pos = start + take
            self._stats.inc("prefill_programs")
            self._stats.inc("prefill_tokens", take)
            _PREFILL_TOKENS.inc(take)
        if req.prefill_pos < plen:
            return
        # prompt fully prefilled: sample the first token from the last REAL
        # position of this chunk, scatter the prefix into pages, join
        # decode. The table is the WHOLE worst-case reservation (scheduler
        # admit); only the prompt-covering prefix is scattered — decode
        # appends fill the rest position by position.
        nb = -(-plen // self.block_size)
        new_layers = self._scatter_jit(padded, nb)(
            self.pool.layers, req._ws_caches, self._table_row(req),
            np.int32((plen - 1) // self.block_size))
        self.pool.replace(new_layers)
        req._ws_caches = None
        if self.prefix_cache:
            # the prompt's full blocks are now resident in the pool: index
            # them so later prompts sharing the prefix skip its prefill
            self.allocator.register_prefix(req.request_id, req.prompt)
            if self.allocator.last_dedup:
                # live dedup (a twin registered first while this prompt
                # prefilled): the slot's row below is read after the swap
                self._stats.inc("dedup_admissions")
        if req.prefill_only:
            # disaggregated prefill pass: the prompt's KV is scattered and
            # its full blocks indexed — they stay resident (evictable,
            # matchable, exportable) after the finish releases the
            # sequence. No first token: the decode replica samples it.
            self._finish(req, "prefill_complete")
            return
        slot = req.slot
        self._tables[slot] = self._table_row(req)
        self._lens[slot] = plen
        self._temps[slot] = req.temperature
        if req.temperature <= 0.0:
            # greedy, eos id or not: the first token is sampled on the
            # device by the program that scatters the slot's state, and
            # its value comes with the next fetch, so admission never
            # waits for the prefill chunk
            if self._dev is None:
                self._dev_init()
            d_toks, d_tables, d_lens, d_temps, d_seed = self._dev
            first_dev, n_toks, n_bt, n_sl, n_temps = self._admit_jit(chunk)(
                logits, plen - 1 - start, d_toks, d_tables, d_lens, d_temps,
                slot, self._tables[slot], plen, req.temperature)
            self._dev = (n_toks, n_bt, n_sl, n_temps, d_seed)
            self._pending.append(
                _InFlight(first_dev, [(0, slot, req)], self.steps))
            req._pending_n += 1
            self.sched.start_running(req)
            return
        # a sampled request draws its first token on the host, from the
        # chunk's logits: the one admission that waits for its prefill
        with self._fetch_span("first_token_logits", behind=False, ticks=0,
                              tokens=1):
            last = np.asarray(jax.device_get(logits[0, plen - 1 - start]))
        first = self._sample_host(last, req)
        self._toks[slot] = first
        if self._dev is not None:
            # join the live decode batch by scattering this slot's state
            # into the device copies (host-known scalars — no sync, the
            # other slots' in-flight tokens are untouched)
            d_toks, d_tables, d_lens, d_temps, d_seed = self._dev
            with self.obs.span("serving.host_upload", what="slot_state"):
                self._dev = (d_toks.at[slot].set(first),
                             d_tables.at[slot].set(
                                 jnp.asarray(self._tables[slot])),
                             d_lens.at[slot].set(plen),
                             d_temps.at[slot].set(req.temperature),
                             d_seed)
        self.sched.start_running(req)
        self._first_token(req, time.monotonic())
        req.output_tokens.append(first)
        req._progress.set()
        if req.eos_token_id is not None and first == req.eos_token_id:
            self._finish(req, "stop")
        elif len(req.output_tokens) >= self._token_cap(req):
            self._finish(req, "length")

    def _first_token(self, req: Request, now: float) -> None:
        """The first token's VALUE is on the host: that instant is the
        request's first_token_time, whenever the program that sampled it
        was dispatched."""
        req.first_token_time = now
        self.obs.on_first_token(req)

    def _token_cap(self, req: Request) -> int:
        """Output tokens at which `req` finishes by length: its budget, or
        the context cap (the prompt and every token but the last take a
        position)."""
        return max(1, min(req.max_new_tokens,
                          self.max_model_len + 1 - len(req.prompt)))

    def _sample_host(self, logits: np.ndarray, req: Request) -> int:
        """First-token sampling for a sampled admission (temperature > 0;
        a greedy one argmaxes on the device, _admit_jit): same
        fold_in(PRNGKey(0), seed) threefry scheme as the compiled decode
        step, plus a per-admission nonce — two sampled requests admitted in
        the SAME tick must draw from distinct streams, and the first token
        must not replay what a decode tick at the same seed would emit."""
        self._sample_nonce += 1
        key = jax.random.fold_in(jax.random.PRNGKey(0), self._step_seed)
        key = jax.random.fold_in(key, self._sample_nonce)
        lg = jnp.asarray(logits, jnp.float32) / max(req.temperature, 1e-6)
        return int(jax.random.categorical(key, lg, axis=-1))

    # ------------------------------------------------------------ decode
    def _dev_init(self):
        with self.obs.span("serving.host_upload", what="decode_state"):
            self._dev = (jnp.asarray(self._toks),
                         jnp.asarray(self._tables),
                         jnp.asarray(self._lens), jnp.asarray(self._temps),
                         jnp.asarray(self._step_seed, jnp.int32))

    def _decode_step(self) -> int:
        if self.spec_k > 0:
            decoded = self._spec_step()
            if decoded is not None:
                return decoded
        running = list(self.sched.running.items())
        needs_sampling = any(req.temperature > 0.0 for _, req in running)
        # fuse 4 decode steps into one dispatch for all-greedy batches.
        # Fused or not, a slot whose request is over (an eos the host has
        # not seen yet, a budget that ran out mid-chunk) decodes on until
        # the fetch that finishes it: the extra tokens are dropped there,
        # and the overflow KV writes can only land in the null block (a
        # position past the reservation or past the table) or the
        # finishing slot's own about-to-be-freed pages — never another
        # sequence's. Prefill still gets its chunk every dispatch, so
        # fusing costs admission at most 3 steps of latency per queued
        # prompt.
        k = 1 if needs_sampling else self.fuse_steps
        with self.obs.span("serving.decode", self.sched.running.values(),
                           batch=len(running), steps=k):
            _, _, pv, bv = self._functional()
            if self._dev is None:
                self._dev_init()
            d_toks, d_tables, d_lens, d_temps, d_seed = self._dev
            # block tables are the full worst-case reservation, uploaded
            # once at admission — a steady-state decode tick touches NO
            # host state but the pending counters: no allocator call, no
            # table scatter, just one compiled-program dispatch
            if k == 1:
                nxt, new_layers, new_lens, new_seed, self._counters = \
                    self._decode_jit(needs_sampling)(
                        pv, bv, d_toks, self.pool.layers, d_tables, d_lens,
                        d_temps, d_seed, self._counters)
                toks = nxt
                items = [(slot, slot, req) for slot, req in running]
            else:
                nxt, new_layers, new_lens, new_seed, toks = \
                    self._decode_multi_jit(k)(
                        pv, bv, d_toks, self.pool.layers, d_tables, d_lens,
                        d_temps, d_seed)
                items = [(i * self.max_slots + slot, slot, req)
                         for i in range(k) for slot, req in running]
            self.pool.replace(new_layers)
            self._dev = (nxt, d_tables, new_lens, d_temps, new_seed)
            self._dispatched = True
            self._step_seed += k
            # the values come with the next tick's fetch; the bookkeeping
            # below needs COUNTS only
            self._pending.append(
                _InFlight(toks, items, self.steps, self._counters))
        self._count_keys([slot for slot, _ in running], k)
        for slot, req in running:
            req._pending_n += k
            self._lens[slot] += k
        return len(running) * k

    def _count_keys(self, running, k) -> None:
        """serving_paged_keys_total, serving_latent_keys_total and
        serving_window_keys_total for one decode dispatch of k steps: the
        pages the kernel fetches (by its own arithmetic, `live_pages`, over
        every slot: an idle one is handed context 1 and fetches a page), in
        tokens, beside the running contexts' keys, which a full or a latent
        layer has to read."""
        bs = self.block_size
        ctx = self._lens.astype(np.int64)[:, None] + 1 + np.arange(k)
        live = int(ctx[running].sum())
        for counter, kinds, window, layers in self._key_counters:
            _, pages = live_pages(ctx, bs, window)
            counter.inc(int(pages.sum()) * bs * layers, kind=kinds[0])
            counter.inc(live * layers, kind=kinds[1])

    def layer_counters(self) -> dict:
        """{layer index: counters} fetched from the device (one transfer,
        which waits for the step in flight: stats() pays it, a finish does
        not, see _fetch), and what they add since the last fetch published
        to the registry: for a sparse layer, pairs by held expert and the
        pairs of experts held elsewhere, then the layer's `extra` counters
        (LayerCacheSpec)."""
        if not self._counters:
            return {}
        with self.obs.span("serving.fetch", what="layer_counters", ticks=0,
                           tokens=0, behind=0):
            vals = jax.device_get(list(self._counters))
        return self._publish_counters(vals)

    def _publish_counters(self, vals) -> dict:
        vals = [np.asarray(v, np.int64) for v in vals]
        for i, v, seen in zip(self._counter_layers, vals,
                              self._counters_published):
            new = v - seen
            seen[:] = v
            extra = self._spec.layers[i].extra
            n = len(v) - len(extra)     # the experts held and one for the others
            for name, add in zip(extra, new[n:]):
                _LAYER_COUNTERS[name].inc(int(add), layer=f"h{i}")
            if not n:
                continue
            held = v[:n - 1]
            _MOE_PAIRS.inc(int(new[:n - 1].sum()), held="yes")
            _MOE_PAIRS.inc(int(new[n - 1]), held="no")
            if held.sum() > 0:
                _MOE_LOAD.set(float(held.max() / held.mean()), layer=f"h{i}")
        self._counters_due = False
        return dict(zip(self._counter_layers, vals))

    def _spec_step(self) -> Optional[int]:
        """One speculative tick, or None to fall through to the plain
        decode path, whose fetch comes a tick later (no request may draft
        right now — all paused by the adaptive throttle, sampled, or out of
        budget).

        Speculation is inherently synchronous on the host side: drafting
        needs every emitted token's VALUE, so the tick fetches what is in
        flight first and its own (targets, accepted) results eagerly. The
        adaptive pause keeps that cost off non-repetitive traffic — when
        nothing drafts, the plain pipelined path runs untouched."""
        # cheap pre-check before paying the fetch: is anyone allowed to
        # draft this tick? (draft_k needs no token values)
        active = False
        for slot, req in self.sched.running.items():
            if req.temperature > 0.0:
                continue
            if req._spec is None:
                req._drafter = NgramDrafter(max_n=self.spec_ngram)
                req._spec = SpecState(self.spec_k,
                                      pause_ticks=self.spec_pause)
            if req._spec.draft_k(self.steps) > 0:
                active = True
        if not active:
            return None
        self._fetch(behind=False)
        running = list(self.sched.running.items())
        if not running:
            return 0
        # draft per slot, capped so a fully-accepted window can never
        # overrun the token budget, the context cap, or the worst-case
        # block reservation (rollback never needs to grow a table)
        drafts = {}
        for slot, req in running:
            if req.temperature > 0.0 or req._spec is None:
                continue
            rid = req.request_id
            room = (self.block_size * len(self.allocator.table(rid))
                    - self.allocator.seq_len(rid) - 1)
            k_r = min(req._spec.draft_k(self.steps),
                      req.max_new_tokens - len(req.output_tokens) - 1,
                      self.max_model_len - 1 - int(self._lens[slot]),
                      room)
            if k_r <= 0:
                continue
            d = req._drafter.propose(req.prompt + req.output_tokens, k_r)
            drafts[slot] = d
            if not d:
                req._spec.record(0, 0, self.steps)
        if not any(drafts.values()):
            return None     # nobody produced a draft: plain path
        # FIXED window width: the verify program is compiled once for
        # W = spec_k + 1 and shorter (or absent) drafts are masked by
        # dls — a varying per-tick max draft length would recompile the
        # step every time the adaptive throttle moved k
        W = 1 + self.spec_k
        _, _, pv, bv = self._functional()
        if self._dev is None:
            self._dev_init()
        d_toks, d_tables, d_lens, d_temps, d_seed = self._dev
        win = np.zeros((self.max_slots, W), np.int32)
        dls = np.zeros(self.max_slots, np.int32)
        for slot, req in running:
            win[slot, 0] = self._toks[slot]
            d = drafts.get(slot, ())
            win[slot, 1:1 + len(d)] = d
            dls[slot] = len(d)
        needs_sampling = any(req.temperature > 0.0 for _, req in running)
        with self.obs.span("serving.spec_verify",
                           self.sched.running.values(),
                           batch=len(running), steps=1, window=W):
            greedy, acc, nxt, new_layers, new_sl, new_seed = self._spec_jit(
                W, needs_sampling)(
                pv, bv, jnp.asarray(win), self.pool.layers, d_tables,
                d_lens, jnp.asarray(dls), d_temps, d_seed)
            self.pool.replace(new_layers)
            self._dev = (nxt, d_tables, new_sl, d_temps, new_seed)
            self._dispatched = True
            self._step_seed += 1
            self._stats.inc("spec_ticks")
        # speculation needs this tick's values before the next draft
        with self._fetch_span("spec_verify", behind=False,
                              ticks=1) as fetch:
            greedy_h, acc_h, nxt_h = jax.device_get((greedy, acc, nxt))
            fetch.set(tokens=int(acc_h.sum()) + len(running))
        now = time.monotonic()
        decoded = 0
        touched = []
        for slot, req in running:
            if req.first_token_time is None:
                self._first_token(req, now)
            dl = int(dls[slot])
            if req.temperature > 0.0:
                # single-token fallback in the mixed batch: the sampled
                # draw fed back by the program
                t = int(nxt_h[slot])
                req.output_tokens.append(t)
                self._toks[slot] = t
                self._lens[slot] += 1
                decoded += 1
                touched.append((slot, req))
                continue
            a = int(acc_h[slot])
            emitted = [int(x) for x in greedy_h[slot, :a + 1]]
            if dl:
                # allocator commit of the whole window via the existing
                # append path, then EXACT rollback of the rejected tail
                # (length rewind + table trim down to the reservation)
                rid = req.request_id
                for _ in range(dl + 1):
                    self.allocator.append_token(rid)
                    if self.allocator.last_fork is not None:
                        raise RuntimeError(
                            "speculative append forked a shared block — "
                            "decode writes must only land in private "
                            "blocks")
                if a < dl:
                    self.allocator.rollback(rid, dl - a)
                    self._stats.inc("spec_rollbacks")
                    self.obs.on_rollback(req, dl - a)
                # record() also advances the global serving_spec_* counters
                req._spec.record(dl, a, self.steps)
                self._stats.inc("spec_proposed", dl)
                self._stats.inc("spec_accepted", a)
            req.output_tokens.extend(emitted)
            self._toks[slot] = emitted[-1]
            self._lens[slot] += a + 1
            decoded += len(emitted)
            touched.append((slot, req))
        for slot, req in touched:
            if req.eos_token_id is not None and \
                    req.eos_token_id in req.output_tokens:
                cut = req.output_tokens.index(req.eos_token_id) + 1
                del req.output_tokens[cut:]
                self._finish(req, "stop")
            elif len(req.output_tokens) >= req.max_new_tokens:
                del req.output_tokens[req.max_new_tokens:]
                self._finish(req, "length")
            elif int(self._lens[slot]) >= self.max_model_len:
                self._finish(req, "length")
        for _, req in touched:
            req._progress.set()
        return decoded

    def _fetch_span(self, what: str, behind: bool, **args):
        """The `serving.fetch` span around a wait for token values, counted
        by whether a later dispatch is in flight behind what it waits for
        (the device works on) or none is (the device idles once this is
        done, until the host has dispatched again)."""
        FETCHES.inc(kind="under_dispatch" if behind else "exposed")
        return self.obs.span("serving.fetch", what=what, behind=int(behind),
                             **args)

    def _fetch(self, behind: bool) -> None:
        """Bring sampled tokens to the host (one transfer), append them in
        tick order, then run the finish checks. `behind`: this tick has
        dispatched, so what EARLIER ticks dispatched is fetched and this
        tick's programs run under the wait and under the bookkeeping after
        it; else everything in flight is fetched, with nothing behind it.
        An eos is found here, a tick after the step that emitted it: the
        step dispatched since decoded one token past it, dropped below or
        by _finish."""
        n = sum(1 for e in self._pending if e.tick < self.steps) if behind \
            else len(self._pending)
        if not n:
            return
        fetched, self._pending = self._pending[:n], self._pending[n:]
        # a finish publishes the layers' counters: those the newest step
        # fetched here returned ride this transfer
        counters = next((e.counters for e in reversed(fetched)
                         if e.counters), ()) if self._counters_due else ()
        with self._fetch_span("tokens", behind, ticks=n,
                              tokens=sum(len(e.items) for e in fetched)):
            vals = jax.device_get([e.tokens for e in fetched]
                                  + list(counters))
        now = time.monotonic()
        if counters:
            self._publish_counters(vals[n:])
        touched = {}
        for arr, e in zip(vals, fetched):
            a = np.asarray(arr)
            for idx, slot, req in e.items:
                req._pending_n -= 1
                if req.first_token_time is None:
                    self._first_token(req, now)
                touched.setdefault(req.request_id,
                                   (req, len(req.output_tokens)))
                t = int(a[idx])
                req.output_tokens.append(t)
                self._toks[slot] = t
        for req, seen in touched.values():
            out = req.output_tokens
            cap = self._token_cap(req)
            new = out[seen:cap]
            if req.eos_token_id is not None and req.eos_token_id in new:
                keep, reason = seen + new.index(req.eos_token_id) + 1, "stop"
            elif len(out) >= cap:
                keep, reason = cap, "length"
            else:
                continue
            # decoded past the finish (a fused chunk's tail): dropped
            OVERSHOOT_TOKENS.inc(len(out) - keep)
            del out[keep:]
            self._finish(req, reason)
        for req, _ in touched.values():
            # wake streaming readers AFTER the finish checks so a reader
            # never observes tokens past an eos truncation
            req._progress.set()

    def _finish(self, req: Request, reason: str) -> None:
        slot = req.slot
        self.sched.finish(req, reason)
        req._pending_n = 0
        # what is in flight for it is never fetched. After a stop or a
        # length that is the overshoot: the step dispatched before the
        # host saw the finish
        dropped = 0
        for e in self._pending:
            live = [it for it in e.items if it[2] is not req]
            dropped += len(e.items) - len(live)
            e.items[:] = live
        self._pending = [e for e in self._pending if e.items]
        if slot is not None:
            self._tables[slot] = 0
            self._lens[slot] = 0
            self._toks[slot] = 0
            self._temps[slot] = 0.0
            if self._dev is not None:
                # the blocks just freed can be reallocated to a request in
                # another slot before this slot is refilled — clear the
                # DEVICE copies too, or the next decode ticks keep writing
                # this dead sequence's K/V into someone else's pages
                d_toks, d_tables, d_lens, d_temps, d_seed = self._dev
                self._dev = (*self._clear_slot_jit()(
                    d_toks, d_tables, d_lens, d_temps, slot), d_seed)
        if reason in ("stop", "length"):
            OVERSHOOT_TOKENS.inc(dropped)
            # the counters ride the next token fetch (or stats())
            self._counters_due = bool(self._counters)
        self.obs.on_finish(req, reason)

    # ------------------------------------------------------------ status
    def snapshot_output(self, req: Request):
        """Consistent (tokens, state, finish_reason) for streaming
        handlers: taken under the engine lock so a reader never races the
        flush's eos truncation."""
        with self._lock:
            return list(req.output_tokens), req.state, req.finish_reason

    def stats(self) -> dict:
        """Legacy JSON snapshot (shape unchanged since r11), now taken
        under the engine lock so a /stats scrape during concurrent
        streaming sees one consistent tick, not a field-by-field race."""
        with self._lock:
            return self._stats_locked()

    def _stats_locked(self) -> dict:
        return {
            "steps": self.steps,
            "kv": self.allocator.occupancy_report(),
            "kv_window": [r.occupancy_report() for r in self.window_rings],
            "layer_counters": {f"h{i}": [int(x) for x in v]
                               for i, v in self.layer_counters().items()},
            "prefix_cache": self.prefix_cache,
            "prefill_programs": self.prefill_programs,
            "batched_prefills": self.batched_prefills,
            "prefill_tokens": self.prefill_tokens,
            "cow_admissions": self.cow_admissions,
            "dedup_admissions": self.dedup_admissions,
            "speculative": {
                "enabled": self.spec_k > 0,
                "k": self.spec_k,
                "ticks": self.spec_ticks,
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "rollbacks": self.spec_rollbacks,
                "acceptance": (self.spec_accepted / self.spec_proposed
                               if self.spec_proposed else 0.0),
            },
            **self.sched.counts(),
        }
