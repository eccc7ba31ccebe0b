"""Autoregressive generation with static-shape KV caches.

Reference analog: the serving decode path built on the cache-KV variant of
fused_multi_transformer (paddle/fluid/operators/fused/
fused_multi_transformer_op.cu) plus the sampling ops (phi top_p_sampling).

TPU-first design:
  * KV caches are STATIC [b, max_len, kv_heads, head_dim] buffers per layer;
    each decode step writes at `pos` via dynamic_update_slice inside the op
    (ops/kernels/nn_ops.cached_multihead_attention) and masks invalid tail
    positions — so the single-token decode step is ONE compiled XLA program
    reused for every token, with cache buffers donated (updated in place in
    HBM, no reallocation).
  * prefill is a second compiled program per prompt length: it runs the full
    prompt through the same cached path at pos=0, filling the cache in one
    pass.
  * sampling (greedy / temperature / top-k / top-p) happens INSIDE the
    compiled step — no device->host round-trip per token except the optional
    EOS check.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import random as _random
from ..core.tensor import Tensor


@dataclass(frozen=True)
class LayerCacheSpec:
    """What one layer keeps between decode steps: the contract between a
    model and whatever holds its cache (generate() below, the serving
    engine). `kind` "full": keys and values of every earlier position;
    "window": of the last `window` positions only, so a holder may keep
    no more; "latent": ONE array of every earlier position, `head_dim` wide
    under one head (a compressed key-value latent and its rotary key, from
    which the layer's attention makes keys and values: no V is kept).
    `counters`: int32 counters the layer adds up beside its cache; the
    serving engine keeps them on the device and hands them to the layer as
    `cache.counters`. Their layout: a sparse layer's pairs by held expert
    and one entry for the pairs of experts held elsewhere, then one entry
    for each name in `extra` (additive counts of the layer's own, which the
    engine publishes as serving_<name>_total{layer}); a layer without
    experts keeps the `extra` ones alone."""

    kind: str
    kv_heads: int
    head_dim: int
    window: int = 0
    counters: int = 0
    extra: Tuple[str, ...] = ()

    def __post_init__(self):
        if self.kind not in ("full", "window", "latent"):
            raise ValueError(f"cache kind {self.kind!r}: full, window or "
                             f"latent")
        if (self.kind == "window") != (self.window > 0):
            raise ValueError("a window layer states its window, a full "
                             "layer none")
        if self.kind == "latent" and self.kv_heads != 1:
            raise ValueError("a latent layer keeps one array under one head")
        experts = self.counters - len(self.extra)
        if experts < 0 or experts == 1:
            raise ValueError("counters: the experts held and one entry for "
                             "the others, or none, then the `extra` ones")

    @property
    def arrays(self) -> int:
        """Arrays a holder keeps for the layer: K and V, or the one latent."""
        return 1 if self.kind == "latent" else 2


@dataclass(frozen=True)
class CacheSpec:
    layers: Tuple[LayerCacheSpec, ...]
    max_positions: int


def uniform_cache_spec(num_layers: int, num_kv_heads: int, head_dim: int,
                       max_positions: int) -> CacheSpec:
    """Every layer full attention over the same K/V heads (GPT, LLaMA)."""
    return CacheSpec((LayerCacheSpec("full", num_kv_heads, head_dim),)
                     * num_layers, max_positions)


def init_kv_cache(batch: int, max_len: int, spec: CacheSpec,
                  dtype=jnp.float32):
    """Allocate the per-layer static buffers [batch, max_len, kv_heads,
    head_dim]: a list of (k, v) arrays, or (latent,) for a latent layer. A
    window layer's is as long as the others: its attention masks what lies
    before the window."""
    return [
        tuple(jnp.zeros((batch, max_len, l.kv_heads, l.head_dim), dtype)
              for _ in range(l.arrays))
        for l in spec.layers
    ]


def _sample_inside_jit(logits, do_sample, temperature, top_k, top_p, seed):
    """logits: [b, vocab] (last position). Returns ids [b] int32."""
    if not do_sample or (temperature is not None and temperature <= 0.0):
        # temperature 0 conventionally means deterministic decoding
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits.astype(jnp.float32)
    if temperature != 1.0:
        logits = logits / temperature
    if top_k:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p and top_p < 1.0:
        from ..ops.kernels.random import nucleus_keep_mask

        order = jnp.argsort(-logits, axis=-1)
        sorted_l = jnp.take_along_axis(logits, order, axis=-1)
        keep_sorted = nucleus_keep_mask(
            jax.nn.softmax(sorted_l, axis=-1), top_p)
        # scatter the keep mask back to vocab order
        keep = jnp.zeros_like(keep_sorted).at[
            jnp.arange(logits.shape[0])[:, None], order].set(keep_sorted)
        logits = jnp.where(keep, logits, -jnp.inf)
    key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


class GenerationMixin:
    """Adds `generate()` to a CausalLM whose forward supports
    `forward(input_ids, caches=..., pos=...) -> (logits, caches)`.

    Subclass contract (GPTForCausalLM / LlamaForCausalLM /
    LagunaForCausalLM):
      * `cache_spec() -> CacheSpec`: per layer, what it keeps (full,
        window or latent; K/V heads, head size), and the positions the
        model allows
      * forward threading as above with static-shape caches.
    """

    def _cache_dtype(self):
        p = next(iter(self.parameters()))
        return p._value.dtype

    def _functional_forward(self):
        """A pure fn(param_vals, buffer_vals, ids, caches, pos) ->
        (logits, caches) over this module, safe to jit."""
        params = list(self.parameters())
        buffers = list(self.buffers())

        def fn(param_vals, buffer_vals, ids, caches, pos):
            saved_p = [(p._value, p.stop_gradient) for p in params]
            saved_b = [b._value for b in buffers]
            try:
                for p, v in zip(params, param_vals):
                    p._value = v
                    p.stop_gradient = True
                for b, v in zip(buffers, buffer_vals):
                    b._value = v
                caches_t = [tuple(Tensor(a) for a in c) for c in caches]
                logits, new_caches = self.forward(
                    Tensor(ids), caches=caches_t, pos=Tensor(pos))
                return logits._value, [
                    tuple(a._value for a in c) for c in new_caches]
            finally:
                for p, (v, sg) in zip(params, saved_p):
                    p._value, p.stop_gradient = v, sg
                for b, v in zip(buffers, saved_b):
                    b._value = v

        return fn, params, buffers

    def generate(self, input_ids, max_new_tokens: int = 32,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None,
                 seed: int = 0):
        """Greedy/sampled autoregressive decoding. Returns the full sequence
        (prompt + generated) as an int32 Tensor [b, s0 + n_new], where n_new
        is max_new_tokens CAPPED at the model's context window
        (max_position_embeddings - prompt_len); the returned tail is also
        truncated early when every row has emitted eos_token_id."""
        was_training = self.training
        self.eval()
        try:
            return self._generate_impl(
                input_ids, max_new_tokens, do_sample, float(temperature),
                int(top_k), float(top_p), eos_token_id, seed)
        finally:
            if was_training:
                self.train()

    def _generate_impl(self, input_ids, max_new_tokens, do_sample,
                       temperature, top_k, top_p, eos_token_id, seed):
        ids = input_ids._value if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
        ids = ids.astype(jnp.int32)
        b, s0 = ids.shape
        spec = self.cache_spec()
        max_pos = spec.max_positions
        max_len = min(int(max_pos), s0 + max_new_tokens)
        n_new = max_len - s0
        if n_new <= 0:
            raise ValueError(
                f"prompt length {s0} leaves no room under "
                f"max_position_embeddings={max_pos}")
        caches = init_kv_cache(b, max_len, spec, self._cache_dtype())

        fn, params, buffers = self._functional_forward()
        param_vals = [p._value for p in params]
        buffer_vals = [b_._value for b_ in buffers]
        sample_cfg = (bool(do_sample), temperature, top_k, top_p)

        def prefill(pv, bv, ids, caches, step_seed):
            logits, caches = fn(pv, bv, ids, caches, jnp.asarray(0, jnp.int32))
            nxt = _sample_inside_jit(logits[:, -1, :], *sample_cfg, step_seed)
            return nxt, caches

        def decode(pv, bv, tok, caches, pos, step_seed):
            logits, caches = fn(pv, bv, tok[:, None], caches, pos)
            nxt = _sample_inside_jit(logits[:, -1, :], *sample_cfg, step_seed)
            return nxt, caches

        # one compiled program per (prompt_len); one for all decode steps.
        # cache buffers are donated so decode updates KV in place in HBM.
        key_pre = ("_gen_prefill", s0, b, max_len, sample_cfg)
        key_dec = ("_gen_decode", b, max_len, sample_cfg)
        cache = getattr(self, "_gen_exec_cache", None)
        if cache is None:
            cache = self._gen_exec_cache = {}
        if key_pre not in cache:
            cache[key_pre] = jax.jit(prefill, donate_argnums=(3,))
        if key_dec not in cache:
            cache[key_dec] = jax.jit(decode, donate_argnums=(3,))

        tok, caches = cache[key_pre](param_vals, buffer_vals, ids, caches,
                                     jnp.asarray(seed, jnp.int32))
        out: List = [tok]
        eos_rows = None
        if eos_token_id is not None:
            eos_rows = np.asarray(jax.device_get(tok)) == eos_token_id
        for t in range(1, n_new):
            if eos_rows is not None and eos_rows.all():
                break
            tok, caches = cache[key_dec](
                param_vals, buffer_vals, tok, caches,
                jnp.asarray(s0 + t - 1, jnp.int32),
                jnp.asarray(seed + t, jnp.int32))
            if eos_rows is not None:
                # rows already finished are padded with EOS, not with the
                # model's (meaningless) continuation samples
                tok_np = np.where(eos_rows, np.int32(eos_token_id),
                                  np.asarray(jax.device_get(tok)))
                eos_rows |= tok_np == eos_token_id
                tok = jnp.asarray(tok_np)
            out.append(tok)
        return Tensor(jnp.concatenate(
            [ids] + [o[:, None] for o in out], axis=1))


def packed_positions(seg_v, s):
    """Per-document positions for a packed row batch: positions restart
    at every segment boundary (shared by GPT/LLaMA packed paths)."""
    import jax.numpy as jnp
    from jax import lax

    b = seg_v.shape[0]
    ar = jnp.arange(s, dtype=jnp.int32)[None, :]
    new_doc = jnp.concatenate(
        [jnp.ones((b, 1), bool), seg_v[:, 1:] != seg_v[:, :-1]], axis=1)
    starts = lax.cummax(jnp.where(new_doc, ar, 0), axis=1)
    return ar - starts
