"""Context (sequence) parallelism: ring attention + Ulysses (DeepSpeed-style).

The reference has NO context parallelism (SURVEY.md §5.7 — ring_attention /
ulysses / context_parallel: absent); only Megatron SP utilities
(fleet/utils/sequence_parallel_utils.py) exist. This module is the fresh
TPU-first design the survey calls for: the sequence dimension is a first-class
mesh axis ("sep"), attention over it runs as

  - ring_attention: K/V chunks rotate around the ICI ring via
    lax.ppermute; partial softmax results merge with the online-softmax
    (logsumexp) combine. O(s_local * s_global) compute per device,
    O(s_local) memory — arbitrary context length scales linearly with the
    ring size.
  - ulysses_attention: all-to-all swaps the sharded dim from sequence to
    heads, runs dense (flash) attention on full sequences for h/n heads,
    and swaps back. Cheaper when heads >= ring size; exact same math.

These are functions of *local shards*, designed to be called inside
shard_map/jit over the mesh — the idiom everything in paddle_tpu.jit compiles
through. All softmax statistics are fp32 regardless of input dtype.
"""
from __future__ import annotations

import functools
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _chunk_attention(q, k, v, scale, extra_mask):
    """Dense attention on one KV chunk returning per-row logsumexp.

    q: [b, sq, h, d]; k, v: [b, sk, h, d]; extra_mask: [sq, sk] additive fp32
    (0 or NEG_INF) or None. Returns (o [b,sq,h,d] fp32, lse [b,h,sq] fp32).
    """
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if extra_mask is not None:
        s = s + extra_mask[None, None, :, :]
    m = jnp.max(s, axis=-1)  # [b,h,sq]
    m = jnp.maximum(m, NEG_INF)  # keep finite when a row is fully masked
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)  # [b,h,sq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    # normalized chunk output
    o = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return o, lse


def _combine(o, lse, o_i, lse_i):
    """Merge two normalized partial attentions by their logsumexps."""
    new_lse = jnp.logaddexp(lse, lse_i)
    w = jnp.exp(lse - new_lse).transpose(0, 2, 1)[..., None]  # [b,sq,h,1]
    w_i = jnp.exp(lse_i - new_lse).transpose(0, 2, 1)[..., None]
    return o * w + o_i * w_i, new_lse


def dense_causal_attention(q, k, v, causal=True, scale=None):
    """Plain dense attention on full [b, s, h, d] arrays — the single-device
    reference the sharded kernels (and their parity tests) reduce to."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    extra = None
    if causal:
        ids = jnp.arange(q.shape[1])
        extra = jnp.where(ids[:, None] >= ids[None, :], 0.0,
                          NEG_INF).astype(jnp.float32)
    o, _ = _chunk_attention(q, k, v, scale, extra)
    return o.astype(q.dtype)


def _flash_chunk_supported(sq, d):
    """Gate for routing ring chunks through the Pallas flash kernel."""
    from ..core import flags as _flags
    from ..ops import pallas as _pallas
    from ..ops.pallas.flash_attention import _RING_BLOCK

    bq, bk = _RING_BLOCK(sq)
    return (_flags.get_flag("use_flash_attention") and _pallas.pallas_enabled()
            and (bq is None or (sq % bq == 0 and sq % bk == 0)) and d <= 256)


def ring_attention(q, k, v, axis_name, causal=False, scale=None, rank=None):
    """Ring attention over the `axis_name` mesh axis (call inside shard_map).

    q, k, v: LOCAL sequence shards [b, s_local, h, d]; global sequence is the
    concatenation over the axis in rank order. Returns the local output shard.

    Causal handling: the incoming chunk index src = (rank - step) mod n; a
    chunk strictly in the future (src > rank) is fully masked (and skipped),
    the diagonal chunk (src == rank) gets the causal mask, past chunks are
    unmasked.

    Per-chunk compute goes through the Pallas flash kernel
    (flash_attention_with_lse — its custom VJP accepts lse cotangents, so
    the online-softmax combine differentiates end to end; VERDICT r3 item 3)
    whenever shapes allow, giving O(block) memory per chunk instead of the
    dense O(s_local^2) score matrix. The three causal cases are a
    lax.switch, so only ONE branch executes per step — future chunks cost a
    cheap skip instead of a fully-masked dense attention.
    """
    n = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name) if rank is None else rank
    b, sq, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    use_flash = _flash_chunk_supported(sq, d)

    def chunk_skip(kc, vc):
        # constants must carry the same varying-manual-axes type as the
        # real chunk branches or lax.switch rejects the branch set
        return (lax.pcast(jnp.zeros((b, sq, h, d), jnp.float32), axis_name,
                          to="varying"),
                lax.pcast(jnp.full((b, h, sq), NEG_INF, jnp.float32),
                          axis_name, to="varying"))

    if use_flash:
        from ..ops import pallas as _pallas
        from ..ops.pallas.flash_attention import (
            _RING_BLOCK,
            flash_attention_with_lse,
        )

        bq, bk = _RING_BLOCK(sq)
        interp = _pallas.interpret_mode()

        def _flash(kc, vc, is_causal):
            o_i, lse_i = flash_attention_with_lse(
                q, kc, vc, scale, is_causal, bq, bk, interp)
            return o_i.astype(jnp.float32), lse_i

        def chunk_diag(kc, vc):
            return _flash(kc, vc, True)

        def chunk_full(kc, vc):
            return _flash(kc, vc, False)
    else:
        if causal:  # the (sq, sq) mask constant is only for the diagonal
            ids = jnp.arange(sq)
            causal_mask = jnp.where(
                ids[:, None] >= ids[None, :], 0.0, NEG_INF).astype(jnp.float32)

            def chunk_diag(kc, vc):
                return _chunk_attention(q, kc, vc, scale, causal_mask)
        else:
            chunk_diag = None  # never dispatched on the non-causal path

        def chunk_full(kc, vc):
            return _chunk_attention(q, kc, vc, scale, None)

    o = jnp.zeros((b, sq, h, d), jnp.float32)
    lse = jnp.full((b, h, sq), NEG_INF, jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]
    kc, vc = k, v
    for step in range(n):
        src = (r - step) % n
        if causal:
            # 0: future chunk (skip), 1: diagonal (causal), 2: past (full);
            # lax.switch executes only the selected branch
            mode = jnp.where(src > r, 0, jnp.where(src == r, 1, 2))
            o_i, lse_i = lax.switch(
                mode, (chunk_skip, chunk_diag, chunk_full), kc, vc)
        else:
            o_i, lse_i = chunk_full(kc, vc)
        o, lse = _combine(o, lse, o_i, lse_i)
        if step != n - 1:
            kc, vc = lax.ppermute((kc, vc), axis_name, perm)
    return o.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name, causal=False, scale=None,
                      dense_fn=None):
    """Ulysses/all-to-all sequence parallelism (call inside shard_map).

    Swaps the sharded dimension seq<->heads with two all-to-alls, runs dense
    attention on the full sequence for h/n heads. Requires h % axis_size == 0.
    """
    n = lax.axis_size(axis_name)
    b, sq, h, d = q.shape
    if h % n != 0:
        raise ValueError(f"ulysses needs heads ({h}) divisible by axis size ({n})")

    def to_full_seq(x):
        # [b, s/n, h, d] -> [b, s, h/n, d]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    def to_shard_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qf, kf, vf = to_full_seq(q), to_full_seq(k), to_full_seq(v)
    if dense_fn is not None:
        of = dense_fn(qf, kf, vf)
    else:
        of = _full_seq_attention(qf, kf, vf, causal=causal, scale=scale)
    return to_shard_seq(of)


def _full_seq_attention(qf, kf, vf, causal, scale):
    """Post-all-to-all attention over the FULL sequence: route through the
    Pallas flash kernel when enabled — the dense fallback materializes an
    O(s_global^2) score matrix, which defeats the long-context point of
    Ulysses (e.g. ~0.5 TB fp32 of scores at s=64k, h=32). Gating mirrors
    scaled_dot_product_attention: flag + shape support + interpret mode on
    CPU (raw pallas_call cannot lower on the CPU backend)."""
    from ..core.flags import get_flag
    from ..ops import pallas as _pallas
    from ..ops.pallas.flash_attention import (flash_attention,
                                              flash_attention_platform,
                                              supports)

    if get_flag("use_flash_attention") and supports(
            qf.shape, kf.shape, None, 0.0, causal):
        if _pallas.interpret_mode():
            return flash_attention(qf, kf, vf, causal=causal, scale=scale,
                                   interpret=True)
        # platform_dependent dispatch: the Mosaic kernel on tpu lowering,
        # the XLA composition on cpu — same trace works for both
        return flash_attention_platform(qf, kf, vf, scale, causal)
    return dense_causal_attention(qf, kf, vf, causal=causal, scale=scale)


# ------------------------------------------------------------------ SP utils
# Reference: fleet/utils/sequence_parallel_utils.py (ScatterOp:83, GatherOp,
# AllGatherOp, ReduceScatterOp, :83-135) — Megatron sequence parallelism
# around TP blocks. Same semantics as local-shard functions.
def scatter_seq(x, axis_name):
    """Keep this rank's 1/n slice of the sequence dim (ScatterOp)."""
    n = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    chunk = x.shape[1] // n if x.ndim > 2 else x.shape[0] // n
    dim = 1 if x.ndim > 2 else 0
    return lax.dynamic_slice_in_dim(x, r * chunk, chunk, axis=dim)


def all_gather_seq(x, axis_name, seq_axis=1):
    """Gather sequence shards to the full sequence (AllGatherOp)."""
    return lax.all_gather(x, axis_name, axis=seq_axis, tiled=True)


def reduce_scatter_seq(x, axis_name, seq_axis=1):
    """Sum partial activations and keep this rank's sequence slice
    (ReduceScatterOp)."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=seq_axis, tiled=True)


def gather_seq(x, axis_name, seq_axis=1):
    """Alias of all_gather_seq (reference GatherOp gathers to all)."""
    return all_gather_seq(x, axis_name, seq_axis)


class RingAttention:
    """Layer-style wrapper matching nn.functional.scaled_dot_product_attention
    signature for sequence-sharded inputs (used by models under sep>1)."""

    def __init__(self, axis_name="sep", causal=False):
        self.axis_name = axis_name
        self.causal = causal

    def __call__(self, q, k, v):
        return ring_attention(q, k, v, self.axis_name, causal=self.causal)


# ---------------------------------------------------------------- model hook
# Registered through the PUBLIC custom-op API (utils.register_custom_op) so
# CP attention is an ordinary op: eager autograd via jax.vjp through
# shard_map, usable inside TrainStep/jit, recorded on static Programs.
# cacheable=False: the kernel captures the ambient mesh, which is not part
# of the op's cache key.
@functools.lru_cache(maxsize=64)
def _sp_attention_fn(mesh, axis_name, mode, causal, _flag_state=None):
    """Jitted partial-manual shard_map for one (mesh, attrs) combination.
    Cached so repeated eager calls hit jit's compile cache instead of
    rebuilding a fresh function identity (and recompiling) every forward.
    `_flag_state` carries the kernel-selection flag values into the cache
    key — ring_attention reads them at TRACE time, so a cached entry traced
    under different flags must not be reused after a set_flags."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    inner = ring_attention if mode == "ring" else ulysses_attention
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        partial(inner, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
        axis_names=frozenset({axis_name}), check_vma=False)
    # partial-manual shard_map (manual 'sep', auto dp/mp) requires a jit
    # scope in jax 0.9; nested jit inlines when already traced
    return jax.jit(fn)


def _register_sp_attention():
    from ..utils import register_custom_op

    @register_custom_op(name="sequence_parallel_attention", cacheable=False)
    def sequence_parallel_attention(q, k, v, *, axis_name="sep", mode="ring",
                                    causal=True):
        """Attention with the sequence dim sharded over `axis_name`.

        q, k, v: GLOBAL [b, s, h, d]. The op wraps ring/Ulysses attention in
        a partial-manual shard_map: only `axis_name` goes manual, so dp/mp
        dims stay under GSPMD and compose with TrainStep shardings. This is
        the TPU-native subsumption of the reference's
        Column/RowSequenceParallelLinear SP layers
        (fleet/utils/sequence_parallel_utils.py:228,340)."""
        from .mesh import get_mesh

        if mode not in ("ring", "ulysses"):
            raise ValueError(
                f"sequence_parallel mode must be 'ring' or 'ulysses', "
                f"got {mode!r}")
        mesh = get_mesh()
        if mesh is None or axis_name not in mesh.axis_names \
                or mesh.shape[axis_name] == 1:
            # no sep axis -> plain dense attention, same math
            return dense_causal_attention(q, k, v, causal=causal)
        from ..core import flags as _flags

        flag_state = (_flags.get_flag("use_flash_attention"),
                      _flags.get_flag("pallas_interpret"))
        return _sp_attention_fn(mesh, axis_name, mode, causal,
                                flag_state)(q, k, v)


_register_sp_attention()
