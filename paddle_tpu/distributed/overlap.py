"""Fine-grained compute/collective overlap: decomposed reduce schedules.

Reference: "T3: Transparent Tracking & Triggering for Fine-grained Overlap
of Compute & Collectives" (PAPERS.md). The coarse bucketing layer
(grad_buckets.py) emits each bucket's all-reduce as ONE `pmean` after the
full backward has traced — XLA may overlap it, but on backends with a slow
monolithic all-reduce (XLA:CPU rendezvous, small-interconnect TPU slices)
the reduce phase still serializes at the tail of the step. This module goes
finer, in two moves:

  1. **Readiness analysis** (analysis/readiness.py): the forward+backward
     is traced to a jaxpr FIRST (`jax.make_jaxpr`, no device execution —
     the same walk-the-jaxpr approach the analysis/ linter uses), and each
     gradient bucket is mapped to the earliest equation index after which
     all of its contributing grads are produced — the earliest LEGAL
     trigger point for its collective.

  2. **Decomposed collective schedule**: each bucket's all-reduce is
     lowered to a chunked ring reduce-scatter -> all-gather built from
     `ppermute` chains (2*(world-1) single-chunk steps instead of one
     monolithic op). The traced backward is then REPLAYED equation by
     equation into the enclosing trace, and ring steps are emitted as soon
     as their bucket's dependency frontier is passed — so the final jaxpr
     literally interleaves collective chunks between backward segments
     (verified deterministically by analysis.verify_overlap_schedule).

A per-bucket cost model (bytes, segments remaining) keeps the `pmean`
fallback where decomposition can't win: tiny buckets (per-op collective
overhead dominates) and world_size <= 2 (a ring degenerates to the same
exchange an all-reduce does).

Numerics: the ring sums shards in ring order, which differs from psum's
reduction order — results are allclose at dtype tolerance, not bitwise
(tests/test_fine_overlap.py locks parity across dtypes, world sizes, and
uneven chunking). The `bucketed` mode remains bitwise vs single-flush.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.core import DropVar
from jax.extend.core import Literal

from ..core.flags import define_flag, get_flag
from ..observability.registry import counter as _obs_counter
from ..observability.registry import gauge as _obs_gauge
from .grad_buckets import coalesce as _coalesce
from .grad_buckets import partition_buckets
from .grad_buckets import uncoalesce as _uncoalesce

define_flag(
    "dp_overlap", "bucketed",
    "Explicit-DP gradient reduction schedule for TrainStep(dp_axis=...): "
    "'bucketed' = one pmean per fixed-byte bucket at flush points "
    "(grad_buckets.py, bitwise vs single all-reduce); 'fine' = analyzer-"
    "driven decomposed ring reduce-scatter/all-gather whose ppermute "
    "chunks are interleaved with the backward segments that no longer "
    "depend on them (allclose parity; see distributed/overlap.py).")
define_flag(
    "dp_overlap_min_kb", 128,
    "Per-bucket byte floor (KB) below which the fine-grained schedule "
    "falls back to a single pmean for that bucket — ring decomposition "
    "pays 2*(world-1) per-op collective overheads and loses on small "
    "buckets.")

# trace-time observability, mirroring grad_buckets: these describe how the
# most recent fine-grained reduction was SCHEDULED
_RING_STEPS = _obs_counter(
    "overlap_ring_steps_total",
    "ppermute ring steps emitted by the fine-grained schedule at trace time.")
_RING_BUCKETS = _obs_gauge(
    "overlap_ring_buckets",
    "Buckets lowered to ring schedules in the most recent fine trace.")
_PSUM_BUCKETS = _obs_gauge(
    "overlap_psum_buckets",
    "Buckets kept on the pmean fallback in the most recent fine trace.")

_LAST_SCHEDULE: Optional[Dict[str, Any]] = None


def last_schedule() -> Optional[Dict[str, Any]]:
    """Stats of the most recently traced fine-grained schedule (per process):
    bucket count, per-bucket decision + readiness index, ring steps emitted
    inline vs drained at the tail. Recorded at trace time — benches and
    tests read this right after forcing a (re)trace."""
    return None if _LAST_SCHEDULE is None else dict(_LAST_SCHEDULE)


def min_ring_bytes() -> int:
    return int(get_flag("dp_overlap_min_kb")) << 10


def choose_schedule(nbytes: int, world: int, eqns_remaining: int,
                    min_bytes: Optional[int] = None) -> str:
    """Per-bucket cost model: 'ring' or 'psum'.

    Bytes: a ring pays 2*(world-1) per-op collective latencies, so small
    buckets lose to one pmean. Segments remaining: a bucket that becomes
    ready at the very tail of the backward has nothing left to overlap
    with — the ring only wins there on raw bandwidth, so it must clear a
    4x byte floor before decomposition is worth it.
    """
    if min_bytes is None:
        min_bytes = min_ring_bytes()
    if world <= 2:
        return "psum"
    floor = min_bytes if eqns_remaining >= 2 * (world - 1) else 4 * min_bytes
    return "ring" if nbytes >= floor else "psum"


# ---------------------------------------------------------------------------
# staged ring all-reduce
# ---------------------------------------------------------------------------

class _RingReduce:
    """Ring reduce-scatter -> all-gather over one flat vector, one
    `step()` == one ppermute chunk exchange, so the scheduler can emit the
    2*(world-1) steps interleaved with other work. `finish()` drains the
    remaining steps and returns the reduced (mean) vector."""

    def __init__(self, flat, axis_name: str, world: int, mean: bool = True):
        self.axis = axis_name
        self.world = int(world)
        self.mean = mean
        self.size = int(flat.shape[0])
        pad = (-self.size) % self.world
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        # [world, chunk]: shard j of the ring is row j
        self.stack = flat.reshape(self.world, -1)
        self.chunk = int(self.stack.shape[1])
        self.idx = lax.axis_index(axis_name)
        self.perm = [(i, (i + 1) % self.world) for i in range(self.world)]
        # reduce-scatter starts from the local copy of shard `idx`
        self.acc = lax.dynamic_slice_in_dim(self.stack, self.idx, 1, 0)[0]
        self.cur = None
        self.out = None
        self.total_steps = 2 * (self.world - 1)
        self._s = 0

    @property
    def done(self) -> bool:
        return self._s >= self.total_steps

    def step(self) -> None:
        """Emit exactly one ppermute exchange (plus its add/placement)."""
        if self.done:
            return
        s, w = self._s, self.world
        self._s += 1
        if s < w - 1:
            # reduce-scatter round r=s+1: after it, this device holds shard
            # (idx - r) summed over devices {idx-r, ..., idx}
            r = s + 1
            self.acc = lax.ppermute(self.acc, self.axis, self.perm)
            mine = lax.dynamic_slice_in_dim(
                self.stack, (self.idx - r) % w, 1, 0)[0]
            self.acc = self.acc + mine
        else:
            g = s - (w - 1)
            if g == 0:
                # reduce-scatter done: this device owns the fully reduced
                # shard (idx + 1) % w; apply the mean once, per-chunk
                if self.mean:
                    self.acc = self.acc / w
                self.out = jnp.zeros((w, self.chunk), self.acc.dtype)
                self.out = lax.dynamic_update_slice_in_dim(
                    self.out, self.acc[None], (self.idx + 1) % w, 0)
                self.cur = self.acc
            # all-gather round: shard received at round g came from g+1 hops
            # back, i.e. it is reduced shard (idx - g) % w
            self.cur = lax.ppermute(self.cur, self.axis, self.perm)
            self.out = lax.dynamic_update_slice_in_dim(
                self.out, self.cur[None], (self.idx - g) % w, 0)
        _RING_STEPS.inc()

    def finish(self):
        while not self.done:
            self.step()
        return self.out.reshape(-1)[:self.size]


def ring_all_reduce(x, axis_name: str, world: Optional[int] = None,
                    mean: bool = True):
    """Decomposed all-reduce of one array over `axis_name` (flush-style:
    all 2*(world-1) ring steps back to back). Call inside a shard_map that
    binds the axis. Allclose to psum/pmean at dtype tolerance."""
    if world is None:
        world = lax.axis_size(axis_name)
    if world <= 1:
        return x
    shape = x.shape
    ring = _RingReduce(x.ravel(), axis_name, world, mean=mean)
    return ring.finish().reshape(shape)


def reduce_flush(g_vals, axis_name: str, bucket_bytes: Optional[int] = None,
                 mean: bool = True, mode: str = "fine"):
    """Flush-style reduction of a grad list with the per-bucket cost model
    applied but NO interleaving (every schedule emitted back to back).

    This is the comm-only cost of the fine schedule — the runtime reduce
    probe (jit/trainer.py) times it standalone to attribute overlapped
    reduce time, and tests use it for numerics parity without a backward.
    `mode='bucketed'` degenerates to grad_buckets.bucket_reduce.
    """
    from .grad_buckets import bucket_reduce, default_bucket_bytes

    if mode != "fine":
        return bucket_reduce(g_vals, axis_name, bucket_bytes, mean=mean)
    if bucket_bytes is None:
        bucket_bytes = default_bucket_bytes()
    world = lax.axis_size(axis_name)
    shapes = [tuple(g.shape) for g in g_vals]
    dtypes = [g.dtype for g in g_vals]
    out: List[Any] = [None] * len(g_vals)
    reduce_ = lax.pmean if mean else lax.psum
    for idxs in partition_buckets(shapes, dtypes, bucket_bytes):
        flat = _coalesce(g_vals, idxs)
        nbytes = int(flat.size) * jnp.dtype(flat.dtype).itemsize
        if choose_schedule(nbytes, world, eqns_remaining=0) == "ring":
            red = _RingReduce(flat, axis_name, world, mean=mean).finish()
        else:
            red = reduce_(flat, axis_name)
        _uncoalesce(red, idxs, shapes, out)
    return out


# ---------------------------------------------------------------------------
# jaxpr replay with interleaved collective emission
# ---------------------------------------------------------------------------

def _replay_eqn(eqn, env: Dict[Any, Any]) -> None:
    """Re-emit one traced equation into the enclosing trace (the
    jax.core.eval_jaxpr idiom: get_bind_params + primitive.bind)."""
    def read(v):
        return v.val if isinstance(v, Literal) else env[v]

    subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
    out = eqn.primitive.bind(*subfuns, *[read(v) for v in eqn.invars],
                             **bind_params)
    if not eqn.primitive.multiple_results:
        out = [out]
    for v, o in zip(eqn.outvars, out):
        if not isinstance(v, DropVar):
            env[v] = o


def overlap_grad_reduce(fwd_bwd, args: tuple, axis_name: str,
                        bucket_bytes: Optional[int] = None,
                        mean: bool = True):
    """Trace `fwd_bwd(*args) -> (loss, [grads], aux)`, then replay it with
    each grad bucket's decomposed all-reduce interleaved at the earliest
    legal trigger point.

    `fwd_bwd` must be pure in its args (TrainStep builds it that way) and
    return a 3-tuple whose SECOND element is the flat list/tuple of
    gradient arrays to reduce. Returns the same 3-tuple with the grads
    reduced over `axis_name` (mean by default); `loss`/aux are returned
    unreduced — callers pmean the loss themselves.

    Must be called inside a shard_map (or other context) binding
    `axis_name`; the inner trace itself contains no collectives, so the
    readiness analysis sees a pure backward.
    """
    global _LAST_SCHEDULE
    from ..analysis import readiness as _readiness
    from .grad_buckets import default_bucket_bytes

    if bucket_bytes is None:
        bucket_bytes = default_bucket_bytes()
    world = lax.axis_size(axis_name)

    closed, out_shape = jax.make_jaxpr(fwd_bwd, return_shape=True)(*args)
    out_leaves, out_tree = jax.tree_util.tree_flatten(out_shape)
    jaxpr = closed.jaxpr
    n_eqns = len(jaxpr.eqns)

    # output layout: (loss, grads, aux) flattened in order
    loss_shape, grads_shape, _aux_shape = out_shape
    n_grads = len(grads_shape)
    grad_lo = len(jax.tree_util.tree_leaves(loss_shape))
    grad_slice = slice(grad_lo, grad_lo + n_grads)

    # readiness: earliest eqn index after which each output is available
    ready = _readiness.output_ready_indices(closed)
    grad_ready = ready[grad_slice]

    shapes = [tuple(g.shape) for g in grads_shape]
    dtypes = [g.dtype for g in grads_shape]
    buckets = partition_buckets(shapes, dtypes, bucket_bytes)
    bucket_ready = [max([grad_ready[i] for i in idxs] + [-1])
                    for idxs in buckets]

    reduce_ = lax.pmean if mean else lax.psum
    stats: Dict[str, Any] = {
        "mode": "fine", "world": world, "n_eqns": n_eqns,
        "n_buckets": len(buckets), "ring_buckets": 0, "psum_buckets": 0,
        "ring_steps_total": 0, "inline_steps": 0, "drained_steps": 0,
        "buckets": [],
    }

    # seed the replay environment
    env: Dict[Any, Any] = {}
    flat_args = jax.tree_util.tree_leaves(args)
    for v, c in zip(jaxpr.constvars, closed.consts):
        env[v] = c
    for v, a in zip(jaxpr.invars, flat_args):
        env[v] = a

    def read_out(v):
        return v.val if isinstance(v, Literal) else env[v]

    # schedule state: buckets waiting on their trigger point, rings in
    # flight with their emission stride
    waiting = sorted(range(len(buckets)), key=lambda b: bucket_ready[b])
    active: List[Dict[str, Any]] = []
    reduced: List[Any] = [None] * n_grads

    def start_bucket(b: int, at_eqn: int) -> None:
        idxs = buckets[b]
        grad_vals = [None] * n_grads
        for i in idxs:
            grad_vals[i] = read_out(jaxpr.outvars[grad_lo + i])
        flat = _coalesce(grad_vals, idxs)
        nbytes = int(flat.size) * jnp.dtype(flat.dtype).itemsize
        remaining = n_eqns - 1 - at_eqn
        decision = choose_schedule(nbytes, world, remaining)
        stats["buckets"].append({
            "bucket": b, "tensors": len(idxs), "bytes": nbytes,
            "ready_eqn": bucket_ready[b], "eqns_remaining": remaining,
            "schedule": decision,
        })
        if decision == "psum":
            stats["psum_buckets"] += 1
            _uncoalesce(reduce_(flat, axis_name), idxs, shapes, reduced)
            return
        stats["ring_buckets"] += 1
        ring = _RingReduce(flat, axis_name, world, mean=mean)
        stats["ring_steps_total"] += ring.total_steps
        stride = max(1, remaining // (ring.total_steps + 1))
        active.append({"ring": ring, "idxs": idxs, "b": b,
                       "next": at_eqn + 1, "stride": stride})

    def pump(at_eqn: int) -> None:
        for ent in list(active):
            if at_eqn >= ent["next"] and not ent["ring"].done:
                ent["ring"].step()
                stats["inline_steps"] += 1
                ent["next"] = at_eqn + ent["stride"]
            if ent["ring"].done:
                _uncoalesce(ent["ring"].finish(), ent["idxs"], shapes,
                            reduced)
                active.remove(ent)

    for i, eqn in enumerate(jaxpr.eqns):
        _replay_eqn(eqn, env)
        while waiting and bucket_ready[waiting[0]] <= i:
            start_bucket(waiting.pop(0), i)
        pump(i)

    # anything not ready until the last eqn, or with leftover ring steps
    while waiting:
        start_bucket(waiting.pop(0), n_eqns - 1)
    for ent in active:
        stats["drained_steps"] += ent["ring"].total_steps - ent["ring"]._s
        _uncoalesce(ent["ring"].finish(), ent["idxs"], shapes, reduced)
    active.clear()

    _RING_BUCKETS.set(stats["ring_buckets"])
    _PSUM_BUCKETS.set(stats["psum_buckets"])
    _LAST_SCHEDULE = stats

    outs = [read_out(v) for v in jaxpr.outvars]
    loss, _, aux = jax.tree_util.tree_unflatten(out_tree, outs)
    return loss, reduced, aux
