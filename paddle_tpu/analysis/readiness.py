"""Readiness analysis + overlap-schedule verification (reusable queries).

The fine-grained overlap scheduler (distributed/overlap.py) needs two
jaxpr-level facts, both answered here with the same walk-the-jaxpr
machinery the lint rules use — exposed as QUERIES, not lint rules, so the
scheduler and tests can call them directly:

  * ``output_ready_indices(closed)``: for each output of a traced program,
    the index of the top-level equation that produces it — i.e. the
    earliest point in program order after which that value exists. The
    scheduler maps each grad bucket to ``max`` over its members: the
    earliest LEGAL trigger point for the bucket's collective.

  * ``verify_overlap_schedule(closed)``: a deterministic check that a
    compiled train step's collective chunks are actually interleaved
    between backward compute segments instead of clustered at the jaxpr
    tail — the schedule property the fine mode exists to establish. Tests
    gate on this instead of wall-clock timing, so overlap regressions are
    caught without flakiness.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from jax.core import DropVar
from jax.extend.core import Literal

from .analyzer import eqn_subjaxprs

# primitives that move data across mesh participants (the schedule's
# "collective chunks"); axis_index is placement arithmetic, not comm
_COLLECTIVE_PRIMS = frozenset({
    "ppermute", "psum", "pmax", "pmin", "pmean", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "pbroadcast",
})
# heavyweight compute that marks a backward segment worth overlapping with
_COMPUTE_PRIMS = frozenset({
    "dot_general", "conv_general_dilated", "scatter-add", "scatter_add",
    "gather", "cumsum", "sort", "reduce_window_sum",
})


def producer_indices(jaxpr) -> Dict[Any, int]:
    """Map each top-level Var to the index of the eqn producing it.
    Vars bound by invars/constvars are absent (ready before eqn 0)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            if not isinstance(v, DropVar):
                out[v] = i
    return out

def output_ready_indices(closed) -> List[int]:
    """For each outvar of the (closed) jaxpr: the top-level eqn index after
    which it is available. -1 for passthrough inputs/consts/literals."""
    jaxpr = getattr(closed, "jaxpr", closed)
    prod = producer_indices(jaxpr)
    return [
        -1 if isinstance(v, Literal) else prod.get(v, -1)
        for v in jaxpr.outvars
    ]


def bucket_ready_indices(ready: List[int],
                         buckets: List[List[int]]) -> List[int]:
    """Earliest legal trigger point per bucket: max readiness over its
    member grads (a bucket may only reduce once ALL members exist)."""
    return [max([ready[i] for i in idxs] + [-1]) for idxs in buckets]


# ---------------------------------------------------------------------------
# schedule verification
# ---------------------------------------------------------------------------

def _body_profile(jaxpr) -> Dict[str, Any]:
    """Positions of collective and compute eqns in ONE jaxpr body."""
    coll, comp = [], []
    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        if name in _COLLECTIVE_PRIMS:
            coll.append(i)
        elif name in _COMPUTE_PRIMS:
            comp.append(i)
    return {"n_eqns": len(jaxpr.eqns), "collectives": coll, "compute": comp}


def _walk_bodies(jaxpr, out: List[Any]) -> None:
    out.append(jaxpr)
    for eqn in jaxpr.eqns:
        for sub in eqn_subjaxprs(eqn):
            _walk_bodies(sub, out)


def schedule_report(closed) -> Dict[str, Any]:
    """Inspect the body holding the collective schedule (the one with the
    most collective eqns — the shard_map body for an explicit-DP step) and
    measure interleaving:

      * ``n_collectives`` / ``n_compute``: eqn counts in that body;
      * ``interleaved_collectives``: collective eqns with at least one
        heavyweight compute eqn AFTER them in program order — nonzero means
        the schedule gives the backend compute to overlap the chunk with;
      * ``tail_clustered``: True when every collective sits after the last
        compute eqn (the single-flush / coarse-bucket shape);
      * ``interleave_ratio``: interleaved / total collectives.
    """
    jaxpr = getattr(closed, "jaxpr", closed)
    bodies: List[Any] = []
    _walk_bodies(jaxpr, bodies)
    profiles = [_body_profile(b) for b in bodies]
    best = max(profiles, key=lambda p: len(p["collectives"]),
               default=None)
    if best is None or not best["collectives"]:
        return {"n_collectives": 0, "n_compute": 0,
                "interleaved_collectives": 0, "tail_clustered": True,
                "interleave_ratio": 0.0}
    last_compute = best["compute"][-1] if best["compute"] else -1
    inter = sum(1 for c in best["collectives"] if c < last_compute)
    n = len(best["collectives"])
    return {
        "n_collectives": n,
        "n_compute": len(best["compute"]),
        "first_collective_eqn": best["collectives"][0],
        "last_compute_eqn": last_compute,
        "interleaved_collectives": inter,
        "tail_clustered": inter == 0,
        "interleave_ratio": round(inter / n, 4),
    }


def verify_overlap_schedule(closed, min_ratio: float = 0.25,
                            raise_on_fail: bool = False) -> Dict[str, Any]:
    """Deterministic overlap gate: the schedule counts as interleaved when
    at least ``min_ratio`` of its collective chunks have backward compute
    scheduled after them. Returns the report with ``ok`` set; raises
    instead when ``raise_on_fail`` and the gate fails."""
    rep = schedule_report(closed)
    rep["ok"] = (rep["n_collectives"] > 0
                 and rep["interleave_ratio"] >= min_ratio)
    if raise_on_fail and not rep["ok"]:
        raise AssertionError(
            f"overlap schedule not interleaved: {rep['n_collectives']} "
            f"collective(s), ratio {rep['interleave_ratio']} < {min_ratio} "
            f"(tail_clustered={rep['tail_clustered']})")
    return rep
