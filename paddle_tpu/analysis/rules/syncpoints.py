"""host-sync: device->host round trips compiled into the step.

Reference analog: the reference's GPU graphs/"no sync in train loop" rule —
any per-step host callback (jax.debug.print, pure_callback, io_callback)
forces XLA to materialize operands to the host every step, serializing the
pipeline the prefetcher and async checkpointing worked to build
(io/prefetch.py, resilience/). `.item()`/device_get can't appear in a
jaxpr (they force concretization at trace), so callbacks + infeed/outfeed
are the statically-visible sync points.
"""
from __future__ import annotations

from ..analyzer import ProgramInfo, eqn_source, iter_eqns
from ..findings import Finding, Severity
from ..registry import register_rule

_SYNC_EXACT = ("infeed", "outfeed", "debug_print")


@register_rule(
    "host-sync", "Host callback / sync point inside the compiled program",
    Severity.WARNING,
    doc="Flags *_callback, debug_print and infeed/outfeed inside the traced "
        "program: each one is a device->host round trip per step.")
def check(program: ProgramInfo):
    for idx, eqn in iter_eqns(program.closed_jaxpr):
        name = eqn.primitive.name
        if "callback" in name or name in _SYNC_EXACT:
            what = "jax.debug.print" if name == "debug_print" else name
            yield Finding(
                rule="host-sync", severity=Severity.WARNING,
                message=f"{what} compiled into the program — a "
                        "device->host sync every step",
                primitive=name, eqn_index=idx, source=eqn_source(eqn),
                fix_hint="move logging/metrics outside the step (read the "
                         "returned loss), or gate it behind a debug flag")
